"""Jit'd public wrappers around the Pallas forest-scoring kernels.

Handles padding to kernel alignment (doc blocks, tree blocks, power-of-two
node axis, lane-padded feature axis) and unpadding of the result. On CPU
(this container) the kernel runs in interpret mode; on TPU it compiles to
Mosaic.

Padded-buffer caching
---------------------
:func:`padded_forest` builds the kernel-aligned device buffers for an
ensemble ONCE and caches them on the :class:`TreeEnsemble` instance (keyed
by segment boundaries × tree-block size × leaf-gather path), so repeated
scoring — the serving hot path — never re-pads. Segment boundaries (cascade
sentinels) need NOT be tree-block aligned: each segment is padded
independently with no-op trees (threshold ``+inf`` ⇒ always-true ⇒ all-ones
mask; leaf values 0), which makes every segment start block-aligned by
construction. Head and tail of a cascade then score from the same buffer
set via ``tree_block_offset`` / ``n_tree_blocks`` —
:func:`repro.forest.ensemble.slice_trees` re-padding is gone from the hot
path.

Leaf-gather layout
------------------
The buffer set carries a per-path leaf layout: the kernel's select-tree
leaf gather (:mod:`repro.kernels.forest_score`, ``leaf_gather="select"``)
walks the leaf-index bits over contiguous halves of the value array, so it
needs the leaf axis padded to a power of two (``leaf_layout="pow2"``,
padding values 0 — never selected, the ctz leaf index stays below the real
leaf count). The one-hot and MXU paths read the native leaf axis
(``leaf_layout="native"``). ``leaf_gather="auto"`` (the default) resolves
via :func:`repro.kernels.forest_score.resolve_leaf_gather`: select tree up
to ``LEAF_SELECT_MAX`` padded leaves, MXU contraction above. All paths are
bit-exact with each other, so the resolved choice is a pure perf knob.

Launch accounting
-----------------
Every kernel dispatch below goes through :func:`_counted_pallas`, a counting
``pallas_call`` wrapper that records the launch **when the call is staged**:
eagerly that is once per call, and under an enclosing ``jax.jit`` it is once
per *trace* — a cached re-execution of the compiled computation adds zero,
because no new launch is staged into it. This is what lets the whole
progressive cascade step (segmented head → stage decisions → compaction →
tail → scatter) compile into ONE XLA computation while the 1-head-launch
contract stays testable: tests trace a fresh step, read
:func:`launch_counts` (split ``plain`` / ``segmented`` / ``gated`` — the
last for launches staged behind a run-time skip condition, e.g. the
query-exit gated tail), and assert the counts do not move on cached
re-executions.

Trace-time vs run-time, under ``lax.cond``: the counters describe the
launches *staged into* a computation, not the launches a particular batch
*executed*. The distinction only matters for the combined
``mode="auto"`` progressive step, where BOTH execution branches live under
one ``lax.cond``: tracing it stages the fused branch's launches (1
segmented + ≤1 plain) AND the staged branch's (≤S+1 plain) — each exactly
once — while at run time only the branch the device pick selects actually
dispatches. So the auto-step contract is ``segmented == 1`` and
``plain == S+2`` (with a tail region; S ≥ 2) per trace, stable across
re-executions regardless of which branch each batch takes.

Row sharding
------------
Under a mesh context (``jax.set_mesh``, entered by
:class:`repro.serve.placement.ServePlacement`) whose :data:`ROW_AXIS` axis
spans several devices, every launch runs under ``jax.shard_map`` over
that axis: each device's kernel scores its own contiguous share of the
document rows. The kernel scores every row independently, so the result
is bit-identical to one device; without the ``shard_map`` the SPMD
partitioner cannot split a Mosaic kernel and would run the whole batch on
every device.
"""

from __future__ import annotations

import dataclasses
import os
import typing
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.kernels.forest_score import (
    _next_pow2,
    forest_score_pallas,
    forest_score_segments_pallas,
    tree_block,
)

if typing.TYPE_CHECKING:  # annotation-only: keeps ops importable before
    from repro.forest.ensemble import TreeEnsemble  # repro.forest (no cycle)

LANE = 128
ALL_ONES = np.uint32(0xFFFFFFFF)
WORD_BITS = 32   # leaves per mask word (repro.forest.ensemble.WORD_BITS)

#: Mesh axis the kernel launches split their document rows over — the
#: query axis of :func:`repro.serve.placement.data_parallel`.
ROW_AXIS = "data"


def env_int(name: str, default: int, *, minimum: int = 1) -> int:
    """THE environment-override helper for the engine's tuning constants.

    Deployment knobs that used to be hard module constants
    (:data:`PADDED_CACHE_MAX`, :data:`LEAF_SELECT_MAX`,
    :data:`repro.core.features.RANK_BLOCKED_MIN_D`) read their value
    through this single chokepoint at import time: unset or empty →
    ``default``; a non-integer or a value below ``minimum`` raises
    immediately (a silently-ignored typo'd override is worse than a
    startup crash). Overrides are read ONCE at module import — set the
    variable before the first ``repro`` import, as with ``XLA_FLAGS``.
    """
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be an integer, got {raw!r}"
        ) from None
    if value < minimum:
        raise ValueError(f"{name} must be >= {minimum}, got {value}")
    return value


# Default doc-block size of every kernel dispatch below. Decision-time
# pricing (repro.metrics.speedup.progressive_cost_model, block_b-rounded
# survivor counts) must quote the same number, so it lives here as THE
# engine constant rather than as scattered literals.
ENGINE_BLOCK_B = 256

# Bound on cached (boundaries, block_t) buffer layouts per ensemble: a
# long-running service sweeping sentinel configs must not leak device
# memory. Eviction is LRU; a re-requested layout is simply re-padded.
PADDED_CACHE_MAX = env_int("REPRO_PADDED_CACHE_MAX", 8)

# Auto leaf-gather policy cutoff: select tree up to this many (padded)
# leaves, MXU contraction above. Measured on a TPU v5e at the widest tree
# either builder takes, 256 leaves (tree blocks of 4, a 1,024-lane leaf
# row as at 64 leaves): the select tree scored lear-msn1-d8's tail launch
# in 4.37 ms against the MXU contraction's 5.01 ms (2,048 rows, 450
# trees), so every tree the engine builds takes the select path; the MXU
# path serves leaf tables wider than that. Overridable per deployment.
LEAF_SELECT_MAX = env_int("REPRO_LEAF_SELECT_MAX", 256)


def resolve_leaf_gather(n_leaves: int) -> str:
    """Concrete leaf-gather path for ``"auto"``: select tree for small leaf
    axes (after power-of-two padding), MXU contraction for wide ones."""
    return "select" if _next_pow2(n_leaves) <= LEAF_SELECT_MAX else "mxu"

_LAUNCH_COUNTS = {"plain": 0, "segmented": 0, "gated": 0}


def reset_launch_counts() -> None:
    """Zero all counters (typically right before tracing a fresh step)."""
    for kind in _LAUNCH_COUNTS:
        _LAUNCH_COUNTS[kind] = 0


def launch_counts() -> dict[str, int]:
    """Launches STAGED since the last reset, keyed ``plain`` /
    ``segmented`` / ``gated``.

    ``gated`` counts launches staged behind a run-time skip condition:
    with query-level exit enabled, the progressive tail launch sits
    under a ``lax.cond`` on the survivor count, so a batch whose queries
    all converged dispatches no tail kernel at all. Like the other two
    counters this is TRACE-time accounting — the gate's run-time
    outcome shows up in the trees-traversed metric, not here.

    Trace-time accounting: a cached re-execution of a compiled step adds
    zero; a ``lax.cond`` with kernel calls in both branches adds both
    branches once (see the module docstring). Use with
    :func:`reset_launch_counts` to assert launch contracts in tests.
    """
    return dict(_LAUNCH_COUNTS)


def _row_shards() -> int:
    """Devices the context mesh splits document rows over (1: no mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else dict(mesh.shape).get(ROW_AXIS, 1)


def _counted_pallas(
    kind: str, call: typing.Callable[..., jax.Array],
    x: jax.Array, *tables: jax.Array, **kwargs: object,
) -> jax.Array:
    """Counting ``pallas_call`` wrapper: record the launch at staging time.

    ``call`` is one of the (jitted) Pallas entry points, ``x`` its
    document rows. The counter bumps when this wrapper's Python body runs —
    per call in eager execution, per trace under an enclosing ``jit`` — so
    counts reflect launches staged into each computation, and stay stable
    across cached re-executions. Under a row-sharding mesh the launch runs
    per device on its share of the rows (module docstring).
    """
    _LAUNCH_COUNTS[kind] += 1
    if _row_shards() == 1:
        return call(x, *tables, **kwargs)
    rows = PartitionSpec(ROW_AXIS)
    return jax.shard_map(
        lambda xs: call(xs, *tables, **kwargs),
        in_specs=rows, out_specs=rows, check_vma=False,
    )(x)


def _pad_to(
    x: jax.Array, axis: int, multiple: int, value: float | jax.Array = 0
) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def effective_block_b(block_b: int, n_rows: int) -> int:
    """Doc-block size a launch over ``n_rows`` rows actually uses: the
    requested block, shrunk to the padded row count for small batches.
    THE block policy — :func:`_prep_x` applies it to every dispatch and
    the decision-time cost model
    (:func:`repro.metrics.speedup.progressive_cost_model`) imports it to
    price staged stages, so the two cannot drift apart.
    """
    return min(block_b, _next_pow2(max(int(n_rows), 8)))


@dataclasses.dataclass(frozen=True)
class PaddedForest:
    """Kernel-aligned device buffers for one ensemble + segment layout.

    Segment ``k`` occupies padded tree blocks
    ``[seg_block_starts[k], seg_block_starts[k] + seg_blocks[k])``; segments
    are contiguous, so any segment range is one contiguous block range.
    """

    feature: jax.Array     # [T_pad, N_pad] i32
    threshold: jax.Array   # [T_pad, N_pad] f32
    mask: jax.Array        # [T_pad, N_pad, W] u32
    leaf_value: jax.Array  # [T_pad, L_layout] f32 — see leaf_layout
    base_score: jax.Array  # [] f32
    boundaries: tuple[int, ...]       # cumulative tree-unit segment ends
    seg_block_starts: tuple[int, ...]  # per-segment start, in blocks
    seg_blocks: tuple[int, ...]        # per-segment length, in blocks
    block_t: int
    # Per mask word, the 128-lane node columns of a tree-block row that
    # hold a node zeroing that word in some tree (see _group_by_word).
    word_columns: tuple[tuple[int, ...], ...]
    leaf_gather: str = "onehot"   # resolved kernel path for this buffer set
    leaf_layout: str = "native"   # "pow2": leaf axis padded for the select
    #   path's contiguous-half bit walk; "native": ensemble leaf axis as-is

    @property
    def n_segments(self) -> int:
        return len(self.boundaries)

    @property
    def n_trees(self) -> int:
        return self.boundaries[-1]


def padded_forest(
    ens: TreeEnsemble,
    boundaries: tuple[int, ...] | None = None,
    block_t: int = 16,
    leaf_gather: str = "auto",
) -> PaddedForest:
    """Pad once, score many: cached kernel-aligned buffers for ``ens``.

    ``boundaries`` are cumulative segment ends in tree units (ascending,
    last == ``ens.n_trees``); ``None`` means one segment. ``leaf_gather``
    picks the kernel's leaf-value resolution path (and with it the leaf
    buffer layout — the select tree needs a power-of-two leaf axis);
    ``"auto"`` resolves per :func:`~repro.kernels.forest_score.resolve_leaf_gather`.
    The result is cached on the ensemble instance keyed by ``(boundaries,
    block_t, leaf_gather)``, bounded to the :data:`PADDED_CACHE_MAX` most
    recently used layouts (LRU eviction — sweeping sentinel configs must
    not leak device memory).
    """
    T, N = ens.feature.shape
    boundaries = tuple(boundaries) if boundaries is not None else (T,)
    assert boundaries[-1] == T, (boundaries, T)
    assert all(b > 0 for b in boundaries)
    assert list(boundaries) == sorted(set(boundaries)), boundaries
    block_t = _tree_block_for(ens, block_t)
    if leaf_gather == "auto":
        leaf_gather = resolve_leaf_gather(ens.n_leaves)

    cache = getattr(ens, "_padded_cache", None)
    if cache is None:
        cache = OrderedDict()
        object.__setattr__(ens, "_padded_cache", cache)
    key = (boundaries, block_t, leaf_gather)
    if key in cache:
        cache.move_to_end(key)
        return cache[key]

    # The builder may run while an enclosing cascade step is TRACING (the
    # classifier's kernel path calls in from inside the jitted step); the
    # buffers must still be concrete — they are cached on the ensemble and
    # outlive the trace. ensure_compile_time_eval escapes the trace: all
    # padding ops below execute eagerly on the concrete ensemble arrays.
    with jax.ensure_compile_time_eval():
        return _build_padded_forest(
            ens, cache, key, boundaries, block_t, leaf_gather
        )


def _tree_block_for(ens: TreeEnsemble, block_t: int) -> int:
    """The tree block :func:`padded_forest` lays ``ens`` out in: the
    requested ``block_t``, capped by the tree count and the padded tree
    shape (:func:`repro.kernels.forest_score.tree_block`)."""
    T, N = ens.feature.shape
    return tree_block(
        min(block_t, _next_pow2(max(T, 1))),
        node_slots(N, ens.mask.shape[2]), ens.n_leaves,
    )


def segment_tree_slots(
    ens: TreeEnsemble, boundaries: tuple[int, ...], block_t: int = 16
) -> tuple[int, ...]:
    """Tree slots per row of each segment of ``boundaries`` (cumulative
    ends, as :func:`padded_forest` takes them), padded to whole tree blocks
    as :func:`padded_forest` pads them — shape arithmetic only, no buffers
    are built."""
    bt = _tree_block_for(ens, block_t)
    starts = (0,) + tuple(boundaries[:-1])
    return tuple(-(-(e - s) // bt) * bt for s, e in zip(starts, boundaries))


def node_slots(n_nodes: int, words: int) -> int:
    """Node axis of the kernel layout: the padded node count, and at least
    one group of :data:`WORD_BITS` slots per mask word when the masks span
    several words (:func:`_group_by_word`)."""
    groups = WORD_BITS * words if words > 1 else 1
    return _next_pow2(max(n_nodes, groups, 2))


def _group_by_word(mask: np.ndarray) -> np.ndarray | None:
    """Per tree, the node each slot of the kernel layout holds: ``[T,
    N]`` indices into the node axis of a ``[T, N, W]`` mask padded to
    :func:`node_slots`; ``None`` for single-word masks (the layout is the
    node axis itself). Free slots take the last node, a padding node.

    Node ``n`` zeroes leaves ``[lo, mid)``; its *top word* is the word of
    leaf ``mid − 1``, the leaf just before ``n`` in in-order. That map is
    one-to-one, so no word is the top word of more than
    :data:`WORD_BITS` nodes of a tree, and group ``w`` — slots ``[32w,
    32w + 32)`` — holds exactly the nodes whose top word is ``w``. A node
    zeroes no word above its top word, and only a node whose span crosses
    a word boundary zeroes one below it, so word ``w``'s AND needs group
    ``w`` and the few groups holding such nodes (``word_columns``)
    instead of every node. AND is order-free: the layout moves no bit of
    the result.
    """
    T, N, W = mask.shape
    if W == 1:
        return None
    zeroed = mask != ALL_ONES                                   # [T, N, W]
    top = np.where(
        zeroed.any(axis=2), W - 1 - np.argmax(zeroed[..., ::-1], axis=2), W
    )                                                           # [T, N]
    order = np.argsort(top, axis=1, kind="stable")
    group = np.take_along_axis(top, order, axis=1)
    counts = np.stack([(top == w).sum(axis=1) for w in range(W + 1)], 1)
    starts = np.cumsum(counts, axis=1) - counts                 # [T, W+1]
    rank = np.arange(N)[None, :] - np.take_along_axis(starts, group, 1)
    real = group < W
    assert (rank[real] < WORD_BITS).all(), "a mask word tops > 32 nodes"
    assert not zeroed[:, -1].any(), "the last node slot is not padding"
    src = np.full((T, N), N - 1, dtype=np.int32)
    rows = np.broadcast_to(np.arange(T)[:, None], (T, N))
    src[rows[real], (group * WORD_BITS + rank)[real]] = order[real]
    return src


def _take_nodes(table: jax.Array, src: jax.Array) -> jax.Array:
    """``table[t, src[t, n], ...]``: a ``[T, N]`` or ``[T, N, W]`` table in
    the node order ``src`` gives."""
    if table.ndim == 3:
        src = jnp.broadcast_to(src[..., None], table.shape)
    return jnp.take_along_axis(table, src, axis=1)


def _word_columns(
    mask: np.ndarray, block_t: int
) -> tuple[tuple[int, ...], ...]:
    """Per mask word, the 128-lane columns of a node-major tree-block row
    (slot ``n`` of tree ``t`` at lane ``n·BT + t``) that hold a node
    zeroing that word in some tree."""
    touched = (mask != ALL_ONES).any(axis=0)                    # [N, W]
    column = np.arange(mask.shape[1]) * block_t // LANE
    return tuple(
        tuple(sorted(set(column[touched[:, w]].tolist())))
        for w in range(mask.shape[2])
    )


def _build_padded_forest(
    ens: TreeEnsemble,
    cache: OrderedDict,
    key: tuple,
    boundaries: tuple[int, ...],
    block_t: int,
    leaf_gather: str,
) -> PaddedForest:
    N = ens.feature.shape[1]
    n_slots = node_slots(N, ens.mask.shape[2])
    # Padded nodes: threshold +inf ⇒ predicate always true ⇒ all-ones mask.
    feat = _pad_to(ens.feature, 1, n_slots)
    thr = _pad_to(ens.threshold.astype(jnp.float32), 1, n_slots, np.inf)
    mask = _pad_to(ens.mask, 1, n_slots, ALL_ONES)
    # The node order and each word's columns are static layout, read from
    # the masks once per cached layout (at warm-up), never per engine call.
    # repro: noqa(TS006) -- a cache-miss build, not a per-batch transfer
    host_mask = np.asarray(mask)
    # Multi-word masks: nodes grouped by the word they top, so that each
    # word ANDs over its own columns.
    src = _group_by_word(host_mask)
    if src is not None:
        feat, thr, mask = (
            _take_nodes(a, jnp.asarray(src)) for a in (feat, thr, mask)
        )
        host_mask = np.take_along_axis(host_mask, src[..., None], axis=1)
    word_columns = _word_columns(host_mask, block_t)
    leaf = ens.leaf_value.astype(jnp.float32)
    # Per-path leaf layout: the select tree's contiguous-half bit walk
    # needs a power-of-two leaf axis; pad values are 0 and unreachable
    # (every ctz leaf index is below the real leaf count).
    leaf_layout = "native"
    if leaf_gather == "select":
        Lp = _next_pow2(max(ens.n_leaves, 1))
        if Lp != ens.n_leaves:
            leaf = _pad_to(leaf, 1, Lp)
        leaf_layout = "pow2"

    # Per-segment tree padding: no-op trees (always-true nodes, zero leaves).
    parts = {name: [] for name in ("feat", "thr", "mask", "leaf")}
    seg_block_starts, seg_blocks = [], []
    start = offset = 0
    for end in boundaries:
        parts["feat"].append(_pad_to(feat[start:end], 0, block_t))
        parts["thr"].append(_pad_to(thr[start:end], 0, block_t, np.inf))
        parts["mask"].append(_pad_to(mask[start:end], 0, block_t, ALL_ONES))
        parts["leaf"].append(_pad_to(leaf[start:end], 0, block_t))
        nb = parts["feat"][-1].shape[0] // block_t
        seg_block_starts.append(offset)
        seg_blocks.append(nb)
        offset += nb
        start = end

    pf = PaddedForest(
        feature=jnp.concatenate(parts["feat"]),
        threshold=jnp.concatenate(parts["thr"]),
        mask=jnp.concatenate(parts["mask"]),
        leaf_value=jnp.concatenate(parts["leaf"]),
        base_score=ens.base_score,
        boundaries=boundaries,
        seg_block_starts=tuple(seg_block_starts),
        seg_blocks=tuple(seg_blocks),
        block_t=block_t,
        word_columns=word_columns,
        leaf_gather=leaf_gather,
        leaf_layout=leaf_layout,
    )
    cache[key] = pf
    while len(cache) > PADDED_CACHE_MAX:
        cache.popitem(last=False)
    return pf


def _row_blocks(n_rows: int, block_b: int) -> tuple[int, int]:
    """Per-device doc-block size and row shards of a launch over
    ``n_rows`` rows on the context mesh."""
    shards = _row_shards()
    return effective_block_b(block_b, -(-n_rows // shards)), shards


def launch_rows(n_rows: int, block_b: int = ENGINE_BLOCK_B) -> int:
    """Rows a launch over ``n_rows`` rows scores on the context mesh, the
    padding :func:`_prep_x` adds included."""
    block_b, shards = _row_blocks(n_rows, block_b)
    return -(-n_rows // (block_b * shards)) * block_b * shards


def _prep_x(X: jax.Array, block_b: int) -> tuple[jax.Array, int]:
    """Pad ``X`` for a launch: rows to whole doc blocks on every row
    shard, features to whole lanes. Returns the padded rows and the
    per-device doc-block size."""
    block_b, shards = _row_blocks(X.shape[0], block_b)
    x = _pad_to(X.astype(jnp.float32), 0, block_b * shards)
    x = _pad_to(x, 1, LANE)
    return x, block_b


def forest_score_range(
    pf: PaddedForest,
    X: jax.Array,
    seg_lo: int = 0,
    seg_hi: int | None = None,
    *,
    block_b: int = ENGINE_BLOCK_B,
    interpret: bool | None = None,
    count_as: str = "plain",
) -> jax.Array:
    """Score ``X: [B, F]`` through segments ``[seg_lo, seg_hi)`` — 1 launch.

    ``base_score`` is added only when the range starts at segment 0
    (mirroring :func:`repro.forest.ensemble.slice_trees` semantics).
    ``count_as`` picks the launch-accounting bucket: ``"plain"`` for an
    unconditional launch, ``"gated"`` when the caller stages this launch
    behind a run-time skip condition (the query-exit gated tail).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    seg_hi = pf.n_segments if seg_hi is None else seg_hi
    assert 0 <= seg_lo < seg_hi <= pf.n_segments, (seg_lo, seg_hi)
    assert count_as in ("plain", "gated"), count_as
    B = X.shape[0]
    x, block_b = _prep_x(X, block_b)

    scores = _counted_pallas(
        count_as, forest_score_pallas,
        x, pf.feature, pf.threshold, pf.mask, pf.leaf_value,
        block_b=block_b,
        block_t=pf.block_t,
        tree_block_offset=pf.seg_block_starts[seg_lo],
        n_tree_blocks=sum(pf.seg_blocks[seg_lo:seg_hi]),
        word_columns=pf.word_columns,
        leaf_gather=pf.leaf_gather,
        interpret=interpret,
    )
    base = pf.base_score if seg_lo == 0 else jnp.zeros_like(pf.base_score)
    return scores[:B] + base


def forest_score_segments(
    pf: PaddedForest,
    X: jax.Array,
    n_segments: int | None = None,
    *,
    block_b: int = ENGINE_BLOCK_B,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-segment partial scores ``[B, S]`` for segments ``[0, S)`` — 1 launch.

    ``cumsum(result, axis=1) + base_score`` gives the prefix score of every
    document at every segment boundary (i.e. at every cascade sentinel).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S = pf.n_segments if n_segments is None else n_segments
    assert 0 < S <= pf.n_segments, (S, pf.n_segments)
    B = X.shape[0]
    x, block_b = _prep_x(X, block_b)

    seg_scores = _counted_pallas(
        "segmented", forest_score_segments_pallas,
        x, pf.feature, pf.threshold, pf.mask, pf.leaf_value,
        seg_block_starts=pf.seg_block_starts[:S],
        n_tree_blocks=pf.seg_block_starts[S - 1] + pf.seg_blocks[S - 1],
        block_b=block_b,
        block_t=pf.block_t,
        word_columns=pf.word_columns,
        leaf_gather=pf.leaf_gather,
        interpret=interpret,
    )
    return seg_scores[:B]


def forest_score(
    ens: TreeEnsemble,
    X: jax.Array,
    *,
    block_b: int = ENGINE_BLOCK_B,
    block_t: int = 16,
    leaf_gather: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Score ``X: [B, F]`` through the ensemble with the Pallas kernel."""
    pf = padded_forest(ens, block_t=block_t, leaf_gather=leaf_gather)
    return forest_score_range(pf, X, block_b=block_b, interpret=interpret)
