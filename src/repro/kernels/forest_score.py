"""Pallas TPU kernel: additive tree-ensemble scoring (QuickScorer, TPU-native).

Tiling
------
Grid ``(B / BB, T_run / BT)``; docs are the parallel axis, tree-blocks the
sequential (minor) accumulation axis. Per grid step, VMEM holds:

- one doc block      ``x        [BB, F]``     (f32, F lane-padded)
- one tree block     ``feature  [1, N·BT]`` / ``threshold [1, N·BT]``
-                    ``mask     [1, W·N·BT]`` (u32, QuickScorer false-node
  masks, W words per node, word-major)
-                    ``leaf_value [1, L·BT]`` (f32)
- the output block   ``scores   [BB, S]``     (f32, accumulated across tree
  blocks; S = 1 for the plain kernel)

The tree block ``BT`` follows from the padded tree shape
(:func:`tree_block`): ``max(N, L)·BT`` stays within :data:`ROW_LANES`, so
16 trees of up to 64 leaves, 8 of 128 and 4 of 256. Every per-step
intermediate is then ``[BB, ≤ 1024]`` whatever the depth, and ``BB`` stays
the engine's 256-row doc block (:data:`repro.kernels.ops.ENGINE_BLOCK_B`).

Lane layout
-----------
Every tree table enters the kernel as ONE lane-dense row per tree block,
node-major: lane ``n·BT + t`` holds node ``n`` of tree ``t`` (leaf tables:
lane ``l·BT + t``; mask word ``w`` at lane ``w·N·BT + n·BT + t``). The
entry points below build these rows from the ``[T, N]`` / ``[T, N, W]`` /
``[T, L]`` tables (a transpose per tree block), padding the node and leaf
axes so a row spans whole 128-lane vregs. With that layout every in-kernel
step is either a slice at a 128-lane boundary, a sublane broadcast of a
row, or a lane rotation (``pltpu.roll``) — the shapes Mosaic compiles —
and no value is ever reshaped across the lane axis.

Algorithm (per doc block × tree block)
--------------------------------------
1. **Feature gather as MXU matmul** — ``x [BB, F] @ onehot [F, N·BT]``
   with the one-hot built from a sublane iota compare against the feature
   row. The matmul is exact on any MXU: ``x`` is split into three bf16
   parts (``hi + mid + lo == x`` exactly) and each part multiplies the
   0/1 one-hot in bf16 with f32 accumulation, so every output is a single
   exact product — tree routing never depends on the MXU's f32 precision.
2. Node predicates ``x_f <= θ`` select, per mask word, either the
   all-ones word or the node's false-leaf word.
3. Order-free AND-reduction over the nodes, word by word: each word ANDs
   the 128-lane node columns ``word_columns[w]`` names, then a
   rotate-and-AND butterfly over the node groups inside the vreg. Every
   lane ``j`` ends up holding the AND for tree ``j mod BT``. A column left
   out of a word holds no node that zeroes it (its words are all ones), so
   leaving it out moves no bit. :func:`repro.kernels.ops.padded_forest`
   orders each tree's nodes by the highest word they zero, so a word of a
   256-leaf tree reads 1–3 of the 8 columns instead of all of them.
4. **Exit-leaf rule** — QuickScorer's: the exit leaf is the lowest set
   bit of the AND, found as count-trailing-zeros via
   ``popcount(~m & (m−1))`` of the lowest nonzero word plus its ``32·w``
   offset. Leaf values are then resolved through one of three
   *leaf-gather paths* (see below).
5. The BT per-tree values are summed by a pairwise rotate-and-add
   (:func:`_pairwise_lane_sum`, the association of
   :func:`_pairwise_tree_sum`), and the tree-block partial accumulates into
   its output column; the first tree step zero-initializes.

Leaf-gather paths
-----------------
Three interchangeable paths resolve ``leaf_value[t, leaf[b, t]]``,
selected by the static ``leaf_gather`` argument; all move the SAME f32
values, so they are bit-exact with each other:

- ``"select"`` (default for L ≤ :data:`repro.kernels.ops.LEAF_SELECT_MAX`,
  256, every tree the builders take): a
  select tree over the bits of the ctz leaf index, MSB first. The high
  bits pick 128-lane blocks of the leaf row (the first round reads the
  row directly); the low bits pick a leaf group inside the last vreg by
  lane rotation. ≈ L·BT/128 + log2(128/BT) vreg selects per doc block
  row. Requires a power-of-two leaf axis; the padded-buffer builder
  (:func:`repro.kernels.ops.padded_forest`) pads the leaf axis and tags
  the layout (``leaf_layout="pow2"``).
- ``"mxu"`` (default for L > :data:`repro.kernels.ops.LEAF_SELECT_MAX`): the
  one-hot-masked leaf row is contracted on the MXU against a 0/1
  tree-selector matrix ``[L·BT, 128]`` — exact because each output sums
  one leaf value against zeros (with the same three-way bf16 split).
- ``"onehot"``: the one-hot-masked leaf row summed on the VPU, kept as
  the in-kernel reference path (and the oracle the parity tests pin the
  other paths against).

Both entry points are dispatched through the counting wrapper in
:mod:`repro.kernels.ops` (``_counted_pallas``): launches are recorded at
staging time (per eager call, per trace under an enclosing ``jit``), so the
cascade engine's end-to-end jitted step keeps a testable launch contract
while XLA fuses the surrounding compact/gather/scatter work.

Tree ranges (head/tail from one buffer)
---------------------------------------
``tree_block_offset`` / ``n_tree_blocks`` restrict a launch to the padded
tree-block range ``[offset, offset + n)`` of a single device-resident buffer
set: the grid's minor axis shrinks to ``n`` and the tree-side index maps add
the static offset. Head and tail of a cascade therefore score from the SAME
padded arrays — no per-call re-slice / re-pad.

Sentinel-segmented output mode
------------------------------
:func:`forest_score_segments_pallas` widens the accumulator to a
``[B, S]`` per-segment block, where the S static segment boundaries
(``seg_block_starts``, in tree-block units) partition the launched tree
range. Each grid step derives its segment id from ``program_id(1)`` (a
static unrolled sum of ``j >= start`` predicates — scalar work) and adds
its partial into that segment's column through a lane-iota mask
(order-free, no dynamic stores). One launch therefore yields the partial
score of every document at EVERY sentinel; prefix scores are a ``[B, S]``
cumsum outside the kernel. The plain kernel is the same body with S = 1.

VMEM budget. ``BB = 256``, ``F ≤ 256`` and ``max(N, L)·BT = 1024`` at
every depth: 16 trees of 64 leaves (``N = 63→64``, 2 mask words) or 4
trees of 256 (``N = 255→256``, 8 words). x 256 KiB (double-buffered 512
KiB); one-hot ``[F, 1024]`` bf16 512 KiB; gathered features and the
predicates ``[BB, 1024]`` 1 MiB each; one mask word ``[BB, 128]`` column
128 KiB at a time; tree rows 4 KiB each for features, thresholds and
leaves and ``W × 4`` KiB for the mask (32 KiB at 256 leaves), double
buffered — a few MiB, inside the default scoped-VMEM limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.typecheck import Array, Float32, Int32, Ref, UInt32

ALL_ONES = np.uint32(0xFFFFFFFF)

LEAF_GATHERS = ("onehot", "select", "mxu")

LANE = 128

#: Lanes of a tree block's widest table row (``max(N, L)·BT``): the paper's
#: depth-6 trees at ``BT = 16`` (64 × 16). Every per-block intermediate is
#: ``[BB, ≤ ROW_LANES]``, whatever the tree width, so the VMEM budget of
#: the module docstring holds at any depth up to 256 leaves.
ROW_LANES = 1024


def tree_block(requested: int, n_nodes: int, n_leaves: int) -> int:
    """Trees per block for a padded tree shape: ``requested`` (a power of
    two), capped so that ``max(N, L)·BT`` stays within :data:`ROW_LANES` —
    16 up to 64 leaves, 8 at 128, 4 at 256."""
    widest = _next_pow2(max(n_nodes, n_leaves))
    return max(1, min(requested, ROW_LANES // widest))


def _next_pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def _lane_read(v: jax.Array, s: int) -> jax.Array:
    """Rotate ``v`` along lanes so that lane ``j`` holds lane ``j + s``
    (mod the lane width) — one XLU rotate per vreg."""
    w = v.shape[-1]
    return pltpu.roll(v, (w - s) % w, v.ndim - 1)


def _exact_dot(a: jax.Array, sel: jax.Array) -> jax.Array:
    """``a @ sel`` for a 0/1 ``sel`` whose every output column picks ONE
    nonzero product, exact in f32 on any MXU.

    ``a`` is split into three bf16 parts with ``hi + mid + lo == a``
    exactly (8 + 8 + 8 significand bits); each part times the 0/1 matrix
    is an exact bf16 product accumulated in f32, and the parts recombine
    exactly.
    """
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(part: jax.Array) -> jax.Array:
        return jax.lax.dot_general(
            part, sel,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    return (dot(hi) + dot(mid)) + dot(lo)


def _ctz_words(words: list[jax.Array]) -> jax.Array:
    """Count trailing zeros of a ``32·W``-bit value held as W uint32
    planes (``words[w]``: bits ``[32w, 32w + 32)``): ``popcount(~m & (m−1))``
    of the lowest nonzero word, plus its bit offset. The AND of QuickScorer
    masks always keeps the exit leaf's bit, so some word is nonzero."""
    leaf = None
    for w in reversed(range(len(words))):
        m = words[w]
        ctz = jax.lax.population_count(~m & (m - jnp.uint32(1))).astype(
            jnp.int32
        ) + jnp.int32(32 * w)
        leaf = ctz if leaf is None else jnp.where(m != 0, ctz, leaf)
    return leaf


def _and_over_columns(
    pred_true: jax.Array, mask_ref: Ref, offset: int,
    columns: tuple[int, ...], block_t: int,
) -> jax.Array:
    """One mask word's AND over the nodes of the 128-lane ``columns`` of a
    node-major row → ``[BB, 128]`` with lane ``j`` holding tree
    ``j mod BT``; the word's row starts at lane ``offset`` of the mask row.
    A column left out holds no node that zeroes this word, so its words
    are all ones. AND is associative and commutative, so the order is
    free."""
    if not columns:
        return jnp.full((pred_true.shape[0], LANE), ALL_ONES)
    m = None
    for c in columns:
        lanes = slice(c * LANE, (c + 1) * LANE)
        word = jnp.where(
            pred_true[:, lanes], ALL_ONES,
            mask_ref[:, offset + c * LANE:offset + (c + 1) * LANE],
        )
        m = word if m is None else m & word
    s = LANE // 2
    while s >= block_t:
        m = m & _lane_read(m, s)
        s //= 2
    return m


def _leaf_blocks(leaf_ref: Ref) -> list[jax.Array]:
    """The leaf row as ``[1, 128]`` blocks, each loaded at its own
    128-lane offset (a slice of the loaded row would keep a lane offset
    that Mosaic cannot broadcast from)."""
    return [
        leaf_ref[:, k * LANE:(k + 1) * LANE]
        for k in range(leaf_ref.shape[1] // LANE)
    ]


def _masked_leaf_blocks(
    leaf: jax.Array, leaf_ref: Ref, block_t: int
) -> list[jax.Array]:
    """Per 128-lane block of the leaf row: the leaf value where the lane's
    leaf index is the tree's exit leaf, 0 elsewhere (one hit per tree)."""
    shift = block_t.bit_length() - 1
    group = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1) >> shift
    per_block = LANE // block_t
    return [
        jnp.where(leaf == group + k * per_block, block, jnp.float32(0.0))
        for k, block in enumerate(_leaf_blocks(leaf_ref))
    ]


def _leaf_values_onehot(
    leaf: jax.Array, leaf_ref: Ref, block_t: int
) -> jax.Array:
    """Reference path: one-hot-masked leaf row, summed on the VPU. Each
    tree has exactly one nonzero term, so every add is exact and the
    order is free."""
    blocks = _masked_leaf_blocks(leaf, leaf_ref, block_t)
    acc = blocks[0]
    for blk in blocks[1:]:
        acc = acc + blk
    s = LANE // 2
    while s >= block_t:
        acc = acc + _lane_read(acc, s)
        s //= 2
    return acc


def _leaf_values_select(
    leaf: jax.Array, leaf_ref: Ref, block_t: int
) -> jax.Array:
    """Select tree on the leaf-index bits, MSB first: the high bits pick
    128-lane blocks of the leaf row, the low bits pick a leaf group inside
    the last vreg by lane rotation. L must be a power of two. Lanes
    ``[0, BT)`` of the result hold the per-tree values."""
    blocks = _leaf_blocks(leaf_ref)
    r = (len(blocks) * (LANE // block_t)).bit_length() - 2  # leaf-index MSB
    while len(blocks) > 1:
        half = len(blocks) // 2
        take_hi = ((leaf >> r) & 1) == 1
        blocks = [
            jnp.where(take_hi, blocks[k + half], blocks[k])
            for k in range(half)
        ]
        r -= 1
    cur = jnp.broadcast_to(blocks[0], leaf.shape)
    while r >= 0:
        take_hi = ((leaf >> r) & 1) == 1
        cur = jnp.where(take_hi, _lane_read(cur, (1 << r) * block_t), cur)
        r -= 1
    return cur


def _leaf_values_mxu(
    leaf: jax.Array, leaf_ref: Ref, block_t: int
) -> jax.Array:
    """MXU contraction: the one-hot-masked leaf row ``[BB, L·BT]`` times a
    0/1 selector ``[L·BT, 128]`` that sums lane ``l·BT + t`` into every
    output lane of tree ``t`` — one exact product per output."""
    masked = jnp.concatenate(
        _masked_leaf_blocks(leaf, leaf_ref, block_t), axis=1
    )
    width = masked.shape[1]
    tree_of_row = jax.lax.broadcasted_iota(jnp.int32, (width, LANE), 0)
    tree_of_col = jax.lax.broadcasted_iota(jnp.int32, (width, LANE), 1)
    selector = jnp.where(
        (tree_of_row & (block_t - 1)) == (tree_of_col & (block_t - 1)),
        1.0, 0.0,
    ).astype(jnp.bfloat16)
    return _exact_dot(masked, selector)


_LEAF_VALUE_FNS = {
    "onehot": _leaf_values_onehot,
    "select": _leaf_values_select,
    "mxu": _leaf_values_mxu,
}


def _pairwise_tree_sum(per_tree: jax.Array) -> jax.Array:
    """Deterministic contiguous-halves sum over the tree axis: [BB, BT]→[BB].

    Explicit elementwise adds instead of ``jnp.sum`` — a ``reduce``'s
    association is implementation-defined and shifts with how XLA fuses the
    surrounding graph, which would break bit-for-bit parity between paths
    whose per-tree values are identical. Handles non-power-of-two BT by
    carrying the odd trailing element. The kernel's in-register
    counterpart is :func:`_pairwise_lane_sum`.
    """
    n = per_tree.shape[1]
    while n > 1:
        half = n // 2
        summed = per_tree[:, :half] + per_tree[:, half:2 * half]
        if n % 2:
            summed = jnp.concatenate([summed, per_tree[:, 2 * half:]], axis=1)
        per_tree = summed
        n = per_tree.shape[1]
    return per_tree[:, 0]


def _pairwise_lane_sum(per_tree: jax.Array, block_t: int) -> jax.Array:
    """:func:`_pairwise_tree_sum` over lanes ``[0, BT)`` of a ``[BB, 128]``
    value → ``[BB, 1]``: each round adds lane ``j + s`` into lane ``j``,
    halving ``s`` — the same contiguous-halves association (BT is a power
    of two), so every leaf-gather path sums in one fixed order."""
    s = block_t // 2
    while s >= 1:
        per_tree = per_tree + _lane_read(per_tree, s)
        s //= 2
    return per_tree[:, :1]


def _score_block(
    x_ref: Ref, feat_ref: Ref, thr_ref: Ref, mask_ref: Ref, leaf_ref: Ref,
    *,
    block_t: int,
    word_columns: tuple[tuple[int, ...], ...],
    leaf_gather: str,
) -> jax.Array:
    """One doc-block × tree-block partial score ``[BB, 1]`` (steps 1-5)."""
    x = x_ref[...]
    feat = feat_ref[...]                                         # [1, N·BT]
    F = x.shape[1]
    width = feat.shape[1]

    # (1) Feature gather: xf[b, n·BT + t] = x[b, feature[t, n]].
    feat_iota = jax.lax.broadcasted_iota(jnp.int32, (F, width), 0)
    onehot = jnp.where(feat_iota == feat, 1.0, 0.0).astype(jnp.bfloat16)
    xf = _exact_dot(x, onehot)                                   # [BB, N·BT]

    # (2) Predicates select, per mask word, the all-ones word or the
    # node's false-leaf word, and (3) the selected words are ANDed over the
    # word's node columns, one [BB, 128] column at a time.
    pred_true = xf <= thr_ref[...]
    words = [
        _and_over_columns(pred_true, mask_ref, w * width, columns, block_t)
        for w, columns in enumerate(word_columns)
    ]

    # (4) Exit leaf → leaf value, lane j = tree j % BT.
    leaf = _ctz_words(words)                                     # [BB, 128]
    per_tree = _LEAF_VALUE_FNS[leaf_gather](leaf, leaf_ref, block_t)
    return _pairwise_lane_sum(per_tree, block_t)                 # [BB, 1]


def _forest_kernel(
    x_ref: Ref,        # [BB, F] f32
    feat_ref: Ref,     # [1, N·BT] i32
    thr_ref: Ref,      # [1, N·BT] f32
    mask_ref: Ref,     # [1, W·N·BT] u32, word-major
    leaf_ref: Ref,     # [1, L·BT] f32
    out_ref: Ref,      # [BB, S] f32 — per-segment partials, accumulated over j
    *,
    seg_block_starts: tuple[int, ...],
    block_t: int,
    word_columns: tuple[tuple[int, ...], ...],
    leaf_gather: str,
) -> None:
    partial = _score_block(
        x_ref, feat_ref, thr_ref, mask_ref, leaf_ref,
        block_t=block_t, word_columns=word_columns, leaf_gather=leaf_gather,
    )

    # Segment id of this tree block: static unrolled predicate sum (scalar).
    j = pl.program_id(1)
    seg = jnp.int32(0)
    for start in seg_block_starts[1:]:
        seg = seg + (j >= start).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Order-free accumulate into the segment's column; no dynamic store.
    column = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(column == seg, partial, jnp.float32(0.0))


def _tree_rows(
    table: jax.Array, block_t: int, width: int, pad: float | np.uint32
) -> jax.Array:
    """``[T, w]`` tree table → ``[T/BT, 1, width·BT]``: one node-major row
    per tree block (lane ``n·BT + t`` = entry ``n`` of tree ``t``), the
    per-tree axis padded to ``width`` with the no-op value ``pad``."""
    T, w = table.shape
    if width > w:
        table = jnp.pad(table, ((0, 0), (0, width - w)), constant_values=pad)
    rows = table.reshape(T // block_t, block_t, width).transpose(0, 2, 1)
    return rows.reshape(T // block_t, 1, width * block_t)


def _check_leaf_gather(leaf_gather: str, n_leaves: int) -> None:
    assert leaf_gather in LEAF_GATHERS, leaf_gather
    if leaf_gather == "select":
        assert n_leaves & (n_leaves - 1) == 0, (
            f"leaf_gather='select' needs a power-of-two leaf axis, got "
            f"{n_leaves} — use repro.kernels.ops.padded_forest (it pads the "
            f"leaf axis and tags the layout) or pass 'mxu'/'onehot'"
        )


def _forest_call(
    x: jax.Array,
    feature: jax.Array,
    threshold: jax.Array,
    mask: jax.Array,
    leaf_value: jax.Array,
    *,
    block_b: int,
    block_t: int,
    tree_block_offset: int,
    n_tree_blocks: int,
    seg_block_starts: tuple[int, ...],
    word_columns: tuple[tuple[int, ...], ...] | None,
    leaf_gather: str,
    interpret: bool,
) -> jax.Array:
    """The one ``pallas_call`` behind both entry points → ``[B, S]``."""
    B, F = x.shape
    T, N = feature.shape
    L = leaf_value.shape[1]
    assert mask.shape[:2] == (T, N), (mask.shape, T, N)
    assert B % block_b == 0 and T % block_t == 0, (B, block_b, T, block_t)
    assert N & (N - 1) == 0, f"node axis must be a power of two, got {N}"
    assert block_t & (block_t - 1) == 0 and block_t <= LANE, block_t
    _check_leaf_gather(leaf_gather, L)
    assert 0 < n_tree_blocks <= T // block_t - tree_block_offset, (
        n_tree_blocks, tree_block_offset, T // block_t
    )

    # Pad each per-tree axis so a block row fills whole vregs: no-op nodes
    # (feature 0, threshold +inf ⇒ always true, all-ones masks) and
    # unreachable zero leaves.
    per_vreg = LANE // block_t
    n_width = max(N, per_vreg)
    l_width = -(-L // per_vreg) * per_vreg
    # The mask row is word-major: word w of node n of tree t at lane
    # w·n_width·BT + n·BT + t, so each word is a 128-aligned slice.
    mask_row = jnp.concatenate(
        [
            _tree_rows(mask[..., w], block_t, n_width, ALL_ONES)
            for w in range(mask.shape[2])
        ],
        axis=2,
    )
    rows = [
        _tree_rows(feature, block_t, n_width, 0),
        _tree_rows(threshold, block_t, n_width, np.inf),
        mask_row,
        _tree_rows(leaf_value, block_t, l_width, 0.0),
    ]
    tree_spec = lambda width: pl.BlockSpec(
        (pl.squeezed, 1, width),
        lambda i, j: (j + tree_block_offset, 0, 0),
    )
    n_columns = n_width * block_t // LANE
    if word_columns is None:
        word_columns = (tuple(range(n_columns)),) * mask.shape[2]
    assert len(word_columns) == mask.shape[2], (word_columns, mask.shape)
    assert all(0 <= c < n_columns for cs in word_columns for c in cs)
    n_seg = len(seg_block_starts)
    kernel = functools.partial(
        _forest_kernel,
        seg_block_starts=seg_block_starts,
        block_t=block_t,
        word_columns=word_columns,
        leaf_gather=leaf_gather,
    )
    return pl.pallas_call(
        kernel,
        grid=(B // block_b, n_tree_blocks),
        in_specs=[
            pl.BlockSpec((block_b, F), lambda i, j: (i, 0)),
            *(tree_spec(r.shape[2]) for r in rows),
        ],
        out_specs=pl.BlockSpec((block_b, n_seg), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_seg), jnp.float32),
        interpret=interpret,
    )(x, *rows)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_b", "block_t", "tree_block_offset", "n_tree_blocks",
        "word_columns", "leaf_gather", "interpret",
    ),
)
def forest_score_pallas(
    x: Float32[Array, "b f"],          # B % block_b == 0, F lane-padded
    feature: Int32[Array, "t n"],      # T % block_t == 0, N power of two
    threshold: Float32[Array, "t n"],
    mask: UInt32[Array, "t n w"],
    leaf_value: Float32[Array, "t l"],
    *,
    block_b: int = 256,
    block_t: int = 16,
    tree_block_offset: int = 0,
    n_tree_blocks: int | None = None,
    word_columns: tuple[tuple[int, ...], ...] | None = None,
    leaf_gather: str = "onehot",
    interpret: bool = True,
) -> Float32[Array, "b"]:
    if n_tree_blocks is None:
        n_tree_blocks = feature.shape[0] // block_t - tree_block_offset
    return _forest_call(
        x, feature, threshold, mask, leaf_value,
        block_b=block_b,
        block_t=block_t,
        tree_block_offset=tree_block_offset,
        n_tree_blocks=n_tree_blocks,
        seg_block_starts=(0,),
        word_columns=word_columns,
        leaf_gather=leaf_gather,
        interpret=interpret,
    )[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "seg_block_starts", "n_tree_blocks", "block_b", "block_t",
        "word_columns", "leaf_gather", "interpret",
    ),
)
def forest_score_segments_pallas(
    x: Float32[Array, "b f"],          # B % block_b == 0, F lane-padded
    feature: Int32[Array, "t n"],      # T % block_t == 0, N power of two
    threshold: Float32[Array, "t n"],
    mask: UInt32[Array, "t n w"],
    leaf_value: Float32[Array, "t l"],
    *,
    seg_block_starts: tuple[int, ...],  # ascending, seg_block_starts[0] == 0
    n_tree_blocks: int,                 # launch covers blocks [0, n)
    block_b: int = 256,
    block_t: int = 16,
    word_columns: tuple[tuple[int, ...], ...] | None = None,
    leaf_gather: str = "onehot",
    interpret: bool = True,
) -> Float32[Array, "b s"]:
    """Single launch → per-segment partial scores ``[B, S]``.

    Segment ``k`` covers tree blocks ``[seg_block_starts[k],
    seg_block_starts[k+1])`` (the last runs to ``n_tree_blocks``). Prefix
    scores at sentinel ``k`` are ``cumsum(out, axis=1)[:, k]``.
    """
    assert seg_block_starts[0] == 0
    assert list(seg_block_starts) == sorted(set(seg_block_starts))
    assert seg_block_starts[-1] < n_tree_blocks
    return _forest_call(
        x, feature, threshold, mask, leaf_value,
        block_b=block_b,
        block_t=block_t,
        tree_block_offset=0,
        n_tree_blocks=n_tree_blocks,
        seg_block_starts=seg_block_starts,
        word_columns=word_columns,
        leaf_gather=leaf_gather,
        interpret=interpret,
    )
