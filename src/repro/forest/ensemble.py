"""Tensorized additive tree ensembles with QuickScorer-style bitmasks.

A ``TreeEnsemble`` stores ``T`` binary decision trees padded to a common
``n_nodes`` internal-node count and ``n_leaves`` leaf count, as dense arrays
shaped ``[T, n_nodes]`` / ``[T, n_leaves]``. Two traversal encodings coexist:

1. **Structural** (``left``/``right`` child indices) for classic root→leaf
   level stepping. Child entries ``>= 0`` index internal nodes; entries
   ``< 0`` encode leaves as ``-(leaf_id + 1)``.
2. **QuickScorer bitmask** (``mask``): for each internal node ``n``, an
   ``n_leaves``-bit mask with zeros at the leaves of the *left* subtree of
   ``n``, stored as ``W = ceil(n_leaves / 32)`` uint32 words (word ``w``
   holds leaves ``[32w, 32w + 32)``; :func:`mask_words`). QuickScorer's
   theorem: the exit leaf of a document is the **lowest set bit** of the
   AND of the masks of its *false* nodes (nodes whose test
   ``x[feat] <= thr`` fails). True/padded nodes contribute the all-ones
   mask, making the reduction order-free — the key property that maps the
   CPU algorithm onto TPU vector units.

Leaves are numbered left-to-right (in-order), which is what makes the
lowest-set-bit rule correct. The word count follows from ``n_leaves``
alone: 1 word up to 32 leaves, 2 for the paper's 64-leaf trees, 8 for the
256-leaf trees of a depth-8 λ-MART. Both builders refuse trees of more
than :data:`MAX_LEAVES` leaves, the widest the forest kernel's tiling
covers (:mod:`repro.kernels.forest_score`).
"""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

ALL_ONES = np.uint32(0xFFFFFFFF)

#: Leaves per mask word.
WORD_BITS = 32
#: The widest tree either builder accepts: the forest kernel derives its
#: tree block from the padded tree shape down to 4 trees of 256 leaves.
MAX_LEAVES = 256


def mask_words(n_leaves: int) -> int:
    """uint32 words of a node's false-leaf mask for ``n_leaves`` leaves."""
    return max(1, -(-int(n_leaves) // WORD_BITS))


def _check_leaves(n_leaves: int) -> None:
    if n_leaves > MAX_LEAVES:
        raise ValueError(
            f"trees of {n_leaves} leaves: the bitmask encoding and the "
            f"forest kernel take at most {MAX_LEAVES}"
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TreeEnsemble:
    """Dense, padded additive ensemble of binary regression trees.

    Instances additionally carry a lazily-attached ``_padded_cache`` dict
    (written via ``object.__setattr__`` by
    :func:`repro.kernels.ops.padded_forest`) holding kernel-aligned buffer
    sets keyed by segment boundaries × tree-block size — pad once, score
    many. The cache is NOT a pytree field: it does not survive jit
    boundaries or :func:`dataclasses.replace`, which is fine because it is
    only ever a cache.
    """

    feature: jax.Array    # [T, N] int32 — split feature per internal node
    threshold: jax.Array  # [T, N] float32 — split threshold (x <= thr → left)
    left: jax.Array       # [T, N] int32 — left child (neg = ~leaf encoding)
    right: jax.Array      # [T, N] int32
    mask: jax.Array       # [T, N, W] uint32 — QS false-node mask, W words
    leaf_value: jax.Array  # [T, L] float32
    base_score: jax.Array  # [] float32 — additive offset (e.g. logit prior)

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def n_nodes(self) -> int:
        return self.feature.shape[1]

    @property
    def n_leaves(self) -> int:
        return self.leaf_value.shape[1]

    @property
    def depth(self) -> int:
        # Padded complete-tree depth bound: n_leaves = 2**depth.
        return int(np.log2(self.n_leaves))

    def astype(self, dtype) -> TreeEnsemble:
        return dataclasses.replace(
            self,
            threshold=self.threshold.astype(dtype),
            leaf_value=self.leaf_value.astype(dtype),
            base_score=self.base_score.astype(dtype),
        )


def slice_trees(ens: TreeEnsemble, start: int, stop: int) -> TreeEnsemble:
    """Sub-ensemble of trees [start, stop) — used to split at a sentinel."""
    keep_base = jnp.where(start == 0, ens.base_score, jnp.zeros_like(ens.base_score))
    return TreeEnsemble(
        feature=ens.feature[start:stop],
        threshold=ens.threshold[start:stop],
        left=ens.left[start:stop],
        right=ens.right[start:stop],
        mask=ens.mask[start:stop],
        leaf_value=ens.leaf_value[start:stop],
        base_score=keep_base,
    )


def concat_ensembles(parts: Sequence[TreeEnsemble]) -> TreeEnsemble:
    base = parts[0].base_score
    return TreeEnsemble(
        feature=jnp.concatenate([p.feature for p in parts]),
        threshold=jnp.concatenate([p.threshold for p in parts]),
        left=jnp.concatenate([p.left for p in parts]),
        right=jnp.concatenate([p.right for p in parts]),
        mask=jnp.concatenate([p.mask for p in parts]),
        leaf_value=jnp.concatenate([p.leaf_value for p in parts]),
        base_score=base,
    )


# ---------------------------------------------------------------------------
# Construction from explicit tree structure (numpy, host side).
# ---------------------------------------------------------------------------


def _leaf_spans(left: np.ndarray, right: np.ndarray, n_nodes: int):
    """In-order leaf numbering: for each internal node return (lo, mid, hi) —
    its subtree covers leaves [lo, hi), left child covers [lo, mid)."""
    spans = np.zeros((n_nodes, 3), dtype=np.int64)
    counter = [0]

    def visit(node: int) -> tuple[int, int]:
        if node < 0:  # leaf
            i = counter[0]
            counter[0] += 1
            return i, i + 1
        lo, mid = visit(int(left[node]))
        _, hi = visit(int(right[node]))
        spans[node] = (lo, mid, hi)
        return lo, hi

    visit(0)
    return spans, counter[0]


def _span_mask(lo: int, hi: int, words: int) -> np.ndarray:
    """``[words]`` uint32: a ``32·words``-bit mask with zeros on bits
    [lo, hi), word ``w`` holding bits [32w, 32w + 32)."""
    assert hi <= words * WORD_BITS, (hi, words)
    bits = ((1 << hi) - 1) ^ ((1 << lo) - 1)  # ones on [lo, hi)
    inv = ~bits & ((1 << (words * WORD_BITS)) - 1)
    return np.array(
        [(inv >> (WORD_BITS * w)) & 0xFFFFFFFF for w in range(words)],
        dtype=np.uint32,
    )


def from_arrays(
    features: list[np.ndarray],
    thresholds: list[np.ndarray],
    lefts: list[np.ndarray],
    rights: list[np.ndarray],
    leaf_values: list[np.ndarray],
    base_score: float = 0.0,
    n_nodes: int | None = None,
    n_leaves: int | None = None,
) -> TreeEnsemble:
    """Build a padded TreeEnsemble from per-tree structure arrays.

    Per-tree convention: internal nodes indexed 0..n_int-1 (root = 0); child
    entries < 0 encode leaf ``-(leaf_slot+1)`` into that tree's
    ``leaf_values``. Leaf slots are renumbered here to in-order so the
    QuickScorer mask rule holds regardless of input numbering.
    """
    T = len(features)
    max_int = max(int(f.shape[0]) for f in features)
    n_nodes = n_nodes or max_int
    max_leaves = max(int(lv.shape[0]) for lv in leaf_values)
    n_leaves = n_leaves or max_leaves
    _check_leaves(n_leaves)
    words = mask_words(n_leaves)

    feat = np.zeros((T, n_nodes), dtype=np.int32)
    thr = np.full((T, n_nodes), np.float32(np.inf))  # padded → always-true node
    left = np.full((T, n_nodes), -1, dtype=np.int32)
    right = np.full((T, n_nodes), -1, dtype=np.int32)
    mask = np.full((T, n_nodes, words), ALL_ONES, dtype=np.uint32)
    lv = np.zeros((T, n_leaves), dtype=np.float32)

    for t in range(T):
        n_int = int(features[t].shape[0])
        feat[t, :n_int] = features[t]
        thr[t, :n_int] = thresholds[t]
        lt, rt = lefts[t].astype(np.int64), rights[t].astype(np.int64)
        spans, n_leaf_t = _leaf_spans(lt, rt, n_int)
        # Renumber leaves to in-order: walk again mapping old slot → in-order id.
        order = np.zeros(n_leaf_t, dtype=np.int64)  # in-order id → old slot
        counter = [0]

        def visit(node: int):
            if node < 0:
                order[counter[0]] = -(node + 1)
                counter[0] += 1
                return
            visit(int(lt[node]))
            visit(int(rt[node]))

        visit(0)
        lv[t, :n_leaf_t] = leaf_values[t][order]
        # Children re-encoded with in-order leaf ids.
        old2new = np.zeros(n_leaf_t, dtype=np.int64)
        old2new[order] = np.arange(n_leaf_t)
        for n in range(n_int):
            for arr_in, arr_out in ((lt, left), (rt, right)):
                c = int(arr_in[n])
                arr_out[t, n] = c if c >= 0 else -(int(old2new[-(c + 1)]) + 1)
            lo, mid, _hi = spans[n]
            mask[t, n] = _span_mask(int(lo), int(mid), words)

    return TreeEnsemble(
        feature=jnp.asarray(feat),
        threshold=jnp.asarray(thr),
        left=jnp.asarray(left),
        right=jnp.asarray(right),
        mask=jnp.asarray(mask),
        leaf_value=jnp.asarray(lv),
        base_score=jnp.float32(base_score),
    )


def from_complete_arrays(
    feature: np.ndarray,   # [T, 2**D - 1] heap-ordered internal nodes
    threshold: np.ndarray,  # [T, 2**D - 1]
    leaf_value: np.ndarray,  # [T, 2**D] left-to-right leaves
    base_score: float = 0.0,
) -> TreeEnsemble:
    """Fast path for complete depth-D trees in heap layout (the GBDT output).

    Heap node ``n`` has children ``2n+1`` / ``2n+2``; leaves are already
    left-to-right so masks come from closed-form spans.
    """
    T, n_int = feature.shape
    depth = int(np.log2(n_int + 1))
    n_leaves = 1 << depth
    _check_leaves(n_leaves)
    words = mask_words(n_leaves)
    left = np.zeros((T, n_int), dtype=np.int32)
    right = np.zeros((T, n_int), dtype=np.int32)
    mask = np.zeros((T, n_int, words), dtype=np.uint32)
    for n in range(n_int):
        d = int(np.floor(np.log2(n + 1)))
        # Heap node n is the (n - (2**d - 1))-th node of level d; its subtree
        # spans 2**(depth - d) leaves starting at that offset.
        pos = n - ((1 << d) - 1)
        span = 1 << (depth - d)
        lo = pos * span
        mid = lo + span // 2
        l_child, r_child = 2 * n + 1, 2 * n + 2
        left[:, n] = l_child if l_child < n_int else -(lo + 1)
        right[:, n] = r_child if r_child < n_int else -(mid + 1)
        mask[:, n] = _span_mask(lo, mid, words)
    return TreeEnsemble(
        feature=jnp.asarray(feature.astype(np.int32)),
        threshold=jnp.asarray(threshold.astype(np.float32)),
        left=jnp.asarray(left),
        right=jnp.asarray(right),
        mask=jnp.asarray(mask),
        leaf_value=jnp.asarray(leaf_value.astype(np.float32)),
        base_score=jnp.float32(base_score),
    )


def random_ensemble(
    key,
    n_trees: int,
    depth: int,
    n_features: int,
    leaf_scale: float = 0.1,
) -> TreeEnsemble:
    """Random complete-tree ensemble — used by tests and kernel sweeps."""
    rng = np.random.default_rng(np.asarray(key)[-1] if hasattr(key, "shape") else key)
    n_int = (1 << depth) - 1
    feature = rng.integers(0, n_features, size=(n_trees, n_int))
    threshold = rng.normal(size=(n_trees, n_int)).astype(np.float32)
    leaf_value = (leaf_scale * rng.normal(size=(n_trees, 1 << depth))).astype(np.float32)
    return from_complete_arrays(feature, threshold, leaf_value)
