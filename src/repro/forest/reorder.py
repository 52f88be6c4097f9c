"""Learned tree reordering for additive ensembles (QWYC-style).

A GBDT's trees arrive in boosting order, but nothing in the additive
model requires traversing them that way. "Quit While You're Ahead"
(arXiv 1806.11202) showed that reordering trees so the *partial* prefix
sum converges to the full score as early as possible makes every
early-exit policy cheaper at matched quality: the sentinel sees a
better score estimate after the same number of trees, so document- and
query-level exits fire sooner.

This module learns such an order offline from per-tree contributions on
a validation slice and materializes the permuted ensemble:

- :func:`per_tree_contributions` — ``[B, T]`` leaf values per (doc,
  tree) on device (the same exit-leaf machinery the kernel implements);
- :func:`greedy_order` — greedy residual-fit: repeatedly pick the tree
  whose contribution best reduces the remaining squared residual to the
  full score (host numpy, float64);
- :func:`variance_order` — cheap baseline: descending contribution
  variance (high-variance trees decide ranks, play them first);
- :func:`reorder_trees` — apply a permutation to every tree-indexed
  array of a :class:`TreeEnsemble` (a NEW instance, so the per-instance
  ``padded_forest`` cache pads the permuted layout once and serves it);
- :func:`prefix_residual` — convergence diagnostic used by the tests
  and the tradeoff bench;
- :func:`learn_order` / :func:`reordered_ensemble` — the offline entry
  points the bench drives.

Determinism contract: reordering only *permutes* the per-tree terms; the
final score equals the identity ordering's score up to reassociation of
the tree-axis reduction, and is BIT-EXACT through every path that
reduces via ``_pairwise_tree_sum`` on the same tree count. That is why
this module sits under the TS003 lint scope
(``config.TREE_SUM_EXTRA_ROOT_SUFFIXES``): a bare ``sum`` anywhere
between leaf values and scores would silently void the invariance the
reorder tests pin. The order *learning* itself runs in host float64 and
never produces a score, so its linear algebra (matmul/einsum) is exempt
by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.forest.ensemble import TreeEnsemble
from repro.forest.scoring import exit_leaves_bitvector
from repro.kernels.forest_score import _pairwise_tree_sum


def per_tree_contributions(ens: TreeEnsemble, X: jax.Array) -> jax.Array:
    """Leaf value each tree contributes per document → ``[B, T]`` f32.

    Exit leaves come from the same QuickScorer bitvector reduction the
    Pallas kernel implements, so the contributions match what any device
    path would accumulate. ``base_score`` is excluded: it is ordering-
    invariant by definition.
    """
    leaves = exit_leaves_bitvector(ens, X)                      # [B, T]
    return jnp.take_along_axis(
        ens.leaf_value[None, :, :], leaves[:, :, None], axis=2
    )[..., 0]


def full_from_contributions(ens: TreeEnsemble, per_tree: jax.Array) -> jax.Array:
    """Total score from a contribution matrix via the sanctioned reducer."""
    return _pairwise_tree_sum(per_tree) + ens.base_score


def greedy_order(contrib: np.ndarray) -> np.ndarray:
    """Greedy residual-fit ordering → permutation ``[T]`` int64.

    At each step, with residual ``r = full − prefix`` over the
    validation docs, adding tree ``t`` changes the squared residual by
    ``||r − C_t||² − ||r||² = ||C_t||² − 2⟨r, C_t⟩`` — so pick the tree
    maximizing ``2⟨r, C_t⟩ − ||C_t||²``. The Gram matrix makes each step
    O(T): picking ``t`` shifts every inner product by ``−G[:, t]``.

    Runs in float64 on host: this learns an *order*, not a score, so it
    is outside the bit-exactness contract — stability across platforms
    comes from float64 headroom plus deterministic argmax tie-breaking
    (numpy argmax takes the first maximum).
    """
    C = np.asarray(contrib, dtype=np.float64)
    B, T = C.shape
    assert B >= 1 and T >= 1, C.shape
    gram = C.T @ C                                              # [T, T]
    # ⟨C_t, r₀⟩ where r₀ = Σ_u C_u: a row of Gram-column totals.
    score = np.einsum("tu->t", gram)
    sq = np.diagonal(gram).copy()
    used = np.zeros(T, dtype=bool)
    order = np.empty(T, dtype=np.int64)
    for i in range(T):
        gain = np.where(used, -np.inf, 2.0 * score - sq)
        t = int(np.argmax(gain))
        order[i] = t
        used[t] = True
        score = score - gram[:, t]
    return order


def variance_order(contrib: np.ndarray) -> np.ndarray:
    """Descending contribution variance → permutation ``[T]`` int64.

    The cheap baseline: a tree whose contribution varies across
    documents separates them; a near-constant tree only shifts every
    score and can safely run late. Stable sort keeps boosting order
    among ties (deterministic across platforms).
    """
    C = np.asarray(contrib, dtype=np.float64)
    B = C.shape[0]
    mean = np.einsum("bt->t", C) / B
    ex2 = np.einsum("bt,bt->t", C, C) / B
    var = ex2 - mean * mean
    return np.argsort(-var, kind="stable").astype(np.int64)


def prefix_residual(contrib: np.ndarray, order: np.ndarray) -> np.ndarray:
    """Mean squared full-score residual after each prefix → ``[T]`` f64.

    ``out[m]`` = mean over docs of ``(prefix_{m+1} − full)²`` under
    ``order`` — the convergence curve an ordering is judged by (lower
    earlier = every exit policy sees a better estimate sooner).
    Float64 diagnostic; einsum keeps the tree-axis discipline the TS003
    scope expects even though no score leaves this function.
    """
    C = np.asarray(contrib, dtype=np.float64)[:, np.asarray(order)]
    prefix = np.cumsum(C, axis=1)                               # [B, T]
    resid = prefix - prefix[:, -1:]
    return np.einsum("bt,bt->t", resid, resid) / C.shape[0]


def reorder_trees(ens: TreeEnsemble, order: np.ndarray) -> TreeEnsemble:
    """Materialize the permuted ensemble (validated permutation).

    Every ``[T, ...]`` array is gathered along axis 0; ``base_score`` is
    ordering-invariant. Returns a NEW ``TreeEnsemble`` instance — its
    per-instance padded-buffer cache starts empty, so the permuted
    layout is padded once on first kernel use and reused after, exactly
    like any other ensemble.
    """
    idx = np.asarray(order)
    T = ens.n_trees
    assert idx.shape == (T,), (idx.shape, T)
    assert np.array_equal(np.sort(idx), np.arange(T)), "not a permutation"
    take = jnp.asarray(idx, dtype=jnp.int32)
    return TreeEnsemble(
        feature=jnp.take(ens.feature, take, axis=0),
        threshold=jnp.take(ens.threshold, take, axis=0),
        left=jnp.take(ens.left, take, axis=0),
        right=jnp.take(ens.right, take, axis=0),
        mask=jnp.take(ens.mask, take, axis=0),
        leaf_value=jnp.take(ens.leaf_value, take, axis=0),
        base_score=ens.base_score,
    )


def learn_order(
    ens: TreeEnsemble,
    X_valid: jax.Array,
    method: str = "greedy",
    max_docs: int | None = 4096,
) -> np.ndarray:
    """Learn a traversal order from a validation slice → ``[T]`` int64.

    ``X_valid`` is ``[B, F]`` flat documents (rank the validation fold's
    docs however you like — the objective is per-document). ``max_docs``
    caps the slice with a deterministic stride (not a prefix: query
    blocks arrive grouped, and a prefix would overfit the first
    queries). ``method`` ∈ {"greedy", "variance", "identity"}.
    """
    assert method in ("greedy", "variance", "identity"), method
    if method == "identity":
        return np.arange(ens.n_trees, dtype=np.int64)
    B = X_valid.shape[0]
    if max_docs is not None and B > max_docs:
        stride = -(-B // max_docs)  # ceil: keeps ≤ max_docs rows
        X_valid = X_valid[::stride]
    contrib = np.asarray(per_tree_contributions(ens, X_valid))
    if method == "greedy":
        return greedy_order(contrib)
    return variance_order(contrib)


def reordered_ensemble(
    ens: TreeEnsemble,
    X_valid: jax.Array,
    method: str = "greedy",
    max_docs: int | None = 4096,
) -> tuple[TreeEnsemble, np.ndarray]:
    """One-call offline entry point: learned order + permuted ensemble."""
    order = learn_order(ens, X_valid, method=method, max_docs=max_docs)
    return reorder_trees(ens, order), order
