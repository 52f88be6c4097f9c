"""Reference scorers for tensorized tree ensembles (pure jnp oracles).

Three implementations with identical semantics:

- :func:`score_numpy_oracle` — per-document recursive traversal in numpy;
  slowest, trusted ground truth for tests.
- :func:`score_level` — vectorized root→leaf stepping (``depth`` dependent
  gather steps). Mirrors classic batched traversal.
- :func:`score_bitvector` — QuickScorer-adapted: order-free AND-reduction of
  false-node masks, exit leaf = lowest set bit. This is the algorithm the
  Pallas kernel implements; it is also the fastest pure-XLA path on TPU
  because it has no sequentially-dependent gathers.

All scorers take ``X: [B, F]`` float and return ``[B]`` scores
(plus optionally per-tree partials).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.forest.ensemble import TreeEnsemble


def _ctz_words(words: jax.Array) -> jax.Array:
    """Count trailing zeros of a multi-word value, ``words[..., w]`` holding
    bits ``[32w, 32w + 32)`` → int32 ``[...]``.

    ctz(m) = popcount(~m & (m - 1)) in the lowest nonzero word; the AND of
    QS masks is never 0 (the exit leaf bit always survives), so no special
    case is needed.
    """
    n_words = words.shape[-1]
    leaf = None
    for w in reversed(range(n_words)):
        m = words[..., w]
        ctz = jax.lax.population_count(~m & (m - jnp.uint32(1))).astype(
            jnp.int32
        ) + jnp.int32(32 * w)
        leaf = ctz if leaf is None else jnp.where(m != 0, ctz, leaf)
    return leaf


def exit_leaves_bitvector(ens: TreeEnsemble, X: jax.Array) -> jax.Array:
    """Exit leaf index per (doc, tree) via mask AND-reduction. → [B, T] int32."""
    # Gather tested feature values: [B, T, N].
    xf = X[:, ens.feature]  # fancy-index over axis 1 with [T, N] indices
    pred_true = xf <= ens.threshold[None, :, :]
    ones = jnp.uint32(0xFFFFFFFF)
    m = jnp.where(pred_true[..., None], ones, ens.mask[None])  # [B, T, N, W]
    # Order-free AND-reduction over the node axis, per word.
    words = jax.lax.reduce(m, ones, jax.lax.bitwise_and, dimensions=(2,))
    return _ctz_words(words)


def score_bitvector(
    ens: TreeEnsemble, X: jax.Array, return_per_tree: bool = False
):
    leaves = exit_leaves_bitvector(ens, X)  # [B, T]
    per_tree = jnp.take_along_axis(
        ens.leaf_value[None, :, :], leaves[:, :, None], axis=2
    )[..., 0]
    scores = per_tree.sum(axis=1) + ens.base_score
    if return_per_tree:
        return scores, per_tree
    return scores


def score_level(ens: TreeEnsemble, X: jax.Array) -> jax.Array:
    """Classic batched root→leaf traversal (depth dependent steps)."""
    B = X.shape[0]
    T = ens.n_trees
    node = jnp.zeros((B, T), dtype=jnp.int32)
    done = jnp.zeros((B, T), dtype=bool)
    leaf = jnp.zeros((B, T), dtype=jnp.int32)

    def step(carry, _):
        node, done, leaf = carry
        safe = jnp.where(done, 0, node)
        f = ens.feature[jnp.arange(T)[None, :], safe]          # [B, T]
        t = ens.threshold[jnp.arange(T)[None, :], safe]
        l = ens.left[jnp.arange(T)[None, :], safe]
        r = ens.right[jnp.arange(T)[None, :], safe]
        xv = jnp.take_along_axis(X, f.reshape(B, -1), axis=1).reshape(B, T)
        child = jnp.where(xv <= t, l, r)
        is_leaf = child < 0
        new_leaf = jnp.where(~done & is_leaf, -(child + 1), leaf)
        new_node = jnp.where(~done & ~is_leaf, child, node)
        new_done = done | is_leaf
        return (new_node, new_done, new_leaf), None

    (node, done, leaf), _ = jax.lax.scan(
        step, (node, done, leaf), None, length=ens.depth + 1
    )
    per_tree = jnp.take_along_axis(ens.leaf_value[None], leaf[:, :, None], axis=2)[..., 0]
    return per_tree.sum(axis=1) + ens.base_score


def partial_scores(ens: TreeEnsemble, X: jax.Array, sentinel: int) -> tuple[jax.Array, jax.Array]:
    """(scores after first ``sentinel`` trees, scores of the remaining tail).

    Full score = partial + tail + base. Used by all early-exit strategies.
    """
    _, per_tree = score_bitvector(ens, X, return_per_tree=True)
    head = per_tree[:, :sentinel].sum(axis=1) + ens.base_score
    tail = per_tree[:, sentinel:].sum(axis=1)
    return head, tail


def score_numpy_oracle(ens: TreeEnsemble, X: np.ndarray) -> np.ndarray:
    """Per-document recursive traversal — trusted ground truth."""
    feature = np.asarray(ens.feature)
    threshold = np.asarray(ens.threshold)
    left = np.asarray(ens.left)
    right = np.asarray(ens.right)
    leaf_value = np.asarray(ens.leaf_value)
    B = X.shape[0]
    out = np.full(B, float(ens.base_score), dtype=np.float64)
    for b in range(B):
        for t in range(ens.n_trees):
            n = 0
            while True:
                child = left[t, n] if X[b, feature[t, n]] <= threshold[t, n] else right[t, n]
                if child < 0:
                    out[b] += leaf_value[t, -(child + 1)]
                    break
                n = child
    return out.astype(np.float32)
