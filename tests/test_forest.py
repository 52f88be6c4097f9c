"""Forest substrate tests: scorer equivalence, slicing, GBDT training."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.forest import (
    GBDTParams,
    score_bitvector,
    score_level,
    score_numpy_oracle,
    partial_scores,
    slice_trees,
    train_gbdt,
    train_lambdamart,
)
from repro.forest.ensemble import (
    MAX_LEAVES,
    from_arrays,
    from_complete_arrays,
    random_ensemble,
)
from repro.kernels.ops import forest_score
from repro.metrics.ranking import mean_ndcg


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


@pytest.mark.parametrize("depth", [1, 3, 6, 8])
@pytest.mark.parametrize("n_trees", [1, 17])
def test_scorers_agree(rng, depth, n_trees):
    ens = random_ensemble(0, n_trees=n_trees, depth=depth, n_features=12)
    X = rng.normal(size=(64, 12)).astype(np.float32)
    ref = score_numpy_oracle(ens, X)
    bv = np.asarray(score_bitvector(ens, jnp.asarray(X)))
    lv = np.asarray(score_level(ens, jnp.asarray(X)))
    np.testing.assert_allclose(bv, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lv, ref, rtol=1e-5, atol=1e-5)


def test_irregular_tree_from_arrays(rng):
    # A lopsided 3-internal-node tree:      n0
    #                                     /    \
    #                                    n1    leafC
    #                                   /  \
    #                                leafA  n2
    #                                      /  \
    #                                   leafB leafD
    feats = [np.array([0, 1, 2])]
    thrs = [np.array([0.0, -1.0, 0.5], dtype=np.float32)]
    lefts = [np.array([1, -1, -2])]
    rights = [np.array([-3, 2, -4])]
    leaf_vals = [np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)]
    ens = from_arrays(feats, thrs, lefts, rights, leaf_vals)
    X = rng.normal(size=(128, 3)).astype(np.float32)
    ref = score_numpy_oracle(ens, X)
    bv = np.asarray(score_bitvector(ens, jnp.asarray(X)))
    np.testing.assert_allclose(bv, ref, rtol=1e-5)


def _lopsided_tree(rng, n_leaves, n_features):
    """A random binary tree of exactly ``n_leaves`` leaves, its splits
    drawn unevenly so that it is far from complete (pre-order internal
    numbering; child < 0 encodes leaf slot -(i + 1))."""
    f, t, lt, rt = [], [], [], []
    leaves = [0]

    def rec(n):
        if n == 1:
            leaves[0] += 1
            return -leaves[0]
        idx = len(f)
        f.append(int(rng.integers(0, n_features)))
        t.append(float(rng.normal()))
        lt.append(0)
        rt.append(0)
        n_left = int(rng.integers(1, n)) if rng.random() < 0.5 else 1
        lt[idx] = rec(n_left)
        rt[idx] = rec(n - n_left)
        return idx

    rec(n_leaves)
    return (np.asarray(f, np.int32), np.asarray(t, np.float32),
            np.asarray(lt, np.int32), np.asarray(rt, np.int32),
            (0.1 * rng.normal(size=n_leaves)).astype(np.float32))


def test_unbalanced_256_leaf_tree_from_arrays(rng):
    """Leaf-wise trees of 256 leaves (LightGBM's shape), far deeper than
    8 levels on some paths: the 8-word masks and the kernel score them as
    the per-document walk does."""
    trees = [_lopsided_tree(rng, n, 10) for n in (256, 256, 129)]
    ens = from_arrays(*(list(a) for a in zip(*trees)))
    assert ens.n_leaves == 256 and ens.mask.shape == (3, 255, 8)
    X = rng.normal(size=(96, 10)).astype(np.float32)
    ref = score_numpy_oracle(ens, X)
    np.testing.assert_allclose(np.asarray(score_bitvector(ens, jnp.asarray(X))),
                               ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(forest_score(ens, jnp.asarray(X), interpret=True)),
        ref, rtol=1e-5, atol=1e-5,
    )


def test_trees_past_the_mask_bound_raise(rng):
    """Neither builder encodes a tree wider than MAX_LEAVES: both raise
    instead of building masks that would score it wrongly."""
    assert MAX_LEAVES == 256
    tree = _lopsided_tree(rng, MAX_LEAVES + 1, 4)
    with pytest.raises(ValueError, match="at most 256"):
        from_arrays(*([a] for a in tree))
    n_int = 2**9 - 1
    with pytest.raises(ValueError, match="at most 256"):
        from_complete_arrays(np.zeros((2, n_int), np.int32),
                             np.zeros((2, n_int), np.float32),
                             np.zeros((2, n_int + 1), np.float32))
    with pytest.raises(ValueError, match="at most 256"):
        random_ensemble(0, n_trees=1, depth=9, n_features=4)


def test_partial_plus_tail_equals_full(rng):
    ens = random_ensemble(1, n_trees=40, depth=5, n_features=8)
    X = rng.normal(size=(32, 8)).astype(np.float32)
    head, tail = partial_scores(ens, jnp.asarray(X), sentinel=13)
    full = score_bitvector(ens, jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(head + tail), np.asarray(full), rtol=1e-4, atol=1e-5)


def test_slice_trees_matches_manual(rng):
    ens = random_ensemble(2, n_trees=20, depth=4, n_features=6)
    X = rng.normal(size=(16, 6)).astype(np.float32)
    head = score_bitvector(slice_trees(ens, 0, 7), jnp.asarray(X))
    tail = score_bitvector(slice_trees(ens, 7, 20), jnp.asarray(X))
    full = score_bitvector(ens, jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(head + tail), np.asarray(full), rtol=1e-4, atol=1e-5)


def test_gbdt_l2_fits_function(rng):
    X = rng.normal(size=(2000, 5)).astype(np.float32)
    y = (np.sin(X[:, 0]) + 0.5 * (X[:, 1] > 0) + 0.1 * X[:, 2]).astype(np.float32)
    params = GBDTParams(n_trees=40, depth=4, learning_rate=0.2)
    ens = train_gbdt(X, y, params, objective="l2")
    pred = np.asarray(score_bitvector(ens, jnp.asarray(X)))
    mse = float(np.mean((pred - y) ** 2))
    base = float(np.var(y))
    assert mse < 0.15 * base, f"GBDT failed to fit: mse={mse}, var={base}"


def test_gbdt_logistic_classifies(rng):
    X = rng.normal(size=(2000, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    w = np.where(y > 0, 2.0, 1.0).astype(np.float32)  # cost-sensitive path
    params = GBDTParams(n_trees=30, depth=4, learning_rate=0.3)
    ens = train_gbdt(X, y, params, objective="logistic", weights=w)
    logits = np.asarray(score_bitvector(ens, jnp.asarray(X)))
    acc = float(np.mean((logits > 0) == (y > 0.5)))
    assert acc > 0.9, f"accuracy {acc}"


def test_lambdamart_improves_ndcg(rng):
    Q, D, F = 60, 24, 6
    X = rng.normal(size=(Q, D, F)).astype(np.float32)
    # Relevance depends on two features → learnable ranking signal.
    util = X[..., 0] + 0.7 * X[..., 1] + 0.2 * rng.normal(size=(Q, D))
    labels = np.clip(np.digitize(util, [-0.5, 0.5, 1.2, 1.8]), 0, 4).astype(np.float32)
    mask = np.ones((Q, D), dtype=bool)
    mask[:, 20:] = rng.random((Q, 4)) > 0.5  # ragged queries
    params = GBDTParams(n_trees=30, depth=4, learning_rate=0.2)
    ens = train_lambdamart(X, labels, mask, params, k=10)
    flat = jnp.asarray(X.reshape(Q * D, F))
    scores = np.asarray(score_bitvector(ens, flat)).reshape(Q, D)
    ndcg = float(mean_ndcg(jnp.asarray(scores), jnp.asarray(labels), jnp.asarray(mask), k=10))
    rand = float(mean_ndcg(jnp.asarray(rng.normal(size=(Q, D)).astype(np.float32)),
                           jnp.asarray(labels), jnp.asarray(mask), k=10))
    assert ndcg > rand + 0.15, f"lambdamart ndcg {ndcg} vs random {rand}"


def test_bitvector_bf16_thresholds_close(rng):
    ens = random_ensemble(3, n_trees=10, depth=4, n_features=4)
    X = rng.normal(size=(16, 4)).astype(np.float32)
    full = np.asarray(score_bitvector(ens, jnp.asarray(X)))
    assert np.all(np.isfinite(full))
