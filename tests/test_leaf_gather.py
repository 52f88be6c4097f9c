"""Leaf-gather path parity: select tree ≡ MXU contraction ≡ one-hot, bit-exact.

The kernel's three leaf-value resolution paths move the same f32 values
(selects relocate them; the one-hot/MXU contractions sum one exact product
against zeros), and the shared tree-axis reduction is an explicit pairwise
add chain — so the paths must agree BIT-FOR-BIT, across leaf counts,
including non-power-of-two leaf axes (ragged ensembles) and leaf tables
wider than the reachable index range (the MXU-threshold regime).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.forest.ensemble import from_arrays, random_ensemble
from repro.forest.scoring import score_level, score_numpy_oracle
from repro.kernels.forest_score import (
    LEAF_GATHERS,
    forest_score_pallas,
)
from repro.kernels.ops import (
    LEAF_SELECT_MAX,
    forest_score,
    forest_score_segments,
    padded_forest,
    resolve_leaf_gather,
)
from repro.kernels.ref import forest_score_ref, leaf_values_ref


def _score_all_paths(ens, X):
    return {
        lg: np.asarray(forest_score(ens, jnp.asarray(X), leaf_gather=lg,
                                    interpret=True))
        for lg in LEAF_GATHERS
    }


@pytest.mark.parametrize("depth", [1, 3, 6, 7, 8])   # L = 2 ... 256
def test_paths_bitexact_pow2_leaves(depth):
    rng = np.random.default_rng(depth)
    ens = random_ensemble(depth, n_trees=24, depth=depth, n_features=20)
    X = rng.normal(size=(100, 20)).astype(np.float32)
    got = _score_all_paths(ens, X)
    for lg in ("select", "mxu"):
        np.testing.assert_array_equal(got[lg], got["onehot"], err_msg=lg)
    np.testing.assert_allclose(
        got["onehot"], score_numpy_oracle(ens, X), rtol=1e-5, atol=1e-5
    )
    # The kernel's oracle (kernels/ref.py) and the structural walk.
    ref = forest_score_ref(jnp.asarray(X), ens.feature, ens.threshold,
                           ens.mask, ens.leaf_value)
    np.testing.assert_allclose(got["onehot"], np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got["onehot"], np.asarray(score_level(ens, jnp.asarray(X))),
        rtol=1e-5, atol=1e-5,
    )


def _random_ragged_trees(rng, leaf_counts, n_features):
    """Random binary trees with EXACT per-tree leaf counts (pre-order
    internal numbering, child < 0 encodes leaf slot -(i+1))."""
    feats, thrs, lefts, rights, leaves = [], [], [], [], []
    for n_leaves in leaf_counts:
        f, t, lt, rt = [], [], [], []
        leaf_ctr = [0]

        def rec(n):
            if n == 1:
                leaf_ctr[0] += 1
                return -leaf_ctr[0]
            idx = len(f)
            f.append(int(rng.integers(0, n_features)))
            t.append(float(rng.normal()))
            lt.append(0)
            rt.append(0)
            n_left = int(rng.integers(1, n))
            lt[idx] = rec(n_left)
            rt[idx] = rec(n - n_left)
            return idx

        rec(n_leaves)
        feats.append(np.asarray(f, np.int32))
        thrs.append(np.asarray(t, np.float32))
        lefts.append(np.asarray(lt, np.int32))
        rights.append(np.asarray(rt, np.int32))
        leaves.append(rng.normal(size=n_leaves).astype(np.float32) * 0.1)
    return from_arrays(feats, thrs, lefts, rights, leaves)


@pytest.mark.parametrize(
    "leaf_counts", [(3, 5, 6, 4), (48, 33, 47, 21), (255, 97, 130)]
)
def test_paths_bitexact_non_pow2_leaves(leaf_counts):
    """Ragged ensembles give a non-power-of-two leaf axis: the select path
    must pad it (padded_forest leaf_layout='pow2') and still agree
    bit-for-bit with the native-layout one-hot/MXU paths."""
    rng = np.random.default_rng(sum(leaf_counts))
    ens = _random_ragged_trees(rng, leaf_counts, n_features=12)
    assert ens.n_leaves & (ens.n_leaves - 1) != 0, ens.n_leaves
    X = rng.normal(size=(64, 12)).astype(np.float32)
    got = _score_all_paths(ens, X)
    for lg in ("select", "mxu"):
        np.testing.assert_array_equal(got[lg], got["onehot"], err_msg=lg)
    np.testing.assert_allclose(
        got["onehot"], score_numpy_oracle(ens, X), rtol=1e-5, atol=1e-5
    )
    pf = padded_forest(ens, leaf_gather="select")
    assert pf.leaf_layout == "pow2"
    assert pf.leaf_value.shape[1] == 1 << (ens.n_leaves - 1).bit_length()


def test_paths_bitexact_wide_leaf_table_L256():
    """L=256 (the widest tree the builders take, at the select/MXU cutoff):
    widen a depth-3 forest's leaf table with junk columns — unreachable
    (every ctz leaf index < 8), so all three paths must still return
    identical scores."""
    rng = np.random.default_rng(7)
    ens = random_ensemble(7, n_trees=16, depth=3, n_features=16)
    pf = padded_forest(ens, leaf_gather="onehot")
    L = 256
    junk = jnp.asarray(
        rng.normal(size=(pf.leaf_value.shape[0], L - pf.leaf_value.shape[1]))
        .astype(np.float32)
    )
    wide_leaf = jnp.concatenate([pf.leaf_value, junk], axis=1)
    x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    got = {
        lg: np.asarray(forest_score_pallas(
            x, pf.feature, pf.threshold, pf.mask, wide_leaf,
            block_b=64, block_t=pf.block_t, leaf_gather=lg, interpret=True,
        ))
        for lg in LEAF_GATHERS
    }
    for lg in ("select", "mxu"):
        np.testing.assert_array_equal(got[lg], got["onehot"], err_msg=lg)
    assert resolve_leaf_gather(L) == "select"
    assert resolve_leaf_gather(2 * L) == "mxu"


def test_segmented_kernel_paths_bitexact():
    """The sentinel-segmented kernel shares _score_block: per-segment
    partials must be path-invariant bit-for-bit too."""
    rng = np.random.default_rng(11)
    ens = random_ensemble(11, n_trees=48, depth=6, n_features=32)
    X = jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32))
    segs = {}
    for lg in LEAF_GATHERS:
        pf = padded_forest(ens, boundaries=(10, 30, 48), leaf_gather=lg)
        segs[lg] = np.asarray(forest_score_segments(pf, X, n_segments=3))
    for lg in ("select", "mxu"):
        np.testing.assert_array_equal(segs[lg], segs["onehot"], err_msg=lg)


def test_resolve_and_layout_policy():
    """Auto policy: select tree up to LEAF_SELECT_MAX padded leaves, MXU
    above; the buffer cache keys on the resolved path (distinct layouts
    are distinct cached entries, same layout is shared)."""
    assert resolve_leaf_gather(2) == "select"
    assert resolve_leaf_gather(LEAF_SELECT_MAX) == "select"
    # Non-pow2 counts resolve on their padded width.
    assert resolve_leaf_gather(LEAF_SELECT_MAX - 1) == "select"
    assert resolve_leaf_gather(LEAF_SELECT_MAX + 1) == "mxu"
    assert resolve_leaf_gather(256) == "select"   # the builders' widest
    assert resolve_leaf_gather(512) == "mxu"

    ens = random_ensemble(13, n_trees=8, depth=3, n_features=8)
    auto = padded_forest(ens)
    assert auto.leaf_gather == "select" and auto.leaf_layout == "pow2"
    assert padded_forest(ens, leaf_gather="select") is auto
    onehot = padded_forest(ens, leaf_gather="onehot")
    assert onehot is not auto and onehot.leaf_layout == "native"


def test_leaf_values_ref_is_the_gather_oracle():
    """The ref-layer gather oracle (take_along_axis) pins what every
    in-kernel path computes."""
    rng = np.random.default_rng(17)
    leaf_tab = jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32))
    leaf = jnp.asarray(rng.integers(0, 8, size=(10, 6)).astype(np.int32))
    got = np.asarray(leaf_values_ref(leaf, leaf_tab))
    expect = np.asarray(leaf_tab)[np.arange(6)[None, :], np.asarray(leaf)]
    np.testing.assert_array_equal(got, expect)
