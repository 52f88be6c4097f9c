"""Pallas forest kernel: shape/dtype sweep vs the pure-jnp oracle."""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.forest.ensemble import random_ensemble
from repro.forest.scoring import score_level, score_numpy_oracle
from repro.kernels.ops import forest_score, forest_score_segments, padded_forest
from repro.kernels.ref import forest_score_ref


@pytest.mark.parametrize(
    "n_docs,n_trees,depth,n_features",
    [
        (8, 1, 1, 3),
        (64, 16, 4, 16),
        (100, 30, 6, 136),    # MSN-1-like feature count, ragged doc count
        (256, 64, 5, 220),    # Istella-like feature count
        (33, 7, 3, 5),        # deliberately unaligned everything
        (40, 12, 7, 136),     # 128 leaves: 4 mask words, blocks of 8 trees
        (40, 9, 8, 136),      # 256 leaves: 8 mask words, blocks of 4 trees
    ],
)
def test_kernel_matches_oracle(n_docs, n_trees, depth, n_features):
    rng = np.random.default_rng(n_docs + n_trees)
    ens = random_ensemble(0, n_trees=n_trees, depth=depth, n_features=n_features)
    X = rng.normal(size=(n_docs, n_features)).astype(np.float32)
    got = np.asarray(forest_score(ens, jnp.asarray(X), interpret=True))
    ref = score_numpy_oracle(ens, X)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_b,block_t", [(8, 1), (32, 4), (256, 16)])
def test_kernel_block_shapes(block_b, block_t):
    rng = np.random.default_rng(7)
    ens = random_ensemble(1, n_trees=48, depth=5, n_features=24)
    X = rng.normal(size=(96, 24)).astype(np.float32)
    got = np.asarray(
        forest_score(ens, jnp.asarray(X), block_b=block_b, block_t=block_t, interpret=True)
    )
    ref = score_numpy_oracle(ens, X)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_input_dtypes(dtype):
    rng = np.random.default_rng(11)
    ens = random_ensemble(2, n_trees=8, depth=4, n_features=10)
    X = rng.normal(size=(40, 10)).astype(np.float32)
    got = np.asarray(forest_score(ens, jnp.asarray(X, dtype=dtype), interpret=True))
    # bf16 inputs may flip predicates for values straddling thresholds; compare
    # against the oracle run at the same precision.
    ref = score_numpy_oracle(ens, np.asarray(jnp.asarray(X, dtype=dtype), np.float32))
    np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("depth", [6, 7, 8])   # 2, 4 and 8 mask words
def test_ref_matches_forest_scoring(depth):
    rng = np.random.default_rng(3)
    ens = random_ensemble(4, n_trees=20, depth=depth, n_features=50)
    X = rng.normal(size=(64, 50)).astype(np.float32)
    ref_kernel = np.asarray(
        forest_score_ref(
            jnp.asarray(X), ens.feature, ens.threshold, ens.mask, ens.leaf_value
        )
    )
    oracle = score_numpy_oracle(ens, X)
    np.testing.assert_allclose(ref_kernel + float(ens.base_score), oracle, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(score_level(ens, jnp.asarray(X))), oracle,
        rtol=1e-5, atol=1e-5,
    )


def test_depth6_scores_pinned_to_the_two_word_encoding():
    """A 64-leaf tree's mask is the two uint32 halves of its 64-bit
    QuickScorer mask, and the kernel's depth-6 scores are bit for bit
    those recorded with the two-word encoding (sha256 of the float32
    bytes, plain launch and a three-segment launch)."""
    ens = random_ensemble(61, n_trees=40, depth=6, n_features=136)
    assert ens.mask.shape == (40, 63, 2)
    mask = np.asarray(ens.mask)
    for n in range(63):
        d = int(np.log2(n + 1))
        span = 64 >> d
        lo = (n - (2**d - 1)) * span
        bits = ((1 << (lo + span // 2)) - 1) ^ ((1 << lo) - 1)
        inv = ~bits & (2**64 - 1)
        assert (mask[:, n, 0] == inv & 0xFFFFFFFF).all()
        assert (mask[:, n, 1] == inv >> 32).all()
    X = np.random.default_rng(61).normal(size=(48, 136)).astype(np.float32)
    plain = np.asarray(forest_score(ens, jnp.asarray(X), interpret=True))
    pf = padded_forest(ens, boundaries=(10, 25, 40))
    seg = np.asarray(forest_score_segments(pf, jnp.asarray(X), interpret=True))
    assert hashlib.sha256(plain.tobytes()).hexdigest() == (
        "1e05dfbad35711ee5542c3d5975e2ae3fca3223e6b4e0549f9a580ee6f88277d"
    )
    assert hashlib.sha256(seg.tobytes()).hexdigest() == (
        "d98f9f416b886686a8a65df5c87a4b4dcfd966100f085a4d71aa4ab2ddeaf704"
    )


@pytest.mark.parametrize("depth", [6, 8])
def test_nodes_grouped_by_mask_word(depth):
    """padded_forest orders each tree's nodes by the highest mask word
    they zero: a word's AND reads its own columns (and those of the few
    nodes whose span crosses a word boundary), bit-exact with ANDing
    every column."""
    from repro.kernels.forest_score import forest_score_pallas

    ens = random_ensemble(9, n_trees=24, depth=depth, n_features=30)
    pf = padded_forest(ens)
    assert pf.word_columns == {
        # 64 leaves, blocks of 16: each word's 32 nodes fill 4 columns.
        6: ((0, 1, 2, 3), (4, 5, 6, 7)),
        # 256 leaves, blocks of 4: word w's nodes fill column w; the root
        # (column 3) zeroes words 0-2, node 1 (column 1) word 0 and
        # node 2 (column 5) word 4.
        8: ((0, 1, 3), (1, 3), (2, 3), (3,), (4, 5), (5,), (6,), (7,)),
    }[depth]
    X = jnp.asarray(np.random.default_rng(9).normal(size=(64, 30)),
                    jnp.float32)
    x = jnp.pad(X, ((0, 0), (0, 128 - 30)))
    args = (x, pf.feature, pf.threshold, pf.mask, pf.leaf_value)
    kw = dict(block_b=64, block_t=pf.block_t, leaf_gather=pf.leaf_gather)
    grouped = forest_score_pallas(*args, word_columns=pf.word_columns, **kw)
    every = forest_score_pallas(*args, **kw)
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(every))
    np.testing.assert_allclose(np.asarray(grouped),
                               score_numpy_oracle(ens, np.asarray(X)),
                               rtol=1e-5, atol=1e-5)
