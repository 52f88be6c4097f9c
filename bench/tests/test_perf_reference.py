"""The float64 reference against the program's own oracle, at a small size
on the CPU, and the check against a bfloat16 control."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, models, reference  # noqa: E402

F = 12


@pytest.fixture(scope="module")
def model():
    ranker = models.draw_forest(models.rng(3, 0), 40, 4, F, 0.1)
    clf = models.draw_forest(models.rng(3, 1), 6, 3, F + 4, 0.1)
    return reference.Model(ranker, clf, sentinel=8, logit_threshold=0.05)


@pytest.fixture(scope="module")
def X():
    return np.random.default_rng(9).standard_normal((48, F),
                                                    dtype=np.float32)


def _ensemble(f):
    from repro.forest.ensemble import from_complete_arrays

    return from_complete_arrays(f.feature, f.threshold, f.leaf)


def test_scores_agree_with_the_program_oracle(model, X):
    from repro.forest.ensemble import slice_trees
    from repro.forest.scoring import score_numpy_oracle

    ens = _ensemble(model.ranker)
    ref = reference.replay(model, X)
    np.testing.assert_allclose(ref.full, score_numpy_oracle(ens, X),
                               atol=1e-5)
    head = slice_trees(ens, 0, model.sentinel)
    np.testing.assert_allclose(ref.partial, score_numpy_oracle(head, X),
                               atol=1e-5)


def test_walk_finds_the_oracle_leaves(model, X):
    leaves, _ = reference.walk(model.ranker, X)
    f = model.ranker
    for b in range(4):
        for t in range(f.n_trees):
            node = 0
            for _ in range(f.depth):
                go_right = X[b, f.feature[t, node]] > f.threshold[t, node]
                node = 2 * node + 1 + int(go_right)
            assert leaves[b, t] == node - (2**f.depth - 1)


def test_lear_features_match_the_program(model, X):
    import jax.numpy as jnp

    from repro.core.features import augment_features

    ref = reference.replay(model, X)
    mine = reference.lear_features(ref.partial.astype(np.float32))
    theirs = np.asarray(augment_features(
        jnp.asarray(X[None]), jnp.asarray(ref.partial[None], jnp.float32),
        jnp.ones((1, X.shape[0]), bool)))[0, :, F:]
    np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-6)


def test_decisions_follow_the_classifier(model, X):
    from repro.forest.scoring import score_numpy_oracle

    ref = reference.replay(model, X)
    aug = reference.lear_features(ref.partial)
    logit = score_numpy_oracle(_ensemble(model.classifier),
                               np.concatenate([X, aug], 1).astype(np.float32))
    clear = ~ref.undecided
    assert clear.sum() >= X.shape[0] - 2
    np.testing.assert_array_equal(ref.cont[clear],
                                  (logit >= model.logit_threshold)[clear])
    assert 0 < ref.cont.sum() < X.shape[0]


def test_check_passes_float32_and_fails_bfloat16_features(model, X):
    ref = reference.replay(model, X)
    served = ref.served().astype(np.float32).astype(np.float64)
    assert reference.score_error(ref, served) < 1e-6
    top = reference.served_top_k(served, 10)
    assert reference.top_k_ok(ref, served, top, 10, harness.TIE)

    control = reference.replay(model, X, "bfloat16").served()
    assert reference.score_error(ref, control) > harness.SCORE_ERR_LIMIT
    # Scores taken from bf16-rounded features alone fail too.
    rounded = X.astype(reference.ml_dtypes.bfloat16).astype(np.float32)
    from_rounded = reference.replay(model, rounded).served()
    assert reference.score_error(ref, from_rounded) > harness.SCORE_ERR_LIMIT


def test_a_wrong_query_or_a_short_answer_fails(model, X):
    ref = reference.replay(model, X)
    other = reference.replay(model, X[::-1].copy()).served()
    assert reference.score_error(ref, other) > harness.SCORE_ERR_LIMIT
    assert reference.score_error(ref, ref.served()[:-1]) == float("inf")
    assert not reference.top_k_ok(ref, ref.served(), np.arange(3), 10,
                                  harness.TIE)


def test_undecided_documents_may_take_either_score(model, X):
    ref = reference.replay(model, X)
    flip = reference.Replay(ref.partial, ref.full, ref.cont,
                            np.ones_like(ref.undecided))
    swapped = np.where(ref.cont, ref.partial, ref.full)
    assert reference.score_error(flip, swapped) == 0.0
    assert reference.score_error(ref, swapped) > 0.0


def test_near_ties_count_neighbours():
    p = np.array([0.0, 5e-6, 1.0, 2.0, 2.0 + 2e-5])
    np.testing.assert_array_equal(reference.near_ties(p), [1, 1, 0, 0, 0])
