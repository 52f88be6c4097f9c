"""Trees of 128 and 256 leaves (depth 7 and 8), at a small size on the CPU:
the float64 reference walks them as an independent recursive walk does,
the serving tier answers as the reference does, and the reader of
``doc_tree_useful_share`` reads the program's slot counter."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, models, reference, work  # noqa: E402

F = 12


def _recursive_leaf(forest, t, x, node=0):
    """Leaf index of document ``x`` in tree ``t``: node ``n`` has children
    ``2n+1`` and ``2n+2``, ``x <= threshold`` goes left."""
    n_int = forest.feature.shape[1]
    if node >= n_int:
        return node - n_int
    left = x[forest.feature[t, node]] <= forest.threshold[t, node]
    return _recursive_leaf(forest, t, x, 2 * node + (1 if left else 2))


def test_depth8_walk_agrees_with_a_recursive_walk():
    forest = models.draw_forest(models.rng(8, 0), 6, 8, F, 0.1)
    assert forest.leaf.shape == (6, 256) and forest.depth == 8
    X = np.random.default_rng(8).standard_normal((40, F), dtype=np.float32)
    leaves, _ = reference.walk(forest, X)
    want = np.array([[_recursive_leaf(forest, t, x) for t in range(6)]
                     for x in X])
    np.testing.assert_array_equal(leaves, want)
    # Leaves in every quarter of the 256 are reached, past the 64 of a
    # depth-6 tree.
    assert all(((want >> 6) == q).any() for q in range(4))
    full = reference.tree_sum(forest, leaves, np.float64)
    by_hand = np.array([sum(float(forest.leaf[t, want[b, t]])
                            for t in range(6)) for b in range(40)])
    np.testing.assert_allclose(full, by_hand, rtol=0, atol=1e-12)


def _tiny(depth):
    return {
        "name": f"tiny-d{depth}", "n_trees": 9, "depth": depth,
        "n_features": F, "sentinel": 3, "classifier_trees": 3,
        "classifier_depth": 3, "leaf_scale": 0.1, "continue_share": 0.5,
        "weights_seed": 40 + depth,
        "threshold_sample": {"queries": 6, "docs": {
            "dist": "uniform", "min": 5, "max": 14}},
    }


def _tier(model):
    """The harness's tier (bench/harness.py::build_tier) with two query
    buckets and the 8 and 16 doc buckets, so that warm-up stays short."""
    from repro.core.lear import LearClassifier
    from repro.forest.ensemble import from_complete_arrays
    from repro.serve.batching import BucketPolicy
    from repro.serve.ranking_service import RankingService, ServiceConfig
    from repro.serve.tier import ServingTier, TierConfig

    def ensemble(f):
        return from_complete_arrays(f.feature, f.threshold, f.leaf)

    service = RankingService(
        ensemble(model.ranker),
        LearClassifier(ensemble(model.classifier), model.sentinel),
        ServiceConfig(threshold=models.probability(model.logit_threshold)),
    )
    return ServingTier(service, F,
                       TierConfig(doc_counts=(8, 16), persistent_cache=False),
                       policy=BucketPolicy(max_queries=2))


@pytest.mark.parametrize("depth", [7, 8])
def test_tier_answers_as_the_reference_past_64_leaves(depth):
    """Lists of 5..14 documents cross the 8 / 16 doc buckets; every answer
    of the tier (interpret-mode kernels) matches the float64 replay."""
    model = models.build(_tiny(depth))
    tier = _tier(model)
    rng = np.random.default_rng(depth)
    requests = [rng.standard_normal((n, F), dtype=np.float32)
                for n in (5, 8, 9, 14, 12, 7)]
    tier.start()
    try:
        answers = [f.result(timeout=300)
                   for f in [tier.submit(X) for X in requests]]
    finally:
        tier.stop()
    k = tier.service.top_k
    continued = 0
    for X, (top, scores) in zip(requests, answers):
        ref = reference.replay(model, X)
        scores = np.asarray(scores, np.float64)
        assert reference.score_error(ref, scores) <= 1e-5
        assert reference.top_k_ok(ref, scores, top, k, 1e-5)
        continued += int(ref.cont.sum())
    assert 0 < continued < sum(len(X) for X in requests)


def _record(service):
    config = {"n_trees": 500, "depth": 8, "n_features": 136, "sentinel": 50,
              "classifier_trees": 10, "classifier_depth": 5}
    cell = harness.Cell(name="hand", chips=1, config=config, mix={},
                        end_to_end=[], per_layer=[])
    return harness.WindowRecord(cell=cell, batcher={}, service=service,
                                steps_s=[], summary=None,
                                peak=work.Peak(1.0, 1.0), chips=1)


def test_doc_tree_useful_share_by_hand():
    """1,000 documents through 50 head and 10 classifier trees, 300 of
    them through the 450 tail trees: 195,000 required document-trees of
    390,000 slots scored."""
    read = harness.reader("doc_tree_useful_share.bulk")
    record = _record({"docs": 1000, "docs_continued": 300,
                      "doc_tree_slots": 390_000})
    assert read(record) == pytest.approx(50.0)
    assert harness.reader("doc_tree_useful_share.steady") is not None


def test_doc_tree_useful_share_reads_nothing_without_the_counter():
    read = harness.reader("doc_tree_useful_share.bulk")
    assert read(_record({"docs": 1000, "docs_continued": 300})) is None
    assert read(_record({"docs": 0, "docs_continued": 0,
                         "doc_tree_slots": 0})) is None
