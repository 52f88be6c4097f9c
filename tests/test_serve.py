"""RankingService serving-path contracts: capacity policy, sync discipline,
adaptive execution mode, and small-query edges.

These tests drive the service with deterministic feature-keyed stage
strategies (continue ⇔ ``features[..., 0] > 0``) so survivor counts are
controlled exactly per batch without training a classifier.
"""

import dataclasses
import logging
import re
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lear import LearClassifier
from repro.core.strategies import QueryExitConfig
from repro.forest.ensemble import random_ensemble
from repro.metrics.speedup import trees_traversed_progressive
from repro.serve.ranking_service import RankingService, ServiceConfig
from strategy_harness import make_dense_stage


def _service(seed=0, n_trees=64, sentinels=(8, 28), **knobs):
    ens = random_ensemble(seed, n_trees=n_trees, depth=4, n_features=12)
    clfs = [
        LearClassifier(
            forest=random_ensemble(100 + i, n_trees=10, depth=3, n_features=16),
            sentinel=s,
        )
        for i, s in enumerate(sentinels)
    ]
    svc = RankingService(
        ens, clfs[0], ServiceConfig(threshold=0.4, **knobs),
        extra_classifiers=clfs[1:],
    )
    # Deterministic stage gate: continue ⇔ feature 0 positive. Replacing the
    # strategy list BEFORE the first batch keeps the jitted-step cache to
    # one entry per mode.
    gate = lambda p, m, features=None: m & (features[..., 0] > 0.0)
    svc.stage_strategies = [gate] * len(svc.sentinels)
    return svc


def _batch(rng, Q, D, F, survive_frac):
    """A [Q, D, F] batch whose gate-survivor count is survive_frac exactly."""
    X = rng.normal(size=(Q, D, F)).astype(np.float32)
    flags = np.zeros((Q, D), np.float32) - 1.0
    n = int(round(survive_frac * D))
    flags[:, :n] = 1.0
    X[..., 0] = flags
    return jnp.asarray(X), jnp.ones((Q, D), bool)


def test_capacity_never_shrinks_below_observed_peak():
    """Regression: one sparse batch must not shrink a stage's bucket under
    already-observed traffic — oscillating survivor counts cause zero
    overflow after the warmup batch."""
    rng = np.random.default_rng(1)
    svc = _service(execution_mode="fused")
    Q, D, F = 2, 64, 12
    dense = _batch(rng, Q, D, F, survive_frac=0.8)   # 102 survivors
    sparse = _batch(rng, Q, D, F, survive_frac=0.05)  # 6 survivors
    svc.rank_batch(*dense)                 # warmup: cold-start bucket (64)
    warmup_overflow = svc.stats.overflow_docs
    assert warmup_overflow > 0             # proves the scenario bites
    for _ in range(3):                     # oscillate: sparse then dense
        svc.rank_batch(*sparse)
        svc.rank_batch(*dense)
    assert svc.stats.overflow_docs == warmup_overflow  # zero after warmup
    # The bucket ratcheted up and the cold-start floor still holds.
    caps = svc._pick_capacities(Q * D)
    assert all(c >= 128 for c in caps), caps


def test_rank_batch_single_fused_device_read(monkeypatch):
    """The whole stats path is ONE jax.device_get — folding mask.sum() into
    the fused read removed the extra per-batch host syncs."""
    rng = np.random.default_rng(2)
    svc = _service(execution_mode="fused")
    X, mask = _batch(rng, 2, 32, 12, survive_frac=0.3)
    svc.rank_batch(X, mask)  # compile outside the counted window
    calls = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda *a, **k: calls.append(1) or real(*a, **k)
    )
    svc.rank_batch(X, mask)
    assert len(calls) == 1, len(calls)


def test_top_k_clamped_to_candidate_count():
    """A query block smaller than top_k returns all D candidates instead of
    crashing jax.lax.top_k."""
    rng = np.random.default_rng(3)
    svc = _service(execution_mode="fused", top_k=10)
    X, mask = _batch(rng, 2, 4, 12, survive_frac=0.5)
    top_idx, scores = svc.rank_batch(X, mask)
    assert top_idx.shape == (2, 4)
    assert scores.shape == (2, 4)


def test_adaptive_mode_tracks_continue_rate():
    """The service picks per-stage tails when survivors shrink fast (big
    head-work saving) and the fused head when survivors stay large, from
    its OBSERVED continue rates; the first batch defaults to fused."""
    rng = np.random.default_rng(4)
    Q, D, F = 2, 64, 12
    # survivor_ema=1.0: track the last batch exactly (keeps the arithmetic
    # of the crossover deterministic in the test).
    svc = _service(
        execution_mode="auto", launch_overhead_trees=512.0, survivor_ema=1.0
    )
    lo = _batch(rng, Q, D, F, survive_frac=0.05)
    hi = _batch(rng, Q, D, F, survive_frac=0.95)

    svc.rank_batch(*lo)                      # cold start: no observed rates
    assert svc.stats.batches_fused == 1
    svc.rank_batch(*lo)                      # observed 5% continue → staged
    assert svc.stats.batches_staged == 1
    for _ in range(4):                       # EMA converges to 95% → fused
        svc.rank_batch(*hi)
    assert svc._pick_mode(Q * D) == "fused"
    assert svc.stats.batches_fused > 1

    # Forced modes bypass the cost model entirely.
    forced = _service(execution_mode="staged", launch_overhead_trees=512.0)
    forced.rank_batch(*hi)
    assert forced.stats.batches_staged == 1


def test_rank_batch_zero_host_transfers_with_lear_classifier():
    """The device-residency acceptance contract: with a REAL LEAR
    classifier in the loop (kernel-scored, device-built augmented
    features), a steady-state rank_batch performs ZERO implicit
    device→host transfers — the single fused jax.device_get at the end is
    the only read."""
    from repro.utils import count_host_transfers

    rng = np.random.default_rng(6)
    ens = random_ensemble(60, n_trees=64, depth=4, n_features=12)
    clfs = [
        LearClassifier(
            forest=random_ensemble(160 + i, n_trees=10, depth=3,
                                   n_features=16),
            sentinel=s,
        )
        for i, s in enumerate((8, 28))
    ]
    svc = RankingService(
        ens, clfs[0],
        ServiceConfig(
            threshold=0.4, execution_mode="auto",
            launch_overhead_trees=512.0,
        ),
        extra_classifiers=clfs[1:],
    )
    X = jnp.asarray(rng.normal(size=(2, 32, 12)).astype(np.float32))
    mask = jnp.ones((2, 32), bool)
    # Warm up both the cold-start trace and the steady-state trace (the
    # capacity ratchet may re-bucket after batch 1).
    svc.rank_batch(X, mask)
    svc.rank_batch(X, mask)
    with count_host_transfers() as counts:
        svc.rank_batch(X, mask)
    assert counts.explicit_gets == 1, counts
    assert counts.implicit_syncs == 0, counts


def test_service_device_pick_matches_host_reference():
    """Acceptance: the in-program (lax.cond) pick chooses exactly the
    branch the host-side reference `_pick_mode` predicts, across a
    continue-rate sweep injected as the survivor EMA."""
    rng = np.random.default_rng(7)
    Q, D, F = 2, 64, 12
    svc = _service(
        execution_mode="auto", launch_overhead_trees=512.0, survivor_ema=1.0
    )
    X, mask = _batch(rng, Q, D, F, survive_frac=0.5)
    svc.rank_batch(X, mask)  # warm up; establishes peaks/EMA
    for rate in (0.02, 0.05, 0.15, 0.3, 0.5, 0.8, 0.95):
        svc._stage_ema = [rate * Q * D] * len(svc.sentinels)
        host_pick = svc._pick_mode(Q * D)
        before = (svc.stats.batches_fused, svc.stats.batches_staged)
        svc.rank_batch(X, mask)
        df = svc.stats.batches_fused - before[0]
        ds = svc.stats.batches_staged - before[1]
        device_pick = "staged" if ds else "fused"
        assert (df, ds) in ((1, 0), (0, 1))
        assert device_pick == host_pick, (rate, device_pick, host_pick)


def test_adaptive_state_is_per_batch_shape():
    """Survivor peaks and the continue-rate EMA are keyed by the padded
    batch shape: a sparse trickle at one shape must not shrink capacities
    or skew the mode pick of another shape's bucket."""
    rng = np.random.default_rng(8)
    svc = _service(execution_mode="fused")
    dense = _batch(rng, 2, 64, 12, survive_frac=0.8)
    tiny = _batch(rng, 1, 8, 12, survive_frac=0.0)
    svc.rank_batch(*dense)
    svc.rank_batch(*dense)
    big = svc.bucket_state(2, 64)
    peaks_before = list(big.peaks)
    ema_before = list(big.ema)
    for _ in range(3):
        svc.rank_batch(*tiny)
    # The tiny bucket adapted independently...
    small = svc.bucket_state(1, 8)
    assert small.peaks is not None and small.peaks != peaks_before
    # ...and the bulk bucket's state is untouched by the trickle.
    assert big.peaks == peaks_before
    assert big.ema == ema_before
    # The introspection surface follows the most recently served shape.
    assert svc._stage_ema == small.ema
    svc.rank_batch(*dense)
    assert svc._stage_ema == big.ema


def test_modes_serve_identical_scores():
    """Fused and staged services return identical responses on a
    non-overflow batch (the engine's bit-exactness surfaces end to end)."""
    rng = np.random.default_rng(5)
    X, mask = _batch(rng, 2, 32, 12, survive_frac=0.25)
    out = {}
    for mode in ("fused", "staged"):
        svc = _service(execution_mode=mode)
        out[mode] = svc.rank_batch(X, mask)
        assert svc.stats.overflow_docs == 0
    np.testing.assert_array_equal(out["fused"][0], out["staged"][0])
    np.testing.assert_array_equal(out["fused"][1], out["staged"][1])


@contextmanager
def _compiled_programs():
    """Names of the programs jax compiles in the block (``jit(step)``, …),
    from :func:`jax.log_compiles`."""
    names = []
    pattern = re.compile(r"Compiling (\S+) ")

    class Handler(logging.Handler):
        def emit(self, record):
            m = pattern.match(record.getMessage())
            if m:
                names.append(m.group(1))

    handler = Handler(level=logging.DEBUG)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            yield names
    finally:
        logger.removeHandler(handler)


def test_engine_call_is_one_program():
    """One engine call compiles, and so dispatches, one program: the
    response and stats are computed inside the step, so a new batch shape
    after a warm bucket compiles the step alone and no eager op after it;
    the same shape again compiles nothing."""
    rng = np.random.default_rng(9)
    svc = _service(execution_mode="auto", launch_overhead_trees=512.0)
    svc.rank_batch(*_batch(rng, 2, 32, 12, survive_frac=0.3))
    X, mask = _batch(rng, 3, 24, 12, survive_frac=0.3)  # a new (Q, D)
    with _compiled_programs() as compiled:
        svc.rank_batch(X, mask)
    assert compiled == ["jit(step)"], compiled
    with _compiled_programs() as compiled:
        svc.rank_batch(X, mask)
    assert compiled == [], compiled


def _eager_post(svc, X, mask):
    """Oracle: the engine call as ``rank_progressive`` followed by the
    response and stats computed as separate eager ops, read once. The
    accounting charges a dense gate as a zero-sentinel stage."""
    Q, D, _ = X.shape
    sentinels = svc.sentinels
    costs = [float(c.n_trees) for c in svc.stage_classifiers]
    if svc.dense_stage is not None:
        sentinels = (0, *sentinels)
        costs = [float(svc.dense_stage.cost_trees), *costs]
    config, extra = svc._engine_call(Q, D)
    res = svc.cascade.rank_progressive(X, mask, config, features=X, **extra)
    masked = jnp.where(mask, res.scores, -jnp.inf)
    picked = (
        res.picked_staged if res.picked_staged is not None
        else config.mode == "staged"
    )
    q_exited = (
        res.query_exited.sum() if res.query_exited is not None
        else jnp.int32(0)
    )
    return jax.device_get((
        jax.lax.top_k(masked, min(svc.top_k, D))[1],
        res.scores,
        jnp.stack([m.sum() for m in res.stage_masks]),
        trees_traversed_progressive(
            mask, res.stage_masks, sentinels, svc.ensemble.n_trees, costs
        ),
        res.overflow,
        mask.sum(),
        picked,
        q_exited,
    ))


@pytest.mark.parametrize("dense", [False, True], ids=["trees", "dense"])
@pytest.mark.parametrize("query_exit", [False, True], ids=["no_qe", "qe"])
@pytest.mark.parametrize("mode", ["fused", "staged", "auto"])
def test_folded_step_matches_eager_post(mode, query_exit, dense):
    """rank_batch's top-k, scores and every ServiceStats counter it fills
    are bit-identical to the step followed by the eager post-step ops, over
    a cold batch that overflows, a sparse one with a query left without
    survivors and, in auto mode, a batch steered to each branch."""
    rng = np.random.default_rng(10)
    Q, D, F = 4, 32, 12
    knobs = dict(
        execution_mode=mode, launch_overhead_trees=512.0, survivor_ema=1.0,
        top_k=5,
    )
    if query_exit:
        knobs["query_exit"] = QueryExitConfig(k=3)
    if dense:
        # A fractional dense cost makes the accounting sum non-integer.
        knobs["dense_stage"] = dataclasses.replace(
            make_dense_stage(F, seed=11, keep_frac=0.75), cost_trees=0.37
        )
    svc = _service(**knobs)
    busy = _batch(rng, Q, D, F, survive_frac=0.8)
    X, mask = _batch(rng, Q, D, F, survive_frac=0.2)
    X = X.at[0, :, 0].set(-1.0)           # query 0: no survivors
    mask = mask.at[1, D // 2:].set(False)  # query 1: padded half
    sparse = (X, mask)
    batches = [(busy, None), (sparse, None)]
    if mode == "auto":
        n = svc.n_stages
        batches += [(sparse, [0.0] * n), (sparse, [float(Q * D)] * n)]

    fields = [f.name for f in dataclasses.fields(svc.stats)
              if not f.name.endswith("_s")]
    for (Xb, mb), ema in batches:
        if ema is not None:
            svc.bucket_state(Q, D).ema = ema
        (top_ref, scores_ref, survivors, traversed, overflow, docs, picked,
         q_exited) = _eager_post(svc, Xb, mb)
        before = dataclasses.asdict(svc.stats)
        top_idx, scores = svc.rank_batch(Xb, mb)
        after = dataclasses.asdict(svc.stats)

        np.testing.assert_array_equal(top_idx, top_ref)
        np.testing.assert_array_equal(scores, scores_ref)
        assert svc.bucket_state(Q, D).ema == [float(n) for n in survivors]
        want = dict(before)
        want["batches"] += 1
        want["batches_staged"] += bool(picked)
        want["batches_fused"] += not bool(picked)
        want["queries"] += Q
        want["docs"] += int(docs)
        want["docs_continued"] += int(survivors[-1])
        want["overflow_docs"] += int(overflow)
        want["queries_exited"] += int(q_exited)
        want["trees_traversed"] += float(traversed)
        want["trees_full_equiv"] += int(docs) * svc.ensemble.n_trees
        # tail_rows and doc_tree_slots count launch shapes, not step
        # outputs (tests/test_tracing.py checks them by hand).
        shapes = ("tail_rows", "doc_tree_slots")
        assert {k: after[k] for k in fields if k not in shapes} == {
            k: want[k] for k in fields if k not in shapes
        }
        assert after["doc_tree_slots"] > before["doc_tree_slots"]
    s = svc.stats
    assert s.overflow_docs > 0                 # the cold batch overflowed
    if query_exit:
        assert s.queries_exited > 0
    if mode == "auto":
        assert s.batches_staged > 0 and s.batches_fused > 0
