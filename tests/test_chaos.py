"""Chaos suite: the fault-tolerance contracts, proven by injection.

Every test here drives the REAL batcher/supervisor/degradation machinery
with injected faults (tests/faults.py) and asserts the tentpole claims:

- a worker crash fails exactly the in-flight bucket, queued requests
  survive the restart, and the tier serves again;
- exhausting the restart budget fails everything TYPED — no future is
  ever left unresolved, before, during, or after the failure;
- a load spike is shed (:class:`Overloaded`) and degraded (rung ladder),
  never absorbed into unbounded queue growth;
- degraded responses are bit-exact with a standalone service configured
  as that rung — degradation changes WHICH configuration serves, not the
  numerics of serving it;
- every installed rung is AOT-warmed: stepping the ladder never jits.

Most tests use :class:`tests.faults.FakeService` (no jax, milliseconds);
the bit-exactness and warmup proofs use the real engine.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from faults import (
    CrashTimes,
    FakeClock,
    FakeService,
    InjectedEngineError,
    PoisonOnce,
    settle,
    spike,
)
from repro.core.lear import LearClassifier
from repro.core.strategies import QueryExitConfig
from repro.forest.ensemble import random_ensemble
from repro.serve.batching import BatcherHooks, BucketPolicy, ContinuousBatcher
from repro.serve.degradation import (
    DegradationController,
    DegradationPolicy,
    ExitRung,
)
from repro.serve.errors import (
    BatcherStopped,
    Overloaded,
    WorkerCrashed,
    WorkerFailed,
)
from repro.serve.ranking_service import RankingService, ServiceConfig
from repro.serve.warmup import warmup_service
from repro.utils import count_lowerings

pytestmark = pytest.mark.chaos

F = 12


def _query(n_docs: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n_docs, F)).astype(np.float32)


def _batcher(svc, policy=None, **kw) -> ContinuousBatcher:
    b = ContinuousBatcher(svc, F, policy or BucketPolicy(), **kw)
    b.start()
    return b


def _assert_scores(scores: np.ndarray, q: np.ndarray) -> None:
    np.testing.assert_allclose(
        scores, FakeService.expected_scores(q), rtol=1e-6
    )


# -- supervision ----------------------------------------------------------


def test_worker_crash_restarts_and_serves_again():
    svc = FakeService()
    crash = CrashTimes(1)
    b = _batcher(
        svc,
        BucketPolicy(max_queries=1, max_wait_ms=1.0),
        hooks=BatcherHooks(on_flush=crash),
        backoff_base_s=0.002,
    )
    q = _query(16)
    with pytest.raises(WorkerCrashed):
        b.submit(q).result(timeout=30)
    assert crash.fired == 1

    # The supervisor restarted the worker: the tier serves again.
    _top, scores = b.submit(q).result(timeout=30)
    _assert_scores(scores, q)
    h = b.health()
    assert h["state"] == "running"
    assert h["crashes"] == 1 and h["restarts"] == 1
    assert "InjectedCrash" in h["last_error"]
    b.stop()
    assert b.stats.worker_crashes == 1
    assert b.stats.completed == 1 and b.stats.failed == 1


def test_queued_requests_survive_a_crash():
    """A crash fails exactly the in-flight bucket; requests still queued
    in OTHER buckets are served after the restart."""
    clock = FakeClock()
    svc = FakeService()
    crash = CrashTimes(1)
    b = _batcher(
        svc,
        BucketPolicy(max_queries=8, max_wait_ms=5.0),
        clock=clock,
        hooks=BatcherHooks(on_flush=crash),
        backoff_base_s=0.002,
    )
    # Queued survivor first (bucket 16; virtual timer frozen → it waits)...
    survivor_q = _query(16, seed=7)
    survivor = b.submit(survivor_q)
    # ...then a FULL bucket-8 flush, which the hook kills mid-air.
    doomed = [b.submit(_query(8, seed=i)) for i in range(8)]
    _, errors = settle(doomed)
    assert len(errors) == 8
    assert all(isinstance(e, WorkerCrashed) for e in errors)

    clock.advance(10.0)  # ripen the survivor's flush timer
    _top, scores = survivor.result(timeout=30)
    _assert_scores(scores, survivor_q)
    b.stop()
    assert b.stats.worker_crashes == 1
    assert b.stats.completed == 1 and b.stats.failed == 8


def test_restart_budget_exhaustion_fails_everything_typed():
    svc = FakeService()
    crash = CrashTimes(10)  # far more faults than the budget tolerates
    b = _batcher(
        svc,
        BucketPolicy(max_queries=1, max_wait_ms=1.0),
        hooks=BatcherHooks(on_flush=crash),
        max_restarts=1,
        backoff_base_s=0.002,
    )
    futs = spike(b, 4, _query(16))
    results, errors = settle(futs)
    assert results == [] and len(errors) == 4
    assert all(
        isinstance(e, (WorkerCrashed, WorkerFailed)) for e in errors
    )
    assert any(isinstance(e, WorkerFailed) for e in errors)

    # The batcher is failed, permanently and typed.
    with pytest.raises(WorkerFailed):
        b.submit(_query(16))
    assert b.health()["state"] == "failed"
    b.stop()
    assert b.health()["state"] == "failed"  # survives stop()


def test_engine_error_fails_bucket_and_loop_survives():
    svc = FakeService()
    b = _batcher(svc, BucketPolicy(max_queries=2, max_wait_ms=2.0))
    svc.fail_next(1)
    futs = [b.submit(_query(8, seed=i)) for i in range(2)]
    _, errors = settle(futs)
    assert len(errors) == 2
    assert all(isinstance(e, InjectedEngineError) for e in errors)

    # An engine error is contained: not a crash, and the next bucket works.
    q = _query(8, seed=9)
    _top, scores = b.submit(q).result(timeout=30)
    _assert_scores(scores, q)
    assert b.health()["crashes"] == 0
    b.stop()
    assert b.stats.worker_crashes == 0


def test_poisoned_batch_fails_one_request_only():
    svc = FakeService()
    b = _batcher(
        svc,
        BucketPolicy(max_queries=4, max_wait_ms=2.0),
        hooks=BatcherHooks(on_result=PoisonOnce()),
    )
    qs = [_query(16, seed=i) for i in range(4)]
    futs = [b.submit(q) for q in qs]
    results, errors = settle(futs)
    # Exactly one request is poisoned; its bucket-mates complete normally.
    assert len(errors) == 1 and isinstance(errors[0], InjectedEngineError)
    assert len(results) == 3
    assert svc.calls == 1  # one padded block served all four
    served = [i for i, f in enumerate(futs) if f.exception() is None]
    for i, (_top, scores) in zip(served, results):
        _assert_scores(scores, qs[i])
    b.stop()
    assert b.stats.completed == 3 and b.stats.failed == 1
    assert b.stats.worker_crashes == 0


# -- overload -------------------------------------------------------------


def test_load_spike_sheds_and_queue_stays_bounded():
    svc = FakeService(latency_s=0.002)
    b = _batcher(
        svc,
        BucketPolicy(max_queries=8, max_wait_ms=1.0, max_queue_depth=8),
    )
    q = _query(16)
    futs = spike(b, 300, q)
    results, errors = settle(futs, timeout_s=60)
    assert len(results) + len(errors) == 300
    assert all(isinstance(e, Overloaded) for e in errors)
    assert b.stats.shed_overload == len(errors) > 0
    # Admission control held: observed depth never exceeded the bound.
    assert b.stats.max_queue_depth <= 8
    assert 0.0 < b.stats.shed_rate < 1.0
    for _top, scores in results:
        _assert_scores(scores, q)
    b.stop()
    assert b.health()["queue_depth"] == 0


def test_load_spike_degrades_then_recovers():
    """Sustained queue delay walks the rung ladder down; calm traffic
    walks it back up. The controller only ever touches the service from
    the worker thread, with pointer swaps the FakeService records."""
    svc = FakeService(latency_s=0.003)
    policy = DegradationPolicy(
        rungs=(
            ExitRung("tight", threshold=0.9),
            ExitRung("tighter", threshold=0.95),
        ),
        degrade_above_ms=5.0,
        recover_below_ms=2.0,
        ema_alpha=0.5,
        dwell_flushes=1,
    )
    ctrl = DegradationController(svc, policy)
    ctrl.install()
    assert svc.n_rungs == 3  # baseline + 2 rungs
    b = _batcher(
        svc,
        BucketPolicy(max_queries=1, max_wait_ms=0.2, max_queue_depth=None),
        degradation=ctrl,
    )
    q = _query(16)
    futs = [b.submit(q) for _ in range(120)]
    settle(futs, timeout_s=60)
    snap = ctrl.snapshot()
    assert snap["degrade_steps"] >= 1
    assert max(svc.rung_history) >= 1

    # Calm trickle traffic: the delay EMA decays below the recovery
    # threshold and the ladder steps back to baseline.
    deadline = time.monotonic() + 30.0
    while ctrl.level != 0:
        assert time.monotonic() < deadline, ctrl.snapshot()
        b.submit(q).result(timeout=30)
    snap = ctrl.snapshot()
    assert snap["level"] == 0 and snap["rung"] == "baseline"
    assert snap["recover_steps"] >= 1
    b.stop()


# -- stop/submit races ----------------------------------------------------


def test_submit_during_drain_is_never_lost():
    """Race submits against stop(): every future either resolves with a
    result or raises a typed error — silently dropping a request into a
    dict nobody flushes is the bug this pins down."""
    for round_seed in range(5):
        svc = FakeService()
        b = _batcher(svc, BucketPolicy(max_queries=4, max_wait_ms=0.5))
        q = _query(16, seed=round_seed)
        futs: list = []
        stop_now = threading.Event()

        def hammer():
            while not stop_now.is_set():
                futs.extend(spike(b, 5, q))

        t = threading.Thread(target=hammer)
        t.start()
        time.sleep(0.02)
        stop_now.set()
        b.stop()
        t.join()
        results, errors = settle(futs, timeout_s=30)
        assert len(results) + len(errors) == len(futs)
        # Admitted requests were served; racing ones got a typed
        # rejection — stop (drain handoff) or shed (admission control).
        assert all(
            isinstance(e, (BatcherStopped, Overloaded)) for e in errors
        )
        for _top, scores in results:
            _assert_scores(scores, q)
        assert b.stats.completed == len(results)


def test_no_future_unresolved_across_random_interleavings():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    op = st.one_of(
        st.tuples(
            st.just("submit"),
            st.integers(min_value=1, max_value=40),
            st.sampled_from([None, 0.0, 5.0, 1000.0]),
        ),
        st.just(("crash",)),
        st.just(("engine_fail",)),
        st.just(("pause",)),
    )

    @hypothesis.settings(
        max_examples=15, deadline=None, derandomize=True,
        suppress_health_check=list(hypothesis.HealthCheck),
    )
    @hypothesis.given(ops=st.lists(op, min_size=1, max_size=30))
    def run(ops):
        svc = FakeService()
        crash = CrashTimes(0)  # armed per "crash" op below
        b = _batcher(
            svc,
            BucketPolicy(max_queries=2, max_wait_ms=0.5, max_queue_depth=16),
            hooks=BatcherHooks(on_flush=crash),
            max_restarts=3,
            backoff_base_s=0.001,
        )
        futs = []
        for item in ops:
            if item[0] == "submit":
                _, n_docs, deadline_ms = item
                futs.extend(spike(b, 1, _query(n_docs), deadline_ms))
            elif item[0] == "crash":
                with crash._lock:
                    crash.remaining += 1
            elif item[0] == "engine_fail":
                svc.fail_next(1)
            else:
                time.sleep(0.002)
        b.stop()
        results, errors = settle(futs, timeout_s=30)
        # THE invariant: every submitted request resolved, one way or the
        # other — no interleaving of submit/crash/fail/stop strands one.
        assert len(results) + len(errors) == len(futs)

    run()


# -- degraded-mode numerics (real engine) ---------------------------------


def _real_service(threshold=0.4, query_exit=None):
    ens = random_ensemble(0, n_trees=64, depth=4, n_features=F)
    clf = LearClassifier(
        forest=random_ensemble(100, n_trees=10, depth=3, n_features=16),
        sentinel=8,
    )
    return RankingService(
        ens, clf,
        ServiceConfig(
            threshold=threshold,
            execution_mode="fused",
            launch_overhead_trees=512.0,
            query_exit=query_exit,
        ),
    )


def test_degraded_rung_is_bitexact_with_standalone_config():
    """Serving at rung N is the SAME computation as a service built with
    that rung's knobs from scratch — degradation trades quality via the
    paper's exit knobs, never via approximate serving."""
    rung_qe = QueryExitConfig(k=5, margin=2.0)
    svc = _real_service(threshold=0.4)
    svc.install_rungs((
        ExitRung("tight", threshold=0.7),
        ExitRung("margin", threshold=0.7, query_exit=rung_qe),
    ))
    X = jnp.asarray(_query(32, seed=3)[None])
    mask = jnp.ones((1, 32), bool)

    svc.set_rung(1)
    top_1, sc_1 = svc.rank_batch(X, mask)
    svc.set_rung(2)
    top_2, sc_2 = svc.rank_batch(X, mask)
    svc.set_rung(0)

    ref_tight = _real_service(threshold=0.7)
    t_ref, s_ref = ref_tight.rank_batch(X, mask)
    np.testing.assert_array_equal(np.asarray(sc_1), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(top_1), np.asarray(t_ref))

    ref_margin = _real_service(threshold=0.7, query_exit=rung_qe)
    t_ref, s_ref = ref_margin.rank_batch(X, mask)
    np.testing.assert_array_equal(np.asarray(sc_2), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(top_2), np.asarray(t_ref))

    # Baseline numerics are untouched by the ladder having been installed.
    base = _real_service(threshold=0.4)
    t_ref, s_ref = base.rank_batch(X, mask)
    _topb, scb = svc.rank_batch(X, mask)
    np.testing.assert_array_equal(np.asarray(scb), np.asarray(s_ref))


def test_rung_warmup_leaves_zero_post_warmup_lowerings():
    """Every rung of the ladder is AOT-compiled by warmup: stepping the
    ladder afterwards — at peak load — never triggers a jit."""
    svc = _real_service(threshold=0.4)
    svc.install_rungs((
        ExitRung("tight", threshold=0.7),
        ExitRung("margin", threshold=0.9, query_exit=QueryExitConfig(k=5, margin=2.0)),
    ))
    report = warmup_service(svc, F, [(1, 32)])
    assert report.rungs_warmed == 3
    assert svc.rung_level == 0  # warmup hands traffic the baseline

    X = jnp.asarray(_query(32, seed=5)[None])
    mask = jnp.ones((1, 32), bool)
    with count_lowerings() as lowerings:
        for level in (0, 1, 2, 1, 0):
            svc.set_rung(level)
            svc.rank_batch(X, mask)
    assert lowerings() == 0, f"{lowerings()} recompiles while stepping rungs"
