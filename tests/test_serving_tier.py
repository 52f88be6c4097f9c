"""Serving-tier contracts: continuous batching, AOT warmup, placement.

The tentpole claims, as tests:
- batched serving is BIT-EXACT with single-query ``rank_batch`` (padding
  rows are inert, per-request top-k reproduces ``lax.top_k`` tie-break);
- the flush policy triggers on full buckets AND on deadlines (a lone
  query is never stranded);
- AOT warmup leaves zero compiles and zero cold-start overflow for the
  warmed shapes, resets stats/EMA, and keeps the seeded peaks;
- the single-device placement path is the identity and the 1×1-mesh path
  is numerically indistinguishable from it.
"""

import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.lear import LearClassifier
from repro.forest.ensemble import random_ensemble
from repro.serve.batching import BucketPolicy, ContinuousBatcher
from repro.serve.errors import BatcherStopped
from repro.serve.placement import local, single_device
from repro.serve.ranking_service import RankingService, ServiceConfig
from repro.serve.tier import ServingTier, TierConfig
from repro.serve.warmup import (
    DEFAULT_CACHE_DIR,
    enable_persistent_cache,
    warmup_service,
)
from repro.utils import count_lowerings

F = 12
REPO = pathlib.Path(__file__).resolve().parents[1]


def _service(seed=0, sentinels=(8, 28), **knobs):
    ens = random_ensemble(seed, n_trees=64, depth=4, n_features=F)
    clfs = [
        LearClassifier(
            forest=random_ensemble(100 + i, n_trees=10, depth=3, n_features=16),
            sentinel=s,
        )
        for i, s in enumerate(sentinels)
    ]
    knobs.setdefault("execution_mode", "fused")
    knobs.setdefault("launch_overhead_trees", 512.0)
    svc = RankingService(
        ens, clfs[0], ServiceConfig(threshold=0.4, **knobs),
        extra_classifiers=clfs[1:],
    )
    # Deterministic stage gate (continue ⇔ feature 0 positive), installed
    # before any trace — keeps survivor counts exact and compiles cheap.
    gate = lambda p, m, features=None: m & (features[..., 0] > 0.0)
    svc.stage_strategies = [gate] * len(svc.sentinels)
    return svc


def _queries(rng, n, lo=20, hi=32):
    qs = []
    for _ in range(n):
        q = rng.normal(size=(int(rng.integers(lo, hi + 1)), F))
        qs.append(q.astype(np.float32))
    return qs


def test_policy_buckets():
    p = BucketPolicy(max_queries=8, min_docs=8, max_docs=256)
    assert p.doc_bucket(1) == 8 and p.doc_bucket(9) == 16
    assert p.doc_bucket(256) == 256
    assert p.query_bucket(1) == 1 and p.query_bucket(3) == 4
    assert p.query_bucket(100) == 8  # clipped at max_queries
    assert p.buckets((20, 30)) == [(1, 32), (2, 32), (4, 32), (8, 32)]
    assert p.buckets((20, 100)) == (
        [(q, 32) for q in (1, 2, 4, 8)] + [(q, 128) for q in (1, 2, 4, 8)]
    )
    with pytest.raises(AssertionError):
        BucketPolicy(max_queries=6)  # not a power of two


def test_batcher_packs_and_is_bitexact():
    """Many concurrent ragged queries → fewer engine batches, every
    response identical to submitting that query alone."""
    rng = np.random.default_rng(0)
    svc = _service()
    b = ContinuousBatcher(
        svc, F, BucketPolicy(max_queries=4, max_wait_ms=50.0)
    )
    b.start()
    queries = _queries(rng, 12)
    futs = [b.submit(q) for q in queries]
    results = [f.result(timeout=120) for f in futs]
    b.stop()

    assert b.stats.completed == 12 and b.stats.failed == 0
    assert b.stats.flushes_full >= 1
    assert svc.stats.batches < 12, "batcher did not pack"
    assert svc.stats.queries == 12

    ref = _service()  # fresh service: no shared adaptive state
    for q, (top, scores) in zip(queries, results):
        t_ref, s_ref = ref.rank_batch(
            jnp.asarray(q[None]), jnp.ones((1, q.shape[0]), bool)
        )
        np.testing.assert_array_equal(scores, np.asarray(s_ref)[0])
        k = min(ref.top_k, q.shape[0])
        np.testing.assert_array_equal(top, np.asarray(t_ref)[0][:k])


def test_deadline_flush_frees_a_lone_query():
    svc = _service()
    b = ContinuousBatcher(svc, F, BucketPolicy(max_queries=8, max_wait_ms=5.0))
    b.start()
    q = np.random.default_rng(1).normal(size=(16, F)).astype(np.float32)
    top, scores = b.submit(q).result(timeout=120)
    assert scores.shape == (16,) and top.shape == (10,)
    b.stop()
    assert b.stats.flushes_deadline == 1 and b.stats.flushes_full == 0


def test_batcher_propagates_engine_errors():
    svc = _service()
    svc.rank_batch = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom"))
    b = ContinuousBatcher(svc, F, BucketPolicy(max_queries=2, max_wait_ms=5.0))
    b.start()
    futs = [b.submit(np.zeros((8, F), np.float32)) for _ in range(2)]
    for f in futs:
        with pytest.raises(RuntimeError, match="boom"):
            f.result(timeout=60)
    b.stop()
    assert b.stats.failed == 2 and b.stats.completed == 0


def test_warmup_no_recompiles_no_cold_start_overflow():
    """After warmup of a (Q, D) bucket: serving a dense batch of that shape
    triggers ZERO jit lowerings and ZERO overflow (capacities were seeded
    at the physical max), and the warmup's own traffic left no stats."""
    svc = _service(execution_mode="auto")
    report = warmup_service(svc, F, [(2, 64)])
    assert report.buckets == [(2, 64)]
    assert svc.stats.batches == 0  # warmup is not traffic
    state = svc.bucket_state(2, 64)
    assert state.peaks == [128] * len(svc.sentinels)  # kept
    assert state.ema is None  # cleared

    X = np.random.default_rng(2).normal(size=(2, 64, F)).astype(np.float32)
    X[..., 0] = 1.0  # every document survives every stage
    X, mask = jnp.asarray(X), jnp.ones((2, 64), bool)
    with count_lowerings() as lowerings:
        svc.rank_batch(X, mask)
        svc.rank_batch(X, mask)
    assert lowerings() == 0, f"{lowerings()} recompiles after warmup"
    assert svc.stats.overflow_docs == 0
    # Without warmup the same dense batch DOES overflow its cold-start
    # capacity — the guarantee above is the warmup, not the workload.
    cold = _service(execution_mode="auto")
    cold.rank_batch(X, mask)
    assert cold.stats.overflow_docs > 0


def test_tier_end_to_end_stats_and_drain():
    svc = _service()
    tier = ServingTier(
        svc, F,
        TierConfig(doc_counts=(32,), warmup=True, persistent_cache=False),
        policy=BucketPolicy(max_queries=2, max_wait_ms=20.0),
    )
    tier.start()
    rng = np.random.default_rng(3)
    futs = [tier.submit(q) for q in _queries(rng, 5)]
    res = [f.result(timeout=120) for f in futs]
    tier.stop()
    assert len(res) == 5
    s = tier.stats()
    assert s["batcher"]["completed"] == 5
    assert s["service"]["queries"] == 5
    assert s["service"]["overflow_docs"] == 0
    assert s["warmup_seconds"] > 0
    assert s["n_devices"] == 1
    # Restart after stop is allowed; submit after stop gets the typed stop.
    with pytest.raises(BatcherStopped):
        tier.submit(_queries(rng, 1)[0])
    # The health surface outlives the worker: state + queue are readable.
    h = tier.health()
    assert h["state"] == "stopped" and h["queue_depth"] == 0
    assert h["crashes"] == 0 and not h["started"]


def test_single_device_placement_is_identity_and_local_mesh_bitexact():
    X = jnp.asarray(
        np.random.default_rng(4).normal(size=(2, 32, F)).astype(np.float32)
    )
    mask = jnp.ones((2, 32), bool)
    sd = single_device()
    assert sd.put(X, mask) == (X, mask) and sd.n_devices == 1

    svc_a, svc_b = _service(), _service()
    t_a, s_a = svc_a.rank_batch(X, mask)
    t_b, s_b = svc_b.rank_batch(X, mask, placement=local())
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
    np.testing.assert_array_equal(np.asarray(t_a), np.asarray(t_b))


_MULTIDEV_PROG = r"""
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from repro.core.lear import LearClassifier
from repro.forest.ensemble import random_ensemble
from repro.serve.placement import data_parallel, single_device
from repro.serve.ranking_service import RankingService, ServiceConfig

def service():
    ens = random_ensemble(0, n_trees=16, depth=2, n_features=6)
    clf = LearClassifier(
        forest=random_ensemble(7, n_trees=4, depth=2, n_features=10),
        sentinel=8,
    )
    svc = RankingService(ens, clf, ServiceConfig(
        threshold=0.4, execution_mode="fused", launch_overhead_trees=512.0,
    ))
    svc.stage_strategies = [
        lambda p, m, features=None: m & (features[..., 0] > 0.0)
    ]
    return svc

pl = data_parallel()
assert pl.n_devices == 8
X = jnp.asarray(np.random.default_rng(0)
                .normal(size=(8, 16, 6)).astype(np.float32))
mask = jnp.ones((8, 16), bool)
Xs, ms = pl.put(X, mask)
# The query axis really is split 8 ways...
assert len(Xs.sharding.device_set) == 8, Xs.sharding
top_s, sc_s = service().rank_batch(Xs, ms, placement=pl)
# ...and a non-divisible Q degrades to replication instead of crashing.
X1, m1 = pl.put(X[:1], mask[:1])
assert len(X1.sharding.device_set) == 8  # replicated across the mesh
service().rank_batch(X1, m1, placement=pl)

top_r, sc_r = service().rank_batch(*single_device().put(X, mask))
np.testing.assert_allclose(np.asarray(sc_s), np.asarray(sc_r),
                           rtol=1e-6, atol=1e-6)
np.testing.assert_array_equal(np.asarray(top_s), np.asarray(top_r))

# data_parallel(4): the folded step (response and stats computed inside the
# one program, under the mesh) is bit-exact with one device, stats included
# (tail_rows counts each device's launch padding, so it differs).
def stats(svc):
    return {k: v for k, v in dataclasses.asdict(svc.stats).items()
            if not k.endswith("_s") and k != "tail_rows"}

pl4 = data_parallel(4)
svc4, svc1 = service(), service()
X4, m4 = pl4.put(X, mask)
assert len(X4.sharding.device_set) == 4, X4.sharding
top4, sc4 = svc4.rank_batch(X4, m4, placement=pl4)
top1, sc1 = svc1.rank_batch(X, mask)
np.testing.assert_array_equal(np.asarray(sc4), np.asarray(sc1))
np.testing.assert_array_equal(np.asarray(top4), np.asarray(top1))
assert stats(svc4) == stats(svc1), (stats(svc4), stats(svc1))
assert svc1.stats.docs_continued > 0
print("MULTIDEV_OK")
"""


def test_data_parallel_placement_8_devices():
    """The sharded serving path on a forced 8-device CPU: query axis split
    across the mesh, results matching the single-device reference, and on
    a ``data_parallel(4)`` mesh bit-exact with it, stats included."""
    res = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_PROG],
        capture_output=True, text=True, timeout=570,
        env={
            "PYTHONPATH": str(REPO / "src"), "PATH": os.environ["PATH"],
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
        cwd=REPO,
    )
    assert "MULTIDEV_OK" in res.stdout, res.stdout + res.stderr


def test_enable_persistent_cache_points_jax_at_dir(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; unset, the cache is the one fixed,
    git-ignored directory inside the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        d = str(tmp_path / "xla-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        assert enable_persistent_cache() == d
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = enable_persistent_cache()
        assert got == DEFAULT_CACHE_DIR == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        ignored = (REPO / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
