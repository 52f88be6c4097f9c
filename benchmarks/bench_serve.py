"""Load-generator benchmark for the continuously-batched serving tier.

Drives :class:`repro.serve.tier.ServingTier` with closed-loop client
threads at 1×/8×/64× concurrency and measures what the tentpole claims:

- **QPS and latency under concurrency** — per-request submit→result wall
  times (p50/p99) and aggregate throughput per stream level. The payoff of
  continuous batching is the 64× row: many concurrent single-query clients
  get packed into full engine blocks, so QPS must beat the serial
  baseline by ≥2× (the acceptance bar for the committed full run).
- **Serial baseline** — the same queries submitted one at a time through a
  warmed service (each padded to the same ``(1, D)`` bucket the tier would
  use): what a deployment without the batcher pays.
- **Warm start** — AOT warmup seconds, and the FIRST real request's
  latency vs steady-state p50 (must be ≤2×: no compile hides behind
  request 1).
- **Zero cold-start overflow** — warmup seeds every bucket's capacities at
  the physical max, so the service must report 0 overflow docs across the
  whole run.
- **Bit-exactness** — a sample of batched responses replayed through a
  fresh single-query service must match score-for-score, index-for-index.
- **Degraded mode** (the fault-tolerance tentpole) — an overload run
  against a tier with a degradation ladder and a bounded queue: shed rate
  and deadline-miss rate stay finite fractions (admission control, not
  queue growth), the rung ladder steps under load and recovers after it,
  and a per-rung quality sweep records NDCG@10 against full-ensemble
  teacher labels (monotone: each cheaper rung may only trade quality
  DOWN, and stepping rungs after warmup triggers ZERO jit lowerings).

CPU wall times are NOT TPU predictions (the kernel runs in interpret mode
here); the *ratios* — batched vs serial QPS, first-request vs steady p50 —
are the portable part. Results go to ``BENCH_serve.json`` at the repo root
(full run committed for the perf trajectory); ``main(smoke=True,
json_path=...)`` is the tiny CI profile used by ``check_bench.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

import jax.numpy as jnp

from repro.core.lear import LearClassifier
from repro.core.strategies import QueryExitConfig
from repro.forest.ensemble import random_ensemble
from repro.forest.scoring import score_numpy_oracle
from repro.metrics.ranking import mean_ndcg
from repro.serve.batching import BucketPolicy
from repro.serve.degradation import DegradationPolicy, ExitRung
from repro.serve.errors import Overloaded
from repro.serve.ranking_service import RankingService, ServiceConfig
from repro.serve.tier import ServingTier, TierConfig
from repro.serve.warmup import warmup_service
from repro.utils import count_lowerings

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

N_FEATURES = 12
SENTINELS = (8, 28)
CONCURRENCY = (1, 8, 64)


def _make_service(n_trees: int, seed: int = 0) -> RankingService:
    ens = random_ensemble(seed, n_trees=n_trees, depth=4,
                          n_features=N_FEATURES)
    clfs = [
        LearClassifier(
            forest=random_ensemble(
                100 + i, n_trees=10, depth=3, n_features=N_FEATURES + 4
            ),
            sentinel=s,
        )
        for i, s in enumerate(SENTINELS)
    ]
    return RankingService(
        ens, clfs[0],
        ServiceConfig(
            threshold=0.4, execution_mode="auto",
            launch_overhead_trees="auto",
        ),
        extra_classifiers=clfs[1:],
    )


def _make_queries(
    rng: np.random.Generator, n: int, lo: int, hi: int
) -> list[np.ndarray]:
    return [
        rng.normal(size=(int(rng.integers(lo, hi + 1)), N_FEATURES))
        .astype(np.float32)
        for _ in range(n)
    ]


def _pct(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q))


def _lat_section(lat_s: list[float], wall_s: float) -> dict:
    return {
        "n_queries": len(lat_s),
        "qps": round(len(lat_s) / wall_s, 2),
        "p50_ms": round(_pct(lat_s, 50) * 1e3, 3),
        "p99_ms": round(_pct(lat_s, 99) * 1e3, 3),
    }


def run_serial(
    n_trees: int, queries: list[np.ndarray], doc_bucket: int
) -> dict:
    """One query at a time through a warmed service — the no-batcher
    deployment, padded to the same (1, D) shape the tier would use."""
    svc = _make_service(n_trees)
    warmup_service(svc, N_FEATURES, [(1, doc_bucket)])
    lat = []
    t_wall = time.perf_counter()
    for q in queries:
        X = np.zeros((1, doc_bucket, N_FEATURES), np.float32)
        m = np.zeros((1, doc_bucket), bool)
        X[0, : len(q)] = q
        m[0, : len(q)] = True
        t0 = time.perf_counter()
        svc.rank_batch(jnp.asarray(X), jnp.asarray(m))
        lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_wall
    out = _lat_section(lat, wall)
    out["overflow_docs"] = svc.stats.overflow_docs
    return out


def run_stream(
    tier: ServingTier, queries: list[np.ndarray], concurrency: int
) -> dict:
    """Closed-loop clients: each thread submits its share sequentially and
    waits for every result before the next submit."""
    chunks = [queries[i::concurrency] for i in range(concurrency)]
    lats: list[list[float]] = [[] for _ in range(concurrency)]
    b0 = dict(
        flushes_full=tier.batcher.stats.flushes_full,
        flushes_deadline=tier.batcher.stats.flushes_deadline,
        batches=tier.service.stats.batches,
        queries=tier.service.stats.queries,
    )

    def client(ci: int) -> None:
        for q in chunks[ci]:
            t0 = time.perf_counter()
            tier.submit(q).result(timeout=600)
            lats[ci].append(time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=(ci,), daemon=True)
        for ci in range(concurrency)
    ]
    t_wall = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_wall

    out = _lat_section([x for per_client in lats for x in per_client], wall)
    out["concurrency"] = concurrency
    d_batches = tier.service.stats.batches - b0["batches"]
    d_queries = tier.service.stats.queries - b0["queries"]
    out["engine_batches"] = d_batches
    out["mean_queries_per_batch"] = round(d_queries / max(d_batches, 1), 2)
    out["flushes_full"] = (
        tier.batcher.stats.flushes_full - b0["flushes_full"]
    )
    out["flushes_deadline"] = (
        tier.batcher.stats.flushes_deadline - b0["flushes_deadline"]
    )
    return out


def check_bitexact(
    tier_results: list[tuple[np.ndarray, np.ndarray]],
    queries: list[np.ndarray],
    n_trees: int,
) -> dict:
    """Replay a sample of batched responses through a fresh single-query
    service: scores and top-k must match exactly."""
    ref = _make_service(n_trees)
    identical = True
    for q, (top, scores) in zip(queries, tier_results):
        t_ref, s_ref = ref.rank_batch(
            jnp.asarray(q[None]), jnp.ones((1, len(q)), bool)
        )
        k = min(ref.top_k, len(q))
        if not (
            np.array_equal(scores, np.asarray(s_ref)[0])
            and np.array_equal(top, np.asarray(t_ref)[0][:k])
        ):
            identical = False
            break
    return {"checked": len(tier_results), "identical": identical}


#: The degradation ladder the bench exercises: level 0 is the baseline
#: (threshold 0.4), each rung trades NDCG for latency via the paper's own
#: exit knobs — tighter document threshold, then tighter still plus a
#: finite query-exit margin.
DEGRADE_RUNGS = (
    ExitRung("tight", threshold=0.6),
    ExitRung(
        "tightest", threshold=0.8,
        query_exit=QueryExitConfig(k=10, margin=2.0),
    ),
)


def _teacher_labels(svc: RankingService, q: np.ndarray) -> np.ndarray:
    """Graded 0..4 relevance from the FULL ensemble's ranking of ``q`` —
    the quality reference every rung is scored against (the paper's
    NDCG@10 setup, with the exact scorer as its own teacher)."""
    teacher = score_numpy_oracle(svc.ensemble, q)
    order = np.argsort(-teacher, kind="stable")
    rank = np.empty(len(q), np.int64)
    rank[order] = np.arange(len(q))
    labels = np.zeros(len(q), np.float32)
    for grade, lo_r, hi_r in ((4, 0, 1), (3, 1, 4), (2, 4, 8), (1, 8, 16)):
        labels[(rank >= lo_r) & (rank < hi_r)] = grade
    return labels


def run_degraded_quality(n_trees: int, smoke: bool) -> tuple[list[dict], int]:
    """NDCG@10 of every rung on a fixed eval block, plus the jit-lowering
    count while STEPPING rungs post-warmup (the AOT ladder guarantee)."""
    n_eval = 4 if smoke else 16
    n_docs = 64
    rng = np.random.default_rng(7)
    X = rng.normal(size=(n_eval, n_docs, N_FEATURES)).astype(np.float32)
    mask = np.ones((n_eval, n_docs), bool)

    svc = _make_service(n_trees, seed=1)
    labels = np.stack([_teacher_labels(svc, x) for x in X])
    svc.install_rungs(DEGRADE_RUNGS)
    warmup_service(svc, N_FEATURES, [(n_eval, n_docs)])

    Xj, mj = jnp.asarray(X), jnp.asarray(mask)
    per_level: list[np.ndarray] = []
    with count_lowerings() as count:
        for level in range(svc.n_rungs):
            svc.set_rung(level)
            _top, scores = svc.rank_batch(Xj, mj)
            per_level.append(np.asarray(scores))
    lowerings = count()

    names = ["baseline"] + [r.name for r in DEGRADE_RUNGS]
    rungs = [
        {
            "level": level,
            "name": names[level],
            "ndcg10": round(float(mean_ndcg(
                jnp.asarray(scores), jnp.asarray(labels), mj, k=10
            )), 4),
        }
        for level, scores in enumerate(per_level)
    ]
    return rungs, lowerings


def run_overload(n_trees: int, smoke: bool) -> dict:
    """Spike a degradation-enabled tier with a bounded queue far past its
    capacity, then trickle until it recovers: the shed/miss/degrade/recover
    numbers the fault-tolerance tentpole commits to."""
    offered = 96 if smoke else 384
    policy = BucketPolicy(
        max_queries=8, max_wait_ms=2.0, min_docs=8, max_queue_depth=64
    )
    # Band placement: a full queue (64 deep, 8 per flush) backs requests
    # up for several flush times (≫ 15 ms), so overload degrades; the
    # recovery threshold must clear max_wait_ms, because trickle
    # traffic's queue delay IS the deadline-flush wait — recovering
    # below the flush window would be unreachable by construction.
    dpolicy = DegradationPolicy(
        rungs=DEGRADE_RUNGS,
        degrade_above_ms=15.0,
        recover_below_ms=6.0,
        ema_alpha=0.5,
        dwell_flushes=2,
    )
    svc = _make_service(n_trees)
    tier = ServingTier(
        svc, N_FEATURES,
        TierConfig(
            doc_counts=(64,), warmup=True, persistent_cache=True,
            degradation=dpolicy,
        ),
        policy=policy,
    )
    tier.start()
    rng = np.random.default_rng(11)
    queries = _make_queries(rng, 32, 33, 64)

    futs = []
    max_level = 0
    for i in range(offered):
        try:
            futs.append(tier.submit(queries[i % len(queries)],
                                    deadline_ms=500.0))
        except Overloaded:
            pass  # counted in BatcherStats.shed_overload
        max_level = max(max_level, tier.degradation.level)
    for f in futs:
        try:
            f.result(timeout=600)
        except Exception:
            pass  # misses/crashes are counted typed in the stats
        max_level = max(max_level, tier.degradation.level)

    # Calm trickle until the ladder walks back to the baseline (bounded:
    # a tier that cannot recover is itself a finding in the JSON).
    recover_budget = time.monotonic() + (10.0 if smoke else 60.0)
    while (
        tier.degradation.level != 0 and time.monotonic() < recover_budget
    ):
        tier.rank(queries[0])
    snap = tier.degradation.snapshot()
    health = tier.health()
    tier.stop()

    s = tier.batcher.stats
    return {
        "offered": offered,
        "completed": s.completed,
        "shed_overload": s.shed_overload,
        "deadline_missed": s.shed_deadline + s.expired_deadline,
        "shed_rate": round(s.shed_rate, 4),
        "deadline_miss_rate": round(s.deadline_miss_rate, 4),
        "queue_depth_limit": policy.max_queue_depth,
        "max_queue_depth_observed": s.max_queue_depth,
        "max_level": max_level,
        "final_level": snap["level"],
        "recovered": snap["level"] == 0,
        "degrade_steps": snap["degrade_steps"],
        "recover_steps": snap["recover_steps"],
        "worker_crashes": s.worker_crashes,
        "health_state": health["state"],
    }


def run_degraded(n_trees: int, smoke: bool) -> dict:
    rungs, lowerings = run_degraded_quality(n_trees, smoke)
    return {
        "overload": run_overload(n_trees, smoke),
        "rungs": rungs,
        "post_warmup_lowerings": lowerings,
    }


def main(json_path: str = JSON_PATH, smoke: bool = False) -> dict:
    n_trees = 32 if smoke else 64
    n_queries = 64 if smoke else 512
    n_bitexact = 4 if smoke else 16
    lo, hi = (33, 64)
    policy = BucketPolicy(max_queries=8, max_wait_ms=2.0, min_docs=8)
    rng = np.random.default_rng(0)
    queries = _make_queries(rng, n_queries, lo, hi)
    doc_bucket = policy.doc_bucket(hi)

    svc = _make_service(n_trees)
    tier = ServingTier(
        svc, N_FEATURES,
        TierConfig(doc_counts=(hi,), warmup=True, persistent_cache=True),
        policy=policy,
    )
    t0 = time.perf_counter()
    tier.start()
    start_seconds = time.perf_counter() - t0

    # The first REAL request after warmup: any compile hiding here shows
    # up as first_ms >> steady p50.
    t0 = time.perf_counter()
    first_result = tier.rank(queries[0])
    first_ms = (time.perf_counter() - t0) * 1e3

    streams = [run_stream(tier, queries, c) for c in CONCURRENCY]

    bitexact_sample = queries[:n_bitexact]
    sample_results = [first_result] + [
        tier.rank(q) for q in bitexact_sample[1:]
    ]
    tier.stop()

    serial = run_serial(n_trees, queries, doc_bucket)
    bitexact = check_bitexact(sample_results, bitexact_sample, n_trees)
    degraded = run_degraded(n_trees, smoke)

    steady_p50 = streams[0]["p50_ms"]
    payload = {
        "config": {
            "smoke": smoke,
            "n_trees": n_trees,
            "n_features": N_FEATURES,
            "sentinels": list(SENTINELS),
            "n_queries": n_queries,
            "doc_range": [lo, hi],
            "max_queries": policy.max_queries,
            "max_wait_ms": policy.max_wait_ms,
            "n_devices": tier.placement.n_devices,
        },
        "serial": serial,
        "streams": streams,
        "speedup": {
            "qps_max_concurrency_vs_serial": round(
                streams[-1]["qps"] / serial["qps"], 2
            ),
        },
        "warmup": {
            "start_seconds": round(start_seconds, 2),
            "warmup_seconds": round(tier.warmup_report.total_seconds, 2),
            "buckets": [list(b) for b in tier.warmup_report.buckets],
            "cache_dir": tier.warmup_report.cache_dir,
            "warm_first_request_ms": round(first_ms, 3),
            "first_to_steady_p50_ratio": round(
                first_ms / max(steady_p50, 1e-9), 3
            ),
        },
        "cold_start_overflow_docs": svc.stats.overflow_docs,
        "bitexact": bitexact,
        "degraded": degraded,
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    print(f"serial        qps={serial['qps']:>8}  p50={serial['p50_ms']}ms"
          f"  p99={serial['p99_ms']}ms")
    for s in streams:
        print(f"stream {s['concurrency']:>3}x   qps={s['qps']:>8}"
              f"  p50={s['p50_ms']}ms  p99={s['p99_ms']}ms"
              f"  q/batch={s['mean_queries_per_batch']}")
    print(f"speedup {payload['speedup']['qps_max_concurrency_vs_serial']}x"
          f"  overflow={payload['cold_start_overflow_docs']}"
          f"  first/p50={payload['warmup']['first_to_steady_p50_ratio']}"
          f"  bitexact={bitexact['identical']}")
    _print_degraded(degraded)
    return payload


def _print_degraded(degraded: dict) -> None:
    ov = degraded["overload"]
    print(f"overload      shed={ov['shed_rate']}"
          f"  miss={ov['deadline_miss_rate']}"
          f"  level max={ov['max_level']} final={ov['final_level']}"
          f"  recovered={ov['recovered']}  depth<= {ov['queue_depth_limit']}")
    rungs = "  ".join(
        f"{r['name']}={r['ndcg10']}" for r in degraded["rungs"]
    )
    print(f"rung ndcg@10  {rungs}"
          f"  lowerings={degraded['post_warmup_lowerings']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI profile (do not commit its numbers)")
    ap.add_argument("--overload-smoke", action="store_true",
                    help="run ONLY the degraded/overload section, tiny — "
                         "the nightly chaos lane's live fire exercise")
    ap.add_argument("--json", default=JSON_PATH)
    args = ap.parse_args()
    if args.overload_smoke:
        degraded = run_degraded(n_trees=32, smoke=True)
        _print_degraded(degraded)
        ov = degraded["overload"]
        ok = (
            ov["worker_crashes"] == 0
            and ov["health_state"] in ("running", "stopped")
            and ov["max_queue_depth_observed"] <= ov["queue_depth_limit"]
            and degraded["post_warmup_lowerings"] == 0
        )
        print(f"overload smoke {'OK' if ok else 'FAILED'}")
        sys.exit(0 if ok else 1)
    main(json_path=args.json, smoke=args.smoke)
