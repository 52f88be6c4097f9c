#!/usr/bin/env python3
"""Chip smoke test: the LEAR serving path end to end on a TPU, at paper width.

    python chip_smoke.py [--seed N]                # one chip
    python chip_smoke.py --four-chips [--seed N]   # data_parallel(4) vs one chip

One-chip run, all in this one process (a chip belongs to one process):

1. A random ensemble of ``lear_msn1``'s shape (1,047 trees, depth 6, 136
   features) and 10-tree LEAR classifiers, made from ``--seed``.
2. Two ``ServingTier`` deployments, each warmed over doc buckets 128 and
   256, then sent the same 32 queries (40–256 documents, from ``--seed``):
   - *forced-continue*: sentinels 50 and 100 at threshold 0, so every
     document runs all 1,047 trees (segmented head launch, both auto-mode
     branches); scores are compared with ``score_numpy_oracle`` and the
     top-10 with the oracle's;
   - *LEAR*: the paper's configuration (sentinel 50, threshold 0.5);
     every score must be the host oracle's partial score (exit) or full
     score (continue), and each exit decision must match the host replay
     of the classifier on host-built LEAR features.
3. The served window must show zero jit lowerings, zero failed or shed
   requests, and the compiled serving step must contain Mosaic kernels
   (``tpu_custom_call``).

``--four-chips`` runs only the LEAR tier on ``data_parallel(4)`` against
the same tier on ``single_device()``: equal results, and each device's
kernels must see their own share of the rows, not the whole batch.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails. The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import lear_msn1  # noqa: E402
from repro.core.lear import LearClassifier  # noqa: E402
from repro.forest.ensemble import random_ensemble, slice_trees  # noqa: E402
from repro.forest.scoring import score_numpy_oracle  # noqa: E402
from repro.serve.batching import BucketPolicy  # noqa: E402
from repro.serve.calibration import last_calibration  # noqa: E402
from repro.serve.placement import data_parallel, single_device  # noqa: E402
from repro.serve.ranking_service import RankingService, ServiceConfig  # noqa: E402
from repro.serve.tier import ServingTier, TierConfig  # noqa: E402
from repro.serve.warmup import enable_persistent_cache  # noqa: E402
from repro.utils import count_lowerings, mosaic_kernel_rows  # noqa: E402

DOC_BUCKETS = (128, 256)
N_QUERIES = 32
DOC_RANGE = (40, 256)
SECOND_SENTINEL = 100          # forced-continue: a 2-stage (segmented) head
CLASSIFIER_DEPTH = 5           # repro.core.lear.train_lear's default depth
LEAR_THRESHOLD = 0.5
# f32 scores of up to 1,047 trees (|score| ≲ 10) against the float64
# oracle: the summation orders differ, each add rounds at 2^-24 relative.
ATOL, RTOL = 1e-4, 1e-5
# Host and device LEAR features differ in the last f32 bits, so a logit
# within this of 0 may fall either side of the 0.5 threshold.
AMBIGUOUS_LOGIT = 1e-4


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def build_models(
    seed: int, n_trees: int, depth: int, n_features: int,
    classifier_trees: int,
) -> tuple:
    ens = random_ensemble(seed, n_trees=n_trees, depth=depth,
                          n_features=n_features)
    clf_width = n_features + 4  # LEAR's augmented features
    clfs = [
        random_ensemble(seed + 1 + i, n_trees=classifier_trees,
                        depth=CLASSIFIER_DEPTH, n_features=clf_width)
        for i in range(2)
    ]
    return ens, clfs


def make_queries(
    seed: int, n_queries: int, n_features: int, lo: int, hi: int
) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [
        rng.normal(size=(int(rng.integers(lo, hi + 1)), n_features))
        .astype(np.float32)
        for _ in range(n_queries)
    ]


def serve(
    service: RankingService, n_features: int, queries: list[np.ndarray],
    doc_buckets: tuple[int, ...], placement=None, timeout_s: float = 600.0,
) -> dict:
    """Warm a tier, send every query, collect every future."""
    tier = ServingTier(
        service, n_features, TierConfig(doc_counts=doc_buckets),
        policy=BucketPolicy(min_docs=min(doc_buckets),
                            max_docs=max(doc_buckets)),
        placement=placement,
    )
    results, errors = [], []
    tier.start()
    try:
        with count_lowerings() as lowerings:
            futures = [tier.submit(q) for q in queries]
            for fut in futures:
                try:
                    results.append(fut.result(timeout=timeout_s))
                except Exception as e:  # a failed request is a finding
                    results.append(None)
                    errors.append(repr(e))
    finally:
        tier.stop()
    stats = tier.stats()
    return {
        "results": results,
        "errors": errors,
        "warmup_s": tier.warmup_report.total_seconds,
        "lowerings": lowerings(),
        "served": stats["batcher"]["completed"],
        "shed": stats["batcher"]["shed_overload"]
        + stats["batcher"]["shed_deadline"],
        "failed": stats["batcher"]["failed"],
        "overflow_docs": stats["service"]["overflow_docs"],
        "continue_rate": stats["service"]["continue_rate"],
        "batches": stats["service"]["batches"],
    }


def check_served(name: str, run: dict, n_queries: int) -> None:
    print(f"[{name}] warmup_s={run['warmup_s']:.3f} "
          f"served_window_lowerings={run['lowerings']} "
          f"served={run['served']} shed={run['shed']} failed={run['failed']} "
          f"batches={run['batches']} overflow_docs={run['overflow_docs']} "
          f"continue_rate={run['continue_rate']:.4f}")
    check(not run["errors"], f"{name}: failed requests {run['errors'][:3]}")
    check(run["served"] == n_queries and run["failed"] == 0
          and run["shed"] == 0, f"{name}: not every request was served")
    check(run["lowerings"] == 0,
          f"{name}: {run['lowerings']} lowerings in the served window")


def close(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Elementwise: ``got`` equals the f32 oracle ``ref`` within tolerance."""
    return np.abs(got - ref) <= ATOL + RTOL * np.abs(ref)


def top_k_matches(top: np.ndarray, ref: np.ndarray, k: int) -> bool:
    """Served top-k equals the reference order, except where the two
    documents swapped at a position tie in the reference scores."""
    want = np.lexsort((np.arange(ref.size), -ref))[:k]
    return all(
        a == b or abs(ref[a] - ref[b]) <= ATOL + RTOL * abs(ref[b])
        for a, b in zip(top[:k], want)
    )


def check_forced_continue(ens, run: dict, queries: list[np.ndarray],
                          top_k: int) -> dict:
    worst = 0.0
    for i, (q, (top, scores)) in enumerate(zip(queries, run["results"])):
        ref = score_numpy_oracle(ens, q)
        err = np.abs(scores - ref)
        worst = max(worst, float(err.max()))
        check(np.all(close(scores, ref)),
              f"forced-continue: query {i} max |err| {err.max():.3g}")
        check(top_k_matches(top, ref, top_k),
              f"forced-continue: query {i} top-{top_k} differs")
    print(f"[forced-continue] oracle max_abs_err={worst:.3g} "
          f"(atol={ATOL} rtol={RTOL}), top-{top_k} identical in "
          f"{len(queries)} queries")
    return {"max_abs_err": worst}


def host_lear_features(q: np.ndarray, partial: np.ndarray) -> np.ndarray:
    """The four LEAR features (repro.core.features.augment_features) in
    numpy: partial, rank (0 = best, ties by index), min-max normalized
    partial, candidate count."""
    rank = np.empty(partial.size, np.float32)
    rank[np.lexsort((np.arange(partial.size), -partial))] = np.arange(
        partial.size
    )
    lo, hi = partial.min(), partial.max()
    norm = np.clip((partial - lo) / np.maximum(hi - lo, np.float32(1e-9)),
                   0.0, 1.0)
    count = np.full(partial.size, partial.size, np.float32)
    aug = np.stack([partial, rank, norm, count], axis=1).astype(np.float32)
    return np.concatenate([q, aug], axis=1)


def check_lear(ens, clf: LearClassifier, run: dict,
               queries: list[np.ndarray]) -> dict:
    head = slice_trees(ens, 0, clf.sentinel)
    n_docs = n_cont = ambiguous = 0
    for i, (q, (_top, scores)) in enumerate(zip(queries, run["results"])):
        partial = score_numpy_oracle(head, q)
        full = score_numpy_oracle(ens, q)
        logit = score_numpy_oracle(clf.forest, host_lear_features(q, partial))
        continued, exited = close(scores, full), close(scores, partial)
        check(np.all(continued | exited),
              f"LEAR: query {i} has scores that are neither the partial "
              f"nor the full oracle score")
        # Where partial and full coincide the decision is not observable.
        decided = continued ^ exited
        host_cont = logit >= 0.0           # sigmoid(logit) >= 0.5
        clear = np.abs(logit) > AMBIGUOUS_LOGIT
        check(np.all((continued == host_cont)[decided & clear]),
              f"LEAR: query {i} exit decisions differ from the host replay")
        n_docs += q.shape[0]
        n_cont += int(continued.sum())
        ambiguous += int((~clear).sum())
    print(f"[lear] host replay: {n_docs} docs, {n_cont} continued, "
          f"decisions match (ambiguous logits skipped: {ambiguous})")
    return {"docs": n_docs, "continued": n_cont, "ambiguous": ambiguous}


def kernel_rows(service: RankingService, n_features: int, Q: int, D: int,
                placement=None) -> list[int]:
    """Row count of each Mosaic kernel launch in the compiled serving step
    for a ``(Q, D)`` batch — per device, in a partitioned program."""
    X = jnp.zeros((Q, D, n_features), jnp.float32)
    mask = jnp.ones((Q, D), bool)
    compiled = service.lower_step(X, mask, placement).compile()
    return mosaic_kernel_rows(compiled.as_text())


class CacheEvents:
    """Persistent compilation cache hits and misses, process-wide."""

    def __init__(self) -> None:
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def one_chip(seed: int) -> None:
    cfg = lear_msn1.config()
    F = cfg.n_features
    ens, (clf_a, clf_b) = build_models(
        seed, cfg.n_trees, cfg.depth, F, cfg.classifier_trees
    )
    queries = make_queries(seed, N_QUERIES, F, *DOC_RANGE)
    auto = dict(execution_mode="auto", launch_overhead_trees="auto")

    forced = RankingService(
        ens, LearClassifier(clf_a, cfg.sentinel),
        ServiceConfig(threshold=0.0, **auto),
        extra_classifiers=[LearClassifier(clf_b, SECOND_SENTINEL)],
    )
    cal = last_calibration()
    print(f"[calibration] {json.dumps(cal, sort_keys=True)}")
    run = serve(forced, F, queries, DOC_BUCKETS)
    check_served("forced-continue", run, N_QUERIES)
    check_forced_continue(ens, run, queries, forced.top_k)

    lear = RankingService(
        ens, LearClassifier(clf_a, cfg.sentinel),
        ServiceConfig(threshold=LEAR_THRESHOLD, **auto),
    )
    run = serve(lear, F, queries, DOC_BUCKETS)
    check_served("lear", run, N_QUERIES)
    check_lear(ens, LearClassifier(clf_a, cfg.sentinel), run, queries)

    Q, D = BucketPolicy().max_queries, max(DOC_BUCKETS)
    rows = kernel_rows(lear, F, Q, D)
    print(f"[hlo] LEAR step ({Q}, {D}): {len(rows)} tpu_custom_call "
          f"launches, rows {rows}")
    check(len(rows) >= 3, "the serving step lacks its Mosaic kernels "
          "(head, classifier, tail)")


def four_chips(seed: int) -> None:
    cfg = lear_msn1.config()
    F = cfg.n_features
    n = 4
    check(len(jax.devices()) >= n, f"--four-chips needs {n} devices")
    ens, (clf_a, _) = build_models(
        seed, cfg.n_trees, cfg.depth, F, cfg.classifier_trees
    )
    queries = make_queries(seed, N_QUERIES, F, *DOC_RANGE)

    def service() -> RankingService:
        return RankingService(
            ens, LearClassifier(clf_a, cfg.sentinel),
            ServiceConfig(threshold=LEAR_THRESHOLD, execution_mode="auto",
                          launch_overhead_trees="auto"),
        )

    dp = data_parallel(n)
    sharded, one = service(), service()
    run_dp = serve(sharded, F, queries, DOC_BUCKETS, placement=dp)
    check_served(f"lear data_parallel({n})", run_dp, N_QUERIES)
    run_one = serve(one, F, queries, DOC_BUCKETS, placement=single_device())
    check_served("lear single_device()", run_one, N_QUERIES)

    bit_exact = True
    for i, (a, b) in enumerate(zip(run_dp["results"], run_one["results"])):
        check(np.array_equal(a[0], b[0]), f"query {i}: top-k differs")
        check(np.allclose(a[1], b[1], rtol=1e-6, atol=1e-6),
              f"query {i}: scores differ")
        bit_exact &= bool(np.array_equal(a[1], b[1]))
    print(f"[four-chips] data_parallel({n}) vs single_device(): top-k "
          f"identical in {N_QUERIES} queries, scores bit-exact={bit_exact}")

    Q, D = BucketPolicy().max_queries, max(DOC_BUCKETS)
    rows = kernel_rows(sharded, F, Q, D, placement=dp)
    print(f"[hlo] data_parallel({n}) step ({Q}, {D}): per-device kernel "
          f"rows {rows} (whole batch: {Q * D} rows)")
    check(len(rows) >= 3, "the sharded step lacks its Mosaic kernels")
    check(max(rows) == Q * D // n,
          "a device's head kernel does not see exactly its own query shard")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="data_parallel(4) vs single_device() only")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    print(f"[device] kind={dev.device_kind!r} count={len(devices)} "
          f"jax={jax.__version__}")
    cache_dir = enable_persistent_cache()
    events = CacheEvents()
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[cache] dir={cache_dir} hits={events.hits} "
          f"misses={events.misses} hit={events.hits > 0}")
    print(f"[total] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
