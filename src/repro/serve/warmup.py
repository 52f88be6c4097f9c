"""AOT warmup: pay every compile before the first real request arrives.

Two cold-start costs stand between service start and steady-state latency:

1. **Tracing/compilation** — the progressive engine jits one step per
   static configuration (batch shape, capacities, mode). The first request
   at a new capacity bucket would eat that compile. :func:`warmup_service`
   drives one synthetic batch through every configured ``(Q, D)`` bucket so
   the step cache is hot; with ``execution_mode="auto"`` both ``lax.cond``
   branches are part of that single compiled step (the pick is a traced
   operand), and the warmup additionally *executes* both branches by
   seeding the survivor EMA at its two extremes.

2. **Capacity re-bucketing** — the compaction-capacity ratchet normally
   learns survivor peaks from traffic, which means batch 1 runs at the
   cold-start estimate and can both overflow (quality loss) and trigger a
   re-jit when the ratchet moves. Warmup seeds each bucket's peaks at
   ``seed_peak_frac × Q × D`` *before* the first trace: with the default
   ``1.0`` the capacities start at the physical maximum (every document
   survives), which cannot overflow and can only ratchet *down* never —
   the running max keeps them pinned, so the bucket compiles exactly once.

Across process restarts the same trace is a cache hit on disk:
:func:`enable_persistent_cache` points jax's persistent compilation cache
at ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at
:data:`DEFAULT_CACHE_DIR` inside the checkout, so restart warmup replays
compiled artifacts instead of re-invoking XLA.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import time
import typing
from collections.abc import Sequence

import jax
import jax.numpy as jnp

from repro.serve.ranking_service import RankingService, ServiceStats

if typing.TYPE_CHECKING:  # annotation-only: avoids a serve-package cycle
    from repro.serve.placement import ServePlacement

DEFAULT_WARMUP_BUCKETS = ((1, 64), (4, 64), (8, 64))

#: Compile-cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed, git-ignored directory inside the checkout — a cache only hits
#: again at the same path.
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
)


def enable_persistent_cache() -> str:
    """Point jax's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else at :data:`DEFAULT_CACHE_DIR`
    (created if needed), and return that directory. Thresholds are dropped
    to "cache everything" — serving steps are small but latency-critical.
    A directory that cannot be created raises: a service that silently
    ran with the cache off would pay every compile on each restart."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


@dataclasses.dataclass
class WarmupReport:
    buckets: list[tuple[int, int]]
    seconds_per_bucket: dict[tuple[int, int], float]
    cache_dir: str | None = None
    rungs_warmed: int = 1   # degradation rungs compiled per bucket

    @property
    def total_seconds(self) -> float:
        return sum(self.seconds_per_bucket.values())


def warmup_service(
    service: RankingService,
    n_features: int,
    buckets: Sequence[tuple[int, int]] = DEFAULT_WARMUP_BUCKETS,
    *,
    seed_peak_frac: float = 1.0,
    run_both_branches: bool = True,
    warm_rungs: bool = True,
    placement: ServePlacement | None = None,
) -> WarmupReport:
    """Compile (and execute) every ``(Q, D)`` serving bucket up front.

    For each bucket: seed the per-bucket survivor peaks (stable capacities
    → exactly one trace, zero cold-start overflow), then run one synthetic
    batch. With mode ``"auto"`` and ``run_both_branches``, run a second
    batch with the EMA forced to the opposite extreme so both ``lax.cond``
    branches have executed, not just compiled. Afterwards the warmup's
    fingerprints are wiped — stats reset, EMAs cleared (real traffic
    starts with the honest cold-start fused default) — but the seeded
    peaks are KEPT: they are the no-overflow guarantee.

    Stage counts come from ``service.n_stages`` (ALL stages, dense gate
    included): a hybrid service's peaks/EMA carry the leading dense
    entry, and because the dense matmul is traced into the same jitted
    step as the tree launches, this one synthetic batch AOT-compiles the
    dense branch too — no separate dense warmup pass exists or is needed.

    With ``warm_rungs`` (default) and a degradation ladder installed
    (:meth:`RankingService.install_rungs`), every rung's step is compiled
    for every bucket — each rung's strategy closures / query-exit config
    are part of the engine's static cache key, so each is its own
    compile. This is what makes degrading under load jit-free: stepping
    the ladder at peak traffic swaps to a step that warmup already paid
    for. The service is left back at rung 0 (baseline).
    """
    n_stages = service.n_stages
    rung_levels: list[int | None] = [None]
    if warm_rungs and service.n_rungs > 1:
        rung_levels = list(range(service.n_rungs))
    report = WarmupReport(
        buckets=[], seconds_per_bucket={}, rungs_warmed=len(rung_levels)
    )
    for Q, D in buckets:
        t0 = time.perf_counter()
        state = service.bucket_state(Q, D)
        if state.peaks is None:
            seed = max(1, min(int(seed_peak_frac * Q * D), Q * D))
            state.peaks = [seed] * n_stages
        X = jnp.zeros((Q, D, n_features), jnp.float32)
        mask = jnp.ones((Q, D), bool)
        # Extreme EMAs steer the device pick to each branch in turn (the
        # cost model prices zero survivors as maximally staged-friendly
        # and full survival as fused-friendly).
        ema_probes = [[0.0] * n_stages]
        if (
            run_both_branches
            and service.execution_mode == "auto"
            and len(service.sentinels) > 1
        ):
            ema_probes.append([float(Q * D)] * n_stages)
        for level in rung_levels:
            if level is not None:
                service.set_rung(level)
            for ema in ema_probes:
                state.ema = ema
                service.rank_batch(X, mask, placement=placement)
        state.ema = None  # real traffic re-learns its own continue rates
        report.buckets.append((Q, D))
        report.seconds_per_bucket[(Q, D)] = time.perf_counter() - t0
    if rung_levels[-1] is not None:
        service.set_rung(0)  # hand real traffic the baseline rung
    # Warmup batches are not traffic: stats restart clean.
    service.stats = ServiceStats()
    return report
