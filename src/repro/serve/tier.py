"""The serving tier: service + placement + AOT warmup + continuous batcher.

:class:`ServingTier` is the deployable unit — what `examples/` and the
load-generator bench (benchmarks/bench_serve.py) stand up:

    tier = ServingTier(service, n_features=F, doc_counts=(64, 256))
    tier.start()                 # persistent cache + AOT warmup + batcher
    fut = tier.submit(features)  # non-blocking, one query
    top_idx, scores = fut.result()
    tier.stop()

``start()`` does the three cold-start moves in order: point jax at the
persistent compilation cache (restarts replay compiled artifacts from
disk), AOT-warm every padded ``(Q, D)`` bucket the batching policy can
produce for the configured ``doc_counts`` (both execution branches), and
only then open the request queue — the first real request lands on a hot
step cache with capacity buckets seeded so cold-start overflow is
impossible.
"""

from __future__ import annotations

import dataclasses
import typing
import warnings
from collections.abc import Sequence
from concurrent.futures import Future

if typing.TYPE_CHECKING:
    import numpy as np
    from numpy.typing import ArrayLike

from repro.serve.batching import (
    BatcherHooks,
    BatcherStats,
    BucketPolicy,
    ContinuousBatcher,
)
from repro.serve.clock import Clock
from repro.serve.degradation import DegradationController, DegradationPolicy
from repro.serve.placement import ServePlacement, single_device
from repro.serve.ranking_service import RankingService
from repro.serve.warmup import (
    WarmupReport,
    enable_persistent_cache,
    warmup_service,
)

_DEPRECATED_TIER_MSG = (
    "repro.serve.tier.ServingTier: keyword configuration (doc_counts=…, "
    "warmup=…, …) is deprecated; pass a TierConfig as the third argument. "
    "The shim builds the equivalent config and will be removed in a "
    "future release."
)


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Frozen bundle of the :class:`ServingTier` deployment knobs.

    The tier-level mirror of :class:`repro.serve.ranking_service.ServiceConfig`
    — what to warm, whether to warm, and whether compiled artifacts persist
    (where: :func:`repro.serve.warmup.enable_persistent_cache`).
    ``policy`` and ``placement`` stay direct constructor arguments: they
    are live objects (thread-owning batcher policy, device mesh), not
    declarative configuration.
    """

    doc_counts: tuple[int, ...] = (64,)
    warmup: bool = True
    persistent_cache: bool = True
    degradation: DegradationPolicy | None = None

    def __post_init__(self) -> None:
        object.__setattr__(
            self, "doc_counts", tuple(int(d) for d in self.doc_counts)
        )
        assert len(self.doc_counts) >= 1, "need at least one doc count"
        assert self.degradation is None or isinstance(
            self.degradation, DegradationPolicy
        )


class ServingTier:
    def __init__(
        self,
        service: RankingService,
        n_features: int,
        config: TierConfig | None = None,
        policy: BucketPolicy | None = None,
        placement: ServePlacement | None = None,
        *,
        clock: Clock | None = None,
        hooks: BatcherHooks | None = None,
        doc_counts: Sequence[int] | None = None,
        warmup: bool | None = None,
        persistent_cache: bool | None = None,
    ) -> None:
        if config is not None and not isinstance(config, TierConfig):
            # Legacy POSITIONAL call: ServingTier(svc, F, (64, 256), …)
            assert doc_counts is None, (config, doc_counts)
            config, doc_counts = None, tuple(config)
        legacy = {
            name: value
            for name, value in (
                ("doc_counts", doc_counts), ("warmup", warmup),
                ("persistent_cache", persistent_cache),
            )
            if value is not None
        }
        if config is None:
            if legacy:
                warnings.warn(
                    _DEPRECATED_TIER_MSG, DeprecationWarning, stacklevel=2
                )
            config = TierConfig(**legacy)
        elif legacy:
            raise TypeError(
                "ServingTier: pass configuration via TierConfig OR the "
                f"deprecated keywords, not both (got {sorted(legacy)})"
            )
        self.config = config
        self.service = service
        self.n_features = int(n_features)
        self.policy = policy or BucketPolicy()
        self.placement = placement or single_device()
        self.doc_counts = config.doc_counts
        self.do_warmup = config.warmup
        self.persistent_cache = config.persistent_cache
        self.warmup_report: WarmupReport | None = None
        self.degradation = (
            DegradationController(service, config.degradation, clock=clock)
            if config.degradation is not None else None
        )
        self.batcher = ContinuousBatcher(
            service, self.n_features, self.policy,
            placement=self.placement, clock=clock, hooks=hooks,
            degradation=self.degradation,
        )
        self._started = False

    def start(self) -> ServingTier:
        assert not self._started, "tier already started"
        cache_dir = enable_persistent_cache() if self.persistent_cache else None
        if self.degradation is not None and self.service.n_rungs == 0:
            # Materialize every rung BEFORE warmup so the warmup pass
            # below AOT-compiles the whole ladder — degrading at peak
            # load must never trigger a jit.
            self.degradation.install()
        if self.do_warmup:
            self.warmup_report = warmup_service(
                self.service,
                self.n_features,
                self.policy.buckets(self.doc_counts),
                placement=self.placement,
            )
            self.warmup_report.cache_dir = cache_dir
        self.batcher.start()
        self._started = True
        return self

    def submit(
        self, features: ArrayLike, deadline_ms: float | None = None
    ) -> Future:
        """Non-blocking: one query's ``[n_docs, F]`` candidates → Future of
        ``(top_idx, scores)``. ``deadline_ms`` is the request's end-to-end
        budget (see :meth:`repro.serve.batching.ContinuousBatcher.submit`
        for the typed rejection/expiry behavior)."""
        return self.batcher.submit(features, deadline_ms=deadline_ms)

    def rank(self, features: ArrayLike) -> tuple[np.ndarray, np.ndarray]:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(features).result()

    def stop(self) -> None:
        if self._started:
            self.batcher.stop()
            self._started = False

    def stats(self) -> dict:
        """Operator snapshot: batcher counters + service aggregates."""
        svc, b = self.service.stats, self.batcher.stats
        return {
            "batcher": {
                f.name: getattr(b, f.name)
                for f in BatcherStats.__dataclass_fields__.values()
            },
            "service": {
                "batches": svc.batches,
                "queries": svc.queries,
                "docs": svc.docs,
                "overflow_docs": svc.overflow_docs,
                "speedup": svc.speedup,
                "continue_rate": svc.continue_rate,
                "batches_fused": svc.batches_fused,
                "batches_staged": svc.batches_staged,
                "queries_exited": svc.queries_exited,
                "query_exit_rate": svc.query_exit_rate,
                "tail_rows": svc.tail_rows,
                "dispatch_s": svc.dispatch_s,
                "post_s": svc.post_s,
                "read_s": svc.read_s,
            },
            "warmup_seconds": (
                self.warmup_report.total_seconds if self.warmup_report else 0.0
            ),
            "n_devices": self.placement.n_devices,
        }

    def health(self) -> dict:
        """Liveness snapshot for operators and load balancers: supervisor
        state (``running``/``backoff``/``failed``/…), restart and crash
        counts, current queue depth, ``queue_wait_ms`` (mean time from
        submit to the start of its flush, over every request handed to an
        engine call), and — when a degradation ladder is configured — the
        current rung and its queue-delay EMA."""
        h = self.batcher.health()
        h["started"] = self._started
        if self.degradation is not None:
            h["degradation"] = self.degradation.snapshot()
        return h
