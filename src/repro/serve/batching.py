"""Continuous batching: many small concurrent queries → padded engine blocks.

The progressive engine wants big padded ``[Q, D, F]`` blocks (jit-stable
shapes, one fused device read per batch); real serving traffic is a stream
of single queries with ragged candidate counts. The
:class:`ContinuousBatcher` closes that gap:

- **Submit** is non-blocking: a query's features go into the pending set
  keyed by its *document bucket* (candidate count rounded up to a power of
  two, floored at ``BucketPolicy.min_docs``) and the caller gets a
  ``Future``.
- **One worker thread owns every engine call** — the RankingService's
  adaptive state (per-bucket peaks/EMA, jit step cache) is touched from
  exactly one thread, so the service itself needs no locks.
- **Flush policy**: a bucket flushes when it holds ``max_queries`` queries
  (full-bucket trigger — the batch the engine was sized for) or when its
  oldest request has waited ``max_wait_ms`` (deadline trigger — bounded
  p99 under trickle traffic). The worker sleeps on a condition variable
  with the earliest pending deadline as its timeout: no polling loop, no
  idle CPU burn.
- **Scatter-back**: the flushed block is padded to the next power-of-two
  query count (so the engine sees the same handful of shapes forever —
  these are exactly the buckets AOT warmup compiles), scored once, and
  each query's slice of the result is scattered back to its Future with a
  per-request top-k. The per-request top-k reproduces ``lax.top_k``'s
  tie-break (descending score, ascending index) so a batched response is
  *bit-exact* with submitting the same query alone.

Fault tolerance (see also :mod:`repro.serve.errors`):

- **Admission control**: ``BucketPolicy.max_queue_depth`` bounds the
  pending set; a submit against a full queue raises
  :class:`~repro.serve.errors.Overloaded` (counted in
  ``BatcherStats.shed_overload``) instead of growing the queue without
  limit.
- **Request deadlines**: ``submit(features, deadline_ms=…)`` gives the
  request an end-to-end budget. The flush schedule subtracts the
  *expected engine time* for the request's bucket (observed flush-time
  EMA, seeded from the startup calibration probe) so a deadlined request
  flushes early enough to make it; a request whose budget still expires
  in the queue is resolved to
  :class:`~repro.serve.errors.DeadlineExceeded` *before* the engine call
  — an already-dead request never wastes engine work.
- **Supervision**: the worker thread runs under a
  :class:`~repro.serve.supervisor.WorkerSupervisor` — a crash fails the
  in-flight bucket with :class:`~repro.serve.errors.WorkerCrashed`,
  queued requests survive, and the worker restarts with bounded backoff.
  Engine errors and per-request poison are contained inside
  :meth:`ContinuousBatcher._flush` (the bucket's — or the one request's —
  futures fail; the loop survives).
- **Degradation**: an optional
  :class:`~repro.serve.degradation.DegradationController` observes each
  flush's queue delay from the worker thread and steps the service
  through its pre-warmed exit rungs.

Padding rows carry ``mask=False`` everywhere, and the engine's masked
reductions make dead rows inert — which is what makes the bit-exactness
claim hold: scoring is per-document, the LEAR features are per-query
masked reductions, and compaction touches only alive documents, so a
query's scores do not depend on its neighbors in the block.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import typing
from collections.abc import Callable, Sequence
from concurrent.futures import Future

import numpy as np

import jax.numpy as jnp

from repro.kernels.forest_score import _next_pow2
from repro.serve.calibration import expected_engine_seconds
from repro.serve.clock import SYSTEM_CLOCK, Clock
from repro.serve.errors import (
    BatcherStopped,
    DeadlineExceeded,
    Overloaded,
    WorkerCrashed,
    WorkerFailed,
)
from repro.serve.ranking_service import RankingService
from repro.serve.supervisor import (
    STATE_NEW,
    SupervisorHealth,
    WorkerSupervisor,
)
from repro.serve.tracing import (
    FLUSH,
    PACK,
    PUT,
    SCATTER,
    STEP,
    WAIT,
    span,
)

if typing.TYPE_CHECKING:  # annotation-only: placement is constructed by
    from numpy.typing import ArrayLike  # the tier, never by the batcher

    from repro.serve.degradation import DegradationController
    from repro.serve.placement import ServePlacement

#: Smoothing for the per-bucket observed engine-seconds EMA that feeds
#: deadline-aware flush scheduling.
ENGINE_TIME_EMA_ALPHA = 0.3

#: Scheduling slack subtracted from a request's deadline when placing its
#: flush: condition-variable wakeups are not instant, and a flush timed at
#: exactly ``expires_at - engine_time`` would race its own expiry check.
FLUSH_SLACK_S = 5e-3


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """When to flush, which padded shapes exist, and how deep the queue goes.

    ``max_queries`` is both the full-bucket flush trigger and the largest
    padded Q; with power-of-two padding the engine sees at most
    ``log2(max_queries)+1`` query shapes per document bucket.
    ``max_queue_depth`` is the admission-control bound: a submit that
    would push the TOTAL pending count past it is rejected with
    :class:`~repro.serve.errors.Overloaded` (``None`` = unbounded, for
    offline/batch use only — a serving deployment should always bound it).
    """

    max_queries: int = 8
    max_wait_ms: float = 2.0
    min_docs: int = 8
    max_docs: int = 4096
    max_queue_depth: int | None = 1024

    def __post_init__(self) -> None:
        assert self.max_queries >= 1
        assert _next_pow2(self.max_queries) == self.max_queries, (
            "max_queries must be a power of two", self.max_queries
        )
        assert self.min_docs >= 1 and self.max_docs >= self.min_docs
        assert self.max_queue_depth is None or self.max_queue_depth >= 1, (
            self.max_queue_depth
        )

    def doc_bucket(self, n_docs: int) -> int:
        assert 1 <= n_docs <= self.max_docs, (n_docs, self.max_docs)
        return max(self.min_docs, _next_pow2(n_docs))

    def query_bucket(self, n_queries: int) -> int:
        return min(self.max_queries, _next_pow2(n_queries))

    def buckets(self, doc_counts: Sequence[int]) -> list[tuple[int, int]]:
        """The (Q, D) padded shapes this policy produces for the given doc
        counts — the warmup list: every query bucket up to ``max_queries``
        crossed with each distinct document bucket."""
        q = 1
        qs = []
        while q <= self.max_queries:
            qs.append(q)
            q *= 2
        ds = sorted({self.doc_bucket(d) for d in doc_counts})
        return [(q, d) for d in ds for q in qs]


@dataclasses.dataclass
class _Pending:
    features: np.ndarray   # [n_docs, F] f32
    n_docs: int
    future: Future
    flush_at: float        # clock time by which this request must flush
    expires_at: float      # end-to-end deadline (inf = none)
    deadline_ms: float     # as submitted (inf = none), for error messages
    enqueued_at: float     # clock time of submit, for queue-wait accounting


@dataclasses.dataclass
class BatcherStats:
    submitted: int = 0
    completed: int = 0
    failed: int = 0
    flushes_full: int = 0
    flushes_deadline: int = 0
    flushes_drain: int = 0
    padded_query_slots: int = 0   # dead rows shipped (padding overhead)
    max_queue_depth: int = 0      # high-water mark actually observed
    shed_overload: int = 0        # submits rejected by admission control
    shed_deadline: int = 0        # submits dead on arrival (budget <= 0)
    expired_deadline: int = 0     # requests that timed out in the queue
    worker_crashes: int = 0       # in-flight buckets lost to worker death
    queue_wait_s: float = 0.0     # submit → flush start, summed over the
    queue_waited: int = 0         #   requests handed to an engine call
    doc_slots: int = 0            # Q_bucket × D_bucket per engine call
    padded_doc_slots: int = 0     # slots of doc_slots with no real document

    @property
    def flushes(self) -> int:
        return self.flushes_full + self.flushes_deadline + self.flushes_drain

    @property
    def shed_rate(self) -> float:
        return self.shed_overload / max(self.submitted, 1)

    @property
    def deadline_miss_rate(self) -> float:
        return (
            self.shed_deadline + self.expired_deadline
        ) / max(self.submitted, 1)


@dataclasses.dataclass
class BatcherHooks:
    """Fault-injection seams, exercised by ``tests/faults.py``.

    ``on_flush(doc_bucket, n_reqs)`` runs on the worker thread after a
    bucket is popped but before the engine call; an exception here escapes
    the worker loop — i.e. it IS a worker crash, handled by the
    supervisor. ``on_result(future)`` runs per request during
    scatter-back; an exception poisons only that request (its future
    fails, its bucket-mates complete).
    """

    on_flush: Callable[[int, int], None] | None = None
    on_result: Callable[[Future], None] | None = None


class ContinuousBatcher:
    """Packs concurrent single-query submissions into engine-sized blocks.

    Lifecycle: ``start()`` → any number of ``submit()`` (thread-safe, from
    any thread) → ``stop()`` (drains pending requests, then joins the
    worker). ``submit`` after ``stop`` raises
    :class:`~repro.serve.errors.BatcherStopped`; the stop/submit handoff
    is atomic under the condition lock, so a submit either lands before
    the drain snapshot (and is served) or raises — never silently lost.
    """

    def __init__(
        self,
        service: RankingService,
        n_features: int,
        policy: BucketPolicy | None = None,
        placement: ServePlacement | None = None,
        *,
        clock: Clock | None = None,
        hooks: BatcherHooks | None = None,
        degradation: DegradationController | None = None,
        max_restarts: int = 5,
        backoff_base_s: float = 0.05,
        backoff_max_s: float = 2.0,
    ) -> None:
        self.service = service
        self.n_features = int(n_features)
        self.policy = policy or BucketPolicy()
        self.placement = placement
        self.hooks = hooks
        self.degradation = degradation
        self.stats = BatcherStats()
        self.max_restarts = int(max_restarts)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self._clock = clock or SYSTEM_CLOCK
        self._pending: dict[int, list[_Pending]] = {}
        self._inflight: list[_Pending] = []
        self._cond = threading.Condition()
        self._running = False
        self._failed = False
        self._supervisor: WorkerSupervisor | None = None
        self._last_sup_health: SupervisorHealth | None = None
        self._flush_seq = 0  # numbers each flush's spans in a trace
        self._engine_s_ema: dict[int, float] = {}

    # -- client side ------------------------------------------------------

    def start(self) -> None:
        assert self._supervisor is None, "batcher already started"
        with self._cond:
            self._running = True
            self._failed = False
        self._supervisor = WorkerSupervisor(
            self._run,
            name="repro-batcher",
            backoff_base_s=self.backoff_base_s,
            backoff_max_s=self.backoff_max_s,
            max_restarts=self.max_restarts,
            clock=self._clock,
            on_crash=self._on_worker_crash,
            on_failed=self._on_worker_failed,
        )
        self._supervisor.start()

    def submit(
        self, features: ArrayLike, deadline_ms: float | None = None
    ) -> Future:
        """Enqueue one query's ``[n_docs, F]`` candidate features; returns a
        Future resolving to ``(top_idx [k], scores [n_docs])``.

        ``deadline_ms`` is the request's END-TO-END budget from this call:
        the batcher schedules the flush early enough to cover the expected
        engine time, and resolves the future to
        :class:`~repro.serve.errors.DeadlineExceeded` if the budget
        expires while queued (a non-positive budget is dead on arrival —
        resolved immediately, never enqueued). Raises
        :class:`~repro.serve.errors.Overloaded` when the queue is at
        ``max_queue_depth`` and :class:`~repro.serve.errors.BatcherStopped`
        after (or racing) ``stop()``.
        """
        feats = np.asarray(features, np.float32)
        assert feats.ndim == 2 and feats.shape[1] == self.n_features, (
            feats.shape, self.n_features
        )
        n_docs = feats.shape[0]
        db = self.policy.doc_bucket(n_docs)
        fut: Future = Future()
        now = self._clock.now()
        with self._cond:
            if self._failed:
                raise WorkerFailed(
                    "serving worker exhausted its restart budget"
                )
            if not self._running:
                raise BatcherStopped("batcher is not running")
            self.stats.submitted += 1
            if deadline_ms is not None and deadline_ms <= 0.0:
                # Dead on arrival: resolve without ever queueing — the
                # engine must not be asked to score an expired request.
                self.stats.shed_deadline += 1
                self.stats.failed += 1
                fut.set_exception(DeadlineExceeded(float(deadline_ms), 0.0))
                return fut
            depth = sum(len(v) for v in self._pending.values())
            limit = self.policy.max_queue_depth
            if limit is not None and depth >= limit:
                self.stats.shed_overload += 1
                raise Overloaded(depth, limit)
            flush_at = now + self.policy.max_wait_ms / 1e3
            expires_at = math.inf
            if deadline_ms is not None:
                expires_at = now + float(deadline_ms) / 1e3
                # Flush early enough that the engine call itself fits in
                # the remaining budget (estimated from the calibrated
                # cost model / observed flush times, plus wakeup slack),
                # clamped at "now" — an already-tight request flushes as
                # soon as possible.
                budget = self._engine_seconds_estimate(db) + FLUSH_SLACK_S
                flush_at = min(flush_at, max(now, expires_at - budget))
            req = _Pending(
                features=feats,
                n_docs=n_docs,
                future=fut,
                flush_at=flush_at,
                expires_at=expires_at,
                deadline_ms=(
                    math.inf if deadline_ms is None else float(deadline_ms)
                ),
                enqueued_at=now,
            )
            self._pending.setdefault(db, []).append(req)
            self.stats.max_queue_depth = max(
                self.stats.max_queue_depth, depth + 1
            )
            self._cond.notify()
        return fut

    def stop(self) -> None:
        """Drain everything still queued, then stop the worker.

        The handoff is atomic: under the condition lock the batcher flips
        to not-running AND snapshots the pending map, so a concurrent
        ``submit`` either landed in the snapshot (and is drained below) or
        observes not-running and raises — no request can slip into a dict
        nobody will ever flush."""
        with self._cond:
            self._running = False
            drain, self._pending = self._pending, {}
            self._cond.notify_all()
        if self._supervisor is not None:
            self._supervisor.stop()
            self._last_sup_health = self._supervisor.health()
            self._supervisor = None
        # Whatever the worker left behind (requests that arrived in its
        # final instants) flushes on the caller's thread — in engine-sized
        # chunks: a drained bucket can hold MORE than max_queries (the
        # worker never popped it), and a flush must never exceed the
        # padded block it allocates.
        step = self.policy.max_queries
        for db, reqs in sorted(drain.items()):
            for i in range(0, len(reqs), step):
                self.stats.flushes_drain += 1
                self._flush(db, reqs[i:i + step], "drain")

    def health(self) -> dict:
        """Liveness snapshot: supervisor state, queue depth, and
        ``queue_wait_ms``, the mean time from submit to the start of its
        flush over every request handed to an engine call so far."""
        sup = (
            self._supervisor.health()
            if self._supervisor is not None
            else self._last_sup_health or SupervisorHealth(STATE_NEW, 0, 0, None)
        )
        with self._cond:
            depth = sum(len(v) for v in self._pending.values())
        wait_s, waited = self.stats.queue_wait_s, self.stats.queue_waited
        return {
            "state": sup.state,
            "restarts": sup.restarts,
            "crashes": sup.crashes,
            "last_error": sup.last_error,
            "queue_depth": depth,
            "queue_wait_ms": 1e3 * wait_s / waited if waited else 0.0,
        }

    # -- supervision callbacks (guard thread) -----------------------------

    def _on_worker_crash(self, exc: BaseException) -> None:
        """Worker died mid-bucket: fail exactly the in-flight requests.
        Queued requests stay queued and are served after the restart."""
        with self._cond:
            inflight, self._inflight = self._inflight, []
            self.stats.worker_crashes += 1
        err = WorkerCrashed(f"serving worker died: {exc!r}")
        err.__cause__ = exc
        for r in inflight:
            self._fail(r, err)

    def _on_worker_failed(self, exc: BaseException) -> None:
        """Supervisor gave up: nothing will ever drain the queue, so fail
        every pending and in-flight future and refuse new submits."""
        with self._cond:
            self._failed = True
            pending, self._pending = self._pending, {}
            inflight, self._inflight = self._inflight, []
            self._cond.notify_all()
        err = WorkerFailed(f"serving worker restart budget exhausted: {exc!r}")
        err.__cause__ = exc
        for reqs in pending.values():
            for r in reqs:
                self._fail(r, err)
        for r in inflight:
            self._fail(r, err)

    # -- worker side ------------------------------------------------------

    def _engine_seconds_estimate(self, db: int) -> float:
        """Expected wall time of one engine flush at doc bucket ``db``:
        the observed per-bucket EMA once traffic exists, else the
        calibration probe's prior (0 when neither is available)."""
        ema = self._engine_s_ema.get(db)
        if ema is not None:
            return ema
        ensemble = getattr(self.service, "ensemble", None)
        if ensemble is None:
            return 0.0
        return expected_engine_seconds(
            self.policy.max_queries * db, ensemble.n_trees
        )

    def _take_ready(
        self, now: float
    ) -> tuple[int | None, list[_Pending] | None, str | None, float | None]:
        """Pop the bucket to flush now, with its trigger, or the earliest
        future flush time. Full buckets beat timer flushes (they amortize
        best); among timer-ripe buckets the most urgent request wins."""
        for db, reqs in sorted(self._pending.items()):
            if len(reqs) >= self.policy.max_queries:
                self._pending[db] = reqs[self.policy.max_queries:]
                return db, reqs[: self.policy.max_queries], "full", None
        ripe_db, ripe_t = None, None
        for db, reqs in self._pending.items():
            if not reqs:
                continue
            t = min(r.flush_at for r in reqs)
            if ripe_t is None or t < ripe_t:
                ripe_db, ripe_t = db, t
        if ripe_t is not None and ripe_t <= now:
            reqs = self._pending.pop(ripe_db)
            return ripe_db, reqs, "deadline", None
        return None, None, None, ripe_t

    def _run(self) -> None:
        while True:
            with self._cond, span(WAIT):
                db = reqs = None
                while True:
                    now = self._clock.now()
                    db, reqs, trigger, next_t = self._take_ready(now)
                    if reqs is not None:
                        break
                    if not self._running:
                        return  # leftovers flush in stop()
                    self._clock.wait(
                        self._cond,
                        None if next_t is None else max(next_t - now, 0.0),
                    )
                self._inflight = reqs
                queue_delay = now - min(r.enqueued_at for r in reqs)
            if trigger == "full":
                self.stats.flushes_full += 1
            else:
                self.stats.flushes_deadline += 1
            if self.degradation is not None:
                # Worker thread: the only place allowed to step the
                # service through its pre-warmed degradation rungs.
                self.degradation.observe(queue_delay)
            hooks = self.hooks
            if hooks is not None and hooks.on_flush is not None:
                # Outside _flush's containment on purpose: an injected
                # failure here IS a worker crash (supervisor territory).
                hooks.on_flush(db, len(reqs))
            t0 = self._clock.now()
            self._flush(db, reqs, trigger)
            elapsed = self._clock.now() - t0
            with self._cond:
                self._inflight = []
                a = ENGINE_TIME_EMA_ALPHA
                prev = self._engine_s_ema.get(db)
                self._engine_s_ema[db] = (
                    elapsed if prev is None else (1 - a) * prev + a * elapsed
                )

    def _flush(
        self, db: int, reqs: list[_Pending], trigger: str = "drain"
    ) -> None:
        """Score one padded block and scatter per-query results back.

        ``trigger`` (``full``, ``deadline`` or ``drain``) only labels the
        flush's ``lear.flush`` span.

        Failure containment, tightest scope first: an expired request is
        resolved without engine work; a request that cannot even be packed
        fails alone (its block row stays masked dead — inert to the
        engine); an engine error fails this bucket's futures but returns
        normally (the worker loop survives); a per-request scatter error
        (injected poison, cancelled future) fails that request alone.
        Anything escaping this method is a worker crash for the
        supervisor.
        """
        self._flush_seq += 1
        with span(FLUSH, bucket=db, queries=len(reqs), trigger=trigger,
                  seq=self._flush_seq):
            with span(PACK):
                block = self._pack(db, reqs)
            if block is None:
                return  # the whole bucket died in the queue: no launch
            live, X, mask = block
            try:
                with span(PUT):
                    X, mask = jnp.asarray(X), jnp.asarray(mask)
                with span(STEP):
                    _, scores = self.service.rank_batch(
                        X, mask, placement=self.placement
                    )
            except Exception as e:
                # Engine failure: this bucket's futures must not hang, and
                # the worker loop must survive to serve the next bucket.
                self._fail_live(live, e)
                return
            with span(SCATTER):
                self._scatter(live, scores)

    def _pack(
        self, db: int, reqs: list[_Pending]
    ) -> tuple[list[_Pending | None], np.ndarray, np.ndarray] | None:
        """Expire dead requests and pack the rest into a padded block:
        ``(live, X [qb, db, F], mask [qb, db])``, or ``None`` when no
        request is left. Counts each packed request's queue wait and the
        block's document slots."""
        now = self._clock.now()
        live: list[_Pending | None] = []
        for r in reqs:
            if r.expires_at <= now:
                self._expire(r, now)
            else:
                live.append(r)
        if not live:
            return None
        qb = self.policy.query_bucket(len(live))
        X = np.zeros((qb, db, self.n_features), np.float32)
        mask = np.zeros((qb, db), bool)
        for i, r in enumerate(live):
            try:
                X[i, : r.n_docs] = r.features
                mask[i, : r.n_docs] = True
            except Exception as e:
                # A malformed request fails alone; its dead row is inert.
                mask[i] = False
                self._fail(r, e)
                live[i] = None
        docs = 0
        for r in live:
            if r is not None:
                self.stats.queue_wait_s += now - r.enqueued_at
                self.stats.queue_waited += 1
                docs += r.n_docs
        self.stats.padded_query_slots += qb - len(live)
        self.stats.doc_slots += qb * db
        self.stats.padded_doc_slots += qb * db - docs
        return live, X, mask

    def _scatter(self, live: list[_Pending | None], scores: object) -> None:
        """Each live request's scores and top-k back to its future."""
        try:
            scores = np.asarray(scores)
        except Exception as e:
            self._fail_live(live, e)
            return
        hooks = self.hooks
        for i, r in enumerate(live):
            if r is None:
                continue
            try:
                if hooks is not None and hooks.on_result is not None:
                    hooks.on_result(r.future)
                s = scores[i, : r.n_docs].copy()
                k = min(self.service.top_k, r.n_docs)
                # lax.top_k order: descending score, ascending index.
                top = np.lexsort((np.arange(r.n_docs), -s))[:k]
                r.future.set_result((top.astype(np.int32), s))
                self.stats.completed += 1
            except Exception as e:
                # Poisoned scatter: one request fails, bucket-mates don't.
                self._fail(r, e)

    # -- resolution helpers -----------------------------------------------

    def _fail_live(
        self, live: list[_Pending | None], exc: BaseException
    ) -> None:
        for r in live:
            if r is not None:
                self._fail(r, exc)

    def _fail(self, r: _Pending, exc: BaseException) -> None:
        if not r.future.done():
            r.future.set_exception(exc)
            self.stats.failed += 1

    def _expire(self, r: _Pending, now: float) -> None:
        self.stats.expired_deadline += 1
        self._fail(
            r,
            DeadlineExceeded(r.deadline_ms, (now - r.enqueued_at) * 1e3),
        )
