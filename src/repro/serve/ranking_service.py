"""Batched ranking service with the LEAR cascade as a first-class feature.

The serving path the paper targets: a query arrives with its candidate
documents (already feature-extracted); the service scores them through the
λ-MART ensemble with document-level early exit and returns the top-k.

Production concerns handled here:
- request batching into fixed-size padded blocks (jit-stable shapes);
- the multi-sentinel progressive engine
  (:meth:`repro.core.cascade.CascadeRanker.rank_progressive`), end-to-end
  jitted — all three forests in the path (ranker head, LEAR classifier,
  ranker tail) go through the same Pallas kernel inside ONE XLA
  computation per batch, and the LEAR augmented features (sort-free
  per-query rank, min/max segment reductions — :mod:`repro.core.features`)
  are built on device between the head launch and the classifier launch;
- adaptive execution mode, picked ON DEVICE: the compiled step contains
  both the fused segmented head and the per-stage-tail branch under a
  ``lax.cond``, and
  :func:`repro.metrics.speedup.progressive_cost_model_device` prices them
  from the smoothed survivor counts (shipped as a tiny operand at submit
  time) — no host round trip and no batch-boundary decision lag; the
  host-side :func:`repro.metrics.speedup.progressive_cost_model` pick is
  kept as the reference the device pick must agree with
  (:meth:`RankingService._pick_mode`);
- a calibrated cost model: ``launch_overhead_trees="auto"`` (the default)
  measures dispatch latency at service startup
  (:func:`repro.serve.calibration.calibrate_launch_overhead_trees`,
  cached per process) instead of trusting a fixed constant;
- compaction capacity from a running per-stage survivor peak with
  headroom, never below the cold-start estimate, bucketed to powers of
  two so re-jits stay bounded;
- cost accounting per batch (trees traversed, the paper's own metric) and
  service-level stats, computed inside the same compiled step as the
  response — the ENTIRE host read (top-k response, scores, per-stage
  survivors, cost, overflow, batch doc count, picked branch) is ONE fused
  ``jax.device_get`` of the step's outputs: one engine call is one
  program and one read, and between batch submit and that read the hot
  path performs zero device→host transfers (guarded by
  :func:`repro.utils.count_host_transfers` in the tests);
- graceful degradation: if survivors exceed capacity, the overflow
  documents keep their sentinel scores (bounded quality loss, never a
  crash) and the stats record it.

The same class serves the beyond-paper cascade for recsys retrieval
(sentinel scorer = any cheap model, full scorer = any expensive model) via
the ``sentinel_fn`` / ``full_fn`` hooks — see examples/cascade_retrieval.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import typing
import warnings
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp

if typing.TYPE_CHECKING:  # annotation-only: avoids a serve-package cycle
    import numpy as np

    from repro.serve.degradation import ExitRung
    from repro.serve.placement import ServePlacement

from repro.core.cascade import (
    SCOPE_CLASSIFIER,
    SCOPE_FEATURES,
    CascadeRanker,
    bucket_capacity,
)
from repro.core.lear import LearClassifier, augment_features
from repro.core.stage import DenseStage, EngineConfig, TreeStage
from repro.core.strategies import QueryExitConfig, dense_keep_fraction
from repro.forest.ensemble import TreeEnsemble
from repro.kernels.ops import (
    ENGINE_BLOCK_B,
    launch_rows,
    resolve_leaf_gather,
    segment_tree_slots,
)
from repro.metrics.speedup import progressive_cost_model
from repro.serve.calibration import calibrate_launch_overhead_trees
from repro.serve.tracing import ADAPT, DISPATCH, POST, READ, span

_DEPRECATED_SERVICE_MSG = (
    "repro.serve.ranking_service.RankingService: keyword configuration "
    "(threshold=…, execution_mode=…, …) is deprecated; pass a "
    "ServiceConfig as the third argument. The shim builds the equivalent "
    "config and will be removed in a future release."
)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Frozen bundle of every :class:`RankingService` tuning knob.

    The serving mirror of :class:`repro.core.stage.EngineConfig`: one
    hashable value instead of nine constructor keywords. The model inputs
    (ensemble, classifiers) stay direct constructor arguments — they are
    the *data* being served, not its configuration.

    ``dense_stage`` (a :class:`repro.core.stage.DenseStage`) turns the
    service into the HYBRID cascade: the dense gate becomes stage 0 of
    every compiled step, survivor adaptation (peaks/EMA/capacities) grows
    a leading dense entry, and accounting charges ``dense.cost_trees``
    per candidate instead of tree traversals for dense-exited documents.
    Set ``dense_stage.capacity`` to pin the dense survivor block;
    ``None`` lets the per-bucket ratchet manage it like any tree stage.
    """

    threshold: float = 0.5
    capacity_headroom: float = 1.25
    top_k: int = 10
    use_kernel_classifier: bool = True
    execution_mode: str = "auto"
    launch_overhead_trees: float | str = "auto"
    survivor_ema: float = 0.3
    query_exit: QueryExitConfig | None = None
    dense_stage: DenseStage | None = None

    def __post_init__(self) -> None:
        assert self.execution_mode in ("auto", "fused", "staged"), (
            self.execution_mode
        )
        # The capacity ratchet needs strictly-positive headroom: in staged
        # mode observed survivor peaks are clipped AT the current bucket (a
        # power of two), so only peak × headroom > bucket can round up to
        # the next bucket — with headroom <= 1 capacity would never grow
        # and an undersized stage would silently overflow forever.
        assert self.capacity_headroom > 1.0, self.capacity_headroom
        assert self.top_k >= 1, self.top_k
        assert 0.0 < self.survivor_ema <= 1.0, self.survivor_ema
        assert self.query_exit is None or isinstance(
            self.query_exit, QueryExitConfig
        )
        assert self.dense_stage is None or isinstance(
            self.dense_stage, DenseStage
        )


@dataclasses.dataclass
class _BucketAdaptState:
    """Adaptive state for ONE padded batch shape ``(Q, D)``.

    The serving tier packs traffic into power-of-two capacity buckets
    (:mod:`repro.serve.batching`); survivor behavior is a function of the
    batch shape (D bounds the survivor count, Q·D scales the head work), so
    both adaptation signals live per bucket: the running survivor ``peaks``
    drive that bucket's compaction-capacity ratchet and the smoothed
    survivor ``ema`` feeds that bucket's fused-vs-staged pick. A sparse
    Q=1 trickle must not shrink (or mis-mode) the Q=64 bulk bucket.
    """

    peaks: list[int] | None = None  # running max survivors per stage
    ema: list[float] | None = None  # smoothed survivors per stage
    tail_skip: float | None = None  # smoothed P(batch skipped the gated
    #   tail launch) — feeds the cost model's query_exit_rate discount


@dataclasses.dataclass(frozen=True)
class _RungState:
    """One installed degradation rung, fully materialized.

    Everything a rung changes is pre-built at install time — strategy
    closures with the rung's threshold baked in, a rung-specific
    :class:`DenseStage` when the dense keep fraction changes — so
    :meth:`RankingService.set_rung` is a pure pointer swap: the same
    closure objects every time (they hash by identity) means every rung
    maps to ONE stable :class:`EngineConfig` and therefore ONE compiled
    step, warmed once by :func:`repro.serve.warmup.warmup_service`.
    """

    name: str
    threshold: float
    strategies: tuple[Callable[..., jax.Array], ...]
    query_exit: QueryExitConfig | None
    dense_stage: DenseStage | None


@dataclasses.dataclass
class ServiceStats:
    batches: int = 0
    queries: int = 0
    docs: int = 0
    docs_continued: int = 0
    overflow_docs: int = 0
    trees_traversed: float = 0.0
    trees_full_equiv: float = 0.0
    batches_fused: int = 0
    batches_staged: int = 0
    queries_exited: int = 0  # query-level exit fired (query_exit enabled)
    tail_rows: int = 0  # rows the tail launches scored, block padding included
    # Document-tree slots the head, classifier and tail launches scored:
    # rows times tree slots per launch, padded rows and tree blocks included.
    doc_tree_slots: int = 0
    # Host seconds of rank_batch's phases (time.perf_counter): placement,
    # engine config and the compiled step's dispatch; what is left between
    # it and the read (unpacking only); the one device_get.
    dispatch_s: float = 0.0
    post_s: float = 0.0
    read_s: float = 0.0

    @property
    def speedup(self) -> float:
        return self.trees_full_equiv / max(self.trees_traversed, 1.0)

    @property
    def continue_rate(self) -> float:
        return self.docs_continued / max(self.docs, 1)

    @property
    def query_exit_rate(self) -> float:
        return self.queries_exited / max(self.queries, 1)


class RankingService:
    """LEAR-cascade ranking over padded [Q, D, F] request blocks.

    ``extra_classifiers`` turn the service into a multi-sentinel cascade:
    stages are ordered by sentinel and each stage's classifier gates the
    survivors of the previous one (nested exit masks). With none, the
    service is the paper's single-sentinel cascade served through the same
    progressive engine (a sentinel list of length 1).
    """

    def __init__(
        self,
        ensemble: TreeEnsemble,
        classifier: LearClassifier,
        config: ServiceConfig | None = None,
        extra_classifiers: Sequence[LearClassifier] = (),
        *,
        threshold: float | None = None,
        capacity_headroom: float | None = None,
        top_k: int | None = None,
        use_kernel_classifier: bool | None = None,
        execution_mode: str | None = None,
        launch_overhead_trees: float | str | None = None,
        survivor_ema: float | None = None,
        query_exit: QueryExitConfig | None = None,
    ) -> None:
        if config is not None and not isinstance(config, ServiceConfig):
            # Legacy POSITIONAL call: RankingService(ens, clf, 0.3, …)
            assert threshold is None, (config, threshold)
            config, threshold = None, float(config)
        legacy = {
            name: value
            for name, value in (
                ("threshold", threshold),
                ("capacity_headroom", capacity_headroom),
                ("top_k", top_k),
                ("use_kernel_classifier", use_kernel_classifier),
                ("execution_mode", execution_mode),
                ("launch_overhead_trees", launch_overhead_trees),
                ("survivor_ema", survivor_ema),
                ("query_exit", query_exit),
            )
            if value is not None
        }
        if config is None:
            if legacy:
                warnings.warn(
                    _DEPRECATED_SERVICE_MSG, DeprecationWarning, stacklevel=2
                )
            config = ServiceConfig(**legacy)
        elif legacy:
            raise TypeError(
                "RankingService: pass configuration via ServiceConfig OR "
                f"the deprecated keywords, not both (got {sorted(legacy)})"
            )
        self.config = config
        self.ensemble = ensemble
        self.classifier = classifier
        self.threshold = config.threshold
        self.headroom = config.capacity_headroom
        self.top_k = config.top_k
        self.use_kernel_classifier = config.use_kernel_classifier
        self.execution_mode = config.execution_mode
        # Price of one extra kernel launch + gather/scatter HBM round trip,
        # in doc·tree equivalents — the cost model's only tunable. "auto"
        # measures it at startup (short timing probe, cached per process)
        # instead of trusting a machine-independent constant.
        loh = config.launch_overhead_trees
        if loh == "auto":
            loh = calibrate_launch_overhead_trees()
        self.launch_overhead_trees = float(loh)
        self.survivor_ema = config.survivor_ema
        # Query-level exit config (None = document-level LEAR only). Part
        # of the compiled step's static key; the per-bucket tail-skip EMA
        # it produces feeds the auto-mode cost model as a traced operand.
        self.query_exit = config.query_exit
        self.dense_stage = config.dense_stage
        self.stats = ServiceStats()
        # Adaptive state is PER padded batch shape (capacity bucket): each
        # (Q, D) the service has seen owns its survivor peaks and EMA.
        # ``_active_key`` is the bucket of the most recent rank_batch —
        # the introspection surface (_stage_peaks/_stage_ema properties,
        # _pick_capacities, _pick_mode) reads through it.
        self._adapt: dict[tuple[int, int] | None, _BucketAdaptState] = {}
        self._active_key: tuple[int, int] | None = None

        stages = sorted([classifier, *extra_classifiers], key=lambda c: c.sentinel)
        self.stage_classifiers = stages
        self.sentinels = tuple(c.sentinel for c in stages)
        assert len(set(self.sentinels)) == len(stages), (
            "stage sentinels must be distinct", self.sentinels
        )
        self.stage_strategies = [self._make_strategy(c) for c in stages]

        # Stage tuples are cached per (strategy identities, dense stage)
        # (see _engine_stage_tuple); the accounting view is fixed at
        # construction. For a hybrid service the dense gate is a
        # zero-sentinel stage charging cost_trees per candidate. The cache
        # is a dict so degradation rungs (each with its own closures and
        # possibly its own dense stage) keep their stage tuples — and
        # therefore their EngineConfig identity — stable across swaps.
        self._stages_cache: dict[tuple, tuple] = {}
        # Degradation rung ladder: None until install_rungs; level 0 is
        # always the baseline configuration.
        self._rungs: tuple[_RungState, ...] | None = None
        self._rung_level = 0
        self.n_stages = len(self.sentinels) + (
            1 if self.dense_stage is not None else 0
        )

        self.cascade = CascadeRanker(
            ensemble=ensemble,
            sentinel=stages[0].sentinel,
            strategy=self.stage_strategies[0],
            classifier_trees=stages[0].n_trees,
        )
        # The ranker's exit-leaf encoding, on every lear.step.dispatch span:
        # mask words per node and the kernel's leaf-gather path.
        self._dispatch_meta = dict(
            mask_words=int(ensemble.mask.shape[2]),
            leaf_gather=resolve_leaf_gather(ensemble.n_leaves),
        )

    def bucket_state(self, Q: int, D: int) -> _BucketAdaptState:
        """Adaptive state for batch shape ``(Q, D)``, created on first use.

        The warmup path (:func:`repro.serve.warmup.warmup_service`) seeds
        ``peaks`` here BEFORE the bucket's first trace so the compaction
        capacities are stable from batch 1 — one trace per bucket and no
        cold-start overflow.
        """
        return self._adapt.setdefault((Q, D), _BucketAdaptState())

    def _active_state(self) -> _BucketAdaptState:
        return self._adapt.setdefault(self._active_key, _BucketAdaptState())

    # Back-compat introspection surface: the pre-bucketing attributes now
    # read/write the ACTIVE bucket's state (the shape most recently served).
    @property
    def _stage_peaks(self) -> list[int] | None:
        return self._active_state().peaks

    @_stage_peaks.setter
    def _stage_peaks(self, value: list[int] | None) -> None:
        self._active_state().peaks = value

    @property
    def _stage_ema(self) -> list[float] | None:
        return self._active_state().ema

    @_stage_ema.setter
    def _stage_ema(self, value: list[float] | None) -> None:
        self._active_state().ema = value

    def _engine_stage_tuple(self) -> tuple:
        """The EngineConfig stage list, rebuilt only when the strategy
        callables (tests swap ``stage_strategies`` in place) or the dense
        stage (degradation rungs swap it) change.

        Caching on the strategy identities keeps the per-batch
        EngineConfigs structurally equal — the TreeStage objects (and the
        closures inside, which hash by identity) are the SAME objects
        every batch, so the engine's compiled-step cache stays hot. A
        dict (not a single slot) so rung switching under load revisits
        cached tuples instead of thrashing one entry.
        """
        key = (tuple(self.stage_strategies), self.dense_stage)
        stages = self._stages_cache.get(key)
        if stages is None:
            tree_stages = tuple(
                TreeStage(
                    sentinel=c.sentinel,
                    strategy=strat,
                    classifier_trees=float(c.n_trees),
                )
                for c, strat in zip(self.stage_classifiers, key[0])
            )
            stages = (
                (self.dense_stage, *tree_stages)
                if self.dense_stage is not None else tree_stages
            )
            self._stages_cache[key] = stages
        return stages

    def _make_strategy(
        self, clf: LearClassifier, threshold: float | None = None
    ) -> Callable[..., jax.Array]:
        # NOTE: the strategy is traced into the cached jitted cascade step,
        # so the threshold is baked in at trace time — ``None`` reads
        # ``self.threshold`` at trace time (the construction-time default);
        # degradation rungs pass their own explicit threshold and get their
        # own closure, hence their own compiled step.
        def strategy(partial, mask, features=None):
            with jax.named_scope(SCOPE_FEATURES):
                aug = augment_features(features, partial, mask)
            th = self.threshold if threshold is None else threshold
            with jax.named_scope(SCOPE_CLASSIFIER):
                return clf.continue_mask(
                    aug, mask, th, use_kernel=self.use_kernel_classifier
                )

        return strategy

    # -- degradation rungs -------------------------------------------------

    @property
    def n_rungs(self) -> int:
        """Installed rung count (baseline included); 0 = no ladder."""
        return len(self._rungs) if self._rungs is not None else 0

    @property
    def rung_level(self) -> int:
        return self._rung_level

    @property
    def rung_names(self) -> tuple[str, ...]:
        return tuple(r.name for r in self._rungs or ())

    def install_rungs(self, rungs: Sequence[ExitRung]) -> None:
        """Materialize the degradation ladder: level 0 is the CURRENT
        configuration (baseline), level ``i`` applies ``rungs[i-1]``'s
        overrides. Each rung's strategy closures (and dense stage, when
        ``dense_keep_frac`` is overridden) are built exactly once here, so
        :meth:`set_rung` swaps stable objects and every rung owns one
        compiled engine step. Install before warmup — the warmup pass
        AOT-compiles every installed rung per bucket."""
        assert self._rungs is None, "rungs already installed"
        assert self._rung_level == 0
        ladder = [_RungState(
            name="baseline",
            threshold=self.threshold,
            strategies=tuple(self.stage_strategies),
            query_exit=self.query_exit,
            dense_stage=self.dense_stage,
        )]
        for rung in rungs:
            th = rung.threshold if rung.threshold is not None else self.threshold
            if rung.threshold is None:
                strategies = ladder[0].strategies  # same closures, same step
            else:
                strategies = tuple(
                    self._make_strategy(c, th)
                    for c in self.stage_classifiers
                )
            dense = self.dense_stage
            if rung.dense_keep_frac is not None:
                assert dense is not None, (
                    "rung overrides dense_keep_frac but the service has "
                    "no dense stage", rung.name,
                )
                dense = dataclasses.replace(
                    dense,
                    policy=functools.partial(
                        dense_keep_fraction,
                        keep_frac=float(rung.dense_keep_frac),
                    ),
                )
            ladder.append(_RungState(
                name=rung.name,
                threshold=th,
                strategies=strategies,
                query_exit=(
                    rung.query_exit if rung.query_exit is not None
                    else self.query_exit
                ),
                dense_stage=dense,
            ))
        self._rungs = tuple(ladder)

    def set_rung(self, level: int) -> None:
        """Swap the active exit configuration to ``level`` of the installed
        ladder. Pointer swaps only — no tracing, no allocation. MUST be
        called from the thread that owns the engine (the batcher worker):
        the next ``rank_batch`` picks up the rung atomically."""
        assert self._rungs is not None, "install_rungs first"
        assert 0 <= level < len(self._rungs), (level, len(self._rungs))
        r = self._rungs[level]
        self._rung_level = level
        self.threshold = r.threshold
        self.stage_strategies = list(r.strategies)
        self.query_exit = r.query_exit
        self.dense_stage = r.dense_stage

    def _cold_start_estimate(self, n_docs: int) -> int:
        # Cold start: assume a 40% survivor rate at EVERY stage
        # (conservative — survivors only shrink; undersizing a later
        # stage on batch 1 would cause real overflow).
        return int(0.4 * n_docs * self.headroom)

    def _pick_capacities(self, n_docs: int) -> list[int]:
        """Per-stage compaction capacities with p99-style headroom.

        Reads the ACTIVE batch-shape bucket's survivor peaks — each padded
        ``(Q, D)`` shape ratchets its own capacities. Each stage gets its
        own bucket sized from the RUNNING MAX of its
        observed survivor counts times ``headroom``, and never below the
        cold-start estimate — one sparse batch must not shrink the bucket
        under the traffic the service has already seen (that would silently
        overflow the next normal batch). Each stage gets its own bucket
        (survivor sets shrink stage over stage; sizing every stage off the
        last one would report phantom overflow at the early stages), and
        buckets are powers of two to bound re-jits. When a stage still
        overflows (survivors were clipped at the old bucket), the observed
        peak equals the old capacity, so ``peak × headroom`` rounds up to
        the next bucket — capacity ratchets up until overflow stops.
        """
        cold = self._cold_start_estimate(n_docs)
        if self._stage_peaks is None:
            want = [cold] * self.n_stages
        else:
            want = [
                max(cold, int(peak * self.headroom))
                for peak in self._stage_peaks
            ]
        caps = [bucket_capacity(w, n_docs) for w in want]
        if (
            self.dense_stage is not None
            and self.dense_stage.capacity is not None
        ):
            # A pinned dense capacity overrides the ratchet (the engine's
            # stage.capacity precedence would anyway); mirroring it here
            # keeps the host cost model pricing the real block size.
            caps[0] = min(int(self.dense_stage.capacity), n_docs)
        return caps

    def _pick_mode(
        self, n_docs: int, capacities: Sequence[int] | None = None
    ) -> str:
        """Host-side REFERENCE pick: fused head vs per-stage tails.

        Serving no longer calls this per batch — with
        ``execution_mode="auto"`` the same decision happens on device
        inside the compiled step (``lax.cond`` on
        :func:`repro.metrics.speedup.progressive_cost_model_device`). This
        method remains the host mirror of that pick, used by tests to
        assert the two agree and by operators for introspection.

        Until the first batch lands there are no observed rates — default
        fused (1 segmented + ≤1 tail launch is the safe floor). After
        that, price both modes with the cost model on the smoothed
        survivor counts — staged stage work at block-rounded survivors
        clipped at capacity (``block_b=ENGINE_BLOCK_B``, matching the
        in-program pick) — and take the cheaper.
        """
        if self.execution_mode != "auto":
            return self.execution_mode
        if self._stage_ema is None or len(self.sentinels) == 1:
            return "fused"
        if capacities is None:
            capacities = self._pick_capacities(n_docs)
        T = self.ensemble.n_trees
        dense = self.dense_stage
        cost = {
            m: progressive_cost_model(
                n_docs, self._stage_ema, self.sentinels, T, m,
                launch_overhead_trees=self.launch_overhead_trees,
                stage_capacities=capacities,
                block_b=ENGINE_BLOCK_B,
                query_exit_rate=self._query_exit_rate_estimate(),
                dense_cost_trees=(
                    float(dense.cost_trees) if dense is not None else 0.0
                ),
                dense_stage=dense is not None,
            )
            for m in ("fused", "staged")
        }
        return "staged" if cost["staged"] < cost["fused"] else "fused"

    def _query_exit_rate_estimate(self) -> float:
        """Smoothed tail-skip probability for the ACTIVE bucket.

        0.0 while query exit is off (no discount) or before the bucket's
        first batch (cold start must not assume the tail gets skipped).
        """
        if self.query_exit is None:
            return 0.0
        return self._active_state().tail_skip or 0.0

    def rank_batch(
        self,
        X: jax.Array,
        mask: jax.Array,
        placement: ServePlacement | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """X: [Q, D, F]; returns (top-k doc indices [Q, k], scores [Q, D]).

        One engine call is one compiled program and one read: the engine
        step with the response and stats computed after it in the same
        program (:meth:`repro.core.cascade.CascadeRanker.serve_progressive`:
        masked top-k, trees traversed, and one int32 vector of per-stage
        survivors, overflow, document count, picked branch and queries
        exited). The step is submitted with everything it needs (with
        ``execution_mode="auto"``, also this bucket's survivor EMA as a
        tiny f32 operand for the in-program mode pick), and the ONLY
        device→host transfer is the single fused
        ``jax.device_get`` of its four outputs. Between dispatch and that
        read the host only unpacks Python objects.

        Adaptation (survivor peaks → capacities, EMA → mode pick) is keyed
        by the padded batch shape ``(Q, D)`` — each serving bucket adapts
        to its own traffic.

        ``placement`` (a :class:`repro.serve.placement.ServePlacement`)
        pins the operands to a device mesh before submit and holds the
        mesh in context while the step runs; ``None`` is the
        single-device fast path and is bit-exact with any 1-device
        placement.
        """
        t0 = time.perf_counter()
        with span(DISPATCH, **self._dispatch_meta):
            if placement is not None:
                X, mask = placement.put(X, mask)
            Q, D, _ = X.shape
            engine_config, extra = self._engine_call(Q, D)
            with _scope(placement):
                out = self.cascade.serve_progressive(
                    X, mask, engine_config, self.top_k, features=X, **extra,
                )
                tail_rows = self._tail_rows(engine_config, Q * D)
                slots = self._doc_tree_slots(engine_config, Q * D)
        t1 = time.perf_counter()
        with span(POST):
            # All that is left between dispatch and read: the response and
            # stats already come out of the one compiled step.
            top_idx, scores, traversed, counts = out
        t2 = time.perf_counter()
        with span(READ):
            # ONE fused device read: the response (top-k + scores) AND the
            # stats (trees traversed; per-stage survivors, overflow, doc
            # count, picked branch, queries exited) — no other host sync
            # anywhere on this path.
            top_idx, scores, traversed, counts = jax.device_get(
                (top_idx, scores, traversed, counts)
            )
        t3 = time.perf_counter()
        with span(ADAPT):
            survivors = counts[:self.n_stages]
            overflow, batch_docs, picked_staged, q_exited = (
                counts[self.n_stages:]
            )
            # Adapt: running max sizes the buckets, the EMA feeds the cost
            # model. Peaks and EMA seed independently — warmup pre-seeds peaks
            # (the no-overflow guarantee) but leaves the EMA to real traffic.
            a = self.survivor_ema
            state = self._active_state()
            if state.peaks is None:
                state.peaks = [int(n) for n in survivors]
            else:
                state.peaks = [
                    max(p, int(n)) for p, n in zip(state.peaks, survivors)
                ]
            if state.ema is None:
                state.ema = [float(n) for n in survivors]
            else:
                state.ema = [
                    (1 - a) * e + a * float(n)
                    for e, n in zip(state.ema, survivors)
                ]
            tail_skipped = False
            if self.query_exit is not None:
                # Zero final-stage survivors ⟺ the gated tail launch was
                # skipped this batch; its smoothed rate is what the cost
                # model discounts the tail launch term by next submit.
                tail_skipped = int(survivors[-1]) == 0
                skipped = 1.0 if tail_skipped else 0.0
                if state.tail_skip is None:
                    state.tail_skip = skipped
                else:
                    state.tail_skip = (1 - a) * state.tail_skip + a * skipped

            s = self.stats
            s.batches += 1
            s.batches_staged += bool(picked_staged)
            s.batches_fused += not bool(picked_staged)
            s.queries += Q
            s.docs += int(batch_docs)
            s.docs_continued += int(survivors[-1])
            s.overflow_docs += int(overflow)
            s.queries_exited += int(q_exited)
            s.trees_traversed += float(traversed)
            s.trees_full_equiv += int(batch_docs) * self.ensemble.n_trees
            s.tail_rows += 0 if tail_skipped else tail_rows
            head_slots, tail_trees = slots[bool(picked_staged)], slots[2]
            s.doc_tree_slots += head_slots + (
                0 if tail_skipped else tail_rows * tail_trees
            )
            s.dispatch_s += t1 - t0
            s.post_s += t2 - t1
            s.read_s += t3 - t2
        return top_idx, scores

    def _tail_rows(self, engine_config: EngineConfig, n_docs: int) -> int:
        """Rows the tail launch of one ``n_docs``-document batch scores: the
        last stage's compaction capacity, padded as the launch pads it on
        the context mesh; 0 with no tail. Call with the placement's mesh
        in context."""
        if self.sentinels[-1] >= self.ensemble.n_trees:
            return 0
        return launch_rows(min(int(engine_config.capacities[-1]), n_docs))

    def _doc_tree_slots(
        self, engine_config: EngineConfig, n_docs: int
    ) -> tuple[int, int, int]:
        """Document-tree slots of one ``n_docs``-document batch, counted
        from the launch shapes: the head and classifier launches of the
        fused branch, the same of the staged branch, and the tail's tree
        slots per row (its rows are :meth:`_tail_rows`). Rows are padded
        as each launch pads them on the context mesh, trees to whole tree
        blocks of each segment. Call with the placement's mesh in
        context."""
        T = self.ensemble.n_trees
        sentinels = engine_config.sentinels
        S = len(sentinels)
        has_tail = sentinels[-1] < T
        seg = segment_tree_slots(
            self.ensemble, sentinels + ((T,) if has_tail else ()),
            engine_config.block_t,
        )
        caps = [min(int(c), n_docs) for c in engine_config.capacities]
        if engine_config.dense is not None:
            head_rows, tree_caps = launch_rows(caps[0]), caps[1:]
        else:
            head_rows, tree_caps = launch_rows(n_docs), caps
        classifiers = 0
        if self.use_kernel_classifier:
            classifiers = launch_rows(n_docs) * sum(
                segment_tree_slots(c.forest, (c.forest.n_trees,))[0]
                for c in self.stage_classifiers
            )
        fused = head_rows * sum(seg[:S]) + classifiers
        staged = head_rows * seg[0] + classifiers + sum(
            launch_rows(tree_caps[k]) * seg[k + 1] for k in range(S - 1)
        )
        return fused, staged, seg[S] if has_tail else 0

    def lower_step(
        self,
        X: jax.Array,
        mask: jax.Array,
        placement: ServePlacement | None = None,
    ) -> jax.stages.Lowered:
        """The engine step :meth:`rank_batch` runs for this batch, lowered
        and not executed — for inspecting the compiled program (for
        example, that its forest launches are Mosaic kernels, each fed its
        own device's rows)."""
        if placement is not None:
            X, mask = placement.put(X, mask)
        Q, D, _ = X.shape
        engine_config, extra = self._engine_call(Q, D)
        with _scope(placement):
            return self.cascade.lower_progressive(
                X, mask, engine_config, top_k=self.top_k, features=X,
                **extra,
            )

    def _engine_call(self, Q: int, D: int) -> tuple[EngineConfig, dict]:
        """Engine config and traced mode operands for one ``(Q, D)``
        batch, from that bucket's adaptive state."""
        self._active_key = (Q, D)
        n_docs = Q * D
        capacities = self._pick_capacities(n_docs)
        mode = self.execution_mode
        extra = {}
        if mode == "auto":
            if len(self.sentinels) == 1:
                mode = "fused"  # S=1: both modes are the same computation
            else:
                # Ship the survivor estimate at submit; the pick happens
                # inside the compiled step. Cold start (no observed rates
                # yet): have_ema=False forces the fused branch. The EMA
                # covers ALL stages (dense entry first for hybrid).
                ema = self._stage_ema or [float(n_docs)] * self.n_stages
                extra = dict(
                    stage_ema=jnp.asarray(ema, jnp.float32),
                    have_ema=self._stage_ema is not None,
                    query_exit_rate=jnp.asarray(
                        self._query_exit_rate_estimate(), jnp.float32
                    ),
                )
        engine_config = EngineConfig(
            stages=self._engine_stage_tuple(),
            mode=mode,
            capacities=tuple(capacities),
            launch_overhead_trees=self.launch_overhead_trees,
            query_exit=self.query_exit,
        )
        return engine_config, extra


def _scope(
    placement: ServePlacement | None,
) -> contextlib.AbstractContextManager:
    return contextlib.nullcontext() if placement is None else placement.scope()


@dataclasses.dataclass
class TwoStageCascade:
    """Beyond-paper: LEAR-style cascade over arbitrary scorers.

    ``sentinel_fn`` cheaply scores all candidates; a learned (or threshold)
    filter keeps the promising ones; ``full_fn`` scores the survivors. Used
    for recsys ``retrieval_cand`` in examples/cascade_retrieval.py.
    """

    sentinel_fn: Callable[[jax.Array], jax.Array]   # ids -> cheap scores
    full_fn: Callable[[jax.Array], jax.Array]       # ids -> full scores
    keep_fraction: float = 0.05

    def score(
        self, cand_ids: jax.Array
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        cheap = self.sentinel_fn(cand_ids)
        C = cand_ids.shape[0]
        keep = max(1, int(C * self.keep_fraction))
        top_vals, top_idx = jax.lax.top_k(cheap, keep)
        survivors = cand_ids[top_idx]
        full = self.full_fn(survivors)
        return survivors, full, cheap
