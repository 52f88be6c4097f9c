"""Sentinel-partitioned cascade execution — early exit as batch compaction.

Three execution paths with identical ranking semantics:

- :meth:`CascadeRanker.rank` — *reference* path: scores every document
  through head and tail, applies the continue mask arithmetically. Used for
  quality evaluation and as the oracle for the compacted paths. Cost is
  accounted in the paper's currency (trees traversed), not saved.
- :meth:`CascadeRanker.rank_compacted` — single-sentinel *reference
  production* path: after the sentinel, surviving documents are gathered
  into a dense prefix (O(n) cumsum stable partition) and ONLY that
  compacted block runs the tail trees through the Pallas kernel.
- :meth:`CascadeRanker.rank_progressive` — the *multi-stage engine* and
  the serving hot path, configured by a single frozen
  :class:`repro.core.stage.EngineConfig` (the stage list + engine knobs;
  the config doubles as the jit-step cache key). The WHOLE step — stage
  scoring, exit decisions, cumsum compaction, tail, scatter — is built
  once per configuration and compiled into ONE end-to-end ``jax.jit``
  computation (XLA is free to fuse compact → gather → tail → scatter);
  launch accounting moved to trace time
  (:func:`repro.kernels.ops._counted_pallas`), so the launch contract
  stays testable. Two execution modes share identical ranking semantics:

  * ``mode="fused"`` (default): one sentinel-segmented Pallas launch over
    the head trees yields the prefix score of every document at EVERY
    sentinel (``[Q, D, S]``); stage decisions are pure vector work (no
    kernel, no HBM round-trip between stages), exit masks are nested
    (``alive_k = alive_{k-1} ∧ continue_k`` — a document that exits never
    re-enters), and exactly ONE tail launch runs the remaining trees on
    the cumsum-compacted survivors of the last stage: 1 segmented head
    launch + ≤1 tail launch total.
  * ``mode="staged"`` (per-stage tails): segment ``k`` is scored ONLY on
    the stage-(k−1) compacted survivors — each stage's capacity is a REAL
    kernel block bound (survivors beyond it retire with their stage-k
    prefix and are charged to ``overflow``), so kernel work shrinks with
    the survivor set at the cost of one launch plus one gather/scatter
    per stage: ≤S+1 plain launches, no segmented launch. With S == 1 the
    two modes are the same computation.
  * ``mode="auto"`` (the ON-DEVICE pick): ONE combined program contains
    both branches under a ``jax.lax.cond`` and the branch predicate is
    computed on device —
    :func:`repro.metrics.speedup.progressive_cost_model_device` prices
    both modes from a traced survivor estimate (``stage_ema``, typically
    the service's smoothed per-stage survivor counts) and the cheaper
    branch executes. No host round trip, no batch-boundary decision lag.
    Both branches are staged at trace time (launch counters account each
    exactly once); at run time exactly one branch's launches execute.

  **Hybrid cascades** (:class:`repro.core.stage.DenseStage` at position
  0): the dense scorer evaluates the ENTIRE ``[Q·D, F]`` block in one
  matmul, its policy prunes the easy majority, and the survivors are
  cumsum-compacted into a block of ``capacities[0]`` — the tree stages
  (both modes' head launches included) then run on THAT block, so no
  tree is ever traversed for a dense-exited document. Dense-exited
  documents keep the dense score as their final score (the distilled
  model stands in for the ensemble); the dense compaction is a real
  kernel block bound in both modes, with real overflow accounting. The
  dense matmul is pure XLA — it adds no Pallas launch, so the launch
  contract is unchanged with ``S`` = the number of TREE stages.

  Mode trade-off: fused scores every candidate document through the whole
  head region, trading redundant VPU work on early-exited documents for
  the elimination of S−1 launches and all intermediate gather/scatter
  traffic. Staged wins when survivors shrink fast and the head region is
  deep. :meth:`repro.serve.ranking_service.RankingService` serves
  ``auto`` by default; the host-side pick via
  :func:`repro.metrics.speedup.progressive_cost_model` remains the
  reference model. The speedup metric stays in the paper's currency
  (trees *logically* traversed under early-exit semantics).

  Strategies and dense policies must be *mask-invariant* (read
  ``partial`` only where the alive mask is set): in staged and hybrid
  execution, exited documents hold stale prefixes (or grid slots never
  scored by the trees), and all stock strategies already mask them out.

A static ``capacity`` bounds each compacted block so the step stays
jit-compatible; :func:`bucket_capacity` buckets requested capacities to
powers of two so the jit cache stays bounded. Survivors beyond capacity
keep their stage prefix score (bounded, graceful quality degradation —
never a crash), and the overflow count is a LAZY device scalar: the hot
path never blocks on it (read it in a stats path via
``int(result.overflow)``). For the same reason, ``rank_progressive``
reports ``speedup`` as a lazy device scalar too; the reference paths keep
returning host floats.

Deprecated keyword configuration (``sentinels=…, capacities=…,
strategies=…, mode=…`` and friends) still works through a shim that
builds the equivalent :class:`~repro.core.stage.EngineConfig` and emits a
``DeprecationWarning`` whose message starts with ``repro.`` — CI runs the
repo's own tests with that warning escalated to an error, proving no
in-repo caller still uses it.
"""

from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compaction import (
    COMPACTORS,
    compact_indices_cumsum,
    compact_indices_cumsum_masked,
)
from repro.core.stage import DenseStage, EngineConfig
from repro.core.strategies import QueryExitConfig, query_converged
from repro.forest.ensemble import TreeEnsemble, slice_trees
from repro.forest.scoring import score_bitvector
from repro.kernels.ops import (
    ENGINE_BLOCK_B,
    PaddedForest,
    forest_score,
    forest_score_range,
    forest_score_segments,
    padded_forest,
)
from repro.metrics.speedup import (
    progressive_cost_model_device,
    speedup_progressive,
    speedup_vs_full,
    trees_traversed_progressive,
)

_DEPRECATED_KWARGS_MSG = (
    "repro.core.cascade.rank_progressive: keyword configuration "
    "(sentinels=…, capacities=…, strategies=…, mode=…) is deprecated; "
    "pass an EngineConfig — e.g. rank_progressive(X, mask, "
    "EngineConfig.trees(sentinels=…, …)). The shim builds the equivalent "
    "config and will be removed in a future release."
)


#: ``jax.named_scope`` names of the compiled step's LEAR stages: they land
#: in each op's ``op_name`` metadata (device traces, compiled HLO) and
#: change no instruction name. Features and classifier are opened by the
#: serving path's LEAR strategy (``RankingService._make_strategy``).
SCOPE_HEAD = "lear/head"
SCOPE_FEATURES = "lear/features"
SCOPE_CLASSIFIER = "lear/classifier"
SCOPE_COMPACTION = "lear/compaction"
SCOPE_TAIL = "lear/tail"


def bucket_capacity(want: int, limit: int, minimum: int = 64) -> int:
    """Power-of-two capacity bucketing (bounded jit cache), clipped to limit."""
    cap = 1 << int(np.ceil(np.log2(max(want, minimum, 1))))
    return min(cap, limit)


@dataclasses.dataclass
class CascadeResult:
    scores: jax.Array          # [Q, D] final scores (exited docs keep the
    #                            score of the stage that exited them — the
    #                            dense score for dense-stage exits)
    continue_mask: jax.Array   # [Q, D] — survivors of the LAST stage
    speedup: float | jax.Array  # trees-traversed speedup vs Full (lazy scalar
    #                             on the progressive path; host float on the
    #                             reference paths)
    overflow: jax.Array | int = 0  # lazy device scalar; docs beyond capacity
    #   (fused: dense + final-stage compactions; staged: summed over stages)
    stage_masks: list | None = None   # progressive: nested alive mask per
    #   stage, dense stage first when present (len == config.n_stages)
    partials: jax.Array | None = None  # progressive: [Q, D, n_stages] — the
    #   score grid each stage's policy saw (fused all-trees: exact sentinel
    #   prefixes for every doc; staged/hybrid: docs already exited hold
    #   their exit-stage score; hybrid slice 0 is the dense score grid)
    mode: str | None = None            # progressive: "fused"|"staged"|"auto"
    picked_staged: jax.Array | None = None  # mode="auto": lazy device bool —
    #   which cond branch executed (True = staged); None for fixed modes
    query_exited: jax.Array | None = None  # query_exit enabled: [Q] lazy bool
    #   — queries whose remaining docs were removed by query-level exit
    #   (converged top-k or no alive docs); None when the knob is off


@dataclasses.dataclass
class CascadeRanker:
    ensemble: TreeEnsemble
    sentinel: int
    strategy: Callable[..., jax.Array]
    classifier_trees: int = 0   # extra per-doc cost charged for the strategy
    _ht_cache: tuple | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )
    # End-to-end jitted progressive steps, keyed by the full static config
    # (buffers, stages, capacities, mode, …). LRU-bounded so sweeping
    # configurations cannot pin unbounded compiled computations.
    _step_cache: OrderedDict = dataclasses.field(
        default_factory=OrderedDict, init=False, repr=False, compare=False
    )

    def _head_tail(self) -> tuple[TreeEnsemble, TreeEnsemble]:
        # Sliced sub-ensembles are cached: repeated rank*() calls reuse the
        # same TreeEnsemble objects (and therefore their padded-buffer
        # caches) instead of re-slicing per call.
        if self._ht_cache is None:
            head = slice_trees(self.ensemble, 0, self.sentinel)
            tail = slice_trees(self.ensemble, self.sentinel, self.ensemble.n_trees)
            self._ht_cache = (head, tail)
        return self._ht_cache

    def rank(
        self, X: jax.Array, mask: jax.Array, **strategy_kwargs: object
    ) -> CascadeResult:
        """Reference path: full compute, masked combine."""
        Q, D, F = X.shape
        flat = X.reshape(Q * D, F)
        head, tail = self._head_tail()
        partial = score_bitvector(head, flat).reshape(Q, D)
        cont = self.strategy(partial, mask, **strategy_kwargs)
        tail_scores = score_bitvector(tail, flat).reshape(Q, D)
        scores = jnp.where(cont, partial + tail_scores, partial)
        sp = speedup_vs_full(
            cont, mask, self.sentinel, self.ensemble.n_trees, self.classifier_trees
        )
        return CascadeResult(scores=scores, continue_mask=cont, speedup=sp)

    def rank_compacted(
        self,
        X: jax.Array,
        mask: jax.Array,
        capacity: int,
        compaction: str = "cumsum",
        **strategy_kwargs: object,
    ) -> CascadeResult:
        """Single-sentinel production path: tail sees only compacted survivors."""
        Q, D, F = X.shape
        head, tail = self._head_tail()
        partial = forest_score(head, X.reshape(Q * D, F)).reshape(Q, D)
        cont = self.strategy(partial, mask, **strategy_kwargs)
        scores, n_cont = _compacted_tail(X, partial, cont, tail, capacity, compaction)
        overflow = jnp.maximum(n_cont - capacity, 0)  # lazy: no device sync
        sp = speedup_vs_full(
            cont, mask, self.sentinel, self.ensemble.n_trees, self.classifier_trees
        )
        return CascadeResult(
            scores=scores, continue_mask=cont, speedup=sp, overflow=overflow
        )

    def rank_progressive(
        self,
        X: jax.Array,
        mask: jax.Array,
        config: EngineConfig | None = None,
        sentinels: Sequence[int] | None = None,
        capacities: Sequence[int] | int | None = None,
        strategies: Sequence[Callable[..., jax.Array]] | None = None,
        *,
        classifier_trees: Sequence[int] | int | None = None,
        block_t: int | None = None,
        leaf_gather: str | None = None,
        mode: str | None = None,
        stage_ema: jax.Array | None = None,
        have_ema: jax.Array | bool = True,
        launch_overhead_trees: float | None = None,
        query_exit: QueryExitConfig | None = None,
        query_exit_rate: jax.Array | float = 0.0,
        **strategy_kwargs: object,
    ) -> CascadeResult:
        """Multi-stage engine, end-to-end jitted (one XLA computation).

        ``config`` (an :class:`repro.core.stage.EngineConfig`) is the
        whole static configuration: the ordered stage list (an optional
        :class:`repro.core.stage.DenseStage` at position 0, then
        :class:`repro.core.stage.TreeStage` entries with increasing
        sentinels) plus the engine knobs. A ``TreeStage`` with
        ``strategy=None`` / ``classifier_trees=None`` inherits the
        ranker's defaults. Per-stage survivor capacities resolve as
        stage.capacity → config.capacities entry → bucket default
        (:func:`bucket_capacity`), each clipped to ``Q·D``.

        Everything else on the signature is either a traced per-call
        operand (``stage_ema``/``have_ema``/``query_exit_rate`` for
        ``mode="auto"``, plus ``**strategy_kwargs`` whose array values
        become traced operands of the jitted step) or the DEPRECATED
        keyword configuration: passing ``sentinels=…`` (and friends)
        without a config still works — the shim builds the equivalent
        ``EngineConfig.trees(...)`` and emits a ``DeprecationWarning``
        (message prefixed ``repro.`` so CI can escalate in-repo use to an
        error). Mixing a config WITH legacy keywords is a ``TypeError``.

        ``mode="auto"`` compiles BOTH modes into one program and picks the
        branch on device with a ``lax.cond``: ``stage_ema`` (``[n_stages]``
        f32, required — dense stage included for hybrid configs) is the
        traced per-stage survivor estimate priced by
        :func:`repro.metrics.speedup.progressive_cost_model_device`;
        ``have_ema`` (traced bool) gates the pick — ``False`` forces the
        fused branch (the safe cold-start floor). The executed branch is
        reported as the lazy ``picked_staged`` device bool on the result.
        Requires ≥ 2 TREE stages (with one the modes are the same
        computation).

        ``config.query_exit`` (a
        :class:`repro.core.strategies.QueryExitConfig`) enables
        query-level early exit: after each stage's document decision,
        :func:`repro.core.strategies.query_converged` folds a per-query
        "top-k stabilized" predicate into the alive mask (stage indices
        count ALL stages — the dense stage is stage 0 of a hybrid
        config) — a converged query's remaining documents skip every
        later stage and the tail, and the tail launch itself moves under
        a ``lax.cond`` on the survivor count (counted as ``gated`` by the
        launch counters). With ``margin=inf`` the transform is
        score-preserving. ``query_exit_rate`` (traced scalar,
        ``mode="auto"`` only) is the tail-skip estimate the in-program
        mode pick prices launches with.
        """
        if config is not None and not isinstance(config, EngineConfig):
            # Legacy POSITIONAL call: rank_progressive(X, mask, [10, 20], …)
            assert sentinels is None, (config, sentinels)
            config, sentinels = None, config
        legacy = {
            name: value
            for name, value in (
                ("sentinels", sentinels), ("capacities", capacities),
                ("strategies", strategies),
                ("classifier_trees", classifier_trees),
                ("block_t", block_t), ("leaf_gather", leaf_gather),
                ("mode", mode), ("launch_overhead_trees", launch_overhead_trees),
                ("query_exit", query_exit),
            )
            if value is not None
        }
        if config is None:
            assert sentinels is not None, (
                "rank_progressive needs an EngineConfig (or the deprecated "
                "sentinels=… keywords)"
            )
            warnings.warn(
                _DEPRECATED_KWARGS_MSG, DeprecationWarning, stacklevel=2
            )
            config = EngineConfig.trees(
                sentinels,
                strategies,
                classifier_trees=classifier_trees,
                capacities=capacities,
                mode=mode if mode is not None else "fused",
                leaf_gather=leaf_gather if leaf_gather is not None else "auto",
                block_t=block_t if block_t is not None else 16,
                launch_overhead_trees=(
                    launch_overhead_trees
                    if launch_overhead_trees is not None else 0.0
                ),
                query_exit=query_exit,
            )
        elif legacy:
            raise TypeError(
                "rank_progressive: pass configuration via EngineConfig OR "
                f"the deprecated keywords, not both (got {sorted(legacy)})"
            )

        step, args = self._progressive_step(
            X, mask, config, stage_ema, have_ema, query_exit_rate,
            strategy_kwargs,
        )
        (scores, alive, stage_masks, partials, overflow, sp, picked,
         q_exited, _traversed) = step(*args)
        return CascadeResult(
            scores=scores,
            continue_mask=alive,
            speedup=sp,
            overflow=overflow,   # lazy: no device sync
            stage_masks=list(stage_masks),
            partials=partials,
            mode=config.mode,
            picked_staged=picked,  # lazy device bool (auto), else None
            query_exited=q_exited if config.query_exit is not None else None,
        )

    def serve_progressive(
        self,
        X: jax.Array,
        mask: jax.Array,
        config: EngineConfig,
        top_k: int,
        *,
        stage_ema: jax.Array | None = None,
        have_ema: jax.Array | bool = True,
        query_exit_rate: jax.Array | float = 0.0,
        **strategy_kwargs: object,
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """:meth:`rank_progressive`'s step with a served batch's response and
        stats computed after it in the same program: one dispatch, and
        ``(top_idx, scores, traversed, counts)`` as lazy device arrays.

        ``top_idx`` [Q, min(top_k, D)] orders the masked scores (padding
        at −inf) by descending score, then ascending index; ``traversed``
        is the trees-traversed total of the speedup's accounting (a dense
        gate is a zero-sentinel stage); ``counts`` is one int32 vector of
        the survivors after each stage, then overflow, the document count,
        the branch that ran (1 = staged) and the queries exited."""
        step, args = self._progressive_step(
            X, mask, config, stage_ema, have_ema, query_exit_rate,
            strategy_kwargs, top_k,
        )
        return step(*args)

    def lower_progressive(
        self,
        X: jax.Array,
        mask: jax.Array,
        config: EngineConfig,
        *,
        stage_ema: jax.Array | None = None,
        have_ema: jax.Array | bool = True,
        query_exit_rate: jax.Array | float = 0.0,
        top_k: int | None = None,
        **strategy_kwargs: object,
    ) -> jax.stages.Lowered:
        """The step :meth:`rank_progressive` (or, with ``top_k``,
        :meth:`serve_progressive`) runs for these arguments, lowered and
        not executed (``.compile().as_text()`` shows the program the device
        runs)."""
        step, args = self._progressive_step(
            X, mask, config, stage_ema, have_ema, query_exit_rate,
            strategy_kwargs, top_k,
        )
        return step.lower(*args)

    def _progressive_step(
        self,
        X: jax.Array,
        mask: jax.Array,
        config: EngineConfig,
        stage_ema: jax.Array | None,
        have_ema: jax.Array | bool,
        query_exit_rate: jax.Array | float,
        strategy_kwargs: dict,
        top_k: int | None = None,
    ) -> tuple[Callable[..., tuple], tuple]:
        """The cached jitted step for ``config`` and its call arguments;
        with ``top_k``, the served step. A served step is its own cache
        entry, so its body traces once, into the one program served."""
        Q, D, F = X.shape
        dense = config.dense
        tree_sentinels = config.sentinels
        S = len(tree_sentinels)
        S_total = config.n_stages
        T = self.ensemble.n_trees
        run_mode = config.mode
        assert 0 < tree_sentinels[0] and tree_sentinels[-1] <= T, (
            tree_sentinels, T
        )
        tree_strategies = tuple(
            st.strategy if st.strategy is not None else self.strategy
            for st in config.tree_stages
        )
        tree_classifier_trees = tuple(
            float(
                st.classifier_trees
                if st.classifier_trees is not None
                else self.classifier_trees
            )
            for st in config.tree_stages
        )

        conf_caps = config.capacities
        if conf_caps is None:
            conf_caps = (None,) * S_total
        elif isinstance(conf_caps, int):
            conf_caps = (conf_caps,) * S_total
        default_cap = bucket_capacity(Q * D, Q * D)
        resolved = tuple(
            min(
                int(
                    st.capacity
                    if st.capacity is not None
                    else (c if c is not None else default_cap)
                ),
                Q * D,
            )
            for st, c in zip(config.stages, conf_caps)
        )

        has_tail = tree_sentinels[-1] < T
        boundaries = tree_sentinels + ((T,) if has_tail else ())
        # leaf_gather picks the kernel's leaf-value resolution path (select
        # tree / MXU contraction / one-hot reference — all bit-exact); the
        # buffer set carries the matching leaf layout, so a distinct path is
        # simply a distinct cached PaddedForest (and thus a distinct step).
        pf = padded_forest(
            self.ensemble, boundaries=boundaries, block_t=config.block_t,
            leaf_gather=config.leaf_gather,
        )

        # Array-valued strategy kwargs (or their shapes, when lowering)
        # become traced operands of the jitted step; everything else (ints,
        # floats, flags) is static config and part of the cache key.
        names = tuple(sorted(strategy_kwargs))
        traced_names = tuple(
            n for n in names
            if isinstance(
                strategy_kwargs[n],
                (jax.Array, np.ndarray, jax.ShapeDtypeStruct),
            )
        )
        static_items = tuple(
            (n, strategy_kwargs[n]) for n in names if n not in traced_names
        )

        if run_mode == "auto":
            assert S >= 2, "mode='auto' needs ≥2 tree stages (S=1: modes equal)"
            assert stage_ema is not None, "mode='auto' requires stage_ema"
            mode_ops = (
                jnp.asarray(stage_ema, jnp.float32),
                jnp.asarray(have_ema, bool),
                jnp.asarray(query_exit_rate, jnp.float32),
            )
            assert mode_ops[0].shape == (S_total,), (
                mode_ops[0].shape, S_total
            )
        else:
            mode_ops = ()

        # Fused mode only ever reads the capacities that bound kernel blocks
        # (the dense gate and the tail); keying on the full tuple would
        # re-trace identical computations whenever the service ratchets an
        # early-stage bucket. Staged and auto read every entry (auto also
        # prices the staged branch with them).
        if run_mode != "fused":
            key_capacities = resolved
        else:
            key_capacities = (
                resolved[:1] if dense is not None else ()
            ) + resolved[-1:]
        key = (
            id(pf), config.stages, key_capacities, tree_strategies,
            tree_classifier_trees, run_mode,
            float(config.launch_overhead_trees), config.query_exit,
            traced_names, static_items, top_k,
        )
        step = self._step_cache.get(key)
        if step is None:
            body = _build_progressive_step(
                pf, dense, tree_sentinels, resolved, tree_strategies,
                tree_classifier_trees, run_mode, traced_names,
                dict(static_items), T,
                launch_overhead_trees=float(config.launch_overhead_trees),
                query_exit=config.query_exit,
            )
            if top_k is not None:
                body = _served(body, top_k, run_mode)
            step = jax.jit(body)
            self._step_cache[key] = step
            while len(self._step_cache) > _STEP_CACHE_MAX:
                self._step_cache.popitem(last=False)
        else:
            self._step_cache.move_to_end(key)

        traced_vals = tuple(strategy_kwargs[n] for n in traced_names)
        return step, (X, mask, traced_vals, mode_ops)


_STEP_CACHE_MAX = 16  # compiled progressive steps kept per ranker (LRU)


def _build_progressive_step(
    pf: PaddedForest,
    dense: DenseStage | None,
    sentinels: tuple[int, ...],
    capacities: tuple[int, ...],
    strategies: tuple,
    classifier_trees: tuple[float, ...],
    mode: str,
    traced_names: tuple[str, ...],
    static_kwargs: dict,
    n_trees: int,
    launch_overhead_trees: float = 0.0,
    query_exit: QueryExitConfig | None = None,
) -> Callable[..., tuple]:
    """Build the end-to-end progressive step body for one configuration,
    for the caller to ``jax.jit`` (alone, or :func:`_served`).

    Everything static (buffers, stages, capacities, mode) is closed over;
    the returned callable takes ``(X, mask, traced_vals, mode_ops)`` —
    ``mode_ops`` is ``()`` for the fixed modes and ``(stage_ema, have_ema,
    query_exit_rate)`` for ``mode="auto"`` — and compiles dense gate →
    head → decisions → compaction → tail → scatter into one XLA
    computation. Launch counters fire while THIS function's body traces
    (see :func:`repro.kernels.ops._counted_pallas`), so a compiled step
    re-executing from cache stages no new launches and moves no counters;
    under ``mode="auto"`` BOTH branch bodies trace into the one program,
    so each branch's launches are accounted exactly once even though only
    one branch executes per batch.

    ``sentinels``/``strategies``/``classifier_trees`` describe the TREE
    stages; ``capacities`` covers ALL stages (``capacities[0]`` is the
    dense gate's survivor block bound when ``dense`` is set). With a
    dense stage, both modes score the tree head on the SAME
    dense-compacted survivor block, so the per-block kernel sums — and
    therefore cross-mode bit-exactness on non-overflow batches — carry
    over unchanged from the all-trees engine: both modes accumulate
    prefixes with the same left-to-right association and identical
    per-doc segment sums, which is also what makes the ``lax.cond``
    branch structures compatible.
    """
    S = len(sentinels)
    has_tail = sentinels[-1] < n_trees
    # Accounting views: the dense stage charges `cost_trees` per candidate
    # document at "sentinel 0" (no trees traversed, one dense evaluation),
    # then the first tree stage charges its sentinel depth on the dense
    # survivors — trees_traversed_progressive handles that uniformly once
    # the dense stage is spliced in as a zero-sentinel stage.
    if dense is not None:
        acct_sentinels = (0, *sentinels)
        acct_costs = (float(dense.cost_trees), *classifier_trees)
        tree_caps = capacities[1:]
    else:
        acct_sentinels = sentinels
        acct_costs = classifier_trees
        tree_caps = capacities

    def final_tail(flat, scores, alive, overflow):
        # Tail launch on the compacted survivors of the last stage. In
        # fused mode only this compaction (plus the dense gate's, for
        # hybrid configs) can drop tail scores; staged mode accumulated
        # per-stage overflow before reaching here. With query-level exit
        # enabled the launch moves under a lax.cond on the survivor count
        # (counted "gated"): a batch whose queries all converged
        # dispatches no tail kernel.
        if not has_tail:
            return scores, overflow
        cap = capacities[-1]
        with jax.named_scope(SCOPE_COMPACTION):
            sel, n_cont = compact_indices_cumsum(alive.reshape(-1), cap)

        def run_tail(s, count_as="plain"):
            with jax.named_scope(SCOPE_COMPACTION):
                x_sel = jnp.take(flat, sel, axis=0)
            with jax.named_scope(SCOPE_TAIL):
                tail_sel = forest_score_range(
                    pf, x_sel, seg_lo=S, count_as=count_as
                )
            with jax.named_scope(SCOPE_COMPACTION):
                return _scatter_tail(s, sel, tail_sel, n_cont)

        if query_exit is None:
            scores = run_tail(scores)
        else:
            scores = jax.lax.cond(
                n_cont > 0, lambda s: run_tail(s, "gated"), lambda s: s,
                scores,
            )
        overflow = overflow + jnp.maximum(n_cont - cap, 0)
        return scores, overflow

    def apply_query_exit(stage_idx: int, prefix, alive, exited):
        # Fold the per-query convergence predicate into the alive mask:
        # once a query converges, none of its documents may re-enter
        # (exit flags accumulate like the nested per-doc stage masks).
        # Stage indices count ALL stages: the dense gate of a hybrid
        # config is stage 0, the first tree stage is stage 1.
        if query_exit is None or stage_idx < query_exit.from_stage:
            return alive, exited
        conv = query_converged(
            prefix, alive, k=query_exit.k, margin=query_exit.margin
        )
        exited = exited | conv
        return alive & ~exited[:, None], exited

    def dense_gate(flat, mask, skw):
        # Stage 0 of a hybrid cascade: score EVERY candidate through the
        # dense model in one matmul (pure XLA — no Pallas launch), prune
        # with the stage policy, then cumsum-compact the survivors into a
        # block of capacities[0]. The tree stages only ever see that
        # block, so a dense-exited document costs zero tree traversals.
        Q, D = mask.shape
        cap = capacities[0]
        d_scores = dense.scorer(flat).reshape(Q, D).astype(jnp.float32)
        # The dense policy sees (scores, mask) only — its knobs (and any
        # extra operands) live in the closure; **strategy_kwargs belong to
        # the tree strategies.
        alive = mask & dense.policy(d_scores, mask)
        exited = jnp.zeros((Q,), bool)
        alive, exited = apply_query_exit(0, d_scores, alive, exited)
        with jax.named_scope(SCOPE_COMPACTION):
            sel, n_cont, within = compact_indices_cumsum_masked(
                alive.reshape(Q * D), cap
            )
            overflow = jnp.maximum(n_cont - cap, 0)
            alive = alive & within.reshape(Q, D)
            x_sel = jnp.take(flat, sel, axis=0)
            valid = jnp.arange(cap) < n_cont
        return d_scores, alive, exited, overflow, sel, x_sel, valid

    def scatter_grid(vec, sel, valid, alive, fallback):
        # Compacted per-doc values back onto the [Q, D] grid: exact for
        # every alive doc (alive ⊆ within-capacity ⊆ scattered), the
        # fallback elsewhere (policies are mask-invariant, so stale slots
        # are never read where it matters).
        Q, D = fallback.shape
        with jax.named_scope(SCOPE_COMPACTION):
            grid = jnp.zeros((Q * D,), jnp.float32).at[sel].add(
                jnp.where(valid, vec, 0.0)
            ).reshape(Q, D)
            return jnp.where(alive, grid, fallback)

    def fused_tree_prefix_vecs(x_sel):
        # One launch over the head trees: prefix score of every survivor
        # at every sentinel, as compacted [C] vectors. A single segment
        # needs no segmented accumulator — it degenerates to the plain
        # kernel (same launch count, less work).
        with jax.named_scope(SCOPE_HEAD):
            if S == 1:
                return [forest_score_range(pf, x_sel, 0, 1)]
            seg = forest_score_segments(pf, x_sel, n_segments=S)
        acc = seg[:, 0] + pf.base_score
        vecs = [acc]
        for k in range(1, S):
            acc = acc + seg[:, k]
            vecs.append(acc)
        return vecs

    def fused_body(flat, mask, skw):
        Q, D = mask.shape
        if dense is None:
            # All-trees fused: the head launch scores the FULL block.
            alive = mask
            exited = jnp.zeros((Q,), bool)
            stage_masks = []
            with jax.named_scope(SCOPE_HEAD):
                if S == 1:
                    head = forest_score_range(pf, flat, 0, 1)
                else:
                    head = forest_score_segments(pf, flat, n_segments=S)
            if S == 1:
                prefixes = [head.reshape(Q, D)]
            else:
                seg = head.reshape(Q, D, S)
                acc = seg[..., 0] + pf.base_score
                prefixes = [acc]
                for k in range(1, S):
                    acc = acc + seg[..., k]
                    prefixes.append(acc)

            # Stage decisions: pure vector work, nested exit masks.
            scores = prefixes[0]
            for k in range(S):
                cont = strategies[k](prefixes[k], alive, **skw)
                alive = alive & cont
                alive, exited = apply_query_exit(
                    k, prefixes[k], alive, exited
                )
                stage_masks.append(alive)
                if k + 1 < S:
                    scores = jnp.where(alive, prefixes[k + 1], scores)
            scores, overflow = final_tail(flat, scores, alive, jnp.int32(0))
            return (
                scores, alive, tuple(stage_masks),
                jnp.stack(prefixes, axis=-1), overflow, exited,
            )

        # Hybrid fused: dense gate → ONE segmented head launch on the
        # dense-compacted survivor block → vector-work stage decisions on
        # the scattered prefix grids → one compacted tail.
        d_scores, alive, exited, overflow, sel, x_sel, valid = dense_gate(
            flat, mask, skw
        )
        stage_masks = [alive]
        vecs = fused_tree_prefix_vecs(x_sel)
        scores = d_scores
        grids = [d_scores]
        prev_grid = d_scores
        for k in range(S):
            grid = scatter_grid(vecs[k], sel, valid, alive, prev_grid)
            scores = jnp.where(alive, grid, scores)
            cont = strategies[k](grid, alive, **skw)
            alive = alive & cont
            alive, exited = apply_query_exit(k + 1, grid, alive, exited)
            stage_masks.append(alive)
            grids.append(grid)
            prev_grid = grid
        scores, overflow = final_tail(flat, scores, alive, overflow)
        return (
            scores, alive, tuple(stage_masks),
            jnp.stack(grids, axis=-1), overflow, exited,
        )

    def staged_body(flat, mask, skw):
        # Per-stage tails: segment k runs only on the compacted survivors
        # of stage k-1; every capacity is a real kernel bound with real
        # overflow accounting.
        Q, D = mask.shape
        if dense is None:
            alive = mask
            exited = jnp.zeros((Q,), bool)
            overflow = jnp.int32(0)
            with jax.named_scope(SCOPE_HEAD):
                prefix = forest_score_range(pf, flat, 0, 1).reshape(Q, D)
            stage_offset = 0
        else:
            d_scores, alive, exited, overflow, sel0, x_sel0, valid0 = (
                dense_gate(flat, mask, skw)
            )
            # First tree segment on the dense-compacted block — the same
            # block (and therefore the same per-doc kernel sums) the
            # fused head scores, which keeps the modes bit-exact.
            with jax.named_scope(SCOPE_HEAD):
                seg0 = forest_score_range(pf, x_sel0, 0, 1)
            prefix = scatter_grid(seg0, sel0, valid0, alive, d_scores)
            stage_offset = 1
        stage_masks = [alive] if dense is not None else []
        prefixes = [d_scores, prefix] if dense is not None else [prefix]
        for k in range(S):
            cont = strategies[k](prefix, alive, **skw)
            alive = alive & cont
            alive, exited = apply_query_exit(
                k + stage_offset, prefix, alive, exited
            )
            if k + 1 < S:
                cap = tree_caps[k]
                with jax.named_scope(SCOPE_COMPACTION):
                    sel, n_cont, within = compact_indices_cumsum_masked(
                        alive.reshape(Q * D), cap
                    )
                    overflow = overflow + jnp.maximum(n_cont - cap, 0)
                    alive = alive & within.reshape(Q, D)
                    x_sel = jnp.take(flat, sel, axis=0)
                # A middle stage scores trees the fused head scores.
                with jax.named_scope(SCOPE_HEAD):
                    seg_sel = forest_score_range(pf, x_sel, k + 1, k + 2)
                with jax.named_scope(SCOPE_COMPACTION):
                    prefix = jnp.where(
                        alive,
                        _scatter_tail(prefix, sel, seg_sel, n_cont),
                        prefix,
                    )
                prefixes.append(prefix)
            stage_masks.append(alive)
        scores, overflow = final_tail(flat, prefix, alive, overflow)
        return (
            scores, alive, tuple(stage_masks),
            jnp.stack(prefixes, axis=-1), overflow, exited,
        )

    def step(X, mask, traced_vals, mode_ops):
        Q, D, F = X.shape
        flat = X.reshape(Q * D, F)
        skw = {**dict(zip(traced_names, traced_vals)), **static_kwargs}

        if mode == "fused":
            out = fused_body(flat, mask, skw)
            picked = None
        elif mode == "staged":
            out = staged_body(flat, mask, skw)
            picked = None
        else:
            # On-device mode pick: price both modes from the traced
            # survivor estimate and run the cheaper branch. Both bodies
            # trace here (cond stages both); one executes per batch.
            stage_ema, have_ema, qe_rate = mode_ops
            fused_cost, staged_cost = progressive_cost_model_device(
                Q * D, stage_ema, sentinels, n_trees,
                launch_overhead_trees=launch_overhead_trees,
                stage_capacities=capacities,
                block_b=ENGINE_BLOCK_B,
                query_exit_rate=qe_rate,
                dense_cost_trees=(
                    float(dense.cost_trees) if dense is not None else 0.0
                ),
                dense_stage=dense is not None,
            )
            picked = jnp.logical_and(have_ema, staged_cost < fused_cost)
            out = jax.lax.cond(
                picked,
                lambda: staged_body(flat, mask, skw),
                lambda: fused_body(flat, mask, skw),
            )
        scores, alive, stage_masks, partials, overflow, exited = out
        sp = speedup_progressive(
            mask, list(stage_masks), acct_sentinels, n_trees,
            list(acct_costs),
        )
        traversed = trees_traversed_progressive(
            mask, list(stage_masks), acct_sentinels, n_trees,
            list(acct_costs),
        )
        return (
            scores, alive, stage_masks, partials, overflow, sp, picked,
            exited, traversed,
        )

    return step


def _served(
    body: Callable[..., tuple], top_k: int, mode: str
) -> Callable[..., tuple]:
    """``body`` followed by a served batch's response and stats (see
    :meth:`CascadeRanker.serve_progressive`), as one callable to jit."""

    def step(X, mask, traced_vals, mode_ops):
        (scores, _alive, stage_masks, _partials, overflow, _sp, picked,
         exited, traversed) = body(X, mask, traced_vals, mode_ops)
        masked = jnp.where(mask, scores, -jnp.inf)
        top_idx = jax.lax.top_k(masked, min(top_k, mask.shape[-1]))[1]
        staged = picked if picked is not None else jnp.bool_(mode == "staged")
        counts = jnp.stack([
            *(m.sum() for m in stage_masks), overflow, mask.sum(), staged,
            exited.sum(),
        ]).astype(jnp.int32)
        return top_idx, scores, traversed, counts

    return step


def _compacted_tail(
    X: jax.Array,
    partial: jax.Array,
    cont: jax.Array,
    tail: TreeEnsemble,
    capacity: int,
    compaction: str = "cumsum",
) -> tuple[jax.Array, jax.Array]:
    """Gather survivors → dense block of ``capacity`` → tail kernel → scatter.

    Kept at the Python level (jitted pieces around one counted kernel call)
    so launch accounting stays truthful.
    """
    Q, D, F = X.shape
    sel, n_cont = COMPACTORS[compaction](cont.reshape(Q * D), capacity)
    x_sel = jnp.take(X.reshape(Q * D, F), sel, axis=0)         # [C, F]
    tail_sel = forest_score(tail, x_sel)                       # [C]
    return _scatter_tail(partial, sel, tail_sel, n_cont), n_cont


@jax.jit
def _scatter_tail(
    scores: jax.Array, sel: jax.Array, tail_sel: jax.Array, n_cont: jax.Array
) -> jax.Array:
    """Scatter valid compacted tail scores back onto the [Q, D] grid."""
    Q, D = scores.shape
    valid = jnp.arange(sel.shape[0]) < n_cont
    deltas = jnp.zeros((Q * D,), jnp.float32).at[sel].add(
        jnp.where(valid, tail_sel, 0.0)
    )
    return scores + deltas.reshape(Q, D)
