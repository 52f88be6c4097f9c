"""Launch-overhead calibration: measure, don't guess, the cost model's knob.

:func:`repro.metrics.speedup.progressive_cost_model` prices one extra
kernel launch (dispatch + gather/scatter HBM round trip) at
``launch_overhead_trees`` doc·tree equivalents. PR 2 shipped a fixed
default; the right value is a property of the *machine* (dispatch latency
vs per-tree scoring throughput), not of the workload — so we measure it
once per process with a short timing probe and reuse it for every service.

The probe scores a tiny synthetic forest twice through the plain kernel —
once over a single tree block (launch-dominated) and once over the full
forest (tree-work-dominated) — and solves::

    per_doctree = (t_full − t_small) / (docs · (trees_full − trees_small))
    overhead_trees = max(t_small − per_doctree · docs · trees_small, 0)
                     / per_doctree

i.e. "the launch's fixed latency, expressed in doc·tree traversals". The
result is cached per backend (module-level) so constructing many
:class:`~repro.serve.ranking_service.RankingService` instances probes only
once, and can be recorded into ``BENCH_kernels.json`` (the kernel bench
does this) so the perf trajectory keeps the calibrated value alongside the
measured fused/staged crossover it should reproduce.

CPU-interpret caveat: on this container the kernel runs in interpret mode,
so the measured overhead is the interpreter's dispatch cost — large, but
directionally correct (staged mode's extra launches are genuinely more
expensive here). On a real TPU the same probe measures Mosaic dispatch.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.forest.ensemble import random_ensemble
from repro.kernels.ops import forest_score_range, padded_forest

DEFAULT_LAUNCH_OVERHEAD_TREES = 4096.0  # fallback when the probe degenerates

# One calibration per (backend, probe shape) per process; keyed so tests
# with a custom probe cannot poison the serving default.
_CALIBRATION_CACHE: dict = {}


def _min_time_us(
    fn: Callable[..., object], *args: object, iters: int
) -> float:
    fn(*args)  # compile / warm caches outside the timed window
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def calibrate_launch_overhead_trees(
    n_docs: int = 128,
    n_trees: int = 64,
    block_t: int = 16,
    iters: int = 5,
    record_path: str | None = None,
) -> float:
    """Measure launch latency in doc·tree equivalents (cached per backend).

    Returns the calibrated ``launch_overhead_trees`` for the current jax
    backend. Degenerate measurements (non-positive per-tree slope, e.g. on
    a noisy box where the small launch out-timed the big one) fall back to
    :data:`DEFAULT_LAUNCH_OVERHEAD_TREES`, and the report says so
    (``"fell_back": true`` in :func:`last_calibration`). With
    ``record_path`` the probe merges its report under
    ``"launch_calibration"`` into that JSON file —
    an operator-facing hook for deployments that track the value out of
    band. The kernel bench does NOT use it (its ``main()`` rewrites
    ``BENCH_kernels.json`` wholesale); it embeds :func:`last_calibration`
    into its own payload instead.
    """
    key = (jax.default_backend(), n_docs, n_trees, block_t)
    cached = _CALIBRATION_CACHE.get(key)
    if cached is not None:
        if record_path is not None:
            _record(record_path, cached)
        return cached["launch_overhead_trees"]

    # A probe-only forest: shape matters (one aligned block vs the full
    # range), values do not. Segment 0 is exactly one tree block so the
    # small launch is as launch-dominated as the kernel allows.
    ens = random_ensemble(0, n_trees=n_trees, depth=3, n_features=16)
    pf = padded_forest(ens, boundaries=(block_t, n_trees), block_t=block_t)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(n_docs, 16)).astype(np.float32)
    )

    t_small = _min_time_us(
        lambda v: forest_score_range(pf, v, 0, 1), x, iters=iters
    )
    t_full = _min_time_us(
        lambda v: forest_score_range(pf, v, 0, 2), x, iters=iters
    )

    per_doctree = (t_full - t_small) / max(n_docs * (n_trees - block_t), 1)
    fell_back = per_doctree <= 0
    if fell_back:
        overhead = DEFAULT_LAUNCH_OVERHEAD_TREES
    else:
        launch_us = max(t_small - per_doctree * n_docs * block_t, 0.0)
        overhead = launch_us / per_doctree

    payload = {
        "backend": jax.default_backend(),
        "probe_docs": n_docs,
        "probe_trees": n_trees,
        "block_t": block_t,
        "t_small_us": round(t_small, 1),
        "t_full_us": round(t_full, 1),
        "per_doctree_us": round(per_doctree, 6),
        "launch_overhead_trees": overhead,
        "fell_back": fell_back,  # degenerate probe → the default above
    }
    _CALIBRATION_CACHE[key] = payload
    if record_path is not None:
        _record(record_path, payload)
    return overhead


def last_calibration() -> dict | None:
    """Most recent probe report (for embedding in bench payloads)."""
    return next(reversed(_CALIBRATION_CACHE.values()), None) \
        if _CALIBRATION_CACHE else None


def expected_engine_seconds(n_docs: int, n_trees: int) -> float:
    """Prior estimate of one engine call's wall time, in seconds.

    Extrapolates the calibration probe's per-doc·tree slope to a full
    block of ``n_docs × n_trees`` work plus one launch overhead — the
    batcher's deadline-aware flush scheduler uses this as the cold-start
    prior before it has observed real flush times for a bucket. Returns
    ``0.0`` when no probe has run in this process (the scheduler then
    assumes the engine is instant, i.e. legacy flush timing).
    """
    cal = last_calibration()
    if cal is None:
        return 0.0
    per_us = float(cal["per_doctree_us"])
    overhead_trees = float(cal["launch_overhead_trees"])
    return max(per_us * (n_docs * n_trees + overhead_trees), 0.0) * 1e-6


def _record(path: str, payload: dict) -> None:
    """Merge the calibration under ``"launch_calibration"``; never raise —
    a read-only checkout or a corrupt target file must not take the
    serving path down (ValueError covers json.JSONDecodeError)."""
    with contextlib.suppress(OSError, ValueError):
        doc = {}
        if os.path.exists(path):
            with open(path) as f:
                doc = json.load(f)
        if not isinstance(doc, dict):
            doc = {}
        doc["launch_calibration"] = payload
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
