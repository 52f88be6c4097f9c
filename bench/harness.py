"""Run one cell of ``BENCHMARK.json`` once: set-up, window, check, result.

Everything about a cell is found by name: the workload entry names its
configuration (``configs[].file``) and its traffic mix
(``bench/mixes/<traffic>.json``), and each per-layer metric is read by
``bench/metrics/<name>.py``, ``<name>`` being the metric's name up to its
first ``.`` (``step_ms.steady`` and ``step_ms.bulk`` both take
``step_ms.py``). No cell has a branch of its own.

The run, in one process (a chip belongs to one process):

1. the models and the threshold from the configuration
   (:mod:`bench.models`), then a ``RankingService`` at its default
   ``ServiceConfig`` and a ``ServingTier`` on ``single_device()`` or, for
   a cell of several chips, ``data_parallel(chips)``;
2. ``tier.start()``: the persistent compile cache and the warm-up of the
   ``(Q, D)`` buckets of the mix's list lengths, and no others;
3. the window: every request goes through ``tier.submit``. An open loop
   sends on its schedule and times each request from when it was due to
   when its future resolved; a closed loop keeps ``in_flight`` requests
   outstanding. ``setup_s`` ends when the first request is sent;
4. after the window: every request's answer (a minute's grace), the
   device's memory peak, then the check of a seeded sample of answers
   against :mod:`bench.reference` (:func:`check`);
5. with ``--trace 1`` the window runs under the profiler with the
   harness's spans, and the per-layer readers take their numbers from the
   counters, the spans and the reduced trace (:mod:`bench.trace`).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import time
from collections.abc import Callable
from pathlib import Path

import numpy as np

from bench import latency, models, reference, traffic, work

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent
#: The forest kernel's launches in the trace: every ``_forest_kernel``
#: ``pallas_call`` runs as a custom call named after its jitted entry point.
FOREST_KERNEL = "forest_score"
#: Requests checked against the reference per run, drawn from the seed, on
#: top of the longest request of each document bucket.
CHECK_SAMPLE = 128
#: How long after the window closes an answer may still come. A request
#: that was not refused at submit and has no answer by then, or whose
#: future holds an error, is ``unanswered``.
GRACE_S = 60.0
#: Limits of the numbers compared (see PERF.md for the readings they were
#: set from). ``score_err`` is the largest gap between a served score and
#: the float64 reference's; two documents whose reference scores lie
#: within ``TIE`` of each other may swap places in the top-k.
SCORE_ERR_LIMIT = 1e-2
TIE = 2 * SCORE_ERR_LIMIT


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload of ``root/BENCHMARK.json`` to its files."""
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "mixes" / f"{w['traffic']}.json") as f:
        mix = json.load(f)

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, metrics_dir: Path = BENCH_DIR / "metrics"
           ) -> Callable[[WindowRecord], float | None]:
    """The ``read`` function of ``metrics_dir/<stem>.py``, ``<stem>`` being
    the metric's name up to its first ``.``."""
    stem = metric.split(".")[0]
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{stem}", metrics_dir / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class WindowRecord:
    """What a traced window leaves for the per-layer readers."""

    cell: Cell
    batcher: dict[str, float]         # BatcherStats deltas, window + drain
    service: dict[str, float]         # ServiceStats deltas, window + drain
    steps_s: list[float]              # host seconds of each engine call
    summary: object | None            # bench.trace.Summary, traced runs
    peak: work.Peak
    chips: int

    @property
    def shape(self) -> work.Shape:
        return work.Shape.of(self.cell.config)

    @property
    def flushes(self) -> float:
        b = self.batcher
        return b["flushes_full"] + b["flushes_deadline"] + b["flushes_drain"]

    def forest_ops(self) -> float:
        return work.forest_ops(self.shape, self.service["docs"],
                               self.service["docs_continued"])

    def forest_bytes(self) -> float:
        return work.forest_bytes(self.shape, self.service["docs"],
                                 self.service["docs_continued"],
                                 self.flushes * self.chips)


def check_device(chips: int):
    """The TPU devices a cell of ``chips`` chips runs on."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def build_tier(cell: Cell, model: reference.Model):
    """The program under test: service, placement and (unstarted) tier."""
    from repro.core.lear import LearClassifier
    from repro.forest.ensemble import from_complete_arrays
    from repro.serve.batching import BucketPolicy
    from repro.serve.placement import data_parallel, single_device
    from repro.serve.ranking_service import RankingService, ServiceConfig
    from repro.serve.tier import ServingTier, TierConfig

    def ensemble(f: reference.Forest):
        return from_complete_arrays(f.feature, f.threshold, f.leaf)

    service = RankingService(
        ensemble(model.ranker),
        LearClassifier(ensemble(model.classifier), model.sentinel),
        ServiceConfig(threshold=models.probability(model.logit_threshold)),
    )
    policy = BucketPolicy()
    if "max_queue_depth" in cell.mix:
        policy = dataclasses.replace(
            policy, max_queue_depth=int(cell.mix["max_queue_depth"]))
    lo, hi = traffic.doc_range(cell.mix)
    buckets = sorted({policy.doc_bucket(n) for n in range(lo, hi + 1)})
    placement = data_parallel(cell.chips) if cell.chips > 1 else (
        single_device())
    return ServingTier(service, cell.config["n_features"],
                       TierConfig(doc_counts=tuple(buckets)), policy=policy,
                       placement=placement)


class Spans:
    """Host spans of a traced run: ``TraceAnnotation`` around the engine
    calls and flushes of the batcher worker, timing each engine call."""

    def __init__(self, tier) -> None:
        from jax.profiler import TraceAnnotation

        self.steps: list[tuple[float, float]] = []
        service, batcher = tier.service, tier.batcher
        rank_batch, flush = service.rank_batch, batcher._flush

        @functools.wraps(rank_batch)
        def timed_step(*args, **kwargs):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.step"):
                out = rank_batch(*args, **kwargs)
            self.steps.append((t0, time.perf_counter() - t0))
            return out

        @functools.wraps(flush)
        def traced_flush(*args, **kwargs):
            with TraceAnnotation("bench.flush"):
                return flush(*args, **kwargs)

        service.rank_batch = timed_step
        batcher._flush = traced_flush


@dataclasses.dataclass
class Window:
    """Per request sent, in order: when it was due (an open loop's schedule,
    a closed loop's send time), when its future resolved (inf if never),
    whether it was answered, and, for the requests drawn for the check,
    the answer itself. Nothing else of an answer is kept, so the window
    leaves the process little to collect."""

    due: list[float]
    done_at: list[float]
    ok: list[bool | None]               # None: no answer; False: error
    shed: list[bool]                    # refused at submit
    results: dict[int, tuple]           # the check's sample: (top, scores)
    start: float
    end: float                          # start + seconds
    drained: float                      # every answer in (or grace over)
    lag_s: list[float]                  # open loop: send time - due time

    def answered(self, i: int) -> bool:
        return self.ok[i] is True

    def latencies_ms(self) -> list[float]:
        """From due time to answer; +inf for a request never answered."""
        return [(d - t) * 1e3 if ok is True else math.inf
                for d, t, ok in zip(self.done_at, self.due, self.ok)]


def check_sample(tr: traffic.Traffic, seed: int) -> set[int]:
    """The requests whose answers the check compares: a seeded sample and
    the longest request of each document bucket (a closed loop's first
    pass through its cycle)."""
    gen = models.rng(seed, 20)
    n = len(tr)
    pick = set(gen.choice(n, size=min(CHECK_SAMPLE, n),
                          replace=False).tolist())
    longest: dict[int, int] = {}
    for i, size in enumerate(tr.sizes.tolist()):
        b = max(8, 1 << (size - 1).bit_length())
        if b not in longest or size > tr.sizes[longest[b]]:
            longest[b] = i
    return pick | set(longest.values())


def _submit(tier, feats, annotate):
    if annotate is None:
        return tier.submit(feats)
    with annotate("bench.submit"):
        return tier.submit(feats)


def drive(tier, tr: traffic.Traffic, mix: dict, seconds: float,
          keep: set[int], annotate=None) -> Window:
    """Send the window's traffic; return once every answer is in or the
    grace period is over. ``keep`` names the requests whose answers the
    check will read."""
    from repro.serve.errors import Overloaded

    due, done_at, ok, shed, lag = [], [], [], [], []
    results: dict[int, tuple] = {}
    inflight: dict[int, object] = {}
    completions: queue.SimpleQueue = queue.SimpleQueue()

    def on_done(i: int, fut) -> None:
        done_at[i] = time.perf_counter()
        good = not fut.cancelled() and fut.exception() is None
        if good and i in keep:
            results[i] = fut.result()
        ok[i] = good
        inflight.pop(i, None)
        completions.put(i)

    def send(i: int, t_due: float) -> None:
        done_at.append(math.inf)
        due.append(t_due)
        ok.append(None)
        try:
            fut = _submit(tier, tr.features(i), annotate)
        except Overloaded:
            ok[i] = False
            shed.append(True)
            return
        shed.append(False)
        inflight[i] = fut
        fut.add_done_callback(functools.partial(on_done, i))

    start = time.perf_counter()
    end = start + seconds
    if mix["loop"] == "open":
        for i in range(len(tr)):
            t_due = start + float(tr.due[i])
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            lag.append(time.perf_counter() - t_due)
            send(i, t_due)
        time.sleep(max(end - time.perf_counter(), 0.0))
    else:
        for i in range(int(mix["in_flight"])):
            send(i, time.perf_counter())
        while (left := end - time.perf_counter()) > 0:
            try:
                completions.get(timeout=left)
            except queue.Empty:
                break
            send(len(due), time.perf_counter())
    deadline = time.perf_counter() + GRACE_S
    for fut in list(inflight.values()):
        try:
            fut.exception(timeout=max(deadline - time.perf_counter(), 0.0))
        except TimeoutError:
            break
    return Window(due=due, done_at=done_at, ok=ok, shed=shed,
                  results=results, start=start, end=end,
                  drained=time.perf_counter(), lag_s=lag)


@dataclasses.dataclass
class Check:
    numbers: dict[str, tuple[float, float]]   # name -> (value, limit)
    checked: int
    docs: int
    undecided: int
    continued: int

    @property
    def correct(self) -> bool:
        return self.checked > 0 and all(
            v <= lim for v, lim in self.numbers.values())


def check(model: reference.Model, tr: traffic.Traffic, win: Window,
          top_k: int, control: bool = False) -> Check:
    """Compare the answers of the drawn sample (``Window.results``)
    against the float64 reference; every request sent and neither
    refused nor answered counts as ``unanswered``.

    ``control`` puts the bfloat16 replay in the program's place: its own
    decisions and scores are checked instead of the served ones."""
    unanswered = sum(1 for i, s in enumerate(win.shed)
                     if not s and not win.answered(i))
    worst, misordered, docs, undecided, continued = 0.0, 0, 0, 0, 0
    for i in sorted(win.results):
        X = tr.features(i)
        ref = reference.replay(model, X)
        if control:
            scores = reference.replay(model, X, "bfloat16").served()
            top = reference.served_top_k(scores, top_k)
        else:
            top, scores = win.results[i]
            scores = np.asarray(scores, np.float64)
        worst = max(worst, reference.score_error(ref, scores))
        misordered += not reference.top_k_ok(ref, scores, top, top_k, TIE)
        docs += X.shape[0]
        undecided += int(ref.undecided.sum())
        continued += int(ref.cont.sum())
    return Check(
        numbers={
            "score_err": (worst, SCORE_ERR_LIMIT),
            "topk_misordered": (float(misordered), 0.0),
            "unanswered": (float(unanswered), 0.0),
        },
        checked=len(win.results), docs=docs, undecided=undecided,
        continued=continued,
    )


def _stats(tier) -> tuple[dict, dict]:
    b = dataclasses.asdict(tier.batcher.stats)
    s = dataclasses.asdict(tier.service.stats)
    return b, s


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    """The cell's end-to-end metrics from the untraced window."""
    lat_ms = win.latencies_ms()
    values = {"setup_s": setup_s}
    # p99_ms stays measurable so that a cell can name it in BENCHMARK.json.
    if cell.mix["loop"] == "open":
        values["p50_ms"] = latency.percentile(lat_ms, 50)
        values["p99_ms"] = latency.percentile(lat_ms, 99)
    values["qps"] = latency.completed_rate(win.done_at, win.start, win.end)
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise KeyError(f"the harness does not measure {m['name']!r} "
                           f"for a {cell.mix['loop']} loop")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, control: bool = False,
             tamper: Callable[[object], None] | None = None,
             log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    if require_tpu:
        devices = check_device(cell.chips)
    else:
        devices = jax.devices()
    devices = devices[:cell.chips]
    from repro.serve.warmup import enable_persistent_cache
    from repro.utils import count_lowerings

    cache_dir = enable_persistent_cache()
    model = models.build(cell.config)
    tier = build_tier(cell, model)
    tr = traffic.generate(cell.mix, cell.config["n_features"], seconds, seed)
    keep = check_sample(tr, seed)
    tier.start()
    if tamper is not None:
        tamper(tier.service)
    spans = Spans(tier) if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    annotate = None
    if trace:
        from jax.profiler import ProfileOptions, TraceAnnotation

        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = TraceAnnotation
    gc.collect()
    gc.freeze()
    before = _stats(tier)
    t_first = time.perf_counter()
    setup_s = t_first - t_start
    try:
        with count_lowerings() as lowerings:
            if trace:
                with TraceAnnotation("bench.window"):
                    win = drive(tier, tr, cell.mix, seconds, keep, annotate)
            else:
                win = drive(tier, tr, cell.mix, seconds, keep)
        after = _stats(tier)
    finally:
        if trace:
            jax.profiler.stop_trace()
        tier.stop()
    mem_peak = memory_peak(devices)
    gc.unfreeze()
    verdict = check(model, tr, win, tier.service.top_k, control)
    attempted = len(win.due)
    failed = sum(not win.answered(i) for i in range(attempted))
    lag_ms = sorted(x * 1e3 for x in win.lag_s)
    print(f"[{cell.name}] seed={seed} cache={cache_dir} "
          f"setup_s={setup_s:.3f} attempted={attempted} failed={failed} "
          f"lowerings_in_window={lowerings()} "
          f"generator_lag_ms p50={_q(lag_ms, 50)} p99={_q(lag_ms, 99)} "
          f"max={lag_ms[-1] if lag_ms else 0.0}", file=log)
    b, s = _delta(after[0], before[0]), _delta(after[1], before[1])
    print(f"[{cell.name}] window: flushes full={b['flushes_full']} "
          f"deadline={b['flushes_deadline']} drain={b['flushes_drain']} "
          f"completed={b['completed']} padded_query_slots="
          f"{b['padded_query_slots']} docs={s['docs']} continued="
          f"{s['docs_continued']} overflow_docs={s['overflow_docs']}",
          file=log)
    print(f"[{cell.name}] check: {verdict.checked} requests, "
          f"{verdict.docs} docs, {verdict.continued} continue in the "
          f"reference, {verdict.undecided} undecided", file=log)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem_peak}
    result = {"correct": verdict.correct, "attempted": attempted,
              "failed": failed}
    if trace:
        from bench import trace as trace_mod

        try:
            summary = trace_mod.summarize(trace_mod.load(trace_dir),
                                          FOREST_KERNEL, cell.chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        record = WindowRecord(
            cell=cell, batcher=b, service=s,
            steps_s=[dt for t0, dt in spans.steps
                     if win.start <= t0 < win.drained],
            summary=summary, peak=work.peak(devices[0].device_kind),
            chips=cell.chips)
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, v] for n, v in summary.device_ops],
            "idle_gaps": [[n, v] for n, v in summary.idle],
        }
    else:
        result["metrics"] = end_to_end(cell, win, setup_s)
        result["device"] = device
    for name, (value, limit) in verdict.numbers.items():
        print(f"check {name} = {value!r} (limit {limit!r})", file=log)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in verdict.numbers.items()}
    return result


def _q(sorted_ms: list[float], q: float) -> float:
    return latency.percentile(sorted_ms, q) if sorted_ms else 0.0


def main(argv: list[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one BENCHMARK.json cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result))
    return 0
