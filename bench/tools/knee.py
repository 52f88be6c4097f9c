#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate the tier keeps up with.

    python3 bench/tools/knee.py --workload msn1-steady \
        --rates 500,1000,2000 --seconds 6 [--trace-at 1000 --trace-dir D]

One process builds and warms the cell's tier once, then offers each rate
for ``--seconds`` with the cell's own list lengths and reports, per rate:
the rate served (answers completed in the window over the window), p50
and p99 from due time, and the backlog trend (median latency of the last
tenth of the requests over that of the first tenth). The knee is the
highest rate at which the served rate keeps up (>= 98% of offered) and
the backlog does not grow (trend < 2). ``--trace-at`` also records a
profiler trace of a short window at that rate into ``--trace-dir``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, latency, models, traffic  # noqa: E402


def one_rate(tier, cell, rate, seconds, seed, annotate=None):
    mix = dict(cell.mix, loop="open", rate_qps=rate)
    tr = traffic.generate(mix, cell.config["n_features"], seconds, seed)
    keep = harness.check_sample(tr, seed)
    win = harness.drive(tier, tr, mix, seconds, keep, annotate)
    lat = win.latencies_ms()
    tenth = max(1, len(lat) // 10)
    first = statistics.median(lat[:tenth])
    last = statistics.median(lat[-tenth:])
    return tr, win, {
        "offered_qps": rate,
        "served_qps": latency.completed_rate(win.done_at, win.start,
                                             win.end),
        "p50_ms": latency.percentile(lat, 50),
        "p99_ms": latency.percentile(lat, 99),
        "backlog_trend": last / first if first > 0 else float("inf"),
        "failed": sum(not win.answered(i) for i in range(len(lat))),
        "drain_s": win.drained - win.end,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace-at", type=float, default=None)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    from repro.serve.warmup import enable_persistent_cache

    enable_persistent_cache()
    model = models.build(cell.config)
    tier = harness.build_tier(cell, model)
    tier.start()
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    rows = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr, win, row = one_rate(tier, cell, rate, args.seconds,
                                    args.seed + k)
            rows.append(row)
            print(json.dumps(row), flush=True)
        if args.trace_at:
            import jax
            from jax.profiler import ProfileOptions, TraceAnnotation

            harness.Spans(tier)
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(args.trace_dir,
                                     profiler_options=opts)
            with TraceAnnotation("bench.window"):
                tr, win, row = one_rate(tier, cell, args.trace_at, 2.0,
                                        args.seed + 100, TraceAnnotation)
            jax.profiler.stop_trace()
            print("traced", json.dumps(row), flush=True)
    finally:
        tier.stop()
    verdict = harness.check(model, tr, win, tier.service.top_k)
    print(f"check of the last window: {verdict.numbers} correct="
          f"{verdict.correct}", flush=True)
    keeps = [r for r in rows if r["served_qps"] >= 0.98 * r["offered_qps"]
             and r["backlog_trend"] < 2.0 and r["failed"] == 0]
    knee = max((r["offered_qps"] for r in keeps), default=None)
    print(f"knee {knee}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows, "knee": knee},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
