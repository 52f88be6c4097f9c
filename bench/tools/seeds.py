#!/usr/bin/env python3
"""Read the checked numbers of many seeds, and the control's, in one process.

    python3 bench/tools/seeds.py --workload msn1-steady --seeds 101-112 \
        --seconds 5 [--out readings.json]

Builds and warms the cell's tier once, then per seed drives a window of
the cell's own traffic at its own load through ``tier.submit`` and checks
the seeded sample twice: the program's answers (the lower readings of
each limit) and the bfloat16 control in the program's place (the upper
readings). This is what ``bench/run.py`` checks, without paying the
set-up once per seed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, models, traffic  # noqa: E402


def seed_list(text: str) -> list[int]:
    if "-" in text:
        lo, hi = (int(x) for x in text.split("-"))
        return list(range(lo, hi + 1))
    return [int(x) for x in text.split(",")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
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
        for seed in seed_list(args.seeds):
            tr = traffic.generate(cell.mix, cell.config["n_features"],
                                  args.seconds, seed)
            win = harness.drive(tier, tr, cell.mix, args.seconds,
                                harness.check_sample(tr, seed))
            t0 = time.perf_counter()
            program = harness.check(model, tr, win, tier.service.top_k)
            t_check = time.perf_counter() - t0
            control = harness.check(model, tr, win, tier.service.top_k,
                                    control=True)
            row = {
                "seed": seed, "requests": len(win.due),
                "checked": program.checked, "docs": program.docs,
                "undecided": program.undecided,
                "continued": program.continued, "check_s": t_check,
                "program": {k: v for k, (v, _) in program.numbers.items()},
                "program_correct": program.correct,
                "control": {k: v for k, (v, _) in control.numbers.items()},
                "control_correct": control.correct,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        tier.stop()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
