#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the machine it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the run's checks as the last lines of standard error and its result
as one JSON object on the last line of standard output. Exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell asks
for, or where the program under test (``src/``) is not beside it.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench/run.py: the program under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        sys.exit(2)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import main

    sys.exit(main(sys.argv[1:], T_START))
