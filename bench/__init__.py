"""The benchmark of record: LEAR served through ``ServingTier`` on a TPU.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything a cell needs is found
by name: its configuration in ``bench/configs/<config>.json``, its traffic
mix in ``bench/mixes/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/``. Nothing here imports the program except the harness,
which drives it; the reference, the traffic generators, the work counts and
the trace reduction stand on their own.
"""
