#!/usr/bin/env python3
"""Cut a short stretch out of a profiler trace, for the reduction's test.

    python3 bench/tools/trim_trace.py TRACE_DIR OUT.pbtxt --ms 20 [--skip-ms 500]

Keeps, from ``--skip-ms`` after the start of the ``bench.window`` span and
for ``--ms`` milliseconds: the op line of every TPU device plane (each op
named by the first 160 characters of its HLO text) and the harness's
``bench.*`` spans of the host plane, clipped to the stretch (the window span becomes the stretch
itself). Writes the result as a text-format XSpace, which
``jax.profiler.ProfileData.from_text_proto`` reads.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _plane(pid: int, name: str, lines: list) -> str:
    """``lines``: ``(name, [(event_name, start_ns, end_ns, stats)])``."""
    names, stat_names, out = {}, {}, []
    out.append(f"planes {{\n  id: {pid}\n  name: {_quote(name)}")
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(lname)}"
                   f"\n    timestamp_ns: 0")
        for ename, a, b, stats in events:
            mid = names.setdefault(ename, len(names) + 1)
            st = "".join(
                f" stats {{ metadata_id: "
                f"{stat_names.setdefault(k, len(stat_names) + 1)} "
                f"str_value: {_quote(v)} }}" for k, v in stats)
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{int(round(a * 1000))} duration_ps: "
                       f"{int(round((b - a) * 1000))}{st} }}")
        out.append("  }")
    for n, i in names.items():
        out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {_quote(n)} }} }}")
    for n, i in stat_names.items():
        out.append(f"  stat_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {_quote(n)} }} }}")
    out.append("}")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=20.0)
    ap.add_argument("--skip-ms", type=float, default=500.0)
    args = ap.parse_args()
    pd = trace.load(args.trace_dir)
    spans = trace.host_spans(pd)
    lo = spans[trace.WINDOW][0][0] + args.skip_ms * 1e6
    hi = lo + args.ms * 1e6
    planes = []
    for d, events in sorted(trace.device_ops(pd).items()):
        kept = [(n[:160], max(a, lo) - lo, min(b, hi) - lo, [])
                for n, a, b, _ in events if b > lo and a < hi]
        planes.append((f"/device:TPU:{d}", [(trace.OPS_LINE, kept)]))
    host = [(trace.WINDOW, 0.0, hi - lo, [])]
    for name in (trace.STEP, trace.FLUSH, trace.SUBMIT):
        host += [(name, a - lo, b - lo, [])
                 for a, b in trace.clip(spans.get(name, []), lo, hi)]
    planes.append((trace.HOST_PLANE, [("bench", host)]))
    text = "\n".join(_plane(i, n, lines)
                     for i, (n, lines) in enumerate(planes, 1))
    with open(args.out, "w") as f:
        f.write(text + "\n")
    print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
