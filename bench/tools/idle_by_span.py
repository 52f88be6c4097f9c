#!/usr/bin/env python3
"""Split a traced window's device idle time by the program's own spans.

    python3 bench/tools/idle_by_span.py TRACE_DIR [--devices 1] [--out R.json]
        [--fixture OUT.pbtxt --ms 20 --skip-ms 500] [--stall-ms 50]

``TRACE_DIR`` is what ``bench/tools/knee.py --trace-at R --trace-dir
TRACE_DIR`` writes. Reduced with :mod:`bench.trace`'s functions, inside the
``bench.window`` span, it reports:

- device 0's idle seconds, split by the innermost ``lear.*`` span of the
  serving path (``repro.serve.tracing``) open on the host when the device
  sat idle, plus ``none`` (no span open);
- the forest launches' device seconds (on the first ``--devices``
  devices), split by the LEAR stage scope (``lear/head``,
  ``lear/classifier``, ``lear/tail``) found in the op event's name or
  stats; where no launch carries a scope the split is ``null``
  (unknown), never guessed;
- each idle gap of at least ``--stall-ms``, with the spans it fell in.

``--fixture`` also writes a trimmed text-format XSpace of ``--ms``
milliseconds from ``--skip-ms`` into the window: every device op (the
forest launches with the stat that named their scope) and the ``lear.*``
spans per host thread, which ``ProfileData.from_text_proto`` reads back.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.tools.trim_trace import _plane  # noqa: E402

#: The serving path's host spans all start with this.
SPAN_PREFIX = "lear."
NO_SPAN = "none"
STAGES = ("lear/head", "lear/classifier", "lear/tail")
_STAGE = re.compile(r"(?:^|/)(lear/(?:head|classifier|tail))(?:/|$)")


@dataclasses.dataclass(frozen=True)
class Report:
    window_s: float
    idle_s: float                                  # device 0
    idle_by_span: list[tuple[str, float]]          # innermost span → s
    forest_s: float                                # all kept devices
    forest_by_stage: dict[str, float] | None       # None: no scope seen
    stalls: list[dict]                             # long idle gaps


def lear_spans(pd) -> list[list[tuple[str, float, float]]]:
    """The ``lear.*`` spans of each host line (thread), as
    ``(name, start_ns, end_ns)``."""
    plane = pd.find_plane_with_name(trace.HOST_PLANE)
    if plane is None:
        return []
    out = []
    for line in plane.lines:
        spans = [(n, a, b) for n, a, b, _ in trace._events(line)
                 if n.startswith(SPAN_PREFIX)]
        if spans:
            out.append(spans)
    return out


def innermost(spans: list[tuple[str, float, float]]
              ) -> dict[str, list[tuple[float, float]]]:
    """For one thread's properly nested spans: by name, the intervals in
    which that span is the innermost one open (its own time less its
    children's)."""
    out: dict[str, list[tuple[float, float]]] = defaultdict(list)
    stack: list[list] = []            # [name, start, end, resume]

    def close(top):
        name, _, end, resume = top
        if resume < end:
            out[name].append((resume, end))

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            if parent[3] < a:
                out[parent[0]].append((parent[3], a))
            parent[3] = max(parent[3], min(b, parent[2]))
        stack.append([name, a, b, a])
    while stack:
        close(stack.pop())
    return {k: trace.merge(v) for k, v in out.items()}


def stage_of(event) -> str | None:
    """The LEAR stage scope in an op event's name or stats, if any."""
    texts = [event.name] + [str(v) for _, v in event.stats]
    for text in texts:
        m = _STAGE.search(text)
        if m:
            return m.group(1)
    return None


def _window(pd) -> tuple[float, float]:
    spans = trace.host_spans(pd)
    if not spans.get(trace.WINDOW):
        raise ValueError(f"the trace has no {trace.WINDOW!r} span")
    return spans[trace.WINDOW][0][0], spans[trace.WINDOW][-1][1]


def reduce(pd, devices: int = 1, kernel: str = "forest_score",
           stall_ms: float = 50.0) -> Report:
    lo, hi = _window(pd)
    per_device = trace.device_ops(pd)
    ordinals = sorted(per_device)[:devices]
    if not ordinals:
        raise ValueError("the trace has no TPU device plane")
    busy0 = trace.merge(trace.clip(
        [(a, b) for _, a, b, _ in per_device[ordinals[0]]], lo, hi))
    idle = trace.gaps(busy0, lo, hi)

    by_name: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for spans in lear_spans(pd):
        for name, iv in innermost(spans).items():
            by_name[name] += iv
    by_name = {k: trace.merge(v) for k, v in by_name.items()}
    any_span = trace.merge([iv for v in by_name.values() for iv in v])
    split = {k: trace.length(trace.overlap(idle, v)) * 1e-9
             for k, v in by_name.items()}
    split[NO_SPAN] = (trace.length(idle)
                      - trace.length(trace.overlap(idle, any_span))) * 1e-9

    forest_ns, staged_ns = 0.0, defaultdict(float)
    for d in ordinals:
        for n, a, b, e in per_device[d]:
            if b <= lo or a >= hi or not trace.is_kernel(n, kernel):
                continue
            t = min(b, hi) - max(a, lo)
            forest_ns += t
            staged_ns[stage_of(e) or "unscoped"] += t
    scoped = any(k != "unscoped" for k in staged_ns)
    forest_by_stage = None
    if scoped:
        forest_by_stage = {k: staged_ns.get(k, 0.0) * 1e-9
                           for k in (*STAGES, "unscoped")}

    stalls = []
    for a, b in idle:
        if (b - a) < stall_ms * 1e6:
            continue
        inside = {k: trace.length(trace.overlap([(a, b)], v)) * 1e-9
                  for k, v in by_name.items()}
        stalls.append({
            "at_s": (a - lo) * 1e-9,
            "idle_s": (b - a) * 1e-9,
            "spans": sorted(((k, v) for k, v in inside.items() if v > 0),
                            key=lambda kv: -kv[1]),
        })
    return Report(
        window_s=(hi - lo) * 1e-9,
        idle_s=trace.length(idle) * 1e-9,
        idle_by_span=sorted(split.items(), key=lambda kv: -kv[1]),
        forest_s=forest_ns * 1e-9,
        forest_by_stage=forest_by_stage,
        stalls=stalls,
    )


def fixture(pd, ms: float, skip_ms: float) -> str:
    """A trimmed text-format XSpace of the stretch (module docstring)."""
    lo = _window(pd)[0] + skip_ms * 1e6
    hi = lo + ms * 1e6
    planes = []
    for d, events in sorted(trace.device_ops(pd).items()):
        kept = []
        for n, a, b, e in events:
            if b <= lo or a >= hi:
                continue
            stats = [(k, str(v)) for k, v in e.stats
                     if _STAGE.search(str(v))][:1]
            kept.append((n[:160], max(a, lo) - lo, min(b, hi) - lo, stats))
        planes.append((f"/device:TPU:{d}", [(trace.OPS_LINE, kept)]))
    host = [("bench", [(trace.WINDOW, 0.0, hi - lo, [])])]
    for k, spans in enumerate(lear_spans(pd)):
        kept = [(n, max(a, lo) - lo, min(b, hi) - lo, [])
                for n, a, b in spans if b > lo and a < hi]
        if kept:
            host.append((f"thread-{k}", kept))
    planes.append((trace.HOST_PLANE, host))
    return "\n".join(_plane(i, n, lines)
                     for i, (n, lines) in enumerate(planes, 1)) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--stall-ms", type=float, default=50.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--ms", type=float, default=20.0)
    ap.add_argument("--skip-ms", type=float, default=500.0)
    args = ap.parse_args()
    pd = trace.load(args.trace_dir)
    report = dataclasses.asdict(reduce(pd, args.devices,
                                       stall_ms=args.stall_ms))
    names = sorted({k for d in trace.device_ops(pd).values()
                    for n, _, _, e in d if trace.is_kernel(n, "forest_score")
                    for k, _ in e.stats})
    report["forest_stat_names"] = names
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.fixture:
        with open(args.fixture, "w") as f:
            f.write(fixture(pd, args.ms, args.skip_ms))
        print(f"wrote {args.fixture}: {os.path.getsize(args.fixture)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
