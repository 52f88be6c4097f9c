"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The harness writes host spans into the trace with ``TraceAnnotation``:
``bench.window`` around the measured window, ``bench.step`` around each
engine call of the batcher worker, ``bench.flush`` around each batcher
flush (pack, engine call, scatter-back) and ``bench.submit`` around each
request the traffic generator sends. From the device planes
(``/device:TPU:<n>``) and their op line this module takes, inside the
window:

- busy seconds per device: the union of the intervals in which an op ran;
- the summed device seconds of the forest kernel's launches;
- the device ops that took most time, summed by HLO instruction name;
- the device's idle time, split by what the host was doing in it: in an
  engine call (dispatch, waiting on the one device read), packing or
  scattering a flush, sending a request, or nothing of the above
  (waiting for work).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW, STEP, FLUSH, SUBMIT = ("bench.window", "bench.step", "bench.flush",
                               "bench.submit")
#: Idle-time labels, by the host span the idle time falls in.
IDLE_IN_STEP = "engine step (dispatch, device read)"
IDLE_IN_FLUSH = "batcher pack/scatter"
IDLE_IN_SUBMIT = "traffic generator submit"
IDLE_NO_SPAN = "no host span (waiting for work)"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Summary:
    window_s: float                   # length of the bench.window span
    busy_s: list[float]               # per device, inside the window
    kernel_s: float                   # forest kernel seconds, all devices
    device_ops: list[tuple[str, float]]  # most device time, by op name
    idle: list[tuple[str, float]]     # device-0 idle seconds by host span

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)


def load(trace_dir: str) -> ProfileData:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of ``[start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` inside ``[lo, hi)``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: list[tuple[float, float]],
            b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns, e


def host_spans(pd: ProfileData) -> dict[str, list[tuple[float, float]]]:
    """The harness's spans, by name, merged over every host thread."""
    spans: dict[str, list[tuple[float, float]]] = defaultdict(list)
    plane = pd.find_plane_with_name(HOST_PLANE)
    if plane is None:
        return {}
    for line in plane.lines:
        for name, a, b, _ in _events(line):
            if name in (WINDOW, STEP, FLUSH, SUBMIT):
                spans[name].append((a, b))
    return {k: merge(v) for k, v in spans.items()}


def op_name(event_name: str) -> str:
    """``%forest_score_pallas.5 = f32[...] custom-call(...)`` (the HLO text
    the TPU trace names an op by) → ``forest_score_pallas``."""
    return re.sub(r"\.\d+$", "", event_name.split(" = ", 1)[0].lstrip("%"))


def is_kernel(event_name: str, kernel: str) -> bool:
    """A custom call whose HLO instruction is named after ``kernel``: a
    Pallas kernel's launch is named after the jitted function around its
    ``pallas_call`` (``forest_score_pallas``, ``..._segments_pallas``)."""
    return (op_name(event_name).startswith(kernel)
            and "custom-call(" in event_name)


def device_ops(pd: ProfileData) -> dict[int, list]:
    """Per TPU ordinal, its op events ``(name, start_ns, end_ns, event)``."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        events = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                events.extend(_events(line))
        out[int(m.group(1))] = events
    return out


def summarize(pd: ProfileData, kernel: str,
              devices: int | None = None) -> Summary:
    """Reduce the trace to the numbers of :class:`Summary`. ``devices``
    keeps the lowest ordinals that the run used."""
    spans = host_spans(pd)
    if not spans.get(WINDOW):
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = spans[WINDOW][0][0], spans[WINDOW][-1][1]
    per_device = device_ops(pd)
    ordinals = sorted(per_device)[:devices]
    if not ordinals:
        raise ValueError("the trace has no TPU device plane")
    busy_s, kernel_ns = [], 0.0
    by_name: dict[str, float] = defaultdict(float)
    busy0: list[tuple[float, float]] = []
    for k, d in enumerate(ordinals):
        inside = [(n, a, b, e) for n, a, b, e in per_device[d]
                  if b > lo and a < hi]
        busy = merge(clip([(a, b) for _, a, b, _ in inside], lo, hi))
        busy_s.append(length(busy) * 1e-9)
        if k == 0:
            busy0 = busy
        for n, a, b, _ in inside:
            t = min(b, hi) - max(a, lo)
            by_name[op_name(n)] += t * 1e-9
            if is_kernel(n, kernel):
                kernel_ns += t
    idle = gaps(busy0, lo, hi)
    step = spans.get(STEP, [])
    flush = spans.get(FLUSH, [])
    submit = spans.get(SUBMIT, [])
    in_step = length(overlap(idle, step))
    in_flush = length(overlap(idle, flush)) - length(
        overlap(overlap(idle, flush), step))
    rest = merge(step + flush)
    in_submit = length(overlap(idle, submit)) - length(
        overlap(overlap(idle, submit), rest))
    labelled = {
        IDLE_IN_STEP: in_step,
        IDLE_IN_FLUSH: in_flush,
        IDLE_IN_SUBMIT: in_submit,
    }
    labelled[IDLE_NO_SPAN] = length(idle) - sum(labelled.values())
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    idle_by = sorted(((k, v * 1e-9) for k, v in labelled.items()),
                     key=lambda kv: -kv[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_s,
        kernel_s=kernel_ns * 1e-9,
        device_ops=top_ops,
        idle=idle_by,
    )
