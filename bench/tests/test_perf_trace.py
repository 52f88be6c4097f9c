"""The trace reduction on a 20 ms stretch of a trace recorded on a TPU v5e
(``msn1-steady`` traffic at 1,000 queries/s, cut by
``bench/tools/trim_trace.py``), and its interval arithmetic."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "msn1-steady-20ms.pbtxt"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(FIXTURE.read_text())


def test_recorded_stretch_reduces_to_its_numbers(pd):
    s = trace.summarize(pd, "forest_score", devices=1)
    assert s.window_s == pytest.approx(0.020, abs=1e-9)
    # Worked from the events when the stretch was cut: 249 ops on the
    # device, 9 of them forest kernel launches.
    assert s.busy_s == [pytest.approx(0.002230178, abs=1e-9)]
    assert s.kernel_s == pytest.approx(0.002139138, abs=1e-9)
    assert s.device_ops[0] == ("forest_score_pallas",
                               pytest.approx(0.002139138, abs=1e-9))
    idle = dict(s.idle)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s[0],
                                               abs=1e-9)
    assert idle[trace.IDLE_IN_STEP] == pytest.approx(0.016143332, abs=1e-9)
    assert max(idle, key=idle.get) == trace.IDLE_IN_STEP


def test_busy_time_is_the_union_of_op_intervals(pd):
    """Brute force at 1 ns: mark every nanosecond some op covers."""
    ops = trace.device_ops(pd)[0]
    spans = trace.host_spans(pd)
    lo, hi = spans[trace.WINDOW][0]
    covered = np.zeros(int(hi - lo), bool)
    kernel = 0.0
    for name, a, b, _ in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            covered[int(round(a - lo)):int(round(b - lo))] = True
            if trace.is_kernel(name, "forest_score"):
                kernel += b - a
    s = trace.summarize(pd, "forest_score", devices=1)
    assert s.busy_s[0] == pytest.approx(covered.sum() * 1e-9, abs=2e-9)
    assert s.kernel_s == pytest.approx(kernel * 1e-9, abs=1e-12)
    assert sum(trace.is_kernel(n, "forest_score") for n, *_ in ops) == 9


def test_kernel_is_matched_by_its_launch_not_its_users():
    launch = ("%forest_score_pallas.5 = f32[1024,1]{1,0} custom-call("
              "f32[1024,256]{1,0} %pad.4), custom_call_target=\"tpu_custom_call\"")
    user = ("%reduce.4 = f32[256]{0} reduce(f32[256,1]{1,0} "
            "%forest_score_pallas.3), to_apply=%forest_score_pallas.reduce")
    assert trace.op_name(launch) == "forest_score_pallas"
    assert trace.is_kernel(launch, "forest_score")
    assert not trace.is_kernel(user, "forest_score")
    assert not trace.is_kernel(launch, "other_kernel")


def test_interval_arithmetic():
    busy = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace.length(busy) == 7
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]
    assert trace.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert trace.overlap(busy, [(2, 6), (8, 20)]) == [(2, 3), (5, 6), (8, 9)]


def test_a_trace_without_the_window_or_a_device_is_refused():
    from jax.profiler import ProfileData

    empty = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(empty, "forest_score")
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "t" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 1000 } } event_metadata { key: 1 value { id: 1 '
        'name: "bench.window" } } }')
    with pytest.raises(ValueError, match="no TPU"):
        trace.summarize(host_only, "forest_score")
