"""``bench/tools/idle_by_span.py``: device idle split by the innermost
``lear.*`` span, forest time split by stage scope, worked by hand on a
made-up trace and checked on a 20 ms stretch recorded on a TPU v5e
(``msn1-steady`` traffic at 800 queries/s in ``bench/tools/knee.py``,
cut by the tool's ``--fixture``)."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.tools import idle_by_span as ibs  # noqa: E402
from bench.tools.trim_trace import _plane  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "msn1-steady-lear-20ms.pbtxt"
LAUNCH = "%forest_score_pallas.{} = f32[8,1]{{1,0}} custom-call(f32[8,256] %p)"


def _made_up():
    """Window [0, 100) ns. Device: the head launch 10-20 (scoped in a
    stat), the tail launch 30-40 (scoped), another op 50-55. Worker:
    flush 5-80 holding pack 5-8 and step 12-60, which holds dispatch
    12-25 and read 45-58; wait 82-100."""
    from jax.profiler import ProfileData

    dev = [
        (LAUNCH.format(3), 10, 20,
         [("tf_op", "jit(step)/lear/head/jit(forest_score_pallas)")]),
        (LAUNCH.format(5), 30, 40,
         [("tf_op", "jit(step)/lear/tail/jit(forest_score_pallas)")]),
        ("%fusion.1 = f32[8] fusion(f32[8] %p)", 50, 55, []),
    ]
    host = [
        ("bench", [(trace.WINDOW, 0, 100, [])]),
        ("worker", [("lear.flush", 5, 80, []), ("lear.pack", 5, 8, []),
                    ("lear.step", 12, 60, []),
                    ("lear.step.dispatch", 12, 25, []),
                    ("lear.step.read", 45, 58, []),
                    ("lear.wait", 82, 100, [])]),
    ]
    text = (_plane(1, "/device:TPU:0", [(trace.OPS_LINE, dev)]) + "\n"
            + _plane(2, trace.HOST_PLANE, host))
    return ProfileData.from_text_proto(text)


def test_innermost_span_takes_the_idle_time():
    r = ibs.reduce(_made_up(), stall_ms=40e-6)
    # Idle: 0-10, 20-30, 40-50, 55-100 = 75 ns.
    assert r.window_s == pytest.approx(100e-9)
    assert r.idle_s == pytest.approx(75e-9)
    split = {k: v * 1e9 for k, v in r.idle_by_span}
    assert split == pytest.approx({
        "none": 5 + 2,                  # 0-5, 80-82
        "lear.pack": 3,                 # 5-8
        "lear.flush": 2 + 20,           # 8-10, 60-80
        "lear.step.dispatch": 5,        # 20-25
        "lear.step": 5 + 5 + 2,         # 25-30, 40-45, 58-60
        "lear.step.read": 5 + 3,        # 45-50, 55-58
        "lear.wait": 18,                # 82-100
    })
    assert sum(split.values()) == pytest.approx(75)
    assert r.forest_s == pytest.approx(20e-9)
    assert {k: v * 1e9 for k, v in r.forest_by_stage.items()} == (
        pytest.approx({"lear/head": 10, "lear/classifier": 0,
                       "lear/tail": 10, "unscoped": 0}))
    # One idle gap of 40 ns or more: 55-100.
    (stall,) = r.stalls
    assert stall["at_s"] == pytest.approx(55e-9)
    assert stall["idle_s"] == pytest.approx(45e-9)
    assert stall["spans"][0] == ("lear.flush", pytest.approx(20e-9))


def test_unscoped_launches_leave_the_split_unknown():
    from jax.profiler import ProfileData

    dev = [(LAUNCH.format(3), 10, 20, [])]
    host = [("bench", [(trace.WINDOW, 0, 100, [])])]
    pd = ProfileData.from_text_proto(
        _plane(1, "/device:TPU:0", [(trace.OPS_LINE, dev)]) + "\n"
        + _plane(2, trace.HOST_PLANE, host))
    r = ibs.reduce(pd)
    assert r.forest_s == pytest.approx(10e-9)
    assert r.forest_by_stage is None
    assert r.idle_by_span == [("none", pytest.approx(90e-9))]


def test_fixture_reads_back_to_the_same_split():
    from jax.profiler import ProfileData

    pd = _made_up()
    back = ProfileData.from_text_proto(ibs.fixture(pd, 100e-6, 0.0))
    a, b = ibs.reduce(pd), ibs.reduce(back)
    assert dict(b.idle_by_span) == pytest.approx(dict(a.idle_by_span))
    assert b.forest_by_stage == pytest.approx(a.forest_by_stage)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(FIXTURE.read_text())


def test_recorded_stretch_reduces_to_its_numbers(recorded):
    r = ibs.reduce(recorded)
    assert r.window_s == pytest.approx(0.020, abs=1e-9)
    # Worked from the events when the stretch was cut: 296 ops on the
    # device, 6 of them forest launches; three flushes on the worker.
    assert r.idle_s == pytest.approx(0.017839020, abs=1e-9)
    assert r.forest_s == pytest.approx(0.002060753, abs=1e-9)
    split = {k: round(v * 1e9) for k, v in r.idle_by_span}
    assert split == {
        "lear.step.post": 12807116, "lear.step.read": 2528949,
        "lear.put": 644514, "lear.step.dispatch": 571105,
        "lear.step": 459081, "lear.pack": 304310, "lear.scatter": 284380,
        "none": 102580, "lear.step.adapt": 72500, "lear.wait": 54430,
        "lear.flush": 10055,
    }
    assert sum(split.values()) == pytest.approx(r.idle_s * 1e9, abs=20)
    # The TPU's op events name no scope: the stage split is unknown.
    assert r.forest_by_stage is None
    assert r.stalls == []


def test_recorded_split_matches_a_brute_force_count(recorded):
    """Mark every nanosecond: busy on device 0, and the innermost span
    open on the worker (spans painted outermost first)."""
    lo, hi = ibs._window(recorded)
    n = int(hi - lo)
    busy = np.zeros(n, bool)
    for _, a, b, _ in trace.device_ops(recorded)[0]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            busy[int(round(a - lo)):int(round(b - lo))] = True
    label = np.full(n, -1, np.int16)
    names = []
    for spans in ibs.lear_spans(recorded):
        for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
            if name not in names:
                names.append(name)
            a, b = max(a, lo), min(b, hi)
            label[int(round(a - lo)):int(round(b - lo))] = names.index(name)
    idle = ~busy
    want = {name: int((idle & (label == k)).sum())
            for k, name in enumerate(names)}
    want[ibs.NO_SPAN] = int((idle & (label == -1)).sum())
    got = dict(ibs.reduce(recorded).idle_by_span)
    for name, ns in want.items():
        assert got.get(name, 0.0) * 1e9 == pytest.approx(ns, abs=8), name
