"""Traffic generators, the end-to-end arithmetic and how cells resolve."""

import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, latency, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "bench" / "mixes").glob("*.json"))


def _mix(name):
    return json.loads((ROOT / "bench" / "mixes" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    a = traffic.generate(_mix(name), 8, 2.0, 2**31 + 7)
    b = traffic.generate(_mix(name), 8, 2.0, 2**31 + 7)
    assert np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(a.rows, b.rows)
    assert np.array_equal(a.pool, b.pool)
    if a.due is not None:
        assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.features(3), b.features(3))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    mix = _mix(name)
    a = traffic.generate(mix, 8, 2.0, 1)
    b = traffic.generate(mix, 8, 2.0, 2)
    assert not np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    lo, hi = traffic.doc_range(mix)
    assert a.sizes.min() >= lo and a.sizes.max() <= hi
    if a.due is not None:
        assert a.due[0] == 0.0 and np.all(np.diff(a.due) > 0)
        rate, n = mix["rate_qps"], len(a)
        gaps_a = traffic.arrival_gaps(rate, n, np.random.default_rng(1))
        gaps_b = traffic.arrival_gaps(rate, n, np.random.default_rng(2))
        assert not np.array_equal(gaps_a, gaps_b)
        assert np.array_equal(np.sort(gaps_a), np.sort(gaps_b))
        assert gaps_a.mean() == pytest.approx(1 / rate, rel=1e-3)


def test_open_loop_schedule_fills_the_window_at_the_rate():
    mix = {"loop": "open", "rate_qps": 500.0,
           "docs": {"dist": "uniform", "min": 8, "max": 32}}
    tr = traffic.generate(mix, 4, 10.0, 5)
    assert len(tr) == 5000
    assert 9.9 < tr.due[-1] < 10.0


def test_lognormal_mean_and_clip():
    spec = {"dist": "lognormal", "mean": 120, "sigma": 0.8, "min": 8,
            "max": 1024}
    sizes = traffic.doc_counts(spec, 20000, np.random.default_rng(0))
    assert sizes.min() >= 8 and sizes.max() <= 1024
    assert 110 < sizes.mean() < 125       # the clip trims the far tail
    assert 80 <= np.median(sizes) <= 95   # exp(mu) = 120 * exp(-0.32)


def test_features_are_standard_normal_rows_of_the_request_length():
    mix = _mix(MIXES[0])
    tr = traffic.generate(mix, 16, 1.0, 3)
    x = tr.features(0)
    assert x.shape == (tr.sizes[0], 16) and x.dtype == np.float32
    assert abs(float(tr.pool.mean())) < 0.01
    assert abs(float(tr.pool.std()) - 1.0) < 0.01


def test_rate_is_all_work_over_all_time():
    done = [0.5, 1.0, 1.5, 2.5, 3.9, 4.0, math.inf]
    # Completions in [1, 4): 1.0, 1.5, 2.5, 3.9 — over the 3 s window.
    assert latency.completed_rate(done, 1.0, 4.0) == pytest.approx(4 / 3)
    with pytest.raises(ValueError):
        latency.completed_rate(done, 2.0, 2.0)


def test_percentiles_cover_all_requests_with_failures_at_infinity():
    lat = [float(i) for i in range(1, 100)] + [math.inf]
    assert latency.percentile(lat, 50) == 50.0
    assert latency.percentile(lat, 99) == 99.0
    assert latency.percentile(lat, 100) == math.inf
    # Two failures in a hundred push the 99th percentile to infinity.
    assert latency.percentile(lat[:-2] + [math.inf] * 2, 99) == math.inf
    with pytest.raises(ValueError):
        latency.percentile([], 50)


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(name):
    cell = harness.load_cell(name)
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert cell.config["name"] == conf["name"]
    assert cell.chips == w["chips"]
    assert cell.mix == _mix(w["traffic"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in e2e


def test_harness_has_no_branch_per_cell():
    names = ({w["name"] for w in SPEC["workloads"]}
             | {c["name"] for c in SPEC["configs"]}
             | {w["traffic"] for w in SPEC["workloads"]})
    for path in (ROOT / "bench").glob("*.py"):
        text = path.read_text()
        for name in names:
            assert name not in text, (path.name, name)


def test_imports_load_no_accelerator_runtime():
    """Importing the benchmark maps no TPU runtime library into the
    process (``import jax`` imports the ``libtpu`` package only to find
    its path)."""
    code = ("import sys; sys.path[:0] = [{!r}, {!r}];"
            "import bench.harness, bench.trace, bench.reference, "
            "bench.models, bench.work;"
            "print([l for l in open('/proc/self/maps') if 'libtpu' in l])")
    out = subprocess.run(
        [sys.executable, "-c", code.format(str(ROOT), str(ROOT / "src"))],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=True)
    assert out.stdout.strip().splitlines()[-1] == "[]"


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    names = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(json.dumps(SPEC)) <= 64 * 1024
