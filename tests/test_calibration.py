"""Launch-overhead calibration: probe arithmetic, process cache, auto wiring.

The probe itself is timed with a FAKE clock (a stand-in ``time`` module
injected into the calibration module's namespace) so the solved
``launch_overhead_trees`` is a deterministic function of the scripted
timings — the kernel still runs, only the measurement is scripted.
"""

import types

import numpy as np
import pytest

from repro.core.lear import LearClassifier
from repro.forest.ensemble import random_ensemble
from repro.serve import calibration
from repro.serve.ranking_service import RankingService, ServiceConfig

import jax
import jax.numpy as jnp

# Non-default probe shape: its cache key must never collide with the
# serving default (128, 64, 16) other tests may have populated.
PROBE = dict(n_docs=100, n_trees=32, block_t=8, iters=1)


@pytest.fixture(autouse=True)
def _isolated_cache():
    saved = dict(calibration._CALIBRATION_CACHE)
    calibration._CALIBRATION_CACHE.clear()
    yield
    calibration._CALIBRATION_CACHE.clear()
    calibration._CALIBRATION_CACHE.update(saved)


def _fake_clock(monkeypatch, times):
    """Script perf_counter readings (seconds). Patches only the calibration
    module's view of ``time`` — jax's own timers stay real."""
    seq = iter(times)
    fake = types.SimpleNamespace(perf_counter=lambda: next(seq))
    monkeypatch.setattr(calibration, "time", fake)


def test_probe_solves_scripted_timings(monkeypatch):
    """t_small=2000µs, t_full=5000µs at the PROBE shape solve to exactly
    800 doc·tree equivalents:
    per_doctree = 3000 / (100·24) = 1.25 µs;
    overhead = (2000 − 1.25·100·8) / 1.25 = 800."""
    _fake_clock(monkeypatch, [0.0, 2000e-6, 1.0, 1.0 + 5000e-6])
    got = calibration.calibrate_launch_overhead_trees(**PROBE)
    assert got == pytest.approx(800.0)
    report = calibration.last_calibration()
    assert report["launch_overhead_trees"] == pytest.approx(800.0)
    assert report["per_doctree_us"] == pytest.approx(1.25)
    assert report["fell_back"] is False


def test_degenerate_probe_falls_back_to_default(monkeypatch):
    """A noisy box where the small launch out-times the big one must not
    produce a negative/zero overhead — it falls back to the default."""
    _fake_clock(monkeypatch, [0.0, 5000e-6, 1.0, 1.0 + 5000e-6])
    got = calibration.calibrate_launch_overhead_trees(**PROBE)
    assert got == calibration.DEFAULT_LAUNCH_OVERHEAD_TREES
    assert calibration.last_calibration()["fell_back"] is True


def test_calibration_cached_per_process(monkeypatch):
    _fake_clock(monkeypatch, [0.0, 2000e-6, 1.0, 1.0 + 5000e-6])
    first = calibration.calibrate_launch_overhead_trees(**PROBE)
    # Second call: any clock read would exhaust the scripted sequence and
    # raise StopIteration — a cache hit never touches the timer.
    second = calibration.calibrate_launch_overhead_trees(**PROBE)
    assert second == first
    assert len(calibration._CALIBRATION_CACHE) == 1
    # A different probe shape is a different key, not a stale hit.
    with pytest.raises(StopIteration):
        calibration.calibrate_launch_overhead_trees(
            n_docs=PROBE["n_docs"] + 1, n_trees=32, block_t=8, iters=1
        )


def test_record_path_merges_not_clobbers(monkeypatch, tmp_path):
    _fake_clock(monkeypatch, [0.0, 2000e-6, 1.0, 1.0 + 5000e-6])
    path = tmp_path / "BENCH.json"
    path.write_text('{"other_section": {"kept": true}}\n')
    calibration.calibrate_launch_overhead_trees(**PROBE, record_path=str(path))
    import json

    doc = json.loads(path.read_text())
    assert doc["other_section"] == {"kept": True}
    assert doc["launch_calibration"]["launch_overhead_trees"] == (
        pytest.approx(800.0)
    )


def test_auto_flows_into_service_and_device_cost_model():
    """``launch_overhead_trees="auto"`` resolves through the process cache
    into the service AND into the static config of the compiled step (the
    device cost model prices launches at exactly the calibrated value)."""
    key = (jax.default_backend(), 128, 64, 16)  # the serving default probe
    calibration._CALIBRATION_CACHE[key] = {"launch_overhead_trees": 777.0}

    ens = random_ensemble(0, n_trees=64, depth=3, n_features=8)
    clfs = [
        LearClassifier(
            forest=random_ensemble(50 + i, n_trees=4, depth=2, n_features=12),
            sentinel=s,
        )
        for i, s in enumerate((8, 28))
    ]
    svc = RankingService(
        ens, clfs[0],
        ServiceConfig(execution_mode="auto", launch_overhead_trees="auto"),
        extra_classifiers=clfs[1:],
    )
    assert svc.launch_overhead_trees == 777.0

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(1, 32, 8)).astype(np.float32))
    svc.rank_batch(X, jnp.ones((1, 32), bool))
    keys = list(svc.cascade._step_cache)
    assert keys, "no compiled step cached"
    assert any(777.0 in k for k in keys), keys
