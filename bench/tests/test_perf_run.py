"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
or the control in the program's place, it comes out not correct."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, models  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY = {
    "name": "tiny", "n_trees": 24, "depth": 4, "n_features": 16,
    "sentinel": 6, "classifier_trees": 4, "classifier_depth": 3,
    "leaf_scale": 0.1, "continue_share": 0.3, "weights_seed": 7,
    "threshold_sample": {"queries": 8,
                         "docs": {"dist": "uniform", "min": 8, "max": 8}},
}


def _cell(loop: str) -> harness.Cell:
    """A tiny cell that reports what the spec's cells of this loop do."""
    like = next(w["name"] for w in SPEC["workloads"]
                if harness.load_cell(w["name"]).mix["loop"] == loop)
    real = harness.load_cell(like)
    mix = {"loop": loop, "in_flight": 16, "rate_qps": 200.0,
           "docs": {"dist": "uniform", "min": 8, "max": 8}}
    return harness.Cell(name="tiny", chips=1, config=TINY, mix=mix,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)


def _altered_answer(service):
    """An answer altered where it is produced: one score per batch."""
    rank_batch = service.rank_batch

    def altered(*args, **kwargs):
        top, scores = rank_batch(*args, **kwargs)
        scores = np.array(scores)
        scores[0, 0] += 0.5
        return top, scores

    service.rank_batch = altered


def _exchange_left_out(service):
    """Only the first of four row shards comes back: the rows the other
    chips computed are never gathered."""
    rank_batch = service.rank_batch

    def first_shard(*args, **kwargs):
        top, scores = rank_batch(*args, **kwargs)
        scores = np.array(scores)
        scores[scores.shape[0] // 4:] = 0.0
        return top, scores

    service.rank_batch = first_shard


def _run(loop, **kwargs):
    return harness.run_cell(_cell(loop), 2**31 + 11, 1.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kwargs)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    r = _run(loop)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in _cell(loop).end_to_end}
    for m in r["metrics"].values():
        assert m["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1


@pytest.mark.parametrize("fault", ["altered_answer", "exchange_left_out",
                                   "control"])
def test_broken_run_is_not_correct(fault):
    if fault == "control":
        r = _run("open", control=True)
    else:
        tamper = {"altered_answer": _altered_answer,
                  "exchange_left_out": _exchange_left_out}[fault]
        r = _run("open", tamper=tamper)
    assert r["correct"] is False
    assert r["checks"]["score_err"]["value"] > r["checks"]["score_err"][
        "limit"]


def test_mix_sets_the_admission_bound():
    cell = _cell("open")
    default = harness.build_tier(cell, models.build(TINY))
    deep = dataclasses.replace(
        cell, mix=dict(cell.mix, max_queue_depth=8192))
    tier = harness.build_tier(deep, models.build(TINY))
    assert default.batcher.policy.max_queue_depth == 1024
    assert tier.batcher.policy.max_queue_depth == 8192


def test_run_without_a_tpu_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
