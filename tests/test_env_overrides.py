"""Environment-variable overrides of the engine tuning constants.

The deployment knobs (`PADDED_CACHE_MAX`, `LEAF_SELECT_MAX`,
`RANK_BLOCKED_MIN_D`, and the dense stage-0 sizing constants
`DENSE_N_VEC` / `DENSE_VEC_DIM` / `DENSE_HIDDEN` / `DENSE_COST_TREES`)
read the environment through the single :func:`repro.kernels.ops.env_int`
helper at import time. The helper's parsing contract is tested
in-process; the end-to-end override path (env → import → behavior change)
needs a fresh interpreter, so it runs in a subprocess — same idiom as the
multi-device check in test_distributed.
"""

import os
import pathlib
import subprocess
import sys

import pytest

from repro.kernels.ops import env_int

REPO = pathlib.Path(__file__).resolve().parents[1]


def _env(**overrides: str) -> dict[str, str]:
    """A fresh interpreter's environment: this checkout's sources on the
    CPU, plus the overrides under test."""
    return {"PYTHONPATH": str(REPO / "src"), "PATH": os.environ["PATH"],
            "JAX_PLATFORMS": "cpu", **overrides}


def test_env_int_default_when_unset(monkeypatch):
    monkeypatch.delenv("REPRO_TEST_KNOB", raising=False)
    assert env_int("REPRO_TEST_KNOB", 42) == 42


def test_env_int_empty_means_default(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_KNOB", "   ")
    assert env_int("REPRO_TEST_KNOB", 42) == 42


def test_env_int_parses_override(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_KNOB", " 128 ")
    assert env_int("REPRO_TEST_KNOB", 42) == 128


def test_env_int_rejects_garbage(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_KNOB", "fast")
    with pytest.raises(ValueError, match="must be an integer"):
        env_int("REPRO_TEST_KNOB", 42)


def test_env_int_rejects_below_minimum(monkeypatch):
    monkeypatch.setenv("REPRO_TEST_KNOB", "0")
    with pytest.raises(ValueError, match="must be >= 1"):
        env_int("REPRO_TEST_KNOB", 42)
    monkeypatch.setenv("REPRO_TEST_KNOB", "-3")
    with pytest.raises(ValueError):
        env_int("REPRO_TEST_KNOB", 42)


_OVERRIDE_PROG = r"""
import repro.kernels.ops as ops
import repro.core.features as features
from repro.forest.ensemble import random_ensemble

# The constants themselves picked up the environment.
assert ops.PADDED_CACHE_MAX == 2, ops.PADDED_CACHE_MAX
assert ops.LEAF_SELECT_MAX == 16, ops.LEAF_SELECT_MAX
assert features.RANK_BLOCKED_MIN_D == 32, features.RANK_BLOCKED_MIN_D

# ... and the behavior behind each constant moved with them.
# 1. Leaf-gather auto policy: the select/mxu crossover is now at 16 leaves.
assert ops.resolve_leaf_gather(16) == "select"
assert ops.resolve_leaf_gather(17) == "mxu"   # default would say "select"

# 2. Padded-buffer LRU: the per-ensemble cache evicts above 2 layouts.
ens = random_ensemble(0, n_trees=8, depth=2, n_features=4)
for bt in (1, 2, 4):
    ops.padded_forest(ens, block_t=bt)
assert len(ens._padded_cache) == 2, len(ens._padded_cache)

# 3. Blocked-rank auto policy: 33 candidates now pick the tiled compare
# (default cutoff 256 would go direct). Wrap the blocked entry point to
# observe the dispatch, and keep the result exact vs the direct form.
import numpy as np, jax.numpy as jnp
calls = []
real_blocked = features.query_ranks_blocked
features.query_ranks_blocked = (
    lambda *a, **k: calls.append(1) or real_blocked(*a, **k)
)
part = jnp.asarray(np.random.default_rng(0).normal(size=(2, 33)),
                   jnp.float32)
mask = jnp.ones((2, 33), bool)
auto = features.query_ranks(part, mask)   # auto → blocked above 32
assert calls, "auto dispatch did not pick the blocked path"
direct = features.query_ranks(part, mask, method="direct")
np.testing.assert_array_equal(np.asarray(auto), np.asarray(direct))
print("OVERRIDES_OK")
"""


def test_override_path_end_to_end():
    """Env → fresh import → constants AND the behavior they gate change."""
    res = subprocess.run(
        [sys.executable, "-c", _OVERRIDE_PROG],
        capture_output=True, text=True, timeout=300,
        env=_env(
            REPRO_PADDED_CACHE_MAX="2",
            REPRO_LEAF_SELECT_MAX="16",
            REPRO_RANK_BLOCKED_MIN_D="32",
        ),
        cwd=REPO,
    )
    assert "OVERRIDES_OK" in res.stdout, res.stdout + res.stderr


_DENSE_OVERRIDE_PROG = r"""
import jax
import jax.numpy as jnp
import repro.models.dense_scorer as ds
from repro.core.stage import DenseStage

# The constants themselves picked up the environment.
assert ds.DENSE_N_VEC == 3, ds.DENSE_N_VEC
assert ds.DENSE_VEC_DIM == 8, ds.DENSE_VEC_DIM
assert ds.DENSE_HIDDEN == 12, ds.DENSE_HIDDEN
assert ds.DENSE_COST_TREES == 9, ds.DENSE_COST_TREES

# ... and the behavior behind them moved: the default-initialized scorer
# is shaped by the overridden constants end to end.
params = ds.init_dense_scorer(jax.random.PRNGKey(0), n_features=10)
assert params["proj"].shape == (10, 3, 8), params["proj"].shape
assert params["pb"].shape == (3, 8), params["pb"].shape
n_pairs = 3 * 2 // 2
assert params["w1"].shape == (3 * 8 + n_pairs, 12), params["w1"].shape
out = ds.dense_score(params, jnp.zeros((5, 10), jnp.float32))
assert out.shape == (5,), out.shape

# The accounting default of a DenseStage follows DENSE_COST_TREES.
stage = DenseStage(
    scorer=ds.make_dense_scorer(params), policy=lambda s, m: m
)
assert stage.stage_cost_trees == 9.0, stage.stage_cost_trees
print("DENSE_OVERRIDES_OK")
"""


def test_dense_override_path_end_to_end():
    """Env → fresh import → dense-scorer shapes and stage accounting move."""
    res = subprocess.run(
        [sys.executable, "-c", _DENSE_OVERRIDE_PROG],
        capture_output=True, text=True, timeout=300,
        env=_env(
            REPRO_DENSE_N_VEC="3",
            REPRO_DENSE_VEC_DIM="8",
            REPRO_DENSE_HIDDEN="12",
            REPRO_DENSE_COST_TREES="9",
        ),
        cwd=REPO,
    )
    assert "DENSE_OVERRIDES_OK" in res.stdout, res.stdout + res.stderr


def test_dense_n_vec_minimum_enforced():
    """n_vec=1 has no pairwise interactions — rejected at import."""
    res = subprocess.run(
        [sys.executable, "-c", "import repro.models.dense_scorer"],
        capture_output=True, text=True, timeout=300,
        env=_env(
            REPRO_DENSE_N_VEC="1",
        ),
        cwd=REPO,
    )
    assert res.returncode != 0
    assert "REPRO_DENSE_N_VEC must be >= 2" in res.stderr


def test_bad_override_fails_at_import():
    """A typo'd override must crash the first repro import, not be ignored."""
    res = subprocess.run(
        [sys.executable, "-c", "import repro.kernels.ops"],
        capture_output=True, text=True, timeout=300,
        env=_env(
            REPRO_LEAF_SELECT_MAX="sixty-four",
        ),
        cwd=REPO,
    )
    assert res.returncode != 0
    assert "REPRO_LEAF_SELECT_MAX must be an integer" in res.stderr
