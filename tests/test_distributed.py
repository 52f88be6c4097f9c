"""Distribution substrate on a local mesh + an 8-device subprocess check.

The full 512-device path is exercised by repro.launch.dryrun; here we test
the pieces that must hold on any mesh: rule resolution, constraint no-ops
without rules, elastic re-mesh divisibility validation, and a real 8-device
SPMD train step in a subprocess (XLA device count is process-global, so the
multi-device case cannot run in-process).
"""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    constrain,
    local_rules,
    multi_pod_rules,
    sharding_rules,
    single_pod_rules,
)
from repro.launch.mesh import make_local_mesh
from repro.train.elastic import remesh, validate_divisibility

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_rules_resolution():
    r = single_pod_rules()
    assert r.resolve("batch", None, "ff") == P(("data",), None, "model")
    m = multi_pod_rules()
    assert m.resolve("batch") == P(("pod", "data"))
    assert m.resolve("experts") == P("model")
    with pytest.raises(KeyError):
        r.resolve("nonexistent")


def test_constrain_noop_without_rules():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)  # no rules context → identity
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_under_local_mesh():
    mesh = make_local_mesh()
    with sharding_rules(single_pod_rules()), jax.sharding.set_mesh(mesh):
        y = jax.jit(lambda v: constrain(v, "batch", "ff"))(jnp.ones((4, 4)))
    assert y.shape == (4, 4)


def test_validate_divisibility():
    mesh = make_local_mesh()  # 1×1 — everything divides
    tree = {"w": jnp.ones((6, 4))}
    logical = {"w": ("batch", "ff")}
    assert validate_divisibility(tree, logical, single_pod_rules(), mesh) == []


def test_remesh_roundtrip_local():
    mesh = make_local_mesh()
    tree = {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}
    logical = {"w": (None, None), "b": (None,)}
    out = remesh(tree, logical, local_rules(), mesh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


_SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import sharding_rules, Rules
from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.models.api import make_cell
from repro.models.synth import synthesize_inputs

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = Rules(table={
    "batch": ("data",), "groups": ("data",), "edges": ("data",),
    "seq": None, "embed": None, "ff": "model", "qkv": "model",
    "vocab": "model", "heads": None, "kv_seq": None, "layers": None,
    "experts": "model", "expert_ff": None, "rows": "model",
    "cands": ("data", "model"), "nodes": None, "dense": None,
})
cfg = get_smoke_config("deepseek-moe-16b")
shape = ShapeSpec(name="t", kind="train", seq_len=32, global_batch=8,
                  microbatch=4)
cell = make_cell(cfg, shape)
with sharding_rules(rules), jax.sharding.set_mesh(mesh):
    state = cell.init_state(jax.random.key(0))
    inputs = synthesize_inputs(cell, 0)
    new_state, metrics = jax.jit(cell.step)(state, inputs)
    loss = float(metrics["loss"])
assert np.isfinite(loss), loss
# Cross-check vs unsharded execution: same step on 1 logical program.
state2 = cell.init_state(jax.random.key(0))
_, m2 = jax.jit(cell.step)(state2, inputs)
assert abs(loss - float(m2["loss"])) < 1e-2, (loss, float(m2["loss"]))
print("SPMD_OK", loss)
"""


def test_8device_spmd_train_step():
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": os.environ["PATH"],
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert "SPMD_OK" in res.stdout, res.stdout + res.stderr
