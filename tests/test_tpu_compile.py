"""The serving path's kernels compile for a TPU v5e at ``lear_msn1`` width.

Compiles — does not run — each forest launch of the main serving path for
a *described* v5e chip (``jax.experimental.topologies``), so a kernel
Mosaic refuses fails here instead of on the chip. Interpret mode, which
every other kernel test uses, cannot show that. The topology is described
inside a fixture, never at import: only one process may load the TPU
compiler's library, and every test worker imports this file.
"""

from __future__ import annotations

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import lear_msn1
from repro.core.cascade import SCOPE_CLASSIFIER, SCOPE_HEAD, SCOPE_TAIL
from repro.core.lear import LearClassifier
from repro.forest.ensemble import random_ensemble
from repro.kernels.forest_score import (
    forest_score_pallas,
    forest_score_segments_pallas,
)
from repro.kernels.ops import LANE, padded_forest
from repro.launch.mesh import make_mesh
from repro.serve.ranking_service import RankingService, ServiceConfig
from repro.utils import mosaic_kernel_rows

CFG = lear_msn1.config()
BLOCK_B = 256
CLASSIFIER_DEPTH = 5   # repro.core.lear.train_lear's default
#: lear-msn1-d8's widths (bench/configs/lear-msn1-d8.json): 500 complete
#: trees of depth 8 over msn1's 136 features, sentinel 50.
D8_TREES, D8_DEPTH = 500, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def ranker():
    return random_ensemble(
        0, n_trees=CFG.n_trees, depth=CFG.depth, n_features=CFG.n_features
    )


@pytest.fixture(scope="module")
def d8_ranker():
    return random_ensemble(
        0, n_trees=D8_TREES, depth=D8_DEPTH, n_features=CFG.n_features
    )


@pytest.fixture(scope="module")
def classifier():
    return random_ensemble(
        1, n_trees=CFG.classifier_trees, depth=CLASSIFIER_DEPTH,
        n_features=CFG.n_features + 4,
    )


def _shapes(sharding, pf, rows, features):
    lanes = -(-features // LANE) * LANE
    x = jax.ShapeDtypeStruct((rows, lanes), jnp.float32, sharding=sharding)
    tables = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in (pf.feature, pf.threshold, pf.mask, pf.leaf_value)
    ]
    return x, tables


def _kernel_launches(compiled) -> int:
    return len(mosaic_kernel_rows(compiled.as_text()))


def _compile_plain(sharding, pf, rows, features, **launch):
    x, tables = _shapes(sharding, pf, rows, features)
    fn = functools.partial(
        forest_score_pallas, block_t=pf.block_t, leaf_gather=pf.leaf_gather,
        word_columns=pf.word_columns, interpret=False, **launch,
    )
    return jax.jit(fn).lower(x, *tables).compile()


def test_plain_launch_select_path(one_chip, ranker):
    """The tail launch of the paper cascade: trees 50..1047, select path."""
    pf = padded_forest(ranker, boundaries=(CFG.sentinel, CFG.n_trees))
    assert pf.leaf_gather == "select"
    compiled = _compile_plain(
        one_chip, pf, BLOCK_B, CFG.n_features, block_b=BLOCK_B,
        tree_block_offset=pf.seg_block_starts[1],
        n_tree_blocks=pf.seg_blocks[1],
    )
    assert _kernel_launches(compiled) == 1


def test_segmented_head_launch(one_chip, ranker):
    """One launch scoring the prefixes at sentinels 50 and 100."""
    pf = padded_forest(ranker, boundaries=(CFG.sentinel, 100, CFG.n_trees))
    x, tables = _shapes(one_chip, pf, BLOCK_B, CFG.n_features)
    fn = functools.partial(
        forest_score_segments_pallas,
        seg_block_starts=pf.seg_block_starts[:2],
        n_tree_blocks=pf.seg_block_starts[1] + pf.seg_blocks[1],
        block_b=BLOCK_B, block_t=pf.block_t, leaf_gather=pf.leaf_gather,
        word_columns=pf.word_columns, interpret=False,
    )
    compiled = jax.jit(fn).lower(x, *tables).compile()
    assert _kernel_launches(compiled) == 1


def test_classifier_launch(one_chip, classifier):
    """The 10-tree LEAR classifier over the augmented feature width."""
    pf = padded_forest(classifier)
    compiled = _compile_plain(
        one_chip, pf, BLOCK_B, CFG.n_features + 4, block_b=BLOCK_B,
    )
    assert _kernel_launches(compiled) == 1


def test_calibration_probe_launch(one_chip):
    """The start-up probe of repro.serve.calibration: depth 3, 16 features,
    128 docs, one tree block and the full forest."""
    ens = random_ensemble(0, n_trees=64, depth=3, n_features=16)
    pf = padded_forest(ens, boundaries=(16, 64), block_t=16)
    for n_blocks in (1, 4):
        compiled = _compile_plain(
            one_chip, pf, 128, 16, block_b=128, n_tree_blocks=n_blocks,
        )
        assert _kernel_launches(compiled) == 1


def _lear_step_hlo(monkeypatch, ranker, classifier, x_sharding,
                   Q, D, mesh=None) -> str:
    """The compiled LEAR serving step's HLO text."""
    # The ops layer picks Mosaic over interpret mode from the default
    # backend, which is the CPU here.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    svc = RankingService(
        ranker, LearClassifier(classifier, CFG.sentinel),
        ServiceConfig(execution_mode="auto", launch_overhead_trees=512.0),
    )
    svc.bucket_state(Q, D).peaks = [Q * D]   # as warmup seeds them
    X = jax.ShapeDtypeStruct((Q, D, CFG.n_features), jnp.float32,
                             sharding=x_sharding)
    mask = jax.ShapeDtypeStruct((Q, D), jnp.bool_, sharding=x_sharding)
    # The mesh is in context as ServePlacement.scope() holds it there;
    # placement.put() needs attached devices, so the shapes come placed.
    with contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh):
        return svc.lower_step(X, mask).compile().as_text()


def _lear_step_kernel_rows(*args, **kwargs) -> list[int]:
    """Row count per kernel launch in the compiled LEAR serving step."""
    return mosaic_kernel_rows(_lear_step_hlo(*args, **kwargs))


@pytest.mark.parametrize("depth", [6, 8])
def test_lear_serving_step_one_chip(monkeypatch, one_chip, ranker,
                                    d8_ranker, classifier, depth):
    """The whole jitted step: head, classifier and tail are Mosaic
    kernels over the full (8, 256) batch — for the paper's depth-6 trees
    and for lear-msn1-d8's 256-leaf trees (8 mask words, blocks of 4)."""
    rows = _lear_step_kernel_rows(
        monkeypatch, ranker if depth == 6 else d8_ranker, classifier,
        one_chip, 8, 256,
    )
    assert rows == [8 * 256] * 3


def test_lear_serving_step_four_chips_row_sharded(monkeypatch, topo, ranker,
                                                  classifier):
    """data_parallel placement on a 2x2 v5e: every device's kernel gets a
    quarter of the rows, not an all-gathered batch."""
    mesh = make_mesh((4, 1), ("data", "model"),
                     devices=np.asarray(topo.devices[:4]))
    rows = _lear_step_kernel_rows(
        monkeypatch, ranker, classifier,
        NamedSharding(mesh, PartitionSpec("data")), 8, 256, mesh=mesh,
    )
    assert rows == [8 * 256 // 4] * 3


def _mosaic_launches(hlo: str) -> list[tuple[str, str]]:
    """``(instruction name, op_name metadata)`` of each Mosaic launch."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.strip().split(" = ", 1)[0].lstrip("%")
            m = re.search(r'op_name="([^"]*)"', line)
            out.append((name, m.group(1) if m else ""))
    return out


@pytest.mark.parametrize("depth", [6, 8])
@pytest.mark.parametrize("chips", [1, 4])
def test_lear_step_launches_carry_their_stage_scope(monkeypatch, topo,
                                                    ranker, d8_ranker,
                                                    classifier, chips,
                                                    depth):
    """Each launch names its LEAR stage in its ``op_name`` metadata, and
    keeps the instruction name the device trace's kernel match reads —
    three ``forest_score_pallas`` launches at depth 6 and at depth 8."""
    if chips == 1:
        mesh, sharding = None, SingleDeviceSharding(topo.devices[0])
    else:
        mesh = make_mesh((4, 1), ("data", "model"),
                         devices=np.asarray(topo.devices[:4]))
        sharding = NamedSharding(mesh, PartitionSpec("data"))
    hlo = _lear_step_hlo(monkeypatch, ranker if depth == 6 else d8_ranker,
                         classifier, sharding, 8, 256, mesh=mesh)
    launches = _mosaic_launches(hlo)
    assert len(launches) == 3
    scopes = []
    for name, op_name in launches:
        assert name.startswith("forest_score_pallas."), name
        found = [s for s in (SCOPE_HEAD, SCOPE_CLASSIFIER, SCOPE_TAIL)
                 if f"/{s}/" in op_name]
        assert len(found) == 1, op_name
        scopes += found
    assert sorted(scopes) == sorted(
        [SCOPE_HEAD, SCOPE_CLASSIFIER, SCOPE_TAIL])
