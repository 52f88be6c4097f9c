"""Unified architecture API: one entry point per (arch × shape) cell.

``make_cell(cfg, shape)`` returns a :class:`Cell` bundling everything the
launcher needs:

- ``abstract_state()``  — ShapeDtypeStruct pytree of the step's carried
  state (TrainState for ``train`` shapes; params (+caches) for serving).
- ``state_logical()``   — matching logical-axis pytree.
- ``input_specs()``     — ShapeDtypeStruct stand-ins for one step's inputs.
- ``input_logical()``   — logical axes for those inputs.
- ``step``              — the pure step function ``(state, inputs) → ...``
  that the dry-run lowers and the trainer/server jit.

The SAME step functions power CPU smoke tests (reduced configs, real
arrays) and the 512-device dry-run (full configs, abstract arrays).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ForestConfig,
    NequIPConfig,
    RecSysConfig,
    ShapeSpec,
    TransformerConfig,
)
from repro.models import nequip as nequip_mod
from repro.models import recsys as recsys_mod
from repro.models import transformer as tfm
from repro.train.optimizer import get_optimizer
from repro.train.trainer import TrainState, make_train_step

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass
class Cell:
    cfg: Any
    shape: ShapeSpec
    step: Callable
    abstract_state: Callable[[], Any]
    state_logical: Callable[[], Any]
    input_specs: Callable[[], Any]
    input_logical: Callable[[], Any]
    init_state: Callable[[jax.Array], Any]  # real init (smoke tests / training)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# ---------------------------------------------------------------------------
# Optimizer-state logical axes.
# ---------------------------------------------------------------------------


def _opt_logical(opt_name: str, abstract_params, param_logical):
    if opt_name == "adamw":
        return {"m": param_logical, "v": param_logical, "count": ()}
    if opt_name == "adafactor":
        def leaf(p, lg):
            lg = tuple(lg)
            if p.ndim >= 2:
                return {"vr": lg[:-1], "vc": lg[:-2] + lg[-1:]}
            return {"v": lg}

        f = jax.tree.map(
            leaf, abstract_params, param_logical,
            is_leaf=lambda x: hasattr(x, "shape"),
        )
        return {"f": f, "count": ()}
    if opt_name == "adagrad_rowwise":
        from repro.train.optimizer import ROWWISE_MIN_ROWS

        def leaf(p, lg):
            lg = tuple(lg)
            if p.ndim == 2 and p.shape[0] >= ROWWISE_MIN_ROWS:
                return lg[:1]
            return lg

        return {
            "acc": jax.tree.map(
                leaf, abstract_params, param_logical,
                is_leaf=lambda x: hasattr(x, "shape"),
            )
        }
    raise ValueError(opt_name)


def _train_cell(cfg, shape, loss_fn, abstract_params_fn, param_logical,
                init_fn, inputs_fn, inputs_logical, microbatch=0,
                accum_dtype=jnp.float32):
    opt = get_optimizer(cfg.optimizer)
    step = make_train_step(loss_fn, opt, microbatch=microbatch,
                           accum_dtype=accum_dtype)

    def abstract_state():
        params = abstract_params_fn()
        opt_state = jax.eval_shape(opt.init, params)
        return TrainState(params=params, opt_state=opt_state,
                          step=_sds((), I32))

    def state_logical():
        return TrainState(
            params=param_logical,
            opt_state=_opt_logical(cfg.optimizer, abstract_params_fn(),
                                   param_logical),
            step=(),
        )

    def init_state(key):
        from repro.train.trainer import init_state as _init

        return _init(init_fn(key), opt)

    return Cell(
        cfg=cfg, shape=shape, step=step,
        abstract_state=abstract_state, state_logical=state_logical,
        input_specs=inputs_fn, input_logical=inputs_logical,
        init_state=init_state,
    )


# ---------------------------------------------------------------------------
# LM transformers.
# ---------------------------------------------------------------------------


def _lm_cell(cfg: TransformerConfig, shape: ShapeSpec) -> Cell:
    B, S = shape.global_batch, shape.seq_len
    plogical = tfm.param_logical(cfg)

    if shape.kind == "train":
        def inputs():
            return {"tokens": _sds((B, S), I32), "labels": _sds((B, S), I32)}

        def inputs_logical():
            return {"tokens": ("batch", None), "labels": ("batch", None)}

        accum = jnp.bfloat16 if cfg.optimizer == "adafactor" else jnp.float32
        return _train_cell(
            cfg, shape, partial(tfm.loss_fn, cfg),
            lambda: tfm.abstract_params(cfg), plogical,
            lambda key: tfm.init(cfg, key),
            inputs, inputs_logical,
            microbatch=shape.microbatch, accum_dtype=accum,
        )

    if shape.kind == "prefill":
        def step(params, inputs):
            return tfm.prefill(cfg, params, inputs["tokens"], cache_len=S)

        def inputs():
            return {"tokens": _sds((B, S), I32)}

        return Cell(
            cfg=cfg, shape=shape, step=step,
            abstract_state=lambda: tfm.abstract_params(cfg),
            state_logical=lambda: plogical,
            input_specs=inputs,
            input_logical=lambda: {"tokens": ("batch", None)},
            init_state=lambda key: tfm.init(cfg, key),
        )

    # decode
    def step(params, inputs):
        return tfm.decode_step(cfg, params, inputs["token"], inputs["caches"],
                               inputs["pos"])

    def cache_sds():
        return jax.eval_shape(lambda: tfm.make_decode_caches(cfg, B, S))

    def inputs():
        return {
            "token": _sds((B, 1), I32),
            "caches": cache_sds(),
            "pos": _sds((), I32),
        }

    def inputs_logical():
        cache_lg = jax.tree.map(
            lambda _: (None, "batch", "kv_seq", None, None),
            cache_sds(), is_leaf=lambda x: hasattr(x, "shape"),
        )
        return {"token": ("batch", None), "caches": cache_lg, "pos": ()}

    return Cell(
        cfg=cfg, shape=shape, step=step,
        abstract_state=lambda: tfm.abstract_params(cfg),
        state_logical=lambda: plogical,
        input_specs=inputs, input_logical=inputs_logical,
        init_state=lambda key: tfm.init(cfg, key),
    )


# ---------------------------------------------------------------------------
# NequIP.
# ---------------------------------------------------------------------------


def _pad512(n: int) -> int:
    """Graph/candidate axes padded to 512 so every mesh factoring divides
    (data=16, data×model=256, pod×data×model=512). The data pipeline emits
    dummy entries (self-edges on a ghost node / zero-weight rows)."""
    return -(-n // 512) * 512


def _nequip_inputs(shape: ShapeSpec):
    if shape.graph_batch and shape.n_nodes < 10_000:
        # batched-small-graphs: totals = per-graph size × batch
        N = _pad512(shape.n_nodes * shape.graph_batch)
        E = _pad512(shape.n_edges * shape.graph_batch)
    else:
        N, E = _pad512(shape.n_nodes), _pad512(shape.n_edges)
    n_graphs = shape.graph_batch or 1
    specs = {
        "positions": _sds((N, 3), F32),
        "species": _sds((N,), I32),
        "edge_src": _sds((E,), I32),
        "edge_dst": _sds((E,), I32),
        "energy": _sds((n_graphs,), F32),
    }
    logical = {
        "positions": ("nodes", None),
        "species": ("nodes",),
        "edge_src": ("edges",),
        "edge_dst": ("edges",),
        "energy": (None,),
    }
    if shape.graph_batch:
        specs["graph_id"] = _sds((N,), I32)
        logical["graph_id"] = ("nodes",)
        specs["forces"] = _sds((N, 3), F32)
        logical["forces"] = ("nodes", None)
    if shape.d_feat:
        specs["node_feat"] = _sds((N, shape.d_feat), F32)
        logical["node_feat"] = ("nodes", None)
    return specs, logical


def _nequip_cell(cfg: NequIPConfig, shape: ShapeSpec) -> Cell:
    d_feat = shape.d_feat
    with_forces = bool(shape.graph_batch)
    loss = partial(nequip_mod.loss_fn, cfg, with_forces=with_forces)
    loss_fn = lambda params, batch: loss(params, batch)
    specs, logical = _nequip_inputs(shape)
    plogical = nequip_mod.param_logical(cfg, d_feat)
    return _train_cell(
        cfg, shape, loss_fn,
        lambda: jax.eval_shape(
            lambda: nequip_mod.init(cfg, jax.random.key(0), d_feat)
        ),
        plogical,
        lambda key: nequip_mod.init(cfg, key, d_feat),
        lambda: specs, lambda: logical,
    )


# ---------------------------------------------------------------------------
# RecSys.
# ---------------------------------------------------------------------------


def _recsys_inputs(cfg: RecSysConfig, shape: ShapeSpec):
    B = shape.batch
    fam = cfg.family
    if shape.n_candidates:
        C = _pad512(shape.n_candidates)
        if fam == "dlrm":
            specs = {
                "dense": _sds((1, cfg.n_dense), F32),
                "sparse": _sds((1, cfg.n_sparse - 1, cfg.multi_hot), I32),
                "cand_ids": _sds((C,), I32),
            }
            logical = {"dense": (None, None), "sparse": (None, None, None),
                       "cand_ids": ("cands",)}
        elif fam == "deepfm":
            specs = {"ids": _sds((1, cfg.n_sparse - 1), I32),
                     "cand_ids": _sds((C,), I32)}
            logical = {"ids": (None, None), "cand_ids": ("cands",)}
        elif fam == "din":
            specs = {"hist_ids": _sds((1, cfg.seq_len), I32),
                     "cand_ids": _sds((C,), I32)}
            logical = {"hist_ids": (None, None), "cand_ids": ("cands",)}
        else:  # bert4rec
            specs = {"ids": _sds((1, cfg.seq_len), I32),
                     "cand_ids": _sds((C,), I32)}
            logical = {"ids": (None, None), "cand_ids": ("cands",)}
        return specs, logical

    if fam == "dlrm":
        specs = {
            "dense": _sds((B, cfg.n_dense), F32),
            "sparse": _sds((B, cfg.n_sparse, cfg.multi_hot), I32),
        }
        logical = {"dense": ("batch", None), "sparse": ("batch", None, None)}
    elif fam == "deepfm":
        specs = {"ids": _sds((B, cfg.n_sparse), I32)}
        logical = {"ids": ("batch", None)}
    elif fam == "din":
        specs = {"hist_ids": _sds((B, cfg.seq_len), I32),
                 "target_id": _sds((B,), I32)}
        logical = {"hist_ids": ("batch", None), "target_id": ("batch",)}
    else:  # bert4rec
        specs = {"ids": _sds((B, cfg.seq_len), I32)}
        logical = {"ids": ("batch", None)}

    if shape.kind == "train":
        if fam == "bert4rec":
            specs.update({"labels": _sds((B, cfg.seq_len), I32),
                          "mask_pos": _sds((B, cfg.seq_len), F32)})
            logical.update({"labels": ("batch", None),
                            "mask_pos": ("batch", None)})
        else:
            specs["label"] = _sds((B,), F32)
            logical["label"] = ("batch",)
    elif fam in ("din", "bert4rec") and shape.kind == "serve":
        if fam == "bert4rec":
            specs["target_id"] = _sds((B,), I32)
            logical["target_id"] = ("batch",)
    return specs, logical


def _recsys_cell(cfg: RecSysConfig, shape: ShapeSpec) -> Cell:
    fam = cfg.family
    plogical = recsys_mod.LOGICAL[fam](cfg)
    specs, logical = _recsys_inputs(cfg, shape)
    init_fn = lambda key: recsys_mod.INIT[fam](cfg, key)
    abstract = lambda: jax.eval_shape(lambda: recsys_mod.INIT[fam](cfg, jax.random.key(0)))

    if shape.kind == "train":
        return _train_cell(
            cfg, shape, partial(recsys_mod.loss_fn, cfg),
            abstract, plogical, init_fn,
            lambda: specs, lambda: logical,
            microbatch=shape.microbatch,
        )

    if shape.n_candidates:
        fwd = recsys_mod.SCORE_CANDIDATES[fam]
    else:
        fwd = recsys_mod.FORWARD[fam]

    def step(params, inputs):
        return fwd(cfg, params, inputs)

    return Cell(
        cfg=cfg, shape=shape, step=step,
        abstract_state=abstract, state_logical=lambda: plogical,
        input_specs=lambda: specs, input_logical=lambda: logical,
        init_state=init_fn,
    )


# ---------------------------------------------------------------------------
# Forest (the paper's arch): LEAR cascade serving.
# ---------------------------------------------------------------------------


def _forest_abstract(cfg: ForestConfig):
    from repro.forest.ensemble import TreeEnsemble, mask_words

    n_int = (1 << cfg.depth) - 1
    n_leaf = 1 << cfg.depth

    def ens(T, F):
        return TreeEnsemble(
            feature=_sds((T, n_int), I32),
            threshold=_sds((T, n_int), F32),
            left=_sds((T, n_int), I32),
            right=_sds((T, n_int), I32),
            mask=_sds((T, n_int, mask_words(n_leaf)), jnp.uint32),
            leaf_value=_sds((T, n_leaf), F32),
            base_score=_sds((), F32),
        )

    return {
        "ranker": ens(cfg.n_trees, cfg.n_features),
        "classifier": ens(cfg.classifier_trees, cfg.n_features + 4),
        "threshold": _sds((), F32),
    }


def _forest_real(cfg: ForestConfig, key):
    from repro.forest.ensemble import random_ensemble

    seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
    return {
        "ranker": random_ensemble(seed, cfg.n_trees, cfg.depth, cfg.n_features),
        "classifier": random_ensemble(
            seed + 1, cfg.classifier_trees, cfg.depth, cfg.n_features + 4
        ),
        "threshold": jnp.float32(0.5),
    }


def _forest_step(cfg: ForestConfig):
    from repro.core.lear import augment_features
    from repro.forest.ensemble import slice_trees
    from repro.forest.scoring import score_bitvector

    def _score(ens, x2d):
        return score_bitvector(ens, x2d)

    def step(params, inputs):
        """LEAR cascade over a padded [Q, D, F] block.

        capacity_frac == 0 → reference path: every document runs every
        tree, exits applied arithmetically (the paper's *quality*
        semantics, used as the §Perf baseline = "Full" cost).

        capacity_frac > 0 → compacted path: per query, only the top
        ⌈frac·D⌉ survivors (stable-partitioned by the classifier verdict)
        traverse the tail trees — the doc dimension of the dominant kernel
        shrinks by ~4× at the paper's continue rates. sentinel2 adds a
        second rank-based cut (beyond-paper multi-sentinel cascade).
        """
        X, mask = inputs["X"], inputs["mask"]
        Q, D, F = X.shape
        ranker = params["ranker"]
        head = slice_trees(ranker, 0, cfg.sentinel)
        part = _score(head, X.reshape(Q * D, F)).reshape(Q, D)
        aug = augment_features(X, part, mask)
        logits = _score(
            params["classifier"], aug.reshape(Q * D, F + 4)
        ).reshape(Q, D)
        cont = mask & (jax.nn.sigmoid(logits) >= params["threshold"])

        if cfg.capacity_frac <= 0:
            tail = slice_trees(ranker, cfg.sentinel, cfg.n_trees)
            tail_scores = _score(tail, X.reshape(Q * D, F)).reshape(Q, D)
            return jnp.where(cont, part + tail_scores, part), cont

        C1 = max(1, int(np.ceil(cfg.capacity_frac * D)))
        order = jnp.argsort(~cont, axis=1, stable=True)            # [Q, D]
        sel = order[:, :C1]                                        # [Q, C1]
        x_sel = jnp.take_along_axis(X, sel[..., None], axis=1)     # [Q, C1, F]
        part_sel = jnp.take_along_axis(part, sel, axis=1)
        valid = jnp.take_along_axis(cont, sel, axis=1)

        s2 = cfg.sentinel2
        if s2 and s2 > cfg.sentinel:
            mid = slice_trees(ranker, cfg.sentinel, s2)
            mid_sel = _score(mid, x_sel.reshape(Q * C1, F)).reshape(Q, C1)
            part2 = part_sel + mid_sel
            C2 = max(1, int(np.ceil((cfg.capacity2_frac or cfg.capacity_frac / 2) * D)))
            C2 = min(C2, C1)
            # Second cut: rank threshold on the refreshed partial scores.
            rank2 = jnp.argsort(
                jnp.argsort(jnp.where(valid, -part2, np.inf), axis=1), axis=1
            )
            keep2 = valid & (rank2 < C2)
            order2 = jnp.argsort(~keep2, axis=1, stable=True)[:, :C2]
            x_sel2 = jnp.take_along_axis(x_sel, order2[..., None], axis=1)
            valid2 = jnp.take_along_axis(keep2, order2, axis=1)
            tail = slice_trees(ranker, s2, cfg.n_trees)
            tail_sel = _score(tail, x_sel2.reshape(Q * C2, F)).reshape(Q, C2)
            delta2 = jnp.zeros((Q, C1)).at[
                jnp.arange(Q)[:, None], order2
            ].add(jnp.where(valid2, tail_sel, 0.0))
            deltas = jnp.where(valid, mid_sel, 0.0) + delta2
        else:
            tail = slice_trees(ranker, cfg.sentinel, cfg.n_trees)
            tail_sel = _score(tail, x_sel.reshape(Q * C1, F)).reshape(Q, C1)
            deltas = jnp.where(valid, tail_sel, 0.0)

        scores = part + jnp.zeros_like(part).at[
            jnp.arange(Q)[:, None], sel
        ].add(deltas)
        return scores, cont

    return step


def _forest_cell(cfg: ForestConfig, shape: ShapeSpec) -> Cell:
    Q, D, F = shape.batch, cfg.max_docs, cfg.n_features

    def inputs():
        return {"X": _sds((Q, D, F), F32), "mask": _sds((Q, D), jnp.bool_)}

    def logical():
        return {"X": ("batch", None, None), "mask": ("batch", None)}

    def plogical():
        from repro.forest.ensemble import TreeEnsemble

        def ens_lg():
            # Trees replicated (documents are the parallel axis).
            return TreeEnsemble(
                feature=(None, None), threshold=(None, None),
                left=(None, None), right=(None, None),
                mask=(None, None, None),
                leaf_value=(None, None), base_score=(),
            )

        return {"ranker": ens_lg(), "classifier": ens_lg(), "threshold": ()}

    return Cell(
        cfg=cfg, shape=shape, step=_forest_step(cfg),
        abstract_state=lambda: _forest_abstract(cfg),
        state_logical=plogical,
        input_specs=inputs, input_logical=logical,
        init_state=lambda key: _forest_real(cfg, key),
    )


# ---------------------------------------------------------------------------
# Dispatch.
# ---------------------------------------------------------------------------


def make_cell(cfg, shape: ShapeSpec) -> Cell:
    if isinstance(cfg, TransformerConfig):
        return _lm_cell(cfg, shape)
    if isinstance(cfg, NequIPConfig):
        return _nequip_cell(cfg, shape)
    if isinstance(cfg, RecSysConfig):
        return _recsys_cell(cfg, shape)
    if isinstance(cfg, ForestConfig):
        return _forest_cell(cfg, shape)
    raise TypeError(type(cfg))
