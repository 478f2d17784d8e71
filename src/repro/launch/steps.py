"""Per-family step builders: (ArchSpec, cell, mesh) -> lowered-compile-ready.

Each builder returns ``(fn, args, in_shardings, out_shardings)`` where every
arg is a ShapeDtypeStruct (abstract init via jax.eval_shape — no allocation,
the multi-pod dry-run contract).
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeCell
from repro.dist import annotate
from repro.dist import sharding as shd
from repro.models import gnn as gnn_mod
from repro.models import sasrec as sasrec_mod
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.train.step import make_train_step

# per-arch training knobs (memory-driven)
TRAIN_OVERRIDES: Dict[str, dict] = {
    "llama3-405b": dict(n_microbatches=8, moment_dtype=jnp.bfloat16),
    "internlm2-20b": dict(n_microbatches=2, moment_dtype=jnp.float32),
    "moonshot-v1-16b-a3b": dict(n_microbatches=2, moment_dtype=jnp.float32),
}


def _ns(mesh: Mesh, spec) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))


def _opt_cfg(arch_id: str) -> adamw.AdamWConfig:
    ov = TRAIN_OVERRIDES.get(arch_id, {})
    return adamw.AdamWConfig(moment_dtype=ov.get("moment_dtype", jnp.float32))


def _data_spec(mesh: Mesh, rank: int) -> P:
    return shd.batch_spec(mesh, rank)


# ------------------------------------------------------------------------- #
# LM family
# ------------------------------------------------------------------------- #


def build_lm(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
             smoke: bool = False):
    annotate.set_mesh(mesh)
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    inputs = cell.inputs(cfg)
    key = jax.random.key(0)
    params = jax.eval_shape(lambda k: tfm.init_transformer(cfg, k), key)
    p_specs = shd.tree_specs(params, shd.LM_RULES, mesh,
                             fsdp_axes=cfg.fsdp_axes, is_moe=cfg.moe)
    p_sh = _ns(mesh, p_specs)

    if cell.kind == "train":
        opt_cfg = _opt_cfg(spec.arch_id)
        nm = TRAIN_OVERRIDES.get(spec.arch_id, {}).get("n_microbatches", 1)
        nm = int(os.environ.get("REPRO_MICRO", nm))  # §Perf knob
        loss = partial(tfm.loss_fn, cfg=cfg)
        step = make_train_step(lambda p, t, l: loss(p, t, l), opt_cfg,
                               n_microbatches=1 if smoke else nm)
        opt = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), params)
        o_specs = adamw.AdamWState(step=P(), m=p_specs, v=p_specs)
        o_sh = _ns(mesh, o_specs)
        b_sh = tuple(shd.input_sharding(mesh, inputs[k].shape,
                                        _data_spec(mesh, 2))
                     for k in ("tokens", "labels"))
        args = (params, opt, inputs["tokens"], inputs["labels"])
        in_sh = (p_sh, o_sh) + b_sh
        out_sh = (p_sh, o_sh, None)
        return step, args, in_sh, out_sh

    if cell.kind == "prefill":
        fn = partial(tfm.forward, cfg=cfg)
        tok_sh = shd.input_sharding(mesh, inputs["tokens"].shape,
                                    _data_spec(mesh, 2))
        return (lambda p, t: fn(p, t)), (params, inputs["tokens"]), \
            (p_sh, tok_sh), None

    # decode
    cb, cl = inputs["cache_batch"], inputs["cache_len"]
    cache = jax.eval_shape(lambda: tfm.init_cache(cfg, cb, cl))
    dax = shd.batch_axes(mesh)
    dax = dax if len(dax) > 1 else (dax[0] if dax else None)
    model_ok = "model" in mesh.axis_names

    def cache_spec(path_leaf_shape):
        # shard batch over data axes, cache length over model (keeps the
        # per-device KV slice bounded on the 500k/32k cells)
        rank = len(path_leaf_shape)
        if rank == 4:   # mla: [L, B, S, d]
            return P(None, dax, "model" if model_ok else None, None)
        if rank == 5:   # gqa: [L, B, Hkv, S, d]
            return P(None, dax, None, "model" if model_ok else None, None)
        return P()

    c_specs = jax.tree.map(
        lambda l: shd.guard_spec(cache_spec(l.shape), l.shape, mesh), cache)
    c_sh = _ns(mesh, c_specs)
    tok_sh = shd.input_sharding(mesh, inputs["tokens"].shape, P(dax))
    fn = partial(tfm.decode_step, cfg=cfg)
    return (lambda p, c, t: fn(p, c, t)), (params, cache, inputs["tokens"]), \
        (p_sh, c_sh, tok_sh), (None, c_sh)


# ------------------------------------------------------------------------- #
# GNN family
# ------------------------------------------------------------------------- #


def build_gnn(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
              smoke: bool = False):
    annotate.set_mesh(mesh)
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    if not smoke:
        # width the input projection to the cell's feature dim
        f = cell.inputs(cfg)["node_feat"].shape[1]
        cfg = dataclasses.replace(cfg, d_in=f)
    inputs = cell.inputs(cfg)
    key = jax.random.key(0)
    params = jax.eval_shape(lambda k: gnn_mod.init_gnn(cfg, k), key)
    p_sh = _ns(mesh, shd.tree_specs(params, shd.GNN_RULES, mesh))

    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    row = all_axes if len(all_axes) > 1 else (all_axes[0] if all_axes else None)

    field_names = list(inputs.keys())

    def to_batch(**kw):
        return gnn_mod.GraphBatch(
            node_feat=kw["node_feat"], senders=kw["senders"],
            receivers=kw["receivers"], edge_mask=kw["edge_mask"],
            node_mask=kw["node_mask"], labels=kw["labels"],
            coords=kw.get("coords"), triplet_kj=kw.get("triplet_kj"),
            triplet_ji=kw.get("triplet_ji"))

    opt_cfg = _opt_cfg(spec.arch_id)
    step = make_train_step(
        lambda p, *arrs: gnn_mod.gnn_loss(
            p, to_batch(**dict(zip(field_names, arrs))), cfg), opt_cfg)
    opt = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), params)
    o_specs = adamw.AdamWState(step=P(),
                               m=shd.tree_specs(params, shd.GNN_RULES, mesh),
                               v=shd.tree_specs(params, shd.GNN_RULES, mesh))
    o_sh = _ns(mesh, o_specs)
    arr_sh = tuple(
        shd.input_sharding(mesh, inputs[n].shape,
                           P(row, *([None] * (len(inputs[n].shape) - 1))))
        for n in field_names)
    args = (params, opt) + tuple(inputs[n] for n in field_names)
    return step, args, (p_sh, o_sh) + arr_sh, (p_sh, o_sh, None)


# ------------------------------------------------------------------------- #
# recsys family
# ------------------------------------------------------------------------- #


def build_recsys(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                 smoke: bool = False):
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    inputs = cell.inputs(cfg)
    key = jax.random.key(0)
    params = jax.eval_shape(lambda k: sasrec_mod.init_sasrec(cfg, k), key)
    p_sh = _ns(mesh, shd.tree_specs(params, shd.RECSYS_RULES, mesh))
    dspec = _data_spec(mesh, 2)

    if cell.kind == "train":
        opt_cfg = _opt_cfg(spec.arch_id)
        step = make_train_step(
            lambda p, s, po, ne: sasrec_mod.train_loss(p, s, po, ne, cfg),
            opt_cfg)
        opt = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), params)
        sp = shd.tree_specs(params, shd.RECSYS_RULES, mesh)
        o_sh = _ns(mesh, adamw.AdamWState(step=P(), m=sp, v=sp))
        b_sh = tuple(shd.input_sharding(mesh, inputs[k].shape, dspec)
                     for k in ("seq", "pos", "neg"))
        args = (params, opt, inputs["seq"], inputs["pos"], inputs["neg"])
        return step, args, (p_sh, o_sh) + b_sh, (p_sh, o_sh, None)

    fn = partial(sasrec_mod.score_candidates, cfg=cfg)
    cand_sh = shd.input_sharding(
        mesh, inputs["candidates"].shape,
        P("model" if "model" in mesh.axis_names else None))
    args = (params, inputs["seq"], inputs["candidates"])
    return (lambda p, s, c: fn(p, s, c)), args, \
        (p_sh, shd.input_sharding(mesh, inputs["seq"].shape, dspec),
         cand_sh), None


# ------------------------------------------------------------------------- #
# mosso family: sharded summarization (edge-partitioned engines)
# ------------------------------------------------------------------------- #


def build_mosso(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                smoke: bool = False):
    from repro.core.engine.state import new_state
    from repro.core.engine.trial import step_fn

    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    inputs = cell.inputs(cfg)
    n_dev = int(mesh.devices.size)
    axes = tuple(mesh.axis_names)

    state1 = jax.eval_shape(lambda: new_state(cfg))
    stacked = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_dev,) + tuple(l.shape), l.dtype),
        state1)
    st_sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P(axes, *([None] * (len(l.shape) - 1)))),
        state1)
    ch_sh = NamedSharding(mesh, P(axes))

    def local_step(st, u, v, ins):
        st0 = jax.tree.map(lambda x: x[0], st)
        st1 = step_fn(st0, u[0], v[0], ins[0], cfg)
        phi = jax.lax.psum(st1.phi, axes)
        st1 = st1._replace(phi=st1.phi)  # local phi stays local
        out = jax.tree.map(lambda x: x[None], st1)
        return out, phi[None]

    dist_step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axes), state1), P(axes), P(axes),
                  P(axes)),
        out_specs=(jax.tree.map(lambda _: P(axes), state1), P(axes)),
        check_vma=False)

    b = cfg.batch
    args = (stacked,
            jax.ShapeDtypeStruct((n_dev, b), jnp.int32),
            jax.ShapeDtypeStruct((n_dev, b), jnp.int32),
            jax.ShapeDtypeStruct((n_dev, b), jnp.bool_))
    in_sh = (st_sh, ch_sh, ch_sh, ch_sh)
    return dist_step, args, in_sh, (st_sh, ch_sh)


BUILDERS = {"lm": build_lm, "gnn": build_gnn, "recsys": build_recsys,
            "mosso": build_mosso}


def build(spec: ArchSpec, cell: ShapeCell, mesh: Mesh, smoke: bool = False):
    return BUILDERS[spec.family](spec, cell, mesh, smoke)
