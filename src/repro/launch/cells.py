"""Cell builders: (architecture x input shape x mesh) -> (jitted fn, args).

Every builder returns ``(fn, args)`` where args are ShapeDtypeStructs with
NamedShardings attached, so ``fn.lower(*args).compile()`` is the multi-pod
dry-run for that cell. The same builders drive real runs when given
materialized arrays.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.core import bfs as BFS
from repro.models import equivariant as EQ, gnn as G, lm as LM, recsys as R
from repro.models.common import make_shard_fn, no_shard
from repro.train import gnn_dist as GD
from repro.train.optim import cosine_schedule, get_optimizer
from repro.train.trainer import make_train_step

from . import synth
from .mesh import all_axes, data_axes
from .sharding import opt_state_struct, replicated, rules_for, sds, spec_shardings, spec_struct


def _optimizer(spec):
    return get_optimizer(spec.optimizer, lr=cosine_schedule(3e-4, 100, 10000))


def rep_tree(tree, mesh):
    rep = replicated(mesh)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), tree)


# ------------------------------------------------------------------------ LM
def _lm_cache_struct(cfg: LM.LMConfig, mesh, batch: int, max_seq: int):
    """KV cache ShapeDtypeStructs with decode shardings: batch over data;
    kv heads over model when they divide, else the sequence dim (split-KV /
    flash-decoding analog). batch==1 shards seq over everything it can."""
    model_size = mesh.shape["model"]
    da = data_axes(mesh)
    caches = []
    for i in range(cfg.n_layers):
        t = max_seq if cfg.layer_is_global(i) else min(cfg.window, max_seq)
        if cfg.n_kv % model_size == 0 and cfg.n_kv >= model_size:
            pspec = P(da, None, "model", None)
        elif batch == 1:
            pspec = P(None, da + ("model",) if t == max_seq else "model", None, None)
        else:
            pspec = P(da, "model" if t == max_seq else None, None, None)
        shp = (batch, t, cfg.n_kv, cfg.d_head)
        ns = NamedSharding(mesh, pspec)
        caches.append({
            "k": jax.ShapeDtypeStruct(shp, cfg.dtype, sharding=ns),
            "v": jax.ShapeDtypeStruct(shp, cfg.dtype, sharding=ns),
        })
    return caches


def build_lm_cell(spec, shape_name: str, mesh, smoke: bool = False,
                  layers_override: int = 0, rules_extra: dict | None = None):
    cfg: LM.LMConfig = spec.smoke if smoke else spec.model
    if cfg.moe_groups == -1:
        import dataclasses as _dc
        import math as _math
        g = _math.prod(mesh.shape[a] for a in data_axes(mesh))
        cfg = _dc.replace(cfg, moe_groups=g)
    if layers_override:
        # exact-flop roofline variant: unrolled, shallow, no microbatching;
        # per-layer cost is then extrapolated linearly to the true depth
        import dataclasses as _dc
        cfg = _dc.replace(cfg, n_layers=layers_override, scan_layers=False)
    shape = dict(spec.shapes[shape_name])
    if smoke:
        shape["seq_len"] = min(shape["seq_len"], 64)
        shape["global_batch"] = min(shape["global_batch"], 4)
    rules = rules_for(mesh, {**spec.rules_override, **(rules_extra or {})})
    shard = make_shard_fn(mesh, rules)
    da = data_axes(mesh)
    pspecs = LM.lm_param_specs(cfg)
    p_shardings = spec_shardings(pspecs, mesh, rules)
    p_sds = spec_struct(pspecs, p_shardings)
    b, s = shape["global_batch"], shape["seq_len"]
    kind = shape["kind"]

    if kind == "train":
        opt = _optimizer(spec)
        accum = spec.grad_accum.get(shape_name, 1) if not (smoke or layers_override) else 1
        loss = lambda p, bt: LM.loss_fn(cfg, p, bt, shard)
        step = make_train_step(loss, opt, accum)
        opt_sds, _ = opt_state_struct(opt, pspecs, mesh, rules)
        batch_sds = {
            "tokens": sds((b, s), np.int32, mesh, da),
            "labels": sds((b, s), np.int32, mesh, da),
        }
        fn = jax.jit(step, donate_argnums=(0, 1))
        return fn, (p_sds, opt_sds, batch_sds)

    if kind == "prefill":
        # last_only: serving needs final-position logits; avoids the
        # [B, S, vocab] materialization (SPerf prefill iteration)
        fn = jax.jit(lambda p, toks: LM.prefill(cfg, p, toks, max_seq=s, shard=shard,
                                                last_only=True))
        toks = sds((b, s), np.int32, mesh, da)
        return fn, (p_sds, toks)

    if kind == "decode":
        cache = _lm_cache_struct(cfg, mesh, b, s)
        tok = sds((b,), np.int32, mesh, da if b > 1 else None)
        pos = jax.ShapeDtypeStruct((), np.int32, sharding=replicated(mesh))
        fn = jax.jit(lambda p, c, t, q: LM.decode_step(cfg, p, c, t, q, shard=shard),
                     donate_argnums=(1,))
        return fn, (p_sds, cache, tok, pos)

    raise ValueError(kind)


# ---------------------------------------------------------------------- GNN
def _gnn_model_cfg(spec, shape):
    return spec.model(shape) if callable(spec.model) else spec.model


def _sharded_dist_step(mesh, axes, local_step, n_stacked: int):
    """shard_map wrapper: params/opt replicated, stacked graph args split
    over the partition axes; per-shard step with pmean'd grads inside."""
    def wrapped(params, opt_state, *stacked):
        def local(params, opt_state, *args):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            new_p, new_o, loss = local_step(params, opt_state, *(sq(a) for a in args))
            return new_p, new_o, loss[None]

        in_specs = (
            jax.tree.map(lambda _: P(), params),
            jax.tree.map(lambda _: P(), opt_state),
            *[jax.tree.map(lambda x: P(axes, *([None] * (x.ndim - 1))), a) for a in stacked],
        )
        out_specs = (
            jax.tree.map(lambda _: P(), params),
            jax.tree.map(lambda _: P(), opt_state),
            P(axes),
        )
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            params, opt_state, *stacked)

    return wrapped


def _gnn_dist_batch_sds(family_cfg, kind_model: str, pg, mesh, axes, d_feat: int):
    p, nl, d = pg.p, pg.n_local, max(pg.d, 1)
    a = lambda shape, dt: jax.ShapeDtypeStruct(
        (p,) + shape, dt, sharding=NamedSharding(mesh, P(axes, *([None] * len(shape)))))
    if kind_model == "gcn":
        return {
            "x_n": a((nl, d_feat), np.float32), "x_d": a((d, d_feat), np.float32),
            "y_n": a((nl,), np.int32), "y_d": a((d,), np.int32),
            "mask_n": a((nl,), np.bool_), "mask_d": a((d,), np.bool_),
        }
    if kind_model == "mgn":
        fe = family_cfg.d_edge_in
        dn_in, dout = family_cfg.d_node_in, family_cfg.d_out
        ef = {k: a((pg.subgraph(k).e_max, fe), np.float32) for k in ("nn", "nd", "dn", "dd")}
        return {
            "x_n": a((nl, dn_in), np.float32), "x_d": a((d, dn_in), np.float32),
            "y_n": a((nl, dout), np.float32), "y_d": a((d, dout), np.float32),
            "ef": ef, "mask_n": a((nl,), np.bool_), "mask_d": a((d,), np.bool_),
        }
    if kind_model == "mace":
        return {
            "pos_n": a((nl, 3), np.float32), "pos_d": a((d, 3), np.float32),
            "spec_n": a((nl,), np.int32), "spec_d": a((d,), np.int32),
            "mask_n": a((nl,), np.bool_), "mask_d": a((d,), np.bool_),
            "target_energy": a((), np.float32),
        }
    raise ValueError(kind_model)


def _base_name(spec) -> str:
    return spec.name.replace("-opt2", "").replace("-opt", "")


def _gnn_local_loss(spec, cfg):
    """Per-partition loss closure for the distributed full-graph cells."""
    name = _base_name(spec)
    if name == "gcn-cora":
        return ("gcn", lambda prm, pgl, pl, w, bt:
                GD.dist_gcn_loss(cfg, prm, pgl, pl, w, bt, None))
    if name == "meshgraphnet":
        return ("mgn", lambda prm, pgl, pl, bt:
                GD.dist_mgn_loss(cfg, prm, pgl, pl, bt, None))
    if name == "graphcast":
        return ("mgn", lambda prm, pgl, pl, bt:
                GD.dist_mgn_loss(cfg, prm, pgl, pl, bt, None, residual=True))
    if name == "mace":
        return ("mace", lambda prm, pgl, pl, bt:
                GD.dist_mace_loss(cfg, prm, pgl, pl, bt, None))
    raise ValueError(name)


def _gnn_param_specs(spec, cfg):
    name = _base_name(spec)
    if name == "gcn-cora":
        return G.gcn_param_specs(cfg)
    if name == "meshgraphnet":
        return G.mgn_param_specs(cfg)
    if name == "graphcast":
        return G.graphcast_param_specs(cfg)
    if name == "mace":
        return EQ.mace_param_specs(cfg)
    raise ValueError(spec.name)


def _mgn_cfg_of(spec, cfg):
    """dist_mgn_* consumes an MGNConfig view of graphcast configs."""
    if _base_name(spec) == "graphcast":
        return G.MGNConfig(n_layers=cfg.n_layers, d_hidden=cfg.d_hidden, mlp_layers=2,
                           d_node_in=cfg.n_vars, d_edge_in=cfg.d_edge_in,
                           d_out=cfg.n_vars, dtype=cfg.dtype,
                           scan_layers=getattr(cfg, "scan_layers", True))
    return cfg


def build_gnn_cell(spec, shape_name: str, mesh, smoke: bool = False,
                   layers_override: int = 0, rules_extra: dict | None = None):
    shape = dict(spec.shapes[shape_name])
    cfg = spec.smoke if smoke else _gnn_model_cfg(spec, shape)
    if layers_override and hasattr(cfg, "n_layers"):
        import dataclasses as _dc
        kw = {"n_layers": layers_override}
        if hasattr(cfg, "scan_layers"):
            kw["scan_layers"] = False
        cfg = _dc.replace(cfg, **kw)
    kind = shape["kind"]
    axes = all_axes(mesh)
    p = math.prod(mesh.shape.values())
    opt = _optimizer(spec)

    if kind == "dist_full":
        n, e, d_feat = shape["n_nodes"], shape["n_edges"], shape["d_feat"]
        if smoke:
            n, e = 512, 2048
            d_feat = getattr(cfg, "d_in", 16)
        pg, plan, weights = synth.synth_partitioned_graph(n, e, p, mesh, axes)
        model_kind, loss_fn = _gnn_local_loss(spec, _mgn_cfg_of(spec, cfg)
                                              if _base_name(spec) in ("meshgraphnet", "graphcast")
                                              else cfg)
        # rebind axis names now that we know them
        if _base_name(spec) == "gcn-cora":
            loss_local = lambda prm, pgl, pl, w, bt: GD.dist_gcn_loss(cfg, prm, pgl, pl, w, bt, axes)
        elif _base_name(spec) == "graphcast":
            mcfg = _mgn_cfg_of(spec, cfg)
            loss_local = lambda prm, pgl, pl, bt: GD.dist_mgn_loss(mcfg, prm, pgl, pl, bt, axes, residual=True)
        elif _base_name(spec) == "meshgraphnet":
            loss_local = lambda prm, pgl, pl, bt: GD.dist_mgn_loss(cfg, prm, pgl, pl, bt, axes)
        else:
            loss_local = lambda prm, pgl, pl, bt: GD.dist_mace_loss(cfg, prm, pgl, pl, bt, axes)
        local_step = GD.make_dist_train_step(loss_local, opt, axes)
        pspecs = _gnn_param_specs(spec, cfg)
        p_sds = rep_tree(spec_struct(pspecs, spec_shardings(pspecs, mesh, rules_for(mesh))), mesh)
        opt_sds = rep_tree(jax.eval_shape(opt.init, p_sds), mesh)
        batch = _gnn_dist_batch_sds(_mgn_cfg_of(spec, cfg), model_kind, pg, mesh, axes, d_feat)
        stacked = (pg, plan, weights, batch) if model_kind == "gcn" else (pg, plan, batch)
        fn = jax.jit(_sharded_dist_step(mesh, axes, local_step, len(stacked)),
                     donate_argnums=(0, 1))
        return fn, (p_sds, opt_sds, *stacked)

    da = data_axes(mesh)
    if kind == "minibatch":
        # DP over all devices: one sampled subgraph per device
        seeds = shape["batch_nodes"] // p if not smoke else 2
        f1, f2 = shape["fanouts"]
        node_cap = seeds * (1 + f1 + f1 * f2)
        edge_cap = seeds * (f1 + f1 * f2)
        d_feat = 100 if not smoke else 8
        return _build_batched_gnn(spec, cfg, mesh, p, axes, node_cap, edge_cap, d_feat, opt,
                                  lead=p, geometric=_base_name(spec) == "mace")

    if kind == "batched_small":
        nb = shape["batch"] if not smoke else 4
        return _build_batched_gnn(spec, cfg, mesh, p, da, shape["n_nodes"], shape["n_edges"],
                                  16, opt, lead=nb, geometric=spec.name == "mace")
    raise ValueError(kind)


def _build_batched_gnn(spec, cfg, mesh, p, lead_axes, node_cap, edge_cap, d_feat, opt,
                       lead: int, geometric: bool):
    """DP training step over a leading batch of independent graphs."""
    a = lambda shape, dt: jax.ShapeDtypeStruct(
        (lead,) + shape, dt,
        sharding=NamedSharding(mesh, P(lead_axes, *([None] * len(shape)))))

    if _base_name(spec) == "gcn-cora":
        batch_sds = {"nodes": a((node_cap, cfg.d_in), np.float32),
                     "senders": a((edge_cap,), np.int32),
                     "receivers": a((edge_cap,), np.int32),
                     "labels": a((node_cap,), np.int32),
                     "mask": a((node_cap,), np.bool_)}

        def single(prm, bt):
            gb = G.GraphBatch(nodes=bt["nodes"], senders=bt["senders"],
                              receivers=bt["receivers"], edge_mask=bt["senders"] < node_cap)
            return G.gcn_loss(cfg, prm, gb, bt["labels"], bt["mask"])
    elif _base_name(spec) in ("meshgraphnet", "graphcast"):
        is_gc = _base_name(spec) == "graphcast"
        d_in = cfg.n_vars if is_gc else cfg.d_node_in
        d_out = cfg.n_vars if is_gc else cfg.d_out
        fe = cfg.d_edge_in
        batch_sds = {"nodes": a((node_cap, d_in), np.float32),
                     "senders": a((edge_cap,), np.int32),
                     "receivers": a((edge_cap,), np.int32),
                     "edge_feats": a((edge_cap, fe), np.float32),
                     "targets": a((node_cap, d_out), np.float32),
                     "mask": a((node_cap,), np.bool_)}

        def single(prm, bt):
            gb = G.GraphBatch(nodes=bt["nodes"], senders=bt["senders"],
                              receivers=bt["receivers"], edge_feats=bt["edge_feats"],
                              node_mask=bt["mask"], edge_mask=bt["senders"] < node_cap)
            if is_gc:
                return G.graphcast_loss(cfg, prm, gb, bt["targets"])
            return G.mgn_loss(cfg, prm, gb, bt["targets"])
    else:  # mace
        batch_sds = {"positions": a((node_cap, 3), np.float32),
                     "species": a((node_cap,), np.int32),
                     "senders": a((edge_cap,), np.int32),
                     "receivers": a((edge_cap,), np.int32),
                     "mask": a((node_cap,), np.bool_),
                     "energy": a((), np.float32)}

        def single(prm, bt):
            gb = G.GraphBatch(nodes=None, senders=bt["senders"], receivers=bt["receivers"],
                              node_mask=bt["mask"], positions=bt["positions"],
                              species=bt["species"])
            return EQ.mace_loss(cfg, prm, gb, bt["energy"][None])

    def loss(prm, bt):
        return jnp.mean(jax.vmap(lambda b: single(prm, b))(bt)), {}

    step = make_train_step(loss, opt)
    pspecs = _gnn_param_specs(spec, cfg)
    p_sds = rep_tree(spec_struct(pspecs, spec_shardings(pspecs, mesh, rules_for(mesh))), mesh)
    opt_sds = rep_tree(jax.eval_shape(opt.init, p_sds), mesh)
    fn = jax.jit(step, donate_argnums=(0, 1))
    return fn, (p_sds, opt_sds, batch_sds)


# -------------------------------------------------------------------- recsys
def build_recsys_cell(spec, shape_name: str, mesh, smoke: bool = False,
                      layers_override: int = 0, rules_extra: dict | None = None):
    cfg: R.XDeepFMConfig = spec.smoke if smoke else spec.model
    shape = dict(spec.shapes[shape_name])
    b = shape["batch"] if not smoke else 8
    rules = rules_for(mesh, {**spec.rules_override, **(rules_extra or {})})
    shard = make_shard_fn(mesh, rules)
    da = data_axes(mesh)
    pspecs = R.xdeepfm_param_specs(cfg)
    p_sds = spec_struct(pspecs, spec_shardings(pspecs, mesh, rules))
    f = cfg.n_sparse
    kind = shape["kind"]
    bspec = {
        "hot_idx": sds((b, f), np.int32, mesh, da if b >= 16 else None),
        "cold_idx": sds((b, f), np.int32, mesh, da if b >= 16 else None),
    }

    if kind == "train":
        opt = _optimizer(spec)
        batch_sds = dict(bspec, labels=sds((b,), np.int32, mesh, da))
        loss = lambda p, bt: (R.xdeepfm_loss(cfg, p, bt, shard), {})
        step = make_train_step(loss, opt)
        opt_sds, _ = opt_state_struct(opt, pspecs, mesh, rules)
        fn = jax.jit(step, donate_argnums=(0, 1))
        return fn, (p_sds, opt_sds, batch_sds)

    if kind == "serve":
        fn = jax.jit(lambda p, bt: R.xdeepfm_logits(cfg, p, bt, shard))
        return fn, (p_sds, bspec)

    if kind == "retrieval":
        nc = shape["n_candidates"] if not smoke else 512
        # 1e6 candidates divide the 16/32-way data axes, not the full mesh
        cands = sds((nc, cfg.d_query), np.float32, mesh, da)
        fn = jax.jit(lambda p, bt, c: R.retrieval_scores(cfg, p, bt, c, top_k=100))
        return fn, (p_sds, bspec, cands)
    raise ValueError(kind)


# ----------------------------------------------------------------------- BFS
def build_bfs_cell(spec, shape_name: str, mesh, smoke: bool = False,
                   layers_override: int = 0, rules_extra: dict | None = None):
    cfg: BFS.BFSConfig = spec.smoke if smoke else spec.model
    shape = dict(spec.shapes[shape_name])
    axes = all_axes(mesh)
    p = math.prod(mesh.shape.values())
    if smoke:
        scale = 12
    elif "scale" in shape:
        scale = shape["scale"]
    else:
        scale = shape["scale_per_device"] + int(math.log2(p))
    n = 1 << scale
    e = n * 32   # Graph500 edge factor 16, doubled
    pg, plan, _ = synth.synth_partitioned_graph(
        n, e, p, mesh, axes, d_frac=0.0175, nn_frac=0.063)
    state = synth.synth_bfs_state(pg, cfg, mesh, axes)
    if cfg.static_exchange:
        run = BFS.make_sharded_bfs(mesh, axes, cfg, with_plan=True)
        return run, (pg, plan, state)
    run = BFS.make_sharded_bfs(mesh, axes, cfg)
    return run, (pg, state)


# ----------------------------------------------------------------- dispatch
def build_cell(arch: str, shape_name: str, mesh, smoke: bool = False,
               layers_override: int = 0, rules_extra: dict | None = None):
    spec = get_arch(arch)
    if shape_name in spec.skip:
        raise ValueError(f"{arch}/{shape_name} skipped: {spec.skip[shape_name]}")
    builder = {
        "lm": build_lm_cell, "gnn": build_gnn_cell,
        "recsys": build_recsys_cell, "bfs": build_bfs_cell,
    }[spec.family]
    return builder(spec, shape_name, mesh, smoke, layers_override=layers_override,
                   rules_extra=rules_extra)


def all_cells(include_skipped: bool = False) -> list:
    from repro.configs import all_archs
    out = []
    for arch in all_archs():
        spec = get_arch(arch)
        for shape_name in spec.shapes:
            skipped = shape_name in spec.skip
            if skipped and not include_skipped:
                continue
            out.append((arch, shape_name, spec.skip.get(shape_name)))
    return out
