"""Generalized degree-separated propagation engine.

The paper's communication model carries 1-bit visited status. Section VI-D
observes the same model extends to algorithms that exchange *values* —
"ranking scores for PageRank ... associative values for normal vertices".
This module is that generalization: one round of

    out[v] = reduce_{(u -> v) in E} w_uv * x[u]

over the four-subgraph partitioned representation, with

* delegate destinations aggregated by a **global psum** (the bitmask
  reduction generalized to feature vectors), and
* nn-edge remote destinations receiving **pre-aggregated partials** via a
  fixed-capacity all_to_all (the point-to-point exchange, with the paper's
  "uniquification" turned into a static plan: the (owner, local-dst) binning
  of nn edges is graph-static, so the permutation/segment structure is
  precomputed on the host once).

This is the substrate the distributed GNN configs (gcn on ogb_products etc.)
train on.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import comm
from .types import CSR, PartitionedGraph, PartitionLayout


@dataclass
class ExchangePlan:
    """Static binning of nn edges by (owner partition, local dst id).

    ``recv_local`` is the receiver-side inverse: for (peer j, slot s) the
    local id that j's slot s refers to on THIS partition -- what makes the
    1-bit static-slot exchange possible (BFS SPerf optimization: senders
    ship slot bitmasks, receivers decode locally)."""

    perm: Any        # [p, E_nn_max] int32: edge order sorted by (owner, local)
    seg_ids: Any     # [p, E_nn_max] int32: run index of unique (owner, local);
    #                  ascending, so each slot's edges are one contiguous run
    seg_owner: Any   # [p, cap_total] int32: owner partition per unique dst (p = invalid)
    seg_pos: Any     # [p, cap_total] int32: slot within the owner's bin
    seg_local: Any   # [p, cap_total] int32: local id at the destination
    recv_local: Any = None  # [p, p, cap_peer] int32: (peer, slot) -> my local id
    src_rows: Any = None  # [p, E_nn_max] int32: nn.rowids[perm], the source
    #                       row of each slot-sorted edge (padding: n_rows)
    seg_end: Any = None   # [p, cap_total] int32: permuted index of each run's
    #                       last edge; E_nn_max for slots past the unique count
    cap_peer: int = 0   # per-peer slot capacity (multiple of 32)
    cap_total: int = 0  # unique (owner, local) capacity per partition
    max_run: int = 1    # longest run (edges of one slot) over all partitions


jax.tree_util.register_dataclass(
    ExchangePlan,
    data_fields=("perm", "seg_ids", "seg_owner", "seg_pos", "seg_local",
                 "recv_local", "src_rows", "seg_end"),
    meta_fields=("cap_peer", "cap_total", "max_run"),
)


@dataclass
class EdgeWeights:
    nn: Any
    nd: Any
    dn: Any
    dd: Any


jax.tree_util.register_dataclass(EdgeWeights, data_fields=("nn", "nd", "dn", "dd"), meta_fields=())


def build_exchange_plan(pg: PartitionedGraph) -> ExchangePlan:
    """Host-side: sort each partition's nn edges by (owner, local dst) and
    record the unique-destination segments and their slots, each
    segment's last sorted edge, and the sorted edges' source rows."""
    p = pg.p
    e_max = pg.nn.e_max
    cols = np.asarray(pg.nn.cols)         # local dst id at the owner
    owners = np.asarray(pg.nn_owner)      # owner partition per nn edge
    rowids = np.asarray(pg.nn.rowids)
    m = np.asarray(pg.nn.m)

    perms = np.tile(np.arange(e_max, dtype=np.int32), (p, 1))
    seg_ids = np.zeros((p, e_max), dtype=np.int32)
    src_rows = np.full((p, e_max), pg.nn.n_rows, dtype=np.int32)
    seg_data = []
    run_ends = []
    max_run = 1
    for k in range(p):
        mk = int(m[k])
        owner = owners[k, :mk]
        local = cols[k, :mk]
        order = np.lexsort((local, owner)).astype(np.int32)
        so, sl = owner[order], local[order]
        new_seg = np.ones(mk, dtype=bool)
        if mk > 1:
            new_seg[1:] = (so[1:] != so[:-1]) | (sl[1:] != sl[:-1])
        sid = np.cumsum(new_seg) - 1
        u_owner = so[new_seg]
        u_local = sl[new_seg]
        # slot within owner's bin
        u_pos = np.zeros(u_owner.shape[0], dtype=np.int32)
        for peer in range(p):
            sel = u_owner == peer
            u_pos[sel] = np.arange(sel.sum(), dtype=np.int32)
        perms[k, :mk] = order
        src_rows[k, :mk] = rowids[k, :mk][order]
        # padding edges get a dedicated trash segment
        seg_ids[k, :mk] = sid
        seg_ids[k, mk:] = (sid[-1] + 1) if mk else 0
        seg_data.append((u_owner, u_pos, u_local))
        starts = np.flatnonzero(new_seg)
        ends = np.append(starts[1:], mk) - 1
        run_ends.append(ends)
        if mk:
            max_run = max(max_run, int((ends - starts).max()) + 1)

    cap_peer = 1
    for u_owner, _, _ in seg_data:
        if u_owner.size:
            cap_peer = max(cap_peer, int(np.bincount(u_owner, minlength=p).max()))
    cap_peer = -(-cap_peer // 32) * 32          # word-align for bit packing
    cap_total = max(1, max((u[0].size for u in seg_data), default=1))
    seg_owner = np.full((p, cap_total), p, dtype=np.int32)
    seg_pos = np.zeros((p, cap_total), dtype=np.int32)
    seg_local = np.zeros((p, cap_total), dtype=np.int32)
    recv_local = np.full((p, p, cap_peer), -1, dtype=np.int32)
    # slots past a partition's unique count point one past the last edge
    seg_end = np.full((p, cap_total), e_max, dtype=np.int32)
    for k, (uo, up, ul) in enumerate(seg_data):
        seg_owner[k, : uo.size] = uo
        seg_pos[k, : up.size] = up
        seg_local[k, : ul.size] = ul
        seg_end[k, : uo.size] = run_ends[k]
        # receiver-side inverse: owner j's table gets (sender k, slot) -> local
        recv_local[uo, k, up] = ul
    return ExchangePlan(
        perm=perms, seg_ids=seg_ids, seg_owner=seg_owner, seg_pos=seg_pos,
        seg_local=seg_local, recv_local=recv_local,
        src_rows=src_rows, seg_end=seg_end,
        cap_peer=cap_peer, cap_total=cap_total, max_run=max_run,
    )


def build_edge_weights(pg: PartitionedGraph, degrees: np.ndarray, mode: str = "sym") -> EdgeWeights:
    """Per-edge weights: 'sym' = 1/sqrt(d_u d_v) (GCN), 'mean' = 1/d_v,
    'sum' = 1. Computed host-side from global degrees."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    deg = np.maximum(degrees.astype(np.float64), 1.0)
    dvids = np.asarray(pg.delegate_vids).reshape(-1)[: max(pg.d, 1)]
    nn_owner = np.asarray(pg.nn_owner)

    def w(csr: CSR, src_kind: str, dst_kind: str) -> np.ndarray:
        rowids = np.asarray(csr.rowids)
        cols = np.asarray(csr.cols)
        p, e = rowids.shape
        out = np.ones((p, e), dtype=np.float32)
        if mode == "sum":
            return out
        for k in range(p):
            mk = int(np.asarray(csr.m)[k])
            r, c = rowids[k, :mk], cols[k, :mk]
            if src_kind == "n":
                src_v = layout.global_of(np.full(mk, k), r)
            else:
                src_v = dvids[np.minimum(r, len(dvids) - 1)]
            if dst_kind == "g":
                dst_v = layout.global_of(nn_owner[k, :mk], c)
            elif dst_kind == "n":
                dst_v = layout.global_of(np.full(mk, k), c)
            else:
                dst_v = dvids[np.minimum(c, len(dvids) - 1)]
            if mode == "sym":
                out[k, :mk] = (1.0 / np.sqrt(deg[src_v] * deg[dst_v])).astype(np.float32)
            elif mode == "mean":
                out[k, :mk] = (1.0 / deg[dst_v]).astype(np.float32)
            else:
                raise ValueError(mode)
        return out

    return EdgeWeights(
        nn=w(pg.nn, "n", "g"), nd=w(pg.nd, "n", "d"),
        dn=w(pg.dn, "d", "n"), dd=w(pg.dd, "d", "d"),
    )


def _gather_messages(csr: CSR, x_src: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Per-edge messages: x_src[row(e)] * w_e, padding rows -> 0."""
    x_ext = jnp.concatenate([x_src, jnp.zeros((1, x_src.shape[1]), x_src.dtype)])
    return x_ext[csr.rowids] * w[:, None]


def _segment_to_cols(csr: CSR, msgs: jnp.ndarray, n_dst: int) -> jnp.ndarray:
    out = jnp.zeros((n_dst, msgs.shape[1]), msgs.dtype)
    return out.at[csr.cols].add(msgs, mode="drop")


def propagate(
    pgv: PartitionedGraph,
    plan: ExchangePlan,
    weights: EdgeWeights,
    x_n: jnp.ndarray,   # [n_local, F] local normal features
    x_d: jnp.ndarray,   # [d, F] replicated delegate features
    axis_names,
    comm_cfg: comm.CommConfig | None = None,
):
    """One aggregation round: returns (out_n [n_local, F], out_d [d, F]).

    out_d is identical on all partitions (a global sum -- the fused
    ``psum`` by default, or the allgather / ring / hierarchical combine
    named by ``comm_cfg.delegate``), mirroring the paper's replicated
    delegate state. :func:`payload_round_bytes` gives the static wire
    model of one round under the same config.
    """
    nl = x_n.shape[0]
    d = x_d.shape[0]

    # delegate destinations: nd + dd partials -> global reduction
    part_d = _segment_to_cols(pgv.nd, _gather_messages(pgv.nd, x_n, weights.nd), d)
    part_d = part_d + _segment_to_cols(pgv.dd, _gather_messages(pgv.dd, x_d, weights.dd), d)
    out_d = comm.delegate_allreduce_sum(part_d, axis_names, comm_cfg)

    # normal destinations: dn is local by construction
    out_n = _segment_to_cols(pgv.dn, _gather_messages(pgv.dn, x_d, weights.dn), nl)

    # nn: static-plan pre-aggregation, payload all_to_all, scatter-add
    msgs = _gather_messages(pgv.nn, x_n, weights.nn)          # [E, F]
    msgs = msgs[plan.perm]                                    # sorted by (owner, local)
    partials = jax.ops.segment_sum(msgs, plan.seg_ids, num_segments=plan.cap_total + 1)[:-1]
    p = pgv.p
    cap = plan.cap_peer
    buf_vals = jnp.zeros((p, cap, x_n.shape[1]), x_n.dtype)
    buf_ids = jnp.full((p, cap), -1, dtype=jnp.int32)
    rows = jnp.minimum(plan.seg_owner, p - 1)
    ok = plan.seg_owner < p
    buf_vals = buf_vals.at[rows, plan.seg_pos].add(jnp.where(ok[:, None], partials, 0), mode="drop")
    buf_ids = buf_ids.at[rows, plan.seg_pos].max(jnp.where(ok, plan.seg_local, -1), mode="drop")
    r_ids, r_vals = comm.exchange_payload(buf_ids, buf_vals, axis_names)
    r_ids = r_ids.reshape(-1)
    r_vals = r_vals.reshape(-1, x_n.shape[1])
    out_n = out_n.at[jnp.clip(r_ids, 0, nl - 1)].add(
        jnp.where((r_ids >= 0)[:, None], r_vals, 0), mode="drop"
    )
    return out_n, out_d


def fetch_nn_dst(
    pgv: PartitionedGraph,
    plan: ExchangePlan,
    x_n: jnp.ndarray,      # [n_local, F] this partition's normal features
    axis_names,
) -> jnp.ndarray:
    """Reverse exchange: per-nn-edge *destination* features.

    Edge-MLP models (MeshGraphNet/GraphCast/MACE) need both endpoint
    features per edge. By Algorithm 1's placement every non-nn edge has both
    endpoints locally available (delegates are replicated); only nn edges
    have a remote destination. The static exchange plan is symmetric, so the
    owner of each unique remote destination ships its feature vector back
    along the same slots: one extra payload all_to_all, no new plan.

    Returns [E_nn_max, F] dst features aligned with pgv.nn edge order.
    """
    p = pgv.p
    cap = plan.cap_peer
    f = x_n.shape[1]
    # 1) tell owners which locals we need (the id buffer of the plan)
    buf_ids = jnp.full((p, cap), -1, dtype=jnp.int32)
    rows = jnp.minimum(plan.seg_owner, p - 1)
    ok = plan.seg_owner < p
    buf_ids = buf_ids.at[rows, plan.seg_pos].max(
        jnp.where(ok, plan.seg_local, -1), mode="drop")
    req = lax.all_to_all(buf_ids, axis_names, split_axis=0, concat_axis=0, tiled=True)
    # 2) owners gather and ship back
    reply_vals = jnp.where(
        (req >= 0)[..., None],
        x_n[jnp.clip(req, 0, x_n.shape[0] - 1)],
        0.0,
    )                                                    # [p, cap, F]
    got = lax.all_to_all(reply_vals, axis_names, split_axis=0, concat_axis=0, tiled=True)
    # 3) scatter back to unique-dst segments, then expand to edges
    seg_vals = jnp.zeros((plan.cap_total + 1, f), x_n.dtype)
    seg_vals = seg_vals.at[
        jnp.where(ok, jnp.arange(plan.cap_total), plan.cap_total),
    ].add(got[rows, plan.seg_pos] * ok[:, None], mode="drop")
    # per-edge (sorted order) -> original edge order via the plan permutation
    per_edge_sorted = seg_vals[jnp.minimum(plan.seg_ids, plan.cap_total)]
    inv = jnp.zeros_like(plan.perm).at[plan.perm].set(
        jnp.arange(plan.perm.shape[0], dtype=plan.perm.dtype))
    return per_edge_sorted[inv]


def aggregate_messages(
    pgv: PartitionedGraph,
    plan: ExchangePlan,
    msgs: dict,            # {"nn","nd","dn","dd"}: [E_max, F] per-edge messages
    axis_names,
    comm_cfg: comm.CommConfig | None = None,
):
    """Two-class aggregation of arbitrary per-edge messages (the BFS comm
    model generalized): delegate destinations globally summed (strategy
    per ``comm_cfg``), nn remote destinations pre-aggregated +
    all_to_all'd. Returns (out_n [n_local,F], out_d [d,F])."""
    nl = pgv.n_local
    d = max(pgv.d, 1)
    f = msgs["nn"].shape[1]
    part_d = _segment_to_cols(pgv.nd, msgs["nd"], d) + _segment_to_cols(pgv.dd, msgs["dd"], d)
    out_d = comm.delegate_allreduce_sum(part_d, axis_names, comm_cfg)
    out_n = _segment_to_cols(pgv.dn, msgs["dn"], nl)
    m = msgs["nn"][plan.perm]
    partials = jax.ops.segment_sum(m, plan.seg_ids, num_segments=plan.cap_total + 1)[:-1]
    p = pgv.p
    cap = plan.cap_peer
    buf_vals = jnp.zeros((p, cap, f), m.dtype)
    buf_ids = jnp.full((p, cap), -1, dtype=jnp.int32)
    rows = jnp.minimum(plan.seg_owner, p - 1)
    ok = plan.seg_owner < p
    buf_vals = buf_vals.at[rows, plan.seg_pos].add(jnp.where(ok[:, None], partials, 0), mode="drop")
    buf_ids = buf_ids.at[rows, plan.seg_pos].max(jnp.where(ok, plan.seg_local, -1), mode="drop")
    r_ids, r_vals = comm.exchange_payload(buf_ids, buf_vals, axis_names)
    r_ids = r_ids.reshape(-1)
    r_vals = r_vals.reshape(-1, f)
    out_n = out_n.at[jnp.clip(r_ids, 0, nl - 1)].add(
        jnp.where((r_ids >= 0)[:, None], r_vals, 0), mode="drop")
    return out_n, out_d


def payload_round_bytes(
    plan: ExchangePlan,
    *,
    axis_sizes,
    d: int,
    feat: int,
    itemsize: int = 4,
    comm_cfg: comm.CommConfig | None = None,
) -> dict:
    """Static per-device wire model of one :func:`propagate` round.

    Payload shapes are graph-static, so -- unlike the traversal paths,
    whose adaptive formats need traced counters -- the engine's wire
    volume is a host-side formula: the delegate sum of ``[d, feat]``
    under the configured combine strategy plus the nn payload
    all_to_all of ``(id + feat * itemsize)`` bytes per plan slot.
    ``axis_sizes`` are the partition-axis sizes (e.g. ``mesh.shape``
    values), matching the byte convention of ``comm/base.py``.
    """
    axis_sizes = tuple(int(s) for s in axis_sizes)
    cplan = comm.CommPlan(cfg=comm_cfg or comm.CommConfig(),
                          axes=tuple(f"ax{i}" for i in range(len(axis_sizes))),
                          sizes=axis_sizes)
    return {
        "delegate_bytes": cplan.delegate_bytes(d * feat, itemsize, "sum"),
        "nn_payload_bytes": cplan.a2a_bytes(
            plan.cap_peer * (4 + feat * itemsize)),
        "p": cplan.p,
    }


def edge_endpoints(
    pgv: PartitionedGraph,
    plan: ExchangePlan,
    x_n: jnp.ndarray,   # [n_local, F]
    x_d: jnp.ndarray,   # [d, F] replicated
    axis_names,
) -> dict:
    """Per-subgraph (src_feats, dst_feats) pairs, each [E_max, F]. Only the
    nn destination requires communication (fetch_nn_dst)."""
    def gather_rows(csr, x_src):
        x_ext = jnp.concatenate([x_src, jnp.zeros((1, x_src.shape[1]), x_src.dtype)])
        return x_ext[csr.rowids]

    def gather_cols(csr, x_dst, n_dst):
        return x_dst[jnp.clip(csr.cols, 0, n_dst - 1)]

    nl, d = x_n.shape[0], x_d.shape[0]
    return {
        "nn": (gather_rows(pgv.nn, x_n), fetch_nn_dst(pgv, plan, x_n, axis_names)),
        "nd": (gather_rows(pgv.nd, x_n), gather_cols(pgv.nd, x_d, d)),
        "dn": (gather_rows(pgv.dn, x_d), gather_cols(pgv.dn, x_n, nl)),
        "dd": (gather_rows(pgv.dd, x_d), gather_cols(pgv.dd, x_d, d)),
    }


def edge_valid_masks(pgv: PartitionedGraph) -> dict:
    """[E_max] validity per subgraph (padding edges excluded)."""
    out = {}
    for kind in ("nn", "nd", "dn", "dd"):
        csr = pgv.subgraph(kind)
        out[kind] = csr.rowids < csr.n_rows
    return out


def scatter_features(pg: PartitionedGraph, x_global: np.ndarray):
    """Host-side: split a global [n, F] feature matrix into
    (x_n [p, n_local, F], x_d [d, F]) following the layout."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    vids = np.arange(pg.n, dtype=np.int64)
    x_n = np.zeros((pg.p, pg.n_local, x_global.shape[1]), x_global.dtype)
    x_n[layout.part_of(vids), layout.local_of(vids)] = x_global
    dvids = np.asarray(pg.delegate_vids).reshape(-1)[: max(pg.d, 1)]
    x_d = x_global[dvids] if pg.d else np.zeros((1, x_global.shape[1]), x_global.dtype)
    return x_n, x_d


def gather_features(pg: PartitionedGraph, out_n: np.ndarray, out_d: np.ndarray) -> np.ndarray:
    """Host-side inverse of scatter_features (delegate rows win)."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    vids = np.arange(pg.n, dtype=np.int64)
    out = np.asarray(out_n)[layout.part_of(vids), layout.local_of(vids)].copy()
    if pg.d:
        dvids = np.asarray(pg.delegate_vids).reshape(-1)[: pg.d]
        out[dvids] = np.asarray(out_d)[: pg.d]
    return out
