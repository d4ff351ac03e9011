"""Distributed BFS / direction-optimized BFS on the four-subgraph
representation (paper Sections IV and V).

One superstep processes the four subgraphs:

  ``nn``  forward push only (paper: DO is never used for nn), producing
          remote normal-vertex updates -> binned all_to_all exchange;
  ``nd``  push from the normal frontier into delegate candidates, or pull
          (via the dn subgraph) for unvisited delegates  -> delegate reduce;
  ``dd``  push/pull among delegates                       -> delegate reduce;
  ``dn``  push from the delegate frontier into local normals, or pull (via
          the nd subgraph) for unvisited normals          -> local only.

The per-subgraph traversal direction is chosen by the paper's workload
estimates: FV = sum of frontier out-degrees, BV ~= |U| (q + s) / q, with two
switch factors per DO subgraph. The step function is written against a named
partition axis, so it runs identically under ``jax.vmap(axis_name=...)``
(single-device emulation / tests) and ``jax.shard_map`` (mesh execution).

TPU adaptation notes (DESIGN.md Section 3): pushes are edge-parallel sweeps
gated by frontier gathers; pulls are chunked gathers under ``lax.while_loop``
(the vectorized analog of the paper's early-exit parent scan).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import comm
from .types import CSR, INF_LEVEL, PartitionedGraph, PartitionLayout

# The single-source sweep's combine monoids, stated once through the comm
# layer's typed registry (``core/comm/base.py``) instead of ad-hoc
# constants: the delegate level reduction is the ``"min"`` spec -- its
# identity *is* INF_LEVEL, so unvisited candidates ride the reduction as
# the identity and can never win -- and the u8 visited-mask path is the
# bit-OR monoid over {0, 1} (max == OR there). The lane-word msBFS sibling
# (``msbfs.py``) threads the same registry through its payload plane.
_MIN_SPEC = comm.COMBINE_SPECS["min"]
assert int(_MIN_SPEC.identity) == int(INF_LEVEL), (
    "INF_LEVEL must equal the min-combine identity: unvisited level "
    "candidates enter delegate reductions as the identity element")

# -----------------------------------------------------------------------------
# Config / state


@dataclass(frozen=True)
class BFSConfig:
    max_iters: int = 64
    cap_nn: int = 0          # per-peer a2a capacity; 0 -> E_nn_max (safe but
                             # p-times oversized); <0 -> |cap_nn| * E_nn_max / p
    enable_do: bool = True
    delegate_u8: bool = False  # communicate the delegate update as a uint8
                               # OR-mask (1 B/delegate) instead of int32
                               # levels (4 B) -- levels derived locally
    static_exchange: bool = False  # nn exchange as 1-bit masks over the
                                   # static (owner, local) slot layout of an
                                   # ExchangePlan: no runtime sort, ~32x less
                                   # a2a volume than 4-byte ids (beyond-paper)
    uniquify: bool = False
    pull_chunk: int = 32
    # direction-switch factors (paper Section VI-B): factor0 switches
    # forward->backward, factor1 switches back. Order: (dd, dn, nd).
    factor0: tuple = (0.5, 0.05, 1e-7)
    factor1: tuple = (1e-3, 1e-4, 1e-9)
    # communication strategies (repro.core.comm.CommConfig): delegate
    # combine (auto = the seed's fused pmin/pmax, or allgather / ring /
    # hierarchical) and the nn wire format of the static exchange (dense
    # slot bitmask / sparse id list / frontier-adaptive switch)
    comm: comm.CommConfig = comm.CommConfig()
    # Out-of-core sweep mode: > 0 streams the dd/nd/dn pushes and the
    # static-exchange slot accumulate over fixed-size edge blocks under
    # ``lax.scan`` and row-blocks the pulls (``edge_chunk // pull_chunk``
    # rows per block) -- same contract as ``MSBFSConfig.edge_chunk``:
    # bit-identical answers/counters, only peak memory changes. The legacy
    # ``bin_by_owner`` nn path (static_exchange=False) stays monolithic:
    # its [E] bool active array is already minimal. 0 = monolithic.
    edge_chunk: int = 0
    # True carries per-sweep device telemetry (``tm_*`` fields of BFSState:
    # per-shard frontier popcounts + the direction-decision bitmask)
    # through the state; False (default) keeps zero-size dummies so the
    # disabled carry compiles away. Same contract as
    # ``MSBFSConfig.telemetry`` -- answers and counters are bit-identical
    # either way.
    telemetry: bool = False


@dataclass
class BFSState:
    level_n: Any      # [p, n_local] int32
    level_d: Any      # [p, d] int32 (replicated content)
    backward: Any     # [p, 3] bool -- current direction per (dd, dn, nd)
    it: Any           # [p] int32
    done: Any         # [p] bool
    # per-iteration statistics [p, max_iters]:
    work_fwd: Any     # edges examined by pushes
    work_bwd: Any     # parent checks by pulls
    nn_sent: Any      # normal vertices sent (post-binning)
    nn_overflow: Any  # dropped by capacity (must be 0 for a valid run)
    delegate_round: Any  # 1 if the delegate reduction carried updates
    # wire-volume accounting (per-device bytes per sweep; comm/base.py
    # byte convention -- summing partition rows gives cluster traffic):
    wire_delegate: Any
    wire_nn: Any
    nn_sparse: Any    # 1 if the static exchange shipped the sparse format
    # device-plane sweep telemetry (cfg.telemetry; zero-size [p, 0] dummies
    # otherwise). Frontier counts accumulate with .add (wire-counter slot
    # convention); the direction bitmask records the sweep's decision:
    tm_frontier_n: Any  # [p, max_iters] int32 -- per-shard frontier popcount
    tm_frontier_d: Any  # [p, max_iters] int32 (replicated content)
    tm_backward: Any    # [p, max_iters] int32 -- bits (1, 2, 4) set when the
                        # (dd, dn, nd) subgraph pulled this sweep


jax.tree_util.register_dataclass(
    BFSState,
    data_fields=(
        "level_n", "level_d", "backward", "it", "done",
        "work_fwd", "work_bwd", "nn_sent", "nn_overflow", "delegate_round",
        "wire_delegate", "wire_nn", "nn_sparse",
        "tm_frontier_n", "tm_frontier_d", "tm_backward",
    ),
    meta_fields=(),
)


def device_view(pg: PartitionedGraph) -> PartitionedGraph:
    """All data leaves get a leading partition axis (delegate data tiled);
    host-only payloads (eidx) are stripped so they never reach devices."""
    dv = np.broadcast_to(
        np.asarray(pg.delegate_vids).astype(np.int32),
        (pg.p, np.asarray(pg.delegate_vids).shape[0]))
    strip = lambda csr: dataclasses.replace(csr, eidx=None)
    return dataclasses.replace(
        pg, delegate_vids=dv, nn=strip(pg.nn), nd=strip(pg.nd),
        dn=strip(pg.dn), dd=strip(pg.dd))


def init_state(pg: PartitionedGraph, source: int, cfg: BFSConfig) -> BFSState:
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    p, nl = pg.p, pg.n_local
    d = max(pg.d, 1)
    level_n = np.full((p, nl), INF_LEVEL, dtype=np.int32)
    level_d = np.full((p, d), INF_LEVEL, dtype=np.int32)
    dvids = np.asarray(pg.delegate_vids)
    pos = np.searchsorted(dvids, source)
    if pg.d and pos < pg.d and dvids[pos] == source:
        level_d[:, pos] = 0
    else:
        level_n[int(layout.part_of(np.int64(source))), int(layout.local_of(np.int64(source)))] = 0
    mi = cfg.max_iters
    z = lambda dt: np.zeros((p, mi), dtype=dt)
    tmi = mi if cfg.telemetry else 0
    tm = lambda: np.zeros((p, tmi), dtype=np.int32)
    return BFSState(
        level_n=level_n, level_d=level_d,
        backward=np.zeros((p, 3), dtype=bool),
        it=np.zeros((p,), dtype=np.int32),
        done=np.zeros((p,), dtype=bool),
        work_fwd=z(np.int32), work_bwd=z(np.int32), nn_sent=z(np.int32),
        nn_overflow=z(np.int32), delegate_round=z(np.int32),
        wire_delegate=z(np.int32), wire_nn=z(np.int32), nn_sparse=z(np.int32),
        tm_frontier_n=tm(), tm_frontier_d=tm(), tm_backward=tm(),
    )


# -----------------------------------------------------------------------------
# Traversal primitives


def _row_degrees(csr: CSR) -> jnp.ndarray:
    return csr.offsets[1:] - csr.offsets[:-1]


def _push_active(csr: CSR, frontier_rows: jnp.ndarray) -> jnp.ndarray:
    """Edge-parallel frontier gather: active flag per (padded) edge slot."""
    f_ext = jnp.concatenate([frontier_rows, jnp.zeros((1,), bool)])
    return f_ext[csr.rowids]


def _push_scatter(csr: CSR, active: jnp.ndarray, n_dst: int) -> jnp.ndarray:
    """Scatter-OR of active edges onto the destination domain."""
    out = jnp.zeros((n_dst,), dtype=jnp.bool_)
    return out.at[csr.cols].max(active, mode="drop")


def _push_fused(csr: CSR, frontier_rows: jnp.ndarray, n_dst: int,
                edge_chunk: int = 0) -> jnp.ndarray:
    """Fused gather + scatter-OR push; ``edge_chunk > 0`` streams edge
    blocks through ``lax.scan`` (the single-bit sibling of
    ``msbfs._push_multi`` -- bit-identical, memory only)."""
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        return _push_scatter(csr, _push_active(csr, frontier_rows), n_dst)
    f_ext = jnp.concatenate([frontier_rows, jnp.zeros((1,), bool)])
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids, (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    col = jnp.pad(csr.cols, (0, pad)).reshape(nblk, edge_chunk)

    def body(out, blk):
        r, c = blk
        return out.at[c].max(f_ext[r], mode="drop"), None

    out, _ = lax.scan(body, jnp.zeros((n_dst,), jnp.bool_), (rid, col))
    return out


def _nn_slots_bits(csr: CSR, frontier_rows: jnp.ndarray, plan,
                   edge_chunk: int = 0):
    """Sender-side unique-slot occupancy for the static-exchange nn path:
    ``(sa [cap_total] bool, act_sum int32)`` with ``act_sum`` the exact
    ``fv_nn_work`` term (``plan.perm`` is a permutation, so the permuted
    sum is identical). Chunked variant streams edge blocks."""
    f_ext = jnp.concatenate([frontier_rows, jnp.zeros((1,), bool)])
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        act = f_ext[csr.rowids]
        sa = jnp.zeros((plan.cap_total + 1,), jnp.bool_).at[plan.seg_ids].max(
            act[plan.perm])[: plan.cap_total]
        return sa, jnp.sum(act.astype(jnp.int32))
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids[plan.perm], (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    seg = jnp.pad(plan.seg_ids, (0, pad),
                  constant_values=plan.cap_total).reshape(nblk, edge_chunk)

    def body(carry, blk):
        sa, tot = carry
        r, s = blk
        act = f_ext[r]
        return (sa.at[s].max(act), tot + jnp.sum(act.astype(jnp.int32))), None

    (sa, tot), _ = lax.scan(
        body, (jnp.zeros((plan.cap_total + 1,), jnp.bool_), jnp.int32(0)),
        (rid, seg))
    return sa[: plan.cap_total], tot


def _pull_rows(cols_table, e_max, starts, ends, rows_active, col_frontier,
               chunk):
    """The pull while_loop over one set of rows (possibly a row-block
    slice; the cols table and frontier are always full)."""
    deg = ends - starts
    n_rows = starts.shape[0]
    max_chunks = -(-e_max // chunk)

    def cond(carry):
        k, found, work = carry
        remaining = rows_active & (~found) & (deg > k * chunk)
        return (k < max_chunks) & jnp.any(remaining)

    def body(carry):
        k, found, work = carry
        remaining = rows_active & (~found) & (deg > k * chunk)
        base = starts + k * chunk
        idx = base[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        valid = remaining[:, None] & (idx < ends[:, None])
        cols = cols_table[jnp.clip(idx, 0, e_max - 1)]
        hit = valid & col_frontier[cols]
        found = found | jnp.any(hit, axis=1)
        work = work + jnp.sum(valid.astype(jnp.int32))
        return k + 1, found, work

    k0 = jnp.int32(0)
    found0 = jnp.zeros((n_rows,), dtype=jnp.bool_)
    _, found, work = lax.while_loop(cond, body, (k0, found0, jnp.int32(0)))
    return found, work


def _pull_chunked(
    csr: CSR, rows_active: jnp.ndarray, col_frontier: jnp.ndarray, chunk: int,
    row_block: int = 0,
):
    """Bottom-up pull: rows scan their parent lists chunk-by-chunk, dropping
    out as soon as a frontier parent is found (paper Section IV-B adapted to
    vectorized chunks). Returns (found [n_rows] bool, work scalar int32).

    ``row_block > 0`` scans fixed-height row blocks in sequence (the
    out-of-core mode) -- bit-identical: each row's early exit and work
    contribution depend only on its own parent list."""
    starts = csr.offsets[:-1]
    ends = csr.offsets[1:]
    if row_block <= 0 or row_block >= csr.n_rows:
        return _pull_rows(csr.cols, csr.e_max, starts, ends, rows_active,
                          col_frontier, chunk)
    n_rows = csr.n_rows
    nblk = -(-n_rows // row_block)
    pad = nblk * row_block - n_rows
    st = jnp.pad(starts, (0, pad)).reshape(nblk, row_block)
    en = jnp.pad(ends, (0, pad)).reshape(nblk, row_block)
    ra = jnp.pad(rows_active, (0, pad)).reshape(nblk, row_block)

    def body(_, blk):
        s, e, a = blk
        return None, _pull_rows(csr.cols, csr.e_max, s, e, a, col_frontier,
                                chunk)

    _, (found, works) = lax.scan(body, None, (st, en, ra))
    return found.reshape(-1)[: n_rows], jnp.sum(works)


def _count(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(mask.astype(jnp.int32))


def _decide_direction(backward, fv, bv, f0, f1):
    """Paper Section IV-B: forward if FV <= factor0*BV else backward, with
    hysteresis through factor1 on the way back."""
    go_back = (~backward) & (fv.astype(jnp.float32) > f0 * bv)
    go_fwd = backward & (fv.astype(jnp.float32) < f1 * bv)
    return (backward | go_back) & ~go_fwd


# -----------------------------------------------------------------------------
# One superstep (runs per-partition under an axis name)


def bfs_step(
    pgv: PartitionedGraph, state: BFSState, cfg: BFSConfig, axis_names, plan=None
) -> BFSState:
    p, nl = pgv.p, pgv.n_local
    d = state.level_d.shape[-1]
    it = state.it
    cplan = comm.plan_for(cfg.comm, axis_names)

    unvisited_n = (state.level_n == INF_LEVEL) & pgv.normal_valid
    unvisited_d = state.level_d == INF_LEVEL
    frontier_n = (state.level_n == it) & pgv.normal_valid
    frontier_d = state.level_d == it

    deg_nn = _row_degrees(pgv.nn)
    deg_nd = _row_degrees(pgv.nd)
    deg_dn = _row_degrees(pgv.dn)
    deg_dd = _row_degrees(pgv.dd)

    # ---- direction decisions (per subgraph, local; paper Section IV-B) ----
    fv_dd = jnp.sum(jnp.where(frontier_d, deg_dd, 0))
    fv_dn = jnp.sum(jnp.where(frontier_d, deg_dn, 0))
    fv_nd = jnp.sum(jnp.where(frontier_n, deg_nd, 0))

    def bv_estimate(q, s, u):
        qf = q.astype(jnp.float32)
        sf = s.astype(jnp.float32)
        return jnp.where(q > 0, u.astype(jnp.float32) * (qf + sf) / jnp.maximum(qf, 1.0), jnp.inf)

    bv_dd = bv_estimate(_count(frontier_d & pgv.dd_src_mask), _count(unvisited_d & pgv.dd_src_mask),
                        _count(unvisited_d & pgv.dd_src_mask))
    bv_dn = bv_estimate(_count(frontier_d & pgv.dn_src_mask), _count(unvisited_d & pgv.dn_src_mask),
                        _count(unvisited_n & pgv.nd_src_mask))
    bv_nd = bv_estimate(_count(frontier_n & pgv.nd_src_mask), _count(unvisited_n & pgv.nd_src_mask),
                        _count(unvisited_d & pgv.dn_src_mask))

    if cfg.enable_do:
        backward = jnp.stack([
            _decide_direction(state.backward[0], fv_dd, bv_dd, cfg.factor0[0], cfg.factor1[0]),
            _decide_direction(state.backward[1], fv_dn, bv_dn, cfg.factor0[1], cfg.factor1[1]),
            _decide_direction(state.backward[2], fv_nd, bv_nd, cfg.factor0[2], cfg.factor1[2]),
        ])
    else:
        backward = jnp.zeros((3,), dtype=jnp.bool_)
    bwd_dd, bwd_dn, bwd_nd = backward[0], backward[1], backward[2]

    # edge_chunk > 0: stream pushes / the slot accumulate over edge blocks
    # and row-block the pulls (see BFSConfig.edge_chunk)
    ec = cfg.edge_chunk
    rb = max(1, ec // max(cfg.pull_chunk, 1)) if ec > 0 else 0

    # ---- dd: delegate -> delegate ----------------------------------------
    push_dd = _push_fused(pgv.dd, frontier_d, d, ec)
    pull_dd, work_dd_b = _pull_chunked(pgv.dd, unvisited_d & pgv.dd_src_mask, frontier_d, cfg.pull_chunk, rb)
    cand_dd = jnp.where(bwd_dd, pull_dd, push_dd)

    # ---- nd: normal -> delegate (pull uses the dn subgraph) ---------------
    push_nd = _push_fused(pgv.nd, frontier_n, d, ec)
    fr_n_ext = frontier_n
    pull_nd, work_nd_b = _pull_chunked(pgv.dn, unvisited_d & pgv.dn_src_mask, fr_n_ext, cfg.pull_chunk, rb)
    cand_nd = jnp.where(bwd_nd, pull_nd, push_nd)

    # ---- dn: delegate -> normal (pull uses the nd subgraph) ---------------
    push_dn = _push_fused(pgv.dn, frontier_d, nl, ec)
    pull_dn, work_dn_b = _pull_chunked(pgv.nd, unvisited_n & pgv.nd_src_mask, frontier_d, cfg.pull_chunk, rb)
    new_n_local = jnp.where(bwd_dn, pull_dn, push_dn)

    # ---- nn: normal -> normal, forward only, remote exchange --------------
    if cfg.static_exchange:
        # SPerf: 1 bit per unique (owner, local) slot on the static plan --
        # no runtime sort, uniquification for free, fixed cap_peer/8 bytes
        # (or the sparse / frontier-adaptive slot-id / compressed codec
        # format per cfg.comm.nn, chosen inside the comm layer)
        sa, act_nn_sum = _nn_slots_bits(pgv.nn, frontier_n, plan, ec)
        rows = jnp.minimum(plan.seg_owner, p - 1)
        ok = plan.seg_owner < p
        dense = jnp.zeros((p, plan.cap_peer), jnp.bool_).at[rows, plan.seg_pos].max(
            sa & ok, mode="drop")
        recv_mask, nn_bytes, nn_sparse, ovf = comm.nn_exchange_bits(
            cplan, dense, plan.recv_local, nl)
        sent = jnp.sum(sa.astype(jnp.int32))
    else:
        # legacy runtime-binned path: kept monolithic (the [E] bool active
        # array is already the minimal working set; see BFSConfig.edge_chunk)
        act_nn = _push_active(pgv.nn, frontier_n)
        act_nn_sum = fv_nn_work(act_nn)
        if cfg.cap_nn > 0:
            cap = cfg.cap_nn
        elif cfg.cap_nn < 0:
            cap = max(-cfg.cap_nn * pgv.nn.e_max // p, 8)
        else:
            cap = pgv.nn.e_max
        buf, ovf, sent = comm.bin_by_owner(
            pgv.nn_owner, pgv.nn.cols, act_nn, p=p, cap=cap, uniquify=cfg.uniquify,
        )
        recv = comm.exchange_normal(buf, axis_names)
        recv_flat = recv.reshape(-1)
        recv_mask = jnp.zeros((nl,), dtype=jnp.bool_).at[
            jnp.clip(recv_flat, 0, nl - 1)
        ].max(recv_flat >= 0, mode="drop")
        nn_bytes = jnp.int32(cplan.a2a_bytes(cap * 4))   # [p, cap] int32 ids
        nn_sparse = jnp.int32(0)

    # ---- delegate global reduction (the paper's bitmask all-reduce, via
    # the pluggable combine strategies of cfg.comm.delegate) ----------------
    cand_d = cand_dd | cand_nd
    if cfg.delegate_u8:
        # 1 B/delegate OR-mask; every partition sets level = it+1 locally.
        # (max over {0,1} == the paper's bitwise OR of visited masks.)
        delta, d_bytes = comm.delegate_combine(
            cplan, (cand_d & unvisited_d).astype(jnp.uint8), "max")
        newly = (delta > 0) & unvisited_d
        new_level_d = jnp.where(newly, it + 1, state.level_d)
        new_d_any = jnp.any(newly)
    else:
        cand_levels = jnp.where(cand_d & unvisited_d, it + 1,
                                _MIN_SPEC.identity).astype(_MIN_SPEC.wire_dtype)
        reduced, d_bytes = comm.delegate_combine(cplan, cand_levels, "min")
        new_level_d = jnp.minimum(state.level_d, reduced)
        new_d_any = jnp.any(new_level_d < state.level_d)

    # ---- normal level updates ---------------------------------------------
    new_n_mask = (new_n_local | recv_mask) & unvisited_n
    new_level_n = jnp.where(new_n_mask, it + 1, state.level_n)
    local_any = jnp.any(new_n_mask)

    updated = comm.any_reduce(local_any | new_d_any, axis_names)

    # ---- statistics --------------------------------------------------------
    w_fwd = (
        jnp.where(bwd_dd, 0, fv_dd) + jnp.where(bwd_nd, 0, fv_nd)
        + jnp.where(bwd_dn, 0, fv_dn) + act_nn_sum
    )
    w_bwd = (
        jnp.where(bwd_dd, work_dd_b, 0) + jnp.where(bwd_nd, work_nd_b, 0)
        + jnp.where(bwd_dn, work_dn_b, 0)
    )
    mi = cfg.max_iters
    slot = jnp.clip(it, 0, mi - 1)
    # device-plane sweep telemetry (static branch; see MSBFSConfig.telemetry)
    if cfg.telemetry:
        tm_frontier_n = state.tm_frontier_n.at[slot].add(_count(frontier_n))
        tm_frontier_d = state.tm_frontier_d.at[slot].add(_count(frontier_d))
        dirmask = (bwd_dd.astype(jnp.int32) + 2 * bwd_dn.astype(jnp.int32)
                   + 4 * bwd_nd.astype(jnp.int32))
        tm_backward = state.tm_backward.at[slot].set(dirmask)
    else:
        tm_frontier_n = state.tm_frontier_n
        tm_frontier_d = state.tm_frontier_d
        tm_backward = state.tm_backward
    return BFSState(
        level_n=new_level_n,
        level_d=new_level_d,
        backward=backward,
        it=it + 1,
        done=~updated,
        work_fwd=state.work_fwd.at[slot].set(w_fwd),
        work_bwd=state.work_bwd.at[slot].set(w_bwd),
        nn_sent=state.nn_sent.at[slot].set(sent),
        nn_overflow=state.nn_overflow.at[slot].set(ovf),
        delegate_round=state.delegate_round.at[slot].set(new_d_any.astype(jnp.int32)),
        wire_delegate=state.wire_delegate.at[slot].add(jnp.int32(d_bytes)),
        wire_nn=state.wire_nn.at[slot].add(nn_bytes),
        nn_sparse=state.nn_sparse.at[slot].add(nn_sparse),
        tm_frontier_n=tm_frontier_n,
        tm_frontier_d=tm_frontier_d,
        tm_backward=tm_backward,
    )


def fv_nn_work(act_nn: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(act_nn.astype(jnp.int32))


# -----------------------------------------------------------------------------
# Drivers


def _run_loop(pgv_stacked, state: BFSState, cfg: BFSConfig, step_fn):
    def cond(s):
        return (~jnp.all(s.done)) & jnp.all(s.it < cfg.max_iters)

    def body(s):
        return step_fn(pgv_stacked, s)

    return lax.while_loop(cond, body, state)


@partial(jax.jit, static_argnames=("cfg",))
def run_bfs_emulated(pgv_stacked: PartitionedGraph, state: BFSState, cfg: BFSConfig,
                     plan=None) -> BFSState:
    """Single-device emulation: partitions are vmap lanes, collectives run
    over the vmapped axis. Used by tests and CPU benchmarks."""
    if plan is None:
        step = jax.vmap(
            lambda pg_l, st_l: bfs_step(pg_l, st_l, cfg, "p"), axis_name="p"
        )
        return _run_loop(pgv_stacked, state, cfg, step)
    step = jax.vmap(
        lambda pg_l, pl_l, st_l: bfs_step(pg_l, st_l, cfg, "p", plan=pl_l),
        axis_name="p", in_axes=(0, 0, 0),
    )
    return _run_loop((pgv_stacked, plan), state, cfg,
                     lambda args, st: step(args[0], args[1], st))


def make_sharded_bfs(mesh, partition_axes: Sequence[str], cfg: BFSConfig,
                     with_plan: bool = False):
    """shard_map BFS over a real device mesh: each partition is a device
    (paper: each partition is a GPU). ``partition_axes`` are the mesh axes
    the partition dimension is split over, e.g. ("pod", "data") -- their
    total size must equal pg.p. ``with_plan=True`` adds the static
    ExchangePlan argument (cfg.static_exchange path)."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(partition_axes)
    spec_leaf = lambda x: P(axes, *([None] * (x.ndim - 1)))

    def specs_for(tree):
        return jax.tree.map(lambda x: spec_leaf(x), tree)

    if with_plan:
        def sharded_step(args, st):
            pgv, plan = args
            in_specs = (specs_for(pgv), specs_for(plan), specs_for(st))
            out_specs = specs_for(st)

            def local(pg_l, pl_l, st_l):
                squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
                unsq = lambda t: jax.tree.map(lambda x: x[None], t)
                new = bfs_step(squeeze(pg_l), squeeze(st_l), cfg, axes,
                               plan=squeeze(pl_l))
                return unsq(new)

            return jax.shard_map(
                local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)(pgv, plan, st)

        @jax.jit
        def run(pgv, plan, st):
            return _run_loop((pgv, plan), st, cfg, sharded_step)

        return run

    def sharded_step(pgv, st):
        in_specs = (specs_for(pgv), specs_for(st))
        out_specs = specs_for(st)

        def local(pg_l, st_l):
            squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
            unsq = lambda t: jax.tree.map(lambda x: x[None], t)
            new = bfs_step(squeeze(pg_l), squeeze(st_l), cfg, axes)
            return unsq(new)

        return jax.shard_map(
            local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )(pgv, st)

    @jax.jit
    def run(pgv, st):
        return _run_loop(pgv, st, cfg, sharded_step)

    return run


def gather_levels(pg: PartitionedGraph, state: BFSState) -> np.ndarray:
    """Assemble global hop distances from partition-local + delegate levels."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    level_n = np.asarray(state.level_n)
    level_d = np.asarray(state.level_d)[0]
    vids = np.arange(pg.n, dtype=np.int64)
    out = level_n[layout.part_of(vids), layout.local_of(vids)].copy()
    if pg.d:
        out[np.asarray(pg.delegate_vids)[0] if np.asarray(pg.delegate_vids).ndim == 2
            else np.asarray(pg.delegate_vids)] = level_d[: pg.d]
    return out
