"""Shape synthesis for dry-run cells: static sizes of the partitioned graph
structures, derived from (n, e, p) with the paper's measured fractions
(Fig. 5 at the suggested TH): delegates ~2% of n (capped by the 4n/p rule),
nn edges ~10%, nd = dn ~28% each, dd ~34%, imbalance allowance 5%.
Only ShapeDtypeStructs are produced -- nothing is allocated.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.bfs import BFSConfig, BFSState
from repro.core.engine import EdgeWeights, ExchangePlan
from repro.core.types import CSR, PartitionedGraph


def _ceil_div(a, b):
    return -(-a // b)


def synth_partitioned_graph(
    n: int, e: int, p: int, mesh, part_axes,
    d_frac: float = 0.02, nn_frac: float = 0.10, imbalance: float = 1.05,
):
    """PartitionedGraph of ShapeDtypeStructs, stacked [p, ...] and sharded
    over ``part_axes``. Returns (pg, plan, weights)."""
    d = max(int(n * d_frac), 8)
    d = min(d, 4 * _ceil_div(n, p) if p > 1 else d)   # paper's 4n/p rule
    n_local = _ceil_div(n, p)
    e_nn = max(int(e * nn_frac / p * imbalance), 8)
    e_nd = max(int(e * 0.28 / p * imbalance), 8)
    e_dd = max(int(e * 0.34 / p * imbalance), 8)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(
            (p,) + shape, dtype,
            sharding=NamedSharding(mesh, P(part_axes, *([None] * len(shape)))))

    def csr(n_rows, e_max, col_dtype):
        return CSR(
            offsets=arr((n_rows + 1,), np.int32),
            cols=arr((e_max,), col_dtype),
            rowids=arr((e_max,), np.int32),
            m=arr((), np.int32),
            eidx=None,            # host-side only, never shipped to devices
            n_rows=n_rows, e_max=e_max,
        )

    pg = PartitionedGraph(
        n=n, p=p, p_rank=p, p_gpu=1, d=d, n_local=n_local, th=64,
        nn=csr(n_local, e_nn, np.int32),
        nn_owner=arr((e_nn,), np.int32),
        nd=csr(n_local, e_nd, np.int32),
        dn=csr(d, e_nd, np.int32),
        dd=csr(d, e_dd, np.int32),
        delegate_vids=arr((d,), np.int32),  # host-only identity, int32 stand-in
        normal_valid=arr((n_local,), np.bool_),
        nd_src_mask=arr((n_local,), np.bool_),
        dn_src_mask=arr((d,), np.bool_),
        dd_src_mask=arr((d,), np.bool_),
    )
    cap_total = e_nn                       # worst case: all nn dsts unique
    cap_peer = max(_ceil_div(cap_total, p) * 2, 8)
    cap_peer = _ceil_div(cap_peer, 32) * 32
    plan = ExchangePlan(
        perm=arr((e_nn,), np.int32),
        seg_ids=arr((e_nn,), np.int32),
        seg_owner=arr((cap_total,), np.int32),
        seg_pos=arr((cap_total,), np.int32),
        seg_local=arr((cap_total,), np.int32),
        recv_local=arr((p, cap_peer), np.int32),
        src_rows=arr((e_nn,), np.int32),
        seg_end=arr((cap_total,), np.int32),
        # a normal destination of an edge-doubled graph has at most th
        # in-edges: 6 doubling steps at th = 64
        cap_peer=cap_peer, cap_total=cap_total, max_run=pg.th,
    )
    weights = EdgeWeights(
        nn=arr((e_nn,), np.float32), nd=arr((e_nd,), np.float32),
        dn=arr((e_nd,), np.float32), dd=arr((e_dd,), np.float32),
    )
    return pg, plan, weights


def synth_bfs_state(pg, cfg: BFSConfig, mesh, part_axes) -> BFSState:
    p = pg.p
    mi = cfg.max_iters

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(
            (p,) + shape, dtype,
            sharding=NamedSharding(mesh, P(part_axes, *([None] * len(shape)))))

    d = max(pg.d, 1)
    return BFSState(
        level_n=arr((pg.n_local,), np.int32),
        level_d=arr((d,), np.int32),
        backward=arr((3,), np.bool_),
        it=arr((), np.int32),
        done=arr((), np.bool_),
        work_fwd=arr((mi,), np.int32),
        work_bwd=arr((mi,), np.int32),
        nn_sent=arr((mi,), np.int32),
        nn_overflow=arr((mi,), np.int32),
        delegate_round=arr((mi,), np.int32),
        wire_delegate=arr((mi,), np.int32),
        wire_nn=arr((mi,), np.int32),
        nn_sparse=arr((mi,), np.int32),
        tm_frontier_n=arr((mi if cfg.telemetry else 0,), np.int32),
        tm_frontier_d=arr((mi if cfg.telemetry else 0,), np.int32),
        tm_backward=arr((mi if cfg.telemetry else 0,), np.int32),
    )
