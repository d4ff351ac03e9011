"""Paper Section V: communication-volume model validation + strategy sweep.

Two modes:

* default -- the seed's model check: total volume <= d * S' / 4 bytes
  (delegate levels, S' = iterations with delegate updates) + 4 * |E_nn|
  bytes (every nn edge a cutting edge, sent once at 4 bytes), measured
  against the BFS run's counters (now including the comm layer's own
  wire-byte accounting).

* ``--strategies`` -- sweep the pluggable comm subsystem on one batched
  msBFS workload at p partitions: every delegate combine strategy
  (allgather-fold, allgather folding through the mask_reduce kernel,
  ppermute ring, hierarchical) crossed with the dense and the
  frontier-adaptive nn wire formats. Each run is checked bit-exact
  against the numpy BFS oracle for every lane, and the per-sweep wire
  bytes each collective recorded (``MSBFSState.wire_delegate`` /
  ``wire_nn``) are written to ``BENCH_comm.json``. Asserts the headline
  claims: ring-OR wire volume <= all-gather-fold at p=4, adaptive nn <=
  dense, and every strategy oracle-exact.

    PYTHONPATH=src python -m benchmarks.comm_model [--strategies]
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.core import bfs as B, comm as C, engine as E, msbfs as M
from repro.core.bfs import BFSConfig
from repro.core.oracle import bfs_levels
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import emit, run_bfs_timed, write_bench


def run(scale: int = 12, th: int = 64, p: int = 4):
    g = rmat_graph(scale, seed=10)
    pg = partition_graph(g, th=th, p_rank=p, p_gpu=1)
    e_nn = int(np.asarray(pg.nn.m).sum())
    res = run_bfs_timed(g, pg, pick_sources(g, 2, seed=11),
                        BFSConfig(max_iters=48, enable_do=False))
    for i, r in enumerate(res):
        nn_bytes = r["nn_sent"] * 4
        bound_nn = 4 * e_nn
        s_prime = r["delegate_rounds"]
        emit(f"comm_model/run{i}", r["time_s"] * 1e6,
             f"nn_bytes={nn_bytes} bound={bound_nn} "
             f"S'={s_prime} S={r['iters']} d={pg.d} "
             f"wire_delegate={r['wire_delegate']} wire_nn={r['wire_nn']} "
             f"overflow={r['overflow']}")
        # measured nn traffic never exceeds the model bound
        assert nn_bytes <= bound_nn
        # delegate exchanges finish no later than the full run
        assert s_prime <= r["iters"]
        # binned ids are 4 bytes per capacity slot: the comm layer's own
        # accounting can only exceed the useful-id volume (padding)
        assert r["wire_nn"] >= nn_bytes / pg.p
    return res


STRATEGIES = (
    ("allgather", C.CommConfig(delegate="allgather")),
    ("allgather+maskfold", C.CommConfig(delegate="allgather", local_fold="ref")),
    ("ring", C.CommConfig(delegate="ring")),
    ("hier", C.CommConfig(delegate="hier")),
)


def run_strategies(scale: int = 10, th: int = 64, p_rank: int = 2,
                   p_gpu: int = 2, n_queries: int = 32,
                   out_path: str = "BENCH_comm.json"):
    g = rmat_graph(scale, seed=10)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
    plan = E.build_exchange_plan(pg)
    pgv = B.device_view(pg)
    sources = pick_sources(g, n_queries, seed=11)
    oracle = [bfs_levels(g, int(s)) for s in sources]

    rows = {}
    for name, ccfg in STRATEGIES:
        for nn in ("dense", "adaptive", "compressed"):
            cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=48,
                                comm=dataclasses.replace(ccfg, nn=nn))
            st = M.init_multi_state(pg, sources, cfg)
            out = M.run_msbfs_emulated(pgv, plan, st, cfg)
            levels = M.gather_levels_multi(pg, out)
            exact = all(np.array_equal(levels[i], oracle[i])
                        for i in range(len(sources)))
            sweeps = int(np.asarray(out.it)[0])
            row = {
                "delegate_bytes": int(np.asarray(out.wire_delegate).sum()),
                "nn_bytes": int(np.asarray(out.wire_nn).sum()),
                "sweeps": sweeps,
                "nn_sparse_sweeps": int(np.asarray(out.nn_sparse)[0].sum()),
                "nn_overflow": int(np.asarray(out.nn_overflow).sum()),
                "oracle_exact": bool(exact),
            }
            row["delegate_bytes_per_sweep"] = row["delegate_bytes"] // max(sweeps, 1)
            row["nn_bytes_per_sweep"] = row["nn_bytes"] // max(sweeps, 1)
            rows[f"{name}/{nn}"] = row
            emit(f"comm_strategies/{name}/{nn}", 0.0,
                 f"delegate_B/sweep={row['delegate_bytes_per_sweep']} "
                 f"nn_B/sweep={row['nn_bytes_per_sweep']} "
                 f"sparse_sweeps={row['nn_sparse_sweeps']} "
                 f"exact={exact}")

    # headline claims of the subsystem, enforced
    assert all(r["oracle_exact"] for r in rows.values()), \
        "a comm strategy broke traversal levels"
    assert all(r["nn_overflow"] == 0 for r in rows.values())
    assert (rows["ring/dense"]["delegate_bytes"]
            <= rows["allgather/dense"]["delegate_bytes"]), \
        "ring-OR must not exceed all-gather-fold wire volume"
    assert (rows["allgather/adaptive"]["nn_bytes"]
            <= rows["allgather/dense"]["nn_bytes"]), \
        "adaptive nn must not exceed the dense format"
    # the compressed codec's exact byte accounting (varint rle / delta-id
    # streams) must beat the adaptive dense/sparse switch it rides on
    assert (rows["allgather/compressed"]["nn_bytes"]
            <= rows["allgather/adaptive"]["nn_bytes"]), \
        "compressed nn accounting must not exceed the adaptive format"
    # the mask_reduce local fold changes compute, never wire bytes
    assert (rows["allgather+maskfold/dense"]["delegate_bytes"]
            == rows["allgather/dense"]["delegate_bytes"])

    summary = {
        "p": pg.p, "d": pg.d, "n": pg.n, "scale": scale,
        "n_queries": n_queries, "cap_peer": plan.cap_peer,
        "strategies": rows,
    }
    write_bench(out_path, "comm_strategies", summary)
    print(f"wrote {out_path}")
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategies", action="store_true",
                    help="sweep comm strategies on msBFS, emit BENCH_comm.json")
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args()
    if args.strategies:
        run_strategies(**({"scale": args.scale} if args.scale else {}))
    else:
        run(**({"scale": args.scale} if args.scale else {}))
