"""Paper Fig. 6 / Fig. 13: traversal rate vs degree threshold TH.

Traversal rates are machine-load noise on CPU emulation, so every TEPS /
time leaf in the emitted ``th_perf`` section of ``BENCH_comm.json`` sits
in the gate's perf tolerance band; the per-TH delegate count and workload
counters are exact."""
from __future__ import annotations

from repro.core.bfs import BFSConfig
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import emit, gmean, run_bfs_timed, write_bench


def run(scale: int = 12, ths=(8, 32, 64, 128, 512), p_rank: int = 2, p_gpu: int = 2,
        n_sources: int = 2, out_json: str | None = None):
    g = rmat_graph(scale, seed=2)
    sources = pick_sources(g, n_sources, seed=3)
    rows = []
    section_rows = {}
    for th in ths:
        pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
        res = run_bfs_timed(g, pg, sources, BFSConfig(max_iters=48, enable_do=True))
        teps = gmean([r["teps"] for r in res])
        work = sum(r["work_fwd"] + r["work_bwd"] for r in res)
        us = 1e6 * sum(r["time_s"] for r in res) / max(len(res), 1)
        emit(f"th_perf/scale{scale}/th{th}", us,
             f"MTEPS={teps/1e6:.2f} d={pg.d} work={work}")
        rows.append((th, teps))
        section_rows[f"th{th}"] = {"mteps": teps / 1e6, "time_us": us,
                                   "delegates": int(pg.d), "work": work}
    if out_json:
        write_bench(out_json, "th_perf", {
            "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                      "p_rank": p_rank, "p_gpu": p_gpu, "seed": 2},
            "ths": list(ths), "n_sources": n_sources,
            "rows": section_rows,
        })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_comm.json")
