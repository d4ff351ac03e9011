"""Paper Fig. 11: strong scaling -- fixed graph, growing p. Per-partition
work should drop ~1/p while communication grows, the crossover the paper
observes beyond 48 GPUs."""
from __future__ import annotations

from repro.core.bfs import BFSConfig
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import run_bfs_timed, write_bench


def run(scale: int = 12, ps=(1, 2, 4, 8), th: int = 64,
        out_json: str | None = None):
    g = rmat_graph(scale, seed=8)
    sources = pick_sources(g, 2, seed=9)
    rows = []
    cells = {}
    for p in ps:
        pg = partition_graph(g, th=th, p_rank=p, p_gpu=1)
        res = run_bfs_timed(g, pg, sources, BFSConfig(max_iters=48, enable_do=True))
        work_pp = sum(r["work_fwd"] + r["work_bwd"] for r in res) / max(len(res), 1) / p
        sent = sum(r["nn_sent"] for r in res) / max(len(res), 1)
        us = 1e6 * sum(r["time_s"] for r in res) / max(len(res), 1)
        print(f"strong_scaling/p{p}: work_per_part={work_pp:.0f} "
              f"nn_sent={sent:.0f} d={pg.d}")
        cells[f"p{p}"] = {
            # exact: work/traffic counters are schedule facts
            "work_per_part": work_pp, "nn_sent": sent, "d": int(pg.d),
            # perf: wall time
            "time_us": us,
        }
        rows.append((p, work_pp, sent))
    # compute per partition shrinks; cut traffic (weakly) grows
    assert rows[-1][1] < rows[0][1]
    assert rows[-1][2] >= rows[0][2] * 0.9
    if out_json:
        write_bench(out_json, "strong_scaling", {
            "graph": {"scale": scale, "th": th, "seed": 8},
            "ps": cells,
        })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_scaling.json")
