"""Paper Fig. 9: weak scaling -- fixed graph size per partition, growing p.

Emulated partitions on CPU measure the *work/communication scaling*, which
is what the paper's argument rests on: per-partition traversal work should
stay ~flat and total comm volume should grow ~log(p) for delegates +
proportionally for nn cut edges."""
from __future__ import annotations

import math

from repro.core.bfs import BFSConfig
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import gmean, run_bfs_timed, write_bench


def run(scale_per_part: int = 9, ps=(1, 2, 4, 8), th: int = 32,
        out_json: str | None = None):
    rows = []
    cells = {}
    for p in ps:
        scale = scale_per_part + int(math.log2(p))
        g = rmat_graph(scale, seed=6)
        pg = partition_graph(g, th=th, p_rank=p, p_gpu=1)
        res = run_bfs_timed(g, pg, pick_sources(g, 2, seed=7),
                            BFSConfig(max_iters=48, enable_do=True))
        work_pp = sum(r["work_fwd"] + r["work_bwd"] for r in res) / max(len(res), 1) / p
        teps = gmean([r["teps"] for r in res])
        us = 1e6 * sum(r["time_s"] for r in res) / max(len(res), 1)
        # modeled comm (paper Section V): delegate rounds * d bytes + nn sent * 4
        comm = sum(r["delegate_rounds"] for r in res) / max(len(res), 1) * pg.d / 4 \
            + sum(r["nn_sent"] for r in res) / max(len(res), 1) * 4
        print(f"weak_scaling/p{p}/scale{scale}: MTEPS={teps/1e6:.2f} "
              f"work_per_part={work_pp:.0f} comm_bytes={comm:.0f}")
        cells[f"p{p}"] = {
            # exact: work and modeled-comm counters are schedule facts
            "scale": scale, "work_per_part": work_pp, "comm_bytes": comm,
            "d": int(pg.d),
            # perf: wall time / throughput
            "time_us": us, "mteps": teps / 1e6,
        }
        rows.append((p, work_pp, comm))
    # weak-scaling: per-partition work stays within ~2.5x over 8x more parts
    assert rows[-1][1] < 2.5 * rows[0][1], rows
    if out_json:
        write_bench(out_json, "weak_scaling", {
            "graph": {"scale_per_part": scale_per_part, "th": th, "seed": 6},
            "ps": cells,
        })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_scaling.json")
