"""Paper Fig. 8: effect of options (DO, uniquify) on workload and traffic.

CPU emulation cannot reproduce wall-clock GPU numbers, so the primary
metrics are the paper's own workload counters: edges examined (DO cuts ~3x)
and nn vertices sent (uniquify can only shrink it). Counters are
deterministic given the graph parameters, so the emitted
``options_ablation`` section of ``BENCH_comm.json`` is gated exactly by
``scripts/bench_gate.py`` -- any drift is a real schedule change."""
from __future__ import annotations

from repro.core.bfs import BFSConfig
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import emit, run_bfs_timed, write_bench


def run(scale: int = 12, th: int = 64, p_rank: int = 2, p_gpu: int = 2,
        out_json: str | None = None):
    g = rmat_graph(scale, seed=4)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
    sources = pick_sources(g, 2, seed=5)
    variants = {
        "plain": BFSConfig(max_iters=48, enable_do=False),
        "DO": BFSConfig(max_iters=48, enable_do=True),
        "DO+U": BFSConfig(max_iters=48, enable_do=True, uniquify=True),
    }
    results = {}
    for name, cfg in variants.items():
        res = run_bfs_timed(g, pg, sources, cfg)
        work = sum(r["work_fwd"] + r["work_bwd"] for r in res)
        sent = sum(r["nn_sent"] for r in res)
        rounds = sum(r["delegate_rounds"] for r in res)
        us = 1e6 * sum(r["time_s"] for r in res) / max(len(res), 1)
        emit(f"options/{name}", us, f"work={work} nn_sent={sent} "
             f"delegate_rounds={rounds}")
        results[name] = {"work": work, "sent": sent,
                         "delegate_rounds": rounds, "time_us": us}
    # paper: DO cuts computation ~3x; uniquify never increases traffic
    assert results["DO"]["work"] < 0.6 * results["plain"]["work"]
    assert results["DO+U"]["sent"] <= results["DO"]["sent"]
    if out_json:
        write_bench(out_json, "options_ablation", {
            "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                      "th": th, "p_rank": p_rank, "p_gpu": p_gpu,
                      "seed": 4},
            "variants": results,
            "do_work_ratio": results["DO"]["work"]
            / max(results["plain"]["work"], 1),
        })
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_comm.json")
