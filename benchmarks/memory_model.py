"""Paper Table I: degree-separated storage vs edge list (16m) and CSR
(8n+8m), plus the measured delta-varint compressed partition sizes.

The paper-claim thresholds (best layout < 0.40 of the edge list;
compressed bytes/edge <= 0.5x the raw degree-separated layout) are not
asserted here -- they are ``CLAIM_BOUNDS`` in :mod:`benchmarks.gate`, so
a miss gates CI with a ``violation`` finding instead of crashing the
benchmark run.
"""
from __future__ import annotations

import time

from repro.core.partition import compress_partition, partition_graph
from repro.graphs.rmat import rmat_graph

from .common import write_bench


def run(scale: int = 14, ths=(16, 64, 256), p_rank: int = 2, p_gpu: int = 2,
        out_json: str | None = None):
    g = rmat_graph(scale, seed=1)
    rows = {}
    out = []
    for th in ths:
        t0 = time.perf_counter()
        pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
        dt = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        cp = compress_partition(pg)
        dt_c = (time.perf_counter() - t0) * 1e6
        mem = pg.memory_bytes(compressed=cp)
        r_el = mem["total"] / mem["edge_list_16m"]
        r_csr = mem["total"] / mem["csr_8n_8m"]
        print(f"memory_model/scale{scale}/th{th}: vs_edge_list={r_el:.3f} "
              f"vs_csr={r_csr:.3f} d={pg.d} "
              f"e_nn_frac={mem['e_nn'] / mem['m']:.4f} "
              f"bytes_per_edge={mem['bytes_per_edge_raw']:.2f}"
              f"->{mem['bytes_per_edge_compressed']:.2f} "
              f"(x{mem['compressed_vs_raw']:.3f})")
        rows[f"th{th}"] = {
            # exact: the memory model is a pure function of the partition
            "vs_edge_list": r_el, "vs_csr": r_csr, "d": int(pg.d),
            "e_nn_frac": mem["e_nn"] / mem["m"],
            # measured (not modeled) compressed sizes: delta-varint
            # degree-separated streams vs the padded raw layout
            "bytes_per_edge_raw": mem["bytes_per_edge_raw"],
            "bytes_per_edge_compressed": mem["bytes_per_edge_compressed"],
            "compressed_vs_raw": mem["compressed_vs_raw"],
            # perf: partition / compression wall time
            "partition_time_us": dt,
            "compress_time_us": dt_c,
        }
        out.append((th, r_el, r_csr))
    # paper claim "about one third of the edge list": published as
    # vs_edge_list_best and bounded by benchmarks.gate.CLAIM_BOUNDS
    best = min(r for _, r, _ in out)
    print(f"memory_model/scale{scale}: vs_edge_list_best={best:.3f}")
    if out_json:
        write_bench(out_json, "memory_model", {
            "graph": {"scale": scale, "p_rank": p_rank, "p_gpu": p_gpu,
                      "seed": 1},
            "vs_edge_list_best": best,
            "ths": rows,
        })
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_scaling.json")
