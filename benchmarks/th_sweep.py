"""Paper Fig. 5 / Fig. 12: distribution of edge kinds and delegates vs TH.

The per-TH edge-kind fractions are a pure function of the graph and the
degree threshold, so the emitted ``th_sweep`` section of
``BENCH_comm.json`` is gated exactly by ``scripts/bench_gate.py``; the
partitioning wall time rides along as a tolerance-banded perf metric."""
from __future__ import annotations

import time

from repro.core.partition import edge_kind_stats
from repro.graphs.rmat import rmat_graph

from .common import emit, write_bench


def run(scale: int = 16, ths=(4, 8, 16, 32, 64, 128, 256, 512, 1024),
        out_json: str | None = None):
    g = rmat_graph(scale, seed=0)
    rows = []
    section_rows = {}
    for th in ths:
        t0 = time.perf_counter()
        s = edge_kind_stats(g, th)
        dt = (time.perf_counter() - t0) * 1e6
        emit(
            f"th_sweep/scale{scale}/th{th}", dt,
            f"delegates={s['frac_delegates']:.4f} nn={s['frac_nn']:.4f} "
            f"nd={s['frac_nd']:.4f} dd={s['frac_dd']:.4f}")
        rows.append(s)
        section_rows[f"th{th}"] = {
            "frac_delegates": round(float(s["frac_delegates"]), 6),
            "frac_nn": round(float(s["frac_nn"]), 6),
            "frac_nd": round(float(s["frac_nd"]), 6),
            "frac_dd": round(float(s["frac_dd"]), 6),
            "time_us": dt,
        }
    # paper invariants: delegates and dd shrink with TH, nn grows with TH
    assert rows[0]["frac_delegates"] > rows[-1]["frac_delegates"]
    assert rows[0]["frac_nn"] < rows[-1]["frac_nn"]
    if out_json:
        write_bench(out_json, "th_sweep", {
            "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                      "seed": 0},
            "ths": list(ths),
            "rows": section_rows,
        })
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(out_json="BENCH_comm.json")
