"""Multi-tenant frontend multiplexing vs back-to-back tenant serving.

The tentpole claim of the serving frontend: multiplexing several tenant
stream sessions into the *shared* W=32 lane word beats serving the same
tenants back-to-back, because back-to-back runs leave the word half empty
and pay every tenant's deep-tail sweeps separately, while the multiplexer
packs concurrent tenants into one traversal epoch (every superstep,
delegate all-reduce and nn all_to_all amortized across tenants -- the
multi-source amortization of the paper's Section V applied across trust
boundaries instead of within one batch).

Workload: two same-sized tailed-RMAT graphs (skewed depth: most sources
converge in O(log n) sweeps, tail tips need ~tail length), four tenants --
one latency-class and one throughput-class per graph -- each submitting a
disjoint source set cycled through all four query kinds in chunked rounds.
Both sides run the *same* engines (refill + overlapped pipeline, shared
compiled-runner pool, caches off so every rep is the same workload):

* **mux**: one :class:`~repro.serve.ServeFrontend`, all four sessions fed
  round-robin with a blocking poll between rounds, then drained.
* **seq**: the same frontend machinery, but each tenant is submitted and
  fully drained before the next one starts (no cross-tenant packing).

Reps are interleaved and the speedup judged on the median of *per-pair*
ratios (machine-load drift hits both sides of a pair and cancels; same
protocol as ``msbfs_throughput.run_overlap``). Every answer is
oracle-exact, mux and seq answers are bit-identical, per-tenant
:class:`~repro.serve.TenantStats` counters are bit-identical between the
two schedules (``peak_in_flight`` excluded: it is schedule-dependent by
design), and the mux engine counters must be identical across reps (the
frontend's admission order is deterministic). Results are written to
``BENCH_serving.json`` (section ``frontend``) with per-tenant p99
submit->deliver latencies from the shared observability plane.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import msbfs as M
from repro.graphs.rmat import pick_sources, rmat_graph
from repro.graphs.synthetic import with_tails
from repro.obs import Observability, tenant_metric
from repro.serve import (Query, QueryKind, SLO_LATENCY, SLO_THROUGHPUT,
                         ServeFrontend, oracle_check)

from .common import emit, write_bench

_KIND_CYCLE = (QueryKind.LEVELS, QueryKind.REACHABILITY,
               QueryKind.DISTANCE_LIMITED)


def _make_graph(scale: int, seed: int, n_tails: int, tail_len: int):
    core = rmat_graph(scale, seed=seed)
    g, tips = with_tails(core, n_tails=n_tails, length=tail_len,
                         seed=seed + 2)
    return core, g, tips


def _tenant_queries(core, tips, per_tenant: int, half: int, seed: int,
                    max_depth: int):
    """One tenant's deterministic query trace: disjoint shallow sources
    with this tenant's share of deep tail tips spread through them, kinds
    cycled -- MULTI_TARGET first so the engine session compiles the
    target-capable variant no matter which tenant's release opens it."""
    n_shallow = per_tenant - len(tips)
    shallow = pick_sources(core, 2 * n_shallow, seed=seed)
    srcs = [int(s) for s in shallow[half * n_shallow:(half + 1) * n_shallow]]
    gap = max(1, len(srcs) // max(len(tips), 1))
    for i, tip in enumerate(tips):
        srcs.insert(1 + i * gap, int(tip))
    srcs = srcs[:per_tenant]
    tpool = tuple(int(s) for s in shallow[:2])
    qs = [Query(srcs[0], QueryKind.MULTI_TARGET, targets=tpool)]
    qs += [Query(s, _KIND_CYCLE[i % 3], max_depth=(
        max_depth if _KIND_CYCLE[i % 3] is QueryKind.DISTANCE_LIMITED
        else None)) for i, s in enumerate(srcs[1:])]
    return qs


def _build_frontend(graphs, runner_cache, cfg, th, p_rank, p_gpu, obs):
    ft = ServeFrontend(obs=obs, runner_cache=runner_cache,
                       cache_capacity=0, reuse_components=False)
    for name, (_, g, _) in graphs.items():
        ft.register_graph(name, g, th=th, p_rank=p_rank, p_gpu=p_gpu,
                          cfg=cfg)
    return ft


def _run_mux(ft, tenants, chunk: int):
    """Round-robin chunked multiplexed serving; returns {tenant: answers}."""
    sessions = {t: ft.open_session(t, gname, slo=slo)
                for t, (gname, slo, _) in tenants.items()}
    answers: dict = {t: {} for t in tenants}

    def take(res):
        for sid, got in res.items():
            answers[sid.split(":", 1)[0]].update(got)

    rounds = max(-(-len(qs) // chunk) for _, _, qs in tenants.values())
    for r in range(rounds):
        for t, (_, _, qs) in tenants.items():
            part = qs[r * chunk:(r + 1) * chunk]
            if part:
                ft.submit(sessions[t], part)
        take(ft.poll(wait=True))
    take(ft.drain())
    return answers


def _run_seq(ft, tenants):
    """Back-to-back baseline: each tenant fully drained before the next."""
    answers: dict = {}
    for t, (gname, slo, qs) in tenants.items():
        sess = ft.open_session(t, gname, slo=slo)
        ft.submit(sess, qs)
        got: dict = {}
        for res in ft.drain().values():
            got.update(res)
        answers[t] = got
    return answers


def run_frontend(scale: int = 7, th: int = 64, p_rank: int = 2,
                 p_gpu: int = 2, n_queries: int = 32, n_tails: int = 8,
                 tail_len: int = 64, per_tenant: int = 16, chunk: int = 4,
                 max_depth: int = 3, reps: int = 5,
                 min_speedup: float = 1.2,
                 out_json: str = "BENCH_serving.json"):
    graphs = {"g1": _make_graph(scale, 3, n_tails, tail_len),
              "g2": _make_graph(scale, 11, n_tails, tail_len)}
    half_tails = n_tails // 2

    tenants: dict = {}
    for gi, (gname, (core, _, tips)) in enumerate(graphs.items()):
        for half, slo in enumerate((SLO_LATENCY, SLO_THROUGHPUT)):
            t = f"tenant{2 * gi + half}"
            share = tips[half * half_tails:(half + 1) * half_tails]
            tenants[t] = (gname, slo, _tenant_queries(
                core, share, per_tenant, half, seed=17 + gi, max_depth=max_depth))

    cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=2 * tail_len + 48)
    runner_cache: dict = {}   # shared pool: compile cost excluded from reps
    mk = lambda obs: _build_frontend(graphs, runner_cache, cfg, th, p_rank,
                                     p_gpu, obs)
    mk(Observability(enabled=False)).warmup(targets=True)
    # one untimed mux pass primes any variant warmup() cannot reach
    _run_mux(mk(Observability(enabled=False)), tenants, chunk)

    times = {"mux": [], "seq": []}
    counter_runs: list = []
    mux_obs = seq_obs = None
    mux_ft = seq_ft = None
    mux_ans = seq_ans = None
    for _ in range(reps):
        mux_obs, seq_obs = Observability(), Observability()
        mux_ft, seq_ft = mk(mux_obs), mk(seq_obs)
        t0 = time.perf_counter()
        mux_ans = _run_mux(mux_ft, tenants, chunk)
        times["mux"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        seq_ans = _run_seq(seq_ft, tenants)
        times["seq"].append(time.perf_counter() - t0)
        counter_runs.append(tuple(
            (name, eng.stats.sweeps, eng.stats.sweep_blocks,
             eng.stats.lanes_used, eng.stats.wire_delegate_bytes,
             eng.stats.wire_nn_bytes)
            for name, eng in mux_ft.engines.items()))

    # deterministic admission: every mux rep traverses the same schedule
    assert all(c == counter_runs[0] for c in counter_runs[1:]), (
        "mux engine counters varied across reps -- the frontend's "
        "admission order is supposed to be deterministic")

    # oracle exactness + mux/seq bit-identical answers and tenant stats
    for t, (gname, _, qs) in tenants.items():
        g = graphs[gname][1]
        assert set(mux_ans[t]) == set(qs) == set(seq_ans[t])
        for q in qs:
            oracle_check(g, q, mux_ans[t][q])
            a, b = mux_ans[t][q], seq_ans[t][q]
            if isinstance(a, dict):
                assert a == b, (t, q)
            else:
                np.testing.assert_array_equal(a, b)
        sa = mux_ft.tenant_stats(t).as_dict()
        sb = seq_ft.tenant_stats(t).as_dict()
        sa.pop("peak_in_flight"), sb.pop("peak_in_flight")
        assert sa == sb, f"tenant {t} stats diverged: {sa} != {sb}"

    n_total = sum(len(qs) for _, _, qs in tenants.values())
    t_mux = float(np.median(times["mux"]))
    t_seq = float(np.median(times["seq"]))
    speedup = float(np.median([ts / tm for ts, tm in
                               zip(times["seq"], times["mux"])]))
    qps_mux, qps_seq = n_total / t_mux, n_total / t_seq

    def tenant_p99(obs: Observability, t: str) -> float:
        hs = obs.metrics.snapshot()["histograms"]
        p99s = [h["p99"] for name, h in hs.items()
                if name.startswith(tenant_metric(t, "latency_s"))]
        return float(max(p99s)) if p99s else 0.0

    per_tenant_stats = {
        t: {"graph": gname, "slo": slo,
            "submitted": mux_ft.tenant_stats(t).submitted,
            "delivered": mux_ft.tenant_stats(t).delivered,
            "kind_counts": dict(mux_ft.tenant_stats(t).kind_counts),
            "p99_latency_s": tenant_p99(mux_obs, t)}
        for t, (gname, slo, _) in tenants.items()}

    section = {
        "graph": {"scale": scale, "n_tails": n_tails, "tail_len": tail_len,
                  "n": {name: int(g.n) for name, (_, g, _) in graphs.items()},
                  "m": {name: int(g.m) for name, (_, g, _) in graphs.items()}},
        "requests": n_total, "n_queries": n_queries,
        "tenants": len(tenants), "per_tenant": per_tenant, "chunk": chunk,
        "qps_mux": qps_mux, "qps_seq": qps_seq, "speedup": speedup,
        "engines": {name: {
            "sweeps": eng.stats.sweeps,
            "sweep_blocks": eng.stats.sweep_blocks,
            "lanes_used": eng.stats.lanes_used,
            "wire_delegate_bytes": eng.stats.wire_delegate_bytes,
            "wire_nn_bytes": eng.stats.wire_nn_bytes,
            "kind_counts": dict(eng.stats.kind_counts),
        } for name, eng in mux_ft.engines.items()},
        "tenant_stats": per_tenant_stats,
        "counters_deterministic": True,
        "answers_bit_identical": True,
    }
    write_bench(out_json, "frontend", section)

    sweeps_mux = sum(e.stats.sweeps for e in mux_ft.engines.values())
    sweeps_seq = sum(e.stats.sweeps for e in seq_ft.engines.values())
    emit("serve/frontend_seq", 1e6 * t_seq / n_total,
         f"qps={qps_seq:.2f} sweeps={sweeps_seq}")
    emit("serve/frontend_mux", 1e6 * t_mux / n_total,
         f"qps={qps_mux:.2f} sweeps={sweeps_mux} "
         f"speedup={speedup:.2f}x")
    assert speedup >= min_speedup, (
        f"multiplexed frontend {qps_mux:.2f} q/s < {min_speedup}x "
        f"back-to-back {qps_seq:.2f} q/s (median per-pair {speedup:.2f}x)")
    return section


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()
    kw = {k: v for k, v in (("scale", args.scale), ("reps", args.reps))
          if v is not None}
    print(run_frontend(**kw))
