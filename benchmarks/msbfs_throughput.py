"""Batched msBFS throughput vs sequential single-source BFS.

The amortization claim of the serving subsystem: one W=32 lane-word msBFS
sweep answers 32 independent queries for roughly the cost of one traversal
(every superstep, delegate all-reduce, and nn all_to_all is shared), so
batched queries/sec should beat 32 sequential ``run_bfs_emulated`` calls by
well over 4x on CPU emulation. Both sides are timed post-compilation, and
every batched answer is checked against the single-source runs.

``--refill`` benchmarks the second amortization layer: batch-at-a-time
retirement vs the mid-flight lane-refill pipeline on a *skewed-depth* query
stream (an RMAT core with path tails attached: most queries converge in
O(log n) sweeps, a few need ~tail-length). Batch mode pays every batch's
slowest lane; refill reseeds converged lanes mid-flight, so deep stragglers
never idle the rest of the word. Reports queries/sec for both engines plus
refill lane utilization, and checks every refill answer against the numpy
oracle.

``--overlap`` benchmarks the overlapped host/device serving pipeline
against the synchronous per-sweep refill driver on one skewed tailed-RMAT
stream cycled through **all four** query kinds: sweeps run in fused
``sweep_block``-sized device blocks that stop exactly at lane-retirement
boundaries, with a speculative next block in flight while the host
processes the previous block's ``lane_active`` word, retired-lane gathers
and reseed descriptors. The pipeline must not change the traversal
schedule, so the benchmark asserts ``ServeStats.sweeps`` and the wire-byte
counters are *bit-identical* between the two drivers, every answer is
oracle-exact, and queries/sec must improve >= ``min_speedup``. Results are
merged into ``BENCH_queries.json``.

``--chunked`` benchmarks the chunked out-of-core sweep mode
(``MSBFSConfig(edge_chunk=...)``) against the monolithic sweep on the
same batch: every state leaf -- levels, work/wire counters, per-sweep
telemetry -- must be bit-identical, answers oracle-exact, and the
per-driver times plus counters land in a ``chunked`` section of
``BENCH_scaling.json``. Defaults to the compressed nn wire format so the
codec byte accounting rides the same run.

``--payload`` benchmarks the per-lane payload plane (weighted SSSP,
min-label components, k-hop sampling): homogeneous runs per kind plus a
seven-kind mixed rotation, each oracle-checked, reporting the wire split
between the bit plane and the int32 payload plane (delegate vs nn) into a
``payload_kinds`` section of ``BENCH_queries.json``. Bit-only runs must
ship exactly zero payload bytes -- the compile-away claim as a counter.

``--mixed`` benchmarks the typed-query subsystem (``repro.serve.queries``)
on one skewed RMAT stream served four ways: full levels, reachability-only
(raw device path and the shipped serving path with per-component reuse),
distance-limited, and a round-robin mixed-kind stream. Every answer is
oracle-checked and a ``BENCH_queries.json`` summary is written. The claim
under test: query kinds that need less than full levels are served faster
on the same substrate -- reachability via the levels-free lane-word
variant plus component reuse (an undirected reachable set is source-
invariant within its component, a reuse level arrays can never have), and
distance-limited via the per-lane depth cap folded into the convergence
word (most of the deep tail sweeps simply never run).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import bfs as B, engine as E, msbfs as M
from repro.core.partition import partition_graph
from repro.core.types import INF_LEVEL
from repro.graphs.rmat import pick_sources, rmat_graph

from .common import emit, write_bench


def run(scale: int = 12, th: int = 64, p_rank: int = 2, p_gpu: int = 2,
        n_queries: int = 32, min_speedup: float = 4.0):
    g = rmat_graph(scale, seed=3)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
    plan = E.build_exchange_plan(pg)
    pgv = B.device_view(pg)
    sources = pick_sources(g, n_queries, seed=1)

    # ---- sequential single-source baseline (compile once, run W times) ----
    cfg1 = B.BFSConfig(max_iters=48, enable_do=True)
    out = B.run_bfs_emulated(pgv, B.init_state(pg, int(sources[0]), cfg1), cfg1)
    jax.block_until_ready(out.level_n)
    seq_levels = {}
    t0 = time.perf_counter()
    for src in sources:
        out = B.run_bfs_emulated(pgv, B.init_state(pg, int(src), cfg1), cfg1)
        jax.block_until_ready(out.level_n)
        seq_levels[int(src)] = B.gather_levels(pg, out)
    t_seq = time.perf_counter() - t0

    # ---- batched msBFS: one sweep for all W queries -----------------------
    cfgm = M.MSBFSConfig(n_queries=n_queries, max_iters=48, enable_do=True)
    outm = M.run_msbfs_emulated(
        pgv, plan, M.init_multi_state(pg, sources, cfgm), cfgm)
    jax.block_until_ready(outm.level_n)
    t0 = time.perf_counter()
    outm = M.run_msbfs_emulated(
        pgv, plan, M.init_multi_state(pg, sources, cfgm), cfgm)
    jax.block_until_ready(outm.level_n)
    t_batch = time.perf_counter() - t0
    levels = M.gather_levels_multi(pg, outm)

    # every query matches the single-source oracle
    for q, src in enumerate(sources):
        np.testing.assert_array_equal(levels[q], seq_levels[int(src)])

    w = len(sources)
    qps_seq = w / t_seq
    qps_batch = w / t_batch
    edges = sum(int((seq_levels[int(s)][g.src] != INF_LEVEL).sum()) // 2
                for s in sources)
    emit("msbfs/seq_1src", 1e6 * t_seq / w,
         f"qps={qps_seq:.2f} gteps={edges / t_seq / 1e9:.4f}")
    emit("msbfs/batched_w32", 1e6 * t_batch / w,
         f"qps={qps_batch:.2f} gteps={edges / t_batch / 1e9:.4f} "
         f"speedup={qps_batch / qps_seq:.1f}x")
    assert qps_batch >= min_speedup * qps_seq, (
        f"batched msBFS {qps_batch:.2f} q/s < {min_speedup}x sequential "
        f"{qps_seq:.2f} q/s")
    return {"qps_seq": qps_seq, "qps_batch": qps_batch,
            "speedup": qps_batch / qps_seq}


def run_refill(scale: int = 11, th: int = 64, p_rank: int = 2, p_gpu: int = 2,
               n_queries: int = 32, n_tails: int = 6, tail_len: int = 96,
               requests: int = 64, min_speedup: float = 1.2):
    """Lane refill vs batch-at-a-time on a skewed-depth query stream."""
    from repro.core.oracle import bfs_levels
    from repro.graphs.synthetic import with_tails
    from repro.serve import BFSServeEngine

    core = rmat_graph(scale, seed=3)
    g, tips = with_tails(core, n_tails=n_tails, length=tail_len, seed=5)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)

    # the stream: mostly shallow core sources, a few deep tail tips, spread
    # deterministically so every lane batch of the baseline catches >= 1
    # straggler (the common case for random arrival order)
    shallow = pick_sources(core, requests - len(tips), seed=1)
    stream = np.asarray(shallow, np.int64).tolist()
    gap = max(1, len(stream) // max(len(tips), 1))
    for i, tip in enumerate(tips):
        stream.insert(i * gap, int(tip))
    stream = np.asarray(stream[:requests], np.int64)

    # deepest query: tip -> core -> another tail's tip (~2*tail_len + diam)
    cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=2 * tail_len + 48,
                        enable_do=True)
    mk = lambda refill: BFSServeEngine(pg=pg, cfg=cfg, cache_capacity=0,
                                       refill=refill)

    results = {}
    for name, refill in (("batch", False), ("refill", True)):
        eng = mk(refill)
        eng.warmup()
        t0 = time.perf_counter()
        levels = eng.query(stream)
        dt = time.perf_counter() - t0
        results[name] = (eng, levels, dt)

    eng_b, lev_b, t_b = results["batch"]
    eng_r, lev_r, t_r = results["refill"]

    # exact oracle parity for every lane of the refill run (incl. refilled)
    for s in np.unique(stream):
        idx = int(np.nonzero(stream == s)[0][0])
        np.testing.assert_array_equal(lev_r[idx], bfs_levels(g, int(s)))
    np.testing.assert_array_equal(lev_r, lev_b)

    qps_b = len(stream) / t_b
    qps_r = len(stream) / t_r
    emit("msbfs/serve_batch", 1e6 * t_b / len(stream),
         f"qps={qps_b:.2f} batches={eng_b.stats.batches}")
    emit("msbfs/serve_refill", 1e6 * t_r / len(stream),
         f"qps={qps_r:.2f} sweeps={eng_r.stats.sweeps} "
         f"refills={eng_r.stats.refills} "
         f"lane_util={eng_r.stats.lane_utilization:.0%} "
         f"speedup={qps_r / qps_b:.2f}x")
    assert qps_r >= min_speedup * qps_b, (
        f"refill {qps_r:.2f} q/s < {min_speedup}x batch {qps_b:.2f} q/s")
    return {"qps_batch": qps_b, "qps_refill": qps_r,
            "speedup": qps_r / qps_b,
            "lane_utilization": eng_r.stats.lane_utilization,
            "sweeps": eng_r.stats.sweeps, "refills": eng_r.stats.refills}


def run_overlap(scale: int = 7, th: int = 64, p_rank: int = 2, p_gpu: int = 2,
                n_queries: int = 32, n_tails: int = 8, tail_len: int = 96,
                requests: int = 40, sweep_block: int = 8, max_depth: int = 3,
                reps: int = 5, min_speedup: float = 1.2,
                out_json: str = "BENCH_queries.json"):
    """Overlapped pipeline vs synchronous refill: same schedule, fewer
    host round trips, >= ``min_speedup`` queries/sec on a skewed stream of
    all four query kinds.

    The default graph is deliberately small: the pipeline's win is the
    removed per-sweep host round trips, so the gate measures it where that
    overhead is a stable fraction of a sweep regardless of how loaded the
    host is (big-graph sweeps drown it in device compute on CPU emulation;
    on real accelerators the round-trip/sweep ratio grows, not shrinks)."""
    from repro.graphs.synthetic import with_tails
    from repro.serve import BFSServeEngine, Query, QueryKind, oracle_check

    core = rmat_graph(scale, seed=3)
    g, tips = with_tails(core, n_tails=n_tails, length=tail_len, seed=5)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)

    # the skewed stream, deep tail tips spread through shallow core sources,
    # cycled through all four query kinds
    shallow = pick_sources(core, requests - len(tips), seed=1)
    stream = np.asarray(shallow, np.int64).tolist()
    gap = max(1, len(stream) // max(len(tips), 1))
    for i, tip in enumerate(tips):
        stream.insert(i * gap, int(tip))
    stream = np.asarray(stream[:requests], np.int64)
    tpool = tuple(int(s) for s in shallow[:2])
    kinds = [lambda s: Query(s),
             lambda s: Query(s, QueryKind.REACHABILITY),
             lambda s: Query(s, QueryKind.DISTANCE_LIMITED,
                             max_depth=max_depth),
             lambda s: Query(s, QueryKind.MULTI_TARGET, targets=tpool)]
    queries = [kinds[i % 4](int(s)) for i, s in enumerate(stream)]

    cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=2 * tail_len + 48)
    # reuse_components=False keeps every rep the same workload (no
    # cross-rep component memoization), so best-of-``reps`` timing is
    # apples-to-apples and the counter totals of the two drivers stay
    # directly comparable
    mk = lambda overlap: BFSServeEngine(
        pg=pg, cfg=cfg, cache_capacity=0, refill=True, overlap=overlap,
        sweep_block=sweep_block, reuse_components=False)
    engines = {"sync": mk(False), "overlap": mk(True)}
    times = {"sync": [], "overlap": []}
    answers = {}
    for eng in engines.values():
        eng.warmup(targets=True)
    # interleave the drivers' reps: each rep times sync and overlap
    # back-to-back, so the speedup is judged on the median of *per-pair*
    # ratios -- slow machine-load drift hits both sides of a pair equally
    # and cancels, unlike independent best-of/median estimates
    for _ in range(reps):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            answers[name] = eng.run_refill_queries(queries)
            times[name].append(time.perf_counter() - t0)
    for name in engines:
        for q in queries:
            oracle_check(g, q, answers[name][q])

    eng_s, t_s = engines["sync"], float(np.median(times["sync"]))
    eng_o, t_o = engines["overlap"], float(np.median(times["overlap"]))
    speedup = float(np.median([ts / to for ts, to in
                               zip(times["sync"], times["overlap"])]))

    # the pipeline must not change the traversal schedule: sweep and
    # wire-volume accounting bit-identical to the per-sweep driver
    for key in ("sweeps", "refills", "lane_sweeps_busy", "lane_sweeps_total",
                "wire_delegate_bytes", "wire_nn_bytes", "nn_sparse_sweeps",
                "nn_overflow", "early_stops"):
        a, b = eng_s.stats.as_dict()[key], eng_o.stats.as_dict()[key]
        assert a == b, f"pipelined driver diverged on {key}: {a} != {b}"

    qps_s = len(queries) / t_s
    qps_o = len(queries) / t_o
    fusion = eng_o.stats.sweeps / max(eng_o.stats.sweep_blocks, 1)
    emit("msbfs/serve_sync_refill", 1e6 * t_s / len(queries),
         f"qps={qps_s:.2f} sweeps={eng_s.stats.sweeps}")
    emit("msbfs/serve_overlap", 1e6 * t_o / len(queries),
         f"qps={qps_o:.2f} blocks={eng_o.stats.sweep_blocks} "
         f"fusion={fusion:.1f}x speedup={speedup:.2f}x")
    assert speedup >= min_speedup, (
        f"overlapped pipeline {qps_o:.2f} q/s < {min_speedup}x synchronous "
        f"refill {qps_s:.2f} q/s (median per-pair speedup {speedup:.2f}x)")

    section = {
        "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                  "n_tails": n_tails, "tail_len": tail_len},
        "requests": int(len(stream)), "n_queries": n_queries,
        "sweep_block": sweep_block,
        "qps_sync": qps_s, "qps_overlap": qps_o,
        "speedup": speedup,
        "sweeps": eng_o.stats.sweeps,
        "sweep_blocks": eng_o.stats.sweep_blocks,
        "fusion": fusion,
        "wire_bytes_total": eng_o.stats.wire_bytes_total,
        "counters_bit_identical": True,
    }
    write_bench(out_json, "overlap", section)
    return section


def run_chunked(scale: int = 12, th: int = 64, p_rank: int = 2,
                p_gpu: int = 2, n_queries: int = 32, edge_chunk: int = 4096,
                nn: str = "compressed", check_oracle: bool = True,
                out_json: str | None = None):
    """Chunked out-of-core sweeps vs the monolithic sweep: bit-identical
    schedule and counters, bounded transient memory.

    Runs the same W-lane msBFS batch twice -- ``MSBFSConfig(edge_chunk=0)``
    and ``MSBFSConfig(edge_chunk=edge_chunk)`` -- and asserts **every**
    state leaf (levels, per-sweep telemetry, work/wire counters) is
    bit-identical, then checks the answers against the numpy oracle.
    This is the acceptance harness for scale-16+ graphs whose monolithic
    [e_max, W] edge-frontier buffers would not fit: the chunked run
    streams ``edge_chunk``-edge blocks through ``lax.scan`` instead.
    Defaults to the compressed nn wire format so one run exercises both
    the codec accounting and the chunked schedule."""
    from repro.core.comm import CommConfig
    from repro.core.oracle import bfs_levels

    g = rmat_graph(scale, seed=3)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
    plan = E.build_exchange_plan(pg)
    pgv = B.device_view(pg)
    sources = pick_sources(g, n_queries, seed=1)

    outs, times = {}, {}
    for name, ec in (("monolithic", 0), ("chunked", edge_chunk)):
        cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=48,
                            enable_do=True, edge_chunk=ec,
                            comm=CommConfig(nn=nn))
        out = M.run_msbfs_emulated(
            pgv, plan, M.init_multi_state(pg, sources, cfg), cfg)
        jax.block_until_ready(out.level_n)          # compile + warm
        t0 = time.perf_counter()
        out = M.run_msbfs_emulated(
            pgv, plan, M.init_multi_state(pg, sources, cfg), cfg)
        jax.block_until_ready(out.level_n)
        outs[name], times[name] = out, time.perf_counter() - t0

    # the chunked schedule must be *bit-identical*: every leaf of the
    # final state, counters and telemetry included
    la, lb = jax.tree.leaves(outs["monolithic"]), jax.tree.leaves(outs["chunked"])
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    levels = M.gather_levels_multi(pg, outs["chunked"])
    if check_oracle:
        for q, src in enumerate(sources):
            np.testing.assert_array_equal(levels[q], bfs_levels(g, int(src)))

    st = outs["chunked"]
    counters = {
        "sweeps": int(np.max(np.asarray(st.it))),
        "work_fwd": int(np.sum(np.asarray(st.work_fwd))),
        "work_bwd": int(np.sum(np.asarray(st.work_bwd))),
        "nn_sent": int(np.sum(np.asarray(st.nn_sent))),
        "wire_delegate_bytes": int(np.sum(np.asarray(st.wire_delegate))),
        "wire_nn_bytes": int(np.sum(np.asarray(st.wire_nn))),
        "nn_overflow": int(np.sum(np.asarray(st.nn_overflow))),
    }
    t_m, t_c = times["monolithic"], times["chunked"]
    emit("msbfs/chunked", 1e6 * t_c / n_queries,
         f"edge_chunk={edge_chunk} nn={nn} sweeps={counters['sweeps']} "
         f"wire_nn={counters['wire_nn_bytes']} "
         f"vs_monolithic={t_c / t_m:.2f}x time")
    section = {
        "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                  "p_rank": p_rank, "p_gpu": p_gpu, "seed": 3},
        "n_queries": n_queries, "edge_chunk": edge_chunk, "nn": nn,
        **counters,
        "counters_bit_identical": True,
        "oracle_exact": bool(check_oracle),
        "time_monolithic_s": t_m, "time_chunked_s": t_c,
    }
    if out_json:
        write_bench(out_json, "chunked", section)
    return section


def run_payload(scale: int = 9, th: int = 64, p_rank: int = 2, p_gpu: int = 2,
                n_queries: int = 32, requests: int = 36,
                out_json: str = "BENCH_queries.json"):
    """Payload-plane query kinds: per-kind wire accounting on one substrate.

    Serves the same source stream as four homogeneous runs -- full levels
    (bit plane only), weighted SSSP and components (both ride the int32
    per-lane payload plane), k-hop sampling (bit plane + depth cap) -- and
    one seven-kind mixed rotation, all through the refill engine. Every
    answer is oracle-checked. The reported wire split (delegate vs nn, bit
    plane vs payload plane) pins the refactor's compile-away claim as
    counters: bit-only runs ship exactly zero payload bytes, payload runs
    ship both planes, and the mixed run's schedule is whatever the lane
    word's union needs. Results land in a ``payload_kinds`` section of
    ``BENCH_queries.json`` for ``scripts/bench_gate.py``."""
    from repro.serve import BFSServeEngine, Query, QueryKind, oracle_check

    g = rmat_graph(scale, seed=7)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)
    srcs = pick_sources(g, requests, seed=1)
    cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=48)

    def serve(queries, payload, targets=False):
        eng = BFSServeEngine(pg=pg, cfg=cfg, cache_capacity=0, refill=True,
                             reuse_components=False)
        eng.warmup(targets=targets, payload=payload)
        t0 = time.perf_counter()
        answers = eng.submit_many(queries)
        dt = time.perf_counter() - t0
        for q, a in zip(queries, answers):
            oracle_check(g, q, a)
        st = eng.stats
        return st, {
            "qps": len(queries) / dt,
            "sweeps": st.sweeps,
            "wire_delegate_bytes": st.wire_delegate_bytes,
            "wire_nn_bytes": st.wire_nn_bytes,
            "wire_pay_delegate_bytes": st.wire_pay_delegate_bytes,
            "wire_pay_nn_bytes": st.wire_pay_nn_bytes,
            "nn_overflow": st.nn_overflow,
        }

    runs = {
        "levels": [Query(int(s)) for s in srcs],
        "weighted_sssp": [Query(int(s), QueryKind.WEIGHTED_SSSP)
                          for s in srcs],
        "components": [Query(int(s), QueryKind.COMPONENTS) for s in srcs],
        "khop_sample": [Query(int(s), QueryKind.KHOP_SAMPLE, max_depth=3)
                        for s in srcs],
    }
    tpool = tuple(int(s) for s in srcs[:2])
    kinds = [lambda s: Query(s),
             lambda s: Query(s, QueryKind.REACHABILITY),
             lambda s: Query(s, QueryKind.DISTANCE_LIMITED, max_depth=3),
             lambda s: Query(s, QueryKind.MULTI_TARGET, targets=tpool),
             lambda s: Query(s, QueryKind.WEIGHTED_SSSP),
             lambda s: Query(s, QueryKind.COMPONENTS),
             lambda s: Query(s, QueryKind.KHOP_SAMPLE, max_depth=2)]
    mixed_q = [kinds[i % len(kinds)](int(s)) for i, s in enumerate(srcs)]

    section: dict = {
        "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                  "th": th, "seed": 7},
        "requests": int(len(srcs)), "n_queries": n_queries,
        "oracle_exact": True,
    }
    for name, queries in runs.items():
        payload = name in ("weighted_sssp", "components")
        _, row = serve(queries, payload)
        emit(f"msbfs/payload_{name}", 1e6 / row["qps"],
             f"qps={row['qps']:.2f} sweeps={row['sweeps']} "
             f"delegate={row['wire_delegate_bytes']}B "
             f"nn={row['wire_nn_bytes']}B "
             f"pay_delegate={row['wire_pay_delegate_bytes']}B "
             f"pay_nn={row['wire_pay_nn_bytes']}B")
        section[name] = row

    st_mx, row = serve(mixed_q, True, targets=True)
    emit("msbfs/payload_mixed", 1e6 / row["qps"],
         f"qps={row['qps']:.2f} sweeps={row['sweeps']} "
         f"pay_delegate={row['wire_pay_delegate_bytes']}B "
         f"pay_nn={row['wire_pay_nn_bytes']}B")
    section["mixed"] = {**row, "kind_counts": st_mx.kind_counts,
                        "early_stops": st_mx.early_stops}

    # compile-away + plane-accounting claims, as counters (deterministic)
    for name in ("levels", "khop_sample"):
        assert section[name]["wire_pay_delegate_bytes"] == 0
        assert section[name]["wire_pay_nn_bytes"] == 0
    for name in ("weighted_sssp", "components", "mixed"):
        assert section[name]["wire_pay_delegate_bytes"] > 0
        assert section[name]["wire_pay_nn_bytes"] > 0
    assert all(section[k]["nn_overflow"] == 0
               for k in (*runs, "mixed"))
    write_bench(out_json, "payload_kinds", section)
    return section


def run_mixed(scale: int = 10, edge_factor: int = 8, th: int = 64,
              p_rank: int = 2, p_gpu: int = 2, n_queries: int = 32,
              requests: int = 40, n_tails: int = 4, tail_len: int = 48,
              max_depth: int = 3, min_reach_speedup: float = 1.3,
              min_raw_reach: float = 0.6,
              out_json: str = "BENCH_queries.json"):
    """Typed-query serving: one skewed stream, four query kinds."""
    from repro.core.oracle import bfs_levels, bfs_levels_limited
    from repro.graphs.synthetic import with_tails
    from repro.serve import BFSServeEngine, Query, QueryKind

    core = rmat_graph(scale, edge_factor=edge_factor, seed=3)
    g, tips = with_tails(core, n_tails=n_tails, length=tail_len, seed=5)
    pg = partition_graph(g, th=th, p_rank=p_rank, p_gpu=p_gpu)

    shallow = pick_sources(core, requests - len(tips), seed=1)
    stream = np.concatenate([shallow, tips]).astype(np.int64)
    np.random.default_rng(0).shuffle(stream)
    stream = stream[:requests]
    tpool = [int(s) for s in shallow[:4]]   # multi-target target pool

    cfg = M.MSBFSConfig(n_queries=n_queries, max_iters=2 * tail_len + 48)
    oracle = {int(s): bfs_levels(g, int(s)) for s in stream}

    def serve(name, queries, check, **eng_kw):
        eng = BFSServeEngine(pg=pg, cfg=cfg, cache_capacity=0, refill=True,
                             **eng_kw)
        eng.warmup(reachability=all(q.kind is QueryKind.REACHABILITY
                                    for q in queries),
                   targets=any(q.kind is QueryKind.MULTI_TARGET
                               for q in queries))
        t0 = time.perf_counter()
        answers = eng.submit_many(queries)
        dt = time.perf_counter() - t0
        for q, a in zip(queries, answers):
            check(q, a)
        return eng, len(queries) / dt

    inf = np.int32(2**30)
    eng_lv, qps_levels = serve(
        "levels", [Query(int(s)) for s in stream],
        lambda q, a: np.testing.assert_array_equal(a, oracle[q.source]))
    reach_q = [Query(int(s), QueryKind.REACHABILITY) for s in stream]
    reach_chk = lambda q, a: np.testing.assert_array_equal(
        a, oracle[q.source] != inf)
    _, qps_reach_raw = serve("reach_raw", reach_q, reach_chk,
                             reuse_components=False)
    eng_re, qps_reach = serve("reach", reach_q, reach_chk)
    eng_dl, qps_dist = serve(
        "distance", [Query(int(s), QueryKind.DISTANCE_LIMITED,
                           max_depth=max_depth) for s in stream],
        lambda q, a: np.testing.assert_array_equal(
            a, bfs_levels_limited(g, q.source, max_depth)))

    from repro.serve import oracle_check

    kinds = [lambda s: Query(s),
             lambda s: Query(s, QueryKind.REACHABILITY),
             lambda s: Query(s, QueryKind.DISTANCE_LIMITED, max_depth=max_depth),
             lambda s: Query(s, QueryKind.MULTI_TARGET, targets=tuple(tpool[:2]))]
    mixed_q = [kinds[i % 4](int(s)) for i, s in enumerate(stream)]

    eng_mx, qps_mixed = serve("mixed", mixed_q,
                              lambda q, a: oracle_check(g, q, a))

    summary = {
        "graph": {"n": int(g.n), "m": int(g.m), "scale": scale,
                  "edge_factor": edge_factor, "n_tails": n_tails,
                  "tail_len": tail_len},
        "requests": int(len(stream)), "n_queries": n_queries,
        "qps": {"levels": qps_levels, "reachability_raw": qps_reach_raw,
                "reachability": qps_reach, "distance_limited": qps_dist,
                "mixed": qps_mixed},
        "speedup_vs_levels": {
            "reachability_raw": qps_reach_raw / qps_levels,
            "reachability": qps_reach / qps_levels,
            "distance_limited": qps_dist / qps_levels,
            "mixed": qps_mixed / qps_levels,
        },
        "levels_sweeps": eng_lv.stats.sweeps,
        "distance_limited_sweeps": eng_dl.stats.sweeps,
        "distance_limited_early_stops": eng_dl.stats.early_stops,
        "reach_component_hits": eng_re.stats.component_hits,
        "reach_fast_batches": eng_re.stats.reach_fast_batches,
        "mixed_kind_counts": eng_mx.stats.kind_counts,
        "mixed_early_stops": eng_mx.stats.early_stops,
        "mixed_early_stops_by_kind": eng_mx.stats.early_stops_by_kind,
        # comm-layer accounting for the mixed run (wire bytes per the
        # core/comm byte convention; nn_overflow must be 0 for validity)
        "mixed_wire_delegate_bytes": eng_mx.stats.wire_delegate_bytes,
        "mixed_wire_nn_bytes": eng_mx.stats.wire_nn_bytes,
        "mixed_nn_sparse_sweeps": eng_mx.stats.nn_sparse_sweeps,
        "mixed_nn_overflow": eng_mx.stats.nn_overflow,
    }
    write_bench(out_json, "mixed", summary)

    emit("msbfs/serve_levels", 1e6 / qps_levels,
         f"qps={qps_levels:.2f} sweeps={eng_lv.stats.sweeps}")
    emit("msbfs/serve_reach", 1e6 / qps_reach,
         f"qps={qps_reach:.2f} raw_qps={qps_reach_raw:.2f} "
         f"comp_hits={eng_re.stats.component_hits} "
         f"speedup={qps_reach / qps_levels:.2f}x")
    emit("msbfs/serve_distlim", 1e6 / qps_dist,
         f"qps={qps_dist:.2f} sweeps={eng_dl.stats.sweeps} "
         f"early_stops={eng_dl.stats.early_stops} "
         f"speedup={qps_dist / qps_levels:.2f}x")
    emit("msbfs/serve_mixed", 1e6 / qps_mixed,
         f"qps={qps_mixed:.2f} early_stops={eng_mx.stats.early_stops} "
         f"speedup={qps_mixed / qps_levels:.2f}x")
    assert qps_reach >= min_reach_speedup * qps_levels, (
        f"reachability-only {qps_reach:.2f} q/s < {min_reach_speedup}x "
        f"full-levels {qps_levels:.2f} q/s")
    # The levels-free variant's per-sweep edge (no level scatter, no [E, W]
    # work counters) is a few percent on CPU emulation -- within run-to-run
    # noise -- so raw is reported, with only a generous regression floor.
    assert qps_reach_raw >= min_raw_reach * qps_levels, (
        f"levels-free reachability path {qps_reach_raw:.2f} q/s regressed "
        f"far below full-levels {qps_levels:.2f} q/s")
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--refill", action="store_true",
                    help="benchmark lane refill vs batch-at-a-time serving")
    ap.add_argument("--mixed", action="store_true",
                    help="benchmark the typed-query kinds on one stream")
    ap.add_argument("--overlap", action="store_true",
                    help="benchmark the overlapped host/device pipeline vs "
                         "the synchronous refill driver")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked out-of-core sweeps vs monolithic: "
                         "bit-identical counters + oracle check")
    ap.add_argument("--payload", action="store_true",
                    help="payload-plane query kinds (weighted SSSP, "
                         "components, k-hop) with per-kind wire accounting")
    ap.add_argument("--edge-chunk", type=int, default=4096,
                    help="edge block size for --chunked")
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args()
    kw = {} if args.scale is None else {"scale": args.scale}
    if args.payload:
        print(run_payload(**kw))
    elif args.chunked:
        print(run_chunked(edge_chunk=args.edge_chunk, **kw))
    elif args.overlap:
        print(run_overlap(**kw))
    elif args.mixed:
        print(run_mixed(**kw))
    elif args.refill:
        print(run_refill(**kw))
    else:
        print(run(**kw))
