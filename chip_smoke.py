"""Chip smoke: serve a mixed query stream on a scale-20 RMAT graph on a TPU.

One chip (the default) runs two phases through ``BFSServeEngine`` on the
Graph500 RMAT graph at scale 20 (1,048,576 vertices, edge factor 16,
edge-doubled), partitioned ``th=64, p_rank=2, p_gpu=2`` (p=4 emulated on
the chip):

1. native: the overlapped refill driver (donation on, default comm) serves
   a skewed stream of 256 requests that rotates all seven query kinds;
   at least one answer per kind is checked against ``repro.core.oracle``;
2. pallas: the same graph, ``kernel_pull="pallas"`` and the Pallas
   delegate fold, serves the stream's first 64 bit-kind requests (the
   kernels sit on the bit plane); its answers must equal the native ones,
   and its compiled blocks must hold Mosaic kernels (``tpu_custom_call``),
   not interpreted ones.

``--chips 4`` runs only the sharded path: the same graph over a mesh of
four chips (one partition each), once with the default comm and once with
``CommConfig(delegate="ring", nn="adaptive")``, each serving the stream's
first 32 bit-kind requests through the per-sweep refill driver and each
compared with the emulated engine on the first chip under the same comm
(identical answers and identical ``ServeStats`` counters).

Earlier lines report graph size, compile and wall seconds per phase (set-up
facts, not speed claims), per-kind checks and device memory. The last line
is one JSON object naming the device. The script exits non-zero, printing
no result, when JAX finds no TPU or any check fails.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SCALE = 20
REQUESTS = 256
PALLAS_REQUESTS = 64
SHARDED_REQUESTS = 32
HOT = 16
# The payload plane's monolithic push working set at scale 20 is an
# [E, W] int32 array per subgraph; with the TPU's 128-lane tiling it needs
# about 23.5 GB of the chip's 16 GB. 2**19-edge blocks bound the sweep to
# about 6.2 GB of temporaries (compile rehearsal for v5e); the traversal
# schedule and every counter are bit-identical to the monolithic sweep.
EDGE_CHUNK = 1 << 19
# oracle checks per kind (the Dijkstra and union-find oracles are pure
# Python: tens of seconds each at this scale)
CHECKS = {"levels": 2, "reachability": 1, "distance_limited": 1,
          "multi_target": 1, "khop_sample": 1, "weighted_sssp": 1,
          "components": 1}


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check ends the run (unlike ``assert``, kept under -O)."""
    if not ok:
        raise RuntimeError(msg)


def build_graph(scale: int):
    from repro.core.partition import partition_graph
    from repro.graphs.rmat import rmat_graph

    t0 = time.perf_counter()
    g = rmat_graph(scale, seed=0)
    pg = partition_graph(g, th=64, p_rank=2, p_gpu=2)
    log(f"graph: rmat scale={scale} n={g.n:,} stored_edges={g.m:,} "
        f"p={pg.p} n_local={pg.n_local:,} delegates={pg.d:,} "
        f"build_s={time.perf_counter() - t0:.1f}")
    return g, pg


def mixed_stream(g, requests: int):
    """The skewed stream of ``examples/bfs_serving.py --mixed``: 80% of
    requests on 16 hot sources, kinds rotating through all seven."""
    from repro.graphs.rmat import pick_sources
    from repro.serve import Query, QueryKind

    candidates = pick_sources(g, 4 * HOT, seed=7)
    hot, cold = candidates[:HOT], candidates[HOT:]
    rng = np.random.default_rng(1)
    stream = np.where(rng.random(requests) < 0.8,
                      rng.choice(hot, requests), rng.choice(cold, requests))
    tpool = tuple(int(s) for s in np.unique(stream)[:2])
    kinds = [lambda s: Query(s),
             lambda s: Query(s, QueryKind.REACHABILITY),
             lambda s: Query(s, QueryKind.DISTANCE_LIMITED, max_depth=3),
             lambda s: Query(s, QueryKind.MULTI_TARGET, targets=tpool),
             lambda s: Query(s, QueryKind.WEIGHTED_SSSP),
             lambda s: Query(s, QueryKind.COMPONENTS),
             lambda s: Query(s, QueryKind.KHOP_SAMPLE, max_depth=2)]
    return [kinds[i % len(kinds)](int(s)) for i, s in enumerate(stream)]


def bit_kinds(queries):
    """The requests that need no payload plane (five of the seven kinds)."""
    from repro.serve import PAYLOAD_KINDS

    return [q for q in queries if q.kind not in PAYLOAD_KINDS]


def same_answer(a, b) -> bool:
    if isinstance(a, dict):
        return a == b
    return np.array_equal(np.asarray(a), np.asarray(b))


def serve(name: str, eng, queries):
    """Compile exactly the variant ``queries`` runs, serve them, and
    report set-up and wall seconds."""
    t0 = time.perf_counter()
    eng.warmup(queries=queries)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    answers = eng.submit_many(queries)
    wall_s = time.perf_counter() - t0
    st = eng.stats
    log(f"{name}: requests={len(queries)} compile_s={compile_s:.1f} "
        f"wall_s={wall_s:.1f} sweeps={st.sweeps} blocks={st.sweep_blocks} "
        f"wire_total_B={st.wire_bytes_total} nn_overflow={st.nn_overflow}")
    require(st.nn_overflow == 0, f"{name}: nn exchange dropped slots")
    return answers


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        ms = d.memory_stats() or {}
        parts.append(f"dev{d.id}: peak_bytes_in_use="
                     f"{ms.get('peak_bytes_in_use', 'n/a')} "
                     f"bytes_in_use={ms.get('bytes_in_use', 'n/a')}")
    return "; ".join(parts)


def engine(pg, **kw):
    from repro.serve import BFSServeEngine

    return BFSServeEngine(pg=pg, refill=True, edge_chunk=EDGE_CHUNK, **kw)


def oracle_checks(g, queries, answers) -> dict:
    """Check the first ``CHECKS[kind]`` distinct queries of every kind
    against the numpy oracle; returns {kind: checks passed}."""
    from repro.serve import oracle_check

    checked = {k: set() for k in CHECKS}
    for q, a in zip(queries, answers):
        k = q.kind.value
        if len(checked[k]) >= CHECKS[k] or q in checked[k]:
            continue
        t0 = time.perf_counter()
        oracle_check(g, q, a)
        checked[k].add(q)
        log(f"  oracle {k} source={q.source}: OK "
            f"({time.perf_counter() - t0:.1f}s)")
    counts = {k: len(v) for k, v in checked.items() if v}
    missing = sorted(set(CHECKS) - set(counts))
    require(not missing, f"no oracle check for kinds {missing}")
    return counts


def one_chip(g, pg, dev, requests=REQUESTS, pallas_requests=PALLAS_REQUESTS):
    from repro.core import msbfs as M
    from repro.core.comm import CommConfig

    queries = mixed_stream(g, requests)

    # ---- native phase: overlapped refill driver, default comm
    eng = engine(pg, overlap=True)
    answers = serve("native", eng, queries)
    counts = oracle_checks(g, queries, answers)
    log("native oracle checks per kind: " + json.dumps(counts, sort_keys=True))
    log("after native: " + memory_line([dev]))
    del eng
    gc.collect()            # release the native engine's device buffers

    # ---- pallas phase: compiled lane-word kernels on the same graph. The
    # kernels sit on the bit plane (pull + delegate OR fold), so the phase
    # serves the stream's first bit-kind requests
    native = dict(zip(queries, answers))
    sub = bit_kinds(queries)[:pallas_requests]
    eng = engine(pg, overlap=True, cfg=M.MSBFSConfig(kernel_pull="pallas"),
                 comm=CommConfig(local_fold="pallas"))
    got = serve("pallas", eng, sub)
    bad = [q for q, a in zip(sub, got) if not same_answer(a, native[q])]
    require(not bad, f"pallas answers differ from native for {bad[:4]}")
    log(f"pallas answers equal native: {len(sub)}/{len(sub)}")
    cfg = eng._session_cfg(sub)
    block, block_donated = eng._block_pair(cfg)
    st = eng._put(M.init_multi_state(pg, [], cfg))
    watch = np.ones(cfg.n_queries, dtype=bool)
    for name, fn in (("block", block), ("block_donated", block_donated)):
        t0 = time.perf_counter()
        text = fn.lower(eng.pgv, eng.plan, st, watch).compile().as_text()
        n_calls = text.count("tpu_custom_call")
        log(f"pallas {name}: tpu_custom_call occurrences={n_calls} "
            f"({time.perf_counter() - t0:.1f}s)")
        require(n_calls > 0, f"pallas {name} holds no Mosaic kernel")
    log("after pallas: " + memory_line([dev]))


def four_chips(g, pg, devices, requests=SHARDED_REQUESTS):
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.core.comm import CommConfig

    queries = bit_kinds(mixed_stream(g, REQUESTS))[:requests]
    # one flat partition axis, as the emulated engine's vmap has: the
    # per-axis wire-byte formulas then count the same collectives
    mesh = Mesh(np.asarray(devices[:4]), ("p",), axis_types=(AxisType.Auto,))
    for comm in (CommConfig(), CommConfig(delegate="ring", nn="adaptive")):
        tag = f"delegate={comm.delegate},nn={comm.nn}"
        ref_eng = engine(pg, comm=comm)
        ref = serve(f"emulated[{tag}]", ref_eng, queries)
        ref_stats = ref_eng.stats.as_dict()
        del ref_eng
        gc.collect()
        eng = engine(pg, comm=comm, mesh=mesh)
        require(eng.sharded, "the 4-device mesh did not shard the engine")
        # each device holds its own partition's quarter of the graph
        for leaf in jax.tree.leaves(eng.pgv):
            for shard in leaf.addressable_shards:
                require(shard.data.shape[0] == pg.p // 4,
                        "a device holds more than its partition")
        got = serve(f"sharded[{tag}]", eng, queries)
        bad = [i for i, (a, b) in enumerate(zip(got, ref))
               if not same_answer(a, b)]
        require(not bad,
                f"sharded answers differ from emulated at {bad[:8]}")
        stats = eng.stats.as_dict()
        diff = {k: (stats[k], ref_stats[k]) for k in stats
                if stats[k] != ref_stats[k]}
        require(not diff, f"sharded ServeStats differ: {diff}")
        log(f"sharded[{tag}] == emulated: answers {len(got)}/{len(got)}, "
            f"ServeStats identical (sweeps={stats['sweeps']} "
            f"wire_delegate_B={stats['wire_delegate_bytes']} "
            f"wire_nn_B={stats['wire_nn_bytes']} "
            f"wire_pay_delegate_B={stats['wire_pay_delegate_bytes']} "
            f"wire_pay_nn_B={stats['wire_pay_nn_bytes']})")
        log(f"sharded[{tag}] memory: " + memory_line(devices[:4]))
        del eng
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    g, pg = build_graph(SCALE)
    if args.chips == 4:
        four_chips(g, pg, devices)
    else:
        one_chip(g, pg, dev)
    log(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
