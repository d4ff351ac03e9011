"""Property tests for the pluggable comm subsystem (core/comm/).

Strategy equivalence is the load-bearing invariant: every delegate combine
strategy (all-gather-fold, ring via ppermute, two-level hierarchical, the
mask_reduce-kernel local fold) must be *bit-exact* with every other on
random lane words -- on the vmap-emulated axis, on a nested two-axis vmap
(the emulated multi-axis mesh), and on real 4- and 8-device shard_map
meshes. The nn wire formats (dense / sparse / adaptive) must decode to the
same received set, with the pinned-sparse overflow counter the only
permitted difference. Randomized via ``tests/_hypo`` (hypothesis when
installed, the deterministic replayer otherwise).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bfs as B, comm, engine as E, msbfs as M
from repro.core.oracle import bfs_levels
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph

from _hypo import given, settings, st

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 host devices (run under the multi-device CI job)")
needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >= 8 host devices (run under the 8-device CI job)")

DELEGATE_CFGS = [
    comm.CommConfig(delegate="allgather"),
    comm.CommConfig(delegate="allgather", local_fold="ref"),
    comm.CommConfig(delegate="ring"),
    comm.CommConfig(delegate="hier"),
]


def _rand_words(rng, p, rows, nw):
    return jnp.asarray(
        rng.integers(0, 2**32, (p, rows, nw), dtype=np.uint32))


# ---------------------------------------------------------- vmap-emulated
@settings(max_examples=15, deadline=None)
@given(p=st.integers(2, 5), rows=st.integers(1, 9), nw=st.integers(1, 3),
       seed=st.integers(0, 10_000))
def test_delegate_or_strategies_bit_exact_vmap(p, rows, nw, seed):
    """ring / hier / mask-fold == all-gather-fold == numpy OR, any p."""
    words = _rand_words(np.random.default_rng(seed), p, rows, nw)
    want = np.bitwise_or.reduce(np.asarray(words), axis=0)
    for cfg in DELEGATE_CFGS:
        got = jax.jit(jax.vmap(
            lambda x: comm.delegate_allreduce_or(x, "p", cfg),
            axis_name="p"))(words)
        for i in range(p):
            np.testing.assert_array_equal(np.asarray(got)[i], want), cfg


@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 9), nw=st.integers(1, 3),
       seed=st.integers(0, 10_000))
def test_delegate_or_strategies_two_axis_emulated(rows, nw, seed):
    """Nested vmap = an emulated (2, 2) mesh: the hierarchical strategy
    actually runs two levels there (intra axis, then inter axis) and must
    still match the flat fold; ring runs per-axis rings."""
    words = _rand_words(np.random.default_rng(seed), 4, rows, nw)
    want = np.bitwise_or.reduce(np.asarray(words), axis=0)
    w4 = words.reshape(2, 2, rows, nw)
    for cfg in DELEGATE_CFGS:
        f = lambda x: comm.delegate_allreduce_or(x, ("outer", "inner"), cfg)
        got = jax.vmap(jax.vmap(f, axis_name="inner"), axis_name="outer")(w4)
        got = np.asarray(got).reshape(4, rows, nw)
        for i in range(4):
            np.testing.assert_array_equal(got[i], want), cfg


# 4 examples keep the shape diversity (p and n both vary) while capping the
# per-example recompilation bill (3 ops x 4 strategies jitted per draw made
# this the slowest comm test at max_examples=10)
@settings(max_examples=4, deadline=None)
@given(p=st.integers(2, 5), n=st.integers(1, 33), seed=st.integers(0, 10_000))
def test_delegate_min_max_sum_strategies_vmap(p, n, seed):
    """The same strategy layer carries the single-source path's folds:
    min (levels), max (u8 masks), sum (payload engine)."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.integers(0, 1000, (p, n), dtype=np.int32))
    oracle = {"min": np.min, "max": np.max, "sum": np.sum}
    for op in ("min", "max", "sum"):
        want = oracle[op](np.asarray(vals), axis=0)
        for cfg in [comm.CommConfig(), comm.CommConfig(delegate="allgather"),
                    comm.CommConfig(delegate="ring"),
                    comm.CommConfig(delegate="hier")]:
            got = jax.jit(jax.vmap(
                lambda x: comm.delegate_combine(
                    comm.plan_for(cfg, "p"), x, op)[0],
                axis_name="p"))(vals)
            for i in range(p):
                np.testing.assert_array_equal(np.asarray(got)[i], want), (op, cfg)


# ------------------------------------------------------ nn wire formats
def _run_nn_words(mode, dense, recv_local, nl, sparse_cap):
    cfg = comm.CommConfig(nn=mode, sparse_cap=sparse_cap)

    def f(d, rl):
        return comm.nn_exchange_words(comm.plan_for(cfg, "p"), d, rl, nl)

    return jax.jit(jax.vmap(f, axis_name="p"))(dense, recv_local)


@settings(max_examples=10, deadline=None)
@given(p=st.integers(2, 4), cap=st.integers(2, 10), w=st.integers(1, 40),
       seed=st.integers(0, 10_000))
def test_nn_words_sparse_matches_dense_when_feasible(p, cap, w, seed):
    """With at most sparse_cap active slots per peer, the sparse and the
    adaptive formats decode to exactly the dense result, overflow 0, and
    adaptive picks sparse."""
    rng = np.random.default_rng(seed)
    nl = 16
    recv_local = jnp.asarray(rng.integers(-1, nl, (p, p, cap), dtype=np.int32))
    dense = np.zeros((p, p, cap, w), dtype=bool)
    for i in range(p):
        for j in range(p):            # <= 2 active slots per peer row
            for s in rng.choice(cap, size=rng.integers(0, 3), replace=False):
                dense[i, j, s] = rng.random(w) < 0.5
    dense = jnp.asarray(dense)
    rd, bd, sd, od = _run_nn_words("dense", dense, recv_local, nl, 2)
    rs, bs, ss, os_ = _run_nn_words("sparse", dense, recv_local, nl, 2)
    ra, ba, sa, oa = _run_nn_words("adaptive", dense, recv_local, nl, 2)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(ra))
    assert int(np.asarray(os_).sum()) == 0
    assert int(np.asarray(oa).sum()) == 0
    assert np.asarray(ss).all()               # pinned sparse always ships sparse
    plan = comm.CommPlan(comm.CommConfig(sparse_cap=2), ("p",), (p,))
    nw = comm.n_words(w)
    if plan.nn_sparse_words_bytes(2, nw) < plan.nn_dense_words_bytes(cap, nw):
        # sparse statically cheaper + feasible: adaptive must take it
        assert np.asarray(sa).all()
        assert int(np.asarray(ba)[0]) < int(np.asarray(bd)[0])
    else:
        # dense cheaper at this tiny cap: adaptive must collapse to dense
        assert not np.asarray(sa).any()


@settings(max_examples=10, deadline=None)
@given(p=st.integers(2, 4), cap=st.integers(4, 10), w=st.integers(1, 40),
       seed=st.integers(0, 10_000))
def test_nn_words_adaptive_falls_back_dense_and_sparse_overflows(p, cap, w, seed):
    """Saturated buffers: adaptive must pick dense (bit-exact, overflow 0)
    while the pinned sparse format counts its dropped slots."""
    rng = np.random.default_rng(seed)
    nl = 16
    recv_local = jnp.asarray(rng.integers(-1, nl, (p, p, cap), dtype=np.int32))
    dense = jnp.asarray(np.ones((p, p, cap, w), dtype=bool))
    rd, bd, sd, od = _run_nn_words("dense", dense, recv_local, nl, 2)
    ra, ba, sa, oa = _run_nn_words("adaptive", dense, recv_local, nl, 2)
    _, _, ss, os_ = _run_nn_words("sparse", dense, recv_local, nl, 2)
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(ra))
    assert not np.asarray(sa).any()
    assert int(np.asarray(oa).sum()) == 0
    assert int(np.asarray(os_).sum()) == p * p * (cap - 2)


@settings(max_examples=10, deadline=None)
@given(p=st.integers(2, 4), seed=st.integers(0, 10_000))
def test_nn_bits_formats_match(p, seed):
    """Single-source slot-bitmask vs slot-id-list vs adaptive parity."""
    rng = np.random.default_rng(seed)
    # 256 slots: the dense bitmask costs 32 B/peer, the 4-id sparse list 16
    cap, nl = 256, 16
    recv_local = jnp.asarray(rng.integers(-1, nl, (p, p, cap), dtype=np.int32))
    active = np.zeros((p, p, cap), dtype=bool)
    for i in range(p):
        for j in range(p):
            active[i, j, rng.choice(cap, 2, replace=False)] = True
    active = jnp.asarray(active)

    def run(mode):
        cfg = comm.CommConfig(nn=mode, sparse_cap=4)

        def f(a, rl):
            return comm.nn_exchange_bits(comm.plan_for(cfg, "p"), a, rl, nl)

        return jax.jit(jax.vmap(f, axis_name="p"))(active, recv_local)

    rd, bd, _, _ = run("dense")
    rs, bs, _, os_ = run("sparse")
    ra, _, sa, oa = run("adaptive")
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(rd), np.asarray(ra))
    assert int(np.asarray(os_).sum()) == 0 and int(np.asarray(oa).sum()) == 0
    assert np.asarray(sa).all()


# ---------------------------------------------------------- wire formulas
def test_plan_byte_formulas():
    """Static accounting: ring is O(1)-in-p, hier between ring and flat
    gather, adaptive sparse caps keep sparse strictly under dense."""
    n, itemsize = 4096, 4
    ag = comm.CommPlan(comm.CommConfig(delegate="allgather"), ("p",), (4,))
    ring = comm.CommPlan(comm.CommConfig(delegate="ring"), ("p",), (4,))
    hier = comm.CommPlan(comm.CommConfig(delegate="hier"), ("a", "b"), (2, 2))
    assert ring.delegate_bytes(n, itemsize) <= ag.delegate_bytes(n, itemsize)
    assert hier.delegate_bytes(n, itemsize) <= ag.delegate_bytes(n, itemsize)
    # ring volume is bounded by 2 payloads at any p; allgather grows linearly
    ring16 = comm.CommPlan(comm.CommConfig(delegate="ring"), ("p",), (16,))
    ag16 = comm.CommPlan(comm.CommConfig(delegate="allgather"), ("p",), (16,))
    assert ring16.delegate_bytes(n, itemsize) <= 2 * n * itemsize
    assert ag16.delegate_bytes(n, itemsize) == 5 * ag.delegate_bytes(n, itemsize)
    # auto-chosen sparse caps are strictly cheaper than dense
    for cap_peer in (64, 256, 4096):
        assert (ag.nn_sparse_words_bytes(ag.sparse_cap_words(cap_peer), 1)
                < ag.nn_dense_words_bytes(cap_peer, 1))
        assert (ag.nn_sparse_bits_bytes(ag.sparse_cap_bits(cap_peer))
                < ag.nn_dense_bits_bytes(cap_peer))


def test_comm_config_validates():
    with pytest.raises(ValueError):
        comm.CommConfig(delegate="nope")
    with pytest.raises(ValueError):
        comm.CommConfig(nn="nope")


def test_payload_round_bytes_model():
    g = rmat_graph(7, seed=0)
    pg = partition_graph(g, th=32, p_rank=2, p_gpu=1)
    plan = E.build_exchange_plan(pg)
    flat = E.payload_round_bytes(plan, axis_sizes=(2,), d=pg.d, feat=8)
    ring = E.payload_round_bytes(plan, axis_sizes=(2,), d=pg.d, feat=8,
                                 comm_cfg=comm.CommConfig(delegate="ring"))
    assert flat["p"] == 2 and flat["nn_payload_bytes"] > 0
    assert ring["delegate_bytes"] <= flat["delegate_bytes"]


# ------------------------------------------------- end-to-end (emulated)
def _strategy_sweep_engine(mesh=None):
    g = rmat_graph(7, seed=4)
    srcs = [int(s) for s in pick_sources(g, 6, seed=5)]
    oracle = {s: bfs_levels(g, s) for s in srcs}
    stats = {}
    for name, ccfg in [
        ("allgather", comm.CommConfig(delegate="allgather")),
        ("ring", comm.CommConfig(delegate="ring")),
        ("hier", comm.CommConfig(delegate="hier")),
        ("ring+adaptive", comm.CommConfig(delegate="ring", nn="adaptive")),
    ]:
        from repro.serve import BFSServeEngine

        eng = BFSServeEngine(g, th=32, p_rank=2, p_gpu=2,
                             cfg=M.MSBFSConfig(n_queries=4, max_iters=64),
                             comm=ccfg, cache_capacity=0, mesh=mesh,
                             refill=True)
        for s, lev in zip(srcs, eng.query(srcs)):
            np.testing.assert_array_equal(lev, oracle[s])
        assert eng.stats.wire_delegate_bytes > 0
        assert eng.stats.wire_nn_bytes > 0
        assert eng.stats.nn_overflow == 0
        stats[name] = eng.stats
    assert (stats["ring"].wire_delegate_bytes
            <= stats["allgather"].wire_delegate_bytes)
    return stats


def test_serve_engine_strategy_sweep_emulated():
    """Every strategy serves oracle-exact refill sessions on the emulated
    path, with live wire counters and ring <= allgather."""
    _strategy_sweep_engine(mesh=None)


def test_msbfs_pinned_sparse_overflow_surfaces():
    """A pinned sparse nn format with a too-small cap drops slots; the
    overflow must surface through ServeStats instead of silently breaking
    answers."""
    from repro.serve import BFSServeEngine

    g = rmat_graph(7, seed=4)
    eng = BFSServeEngine(g, th=32, p_rank=2, p_gpu=2,
                         cfg=M.MSBFSConfig(n_queries=4, max_iters=64),
                         comm=comm.CommConfig(nn="sparse", sparse_cap=1),
                         cache_capacity=0)
    eng.run_batch(np.asarray(pick_sources(g, 2, seed=5)))
    assert eng.stats.nn_sparse_sweeps > 0
    assert eng.stats.nn_overflow > 0          # surfaced, not silent
    d = eng.stats.as_dict()
    for key in ("wire_delegate_bytes", "wire_nn_bytes", "wire_bytes_total",
                "nn_sparse_sweeps", "nn_overflow", "early_stops_by_kind"):
        assert key in d


def test_serve_stats_early_stops_by_kind():
    from repro.serve import BFSServeEngine, Query, QueryKind

    g = rmat_graph(7, seed=4)
    eng = BFSServeEngine(g, th=32, p_rank=2, p_gpu=2,
                         cfg=M.MSBFSConfig(n_queries=4, max_iters=64),
                         cache_capacity=0)
    srcs = [int(s) for s in pick_sources(g, 3, seed=6)]
    eng.submit_many([Query(srcs[0]),
                     Query(srcs[1], QueryKind.DISTANCE_LIMITED, max_depth=1),
                     Query(srcs[2], QueryKind.DISTANCE_LIMITED, max_depth=1)])
    assert eng.stats.early_stops == sum(eng.stats.early_stops_by_kind.values())
    assert eng.stats.early_stops_by_kind.get("distance_limited", 0) == 2


def test_bfs_single_source_strategies_oracle_exact():
    """The single-source path end-to-end under ring/u8/static-adaptive."""
    g = rmat_graph(7, seed=6)
    pg = partition_graph(g, th=32, p_rank=2, p_gpu=1)
    plan = E.build_exchange_plan(pg)
    pgv = B.device_view(pg)
    src = int(pick_sources(g, 1, seed=2)[0])
    want = bfs_levels(g, src)
    for cfg, with_plan in [
        (B.BFSConfig(max_iters=48, comm=comm.CommConfig(delegate="ring")), False),
        (B.BFSConfig(max_iters=48, delegate_u8=True,
                     comm=comm.CommConfig(delegate="ring")), False),
        (B.BFSConfig(max_iters=48, static_exchange=True,
                     comm=comm.CommConfig(nn="adaptive")), True),
        (B.BFSConfig(max_iters=48, static_exchange=True,
                     comm=comm.CommConfig(delegate="hier", nn="adaptive")), True),
    ]:
        st = B.init_state(pg, src, cfg)
        out = B.run_bfs_emulated(pgv, st, cfg, plan=plan if with_plan else None)
        np.testing.assert_array_equal(B.gather_levels(pg, out), want)
        assert int(np.asarray(out.wire_delegate).sum()) > 0


# ------------------------------------------------------- shard_map meshes
def _shard_reduce(mesh, axes, fn, x):
    """Run ``fn`` (device-local [rows, ...] -> same) under shard_map with
    the leading axis of ``x`` split over ``axes``."""
    from jax.sharding import PartitionSpec as P

    spec = P(axes, *([None] * (x.ndim - 1)))
    f = jax.shard_map(lambda xl: fn(xl[0])[None], mesh=mesh,
                      in_specs=spec, out_specs=spec, check_vma=False)
    return jax.jit(f)(x)


@needs4
def test_delegate_or_strategies_bit_exact_shard_map_4dev():
    """Satellite property: ring-OR and hierarchical reduce bit-exact with
    all-gather-fold on random lane words on a real (2, 2) shard_map mesh."""
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 2), ("data", "model"))
    rng = np.random.default_rng(7)
    words = _rand_words(rng, 4, 6, 2)
    want = np.bitwise_or.reduce(np.asarray(words), axis=0)
    axes = ("data", "model")
    for cfg in DELEGATE_CFGS:
        got = _shard_reduce(
            mesh, axes,
            lambda x, c=cfg: comm.delegate_allreduce_or(x, axes, c), words)
        for i in range(4):
            np.testing.assert_array_equal(np.asarray(got)[i], want), cfg


@needs4
def test_serve_engine_strategy_sweep_sharded_4dev():
    """The full refill engine under every strategy on a 4-device mesh."""
    from repro.launch.mesh import make_test_mesh

    _strategy_sweep_engine(mesh=make_test_mesh((2, 2), ("data", "model")))


@needs8
def test_delegate_strategies_bit_exact_shard_map_8dev_two_axis():
    """The (2, 4) mesh: the hierarchical strategy's two levels have
    different sizes (intra 2, inter 4) -- the asymmetric case the flat
    4-device mesh cannot cover."""
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 4), ("node", "gpu"))
    rng = np.random.default_rng(8)
    words = _rand_words(rng, 8, 5, 2)
    want = np.bitwise_or.reduce(np.asarray(words), axis=0)
    axes = ("node", "gpu")
    for cfg in DELEGATE_CFGS:
        got = _shard_reduce(
            mesh, axes,
            lambda x, c=cfg: comm.delegate_allreduce_or(x, axes, c), words)
        for i in range(8):
            np.testing.assert_array_equal(np.asarray(got)[i], want), cfg
    # hier really pays two levels on (2, 4): (2-1) + (4-1) payloads vs 7
    plan_h = comm.CommPlan(comm.CommConfig(delegate="hier"), axes, (2, 4))
    plan_a = comm.CommPlan(comm.CommConfig(delegate="allgather"), axes, (2, 4))
    n = 5 * 2
    assert plan_h.delegate_bytes(n, 4) == 4 * n * 4
    assert plan_a.delegate_bytes(n, 4) == 7 * n * 4


@needs8
def test_serve_engine_hier_sharded_8dev():
    """An 8-partition graph served on the (2, 4) mesh under the
    hierarchical delegate combine + adaptive nn format, oracle-exact."""
    from repro.launch.mesh import make_test_mesh
    from repro.serve import BFSServeEngine

    mesh = make_test_mesh((2, 4), ("node", "gpu"))
    g = rmat_graph(8, seed=9)
    srcs = [int(s) for s in pick_sources(g, 6, seed=3)]
    eng = BFSServeEngine(
        g, th=32, p_rank=2, p_gpu=4,
        cfg=M.MSBFSConfig(n_queries=4, max_iters=64),
        comm=comm.CommConfig(delegate="hier", nn="adaptive"),
        cache_capacity=0, mesh=mesh, refill=True)
    assert eng.sharded
    for s, lev in zip(srcs, eng.query(srcs)):
        np.testing.assert_array_equal(lev, bfs_levels(g, s))
    assert eng.stats.wire_delegate_bytes > 0
    assert eng.stats.nn_overflow == 0
