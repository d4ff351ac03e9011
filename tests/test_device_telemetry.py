"""Device-plane telemetry: the in-jit ``tm_*`` sweep carry, its host-side
harvest, and the per-shard imbalance export.

Pinned invariants:

* telemetry-on vs telemetry-off engines produce bit-identical answers and
  bit-identical ``ServeStats`` across the batch, refill, and overlapped
  drivers (emulated mesh here; the ``@needs4`` variants repeat it on a
  real 4-device shard_map mesh);
* the disabled path carries zero-size buffers (compiled away) and
  harvests to ``None``;
* per-shard wire telemetry sums *exactly* to the global ``ServeStats``
  wire counters, and per-sweep frontier telemetry sums exactly to the
  oracle's per-level vertex counts.
"""
import jax
import numpy as np
import pytest

from repro.core import bfs as B
from repro.core import msbfs as M
from repro.core.oracle import bfs_levels
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph
from repro.launch.mesh import make_test_mesh
from repro.obs import Observability, harvest_telemetry, shard_metric, skew
from repro.serve import BFSServeEngine, Query, oracle_check

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 host devices (run under the multi-device CI job)")

@pytest.fixture(scope="module")
def graph():
    return rmat_graph(8, seed=11)


def make_engine(g, telemetry=False, obs=None, **kw):
    cfg = M.MSBFSConfig(n_queries=4, max_iters=96, telemetry=telemetry)
    return BFSServeEngine(g, th=32, p_rank=2, p_gpu=2, cfg=cfg,
                          cache_capacity=0, obs=obs, **kw)


# ------------------------------------------------- disabled path / harvest
def test_disabled_state_carries_zero_size_buffers(graph):
    pg = partition_graph(graph, th=32, p_rank=2, p_gpu=2)
    srcs = [int(s) for s in pick_sources(graph, 4, seed=1)]

    off = M.init_multi_state(pg, srcs, M.MSBFSConfig(n_queries=4))
    assert np.asarray(off.tm_frontier_n).shape == (pg.p, 0)
    assert harvest_telemetry(off) is None

    on = M.init_multi_state(
        pg, srcs, M.MSBFSConfig(n_queries=4, max_iters=64, telemetry=True))
    assert np.asarray(on.tm_frontier_n).shape == (pg.p, 64)

    boff = B.init_state(pg, srcs[0], B.BFSConfig(max_iters=48))
    assert np.asarray(boff.tm_frontier_n).shape == (pg.p, 0)
    assert harvest_telemetry(boff) is None
    bon = B.init_state(pg, srcs[0],
                       B.BFSConfig(max_iters=48, telemetry=True))
    assert np.asarray(bon.tm_frontier_n).shape == (pg.p, 48)

    # pre-telemetry states (no tm_* fields at all) harvest to None too
    class Legacy:
        pass

    assert harvest_telemetry(Legacy()) is None


def test_skew_edge_cases():
    assert skew([]) == 0.0
    assert skew([0, 0, 0]) == 0.0
    assert skew([5, 5, 5, 5]) == pytest.approx(1.0)
    assert skew([3, 1]) == pytest.approx(1.5)


# --------------------------------------------- schedule stays bit-identical
@pytest.mark.parametrize("mode", ["batch", "refill", "overlap"])
def test_telemetry_never_changes_schedule(graph, mode):
    """Answers and every ServeStats counter bit-identical telemetry-on
    (with obs attached) vs a bare engine, on every driver."""
    g = graph
    kw = {"batch": {}, "refill": {"refill": True},
          "overlap": {"refill": True, "overlap": True}}[mode]
    queries = [Query(int(s)) for s in pick_sources(g, 8, seed=3)]

    obs = Observability()
    eng_on = make_engine(g, telemetry=True, obs=obs, **kw)
    eng_off = make_engine(g, **kw)
    ans_on = eng_on.submit_many(queries)
    ans_off = eng_off.submit_many(queries)

    assert eng_on.stats.as_dict() == eng_off.stats.as_dict()
    for q, a, b in zip(queries, ans_on, ans_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        oracle_check(g, q, a)

    # the instrumented run actually harvested something
    tel = eng_on.last_telemetry
    assert tel is not None and tel.sweeps > 0
    assert eng_off.last_telemetry is None
    assert tel.p == eng_on.pg.p
    assert int(tel.shard_frontier().sum()) > 0


def test_shard_telemetry_sums_to_global_wire_counters(graph):
    """One batch traversal: the per-shard per-sweep wire split must sum
    exactly to the global ServeStats wire counters, and the harvested
    nn_sparse record to the nn_sparse_sweeps counter."""
    g = graph
    eng = make_engine(g, telemetry=True)
    queries = [Query(int(s)) for s in pick_sources(g, 4, seed=5)]
    for q, a in zip(queries, eng.submit_many(queries)):
        oracle_check(g, q, a)

    st, tel = eng.stats, eng.last_telemetry
    assert st.batches == 1 and tel is not None
    assert int(tel.wire_delegate.sum()) == st.wire_delegate_bytes
    assert int(tel.wire_nn.sum()) == st.wire_nn_bytes
    assert int(tel.shard_wire_bytes().sum()) == st.wire_bytes_total
    assert int(tel.nn_sparse.sum()) == st.nn_sparse_sweeps
    # delegate combine is symmetric across shards; nn wire is per shard
    assert tel.wire_delegate.shape == tel.wire_nn.shape == (
        eng.pg.p, eng.cfg.max_iters)


def test_bfs_frontier_telemetry_matches_oracle_levels(graph):
    """Single-source BFS: for every executed sweep t, the per-shard
    normal-frontier counts plus the (replicated) delegate-frontier count
    must equal the oracle's number of level-t vertices exactly."""
    g = graph
    pg = partition_graph(g, th=32, p_rank=2, p_gpu=2)
    src = int(pick_sources(g, 1, seed=2)[0])
    cfg = B.BFSConfig(max_iters=48, enable_do=True, telemetry=True)
    out = B.run_bfs_emulated(B.device_view(pg), B.init_state(pg, src, cfg),
                             cfg)
    levels = bfs_levels(g, src)
    np.testing.assert_array_equal(B.gather_levels(pg, out), levels)

    tel = harvest_telemetry(out)
    sweeps = int(np.asarray(out.it)[0])
    assert tel is not None and tel.sweeps == sweeps
    for t in range(sweeps):
        oracle_t = int(np.sum(levels == t))
        got = int(tel.frontier_n[:, t].sum()) + int(tel.frontier_d[0, t])
        assert got == oracle_t, (t, got, oracle_t)
    # delegate frontier content is replicated across shards
    np.testing.assert_array_equal(
        tel.frontier_d, np.broadcast_to(tel.frontier_d[:1],
                                        tel.frontier_d.shape))
    # sweeps past the executed prefix never accumulated anything
    assert int(tel.frontier_n[:, sweeps:].sum()) == 0
    # the direction record stays within the 3-bit dd/dn/nd mask
    assert tel.dir_backward.shape == tel.frontier_n.shape
    assert 0 <= int(tel.dir_backward.min()) <= int(tel.dir_backward.max()) <= 7


def test_shard_metrics_export(graph):
    """The harvested telemetry lands in the registry under the canonical
    device.* names with exact per-shard totals."""
    g = graph
    obs = Observability()
    eng = make_engine(g, telemetry=True, obs=obs)
    eng.submit_many([Query(int(s)) for s in pick_sources(g, 4, seed=7)])

    tel = eng.last_telemetry
    snap = obs.metrics.snapshot()
    ftot = tel.shard_frontier()
    wtot = tel.shard_wire_bytes()
    for i in range(tel.p):
        assert snap["gauges"][shard_metric(i, "frontier_total")] == int(ftot[i])
        assert snap["gauges"][shard_metric(i, "wire_bytes")] == int(wtot[i])
        h = snap["histograms"][shard_metric(i, "frontier_per_sweep")]
        assert h["count"] == min(tel.sweeps, tel.frontier_n.shape[1])
    assert snap["gauges"]["device.sweeps"] == tel.sweeps
    assert snap["gauges"]["device.frontier_skew"] == pytest.approx(skew(ftot))
    assert snap["gauges"]["device.wire_skew"] == pytest.approx(skew(wtot))
    assert snap["histograms"]["device.frontier_skew_dist"]["count"] == \
        eng.stats.batches


# ------------------------------------------------------- sharded (4 devices)
@needs4
@pytest.mark.parametrize("mode", ["batch", "refill"])
def test_sharded_telemetry_parity_multidevice(graph, mode):
    """Telemetry-on/off parity of answers + ServeStats on a real 4-device
    shard_map mesh, and the per-shard wire sums still land exactly on the
    global counters there."""
    g = graph
    # batch mode uses one lane-width of queries so exactly one traversal
    # runs and the harvested telemetry reconciles exactly against stats
    kw, nq = {"batch": ({"refill": False}, 4),
              "refill": ({"refill": True}, 8)}[mode]
    queries = [Query(int(s)) for s in pick_sources(g, nq, seed=9)]

    mesh_on = make_test_mesh((2, 2), ("data", "model"))
    mesh_off = make_test_mesh((2, 2), ("data", "model"))
    eng_on = make_engine(g, telemetry=True, mesh=mesh_on, **kw)
    eng_off = make_engine(g, mesh=mesh_off, **kw)
    assert eng_on.sharded and eng_off.sharded
    ans_on = eng_on.submit_many(queries)
    ans_off = eng_off.submit_many(queries)

    assert eng_on.stats.as_dict() == eng_off.stats.as_dict()
    for q, a, b in zip(queries, ans_on, ans_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        oracle_check(g, q, a)

    tel = eng_on.last_telemetry
    assert tel is not None and tel.p == eng_on.pg.p
    assert int(tel.shard_frontier().sum()) > 0
    if mode == "batch":
        st = eng_on.stats
        assert int(tel.wire_delegate.sum()) == st.wire_delegate_bytes
        assert int(tel.wire_nn.sum()) == st.wire_nn_bytes
        assert int(tel.shard_wire_bytes().sum()) == st.wire_bytes_total
