"""Device-plane telemetry: the in-jit ``tm_*`` sweep carry, its host-side
harvest, the per-shard imbalance export, and the sampled dispatch
profiler.

Pinned invariants:

* telemetry-on vs telemetry-off engines produce bit-identical answers and
  bit-identical ``ServeStats`` across the batch, refill, and overlapped
  drivers (emulated mesh here; the ``@needs4`` variants repeat it on a
  real 4-device shard_map mesh);
* the disabled path carries zero-size buffers (compiled away) and
  harvests to ``None``;
* per-shard wire telemetry sums *exactly* to the global ``ServeStats``
  wire counters, and per-sweep frontier telemetry sums exactly to the
  oracle's per-level vertex counts;
* profiler sampling is deterministic (counter-based, no RNG) so sample
  counts are pinnable, and profiling never changes answers or stats;
* ``scripts/profile_sweep.py`` emits a schema-valid ``repro-bench/1``
  calibration artifact that the bench gate accepts.
"""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.core import bfs as B
from repro.core import msbfs as M
from repro.core.oracle import bfs_levels
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph
from repro.launch.mesh import make_test_mesh
from repro.obs import (NULL_PROFILER, DispatchProfiler, Observability,
                       as_profiler, harvest_telemetry, shard_metric, skew)
from repro.serve import BFSServeEngine, Query, oracle_check

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 host devices (run under the multi-device CI job)")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    (with obs + profiler attached) vs a bare engine, on every driver."""
    g = graph
    kw = {"batch": {}, "refill": {"refill": True},
          "overlap": {"refill": True, "overlap": True}}[mode]
    queries = [Query(int(s)) for s in pick_sources(g, 8, seed=3)]

    obs = Observability()
    eng_on = make_engine(g, telemetry=True, obs=obs, profile=True, **kw)
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
    # and the profiler sampled real dispatches
    assert eng_on.profiler.sampled == eng_on.profiler.dispatches > 0


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


# ---------------------------------------------------------------- profiler
def test_profiler_deterministic_sampling():
    clock = iter(float(i) for i in range(1000))
    prof = DispatchProfiler(sample_rate=0.5, clock=lambda: next(clock))
    assert prof.sample_every == 2
    for _ in range(5):
        assert prof.timed("x", lambda: 42) == 42
    # first dispatch sampled, then every 2nd: calls 1, 3, 5
    assert prof.dispatches == 5 and prof.sampled == 3
    s = prof.summary()
    assert s["sample_rate"] == 0.5
    assert s["dispatch_latency_s"]["x"]["count"] == 3
    # a second name gets its own counter (its first call is sampled)
    prof.timed("y", lambda: None)
    assert prof.sampled == 4

    full = DispatchProfiler(sample_rate=1.0, clock=lambda: next(clock))
    for _ in range(4):
        full.timed("z", lambda: 0)
    assert full.sampled == full.dispatches == 4


def test_profiler_mirrors_into_obs():
    clock = iter(float(i) for i in range(1000))
    obs = Observability()
    prof = DispatchProfiler(sample_rate=1.0, obs=obs,
                            clock=lambda: next(clock))
    prof.timed("batch", lambda a: a + 1, 1)
    snap = obs.metrics.snapshot()
    assert snap["histograms"]["profile.dispatch_s.batch"]["count"] == 1
    assert snap["counters"]["profile.samples"] == 1
    # bind_obs only fills an empty slot
    other = Observability()
    prof.bind_obs(other)
    assert prof.obs is obs


def test_as_profiler_coercions():
    assert as_profiler(None) is NULL_PROFILER
    assert as_profiler(False) is NULL_PROFILER
    assert as_profiler(NULL_PROFILER) is NULL_PROFILER
    p = as_profiler(True)
    assert isinstance(p, DispatchProfiler) and p.sample_every == 1
    assert as_profiler(0.25).sample_every == 4
    inst = DispatchProfiler(sample_rate=0.5)
    assert as_profiler(inst) is inst
    with pytest.raises(TypeError):
        as_profiler("always")
    with pytest.raises(ValueError):
        DispatchProfiler(sample_rate=0.0)
    with pytest.raises(ValueError):
        DispatchProfiler(sample_rate=1.5)
    # null profiler surface is inert
    assert NULL_PROFILER.timed("x", lambda: 7) == 7
    assert NULL_PROFILER.summary() == {}
    assert NULL_PROFILER.start_trace() is False
    with NULL_PROFILER.trace_session():
        pass


def test_trace_session_without_dir_is_noop():
    prof = DispatchProfiler(sample_rate=1.0)
    assert prof.start_trace() is False
    with prof.trace_session():
        pass
    assert prof._tracing is False


@pytest.mark.parametrize("stage", ["start", "stop"])
def test_trace_capture_failure_propagates(tmp_path, monkeypatch, stage):
    """A capture that was asked for (``trace_dir`` set) and fails raises out
    of the traced window instead of leaving a clean run with no trace."""
    def boom(*a, **k):
        raise RuntimeError(f"profiler {stage} failed")

    if stage == "start":
        monkeypatch.setattr(jax.profiler, "start_trace", boom)
    else:
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    prof = DispatchProfiler(sample_rate=1.0, trace_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match=f"profiler {stage} failed"):
        with prof.trace_session():
            pass
    assert prof._tracing is False


# ------------------------------------------------- calibration artifact
def _load_profile_sweep():
    path = os.path.join(_REPO, "scripts", "profile_sweep.py")
    spec = importlib.util.spec_from_file_location("profile_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_profile_sweep_calibration_artifact(tmp_path):
    """A tiny 1-cell matrix run emits a schema-valid repro-bench/1
    device_calibration artifact the bench gate accepts."""
    from benchmarks.common import BENCH_SCHEMA, load_bench
    from benchmarks.gate import gate_files

    ps = _load_profile_sweep()
    out = str(tmp_path / "CALIB_device.json")
    payload = ps.run_matrix(
        scale=7, requests=6, n_queries=4, max_iters=64,
        delegates=("auto",), nn_formats=("dense",), sweep_blocks=(4,),
        out=out)

    doc = load_bench(out)
    assert doc["schema"] == BENCH_SCHEMA
    sec = doc["benchmarks"]["device_calibration"]
    assert sec["graph"]["scale"] == 7 and sec["graph"]["p"] == 4
    (key,) = sec["cells"].keys()
    assert key == "delegate=auto,nn=dense,block=4"
    cell = sec["cells"][key]
    for exact in ("sweeps", "wire_delegate_bytes", "wire_nn_bytes",
                  "nn_sparse_sweeps", "frontier_skew", "wire_skew"):
        assert exact in cell, exact
    assert cell["sweeps"] > 0 and cell["wire_delegate_bytes"] > 0
    prof = cell["profile"]
    assert prof["sampled"] > 0
    assert "block" in prof["dispatch_latency_s"]
    assert payload["cells"][key]["sweeps"] == cell["sweeps"]

    # the gate parses + self-diffs the artifact clean
    rep = gate_files([out], [out])
    assert rep["status"] == "pass"
    assert all(f["status"] == "ok"
               for r in rep["reports"] for f in r["findings"])
