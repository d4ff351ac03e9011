"""The nn slot words: the scatter-free segmented OR against a scatter-OR
reference, and the exchange plan's run fields it reads.

``_nn_slots_multi`` builds each unique (owner, local) slot's lane word by
a segmented OR of packed uint32 words over the plan's slot-sorted edge
runs. The reference below is the scatter-OR it replaced (bool rows
gathered per edge, ``.at[seg].max`` into the slot table, monolithic or
streamed in ``edge_chunk`` blocks); both must give the same ``sa`` and
``act_sum`` on every partition, for every frontier.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import engine as E, msbfs as M
from repro.core.partition import partition_graph
from repro.core.types import COOGraph
from repro.graphs.rmat import rmat_graph


def _scatter_reference(csr, frontier_rows, plan, edge_chunk):
    """The scatter-OR slot build: ``(sa [cap_total, W] bool, act_sum)``."""
    w = frontier_rows.shape[-1]
    f_ext = jnp.concatenate(
        [frontier_rows, jnp.zeros((1, w), frontier_rows.dtype)])
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        act = f_ext[csr.rowids]
        sa = jnp.zeros((plan.cap_total + 1, w), jnp.bool_).at[
            plan.seg_ids].max(act[plan.perm])[: plan.cap_total]
        return sa, jnp.sum(act.astype(jnp.int32))
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids[plan.perm], (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    seg = jnp.pad(plan.seg_ids, (0, pad),
                  constant_values=plan.cap_total).reshape(nblk, edge_chunk)

    def body(carry, blk):
        sa, tot = carry
        r, s = blk
        act = f_ext[r]
        return (sa.at[s].max(act), tot + jnp.sum(act.astype(jnp.int32))), None

    (sa, tot), _ = lax.scan(
        body,
        (jnp.zeros((plan.cap_total + 1, w), jnp.bool_), jnp.int32(0)),
        (rid, seg))
    return sa[: plan.cap_total], tot


_reference = jax.jit(_scatter_reference, static_argnums=(3,))
_slots = jax.jit(M._nn_slots_multi)


def _urand(scale, edge_factor, seed):
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    return COOGraph(n, rng.integers(0, n, m), rng.integers(0, n, m)) \
        .without_self_loops().deduped().symmetrized()


def _directed_hub(seed):
    """Directed: vertex 0 keeps out-degree 1 (a normal vertex) but takes
    200 in-edges, so one slot's run is 200 edges long (8 doubling steps)."""
    rng = np.random.default_rng(seed)
    n = 512
    src = np.concatenate([np.arange(1, 201), rng.integers(0, n, 1500), [0]])
    dst = np.concatenate([np.zeros(200, np.int64), rng.integers(0, n, 1500),
                          [7]])
    return COOGraph(n, src, dst).without_self_loops().deduped()


def _one_sided(seed):
    """Directed, p = 2: only even (partition 0) vertices have out-edges,
    so partition 1 has no nn edges at all."""
    rng = np.random.default_rng(seed)
    n = 256
    src = 2 * rng.integers(0, n // 2, 1200)
    dst = rng.integers(0, n, 1200)
    return COOGraph(n, src, dst).without_self_loops().deduped()


GRAPHS = {
    "urand-p1": lambda: partition_graph(_urand(9, 8, 1), th=64),
    "rmat-p2": lambda: partition_graph(rmat_graph(9, seed=2), th=16,
                                       p_rank=2),
    "rmat-p4": lambda: partition_graph(rmat_graph(9, seed=3), th=16,
                                       p_rank=2, p_gpu=2),
    "directed-hub": lambda: partition_graph(_directed_hub(4), th=64),
    "empty-partition": lambda: partition_graph(_one_sided(5), th=64,
                                               p_rank=2),
}
_BUILT: dict = {}


def _graph(name):
    if name not in _BUILT:
        pg = GRAPHS[name]()
        _BUILT[name] = (pg, E.build_exchange_plan(pg))
    return _BUILT[name]


def _part(tree, k):
    return jax.tree.map(lambda x: np.asarray(x)[k], tree)


def _frontier(kind, n_rows, w, seed):
    if kind == "none":
        return np.zeros((n_rows, w), bool)
    if kind == "all":
        return np.ones((n_rows, w), bool)
    return np.random.default_rng(seed).random((n_rows, w)) < 0.3


def test_graphs_cover_the_cases():
    """The graphs above are what their names say."""
    assert _graph("rmat-p2")[0].d > 0 and _graph("rmat-p4")[0].d > 0
    assert _graph("rmat-p4")[0].p == 4
    pg, plan = _graph("directed-hub")
    assert plan.max_run > pg.th and M.nn_scan_steps(plan.max_run) > 6
    m = np.asarray(_graph("empty-partition")[0].nn.m)
    assert m[0] > 0 and m[1] == 0


@pytest.mark.parametrize("edge_chunk", [0, 1])
@pytest.mark.parametrize("frontier", ["random", "none", "all"])
@pytest.mark.parametrize("w", [32, 64])
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_segmented_or_matches_scatter_reference(graph, w, frontier,
                                                edge_chunk):
    pg, plan = _graph(graph)
    for k in range(pg.p):
        csr, plan_k = _part(pg.nn, k), _part(plan, k)
        front = jnp.asarray(_frontier(frontier, pg.n_local, w, seed=10 + k))
        sa, act = _slots(front, plan_k)
        sa_ref, act_ref = _reference(csr, front, plan_k, edge_chunk)
        assert sa.shape == (plan.cap_total, w) and sa.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sa_ref))
        assert int(act) == int(act_ref)


@pytest.mark.parametrize("w", [32, 64])
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_blocked_gathers_match_scatter_reference(graph, w, monkeypatch):
    """The word and run-end gathers issued in blocks of 7 indices (every
    tail block padded) give the same slot words."""
    monkeypatch.setattr(M, "GATHER_BLOCK", 7)
    slots = jax.jit(M._nn_slots_multi)
    pg, plan = _graph(graph)
    for k in range(pg.p):
        csr, plan_k = _part(pg.nn, k), _part(plan, k)
        front = jnp.asarray(_frontier("random", pg.n_local, w, seed=20 + k))
        sa, act = slots(front, plan_k)
        sa_ref, act_ref = _reference(csr, front, plan_k, 0)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sa_ref))
        assert int(act) == int(act_ref)


@pytest.mark.parametrize("graph", list(GRAPHS))
def test_plan_run_fields(graph):
    """``seg_end`` names each run's last permuted edge, ``src_rows`` the
    sorted edges' source rows, ``max_run`` the longest run; slots past a
    partition's unique count read a zero word."""
    pg, plan = _graph(graph)
    e_max, n_rows = pg.nn.e_max, pg.nn.n_rows
    rowids = np.asarray(pg.nn.rowids)
    longest = 1
    for k in range(pg.p):
        mk = int(np.asarray(pg.nn.m)[k])
        seg = np.asarray(plan.seg_ids)[k, :mk]
        perm = np.asarray(plan.perm)[k, :mk]
        n_seg = int(seg[-1]) + 1 if mk else 0
        assert np.all(np.diff(seg) >= 0)
        np.testing.assert_array_equal(np.asarray(plan.src_rows)[k, :mk],
                                      rowids[k][perm])
        assert np.all(np.asarray(plan.src_rows)[k, mk:] == n_rows)
        ends = np.asarray(plan.seg_end)[k]
        want = np.array([np.flatnonzero(seg == s)[-1] for s in range(n_seg)],
                        np.int32)
        np.testing.assert_array_equal(ends[:n_seg], want)
        assert np.all(ends[n_seg:] == e_max)
        if mk:
            longest = max(longest, int(np.bincount(seg).max()))
        sa, _ = _slots(jnp.ones((pg.n_local, 32), bool), _part(plan, k))
        assert not np.asarray(sa)[n_seg:].any()
        assert np.asarray(sa)[:n_seg].all()
    assert plan.max_run == longest


def test_nn_scan_steps():
    assert [M.nn_scan_steps(r) for r in (1, 2, 3, 4, 5, 63, 64, 65)] == \
        [0, 1, 2, 2, 3, 6, 6, 7]


def test_engine_records_scan_gauges():
    """The serve engine records the plan's longest run and the doubling
    steps once, at construction, when observability is on."""
    from repro.obs import Observability
    from repro.serve import BFSServeEngine
    pg, _ = _graph("directed-hub")
    obs = Observability()
    eng = BFSServeEngine(pg=pg, obs=obs, cache_capacity=0)
    gauges = obs.metrics.snapshot()["gauges"]
    assert gauges["msbfs.nn.max_run"] == eng.plan.max_run > pg.th
    assert gauges["msbfs.nn.scan_steps"] == M.nn_scan_steps(eng.plan.max_run)


def test_synth_plan_builds_and_lowers():
    """The dry-run cells' stand-in plan carries the run fields and the
    segmented OR lowers on it, with no scatter or sort in the program."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.synth import synth_partitioned_graph
    mesh = make_test_mesh((1,), ("data",))
    pg, plan, _ = synth_partitioned_graph(4096, 4096 * 32, 1, mesh, ("data",))
    assert plan.src_rows.shape == plan.perm.shape
    assert plan.seg_end.shape == (1, plan.cap_total)
    assert M.nn_scan_steps(plan.max_run) == 6
    front = jax.ShapeDtypeStruct((1, pg.n_local, 32), jnp.bool_)
    text = jax.jit(jax.vmap(M._nn_slots_multi)).lower(front, plan).as_text()
    assert not re.search(r"stablehlo\.(scatter|sort)\b", text)
