"""The serving stack on the profiler's clock: an enabled tracer mirrors
every span into a ``jax.profiler.TraceAnnotation`` (and a disabled one
opens none), the traversal step's phases carry ``msbfs.*`` named scopes,
and the engine's gathers split the device-to-host copy
(``serve.gather.fetch``) from the host's assembly, with every span of a
session carrying its ``session`` id. That obs on or off leaves answers
and ``ServeStats`` bit-identical is pinned in ``tests/test_obs.py``."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bfs as B, engine as E, msbfs as M
from repro.core.partition import partition_graph
from repro.graphs.rmat import pick_sources, rmat_graph
from repro.obs import Observability, Tracer
from repro.serve import BFSServeEngine, Query, QueryKind

SESSION_SPANS = ("serve.session.open", "serve.block.wait", "serve.boundary",
                 "serve.gather", "serve.gather.deferred",
                 "serve.gather.fetch", "serve.reseed", "serve.sweep",
                 "serve.batch")
GATHERS = ("serve.gather", "serve.gather.deferred")


class Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs every
    enter and exit with the annotation's name and arguments."""

    def __init__(self):
        self.log = []
        self.stack = []
        rec = self

        class Note:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                rec.log.append(("enter", self.name, self.kw,
                                tuple(rec.stack)))
                rec.stack.append(self.name)
                return self

            def __exit__(self, *exc):
                assert rec.stack.pop() == self.name
                rec.log.append(("exit", self.name, self.kw, ()))
                return False

        self.cls = Note

    def entered(self):
        return [(name, kw, parents) for ev, name, kw, parents in self.log
                if ev == "enter"]


@pytest.fixture
def notes(monkeypatch):
    rec = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    return rec


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(8, seed=11)


def make_engine(g, obs=None, **kw):
    cfg = M.MSBFSConfig(n_queries=4, max_iters=96)
    return BFSServeEngine(g, th=32, p_rank=2, p_gpu=2, cfg=cfg,
                          cache_capacity=0, obs=obs, **kw)


def level_queries(g, n=8, seed=3):
    return [Query(int(s)) for s in pick_sources(g, n, seed=seed)]


# ------------------------------------------------------ tracer annotations
def test_enabled_tracer_opens_one_annotation_per_span(notes):
    tr = Tracer()
    with tr.span("outer", session=7):
        with tr.span("inner", lanes=3) as sp:
            sp.set(late=1)           # after entry: the ring buffer only
        tr.instant("point")          # instants stay in the ring buffer
    assert notes.log == [
        ("enter", "outer", {"session": 7}, ()),
        ("enter", "inner", {"lanes": 3}, ("outer",)),
        ("exit", "inner", {"lanes": 3}, ()),
        ("exit", "outer", {"session": 7}, ()),
    ]
    spans = {e.name: e for e in tr.events() if e.is_span}
    assert set(spans) == {"outer", "inner"} and spans["inner"].depth == 1
    assert spans["inner"].args == {"lanes": 3, "late": 1}


def test_annotation_closes_when_the_span_raises(notes):
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert [ev[:2] for ev in notes.log] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("exit", "outer")]
    assert notes.stack == []


@pytest.mark.parametrize("where", ["tracer", "engine"])
def test_disabled_tracer_opens_no_annotation(notes, graph, where):
    if where == "tracer":
        tr = Tracer(enabled=False)
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        assert tr.events() == []
    else:
        eng = make_engine(graph, refill=True, overlap=True)
        assert all(a is not None for a in eng.submit_many(
            level_queries(graph)))
    assert notes.log == []


# ------------------------------------------------- engine spans + sessions
@pytest.mark.parametrize("mode,gather", [
    ("batch", "serve.gather"), ("refill", "serve.gather"),
    ("overlap", "serve.gather.deferred")])
def test_gather_fetch_nests_and_sessions_are_tagged(notes, graph, mode,
                                                    gather):
    kw = {"batch": {}, "refill": {"refill": True},
          "overlap": {"refill": True, "overlap": True}}[mode]
    obs = Observability()
    eng = make_engine(graph, obs=obs, **kw)
    first = level_queries(graph, seed=3)
    eng.submit_many(first)
    eng.submit_many(level_queries(graph, seed=9))
    spans = [e for e in obs.trace.events() if e.is_span]

    # ring buffer: every fetch sits inside a gather of its own session,
    # one level deeper, and is the only thing the gather nests
    fetches = [e for e in spans if e.name == "serve.gather.fetch"]
    gathers = [e for e in spans if e.name in GATHERS]
    assert fetches and len(fetches) == len(gathers)
    assert {e.name for e in gathers} == {gather}
    for f in fetches:
        (parent,) = [g for g in gathers if g.ts <= f.ts
                     and f.ts + f.dur <= g.ts + g.dur]
        assert f.depth == parent.depth + 1
        assert f.args["session"] == parent.args["session"]
        assert f.args["lanes"] == parent.args["lanes"]

    # every span of a session carries its id; the two submissions
    # (batch mode: the batches) get ids of their own
    tagged = [e for e in spans if e.name in SESSION_SPANS]
    assert tagged and all("session" in e.args for e in tagged)
    ids = sorted({e.args["session"] for e in tagged})
    want = 4 if mode == "batch" else 2      # 8 queries, 4 lanes a batch
    assert ids == list(range(1, want + 1))

    # profiler plane: the same nesting, one annotation per span
    opened = notes.entered()
    assert len(opened) == len(spans)
    for name, args, parents in opened:
        if name == "serve.gather.fetch":
            assert parents[-1] == gather
        if name in SESSION_SPANS:
            assert "session" in args


def test_spans_land_on_the_profiler_host_plane(graph, tmp_path):
    """A real capture on the CPU: the engine's spans are host-plane events
    of the same names, with their arguments, and fetches nest in
    gathers on the capture's clock."""
    from jax.profiler import ProfileData

    obs = Observability()
    eng = make_engine(graph, obs=obs, refill=True, overlap=True)
    eng.warmup()
    obs.trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit_many(level_queries(graph))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats)))
    ring = {}
    for e in obs.trace.events():
        if e.is_span:
            ring[e.name] = ring.get(e.name, 0) + 1
    assert {k: len(v) for k, v in host.items()} == ring
    for s, e, stats in host["serve.gather.fetch"]:
        assert any(gs <= s and e <= ge and gst["session"] == stats["session"]
                   for gs, ge, gst in host["serve.gather.deferred"])
    assert all("session" in st for _, _, st in host["serve.boundary"])


# ---------------------------------------------------------- device scopes
SCOPES = ["msbfs.direction", "msbfs.dd", "msbfs.nd", "msbfs.dn",
          "msbfs.nn.slots", "msbfs.nn.exchange", "msbfs.delegate.combine",
          "msbfs.payload", "msbfs.update", "msbfs.block", "msbfs.reseed"]


@pytest.fixture(scope="module")
def lowered(graph):
    """Lowered text, debug locations included, of the emulated fused block
    (payload plane on, so every phase is traced) and of the lane reseed,
    on a partition of the test graph that has delegates."""
    pg = partition_graph(graph, th=16, p_rank=2, p_gpu=1)
    assert pg.d > 0
    cfg = M.MSBFSConfig(n_queries=4, max_iters=32, payload=True,
                        edge_chunk=256)
    st = M.init_multi_state(pg, [1, 2], cfg)
    block = M.make_msbfs_block_emulated(cfg, 4)
    text = block.lower(B.device_view(pg), E.build_exchange_plan(pg), st,
                       jnp.ones((4,), bool)).as_text(debug_info=True)
    lanes = [jnp.asarray(np.zeros(4, dt))
             for dt in (bool, np.int32, np.int32, np.int32, bool)]
    text += M.reseed_lanes.lower(st, *lanes).as_text(debug_info=True)
    return text


@pytest.mark.parametrize("scope", SCOPES)
def test_msbfs_scope_in_lowered_block(lowered, scope):
    pat = re.compile(r"[/(]" + re.escape(scope) + r"[)/]")
    hits = [loc for loc in set(re.findall(r'loc\("([^"]*)"', lowered))
            if pat.search(loc)]
    assert hits, scope
    # a scope names ops of its own phase, never wraps another scope
    assert not any(re.search(r"[/(]msbfs\.[a-z.]+[)/].*" + re.escape(scope),
                             loc) for loc in hits)


def test_nn_slots_scope_holds_no_scatter_or_sort(lowered):
    """The nn slot words are a segmented OR over the plan's slot-sorted
    runs: ``msbfs.nn.slots`` names the word gathers, the popcount and the
    ORs, and no scatter or sort."""
    prims = set(re.findall(r"[/(]msbfs\.nn\.slots\)?/([\w-]+)", lowered))
    assert {"gather", "population_count", "or"} <= prims, prims
    assert not [op for op in prims if "scatter" in op or "sort" in op]


def test_block_module_name_keeps_scoped_builds_apart(lowered):
    """The persistent compilation cache's key leaves op metadata out, so
    only the module's name keeps an executable cached from a build of the
    block without the scopes from being loaded in its place."""
    assert re.findall(r"module @(\w+)", lowered) == [
        "jit_msbfs_block", "jit__reseed_lanes_impl"]
