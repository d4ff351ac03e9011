"""``bench/attribution.py``: idle time named by the engine's host spans
and device time by the traversal step's scopes, on two traces recorded on
a TPU v5e chip -- one of a program without the spans and scopes (the
gap-urand cell cut to scale 12), one with them (the graph500 key sets cut
to scale 12) -- and on hand-made intervals.

    python -m pytest bench/tests
"""
import hashlib
import os

import pytest

import attribution as at
import run

tr = run.tr
TESTDATA = os.path.join(run.HERE, "testdata")
PLAIN = os.path.join(TESTDATA, "keysets-scale12.xplane.pb")
SCOPED = os.path.join(TESTDATA, "keysets-graph500-scale12-scoped.xplane.pb")
S = 1_000_000_000     # ns
READERS = ("gather_idle_share.bfs", "nn_ms_per_sweep.bfs")


def snapshot(path):
    out = {}
    for name in sorted(os.listdir(path)):
        with open(os.path.join(path, name), "rb") as f:
            out[name] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def untouched():
    """xprof caches its result beside the file it reads: the readers must
    leave the recorded traces' directory as it was."""
    before = snapshot(TESTDATA)
    yield
    assert snapshot(TESTDATA) == before


def test_trace_without_spans_or_scopes_reads_nothing(untouched):
    window, spans, devices = at.read(PLAIN)
    scopes = at.op_scopes(PLAIN)
    assert spans == [] and list(devices) == ["/device:TPU:0"]
    # every operation ran in a program that xprof profiled
    assert {p for *_, p in devices["/device:TPU:0"]} <= set(scopes)
    host = at.host_split(window, spans, devices)
    red = tr.reduce_file(PLAIN)
    assert host["window_s"] == pytest.approx(red["window_s"])
    assert host["busy_s"] == pytest.approx(red["busy_s"])
    assert host["gather_idle_s"] is None
    assert host["idle_by_span"] == {at.NONE: pytest.approx(host["idle_s"])}
    assert scopes and all(s == at.NONE for ops in scopes.values()
                          for s in ops.values())
    assert at.device_split(window, devices, scopes) is None
    ctx = {"trace": PLAIN, "stats": {"sweeps": 5}}
    for name in READERS:
        assert run.reader(name)(ctx) is None, name


def test_scoped_trace_names_idle_and_busy_time(untouched):
    att = at.attribute(SCOPED)
    host, dev = att["host"], att["device"]
    red = tr.reduce_file(SCOPED)
    assert host["busy_s"] == pytest.approx(red["busy_s"])
    # innermost operations, as bench/trace.py counts them: the split adds
    # up to the device time, never more than the busy union
    assert sum(dev.values()) <= red["busy_s"] + 1e-9
    assert sum(dev.values()) == pytest.approx(red["busy_s"], rel=0.02)
    share = at.shares(att)
    assert share["busy_under_msbfs"] > 0.9 and share["idle_under_serve"] > 0.9
    # Kronecker degrees: the delegate subgraphs take most of the sweep
    assert dev["msbfs.nd"] > dev["msbfs.nn.slots"] > 0
    assert 0 < host["gather_idle_s"] < host["idle_s"]
    assert host["idle_by_span"]["serve.gather.fetch"] > 0
    sweeps = 31
    ctx = {"trace": SCOPED, "stats": {"sweeps": sweeps}}
    nn = run.reader("nn_ms_per_sweep.bfs")(ctx)
    assert nn == pytest.approx(1000.0 * (dev["msbfs.nn.slots"]
                                         + dev["msbfs.nn.exchange"]) / sweeps)
    assert run.reader("gather_idle_share.bfs")(ctx) == pytest.approx(
        100.0 * host["gather_idle_s"] / host["window_s"])


def test_idle_gaps_go_to_the_innermost_span():
    window = (0, 10 * S)
    spans = [(1 * S, 5 * S, "serve.boundary"),
             (int(1.5 * S), int(4.5 * S), "serve.gather"),
             (2 * S, 3 * S, "serve.gather.fetch"),
             (6 * S, 8 * S, "serve.gather.deferred"),
             (6 * S, 7 * S, "serve.gather.fetch"),
             (8 * S, int(9.5 * S), "serve.block.wait")]
    ops = [(0, int(2.5 * S), "fusion.1", "p"),
           (int(3.5 * S), int(6.5 * S), "fusion.2", "p"),
           (9 * S, 10 * S, "fusion.1", "p")]
    host = at.host_split(window, spans, {"/device:TPU:0": ops})
    assert host["window_s"] == pytest.approx(10.0)
    assert host["busy_s"] == pytest.approx(6.5)
    assert host["idle_s"] == pytest.approx(3.5)
    # idle 2.5-3.5 s: the fetch, then the gather's own assembly; 6.5-9 s:
    # the deferred gather's fetch, its unpack, then the block wait
    assert host["idle_by_span"] == {
        "serve.gather.deferred": pytest.approx(1.0),
        "serve.block.wait": pytest.approx(1.0),
        "serve.gather.fetch": pytest.approx(1.0),
        "serve.gather": pytest.approx(0.5)}
    assert host["gather_idle_s"] == pytest.approx(2.5)
    ctx = {"stats": {}, "attribution": {"host": host, "device": None}}
    assert run.reader("gather_idle_share.bfs")(ctx) == pytest.approx(25.0)
    assert at.shares(ctx["attribution"]) == {
        "idle_under_serve": pytest.approx(1.0), "busy_under_msbfs": None}
    # the host's own time, innermost span first
    assert host["host_self_by_span"] == {
        at.NONE: pytest.approx(2.5),
        "serve.gather.fetch": pytest.approx(2.0),
        "serve.gather": pytest.approx(2.0),
        "serve.block.wait": pytest.approx(1.5),
        "serve.boundary": pytest.approx(1.0),
        "serve.gather.deferred": pytest.approx(1.0)}


def test_idle_is_averaged_over_devices_and_none_outside_spans():
    window = (0, 4 * S)
    spans = [(0, 1 * S, "serve.gather")]
    devices = {"/device:TPU:0": [(1 * S, 4 * S, "a", "p")],
               "/device:TPU:1": [(0, 2 * S, "a", "p")],
               "/device:TPU:2": []}
    host = at.host_split(window, spans, devices)
    # chip 0 idles 0-1 s under the gather; chip 1 idles 2-4 s under none
    assert host["busy_s"] == pytest.approx(2.5)
    assert host["idle_by_span"] == {at.NONE: pytest.approx(1.0),
                                    "serve.gather": pytest.approx(0.5)}
    assert host["gather_idle_s"] == pytest.approx(0.5)
    assert at.host_split(None, spans, devices) is None
    assert at.host_split(window, spans, {"/device:TPU:0": []}) is None


def test_operations_go_to_the_program_they_ran_in():
    programs = [(0, 10, "jit_a(1)"), (20, 30, "jit_b(2)")]
    ops = [(21, 22, "%fusion.3 = s32[4] fusion(x)"), (1, 2, "%copy.1 = x"),
           (12, 13, "%copy.2 = y")]
    assert at.in_programs(ops, programs) == [
        (1, 2, "copy.1", "jit_a(1)"), (12, 13, "copy.2", None),
        (21, 22, "fusion.3", "jit_b(2)")]


def test_profile_scopes_take_the_root_else_the_fused_majority():
    def op(name, prov, *children):
        return {"name": name, "xla": {"provenance": prov},
                "children": list(children)}

    profile = {"byProgram": {"name": "by_program", "children": [
        {"name": "jit_msbfs_block(7)", "children": [
            {"name": "custom fusion", "children": [
                op("fusion.1", "jit(msbfs_block)/while/body/"
                   "vmap(msbfs.nn.slots)/while/body/gather:"),
                op("fusion.2", "jit(msbfs_block)/while:",
                   op("fusion.3", ":"),
                   op("fusion.4", ".../vmap(msbfs.dd)/gather:",
                      op("gather.5", ".../vmap(msbfs.dd)/gather:")),
                   op("scatter.6", ".../vmap(msbfs.update)/select:")),
                op("copy.7", ":")]}]},
        {"name": "jit__reseed_lanes_impl(9)", "children": [
            {"name": "scatter", "children": [
                op("scatter.1", "jit(_reseed_lanes_impl)/msbfs.reseed/"
                   "scatter:")]}]}]}}
    assert at.profile_scopes(profile) == {
        "jit_msbfs_block(7)": {"fusion.1": "msbfs.nn.slots",
                               "fusion.2": "msbfs.dd",
                               "copy.7": at.NONE},
        "jit__reseed_lanes_impl(9)": {"scatter.1": "msbfs.reseed"}}


def test_device_time_goes_to_the_scope_of_innermost_operations():
    scopes = {"blk": {"while.1": at.NONE, "fusion.1": "msbfs.nn.slots",
                      "fusion.2": "msbfs.dd"},
              "rsd": {"fusion.1": "msbfs.reseed"}}
    ops = [(0, 6 * S, "while.1", "blk"),        # a loop: only its body
           (0, 3 * S, "fusion.1", "blk"), (3 * S, 5 * S, "fusion.2", "blk"),
           (7 * S, 8 * S, "fusion.1", "rsd"), (8 * S, 9 * S, "copy.9", None),
           (9 * S, 12 * S, "fusion.1", "blk")]   # clipped to the window
    dev = at.device_split((0, 10 * S), {"/device:TPU:0": ops}, scopes)
    assert dev == {"msbfs.nn.slots": pytest.approx(4.0),
                   "msbfs.dd": pytest.approx(2.0),
                   at.NONE: pytest.approx(1.0),   # outside every program
                   "msbfs.reseed": pytest.approx(1.0)}
    ctx = {"stats": {"sweeps": 4},
           "attribution": {"host": None, "device": dev}}
    assert run.reader("nn_ms_per_sweep.bfs")(ctx) == pytest.approx(1000.0)
    assert run.reader("gather_idle_share.bfs")(ctx) is None
    ctx["stats"] = {"sweeps": 0}
    assert run.reader("nn_ms_per_sweep.bfs")(ctx) is None
    assert at.device_split((0, 10 * S), {"/device:TPU:0": ops}, None) is None
    assert at.device_split((0, 10 * S), {"/device:TPU:0": ops[4:5]},
                           scopes) is None
