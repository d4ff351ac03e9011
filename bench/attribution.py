"""Attribute a traced window's device time to the program's own names.

    python3 bench/attribution.py <trace.xplane.pb>

``bench/trace.py`` reduces the window to busy time, idle gaps and XLA
operations. This module names them after the program instead:

* idle: the engine's host spans (``serve.*``, ``repro.obs``) reach the
  profiler's host plane as annotations, on the device planes' clock. Each
  idle stretch of a device is given to the innermost ``serve.*`` span the
  host was in at the time (``none`` where it was in none);
* busy: the traversal step names its phases with ``jax.named_scope``
  (``msbfs.*``, ``core/msbfs.py``), which XLA keeps as each operation's
  framework-op path. The device time of the window is counted as
  ``bench/trace.py`` counts it (innermost operations only, clipped to the
  window), and each operation goes to the innermost ``msbfs.*`` scope of
  its path. The paths come from xprof's ``op_profile``, per compiled
  program (the trace's ``XLA Modules`` line says which program an
  operation ran in). A fusion whose root names no scope goes to the scope
  most of its fused operations name. ``op_profile`` lists at most 100
  operations under a node and groups identical ones under a node of its
  own; xprof's ``hlo_stats`` table lists every operation with its own
  path, and names those. xprof's own per-path times
  (``framework_op_stats``) are not used: they count a loop's time again
  beside the operations of its body.

A program without these spans and scopes (or a host without xprof)
yields ``None`` for what it cannot name, never an error. xprof caches
its result beside the file it reads, so it reads a copy in a temporary
directory and the trace's own directory is left as it was.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
from collections import Counter

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_trace", os.path.join(HERE, "trace.py"))
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)

SPANS = "serve."
GATHERS = ("serve.gather", "serve.gather.deferred")
MODULES_LINE = "XLA Modules"
SCOPE = re.compile(r"msbfs(?:\.[a-z]+)+")
NN = ("msbfs.nn.slots", "msbfs.nn.exchange")
DELEGATE = ("msbfs.dd", "msbfs.nd", "msbfs.dn", "msbfs.delegate.combine")
NONE = "none"
DUPLICATES = " and its duplicate(s)"
PROGRAM_ID = re.compile(r"\((\d+)\)$")


def read(path: str):
    """``(window, spans, devices)`` from a trace file: the window as
    ``(start_ns, end_ns)`` (None without one), the host's ``serve.*``
    spans as ``[(start_ns, end_ns, name)]``, and per device plane its
    operations as ``[(start_ns, end_ns, op_name, program)]``."""
    from jax.profiler import ProfileData

    window, spans, devices = None, [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops, programs = [], []
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, MODULES_LINE):
                    out = ops if line.name == tr.OPS_LINE else programs
                    out.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
            if ops:
                devices[plane.name] = in_programs(ops, programs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tr.WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(SPANS):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return window, spans, devices


def in_programs(ops, programs) -> list:
    """Each operation ``(start, end, text)`` as ``(start, end, op_name,
    program)``: the program is the ``XLA Modules`` event the operation
    starts in (None outside every one)."""
    programs = sorted(programs)
    out, j = [], 0
    for s, e, text in sorted(ops):
        while j < len(programs) and programs[j][1] <= s:
            j += 1
        inside = j < len(programs) and programs[j][0] <= s
        out.append((s, e, tr.op_name(text),
                    programs[j][2] if inside else None))
    return out


def innermost(spans, lo: float, hi: float) -> list:
    """``[lo, hi)`` cut into ``(start, end, name)`` pieces, each named by
    the innermost span covering it (the one entered last; ``none`` where
    none does). Spans of one thread nest, so that is the open span
    deepest in the call stack."""
    edges = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                               if lo < t < hi})
    starts = sorted((s, -e, n) for s, e, n in spans if e > lo and s < hi)
    out, open_, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            open_.append((starts[k][0], -starts[k][1], starts[k][2]))
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        name = max(open_, key=lambda sp: (sp[0], -sp[1]))[2] if open_ else NONE
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def overlap_by_name(gaps, pieces) -> dict:
    """Total overlap of sorted disjoint ``(start, end)`` gaps with sorted
    disjoint ``(start, end, name)`` pieces, per name (one pass over
    both)."""
    out, j = {}, 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, name = pieces[k]
            out[name] = out.get(name, 0.0) + min(e, g1) - max(s, g0)
            k += 1
    return out


def _inside(ops, lo: float, hi: float) -> list:
    """Operations clipped to ``[lo, hi)``, their other fields kept."""
    return [(max(o[0], lo), min(o[1], hi)) + tuple(o[2:]) for o in ops
            if o[1] > lo and o[0] < hi]


def host_split(window, spans, devices) -> dict | None:
    """Where the host was while each device idled, from :func:`read`'s
    output; None without a window or a device operation inside it.
    Idle seconds are averaged over the devices that ran anything, as
    ``bench/trace.py`` averages busy time. ``gather_idle_s`` is the idle
    time under ``serve.gather`` or ``serve.gather.deferred`` (their
    ``serve.gather.fetch`` included); None when the host never entered
    either."""
    if window is None:
        return None
    lo, hi = window
    pieces = innermost(spans, lo, hi)
    gather = [(s, e, "gather") for s, e in
              tr.union((s, e) for s, e, n in spans if n in GATHERS)]
    idle_by, gather_idle, busy, used = {}, 0.0, 0.0, 0
    for ops in devices.values():
        merged = tr.union(o[:2] for o in _inside(ops, lo, hi))
        if not merged:
            continue
        used += 1
        busy += sum(e - s for s, e in merged)
        idle = tr.gaps(merged, lo, hi)
        gather_idle += overlap_by_name(idle, gather).get("gather", 0.0)
        for name, t in overlap_by_name(idle, pieces).items():
            idle_by[name] = idle_by.get(name, 0.0) + t
    if not used:
        return None
    span_self: dict = {}
    for s, e, name in pieces:
        span_self[name] = span_self.get(name, 0.0) + e - s
    window_s = (hi - lo) * 1e-9
    busy_s = busy * 1e-9 / used
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_s": window_s - busy_s,
            "gather_idle_s": gather_idle * 1e-9 / used if gather else None,
            "idle_by_span": _seconds(idle_by, 1e-9 / used),
            "host_self_by_span": _seconds(span_self, 1e-9)}


def _seconds(d: dict, scale: float) -> dict:
    return {k: v * scale for k, v in sorted(d.items(), key=lambda kv: -kv[1])}


def scope_of(path: str) -> str:
    """The innermost ``msbfs.*`` scope named in a framework-op path."""
    found = SCOPE.findall(path)
    return found[-1] if found else NONE


def node_scope(node: dict) -> str:
    """An ``op_profile`` operation's scope: its own path's, else the one
    most of its fused operations' paths name."""
    own = scope_of(node["xla"].get("provenance") or "")
    if own != NONE:
        return own
    votes: Counter = Counter()
    stack = list(node.get("children", []))
    while stack:
        n = stack.pop()
        if n.get("xla"):
            votes[scope_of(n["xla"].get("provenance") or "")] += 1
        stack.extend(n.get("children", []))
    votes.pop(NONE, None)
    return votes.most_common(1)[0][0] if votes else NONE


def profile_scopes(profile: dict) -> dict:
    """``{program: {op_name: scope}}`` from a parsed ``op_profile``: a
    program's executed operations are the first nodes with ``xla``
    details under it (the nodes below those are fused into them), except
    a node that groups identical operations (``<op> and its
    duplicate(s)``), whose children are the operations."""
    out: dict = {}
    for prog in profile.get("byProgram", {}).get("children", []):
        ops = out.setdefault(prog["name"], {})
        stack = list(prog.get("children", []))
        while stack:
            n = stack.pop()
            if n.get("xla") and not n["name"].endswith(DUPLICATES):
                ops[n["name"]] = node_scope(n)
            else:
                stack.extend(n.get("children", []))
    return out


def stats_scopes(table: dict, programs) -> dict:
    """``{program: {op_name: scope}}`` from a parsed ``hlo_stats`` table:
    every operation of every program, by its own path. ``programs`` are
    the programs' names (``jit_f(<id>)``); the table gives only the id."""
    name_of = {m.group(1): p for p in programs
               if (m := PROGRAM_ID.search(p))}
    cols = [c["id"] for c in table.get("cols", [])]
    out: dict = {}
    for row in table.get("rows", []):
        r = dict(zip(cols, ((c or {}).get("v") for c in row["c"])))
        prog = name_of.get(str(r.get("program_id")))
        if prog is not None:
            out.setdefault(prog, {})[r["hlo_op_name"]] = scope_of(
                r.get("tf_op_name") or "")
    return out


def op_scopes(path: str) -> dict | None:
    """``{program: {op_name: scope}}`` of a copy of the trace: xprof's
    ``hlo_stats`` for every operation, :func:`profile_scopes` of its
    ``op_profile`` where that lists the operation (it also names a fusion
    whose root names no scope); None where xprof is missing or fails."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    tools = {}
    with tempfile.TemporaryDirectory() as tmp:
        copy = shutil.copy(path, tmp)
        for tool in ("op_profile", "hlo_stats"):
            try:
                data, _ = raw_to_tool_data.xspace_to_tool_data(
                    [copy], tool, {})
                tools[tool] = json.loads(data)
            except Exception as exc:  # noqa: BLE001 -- a reader never raises
                print(f"attribution: xprof {tool} failed: {exc!r}",
                      file=sys.stderr)
                return None
    profile = profile_scopes(tools["op_profile"])
    stats = stats_scopes(tools["hlo_stats"], profile)
    return {p: {**stats.get(p, {}), **ops} for p, ops in profile.items()}


def device_split(window, devices, scopes) -> dict | None:
    """Device time (s) of the window per ``msbfs.*`` scope, innermost
    operations only, averaged over the devices that ran anything; None
    without a window or the scopes, or when no operation names a scope
    (a program without them)."""
    if window is None or scopes is None:
        return None
    lo, hi = window
    out: dict = {}
    used = 0
    for ops in devices.values():
        inside = _inside(ops, lo, hi)
        if not inside:
            continue
        used += 1
        for s, e, (name, program) in tr.leaves(
                (s, e, (name, program)) for s, e, name, program in inside):
            k = scopes.get(program, {}).get(name, NONE)
            out[k] = out.get(k, 0.0) + e - s
    if not used or set(out) <= {NONE}:
        return None
    return _seconds(out, 1e-9 / used)


def attribute(path: str) -> dict:
    """Both splits of one trace file (either may be None)."""
    window, spans, devices = read(path)
    return {"host": host_split(window, spans, devices),
            "device": device_split(window, devices, op_scopes(path))}


def of(ctx: dict) -> dict | None:
    """The splits of a run's traced window, computed once per run and
    kept in the readers' shared context; None without a trace."""
    if "attribution" not in ctx:
        path = ctx.get("trace")
        try:
            ctx["attribution"] = attribute(path) if path else None
        except Exception as exc:  # noqa: BLE001 -- a reader never raises
            print(f"attribution: {exc!r}", file=sys.stderr)
            ctx["attribution"] = None
    return ctx["attribution"]


def ms_per_sweep(ctx: dict, scopes) -> float | None:
    """Device time under ``scopes`` per sweep of the window (ms)."""
    att = of(ctx)
    sweeps = ctx["stats"].get("sweeps", 0)
    if att is None or att["device"] is None or sweeps <= 0:
        return None
    return 1000.0 * sum(att["device"].get(k, 0.0) for k in scopes) / sweeps


def shares(att: dict) -> dict:
    """The share of the idle time under some ``serve.*`` span and of the
    busy time under some ``msbfs.*`` scope (None where not read)."""
    out = {}
    for key, split in (("idle_under_serve",
                        (att["host"] or {}).get("idle_by_span")),
                       ("busy_under_msbfs", att["device"])):
        out[key] = (sum(v for k, v in split.items() if k != NONE)
                    / sum(split.values())) if split else None
    return out


def main(argv=None) -> int:
    (path,) = argv if argv is not None else sys.argv[1:]
    att = attribute(path)
    print(json.dumps(att | shares(att), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
