"""Reduce a ``jax.profiler`` trace of the measured window to device metrics.

The run wraps its window in a ``bench.window`` annotation and each of its
calls into the program in ``bench.submit``, ``bench.poll``,
``bench.generator`` or ``bench.drain``; all of them land on the host plane
of the trace, on the same clock as the device planes. From the trace file
(``*.xplane.pb``, read with ``jax.profiler.ProfileData``) this module
takes, inside the window:

* busy time: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), averaged over the devices that ran any;
* the idle gaps: the window less that union, each named by the harness
  annotation that covers most of it (``idle`` where none does);
* the operations that took the most device time, by their XLA names
  (innermost operations only: a loop's own event holds its body's).
"""
from __future__ import annotations

import glob
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(log_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi)`` that no merged interval in ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a0: float, a1: float, spans) -> float:
    return sum(max(0.0, min(a1, e) - max(a0, s)) for s, e in spans)


def leaves(ops) -> list:
    """The operations that hold no other: a loop's event spans the ops of
    its body on the same line, and counting both would count twice."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    parent = [False] * len(ops)
    stack: list = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] < max(e, s + 1):
            stack.pop()             # ended, or only overlaps: no parent
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def op_name(text: str) -> str:
    """An operation's XLA name (``fusion.156``) from the trace's event name,
    which may hold the whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(path: str):
    """``(host_spans, device_ops)`` from a trace file: host spans as
    ``{name: [(start_ns, end_ns), ...]}`` for the harness annotations, and
    per device plane a list of ``(start_ns, end_ns, op_name)``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    host: dict = {}
    devices: dict = {}
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                op_name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return host, devices


def reduce(host: dict, devices: dict) -> dict | None:
    """Window metrics from :func:`read`'s output, or None when the trace
    holds no window or no device operation inside it."""
    if not host.get(WINDOW):
        return None
    lo, hi = host[WINDOW][0]
    window_ns = hi - lo
    notes = {k: v for k, v in host.items() if k != WINDOW}
    busy_ns, per_op, idle = [], {}, []
    for ops in devices.values():
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                  if e > lo and s < hi]
        if not inside:
            continue
        merged = union((s, e) for s, e, _ in inside)
        busy_ns.append(sum(e - s for s, e in merged))
        for s, e, name in leaves(inside):
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        idle += gaps(merged, lo, hi)
    if not busy_ns:
        return None
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    window_s = window_ns * 1e-9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    longest = []
    for g0, g1 in sorted(idle, key=lambda g: g[0] - g[1])[:TOP]:
        cover = {k: overlap(g0, g1, v) for k, v in notes.items()}
        best = max(cover, key=cover.get) if cover else None
        longest.append([best if best and cover[best] > 0 else "idle",
                        (g1 - g0) * 1e-9])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": len(busy_ns),
        "device_ops": [[k, v * 1e-9 / len(busy_ns)] for k, v in top],
        "idle_gaps": longest,
    }


def reduce_file(path: str) -> dict | None:
    return reduce(*read(path))
