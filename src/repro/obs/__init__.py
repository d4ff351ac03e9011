"""Serving observability plane: structured tracing + metrics.

One :class:`Observability` object bundles the two sensors every serving
component shares:

* ``obs.trace`` -- a ring-buffered structured :class:`~repro.obs.trace.Tracer`
  (spans for sweep blocks, host boundaries, reseeds, gathers; instants
  for cache/component/dedup resolutions) exporting Chrome/Perfetto
  ``trace_event`` JSON.
* ``obs.metrics`` -- a :class:`~repro.obs.metrics.MetricsRegistry` of
  counters, gauges, and fixed-bucket histograms (per-kind submit->deliver
  latency, sweep duration, wire bytes, lane utilization) with
  deterministic p50/p95/p99 summaries.

Pass one to the engine -- ``BFSServeEngine(..., obs=Observability())`` --
and every pipeline stage becomes a span and every ``ServeStats`` counter
a metric. The engine's traversal *schedule is bit-identical* with
observability on or off (the tracer never touches device state; pinned in
``tests/test_obs.py``), and the default :data:`NULL_OBS` is free: disabled
tracer + disabled registry, both handing out shared no-op objects.

Both clocks are injectable (``Observability(clock=...)``) so tests drive
deterministic timestamps -- the same pattern as ``serve/cache.py``.

While a ``jax.profiler`` capture runs, every span of an enabled tracer
also lands on the profiler's host plane, on the device planes' clock
(``obs/trace.py``); the traversal step names its phases with
``jax.named_scope`` (``msbfs.*``, ``core/msbfs.py``), so device time in
the same capture is attributed to them. One device-plane companion lives
alongside the host-plane pair: ``obs/device.py`` harvests the in-jit
sweep telemetry carry into ``device.shard.<i>.*`` imbalance metrics.

See ``README.md`` in this package for the event taxonomy, exporter usage,
and how to open a trace in Perfetto.
"""
from __future__ import annotations

import time

from .device import (SweepTelemetry, export_shard_metrics, harvest_telemetry,
                     skew)
from .metrics import (BYTES_BUCKETS, LATENCY_BUCKETS, NULL_INSTRUMENT,
                      RATIO_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry, exp_buckets, sanitize_label,
                      shard_metric, tenant_metric)
from .trace import NULL_SPAN, TraceEvent, Tracer


class Observability:
    """The tracer + metrics pair threaded through the serving stack.

    ``enabled=False`` (what :data:`NULL_OBS` is) builds disabled members:
    every ``span``/``instant``/``counter``/``histogram`` call degenerates
    to a shared no-op, so unconditionally-instrumented code costs nothing.
    """

    def __init__(self, *, enabled: bool = True, trace_capacity: int = 65536,
                 clock=time.perf_counter):
        self.enabled = bool(enabled)
        self.clock = clock
        self.trace = Tracer(capacity=trace_capacity, clock=clock,
                            enabled=self.enabled)
        self.metrics = MetricsRegistry(enabled=self.enabled)

    def export(self, trace_path: str | None = None,
               metrics_path: str | None = None) -> None:
        """Write the Perfetto trace and/or the metrics snapshot JSON."""
        if trace_path is not None:
            self.trace.export(trace_path)
        if metrics_path is not None:
            self.metrics.export_json(metrics_path)


#: the shared disabled plane (what an engine without ``obs=`` runs on)
NULL_OBS = Observability(enabled=False)


__all__ = [
    "BYTES_BUCKETS", "Counter", "Gauge", "Histogram", "LATENCY_BUCKETS",
    "MetricsRegistry", "NULL_INSTRUMENT", "NULL_OBS", "NULL_SPAN",
    "Observability", "RATIO_BUCKETS", "SweepTelemetry", "TraceEvent",
    "Tracer", "exp_buckets",
    "export_shard_metrics", "harvest_telemetry", "sanitize_label",
    "shard_metric", "skew", "tenant_metric",
]
