"""Dispatch-latency profiling: sampled ``block_until_ready`` bracketing.

The serving engine's dispatches are asynchronous (that is the whole point
of the overlapped pipeline), so wall-clock around a dispatch call measures
tracing + enqueue, not the device. A :class:`DispatchProfiler` brackets a
*sampled subset* of dispatches with ``jax.block_until_ready`` -- dispatch
to results-ready, the latency the autotuner's cost model wants -- at a
configurable sample rate, so the measurement perturbs steady-state
pipelining only on the sampled dispatches.

Sampling is **deterministic**: the first dispatch of each name is sampled,
then every ``round(1/sample_rate)``-th after it (a counter, no RNG) --
two identical runs sample identical dispatches, which is what lets tests
pin the sample counts. Blocking never changes the traversal schedule --
the device computation is already enqueued and identical; only host
timing moves (pinned in ``tests/test_device_telemetry.py``).

Latencies land in the profiler's own histograms (always, so
:meth:`DispatchProfiler.summary` feeds ``CALIB_device.json`` without an
obs plane) and are mirrored into an attached
:class:`~repro.obs.Observability` registry as
``profile.dispatch_s.<name>`` histograms when one is enabled.

Optional ``jax.profiler`` session capture: construct with
``trace_dir=...`` and wrap the serving window in :meth:`trace_session`
(or call :meth:`start_trace` / :meth:`stop_trace`) to drop a TensorBoard/
Perfetto device trace next to the sampled latencies. A capture that was
asked for and fails raises: a run that exits cleanly has its trace.

Surfaced as ``BFSServeEngine(profile=...)`` / ``ServeFrontend(profile=
...)``: pass a profiler instance, ``True`` (sample every dispatch), or a
float sample rate.
"""
from __future__ import annotations

import contextlib
import time

import jax

from .metrics import Histogram, LATENCY_BUCKETS


class DispatchProfiler:
    """Sampled dispatch-latency measurement (see module docstring).

    Parameters
    ----------
    sample_rate : fraction of dispatches (per name) to bracket with
        ``block_until_ready``; 1.0 measures every dispatch, 0.1 every
        10th. Deterministic counter-based sampling, no RNG.
    obs : optional :class:`~repro.obs.Observability` to mirror samples
        into (``profile.dispatch_s.<name>`` histograms +
        ``profile.dispatches`` / ``profile.samples`` counters).
    trace_dir : optional directory for ``jax.profiler`` session capture.
    clock : injectable timer (tests pass a fake; default
        ``time.perf_counter``).
    """

    enabled = True

    def __init__(self, *, sample_rate: float = 1.0, obs=None,
                 trace_dir: str | None = None, clock=time.perf_counter):
        rate = float(sample_rate)
        if not (0.0 < rate <= 1.0):
            raise ValueError(f"sample_rate must be in (0, 1], got {rate}")
        self.sample_rate = rate
        self.sample_every = max(1, int(round(1.0 / rate)))
        self.obs = obs
        self.trace_dir = trace_dir
        self.clock = clock
        self.dispatches = 0
        self.sampled = 0
        self._counts: dict[str, int] = {}
        self._hists: dict[str, Histogram] = {}
        self._tracing = False

    def bind_obs(self, obs) -> None:
        """Attach an obs plane post-construction (the engine binds its own
        when the profiler was built without one)."""
        if self.obs is None and obs is not None and obs.enabled:
            self.obs = obs

    # -- dispatch sampling ----------------------------------------------------
    def timed(self, name: str, fn, *args, **kw):
        """Run ``fn(*args, **kw)``; on sampled dispatches, bracket with
        ``block_until_ready`` on the result (pytrees fine) and record the
        dispatch->ready latency under ``name``. Unsampled dispatches pay
        one dict lookup and an increment."""
        self.dispatches += 1
        n = self._counts.get(name, 0)
        self._counts[name] = n + 1
        if n % self.sample_every:
            return fn(*args, **kw)
        t0 = self.clock()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        dt = self.clock() - t0
        self.sampled += 1
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram(LATENCY_BUCKETS)
        h.record(dt)
        if self.obs is not None and self.obs.enabled:
            m = self.obs.metrics
            m.histogram(f"profile.dispatch_s.{name}").record(dt)
            m.counter("profile.samples").inc()
        return out

    # -- jax.profiler session capture ----------------------------------------
    def start_trace(self) -> bool:
        """Begin a ``jax.profiler`` capture into ``trace_dir`` (no-op
        without one, or when already tracing). Returns True iff a capture
        started; a capture that fails to start raises."""
        if self.trace_dir is None or self._tracing:
            return False
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        return True

    def stop_trace(self) -> None:
        """End the capture :meth:`start_trace` began and write it out (a
        failure to write raises)."""
        if not self._tracing:
            return
        self._tracing = False
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def trace_session(self):
        """``with profiler.trace_session(): serve(...)`` -- a
        ``jax.profiler`` capture around the block when ``trace_dir`` is
        set."""
        self.start_trace()
        try:
            yield self
        finally:
            self.stop_trace()

    # -- export ---------------------------------------------------------------
    def summary(self) -> dict:
        """JSON-ready snapshot: sampling parameters, dispatch/sample
        counts, and per-name latency summaries (count/mean/p50/p95/p99/
        max) under ``dispatch_latency_s`` -- the payload
        ``scripts/profile_sweep.py`` embeds in ``CALIB_device.json``."""
        return {
            "sample_rate": self.sample_rate,
            "dispatches": self.dispatches,
            "sampled": self.sampled,
            "dispatch_latency_s": {
                name: h.summary() for name, h in sorted(self._hists.items())
            },
        }


class _NullProfiler:
    """Shared disabled profiler: ``timed`` is a raw passthrough."""

    enabled = False
    sample_rate = 0.0
    dispatches = 0
    sampled = 0
    trace_dir = None

    def timed(self, name, fn, *args, **kw):
        return fn(*args, **kw)

    def bind_obs(self, obs) -> None:
        pass

    def start_trace(self) -> bool:
        return False

    def stop_trace(self) -> None:
        pass

    @contextlib.contextmanager
    def trace_session(self):
        yield self

    def summary(self) -> dict:
        return {}


NULL_PROFILER = _NullProfiler()


def as_profiler(profile, obs=None):
    """Coerce the engine-facing ``profile=`` argument: ``None``/``False``
    -> the shared null profiler; ``True`` -> sample every dispatch; a
    number -> that sample rate; a profiler instance passes through (and
    gets ``obs`` bound if it has none)."""
    if profile is None or profile is False:
        return NULL_PROFILER
    if isinstance(profile, (DispatchProfiler, _NullProfiler)):
        profile.bind_obs(obs)
        return profile
    if profile is True:
        return DispatchProfiler(sample_rate=1.0, obs=obs)
    if isinstance(profile, (int, float)):
        return DispatchProfiler(sample_rate=float(profile), obs=obs)
    raise TypeError(f"profile must be None/bool/float/DispatchProfiler, "
                    f"got {type(profile).__name__}")
