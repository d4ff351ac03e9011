"""Jitted msBFS serving engine: typed query queue -> lane batches -> results.

One ``BFSServeEngine`` owns a partitioned graph, the static exchange plan,
and compiled msBFS runners (compiled once; every batch reuses them because
lane-word shapes are static in ``n_queries``).  ``submit`` answers typed
:class:`~repro.serve.queries.Query` descriptors -- full levels,
reachability masks, distance-limited levels, multi-target depths -- and
``query`` stays as the classic full-levels sugar.  Cache hits are returned
immediately; misses are packed into lane batches (kinds mix freely),
traversed, unpacked per kind, and cached under ``(graph_id, kind, params,
source)`` keys.

Three execution dimensions, the first two picked at construction:

* **placement** -- ``mesh=None`` (or a 1-device mesh) runs the vmap-emulated
  path; a multi-device mesh runs every sweep under ``shard_map`` with one
  graph partition per device (``msbfs.make_sharded_msbfs``).
* **scheduling** -- ``refill=False`` retires whole batches at once;
  ``refill=True`` runs the continuously-fed pipeline: each sweep reports a
  per-lane convergence mask, converged lanes are retired (their results
  unpacked and attributed via the :class:`~repro.serve.batcher.LaneScheduler`
  generation counters) and reseeded from the pending queue at the next sweep
  boundary, so a deep straggler query never idles the other W-1 lanes.
  Distance-limited and multi-target lanes retire through the same
  convergence word the moment their early-exit condition latches.
* **specialization** -- a batch (or refill drain session) that is
  homogeneously ``REACHABILITY`` compiles to the levels-free msBFS variant
  (``track_levels=False``): pure lane words, no level scatter, no per-edge
  work counters. Mixed batches keep levels for everyone and unpack per
  kind.

On top of refill scheduling, ``overlap=True`` drives sessions through the
overlapped host/device pipeline (fused ``sweep_block``-sweep device blocks
that stop exactly at lane-retirement boundaries + a speculative next block
in flight while the host unpacks -- bit-identical schedule and counters,
fewer round trips), and ``submit_stream`` / ``poll`` / ``drain_stream``
feed and drain the same lane word incrementally instead of batch-at-a-time
(see README.md, "Overlapped host/device pipeline").
"""
from __future__ import annotations

import hashlib
from collections import deque
from dataclasses import dataclass, field, fields as _dc_fields, \
    replace as _dc_replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bfs as B, comm as C, engine as E, msbfs as M
from repro.core.partition import partition_graph
from repro.core.types import COOGraph, PartitionLayout, PartitionedGraph
from repro.core.weights import SSSP_DELTA
from repro.obs import (BYTES_BUCKETS, NULL_OBS, RATIO_BUCKETS, Observability,
                       as_profiler, export_shard_metrics, harvest_telemetry)

from .batcher import LaneScheduler
from .cache import LRUCache
from .queries import (MAX_TARGETS, PAYLOAD_KINDS, Query, QueryKind, as_query,
                      dedupe, unpack_result)

# max_iters stretch factor for payload sessions: weighted distances run up
# to SSSP_WMAX x the hop depth, and delta-stepping revisits a vertex once
# per improving bucket, so the sweep budget scales well past the bit
# diameter bound.
PAYLOAD_ITERS_FACTOR = 6


def default_graph_id(pg: PartitionedGraph) -> str:
    """Content-derived cache namespace for a partitioned graph.

    Digests the *adjacency content* of all four degree-separated subgraphs
    (offsets, column ids, per-partition edge counts) plus the delegate id
    map -- not just the shape. Two different graphs that happen to
    partition to identical shapes (same ``n/p/d/th/m``) must never share
    cache keys: the moment a cache or result store outlives one engine
    (the frontend's shared-catalog scenario) a shape-only id would let one
    graph serve the other's stale answers. The shape prefix stays for
    debuggability; the digest carries the identity. Pass ``graph_id=`` to
    the engine to override (e.g. an epoch-tagged id for mutable graphs).
    """
    h = hashlib.sha256()
    for csr in (pg.nn, pg.nd, pg.dn, pg.dd):
        for arr in (csr.offsets, csr.cols, csr.m):
            a = np.ascontiguousarray(np.asarray(arr))
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    dv = np.ascontiguousarray(np.asarray(pg.delegate_vids))
    h.update(dv.tobytes())
    m = int(np.asarray(pg.nn.m).sum() + np.asarray(pg.dd.m).sum())
    return (f"pg-n{pg.n}-p{pg.p}-d{pg.d}-th{pg.th}-m{m}"
            f"-{h.hexdigest()[:12]}")


def _is_ready(x) -> bool:
    """True once a device array's value is available (non-blocking); arrays
    without readiness introspection report ready and the caller falls back
    to a blocking fetch."""
    probe = getattr(x, "is_ready", None)
    return True if probe is None else bool(probe())


@dataclass
class ServeStats:
    """Serving counters.

    Lane accounting invariants (pinned by tests/test_serve_refill.py):

    * ``lanes_used`` is the number of lane occupancies -- every traversed
      query counts exactly once, in both scheduling modes.
    * batch mode: each batch accounts a full lane word, so
      ``lanes_used + lanes_padded == batches * n_queries``.
    * refill mode: a drain session of k queries accounts
      ``max(n_queries, k)`` lane slots (k used, ``max(0, n_queries - k)``
      padded) -- refilled lanes reuse slots instead of padding new words.
    * ``lane_sweeps_busy / lane_sweeps_total`` is the refill pipeline's lane
      utilization (what ``--refill`` benchmarks report).

    Typed-query counters: ``kind_counts`` tallies submissions per kind
    (cache hits included), ``early_stops`` counts lanes retired through a
    latched early exit (depth cap reached / all targets hit) rather than
    natural frontier exhaustion -- attributed per kind in
    ``early_stops_by_kind`` -- and ``reach_fast_batches`` counts batches
    or drain sessions served by the levels-free reachability variant.
    ``dedup_hits`` counts queries dropped as exact duplicates by the
    refill/stream entry points (both :meth:`run_refill` and
    :meth:`run_refill_queries` dedup-with-stats; duplicate submissions
    collapse onto the surviving query's result).

    Overlapped-pipeline counters (``overlap=True`` engines and the
    streaming API): ``sweep_blocks`` counts fused device dispatches --
    ``sweeps / sweep_blocks`` is the realized fusion factor. The pipeline
    never changes the traversal schedule, so ``sweeps`` and every wire
    counter stay bit-identical to the per-sweep driver.

    Wire-volume counters (the comm layer's per-sweep accounting summed
    over every traversal this engine ran; ``comm/base.py`` byte
    convention, partition rows included, so these are total cluster
    traffic): ``wire_delegate_bytes`` for the delegate combine,
    ``wire_nn_bytes`` for the nn frontier exchange, ``nn_sparse_sweeps``
    counting sweeps that shipped the sparse nn format, and
    ``nn_overflow`` surfacing active slots dropped by a pinned-sparse
    cap (always 0 under the dense and adaptive formats; a nonzero value
    means answers may be wrong and the cap must grow).
    """

    queries: int = 0
    batches: int = 0
    cache_hits: int = 0
    lanes_used: int = 0       # seeded lanes across all batches/sessions
    lanes_padded: int = 0     # lane slots never occupied by a query
    refills: int = 0          # mid-flight lane reseeds
    sweeps: int = 0           # host-stepped supersteps (refill mode only)
    lane_sweeps_busy: int = 0
    lane_sweeps_total: int = 0
    early_stops: int = 0      # lanes retired via depth-cap/target latch
    reach_fast_batches: int = 0
    component_hits: int = 0   # reachability answers reused across sources
    dedup_hits: int = 0       # duplicate submissions detected (refill/stream)
    sweep_blocks: int = 0     # fused device dispatches (pipelined driver)
    kind_counts: dict = field(default_factory=dict)
    early_stops_by_kind: dict = field(default_factory=dict)
    wire_delegate_bytes: int = 0
    wire_nn_bytes: int = 0
    wire_pay_delegate_bytes: int = 0   # payload-plane delegate combine
    wire_pay_nn_bytes: int = 0         # payload-plane nn exchange
    nn_sparse_sweeps: int = 0
    nn_overflow: int = 0

    @property
    def lane_utilization(self) -> float:
        return self.lane_sweeps_busy / max(self.lane_sweeps_total, 1)

    @property
    def wire_bytes_total(self) -> int:
        return (self.wire_delegate_bytes + self.wire_nn_bytes
                + self.wire_pay_delegate_bytes + self.wire_pay_nn_bytes)

    def note_kind(self, kind: QueryKind) -> None:
        self.kind_counts[kind.value] = self.kind_counts.get(kind.value, 0) + 1

    def note_early_stop(self, kind: QueryKind) -> None:
        self.early_stops += 1
        self.early_stops_by_kind[kind.value] = (
            self.early_stops_by_kind.get(kind.value, 0) + 1)

    def note_traversal(self, state) -> None:
        """Fold one finished traversal state's comm counters in (batch
        runs and refill drain sessions alike)."""
        self.wire_delegate_bytes += int(np.asarray(state.wire_delegate).sum())
        self.wire_nn_bytes += int(np.asarray(state.wire_nn).sum())
        # zero-width [p, 0] buffers on bit-only states sum to exactly 0, so
        # these counters stay untouched outside payload sessions
        self.wire_pay_delegate_bytes += int(
            np.asarray(state.wire_pay_delegate).sum())
        self.wire_pay_nn_bytes += int(np.asarray(state.wire_pay_nn).sum())
        # the format flag is a global decision (replicated): row 0 only;
        # overflow is per-device send-side drops: sum every partition
        self.nn_sparse_sweeps += int(np.asarray(state.nn_sparse)[0].sum())
        self.nn_overflow += int(np.asarray(state.nn_overflow).sum())

    def as_dict(self) -> dict:
        """Every counter field plus the derived ``wire_bytes_total``.

        Derived from ``dataclasses.fields`` so a newly added counter can
        never be silently dropped from exports (dict-valued fields are
        copied; tests/test_obs.py pins the exactness)."""
        out = {f.name: (dict(v) if isinstance(v := getattr(self, f.name),
                                              dict) else v)
               for f in _dc_fields(self)}
        out["wire_bytes_total"] = self.wire_bytes_total
        return out


@dataclass
class _Session:
    """Host-side bookkeeping for one refill drain / stream session.

    Shared by the synchronous per-sweep driver, the overlapped pipelined
    driver, and the streaming API -- retirement-boundary processing
    (:meth:`BFSServeEngine._process_boundary`) is one code path, which is
    what guarantees the pipelined schedule (and therefore every
    ``ServeStats`` counter) is bit-identical to the per-sweep driver's.
    """

    cfg: M.MSBFSConfig
    reach_fast: bool
    sched: LaneScheduler
    state: Any                       # device MSBFSState (latest processed)
    step_once: Any                   # per-sweep runner (sync driver)
    block: Any = None                # fused k-sweep runner (pipelined)
    block_donated: Any = None        # same, donating its input state
    stream: bool = False
    results: dict = field(default_factory=dict)
    expected: dict = field(default_factory=dict)  # item -> (lane, generation)
    seen: set = field(default_factory=set)        # stream dedup identity
    undelivered: deque = field(default_factory=deque)  # stream delivery queue
    cached: set = field(default_factory=set)      # already in (or exempt
                                                  # from) the engine LRU --
                                                  # never re-put, so a
                                                  # delivery can't slide a
                                                  # TTL deadline forward
    cur: Any = None         # pipelined: in-flight block to process next
    head: Any = None        # pipelined: speculative successor block
    t_submit: dict = field(default_factory=dict)  # obs: query -> submit ts
    has_reach: bool = False  # session saw a REACHABILITY query (gates defer)
    busy_at_dispatch: int = 0
    exclusive: bool = False  # state is exclusively owned (safe to donate)
    it_prev: int = 0        # device `it` at the last processed boundary
    sweeps: int = 0         # session sweep count (guard)
    n_queries_seen: int = 0  # guard scaling (grows with stream submits)
    lanes_seeded: int = 0   # stream padding accounting at close

    @property
    def guard(self) -> int:
        return (self.cfg.max_iters * max(1, self.n_queries_seen)
                + self.sched.width)

    def complete(self, q, res, skip_cache: bool = False) -> None:
        """Record a finished result. Stream sessions also queue it for the
        next delivery. ``skip_cache`` marks results resolved from an
        existing memo at submit time (LRU hits, already-mapped components)
        which must not be (re)written to the engine LRU -- a delivery must
        never slide a TTL deadline forward. Results computed (or first
        materialized) by this session -- traversals and boundary-time
        component answers -- are cached once, exactly like
        ``submit_many``'s served dict."""
        self.results[q] = res
        if self.stream:
            self.undelivered.append(q)
            if skip_cache:
                self.cached.add(q)


class BFSServeEngine:
    """Serve typed traversal queries from batched msBFS sweeps.

    Parameters
    ----------
    graph / pg : give either the raw ``COOGraph`` (partitioned here with
        ``th``/``p_rank``/``p_gpu``) or an already-partitioned graph.
    cfg : msBFS config; ``cfg.n_queries`` is the lane width W.
    comm : communication strategies (``repro.core.comm.CommConfig``) --
        delegate combine (allgather / ring / hierarchical) and nn wire
        format (dense / sparse / frontier-adaptive); sugar for passing a
        cfg with ``comm=`` set. Wire volumes land in the ``stats``
        counters either way.
    cache_capacity : LRU entries (query-descriptor keyed); 0 disables.
    cache_ttl : default per-entry time-to-live in seconds (None = entries
        never expire -- the immutable-graph default).
    graph_id : cache key namespace; defaults to :func:`default_graph_id`,
        a digest of the partitioned adjacency *content* -- two engines on
        the same graph share semantics, and two different graphs can never
        collide even when their partition shapes match exactly.
    mesh / partition_axes : a device mesh to run sweeps on under
        ``shard_map`` (the product of the partition axes' sizes must equal
        ``pg.p``). ``None`` -- or a mesh spanning a single device -- uses
        the vmap-emulated path, so CPU tests and 1-device deployments
        degenerate to the classic engine.
    refill : serve misses through the continuously-fed lane-refill pipeline
        instead of batch-at-a-time traversals.
    overlap : drive refill sessions through the overlapped host/device
        pipeline: sweeps run in fused ``sweep_block``-sized device blocks
        that stop *exactly* at lane-retirement boundaries, and a
        speculative next block is kept in flight while the host processes
        the previous block's ``lane_active`` word, retired-lane gathers,
        and reseed descriptors (the host only ever blocks on the lagging
        handle, never the pipeline head). The traversal schedule -- and so
        ``ServeStats.sweeps`` and the wire-byte counters -- is
        bit-identical to the per-sweep driver. Implies nothing unless
        ``refill=True`` (batch mode already runs one fused device loop).
    sweep_block : sweeps fused per device dispatch when ``overlap=True``
        (the convergence-poll cadence k; retirements still land exactly).
    edge_chunk : when > 0, stream every push scatter and nn slot marking
        through fixed-size edge blocks of this many edges (and pull
        gathers through the matching row blocks) instead of
        materializing the full per-subgraph edge frontier at once --
        ``MSBFSConfig(edge_chunk=...)``. Caps transient sweep memory at
        O(edge_chunk * W) per subgraph so scale-16+ partitions fit; the
        traversal schedule and every counter stay bit-identical to the
        monolithic sweep (see ``serve/README.md``, "memory footprint").
        Sugar for passing a ``cfg`` with the field set; 0 = monolithic.
    specialize_reachability : compile homogeneous REACHABILITY batches to
        the levels-free msBFS variant (lazily, on first use).
    obs : an :class:`repro.obs.Observability` plane; every pipeline stage
        becomes a trace span (sweep blocks, boundaries, reseeds, gathers,
        cache/component/dedup resolutions as instants) and every
        ``ServeStats`` counter a metric, including per-kind
        submit->deliver latency histograms. Tracing is host-side only --
        the traversal schedule (and every counter) is bit-identical with
        ``obs`` on or off. Default: the shared disabled plane (free).
        A ``cfg`` built with ``telemetry=True`` additionally carries the
        in-jit sweep-telemetry buffers through every traversal; the
        engine harvests them at the existing host boundaries (batch
        completion / session close -- zero extra syncs) into
        ``self.last_telemetry`` and the ``device.shard.<i>.*`` imbalance
        metrics (see ``obs/device.py``).
    profile : dispatch-latency profiling (``obs/profile.py``): pass a
        :class:`repro.obs.DispatchProfiler`, ``True`` (bracket every
        dispatch with ``block_until_ready``), or a float sample rate.
        Sampled dispatches measure dispatch->results-ready latency per
        dispatch site (``batch`` / ``sweep`` / ``block``); the traversal
        schedule and every ``ServeStats`` counter stay bit-identical --
        only host timing moves. Default off (a shared null passthrough).
    reuse_components : memoize reachability answers *per connected
        component*: on an undirected graph the reachable set is the
        source's component, so every later REACHABILITY query from an
        already-mapped component is answered without a traversal (counted
        in ``stats.component_hits``) -- a reuse level arrays can never
        have, since levels differ per source. The repo's Graph500 / RMAT
        graphs are all symmetrized; set False for directed edge lists,
        where reachability is not symmetric and the reuse would be wrong.
    runner_cache : a dict shared across engines so same-shape graphs reuse
        one set of compiled runners instead of retracing (the frontend's
        engine pool passes one per catalog). Keys include every shape and
        static argument a runner specializes on, so sharing is always
        safe; ``None`` (default) keeps a private per-engine dict.
    """

    def __init__(
        self,
        graph: COOGraph | None = None,
        *,
        pg: PartitionedGraph | None = None,
        th: int = 64,
        p_rank: int = 1,
        p_gpu: int = 2,
        cfg: M.MSBFSConfig | None = None,
        comm: C.CommConfig | None = None,
        cache_capacity: int = 256,
        cache_ttl: float | None = None,
        graph_id: str | None = None,
        mesh=None,
        partition_axes=None,
        refill: bool = False,
        overlap: bool = False,
        sweep_block: int = 8,
        edge_chunk: int = 0,
        specialize_reachability: bool = True,
        reuse_components: bool = True,
        obs: Observability | None = None,
        profile=None,
        runner_cache: dict | None = None,
    ):
        self.obs = obs if obs is not None else NULL_OBS
        self.profiler = as_profiler(profile, obs=self.obs)
        self.last_telemetry = None   # latest harvested SweepTelemetry
        if pg is None:
            if graph is None:
                raise ValueError("need graph= or pg=")
            pg = partition_graph(graph, th=th, p_rank=p_rank, p_gpu=p_gpu)
        self.pg = pg
        self.cfg = cfg or M.MSBFSConfig()
        if comm is not None:
            # sugar: swap the comm strategies without rebuilding the whole
            # msBFS config (every derived per-batch variant inherits them)
            self.cfg = _dc_replace(self.cfg, comm=comm)
        if int(edge_chunk):
            # sugar: flip on chunked out-of-core sweeps (bit-identical
            # schedule, bounded O(edge_chunk * W) transient memory)
            self.cfg = _dc_replace(self.cfg, edge_chunk=int(edge_chunk))
        if not self.cfg.track_levels or not self.cfg.enable_targets:
            raise ValueError(
                "pass a track_levels=True, enable_targets=True cfg; the "
                "engine derives the specialized per-batch variants itself")
        self.refill = bool(refill)
        self.overlap = bool(overlap)
        if int(sweep_block) < 1:
            raise ValueError(f"sweep_block must be >= 1, got {sweep_block}")
        self.sweep_block = int(sweep_block)
        self._stream: _Session | None = None
        self.specialize_reachability = bool(specialize_reachability)
        self.reuse_components = bool(reuse_components)
        self._comp_id = np.full(pg.n, -1, dtype=np.int32)
        self._comp_masks: dict[int, np.ndarray] = {}
        # full component-label map ([n] int32, min vertex id per component)
        # once any COMPONENTS traversal finishes: every later COMPONENTS
        # query -- and every reachability mask -- derives from it without a
        # traversal (the component memo the new kind reuses and feeds)
        self._comp_labels: np.ndarray | None = None
        # lazily built per-partition global-id planes for payload reseeds
        self._gid_planes: tuple | None = None
        self.pgv = B.device_view(pg)
        self.plan = E.build_exchange_plan(pg)
        if graph_id is None:
            graph_id = default_graph_id(pg)
        self.graph_id = graph_id
        self.cache = LRUCache(cache_capacity, ttl=cache_ttl, obs=self.obs)
        self.stats = ServeStats()
        if self.obs.enabled:
            # one metadata event anchoring the trace: graph shape + the
            # comm plan's static strategy/byte model (core/comm/base.py)
            self.obs.trace.instant(
                "engine.init", graph_id=self.graph_id, n=int(pg.n),
                p=int(pg.p), d=int(pg.d), th=int(pg.th),
                n_queries=int(self.cfg.n_queries),
                refill=self.refill, overlap=self.overlap,
                sweep_block=self.sweep_block,
                comm=self.cfg.comm.as_dict())
        self._layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
        # exactly the pg.d real delegate ids -- *empty* on a delegate-free
        # graph (the replicated arrays pad to max(d, 1) for static shapes,
        # but a padded id here would misclassify a source as a delegate)
        self._dvids = np.asarray(pg.delegate_vids).reshape(-1)[: pg.d]

        self.mesh = mesh
        self.sharded = False
        self._axes = None
        if mesh is not None:
            axes = (tuple(partition_axes) if partition_axes is not None
                    else tuple(mesh.axis_names))
            ndev = int(np.prod([mesh.shape[a] for a in axes]))
            if ndev > 1:
                if ndev != pg.p:
                    raise ValueError(
                        f"mesh axes {axes} span {ndev} devices but the graph "
                        f"has p={pg.p} partitions")
                from jax.sharding import NamedSharding, PartitionSpec as P

                def put(tree):
                    def leaf(x):
                        spec = P(axes, *([None] * (np.ndim(x) - 1)))
                        return jax.device_put(x, NamedSharding(mesh, spec))
                    return jax.tree.map(leaf, tree)

                self._put = put
                self.pgv = put(self.pgv)
                self.plan = put(self.plan)
                self._axes = axes
                self.sharded = True
        if not self.sharded:
            self._put = lambda tree: tree
        # compiled runner pairs (run_full, step_once) and fused k-sweep
        # block pairs (block, block_donated), keyed by ("run"|"block",
        # shape_key, static per-batch config variant [, sweep geometry]) and
        # built lazily on first use -- target-free batches compile the
        # target bookkeeping away, homogeneous REACHABILITY batches the
        # levels. ``runner_cache=`` injects a *shared* dict (the frontend's
        # per-catalog pool): every array shape and static argument a runner
        # closes over is part of the key, so same-shape tenants reuse one
        # compilation and different-shape tenants can never collide.
        self._shape_key = self._runner_shape_key()
        self._runners: dict = runner_cache if runner_cache is not None else {}

    # -- runner construction ------------------------------------------------
    def _runner_shape_key(self):
        """Hashable identity of everything a compiled runner specializes
        on *besides* the msBFS config variant: the device-view / exchange-
        plan leaf shapes+dtypes (what the jitted sweeps trace against),
        the partition geometry, and -- for sharded engines -- the exact
        device assignment and partition axes. Two engines with equal keys
        can share one compilation; the traced computation is identical."""
        leaves = jax.tree_util.tree_leaves((self.pgv, self.plan))
        arrs = tuple(
            (tuple(getattr(x, "shape", ())),
             str(getattr(x, "dtype", type(x).__name__)))
            for x in leaves)
        pg = self.pg
        geom = (int(pg.n), int(pg.p), int(pg.p_rank), int(pg.p_gpu),
                int(pg.d), int(pg.th))
        mesh_key = None
        if self.sharded:
            mesh_key = (tuple(int(d.id) for d in
                              np.asarray(self.mesh.devices).reshape(-1)),
                        tuple(self.mesh.axis_names),
                        tuple(np.asarray(self.mesh.devices).shape),
                        tuple(self._axes))
        return (arrs, geom, mesh_key)

    def _build_runners(self, cfg: M.MSBFSConfig) -> tuple:
        if self.sharded:
            return (M.make_sharded_msbfs(self.mesh, self._axes, cfg),
                    M.make_sharded_msbfs_step(self.mesh, self._axes, cfg))
        run = lambda pgv, plan, st: M.run_msbfs_emulated(pgv, plan, st, cfg)
        step = lambda pgv, plan, st: M.msbfs_step_emulated(pgv, plan, st, cfg)
        return run, step

    def _payload_cfg(self, cfg: M.MSBFSConfig) -> M.MSBFSConfig:
        """The payload=True sibling of ``cfg``: carries the [n_local, W]
        int32 payload plane and stretches the sweep budget (weighted
        distances and bucket revisits outrun the bit diameter bound)."""
        return _dc_replace(cfg, payload=True,
                           max_iters=cfg.max_iters * PAYLOAD_ITERS_FACTOR)

    def _session_cfg(self, queries) -> M.MSBFSConfig:
        """The static msBFS variant this batch/session compiles to."""
        if self._reach_fast(queries):
            return _dc_replace(self.cfg, track_levels=False,
                               enable_targets=False)
        if any(q.kind is QueryKind.MULTI_TARGET for q in queries):
            cfg = self.cfg
        else:
            cfg = _dc_replace(self.cfg, enable_targets=False)
        if any(q.kind in PAYLOAD_KINDS for q in queries):
            cfg = self._payload_cfg(cfg)
        return cfg

    def _runner_pair(self, cfg: M.MSBFSConfig) -> tuple:
        key = ("run", self._shape_key, cfg)
        pair = self._runners.get(key)
        if pair is None:
            pair = self._runners[key] = self._build_runners(cfg)
        return pair

    def _block_pair(self, cfg: M.MSBFSConfig) -> tuple:
        """(block, block_donated) fused k-sweep runners for ``cfg``."""
        key = ("block", self._shape_key, cfg, self.sweep_block)
        pair = self._runners.get(key)
        if pair is None:
            k = self.sweep_block
            if self.sharded:
                mk = lambda don: M.make_sharded_msbfs_block(
                    self.mesh, self._axes, cfg, k, donate=don)
            else:
                mk = lambda don: M.make_msbfs_block_emulated(
                    cfg, k, donate=don)
            pair = self._runners[key] = (mk(False), mk(True))
        return pair

    def _reach_fast(self, queries) -> bool:
        return (self.specialize_reachability
                and all(q.kind is QueryKind.REACHABILITY for q in queries))

    def _gather_rows(self, cfg: M.MSBFSConfig, reach_fast: bool, state,
                     lanes, items) -> list:
        """Kind-aware per-lane result rows for ``lanes`` (aligned with the
        typed ``items``): payload kinds read their payload-plane column,
        everything else the level (or packed-reach) columns -- at most one
        gather per plane leaves the device."""
        if reach_fast:
            return list(M.gather_reachable_multi(self.pg, state, lanes=lanes))
        pay = [cfg.payload and as_query(it).kind in PAYLOAD_KINDS
               for it in items]
        rows = (M.gather_levels_multi(self.pg, state, lanes=lanes)
                if not all(pay) else None)
        prows = (M.gather_payload_multi(self.pg, state, lanes=lanes)
                 if any(pay) else None)
        return [prows[i] if pp else rows[i] for i, pp in enumerate(pay)]

    # -- observability hooks ------------------------------------------------
    def _record_latency(self, kind: QueryKind, dt: float) -> None:
        """One submit->deliver latency sample, bucketed per query kind."""
        self.obs.metrics.histogram(f"serve.latency_s.{kind.value}").record(dt)

    def _note_traversal(self, state, sweeps: int) -> None:
        """``stats.note_traversal`` plus the metrics mirror: the finished
        traversal's wire volume as a per-sweep histogram sample. States
        carrying the in-jit telemetry buffers (``cfg.telemetry=True``)
        are additionally harvested here -- this is a point where the
        engine already fetched the state host-side, so the device-plane
        snapshot (``self.last_telemetry``) and the per-shard imbalance
        metrics cost zero extra syncs."""
        pre = self.stats.wire_bytes_total
        self.stats.note_traversal(state)
        tel = harvest_telemetry(state)
        if tel is not None:
            self.last_telemetry = tel
            export_shard_metrics(self.obs, tel)
        if self.obs.enabled and sweeps > 0:
            self.obs.metrics.histogram(
                "serve.wire_bytes_per_sweep", BYTES_BUCKETS).record(
                    (self.stats.wire_bytes_total - pre) / sweeps)

    def _export_stats(self) -> None:
        """Mirror every ``ServeStats`` counter into the metrics registry
        (``as_dict`` is fields-derived, so a newly added counter shows up
        here automatically)."""
        if not self.obs.enabled:
            return
        m = self.obs.metrics
        for k, v in self.stats.as_dict().items():
            if isinstance(v, dict):
                for kk, vv in v.items():
                    m.gauge(f"serve.stats.{k}.{kk}").set(vv)
            else:
                m.gauge(f"serve.stats.{k}").set(v)
        m.gauge("serve.lane_utilization").set(self.stats.lane_utilization)
        if self.stats.sweep_blocks:
            m.gauge("serve.fusion_factor").set(
                self.stats.sweeps / self.stats.sweep_blocks)

    def _validate_queries(self, queries) -> None:
        """Range-check every source *and* target before any lane is seeded
        (the refill path seeds targets through ``_seed_descriptors``, which
        must never scatter an out-of-range coordinate)."""
        ids = [q.source for q in queries]
        for q in queries:
            ids.extend(q.targets or ())
        M.validate_sources(self.pg, ids)

    # -- per-component reuse (reachability masks + COMPONENTS labels) -------
    def _component_of(self, q: Query):
        """The memoized component answer covering ``q``, or None.

        REACHABILITY: the source's reachable mask, from a previously
        registered mask or materialized (and registered) from the full
        label map a COMPONENTS traversal left behind. COMPONENTS: the full
        ``[n]`` label map itself, once any traversal computed it -- the one
        answer every COMPONENTS query shares."""
        if not self.reuse_components:
            return None
        if q.kind is QueryKind.COMPONENTS:
            return self._comp_labels
        if q.kind is not QueryKind.REACHABILITY:
            return None
        cid = self._comp_id[q.source]
        if cid >= 0:
            return self._comp_masks[cid]
        if self._comp_labels is not None:
            mask = self._comp_labels == self._comp_labels[q.source]
            cid = len(self._comp_masks)
            self._comp_masks[cid] = mask
            self._comp_id[mask] = cid
            return mask
        return None

    def _register_component(self, q: Query, result) -> None:
        """Record a served reachability mask as its source's component, or
        a served COMPONENTS label map as the whole-graph component memo."""
        if not self.reuse_components:
            return
        if q.kind is QueryKind.COMPONENTS:
            if self._comp_labels is None:
                self._comp_labels = np.array(result)
        elif (q.kind is QueryKind.REACHABILITY
                and self._comp_id[q.source] < 0):
            cid = len(self._comp_masks)
            self._comp_masks[cid] = np.array(result)
            self._comp_id[result] = cid

    # -- core batch path ----------------------------------------------------
    def run_batch(self, sources: np.ndarray) -> np.ndarray:
        """Traverse one full-levels lane batch (classic API): [k, n]."""
        qs = [as_query(int(s)) for s in sources]
        res = self.run_batch_queries(qs)
        return np.stack([res[q] for q in qs]) if qs else np.zeros(
            (0, self.pg.n), dtype=np.int32)

    def run_batch_queries(self, queries) -> dict:
        """Traverse one (possibly mixed-kind) lane batch of typed queries:
        {query: per-kind result}. Homogeneous REACHABILITY batches run on
        the levels-free variant."""
        w = self.cfg.n_queries
        if len(queries) > w:
            raise ValueError(f"{len(queries)} queries > n_queries={w}")
        if not queries:
            return {}
        reach_fast = self._reach_fast(queries)
        cfg = self._session_cfg(queries)
        run_full, _ = self._runner_pair(cfg)
        sweeps = 0
        with self.obs.trace.span("serve.batch", n=len(queries),
                                 reach_fast=reach_fast) as sp:
            st = self._put(M.init_multi_state(
                self.pg, [q.source for q in queries], cfg,
                depth_caps=[q.depth_cap for q in queries],
                targets=[q.targets for q in queries],
                payload_modes=[q.payload_mode for q in queries]))
            out = self.profiler.timed("batch", run_full,
                                      self.pgv, self.plan, st)
            with self.obs.trace.span("serve.gather", lanes=len(queries)):
                rows = self._gather_rows(cfg, reach_fast, out,
                                         np.arange(len(queries)), queries)
            if self.obs.enabled:
                # host-side introspection only (the run already finished):
                # never changes the traversal schedule or any counter
                sweeps = int(np.asarray(out.it)[0])
                sp.set(sweeps=sweeps)
        if reach_fast:
            self.stats.reach_fast_batches += 1
        stops = np.asarray(out.lane_stop)[0]
        self.stats.batches += 1
        self.stats.lanes_used += len(queries)
        self.stats.lanes_padded += w - len(queries)
        self._note_traversal(out, sweeps)
        for i, q in enumerate(queries):
            if stops[i]:
                self.stats.note_early_stop(q.kind)
        return {q: unpack_result(q, rows[i], packed_reach=reach_fast)
                for i, q in enumerate(queries)}

    # -- refill path --------------------------------------------------------
    def _pay_gids(self) -> tuple:
        """Per-partition global-id planes for payload reseeds: ``gid_n``
        [p, n_local] int32 with the combine identity at invalid slots and
        ``gid_d`` [max(d, 1)] int32 with the identity at padding -- the
        host-side constants ``msbfs.reseed_lanes`` seeds components lanes
        from (identity slots stay out of the worklist)."""
        if self._gid_planes is None:
            pg = self.pg
            p, nl = pg.p, pg.n_local
            gid_n = np.full((p, nl), M.PAY_IDENT, dtype=np.int32)
            valid = np.asarray(pg.normal_valid)
            for k in range(p):
                gids = self._layout.global_of(np.full(nl, k), np.arange(nl))
                gid_n[k, valid[k]] = gids[valid[k]].astype(np.int32)
            gid_d = np.full((max(pg.d, 1),), M.PAY_IDENT, dtype=np.int32)
            gid_d[: pg.d] = self._dvids.astype(np.int32)
            self._gid_planes = (gid_n, gid_d)
        return self._gid_planes

    def _seed_descriptors(self, assignments, payload: bool = False):
        """Host-side lane seed coordinates + typed-query parameters for
        ``msbfs.reseed_lanes``. ``payload=True`` (payload sessions only --
        the reseed scatters need real-width payload planes) appends the
        per-lane payload descriptors and the global-id seed planes."""
        w, t = self.cfg.n_queries, MAX_TARGETS
        mask = np.zeros(w, dtype=bool)
        part = np.zeros(w, dtype=np.int32)
        local = np.zeros(w, dtype=np.int32)
        dpos = np.zeros(w, dtype=np.int32)
        isd = np.zeros(w, dtype=bool)
        cap = np.full(w, M.NO_DEPTH_CAP, dtype=np.int32)
        tpart = np.zeros((w, t), dtype=np.int32)
        tlocal = np.zeros((w, t), dtype=np.int32)
        tdpos = np.zeros((w, t), dtype=np.int32)
        tisd = np.zeros((w, t), dtype=bool)
        tvalid = np.zeros((w, t), dtype=bool)
        play = np.zeros(w, dtype=bool)
        pseed_all = np.zeros(w, dtype=bool)
        pweighted = np.zeros(w, dtype=bool)
        pdelta = np.full(w, M.PAY_IDENT, dtype=np.int32)
        for a in assignments:
            mask[a.lane] = True
            (isd[a.lane], part[a.lane], local[a.lane],
             dpos[a.lane]) = M.locate_source(self.pg, self._layout,
                                             self._dvids, a.source)
            q = as_query(a.item if a.item is not None else a.source)
            if q.depth_cap is not None:
                cap[a.lane] = q.depth_cap
            for j, tgt in enumerate(q.targets or ()):
                (tisd[a.lane, j], tpart[a.lane, j], tlocal[a.lane, j],
                 tdpos[a.lane, j]) = M.locate_source(
                     self.pg, self._layout, self._dvids, int(tgt))
                tvalid[a.lane, j] = True
            mode = q.payload_mode
            if mode is not None:
                play[a.lane] = True
                if mode == "sssp":
                    pweighted[a.lane] = True
                    pdelta[a.lane] = np.int32(SSSP_DELTA)
                else:                       # components: INF bucket = plain
                    pseed_all[a.lane] = True  # min-label propagation
        base = (mask, part, local, dpos, isd, cap,
                tpart, tlocal, tdpos, tisd, tvalid)
        if not payload:
            return base
        gid_n, gid_d = self._pay_gids()
        return base + (play, pseed_all, pweighted, pdelta, gid_n, gid_d)

    def run_refill(self, sources: np.ndarray) -> dict:
        """Classic full-levels drain (kept for direct callers): dedups
        ``sources`` (counted in ``stats.dedup_hits``) and returns
        {source: levels [n] int32}."""
        sources = M.validate_sources(self.pg, sources)
        qs = [as_query(int(s)) for s in sources.tolist()]
        return {q.source: lev
                for q, lev in self.run_refill_queries(qs).items()}

    def run_refill_queries(self, queries) -> dict:
        """Drain typed ``queries`` through the continuously-fed lane
        pipeline: {query: per-kind result}.

        Exact duplicate descriptors are dropped up front (counted in
        ``stats.dedup_hits``; queries of different kinds or params on the
        same source are distinct) -- the same dedup-with-stats semantics as
        :meth:`run_refill`, so the two entry points can never disagree.

        Lanes are retired the sweep their early-exit latches or their
        frontier empties, and reseeded from the pending queue at the next
        sweep boundary; results are attributed through the scheduler's
        (lane, generation) bookkeeping. Kinds mix freely across refill
        generations; a homogeneously-REACHABILITY session runs on the
        levels-free variant. ``overlap=True`` engines drain through the
        pipelined driver (same schedule, same counters, fewer host
        round trips).
        """
        queries, dups = dedupe([as_query(q) for q in queries])
        self.stats.dedup_hits += dups
        if dups and self.obs.enabled:
            self.obs.trace.instant("serve.dedup", dropped=dups)
        if not queries:
            return {}
        self._validate_queries(queries)
        with self.obs.trace.span("serve.refill_drain", n=len(queries),
                                 overlap=self.overlap):
            sess = self._open_session(queries)
            if self.overlap:
                while sess.sched.n_busy:
                    self._pipeline_advance(sess)
            else:
                self._drain_sync(sess)
            self._close_session(sess)
        return sess.results

    # -- session machinery (shared by sync / pipelined / streaming) ---------
    def _open_session(self, queries, stream: bool = False) -> _Session:
        """Build the per-session state: pick the static msBFS variant from
        the opening query set, seed the initial lane fill, and account the
        session-open stats exactly as the classic drain did. A stream
        session opens with an empty lane word (queries are enqueued by
        ``submit_stream`` after cache/dedup filtering and seeded by
        ``poll``). A homogeneously-REACHABILITY opening set compiles the
        levels-free fast path (and the session then only accepts that
        kind); any other stream opening compiles the fully-general variant
        -- a stream feed is open-ended, so later MULTI_TARGET submissions
        must be seedable without a retrace."""
        w = self.cfg.n_queries
        reach_fast = self._reach_fast(queries)
        if stream and not reach_fast:
            # open-ended feed: compile the fully-general variant so later
            # MULTI_TARGET submissions never retrace. The payload plane is
            # opt-in at open time (it changes the compiled state shape):
            # an opening set with a payload kind carries it for the whole
            # session, a bit-only opening keeps the bit-identical schedule
            # (later payload submissions raise; drain_stream first).
            cfg = self.cfg
            if any(q.kind in PAYLOAD_KINDS for q in queries):
                cfg = self._payload_cfg(cfg)
        else:
            cfg = self._session_cfg(queries)
        with self.obs.trace.span("serve.session.open", n=len(queries),
                                 stream=stream, reach_fast=reach_fast):
            _, step_once = self._runner_pair(cfg)
            sess = _Session(
                cfg=cfg, reach_fast=reach_fast,
                sched=LaneScheduler(w, pending=() if stream else queries,
                                    obs=self.obs),
                state=self._put(M.init_multi_state(self.pg, [], cfg)),
                step_once=step_once, stream=stream,
                n_queries_seen=0 if stream else len(queries), exclusive=True,
                has_reach=any(q.kind is QueryKind.REACHABILITY
                              for q in queries),
            )
            if self.overlap or stream:
                sess.block, sess.block_donated = self._block_pair(cfg)
            if reach_fast:
                self.stats.reach_fast_batches += 1
            self._fill(sess, initial=True)
        self.stats.batches += 1
        if not stream:
            self.stats.lanes_padded += max(0, w - len(queries))
        return sess

    def _reseed(self, sess: _Session, assignments):
        desc = self._seed_descriptors(assignments, payload=sess.cfg.payload)
        reseed = (M.reseed_lanes_donated if sess.exclusive
                  else M.reseed_lanes)
        return reseed(sess.state, *map(jnp.asarray, desc))

    def _fill(self, sess: _Session, initial: bool = False) -> list:
        """Assign pending queries to idle lanes and reseed them on device;
        ``initial`` fills count toward ``lanes_used`` only, later ones are
        mid-flight ``refills``."""
        fresh = sess.sched.fill_idle()
        if fresh:
            with self.obs.trace.span("serve.reseed", lanes=len(fresh),
                                     initial=initial):
                sess.state = self._reseed(sess, fresh)
            sess.exclusive = True
            self.stats.lanes_used += len(fresh)
            sess.lanes_seeded += len(fresh)
            if not initial:
                self.stats.refills += len(fresh)
            for a in fresh:
                sess.expected[a.item] = (a.lane, a.generation)
        return fresh

    def _process_boundary(self, sess: _Session, active: np.ndarray,
                          defer: bool = False):
        """Retirement-boundary processing on ``sess.state`` (whose
        ``lane_active`` word is ``active``): retire every newly converged
        lane, attribute results through the (lane, generation) bookkeeping,
        apply per-component reachability reuse, and refill idle lanes from
        the pending queue. Returns ``(changed, deferred)``: ``changed`` is
        True iff the scheduler changed (the pipelined driver must then
        discard its frozen speculative block); ``deferred`` carries the
        retired lanes' gather/unpack work when ``defer=True`` so the
        pipelined driver can dispatch the next block *before* the host
        touches the level columns (finish with :meth:`_finish_boundary`).

        Deferral is only requested when per-component reuse cannot observe
        this boundary (``reuse_components`` off, or no REACHABILITY query
        in the session): reuse must register the freshly gathered mask
        before the cut/pending/refill decisions, so those boundaries keep
        the eager order and stay schedule-identical to the sync driver.
        """
        sched, results = sess.sched, sess.results
        finished = sched.busy & ~active
        if not finished.any():
            return False, None
        fin_lanes = np.nonzero(finished)[0]
        fin_items = [sched.lane_item[int(q)] for q in fin_lanes]
        pre_state = sess.state
        with self.obs.trace.span("serve.boundary", retired=len(fin_lanes),
                                 defer=defer):
            if not defer:
                # only the retired lanes' columns leave the device: [k, n]
                with self.obs.trace.span("serve.gather",
                                         lanes=len(fin_lanes)):
                    rows = self._gather_rows(sess.cfg, sess.reach_fast,
                                             pre_state, fin_lanes, fin_items)
            stops = np.asarray(pre_state.lane_stop)[0]
            fins = []
            for i, q in enumerate(fin_lanes):
                item, gen = sched.retire(int(q))
                assert sess.expected.pop(item) == (int(q), gen), (
                    "lane generation bookkeeping out of sync")
                fins.append(item)
                if not defer:
                    sess.complete(item, unpack_result(
                        item, rows[i], packed_reach=sess.reach_fast))
                    self._register_component(item, results[item])
                if stops[q]:
                    self.stats.note_early_stop(item.kind)
            if self.reuse_components:
                # a freshly mapped component may cover other reachability
                # queries: answer pending ones without a lane, and cut
                # *active* lanes short -- their traversal result is already
                # known, so a deep straggler stops costing sweeps the
                # moment any same-component lane retires
                for lane in np.nonzero(sched.busy)[0]:
                    mask = self._component_of(as_query(sched.lane_item[lane]))
                    if mask is not None:
                        item, _ = sched.retire(int(lane))
                        sess.expected.pop(item)
                        sess.complete(item, np.array(mask))
                        self.stats.component_hits += 1
                        if self.obs.enabled:
                            self.obs.trace.instant(
                                "serve.component.cut",
                                source=getattr(item, "source", item))
                if sched.pending:
                    keep = []
                    for item in sched.pending:
                        mask = self._component_of(as_query(item))
                        if mask is None:
                            keep.append(item)
                        else:
                            sess.complete(item, np.array(mask))
                            self.stats.component_hits += 1
                    sched.pending.clear()
                    sched.pending.extend(keep)
            self._fill(sess)
        return True, ((pre_state, fin_lanes, fins) if defer else None)

    def _finish_boundary(self, sess: _Session, deferred) -> None:
        """The deferred half of a retirement boundary: gather the retired
        lanes' columns from the *pre-reseed* state and unpack per kind --
        run after the next block is already in flight, so the host-side
        unpacking overlaps the device's next sweeps."""
        pre_state, fin_lanes, fins = deferred
        with self.obs.trace.span("serve.gather.deferred",
                                 lanes=len(fin_lanes)):
            rows = self._gather_rows(sess.cfg, sess.reach_fast,
                                     pre_state, fin_lanes, fins)
            for i, item in enumerate(fins):
                sess.complete(item, unpack_result(
                    item, rows[i], packed_reach=sess.reach_fast))
                self._register_component(item, sess.results[item])

    def _close_session(self, sess: _Session) -> None:
        self._note_traversal(sess.state, sess.sweeps)
        if sess.stream:
            self.stats.lanes_padded += max(
                0, self.cfg.n_queries - sess.lanes_seeded)
        if self.obs.enabled:
            self.obs.metrics.histogram(
                "serve.session_sweeps", RATIO_BUCKETS).record(sess.sweeps)
            self.obs.trace.instant("serve.session.close",
                                   sweeps=sess.sweeps,
                                   results=len(sess.results))
            self._export_stats()

    # -- synchronous per-sweep driver ---------------------------------------
    def _drain_sync(self, sess: _Session) -> None:
        """One host round trip per sweep: step, poll ``lane_active``,
        process retirements (the pre-pipeline driver, kept as the
        ground-truth schedule the overlapped driver must reproduce)."""
        sched = sess.sched
        w = self.cfg.n_queries
        obs = self.obs
        while sched.n_busy:
            busy_now = sched.n_busy
            t0 = obs.clock() if obs.enabled else 0.0
            with obs.trace.span("serve.sweep", busy=busy_now):
                sess.state = self.profiler.timed(
                    "sweep", sess.step_once, self.pgv, self.plan, sess.state)
                sess.exclusive = False
                sess.sweeps += 1
                self.stats.sweeps += 1
                self.stats.lane_sweeps_busy += busy_now
                self.stats.lane_sweeps_total += w
                if sess.sweeps > sess.guard:
                    raise RuntimeError(
                        f"refill pipeline exceeded {sess.guard} sweeps with "
                        f"{sched.n_busy} lanes still busy")
                active = np.asarray(sess.state.lane_active)[0]
            if obs.enabled:
                obs.metrics.histogram("serve.sweep_duration_s").record(
                    obs.clock() - t0)
            self._process_boundary(sess, active)

    # -- overlapped pipelined driver ----------------------------------------
    def _pipeline_advance(self, sess: _Session, wait: bool = True) -> bool:
        """Advance the overlapped pipeline by one block boundary.

        Dispatches a fused ``sweep_block``-sweep block (plus a speculative
        successor chained behind it), then ready-checks the *lagging*
        handle -- the earlier block's output -- never the pipeline head.
        While the host unpacks retired lanes and builds reseed descriptors,
        the successor keeps the device busy. The fused block stops at the
        exact sweep any watched lane converges, and a speculative block
        dispatched across a retirement boundary freezes itself (zero
        sweeps), so the traversal schedule is bit-identical to
        :meth:`_drain_sync`.

        Returns False without processing when ``wait=False`` and the
        lagging handle isn't ready yet (the streaming ``poll(wait=False)``
        path); True after a boundary was processed.
        """
        sched = sess.sched
        w = self.cfg.n_queries
        obs = self.obs
        if sess.cur is None:
            if not sched.n_busy:
                if not sched.pending:
                    return False
                self._fill(sess, initial=sess.sweeps == 0)
            watch = np.ascontiguousarray(sched.busy)
            blockfn = sess.block_donated if sess.exclusive else sess.block
            if obs.enabled:
                obs.trace.instant("serve.block.dispatch", busy=sched.n_busy)
            sess.cur = self.profiler.timed(
                "block", blockfn, self.pgv, self.plan, sess.state, watch)
            sess.exclusive = False
            # no speculation on a fresh dispatch: this site is only reached
            # right after a scheduler change (or at session start), where a
            # head would be a doomed (frozen) dispatch if another
            # retirement lands. The quiet-boundary branch below starts
            # speculating once a no-retirement streak begins -- deep-tail
            # stretches, exactly where a chained head keeps the device
            # busy through the host's fetch.
            sess.head = None
            sess.busy_at_dispatch = sched.n_busy
        if not wait and not _is_ready(sess.cur.lane_active):
            return False
        cur = sess.cur
        t0 = obs.clock() if obs.enabled else 0.0
        with obs.trace.span("serve.block.wait",
                            busy=sess.busy_at_dispatch) as bsp:
            jax.block_until_ready(cur.lane_active)   # the lagging handle only
            active = np.asarray(cur.lane_active)[0]
            if (sched.busy & ~active).any():
                # the block early-stopped at the retirement sweep: read the
                # executed count off the device iteration counter
                it_cur = int(np.asarray(cur.it)[0])
            else:
                # no watched lane retired, so the fused loop ran its full k
                # sweeps -- no second device fetch needed
                it_cur = sess.it_prev + self.sweep_block
            bsp.set(sweeps=it_cur - sess.it_prev)
        if obs.enabled:
            obs.metrics.histogram("serve.block_wait_s").record(
                obs.clock() - t0)
        ran = it_cur - sess.it_prev
        busy_now = sess.busy_at_dispatch
        sess.it_prev = it_cur
        sess.sweeps += ran
        self.stats.sweeps += ran
        self.stats.lane_sweeps_busy += busy_now * ran
        self.stats.lane_sweeps_total += w * ran
        self.stats.sweep_blocks += 1
        if sess.sweeps > sess.guard:
            raise RuntimeError(
                f"refill pipeline exceeded {sess.guard} sweeps with "
                f"{sched.n_busy} lanes still busy")
        sess.state = cur
        defer = not (self.reuse_components and sess.has_reach)
        changed, deferred = self._process_boundary(sess, active, defer=defer)
        if (not changed and sess.stream and sched.pending
                and sched.n_busy < w):
            # a stream session may have been fed mid-flight while lanes sat
            # idle: seed them at this (quiet) block boundary instead of
            # letting new queries starve behind a deep straggler. Batch
            # drains never hit this (their pending queue only outlives a
            # fill when every lane is busy), so the sync-schedule parity of
            # run_refill_queries is untouched.
            changed = bool(self._fill(sess))
        if changed:
            # a speculative head (if any) saw a converged watched lane at
            # entry and froze (zero sweeps): drop it and redispatch from
            # the post-reseed state *before* unpacking the retired lanes,
            # so the host-side gathers run under the next block's sweeps
            sess.cur = None
            sess.head = None
            if sched.n_busy:
                watch = np.ascontiguousarray(sched.busy)
                blockfn = (sess.block_donated if sess.exclusive
                           else sess.block)
                if obs.enabled:
                    obs.trace.instant("serve.block.dispatch",
                                      busy=sched.n_busy)
                # speculative heads (`sess.block(...)` below) stay
                # unprofiled: blocking on a handle chained ahead of the
                # lagging one would defeat the very overlap it measures
                sess.cur = self.profiler.timed(
                    "block", blockfn, self.pgv, self.plan, sess.state, watch)
                sess.exclusive = False
                sess.busy_at_dispatch = sched.n_busy
            if deferred is not None:
                self._finish_boundary(sess, deferred)
        else:
            if ran == 0:
                raise RuntimeError(
                    "overlapped pipeline made no progress (no sweeps ran "
                    "and no lane retired)")
            # no retirement: the head (when speculated) is the true
            # continuation; chain the next speculative block behind it
            watch = np.ascontiguousarray(sched.busy)
            nxt = sess.head
            if nxt is None:
                nxt = sess.block(self.pgv, self.plan, cur, watch)
            sess.cur = nxt
            if obs.enabled:
                obs.trace.instant("serve.block.speculate", busy=sched.n_busy)
            sess.head = sess.block(self.pgv, self.plan, nxt, watch)
            sess.busy_at_dispatch = sched.n_busy
        return True

    # -- streaming API ------------------------------------------------------
    def submit_stream(self, queries, *, front: bool = False) -> int:
        """Feed typed queries into the continuously-fed serving stream.

        Opens a stream session on first use (the static msBFS variant --
        levels-free reachability, target support -- is picked from this
        first submission's kinds; a later submission needing a different
        variant raises, ``drain_stream`` first). Cache, component and exact
        in-session duplicate hits are resolved immediately without a lane
        (counted in ``cache_hits`` / ``component_hits`` / ``dedup_hits``)
        and delivered by the next :meth:`poll`. Returns the number of
        queries enqueued for traversal.

        ``front=True`` enqueues this submission's traversal misses *ahead*
        of the already-pending queue (batch order preserved): the
        SLO-preemption hook latency-class frontend traffic uses to claim
        the next idle lanes before queued batch-throughput queries.

        Unlike :meth:`submit_many`, this never blocks on a traversal:
        lanes are seeded and sweeps dispatched by :meth:`poll` /
        :meth:`drain_stream`, so callers interleave feeding and draining.
        """
        qs = [as_query(q) for q in queries]
        if not qs:
            return 0
        self._validate_queries(qs)
        if self._stream is not None:
            sess = self._stream
            if sess.reach_fast and any(q.kind is not QueryKind.REACHABILITY
                                       for q in qs):
                raise ValueError(
                    "stream session is specialized to levels-free "
                    "REACHABILITY; drain_stream() before submitting other "
                    "kinds")
            if not sess.cfg.enable_targets and any(
                    q.kind is QueryKind.MULTI_TARGET for q in qs):
                raise ValueError(
                    "stream session was compiled without target support; "
                    "drain_stream() before submitting MULTI_TARGET queries")
            if not sess.cfg.payload and any(
                    q.kind in PAYLOAD_KINDS for q in qs):
                raise ValueError(
                    "stream session was compiled without the payload "
                    "plane; drain_stream() before submitting WEIGHTED_SSSP "
                    "or COMPONENTS queries")
        else:
            self._stream = self._open_session(qs, stream=True)
            sess = self._stream
        self.stats.queries += len(qs)
        for q in qs:
            self.stats.note_kind(q.kind)
        obs = self.obs
        if obs.enabled:
            obs.trace.instant("serve.submit_stream", n=len(qs))
            now = obs.clock()
            for q in qs:
                # latest-submit wins: a re-submission restarts the
                # submit->deliver latency clock for its next delivery
                sess.t_submit[q] = now
        # traversal misses are collected and enqueued in one scheduler call
        # so a front=True submission lands as one contiguous run ahead of
        # the pending queue (its own order intact)
        to_seed: list = []
        seeding: set = set()
        for q in qs:
            if q in sess.seen:
                # duplicate within the session. Completed-but-undelivered
                # and in-flight/pending twins deliver once on their own; a
                # result already handed out (and released -- the session
                # keeps no delivered arrays) is re-answered from the LRU,
                # or re-enqueued when nothing holds it anymore
                self.stats.dedup_hits += 1
                if q in sess.results:
                    sess.undelivered.append(q)
                elif (q in sess.expected or q in sess.sched.pending
                      or q in seeding):
                    pass
                else:
                    hit = self.cache.get(q.key(self.graph_id))
                    if hit is not None:
                        self.stats.cache_hits += 1
                        sess.complete(q, hit, skip_cache=True)
                    else:
                        sess.cached.discard(q)   # fresh traversal recaches
                        to_seed.append(q)
                        seeding.add(q)
                        sess.n_queries_seen += 1
                continue
            sess.seen.add(q)
            hit = self.cache.get(q.key(self.graph_id))
            if hit is not None:
                self.stats.cache_hits += 1
                if obs.enabled:
                    obs.trace.instant("serve.cache.hit", source=q.source,
                                      kind=q.kind.value)
                sess.complete(q, hit, skip_cache=True)
                continue
            mask = self._component_of(q)
            if mask is not None:
                self.stats.component_hits += 1
                if obs.enabled:
                    obs.trace.instant("serve.component.hit",
                                      source=q.source)
                sess.complete(q, np.array(mask), skip_cache=True)
                continue
            if q.kind is QueryKind.REACHABILITY:
                sess.has_reach = True
            to_seed.append(q)
            seeding.add(q)
            sess.n_queries_seen += 1
        if to_seed:
            sess.sched.submit_stream(to_seed, front=front)
        return len(to_seed)

    def stream_status(self) -> dict:
        """Host-side snapshot of the stream session (all zeros when no
        session is open): ``busy`` lanes traversing now, ``pending``
        queries queued for a lane, ``undelivered`` completed results
        waiting for the next :meth:`poll`. The admission layer sizes its
        throughput-class releases off ``busy + pending`` headroom."""
        sess = self._stream
        if sess is None:
            return {"open": False, "busy": 0, "pending": 0, "undelivered": 0}
        return {"open": True, "busy": int(sess.sched.n_busy),
                "pending": len(sess.sched.pending),
                "undelivered": len(sess.undelivered)}

    def poll(self, wait: bool = True) -> dict:
        """Advance the stream by (at most) one pipeline boundary and return
        the newly completed results: {query: per-kind result}.

        ``wait=False`` never blocks: if the lagging block handle isn't
        ready yet, only already-completed results (cache/component/dedup
        hits, earlier retirements) are returned. Returned arrays are owned
        copies; completed results are cached under the engine's LRU keys.

        Delivery never depends on pipeline progress: cache/component/dedup
        hits are completed at submit time and the undelivered queue is
        drained unconditionally, so a session whose remaining work is
        exclusively hits hands everything out on a *single* non-blocking
        poll -- no spin-until-``wait=True`` (pinned in
        ``tests/test_serve_frontend.py``).
        """
        sess = self._stream
        if sess is None:
            return {}
        with self.obs.trace.span("serve.poll", wait=wait):
            if sess.sched.n_busy or sess.sched.pending:
                self._pipeline_advance(sess, wait=wait)
            return self._deliver(sess)

    def drain_stream(self) -> dict:
        """Run the stream to completion, close the session, and return
        every result not yet handed out by :meth:`poll`."""
        sess = self._stream
        if sess is None:
            return {}
        while sess.sched.n_busy or sess.sched.pending:
            self._pipeline_advance(sess)
        self._stream = None
        self._close_session(sess)
        return self._deliver(sess)

    def _deliver(self, sess: _Session) -> dict:
        """Drain the undelivered queue: O(newly completed), not O(session
        history). Each session-computed result is written to the LRU
        exactly once (submit-time memo hits never refresh a TTL), then
        *released* from the session -- a long-lived stream stays
        O(in-flight) in host memory, not O(every query ever streamed);
        later re-submissions are answered from the LRU or re-traversed."""
        own = lambda r: dict(r) if isinstance(r, dict) else np.array(r)
        obs = self.obs
        out = {}
        while sess.undelivered:
            q = sess.undelivered.popleft()
            if q in out:
                continue
            res = sess.results.pop(q, None)
            if res is None:
                continue            # stale queue entry: delivered earlier
            if q not in sess.cached:
                self.cache.put(q.key(self.graph_id), res)
                sess.cached.add(q)
            if obs.enabled:
                ts = sess.t_submit.pop(q, None)
                if ts is not None:
                    self._record_latency(q.kind, obs.clock() - ts)
            out[q] = own(res)
        if out and obs.enabled:
            self._export_stats()
        return out

    # -- public API ---------------------------------------------------------
    def submit_many(self, queries) -> list:
        """Per-kind results for each query (raw ints coerce to LEVELS).

        Duplicate and cached queries cost nothing extra; only unique misses
        occupy lanes.
        """
        qs = [as_query(q) for q in queries]
        if not qs:
            return []
        self._validate_queries(qs)
        obs = self.obs
        t0 = obs.clock() if obs.enabled else 0.0
        self.stats.queries += len(qs)
        for q in qs:
            self.stats.note_kind(q.kind)
        results: dict = {}
        misses: list = []
        for q in dict.fromkeys(qs):  # dedup, keep order
            hit = self.cache.get(q.key(self.graph_id))
            if hit is not None:
                self.stats.cache_hits += 1
                if obs.enabled:
                    obs.trace.instant("serve.cache.hit", source=q.source,
                                      kind=q.kind.value)
                results[q] = hit
                continue
            memo = self._component_of(q)
            if memo is not None:   # mapped component (or label map known)
                self.stats.component_hits += 1
                if obs.enabled:
                    obs.trace.instant("serve.component.hit",
                                      source=q.source)
                results[q] = np.array(memo)
                continue
            misses.append(q)
        if obs.enabled:
            obs.trace.instant("serve.submit_many", n=len(qs),
                              misses=len(misses))
        if self.refill:
            served = self.run_refill_queries(misses)
        else:
            served = {}
            remaining = list(misses)
            while remaining:
                if self.reuse_components:
                    # components mapped by earlier batches answer later
                    # reachability misses without a lane
                    still = []
                    for q in remaining:
                        mask = self._component_of(q)
                        if mask is None:
                            still.append(q)
                        else:
                            served[q] = np.array(mask)
                            self.stats.component_hits += 1
                    remaining = still
                    if not remaining:
                        break
                batch = remaining[: self.cfg.n_queries]
                remaining = remaining[self.cfg.n_queries:]
                batch_res = self.run_batch_queries(batch)
                for q, res in batch_res.items():
                    self._register_component(q, res)
                served.update(batch_res)
        for q, res in served.items():
            results[q] = res
            self.cache.put(q.key(self.graph_id), res)
        if obs.enabled:
            # a blocking submit delivers everything at once: one
            # submit->deliver latency sample per query, bucketed per kind
            dt = obs.clock() - t0
            for q in qs:
                self._record_latency(q.kind, dt)
            self._export_stats()
        # hand out copies: the same object is cached (and shared by
        # duplicate queries), so caller mutation must never reach it
        own = lambda r: dict(r) if isinstance(r, dict) else np.array(r)
        return [own(results[q]) for q in qs]

    def submit(self, query):
        """One typed query -> its per-kind result."""
        return self.submit_many([query])[0]

    def query(self, sources) -> np.ndarray:
        """Full levels for each source: [len(sources), n] int32 (classic
        API; sugar over LEVELS-kind ``submit_many``)."""
        sources = np.asarray(sources, dtype=np.int64).reshape(-1)
        if sources.size == 0:
            return np.zeros((0, self.pg.n), dtype=np.int32)
        return np.stack(self.submit_many([int(s) for s in sources]))

    def query_one(self, source: int) -> np.ndarray:
        return self.query([source])[0]

    def sample_khop(self, source: int, k: int, sampler):
        """Serve a ``KHOP_SAMPLE`` query and feed its node pool straight
        into a :class:`repro.graphs.sampler.NeighborSampler`: the traversal
        engine finds the k-hop seed pool (cached under the typed key like
        any other query), the sampler draws the fanout-capped minibatch --
        one traversal substrate under both the serving and GNN stacks."""
        pool = self.submit(Query(int(source), kind=QueryKind.KHOP_SAMPLE,
                                 max_depth=int(k)))
        return sampler.sample(pool)

    def warmup(self, reachability: bool = False, targets: bool = False,
               payload: bool = False, queries=None) -> None:
        """Compile the runners the configured driver dispatches (vertex 0
        as a throwaway source): the fused while-loop for batch engines;
        the single-step runner for the per-sweep refill driver; the fused
        k-sweep block for the overlapped one. Refill engines also compile
        the lane reseed, and both donating siblings the drivers use on a
        state nothing else holds.

        By default only the target-free levels variant (the common serving
        case) is compiled; ``targets=True`` adds the multi-target variant,
        ``reachability=True`` the levels-free reachability one, and
        ``payload=True`` the payload-plane (WEIGHTED_SSSP / COMPONENTS)
        one. ``queries`` instead compiles exactly the one variant that a
        batch or drain of those queries runs."""
        if queries is not None:
            cfgs = [self._session_cfg([as_query(q) for q in queries])]
        else:
            cfgs = [_dc_replace(self.cfg, enable_targets=False)]
            if targets:
                cfgs.append(self.cfg)
            if reachability and self.specialize_reachability:
                cfgs.append(_dc_replace(self.cfg, track_levels=False,
                                        enable_targets=False))
            if payload:
                cfgs.append(self._payload_cfg(
                    _dc_replace(self.cfg, enable_targets=False)))
                if targets:        # mixed sessions carrying both planes
                    cfgs.append(self._payload_cfg(self.cfg))
        with self.obs.trace.span("serve.warmup", variants=len(cfgs)):
            for cfg in cfgs:
                run_full, step_once = self._runner_pair(cfg)
                fresh = lambda: self._put(
                    M.init_multi_state(self.pg, [0], cfg))
                st = fresh()
                if not self.refill:
                    run_full(self.pgv, self.plan, st)
                    continue
                desc = [jnp.asarray(x) for x in
                        self._seed_descriptors([], payload=cfg.payload)]
                M.reseed_lanes(st, *desc)
                M.reseed_lanes_donated(fresh(), *desc)
                if self.overlap:
                    # all-ones watch with only lane 0 active: the block's
                    # stop condition fires at entry, so this compiles the
                    # fused loop without running sweeps
                    watch = np.ones(self.cfg.n_queries, dtype=bool)
                    block, block_donated = self._block_pair(cfg)
                    block(self.pgv, self.plan, st, watch)
                    block_donated(self.pgv, self.plan, fresh(), watch)
                else:
                    step_once(self.pgv, self.plan, st)
