"""Batched multi-source BFS (msBFS) over the four-subgraph representation.

The paper's communication model carries **1 bit of visited status per
vertex** -- a global bitmask OR-reduction for delegates and point-to-point
exchange of newly visited normal vertices.  That model generalizes for free
to ``W`` concurrent, independent BFS queries by widening each bit to a
W-bit **lane word**: lane ``q`` of vertex ``v``'s word is query ``q``'s
visited/frontier bit (the compression insight of multi-GPU msBFS work,
arXiv:1704.00513, applied to the bitmap frontier of arXiv:1104.4518).

Every traversal sweep, every delegate all-reduce, and every nn all_to_all
is then amortized over the whole batch:

* **push** is a scatter-OR of lane words along edges (one gather + one
  scatter for all W queries); along nn edges, whose destinations the
  exchange plan sorts into contiguous per-slot runs, it is a scatter-free
  segmented OR of packed uint32 lane words over those runs;
* **pull** is the chunked parent scan with *word-OR early exit*: a row
  drops out of the scan as soon as the accumulated parent word covers all
  of its still-unvisited lanes;
* **delegate reduction** packs the candidate lanes to ``[d, n_words]``
  uint32 and runs one global bitwise-OR combine through the pluggable
  strategy layer (:func:`repro.core.comm.delegate_combine`: allgather-fold
  / ppermute ring / two-level hierarchical, per ``MSBFSConfig(comm=...)``);
* **nn exchange** reuses the static :class:`~repro.core.engine.ExchangePlan`
  slot layout and ships one uint32 word per 32 queries per unique
  (owner, local) slot -- ``cap_total * n_words * 4`` bytes of a2a volume,
  ~1 bit/query/slot, with no runtime sort; small-frontier sweeps can
  instead ship capped (slot id, word) pairs, switched per sweep by the
  frontier-adaptive format (``CommConfig(nn="adaptive")``);
* **wire accounting**: every sweep records the bytes each collective put
  on the wire (``MSBFSState.wire_delegate`` / ``wire_nn``), threaded up
  through ``ServeStats`` and ``benchmarks/comm_model.py --strategies``;
* **direction optimization** is decided *per lane* from per-lane FV/BV
  estimates (frontier out-degree sums and unvisited counts computed by
  masked popcounts), so a query in its high-frontier middle iterations can
  pull while a late straggler query in the same batch still pushes.

On device the lane axis is kept as trailing bools (vectorized compute);
packing to uint32 happens at the two communication boundaries, so the wire
format matches the paper's Section V accounting, and in the nn slot scan,
whose per-edge words it shrinks from W bytes to 4 per 32 lanes.

**Typed queries.** Each lane additionally carries query parameters so the
serving layer can compile richer query shapes onto the same substrate
(``repro.serve.queries``):

* a per-lane **depth cap** (``MSBFSState.depth_cap``) folds into the
  frontier gate: a lane past its cap contributes no frontier anywhere --
  push gather, pull scan, nn exchange and delegate candidates all drop out
  the same sweep (the bookkeeping-cutting observation of arXiv:1104.4518);
* per-lane **target words** (``target_n`` / ``target_d``): a multi-target
  lane latches ``lane_stop`` the sweep its last unvisited target is
  marked, and retires through the same ``lane_active`` convergence word the
  refill scheduler already watches;
* a **reachability-only mode** (``MSBFSConfig(track_levels=False)``, legal
  when every lane in the batch is a reachability query): level arrays are
  replaced by bool visited words plus an explicit frontier word -- no level
  scatter, no ``it`` arithmetic, no per-edge work counters, pure lane
  words end to end.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import comm
from .bfs import _decide_direction, _row_degrees
from .types import CSR, INF_LEVEL, PartitionedGraph, PartitionLayout
from .weights import edge_weights

# Sentinel per-lane depth cap meaning "unlimited" (any reachable depth is
# < max_iters << NO_DEPTH_CAP, so the gate `depth < cap` never fires).
NO_DEPTH_CAP = np.int32(INF_LEVEL)

# The per-lane payload combine identity (min/min_plus specs): +inf in the
# min semiring. Equal to INF_LEVEL by construction, so "unreached" means
# the same thing in the level and payload planes.
PAY_IDENT = np.int32(comm.COMBINE_SPECS["min_plus"].identity)

# Lane-word packing lives with the wire formats in the comm package;
# re-exported here because every msBFS caller packs/unpacks through this
# module's namespace.
from .comm import n_words, pack_lanes, unpack_lanes  # noqa: E402,F401


# -----------------------------------------------------------------------------
# Config / state


@dataclass(frozen=True)
class MSBFSConfig:
    n_queries: int = 32     # W: concurrent BFS queries per batch
    max_iters: int = 64
    enable_do: bool = True
    pull_chunk: int = 32
    # per-lane direction-switch factors, order (dd, dn, nd) as in BFSConfig
    factor0: tuple = (0.5, 0.05, 1e-7)
    factor1: tuple = (1e-3, 1e-4, 1e-9)
    # False compiles the reachability-only variant: bool visited words +
    # explicit frontier words instead of int32 levels (legal only when no
    # lane in the batch needs hop distances).
    track_levels: bool = True
    # False compiles away the per-sweep multi-target coverage scan (the
    # [n_local, W] target-word pass and its extra reduce word) for batches
    # with no MULTI_TARGET lane; seeding targets then raises.
    enable_targets: bool = True
    # Route the chunked pull through the dispatching ELL kernel wrapper
    # (`repro.kernels.ops.ell_pull_multi`) on packed lane words instead of
    # the native bool-lane gather. None = native; "ref" / "pallas" pin the
    # dispatch target; "auto" lets the wrapper pick per backend.
    kernel_pull: str | None = None
    # Communication strategies (repro.core.comm.CommConfig): how the
    # delegate lane words are combined (allgather-fold / ring / two-level
    # hierarchical, optionally folding through the mask_reduce kernel) and
    # which wire format the nn exchange ships (dense slot words / sparse
    # capped id+word pairs / the per-sweep frontier-adaptive switch). The
    # default reproduces the seed behavior bit-for-bit.
    comm: comm.CommConfig = comm.CommConfig()
    # Out-of-core sweep mode (ROADMAP item 2): > 0 streams the bool push
    # scatters and the payload plane through a ``lax.scan`` over
    # fixed-size edge blocks and row-blocks the pull scan
    # (``edge_chunk // pull_chunk`` rows per block), so peak sweep memory
    # is O(edge_chunk * W) instead of O(E_max * W) -- a partition whose
    # decoded [E, W] working set exceeds device memory still traverses.
    # The nn slot words are not chunked: their segmented OR holds one
    # packed uint32 word per 32 lanes per edge (:func:`_nn_slots_multi`).
    # Bit-identical to the monolithic path by construction: scatter-OR is
    # order-independent, each pull row's early exit and work count depend
    # only on that row, and all counters are exact int32 sums -- chunking
    # may only change memory, never answers or schedule (pinned in
    # tests/test_compression.py). 0 (the default) = monolithic.
    edge_chunk: int = 0
    # True carries the device-plane sweep-telemetry arrays (``tm_*`` fields
    # of MSBFSState: per-sweep per-shard frontier popcounts and packed
    # direction-decision words) through the state. The telemetry writes are
    # pure extra accumulation into their own buffers -- the traversal
    # schedule, every answer and every ServeStats counter stay bit-identical
    # (pinned in tests/test_device_telemetry.py). False (the default) keeps
    # zero-size dummies in the carry, so the disabled path compiles the
    # telemetry away entirely.
    telemetry: bool = False
    # True carries the per-lane small-int *payload plane* through the state
    # ([n_local, W] / [d, W] int32 + pending words) and runs the min-combine
    # sweep branch alongside the bit-word one: weighted SSSP (min_plus over
    # synthetic edge weights, delta-stepping buckets folded into the sweep
    # loop) and connected components (min-label propagation, an INF-bucket
    # degenerate of the same branch). Per-lane dynamic flags
    # (``pay_weighted`` / ``pay_delta`` / seed-all at reseed) pick the kind,
    # so one compiled variant serves both, mixed freely with bit lanes in
    # the same W-word. False (the default) keeps zero-width ``[.., 0]``
    # payload dummies in the carry -- the same compile-away contract as
    # ``telemetry``: the bit-only schedule and every counter stay
    # bit-identical to the pre-payload substrate.
    payload: bool = False


@dataclass
class MSBFSState:
    """Lane-word traversal state.

    Levels are stored *absolute*: a lane seeded at global iteration ``b``
    records its sources at value ``b`` (``base_it``) and depth-k vertices at
    ``b + k``, so the shared frontier test ``level == it`` needs no per-lane
    offset arithmetic on the hot path. :func:`gather_levels_multi` subtracts
    ``base_it`` when unpacking -- that is what makes mid-flight lane refill
    (retire a converged lane, reseed it with a fresh query at the current
    ``it``) a pure state edit with no change to the sweep.
    """

    level_n: Any     # [p, n_local, W] int32 (absolute: base_it[q] + depth);
                     # bool visited words when cfg.track_levels is False
    level_d: Any     # [p, d, W] int32 (replicated content); bool in
                     # reachability-only mode
    backward: Any    # [p, 3, W] bool -- per-lane direction per (dd, dn, nd)
    it: Any          # [p] int32
    done: Any        # [p] bool
    lane_active: Any  # [p, W] bool -- lane's frontier non-empty at `it`
                      # (replicated; the refill retirement signal)
    base_it: Any     # [p, W] int32 -- iteration the lane was (re)seeded at
    # typed-query per-lane parameters (repro.serve.queries):
    lane_stop: Any   # [p, W] bool -- latched early-exit (cap / targets hit)
    depth_cap: Any   # [p, W] int32 -- max hop depth (NO_DEPTH_CAP = none)
    has_targets: Any  # [p, W] bool -- lane retires once targets are covered
    target_n: Any    # [p, n_local, W] bool -- target marks (owner partition)
    target_d: Any    # [p, d, W] bool -- target marks (replicated)
    # reachability-only mode frontier words ([p, 1, 1] dummies otherwise):
    frontier_n: Any  # [p, n_local, W] bool
    frontier_d: Any  # [p, d, W] bool
    # per-iteration statistics [p, max_iters]:
    work_fwd: Any    # edge-lane pairs examined by pushes
    work_bwd: Any    # parent-word checks by pulls
    nn_sent: Any     # active (slot, lane) pairs signalled in the nn exchange
    delegate_round: Any  # 1 if the delegate reduction carried updates
    # wire-volume accounting [p, max_iters] int32 (accumulated with .add,
    # so refill sessions running past max_iters keep exact totals in the
    # last slot). Per-device bytes put on the wire; summing the partition
    # rows gives total cluster traffic (comm/base.py byte convention):
    wire_delegate: Any   # delegate-combine bytes per sweep
    wire_nn: Any         # nn-exchange bytes per sweep
    nn_sparse: Any       # 1 if the sweep shipped the sparse nn format
    nn_overflow: Any     # active slots dropped by a pinned-sparse cap
                         # (must be 0 for a valid run; adaptive never drops)
    # device-plane sweep telemetry (cfg.telemetry; zero-size [p, 0, ...]
    # dummies otherwise so the disabled carry compiles away). Frontier
    # popcounts accumulate with .add (wire-counter convention: refill
    # sessions past max_iters keep exact totals in the last slot); the
    # packed direction words record the last decision per slot:
    tm_frontier_n: Any   # [p, max_iters] int32 -- per-shard expand-gated
                         # normal-frontier popcount per sweep
    tm_frontier_d: Any   # [p, max_iters] int32 -- delegate-frontier
                         # popcount (content replicated across shards)
    tm_backward: Any     # [p, max_iters, 3, n_words(W)] uint32 -- the
                         # per-lane (dd, dn, nd) pull decisions, packed
    # per-lane payload plane (cfg.payload; zero-width [.., 0] dummies
    # otherwise -- the telemetry compile-away contract). Values are
    # absolute small ints under the min combine (SSSP distances /
    # component labels), PAY_IDENT = +inf = "unreached"; ``pending`` marks
    # vertices whose payload improved and has not been expanded yet
    # (label-correcting worklist); ``pay_bucket`` is the delta-stepping
    # threshold gating expansion (INF for components = plain min-label
    # propagation), ``pay_delta`` the per-lane bucket width, ``pay_weighted``
    # whether pushes add the synthetic edge weight (SSSP) or 0 (labels):
    payload_n: Any       # [p, n_local, Wp] int32
    payload_d: Any       # [p, d, Wp] int32 (replicated content)
    pay_pending_n: Any   # [p, n_local, Wp] bool
    pay_pending_d: Any   # [p, d, Wp] bool
    pay_bucket: Any      # [p, Wp] int32
    pay_delta: Any       # [p, Wp] int32
    pay_weighted: Any    # [p, Wp] bool
    # payload wire accounting [p, max_iters] int32 ([p, 0] when disabled),
    # same .add convention as wire_delegate / wire_nn:
    wire_pay_delegate: Any   # payload delegate-combine bytes per sweep
    wire_pay_nn: Any         # payload nn-exchange bytes per sweep


jax.tree_util.register_dataclass(
    MSBFSState,
    data_fields=("level_n", "level_d", "backward", "it", "done",
                 "lane_active", "base_it",
                 "lane_stop", "depth_cap", "has_targets",
                 "target_n", "target_d", "frontier_n", "frontier_d",
                 "work_fwd", "work_bwd", "nn_sent", "delegate_round",
                 "wire_delegate", "wire_nn", "nn_sparse", "nn_overflow",
                 "tm_frontier_n", "tm_frontier_d", "tm_backward",
                 "payload_n", "payload_d", "pay_pending_n", "pay_pending_d",
                 "pay_bucket", "pay_delta", "pay_weighted",
                 "wire_pay_delegate", "wire_pay_nn"),
    meta_fields=(),
)


def validate_sources(pg: PartitionedGraph, sources) -> np.ndarray:
    """Flatten to int64 and range-check source vertex ids."""
    sources = np.asarray(sources, dtype=np.int64).reshape(-1)
    if sources.size and ((sources < 0).any() or (sources >= pg.n).any()):
        bad = sources[(sources < 0) | (sources >= pg.n)]
        raise ValueError(f"source ids out of range [0, {pg.n}): {bad[:8].tolist()}")
    return sources


def locate_source(pg: PartitionedGraph, layout: PartitionLayout,
                  dvids: np.ndarray, src: int):
    """Host-side seed coordinates for one source vertex.

    Returns ``(is_delegate, part, local, dpos)``: a delegate source seeds
    position ``dpos`` of the replicated delegate levels; a normal source
    seeds ``(part, local)`` of the owner partition. Shared by
    :func:`init_multi_state` and the serve engine's refill reseeding so the
    delegate classification can never diverge between the two. ``dvids``
    must hold exactly the ``pg.d`` real delegate ids (empty on a
    delegate-free graph) -- padded entries here would misclassify."""
    pos = int(np.searchsorted(dvids, src))
    if pos < dvids.size and dvids[pos] == src:
        return True, 0, 0, pos
    return (False, int(layout.part_of(np.int64(src))),
            int(layout.local_of(np.int64(src))), 0)


def init_multi_state(
    pg: PartitionedGraph, sources: Sequence[int], cfg: MSBFSConfig,
    *, depth_caps: Sequence | None = None, targets: Sequence | None = None,
    payload_modes: Sequence | None = None,
) -> MSBFSState:
    """Seed one lane per source. Fewer than ``n_queries`` sources leaves the
    tail lanes unseeded (a partial batch): they stay at INF_LEVEL and never
    contribute work.

    ``depth_caps`` (aligned with ``sources``) gives lane ``q`` a max hop
    depth (``None`` entries = unlimited); ``targets`` gives lane ``q`` a
    sequence of target vertex ids (``None`` / empty = none) -- the lane
    retires the sweep all of its targets are visited.

    ``payload_modes`` (aligned with ``sources``; requires ``cfg.payload``)
    turns lane ``q`` into a payload lane instead of a bit lane: ``"sssp"``
    seeds payload 0 at the source with delta-stepping buckets over the
    synthetic edge weights; ``"components"`` seeds every valid vertex with
    its own global id under plain min-label propagation (INF bucket). A
    payload lane's bit columns stay empty (inert in the bit machinery);
    ``None`` entries are ordinary bit lanes."""
    w = cfg.n_queries
    sources = validate_sources(pg, sources)
    if sources.size > w:
        raise ValueError(f"{sources.size} sources > n_queries={w}")
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    p, nl = pg.p, pg.n_local
    d = max(pg.d, 1)
    # exactly pg.d real delegate ids: on a delegate-free graph this must be
    # *empty*, never one bogus padded id (the replicated delegate arrays
    # still pad to max(d, 1) for static shapes, but classification may only
    # ever consult real ids)
    dvids = np.asarray(pg.delegate_vids).reshape(-1)[: pg.d]
    if cfg.track_levels:
        level_n = np.full((p, nl, w), INF_LEVEL, dtype=np.int32)
        level_d = np.full((p, d, w), INF_LEVEL, dtype=np.int32)
        frontier_n = np.zeros((p, 1, 1), dtype=bool)
        frontier_d = np.zeros((p, 1, 1), dtype=bool)
    else:
        level_n = np.zeros((p, nl, w), dtype=bool)     # visited words
        level_d = np.zeros((p, d, w), dtype=bool)
        frontier_n = np.zeros((p, nl, w), dtype=bool)
        frontier_d = np.zeros((p, d, w), dtype=bool)
    # per-lane payload plane (zero-width when cfg.payload is off)
    wp = w if cfg.payload else 0
    payload_n = np.full((p, nl, wp), PAY_IDENT, dtype=np.int32)
    payload_d = np.full((p, d, wp), PAY_IDENT, dtype=np.int32)
    pay_pending_n = np.zeros((p, nl, wp), dtype=bool)
    pay_pending_d = np.zeros((p, d, wp), dtype=bool)
    pay_bucket = np.full((p, wp), PAY_IDENT, dtype=np.int32)
    pay_delta = np.full((p, wp), PAY_IDENT, dtype=np.int32)
    pay_weighted = np.zeros((p, wp), dtype=bool)
    modes = list(payload_modes) if payload_modes is not None else []
    modes += [None] * (len(sources) - len(modes))
    if any(m is not None for m in modes) and not cfg.payload:
        raise ValueError("payload_modes given but cfg.payload is False")
    for q, src in enumerate(sources):
        isd, part, local, dpos = locate_source(pg, layout, dvids, int(src))
        mode = modes[q]
        if mode is not None:
            # payload lane: bit columns stay empty; seed the payload plane
            from .weights import SSSP_DELTA
            if mode == "sssp":
                if isd:
                    payload_d[:, dpos, q] = 0
                    pay_pending_d[:, dpos, q] = True
                else:
                    payload_n[part, local, q] = 0
                    pay_pending_n[part, local, q] = True
                pay_bucket[:, q] = np.int32(SSSP_DELTA)
                pay_delta[:, q] = np.int32(SSSP_DELTA)
                pay_weighted[:, q] = True
            elif mode == "components":
                valid = np.asarray(pg.normal_valid)              # [p, nl]
                for k in range(p):
                    gids = layout.global_of(np.full(nl, k), np.arange(nl))
                    payload_n[k, valid[k], q] = gids[valid[k]].astype(np.int32)
                pay_pending_n[:, :, q] = valid
                if pg.d:
                    payload_d[:, : pg.d, q] = dvids.astype(np.int32)[None, :]
                    pay_pending_d[:, : pg.d, q] = True
            else:
                raise ValueError(f"unknown payload mode {mode!r}")
            continue
        if isd:
            level_d[:, dpos, q] = 0 if cfg.track_levels else True
            if not cfg.track_levels:
                frontier_d[:, dpos, q] = True
        else:
            level_n[part, local, q] = 0 if cfg.track_levels else True
            if not cfg.track_levels:
                frontier_n[part, local, q] = True
    depth_cap = np.full((p, w), NO_DEPTH_CAP, dtype=np.int32)
    if depth_caps is not None:
        for q, cap in enumerate(depth_caps):
            if cap is not None:
                depth_cap[:, q] = np.int32(cap)
    target_n = np.zeros((p, nl, w), dtype=bool)
    target_d = np.zeros((p, d, w), dtype=bool)
    has_targets = np.zeros((p, w), dtype=bool)
    if targets is not None:
        for q, tgts in enumerate(targets):
            if tgts is None or len(tgts) == 0:
                continue
            if not cfg.enable_targets:
                raise ValueError(
                    "targets given but cfg.enable_targets is False")
            has_targets[:, q] = True
            for t in validate_sources(pg, tgts):
                isd, part, local, dpos = locate_source(pg, layout, dvids, int(t))
                if isd:
                    target_d[:, dpos, q] = True
                else:
                    target_n[part, local, q] = True
    mi = cfg.max_iters
    z = lambda: np.zeros((p, mi), dtype=np.int32)
    # telemetry carry: real [p, mi]-shaped buffers only when asked for;
    # zero-size otherwise (the same compile-away trick as the reachability
    # dummies above, taken to its limit -- XLA carries nothing)
    tmi = mi if cfg.telemetry else 0
    tm_frontier_n = np.zeros((p, tmi), dtype=np.int32)
    tm_frontier_d = np.zeros((p, tmi), dtype=np.int32)
    tm_backward = np.zeros((p, tmi, 3, n_words(w)), dtype=np.uint32)
    lane_active = np.zeros((p, w), dtype=bool)
    lane_active[:, : sources.size] = True
    return MSBFSState(
        level_n=level_n, level_d=level_d,
        backward=np.zeros((p, 3, w), dtype=bool),
        it=np.zeros((p,), dtype=np.int32),
        done=np.zeros((p,), dtype=bool),
        lane_active=lane_active,
        base_it=np.zeros((p, w), dtype=np.int32),
        lane_stop=np.zeros((p, w), dtype=bool),
        depth_cap=depth_cap,
        has_targets=has_targets,
        target_n=target_n, target_d=target_d,
        frontier_n=frontier_n, frontier_d=frontier_d,
        work_fwd=z(), work_bwd=z(), nn_sent=z(), delegate_round=z(),
        wire_delegate=z(), wire_nn=z(), nn_sparse=z(), nn_overflow=z(),
        tm_frontier_n=tm_frontier_n, tm_frontier_d=tm_frontier_d,
        tm_backward=tm_backward,
        payload_n=payload_n, payload_d=payload_d,
        pay_pending_n=pay_pending_n, pay_pending_d=pay_pending_d,
        pay_bucket=pay_bucket, pay_delta=pay_delta,
        pay_weighted=pay_weighted,
        wire_pay_delegate=np.zeros((p, mi if cfg.payload else 0), np.int32),
        wire_pay_nn=np.zeros((p, mi if cfg.payload else 0), np.int32),
    )


# -----------------------------------------------------------------------------
# Lane-word traversal primitives


def _push_active_multi(csr: CSR, frontier_rows: jnp.ndarray) -> jnp.ndarray:
    """Per-edge active lane words: [E, W] bool (frontier gather)."""
    w = frontier_rows.shape[-1]
    f_ext = jnp.concatenate(
        [frontier_rows, jnp.zeros((1, w), frontier_rows.dtype)])
    return f_ext[csr.rowids]


def _push_scatter_multi(csr: CSR, act: jnp.ndarray, n_dst: int) -> jnp.ndarray:
    """Scatter-OR of active lane words onto the destination domain."""
    out = jnp.zeros((n_dst, act.shape[-1]), dtype=jnp.bool_)
    return out.at[csr.cols].max(act, mode="drop")


def _push_multi(csr: CSR, frontier_rows: jnp.ndarray, n_dst: int,
                edge_chunk: int = 0) -> jnp.ndarray:
    """Fused push: frontier gather + scatter-OR in one step.

    ``edge_chunk > 0`` streams fixed-size edge blocks through a
    ``lax.scan`` instead of materializing the [E, W] active array: peak
    memory O(edge_chunk * W). Bit-identical to the monolithic path --
    scatter-OR is order-independent (padding edges carry rowid = n_rows,
    whose extended-frontier row is all False, so they scatter nothing).
    """
    w = frontier_rows.shape[-1]
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        return _push_scatter_multi(
            csr, _push_active_multi(csr, frontier_rows), n_dst)
    f_ext = jnp.concatenate(
        [frontier_rows, jnp.zeros((1, w), frontier_rows.dtype)])
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids, (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    col = jnp.pad(csr.cols, (0, pad)).reshape(nblk, edge_chunk)

    def body(out, blk):
        r, c = blk
        return out.at[c].max(f_ext[r], mode="drop"), None

    out, _ = lax.scan(body, jnp.zeros((n_dst, w), jnp.bool_), (rid, col))
    return out


# XLA's TPU gather costs about 8.6 ns an index; issued as a loop over
# blocks of this many indices it costs about 7.4 ns (TPU v5e, 33.5M indices)
GATHER_BLOCK = 1 << 18


def _gather_blocks(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for 1-D ``idx``, issued in blocks of ``GATHER_BLOCK``
    indices (the tail block padded with index 0, its extra reads dropped)."""
    e = idx.shape[0]
    if e <= GATHER_BLOCK:
        return table[idx]
    nb = -(-e // GATHER_BLOCK)
    blocks = jnp.pad(idx, (0, nb * GATHER_BLOCK - e)).reshape(nb, GATHER_BLOCK)
    return lax.map(lambda b: table[b], blocks).reshape(-1)[:e]


def nn_scan_steps(max_run: int) -> int:
    """Doubling steps of :func:`_nn_slots_multi`'s segmented OR for runs of
    at most ``max_run`` edges: ``ceil(log2(max_run))``."""
    return (int(max_run) - 1).bit_length()


def _nn_slots_multi(frontier_rows: jnp.ndarray, plan):
    """Sender-side unique-slot lane words for the nn exchange.

    Returns ``(sa [cap_total, W] bool, act_sum int32)`` where ``act_sum``
    is the total active (edge, lane) count -- exactly
    ``jnp.sum(_push_active_multi(nn, frontier_rows))``, the nn term of
    ``work_fwd``.

    No scatter: the plan sorts each partition's nn edges by slot, so a
    slot's edges are one contiguous run of ``plan.seg_ids`` and its lane
    word is a segmented OR over that run. The frontier is packed to
    ``n_words(W)`` uint32 words a row; each slot-sorted edge gathers its
    source row's words (``plan.src_rows``; padding edges read the appended
    zero row), and ``act_sum`` is their popcount. A Hillis-Steele
    inclusive scan of ``ceil(log2(plan.max_run))`` doubling steps ORs into
    each edge the word of the edge ``k`` back when both lie in one run,
    which is exact because runs are contiguous; each slot then reads its
    run's last edge (``plan.seg_end``; slots past the partition's unique
    count read the appended zero word). The words stay ``nw`` separate
    ``[E]`` vectors, 4 bytes an edge each: not chunked by ``edge_chunk``.
    """
    w = frontier_rows.shape[-1]
    words = pack_lanes(frontier_rows)                          # [nl, nw]
    words = jnp.concatenate(
        [words, jnp.zeros((1, words.shape[-1]), jnp.uint32)])
    seg = plan.seg_ids
    cols, act_sum = [], jnp.int32(0)
    for i in range(words.shape[-1]):
        x = _gather_blocks(words[:, i], plan.src_rows)         # [E] uint32
        act_sum += jnp.sum(lax.population_count(x).astype(jnp.int32))
        for k in (1 << j for j in range(nn_scan_steps(plan.max_run))):
            same = jnp.concatenate(
                [jnp.zeros((k,), bool), seg[k:] == seg[:-k]])
            prev = jnp.concatenate([jnp.zeros((k,), jnp.uint32), x[:-k]])
            x = x | jnp.where(same, prev, jnp.uint32(0))
        x = jnp.concatenate([x, jnp.zeros((1,), jnp.uint32)])
        cols.append(_gather_blocks(x, plan.seg_end))           # [cap_total]
    return unpack_lanes(jnp.stack(cols, axis=-1), w), act_sum


def _push_payload(csr: CSR, front: jnp.ndarray, pay_rows: jnp.ndarray,
                  gid_rows: jnp.ndarray, gid_cols: jnp.ndarray, n_dst: int,
                  wsel: jnp.ndarray, edge_chunk: int = 0) -> jnp.ndarray:
    """Min-plus push: scatter-min of ``payload[src] + weight`` onto the
    destination domain -- the payload sibling of :func:`_push_multi` under
    the ``min_plus`` combine spec.

    ``front [R, W]`` gates which (row, lane) pairs relax; ``gid_rows [R]`` /
    ``gid_cols [n_dst]`` are the global ids the synthetic edge weight is
    hashed from; ``wsel [W]`` picks which lanes add the weight (SSSP) vs 0
    (min-label components). Non-participating pairs carry the identity, and
    identity + weight >= identity, so padding edges and gated lanes are
    scatter no-ops by construction. ``edge_chunk > 0`` streams fixed-size
    edge blocks exactly like the bit push (scatter-min is
    order-independent: memory only, never values)."""
    w = front.shape[-1]
    ident = jnp.int32(PAY_IDENT)
    vals_rows = jnp.where(front, pay_rows, ident)
    v_ext = jnp.concatenate([vals_rows, jnp.full((1, w), ident, jnp.int32)])
    g_ext = jnp.concatenate(
        [gid_rows.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
    gid_cols = gid_cols.astype(jnp.int32)
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        we = edge_weights(g_ext[csr.rowids],
                          gid_cols[jnp.clip(csr.cols, 0, n_dst - 1)])
        vals = v_ext[csr.rowids] + jnp.where(wsel[None, :], we[:, None], 0)
        out = jnp.full((n_dst, w), ident, jnp.int32)
        return out.at[csr.cols].min(vals, mode="drop")
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids, (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    col = jnp.pad(csr.cols, (0, pad)).reshape(nblk, edge_chunk)

    def body(out, blk):
        r, c = blk
        we = edge_weights(g_ext[r], gid_cols[jnp.clip(c, 0, n_dst - 1)])
        vals = v_ext[r] + jnp.where(wsel[None, :], we[:, None], 0)
        return out.at[c].min(vals, mode="drop"), None

    out, _ = lax.scan(body, jnp.full((n_dst, w), ident, jnp.int32),
                      (rid, col))
    return out


def _nn_slots_payload(csr: CSR, front_n: jnp.ndarray, pay_n: jnp.ndarray,
                      gid_rows: jnp.ndarray, dst_gid_e: jnp.ndarray, plan,
                      wsel: jnp.ndarray, edge_chunk: int = 0) -> jnp.ndarray:
    """Sender-side per-slot payload minimums for the nn payload exchange:
    the min-combine sibling of :func:`_nn_slots_multi`. Edges sharing a
    unique (owner, local) slot pre-fold with min *after* adding each edge's
    own weight (weights differ per source even at a shared destination,
    so the fold cannot happen receiver-side). ``dst_gid_e [E]`` is the
    per-edge destination global id in original edge order
    (``global_of(nn_owner, nn.cols)``); padding edges land in the trash
    segment the slice drops. Returns ``[cap_total, W] int32``."""
    w = front_n.shape[-1]
    ident = jnp.int32(PAY_IDENT)
    vals_rows = jnp.where(front_n, pay_n, ident)
    v_ext = jnp.concatenate([vals_rows, jnp.full((1, w), ident, jnp.int32)])
    g_ext = jnp.concatenate(
        [gid_rows.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
    if edge_chunk <= 0 or edge_chunk >= csr.e_max:
        rid_p = csr.rowids[plan.perm]
        we = edge_weights(g_ext[rid_p], dst_gid_e[plan.perm])
        vals = v_ext[rid_p] + jnp.where(wsel[None, :], we[:, None], 0)
        return jnp.full((plan.cap_total + 1, w), ident, jnp.int32).at[
            plan.seg_ids].min(vals)[: plan.cap_total]
    nblk = -(-csr.e_max // edge_chunk)
    pad = nblk * edge_chunk - csr.e_max
    rid = jnp.pad(csr.rowids[plan.perm], (0, pad),
                  constant_values=csr.n_rows).reshape(nblk, edge_chunk)
    dg = jnp.pad(dst_gid_e[plan.perm], (0, pad)).reshape(nblk, edge_chunk)
    seg = jnp.pad(plan.seg_ids, (0, pad),
                  constant_values=plan.cap_total).reshape(nblk, edge_chunk)

    def body(sa, blk):
        r, g, s = blk
        vals = v_ext[r] + jnp.where(wsel[None, :],
                                    edge_weights(g_ext[r], g)[:, None], 0)
        return sa.at[s].min(vals), None

    sa, _ = lax.scan(
        body, jnp.full((plan.cap_total + 1, w), ident, jnp.int32),
        (rid, dg, seg))
    return sa[: plan.cap_total]


def _pull_rows_multi(cols_table, e_max, starts, ends, rows_need, col_frontier,
                     chunk, kernel, frontier_words, force):
    """The pull while_loop over one set of rows (see
    :func:`_pull_chunked_multi`). ``starts``/``ends``/``rows_need`` may be a
    row-block slice; ``cols_table``/``col_frontier`` are always the full
    tables (offsets index into the whole edge array)."""
    deg = ends - starts
    n_rows = starts.shape[0]
    w = rows_need.shape[-1]
    max_chunks = -(-e_max // chunk)
    if kernel is not None:
        from repro.kernels import ops as _kops

    def remaining(k, acc):
        unsat = jnp.any(rows_need & ~acc, axis=1)
        return unsat & (deg > k * chunk)

    def cond(carry):
        k, acc, work = carry
        return (k < max_chunks) & jnp.any(remaining(k, acc))

    def body(carry):
        k, acc, work = carry
        rem = remaining(k, acc)
        base = starts + k * chunk
        idx = base[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        valid = rem[:, None] & (idx < ends[:, None])
        cols = cols_table[jnp.clip(idx, 0, e_max - 1)]
        if kernel is None:
            lanes = col_frontier[cols] & valid[..., None]   # [R, chunk, W]
            acc = acc | jnp.any(lanes, axis=1)
        else:
            parents = jnp.where(valid, cols, -1).astype(jnp.int32)
            need = pack_lanes(rows_need & ~acc)             # [R, nw]
            hits = _kops.ell_pull_multi(parents, frontier_words, need,
                                        force=force)
            acc = acc | unpack_lanes(hits, w)
        work = work + jnp.sum(valid.astype(jnp.int32))
        return k + 1, acc, work

    acc0 = jnp.zeros((n_rows, w), dtype=jnp.bool_)
    _, acc, work = lax.while_loop(cond, body, (jnp.int32(0), acc0, jnp.int32(0)))
    return acc & rows_need, work


def _pull_chunked_multi(
    csr: CSR, rows_need: jnp.ndarray, col_frontier: jnp.ndarray, chunk: int,
    kernel: str | None = None, row_block: int = 0,
):
    """Chunked bottom-up pull with word-OR early exit.

    ``rows_need [R, W]``: lanes each row still wants (unvisited, in backward
    mode). A row scans its parent list chunk by chunk, OR-accumulating the
    parents' frontier words, and drops out as soon as the accumulated word
    covers every needed lane -- the lane-word generalization of the paper's
    single-bit early exit. Returns (found [R, W] bool, work scalar int32).

    ``kernel`` routes the per-chunk parent scan through the dispatching
    ELL-tile wrapper :func:`repro.kernels.ops.ell_pull_multi` on *packed*
    uint32 lane words (the TPU kernel path): each chunk is an ELL tile of
    ``chunk`` parent columns, the frontier table is packed once up front,
    and the still-wanted lanes (``rows_need & ~acc``) are the kernel's
    active words. ``None`` keeps the native bool-lane gather; ``"ref"`` /
    ``"pallas"`` pin the wrapper's dispatch; ``"auto"`` lets it pick per
    backend.

    ``row_block > 0`` (the out-of-core mode) scans fixed-height row blocks
    in sequence, bounding the live [rows, chunk, W] working set to
    ``row_block`` rows. Bit-identical to the monolithic scan: each row's
    accumulated word, early exit, and ``work`` contribution depend only on
    that row's own parent list, so blocking changes evaluation order but
    no value, and ``work`` is an exact int32 sum either way.
    """
    starts = csr.offsets[:-1]
    ends = csr.offsets[1:]
    frontier_words = force = None
    if kernel is not None:
        frontier_words = pack_lanes(col_frontier)           # [N, nw], once
        force = None if kernel == "auto" else kernel
    if row_block <= 0 or row_block >= csr.n_rows:
        return _pull_rows_multi(csr.cols, csr.e_max, starts, ends, rows_need,
                                col_frontier, chunk, kernel, frontier_words,
                                force)
    n_rows = csr.n_rows
    nblk = -(-n_rows // row_block)
    pad = nblk * row_block - n_rows
    # padded rows: deg 0 and rows_need False -> never remaining, no work
    st = jnp.pad(starts, (0, pad)).reshape(nblk, row_block)
    en = jnp.pad(ends, (0, pad)).reshape(nblk, row_block)
    nd = jnp.pad(rows_need, ((0, pad), (0, 0))).reshape(
        nblk, row_block, rows_need.shape[-1])

    def body(_, blk):
        s, e, n = blk
        return None, _pull_rows_multi(csr.cols, csr.e_max, s, e, n,
                                      col_frontier, chunk, kernel,
                                      frontier_words, force)

    _, (found, works) = lax.scan(body, None, (st, en, nd))
    return (found.reshape(nblk * row_block, -1)[: n_rows],
            jnp.sum(works))


def _lane_count(mask: jnp.ndarray) -> jnp.ndarray:
    """Per-lane popcount of a [rows, W] mask -> [W] int32."""
    return jnp.sum(mask.astype(jnp.int32), axis=0)


def _lane_degree_sum(mask: jnp.ndarray, deg: jnp.ndarray) -> jnp.ndarray:
    """Per-lane frontier out-degree sum (FV estimate) -> [W] int32."""
    return jnp.sum(mask.astype(jnp.int32) * deg[:, None], axis=0)


# per-lane direction switch: bfs._decide_direction is elementwise, so it
# applies to [W] lane vectors unchanged (one hysteresis state per query)
_decide_direction_lane = _decide_direction


def _bv_estimate_lane(q, s, u):
    qf = q.astype(jnp.float32)
    sf = s.astype(jnp.float32)
    return jnp.where(q > 0, u.astype(jnp.float32) * (qf + sf) / jnp.maximum(qf, 1.0),
                     jnp.inf)


# -----------------------------------------------------------------------------
# One superstep (runs per-partition under an axis name)


def msbfs_step(
    pgv: PartitionedGraph, plan, state: MSBFSState, cfg: MSBFSConfig, axis_names
) -> MSBFSState:
    p, nl = pgv.p, pgv.n_local
    w = cfg.n_queries
    d = state.level_d.shape[-2]
    it = state.it
    # strategies bound to this step's partition axes (static at trace time)
    cplan = comm.plan_for(cfg.comm, axis_names)

    with jax.named_scope("msbfs.direction"):
        # Typed-query liveness gate: a lane with a latched stop (all targets
        # hit) or at its depth cap contributes no frontier this sweep, so its
        # push gather, pull scan, nn exchange slots and delegate candidates all
        # drop out together -- the early exit the distance-limited and
        # multi-target kinds buy on this substrate.
        depth = it - state.base_it                               # [W]
        expand = ~state.lane_stop & (depth < state.depth_cap)    # [W]

        nv = pgv.normal_valid[:, None]
        if cfg.track_levels:
            unvis_n = (state.level_n == INF_LEVEL) & nv
            unvis_d = state.level_d == INF_LEVEL
            frontier_n = (state.level_n == it) & nv & expand[None, :]
            frontier_d = (state.level_d == it) & expand[None, :]
        else:
            # Reachability-only batches: level arrays are bool visited words and
            # the frontier is explicit state -- no level arithmetic anywhere.
            unvis_n = ~state.level_n & nv
            unvis_d = ~state.level_d
            frontier_n = state.frontier_n & nv & expand[None, :]
            frontier_d = state.frontier_d & expand[None, :]

        deg_nd = _row_degrees(pgv.nd)
        deg_dn = _row_degrees(pgv.dn)
        deg_dd = _row_degrees(pgv.dd)

        # ---- per-lane direction decisions (paper Section IV-B, widened) ---
        fv_dd = _lane_degree_sum(frontier_d, deg_dd)
        fv_dn = _lane_degree_sum(frontier_d, deg_dn)
        fv_nd = _lane_degree_sum(frontier_n, deg_nd)
        if cfg.enable_do:
            bv_dd = _bv_estimate_lane(
                _lane_count(frontier_d & pgv.dd_src_mask[:, None]),
                _lane_count(unvis_d & pgv.dd_src_mask[:, None]),
                _lane_count(unvis_d & pgv.dd_src_mask[:, None]))
            bv_dn = _bv_estimate_lane(
                _lane_count(frontier_d & pgv.dn_src_mask[:, None]),
                _lane_count(unvis_d & pgv.dn_src_mask[:, None]),
                _lane_count(unvis_n & pgv.nd_src_mask[:, None]))
            bv_nd = _bv_estimate_lane(
                _lane_count(frontier_n & pgv.nd_src_mask[:, None]),
                _lane_count(unvis_n & pgv.nd_src_mask[:, None]),
                _lane_count(unvis_d & pgv.dn_src_mask[:, None]))
            backward = jnp.stack([
                _decide_direction_lane(state.backward[0], fv_dd, bv_dd, cfg.factor0[0], cfg.factor1[0]),
                _decide_direction_lane(state.backward[1], fv_dn, bv_dn, cfg.factor0[1], cfg.factor1[1]),
                _decide_direction_lane(state.backward[2], fv_nd, bv_nd, cfg.factor0[2], cfg.factor1[2]),
            ])
            # A converged (or never-seeded) lane must not pull: its frontier word
            # is empty, so its pull early-exit can never be satisfied and would
            # rescan full parent lists every remaining sweep. Forward mode with
            # an empty frontier is free.
            backward = backward & state.lane_active[None, :]
        else:
            backward = jnp.zeros((3, w), dtype=jnp.bool_)
        bwd_dd, bwd_dn, bwd_nd = backward[0], backward[1], backward[2]

    # Lanes in forward mode push their frontier word; lanes in backward mode
    # pull into their unvisited word. Results are disjoint per lane, so the
    # per-lane merge is a plain OR.
    # edge_chunk > 0: stream the pushes over edge blocks and row-block the
    # pulls at ~edge_chunk edge slots per step (see MSBFSConfig.edge_chunk
    # -- bit-identical to monolithic, memory only)
    ec = cfg.edge_chunk
    rb = max(1, ec // max(cfg.pull_chunk, 1)) if ec > 0 else 0

    # ---- dd: delegate -> delegate ----------------------------------------
    with jax.named_scope("msbfs.dd"):
        push_dd = _push_multi(pgv.dd, frontier_d & ~bwd_dd[None, :], d, ec)
        pull_dd, work_dd_b = _pull_chunked_multi(
            pgv.dd, unvis_d & pgv.dd_src_mask[:, None] & bwd_dd[None, :],
            frontier_d, cfg.pull_chunk, cfg.kernel_pull, rb)
        cand_dd = push_dd | pull_dd

    # ---- nd: normal -> delegate (pull walks the dn subgraph) --------------
    with jax.named_scope("msbfs.nd"):
        push_nd = _push_multi(pgv.nd, frontier_n & ~bwd_nd[None, :], d, ec)
        pull_nd, work_nd_b = _pull_chunked_multi(
            pgv.dn, unvis_d & pgv.dn_src_mask[:, None] & bwd_nd[None, :],
            frontier_n, cfg.pull_chunk, cfg.kernel_pull, rb)
        cand_nd = push_nd | pull_nd

    # ---- dn: delegate -> normal (pull walks the nd subgraph) --------------
    with jax.named_scope("msbfs.dn"):
        push_dn = _push_multi(pgv.dn, frontier_d & ~bwd_dn[None, :], nl, ec)
        pull_dn, work_dn_b = _pull_chunked_multi(
            pgv.nd, unvis_n & pgv.nd_src_mask[:, None] & bwd_dn[None, :],
            frontier_d, cfg.pull_chunk, cfg.kernel_pull, rb)
        cand_dn = push_dn | pull_dn

    # ---- nn: normal -> normal, forward only, static slot exchange ---------
    # format (dense lane words / sparse id+word pairs / per-sweep adaptive
    # switch / compressed codec) selected by cfg.comm.nn in the comm layer
    with jax.named_scope("msbfs.nn.slots"):
        sa, act_nn_sum = _nn_slots_multi(frontier_n, plan)
    with jax.named_scope("msbfs.nn.exchange"):
        rows = jnp.minimum(plan.seg_owner, p - 1)
        ok = plan.seg_owner < p
        dense = jnp.zeros((p, plan.cap_peer, w), jnp.bool_).at[rows, plan.seg_pos].max(
            sa & ok[:, None], mode="drop")
        recv, nn_bytes, nn_sparse, nn_ovf = comm.nn_exchange_words(
            cplan, dense, plan.recv_local, nl)
        sent = jnp.sum(sa.astype(jnp.int32))

    # ---- delegate global reduction: packed-word bitwise-OR combine --------
    # (allgather-fold / ring / hierarchical per cfg.comm.delegate; the
    # local fold optionally runs through the mask_reduce lane-word kernel)
    with jax.named_scope("msbfs.delegate.combine"):
        cand_d_words = pack_lanes(cand_dd | cand_nd)             # [d, nw]
        reduced, d_bytes = comm.delegate_combine(cplan, cand_d_words, "or")
        newly_d = unpack_lanes(reduced, w) & unvis_d
        new_d_any = jnp.any(newly_d)

    # ---- payload plane sweep (static branch: compiled away entirely when
    # cfg.payload is off, like telemetry) -----------------------------------
    if cfg.payload:
        with jax.named_scope("msbfs.payload"):
            ident = jnp.int32(PAY_IDENT)
            wsel = state.pay_weighted                             # [W]
            # global-id vectors for the synthetic edge weights: this
            # partition's normal rows (layout formula on the in-trace flat
            # partition index) and the replicated delegate vids
            me = comm.codec.self_flat_index(cplan.axes, cplan.sizes)
            part_base = (me // pgv.p_gpu) + pgv.p_rank * (me % pgv.p_gpu)
            gid_n = part_base + p * jnp.arange(nl, dtype=jnp.int32)
            dv = pgv.delegate_vids.reshape(-1).astype(jnp.int32)
            kd = min(int(dv.shape[0]), d)
            gid_d = jnp.zeros((d,), jnp.int32)
            if kd:
                gid_d = gid_d.at[:kd].set(dv[:kd])
            # frontier: worklist vertices under the lane's current bucket
            pfront_n = (state.pay_pending_n & nv
                        & (state.payload_n < state.pay_bucket[None, :]))
            pfront_d = (state.pay_pending_d
                        & (state.payload_d < state.pay_bucket[None, :]))
            ppush_dd = _push_payload(pgv.dd, pfront_d, state.payload_d,
                                     gid_d, gid_d, d, wsel, ec)
            ppush_nd = _push_payload(pgv.nd, pfront_n, state.payload_n,
                                     gid_n, gid_d, d, wsel, ec)
            ppush_dn = _push_payload(pgv.dn, pfront_d, state.payload_d,
                                     gid_d, gid_n, nl, wsel, ec)
            # nn: per-edge dst gid from the pre-split (owner, local) pair
            nn_dst_gid = ((pgv.nn_owner // pgv.p_gpu)
                          + pgv.p_rank * (pgv.nn_owner % pgv.p_gpu)
                          + p * pgv.nn.cols.astype(jnp.int32)).astype(jnp.int32)
            sa_pay = _nn_slots_payload(pgv.nn, pfront_n, state.payload_n, gid_n,
                                       nn_dst_gid, plan, wsel, ec)
            dense_pay = jnp.full((p, plan.cap_peer, w), ident, jnp.int32).at[
                rows, plan.seg_pos].min(
                    jnp.where(ok[:, None], sa_pay, ident), mode="drop")
            recv_pay, pay_nn_bytes, _pay_sparse, pay_nn_ovf = \
                comm.nn_exchange_payload(cplan, dense_pay, plan.recv_local, nl)
            # delegate payload combine: native fused pmin under "auto"
            red_pd, pay_d_bytes = comm.delegate_combine(
                cplan, jnp.minimum(ppush_dd, ppush_nd), "min")
            new_pay_d = jnp.minimum(state.payload_d, red_pd)
            imp_d = new_pay_d < state.payload_d
            new_pay_n = jnp.where(
                nv, jnp.minimum(state.payload_n,
                                jnp.minimum(ppush_dn, recv_pay)), ident)
            imp_n = new_pay_n < state.payload_n
            # expanded vertices leave the worklist; improved ones (re)enter it
            new_pend_n = (state.pay_pending_n & ~pfront_n) | imp_n
            new_pend_d = (state.pay_pending_d & ~pfront_d) | imp_d
            # local per-lane convergence rows, folded into the one lane
            # reduction below instead of adding a collective: pending-any,
            # under-bucket-any, and the *negated* pending minimum (one pmax
            # yields a global min for the bucket advance)
            l_pend = jnp.any(new_pend_n, axis=0) | jnp.any(new_pend_d, axis=0)
            l_under = (
                jnp.any(new_pend_n & (new_pay_n < state.pay_bucket[None, :]),
                        axis=0)
                | jnp.any(new_pend_d & (new_pay_d < state.pay_bucket[None, :]),
                          axis=0))
            minpend = jnp.minimum(
                jnp.min(jnp.where(new_pend_n, new_pay_n, ident), axis=0),
                jnp.min(jnp.where(new_pend_d, new_pay_d, ident), axis=0))
            pay_rows = jnp.stack([l_pend.astype(jnp.int32),
                                  l_under.astype(jnp.int32), -minpend])

    # ---- level / visited updates ------------------------------------------
    with jax.named_scope("msbfs.update"):
        newly_n = (cand_dn | recv) & unvis_n
        if cfg.track_levels:
            new_level_d = jnp.where(newly_d, it + 1, state.level_d)
            new_level_n = jnp.where(newly_n, it + 1, state.level_n)
            new_frontier_n, new_frontier_d = state.frontier_n, state.frontier_d
        else:
            new_level_d = state.level_d | newly_d                # visited words
            new_level_n = state.level_n | newly_n
            new_frontier_n, new_frontier_d = newly_n, newly_d

        # per-lane convergence: lane q stays live iff it marked a new vertex on
        # some partition this sweep (delegate updates are already global). The
        # target word rides the same one-word collective: flag 1 is "lane q
        # still has an unvisited target somewhere".
        if cfg.enable_targets:
            unhit_n = jnp.any(state.target_n & unvis_n & ~newly_n, axis=0)
            flags = jnp.stack([jnp.any(newly_n, axis=0), unhit_n])   # [2, W]
            if cfg.payload:
                red_all = comm.lane_fold_reduce(
                    jnp.concatenate([flags.astype(jnp.int32), pay_rows]),
                    axis_names)
                red = red_all[:2] > 0
            else:
                red = comm.lane_any_reduce(flags, axis_names)
            unhit = red[1] | jnp.any(state.target_d & unvis_d & ~newly_d, axis=0)
            upd_global = red[0]
            stop_targets = state.has_targets & ~unhit
        else:
            if cfg.payload:
                red_all = comm.lane_fold_reduce(jnp.concatenate(
                    [jnp.any(newly_n, axis=0).astype(jnp.int32)[None],
                     pay_rows]), axis_names)
                upd_global = red_all[0] > 0
            else:
                upd_global = comm.lane_any_reduce(jnp.any(newly_n, axis=0),
                                                  axis_names)
            stop_targets = jnp.zeros_like(state.lane_stop)
        # latch the stop: every target covered, or the next sweep would exceed
        # the lane's depth cap
        new_stop = (state.lane_stop | stop_targets
                    | (depth + 1 >= state.depth_cap))
        lane_upd = (upd_global | jnp.any(newly_d, axis=0)) & ~new_stop
        if cfg.payload:
            # payload lanes stay live while pending work remains anywhere (their
            # bit planes are empty, so the bit rows never fire for them). The
            # same fold resolves the delta-stepping bucket advance: pending
            # exists but none under the current bucket -> jump the bucket to the
            # global pending minimum's next bucket boundary. Components lanes
            # (delta = bucket = +inf) never advance: every finite pending value
            # is already under the bucket.
            g_pend = red_all[-3] > 0
            g_under = red_all[-2] > 0
            g_minpend = -red_all[-1]
            lane_upd = lane_upd | g_pend
            dstep = jnp.maximum(state.pay_delta, 1)
            nb = (jnp.clip(g_minpend, 0, PAY_IDENT) // dstep + 1) * dstep
            new_bucket = jnp.where(g_pend & ~g_under,
                                   jnp.minimum(nb, jnp.int32(PAY_IDENT)),
                                   state.pay_bucket)
        updated = jnp.any(lane_upd)

        # ---- statistics ----------------------------------------------------
        w_fwd = (
            jnp.sum(jnp.where(bwd_dd, 0, fv_dd)) + jnp.sum(jnp.where(bwd_nd, 0, fv_nd))
            + jnp.sum(jnp.where(bwd_dn, 0, fv_dn))
        )
        if cfg.track_levels:
            # exact per-edge-lane push count; the reachability-only variant
            # keeps the frontier degree-sum estimates above instead of
            # materializing the [E, W] int32 count
            w_fwd = w_fwd + act_nn_sum
        w_bwd = work_dd_b + work_nd_b + work_dn_b
        slot = jnp.clip(it, 0, cfg.max_iters - 1)
        # ---- device-plane sweep telemetry (static branch: the disabled path
        # returns the zero-size carry untouched and XLA compiles all of this
        # away -- the expand-gated frontier masks and the direction word are
        # already live values, so telemetry adds no new collective, no new
        # host sync, only its own accumulation) -------------------------------
        if cfg.telemetry:
            tm_frontier_n = state.tm_frontier_n.at[slot].add(
                jnp.sum(frontier_n.astype(jnp.int32)))
            tm_frontier_d = state.tm_frontier_d.at[slot].add(
                jnp.sum(frontier_d.astype(jnp.int32)))
            tm_backward = state.tm_backward.at[slot].set(pack_lanes(backward))
        else:
            tm_frontier_n = state.tm_frontier_n
            tm_frontier_d = state.tm_frontier_d
            tm_backward = state.tm_backward
        if cfg.payload:
            wire_pay_delegate = state.wire_pay_delegate.at[slot].add(
                jnp.int32(pay_d_bytes))
            wire_pay_nn = state.wire_pay_nn.at[slot].add(pay_nn_bytes)
            nn_ovf = nn_ovf + pay_nn_ovf       # overflow guard covers both planes
        else:
            new_pay_n, new_pay_d = state.payload_n, state.payload_d
            new_pend_n, new_pend_d = state.pay_pending_n, state.pay_pending_d
            new_bucket = state.pay_bucket
            wire_pay_delegate = state.wire_pay_delegate
            wire_pay_nn = state.wire_pay_nn
        return MSBFSState(
            level_n=new_level_n,
            level_d=new_level_d,
            backward=backward,
            it=it + 1,
            done=~updated,
            lane_active=lane_upd,
            base_it=state.base_it,
            lane_stop=new_stop,
            depth_cap=state.depth_cap,
            has_targets=state.has_targets,
            target_n=state.target_n,
            target_d=state.target_d,
            frontier_n=new_frontier_n,
            frontier_d=new_frontier_d,
            work_fwd=state.work_fwd.at[slot].set(w_fwd),
            work_bwd=state.work_bwd.at[slot].set(w_bwd),
            nn_sent=state.nn_sent.at[slot].set(sent),
            delegate_round=state.delegate_round.at[slot].set(new_d_any.astype(jnp.int32)),
            wire_delegate=state.wire_delegate.at[slot].add(jnp.int32(d_bytes)),
            wire_nn=state.wire_nn.at[slot].add(nn_bytes),
            nn_sparse=state.nn_sparse.at[slot].add(nn_sparse),
            nn_overflow=state.nn_overflow.at[slot].add(nn_ovf),
            tm_frontier_n=tm_frontier_n,
            tm_frontier_d=tm_frontier_d,
            tm_backward=tm_backward,
            payload_n=new_pay_n,
            payload_d=new_pay_d,
            pay_pending_n=new_pend_n,
            pay_pending_d=new_pend_d,
            pay_bucket=new_bucket,
            pay_delta=state.pay_delta,
            pay_weighted=state.pay_weighted,
            wire_pay_delegate=wire_pay_delegate,
            wire_pay_nn=wire_pay_nn,
        )


# -----------------------------------------------------------------------------
# Lane retirement / refill


@jax.named_scope("msbfs.reseed")
def _reseed_lanes_impl(
    state: MSBFSState,
    lane_mask: jnp.ndarray,       # [W] bool: lanes to retire + reseed
    src_part: jnp.ndarray,        # [W] int32: owner partition (normal source)
    src_local: jnp.ndarray,       # [W] int32: local id      (normal source)
    src_dpos: jnp.ndarray,        # [W] int32: delegate pos  (delegate source)
    src_is_delegate: jnp.ndarray,  # [W] bool
    depth_cap: jnp.ndarray | None = None,       # [W] int32 (NO_DEPTH_CAP = none)
    tgt_part: jnp.ndarray | None = None,        # [W, T] int32
    tgt_local: jnp.ndarray | None = None,       # [W, T] int32
    tgt_dpos: jnp.ndarray | None = None,        # [W, T] int32
    tgt_is_delegate: jnp.ndarray | None = None,  # [W, T] bool
    tgt_valid: jnp.ndarray | None = None,       # [W, T] bool
    # payload-lane reseed parameters (all-or-none; only legal on a
    # cfg.payload state -- the planes must have real lane width):
    pay_lane: jnp.ndarray | None = None,        # [W] bool: reseed as payload
    pay_seed_all: jnp.ndarray | None = None,    # [W] bool: components seeding
    pay_weighted: jnp.ndarray | None = None,    # [W] bool: add edge weights
    pay_delta: jnp.ndarray | None = None,       # [W] int32: bucket width
    gid_n: jnp.ndarray | None = None,           # [p, nl] int32 global ids,
                                                # PAY_IDENT at invalid slots
    gid_d: jnp.ndarray | None = None,           # [d] int32 delegate gids,
                                                # PAY_IDENT at padding
) -> MSBFSState:
    """Retire converged lanes and reseed them with fresh queries in place.

    For every lane in ``lane_mask``: the lane's level columns are cleared to
    INF, its new source is seeded at the *current* global iteration (so the
    shared ``level == it`` frontier test picks it up on the very next
    sweep), ``base_it`` records the seed iteration for unpacking, the lane's
    direction hysteresis resets to forward, and its typed-query parameters
    (depth cap, target words, stop latch) are replaced -- omitted parameter
    arrays reset reseeded lanes to plain full-levels semantics. Untouched
    lanes are bit-identical -- the sweep, the packed wire formats, and the
    other queries' levels never see the refill.

    The scatter trick: non-reseeded lanes scatter INF_LEVEL at a dummy
    location via ``.min`` (False via ``.max`` in reachability-only mode),
    which is a no-op against any stored level.
    """
    w = lane_mask.shape[0]
    lanes = jnp.arange(w, dtype=jnp.int32)
    it = state.it[0]                      # replicated across partitions
    clear = lane_mask[None, None, :]
    seed_n = lane_mask & ~src_is_delegate
    seed_d = lane_mask & src_is_delegate
    if pay_lane is not None:
        # payload lanes keep their bit columns empty: suppress bit seeding
        seed_n = seed_n & ~pay_lane
        seed_d = seed_d & ~pay_lane
    idx_n = (jnp.where(seed_n, src_part, 0), jnp.where(seed_n, src_local, 0),
             lanes)
    idx_d = jnp.where(seed_d, src_dpos, 0)

    if state.level_n.dtype == jnp.bool_:
        # reachability-only mode: visited + frontier words, seed = True
        level_n = (state.level_n & ~clear).at[idx_n].max(seed_n)
        level_d = (state.level_d & ~clear).at[:, idx_d, lanes].max(
            seed_d[None, :])
        frontier_n = (state.frontier_n & ~clear).at[idx_n].max(seed_n)
        frontier_d = (state.frontier_d & ~clear).at[:, idx_d, lanes].max(
            seed_d[None, :])
    else:
        level_n = jnp.where(clear, INF_LEVEL, state.level_n)
        level_d = jnp.where(clear, INF_LEVEL, state.level_d)
        vals_n = jnp.where(seed_n, it, INF_LEVEL).astype(level_n.dtype)
        level_n = level_n.at[idx_n].min(vals_n)
        vals_d = jnp.where(seed_d, it, INF_LEVEL).astype(level_d.dtype)
        level_d = level_d.at[:, idx_d, lanes].min(vals_d[None, :])
        frontier_n, frontier_d = state.frontier_n, state.frontier_d

    # typed-query parameter state for the reseeded lanes
    cap_vals = NO_DEPTH_CAP if depth_cap is None else depth_cap
    new_cap = jnp.where(lane_mask[None, :], cap_vals, state.depth_cap)
    target_n = state.target_n & ~clear
    target_d = state.target_d & ~clear
    if tgt_valid is None:
        has_targets = state.has_targets & ~lane_mask[None, :]
    else:
        tn = tgt_valid & ~tgt_is_delegate & lane_mask[:, None]   # [W, T]
        lanes_wt = jnp.broadcast_to(lanes[:, None], tn.shape)
        target_n = target_n.at[jnp.where(tn, tgt_part, 0),
                               jnp.where(tn, tgt_local, 0), lanes_wt].max(tn)
        td = tgt_valid & tgt_is_delegate & lane_mask[:, None]
        target_d = target_d.at[:, jnp.where(td, tgt_dpos, 0), lanes_wt].max(
            td[None])
        has_targets = jnp.where(lane_mask[None, :],
                                jnp.any(tgt_valid, axis=1)[None, :],
                                state.has_targets)

    extra = {}
    if pay_lane is not None:
        # payload-plane reseed: clear the retired lanes' columns to the
        # identity (covers bit lanes reusing a former payload lane too),
        # then seed per kind. The reseeded bucket starts at the lane's
        # delta (INF for components = plain min-label propagation).
        ident = jnp.int32(PAY_IDENT)
        pay_n = jnp.where(clear, ident, state.payload_n)
        pay_d = jnp.where(clear, ident, state.payload_d)
        pend_n = state.pay_pending_n & ~clear
        pend_d = state.pay_pending_d & ~clear
        # seed-all lanes (components): own gid everywhere valid (the gid
        # planes carry the identity at invalid/padded slots, which also
        # keeps those slots out of the worklist)
        sa = lane_mask & pay_lane & pay_seed_all
        pay_n = jnp.where(sa[None, None, :], gid_n[..., None], pay_n)
        pend_n = pend_n | (sa[None, None, :] & (gid_n[..., None] < ident))
        pay_d = jnp.where(sa[None, None, :], gid_d[None, :, None], pay_d)
        pend_d = pend_d | (sa[None, None, :] & (gid_d[None, :, None] < ident))
        # single-source lanes (sssp): payload 0 at the source
        ss = lane_mask & pay_lane & ~pay_seed_all
        ss_n = ss & ~src_is_delegate
        ss_d = ss & src_is_delegate
        idx_pn = (jnp.where(ss_n, src_part, 0),
                  jnp.where(ss_n, src_local, 0), lanes)
        pay_n = pay_n.at[idx_pn].min(jnp.where(ss_n, 0, ident))
        pend_n = pend_n.at[idx_pn].max(ss_n)
        idx_pd = jnp.where(ss_d, src_dpos, 0)
        pay_d = pay_d.at[:, idx_pd, lanes].min(
            jnp.where(ss_d, 0, ident)[None, :])
        pend_d = pend_d.at[:, idx_pd, lanes].max(ss_d[None, :])
        extra = dict(
            payload_n=pay_n, payload_d=pay_d,
            pay_pending_n=pend_n, pay_pending_d=pend_d,
            pay_bucket=jnp.where(lane_mask[None, :], pay_delta,
                                 state.pay_bucket),
            pay_delta=jnp.where(lane_mask[None, :], pay_delta,
                                state.pay_delta),
            pay_weighted=jnp.where(lane_mask[None, :], pay_weighted,
                                   state.pay_weighted),
        )

    return dataclasses.replace(
        state,
        level_n=level_n,
        level_d=level_d,
        frontier_n=frontier_n,
        frontier_d=frontier_d,
        backward=state.backward & ~lane_mask[None, None, :],
        base_it=jnp.where(lane_mask[None, :], it, state.base_it),
        lane_active=state.lane_active | lane_mask[None, :],
        lane_stop=state.lane_stop & ~lane_mask[None, :],
        depth_cap=new_cap,
        has_targets=has_targets,
        target_n=target_n,
        target_d=target_d,
        done=state.done & ~jnp.any(lane_mask),
        **extra,
    )


# The public jitted entry point, plus an input-donating sibling for the
# serving drivers: a state that nothing else references (the engine tracks
# this as ``exclusive``) is reseeded in place. A donated input is deleted
# on every backend, XLA:CPU included.
reseed_lanes = jax.jit(_reseed_lanes_impl)
reseed_lanes_donated = jax.jit(_reseed_lanes_impl, donate_argnums=(0,))


# -----------------------------------------------------------------------------
# Drivers


def _run_loop(args, state: MSBFSState, cfg: MSBFSConfig, step_fn):
    def cond(s):
        return (~jnp.all(s.done)) & jnp.all(s.it < cfg.max_iters)

    def body(s):
        return step_fn(args, s)

    return lax.while_loop(cond, body, state)


def _vmapped_step(cfg: MSBFSConfig):
    return jax.vmap(
        lambda pg_l, pl_l, st_l: msbfs_step(pg_l, pl_l, st_l, cfg, "p"),
        axis_name="p", in_axes=(0, 0, 0),
    )


@partial(jax.jit, static_argnames=("cfg",))
def run_msbfs_emulated(
    pgv_stacked: PartitionedGraph, plan_stacked, state: MSBFSState, cfg: MSBFSConfig
) -> MSBFSState:
    """Single-device emulation: partitions are vmap lanes, collectives run
    over the vmapped axis (same contract as ``bfs.run_bfs_emulated``)."""
    step = _vmapped_step(cfg)
    return _run_loop((pgv_stacked, plan_stacked), state, cfg,
                     lambda args, st: step(args[0], args[1], st))


@partial(jax.jit, static_argnames=("cfg",))
def msbfs_step_emulated(
    pgv_stacked: PartitionedGraph, plan_stacked, state: MSBFSState, cfg: MSBFSConfig
) -> MSBFSState:
    """One emulated superstep -- the host-stepped sibling of
    :func:`run_msbfs_emulated` that the refill engine drives so it can
    retire/reseed lanes at sweep boundaries."""
    return _vmapped_step(cfg)(pgv_stacked, plan_stacked, state)


def _make_sharded_step(mesh, axes: tuple, cfg: MSBFSConfig):
    """One shard_map superstep over a real device mesh (shared by the
    fused-loop and host-stepped sharded drivers)."""
    from jax.sharding import PartitionSpec as P

    spec_leaf = lambda x: P(axes, *([None] * (x.ndim - 1)))
    specs_for = lambda tree: jax.tree.map(spec_leaf, tree)

    def sharded_step(pgv, plan, st):
        in_specs = (specs_for(pgv), specs_for(plan), specs_for(st))
        out_specs = specs_for(st)

        def local(pg_l, pl_l, st_l):
            squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
            unsq = lambda t: jax.tree.map(lambda x: x[None], t)
            return unsq(msbfs_step(squeeze(pg_l), squeeze(pl_l), squeeze(st_l),
                                   cfg, axes))

        return jax.shard_map(
            local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(pgv, plan, st)

    return sharded_step


def make_sharded_msbfs(mesh, partition_axes, cfg: MSBFSConfig):
    """shard_map msBFS over a real device mesh (each partition a device)."""
    step = _make_sharded_step(mesh, tuple(partition_axes), cfg)

    @jax.jit
    def run(pgv, plan, st):
        return _run_loop((pgv, plan), st, cfg,
                         lambda args, s: step(args[0], args[1], s))

    return run


def make_sharded_msbfs_step(mesh, partition_axes, cfg: MSBFSConfig):
    """Jitted single shard_map superstep: ``step(pgv, plan, state) -> state``
    (the mesh analog of :func:`msbfs_step_emulated`, for the refill engine)."""
    return jax.jit(_make_sharded_step(mesh, tuple(partition_axes), cfg))


# -----------------------------------------------------------------------------
# Fused k-sweep blocks (the overlapped serving pipeline's device step)


def _block_loop(step_fn, args, state: MSBFSState, watch: jnp.ndarray, k: int):
    """Run up to ``k`` fused sweeps, stopping *at the exact sweep* any
    watched lane converges.

    ``watch [W] bool`` is the set of lanes the host is waiting on (the
    scheduler's busy mask). The loop condition re-checks it after every
    sweep, so the state the host sees at a block boundary is bit-identical
    to what the per-sweep driver would have produced: a retirement is never
    overshot, reseeds land at the same iteration, and the per-sweep
    statistics and wire counters (accumulated inside the carried state --
    ``work_*``, ``wire_*``, ``nn_*``) stay exact despite the fusion.

    A corollary that the pipelined engine leans on: dispatching a block
    whose ``watch`` already has a converged lane runs **zero** sweeps and
    returns the state unchanged -- a speculative next block dispatched
    before the host has examined the previous block's ``lane_active`` word
    freezes itself instead of corrupting the schedule.
    """

    @jax.named_scope("msbfs.block")
    def cond(carry):
        s, i = carry
        return (i < k) & ~jnp.any(watch[None, :] & ~s.lane_active)

    def body(carry):
        s, i = carry
        s = step_fn(args, s)
        with jax.named_scope("msbfs.block"):
            return s, i + jnp.int32(1)

    s, _ = lax.while_loop(cond, body, (state, jnp.int32(0)))
    return s


def make_msbfs_block_emulated(cfg: MSBFSConfig, k: int, donate: bool = False):
    """Jitted fused block for the vmap-emulated path:
    ``block(pgv_stacked, plan_stacked, state, watch) -> state`` runs up to
    ``k`` supersteps on device per host round trip (see :func:`_block_loop`
    for the exact-stop semantics). ``donate=True`` donates the input
    state's buffers to the output (in-place sweeps); the input state is
    deleted on every backend, so only pass a state nothing else holds."""
    step = _vmapped_step(cfg)

    # The jitted function's name names the compiled module, and unlike op
    # metadata (the ``msbfs.*`` scopes) it is part of the persistent
    # compilation cache's key: an executable cached by a build of the
    # block without those scopes is never loaded, stale names and all, in
    # this one's place.
    def msbfs_block(pgv_stacked, plan_stacked, state, watch):
        return _block_loop(lambda a, s: step(a[0], a[1], s),
                           (pgv_stacked, plan_stacked), state, watch, k)

    return jax.jit(msbfs_block, donate_argnums=(2,) if donate else ())


def make_sharded_msbfs_block(mesh, partition_axes, cfg: MSBFSConfig, k: int,
                             donate: bool = False):
    """The shard_map sibling of :func:`make_msbfs_block_emulated`: up to
    ``k`` fused supersteps over a real device mesh per dispatch, with the
    same stop-at-retirement contract."""
    step = _make_sharded_step(mesh, tuple(partition_axes), cfg)

    def msbfs_block(pgv, plan, state, watch):   # named as the emulated one
        return _block_loop(lambda a, s: step(a[0], a[1], s),
                           (pgv, plan), state, watch, k)

    return jax.jit(msbfs_block, donate_argnums=(2,) if donate else ())


def _gather_lane_columns(pg: PartitionedGraph, state: MSBFSState, lanes):
    """Host-side assembly of per-lane global vertex columns: [k, n] in the
    level arrays' dtype, plus the matching per-lane base iterations [k]."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    level_n = np.asarray(state.level_n)           # [p, nl, W]
    level_d = np.asarray(state.level_d)[0]        # [d, W]
    bi = state.base_it
    if lanes is not None:
        lanes = np.asarray(lanes)
        level_n = level_n[..., lanes]             # [p, nl, k]
        level_d = level_d[..., lanes]             # [d, k]
        bi = np.asarray(bi)[..., lanes]
    vids = np.arange(pg.n, dtype=np.int64)
    out = level_n[layout.part_of(vids), layout.local_of(vids)]   # [n, k]
    out = np.ascontiguousarray(out.T)                            # [k, n]
    if pg.d:
        dvids = np.asarray(pg.delegate_vids).reshape(-1)[: pg.d]
        out[:, dvids] = level_d[: pg.d].T
    return out, np.asarray(bi)[0]


def gather_levels_multi(
    pg: PartitionedGraph, state: MSBFSState, lanes=None
) -> np.ndarray:
    """Assemble per-query global hop distances: [W, n] int32.

    Stored levels are absolute (seed iteration + depth); each lane's
    ``base_it`` is subtracted here so refilled lanes unpack to plain hop
    distances, identical to a fresh batch run.

    ``lanes`` (optional 1-D index array) restricts unpacking to those lane
    columns -- returns ``[len(lanes), n]``. The refill engine retires a few
    lanes at a time; slicing keeps the host-side assembly O(k * n) instead
    of O(W * n). The slice happens host-side *after* the transfer: slicing
    the device array would re-jit a gather per distinct retirement count,
    which costs far more than the extra copied columns."""
    out, base = _gather_lane_columns(pg, state, lanes)
    return np.where(out == INF_LEVEL, INF_LEVEL, out - base[:, None])


def gather_reachable_multi(
    pg: PartitionedGraph, state: MSBFSState, lanes=None
) -> np.ndarray:
    """Assemble per-query reachability masks: [W, n] bool.

    The reachability-only (``track_levels=False``) sibling of
    :func:`gather_levels_multi`: the state's bool visited words unpack
    directly, with no base-iteration arithmetic."""
    out, _ = _gather_lane_columns(pg, state, lanes)
    return out


def gather_payload_multi(
    pg: PartitionedGraph, state: MSBFSState, lanes=None
) -> np.ndarray:
    """Assemble per-lane global payload columns: [k, n] int32.

    The payload-plane sibling of :func:`gather_levels_multi`. Payload
    values are already absolute (SSSP distances from the seed's 0,
    component labels = global ids), so unlike levels there is no
    base-iteration subtraction; PAY_IDENT marks unreached vertices."""
    layout = PartitionLayout(pg.n, pg.p_rank, pg.p_gpu)
    pay_n = np.asarray(state.payload_n)           # [p, nl, Wp]
    pay_d = np.asarray(state.payload_d)[0]        # [d, Wp]
    if lanes is not None:
        lanes = np.asarray(lanes)
        pay_n = pay_n[..., lanes]
        pay_d = pay_d[..., lanes]
    vids = np.arange(pg.n, dtype=np.int64)
    out = pay_n[layout.part_of(vids), layout.local_of(vids)]     # [n, k]
    out = np.ascontiguousarray(out.T)                            # [k, n]
    if pg.d:
        dvids = np.asarray(pg.delegate_vids).reshape(-1)[: pg.d]
        out[:, dvids] = pay_d[: pg.d].T
    return out
