"""Typed communication plans: strategy selection + static wire accounting.

A :class:`CommConfig` names the *strategies* (how the delegate combine is
reduced, which nn wire format ships the frontier); a :class:`CommPlan`
binds those choices to the concrete partition axes of one traced step
(axis names + their static sizes) and owns the byte formulas every
traversal layer uses for its wire-volume counters.  The plan is built at
trace time (:func:`plan_for`) -- axis sizes are static Python ints inside
``vmap(axis_name=...)`` and ``shard_map`` alike -- so accounting costs no
device work beyond one scalar add per collective.

Byte convention: **bytes put on the wire per device per collective call**
(payload only; the one-word control reductions of the convergence masks
are excluded as constant and negligible).  Summing a state's per-partition
counter rows therefore yields total cluster traffic.

* all-gather + local fold over P devices: each device's payload travels to
  the other P-1, so ``(P-1) * nbytes``.
* ring allreduce (reduce-scatter + all-gather over chunks of
  ``ceil(L / p)`` elements, per axis): ``2 * (p-1) * ceil(L/p) * itemsize``
  -- O(1) in p, the reason the ring strategy exists.
* two-level hierarchical (paper Section V-A's intra-/inter-node
  AllReduce): the gather-fold cost of each level, ``(P1-1) + (P2-1)``
  payloads instead of ``(P1*P2 - 1)``.
* ``auto`` (native fused ``psum``/``pmin``/``pmax``): modeled with the
  bandwidth-optimal ring formula, which is what fused allreduces
  implement underneath.
* all_to_all of a ``[p, ...]`` buffer: the p-1 non-self rows leave the
  device, ``(p-1)/p`` of the buffer bytes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

from jax import lax

AxisNames = Sequence[str] | str

#: delegate-combine strategies (CommConfig.delegate)
DELEGATE_STRATEGIES = ("auto", "allgather", "ring", "hier")
#: nn wire formats (CommConfig.nn)
NN_FORMATS = ("dense", "sparse", "adaptive", "compressed")


@dataclass(frozen=True)
class CombineSpec:
    """A typed per-lane combine: the monoid one traversal payload reduces
    under, threaded through every layer that used to hardwire bitwise OR.

    ``op`` names the :mod:`.reduce` fold (and thereby the matching native
    collective -- ``pmin``/``pmax``/``psum`` where one exists); ``identity``
    is the scatter/exchange neutral element (what empty slots and
    non-participating lanes carry); ``wire_dtype`` the dtype whose itemsize
    the byte formulas count.
    """

    op: str
    identity: int
    wire_dtype: str

    @property
    def itemsize(self) -> int:
        return 4        # uint32 lane words and int32 payloads alike


#: the combine specs the traversal substrate serves: ``or`` is the BFS
#: bit-word monoid (identity 0, packed uint32 words on the wire);
#: ``min_plus`` the weighted-distance semiring's additive combine
#: (min with +inf identity, edge weights added on the push side);
#: ``min`` plain label minimization (components: min_plus with 0 weights).
COMBINE_SPECS = {
    "or": CombineSpec(op="or", identity=0, wire_dtype="uint32"),
    "min_plus": CombineSpec(op="min", identity=2 ** 30, wire_dtype="int32"),
    "min": CombineSpec(op="min", identity=2 ** 30, wire_dtype="int32"),
}


def as_axes(axis_names: AxisNames) -> tuple:
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def axis_size(axis_names: AxisNames) -> int:
    """Size of one named axis, or the product over a sequence of them
    (a static int under both ``vmap(axis_name=...)`` and ``shard_map``)."""
    return math.prod(lax.axis_size(a) for a in as_axes(axis_names))


@dataclass(frozen=True)
class CommConfig:
    """Strategy selection for one traversal/propagation layer.

    ``delegate``
        ``"auto"`` -- the native fused collective where one exists
        (``pmin``/``pmax``/``psum``); bitwise-OR has no fused primitive,
        so it falls back to ``"allgather"``.  This is the seed behavior.
        ``"allgather"`` -- gather all P partials, fold locally
        (volume grows linearly with P).
        ``"ring"`` -- reduce-scatter + all-gather rings via
        ``lax.ppermute``, per partition axis: O(1)-in-P volume.
        ``"hier"`` -- two-level gather-fold over a multi-axis mesh
        (``axes[:hier_split]`` intra, the rest inter -- the paper's
        intra-node / inter-node AllReduce split). On a flat
        single-name axis it degenerates to ``"allgather"``.
    ``hier_split``
        How many leading mesh axes form the intra level of ``"hier"``.
    ``local_fold``
        Route the K-way local OR fold of the gather-based strategies
        through the ``kernels.ops.mask_reduce`` lane-word kernel:
        ``None`` native ``lax.reduce`` (default), ``"ref"`` / ``"pallas"``
        pin the dispatch, ``"auto"`` picks per backend (same convention
        as ``MSBFSConfig.kernel_pull``). uint32 OR payloads only.
    ``nn``
        Wire format of the frontier nn exchange over the static
        ExchangePlan slots. ``"dense"`` -- one bit per (slot, query),
        fixed volume (the seed format). ``"sparse"`` -- ship only active
        slots as (slot id, lane word) pairs, capped at ``sparse_cap``
        per peer; slots beyond the cap are *dropped and counted* in the
        overflow counter, exactly like ``bin_by_owner``. ``"adaptive"``
        -- per sweep, pick sparse when every peer's active-slot count
        fits the cap (small frontiers) and dense otherwise: the
        communication analog of direction optimization, decided from the
        frontier counters the sweep already computes and globally agreed
        via one scalar reduce so no partition can diverge. ``"compressed"``
        -- the active-slot set as the cheaper of two LEB128 varint
        streams, run-length bitmap vs delta-encoded slot ids (see
        :mod:`repro.core.comm.codec`); transport rides the same
        globally-agreed adaptive switch (never drops), counters carry the
        codec's exact byte cost.
    ``sparse_cap``
        Active-slot capacity per peer of the sparse format. 0 picks a
        cap that keeps sparse strictly cheaper than dense
        (``cap_peer // 4`` lane-word slots, ``cap_peer // 64`` single-bit
        slots).
    """

    delegate: str = "auto"
    hier_split: int = 1
    local_fold: str | None = None
    nn: str = "dense"
    sparse_cap: int = 0

    def __post_init__(self):
        if self.delegate not in DELEGATE_STRATEGIES:
            raise ValueError(
                f"delegate={self.delegate!r} not in {DELEGATE_STRATEGIES}")
        if self.nn not in NN_FORMATS:
            raise ValueError(f"nn={self.nn!r} not in {NN_FORMATS}")
        if self.local_fold not in (None, "ref", "pallas", "auto"):
            raise ValueError(
                f"local_fold={self.local_fold!r} not in "
                "(None, 'ref', 'pallas', 'auto')")

    def as_dict(self) -> dict:
        """JSON-serializable strategy description (what the observability
        plane stamps on traces and bench reports)."""
        return {"delegate": self.delegate, "hier_split": self.hier_split,
                "local_fold": self.local_fold, "nn": self.nn,
                "sparse_cap": self.sparse_cap}


@dataclass(frozen=True)
class CommPlan:
    """A CommConfig bound to concrete partition axes (names + sizes)."""

    cfg: CommConfig
    axes: tuple        # axis names, e.g. ("p",) or ("data", "model")
    sizes: tuple       # static per-axis sizes; prod == p

    @property
    def p(self) -> int:
        return math.prod(self.sizes)

    # -- delegate combine ---------------------------------------------------
    def delegate_groups(self) -> tuple:
        """Axis-name groups reduced in sequence (hier: intra, then inter)."""
        if self.cfg.delegate == "hier" and len(self.axes) > 1:
            s = max(1, min(self.cfg.hier_split, len(self.axes) - 1))
            return (self.axes[:s], self.axes[s:])
        return (self.axes,)

    def group_size(self, group: tuple) -> int:
        return math.prod(self.sizes[self.axes.index(a)] for a in group)

    def effective_delegate(self, op: str) -> str:
        """``auto`` resolves per op: native fused collectives exist for
        min/max/sum; bitwise-OR has none, so it gathers and folds."""
        if self.cfg.delegate == "auto":
            return "allgather" if op == "or" else "auto"
        return self.cfg.delegate

    def delegate_bytes(self, n_elems: int, itemsize: int,
                       op: str = "or") -> int:
        """Per-device wire bytes of one delegate combine of ``n_elems``."""
        nbytes = n_elems * itemsize
        strategy = self.effective_delegate(op)
        if strategy in ("ring", "auto"):
            return sum(2 * (s - 1) * -(-n_elems // s) * itemsize
                       for s in self.sizes if s > 1)
        if strategy == "hier":
            return sum((self.group_size(g) - 1) * nbytes
                       for g in self.delegate_groups() if g)
        return (self.p - 1) * nbytes                    # allgather

    # -- nn exchange --------------------------------------------------------
    def sparse_cap_words(self, cap_peer: int) -> int:
        # clamp to cap_peer: more sparse slots than slots exist is meaningless
        return min(max(1, self.cfg.sparse_cap or cap_peer // 4), cap_peer)

    def sparse_cap_bits(self, cap_peer: int) -> int:
        return min(max(1, self.cfg.sparse_cap or cap_peer // 64), cap_peer)

    def nn_dense_words_bytes(self, cap_peer: int, nw: int) -> int:
        return (self.p - 1) * cap_peer * nw * 4

    def nn_sparse_words_bytes(self, cap_sparse: int, nw: int) -> int:
        return (self.p - 1) * cap_sparse * (4 + nw * 4)   # slot id + words

    def nn_dense_payload_bytes(self, cap_peer: int, w: int) -> int:
        """Dense per-lane payload plane: one int32 per (slot, lane)."""
        return (self.p - 1) * cap_peer * w * 4

    def nn_sparse_payload_bytes(self, cap_sparse: int, w: int) -> int:
        """Sparse (slot id, payload row) records: 4 B id + W int32."""
        return (self.p - 1) * cap_sparse * (4 + w * 4)

    def nn_dense_bits_bytes(self, cap_peer: int) -> int:
        return (self.p - 1) * -(-cap_peer // 32) * 4

    def nn_sparse_bits_bytes(self, cap_sparse: int) -> int:
        return (self.p - 1) * cap_sparse * 4              # slot ids only

    # Compressed-format *worst cases* (documentation bounds only -- actual
    # counters use the exact traced stream lengths from comm.codec):
    # delta stream <= 5 B per active slot, rle stream <= cap + 1 B (run
    # lengths sum to cap and varint_len(L) <= L for L >= 1, plus the
    # optional leading zero run); min(rle, delta) <= cap + 1.
    def nn_compressed_words_max_bytes(self, cap_peer: int, nw: int) -> int:
        return (self.p - 1) * (cap_peer + 1 + cap_peer * nw * 4)

    def nn_compressed_bits_max_bytes(self, cap_peer: int) -> int:
        return (self.p - 1) * (cap_peer + 1)

    def a2a_bytes(self, per_peer_nbytes: int) -> int:
        """Per-device bytes of an all_to_all with ``per_peer_nbytes`` per
        peer row (the p-1 non-self rows leave the device)."""
        return (self.p - 1) * per_peer_nbytes

    def as_dict(self) -> dict:
        """The bound plan as JSON-serializable accounting metadata: the
        strategy config plus the concrete axes it was bound to."""
        return {"axes": list(self.axes), "sizes": list(self.sizes),
                "p": self.p, **self.cfg.as_dict()}


def plan_for(cfg: CommConfig | None, axis_names: AxisNames) -> CommPlan:
    """Bind ``cfg`` to the traced step's partition axes. Axis sizes resolve
    to static Python ints under both ``vmap(axis_name=...)`` and
    ``shard_map`` (``lax.axis_size``), so the plan -- and every byte
    formula on it -- is compile-time data."""
    axes = as_axes(axis_names)
    sizes = tuple(lax.axis_size(a) for a in axes)
    return CommPlan(cfg=cfg or CommConfig(), axes=axes, sizes=sizes)
