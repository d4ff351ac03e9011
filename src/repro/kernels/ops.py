"""Dispatching wrappers for the Pallas kernels.

``force="pallas"`` runs the kernel, ``force="ref"`` the pure-jnp
reference, and ``None`` (``"auto"`` at the config level) picks the kernel
on TPU and the reference elsewhere. On TPU a kernel is always compiled
with Mosaic, never interpreted; off TPU (CPU tests, dry runs) a pinned
``"pallas"`` runs in interpret mode. :func:`on_tpu` is the one backend
check: tests that compile for a described TPU patch it.
"""
from __future__ import annotations

import jax

from . import ref
from .cin_fused import cin_fused as _cin_pallas
from .ell_pull import ell_pull as _ell_pallas
from .ell_pull_multi import ell_pull_multi as _ell_multi_pallas
from .ell_pull_payload import ell_pull_payload as _ell_payload_pallas
from .mask_reduce import mask_reduce as _mask_pallas
from .mask_reduce import payload_min_fold as _payfold_pallas
from .segment_bag import segment_bag as _bag_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(force: str | None) -> bool:
    if force == "pallas":
        return True
    if force == "ref":
        return False
    return on_tpu()


def ell_pull(parents, frontier_mask, active, *, force: str | None = None, **kw):
    if _use_pallas(force):
        return _ell_pallas(parents, frontier_mask, active,
                           interpret=not on_tpu(), **kw)
    return ref.ell_pull_ref(parents, frontier_mask, active)


def ell_pull_multi(parents, frontier_words, active_words, *,
                   force: str | None = None, **kw):
    if _use_pallas(force):
        return _ell_multi_pallas(parents, frontier_words, active_words,
                                 interpret=not on_tpu(), **kw)
    return ref.ell_pull_multi_ref(parents, frontier_words, active_words)


def segment_bag(table, indices, weights=None, *, force: str | None = None, **kw):
    if _use_pallas(force):
        return _bag_pallas(table, indices, weights,
                           interpret=not on_tpu(), **kw)
    return ref.segment_bag_ref(table, indices, weights)


def cin_fused(x0, xk, w, *, force: str | None = None, **kw):
    if _use_pallas(force):
        return _cin_pallas(x0, xk, w, interpret=not on_tpu(), **kw)
    return ref.cin_fused_ref(x0, xk, w)


def mask_reduce(partials, prev, *, force: str | None = None,
                with_count: bool = True, **kw):
    if _use_pallas(force):
        return _mask_pallas(partials, prev, with_count=with_count,
                            interpret=not on_tpu(), **kw)
    return ref.mask_reduce_ref(partials, prev, with_count=with_count)


def ell_pull_payload(parents, payload, weights, active, *,
                     force: str | None = None, **kw):
    if _use_pallas(force):
        return _ell_payload_pallas(parents, payload, weights, active,
                                   interpret=not on_tpu(),
                                   **kw)
    return ref.ell_pull_payload_ref(parents, payload, weights, active)


def payload_min_fold(partials, prev, *, force: str | None = None,
                     with_count: bool = True, **kw):
    if _use_pallas(force):
        return _payfold_pallas(partials, prev, with_count=with_count,
                               interpret=not on_tpu(), **kw)
    return ref.payload_min_fold_ref(partials, prev, with_count=with_count)
