"""Delegate bitmask combine kernel: word-wise OR of K partial masks +
per-word popcount of the delta vs the previous mask.

This is the local phase of the paper's delegate reduction (Section V-A):
GPU_0 ORs the partial masks of its peer GPUs before the global all-reduce,
and the popcount of newly set bits feeds the direction-decision workload
estimates. VPU-only kernel; tiles of words per program. The flat word
vectors are laid out as ``[words / 128, 128]`` planes, so every block is a
whole number of (sublane, lane) tiles whatever the word count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_TILE_WORDS = 8192


def _popcount32(x: jnp.ndarray) -> jnp.ndarray:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _fold(parts_ref, prev_ref, op):
    combined = prev_ref[...]        # [TS, 128]
    for k in range(parts_ref.shape[0]):   # parts: [K, TS, 128]
        combined = op(combined, parts_ref[k])
    return combined


def _kernel(parts_ref, prev_ref, or_ref, newcnt_ref):
    combined = _fold(parts_ref, prev_ref, jnp.bitwise_or)
    or_ref[...] = combined
    newcnt_ref[...] = _popcount32(combined & ~prev_ref[...])


def _kernel_fold(parts_ref, prev_ref, or_ref):
    """Fold-only variant: the delegate-combine local fold wants just the
    OR'd mask, so the popcount VPU pass (and its output buffer) is
    compiled away."""
    or_ref[...] = _fold(parts_ref, prev_ref, jnp.bitwise_or)


def _kernel_min(parts_ref, prev_ref, min_ref, impcnt_ref):
    """Payload (min-combine) variant: elementwise min of K partial payload
    planes + a 0/1 improved flag per element (the payload analog of the
    popcount of newly set bits)."""
    combined = _fold(parts_ref, prev_ref, jnp.minimum)
    min_ref[...] = combined
    impcnt_ref[...] = (combined < prev_ref[...]).astype(jnp.int32)


def _kernel_min_fold(parts_ref, prev_ref, min_ref):
    min_ref[...] = _fold(parts_ref, prev_ref, jnp.minimum)


def _fold_call(kernel, partials, prev, tile_words: int, interpret: bool,
               with_count: bool):
    """Run ``kernel`` over ``[K, NW]`` partials and an ``[NW]`` prev laid
    out as ``[S, 128]`` planes; returns the fold (and the per-word count
    when ``with_count``) cut back to ``[NW]``."""
    if tile_words % LANES:
        raise ValueError(
            f"tile_words={tile_words} is not a multiple of {LANES}")
    k, nw = partials.shape
    nw_pad = -(-nw // tile_words) * tile_words
    ts = tile_words // LANES
    partials = jnp.pad(partials, ((0, 0), (0, nw_pad - nw))).reshape(
        k, nw_pad // LANES, LANES)
    prev = jnp.pad(prev, (0, nw_pad - nw)).reshape(nw_pad // LANES, LANES)
    plane = pl.BlockSpec((ts, LANES), lambda i: (i, 0))
    shapes = [jax.ShapeDtypeStruct(prev.shape, prev.dtype)]
    if with_count:
        shapes.append(jax.ShapeDtypeStruct(prev.shape, jnp.int32))
    outs = pl.pallas_call(
        kernel,
        grid=(nw_pad // tile_words,),
        in_specs=[pl.BlockSpec((k, ts, LANES), lambda i: (0, i, 0)), plane],
        out_specs=[plane] * len(shapes),
        out_shape=shapes,
        interpret=interpret,
    )(partials, prev)
    outs = [o.reshape(-1)[:nw] for o in outs]
    return outs[0], (outs[1] if with_count else None)


@functools.partial(jax.jit,
                   static_argnames=("tile_words", "interpret", "with_count"))
def mask_reduce(
    partials: jnp.ndarray,   # [K, NW] uint32 -- per-peer partial masks
    prev: jnp.ndarray,       # [NW] uint32 -- mask from the previous iteration
    *,
    tile_words: int = DEFAULT_TILE_WORDS,
    interpret: bool = True,
    with_count: bool = True,
):
    """Returns (or_mask [NW] uint32, new_bits_per_word [NW] int32).

    ``with_count=False`` skips the popcount of newly set bits (the second
    element is then ``None``) -- the shape the comm layer's local fold
    uses, where only the combined mask goes back on the wire."""
    return _fold_call(_kernel if with_count else _kernel_fold, partials,
                      prev, tile_words, interpret, with_count)


@functools.partial(jax.jit,
                   static_argnames=("tile_words", "interpret", "with_count"))
def payload_min_fold(
    partials: jnp.ndarray,   # [K, NW] int32 -- per-peer payload partials
    prev: jnp.ndarray,       # [NW] int32 -- payload plane being folded into
    *,
    tile_words: int = DEFAULT_TILE_WORDS,
    interpret: bool = True,
    with_count: bool = True,
):
    """Returns (min_combined [NW] int32, improved [NW] int32 0/1).

    The min-combine (payload) sibling of :func:`mask_reduce`: the local
    fold of a gathered delegate *payload* combine (``min_plus`` spec) is a
    K-way elementwise min instead of an OR, and "newly set bits" becomes
    "payload improved here". ``with_count=False`` skips the improved-flag
    pass (second element ``None``) -- the comm-layer local-fold shape."""
    return _fold_call(_kernel_min if with_count else _kernel_min_fold,
                      partials, prev, tile_words, interpret, with_count)
