"""Multi-query (lane-word) pull visit kernel over ELL-padded parent lists.

The msBFS generalization of :mod:`repro.kernels.ell_pull`: the frontier is
no longer one bit per vertex but one ``n_words``-wide uint32 lane word per
vertex (bit q of word k = query 32k+q's frontier membership).  Each row
gathers its parents' lane words, OR-reduces them across the row, and masks
with the row's still-unvisited lane word:

    out[r] = (OR_{u in parents(r)} frontier[u]) & active[r]

The parent-word gather runs in XLA, straight from HBM, into a rows-minor
``[NW, K, R]`` plane; the kernel masks padded parents and folds the K
columns per tile of ``tile_rows`` rows. Rows sit on the 128-wide lane axis
(``[.., rows / 128, 128]`` blocks), so a one-word lane (W=32) is never
padded out to a 128-lane tile, and VMEM holds one row tile -- never the
frontier table, whose size grows with the partition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TILE_ROWS = 4096
LANES = 128


def _kernel(parents_ref, words_ref, active_ref, out_ref):
    # parents [K, TS, 128] int32 (-1 padded); words [NW, K, TS, 128] uint32
    # gathered parent words; active/out [NW, TS, 128] uint32
    acc = jnp.zeros(out_ref.shape, jnp.uint32)
    for k in range(parents_ref.shape[0]):       # unrolled word-OR chain
        valid = parents_ref[k] >= 0
        acc = acc | jnp.where(valid[None], words_ref[:, k], jnp.uint32(0))
    out_ref[...] = acc & active_ref[...]


def rows_minor(x: jnp.ndarray, r_pad: int, fill=0) -> jnp.ndarray:
    """``[R, *T]`` -> ``[*T, r_pad / 128, 128]``: rows padded with ``fill``
    and moved onto the lane axis (the block layout of the ELL kernels)."""
    x = jnp.pad(x, ((0, r_pad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1),
                constant_values=fill)
    x = jnp.moveaxis(x, 0, -1)
    return x.reshape(x.shape[:-1] + (r_pad // LANES, LANES))


def rows_major(x: jnp.ndarray, r: int) -> jnp.ndarray:
    """Inverse of :func:`rows_minor` for a ``[*T, S, 128]`` tile plane."""
    x = x.reshape(x.shape[:-2] + (-1,))
    return jnp.moveaxis(x, -1, 0)[:r]


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def ell_pull_multi(
    parents: jnp.ndarray,        # [R, K] int32, -1 padded
    frontier_words: jnp.ndarray,  # [N, NW] uint32: per-vertex lane word
    active_words: jnp.ndarray,   # [R, NW] uint32: lanes each row still wants
    *,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = True,
) -> jnp.ndarray:
    r, k = parents.shape
    nw = frontier_words.shape[-1]
    if k == 0:  # no parent columns: pallas rejects zero-width blocks
        return jnp.zeros((r, nw), jnp.uint32)
    if tile_rows % LANES:
        raise ValueError(f"tile_rows={tile_rows} is not a multiple of {LANES}")
    r_pad = -(-r // tile_rows) * tile_rows
    ts = tile_rows // LANES
    cols = rows_minor(parents, r_pad, fill=-1)                # [K, S, 128]
    # XLA gather from HBM along the transposed table: [NW, K, S, 128],
    # rows minor throughout (no lane padding of NW)
    words = frontier_words.T[:, jnp.maximum(cols, 0)]
    active = rows_minor(active_words, r_pad)                  # [NW, S, 128]
    out = pl.pallas_call(
        _kernel,
        grid=(r_pad // tile_rows,),
        in_specs=[
            pl.BlockSpec((k, ts, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((nw, k, ts, LANES), lambda i: (0, 0, i, 0)),
            pl.BlockSpec((nw, ts, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((nw, ts, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(active.shape, jnp.uint32),
        interpret=interpret,
    )(cols, words, active)
    return rows_major(out, r)
