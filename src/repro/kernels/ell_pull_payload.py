"""Per-lane payload (min-plus) pull kernel over ELL-padded parent lists.

The payload sibling of :mod:`repro.kernels.ell_pull_multi` for the
``min_plus`` combine spec: instead of OR-ing the parents' uint32 lane
words, each row takes the elementwise *minimum* over its parents of
``payload[parent] + weight(edge)`` -- the weighted-SSSP relaxation (and,
with zero weights, min-label propagation for components):

    out[r, q] = min_{k: parents[r,k] >= 0} (payload[parents[r,k], q] + w[r,k])

masked to the identity (+inf) where ``active[r, q] == 0``. Same layout as
ell_pull_multi: XLA gathers the parents' payload rows from HBM into a
rows-minor ``[W, K, R]`` plane, and the kernel adds the edge weights,
masks padded parents and min-folds the K columns per tile of rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.comm import COMBINE_SPECS

from .ell_pull_multi import LANES, rows_major, rows_minor

DEFAULT_TILE_ROWS = 1024
_IDENT = COMBINE_SPECS["min_plus"].identity


def _kernel(parents_ref, payload_ref, weights_ref, active_ref, out_ref):
    # parents/weights [K, TS, 128] int32; payload [W, K, TS, 128] int32
    # gathered parent rows; active/out [W, TS, 128] int32
    acc = jnp.full(out_ref.shape, _IDENT, jnp.int32)
    for k in range(parents_ref.shape[0]):       # unrolled min-plus chain
        valid = parents_ref[k] >= 0
        vals = payload_ref[:, k] + weights_ref[k][None]
        acc = jnp.minimum(acc, jnp.where(valid[None], vals, jnp.int32(_IDENT)))
    out_ref[...] = jnp.where(active_ref[...] != 0, acc, jnp.int32(_IDENT))


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def ell_pull_payload(
    parents: jnp.ndarray,        # [R, K] int32, -1 padded
    payload: jnp.ndarray,        # [N, W] int32: per-vertex lane payloads
    weights: jnp.ndarray,        # [R, K] int32: per-parent edge weights
    active: jnp.ndarray,         # [R, W] int32: lanes each row still wants
    *,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool = True,
) -> jnp.ndarray:
    r, k = parents.shape
    w = payload.shape[-1]
    if k == 0:  # no parent columns: pallas rejects zero-width blocks
        return jnp.full((r, w), _IDENT, jnp.int32)
    if tile_rows % LANES:
        raise ValueError(f"tile_rows={tile_rows} is not a multiple of {LANES}")
    r_pad = -(-r // tile_rows) * tile_rows
    ts = tile_rows // LANES
    cols = rows_minor(parents, r_pad, fill=-1)                # [K, S, 128]
    wts = rows_minor(weights, r_pad)                          # [K, S, 128]
    # XLA gather from HBM along the transposed table: [W, K, S, 128],
    # rows minor throughout (no lane padding of W)
    vals = payload.T[:, jnp.maximum(cols, 0)]
    act = rows_minor(active, r_pad)                           # [W, S, 128]
    out = pl.pallas_call(
        _kernel,
        grid=(r_pad // tile_rows,),
        in_specs=[
            pl.BlockSpec((k, ts, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((w, k, ts, LANES), lambda i: (0, 0, i, 0)),
            pl.BlockSpec((k, ts, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((w, ts, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((w, ts, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(act.shape, jnp.int32),
        interpret=interpret,
    )(cols, vals, wts, act)
    return rows_major(out, r)
