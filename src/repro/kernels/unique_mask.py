"""Adjacent-unique mask Pallas kernel over lexsorted rows.

Given lexsorted rows, emits mask[i] = 1 iff row i differs from row i-1 and
is not padding.  This is the dedup core fused after the sort (GLog's
duplicate elimination).

Each column travels as its own lane-dense (n // 128, 128) int32 array, with
a second copy shifted down by one row supplying row i-1, so a block is a
plain (rows, 128) tile: an (n, C) block would pad C up to 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
MIN_TILE = 8 * LANES       # one (8, 128) int32 vreg: the smallest block


def _unique_kernel(*refs, n_cols: int, pad: int):
    cur = refs[:n_cols]
    prev = refs[n_cols:2 * n_cols]
    out_ref = refs[2 * n_cols]
    c0 = cur[0][...]
    neq = c0 != prev[0][...]
    for c in range(1, n_cols):
        neq = jnp.logical_or(neq, cur[c][...] != prev[c][...])
    out_ref[...] = jnp.where(jnp.logical_and(neq, c0 != pad), 1, 0
                             ).astype(jnp.int32)


def unique_mask(cols, pad: int, tile: int, *, interpret: bool):
    """cols: C lane-dense (n // 128, 128) int32 columns of lexsorted rows
    (PAD rows last), ``n % tile == 0``.  Row -1 reads as a PAD row, which
    differs from every valid row.  Returns the (n // 128, 128) int32 mask."""
    n_rows = cols[0].shape[0]
    rows = tile // LANES
    assert tile >= MIN_TILE and (tile & (tile - 1)) == 0
    assert n_rows % rows == 0
    shifted = []
    for c in cols:
        flat = c.reshape(-1)
        shifted.append(jnp.concatenate(
            [jnp.full((1,), pad, flat.dtype), flat[:-1]]).reshape(c.shape))
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_unique_kernel, n_cols=len(cols), pad=pad),
        grid=(n_rows // rows,),
        in_specs=[spec] * (2 * len(cols)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(cols[0].shape, jnp.int32),
        name="unique_mask",
        interpret=interpret,
    )(*cols, *shifted)
