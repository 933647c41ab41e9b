"""Bitonic sort Pallas kernel (int32 key + int32 payload).

TPU adaptation of GLog's sort-based join/dedup machinery.  Keys and
payloads travel as (rows, 128) int32 arrays, element ``i`` at row
``i // 128``, lane ``i % 128``; one grid cell holds one ``tile``-element
block in VMEM.  The sorting network is the standard bitonic one in which
every element knows its direction from its global index: stage ``(size,
j)`` pairs element ``i`` with ``i ^ j`` and orders the pair ascending iff
``i & size == 0``.  Direction masks come from ``broadcasted_iota``, so no
block is ever reversed.

Stages whose distance ``j`` is below the tile run inside the kernel (the
partner sits in the same block: a lane rotation for ``j < 128``, a row
rotation above).  Stages at distance ``>= tile`` pair elements of different
blocks and run as plain XLA elementwise passes (:func:`cmp_exchange_xla`)
between kernel calls; ``repro.kernels.ops.sort_with_payload`` drives the
whole network.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_TILE = 8 * LANES       # one (8, 128) int32 vreg: the smallest block


def _stage(keys, vals, gidx, asc, axis_idx, axis, j, unit):
    """One compare-exchange stage at distance ``j`` along ``axis`` (``j *
    unit`` elements; ``j`` may be traced).  The partner ``x[i ^ j]`` comes
    from the one of the two rotations that brought index ``i ^ j`` — checked
    against a rotated iota, so it holds whatever the rotation's sign
    convention."""
    n = axis_idx.shape[axis]
    from_fwd = pltpu.roll(axis_idx, j, axis) == (axis_idx ^ j)

    def partner(x):
        return jnp.where(from_fwd, pltpu.roll(x, j, axis),
                         pltpu.roll(x, n - j, axis))
    pk, pv = partner(keys), partner(vals)
    # boolean algebra only: Mosaic has no i1 select or equality
    take_min = jnp.logical_not(jnp.logical_xor((gidx & (j * unit)) == 0,
                                               asc))
    swap = jnp.logical_or(
        jnp.logical_and(take_min, pk < keys),
        jnp.logical_and(jnp.logical_not(take_min), pk > keys))
    return jnp.where(swap, pk, keys), jnp.where(swap, pv, vals)


def _network_kernel(k_ref, v_ref, ko_ref, vo_ref, *, tile: int, sizes):
    keys = k_ref[...]
    vals = v_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    gidx = pl.program_id(0) * tile + row * LANES + lane
    for size in sizes:
        asc = (gidx & size) == 0
        top = min(size, tile) // 2
        # distances top, top/2, ..., 1: row rotations down to 128, then
        # lane rotations; each run is one loop over a traced distance
        for first, axis, axis_idx, unit in (
                (top // LANES, 0, row, LANES),
                (min(top, LANES // 2), 1, lane, 1)):
            if first < 1:
                continue
            def body(i, kv, first=first, axis=axis, axis_idx=axis_idx,
                     unit=unit):
                j = jnp.right_shift(jnp.int32(first), i)
                return _stage(*kv, gidx, asc, axis_idx, axis, j, unit)
            keys, vals = jax.lax.fori_loop(0, first.bit_length(), body,
                                           (keys, vals))
    ko_ref[...] = keys
    vo_ref[...] = vals


def network_stages(keys, vals, tile: int, sizes, *, interpret: bool):
    """Run the in-block stages of the bitonic network for each merge
    ``size`` in ``sizes`` (distances ``min(size, tile) / 2`` down to 1) on
    every ``tile``-element block.  keys/vals: (n // 128, 128) int32 with
    ``n % tile == 0``; ``tile`` a power of two >= ``MIN_TILE``."""
    n_rows = keys.shape[0]
    rows = tile // LANES
    assert tile >= MIN_TILE and (tile & (tile - 1)) == 0
    assert keys.shape[1] == LANES and n_rows % rows == 0
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_network_kernel, tile=tile, sizes=tuple(sizes)),
        grid=(n_rows // rows,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(keys.shape, keys.dtype),
                   jax.ShapeDtypeStruct(vals.shape, vals.dtype)],
        name="bitonic_network",
        interpret=interpret,
    )(keys, vals)


def cmp_exchange_xla(keys, vals, j: int, size: int):
    """One cross-block stage ``(size, j)`` as a plain XLA elementwise pass
    over flat (n,) arrays: pairs ``(i, i + j)`` for ``i & j == 0``, ordered
    ascending iff ``i & size == 0`` (``size >= 2j``, so the direction is
    constant over each pair block)."""
    n = keys.shape[0]
    kk = keys.reshape(n // (2 * j), 2, j)
    vv = vals.reshape(n // (2 * j), 2, j)
    blk = jnp.arange(n // (2 * j), dtype=jnp.int32)[:, None]
    asc = ((blk * (2 * j)) & size) == 0
    lo_k, hi_k = kk[:, 0], kk[:, 1]
    lo_v, hi_v = vv[:, 0], vv[:, 1]
    swap = jnp.where(asc, lo_k > hi_k, lo_k < hi_k)
    keys = jnp.stack([jnp.where(swap, hi_k, lo_k),
                      jnp.where(swap, lo_k, hi_k)], axis=1).reshape(n)
    vals = jnp.stack([jnp.where(swap, hi_v, lo_v),
                      jnp.where(swap, lo_v, hi_v)], axis=1).reshape(n)
    return keys, vals
