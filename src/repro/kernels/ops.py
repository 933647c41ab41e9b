"""Jit-able wrappers around the Pallas kernels.

Interpret mode is chosen when a wrapper is called, not when this module is
imported: the kernel bodies run through the Pallas interpreter on the CPU
backend only.  On a TPU a kernel compiles or the call fails; it never falls
back to the interpreter or to the jnp reference.

Edge shapes: the engine always calls these on pow-2 capacity buckets, but
the wrappers normalize everything else — empty inputs return immediately,
lengths are padded up to a whole number of tiles (tiles are at least one
(8, 128) int32 vreg) with key-space maxima or PAD rows and sliced back,
and narrow keys are widened to int32 for the kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.relation import next_pow2, pad_of
from repro.kernels import bitonic_sort as BS
from repro.kernels import unique_mask as UM


def _interpret() -> bool:
    """Pallas interpret mode: on the CPU backend, and nowhere else."""
    return jax.default_backend() == "cpu"


def _pow2_tile(tile: int, n: int) -> int:
    """Pow-2 tile of at least one vreg, at most ``max(tile, n)`` rounded
    down to a power of two."""
    t = max(BS.MIN_TILE, min(tile, next_pow2(n)))
    return 1 << (t.bit_length() - 1)


def _order_key(keys):
    """Order-preserving int32 image of int32/int16/uint32 keys."""
    if keys.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(keys ^ jnp.uint32(1 << 31),
                                            jnp.int32)
    if not jnp.issubdtype(keys.dtype, jnp.signedinteger) or \
            keys.dtype.itemsize > 4:
        raise TypeError(f"sort keys of {keys.dtype}: the network sorts "
                        "32-bit words")
    return keys.astype(jnp.int32)


def sort_with_payload(keys, vals, tile: int = 1024):
    """Full sort of (n,) keys + payload through the bitonic network: the
    in-block stages in the Pallas kernel, the cross-block stages as XLA
    passes between its calls.  The network sorts (key, position) pairs —
    positions past ``n`` belong to padding, which is compacted out
    afterwards, so real keys may equal the padding sentinel (the engine's
    PAD) — and the caller's keys and payload are gathered by the resulting
    permutation, which is therefore always a permutation of the input."""
    n = keys.shape[0]
    if n == 0:
        return keys, vals
    interpret = _interpret()
    t = _pow2_tile(tile, n)
    m = max(next_pow2(n), t)
    k = _order_key(keys)
    if m != n:
        k = jnp.concatenate(
            [k, jnp.full((m - n,), jnp.iinfo(jnp.int32).max, jnp.int32)])
    pos = jnp.arange(m, dtype=jnp.int32)
    shape2d = (m // BS.LANES, BS.LANES)
    sizes = []
    size = 2
    while size <= t:
        sizes.append(size)
        size *= 2
    k, pos = BS.network_stages(k.reshape(shape2d), pos.reshape(shape2d), t,
                               sizes, interpret=interpret)
    while size <= m:
        k, pos = k.reshape(m), pos.reshape(m)
        j = size // 2
        while j >= t:
            k, pos = BS.cmp_exchange_xla(k, pos, j, size)
            j //= 2
        k, pos = BS.network_stages(k.reshape(shape2d), pos.reshape(shape2d),
                                   t, (size,), interpret=interpret)
        size *= 2
    pos = pos.reshape(m)
    if m != n:
        # drop the padding positions (>= n), keeping sorted order: they
        # only interleave with real entries inside the sentinel-key tie
        # group, so an order-preserving compaction is still sorted by key
        keep = pos < n
        slot = jnp.where(keep, jnp.cumsum(keep) - 1, n)
        pos = jnp.zeros((n + 1,), jnp.int32).at[slot].set(pos, mode="drop")
        pos = pos[:n]
    return keys[pos], vals[pos]


def unique_mask(data, tile: int = 1024):
    """(n,) int32 first-occurrence mask over lexsorted (n, C) rows."""
    n = data.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    if data.dtype.itemsize > 4:
        raise TypeError(f"rows of {data.dtype}: the kernel compares 32-bit "
                        "words")
    t = _pow2_tile(tile, n)
    m = -(-n // t) * t
    pad = pad_of(data)
    cols = []
    for c in range(data.shape[1]):
        col = data[:, c].astype(jnp.int32)
        if m != n:
            # PAD rows are masked out by the kernel and sliced off
            col = jnp.concatenate([col, jnp.full((m - n,), pad, jnp.int32)])
        cols.append(col.reshape(m // UM.LANES, UM.LANES))
    out = UM.unique_mask(cols, pad, t, interpret=_interpret())
    return out.reshape(m)[:n]
