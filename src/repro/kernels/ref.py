"""Pure-jnp oracles for every Pallas kernel (allclose targets for tests)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.engine.relation import PAD


def sort_with_payload_ref(keys, vals):
    """Full-sort oracle matching ``kernels.ops.sort_with_payload``: sorted
    keys plus a payload permutation consistent with them."""
    order = jnp.argsort(keys, stable=True)
    return keys[order], vals[order]


def unique_mask_ref(data):
    prev = jnp.concatenate(
        [jnp.full((1, data.shape[1]), PAD, data.dtype), data[:-1]], axis=0)
    neq = jnp.any(data != prev, axis=1)
    neq = neq.at[0].set(True)
    valid = data[:, 0] != PAD
    return jnp.logical_and(neq, valid).astype(jnp.int32)
