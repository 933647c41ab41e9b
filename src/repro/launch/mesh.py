"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The single-pod mesh is
(16, 16) = 256 chips ("data", "model"); the multi-pod mesh is (2, 16, 16) =
512 chips ("pod", "data", "model") — "pod" is a second data-parallel tier
whose collectives cross the inter-pod links (DCN/optical), which the roofline
accounts separately.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.models.layers import MeshCtx


def _auto_mesh(shape, axis_names, devices=None):
    return jax.make_mesh(shape, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()[:n]
    return _auto_mesh(shape, axes, devices=devs)


def make_mesh_ctx(mesh) -> MeshCtx:
    names = mesh.axis_names
    if "pod" in names:
        return MeshCtx(mesh=mesh, dp=("pod", "data"), tp="model")
    return MeshCtx(mesh=mesh, dp=("data",), tp="model")


def make_host_mesh(dp: int = 1, tp: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return _auto_mesh((dp, tp), ("data", "model"))


def make_data_mesh(ndev: int | None = None):
    """Pure data-parallel mesh for the sharded materializer: the first
    ``ndev`` (default: all) local devices on the "data" axis."""
    n = ndev if ndev is not None else len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def axis_size(mesh, axis) -> int:
    """Total device count along one mesh axis name or a tuple of names
    (the shard count of anything partitioned over ``axis``)."""
    n = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        n *= mesh.shape[a]
    return n
