"""Compile the Pallas kernels and the engine's jitted cores for a described
TPU v5e chip (no chip attached: the TPU compiler runs on the host).

This catches what interpret mode cannot — primitives Mosaic cannot lower,
blocks that do not fit VMEM, programs that do not fit the chip's 16 GB — at
the sizes the chip smoke test runs.  The topology is described inside a
fixture, never while the module is imported, and everything stays in this
one file: only the worker that runs it loads the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.engine import ops
from repro.kernels import ops as K

V5E_HBM_BYTES = 16 * 10 ** 9
KERNEL_ROWS = 1 << 20
STORE_ROWS = 1 << 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """Returns ``compile(fn, *(shape, dtype))`` -> (hlo text, memory).
    Kernels compile for real (the wrappers would choose interpret mode on
    this CPU backend), and the persistent cache is off: a TPU executable
    written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(K, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in shapes]
        compiled = jax.jit(fn).lower(*avals).compile()
        return compiled.as_text(), compiled.memory_analysis()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _footprint(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


def test_bitonic_sort_kernel_compiles(compile_for_chip):
    text, mem = compile_for_chip(
        lambda k, v: K.sort_with_payload(k, v),
        ((KERNEL_ROWS,), jnp.int32), ((KERNEL_ROWS,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _footprint(mem) < V5E_HBM_BYTES


def test_unique_mask_kernel_compiles(compile_for_chip):
    text, mem = compile_for_chip(lambda d: K.unique_mask(d),
                                 ((KERNEL_ROWS, 2), jnp.int32))
    assert "tpu_custom_call" in text
    # lane-dense columns: no (n, 2) block padded out to 128 lanes
    assert mem.temp_size_in_bytes <= 4 * KERNEL_ROWS * 2 * 4


STORE = ((STORE_ROWS, 2), jnp.int32)
DELTA = ((STORE_ROWS // 4, 2), jnp.int32)
COUNT = ((), jnp.int32)
CORES = {      # name -> (core, argument shapes, routes through a kernel)
    "lexsort_core": (lambda d: ops.lexsort_core(d, pallas=False),
                     (STORE,), False),
    "member_mask_core": (ops.member_mask_core, (STORE, STORE), False),
    "merge_core": (ops.merge_core, (STORE, DELTA, COUNT, COUNT), False),
    "keysort_core[pallas]": (lambda d: ops.keysort_core(d, 1, pallas=True),
                             (STORE,), True),
    "dedup_mask_core[pallas]": (
        lambda d: ops.dedup_mask_core(d, pallas=True), (STORE,), True),
}


@pytest.mark.parametrize("name", list(CORES))
def test_engine_core_compiles(compile_for_chip, name):
    """The jitted cores at an int32 (2^24, 2) store capacity; a Pallas
    kernel shows up as a ``tpu_custom_call`` exactly where one is routed."""
    fn, shapes, kernel = CORES[name]
    text, mem = compile_for_chip(fn, *shapes)
    assert ("tpu_custom_call" in text) == kernel
    assert _footprint(mem) < V5E_HBM_BYTES
