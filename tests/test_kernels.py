"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the Pallas kernel bodies on CPU)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.engine.relation import PAD
from repro.kernels import ops as K
from repro.kernels import ref as R


@pytest.mark.parametrize("n,tile", [(64, 64), (256, 64), (1024, 256),
                                    (2048, 512), (4096, 4096),
                                    (8192, 1024)])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.int16])
def test_bitonic_sort_sweep(n, tile, dtype):
    rng = np.random.default_rng(n + tile)
    hi = min(1 << 20, int(np.iinfo(dtype).max))
    keys = jnp.asarray(rng.integers(0, hi, n).astype(dtype))
    vals = jnp.arange(n, dtype=jnp.int32)
    ks, vs = K.sort_with_payload(keys, vals, tile=tile)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(np.asarray(keys)))
    # payload is a permutation consistent with keys
    np.testing.assert_array_equal(np.asarray(keys)[np.asarray(vs)],
                                  np.asarray(ks))


@pytest.mark.parametrize("n,c,tile", [(128, 1, 64), (256, 2, 64),
                                      (512, 3, 128), (1024, 4, 256)])
def test_unique_mask_sweep(n, c, tile):
    rng = np.random.default_rng(n * c)
    data = rng.integers(0, 7, (n, c)).astype(np.int32)
    data = data[np.lexsort(data.T[::-1])]
    k = rng.integers(0, n // 4)
    if k:
        data[-k:] = np.iinfo(np.int32).max
        data = np.concatenate([data[:-k][np.lexsort(data[:-k].T[::-1])],
                               data[-k:]])
    got = K.unique_mask(jnp.asarray(data), tile=tile)
    want = R.unique_mask_ref(jnp.asarray(data))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sort_with_pad_sentinels():
    """PAD rows must sort to the end (engine invariant)."""
    n = 256
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 100, n).astype(np.int32)
    keys[200:] = np.iinfo(np.int32).max
    ks, _ = K.sort_with_payload(jnp.asarray(keys),
                                jnp.arange(n, dtype=jnp.int32), tile=64)
    assert (np.asarray(ks)[-56:] == np.iinfo(np.int32).max).all()


# ---------------------------------------------------------------------------
# edge shapes: empty inputs, non-pow2 lengths, all-PAD / all-duplicate data
# (the happy-path sweeps above only cover pow-2 engine buckets)
# ---------------------------------------------------------------------------
def test_sort_empty():
    ks, vs = K.sort_with_payload(jnp.zeros((0,), jnp.int32),
                                 jnp.zeros((0,), jnp.int32))
    assert ks.shape == (0,) and vs.shape == (0,)


@pytest.mark.parametrize("n", [1, 3, 96, 300, 1000])
def test_sort_non_pow2(n):
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 1 << 20, n).astype(np.int32)
    vals = jnp.arange(n, dtype=jnp.int32)
    ks, vs = K.sort_with_payload(jnp.asarray(keys), vals, tile=64)
    want_k, _ = R.sort_with_payload_ref(jnp.asarray(keys), vals)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(want_k))
    # payload consistent with keys (no sentinel keys here, so the payload
    # is a permutation of [0, n))
    np.testing.assert_array_equal(np.asarray(keys)[np.asarray(vs)],
                                  np.asarray(ks))


def test_sort_all_pad():
    """All-PAD input: keys tie with the non-pow2 padding sentinels, but the
    payload must still be a permutation of the caller's."""
    for n in (64, 100):
        keys = jnp.full((n,), PAD, jnp.int32)
        ks, vs = K.sort_with_payload(keys, jnp.arange(n, dtype=jnp.int32))
        assert (np.asarray(ks) == PAD).all()
        assert sorted(np.asarray(vs).tolist()) == list(range(n))


def test_sort_non_pow2_with_pad_keys():
    """Non-pow2 input whose real keys include the padding sentinel: the
    synthetic padding entries must never leak into the payload (regression:
    keys=[5, PAD, 7] once returned payload index 3 for n=3)."""
    keys = jnp.array([5, PAD, 7], jnp.int32)
    ks, vs = K.sort_with_payload(keys, jnp.arange(3, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(ks), [5, 7, PAD])
    np.testing.assert_array_equal(np.asarray(vs), [0, 2, 1])
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 50, 300).astype(np.int32)
    keys[rng.choice(300, 40, replace=False)] = PAD
    ks, vs = K.sort_with_payload(jnp.asarray(keys),
                                 jnp.arange(300, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(ks), np.sort(keys))
    vs = np.asarray(vs)
    assert sorted(vs.tolist()) == list(range(300))
    np.testing.assert_array_equal(keys[vs], np.asarray(ks))


def test_sort_all_duplicates():
    n = 256
    keys = jnp.full((n,), 7, jnp.int32)
    ks, vs = K.sort_with_payload(keys, jnp.arange(n, dtype=jnp.int32),
                                 tile=64)
    assert (np.asarray(ks) == 7).all()
    assert sorted(np.asarray(vs).tolist()) == list(range(n))


def test_unique_mask_empty():
    got = K.unique_mask(jnp.zeros((0, 2), jnp.int32))
    assert got.shape == (0,)


@pytest.mark.parametrize("n,c", [(1, 1), (96, 2), (300, 3), (1000, 2)])
def test_unique_mask_non_pow2(n, c):
    rng = np.random.default_rng(n + c)
    data = rng.integers(0, 5, (n, c)).astype(np.int32)
    data = data[np.lexsort(data.T[::-1])]
    got = K.unique_mask(jnp.asarray(data))
    want = R.unique_mask_ref(jnp.asarray(data))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unique_mask_all_pad():
    data = jnp.full((128, 2), PAD, jnp.int32)
    got = K.unique_mask(data)
    assert (np.asarray(got) == 0).all()


def test_unique_mask_all_duplicates():
    data = jnp.tile(jnp.array([[3, 4]], jnp.int32), (256, 1))
    got = K.unique_mask(data, tile=64)
    want = R.unique_mask_ref(data)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got.sum()) == 1
