"""Op-level check of the engine's row cores at a narrow store dtype.

Runs each sort, search and scatter that a 16-bit store goes through, on
the default backend, against a numpy reference over the same seeded
rows, and names every op whose output differs.  Also runs the arithmetic
int16 pair key ``(c0 << 16) | c1`` (an order-preserving 32-bit key the
engine no longer uses) with the sort and searches it fed, so a backend
that mishandles it is caught at the op.

    python scripts/narrow_ops_check.py                 # int16 and int32
    python scripts/narrow_ops_check.py --rows 4096 --dtype int16

Prints one line per op and exits 1 if any op differs.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))


def _rows(rng, n, dtype, n_valid):
    """(n, 2) rows: ``n_valid`` random non-negative ids (with repeats,
    below 32,500 so they fit int16), PAD rows after them."""
    pad = np.iinfo(dtype).max
    out = np.full((n, 2), pad, dtype)
    out[:n_valid] = rng.integers(0, 32_500, (n_valid, 2))
    return out


def _lexsorted(a):
    return a[np.lexsort((a[:, 1], a[:, 0]))]


def _pair_key(a):
    return (a[:, 0].astype(np.int64) << 16) | a[:, 1].astype(np.int64)


def check_ops(dtype=np.int16, n=1 << 17, seed=0):
    """{op name: True if it matched numpy} for rows of ``dtype``."""
    import jax
    import jax.numpy as jnp
    from repro.engine import ops

    dtype = np.dtype(dtype)
    rng = np.random.default_rng(seed)
    a = _rows(rng, n, dtype, 3 * n // 4)
    b = _rows(rng, n // 2, dtype, n // 4)
    a_sorted, b_sorted = _lexsorted(a), _lexsorted(b)
    na, nb = 3 * n // 4, n // 4
    mask = rng.random(n) < 0.5
    res = {}

    def run(name, fn, *args):
        return np.asarray(jax.jit(fn)(*args))

    got = run("lexsort", lambda d: ops.lexsort_core(d, pallas=False), a)
    res["lexsort_core"] = np.array_equal(got, a_sorted)

    got = run("keysort", lambda d: ops.keysort_core(d, 0, pallas=False), a)
    res["keysort_core"] = np.array_equal(
        got, a[np.argsort(a[:, 0], kind="stable")])

    got = run("compact", lambda d, m: ops.compact_core(d, m, n), a, mask)
    want = np.full_like(a, np.iinfo(dtype).max)
    want[:mask.sum()] = a[mask]
    res["compact_core"] = np.array_equal(got, want)

    big = np.full((n + n // 2, 2), np.iinfo(dtype).max, dtype)
    big[:na] = a_sorted[:na]
    got = run("merge", lambda x, y: ops.merge_core(x, y, na, nb), big,
              b_sorted)
    want = np.full_like(big, np.iinfo(dtype).max)
    want[:na + nb] = _lexsorted(np.concatenate([a_sorted[:na],
                                                b_sorted[:nb]]))
    res["merge_core"] = np.array_equal(got, want)

    got = run("member", ops.member_mask_core, a, b_sorted)
    have = set(map(tuple, b_sorted[:nb].tolist()))
    want = np.array([tuple(r) in have for r in a.tolist()])
    want[na:] = False
    res["member_mask_core"] = np.array_equal(got, want)

    got = run("dedup", lambda d: ops.dedup_mask_core(d, pallas=False),
              a_sorted)
    want = np.ones(n, bool)
    want[1:] = np.any(a_sorted[1:] != a_sorted[:-1], axis=1)
    want[na:] = False
    res["dedup_mask_core"] = np.array_equal(got, want)

    perm = rng.permutation(n).astype(np.int32)
    got = run("scatter", lambda d, p: jnp.zeros_like(d).at[p].set(
        d, mode="drop"), a, perm)
    want = np.zeros_like(a)
    want[perm] = a
    res["row_scatter"] = np.array_equal(got, want)

    if dtype == np.int16:
        def pair_key(d):
            return (jnp.left_shift(d[:, 0].astype(jnp.int32), 16)
                    | d[:, 1].astype(jnp.int32))
        ka, kb = _pair_key(a), _pair_key(b_sorted)
        res["pair_key"] = np.array_equal(run("key", pair_key, a), ka)
        got = run("pair_sort", lambda d: d[jnp.argsort(pair_key(d))], a)
        res["pair_key_argsort"] = np.array_equal(got, a_sorted)
        for side in ("left", "right"):
            got = run(side, lambda h, p: jnp.searchsorted(
                pair_key(h), pair_key(p), side=side), b_sorted, a)
            res[f"pair_key_searchsorted_{side}"] = np.array_equal(
                got, np.searchsorted(kb, ka, side=side))
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 17)
    ap.add_argument("--dtype", choices=("int16", "int32"), action="append")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax
    print(f"backend {jax.default_backend()} "
          f"{jax.devices()[0].device_kind}", flush=True)
    bad = 0
    for dt in args.dtype or ("int16", "int32"):
        for name, ok in check_ops(np.dtype(dt), args.rows, args.seed).items():
            print(f"{dt} {name}: {'ok' if ok else 'DIFFERS'}", flush=True)
            bad += not ok
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
