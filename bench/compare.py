"""The comparison that decides ``correct``: a pass's fact set against the
plain reference's, exactly.

Both sides are brought to the reference's term ranks (see
``bench/reference/datalog.py``): a program term that the reference does not
know gets rank -1, and its row counts as extra.  ``missing`` counts the
reference's facts that the pass lacks, ``extra`` the pass's facts that the
reference lacks, duplicate rows included.  Both have the limit 0.
"""
from __future__ import annotations

import numpy as np


def to_ranks(rows: np.ndarray, decode, rank: dict) -> np.ndarray:
    """Store rows of dictionary ids -> rows of reference ranks (-1 where
    the decoded term is not one of the reference's)."""
    rows = np.asarray(rows)
    if rows.size == 0:
        return np.zeros(rows.shape, np.int64)
    uniq, inv = np.unique(rows, return_inverse=True)
    r = np.array([rank.get(decode(int(i)), -1) for i in uniq], np.int64)
    return r[inv].reshape(rows.shape)


def _keys(rows: np.ndarray, base: int) -> np.ndarray:
    """Unique row keys: each row as one mixed-radix int64."""
    if base ** rows.shape[1] >= 2 ** 62:
        raise ValueError(f"{base} terms of arity {rows.shape[1]} do not "
                         "pack into an int64 key")
    k = np.zeros(len(rows), np.int64)
    for c in range(rows.shape[1]):
        k = k * base + rows[:, c]
    return np.unique(k)


def compare(ref: dict, got: dict) -> dict:
    """``{"missing": n, "extra": n}`` of ``got`` (``{pred: rank rows}``)
    against ``ref`` (a reference's result)."""
    base = len(ref["terms"]) + 1
    missing = extra = 0
    for pred in sorted(set(ref["facts"]) | set(got)):
        r = ref["facts"].get(pred)
        g = got.get(pred)
        if g is None or len(g) == 0:
            missing += 0 if r is None else len(np.unique(r, axis=0))
            continue
        if r is None or r.shape[1] != g.shape[1]:
            extra += len(g)
            missing += 0 if r is None else len(np.unique(r, axis=0))
            continue
        known = (g >= 0).all(axis=1)
        extra += int((~known).sum())
        g = g[known]
        gk, rk = _keys(g, base), _keys(r, base)
        extra += len(g) - len(gk)
        missing += len(np.setdiff1d(rk, gk, assume_unique=True))
        extra += len(np.setdiff1d(gk, rk, assume_unique=True))
    return {"missing": int(missing), "extra": int(extra)}
