"""Plain transitive-closure reference: boolean reachability by matrix.

For the rules ``e(X, Y) -> T(X, Y)`` and ``T(X, Y) & e(Y, Z) -> T(X, Z)``
over one edge relation: ``R`` starts as the adjacency matrix ``A`` and each
round adds ``R @ A`` (float32 products, exact for fewer than 2**24 nodes)
until nothing changes.  Independent of the engine under test.

Returns the same form as :func:`bench.reference.datalog.evaluate`.
"""
from __future__ import annotations

import numpy as np

from bench.reference.datalog import intern, parse

TC_RULES = [(("T", ("X", "Y")), [("e", ("X", "Y"))]),
            (("T", ("X", "Z")), [("T", ("X", "Y")), ("e", ("Y", "Z"))])]


def evaluate(rules_text: str, tables: dict, max_rounds: int | None = None):
    """Closure of ``tables["e"]``; with ``max_rounds``, the facts after that
    many productive rounds (round 1 copies ``e`` into ``T``)."""
    if parse(rules_text) != TC_RULES:
        raise ValueError("tc_matrix evaluates the two transitive-closure "
                         "rules only")
    terms, ids = intern(tables)
    n = len(terms)
    if n >= 1 << 24:
        raise ValueError(f"{n} nodes: float32 path counts would round")
    edges = ids["e"]
    a = np.zeros((n, n), np.float32)
    a[edges[:, 0], edges[:, 1]] = 1.0
    reach = a > 0
    rounds = 1 if len(edges) else 0
    while max_rounds is None or rounds < max_rounds:
        nxt = reach | ((reach.astype(np.float32) @ a) > 0)
        if (nxt == reach).all():
            break
        reach = nxt
        rounds += 1
    src, dst = np.nonzero(reach)
    e = np.unique(edges, axis=0)
    return {"terms": terms,
            "facts": {"e": e, "T": np.stack([src, dst], 1).astype(np.int64)},
            "rounds": rounds}
