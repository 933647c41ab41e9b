"""Uniform random directed graph with a fixed out-degree, as host arrays.

The shape of OpenRuleBench's transitive-closure data (Liang, Fodor, Wan &
Kifer, 2009): every node has ``out_degree`` distinct successors drawn
uniformly from all ``nodes`` nodes.  Node terms are whole numbers.  The
seed draws the whole graph; every seed gives the same number of edges.
"""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int) -> dict:
    """``{"e": (nodes * out_degree, 2) int64 ndarray}`` for the
    configuration's ``nodes`` and ``out_degree``, drawn from ``seed``, its
    edges in an order drawn from the seed too."""
    n, d = config["nodes"], config["out_degree"]
    rng = np.random.default_rng(seed)
    dst = np.stack([rng.choice(n, size=d, replace=False) for _ in range(n)])
    edges = np.stack([np.repeat(np.arange(n), d), dst.reshape(-1)], axis=1)
    return {"e": edges[rng.permutation(len(edges))].astype(np.int64)}
