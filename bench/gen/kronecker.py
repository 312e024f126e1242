"""Graph500 Kronecker generator (Graph500 specification, section 3).

The specification's reference generator, vectorised: each of the
``edgefactor * 2**scale`` edges picks one quadrant of the adjacency
matrix per bit level with initiator probabilities A, B, C and
D = 1 - A - B - C; vertex ids are then scrambled by a random
permutation, and the edge list is shuffled. Duplicates and self-loops
are kept as generated. ``undirected`` stores every edge as both arcs,
the way Graph500 and the LDBC Graphalytics ``graph500-*`` data sets
hold it. Weights are uniform in [0, 1), as in Graph500's SSSP kernel.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    """-> (n, src, dst, w): int64 arcs and float32 weights."""
    scale = int(params["scale"])
    n = 2 ** scale
    m = int(params["edgefactor"]) * n
    a, b, c = (float(params[k]) for k in ("A", "B", "C"))
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, dtype=np.int64)
    j = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    i, j = perm[i], perm[j]
    order = rng.permutation(m)
    i, j = i[order], j[order]
    w = rng.random(m, dtype=np.float32)
    if params.get("undirected", True):
        i, j = np.concatenate([i, j]), np.concatenate([j, i])
        w = np.concatenate([w, w])
    return n, i, j, w
