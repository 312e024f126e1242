"""Road-shaped planar grid: the degree and shape of a DIMACS road network.

A ``side x side`` grid whose horizontal and vertical neighbour edges are
each kept with probability ``keep``, stored as both arcs with one
positive travel time, drawn uniform in ``[w_min, w_max)``. Vertex ids
follow the grid's row-major order. Keeping 70% of the edges gives 2.78
arcs per vertex, the mean degree of DIMACS USA-road-t.NY.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    """-> (n, src, dst, w): int64 arcs and float32 weights."""
    side = int(params["side"])
    n = side * side
    ids = np.arange(n, dtype=np.int64).reshape(side, side)
    u = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    v = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    kept = rng.random(u.size) < float(params["keep"])
    u, v = u[kept], v[kept]
    w = rng.uniform(float(params["w_min"]), float(params["w_max"]),
                    size=u.size).astype(np.float32)
    return n, np.concatenate([u, v]), np.concatenate([v, u]), \
        np.concatenate([w, w])
