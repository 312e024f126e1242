"""Plain references and the numbers that decide ``correct``.

Nothing here imports the engine: the references take the benchmark's
own edge list (``n, src, dst, w`` from the generator, with any edits
applied by the benchmark itself) and compute the answer in float64 with
numpy and scipy. The comparisons return one number each; the harness
holds it to the limit that the configuration's ``checks`` state.

``control`` versions compute the same answer in bfloat16 (each value and
each message rounded to bfloat16, sums in float32): the step below the
float32 that the configurations state. A control must read as not
correct under every limit it is held to.
"""
from __future__ import annotations

import numpy as np

INF_CUT = 1e17  # the engine's finite infinity is 1e18


def bf16(x) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even),
    returned as float32."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


# -- PageRank ---------------------------------------------------------------
def pagerank(n, src, dst, damping, tol=1e-13, low=False):
    """x = (1-d)/n + d * A^T (x / outdeg); dangling mass vanishes, as in
    the engine's program (outdeg floored at 1). ``low``: the bfloat16
    control."""
    outdeg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    x = np.full(n, 1.0 / n)
    if low:
        x = bf16(x)
    # float64 reaches tol in about 200 steps; bfloat16 stalls at its own
    # rounding, and 300 steps leave 0.85^300 of the start behind
    for _ in range(300 if low else 3000):
        msg = x[src] / outdeg[src]
        if low:
            msg = bf16(msg).astype(np.float32)
        agg = np.bincount(dst, msg, minlength=n)
        nx = (1 - damping) / n + damping * agg
        if low:
            nx = bf16(nx).astype(np.float64)
        done = np.abs(nx - x).sum() < tol
        x = nx
        if done:
            break
    return x


# -- shortest paths ---------------------------------------------------------
def _lightest(n, src, dst, w):
    """One arc per (src, dst) pair, the lightest: a sparse matrix would add
    parallel arcs up."""
    key = np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)
    order = np.lexsort((w, key))
    key, w = key[order], np.asarray(w, np.float64)[order]
    first = np.r_[True, key[1:] != key[:-1]]
    return key[first] // n, key[first] % n, w[first]


def dijkstra(n, src, dst, w, sources) -> np.ndarray:
    """(len(sources), n) shortest distances in float64; inf = unreachable."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra as sp_dijkstra
    s, d, ww = _lightest(n, src, dst, w)
    m = csr_matrix((ww, (s, d)), shape=(n, n))
    return np.atleast_2d(sp_dijkstra(m, directed=True, indices=sources))


def sssp_low(n, src, dst, w, source) -> np.ndarray:
    """The bfloat16 control: Bellman-Ford with every distance and every
    path sum rounded to bfloat16."""
    s, d, ww = _lightest(n, src, dst, w)
    ww = bf16(ww.astype(np.float32))
    dist = np.full(n, np.inf, np.float32)
    dist[source] = 0.0
    for _ in range(n):
        cand = bf16(dist[s] + ww)
        nd = dist.copy()
        np.minimum.at(nd, d, cand)
        if np.array_equal(nd, dist):
            break
        dist = nd
    return dist.astype(np.float64)


def dist_gap(got, want) -> float:
    """Largest relative distance gap over reachable vertices; inf when the
    reachable sets differ or a value is not finite."""
    got = np.asarray(got, np.float64)
    reach = np.isfinite(want)
    if np.isnan(got).any() or not np.array_equal(got < INF_CUT, reach):
        return float("inf")
    if not reach.any():
        return 0.0
    err = np.abs(got[reach] - want[reach]) / np.maximum(want[reach], 1e-30)
    return float(err.max())
