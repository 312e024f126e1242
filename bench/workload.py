"""The one traffic generator: what a traffic mix's data file asks for.

A mix's ``kind`` names the module under ``bench/kinds/`` that drives it;
this module draws what it sends:

- ``jobs`` — analytics jobs of one vertex program. Jobs of a program with
  a source (SSSP) take their sources from :func:`job_sources`.
- ``edits`` — micro-batches of undirected edge edits against a live
  graph, built by :class:`EditStream`.

The graph is the configuration's, fixed by its ``graph_seed``; every
other draw comes from the run's seed, so the same seed sends the same
sources and edits.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use (graph, sources, edits) of one
    seed. Any whole number is a valid seed."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def job_sources(n: int, src: np.ndarray, rule: dict, count: int,
                pool_rng: np.random.Generator,
                order_rng: np.random.Generator) -> np.ndarray:
    """A pool of ``count`` job sources, in the order the jobs run.

    ``{"rule": "random", "min_degree": k}``: the pool is drawn from
    ``pool_rng`` uniformly, without replacement, among vertices with at
    least ``k`` out-arcs; ``order_rng`` shuffles it. With the pool drawn
    from the configuration's seed, every run sends the same set of jobs
    in another order.
    """
    if rule["rule"] != "random":
        raise ValueError(f"unknown source rule {rule['rule']!r}")
    deg = np.bincount(src, minlength=n)
    cand = np.flatnonzero(deg >= int(rule["min_degree"]))
    pool = pool_rng.choice(cand, size=count, replace=False)
    return order_rng.permutation(pool)


def rmat_pairs(n: int, count: int, abcd, rng: np.random.Generator):
    """``count`` vertex pairs from the R-MAT generator with quadrant
    probabilities ``abcd`` over ``ceil(log2 n)`` bit levels, ids taken
    modulo ``n``."""
    a, b, c, _ = (float(x) for x in abcd)
    levels = max(1, int(np.ceil(np.log2(n))))
    u = np.zeros(count, np.int64)
    v = np.zeros(count, np.int64)
    for bit in range(levels):
        r = rng.random(count)
        u |= (r >= a + b).astype(np.int64) << bit
        v |= (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(
            np.int64) << bit
    return u % n, v % n


class EditStream:
    """Batches of undirected edits over a live edge multiset: a sliding
    window over a stream of R-MAT edge insertions.

    Batch ``k`` inserts ``inserts`` new edges, each a pair drawn from
    R-MAT with the mix's ``rmat`` probabilities and a weight uniform in
    [0, 1), and deletes the pairs that batch ``k - delete_lag`` inserted
    and that are still live. Every edit applies to both arcs. A delete
    removes every live copy of its pair, both ways, which is the engine's
    pair-delete semantics. The generator keeps its own copy of the
    multiset, which is what the reference runs on. ``last_edits`` is the
    number of edits of the batch just made.
    """

    def __init__(self, n, src, dst, w, params: dict,
                 rng: np.random.Generator):
        self.n = n
        self.p = params
        self.rng = rng
        cap = 2 * src.size
        self.src = np.empty(cap, np.int64)
        self.dst = np.empty(cap, np.int64)
        self.w = np.empty(cap, np.float32)
        self.alive = np.zeros(cap, bool)
        self.size = 0
        self._append(src, dst, w)
        # pair -> arc positions: the base arcs through one sorted key
        # array, the inserted ones through a dict
        keys = src * n + dst
        self.base_order = np.argsort(keys, kind="stable")
        self.base_keys = keys[self.base_order]
        self.added: dict[int, list[int]] = {}
        self.inserted: list[list[tuple[int, int]]] = []  # pairs per batch
        self.last_edits = 0

    def _append(self, s, d, w):
        lo, hi = self.size, self.size + s.size
        if hi > self.src.size:
            cap = 2 * hi
            for name in ("src", "dst", "w", "alive"):
                old = getattr(self, name)
                new = np.zeros(cap, old.dtype)
                new[:lo] = old[:lo]
                setattr(self, name, new)
        self.src[lo:hi], self.dst[lo:hi], self.w[lo:hi] = s, d, w
        self.alive[lo:hi] = True
        self.size = hi
        return lo, hi

    def _at(self, k: int) -> np.ndarray:
        """Positions of every copy, live or dead, of the arc key ``k``."""
        lo, hi = np.searchsorted(self.base_keys, [k, k + 1])
        return np.r_[self.base_order[lo:hi],
                     np.asarray(self.added.get(k, []), np.int64)]

    def _live(self, s, d) -> bool:
        return bool(self.alive[self._at(s * self.n + d)].any())

    def _kill(self, s, d) -> None:
        for k in {s * self.n + d, d * self.n + s}:
            at = self._at(k)
            self.added.pop(k, None)
            at = at[self.alive[at]]
            self.alive[at] = False

    def edges(self):
        """The live multiset: (src, dst, w) arcs."""
        live = self.alive[:self.size]
        return (self.src[:self.size][live], self.dst[:self.size][live],
                self.w[:self.size][live])

    def next_batch(self) -> dict:
        """-> dict(ins_src, ins_dst, ins_w, del_src, del_dst): arcs, both
        ways, ready for ``DeltaBatch``; the live multiset moves on."""
        p, rng, n = self.p, self.rng, self.n
        lag = int(p["delete_lag"])
        pairs = []
        if len(self.inserted) >= lag:
            pairs = sorted({(min(u, v), max(u, v))
                            for u, v in self.inserted[-lag]
                            if self._live(u, v)})
        self.inserted = self.inserted[-lag:]
        a, b = rmat_pairs(n, int(p["inserts"]), p["rmat"], rng)
        w = rng.random(a.size, dtype=np.float32)
        for u, v in pairs:
            self._kill(u, v)
        ins_s, ins_d = np.concatenate([a, b]), np.concatenate([b, a])
        lo, _ = self._append(ins_s, ins_d, np.concatenate([w, w]))
        for i, k in enumerate((ins_s * n + ins_d).tolist(), lo):
            self.added.setdefault(k, []).append(i)
        self.inserted.append(list(zip(a.tolist(), b.tolist())))
        self.last_edits = a.size + len(pairs)
        ds = np.array([u for u, _ in pairs], np.int64)
        dd = np.array([v for _, v in pairs], np.int64)
        # a pair delete names each direction once; a self-loop has one
        ret_s, ret_d = np.concatenate([ds, dd]), np.concatenate([dd, ds])
        keep = np.r_[np.ones(ds.size, bool), ds != dd]
        return dict(ins_src=ins_s, ins_dst=ins_d,
                    ins_w=np.concatenate([w, w]),
                    del_src=ret_s[keep], del_dst=ret_d[keep])
