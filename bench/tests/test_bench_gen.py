"""The generators: Graph500's Kronecker skew and the road grid's degree."""
import numpy as np
import pytest

from bench import harness, workload

KRON = {"generator": "kronecker", "scale": 12, "edgefactor": 16, "A": 0.57,
        "B": 0.19, "C": 0.19, "undirected": True}
ROAD = {"generator": "road_grid", "side": 64, "keep": 0.7, "w_min": 1.0,
        "w_max": 100.0}


def gen(config, seed):
    mod = harness.load_module("gen", config["generator"])
    return mod.generate(config, workload.rng_for(seed, 0))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_kronecker_shape_and_skew(seed):
    n, s, d, w = gen(KRON, seed)
    assert n == 4096 and s.size == 2 * 16 * n  # both arcs of each edge
    half = s.size // 2
    assert np.array_equal(s[:half], d[half:]) and np.array_equal(w[:half],
                                                                  w[half:])
    assert w.dtype == np.float32 and w.min() >= 0 and w.max() < 1
    deg = np.sort(np.bincount(d, minlength=n))[::-1]
    # R-MAT (.57, .19, .19) at scale 12: the top 256 vertices hold ~63% of
    # the in-arcs (42% at scale 14, 34% at 15), against ~72% at every
    # scale for the Zipf generator the engine's own tests use
    assert 0.60 < deg[:256].sum() / deg.sum() < 0.67
    assert 0.15 < (deg == 0).mean() < 0.22  # isolated vertices


def test_generators_follow_the_seed():
    a, b, c = gen(KRON, 1), gen(KRON, 1), gen(KRON, 2)
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("side,lo,hi", [(64, 2.70, 2.82), (128, 2.74, 2.82)])
def test_road_grid_degree(side, lo, hi):
    n, s, d, w = gen(dict(ROAD, side=side), 3)
    assert n == side * side
    assert lo < s.size / n < hi  # USA-road-t.NY: 2.78 arcs per vertex
    # planar grid: every arc joins row-major neighbours
    assert set(np.unique(np.abs(s - d)).tolist()) <= {1, side}
    assert w.min() >= 1.0 and w.max() < 100.0


def test_job_sources_are_one_pool_in_another_order():
    n, s, _, _ = gen(KRON, 4)
    deg = np.bincount(s, minlength=n)
    rule = {"rule": "random", "min_degree": 1}

    def draw(seed):
        return workload.job_sources(n, s, rule, 8, workload.rng_for(1, 1),
                                    workload.rng_for(seed, 1))

    a, b = draw(9), draw(2 ** 31 + 9)
    assert (deg[a] >= 1).all() and np.unique(a).size == 8
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    with pytest.raises(ValueError, match="unknown source rule"):
        workload.job_sources(n, s, {"rule": "ids"}, 3, workload.rng_for(9, 1),
                             workload.rng_for(9, 1))


def test_rmat_pairs_follow_the_quadrants():
    u, v = workload.rmat_pairs(1 << 10, 20000, [0.5, 0.1, 0.1, 0.3],
                               workload.rng_for(3, 2))
    assert u.min() >= 0 and max(u.max(), v.max()) < 1 << 10
    top_u, top_v = u >> 9, v >> 9  # the quadrant of the first level drawn
    share = [np.mean((top_u == i) & (top_v == j)) for i, j in
             ((0, 0), (0, 1), (1, 0), (1, 1))]
    assert np.allclose(share, [0.5, 0.1, 0.1, 0.3], atol=0.015)


def test_edit_stream_keeps_the_multiset():
    n, s, d, w = gen(dict(KRON, scale=9), 5)
    params = {"rmat": [0.5, 0.1, 0.1, 0.3], "inserts": 50, "delete_lag": 5}
    es = workload.EditStream(n, s, d, w, params, workload.rng_for(5, 2))
    live = {}
    for a, b, x in zip(s.tolist(), d.tolist(), w.tolist()):
        live.setdefault((a, b), []).append(x)
    sent = []
    for k in range(120):  # past the first capacity: the arrays grow
        bt = es.next_batch()
        assert bt["ins_src"].size == 2 * 50
        pairs = set(zip(bt["del_src"].tolist(), bt["del_dst"].tolist()))
        assert all((v, u) in pairs for u, v in pairs)  # both ways
        # the deletes are the live pairs that batch k - 5 inserted
        want = set() if k < 5 else {
            p for p in sent[k - 5] if p in live or p[::-1] in live}
        assert {(min(p), max(p)) for p in pairs} == \
            {(min(p), max(p)) for p in want}
        assert es.last_edits == 50 + len({(min(p), max(p)) for p in pairs})
        for p in pairs:
            live.pop(p, None)
        for a, b, x in zip(bt["ins_src"].tolist(), bt["ins_dst"].tolist(),
                           bt["ins_w"].tolist()):
            live.setdefault((a, b), []).append(x)
        sent.append(list(zip(bt["ins_src"][:50].tolist(),
                             bt["ins_dst"][:50].tolist())))
    rs, rd, rw = es.edges()
    got = sorted(zip(rs.tolist(), rd.tolist(), rw.tolist()))
    want = sorted((a, b, x) for (a, b), xs in live.items() for x in xs)
    assert got == want
