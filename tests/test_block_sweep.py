"""Parity property suite for the Pallas block sweep.

The acceptance bar from the kernel module's docstring: ``use_pallas=True``
must reproduce the dense reference — min/max VALUES bitwise equal, and
sum values within ``block_sweep.sum_tolerance`` of the graph's largest
in-degree (the same f32 terms, summed in the MXU's order instead of the
scatter's) — for sum- and min-combine programs, single-lane and
lane-batched, fused and host execution, flat and sub-block-masked
sweeps, with and without padding lanes.

EVERY counter (iterations, updates, edges processed, block loads, bytes
loaded) is held identical too, but that holds only under the
interpreter, where the kernel's sum scatter-adds in edge order. The
chip's sum form, the one-hot MXU product, reorders each slot's sum, so a
block's residual can cross the convergence threshold a superstep
earlier or later and the counters drift (on a v5e chip, 484 PageRank
supersteps against 478 dense at scale 16). The ``mxu_sum`` tests run
that form through the engines under the interpreter: values within the
stated tolerance, counters bounded.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import algorithms as A
from repro.core import graph as G
from repro.core.engine import (EngineConfig, StructureAwareEngine,
                               coupling_from_counts)
from repro.kernels import ops, ref
from repro.kernels.block_sweep import CHUNK_TILES, sum_tolerance
from repro.serve.lanes import LaneEngine
from repro.stream import StreamingEngine

RNG = np.random.default_rng(0)

_COUNTERS = ("iterations", "updates", "edges_processed", "block_loads",
             "bytes_loaded", "converged")

_PROGRAMS = {
    "pagerank": lambda: A.pagerank(),     # sum combine
    "sssp": lambda: A.sssp(0),            # min combine, weighted
    "cc": lambda: A.cc(),                 # min combine, label propagation
}

_FAMILIES = {
    "k_sssp": (lambda: A.k_source_sssp(), [3, 77]),    # min
    "k_bfs": (lambda: A.k_source_bfs(), [1, 40]),      # min, unweighted
    "ppr": (lambda: A.k_personalized_pagerank(),
            [[2], [9, 11]]),                           # sum (MXU combine)
}


def _assert_values(combine, got, want, g, label):
    """Bitwise for min/max; within the kernel's stated bound for sum."""
    if combine == "sum":
        np.testing.assert_allclose(got, want,
                                   rtol=sum_tolerance(g.in_deg.max()),
                                   atol=0, err_msg=label)
    else:
        assert np.array_equal(got, want), f"{label}: values not bitwise"


def _assert_counters(mp, md, label):
    for f in _COUNTERS:
        assert getattr(mp, f) == getattr(md, f), \
            f"{label}: counter {f} diverged: {getattr(mp, f)} " \
            f"vs {getattr(md, f)}"


# -- per-tile segmented min/max kernels vs the scatter oracles ---------------
@pytest.mark.parametrize("e,c", [(1, 128), (100, 256), (513, 512),
                                 (2048, 128)])
@pytest.mark.parametrize("combine", ["min", "max"])
def test_seg_select_sweep(e, c, combine):
    ident = 1e18 if combine == "min" else -1e18
    msg = jnp.asarray(RNG.normal(size=e).astype(np.float32))
    dst = jnp.asarray(RNG.integers(0, c, size=e).astype(np.int32))
    fn = ops.edge_block_min if combine == "min" else ops.edge_block_max
    rfn = ref.edge_block_min if combine == "min" else ref.edge_block_max
    got = fn(msg, dst, c, ident)
    want = rfn(msg, dst, c, ident)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@given(e=st.integers(1, 3000), c=st.sampled_from([128, 256, 512]),
       seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_seg_min_property_bitwise(e, c, seed):
    rng = np.random.default_rng(seed)
    msg = jnp.asarray(rng.normal(size=e).astype(np.float32))
    dst = jnp.asarray(rng.integers(0, c, size=e).astype(np.int32))
    got = ops.edge_block_min(msg, dst, c, 1e18)
    want = ref.edge_block_min(msg, dst, c, 1e18)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -- the combine kernel's two sum forms ---------------------------------------
@pytest.mark.parametrize("nl", [1, 8])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_combine_kernel_forms(combine, nl):
    """The chip's form of the combine kernel (one-hot on the MXU for sum,
    ``edge_order=False``) against the interpreter's edge-order form, on
    one chunk of random rows: min/max bitwise, sum within the reordering
    bound of its terms."""
    from repro.kernels.block_sweep import CHUNK_TILES, _combine_call
    rng = np.random.default_rng(nl)
    c, tile = 128, 512
    ident = {"sum": 0.0, "min": 1e18, "max": -1e18}[combine]
    msg = jnp.asarray(rng.random((CHUNK_TILES, nl, tile), np.float32))
    dst = jnp.asarray(rng.integers(0, c, (CHUNK_TILES, 1, tile), np.int32))
    agg = jnp.asarray(rng.random((nl, c), np.float32)) if combine == "sum" \
        else jnp.full((nl, c), ident, jnp.float32)
    got, want = (np.asarray(_combine_call(
        agg, msg, dst, combine=combine, identity=ident, interpret=True,
        edge_order=eo)) for eo in (False, True))
    if combine != "sum":
        assert np.array_equal(got, want)
        return
    # the aggregate's initial value is one more term per slot
    terms = np.bincount(np.asarray(dst).ravel(), minlength=c) + 1
    assert np.all(np.abs(got - want) <= sum_tolerance(terms) * np.abs(want))


# -- single-lane engine parity: fused device loop ----------------------------
@given(n=st.integers(200, 500), avg=st.integers(3, 6),
       seed=st.integers(0, 1000),
       prog=st.sampled_from(sorted(_PROGRAMS)),
       subblocks=st.sampled_from([1, 8]))
@settings(max_examples=6, deadline=None)
def test_fused_sweep_bitwise_property(n, avg, seed, prog, subblocks):
    g = G.powerlaw_graph(n, avg, seed=seed, weighted=(prog == "sssp"))
    program = _PROGRAMS[prog]()
    kw = dict(t2=1e-9, width=4, block_size=64, subblocks=subblocks)
    rd = StructureAwareEngine(g, program, EngineConfig(**kw)).run()
    rp = StructureAwareEngine(
        g, program, EngineConfig(use_pallas=True, **kw)).run()
    _assert_values(program.combine, rp.values, rd.values, g,
                   f"{prog} sb={subblocks}")
    _assert_counters(rp.metrics, rd.metrics, f"{prog} sb={subblocks}")


# -- single-lane engine parity at the edges of the chunk's slice window ------
# tile geometries (graph n, avg_deg, seed; sub-blocks per block) where a
# chunk's window of CHUNK_TILES rows meets the storage's ends
_GEOMETRIES = {
    # 6 tile rows, fewer than a chunk: one window of them all, with cov
    "short_s8": ((300, 4, 5), 8),
    # tile_cnt [11, 2] over 13 rows: the hub's second chunk and the last
    # block's chunk are shifted back over the hub's live rows
    "shifted": ((128, 48, 2), 1),
    "shifted_s8": ((128, 48, 2), 8),
}


def _geometry_pair(prog, geometry, fused):
    (n, avg, seed), subblocks = _GEOMETRIES[geometry]
    g = G.powerlaw_graph(n, avg, seed=seed, weighted=(prog == "sssp"))
    program = _PROGRAMS[prog]()
    kw = dict(t2=1e-9, width=4, block_size=64, subblocks=subblocks)
    ed = StructureAwareEngine(g, program, EngineConfig(**kw))
    ep = StructureAwareEngine(g, program, EngineConfig(use_pallas=True, **kw))
    store = ep.plan.unified
    n_tiles = store.src.shape[0]
    k = min(CHUNK_TILES, n_tiles)
    starts = [t0 + i for t0, cnt in zip(store.tile_start, store.tile_cnt)
              for i in range(0, cnt, k)]
    if geometry.startswith("short"):
        assert n_tiles < CHUNK_TILES, n_tiles
    else:
        assert max(starts) > n_tiles - k, (starts, n_tiles)
    rd, rp = ed.run(fused=fused), ep.run(fused=fused)
    label = f"{'fused' if fused else 'host'} {prog} {geometry}"
    _assert_values(program.combine, rp.values, rd.values, g, label)
    _assert_counters(rp.metrics, rd.metrics, label)


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("prog", ["pagerank", "sssp"])
def test_host_path_bitwise(prog, geometry):
    _geometry_pair(prog, geometry, fused=False)


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("prog", ["pagerank", "sssp"])
def test_fused_sweep_window_bitwise(prog, geometry):
    _geometry_pair(prog, geometry, fused=True)


# -- lane-batched parity (the PPR scatter fix) -------------------------------
def _lane_pair(n, seed, family, subblocks, padding):
    g = G.powerlaw_graph(n, avg_deg=5, seed=seed, weighted=True)
    cfg = EngineConfig(t2=1e-9, width=4, block_size=64,
                       subblocks=subblocks)
    se = StreamingEngine(g, A.pagerank(), cfg)
    es = se.snapshot()
    factory, params = _FAMILIES[family]
    fam = factory()
    vals0, vconst = fam.lane_init(se.n, params)
    lane_active = np.array([True, not padding])
    ed = es.ed if family == "ppr" else es.ed._replace(
        aux=jnp.zeros(se.n, jnp.float32))
    kw = dict(ed=ed,
              coupling=coupling_from_counts(es.coupling_counts, fam,
                                            es.engine.plan.block_size),
              values0=vals0, vconst=vconst, lane_active=lane_active,
              edge_counts=es.edge_counts)
    rd = LaneEngine(es.engine, fam, use_pallas=False).run(**kw)
    rp = LaneEngine(es.engine, fam, use_pallas=True).run(**kw)
    return g, rd, rp


@given(seed=st.integers(0, 1000),
       family=st.sampled_from(sorted(_FAMILIES)),
       subblocks=st.sampled_from([1, 8]),
       padding=st.booleans())
@settings(max_examples=6, deadline=None)
def test_lane_sweep_bitwise_property(seed, family, subblocks, padding):
    g, rd, rp = _lane_pair(400, seed, family, subblocks, padding)
    label = f"{family} sb={subblocks} pad={padding}"
    _assert_values(_FAMILIES[family][0]().combine, rp.values, rd.values, g,
                   label)
    _assert_counters(rp.metrics, rd.metrics, label)
    assert np.array_equal(rd.lane_iterations, rp.lane_iterations), label
    assert np.array_equal(rd.lane_converged, rp.lane_converged), label


# -- the chip's sum form through the engines ----------------------------------
@pytest.fixture
def mxu_sum(monkeypatch):
    """Run the kernel's chip form of the sum (the one-hot MXU product)
    under the interpreter, in place of its edge-order scatter, through
    freshly built sweeps."""
    from repro.kernels import block_sweep as bs
    monkeypatch.setattr(bs, "_BUILDER_CACHE", {})
    monkeypatch.setattr(bs, "_combine_call", functools.partial(
        bs._combine_call, edge_order=False))


def _assert_counters_near(mp, md, label, frac=0.25):
    """Both runs converge, and their work counters stay within ``frac``
    of each other: the reordered sums shift when blocks converge, not how
    much work convergence takes."""
    assert mp.converged and md.converged, label
    for f in ("iterations", "edges_processed", "block_loads"):
        a, b = getattr(mp, f), getattr(md, f)
        assert abs(a - b) <= frac * b, f"{label}: {f} {a} vs {b}"


@pytest.mark.parametrize("seed,subblocks", [(1, 1), (2, 8), (3, 1)])
def test_fused_sweep_mxu_sum(mxu_sum, seed, subblocks):
    g = G.powerlaw_graph(400, 5, seed=seed)
    kw = dict(t2=1e-9, width=4, block_size=64, subblocks=subblocks)
    rd = StructureAwareEngine(g, A.pagerank(), EngineConfig(**kw)).run()
    rp = StructureAwareEngine(
        g, A.pagerank(), EngineConfig(use_pallas=True, **kw)).run()
    label = f"mxu pagerank seed={seed} sb={subblocks}"
    _assert_values("sum", rp.values, rd.values, g, label)
    _assert_counters_near(rp.metrics, rd.metrics, label)


@pytest.mark.parametrize("subblocks", [1, 8])
def test_lane_sweep_mxu_sum(mxu_sum, subblocks):
    g, rd, rp = _lane_pair(400, 4, "ppr", subblocks, padding=True)
    label = f"mxu ppr sb={subblocks}"
    _assert_values("sum", rp.values, rd.values, g, label)
    _assert_counters_near(rp.metrics, rd.metrics, label)
    assert np.array_equal(rd.lane_converged, rp.lane_converged), label


def test_lane_engine_inherits_engine_flag():
    """LaneEngine(use_pallas=None) follows the geometry owner's config, so
    a Pallas engine serves Pallas lanes without restating the flag."""
    g = G.powerlaw_graph(200, 4, seed=0)
    eng = StructureAwareEngine(
        g, A.pagerank(),
        EngineConfig(block_size=64, width=2, use_pallas=True))
    assert LaneEngine(eng, A.k_source_sssp()).use_pallas is True
    assert LaneEngine(eng, A.k_source_sssp(),
                      use_pallas=False).use_pallas is False


def test_service_use_pallas_plumbing():
    """QueryService(use_pallas=True) answers bitwise-identically to the
    dense service over the same streaming engine."""
    from repro.serve import Query, QueryService
    g = G.powerlaw_graph(400, avg_deg=5, seed=7, weighted=True)
    cfg = EngineConfig(t2=1e-9, width=4, block_size=64)
    results = {}
    for flag in (False, True):
        se = StreamingEngine(g, A.pagerank(), cfg)
        svc = QueryService(se, max_lanes=2, prewarm=False,
                           use_pallas=flag)
        svc.submit(Query(kind="sssp", source=3))
        svc.submit(Query(kind="sssp", source=11))
        results[flag] = svc.run_pending()
    for rd, rp in zip(results[False], results[True]):
        assert np.array_equal(rd.values, rp.values)
        assert rd.iterations == rp.iterations
