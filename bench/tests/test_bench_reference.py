"""The plain references against the engine, and the controls against the
limits, at a size the CPU holds.

The controls are the references computed in bfloat16, the step below the
float32 the configurations state: each must read above the limit its
configuration sets, and the engine's answers below it.
"""
import numpy as np
import pytest

from bench import harness, reference, workload
from bench.tests import tiny


def graph(name):
    c = tiny.cell(name)
    return c, harness.build_graph(c)


def limit(c, prog, name):
    return c.config["programs"][prog]["limits"][name]


@pytest.fixture(scope="module")
def kron():
    return graph("g500-s16.pagerank")


def test_pagerank_engine_within_limit_and_control_not(kron):
    from repro.core.engine import StructureAwareEngine
    from repro.core.graph import from_edges
    c, (n, s, d, w) = kron
    prog = harness.load_module("programs", "pagerank")
    params = c.config["programs"]["pagerank"]
    res = StructureAwareEngine(from_edges(n, s, d, w), prog.make(params),
                               harness.engine_config(c.config)).run()
    got = prog.compare(n, s, d, w, params, [(None, res.values)])
    low = prog.compare(n, s, d, w, params, [(None, res.values)], low=True)
    lim = limit(c, "pagerank", "rank_l1")
    assert got["rank_l1"] < lim < low["rank_l1"]


@pytest.mark.parametrize("name", ["g500-s16.sssp", "road-grid128.sssp"])
def test_sssp_job_is_the_cold_run_and_matches_dijkstra(name):
    """The harness runs every job on one engine from a new source's start
    state; that must be the very run a new engine for that source makes."""
    from repro.core.engine import StructureAwareEngine
    from repro.core.graph import from_edges
    c, (n, s, d, w) = graph(name)
    prog = harness.load_module("programs", "sssp")
    params = c.config["programs"]["sssp"]
    g = from_edges(n, s, d, w)
    cfg = harness.engine_config(c.config)
    a, b = workload.job_sources(n, s, params["sources"], 2,
                                workload.rng_for(12, 1),
                                workload.rng_for(13, 1))
    eng = StructureAwareEngine(g, prog.make(params, a), cfg)
    eng.run()
    job = eng.run(warm=harness.cold_start(eng, prog.make(params, b).init(g)[0]))
    fresh = StructureAwareEngine(g, prog.make(params, b), cfg).run()
    assert np.array_equal(job.values, fresh.values)
    assert job.metrics.iterations == fresh.metrics.iterations
    assert job.metrics.edges_processed == fresh.metrics.edges_processed
    answers = [(b, job.values)]
    got = prog.compare(n, s, d, w, params, answers)["dist_gap"]
    low = prog.compare(n, s, d, w, params, answers, low=True)["dist_gap"]
    assert got < limit(c, "sssp", "dist_gap") < low


def test_dist_gap_reads_reachability():
    want = np.array([0.0, 1.0, np.inf])
    assert reference.dist_gap([0.0, 1.0, 1e18], want) == 0.0
    assert reference.dist_gap([0.0, 1e18, 1e18], want) == float("inf")
    assert reference.dist_gap([0.0, 1.0, 5.0], want) == float("inf")
    assert reference.dist_gap([0.0, 1.5, 1e18], want) == pytest.approx(0.5)


def test_bf16_rounding():
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -9, 3.14159], np.float32)
    got = reference.bf16(x)
    assert got[0] == 1.0 and got[1] == 1.0  # a tie goes to even
    assert got[2] == 1.0 + 2 ** -7
    assert abs(got[3] - 3.140625) < 1e-7
