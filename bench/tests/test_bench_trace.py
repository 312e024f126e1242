"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a small trace recorded on a TPU v5e chip.

``fixtures/small.xplane.pb``: two PageRank jobs of three supersteps each
on a scale-9 Kronecker graph, Pallas sweep on, inside a ``window`` span
with a ``job`` span around each job.
"""
from pathlib import Path

import numpy as np
import pytest

from bench import harness, tracereduce
from bench.tests import tiny

FIXTURE = Path(__file__).parent / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tracereduce.reduce_trace(FIXTURE, harness.KERNEL, harness.SPANS)


def test_busy_and_window(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(summary.idle_by_span.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert set(summary.idle_by_span) <= {"none", "job"}
    assert summary.idle_by_span["job"] > 0  # host syncs inside a job


def test_kernel_calls_and_breakdown(summary):
    assert summary.kernel_calls and set(summary.kernel_calls) == \
        set(summary.kernel_s)
    for text in summary.kernel_calls:
        assert tracereduce.op_name(text).split(".")[0] == "block_sweep"
    bd = summary.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # self times: a while loop is not counted again for its body
    total = sum(summary.op_s.values())
    assert total <= summary.busy_s * (1 + 1e-6)
    gaps = [s for _, s in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_readers_on_the_trace(summary, monkeypatch):
    c = tiny.cell("g500-s16.pagerank")
    run = harness.Run(cell=c, trace=summary, arcs=16384,
                      peaks={"hbm_bytes_per_s": 819e9})
    run.jobs = [{"iterations": 3, "edges_processed": 40000}]
    share = harness.load_module("metrics", "block_sweep_roofline").read(run)
    assert 0 < share < 100
    idle = harness.load_module("metrics", "device_idle.job").read(run)
    assert idle == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))
    run.trace = None
    assert harness.load_module("metrics", "block_sweep_roofline").read(run) \
        is None


def test_union_and_self_times():
    s = np.array([0.0, 1.0, 2.0, 5.0, 10.0])
    e = np.array([9.0, 2.0, 4.0, 6.0, 11.0])
    us, ue = tracereduce._union(s, e)
    assert us.tolist() == [0.0, 10.0] and ue.tolist() == [9.0, 11.0]
    # [0, 9) holds three others: its own time is what they leave
    assert tracereduce._self_times(s, e).tolist() == [5.0, 1.0, 2.0, 1.0, 1.0]
    assert tracereduce.op_name("%fusion.7 = f32[8] fusion(x)") == "fusion.7"
    assert tracereduce.op_name("jit_chunk(123)") == "jit_chunk(123)"
