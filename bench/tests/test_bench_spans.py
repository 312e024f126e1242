"""The program's spans, scopes and host-sync counts, and the numbers
``bench/spans.py`` reads from them, on small traces recorded on a TPU v5e
chip.

``fixtures/spans_jobs.xplane.pb.gz``: two PageRank jobs of three
supersteps (two chunks) on a scale-9 Kronecker graph, Pallas sweep on,
inside a ``window`` span with a ``job`` span around each;
``fixtures/spans_stream.xplane.pb.gz``: two ``stream`` batches on a live
SSSP, an ``ingest`` span around each; ``fixtures/spans.json``: the jobs
engine's ``op_scopes()`` and the jobs' and batches' ``host_syncs``.
``record_spans.py`` records them.
"""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spans, tracereduce

FIXTURES = Path(__file__).parent / "fixtures"
RECORD = json.loads((FIXTURES / "spans.json").read_text())


def reduce_fixture(name, tmp_path_factory):
    path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress(
        (FIXTURES / f"{name}.xplane.pb.gz").read_bytes()))
    return path, spans.reduce_program(path)


@pytest.fixture(scope="module")
def jobs_file(tmp_path_factory):
    return reduce_fixture("spans_jobs", tmp_path_factory)


@pytest.fixture(scope="module")
def jobs(jobs_file):
    return jobs_file[1]


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    return reduce_fixture("spans_stream", tmp_path_factory)[1]


def test_program_spans_in_the_trace(jobs, stream):
    n = jobs.span_count
    assert n["engine.run"] == 2
    assert n["engine.chunk"] == n["engine.sync"] == n["engine.boundary"]
    assert n["engine.repartition"] >= 2
    s = stream.span_count
    assert s["stream.ingest"] == s["stream.apply"] == 2
    assert s["stream.commit"] == s["stream.reconverge"] == 2
    for summary in (jobs, stream):
        idle = summary.window_s - summary.busy_s
        assert sum(summary.idle_by_program_span.values()) == \
            pytest.approx(idle, rel=1e-6)
        # a span's idle time holds that of the spans nested in it
        inside = summary.idle_in_span
        assert inside["engine.run"] >= inside["engine.chunk"] >= \
            inside["engine.sync"] > 0
    assert stream.idle_in_span["stream.ingest"] >= \
        stream.idle_in_span["stream.apply"] >= \
        stream.idle_in_span["stream.commit"]


def test_window_and_busy_as_the_harness_reads_them(jobs_file):
    """The benchmark's own reduction and this one agree on the window,
    the busy time and every operation's self time."""
    path, prog = jobs_file
    summary = tracereduce.reduce_trace(path, harness.KERNEL, harness.SPANS)
    assert prog.window_s == summary.window_s
    assert prog.busy_s == summary.busy_s
    assert prog.devices == summary.devices
    by_name: dict = {}
    for text, sec in prog.op_text_s.items():
        name = tracereduce.op_name(text)
        by_name[name] = by_name.get(name, 0.0) + sec
    assert by_name == pytest.approx(summary.op_s)


def test_scopes_name_the_busy_time(jobs):
    by_scope = spans.scope_seconds(jobs, RECORD["scopes"])
    assert sum(by_scope.values()) == pytest.approx(
        sum(jobs.op_text_s.values()))
    named = sum(v for k, v in by_scope.items() if k != "unscoped")
    assert named >= 0.9 * jobs.busy_s
    assert {"sweep_gather_values", "sweep_fold", "post", "select",
            "converge"} <= set(by_scope)


def test_job_numbers(jobs):
    gather = spans.gather_share(jobs, RECORD["scopes"])
    post = spans.post_share(jobs, RECORD["scopes"])
    assert 0 < gather < 100 and 0 < post < 100 and gather + post < 100
    idle = spans.boundary_idle_ms(jobs)
    assert idle == pytest.approx(
        1e3 * (jobs.idle_in_span["engine.sync"]
               + jobs.idle_in_span["engine.boundary"])
        / jobs.span_count["engine.boundary"])
    assert idle > 0
    # a fused run reads once at its start and end, and seven times a chunk
    chunks = jobs.span_count["engine.chunk"] / len(RECORD["jobs"])
    assert np.mean([j["host_syncs"] for j in RECORD["jobs"]]) == \
        2 + 7 * chunks


def test_stream_numbers(stream):
    share = spans.apply_idle(stream)
    assert share == pytest.approx(
        100 * stream.idle_in_span["stream.apply"] / stream.window_s)
    assert 0 < share < 100
    assert spans.boundary_idle_ms(stream) > 0
    # the apply reads nothing: a batch's reads are its warm run's
    chunks = stream.span_count["engine.chunk"] / len(RECORD["batches"])
    assert np.mean([b["host_syncs"] for b in RECORD["batches"]]) == \
        2 + 7 * chunks


def test_numbers_without_the_programs_spans():
    """A program with no spans or scopes (the trace of ``small.xplane.pb``
    predates them) reads as nothing, not as zero."""
    old = spans.reduce_program(FIXTURES / "small.xplane.pb")
    assert old.span_count == {} and old.busy_s > 0
    assert old.idle_by_program_span["none"] == pytest.approx(
        old.window_s - old.busy_s, rel=1e-6)
    assert spans.gather_share(old, None) is None
    assert spans.post_share(old, {}) is None
    assert spans.boundary_idle_ms(old) is None
    assert spans.apply_idle(old) is None


def test_overlap_and_innermost():
    s, e = np.array([0.0, 5.0]), np.array([4.0, 9.0])
    bs, be = np.array([1.0, 3.0, 8.0]), np.array([2.0, 6.0, 20.0])
    # [0,4) meets [1,2) and [3,4); [5,9) meets [5,6) and [8,9)
    assert spans._overlap(s, e, bs, be) == 4.0
    inner = spans._innermost([("run", 0, 10), ("chunk", 0, 6),
                              ("sync", 2, 6), ("boundary", 6, 9)])
    assert {k: (v[0].tolist(), v[1].tolist()) for k, v in inner.items()} \
        == {"chunk": ([0.0], [2.0]), "sync": ([2.0], [6.0]),
            "boundary": ([6.0], [9.0]), "run": ([9.0], [10.0])}


def test_cli_prints_the_numbers(capsys):
    assert spans.main([str(FIXTURES / "spans_jobs.xplane.pb.gz"),
                       "--scopes", str(FIXTURES / "spans.json")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["span_count"]["engine.run"] == 2
    assert 0 < out["gather_share"] < 100 and out["boundary_idle_ms"] > 0
    assert out["apply_idle"] is None
