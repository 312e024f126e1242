"""Observability layer: tracing must be a pure observer.

The load-bearing property: ``run(trace=True)`` (history buffer in the
fused carry, spans recording on the host) is BITWISE identical to the
untraced run — values and every algorithmic counter — on the fused and
host paths and across warm streaming batches. Plus the timeline-sum
property (per-superstep deltas sum exactly to the aggregate ``Metrics``
counters), the ``as_dict``/@property parity contract, the Chrome-trace
exporter schema, the ring-buffer bound, and the CLI renderer.
"""
import json

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import algorithms as A
from repro.core import graph as G
from repro.core.engine import (TIMELINE_FLOAT_COLS, TIMELINE_INT_COLS,
                               EngineConfig, StructureAwareEngine,
                               _hist_cap)
from repro.core.metrics import (COUNTER_FIELDS, Metrics, ServeMetrics,
                                StreamMetrics)
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.__main__ import main as obs_cli
from repro.stream import StreamingEngine, synthetic_stream

CFG = EngineConfig(t2=1e-9, width=4, block_size=128)
PROGS = {"pagerank": A.pagerank, "sssp": lambda: A.sssp(0), "cc": A.cc}


def _counters(m: Metrics) -> dict:
    return {k: getattr(m, k) for k in COUNTER_FIELDS}


# -- bitwise parity: tracing is a pure observer ------------------------------
@given(seed=st.integers(0, 20), n=st.integers(200, 600),
       algo=st.sampled_from(["pagerank", "sssp", "cc"]),
       fused=st.booleans())
@settings(max_examples=6, deadline=None)
def test_traced_run_bitwise_identical_property(seed, n, algo, fused):
    g = G.powerlaw_graph(n, avg_deg=4, seed=seed, weighted=True)
    eng = StructureAwareEngine(g, PROGS[algo](), CFG)
    plain = eng.run(fused=fused)
    traced = eng.run(fused=fused, trace=True)
    assert np.array_equal(plain.values, traced.values), \
        f"{algo} values diverged under tracing (fused={fused})"
    assert plain.metrics.iterations == traced.metrics.iterations
    assert _counters(plain.metrics) == _counters(traced.metrics)
    assert plain.metrics.converged == traced.metrics.converged
    assert plain.timeline is None
    assert traced.timeline is not None
    assert len(traced.timeline) == traced.metrics.iterations


# -- the timeline-sum property -----------------------------------------------
@given(seed=st.integers(0, 20), algo=st.sampled_from(["pagerank", "sssp"]),
       fused=st.booleans(), adaptive=st.booleans())
@settings(max_examples=6, deadline=None)
def test_timeline_sums_to_aggregate_counters_property(seed, algo, fused,
                                                      adaptive):
    """Every ``block_io_bytes``-derived counter reconstructed by summing
    the per-superstep timeline equals the aggregate ``Metrics`` total —
    the rows go through the same per-block accounting table."""
    g = G.powerlaw_graph(400, avg_deg=5, seed=seed, weighted=True)
    cfg = EngineConfig(t2=1e-9, width=4, block_size=128,
                       adaptive=adaptive)
    res = StructureAwareEngine(g, PROGS[algo](), cfg).run(
        fused=fused, trace=True)
    tl = res.timeline
    assert len(tl) == res.metrics.iterations
    for field in COUNTER_FIELDS:
        assert sum(r[field] for r in tl) == getattr(res.metrics, field), \
            f"timeline {field} sum != aggregate (fused={fused})"
    cols = set(TIMELINE_INT_COLS) | set(TIMELINE_FLOAT_COLS) \
        | {"superstep", "width"}
    for r in tl:
        assert cols <= set(r)
    assert [r["superstep"] for r in tl] == list(range(len(tl)))


def test_streaming_warm_batches_identical_under_recording():
    """Two identical streaming engines, one ingesting with a recorder
    installed: per-batch reports and final values are bitwise equal, and
    the recorder holds the ingest/reconverge/run span hierarchy."""
    g = G.powerlaw_graph(300, avg_deg=4, seed=3, weighted=True)
    batches = synthetic_stream(g, 3, 30, seed=4, delete_frac=0.25,
                               weighted=True)
    plain = StreamingEngine(g, A.pagerank(), CFG)
    traced = StreamingEngine(g, A.pagerank(), CFG)
    with obs_trace.recording() as rec:
        reps_t = [traced.ingest(b) for b in batches]
    reps_p = [plain.ingest(b) for b in batches]
    for rp, rt in zip(reps_p, reps_t):
        assert rp.iterations == rt.iterations
        assert rp.edges_processed == rt.edges_processed
        assert rp.dirty_blocks == rt.dirty_blocks
        assert rp.bytes_uploaded == rt.bytes_uploaded
    assert np.array_equal(plain.values, traced.values)
    names = {e["name"] for e in rec.events if e["type"] == "span"}
    assert {"ingest", "reconverge", "run", "chunk"} <= names
    ing = [e for e in rec.events
           if e["type"] == "span" and e["name"] == "ingest"]
    assert len(ing) == len(batches)
    assert all(e["args"]["iterations"] == rp.iterations
               for e, rp in zip(ing, reps_p))


def test_run_trace_autodetects_installed_recorder():
    g = G.uniform_graph(200, deg=4, seed=0, weighted=True)
    eng = StructureAwareEngine(g, A.pagerank(), CFG)
    assert eng.run().timeline is None
    with obs_trace.recording() as rec:
        res = eng.run()  # trace=None + installed recorder -> traced
    assert res.timeline is not None
    assert any(e["type"] == "counter" for e in rec.events)
    assert eng.run().timeline is None  # uninstalled again


def test_spans_only_recorder_keeps_the_untraced_chunk():
    """A recorder installed with ``timeline=False`` records the engine's
    spans, but the run stays on the untraced chunk: no timeline, no
    counter rows, values and counters bitwise the untraced run's."""
    g = G.uniform_graph(200, deg=4, seed=0, weighted=True)
    eng = StructureAwareEngine(g, A.pagerank(), CFG)
    plain = eng.run()
    with obs_trace.recording(timeline=False) as rec:
        res = eng.run()
    assert res.timeline is None
    assert not any(e["type"] == "counter" for e in rec.events)
    assert {("engine", "run"), ("engine", "chunk")} <= {
        (e["cat"], e["name"]) for e in rec.events}
    assert not any(k[1] == 16 for k in eng._fns if k[0] == "chunk")
    assert np.array_equal(res.values, plain.values)
    assert res.metrics.iterations == plain.metrics.iterations


def test_op_scopes_name_the_pool_writes():
    """Under an out-of-core budget ``op_scopes`` also names the tier's
    placement and compaction writes, by ``ooc_place``."""
    from repro.obs import scopes
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    cfg = EngineConfig(t2=1e-9, width=4, block_size=128)
    cnt = StructureAwareEngine(g, A.pagerank(), cfg).plan.unified.tile_cnt
    eng = StructureAwareEngine(g, A.pagerank(), EngineConfig(
        **{**cfg.__dict__,
           "resident_rows": int(np.sort(cnt)[::-1][:6].sum()) + 1}))
    got = eng.op_scopes()
    assert "ooc_place" in got.values()
    assert set(got.values()) <= set(scopes.SCOPES) | set(
        scopes.TIER_SCOPES) | {scopes.UNSCOPED}


# -- as_dict / @property parity ----------------------------------------------
@pytest.mark.parametrize("cls", [Metrics, StreamMetrics, ServeMetrics])
def test_every_property_lands_in_as_dict(cls):
    m = cls()
    d = m.as_dict()
    props = [name for klass in type(m).__mro__
             for name, attr in vars(klass).items()
             if isinstance(attr, property)]
    assert props, f"{cls.__name__} grew property-less — update the test"
    for name in props:
        assert name in d, f"{cls.__name__}.{name} missing from as_dict()"
        assert d[name] == getattr(m, name)
    # and the dataclass fields are all still there too
    import dataclasses
    for f in dataclasses.fields(cls):
        assert f.name in d


# -- recorder / exporter ------------------------------------------------------
def test_ring_buffer_bounds_memory_and_counts_drops():
    rec = obs_trace.TraceRecorder(capacity=8)
    for i in range(20):
        with rec.span("s", cat="t", i=i):
            pass
    assert len(rec.events) == 8
    assert rec.dropped == 12
    # oldest dropped, newest kept
    assert [e["args"]["i"] for e in rec.events] == list(range(12, 20))


def test_span_without_recorder_is_noop():
    assert obs_trace.current() is None
    with obs_trace.span("x", cat="y", a=1) as h:
        h.set(b=2)  # must not raise
    assert (h.t0, h.t1) == (0.0, 0.0)  # the shared no-op handle
    assert obs_trace.current() is None


def test_nested_spans_depth_and_args():
    with obs_trace.recording() as rec:
        with obs_trace.span("outer", cat="t") as o:
            with obs_trace.span("inner", cat="t"):
                pass
            o.set(k=3)
    spans = {e["name"]: e for e in rec.events}
    assert spans["inner"]["depth"] == 1
    assert spans["outer"]["depth"] == 0
    assert spans["outer"]["args"] == {"k": 3}
    assert spans["outer"]["dur"] >= spans["inner"]["dur"]


def test_chrome_export_schema_valid(tmp_path):
    with obs_trace.recording() as rec:
        with obs_trace.span("a", cat="x", n=1):
            rec.counter_rows("c", [{"v": 1, "skip": "str"},
                                   {"v": 2}], 0.0, 1.0)
        with obs_trace.span("mark", cat="x", note="hi"):
            pass
    payload = obs_export.to_chrome(rec, meta={"suite": "unit"})
    assert obs_export.validate(payload) == []
    phs = [e["ph"] for e in payload["traceEvents"]]
    assert phs.count("C") == 2 and phs.count("X") == 2 and "i" not in phs
    cs = [e for e in payload["traceEvents"] if e["ph"] == "C"]
    assert all("skip" not in e["args"] for e in cs)  # non-numeric filtered
    assert cs[0]["ts"] < cs[1]["ts"]  # interpolated placement
    assert payload["otherData"]["suite"] == "unit"
    p = obs_export.write(rec, str(tmp_path / "t.json"))
    assert obs_export.validate(json.load(open(p))) == []


def test_validate_rejects_malformed_payloads():
    assert obs_export.validate([]) != []
    assert obs_export.validate({}) != []
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": -1},
        {"ph": "C", "name": "c", "pid": 1, "tid": 1, "ts": 0,
         "args": {"v": "nan"}},
    ]}
    errs = obs_export.validate(bad)
    assert len(errs) >= 3


def test_cli_render_and_validate(tmp_path, capsys):
    g = G.uniform_graph(200, deg=4, seed=1, weighted=True)
    with obs_trace.recording() as rec:
        StructureAwareEngine(g, A.pagerank(), CFG).run()
    path = obs_export.write(rec, str(tmp_path / "trace_run.json"))
    assert obs_cli(["validate", path]) == 0
    assert obs_cli(["render", path, "--limit", "10"]) == 0
    out = capsys.readouterr().out
    assert "valid chrome-trace JSON" in out
    assert "phase breakdown" in out and "engine/run" in out
    assert "superstep counters" in out


# -- history-capacity buckets -------------------------------------------------
def test_hist_cap_pow2_buckets():
    assert _hist_cap(1) == 16 and _hist_cap(16) == 16
    assert _hist_cap(17) == 32 and _hist_cap(32) == 32
    assert _hist_cap(33) == 64
    assert _hist_cap(1000) == 1024  # no upper clamp
    for s in range(1, 200):
        assert _hist_cap(s) >= s  # a chunk always fits its buffer


# -- spans on the profiler's clock -------------------------------------------
def _host_spans(trace_dir) -> list:
    """(name, start, end) of every ``<cat>.<name>`` program span on the
    host plane of the one trace under ``trace_dir``."""
    from pathlib import Path

    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.split(".")[0] in ("engine", "stream")]


def _inside(spans, inner: str, outer: str) -> bool:
    """Every ``inner`` span lies inside some ``outer`` span (and there is
    at least one)."""
    outs = [(s, e) for n, s, e in spans if n == outer]
    ins = [(s, e) for n, s, e in spans if n == inner]
    return bool(ins) and all(any(os <= s and e <= oe for os, oe in outs)
                             for s, e in ins)


def test_spans_nest_on_the_profiler_clock(tmp_path):
    """With no recorder installed, a profiler session still sees every
    program span as ``<cat>.<name>``, nested as the code nests them; the
    recorder stays empty and the values are those of an unprofiled run."""
    import jax
    g = G.powerlaw_graph(400, avg_deg=4, seed=2, weighted=True)
    cfg = EngineConfig(t2=1e-9, width=4, block_size=128,
                       repartition_interval=1)
    batch = synthetic_stream(g, 1, 30, seed=5, delete_frac=0.25,
                             weighted=True)[0]
    eng = StructureAwareEngine(g, A.pagerank(), cfg)
    plain = eng.run()
    twin = StreamingEngine(g, A.sssp(0), cfg)
    se = StreamingEngine(g, A.sssp(0), cfg)
    twin.ingest(batch)
    assert obs_trace.current() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.run()
        se.ingest(batch)
    finally:
        jax.profiler.stop_trace()
    assert obs_trace.current() is None
    assert np.array_equal(plain.values, res.values)
    assert _counters(plain.metrics) == _counters(res.metrics)
    assert np.array_equal(twin.values, se.values)
    spans = _host_spans(tmp_path)
    assert _inside(spans, "engine.chunk", "engine.run")
    assert _inside(spans, "engine.sync", "engine.chunk")
    assert _inside(spans, "engine.repartition", "engine.boundary")
    assert _inside(spans, "stream.apply", "stream.ingest")
    assert _inside(spans, "stream.commit", "stream.apply")
    assert _inside(spans, "stream.reconverge", "stream.ingest")
    # one sync and one boundary per chunk
    count = {n: sum(1 for m, _, _ in spans if m == n)
             for n in ("engine.chunk", "engine.sync", "engine.boundary")}
    assert len(set(count.values())) == 1


# -- the host-sync counter ----------------------------------------------------
@pytest.fixture
def fetches(monkeypatch):
    """Count the device reads below ``HostSyncs`` independently."""
    from repro.core import metrics as M
    seen = []

    def fetch(x):
        seen.append(1)
        return np.asarray(x)

    monkeypatch.setattr(M, "_fetch", fetch)
    return seen


@pytest.mark.parametrize("fused,trace", [(True, False), (True, True),
                                         (False, False)])
def test_host_syncs_count_every_read(fetches, fused, trace):
    g = G.powerlaw_graph(400, avg_deg=4, seed=4, weighted=True)
    eng = StructureAwareEngine(g, A.sssp(0), CFG)
    res = eng.run(fused=fused, trace=trace)
    assert res.metrics.host_syncs == len(fetches) > 0
    if fused:
        # a read at the start and one at the end; per chunk the seven
        # chunk results, and the two history buffers when traced
        per_chunk = 9 if trace else 7
        assert res.metrics.host_syncs == 2 + per_chunk * len(res.history)


def test_stream_batch_host_syncs(fetches):
    g = G.powerlaw_graph(300, avg_deg=4, seed=3, weighted=True)
    se = StreamingEngine(g, A.sssp(0), CFG)
    total = 0
    for b in synthetic_stream(g, 2, 30, seed=4, delete_frac=0.25,
                              weighted=True):
        before = len(fetches)
        rep = se.ingest(b)
        assert rep.host_syncs == len(fetches) - before
        total += rep.host_syncs
    assert se.metrics.host_syncs == total > 0


# -- device operations named by scope ----------------------------------------
def test_op_scopes_name_the_superstep():
    from repro.obs import scopes
    g = G.powerlaw_graph(400, avg_deg=4, seed=1, weighted=True)
    eng = StructureAwareEngine(g, A.pagerank(), EngineConfig(
        t2=1e-9, width=4, block_size=128, use_pallas=True))
    got = eng.op_scopes()
    assert got and all(scopes.op_key(k) == k for k in got)
    assert set(got.values()) <= set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert {"select", "post", "converge", "sweep_fold", "sweep_hot",
            "sweep_cold", "account", "chunk_loop"} <= set(got.values())


HLO = """\
HloModule jit_chunk

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %gather.3 = f32[8]{0} gather(f32[8]{0} %param_0), metadata={op_name="jit(chunk)/while/body/vmap(sweep_gather_values)/gather"}
}

%fused_computation.2 (param_0.1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %max.1 = f32[8]{0} maximum(%param_0.1, %param_0.1), metadata={op_name="jit(chunk)/while/body/post/max"}
  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0}) tuple(%max.1, %max.1)
}

%body.5 (p: (f32[8])) -> (f32[8]) {
  %p = (f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%p), index=0
  %fusion.7 = f32[8]{0:T(1024)} fusion(%get-tuple-element.1), kind=kCustom, calls=%fused_computation.1
  %fusion.8 = (f32[8]{0}, f32[8]{0}) fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple.2 = (f32[8]{0}) tuple(%fusion.7)
}

%cond.6 (q: (f32[8])) -> pred[] {
  %q = (f32[8]{0}) parameter(0)
  ROOT %constant.4 = pred[] constant(true)
}

ENTRY %main.9 (values: f32[8]) -> (f32[8]) {
  %values = f32[8]{0} parameter(0), metadata={op_name="values"}
  %tuple.1 = (f32[8]{0}) tuple(%values)
  ROOT %while.3 = (f32[8]{0}) while(%tuple.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(chunk)/chunk_loop/while"}
}
"""


def test_op_scopes_reads_compiled_text():
    """Fusions resolve through their root (a tuple root through its
    operands), loop bodies inherit the loop's scope, fused insides are
    not operations of their own, and a trace event's name gives the same
    key as the compiled line."""
    from repro.obs import scopes
    got = scopes.op_scopes(HLO)
    assert got["%fusion.7 = f32[8]{0:T(1024)}"] == "sweep_gather_values"
    assert got["%fusion.8 = (f32[8]{0}, f32[8]{0})"] == "post"
    assert got["%while.3 = (f32[8]{0})"] == "chunk_loop"
    assert got["%get-tuple-element.1 = f32[8]{0}"] == "chunk_loop"
    assert got["%values = f32[8]{0}"] == scopes.UNSCOPED
    assert not any(k.startswith(("%gather.3", "%max.1")) for k in got)
    event = ("%fusion.7 = f32[8]{0:T(1024)} fusion(f32[8]{0} "
             "%get-tuple-element.1), kind=kCustom, calls=%fused_computation.1")
    assert scopes.op_key(event) in got
    assert scopes.scope_of("a/sweep_fold/b/jit(_where)/select_n") == \
        "sweep_fold"
    assert scopes.merge([{"k": "post", "j": "select"},
                         {"k": "select", "j": "select"}]) == \
        {"k": scopes.UNSCOPED, "j": "select"}
