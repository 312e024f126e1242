"""Out-of-core block tier + epoch persistence.

Acceptance properties:

  (1) residency must not change the computation — a budget-constrained
      run (resident_rows below the plan's rows) is BITWISE-identical in
      values and in every algorithmic counter to the fully resident run,
      for PR/SSSP/CC on both the fused and host paths, and across warm
      streaming batches including deletes (only the spill-traffic
      counters may differ); with no budget the engine is the one it was
      before the tier had a pool;
  (2) the budget is real — the device row arrays hold exactly
      ``resident_rows`` rows, the resident runs fit in them, evictions
      actually happen, a fragmented pool is compacted, a pool below one
      superstep's demand is refused at construction, and a sweep of a
      non-resident block raises;
  (3) save -> restore round-trips the fixpoint exactly and the warm
      verification pass reconverges to live-fixpoint parity in far fewer
      supersteps than a cold run;
  (4) pinned query epochs survive eviction.
"""
import hashlib
import os

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import algorithms as A
from repro.core import graph as G
from repro.core.engine import EngineConfig, StructureAwareEngine
from repro.ooc.store import SpillStore
from repro.stream import DeltaBatch, StreamingEngine, synthetic_stream

CFG = EngineConfig(t2=1e-9, width=4, block_size=128)
PROGS = {"pagerank": A.pagerank, "sssp": lambda: A.sssp(0), "cc": A.cc}

# counters that legitimately differ between budget and resident runs:
# the spill tier's own traffic, the host reads of its one-superstep
# chunks (plus wall time); everything else in Metrics.as_dict is part of
# the algorithmic trajectory and must match
SPILL_FIELDS = ("spill_evictions", "bytes_spilled", "prefetch_hits",
                "prefetch_misses", "bytes_fetched", "prefetch_hit_rate",
                "compactions", "rows_moved", "wall_time_s", "host_syncs")


def _need_rows(engine) -> int:
    """One superstep's demand: the width + 2 largest block runs."""
    cnt = engine.plan.unified.tile_cnt
    return int(np.sort(cnt)[::-1][:engine.config.width + 2].sum())


def _budget(engine, extra: int = 1, **kw) -> EngineConfig:
    """CFG with a pool of ``extra`` rows over ``engine``'s least one;
    the budget must bind (fewer rows than the plan's)."""
    rows = _need_rows(engine) + extra
    assert rows < engine.plan.unified.src.shape[0]
    return EngineConfig(**{**CFG.__dict__, "resident_rows": rows, **kw})


def _assert_same_trajectory(res_full, res_budget):
    assert np.array_equal(res_full.values, res_budget.values)
    a, b = res_full.metrics.as_dict(), res_budget.metrics.as_dict()
    for k in a:
        if k in SPILL_FIELDS:
            continue
        assert a[k] == b[k], f"counter {k}: {a[k]} != {b[k]}"


# -- (1) bitwise parity under a residency budget -----------------------------
@settings(max_examples=6, deadline=None)
@given(prog=st.sampled_from(sorted(PROGS)),
       extra=st.integers(min_value=0, max_value=4),
       fused=st.booleans())
def test_budget_run_bitwise_identical(prog, extra, fused):
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full = StructureAwareEngine(g, PROGS[prog](), CFG)
    eng = StructureAwareEngine(g, PROGS[prog](), _budget(full, extra))
    _assert_same_trajectory(full.run(fused=fused), eng.run(fused=fused))
    assert eng.spill.spilled_blocks.size > 0  # it really ran out of core


def test_budget_warm_stream_bitwise_identical():
    """Warm streaming reconvergence (inserts + deletes, non-monotone
    re-heats included) under a budget matches the fully resident stream
    batch for batch — values bitwise, reports field for field."""
    g = G.powerlaw_graph(1200, avg_deg=5, seed=11, weighted=True)
    batches = synthetic_stream(g, 4, 60, seed=5, weighted=True,
                               delete_frac=0.3)
    se_full = StreamingEngine(g, A.sssp(0), CFG)
    se_budget = StreamingEngine(g, A.sssp(0), _budget(se_full.engine))
    assert np.array_equal(se_full.values, se_budget.values)
    for batch in batches:
        rf = se_full.ingest(batch)
        rb = se_budget.ingest(batch)
        assert np.array_equal(se_full.values, se_budget.values)
        for f in ("iterations", "edges_processed", "dirty_blocks",
                  "vertices_reset", "converged", "blocks_retired",
                  "mean_dispatch_width"):
            assert getattr(rf, f) == getattr(rb, f), f
    assert se_budget.metrics.spill_evictions > 0
    m = se_budget.metrics.as_dict()
    assert 0.0 <= m["prefetch_hit_rate"] <= 1.0


# -- (2) the budget is enforced ----------------------------------------------
def test_residency_budget_enforced():
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3)
    cfg = _budget(StructureAwareEngine(g, A.pagerank(), CFG))
    eng = StructureAwareEngine(g, A.pagerank(), cfg)
    # the device row arrays are the pool: exactly resident_rows rows
    ed = eng.edge_state
    for a in (ed.src, ed.dstl, ed.w, ed.valid, ed.cov):
        assert a.shape[0] == cfg.resident_rows
    res = eng.run()
    assert res.metrics.converged
    spill = eng.spill
    ed = eng.edge_state
    for a in (ed.src, ed.dstl, ed.w, ed.valid, ed.cov):
        assert a.shape[0] == cfg.resident_rows
    # the resident runs fit the pool without overlapping, and the device
    # table is the host allocator's
    live = np.flatnonzero(spill.resident & (spill.cnt > 0))
    runs = sorted((int(spill.start[b]), int(spill.cnt[b])) for b in live)
    assert all(s + n <= t for (s, n), (t, _) in zip(runs, runs[1:]))
    assert runs[-1][0] + runs[-1][1] <= cfg.resident_rows
    assert np.array_equal(np.asarray(ed.resident), spill.resident)
    assert np.array_equal(np.asarray(ed.start)[live], spill.start[live])
    # each resident run holds its block's plan rows
    u = eng.plan.unified
    for b in live:
        s, n, p0 = int(spill.start[b]), int(spill.cnt[b]), \
            int(u.tile_start[b])
        assert np.array_equal(np.asarray(ed.src[s:s + n]), u.src[p0:p0 + n])
        assert np.array_equal(np.asarray(ed.valid[s:s + n]),
                              u.valid[p0:p0 + n])
    assert res.metrics.spill_evictions > 0
    assert res.metrics.bytes_spilled > 0 and res.metrics.bytes_fetched > 0
    # pinned blocks (host-pad block 0 + the fused pad block) never spill
    assert spill.resident[0] and spill.resident[eng.pad_id]
    total = res.metrics.prefetch_hits + res.metrics.prefetch_misses
    assert total > 0
    assert res.metrics.prefetch_hit_rate == \
        res.metrics.prefetch_hits / total


def test_budget_too_small_rejected():
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3)
    need = _need_rows(StructureAwareEngine(g, A.pagerank(), CFG))
    with pytest.raises(ValueError, match="resident_rows"):
        StructureAwareEngine(
            g, A.pagerank(),
            EngineConfig(**{**CFG.__dict__, "resident_rows": need - 1}))


@pytest.mark.parametrize("prog", sorted(PROGS))
def test_pool_below_one_superstep_refused_at_construction(prog):
    """The least pool is one superstep's demand, the width + 2 largest
    runs: one row less is refused when the engine is built, before any
    run; that many rows is accepted and converges."""
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full = StructureAwareEngine(g, PROGS[prog](), CFG)
    need = _need_rows(full)
    with pytest.raises(ValueError, match=f"need >= {need} rows"):
        StructureAwareEngine(
            g, PROGS[prog](),
            EngineConfig(**{**CFG.__dict__, "resident_rows": need - 1}))
    eng = StructureAwareEngine(g, PROGS[prog](), _budget(full, 0))
    assert eng.edge_state.src.shape[0] == need
    assert eng.run().metrics.converged


@pytest.mark.parametrize("fused", [True, False])
def test_budget_forces_compaction(fused):
    """A pool two rows over one superstep's demand, over a hub run of 112
    rows and a tail of 1-4 row runs: evictions leave free rows in holes
    too small for the next admission, which compacts the resident runs
    in place, and the run stays bitwise the resident one."""
    g = G.powerlaw_graph(3000, avg_deg=24, seed=5, weighted=True)
    full = StructureAwareEngine(g, A.pagerank(), CFG)
    eng = StructureAwareEngine(g, A.pagerank(), _budget(full, 2))
    compact = eng.spill.compact
    holes = []

    def spy():
        # a compaction comes only when no free range fits: the free rows
        # lie in more than one range
        holes.append(len(eng.spill._gaps()))
        compact()

    eng.spill.compact = spy
    res = eng.run(fused=fused)
    _assert_same_trajectory(full.run(fused=fused), res)
    assert res.metrics.compactions > 0 and res.metrics.rows_moved > 0
    assert len(holes) == res.metrics.compactions and min(holes) > 1
    assert eng.edge_state.src.shape[0] == _need_rows(full) + 2


@pytest.mark.parametrize("fused", [True, False])
def test_missed_prediction_raises(fused, monkeypatch):
    """A demand set that leaves out the scheduled blocks: the sweep would
    read another block's rows, so the run raises through the
    non-resident sweep count instead of returning values."""
    from repro.ooc import prefetch
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full = StructureAwareEngine(g, A.pagerank(), CFG)
    eng = StructureAwareEngine(g, A.pagerank(), _budget(full))
    monkeypatch.setattr(prefetch, "demand_blocks",
                        lambda sel, pad_id: np.array([pad_id]))
    with pytest.raises(RuntimeError, match="not resident"):
        eng.run(fused=fused)


# values (sha256 of the float32 bytes, first 16 hex digits) and counters
# of fully resident runs of the engine before the tier had a pool, on
# powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True) with CFG
UNBUDGETED = {
    "pagerank": ("34a0e4a7fe1f5b31", 88, 44797, 649438, 352, 7973480),
    "sssp": ("fa18efa44dbc6e8c", 50, 25011, 198858, 198, 2487672),
}


@pytest.mark.parametrize("mode", ["fused", "host", "pallas"])
@pytest.mark.parametrize("prog", ["pagerank", "sssp"])
def test_no_budget_is_the_resident_engine(prog, mode):
    """No budget (or one of at least the plan's rows): no spill tier, the
    row arrays are the plan's with its own starts, and values and
    counters are bitwise those of the engine before the pool existed."""
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    cfg = EngineConfig(**{**CFG.__dict__, "use_pallas": mode == "pallas"})
    eng = StructureAwareEngine(g, PROGS[prog](), cfg)
    u = eng.plan.unified
    same = StructureAwareEngine(g, PROGS[prog](), EngineConfig(
        **{**cfg.__dict__, "resident_rows": int(u.src.shape[0])}))
    for e in (eng, same):
        assert e.spill is None
        ed = e.edge_state
        assert np.array_equal(np.asarray(ed.src), u.src)
        assert np.array_equal(np.asarray(ed.dstl), u.dst_local)
        assert np.array_equal(np.asarray(ed.w), u.w)
        assert np.array_equal(np.asarray(ed.valid), u.valid)
        assert np.array_equal(np.asarray(ed.start), u.tile_start)
        assert bool(np.asarray(ed.resident).all())
    res = eng.run(fused=mode != "host")
    m = res.metrics
    digest = hashlib.sha256(
        np.asarray(res.values, np.float32).tobytes()).hexdigest()[:16]
    assert (digest, m.iterations, m.updates, m.edges_processed,
            m.block_loads, m.bytes_loaded) == UNBUDGETED[prog]
    assert m.compactions == m.rows_moved == m.spill_evictions == 0


def test_disk_tier_roundtrip(tmp_path):
    """spill_dir + keep_host=False: payloads must survive a device-evict
    -> npz segment -> demand-fetch round trip with no host cache — the
    graphs-bigger-than-RAM configuration — and still land bitwise."""
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full_eng = StructureAwareEngine(g, A.pagerank(), CFG)
    full = full_eng.run()
    eng = StructureAwareEngine(
        g, A.pagerank(), _budget(full_eng, spill_dir=str(tmp_path)))
    assert isinstance(eng.spill, SpillStore)
    assert not eng.spill.keep_host  # a directory means disk is the tier
    res = eng.run()
    _assert_same_trajectory(full, res)
    eng.spill.wait()
    segs = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert segs, "evictions must have produced npz segments"


# -- (3) epoch persistence ---------------------------------------------------
def test_save_restore_fixpoint_roundtrip(tmp_path):
    g = G.powerlaw_graph(1200, avg_deg=5, seed=11, weighted=True)
    se = StreamingEngine(g, A.pagerank(), CFG)
    for batch in synthetic_stream(g, 2, 50, seed=5, weighted=True):
        se.ingest(batch)
    ck = se.save_epoch(str(tmp_path / "ck"))
    ck.wait()
    # verify=False: the checkpointed values come back BITWISE
    se_raw = StreamingEngine.restore(str(tmp_path / "ck"), A.pagerank(),
                                     CFG, verify=False)
    assert np.array_equal(se_raw.values, se.values)
    assert se_raw.epoch == se.epoch and se_raw.n == se.n
    # verify=True: the warm verification pass re-heats every block once
    # and must reconverge to live-fixpoint parity...
    se_warm = StreamingEngine.restore(str(tmp_path / "ck"), A.pagerank(),
                                      CFG)
    assert se_warm.initial_result.metrics.converged
    assert np.allclose(se_warm.values, se.values, atol=1e-6)
    # ...in far fewer supersteps than a cold start of the same graph
    cold = StructureAwareEngine(se.current_graph(), A.pagerank(),
                                CFG).run()
    warm_it = se_warm.initial_result.metrics.iterations
    assert warm_it < cold.metrics.iterations / 2, \
        f"warm restart took {warm_it} vs cold {cold.metrics.iterations}"
    # the restored engine is a full StreamingEngine: it can keep ingesting
    rep = se_warm.ingest(DeltaBatch.of(ins=[(1, 2), (3, 4)], dels=[]))
    assert rep.converged


def test_restore_under_budget_and_crossover(tmp_path):
    """A checkpoint written fully resident restores under an OOC budget
    (and vice versa) — persistence is independent of residency."""
    g = G.powerlaw_graph(1200, avg_deg=5, seed=11, weighted=True)
    cfg_b = _budget(StreamingEngine(g, A.sssp(0), CFG).engine, 2)
    se = StreamingEngine(g, A.sssp(0), cfg_b)  # written under a budget
    se.ingest(synthetic_stream(g, 1, 40, seed=6, weighted=True)[0])
    se.save_epoch(str(tmp_path / "ck")).wait()
    back_full = StreamingEngine.restore(str(tmp_path / "ck"), A.sssp(0),
                                        CFG, verify=False)
    back_ooc = StreamingEngine.restore(str(tmp_path / "ck"), A.sssp(0),
                                       cfg_b, verify=True)
    assert np.array_equal(back_full.values, se.values)
    assert back_ooc.engine.spill is not None
    assert np.allclose(back_ooc.values, se.values, atol=1e-6)


def test_checkpoint_edges_tuple_roundtrip(tmp_path):
    """The epoch checkpoint stores the COO truth as a TUPLE — the treedef
    round-trip (ckpt/manager) must bring it back as one, with dtypes."""
    from repro.ooc.snapshot import GraphCheckpoint
    g = G.powerlaw_graph(800, avg_deg=4, seed=2, weighted=True)
    se = StreamingEngine(g, A.pagerank(), CFG)
    se.save_epoch(str(tmp_path / "ck")).wait()
    tree, meta = GraphCheckpoint(str(tmp_path / "ck")).load()
    assert isinstance(tree["edges"], tuple) and len(tree["edges"]) == 3
    src, dst, w = tree["edges"]
    assert src.dtype == np.int64 and w.dtype == np.float32
    assert meta["n"] == g.n and meta["format"] == "graph-epoch-v1"
    gs, gd, gw = G.edges_of(se.current_graph())
    order = np.lexsort((dst, src))
    gorder = np.lexsort((gd, gs))
    assert np.array_equal(src[order], gs[gorder])
    assert np.array_equal(dst[order], gd[gorder])


# -- (4) pinned epochs survive eviction --------------------------------------
def test_pinned_epoch_survives_eviction():
    from repro.serve import Query, QueryService
    g = G.powerlaw_graph(900, avg_deg=5, seed=7, weighted=True)
    cfg_b = _budget(StreamingEngine(g, A.sssp(0), CFG).engine)
    se = StreamingEngine(g, A.sssp(0), cfg_b)
    assert se.metrics.spill_evictions > 0 or \
        se.initial_result.metrics.spill_evictions > 0
    svc = QueryService(se, max_lanes=1)
    qid = svc.submit(Query(kind="sssp", source=3))
    # the pin is taken while blocks are spilled: it must already be a
    # materialized self-contained copy (no spilled holes)
    es = svc._pending[0].epoch_state
    assert es.preserved
    assert bool(np.asarray(es.ed.valid).sum()) and \
        int(np.asarray(es.ed.valid).sum()) == int(se.engine.edge_counts.sum())
    # ingest mutates + evicts underneath the pin; the answer must equal a
    # cold run on the PINNED (pre-ingest) graph
    frozen = se.current_graph()
    se.ingest(synthetic_stream(g, 1, 80, seed=9, weighted=True,
                               delete_frac=0.3)[0])
    r = [x for x in svc.run_pending() if x.query_id == qid][0]
    ref = StructureAwareEngine(frozen, A.sssp(3), CFG).run()
    assert np.array_equal(r.values, ref.values)
