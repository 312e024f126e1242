"""Out-of-core block tier + epoch persistence.

Acceptance properties:

  (1) residency must not change the computation — a budget-constrained
      run (resident_blocks < P) is BITWISE-identical in values and in
      every algorithmic counter to the fully resident run, for PR/SSSP/CC
      on both the fused and host paths, and across warm streaming batches
      including deletes (only the spill-traffic counters may differ);
  (2) the budget is real — the resident set never exceeds it after the
      first admission and evictions actually happen;
  (3) save -> restore round-trips the fixpoint exactly and the warm
      verification pass reconverges to live-fixpoint parity in far fewer
      supersteps than a cold run;
  (4) pinned query epochs survive eviction.
"""
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
                "wall_time_s", "host_syncs")


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
       budget=st.integers(min_value=6, max_value=10),
       fused=st.booleans())
def test_budget_run_bitwise_identical(prog, budget, fused):
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full = StructureAwareEngine(g, PROGS[prog](), CFG)
    assert full.plan.num_blocks > budget  # the budget must actually bind
    eng = StructureAwareEngine(
        g, PROGS[prog](),
        EngineConfig(**{**CFG.__dict__, "resident_blocks": budget}))
    _assert_same_trajectory(full.run(fused=fused), eng.run(fused=fused))
    assert eng.spill.spilled_blocks.size > 0  # it really ran out of core


def test_budget_warm_stream_bitwise_identical():
    """Warm streaming reconvergence (inserts + deletes, non-monotone
    re-heats included) under a budget matches the fully resident stream
    batch for batch — values bitwise, reports field for field."""
    g = G.powerlaw_graph(1200, avg_deg=5, seed=11, weighted=True)
    batches = synthetic_stream(g, 4, 60, seed=5, weighted=True,
                               delete_frac=0.3)
    cfg_b = EngineConfig(**{**CFG.__dict__, "resident_blocks": 7})
    se_full = StreamingEngine(g, A.sssp(0), CFG)
    se_budget = StreamingEngine(g, A.sssp(0), cfg_b)
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
    eng = StructureAwareEngine(
        g, A.pagerank(),
        EngineConfig(**{**CFG.__dict__, "resident_blocks": 7}))
    res = eng.run()
    assert res.metrics.converged
    spill = eng.spill
    assert int(spill.resident.sum()) <= 7
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
    with pytest.raises(ValueError, match="resident_blocks"):
        StructureAwareEngine(
            g, A.pagerank(),
            EngineConfig(**{**CFG.__dict__, "resident_blocks":
                            CFG.width + 1}))


def test_disk_tier_roundtrip(tmp_path):
    """spill_dir + keep_host=False: payloads must survive a device-evict
    -> npz segment -> demand-fetch round trip with no host cache — the
    graphs-bigger-than-RAM configuration — and still land bitwise."""
    g = G.powerlaw_graph(1500, avg_deg=6, seed=3, weighted=True)
    full = StructureAwareEngine(g, A.pagerank(), CFG).run()
    eng = StructureAwareEngine(
        g, A.pagerank(),
        EngineConfig(**{**CFG.__dict__, "resident_blocks": 7,
                        "spill_dir": str(tmp_path)}))
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
    cfg_b = EngineConfig(**{**CFG.__dict__, "resident_blocks": 7})
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
    cfg_b = EngineConfig(**{**CFG.__dict__, "resident_blocks": 7})
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
