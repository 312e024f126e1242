"""The out-of-core cell ``g500-s16-ooc.pagerank_paged`` at a CPU size.

At scale 10 the plan has too few blocks for two thirds of its rows to
hold one superstep's demand at the configuration's block size and width,
so both cells compared here run with 32-vertex blocks and a width of 2;
the pool is two thirds of that plan's tile rows, so the budget binds.
"""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

ENGINE = {"block_size": 32, "width": 2}
CELL = "g500-s16-ooc.pagerank_paged"


def tiny_rows():
    """Tile rows of the tiny plan at ``ENGINE``'s block size."""
    from repro.core.engine import StructureAwareEngine
    from repro.core.graph import from_edges
    c = tiny.cell("g500-s16.pagerank")
    n, src, dst, w = harness.build_graph(c)
    prog = harness.load_module("programs", "pagerank").make(
        c.config["programs"]["pagerank"], None)
    eng = StructureAwareEngine(from_edges(n, src, dst, w), prog,
                               harness.engine_config(
                                   {"engine": {**c.config["engine"],
                                               **ENGINE}}))
    return int(eng.plan.unified.src.shape[0])


def cells():
    paged = tiny.cell(CELL)
    resident = tiny.cell("g500-s16.pagerank")
    rows = tiny_rows()
    paged.config["engine"] = {**paged.config["engine"], **ENGINE,
                              "resident_rows": 2 * rows // 3}
    resident.config["engine"] = {**resident.config["engine"], **ENGINE}
    return paged, resident


def test_paged_cell_matches_the_resident_one(monkeypatch):
    paged, resident = cells()
    line = tiny.execute(paged, traced=True, monkeypatch=monkeypatch)
    ref = tiny.execute(resident, traced=True, monkeypatch=monkeypatch)
    assert line["correct"] and line["attempted"] > 0, line["checks"]
    got, want = line["metrics"], ref["metrics"]
    for m in ("supersteps.job", "edge_visits.job"):
        assert got[m]["value"] == want[m]["value"], m
    assert got["paged_mb.job"]["value"] > 0
    assert 0 < got["paging_share.job"]["value"] < 100


def test_misplaced_row_is_not_correct(monkeypatch):
    """Pool writes one row past their place: the table still names each
    run's start, so the sweeps read shifted rows and the answers drift
    from the reference."""
    from repro.core.engine import StructureAwareEngine
    orig = StructureAwareEngine.write_pool_rows

    def shifted(self, rows, payload):
        n = int(self.edge_state.src.shape[0])
        return orig(self, (np.asarray(rows) + 1) % n, payload)

    monkeypatch.setattr(StructureAwareEngine, "write_pool_rows", shifted)
    paged, _ = cells()
    # a job on shifted rows may not converge: stop it at four times the
    # 473 supersteps of the sound job (a job stopped unconverged fails)
    paged.config["engine"]["max_iterations"] = 2000
    line = tiny.execute(paged, monkeypatch=monkeypatch)
    assert line["attempted"] > 0
    assert line["correct"] is False, line["checks"]


def test_paging_sums_per_window_job():
    """``paged_jobs.paging``: the window's jobs are the last engine runs;
    a job's bytes are its admissions' and prefetches', its tier seconds
    the union of the tier's spans inside it."""
    kind = harness.load_module("kinds", "paged_jobs")

    def span(name, cat, ts, dur, **args):
        return {"type": "span", "name": name, "cat": cat, "ts": ts,
                "dur": dur, "depth": 0, "args": args}

    events = [
        span("run", "engine", 0.0, 1.0),  # the warm-up job
        span("admit", "ooc", 0.1, 0.1, bytes=5),
        span("spill_evict", "ooc", 2.1, 0.05, bytes=99),
        span("admit", "ooc", 2.0, 0.2, bytes=10),
        span("prefetch", "ooc", 2.5, 0.1, bytes=7),
        span("run", "engine", 2.0, 2.0),
        span("compact", "ooc", 5.0, 0.5, rows=3),
        span("run", "engine", 4.5, 1.0),
    ]
    got = kind.paging(events, 2)
    assert [j["bytes"] for j in got] == [17, 0]
    assert got[0]["tier_s"] == pytest.approx(0.3)
    assert got[1]["tier_s"] == pytest.approx(0.5)
    assert [j["wall_s"] for j in got] == [2.0, 1.0]
    assert kind.paging(events, 0) == []
