"""Out-of-core table: residency-budget sweep + warm-restart TTC.

Two claims, both measured against the fully resident engine on the SAME
graph and config so the rows isolate the spill tier's contribution:

  * ``ooc_budget`` — the engine converges BITWISE-identically (values and
    algorithmic counters) under shrinking device budgets; the rows track
    the paging overhead (spill traffic, prefetch hit rate, slowdown vs
    fully resident) as the device pool of tile rows shrinks. The floor
    row runs at the ``width + 2`` largest block runs — the minimum the
    admission guarantee allows.
  * ``ooc_restart`` — save_epoch -> restore(verify=True) reconverges from
    the checkpointed fixpoint in a fraction of the cold-start supersteps;
    the derived field carries the warm/cold TTC and iteration ratios that
    README/ROADMAP quote.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import numpy as np

from repro.core import algorithms as A
from repro.core import graph as G
from repro.core.engine import EngineConfig, StructureAwareEngine
from repro.stream import StreamingEngine, synthetic_stream


def run(n: int = 20000):
    cfg = EngineConfig(t2=1e-8, width=16, block_size=512)
    g = G.powerlaw_graph(n, avg_deg=8, seed=1, weighted=True)
    rows = []

    # -- budget sweep: fully resident baseline, then tightening budgets ----
    full_eng = StructureAwareEngine(g, A.pagerank(), cfg)
    P = full_eng.plan.num_blocks
    cnt = full_eng.plan.unified.tile_cnt
    total = int(cnt.sum())
    t0 = time.perf_counter()
    full = full_eng.run()
    us_full = (time.perf_counter() - t0) * 1e6
    rows.append((
        "ooc/powerlaw/pagerank/resident_all", us_full,
        f"P={P};iters={full.metrics.iterations};"
        f"bytes_loaded={full.metrics.bytes_loaded}"))
    floor = int(np.sort(cnt)[::-1][:cfg.width + 2].sum())
    budgets = sorted({max(3 * total // 4, floor), max(total // 2, floor),
                      floor}, reverse=True)
    for budget in budgets:
        if budget >= total:
            continue
        eng = StructureAwareEngine(
            g, A.pagerank(),
            dataclasses.replace(cfg, resident_rows=budget))
        t0 = time.perf_counter()
        res = eng.run()
        us = (time.perf_counter() - t0) * 1e6
        m = res.metrics
        bitwise = np.array_equal(full.values, res.values)
        rows.append((
            f"ooc/powerlaw/pagerank/resident{budget}", us,
            f"P={P};rows={total};budget={budget};iters={m.iterations};"
            f"bitwise={bitwise};evictions={m.spill_evictions};"
            f"spilled_mb={m.bytes_spilled / 1e6:.1f};"
            f"fetched_mb={m.bytes_fetched / 1e6:.1f};"
            f"hit_rate={m.prefetch_hit_rate:.2f};"
            f"slowdown_vs_resident={us / max(us_full, 1e-9):.2f}x"))

    # -- warm restart: checkpointed fixpoint vs cold start -----------------
    se = StreamingEngine(g, A.pagerank(), cfg)
    for b in synthetic_stream(g, 2, 200, seed=3, delete_frac=0.2,
                              weighted=True):
        se.ingest(b)
    tmp = tempfile.mkdtemp(prefix="bench_ooc_ck_")
    try:
        t0 = time.perf_counter()
        se.save_epoch(tmp).wait()
        us_save = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        back = StreamingEngine.restore(tmp, A.pagerank(), cfg, verify=True)
        us_warm = (time.perf_counter() - t0) * 1e6
        mutated = se.current_graph()
        t0 = time.perf_counter()
        cold = StructureAwareEngine(mutated, A.pagerank(), cfg).run()
        us_cold = (time.perf_counter() - t0) * 1e6
        wm = back.initial_result.metrics
        agree = np.allclose(back.values, se.values, rtol=1e-4, atol=1e-6)
        rows.append((
            "ooc/powerlaw/pagerank/restart_warm", us_warm,
            f"iters={wm.iterations};cold_iters={cold.metrics.iterations};"
            f"iter_gain={cold.metrics.iterations / max(wm.iterations, 1):.1f}x;"
            f"agree={agree};save_us={us_save:.0f};"
            f"ttc_gain_vs_cold={us_cold / max(us_warm, 1e-9):.2f}x"))
        rows.append((
            "ooc/powerlaw/pagerank/restart_cold", us_cold,
            f"iters={cold.metrics.iterations}"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows
