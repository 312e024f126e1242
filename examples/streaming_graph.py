"""Streaming demo: a long-lived engine serving edge deltas beats rerunning
a batch job per snapshot.

A core-periphery graph (the paper's convergence-skew regime) converges
once, then a synthetic delta stream — preferential-attachment inserts,
random unfollows, the occasional celebrity burst — is ingested batch by
batch. Each batch re-heats only the dirty blocks and reconverges from the
previous fixpoint inside the already-compiled fused superstep; the cold
column reruns the full convergence from scratch on the same mutated graph.

With ``--resident-rows`` the warm engine additionally runs OUT OF CORE:
the device holds a pool of that many edge tile rows, the rest of the
graph's rows spill to a host/disk tier and page back in ahead of the
schedule — the
values stay bitwise-identical to the fully resident run. ``--snapshot-dir``
then demos epoch persistence: save the live epoch, restore it in a fresh
engine, and warm-reconverge in a handful of supersteps instead of a cold
start.

    PYTHONPATH=src python examples/streaming_graph.py [--n 10000] \
        [--resident-rows 60] [--snapshot-dir /tmp/epoch]
"""
import argparse
import dataclasses

import numpy as np

from repro.core import algorithms as A
from repro.core import graph as G
from repro.core.engine import EngineConfig
from repro.stream import StreamConfig, StreamingEngine, synthetic_stream


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10000)
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=150)
    ap.add_argument("--subblocks", type=int, default=1,
                    help="sub-blocks per partition block (hierarchical "
                         "activity tracking; 1 = flat blocks)")
    ap.add_argument("--resident-rows", type=int, default=None,
                    help="device pool for the warm engine's edge tile rows "
                         "(out-of-core; default: fully resident)")
    ap.add_argument("--spill-dir", default=None,
                    help="spill evicted tiles to npz segments here instead "
                         "of the host cache (needs --resident-rows)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="save the final epoch here, then restore + "
                         "warm-reconverge a fresh engine from it")
    args = ap.parse_args()

    g = G.core_periphery_graph(args.n, avg_deg=8, seed=1, chords=1,
                               weighted=True)
    cfg = EngineConfig(t2=1e-8, width=16, block_size=512,
                       subblocks=args.subblocks)
    prog = A.pagerank()

    warm_cfg = dataclasses.replace(cfg, resident_rows=args.resident_rows,
                                   spill_dir=args.spill_dir)
    warm = StreamingEngine(g, prog, warm_cfg)
    cold = StreamingEngine(g, prog, cfg, StreamConfig(warm=False))
    print(f"initial convergence: {warm.initial_result.metrics.iterations} "
          f"iterations, {warm.initial_result.metrics.edges_processed} edges")

    batches = synthetic_stream(g, args.batches, args.batch_size, seed=3,
                               delete_frac=0.2, weighted=True)
    print(f"\n{'batch':>5s} {'+ins':>5s} {'-del':>5s} {'dirty':>9s} "
          f"{'width':>6s} {'retired':>8s} "
          f"{'warm edges':>11s} {'cold edges':>11s} {'warm ms':>8s} "
          f"{'cold ms':>8s}")
    for i, b in enumerate(batches):
        rw = warm.ingest(b)
        rc = cold.ingest(b)
        # width/retired: the adaptive active set at work — a small batch
        # reconverges in a narrow dispatch bucket and ends with most
        # blocks individually retired, so effort shrinks with batch size
        print(f"{i:5d} {rw.inserts:5d} {rw.deletes:5d} "
              f"{rw.dirty_blocks:3d}/{rw.num_blocks:<3d}   "
              f"{rw.mean_dispatch_width:6.1f} "
              f"{rw.blocks_retired:3d}/{rw.num_blocks:<3d} "
              f"{rw.edges_processed:11d} {rc.edges_processed:11d} "
              f"{rw.latency_s * 1e3:8.1f} {rc.latency_s * 1e3:8.1f}")

    assert np.allclose(warm.values, cold.values, rtol=1e-3, atol=1e-5), \
        "warm and cold disagree!"
    mw, mc = warm.metrics, cold.metrics
    print(f"\nwarm vs cold over {mw.batches} batches: "
          f"{mc.edges_reprocessed / max(mw.edges_reprocessed, 1):.2f}x fewer "
          f"edges reprocessed, "
          f"{mc.latency_per_batch_s / max(mw.latency_per_batch_s, 1e-9):.2f}x "
          f"faster per batch, mean dirty fraction {mw.dirty_frac:.2f} "
          f"({mw.appended_blocks} in-place appends, {mw.rebuilt_blocks} "
          f"block rebuilds, {mw.plan_rebuilds} plan rebuilds); "
          f"mean dispatch width {mw.mean_dispatch_width:.1f} "
          f"of {warm.engine.config.width}, hot-depth histogram "
          f"{dict(sorted(mw.inner_depth_hist.items(), reverse=True))}")
    if args.subblocks > 1:
        print(f"hierarchical partitions (S={args.subblocks}): mean dirty "
              f"sub-block fraction {mw.subblock_dirty_frac:.2f} vs block "
              f"fraction {mw.dirty_frac:.2f}, mean sub-blocks swept per "
              f"block load {mw.mean_subblock_dispatch:.2f}")
    if args.resident_rows is not None:
        rows = warm.engine.plan.unified.src.shape[0]
        init = warm.initial_result.metrics
        # paging never changes the schedule, so the budget run is
        # bitwise-equal to a fully resident warm engine (the property
        # tests in tests/test_ooc.py pin this); here the cold column
        # already cross-checks the converged values above
        print(f"out-of-core: {args.resident_rows}/{rows} tile rows "
              f"resident; "
              f"spill traffic incl. initial run: "
              f"{mw.spill_evictions + init.spill_evictions} evictions, "
              f"{(mw.bytes_spilled + init.bytes_spilled) / 1e6:.1f} MB out, "
              f"{(mw.bytes_fetched + init.bytes_fetched) / 1e6:.1f} MB in, "
              f"prefetch hit rate {mw.prefetch_hit_rate:.2f}")

    if args.snapshot_dir:
        warm.save_epoch(args.snapshot_dir).wait()
        back = StreamingEngine.restore(args.snapshot_dir, A.pagerank(),
                                       warm_cfg, verify=True)
        wm = back.initial_result.metrics
        assert np.allclose(back.values, warm.values, rtol=1e-4, atol=1e-6), \
            "restored epoch disagrees with the live engine!"
        print(f"\nepoch persistence: saved epoch {warm.epoch} to "
              f"{args.snapshot_dir}, restored + warm-reconverged in "
              f"{wm.iterations} supersteps (initial cold start took "
              f"{warm.initial_result.metrics.iterations})")


if __name__ == "__main__":
    main()
