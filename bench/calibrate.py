"""Readings that set a cell's limits: the program's on many seeds, the
control's on a few.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control 3 --seconds 8

One process holds the chip. For each seed it makes one run of the cell as
the benchmark makes it (set-up, a window of ``--seconds`` at the cell's
own load, the comparison with the reference) and prints the numbers
compared. For the first ``--control`` seeds it also prints the control's
readings: the reference computed in bfloat16, put in the program's place
on the same answers. A limit goes above the largest reading of the
program and below the smallest of the control. Each seed's reading is
one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.resolve(args.workload)
    devs = harness.require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        t = time.perf_counter()
        run = harness.execute_run(cell, seed, args.seconds, False, devs, t)
        out = {"seed": seed, "attempted": len(run.jobs) + len(run.batches),
               "failed": run.failed,
               "program": {n: v for n, (v, _) in run.checks.items()},
               "limits": {n: lim for n, (_, lim) in run.checks.items()}}
        if k < args.control:
            prog, cargs = run.compared
            out["control"] = prog.compare(*cargs, low=True)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
