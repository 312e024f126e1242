# One function per paper table. Prints ``name,us_per_call,derived`` CSV;
# ``--json`` additionally writes BENCH_runtime.json so PRs can track the
# perf trajectory.
from __future__ import annotations

import argparse
import json
import os
import platform
import sys


def _host_meta(repeats: int) -> dict:
    """Host metadata recorded next to the timing rows: cross-PR comparisons
    on shared/small boxes are only meaningful when the host (and the
    best-of-K protocol) is pinned alongside the numbers."""
    import jax
    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "repeats": repeats,
        "timing": "best-of-%d per row (min us_per_call)" % repeats,
    }


def _merge_best(attempts: list[list[tuple]]) -> list[tuple]:
    """Best-of-K merge: keep each row at its minimum us_per_call (derived
    travels with the winning repeat). Row order follows the first attempt;
    rows that only appear in later repeats are appended."""
    order: list[str] = []
    best: dict[str, tuple] = {}
    for rows in attempts:
        for name, us, derived in rows:
            if name not in best:
                order.append(name)
                best[name] = (name, us, derived)
            elif us < best[name][1]:
                best[name] = (name, us, derived)
    return [best[name] for name in order]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000,
                    help="graph size for the engine benchmarks")
    ap.add_argument("--suites", default=None,
                    help="comma list: runtime,convergence,io,kernels,"
                         "streaming,stream_subblock,serving,ooc — plus "
                         "serving_smoke, a cheap 2-lane serving subset "
                         "(small n) CI can run without the full matrix")
    ap.add_argument("--only", default=None,
                    help="deprecated alias of --suites")
    ap.add_argument("--lanes", type=int, default=8,
                    help="lane width for the serving suite")
    ap.add_argument("--repeats", type=int, default=1,
                    help="run each suite K times and keep the best "
                         "us_per_call per row — damps the ~±15%% run noise "
                         "of small shared boxes")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_runtime.json ({meta, rows}) next to "
                         "the CSV output")
    ap.add_argument("--trace", action="store_true",
                    help="record host spans + device superstep timelines "
                         "while each suite runs and write "
                         "results/trace_<suite>.json (Chrome-trace JSON; "
                         "open in ui.perfetto.dev or render with "
                         "`python -m repro.obs render <file>`)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_convergence, bench_io, bench_kernels,
                            bench_ooc, bench_runtime, bench_serving,
                            bench_streaming)
    suites = {
        "runtime": lambda: bench_runtime.run(args.n),
        "convergence": lambda: bench_convergence.run(args.n),
        "io": lambda: bench_io.run(args.n),
        "kernels": bench_kernels.run,
        "streaming": lambda: bench_streaming.run(args.n),
        # hierarchical partitions: sub-block vs block activity tracking
        # on small warm batches (the P-pigeonhole comparison)
        "stream_subblock": lambda: bench_streaming.run_subblock(args.n),
        "serving": lambda: bench_serving.run(args.n, lanes=args.lanes),
        # out-of-core tier: residency-budget sweep + warm-restart TTC
        "ooc": lambda: bench_ooc.run(args.n),
        # CI smoke subset: tiny graph, 2 lanes — exercises the whole
        # serve stack (lanes, pinning, churn) without the full matrix
        "serving_smoke": lambda: bench_serving.run(min(args.n, 1500),
                                                   lanes=2),
    }
    default = [k for k in suites if k != "serving_smoke"]
    sel = args.suites or args.only
    pick = sel.split(",") if sel else default
    unknown = [k for k in pick if k not in suites]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; have {sorted(suites)}")
    if args.json and "io" not in pick:
        # the bytes-loaded trajectory is tracked across PRs: a JSON payload
        # without the I/O table rows silently drops it
        pick.append("io")
    repeats = max(args.repeats, 1)
    if args.trace:
        from repro.obs import export as obs_export
        from repro.obs import trace as obs_trace
        os.makedirs("results", exist_ok=True)
    print("name,us_per_call,derived")
    ok = True
    records = []
    for key in pick:
        attempts: list[list[tuple]] = []
        err = None
        rec = obs_trace.install(obs_trace.TraceRecorder()) \
            if args.trace else None
        try:
            for _ in range(repeats):
                try:
                    attempts.append(suites[key]())
                except ImportError:
                    # a suite that cannot even import is a broken harness,
                    # not a data point — fail loudly instead of emitting an
                    # ERROR row
                    raise
                except Exception as e:  # noqa: BLE001
                    err = e
                    break
        finally:
            if rec is not None:
                obs_trace.uninstall()
                path = obs_export.write(
                    rec, os.path.join("results", f"trace_{key}.json"),
                    meta={"suite": key, "n": args.n, "repeats": repeats})
                print(f"wrote {path} ({len(rec.events)} events)",
                      file=sys.stderr)
        if err is not None and not attempts:
            ok = False
            print(f"{key},-1,ERROR:{err!r}")
            # keep the failure in-band in the JSON payload too: a suite's
            # rows silently vanishing would read as a perf change
            records.append({"suite": key, "name": key, "us_per_call": -1,
                            "derived": f"ERROR:{err!r}"})
            continue
        if err is not None:
            # a repeat died after others succeeded: the merged rows are
            # best-of-fewer than advertised — record that in-band so a
            # later reader of the committed JSON sees it, not just CI
            ok = False
            print(f"{key},-1,ERROR(partial):{err!r}", file=sys.stderr)
            records.append({
                "suite": key, "name": f"{key}/__partial_error",
                "us_per_call": -1,
                "derived": (f"ERROR(best-of-{len(attempts)} only, "
                            f"repeat {len(attempts) + 1} died):{err!r}")})
        for name, us, derived in _merge_best(attempts):
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
            records.append({"suite": key, "name": name,
                            "us_per_call": round(float(us), 1),
                            "derived": derived})
    if args.json:
        # a partial --suites run must not clobber the other suites' rows:
        # keep any existing row whose suite was not re-run this time, so
        # `--suites kernels --json` appends/refreshes in place
        kept = []
        if os.path.exists("BENCH_runtime.json"):
            try:
                with open("BENCH_runtime.json") as f:
                    prev = json.load(f)
                kept = [r for r in prev.get("rows", [])
                        if r.get("suite") not in pick]
            except (json.JSONDecodeError, OSError):
                kept = []
        with open("BENCH_runtime.json", "w") as f:
            json.dump({"meta": _host_meta(repeats),
                       "rows": kept + records}, f, indent=1)
        print(f"wrote BENCH_runtime.json ({len(kept + records)} rows, "
              f"{len(records)} new)", file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == '__main__':
    main()
