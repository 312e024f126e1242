"""Analytics jobs of one vertex program, back to back in one closed-loop
client, on one engine built in set-up.

A job with a source (SSSP) starts from :func:`harness.cold_start`, the
state a new engine for that source would build; the sources cycle
through the pool :func:`workload.job_sources` draws, in the order the
run's seed gives. ``converge_s`` is the mean time of the jobs started in
the window, each run to its end.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness, workload

CHECK_SAMPLE = 8  # most answers of a run compared with the reference
# the warm-up runs this many supersteps of one job: every program a job
# calls is then compiled (the chunks at every bucket are compiled before)
WARMUP_SUPERSTEPS = 32


def run(run: harness.Run, graph, seed: int, seconds: float, traced: bool,
        counter: harness.CompileCounter, t_start: float, devs) -> None:
    import jax
    from repro.core.engine import StructureAwareEngine
    from repro.core.graph import from_edges

    cell = run.cell
    pname = cell.traffic["program"]
    params = cell.config["programs"][pname]
    prog = harness.load_module("programs", pname, cell.bench)
    n, src, dst, w = graph
    g = from_edges(n, src, dst, w)
    sources = (workload.job_sources(
        n, src, params["sources"], int(cell.traffic["source_pool"]),
        workload.rng_for(cell.config["graph_seed"], 1),
        workload.rng_for(seed, 1)) if prog.SOURCED else None)

    def source(k):
        return None if sources is None else int(sources[k % len(sources)])

    eng = StructureAwareEngine(g, prog.make(params, source(-1)),
                               harness.engine_config(cell.config))
    eng.prewarm_buckets()

    def job(k, **kw):
        if sources is None:
            return eng.run(**kw)
        return eng.run(warm=harness.cold_start(
            eng, prog.make(params, source(k)).init(g)[0]), **kw)

    with jax.profiler.TraceAnnotation("warmup"):
        job(-1, max_iterations=WARMUP_SUPERSTEPS)
    run.arcs = int(src.size)
    setup = time.perf_counter() - t_start
    answers = []
    counter.on = True
    with harness.Window(run, traced, seconds) as win:
        k = 0
        while win.open():
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("job"):
                res = job(k)
            dt = time.perf_counter() - t
            m = res.metrics
            run.jobs.append({"seconds": dt, "iterations": m.iterations,
                             "edges_processed": m.edges_processed,
                             "converged": m.converged})
            answers.append((source(k), res.values))
            k += 1
    counter.on = False
    run.memory_peak_bytes = harness.memory_peak(devs)
    run.failed = sum(not j["converged"] for j in run.jobs)
    run.e2e = {"setup_s": setup,
               "converge_s": float(np.mean([j["seconds"]
                                            for j in run.jobs]))}
    harness.log(f"jobs: {len(run.jobs)} in {run.window_s:.3f} s; seconds "
                f"{[round(j['seconds'], 4) for j in run.jobs]}; supersteps "
                f"{[j['iterations'] for j in run.jobs]}")
    del eng, res
    gc.collect()
    # every answer when they are few; else a sample drawn from the seed,
    # with the job that ran longest in it
    if len(answers) > CHECK_SAMPLE:
        rng = workload.rng_for(seed, 3)
        longest = int(np.argmax([j["iterations"] for j in run.jobs]))
        rest = np.delete(np.arange(len(answers)), longest)
        pick = [longest, *rng.choice(rest, CHECK_SAMPLE - 1, replace=False)]
        answers = [answers[i] for i in sorted(pick)]
    harness.compare(run, prog, params, (n, src, dst, w, params, answers))
