"""Batches of undirected edge edits against a live graph, back to back in
one closed-loop client, on a ``StreamingEngine`` built in set-up.

The engine runs the mix's program from the graph's highest-degree vertex.
Set-up ingests the mix's warm-up batches; in the window each batch is
drawn from :class:`workload.EditStream`, then handed to ``ingest``, whose
return is the batch's freshness latency (ingested and reconverged, values
ready). After the window the final values meet the reference on the
benchmark's own multiset with the same edits applied.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness, workload


def run(run: harness.Run, graph, seed: int, seconds: float, traced: bool,
        counter: harness.CompileCounter, t_start: float, devs) -> None:
    import jax
    from repro.core.graph import from_edges
    from repro.stream import DeltaBatch, StreamingEngine

    cell = run.cell
    tr = cell.traffic
    pname = tr["program"]
    params = cell.config["programs"][pname]
    prog = harness.load_module("programs", pname, cell.bench)
    n, src, dst, w = graph
    source = int(np.argmax(np.bincount(src, minlength=n)))
    stream = workload.EditStream(n, src, dst, w, tr,
                                 workload.rng_for(seed, 2))
    se = StreamingEngine(from_edges(n, src, dst, w),
                         prog.make(params, source),
                         harness.engine_config(cell.config))
    with jax.profiler.TraceAnnotation("warmup"):
        for _ in range(int(tr["warmup_batches"])):
            se.ingest(DeltaBatch(**stream.next_batch()))
    run.arcs = int(src.size)
    setup = time.perf_counter() - t_start
    lat = []
    edits = 0
    counter.on = True
    with harness.Window(run, traced, seconds) as win:
        while win.open():
            batch = DeltaBatch(**stream.next_batch())
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("ingest"):
                rep = se.ingest(batch)
            lat.append(time.perf_counter() - t)
            edits += stream.last_edits
            run.batches.append({
                "ingest_s": rep.ingest_time_s, "iterations": rep.iterations,
                "converged": rep.converged,
                "plan_rebuild": rep.plan_rebuild})
    counter.on = False
    run.memory_peak_bytes = harness.memory_peak(devs)
    run.failed = sum(not b["converged"] for b in run.batches)
    run.e2e = {"setup_s": setup,
               "edits_per_s": edits / run.window_s,
               "batch_p95_ms": float(np.percentile(lat, 95)) * 1e3}
    harness.log(
        f"batches: {len(run.batches)} in {run.window_s:.3f} s; latency "
        f"median {np.median(lat) * 1e3:.1f} ms; plan rebuilds "
        f"{sum(b['plan_rebuild'] for b in run.batches)}")
    values = np.array(se.values)
    del se
    gc.collect()
    # the reference runs on the generator's own multiset after the same
    # batches, never on the engine's graph
    rs, rd, rw = stream.edges()
    harness.compare(run, prog, params,
                    (n, rs, rd, rw, params, [(source, values)]))
