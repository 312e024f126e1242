"""Record the span fixtures of ``test_bench_spans.py`` on a TPU chip.

    python3 bench/tests/record_spans.py --out <dir> \
        [--supersteps 3]

Writes two small profiler traces, gzipped, and one JSON file, as a
traced run of the benchmark would see them (the harness's ``window``
span around its ``job`` or ``ingest`` spans, the program's own spans
inside):

- ``spans_jobs.xplane.pb.gz``: two PageRank jobs of ``--supersteps`` each
  on a scale-9 Kronecker graph, Pallas sweep on (the first chunk
  boundary repartitions);
- ``spans_stream.xplane.pb.gz``: two 100-edit batches of the ``stream``
  mix on a live SSSP, each reconverged in at most ``--supersteps``;
- ``spans.json``: the jobs engine's ``op_scopes()`` and the jobs' and
  batches' ``host_syncs``.

The dispatch width is 2 and the repartition interval 1, so that the
files stay under 1 MB together (0.42 MB at three supersteps).
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--supersteps", type=int, default=3)
    args = ap.parse_args(argv)
    out, steps = Path(args.out), args.supersteps
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness, tracereduce, workload
    from bench.tests import tiny
    from repro.core.engine import StructureAwareEngine
    from repro.core.graph import from_edges
    from repro.stream import DeltaBatch, StreamingEngine

    engine = {"use_pallas": True, "t2": 1e-6, "width": 2,
              "repartition_interval": 1}
    cell = tiny.cell("g500-s16.pagerank", scale=9, engine=engine)
    n, src, dst, w = graph = harness.build_graph(cell)
    pr = harness.load_module("programs", "pagerank", cell.bench)
    eng = StructureAwareEngine(from_edges(n, src, dst, w),
                               pr.make(cell.config["programs"]["pagerank"],
                                       None),
                               harness.engine_config(cell.config))
    eng.prewarm_buckets()
    eng.run(max_iterations=steps)  # compiles what a job calls

    scell = tiny.cell("g500-s16.stream", scale=9,
                      engine={**engine, "max_iterations": steps})
    sp = harness.load_module("programs", "sssp", scell.bench)
    stream = workload.EditStream(n, src, dst, w, scell.traffic,
                                 workload.rng_for(7, 2))
    se = StreamingEngine(from_edges(n, src, dst, w),
                         sp.make(scell.config["programs"]["sssp"], 0),
                         harness.engine_config(scell.config))
    se.ingest(DeltaBatch(**stream.next_batch()))  # compiles the commits

    out.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # the annotations, not the runtime's own
    record = {"scopes": eng.op_scopes(), "jobs": [], "batches": []}

    def traced(name, span, calls):
        tdir = out / f"trace_{name}"
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            for call in calls:
                with jax.profiler.TraceAnnotation(span):
                    call()
        jax.profiler.stop_trace()
        (out / f"spans_{name}.xplane.pb.gz").write_bytes(
            gzip.compress(tracereduce.find_trace(tdir).read_bytes(), 9))
        shutil.rmtree(tdir)

    def job():
        m = eng.run(max_iterations=steps).metrics
        record["jobs"].append({"iterations": m.iterations,
                               "host_syncs": m.host_syncs})

    def batch():
        rep = se.ingest(DeltaBatch(**stream.next_batch()))
        record["batches"].append({"iterations": rep.iterations,
                                  "host_syncs": rep.host_syncs})

    traced("jobs", "job", [job, job])
    traced("stream", "ingest", [batch, batch])
    (out / "spans.json").write_text(json.dumps(record, indent=1))
    for p in sorted(out.iterdir()):
        print(p.name, p.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
