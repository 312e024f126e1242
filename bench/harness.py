"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the configuration: the JSON file its ``configs`` entry names, holding
  the generator (``bench/gen/<generator>.py``), the engine settings, and
  each vertex program's parameters and limits;
- the traffic mix: ``bench/traffic/<traffic>.json``, a data file read by
  the generator in :mod:`bench.workload`; its ``kind`` names the module
  ``bench/kinds/<kind>.py`` whose ``run`` drives set-up and the window
  (``jobs``: analytics jobs back to back; ``edits``: edit batches on a
  live graph);
- the vertex program: ``bench/programs/<program>.py``, which builds the
  engine's program and compares its answers with the plain reference;
- each per-layer metric: ``bench/metrics/<metric>.py``, a reader with
  ``read(run) -> float | None``.

A later cell, configuration, mix, kind of mix or metric is new files and
new entries, with no edit here.

A run: set-up (the configuration's graph, engine built, every dispatch
bucket compiled, a short warm-up), then a closed-loop window of
``--seconds``; with ``--trace 1`` the window runs under the profiler.
After the window the device's peak memory is read, the engine is freed
and the answers are compared with the reference. The numbers compared
go to standard error, each beside its limit, as the last lines, and
into the result's last key, ``checks``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import tracereduce, workload

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"  # traces of --trace 1 runs; kept in the checkout
KERNEL = "block_sweep"  # the Pallas kernel's name in compiled programs
SPANS = ("job", "ingest", "warmup")  # the harness's own profiler spans
TRACE_SECONDS = 3.0  # least traced stretch of a --trace 1 window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding things by name ---------------------------------------------------
def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module; names may hold ``.``/``-``."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    tag = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(f"bench_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    bench: Path  # the directory the pieces are found in
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # the spec's entries this cell reports
    per_layer: list


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` and every piece it
    names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    kind = traffic.get("kind") if isinstance(traffic, dict) else None
    if not isinstance(kind, str) or not (
            root / "bench" / "kinds" / f"{kind}.py").is_file():
        raise ValueError(f"traffic {w['traffic']!r} names no kind under "
                         "bench/kinds/")

    def listed(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in spec["end_to_end"] if listed(m) is not False]
    e2e_names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if listed(m) or (listed(m) is None and m["moves"] in e2e_names)]
    return Cell(name=name, bench=root / "bench", chips=int(w["chips"]),
                config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per)


# -- the device ---------------------------------------------------------------
def require_chips(chips: int):
    """The devices of a TPU with at least ``chips`` chips; anything else
    ends the run with no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU found (platform {d.platform!r}): the "
                         "benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) while
    ``on``: there should be none inside the window."""

    def __init__(self):
        from jax import monitoring
        self.on = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# -- the run --------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a run hands the per-layer readers and the result line."""

    cell: Cell
    arcs: int = 0  # arcs of the graph at set-up
    jobs: list = dataclasses.field(default_factory=list)
    batches: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    e2e: dict = dataclasses.field(default_factory=dict)
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)  # name: (v, lim)
    trace: object = None  # tracereduce.TraceSummary of a --trace 1 run
    peaks: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    compared: tuple = ()  # (program module, compare's arguments)


class Window:
    """The measured window. In a traced run the profiler records its start,
    up to the end of the first job or batch that ends after
    ``TRACE_SECONDS``: a short stretch, since the device's trace buffer
    holds a few seconds of this engine's operations. The counters still
    cover the whole window."""

    def __init__(self, run: Run, traced: bool, seconds: float):
        self.run, self.traced, self.seconds = run, traced, seconds
        self.tdir = OUT / "trace" / run.cell.name
        self.tracing = False
        self.annotation = None

    def __enter__(self):
        import jax
        if self.traced:
            shutil.rmtree(self.tdir, ignore_errors=True)
            # no Python tracer: it would trace every call the host makes
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.tdir), profiler_options=opts)
            self.tracing = True
            self.annotation = jax.profiler.TraceAnnotation(tracereduce.WINDOW)
            self.annotation.__enter__()
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        return self

    def open(self) -> bool:
        """Whether the next job or batch starts inside the window; ends the
        traced stretch once it is long enough."""
        now = time.perf_counter()
        if self.tracing and now - self.t0 >= TRACE_SECONDS:
            self._stop_trace()
        return now < self.t_end

    def _stop_trace(self):
        import jax
        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False

    def __exit__(self, *exc):
        self.run.window_s = time.perf_counter() - self.t0
        if self.tracing:
            self._stop_trace()
        if self.traced and exc[0] is None:
            t = time.perf_counter()
            path = tracereduce.find_trace(self.tdir)
            self.run.trace = tracereduce.reduce_trace(path, KERNEL, SPANS)
            log(f"trace: {path.stat().st_size} bytes, reduced in "
                f"{time.perf_counter() - t:.1f} s")
            shutil.rmtree(self.tdir, ignore_errors=True)


def engine_config(config: dict):
    from repro.core.engine import EngineConfig
    return EngineConfig(**config.get("engine", {}))


def build_graph(cell: Cell):
    """The configuration's graph: fixed by its own ``graph_seed``, so every
    run of every cell on it works on the same data; a run's ``--seed``
    draws only what the traffic sends (sources, edits)."""
    gen = load_module("gen", cell.config["generator"], cell.bench)
    return gen.generate(cell.config,
                        workload.rng_for(cell.config["graph_seed"], 0))


def cold_start(eng, init):
    """A new source's start for an engine built once: its initial values
    (``init``, by original vertex id), permuted and padded, with every
    block unseen and the plan's born-hot prefix hot. That is the start
    state a new engine for that source would build, so the run that
    follows is the cold run from it."""
    from repro.core import state
    from repro.core.engine import WarmStart
    plan = eng.plan
    return WarmStart(
        values=eng.pad_values(np.asarray(init)[plan.order]),
        psd=state.init_psd(plan.num_blocks, eng.config.subblocks),
        is_hot=np.arange(plan.num_blocks) < plan.barrier_block)


def compare(run: Run, prog, params: dict, args: tuple) -> None:
    """Hold the answers to the reference, after the window: each number
    compared goes into ``run.checks`` beside the configuration's limit."""
    t = time.perf_counter()
    run.compared = (prog, args)
    got = prog.compare(*args)
    log(f"reference: {len(args[-1])} answers compared in "
        f"{time.perf_counter() - t:.1f} s")
    for name, value in got.items():
        run.checks[name] = (value, float(params["limits"][name]))


def result_line(run: Run, traced: bool, devs) -> dict:
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    metrics = {}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        for m in run.cell.per_layer:
            value = load_module("metrics", m["name"],
                                run.cell.bench).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    ok = all(v <= lim for v, lim in run.checks.values())
    out = {"correct": bool(ok and run.checks and run.failed == 0
                           and (run.jobs or run.batches)),
           "attempted": len(run.jobs) + len(run.batches),
           "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out


def execute(cell: Cell, seed: int, seconds: float, traced: bool, devs,
            t_start: float) -> dict:
    """Set-up, window and comparison of one run on ``devs``: the result
    line. The look for a chip is the caller's."""
    return result_line(execute_run(cell, seed, seconds, traced, devs,
                                   t_start), traced, devs)


def execute_run(cell: Cell, seed: int, seconds: float, traced: bool, devs,
                t_start: float) -> Run:
    from bench import roofline
    run = Run(cell=cell, peaks=roofline.peaks(devs[0].device_kind))
    counter = CompileCounter()
    graph = build_graph(cell)
    load_module("kinds", cell.traffic["kind"], cell.bench).run(
        run, graph, seed, seconds, traced, counter, t_start, devs)
    log(f"compiles inside the window: {counter.count}")
    if traced:
        tr = run.trace
        log(f"trace: busy {tr.busy_s:.4f} s of {tr.window_s:.4f} s; idle "
            f"by span {json.dumps(tr.idle_by_span)}")
    return run


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    import jax
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    # every program of a cell is worth keeping: the next run reads it back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = require_chips(cell.chips)
    line = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                   t_start)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0
