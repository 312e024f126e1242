"""Chip smoke test: drive the graph engine's main paths once on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the DistributedEngine
                                      # phase and its single-chip reference

One process holds the chip(s) for the whole run. Every phase checks its
answers against a plain reference that does not use the engine (numpy
power iterations, scipy's Dijkstra), and any failed check raises: the
script exits non-zero and never prints the final line.

One-chip phases, on a scale-:data:`SCALE` power-law graph
(``powerlaw_graph``, average degree 16, weighted, generated from
``--seed``):

- analytics — PageRank and SSSP through the fused engine, default config;
- kernel — the same two with ``use_pallas=True``: the compiled chunk must
  contain the Pallas kernel, SSSP must be bitwise equal to the dense run,
  and the block sums of a PageRank sweep must match the dense scatter
  within the kernel's stated tolerance;
- streaming — a ``StreamingEngine`` (SSSP) ingests three edit batches with
  deletes; the warm values must equal a cold run on the final graph;
- serving — one 8-lane k-SSSP batch and one PPR batch through
  ``QueryService`` on the live graph.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed on a TPU. The one-chip run is
meant to end within 20 minutes on a v5e chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

# Graph500 / Graphalytics "small" class is scale 22; the smoke runs scale
# 16 because the engine's work grows much faster than the graph on this
# generator. Sum programs: the hub block (the 256 top in-degree vertices)
# holds ~72% of the edges and the staleness coupling re-arms it after
# every other block's sweep, so it is swept at depth 8 in every superstep;
# at scale 20 one dense PageRank run did not end within 12 minutes on a
# v5e chip. Min programs: a dense SSSP run takes 37, 227, 1441, 8539 and
# 20074 supersteps at scales 12, 14, 16, 17 and 18 (2.3e9 edges processed
# for a 4.2e6-edge graph at 18), at ~7.5 ms a superstep on the chip.
FULL_SCALE = 22
SCALE = 16
# DistributedEngine pads every hot block to the hub block's in-edge count
# (823,040 edges a row at scale 16, 213,248 at 14) and dispatches from the
# host once per iteration (457 iterations at scale 16, 90 at 14), so the
# four-chip check runs at scale 14
DISTRIBUTED_SCALE = 14
STREAM_BATCH = 2000  # edits per streamed batch
DAMPING = 0.85


def log(msg: str) -> None:
    print(msg, flush=True)


@contextmanager
def phase(name: str):
    t = time.perf_counter()
    log(f"phase {name}: start")
    yield
    log(f"phase {name}: passed in {time.perf_counter() - t:.1f} s")


def require_tpu():
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU found (platform {d.platform!r}); "
                         "this smoke test runs only on the chip")
    return d, devs


# -- references (no engine code) ---------------------------------------------
def coo(g):
    from repro.core.graph import edges_of
    s, d, w = edges_of(g)
    return s, d, w.astype(np.float64)


def pagerank_ref(g, d: float = DAMPING, tol: float = 1e-13):
    """x = (1-d)/n + d * A^T (x / outdeg); dangling mass vanishes."""
    s, t, _ = coo(g)
    outdeg = np.maximum(g.out_deg, 1).astype(np.float64)
    x = np.full(g.n, 1.0 / g.n)
    for _ in range(2000):
        nx = (1 - d) / g.n + d * np.bincount(t, x[s] / outdeg[s],
                                             minlength=g.n)
        done = np.abs(nx - x).sum() < tol
        x = nx
        if done:
            break
    return x


def ppr_ref(g, reset, d: float = DAMPING, tol: float = 1e-13):
    """x = (1-d) r + d * A^T (x / outdeg), r uniform over ``reset``."""
    s, t, _ = coo(g)
    outdeg = np.maximum(g.out_deg, 1).astype(np.float64)
    r = np.zeros(g.n)
    np.add.at(r, np.asarray(reset, dtype=np.int64), 1.0 / len(reset))
    x = r.copy()
    for _ in range(2000):
        nx = (1 - d) * r + d * np.bincount(t, x[s] / outdeg[s],
                                           minlength=g.n)
        done = np.abs(nx - x).sum() < tol
        x = nx
        if done:
            break
    return x


def dijkstra_ref(g, sources):
    """Shortest distances from each source; parallel edges keep their
    lightest copy (a sparse matrix would add them up)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra
    s, t, w = coo(g)
    key = s * g.n + t
    order = np.lexsort((w, key))
    key, w = key[order], w[order]
    first = np.r_[True, key[1:] != key[:-1]]
    key, w = key[first], w[first]
    m = csr_matrix((w, (key // g.n, key % g.n)), shape=(g.n, g.n))
    return np.atleast_2d(dijkstra(m, directed=True, indices=sources))


# -- checks ------------------------------------------------------------------
def rank_bound(cfg) -> float:
    """L1 distance to the fixpoint that a converged run may keep: the
    engine stops when the blocks' mean residuals sum under t2, so the
    total residual is under block_size * t2, and the damped map amplifies
    a residual by at most 1/(1-d). Doubled: a block's recorded residual
    is from its last sweep, not from the final state."""
    return 2.0 * cfg.block_size * cfg.t2 / (1.0 - DAMPING)


def check_rank(name, got, want, bound):
    err = float(np.abs(np.asarray(got, np.float64) - want).sum())
    log(f"  {name}: L1 error {err:.3e} vs bound {bound:.3e} "
        f"(|x|_1 {want.sum():.4f})")
    assert np.all(np.isfinite(got)), f"{name}: non-finite values"
    assert err <= bound, f"{name}: L1 error {err} over {bound}"


def check_dist(name, got, want):
    """Reachability exactly; distances within f32 rounding of the path
    sums (at most hops * u relative; 1e-5 covers 160 hops)."""
    from repro.core.algorithms import INF
    got = np.asarray(got, np.float64)
    reach = np.isfinite(want)
    assert np.array_equal(got < INF / 2, reach), \
        f"{name}: reachable sets differ"
    err = np.abs(got[reach] - want[reach]) / np.maximum(want[reach], 1.0)
    worst = float(err.max()) if err.size else 0.0
    log(f"  {name}: {int(reach.sum())} reachable, max rel error "
        f"{worst:.2e}")
    assert worst <= 1e-5, f"{name}: distance error {worst}"


def sweep_sum_check(eng, values_orig):
    """Run one PageRank block sweep through the dense scatter and through
    the Pallas kernel from the same state, for the hub block and a spread
    of others, and hold every slot to the kernel's stated bound."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import make_tiled_processor
    from repro.kernels.block_sweep import sum_tolerance
    p = eng.plan
    vals = jnp.asarray(eng.pad_values(
        np.asarray(values_orig, np.float32)[p.order]))
    procs = [make_tiled_processor(eng.program, p.unified, p.block_size,
                                  p.n_live, p.graph.n, flag)[0]
             for flag in (False, True)]
    fns = [jax.jit(lambda v, r, f=f: f(eng.edge_state, v, r)[1])
           for f in procs]
    rows = sorted({0, 1, p.num_blocks // 2, p.num_blocks - 1})
    worst = 0.0
    for row in rows:
        dense, kern = (np.asarray(f(vals, jnp.int32(row)), np.float64)
                       for f in fns)
        lo = row * p.block_size
        terms = p.graph.in_deg[lo:lo + p.block_size]
        terms = np.pad(terms, (0, p.block_size - terms.size))
        bound = sum_tolerance(terms) * np.abs(dense)
        diff = np.abs(kern - dense)
        assert np.all(diff <= bound), \
            f"block {row}: kernel sum off by {diff.max()} (bound exceeded)"
        rel = diff / np.maximum(np.abs(dense), 1e-30)
        worst = max(worst, float(rel.max()))
    log(f"  block sums kernel vs scatter on blocks {rows}: max rel diff "
        f"{worst:.2e} (hub block: {int(p.graph.in_deg[:p.block_size].sum())}"
        f" terms)")


# -- phases ------------------------------------------------------------------
def build_engine(g, program, cfg):
    from repro.core.engine import StructureAwareEngine
    t = time.perf_counter()
    eng = StructureAwareEngine(g, program, cfg)
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    eng.prewarm_buckets()
    t_compile = time.perf_counter() - t
    log(f"  {program.name}{' pallas' if cfg.use_pallas else ''}: build "
        f"{t_build:.1f} s, compile {t_compile:.1f} s "
        f"(P={eng.plan.num_blocks}, tiles={eng.plan.unified.src.shape[0]})")
    return eng


def run_engine(eng):
    t = time.perf_counter()
    res = eng.run()
    m = res.metrics
    log(f"  {eng.program.name}: {m.iterations} supersteps, "
        f"{m.edges_processed} edges, run {time.perf_counter() - t:.1f} s")
    assert m.converged, f"{eng.program.name} did not converge"
    return res


def one_chip(seed: int) -> None:
    from repro.core import algorithms as A
    from repro.core import graph as G
    from repro.core.engine import EngineConfig, StructureAwareEngine
    from repro.serve import Query, QueryService
    from repro.stream import StreamingEngine, synthetic_stream

    cfg = EngineConfig()
    with phase("graph"):
        t = time.perf_counter()
        g = G.powerlaw_graph(2 ** SCALE, avg_deg=16, seed=seed,
                             weighted=True)
        log(f"  powerlaw scale {SCALE}: n={g.n} m={g.m}, generated in "
            f"{time.perf_counter() - t:.1f} s")
        log(f"  cut: scale {SCALE} instead of {FULL_SCALE} (see SCALE in "
            "chip_smoke.py)")
        src = int(np.argmax(g.out_deg))

    with phase("analytics"):
        pr_want = pagerank_ref(g)
        sp_want = dijkstra_ref(g, [src])[0]
        eng = build_engine(g, A.pagerank(), cfg)
        pr_dense = run_engine(eng).values
        check_rank("pagerank", pr_dense, pr_want, rank_bound(cfg))
        del eng
        eng = build_engine(g, A.sssp(src), cfg)
        sp_dense = run_engine(eng).values
        check_dist("sssp", sp_dense, sp_want)
        del eng
        gc.collect()

    with phase("kernel"):
        pcfg = EngineConfig(use_pallas=True)
        eng = build_engine(g, A.pagerank(), pcfg)
        text = eng.compiled_chunk_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the chunk"
        log(f"  compiled chunk holds {text.count('tpu_custom_call')} "
            "tpu_custom_call sites")
        pr_kern = run_engine(eng).values
        check_rank("pagerank pallas", pr_kern, pr_want, rank_bound(cfg))
        check_rank("pagerank pallas vs dense", pr_kern,
                   np.asarray(pr_dense, np.float64), 2 * rank_bound(cfg))
        sweep_sum_check(eng, pr_dense)
        del eng
        eng = build_engine(g, A.sssp(src), pcfg)
        sp_kern = run_engine(eng).values
        assert np.array_equal(sp_kern, sp_dense), \
            "sssp pallas is not bitwise equal to the dense run"
        log("  sssp pallas: bitwise equal to the dense run")
        del eng
        gc.collect()

    with phase("streaming"):
        t = time.perf_counter()
        se = StreamingEngine(g, A.sssp(src), cfg)
        log(f"  bootstrap (build + cold run): "
            f"{time.perf_counter() - t:.1f} s, "
            f"{se.initial_result.metrics.iterations} supersteps")
        batches = synthetic_stream(g, 3, STREAM_BATCH, seed=seed + 1,
                                   delete_frac=0.2, weighted=True)
        for i, b in enumerate(batches):
            rep = se.ingest(b)
            log(f"  batch {i}: +{rep.inserts} -{rep.deletes} edges, "
                f"{rep.dirty_blocks}/{rep.num_blocks} dirty blocks, "
                f"{rep.iterations} supersteps, latency "
                f"{rep.latency_s * 1e3:.0f} ms")
            assert rep.converged, f"batch {i} did not reconverge"
        assert sum(b.n_deletes for b in batches) > 0, "no deletes streamed"
        g_live = se.current_graph()
        cold = run_engine(StructureAwareEngine(g_live, A.sssp(src), cfg))
        assert np.array_equal(se.values, cold.values), \
            "warm values differ from a cold run on the final graph"
        log("  warm values bitwise equal to a cold run on the final graph")
        rng = np.random.default_rng(seed + 2)
        sources = rng.choice(g.n, size=8, replace=False)
        live_want = dijkstra_ref(g_live, [src, *sources])
        check_dist("streamed sssp", se.values, live_want[0])
        del cold
        gc.collect()

    with phase("serving"):
        svc = QueryService(se, max_lanes=8)
        for s in sources:
            svc.submit(Query(kind="sssp", source=int(s)))
        res = svc.run_pending()
        assert len(res) == 8 and all(r.lanes == 8 for r in res), \
            "k-SSSP queries did not share one 8-lane batch"
        for r, s, w in zip(sorted(res, key=lambda r: r.query_id), sources,
                           live_want[1:]):
            assert r.converged
            check_dist(f"k-sssp lane from {s}", r.values, w)
        log(f"  k-SSSP batch: {res[0].batch_iterations} supersteps, "
            f"run {res[0].run_s:.1f} s")
        resets = [[int(v)] for v in rng.choice(g.n, size=2, replace=False)]
        resets += [[int(v) for v in rng.choice(g.n, size=3, replace=False)]]
        for rs in resets:
            svc.submit(Query(kind="ppr", reset=rs))
        res = svc.run_pending()
        assert len(res) == len(resets) and res[0].lanes == len(resets)
        for r, rs in zip(sorted(res, key=lambda r: r.query_id), resets):
            assert r.converged
            check_rank(f"ppr lane reset {rs}", r.values, ppr_ref(g_live, rs),
                       rank_bound(cfg))
        log(f"  PPR batch: {res[0].batch_iterations} supersteps, "
            f"run {res[0].run_s:.1f} s")


def four_chips(seed: int, devs) -> None:
    """CC on a 4-device mesh through DistributedEngine, exactly equal to
    the single-device fused engine on the same graph."""
    from jax.sharding import Mesh
    from repro.core import algorithms as A
    from repro.core import graph as G
    from repro.core.distributed import DistributedEngine
    from repro.core.engine import EngineConfig

    assert len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}"
    cfg = EngineConfig()
    with phase("graph"):
        g = G.powerlaw_graph(2 ** DISTRIBUTED_SCALE, avg_deg=16, seed=seed)
        log(f"  powerlaw scale {DISTRIBUTED_SCALE}: n={g.n} m={g.m} (see "
            "DISTRIBUTED_SCALE in chip_smoke.py)")
    with phase("distributed"):
        single = run_engine(build_engine(g, A.cc(), cfg)).values
        mesh = Mesh(np.array(devs), ("data",))
        t = time.perf_counter()
        dist = DistributedEngine(g, A.cc(), cfg, mesh=mesh)
        log(f"  mesh {dict(mesh.shape)} over {[d.id for d in devs]}, "
            f"{dist.bpd} blocks per device, build "
            f"{time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        res = dist.run()
        m = res.metrics
        log(f"  cc on 4 chips: {m.iterations} iterations, run "
            f"{time.perf_counter() - t:.1f} s")
        assert m.converged, "distributed cc did not converge"
        assert np.array_equal(res.values, single), \
            "cc on 4 chips differs from the single-chip fused engine"
        log(f"  cc labels equal on all {g.n} vertices "
            f"({len(np.unique(single))} components)")
        # the work is spread: the block rows are split over all four
        # devices and the replicas are reconciled by collectives
        compiled = dist.compiled_hot_dispatch()
        spread = len(compiled.input_shardings[0][3].device_set)
        text = compiled.as_text()
        colls = [c for c in ("all-reduce", "all-gather", "reduce-scatter")
                 if c in text]
        log(f"  block rows sharded over {spread} devices; collectives "
            f"{colls}")
        assert spread == 4 and colls, "the mesh work is not spread"
        log("  bytes in use per device: "
            f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devs]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    log(f"compile cache: {use_compile_cache()}")
    device, devs = require_tpu()
    if args.chips == 4:
        four_chips(args.seed, devs)
    else:
        one_chip(args.seed)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
