"""Trace-time contract enforcement + golden jaxpr hashing.

Drives the registered contracts (:mod:`repro.analysis.contracts`)
through jax's abstract evaluation:

  * ``elementwise`` — trace on ``ShapeDtypeStruct``s and reject jaxprs
    containing cross-axis-0 primitives (gather/scatter/sort/reduce/
    scan); numpy host functions that cannot trace fall back to a
    concrete slicewise probe (``f(x)[i] == f(x[i:i+1])[0]``).
  * ``structure_independent`` — differential check: init values over two
    same-``n`` graphs with different edge sets must be bitwise equal
    (``lane_init`` sees no graph at all; it is probed for determinism).
  * ``decision_identical`` — seeded trials comparing the device select
    against its host twin, decision for decision.
  * ``one_executable_per`` — build a tiny engine, call each compiled-
    function getter twice per key, assert the identical object comes
    back and the cache does not grow.
  * golden jaxprs — canonicalized-and-hashed traces of the compiled
    entry points (device select, tiled sweeps, fused chunk, lane chunk,
    row scatter), committed in ``golden_jaxprs.json`` so a silent trace-
    structure change diffs loudly in CI. Hashes are stable for a fixed
    jax version; on a version mismatch the comparison is SKIPPED (with a
    regeneration hint), not failed.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import Contract
from repro.analysis.lint import Finding

GOLDEN_PATH = Path(__file__).with_name("golden_jaxprs.json")

# Primitives an elementwise (axis-0-local) function must not emit.
# Structural data movement, reductions, sorts, scans and inner control
# flow all couple vertices; pure elementwise math never lowers to these.
_CROSS_VERTEX_PRIMITIVES = {
    "gather", "scatter", "scatter-add", "scatter-mul", "scatter-min",
    "scatter-max", "dynamic_slice", "dynamic_update_slice", "sort",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_precision_window", "reduce_window_sum", "reduce_window_max",
    "dot_general", "conv_general_dilated", "while", "scan", "cond",
    "segment_sum",
}


# -- elementwise -------------------------------------------------------------
def _probe_args(contract: Contract, n: int, rng: np.random.Generator):
    """Concrete seeded inputs for a contract target: one array per
    parameter (axis 0 of length ``n``), honoring an explicit ``shapes``
    spec ("static" entries become plain Python scalars)."""
    params = list(inspect.signature(contract.target).parameters)
    shapes = contract.meta.get("shapes")
    args = []
    for i, name in enumerate(params):
        spec = shapes[i] if shapes is not None and i < len(shapes) else (n,)
        if spec == "static":
            args.append(n)
            continue
        shape = tuple(n if d == 8 and j == 0 else d
                      for j, d in enumerate(spec))
        # positive, non-degenerate values: aux_fn divides by these, and
        # min-combine deltas need distinct magnitudes
        args.append((rng.random(shape) * 4.0 + 0.5).astype(np.float32))
    return args


def _walk_jaxpr(jaxpr) -> set[str]:
    prims: set[str] = set()
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                prims |= _walk_jaxpr(inner)
            elif hasattr(v, "eqns"):
                prims |= _walk_jaxpr(v)
    return prims


def check_elementwise(contract: Contract) -> list[Finding]:
    rng = np.random.default_rng(7)
    n = 8
    args = _probe_args(contract, n, rng)
    where = f"{contract.module}:{contract.qualname}"
    # jaxpr path: traceable (jnp) functions get the primitive denylist
    try:
        jaxpr = jax.make_jaxpr(contract.target)(*args)
    except Exception:
        jaxpr = None  # numpy host fn — fall through to the probe
    if jaxpr is not None:
        bad = _walk_jaxpr(jaxpr.jaxpr) & _CROSS_VERTEX_PRIMITIVES
        if bad:
            return [Finding(
                "TC001", where, 0,
                f"@elementwise fn traces cross-vertex primitives "
                f"{sorted(bad)} — out[i] must depend on in[i] only")]
    # concrete slicewise probe (also exercises numpy host fns): full
    # output row i must equal the output of the length-1 slice at i
    try:
        full = np.asarray(contract.target(*args))
    except Exception as e:
        return [Finding("TC001", where, 0,
                        f"@elementwise fn failed on probe inputs: {e!r}")]
    if full.shape[:1] != (n,):
        return [Finding(
            "TC001", where, 0,
            f"@elementwise fn returned leading axis {full.shape[:1]} "
            f"for input axis ({n},) — must map axis 0 one-to-one")]
    for i in range(n):
        sliced = [a[i:i + 1] if isinstance(a, np.ndarray) else a
                  for a in args]
        row = np.asarray(contract.target(*sliced))[0]
        if not np.allclose(full[i], row, rtol=1e-6, atol=1e-6,
                           equal_nan=True):
            return [Finding(
                "TC001", where, 0,
                f"@elementwise violated at vertex {i}: full-batch row "
                f"{full[i]!r} != single-slice result {row!r}")]
    return []


# -- structure_independent ---------------------------------------------------
def _two_graphs(n: int = 64):
    from repro.core import graph as G
    return (G.uniform_graph(n, deg=4, seed=0, weighted=True),
            G.uniform_graph(n, deg=6, seed=3, weighted=True))


def check_structure_independent(contract: Contract) -> list[Finding]:
    where = f"{contract.module}:{contract.qualname}"
    params = list(inspect.signature(contract.target).parameters)
    if params and params[0] == "n":
        # lane_init(n, params): cannot see structure by construction;
        # probe determinism (bitwise-equal repeat calls)
        n = 64
        lane_params = ([3, 17, 41] if "pagerank" not in contract.qualname
                       else [[3, 17], [5], [9, 11, 13]])
        a = contract.target(n, lane_params)[0]
        b = contract.target(n, lane_params)[0]
        if not np.array_equal(a, b):
            return [Finding("TC002", where, 0,
                            "@structure_independent lane_init is not "
                            "deterministic across repeat calls")]
        return []
    g1, g2 = _two_graphs()
    try:
        v1 = np.asarray(contract.target(g1)[0])
        v2 = np.asarray(contract.target(g2)[0])
    except Exception as e:
        return [Finding("TC002", where, 0,
                        f"@structure_independent init failed: {e!r}")]
    if not np.array_equal(v1, v2):
        diff = int((v1 != v2).sum())
        return [Finding(
            "TC002", where, 0,
            f"@structure_independent init VALUES differ on two graphs "
            f"with the same n ({diff}/{v1.size} entries) — values must "
            f"be a function of n and program parameters only")]
    return []


# -- decision_identical ------------------------------------------------------
def check_decision_identical(contract: Contract) -> list[Finding]:
    where = f"{contract.module}:{contract.qualname}"
    twin = contract.meta.get("twin")
    if twin is None or not callable(twin):
        return [Finding("TC003", where, 0,
                        "@decision_identical has no callable twin")]
    if contract.qualname != "make_device_select":
        # other decision-identical pairs (the streaming successors
        # oracle) are enforced by their hypothesis property suites; the
        # contract marker records the pairing
        return []
    from repro.core.schedule import Scheduler
    rng = np.random.default_rng(11)
    width, cold_frac, min_psd = 4, 0.25, np.float32(1e-6)
    select = contract.target(width, cold_frac, float(min_psd), pad_id=0)
    sched = Scheduler(width=width, i2=3, cold_frac=cold_frac,
                      min_psd=float(min_psd))
    for trial in range(20):
        p = 8 if trial % 2 == 0 else 5
        shape = (p,) if trial % 3 else (p, 2)
        psd = (rng.random(shape) * rng.integers(0, 3, shape)
               ).astype(np.float32)
        is_hot = rng.random(p) < 0.5
        for it in range(4):
            hr, hok, cr, cok = select(jnp.int32(it), jnp.int32(sched.i2),
                                      jnp.asarray(psd),
                                      jnp.asarray(is_hot))
            sel = sched.select(it, psd, is_hot)
            dev_hot = np.asarray(hr)[np.asarray(hok)]
            dev_cold = np.asarray(cr)[np.asarray(cok)]
            if not (np.array_equal(dev_hot, sel.hot_ids)
                    and np.array_equal(dev_cold, sel.cold_ids)):
                return [Finding(
                    "TC003", where, 0,
                    f"device select diverged from host twin at trial "
                    f"{trial} it {it}: device hot={dev_hot.tolist()} "
                    f"cold={dev_cold.tolist()} vs host "
                    f"hot={sel.hot_ids.tolist()} "
                    f"cold={sel.cold_ids.tolist()}")]
    return []


# -- one_executable_per ------------------------------------------------------
def _tiny_engine(use_pallas: bool = False):
    from repro.core.algorithms import pagerank
    from repro.core.engine import EngineConfig, StructureAwareEngine
    g, _ = _two_graphs(200)
    return StructureAwareEngine(g, pagerank(),
                                EngineConfig(block_size=64, width=2,
                                             use_pallas=use_pallas))


def check_one_executable_per(contracts: list[Contract]) -> list[Finding]:
    """Single driver for every registered compile-cache getter: the
    getters are lazy (jax.jit wrapping compiles nothing until called),
    so identity + cache-size checks are cheap."""
    if not contracts:
        return []
    out = []
    eng = _tiny_engine()
    from repro.core.algorithms import k_source_sssp
    from repro.serve.lanes import LaneEngine
    lane = LaneEngine(eng, k_source_sssp())

    def probe(obj, getter, *argsets):
        name = f"{getter.__module__}:{getter.__qualname__}"
        for args in argsets:
            first = getter(obj, *args)
            size = len(obj._fns)
            again = getter(obj, *args)
            if again is not first:
                out.append(Finding(
                    "TC004", name, 0,
                    f"@one_executable_per returned a fresh executable "
                    f"on repeat call with key args {args!r}"))
            elif len(obj._fns) != size:
                out.append(Finding(
                    "TC004", name, 0,
                    f"@one_executable_per grew the compile cache on a "
                    f"repeat call with key args {args!r}"))

    by_name = {c.qualname: c for c in contracts}
    for qual, c in by_name.items():
        fn = c.target
        if qual.startswith("StructureAwareEngine._get_chunk"):
            probe(eng, fn, (2,), (None,), (2, 16))
        elif qual.startswith("StructureAwareEngine._get_fn"):
            probe(eng, fn, (True, 2), (False, 2))
        elif qual.startswith("LaneEngine._get_chunk"):
            probe(lane, fn, (2,))
        elif qual == "make_block_sweep":
            # module-level builder with its own memo (not an obj._fns
            # cache): a repeat build over the same (program, geometry,
            # mode) must return the identical sweep closure
            from repro.kernels import block_sweep as bs
            store = eng.plan.unified
            args = (eng.program, store.tile_cnt)
            kwargs = dict(tile_w=int(store.src.shape[1]),
                          block_size=eng.plan.block_size,
                          n_total=eng.plan.graph.n)
            first = fn(*args, **kwargs)
            size = len(bs._BUILDER_CACHE)
            again = fn(*args, **kwargs)
            if again is not first:
                out.append(Finding(
                    "TC004", f"{c.module}:{qual}", 0,
                    "@one_executable_per kernel builder minted a fresh "
                    "sweep closure on a repeat build"))
            elif len(bs._BUILDER_CACHE) != size:
                out.append(Finding(
                    "TC004", f"{c.module}:{qual}", 0,
                    "@one_executable_per kernel builder cache grew on a "
                    "repeat build"))
        elif qual.startswith("StructureAwareEngine._chunked_scatter"):
            # exercised through update_edge_rows: same scatter key twice
            rows = np.array([0], dtype=np.int32)
            t = eng._ed.src.shape[1]
            payload = dict(src=np.zeros((1, t), np.int32),
                           dst_local=np.zeros((1, t), np.int32),
                           w=np.zeros((1, t), np.float32),
                           valid=np.zeros((1, t), bool))
            eng.update_edge_rows(rows, **payload)
            size = len(eng._fns)
            eng.update_edge_rows(rows, **payload)
            if len(eng._fns) != size:
                out.append(Finding(
                    "TC004", f"{c.module}:{qual}", 0,
                    "@one_executable_per scatter cache grew on an "
                    "identical repeat scatter"))
    return out


# -- golden jaxprs -----------------------------------------------------------
def _canonical_hash(jaxpr) -> str:
    text = re.sub(r"\s+", " ", str(jaxpr)).strip()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def golden_entries() -> dict[str, str]:
    """Trace the compiled entry points on a tiny deterministic engine and
    hash the canonicalized jaxprs. Tracing only — nothing compiles."""
    from repro.core.schedule import make_device_select
    eng = _tiny_engine()
    from repro.core.algorithms import k_source_sssp
    from repro.serve.lanes import LaneEngine
    lane = LaneEngine(eng, k_source_sssp())
    p = eng.plan
    w = 2
    entries: dict[str, str] = {}

    select = make_device_select(4, 0.25, 1e-6, pad_id=0)
    entries["device_select_w4"] = _canonical_hash(jax.make_jaxpr(select)(
        jnp.int32(0), jnp.int32(4),
        jax.ShapeDtypeStruct((8, 2), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.bool_)))

    hot, cold = eng._sweeps(w)
    values = jax.ShapeDtypeStruct((eng._values_len,), jnp.float32)
    ps = jax.ShapeDtypeStruct((p.num_blocks, eng.config.subblocks),
                              jnp.float32)
    rows = jax.ShapeDtypeStruct((w,), jnp.int32)
    ok = jax.ShapeDtypeStruct((w,), jnp.bool_)
    entries["tiled_hot_sweep_w2"] = _canonical_hash(
        jax.make_jaxpr(hot)(eng._ed, values, ps, ps, rows, ok))
    entries["tiled_cold_sweep_w2"] = _canonical_hash(
        jax.make_jaxpr(cold)(eng._ed, values, ps, ps, rows, ok))

    counts = jax.ShapeDtypeStruct((p.num_blocks, eng.config.subblocks),
                                  jnp.int32)
    hslots = jax.ShapeDtypeStruct((p.num_blocks,), jnp.int32)
    entries["fused_chunk_w2"] = _canonical_hash(jax.make_jaxpr(
        eng._get_chunk(w))(
        eng._ed, eng._coupling_dev, values, ps, ps, counts, hslots,
        jax.ShapeDtypeStruct((w,), jnp.int32), jnp.int32(0), jnp.int32(0),
        jnp.int32(0), jax.ShapeDtypeStruct((p.num_blocks,), jnp.bool_),
        jnp.int32(4)))

    # the traced fused chunk (history-buffer variant behind
    # engine.run(trace=True)): extra int32 accounting table + the two
    # history buffers in the carry. Its OWN golden pins the traced trace
    # structure; the untraced entry above staying bit-identical across
    # this PR is the proof that trace=None compiles to exactly the
    # historical loop.
    from repro.core.engine import (TIMELINE_FLOAT_COLS, TIMELINE_INT_COLS)
    cap = 16
    acct = jax.ShapeDtypeStruct((p.num_blocks, 4), jnp.int32)
    hist_i = jax.ShapeDtypeStruct((cap, len(TIMELINE_INT_COLS)), jnp.int32)
    hist_f = jax.ShapeDtypeStruct((cap, len(TIMELINE_FLOAT_COLS)),
                                  jnp.float32)
    entries["fused_chunk_traced_w2_c16"] = _canonical_hash(jax.make_jaxpr(
        eng._get_chunk(w, cap))(
        eng._ed, eng._coupling_dev, values, ps, ps, counts, hslots,
        jax.ShapeDtypeStruct((w,), jnp.int32), jnp.int32(0), jnp.int32(0),
        jnp.int32(0), jax.ShapeDtypeStruct((p.num_blocks,), jnp.bool_),
        jnp.int32(4), acct, hist_i, hist_f))

    # lane chunk (serve path): chunk(ed, coupling, vconst, values, psd,
    # dmax, calm, counts, hslots, sbacc, lane_done, lane_it, it0, it_end,
    # is_hot, i2); at subblocks == 1 the lane psd/dmax are (P, L) and
    # calm/counts are (P,)
    nl = 2
    lvals = jax.ShapeDtypeStruct((eng._values_len, nl), jnp.float32)
    lps = jax.ShapeDtypeStruct((p.num_blocks, nl), jnp.float32)
    pvec_i = jax.ShapeDtypeStruct((p.num_blocks,), jnp.int32)
    entries["lane_chunk_w2_l2"] = _canonical_hash(jax.make_jaxpr(
        lane._get_chunk(w))(
        eng._ed, eng._coupling_dev, lvals, lvals, lps, lps,
        pvec_i, pvec_i, jax.ShapeDtypeStruct((w,), jnp.int32),
        jnp.int32(0),
        jax.ShapeDtypeStruct((nl,), jnp.bool_),
        jax.ShapeDtypeStruct((nl,), jnp.int32),
        jnp.int32(0), jnp.int32(0),
        jax.ShapeDtypeStruct((p.num_blocks,), jnp.bool_), jnp.int32(4)))

    # the fused Pallas sweep paths: same tiny geometry with
    # use_pallas=True — the hot/cold sweeps now wrap one pallas_call per
    # block and a silent change to the kernel's trace (grid, block specs,
    # in-kernel combine) must diff loudly here, exactly like the dense
    # entries above
    engp = _tiny_engine(use_pallas=True)
    hot_p, cold_p = engp._sweeps(w)
    entries["pallas_hot_sweep_w2"] = _canonical_hash(
        jax.make_jaxpr(hot_p)(engp._ed, values, ps, ps, rows, ok))
    entries["pallas_cold_sweep_w2"] = _canonical_hash(
        jax.make_jaxpr(cold_p)(engp._ed, values, ps, ps, rows, ok))
    lane_p = LaneEngine(engp, k_source_sssp())
    entries["pallas_lane_chunk_w2_l2"] = _canonical_hash(jax.make_jaxpr(
        lane_p._get_chunk(w))(
        engp._ed, engp._coupling_dev, lvals, lvals, lps, lps,
        pvec_i, pvec_i, jax.ShapeDtypeStruct((w,), jnp.int32),
        jnp.int32(0),
        jax.ShapeDtypeStruct((nl,), jnp.bool_),
        jax.ShapeDtypeStruct((nl,), jnp.int32),
        jnp.int32(0), jnp.int32(0),
        jax.ShapeDtypeStruct((p.num_blocks,), jnp.bool_), jnp.int32(4)))

    # the donated row scatter (streaming commit path): same closure the
    # engine builds lazily in _chunked_scatter
    na = 5

    def row_scatter(*args):
        arrs, r, payloads = args[:na], args[na], args[na + 1:]
        return tuple(a.at[r].set(pl) for a, pl in zip(arrs, payloads))

    t = eng._ed.src.shape[1]
    chunk = 16

    def tile(dt):
        return jax.ShapeDtypeStruct(eng._ed.src.shape, dt)

    def pay(dt):
        return jax.ShapeDtypeStruct((chunk, t), dt)

    entries["row_scatter_c16"] = _canonical_hash(jax.make_jaxpr(
        row_scatter)(
        tile(jnp.int32), tile(jnp.int32), tile(jnp.float32),
        tile(jnp.bool_),
        jax.ShapeDtypeStruct(eng._ed.cov.shape, jnp.bool_),
        jax.ShapeDtypeStruct((chunk,), jnp.int32),
        pay(jnp.int32), pay(jnp.int32), pay(jnp.float32), pay(jnp.bool_),
        jax.ShapeDtypeStruct((chunk, eng._ed.cov.shape[1]), jnp.bool_)))
    return entries


def write_golden(path: Path = GOLDEN_PATH) -> dict:
    payload = {"jax_version": jax.__version__,
               "entries": golden_entries()}
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return payload


def check_golden(path: Path = GOLDEN_PATH) -> tuple[list[Finding], str]:
    """Returns (findings, status). Status is 'ok', 'skipped', or
    'missing'."""
    if not path.exists():
        return ([Finding(
            "TC005", str(path), 0,
            "golden_jaxprs.json missing — run `python -m repro.analysis "
            "--update-golden` and commit the result")], "missing")
    stored = json.loads(path.read_text())
    if stored.get("jax_version") != jax.__version__:
        return ([], "skipped")
    current = golden_entries()
    out = []
    for name, want in sorted(stored.get("entries", {}).items()):
        got = current.get(name)
        if got is None:
            out.append(Finding(
                "TC005", str(path), 0,
                f"golden entry '{name}' no longer traceable — if the "
                f"entry point moved intentionally, regenerate with "
                f"--update-golden"))
        elif got != want:
            out.append(Finding(
                "TC005", str(path), 0,
                f"trace structure of '{name}' changed "
                f"({want} -> {got}) — if intentional, regenerate with "
                f"`python -m repro.analysis --update-golden` and commit"))
    for name in sorted(set(current) - set(stored.get("entries", {}))):
        out.append(Finding(
            "TC005", str(path), 0,
            f"new golden entry '{name}' not in committed file — "
            f"regenerate with --update-golden"))
    return (out, "ok")


# -- driver ------------------------------------------------------------------
def check_contracts(contracts: list[Contract]) -> list[Finding]:
    findings: list[Finding] = []
    oep = []
    for c in contracts:
        if c.kind == "elementwise":
            findings += check_elementwise(c)
        elif c.kind == "structure_independent":
            findings += check_structure_independent(c)
        elif c.kind == "decision_identical":
            findings += check_decision_identical(c)
        elif c.kind == "one_executable_per":
            oep.append(c)
        # @deterministic is enforced by the lint layer (RA004)
    findings += check_one_executable_per(oep)
    return findings
