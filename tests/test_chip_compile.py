"""Compile the Pallas block sweep for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles each sweep
variant for one chip of a ``v5e:2x2`` topology at a deployment-sized
shape (n = 2^20 vertices, 512-wide tiles, 256-vertex blocks, 8 lanes),
which catches what the Pallas interpreter cannot — tiling rules, memory
spaces, unsupported relayouts. Each compiled program must contain the
kernel (``tpu_custom_call``), so the sweep neither fell back to XLA nor
compiled in interpret mode. Each run's first row is read from the traced
row-start table ``ed.start``, as an out-of-core pool needs.

The topology is described inside a module fixture, never while a module
is imported: the description loads the TPU library, which only one
process may hold.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import algorithms as A
from repro.core.engine import EdgeData
from repro.kernels.block_sweep import make_block_sweep

N = 2 ** 20
TILE = 512
BLOCK = 256
LANES = 8
P = N // BLOCK
# a power-law-shaped tile run: one hub block holding most of the edges
# and a long tail of 1-3 tile blocks (the shape powerlaw_graph produces)
TILE_CNT = np.concatenate([[24478], 1 + np.arange(P - 1) % 3]).astype(
    np.int32)
N_TILES = int(TILE_CNT.sum())

VARIANTS = {
    # name: (program factory, lanes, subblocks)
    "sum": (A.pagerank, False, 1),
    "min": (lambda: A.sssp(0), False, 1),
    "sum_s8": (A.pagerank, False, 8),
    "ppr_lanes": (A.k_personalized_pagerank, True, 1),
    "ksssp_lanes": (A.k_source_sssp, True, 1),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure here means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def compiled_sweep(one_chip, no_cache):
    """``(variant, form) -> compiled text``, each compiled once a module."""
    memo = {}

    def build(variant, form):
        if (variant, form) not in memo:
            memo[variant, form] = _compile_sweep(variant, form, one_chip)
        return memo[variant, form]
    return build


def _compile_sweep(variant, form, one_chip):
    factory, lanes, subblocks = VARIANTS[variant]
    sweep = make_block_sweep(
        factory(), TILE_CNT, tile_w=TILE, block_size=BLOCK, n_total=N,
        subblocks=subblocks, lanes=lanes, interpret=False)
    ed = EdgeData(src=_spec((N_TILES, TILE), jnp.int32, one_chip),
                  dstl=_spec((N_TILES, TILE), jnp.int32, one_chip),
                  w=_spec((N_TILES, TILE), jnp.float32, one_chip),
                  valid=_spec((N_TILES, TILE), jnp.bool_, one_chip),
                  cov=_spec((N_TILES, subblocks), jnp.bool_, one_chip),
                  aux=_spec((N,), jnp.float32, one_chip),
                  start=_spec((P,), jnp.int32, one_chip),
                  resident=_spec((P,), jnp.bool_, one_chip))
    vshape = (N, LANES) if lanes else (N,)
    vals = _spec(vshape, jnp.float32, one_chip)
    masked = subblocks > 1

    def one(ed, values, vconst, row, sub_act):
        sa = sub_act if masked else None
        if lanes:
            return sweep(ed, values, vconst, row, sa)
        return sweep(ed, values, row, sa)

    # "vmap" is the cold sweep's form: a slate of rows, one grid step each
    fn = (jax.vmap(one, in_axes=(None, None, None, 0, 0)) if form == "vmap"
          else one)
    row_shape = (2,) if form == "vmap" else ()
    return jax.jit(fn).lower(
        ed, vals, vals, _spec(row_shape, jnp.int32, one_chip),
        _spec(row_shape + (subblocks,), jnp.bool_, one_chip)).compile(
        ).as_text()


@pytest.mark.parametrize("form", ["row", "vmap"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_block_sweep_compiles_for_v5e(variant, form, compiled_sweep):
    assert "tpu_custom_call" in compiled_sweep(variant, form)


@pytest.mark.parametrize("form", ["row", "vmap"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_tile_rows_are_sliced_for_v5e(variant, form, compiled_sweep):
    """Each chunk reads its tile rows as contiguous slices: no ``gather``
    of the compiled sweep is under ``sweep_gather_rows``, which still
    names the ops that load the rows (in the engine's fused chunk too,
    ``test_chunk_scopes_for_v5e``)."""
    from repro.obs import scopes
    text = compiled_sweep(variant, form)
    row_gathers = [
        line[:200] for line in text.splitlines()
        if re.search(r"= \S+ gather\(", line)
        and scopes.scope_of(_op_name(line)) == "sweep_gather_rows"]
    assert not row_gathers, row_gathers
    assert "sweep_gather_rows" in scopes.op_scopes(text).values()


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


# programs whose edge_map reads aux and not w: the message is mapped once
# per vertex and gathered once per edge
SRC_MAPPED = ("sum", "sum_s8", "ppr_lanes")


def _in_loops(text):
    """Instruction lines of a compiled module, each with whether it runs
    inside a ``while`` body (directly or through the computations that
    the body calls)."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            cur = head[1] if head[0] == "ENTRY" else head[0]
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    called = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)"
                        r"|branch_computations=\{([^}]*)\}")
    todo = [b for body in comps.values() for line in body
            for b in re.findall(r"body=(%[\w.\-]+)", line)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in looped or c not in comps:
            continue
        looped.add(c)
        for line in comps[c]:
            for one, many in called.findall(line):
                todo += [one] if one else re.findall(r"%[\w.\-]+", many)
    return [(line, c in looped) for c, body in comps.items()
            for line in body]


@pytest.mark.parametrize("form", ["row", "vmap"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_message_gathered_once_for_v5e(variant, form, compiled_sweep):
    """Each chunk gathers one array per edge, under
    ``sweep_gather_values``, and never aux. Where ``edge_map`` ignores
    ``w`` (PageRank, the PPR lanes) that array is the message mapped once
    per vertex under ``sweep_src_map``, outside the chunk loop's body, so
    the map is not fused back into the gather; SSSP gathers its values
    and maps no vertex."""
    from repro.obs import scopes
    lines = _in_loops(compiled_sweep(variant, form))
    gathers = [(scopes.scope_of(_op_name(line)), looped)
               for line, looped in lines
               if re.search(r"= \S+ gather\(", line)]
    assert gathers.count(("sweep_gather_values", True)) == 1, gathers
    assert not [g for g in gathers if g[0] == "sweep_gather_aux"], gathers
    mapped = [looped for line, looped in lines
              if scopes.scope_of(_op_name(line)) == "sweep_src_map"]
    if variant in SRC_MAPPED:
        assert mapped and not any(mapped), mapped
    else:
        assert not mapped


def test_chunk_scopes_for_v5e(one_chip, no_cache, monkeypatch):
    """The engine's fused chunk compiled for v5e: every named scope of
    PageRank's superstep reaches the compiled program, and every operation
    that does work maps to one of them (``op_scopes``' reading)."""
    from repro.core import graph as G
    from repro.core.engine import EngineConfig, StructureAwareEngine
    from repro.kernels import ops as kops
    from repro.obs import scopes

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    g = G.powerlaw_graph(4096, avg_deg=8, seed=0, weighted=True)
    eng = StructureAwareEngine(g, A.pagerank(), EngineConfig(
        t2=1e-6, use_pallas=True))
    wb = eng._ladder[0]  # the widest dispatch bucket

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    args = jax.tree.map(spec, eng._idle_chunk_args(wb))
    text = eng._get_chunk(wb).lower(*args).compile().as_text()
    got = scopes.op_scopes(text)
    # PageRank maps its message per vertex: no aux is gathered per edge
    assert set(scopes.SCOPES) - {"sweep_gather_aux"} <= set(got.values())
    assert "sweep_gather_aux" not in got.values()
    idle = ("parameter(", "get-tuple-element(", "bitcast(", "constant(",
            "tuple(", "copy(", "copy-start(", "copy-done(", "custom-call()")
    for line in text.splitlines():
        key = scopes.op_key(line)
        if key in got and got[key] == scopes.UNSCOPED:
            assert any(op in line for op in idle), line[:200]


def test_pool_chunk_compiles_for_v5e(one_chip, no_cache, monkeypatch):
    """An engine under an out-of-core budget: its fused chunk reads every
    run through the traced row-start table of a pool smaller than the
    plan, still calls the kernel and still slices its tile rows, and the
    pool's placement and compaction writes compile under ``ooc_place``."""
    from repro.core import graph as G
    from repro.core.engine import EngineConfig, StructureAwareEngine
    from repro.kernels import ops as kops
    from repro.obs import scopes

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    g = G.powerlaw_graph(4096, avg_deg=8, seed=0, weighted=True)
    cnt = StructureAwareEngine(g, A.pagerank(), EngineConfig(
        t2=1e-6)).plan.unified.tile_cnt
    rows = int(np.sort(cnt)[::-1][:10].sum()) + 2
    assert rows < int(cnt.sum())
    eng = StructureAwareEngine(g, A.pagerank(), EngineConfig(
        t2=1e-6, use_pallas=True, resident_rows=rows))
    assert eng.edge_state.src.shape[0] == rows
    wb = eng._ladder[0]

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    args = jax.tree.map(spec, eng._idle_chunk_args(wb))
    text = eng._get_chunk(wb).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [line for line in text.splitlines()
                if re.search(r"= \S+ gather\(", line)
                and scopes.scope_of(_op_name(line)) == "sweep_gather_rows"]
    eng.spill.prewarm()
    ed = eng.edge_state
    arrays = [spec(a) for a in (ed.src, ed.dstl, ed.w, ed.valid, ed.cov)]
    for key, k in (("pool_move", eng._MOVE_CHUNK),
                   *((f"pool_place_{k}", k) for k in eng._PLACE_CHUNKS)):
        idx = _spec((k,), jnp.int32, one_chip)
        more = ([idx] if key == "pool_move" else
                [_spec((k,) + a.shape[1:], a.dtype, one_chip)
                 for a in arrays])
        got = scopes.op_scopes(eng._fns[key].lower(
            *arrays, idx, *more).compile().as_text())
        assert "ooc_place" in got.values(), key
