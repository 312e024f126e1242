"""Compile the Pallas block sweep for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles each sweep
variant for one chip of a ``v5e:2x2`` topology at a deployment-sized
shape (n = 2^20 vertices, 512-wide tiles, 256-vertex blocks, 8 lanes),
which catches what the Pallas interpreter cannot — tiling rules, memory
spaces, unsupported relayouts. Each compiled program must contain the
kernel (``tpu_custom_call``), so the sweep neither fell back to XLA nor
compiled in interpret mode.

The topology is described inside a module fixture, never while a module
is imported: the description loads the TPU library, which only one
process may hold.
"""
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
TILE_START = np.concatenate([[0], np.cumsum(TILE_CNT)[:-1]]).astype(np.int32)
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


@pytest.mark.parametrize("form", ["row", "vmap"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_block_sweep_compiles_for_v5e(variant, form, one_chip, no_cache):
    factory, lanes, subblocks = VARIANTS[variant]
    sweep = make_block_sweep(
        factory(), TILE_START, TILE_CNT, n_tiles=N_TILES, tile_w=TILE,
        block_size=BLOCK, n_total=N, subblocks=subblocks, lanes=lanes,
        interpret=False)
    ed = EdgeData(src=_spec((N_TILES, TILE), jnp.int32, one_chip),
                  dstl=_spec((N_TILES, TILE), jnp.int32, one_chip),
                  w=_spec((N_TILES, TILE), jnp.float32, one_chip),
                  valid=_spec((N_TILES, TILE), jnp.bool_, one_chip),
                  cov=_spec((N_TILES, subblocks), jnp.bool_, one_chip),
                  aux=_spec((N,), jnp.float32, one_chip))
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
    compiled = jax.jit(fn).lower(
        ed, vals, vals, _spec(row_shape, jnp.int32, one_chip),
        _spec(row_shape + (subblocks,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
