"""jit'd dispatch wrappers for the Pallas kernels.

``interpret`` auto-detects: compiled Mosaic lowering on TPU, Python
interpretation elsewhere (CPU validation). Every op has a pure-jnp oracle in
ref.py; tests sweep shapes/dtypes and assert allclose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import block_sweep as _bs
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import spmv as _spmv
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def edge_block_sum(msg: jnp.ndarray, dst: jnp.ndarray,
                   block_size: int) -> jnp.ndarray:
    return _spmv.edge_block_sum(msg, dst, block_size,
                                interpret=_interpret())


def edge_block_min(msg: jnp.ndarray, dst: jnp.ndarray, block_size: int,
                   identity: float) -> jnp.ndarray:
    return _bs.edge_block_min(msg, dst, block_size, identity,
                              interpret=_interpret())


def edge_block_max(msg: jnp.ndarray, dst: jnp.ndarray, block_size: int,
                   identity: float) -> jnp.ndarray:
    return _bs.edge_block_max(msg, dst, block_size, identity,
                              interpret=_interpret())


def make_block_sweep(program, store, block_size: int, n_total: int, *,
                     subblocks: int = 1, lanes: bool = False):
    """Build the per-block sweep over ``store``'s tile geometry: gather and
    ``edge_map`` in XLA, the segmented combine in one ``pallas_call`` per
    chunk of tile rows, ``apply`` in XLA. See
    :mod:`repro.kernels.block_sweep`."""
    return _bs.make_block_sweep(
        program, store.tile_start, store.tile_cnt,
        n_tiles=int(store.src.shape[0]), tile_w=int(store.src.shape[1]),
        block_size=block_size, n_total=n_total, subblocks=subblocks,
        lanes=lanes, interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


def attention(q, k, v, causal: bool = True, use_pallas: bool = False):
    """Model-facing attention entry point: Pallas kernel on TPU / by flag,
    reference math elsewhere (the dry-run lowers the XLA path)."""
    if use_pallas:
        return flash_attention(q, k, v, causal=causal)
    return ref.attention(q, k, v, causal=causal)


def ssd_intra_chunk(c, b, u, ld):
    return _ssd.ssd_intra_chunk(c, b, u, ld, interpret=_interpret())
