"""Pallas TPU kernel: edge-block segment-sum (the engine's hot spot).

TPU adaptation of the paper's cache-block processing: a
partition's edge slice is streamed HBM->VMEM in tiles of ``tile_e`` edges;
the scatter-style segment reduction is re-expressed as a one-hot matmul so
it runs on the MXU (systolic array) instead of a serial scatter unit:

    out[c] = sum_e msg[e] * [dst[e] == c]   ==   (1, E_t) @ (E_t, C)

Block shapes: tile_e x C one-hot in f32 (512 x 512 -> 1 MiB VMEM), MXU-
aligned (multiples of 128 on both contraction and output dims). The output
block is revisited by every grid step (accumulator-in-VMEM pattern): zeroed
at step 0, flushed once at the end — HBM traffic is exactly E reads +
C writes, the roofline minimum for this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(msg_ref, dst_ref, out_ref, *, tile_e: int, block_c: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    msg = msg_ref[...].astype(jnp.float32)  # (1, tile_e)
    dst = dst_ref[...]  # (1, tile_e) int32
    # one-hot on the MXU contraction dim: (tile_e, block_c)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_e, block_c), 1)
    onehot = (dst.reshape(tile_e, 1) == cols).astype(jnp.float32)
    # HIGHEST: the one-hot is exact in bf16 and the message splits into
    # three bf16 terms, so the products are exact (DEFAULT would round
    # each message to bf16 on the MXU)
    out_ref[...] += jnp.dot(msg, onehot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_size", "tile_e",
                                             "interpret"))
def edge_block_sum(msg: jnp.ndarray, dst: jnp.ndarray, block_size: int,
                   tile_e: int = 512, interpret: bool = True) -> jnp.ndarray:
    """Segment-sum ``msg`` into ``block_size`` slots addressed by ``dst``.

    msg: (E,) float; dst: (E,) int32 in [0, block_size). E is padded to a
    multiple of tile_e (pad messages are 0 so slot 0 is unaffected).
    """
    e = msg.shape[0]
    pad = (-e) % tile_e
    if pad:
        msg = jnp.pad(msg, (0, pad))
        dst = jnp.pad(dst, (0, pad))
    e_pad = e + pad
    grid = (e_pad // tile_e,)
    out = pl.pallas_call(
        functools.partial(_kernel, tile_e=tile_e, block_c=block_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_e), lambda i: (0, i)),
            pl.BlockSpec((1, tile_e), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_size), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, block_size), jnp.float32),
        interpret=interpret,
    )(msg.reshape(1, e_pad).astype(jnp.float32),
      dst.reshape(1, e_pad).astype(jnp.int32))
    return out.reshape(block_size).astype(msg.dtype)
