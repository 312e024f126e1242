"""Pallas TPU kernel family: the block sweep's segmented combine.

The engines' per-block update is gather -> ``edge_map`` -> segmented
combine -> ``apply`` over the block's run of ``(TILE,)`` edge tile rows.
The dense path expresses it as a ``fori_loop`` of HLO gathers and serial
scatters (``make_tiled_processor`` / ``make_lane_processor``).
``make_block_sweep`` keeps the gather and ``edge_map`` in XLA, because a
gather from the whole vertex array has no Mosaic lowering, and moves the
segmented combine into one ``pallas_call`` per chunk of
:data:`CHUNK_TILES` tile rows:

- ``sum`` — a one-hot matmul on the MXU, ``(L, TILE) @ (TILE, C)`` per
  tile row at ``Precision.HIGHEST``, single-lane (L = 1) and
  lane-batched (the PPR lanes) alike;
- ``min`` / ``max`` — a masked select against the same one-hot and a
  reduce over the tile axis per lane; exact in any order, so SSSP, BFS
  and CC stay bitwise.

Per chunk the kernel reads ``K * L * TILE`` messages, ``K * TILE`` slot
offsets and the ``(L, C)`` aggregate, and writes the aggregate back; the
XLA side reads each row array (src, dst, w, valid, and cov when masked)
as one contiguous ``(K, TILE)`` slice and gathers the source values. The
chunk loop runs the block's own ``ceil(tile_cnt / K)`` steps, so a block
costs its true edge count, not the largest block's.

Sub-block activity (``subblocks = S > 1``) masks whole tile rows whose
``cov`` misses every live sub-range, and a chunk with no live row skips
the kernel — the dense path's identity branch.

Parity with the dense oracle is tested in ``tests/test_block_sweep.py``:
min/max bitwise, sum within :func:`sum_tolerance`. ``interpret=True``
runs the kernels under the Pallas interpreter on CPU;
``tests/test_chip_compile.py`` compiles them for a v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.analysis.contracts import one_executable_per
from repro.obs.scopes import gather

# one sweep callable per (program, run lengths, mode): the engines build
# their processors once per epoch, and repeated builds (prewarm, contract
# probes) must not mint fresh closures or the jit caches downstream refill
_BUILDER_CACHE: dict = {}
_BUILDER_CACHE_CAP = 32


# -- per-tile segmented min/max (the _combine_local counterpart) -------------
def _seg_kernel(msg_ref, dst_ref, out_ref, *, tile_e: int, block_c: int,
                combine: str, identity: float):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, identity)

    msg = msg_ref[...].reshape(tile_e)
    dst = dst_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_e, block_c), 1)
    onehot = dst.reshape(tile_e, 1) == cols
    sel = jnp.where(onehot, msg.reshape(tile_e, 1), identity)
    if combine == "min":
        out_ref[...] = jnp.minimum(out_ref[...],
                                   sel.min(axis=0).reshape(1, block_c))
    else:
        out_ref[...] = jnp.maximum(out_ref[...],
                                   sel.max(axis=0).reshape(1, block_c))


@functools.partial(jax.jit, static_argnames=("block_size", "identity",
                                             "combine", "tile_e",
                                             "interpret"))
def _edge_block_select(msg, dst, block_size: int, identity: float,
                       combine: str, tile_e: int = 512,
                       interpret: bool = True):
    """Segmented min/max of ``msg`` into ``block_size`` slots: the scatter
    ``full(identity).at[dst].min(msg)`` as a masked select + tree reduce
    (exact, so bitwise vs the scatter). Pad messages are ``identity`` so
    slot 0 is unaffected."""
    e = msg.shape[0]
    pad = (-e) % tile_e
    if pad:
        msg = jnp.pad(msg, (0, pad), constant_values=identity)
        dst = jnp.pad(dst, (0, pad))
    e_pad = e + pad
    out = pl.pallas_call(
        functools.partial(_seg_kernel, tile_e=tile_e, block_c=block_size,
                          combine=combine, identity=identity),
        grid=(e_pad // tile_e,),
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


def edge_block_min(msg, dst, block_size: int, identity: float,
                   tile_e: int = 512, interpret: bool = True):
    return _edge_block_select(msg, dst, block_size, identity, "min",
                              tile_e=tile_e, interpret=interpret)


def edge_block_max(msg, dst, block_size: int, identity: float,
                   tile_e: int = 512, interpret: bool = True):
    return _edge_block_select(msg, dst, block_size, identity, "max",
                              tile_e=tile_e, interpret=interpret)


# -- the block sweep -----------------------------------------------------------
# tile rows per combine call: a block's tile run is walked in chunks of this
# many rows (the last chunk masked), so a 3-tile block pays one call and the
# hub block amortizes the per-call cost over 8 tiles
CHUNK_TILES = 8


def sum_tolerance(terms):
    """Relative bound on the gap between the kernel's MXU sum and the
    dense scatter for a slot that sums ``terms`` f32 terms of one sign.

    Under ``Precision.HIGHEST`` the MXU splits each f32 message into three
    bf16 parts whose sum is exact, and the one-hot factor is exact, so
    every product is exact; the two paths add the same values in
    different orders. Any order of an m-term sum is within ``(m - 1) u``
    of the exact sum (u = 2^-24), the MXU's over 3m parts: the two
    results are within ``6 m u`` of each other, relative to the slot.
    """
    return 6.0 * np.maximum(terms, 1) * 2.0 ** -24


def _combine_kernel(agg_ref, msg_ref, dst_ref, out_ref, *, combine: str,
                    identity: float, edge_order: bool):
    """Fold one chunk of a block's tile run into the block's aggregate.

    ``agg_ref``/``out_ref`` are the lane-major ``(L, C)`` aggregate
    before/after the chunk. ``msg_ref`` is ``(K, L, TILE)`` f32 (masked
    slots already carry the combine identity) and ``dst_ref`` the
    ``(K, 1, TILE)`` int32 slot offsets. Tile rows fold into the aggregate
    one at a time, in row order, exactly as the dense path merges its
    per-tile partials. Per row the one-hot ``hit[e, c] = dst[e] == c``
    (``(TILE, C)``) is built on the VPU:

    - ``sum``: ``msg @ hit`` on the MXU, ``(L, TILE) @ (TILE, C)``, at
      ``Precision.HIGHEST``: the one-hot is exact in bf16 and the message
      splits into three bf16 terms, so every product is exact and only
      the order of the f32 additions differs from the scatter
      (:func:`sum_tolerance`). With ``edge_order`` (the interpreter) the row
      is scatter-added into the aggregate in edge order — the dense
      path's arithmetic — so CPU runs stay bitwise equal to it;
    - ``min`` / ``max``: a masked select and a reduce over the tile axis
      per lane — exact in any order, so bitwise equal to the scatter.
    """
    k, nl, tile = msg_ref.shape
    c = out_ref.shape[1]
    slots = lax.broadcasted_iota(jnp.int32, (tile, c), 1)
    lane_of = lax.broadcasted_iota(jnp.int32, (nl, c), 0)
    acc = agg_ref[...]
    for j in range(k):
        msg = msg_ref[j]
        if combine == "sum" and edge_order:
            acc = acc.T.at[dst_ref[j][0]].add(msg.T).T
            continue
        hit = dst_ref[j].reshape(tile, 1) == slots
        if combine == "sum":
            acc = acc + jnp.dot(msg, hit.astype(jnp.float32),
                                precision=lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
            continue
        red = jnp.min if combine == "min" else jnp.max
        mer = jnp.minimum if combine == "min" else jnp.maximum
        for lane in range(nl):
            col = msg[lane:lane + 1, :].reshape(tile, 1)
            row = red(jnp.where(hit, col, identity), axis=0, keepdims=True)
            acc = jnp.where(lane_of == lane, mer(acc, row), acc)
    out_ref[...] = acc


def _combine_call(agg, msg, dst, *, combine: str, identity: float,
                  interpret: bool, edge_order: bool | None = None):
    """``(L, C)`` aggregate + ``(K, L, TILE)`` messages + ``(K, 1, TILE)``
    slots -> the ``(L, C)`` aggregate after the chunk. The operands are a
    few hundred KiB, so they sit whole in VMEM: no grid, no block index
    maps (vmap over the cold slate adds the grid). ``edge_order`` defaults
    to ``interpret``."""
    return pl.pallas_call(
        functools.partial(_combine_kernel, combine=combine,
                          identity=identity,
                          edge_order=(interpret if edge_order is None
                                      else edge_order)),
        out_shape=jax.ShapeDtypeStruct(agg.shape, jnp.float32),
        interpret=interpret,
        name="block_sweep",
    )(agg, msg, dst)


def _cache_put(key, sweep):
    if len(_BUILDER_CACHE) >= _BUILDER_CACHE_CAP:
        _BUILDER_CACHE.pop(next(iter(_BUILDER_CACHE)))
    _BUILDER_CACHE[key] = sweep


@one_executable_per("program", "tile geometry", "subblocks", "lanes")
def make_block_sweep(program, tile_cnt, *, tile_w: int, block_size: int,
                     n_total: int, subblocks: int = 1, lanes: bool = False,
                     interpret: bool = True):
    """Build the block sweep for one program over one tile geometry: the
    blocks' run lengths ``tile_cnt``. Where each run starts is read from
    ``ed.start`` at run time, so one sweep serves the plan's layout and an
    out-of-core pool alike.

    Returns ``sweep(ed, values, row[, sub_act])`` — or
    ``sweep(ed, values, vconst, row[, sub_act])`` with ``lanes=True`` —
    producing the block's post-``apply`` ``(C,)`` / ``(C, L)`` values
    (pre vmask/keep masking, exactly what the dense processors compute
    before their delta tails). Memoized per (program, geometry, mode) so
    repeated processor builds reuse one closure and the downstream jit
    caches stay warm.

    The block's tile run is walked in chunks of ``K`` rows
    (:data:`CHUNK_TILES`, or the row arrays' row count if that is smaller)
    by an XLA loop whose trip count is the block's own
    ``ceil(tile_cnt / K)``. Each step reads every row array as one
    contiguous slice of ``K`` rows starting at the chunk's first row; a
    window that would run past the arrays' end is shifted back to end
    at their last row, and the rows it shifts over (the chunk before, or
    another block's) are masked. It then gathers the chunk's source
    values and applies ``edge_map`` in XLA (a vertex-array gather has no
    Mosaic lowering), masks invalid slots, rows outside the chunk's part
    of the block's run and — with ``subblocks > 1`` — rows whose covered
    sub-ranges are all inactive to the identity, and hands the chunk to
    :func:`_combine_kernel`. ``apply`` runs in XLA on the block's
    aggregate.

    Parity with the dense scatter path: ``min``/``max`` are bitwise equal;
    each ``sum`` slot is within :func:`sum_tolerance` of the scatter's
    (bitwise under the interpreter, which sums in edge order).
    """
    tc = np.asarray(tile_cnt, dtype=np.int32)
    key = (program, tc.tobytes(), int(tile_w), int(block_size),
           int(n_total), int(subblocks), bool(lanes), bool(interpret))
    cached = _BUILDER_CACHE.get(key)
    if cached is not None:
        return cached

    c = block_size
    tile = tile_w
    masked = subblocks > 1
    is_sum = program.combine == "sum"
    ident = float(program.identity)
    tc_d = jnp.asarray(tc)
    combine = functools.partial(_combine_call, combine=program.combine,
                                identity=ident, interpret=interpret)

    def block_agg(ed, values, row, sub_act):
        nl = values.shape[1] if lanes else 1
        t0, cnt = ed.start[row], tc_d[row]
        agg0 = jnp.full((nl, c), 0.0 if is_sum else ident, jnp.float32)
        n_tiles = int(ed.src.shape[0])
        if n_tiles == 0:
            return agg0
        # arrays of fewer rows than a chunk are read in one window of all
        k = min(CHUNK_TILES, n_tiles)

        def chunk(i, agg):
            # rows [s0, s0 + k): a window past the arrays' end is
            # shifted back to end at their last row (computed here, as
            # dynamic_slice would clamp it silently), and the rows it
            # shifts over are masked like rows past the block's run
            s = t0 + i * k
            s0 = jnp.minimum(s, n_tiles - k)
            rid = s0 + jnp.arange(k, dtype=jnp.int32)
            live = (rid >= s) & (rid < t0 + cnt)

            def rows(a):
                with jax.named_scope("sweep_gather_rows"):
                    return lax.dynamic_slice_in_dim(a, s0, k)

            # read before the cond below: vmapped (the cold sweep) it runs
            # both branches on operands broadcast to the slate, and a
            # slice taken inside would broadcast the whole arrays first
            valid, src, w, dstl = map(rows,
                                      (ed.valid, ed.src, ed.w, ed.dstl))
            if masked:
                live = live & (rows(ed.cov) & sub_act).any(axis=1)

            def accumulate(agg):
                ok = valid & live[:, None]
                idx = src.reshape(-1) if lanes else src
                vals = gather("sweep_gather_values", values, idx)
                aux = gather("sweep_gather_aux", ed.aux, idx)
                if lanes:
                    msg = program.edge_map(vals, aux, w.reshape(-1))
                    msg = jnp.where(ok.reshape(-1, 1), msg, ident)
                    msg = msg.reshape(k, tile, nl).transpose(0, 2, 1)
                else:
                    msg = program.edge_map(vals, aux, w)
                    msg = jnp.where(ok, msg, ident)[:, None, :]
                msg = msg.astype(jnp.float32)
                with jax.named_scope("sweep_fold"):
                    return combine(agg, msg, dstl[:, None, :])

            if not masked:
                return accumulate(agg)
            # a chunk whose rows are all masked leaves agg untouched, the
            # dense path's identity branch (a real skip in the hot sweep)
            return lax.cond(live.any(), accumulate, lambda a: a, agg)

        return lax.fori_loop(0, (cnt + k - 1) // k, chunk, agg0)

    if lanes:
        def sweep(ed, values, vconst, row, sub_act=None):
            nl = values.shape[1]
            base = row * c
            old = lax.dynamic_slice(values, (base, 0), (c, nl))
            vc = lax.dynamic_slice(vconst, (base, 0), (c, nl))
            agg = block_agg(ed, values, row, sub_act)
            with jax.named_scope("sweep_apply"):
                return program.apply(old, agg.T, vc, n_total)
    else:
        def sweep(ed, values, row, sub_act=None):
            base = row * c
            old = lax.dynamic_slice(values, (base,), (c,))
            agg = block_agg(ed, values, row, sub_act)
            with jax.named_scope("sweep_apply"):
                return program.apply(old, agg.reshape(c), n_total)

    _cache_put(key, sweep)
    return sweep
