"""Structure-aware iteration driver (paper §3–§4, Algorithms 1–3).

The engine executes one vertex program over a :class:`PartitionPlan`:

  * hot-labelled blocks run **sequentially** within an iteration (the paper's
    asynchronous mode — each block sees the freshest values, Maiter-style
    delta propagation through the hubs);
  * cold-labelled blocks run **batched** from a post-hot snapshot (the
    paper's synchronous mode);
  * the scheduler picks the top-PSD m hot + n cold blocks per iteration
    (Alg. 3) and the repartitioner re-labels blocks on a growing cadence
    (Alg. 2);
  * convergence is SUM_j PSD(j) < T2 (§4), with unvisited blocks carrying an
    UNSEEN sentinel so the whole graph is covered at least once.

Superstep fusion (default execution mode). One iteration =
schedule -> hot dispatch -> cold dispatch -> staleness post -> convergence
test, and the whole sequence is traced into a single jitted
``lax.while_loop`` over the unified tiled storage (``PartitionPlan.unified``
— any block id, no host-side hot/cold routing). The host is consulted only
at **repartition boundaries** (every ``repartition_interval`` iterations,
growing by ``repartition_growth``): one device->host sync per boundary pulls
the PSD vector, flushes the device-resident metric counters, snapshots
history, and re-labels blocks (Alg. 2 stays host-side — it is O(P) numpy
bookkeeping on a cadence, not per-iteration work). Host transfers per run
are therefore O(iterations / repartition_interval), not O(iterations); the
per-iteration ``np.asarray(psd)`` round-trip of the host-driven loop
dominated wall time for exactly the many-small-iteration workloads the
paper targets. The reference host-driven loop is kept as
``run(fused=False)`` (per-iteration history, and the base for the
shard_map distributed engine).

Dynamic edge state (streaming support). The tiled edge arrays, the
per-vertex aux, and the staleness-coupling matrix are **traced arguments**
of every jitted function (:class:`EdgeData`), not closure constants: the
compiled superstep is keyed only on the tile GEOMETRY (tile_start /
tile_cnt / shapes), so the streaming subsystem can mutate edges in place
and re-enter the same executable — a closure-captured array would bake the
edge list into the XLA program and force a recompile per delta batch.
``run(warm=WarmStart(...))`` re-enters convergence from an
already-converged state with only the dirty blocks re-heated (PSD =
UNSEEN, labelled hot); clean blocks start individually converged and
re-arm through the staleness coupling — the universal repartitioner's
cold->hot path (§3.3), applied to graph mutation instead of in-run decay.

Correctness beyond the paper's prose: partial scheduling needs a staleness
signal — when block j's vertices change, downstream blocks (containing j's
out-neighbours) must become schedulable again even if their own PSD already
decayed to 0 (the paper's 'cold partitions can re-heat'). We precompute the
block->affected-blocks adjacency once (host, O(m)) and bump downstream PSDs
after each iteration. Without this, min/max programs can terminate with
stale values; with it, every engine run reaches the same fixpoint as the
synchronous baseline (tested property), fused or host-driven.

Adaptive active-set execution (``EngineConfig.adaptive``, default on). The
paper's "low-activity vertices are computed less often, high-status
partitions more deeply" is made concrete with three mechanisms, applied
identically by the fused and host paths (decision parity is property
tested):

  * **block-local convergence flags** — a per-block ``calm`` counter
    (device state, updated in the staleness post) counts consecutive
    supersteps under the scheduler's pruning floor; ``calm >=
    retire_after`` retires the block from the *active set*. A
    staleness-coupling or aux bump that lifts the block's PSD back over
    the floor resets calm and re-arms it.
  * **priority-scaled inner depth** — hot slot i (PSD rank i) runs
    ``max(1, hot_inner_iters >> i)`` block-local Gauss-Seidel passes:
    deep async iteration is spent on the top of the hot queue, not on
    every scheduled block.
  * **shrinking dispatch width** — the fused chunk is compiled per
    dispatch-width bucket (powers of two down from ``cfg.width``); at
    each repartition boundary the host picks the bucket covering the live
    active set (non-retired blocks), so tail supersteps stop paying
    full-width sweeps over padded slots. Warm streaming restarts seed
    ``calm`` so only the perturbed blocks are active — a small delta
    batch starts narrow (see ``WarmStart.calm`` / ``WarmStart.i2``).

``adaptive=False`` restores the fixed-slate dispatch (constant width,
constant inner depth, floor-prune only) with the exact pre-adaptive
trajectory.

Hierarchical partitions (``EngineConfig.subblocks``, default 1). Every
block is split into S equal contiguous sub-ranges and the activity state
grows a trailing sub-block axis: psd/dmax/calm are (P, S) device arrays.
Scheduling and repartitioning stay BLOCK-granular (block priority = max
over sub-blocks, preserving Eq. 1), but inside a scheduled block the
sweep masks sub-blocks whose PSD sits under the pruning floor: their
vertices keep their values, their PSD/calm rows are left to retire, and
edge tiles covering only masked sub-ranges are skipped (tiles inherit
the CSC dst order, so a tile spans few contiguous sub-ranges). The
staleness coupling is SUB-granular at S > 1 — the count matrix grows a
destination-sub axis, (P, P, S), so an upstream delta re-arms only the
sub-ranges that actually receive edges from the moving block; without
this a single bump would arm whole rows and the P-pigeonhole would just
reappear one level down. The same t2/P floor argument that makes block
pruning safe makes sub-block pruning safe (a frozen sub-block's residual
is below the floor by construction, and any upstream movement re-arms it
through its own coupling column). ``subblocks=1`` keeps psd at (P, 1)
and the coupling at (P, P) — every fold is a bitwise identity and the
sweep bodies trace to the exact flat code path, so the PR-5 trajectory
is reproduced value for value.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis.contracts import one_executable_per
from repro.core import state as state_lib
from repro.core.algorithms import LaneProgram, VertexProgram
from repro.core.graph import Graph, symmetrize
from repro.core.metrics import COUNTER_FIELDS, Metrics, Timer, \
    block_io_bytes
from repro.obs import trace as obs_trace
from repro.core.partition import (EdgeStorage, PartitionPlan, TiledStorage,
                                  build_plan)
from repro.core.repartition import RepartitionState
from repro.core.schedule import (Scheduler, Selection, make_device_select,
                                 pick_width, schedule_predictor,
                                 width_ladder)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    block_size: int = 256
    width: int = 8  # W = m + n (paper: worker count)
    i2: int = 4  # cold-admission cadence (paper I2)
    cold_frac: float = 0.25  # n/W; paper requires m > n
    repartition_interval: int = 4  # paper I1 (grows over time)
    repartition_growth: float = 1.5
    hot_inner_iters: int = 8  # async hot mode: block-local Gauss-Seidel
    hot_ratio: float = 0.1
    sample_frac: float = 0.1
    alpha: float | None = None  # Eq. 1 alpha; None -> suggest_alpha
    t2: float = 1e-6  # paper's default convergence threshold
    max_iterations: int = 100000
    stale_eps: float = 1e-12  # PSD above this marks downstream blocks dirty
    use_pallas: bool = False  # block sweeps combine in the Pallas kernel
    fused: bool = True  # device-resident lax.while_loop superstep
    adaptive: bool = True  # active-set execution (False = fixed-slate)
    subblocks: int = 1  # sub-blocks per block (hierarchical activity tracking)
    retire_after: int = 3  # consecutive sub-floor supersteps before retire
    min_width: int = 2  # narrowest dispatch-width bucket
    # out-of-core block tier: device memory modeled as a fixed budget of
    # resident block slots. None (default) = fully resident — no spill
    # tier is built and the trajectory is bitwise-identical to before the
    # tier existed. With resident_blocks < P the engine evicts cold
    # blocks' edge tile rows to host/disk (repro.ooc.store) and pages the
    # predicted schedule back in before each superstep; budget must be
    # >= width + 2 (the scheduled slate plus the pinned pad blocks).
    resident_blocks: int | None = None
    spill_dir: str | None = None  # npz segment dir; None = host cache only
    tile_slack: float = 0.0  # spare tile capacity per block (streaming)
    spare_tiles: int = 0  # flat extra tiles per block (streaming)
    keep_dead_blocks: bool = False  # dead vertices get block slots (streaming)
    seed: int = 0


@dataclasses.dataclass
class RunResult:
    values: np.ndarray  # indexed by ORIGINAL vertex id
    metrics: Metrics
    history: list  # per-iteration dicts (for convergence curves)
    # per-SUPERSTEP trace timeline (``run(trace=True)``; None otherwise):
    # dicts with TIMELINE_INT_COLS / TIMELINE_FLOAT_COLS plus
    # superstep/width. The integer counter columns sum exactly to the
    # aggregate Metrics counters (property-tested) — the timeline is the
    # time-resolved decomposition of the same accounting, not a parallel
    # estimate.
    timeline: list | None = None


@dataclasses.dataclass(frozen=True)
class WarmStart:
    """Re-enter convergence from a previous fixpoint (streaming re-heat).

    ``values`` is in PERMUTED order, padded to the engine's value length;
    ``psd`` carries UNSEEN for dirty blocks / 0 for clean ones (see
    ``state.warm_psd``); ``is_hot`` is the dirty mask — warm runs always
    repartition in universal mode, since an arbitrary dirty set is not a
    prefix barrier.

    Adaptive extras (both ignored when ``config.adaptive`` is off):
    ``calm`` seeds the block-local convergence counters (see
    ``state.warm_calm``) so a small perturbation starts in a narrow
    dispatch bucket; ``i2`` overrides the cold-admission cadence for this
    run (``schedule.adaptive_i2`` scales it with the batch size).
    """

    values: np.ndarray
    psd: np.ndarray
    is_hot: np.ndarray
    calm: np.ndarray | None = None
    i2: int | None = None


class EdgeData(NamedTuple):
    """Device-resident dynamic state of the tiled layout — everything a
    delta batch can change without changing tile geometry. Passed as a
    traced argument to every jitted engine function (NOT closed over), so
    in-place streaming mutation re-uses the compiled executable."""

    src: jax.Array  # (n_tiles, TILE) int32
    dstl: jax.Array  # (n_tiles, TILE) int32
    w: jax.Array  # (n_tiles, TILE) float32
    valid: jax.Array  # (n_tiles, TILE) bool
    cov: jax.Array  # (n_tiles, S) bool — sub-block dst coverage per tile
    aux: jax.Array  # (n,) float32 per-vertex constant (e.g. out-degree)


def tile_coverage(dst_local, valid, subblocks: int,
                  block_size: int | None = None) -> np.ndarray:
    """(n_tiles, S) bool: which of a block's S sub-ranges each tile's VALID
    destinations land in. Coverage is a function of tile structure only
    (dstl/valid), not of values, so it is computed host-side once per
    epoch — and per touched row on streaming commits — instead of by a
    scatter inside every traced tile visit. At S = 1 it degenerates to
    'tile has any valid slot' (unused by the flat trace)."""
    d = np.asarray(dst_local)
    v = np.asarray(valid, dtype=bool)
    if subblocks <= 1:
        return v.any(axis=1, keepdims=True)
    sub = block_size // subblocks
    cov = np.zeros((d.shape[0], subblocks), dtype=bool)
    ii, jj = np.nonzero(v)
    cov[ii, d[ii, jj] // sub] = True
    return cov


def edge_data(store: TiledStorage, aux, subblocks: int = 1,
              block_size: int | None = None) -> EdgeData:
    return EdgeData(src=jnp.asarray(store.src),
                    dstl=jnp.asarray(store.dst_local),
                    w=jnp.asarray(store.w), valid=jnp.asarray(store.valid),
                    cov=jnp.asarray(tile_coverage(
                        store.dst_local, store.valid, subblocks,
                        block_size)),
                    aux=jnp.asarray(aux))


# -- adaptive-schedule decision helpers --------------------------------------
# Module-level so the multi-lane query engine (repro.serve.lanes) applies the
# SAME decisions as the single-program engine — the single-lane service path
# reproduces the engine trajectory exactly because these are shared, not
# reimplemented.
def inner_depths(cfg: EngineConfig, width: int) -> np.ndarray:
    """Per-slot Gauss-Seidel depth for the hot sweep, by PSD rank: slot 0
    (the hottest block) runs the full ``hot_inner_iters``, halving per rank
    down to 1 — deep async iteration is spent where the delta mass is, not
    on every scheduled block. Dense mode keeps the constant depth. Depth
    depends only on the absolute slot index, so host and fused ranks (and
    every width bucket) agree."""
    t = max(cfg.hot_inner_iters, 1)
    if not cfg.adaptive:
        return np.full(width, t, dtype=np.int32)
    return np.maximum(1, t >> np.minimum(np.arange(width), 30)) \
        .astype(np.int32)


def dispatch_width(cfg: EngineConfig, ladder: list[int], active: int,
                   psd_host: np.ndarray) -> int:
    """Dispatch bucket for the live active-set size (non-retired blocks),
    chosen by the host at repartition boundaries. While an UNSEEN re-heat
    wave is still in flight the bucket gets 2x headroom: unprocessed
    blocks are about to re-arm their neighbourhood through the staleness
    coupling, and a bucket that exactly covers today's active set
    throttles that propagation (measured: more supersteps at barely-lower
    per-superstep cost). Once the wave has passed, the active count is
    trustworthy and the tail narrows for real."""
    if not cfg.adaptive:
        return cfg.width
    if bool((psd_host >= state_lib.UNSEEN).any()):
        active *= 2
    return pick_width(ladder, active)


def acct_table(plan: PartitionPlan, edge_counts: np.ndarray) -> np.ndarray:
    """(P, len(COUNTER_FIELDS)) host-side accounting row per schedule of a
    block: [vertices updated, edges processed, 1 load, bytes loaded]. The
    device only counts schedules per block (small exact int32s); the host
    multiplies through this table at flush time, so metric totals stay
    exact at any scale. ``edge_counts`` is the CALLER'S live per-block
    count (warm streaming runs and pinned query epochs bill mutated blocks
    at their size when the run started, not the plan snapshot)."""
    acct = np.zeros((plan.num_blocks, 4), dtype=np.int64)
    for b in range(plan.num_blocks):
        lo, hi = plan.block_range(b)
        e = int(edge_counts[b])
        acct[b] = (hi - lo, e, 1, block_io_bytes(e, plan.block_size))
    return acct


# -- per-superstep trace timeline --------------------------------------------
# Column layout of the traced chunk's history buffers (RunResult.timeline
# keys): the four COUNTER_FIELDS deltas, then hot dispatches / retired
# blocks / UNSEEN blocks (int32 — the per-superstep deltas are chunk-local
# and small; the aggregate totals still flow through the int64 host acct
# path), and the block-folded finite PSD sum/max (float32).
TIMELINE_INT_COLS = COUNTER_FIELDS + ("hot_loads", "retired", "unseen")
TIMELINE_FLOAT_COLS = ("psd_sum", "psd_max")


def _hist_cap(span: int) -> int:
    """Power-of-two history-buffer capacity covering a traced chunk span.
    Chunk spans follow the repartition cadence, which GROWS 1.5x per
    boundary — keying the traced executable on the raw span would compile
    one variant per boundary. Pow2 bucketing (floor 16) keeps the
    executable count logarithmic in the final interval while the chunk
    boundaries themselves stay exactly where the untraced run puts them
    (capacity never changes the trajectory, only the buffer shape)."""
    return max(16, 1 << max(span - 1, 1).bit_length())


def _combine_local(program: VertexProgram, msg, dst_local, block_size,
                   use_pallas: bool):
    if program.combine == "sum":
        if use_pallas:
            from repro.kernels import ops as kops
            return kops.edge_block_sum(msg, dst_local, block_size)
        return jnp.zeros(block_size, jnp.float32).at[dst_local].add(msg)
    if program.combine == "min":
        if use_pallas:
            from repro.kernels import ops as kops
            return kops.edge_block_min(msg, dst_local, block_size,
                                       float(program.identity))
        return jnp.full(block_size, program.identity).at[dst_local].min(msg)
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.edge_block_max(msg, dst_local, block_size,
                                   float(program.identity))
    return jnp.full(block_size, program.identity).at[dst_local].max(msg)


def make_block_processor(program: VertexProgram, store: EdgeStorage, aux,
                         block_size: int, n_live: int, n_total: int,
                         use_pallas: bool):
    """Returns (process_one, gids): the pull-mode update for one block row of
    one storage group. Shared by the local and shard_map engines."""
    src = jnp.asarray(store.src)
    dstl = jnp.asarray(store.dst_local)
    ew = jnp.asarray(store.w)
    evalid = jnp.asarray(store.valid)
    gids = jnp.asarray(store.block_ids, dtype=jnp.int32)
    c = block_size

    def process_one(values, row):
        e_src = src[row]
        msg = program.edge_map(values[e_src], aux[e_src], ew[row])
        msg = jnp.where(evalid[row], msg, program.identity)
        agg = _combine_local(program, msg, dstl[row], c, use_pallas)
        base = gids[row] * c
        old = lax.dynamic_slice(values, (base,), (c,))
        new = program.apply(old, agg, n_total)
        vmask = (base + jnp.arange(c)) < n_live
        new = jnp.where(vmask, new, old)
        delta = jnp.where(vmask, program.sd_delta(old, new), 0.0)
        cnt = jnp.maximum(vmask.sum(), 1)
        # (mean, max) per-block deltas: mean is the paper's PSD; max feeds the
        # sound staleness bound (mean-based coupling under-estimates when the
        # delta mass is concentrated on a hub).
        return base, new, delta.sum() / cnt, delta.max()

    def process_iterated(values, row, t_inner):
        """Asynchronous hot mode, TPU-native: the block's edge slice is
        VMEM-resident, so re-applying the block update t_inner times costs
        ONE partition load but advances intra-block dependency chains
        t_inner hops (the paper's per-vertex async propagation, at block
        granularity). Writes only within the block's own range."""
        base = gids[row] * c
        old = lax.dynamic_slice(values, (base,), (c,))

        def inner(_, vals):
            _, new, _, _ = process_one(vals, row)
            return lax.dynamic_update_slice(vals, new, (base,))

        vals2 = lax.fori_loop(0, t_inner, inner, values)
        newb = lax.dynamic_slice(vals2, (base,), (c,))
        vmask = (base + jnp.arange(c)) < n_live
        delta = jnp.where(vmask, program.sd_delta(old, newb), 0.0)
        cnt = jnp.maximum(vmask.sum(), 1)
        return base, newb, delta.sum() / cnt, delta.max()

    return process_one, process_iterated, gids


def make_tiled_processor(program: VertexProgram, store: TiledStorage,
                         block_size: int, n_live: int, n_total: int,
                         use_pallas: bool, subblocks: int = 1):
    """Block processor over the unified tiled layout: ``row`` is the GLOBAL
    block id and the per-block work is a fori over that block's tile rows,
    so compute scales with the block's true edge count rather than a shared
    padded capacity. Only the tile GEOMETRY (tile_start/tile_cnt) is closed
    over; the edge arrays and aux arrive per call as an :class:`EdgeData`,
    so streaming mutations never invalidate the trace.

    With ``subblocks = S > 1`` the processors take a ``sub_act`` (S,) bool
    mask (which of the block's S equal sub-ranges are live) and return
    PER-SUB-BLOCK (S,) mean/max deltas. Masked sub-ranges keep their old
    values and report no delta, and a tile whose valid destinations all
    land in masked sub-ranges is skipped entirely (tiles are CSC-ordered,
    so each covers a narrow dst range — this is where a one-hot-sub block
    stops paying its whole edge slice). ``sub_act=None`` (the S = 1 path)
    traces to EXACTLY the flat per-block code — bitwise parity with the
    non-hierarchical engine is by construction, not by rounding luck."""
    tile_start = jnp.asarray(store.tile_start, dtype=jnp.int32)
    tile_cnt = jnp.asarray(store.tile_cnt, dtype=jnp.int32)
    gids = jnp.arange(store.num_blocks, dtype=jnp.int32)
    c = block_size
    sub = c // max(subblocks, 1)

    if program.combine == "sum":
        agg0 = jnp.zeros(c, jnp.float32)
        merge = jnp.add
    elif program.combine == "min":
        agg0 = jnp.full(c, program.identity)
        merge = jnp.minimum
    else:
        agg0 = jnp.full(c, program.identity)
        merge = jnp.maximum

    # under use_pallas the whole per-block update (gather → edge_map →
    # combine → apply, sub_act-masked in-kernel) is ONE fused pallas_call;
    # the dense fori below stays the bitwise reference and its trace is
    # untouched (the golden jaxprs pin it)
    fused = None
    if use_pallas:
        from repro.kernels import ops as kops
        fused = kops.make_block_sweep(program, store, c, n_total,
                                      subblocks=subblocks)

    def process_one(ed: EdgeData, values, row, sub_act=None):
        if fused is not None:
            new = fused(ed, values, row, sub_act)
            base = row * c
            old = lax.dynamic_slice(values, (base,), (c,))
        else:
            t0 = tile_start[row]

            def tile_compute(t, agg):
                r = t0 + t
                e_src = ed.src[r]
                msg = program.edge_map(values[e_src], ed.aux[e_src],
                                       ed.w[r])
                msg = jnp.where(ed.valid[r], msg, program.identity)
                return merge(agg,
                             _combine_local(program, msg, ed.dstl[r], c,
                                            use_pallas))

            if sub_act is None:
                tile_body = tile_compute
            else:
                def tile_body(t, agg):
                    r = t0 + t
                    # skip the gather/combine when every sub-range this
                    # tile's valid destinations cover (ed.cov —
                    # precomputed per epoch, maintained per touched row by
                    # streaming commits) is masked: identity branch — the
                    # vmapped cold sweep lowers this to a select, the
                    # sequential hot sweep skips for real
                    return lax.cond((ed.cov[r] & sub_act).any(),
                                    lambda a: tile_compute(t, a),
                                    lambda a: a, agg)

            agg = lax.fori_loop(0, tile_cnt[row], tile_body, agg0)
            base = row * c
            old = lax.dynamic_slice(values, (base,), (c,))
            new = program.apply(old, agg, n_total)
        vmask = (base + jnp.arange(c)) < n_live
        if sub_act is None:
            new = jnp.where(vmask, new, old)
            delta = jnp.where(vmask, program.sd_delta(old, new), 0.0)
            cnt = jnp.maximum(vmask.sum(), 1)
            return base, new, delta.sum() / cnt, delta.max()
        keep = vmask & jnp.repeat(sub_act, sub)
        new = jnp.where(keep, new, old)
        delta = jnp.where(keep, program.sd_delta(old, new), 0.0)
        dsub = delta.reshape(subblocks, sub)
        cnt = jnp.maximum(vmask.reshape(subblocks, sub).sum(axis=1), 1)
        return base, new, dsub.sum(axis=1) / cnt, dsub.max(axis=1)

    def process_iterated(ed: EdgeData, values, row, t_inner, sub_act=None):
        """Asynchronous hot mode (see make_block_processor): t_inner
        block-local Gauss-Seidel passes per partition load."""
        base = row * c
        old = lax.dynamic_slice(values, (base,), (c,))

        def inner(_, vals):
            _, new, _, _ = process_one(ed, vals, row, sub_act)
            return lax.dynamic_update_slice(vals, new, (base,))

        vals2 = lax.fori_loop(0, t_inner, inner, values)
        newb = lax.dynamic_slice(vals2, (base,), (c,))
        vmask = (base + jnp.arange(c)) < n_live
        if sub_act is None:
            delta = jnp.where(vmask, program.sd_delta(old, newb), 0.0)
            cnt = jnp.maximum(vmask.sum(), 1)
            return base, newb, delta.sum() / cnt, delta.max()
        keep = vmask & jnp.repeat(sub_act, sub)
        delta = jnp.where(keep, program.sd_delta(old, newb), 0.0)
        dsub = delta.reshape(subblocks, sub)
        cnt = jnp.maximum(vmask.reshape(subblocks, sub).sum(axis=1), 1)
        return base, newb, dsub.sum(axis=1) / cnt, dsub.max(axis=1)

    return process_one, process_iterated, gids


def make_lane_processor(program: LaneProgram, store: TiledStorage,
                        block_size: int, n_live: int, n_total: int,
                        subblocks: int = 1, use_pallas: bool = False):
    """Lane-axis generalization of :func:`make_tiled_processor`: vertex
    values are ``(values_len, L)`` and one pass over a block's edge tiles
    advances every lane — the edge slice (src ids, weights, validity) is
    read ONCE per tile and the gather/combine/apply math is vectorized
    over the lane axis, so L queries share each partition load. The lane
    count is taken from the runtime shapes (jit specializes per L; the
    query service pads batches to a fixed L so one executable serves the
    steady state). ``vconst`` is the per-vertex-per-lane constant matrix
    (personalized restart vectors); families that ignore it get zeros.
    Per-block results are per-lane vectors: (base, new (C, L), mean-delta
    (L,), max-delta (L,)) — the (P, L) PSD state the lane superstep
    schedules on. With ``subblocks = S > 1`` the processors additionally
    take a shared (S,) ``sub_act`` mask (lane-folded: a sub-range is live
    if ANY running lane prices it over the floor) and the deltas grow a
    leading sub-block axis — (S, L) — mirroring
    :func:`make_tiled_processor`; ``sub_act=None`` is the exact flat
    path."""
    tile_start = jnp.asarray(store.tile_start, dtype=jnp.int32)
    tile_cnt = jnp.asarray(store.tile_cnt, dtype=jnp.int32)
    gids = jnp.arange(store.num_blocks, dtype=jnp.int32)
    c = block_size
    sub = c // max(subblocks, 1)

    if program.combine == "sum":
        def combine(msg, dstl, nl):
            return jnp.zeros((c, nl), jnp.float32).at[dstl].add(msg)
        merge = jnp.add
    elif program.combine == "min":
        def combine(msg, dstl, nl):
            return jnp.full((c, nl), program.identity).at[dstl].min(msg)
        merge = jnp.minimum
    else:
        def combine(msg, dstl, nl):
            return jnp.full((c, nl), program.identity).at[dstl].max(msg)
        merge = jnp.maximum

    # the lane-batched fused kernel: one pallas_call per block sweeps all
    # L lanes with the (C, L) accumulator VMEM-resident and the sum
    # combine as a (C, E_t) @ (E_t, L) MXU matmul — this is the fix for
    # the scatter-bound PPR lane combine below
    fused = None
    if use_pallas:
        from repro.kernels import ops as kops
        fused = kops.make_block_sweep(program, store, c, n_total,
                                      subblocks=subblocks, lanes=True)

    def process_one(ed: EdgeData, values, vconst, row, sub_act=None):
        nl = values.shape[1]
        if fused is not None:
            new = fused(ed, values, vconst, row, sub_act)
            base = row * c
            old = lax.dynamic_slice(values, (base, 0), (c, nl))
            vmask = (base + jnp.arange(c)) < n_live
            if sub_act is None:
                new = jnp.where(vmask[:, None], new, old)
                delta = jnp.where(vmask[:, None],
                                  program.sd_delta(old, new), 0.0)
                cnt = jnp.maximum(vmask.sum(), 1)
                return (base, new, delta.sum(axis=0) / cnt,
                        delta.max(axis=0))
            keep = vmask & jnp.repeat(sub_act, sub)
            new = jnp.where(keep[:, None], new, old)
            delta = jnp.where(keep[:, None], program.sd_delta(old, new),
                              0.0)
            dsub = delta.reshape(subblocks, sub, nl)
            cnt = jnp.maximum(vmask.reshape(subblocks, sub).sum(axis=1), 1)
            return (base, new, dsub.sum(axis=1) / cnt[:, None],
                    dsub.max(axis=1))
        t0 = tile_start[row]
        if program.combine == "sum":
            agg0 = jnp.zeros((c, nl), jnp.float32)
        else:
            agg0 = jnp.full((c, nl), program.identity)

        def tile_compute(t, agg):
            r = t0 + t
            e_src = ed.src[r]
            msg = program.edge_map(values[e_src], ed.aux[e_src], ed.w[r])
            msg = jnp.where(ed.valid[r][:, None], msg, program.identity)
            return merge(agg, combine(msg, ed.dstl[r], nl))

        if sub_act is None:
            tile_body = tile_compute
        else:
            def tile_body(t, agg):
                r = t0 + t
                return lax.cond((ed.cov[r] & sub_act).any(),
                                lambda a: tile_compute(t, a),
                                lambda a: a, agg)

        agg = lax.fori_loop(0, tile_cnt[row], tile_body, agg0)
        base = row * c
        old = lax.dynamic_slice(values, (base, 0), (c, nl))
        vc = lax.dynamic_slice(vconst, (base, 0), (c, nl))
        new = program.apply(old, agg, vc, n_total)
        vmask = (base + jnp.arange(c)) < n_live
        if sub_act is None:
            new = jnp.where(vmask[:, None], new, old)
            delta = jnp.where(vmask[:, None], program.sd_delta(old, new),
                              0.0)
            cnt = jnp.maximum(vmask.sum(), 1)
            return base, new, delta.sum(axis=0) / cnt, delta.max(axis=0)
        keep = vmask & jnp.repeat(sub_act, sub)
        new = jnp.where(keep[:, None], new, old)
        delta = jnp.where(keep[:, None], program.sd_delta(old, new), 0.0)
        dsub = delta.reshape(subblocks, sub, nl)
        cnt = jnp.maximum(vmask.reshape(subblocks, sub).sum(axis=1), 1)
        return (base, new, dsub.sum(axis=1) / cnt[:, None],
                dsub.max(axis=1))

    def process_iterated(ed: EdgeData, values, vconst, row, t_inner,
                         sub_act=None):
        """Asynchronous hot mode (see make_block_processor): t_inner
        block-local Gauss-Seidel passes per partition load, all lanes."""
        nl = values.shape[1]
        base = row * c
        old = lax.dynamic_slice(values, (base, 0), (c, nl))

        def inner(_, vals):
            _, new, _, _ = process_one(ed, vals, vconst, row, sub_act)
            return lax.dynamic_update_slice(vals, new, (base, 0))

        vals2 = lax.fori_loop(0, t_inner, inner, values)
        newb = lax.dynamic_slice(vals2, (base, 0), (c, nl))
        vmask = (base + jnp.arange(c)) < n_live
        if sub_act is None:
            delta = jnp.where(vmask[:, None], program.sd_delta(old, newb),
                              0.0)
            cnt = jnp.maximum(vmask.sum(), 1)
            return base, newb, delta.sum(axis=0) / cnt, delta.max(axis=0)
        keep = vmask & jnp.repeat(sub_act, sub)
        delta = jnp.where(keep[:, None], program.sd_delta(old, newb), 0.0)
        dsub = delta.reshape(subblocks, sub, nl)
        cnt = jnp.maximum(vmask.reshape(subblocks, sub).sum(axis=1), 1)
        return (base, newb, dsub.sum(axis=1) / cnt[:, None],
                dsub.max(axis=1))

    return process_one, process_iterated, gids


class StructureAwareEngine:
    """Paper pipeline: build plan -> iterate (schedule, process, repartition)."""

    def __init__(self, graph: Graph, program: VertexProgram,
                 config: EngineConfig = EngineConfig()):
        self.program = program
        self.config = config
        g = symmetrize(graph) if program.needs_symmetric else graph
        self.plan = build_plan(
            g, block_size=config.block_size, alpha=config.alpha,
            sample_frac=config.sample_frac, hot_ratio=config.hot_ratio,
            seed=config.seed, tile_slack=config.tile_slack,
            spare_tiles=config.spare_tiles,
            keep_dead=config.keep_dead_blocks,
            subblocks=config.subblocks)
        vals0, aux0 = program.init(g)  # original ids ...
        self.values0 = vals0[self.plan.order]  # ... permuted to plan order
        self.aux = jnp.asarray(aux0[self.plan.order])
        self._init_dead()
        # Pad the value vector so every block's (base, block_size) slice is
        # in-bounds: lax.dynamic_slice CLAMPS out-of-range starts, which would
        # silently corrupt the last block's writes.
        p = self.plan
        self._values_len = max(p.num_blocks * p.block_size, p.graph.n)
        self.values0 = self.pad_values(self.values0)
        # Per-block true edge counts: a MUTABLE copy (streaming updates it);
        # feeds the exact metric accounting and the bytes cost model.
        self.edge_counts = np.array(p.unified.edges, dtype=np.int64)
        self._ed = edge_data(p.unified, self.aux, self.config.subblocks,
                             p.block_size)
        self._block_affects = self._build_block_affects()
        self._coupling = self._build_coupling_matrix()
        self._coupling_dev = jnp.asarray(self._coupling)
        self._post = jax.jit(self._make_post())
        self._fns: dict = {}
        # descending dispatch-width buckets; the host picks per boundary
        self._ladder = (width_ladder(config.width, config.min_width)
                        if config.adaptive else [config.width])
        # pad block for dispatch slots beyond the take counts: the sweeps
        # still compute padded slots, so it is the cheapest block's id —
        # and under an out-of-core budget it is pinned resident
        tile_cnt = p.unified.tile_cnt
        self.pad_id = int(np.argmin(tile_cnt)) if tile_cnt.size else 0
        # activity state of the last completed run (the epoch-persistence
        # record; see repro.ooc.snapshot)
        self.last_psd: np.ndarray | None = None
        self.last_calm: np.ndarray | None = None
        self.spill = None
        if (config.resident_blocks is not None
                and config.resident_blocks < p.num_blocks):
            from repro.ooc.store import SpillStore  # avoid import cycle
            self.spill = SpillStore(self, config.resident_blocks,
                                    directory=config.spill_dir)

    # -- one-time host preprocessing ---------------------------------------
    def _init_dead(self):
        """Dead partition: processed once at start (§3.2) — apply() with the
        identity aggregate, after which these vertices are final."""
        p = self.plan
        if p.n_dead == 0:
            return
        dead = slice(p.n_live, p.graph.n)
        old = jnp.asarray(self.values0[dead])
        agg = jnp.full(p.n_dead, 0.0 if self.program.combine == "sum"
                       else self.program.identity, jnp.float32)
        self.values0 = np.array(self.values0)
        self.values0[dead] = np.asarray(
            self.program.apply(old, agg, p.graph.n))

    def _build_block_affects(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """block j -> (target blocks, coupling weights).

        Soundness: with v = MAX per-vertex delta in block j, the delta mass
        entering block b is <= v * sum_{u in j} min(edges(u->b)/outdeg(u), 1)
        <= v * min(W_jb, C_j), so b's mean-PSD can move by at most
        decay * v * min(W_jb, C) / C. For min/max programs improvements
        propagate undiminished and unsplit, so the coupling is 1 on every
        reachable target (correctness over tightness)."""
        p = self.plan
        g = p.graph
        c = p.block_size
        out: list[tuple[np.ndarray, np.ndarray]] = []
        for b in range(p.num_blocks):
            lo, hi = p.block_range(b)
            dsts = g.out_dst[g.out_indptr[lo]:g.out_indptr[hi]]
            blocks, counts = np.unique(dsts // c, return_counts=True)
            keep = blocks < p.num_blocks
            blocks, counts = blocks[keep], counts[keep]
            out.append((blocks.astype(np.int64), counts.astype(np.int64)))
        return out

    def _build_coupling_matrix(self) -> np.ndarray:
        """Dense (P, P) staleness-coupling matrix (decay folded in): the
        device-side bump is the max-product matvec
        ``bump_b = max_j dmax_j * K[j, b]``. With sub-blocks the counts
        (and hence K) grow a destination-sub axis — (P, P, S) — so the
        bump lands per sub-range: ``bump_{b,s} = max_j dmax_j * K[j, b,
        s]``. The underlying edge-count matrix is kept as
        ``self.coupling_counts`` — the truth the streaming subsystem
        maintains incrementally."""
        p = self.plan
        s = self.config.subblocks
        if s == 1:
            w = np.zeros((p.num_blocks, p.num_blocks), dtype=np.int64)
            for j, (tgt, counts) in enumerate(self._block_affects):
                w[j, tgt] = counts
        else:
            g, c, ks = p.graph, p.block_size, p.sub_size
            w = np.zeros((p.num_blocks, p.num_blocks, s), dtype=np.int64)
            for j in range(p.num_blocks):
                lo, hi = p.block_range(j)
                dsts = g.out_dst[g.out_indptr[lo]:g.out_indptr[hi]]
                d = dsts[dsts // c < p.num_blocks]  # drop the dead tail
                np.add.at(w[j], (d // c, (d % c) // ks), 1)
        self.coupling_counts = w
        return coupling_from_counts(w, self.program, p.block_size)

    def _make_post(self):
        eps = self.config.stale_eps
        floor = self._psd_floor()

        def post(coupling, psd, dmax, calm):
            """Consume dmax: re-arm downstream blocks, then reset. Also
            advances the block-local convergence counters: a superstep
            spent under the pruning floor increments ``calm``; any PSD at
            or over the floor (own activity OR an incoming bump) resets it
            — the retire/re-arm hysteresis of the adaptive active set.

            Polymorphic over the sub-block axis: with (P, S) state the
            outgoing signal stays block-granular (the block's max
            sub-delta — deltas anywhere in the source block can reach any
            of its out-edges) but the incoming bump is SUB-resolved
            through the (P, P, S) coupling: only the target sub-ranges
            that receive edges from the moving block re-arm. Calm then
            advances per sub-block. 1-D state traces to the exact flat
            path (the retire/re-arm unit test drives it directly)."""
            d = jnp.where(dmax > eps, dmax, 0.0)
            if psd.ndim == 2:
                dblk = d.max(axis=1)
                if coupling.ndim == 3:  # (P, P, S): sub-resolved bump
                    bump = jnp.max(dblk[:, None, None] * coupling, axis=0)
                else:  # S = 1 keeps the flat (P, P) coupling: exact old path
                    bump = jnp.max(dblk[:, None] * coupling, axis=0)[:, None]
                psd = jnp.maximum(psd, jnp.minimum(bump, 1e29))
            else:
                bump = jnp.max(d[:, None] * coupling, axis=0)
                psd = jnp.maximum(psd, jnp.minimum(bump, 1e29))
            calm = jnp.where(psd < floor, calm + 1, 0).astype(jnp.int32)
            return psd, jnp.zeros_like(dmax), calm
        return post

    def _psd_floor(self) -> float:
        """Per-block pruning floor (t2/P): skipping blocks below it is safe
        — if every block were below it, SUM(psd) < t2 and we are converged.
        The ONE definition shared by the scheduler's live test and the
        calm/retire counters, so they can never disagree."""
        return self.config.t2 / max(self.plan.num_blocks, 1)

    def _inner_depths(self, width: int) -> np.ndarray:
        return inner_depths(self.config, width)

    def _pick_width(self, active: int, psd_host: np.ndarray) -> int:
        return dispatch_width(self.config, self._ladder, active, psd_host)

    def _active_count(self, calm_host: np.ndarray) -> int:
        """Blocks still in the active set: a block is live while ANY of its
        sub-blocks is (calm is (P, S); 1-D input keeps the flat meaning)."""
        if not self.config.adaptive:
            return self.plan.num_blocks
        live = np.asarray(calm_host) < self.config.retire_after
        if live.ndim == 2:
            live = live.any(axis=-1)
        return int(live.sum())

    def _subblocks_retired(self, calm_host: np.ndarray) -> int:
        """Sub-blocks retired at end of run (0 on the dense path, where
        calm never gates anything — mirrors blocks_retired)."""
        if not self.config.adaptive:
            return 0
        return int((np.asarray(calm_host) >=
                    self.config.retire_after).sum())

    def _acct_table(self) -> np.ndarray:
        return acct_table(self.plan, self.edge_counts)

    # -- streaming hooks -----------------------------------------------------
    def edge_snapshot(self) -> EdgeData:
        """Device-side DEEP COPY of the current dynamic edge state. The
        incremental commit path mutates the resident buffers through
        DONATED scatters, which invalidates any outstanding reference to
        them — a caller that must keep reading this epoch across future
        commits (the query service's snapshot isolation) copies first.
        O(m) device bytes, zero host traffic — except under an
        out-of-core budget, where the snapshot's spilled holes are
        materialized from the spill tier's truth (residency unchanged):
        a pinned epoch must survive the eviction of its blocks."""
        ed = EdgeData(*(jnp.array(a) for a in self._ed))
        if self.spill is not None:
            ed = self.spill.materialize(ed)
        return ed

    @property
    def edge_state(self) -> EdgeData:
        """The LIVE device-resident dynamic edge state. Borrow only where
        no incremental commit can intervene; across commits, take
        :meth:`edge_snapshot` instead (the commits donate these buffers)."""
        return self._ed

    def set_edge_data(self, *, src=None, dst_local=None, w=None, valid=None,
                      aux=None) -> None:
        """Swap (parts of) the device-resident dynamic edge state with a
        FULL re-upload — the whole-array fallback of the row-granular
        ``update_edge_rows`` / ``update_aux`` path the streaming engine
        uses (kept as external API for callers that rebuilt their arrays
        wholesale). Shapes must match the compiled epoch — a geometry
        change needs a new engine, not new arrays."""
        ed = self._ed
        new_dstl = (jnp.asarray(dst_local, jnp.int32)
                    if dst_local is not None else ed.dstl)
        new_valid = (jnp.asarray(valid, bool) if valid is not None
                     else ed.valid)
        cov = ed.cov
        if dst_local is not None or valid is not None:
            cov = jnp.asarray(tile_coverage(
                np.asarray(new_dstl), np.asarray(new_valid),
                self.config.subblocks, self.plan.block_size))
        new = EdgeData(
            src=jnp.asarray(src, jnp.int32) if src is not None else ed.src,
            dstl=new_dstl,
            w=jnp.asarray(w, jnp.float32) if w is not None else ed.w,
            valid=new_valid, cov=cov,
            aux=jnp.asarray(aux, jnp.float32) if aux is not None else ed.aux)
        for name in EdgeData._fields:
            if getattr(new, name).shape != getattr(ed, name).shape:
                raise ValueError(
                    f"EdgeData.{name} shape {getattr(new, name).shape} != "
                    f"compiled epoch shape {getattr(ed, name).shape}")
        self._ed = new
        if aux is not None:
            self.aux = new.aux

    def set_coupling(self, coupling: np.ndarray) -> None:
        """Full (P, P) coupling swap — whole-matrix fallback of
        ``update_coupling_rows``."""
        if coupling.shape != self._coupling.shape:
            raise ValueError("coupling shape changed within an epoch")
        self._coupling = np.asarray(coupling, dtype=np.float32)
        self._coupling_dev = jnp.asarray(self._coupling)

    # -- incremental streaming commits (sub-O(m) host->device path) ----------
    # The scatter functions are jitted with DONATED destination buffers, so
    # the device-resident state is updated in place and the host->device
    # payload is only the touched rows/entries — never the full arrays the
    # set_edge_data / set_coupling path re-uploads. Each scatter runs in
    # FIXED-SIZE chunks (one compiled variant per scatter type — per-batch
    # index counts never trigger a recompile), with the final partial
    # chunk padded by duplicates of entry 0 (identical payload, so the
    # duplicate scatter is order-independent). The returned byte counts
    # bill the chunked transfer that actually crosses to the device,
    # indices included.
    _ROW_CHUNK = 16  # tile rows per scatter call (~100KB payload)
    _AUX_CHUNK = 256  # aux entries per scatter call
    _COUPLING_CHUNK = 16  # coupling rows per scatter call

    @one_executable_per("scatter-type")
    def _chunked_scatter(self, key: str, arrays: tuple, idx: np.ndarray,
                         payloads: list, chunk: int) -> tuple[tuple, int]:
        """Scatter ``payloads`` into ``arrays`` at ``idx`` in fixed-size
        chunks through one cached donated jit. Returns (new arrays, padded
        entry count)."""
        k = int(idx.size)
        pk = -(-k // chunk) * chunk
        if pk != k:
            pad = pk - k
            idx = np.concatenate([idx, np.full(pad, idx[0], idx.dtype)])
            payloads = [np.concatenate([p, np.repeat(p[:1], pad, axis=0)])
                        for p in payloads]
        fn = self._fns.get(key)
        if fn is None:
            na = len(arrays)

            def scatter(*args):
                arrs, r, ps = args[:na], args[na], args[na + 1:]
                return tuple(a.at[r].set(p) for a, p in zip(arrs, ps))

            fn = jax.jit(scatter, donate_argnums=tuple(range(na)))
            self._fns[key] = fn
        for at in range(0, pk, chunk):
            arrays = fn(*arrays, jnp.asarray(idx[at:at + chunk]),
                        *(jnp.asarray(p[at:at + chunk]) for p in payloads))
        return arrays, pk

    def update_edge_rows(self, rows: np.ndarray, *, src, dst_local, w,
                         valid) -> int:
        """Scatter updated TILE ROWS into the device-resident EdgeData.
        ``rows`` are unified-tile row indices; the payloads are the matching
        (len(rows), TILE) slices. Returns the transferred bytes (chunked
        payload + indices)."""
        rows = np.asarray(rows, dtype=np.int32)
        if rows.size == 0:
            return 0
        ed = self._ed
        cov = tile_coverage(dst_local, valid, self.config.subblocks,
                            self.plan.block_size)
        (ns, nd, nw, nv, nc), pk = self._chunked_scatter(
            "row_scatter", (ed.src, ed.dstl, ed.w, ed.valid, ed.cov), rows,
            [np.asarray(src, np.int32), np.asarray(dst_local, np.int32),
             np.asarray(w, np.float32), np.asarray(valid, bool), cov],
            self._ROW_CHUNK)
        self._ed = EdgeData(src=ns, dstl=nd, w=nw, valid=nv, cov=nc,
                            aux=ed.aux)
        # 4B src + 4B dst offset + 4B w + 1B valid per slot + 1B per
        # sub-block coverage bit + 4B row index
        return pk * (int(ns.shape[1]) * 13 + int(nc.shape[1]) + 4)

    def update_aux(self, idx: np.ndarray, vals: np.ndarray) -> int:
        """Scatter changed per-vertex aux entries into the device-resident
        EdgeData. Returns the transferred bytes (chunked values +
        indices)."""
        idx = np.asarray(idx, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.float32)
        if idx.size == 0:
            return 0
        (new_aux,), pk = self._chunked_scatter(
            "aux_scatter", (self._ed.aux,), idx, [vals], self._AUX_CHUNK)
        self._ed = self._ed._replace(aux=new_aux)
        self.aux = new_aux
        return pk * 8

    def update_coupling_rows(self, rows: np.ndarray,
                             row_vals: np.ndarray) -> int:
        """Replace changed ROWS of the staleness-coupling matrix (host copy
        + donated device scatter) — O(changed_rows * P) payload, not the
        full (P, P) re-upload of ``set_coupling``. Returns the transferred
        bytes (chunked rows + indices)."""
        rows = np.asarray(rows, dtype=np.int32)
        row_vals = np.asarray(row_vals, dtype=np.float32)
        if rows.size == 0:
            return 0
        self._coupling[rows] = row_vals
        (new_c,), pk = self._chunked_scatter(
            "coupling_scatter", (self._coupling_dev,), rows, [row_vals],
            self._COUPLING_CHUNK)
        self._coupling_dev = new_c
        return pk * (int(self._coupling[0].size) * 4 + 4)

    @property
    def values_nbytes(self) -> int:
        """Bytes of one padded warm-values upload."""
        return int(self._values_len * 4)

    def full_upload_bytes(self) -> int:
        """Host->device bytes of a FULL dynamic-state refresh (EdgeData +
        aux + coupling + warm values) — what every delta batch paid before
        the row-granular update path, and the denominator of the streaming
        ``upload_frac``."""
        ed = self._ed
        edge_bytes = sum(int(a.size) * a.dtype.itemsize for a in ed)
        return int(edge_bytes + self._coupling.nbytes + self._values_len * 4)

    def pad_values(self, values_perm: np.ndarray) -> np.ndarray:
        """Pad a permuted (n,) value vector to the engine's value length."""
        pad = self._values_len - values_perm.shape[0]
        if pad:
            return np.concatenate(
                [values_perm, np.zeros(pad, dtype=values_perm.dtype)])
        return values_perm

    # -- jitted block processing -------------------------------------------
    def _processor(self):
        if getattr(self, "_proc", None) is None:
            plan, cfg = self.plan, self.config
            self._proc = make_tiled_processor(
                self.program, plan.unified, plan.block_size,
                plan.n_live, plan.graph.n, cfg.use_pallas,
                subblocks=cfg.subblocks)
        return self._proc

    def _sweeps(self, width: int | None = None):
        """(hot_sweep, cold_sweep): the two dispatch bodies, shared at trace
        time by the host-loop fns and the fused superstep so the semantics
        cannot diverge. Both take (ed, values, psd, dmax, rows, ok) with
        (width,) block-id slots; hot is sequential (async, each block sees
        earlier writes) with a per-rank inner depth, cold reads one
        snapshot (sync)."""
        cfg, plan = self.config, self.plan
        width = cfg.width if width is None else width
        depths = jnp.asarray(self._inner_depths(width))
        process_one, process_iterated, gids = self._processor()
        write_one = self._write_one(plan.block_size)
        subblocks = cfg.subblocks
        floor = self._psd_floor()

        # Sub-block activity masks are derived from the block's OWN psd row
        # at slot entry. Within a superstep the scheduled rows are distinct
        # and each sweep slot writes only its own row, so this equals the
        # pre-superstep psd — the invariant the sb-dispatch accounting in
        # _get_chunk / _run_host relies on. At S = 1 every scheduled block
        # clears the floor (selection pruned it otherwise), so the mask
        # would be all-true; sub_act=None keeps the flat trace instead.
        def hot_sweep(ed, values, psd, dmax, rows, ok):
            def body(i, carry):
                values, psd, dmax = carry
                row = rows[i]
                sub_act = None if subblocks == 1 else psd[row] >= floor
                base, new, psd_val, dmax_val = process_iterated(
                    ed, values, row, depths[i], sub_act)
                return write_one(values, psd, dmax, base, new, psd_val,
                                 dmax_val, gids[row], ok[i], sub_act)
            return lax.fori_loop(0, width, body, (values, psd, dmax))

        def cold_sweep(ed, values, psd, dmax, rows, ok):
            if subblocks == 1:
                bases, news, psd_vals, dmax_vals = jax.vmap(
                    lambda r: process_one(ed, values, r))(rows)
                sub_acts = None
            else:
                sub_acts = psd[rows] >= floor  # (W, S)
                bases, news, psd_vals, dmax_vals = jax.vmap(
                    lambda r, sa: process_one(ed, values, r, sa))(
                        rows, sub_acts)

            def body(i, carry):
                values, psd, dmax = carry
                return write_one(values, psd, dmax, bases[i], news[i],
                                 psd_vals[i], dmax_vals[i],
                                 gids[rows[i]], ok[i],
                                 None if sub_acts is None else sub_acts[i])
            return lax.fori_loop(0, width, body, (values, psd, dmax))

        return hot_sweep, cold_sweep

    @staticmethod
    def _write_one(c):
        def write_one(values, psd, dmax, base, new, psd_val, dmax_val, gid,
                      ok, sub_act=None):
            cur = lax.dynamic_slice(values, (base,), (c,))
            values = lax.dynamic_update_slice(
                values, jnp.where(ok, new, cur), (base,))
            if sub_act is not None:
                # masked sub-blocks were not swept: their psd/calm rows
                # must keep decaying toward retirement, not be overwritten
                # with the masked sweep's zero delta
                psd_val = jnp.where(sub_act, psd_val, psd[gid])
                dmax_val = jnp.where(sub_act, dmax_val, dmax[gid])
            psd = jnp.where(ok, psd.at[gid].set(psd_val), psd)
            dmax = jnp.where(ok, dmax.at[gid].set(dmax_val), dmax)
            return values, psd, dmax
        return write_one

    @one_executable_per("sequential", "width")
    def _get_fn(self, sequential: bool, width: int | None = None) -> Callable:
        width = self.config.width if width is None else width
        key = ("unified", sequential, width)
        if key in self._fns:
            return self._fns[key]
        hot_sweep, cold_sweep = self._sweeps(width)
        fn = jax.jit(hot_sweep if sequential else cold_sweep,
                     donate_argnums=(1, 2, 3))
        self._fns[key] = fn
        return fn

    # -- host-side dispatch (run(fused=False) reference path) ---------------
    def _dispatch(self, values, psd, dmax, block_ids: np.ndarray,
                  sequential: bool, width: int | None = None):
        """Run the selected blocks through the unified processor, padded to
        the given dispatch bucket (the adaptive host loop passes its
        current bucket; default is the configured width). Slot index ==
        PSD rank, which is what the hot sweep's depth ladder keys on."""
        w = self.config.width if width is None else width
        for at in range(0, block_ids.size, w):
            chunk = block_ids[at:at + w]
            rows = np.zeros(w, dtype=np.int32)
            ok = np.zeros(w, dtype=bool)
            rows[:chunk.size] = chunk.astype(np.int32)
            ok[:chunk.size] = True
            fn = self._get_fn(sequential, w)
            values, psd, dmax = fn(self._ed, values, psd, dmax,
                                   jnp.asarray(rows), jnp.asarray(ok))
        return values, psd, dmax

    def _account(self, metrics: Metrics, ids: np.ndarray):
        p = self.plan
        for b in ids:
            lo, hi = p.block_range(int(b))
            e = int(self.edge_counts[int(b)])
            metrics.updates += hi - lo
            metrics.block_loads += 1
            metrics.bytes_loaded += block_io_bytes(e, p.block_size)
            metrics.edges_processed += e

    # -- fused device-resident loop -----------------------------------------
    @one_executable_per("width", "trace_cap")
    def _get_chunk(self, width: int | None = None,
                   trace_cap: int | None = None) -> Callable:
        """Jitted multi-iteration chunk: lax.while_loop over fused
        supersteps (schedule -> hot -> cold -> staleness post -> convergence
        test), stopping at the iteration cap, at convergence, or when the
        schedule goes empty. The host supplies the (constant within a
        chunk) hot/cold labels, the dispatch-width bucket (one compiled
        chunk per bucket — ``width`` keys the cache), and the traced
        cold-admission cadence ``i2``; it consumes one
        psd/calm/counters sync per call.

        ``trace_cap=None`` (the default) is EXACTLY the historical chunk
        — same closure, same trace, byte-identical golden jaxpr. With a
        capacity (a :func:`_hist_cap` pow2 bucket; keys the cache
        alongside ``width``) the carry grows two bounded history buffers
        — ``(cap, len(TIMELINE_INT_COLS))`` int32 and
        ``(cap, len(TIMELINE_FLOAT_COLS))`` float32 — and every
        superstep writes its counter deltas / dispatch stats / PSD fold
        at traced index ``it - it0``. The buffers ride the existing
        boundary sync, so per-superstep resolution costs zero extra host
        round-trips, and the algorithmic carry math is untouched — the
        traced trajectory is bitwise the untraced one."""
        width = self.config.width if width is None else width
        key = ("chunk", width, trace_cap)
        if key in self._fns:
            return self._fns[key]
        cfg, plan = self.config, self.plan
        t2 = cfg.t2
        hot_sweep, cold_sweep = self._sweeps(width)
        post = self._make_post()
        select = make_device_select(
            width=width, cold_frac=cfg.cold_frac,
            min_psd=self._psd_floor(), pad_id=self.pad_id)

        floor = self._psd_floor()

        def superstep(it, i2, ed, coupling, values, psd, dmax, calm, counts,
                      hslots, sbacc, is_hot):
            hot_rows, hot_ok, cold_rows, cold_ok = select(it, i2, psd,
                                                          is_hot)
            # sub-dispatch accounting from the PRE-sweep psd — identical to
            # the sub_act masks the sweeps derive (rows are distinct within
            # a superstep; see _sweeps). At S = 1 every ok block counts 1,
            # so sbacc == block loads and the mean dispatch is exactly 1.0.
            live = (psd >= floor).sum(axis=-1).astype(jnp.int32)
            sbacc = sbacc + jnp.where(hot_ok, live[hot_rows], 0).sum() \
                + jnp.where(cold_ok, live[cold_rows], 0).sum()
            values, psd, dmax = hot_sweep(ed, values, psd, dmax, hot_rows,
                                          hot_ok)
            values, psd, dmax = cold_sweep(ed, values, psd, dmax, cold_rows,
                                           cold_ok)
            counts = counts.at[hot_rows].add(hot_ok.astype(jnp.int32))
            counts = counts.at[cold_rows].add(cold_ok.astype(jnp.int32))
            hslots = hslots + hot_ok.astype(jnp.int32)  # depth-hist feed
            # staleness propagation + calm/retire counter advance
            psd, dmax, calm = post(coupling, psd, dmax, calm)
            scheduled = hot_ok.any() | cold_ok.any()
            return values, psd, dmax, calm, counts, hslots, sbacc, scheduled

        def chunk(ed, coupling, values, psd, dmax, calm, counts, hslots,
                  sbacc, it0, it_end, is_hot, i2):
            def cond(carry):
                it, _, _, _, _, _, _, _, done = carry
                return (it < it_end) & jnp.logical_not(done)

            def body(carry):
                it, values, psd, dmax, calm, counts, hslots, sbacc, _ = \
                    carry
                (values, psd, dmax, calm, counts, hslots, sbacc,
                 scheduled) = superstep(it, i2, ed, coupling, values, psd,
                                        dmax, calm, counts, hslots, sbacc,
                                        is_hot)
                conv = state_lib.converged_device(psd, t2)
                # empty schedule: no iteration happened (host parity: the
                # reference loop breaks before processing)
                it = it + jnp.where(scheduled, 1, 0).astype(it.dtype)
                done = conv | jnp.logical_not(scheduled)
                return (it, values, psd, dmax, calm, counts, hslots, sbacc,
                        done)

            (it, values, psd, dmax, calm, counts, hslots, sbacc,
             _) = lax.while_loop(
                cond, body,
                (it0, values, psd, dmax, calm, counts, hslots, sbacc,
                 jnp.bool_(False)))
            return (it, values, psd, dmax, calm, counts, hslots, sbacc,
                    state_lib.converged_device(psd, t2))

        if trace_cap is None:
            fn = jax.jit(chunk, donate_argnums=(2, 3, 4, 5, 6, 7, 8))
            self._fns[key] = fn
            return fn

        # -- traced variant: bounded per-superstep history in the carry --
        nblocks = plan.num_blocks
        retire = cfg.retire_after
        adaptive = cfg.adaptive

        def superstep_traced(it, it0, i2, ed, coupling, values, psd, dmax,
                             calm, counts, hslots, sbacc, hist_i, hist_f,
                             is_hot, acct):
            # re-derive the slate for the delta accounting: pure repeat of
            # the select inside ``superstep`` (identical inputs), so XLA
            # CSEs it away — and even uncached it could only duplicate
            # work, never change a decision
            hot_rows, hot_ok, cold_rows, cold_ok = select(it, i2, psd,
                                                          is_hot)
            (values, psd, dmax, calm, counts, hslots, sbacc,
             scheduled) = superstep(it, i2, ed, coupling, values, psd,
                                    dmax, calm, counts, hslots, sbacc,
                                    is_hot)
            # per-superstep counter delta through the SAME acct table the
            # host multiplies at the boundary flush: the timeline rows sum
            # exactly to the aggregate Metrics counters by construction
            delta = ((acct[hot_rows]
                      * hot_ok[:, None].astype(jnp.int32)).sum(axis=0)
                     + (acct[cold_rows]
                        * cold_ok[:, None].astype(jnp.int32)).sum(axis=0))
            folded = psd.max(axis=-1)  # block fold of the post-post psd
            finite = folded < state_lib.UNSEEN
            if adaptive:
                live = (calm < retire).any(axis=-1)
                retired = (nblocks - live.sum()).astype(jnp.int32)
            else:
                retired = jnp.int32(0)
            row_i = jnp.concatenate([
                delta.astype(jnp.int32),
                jnp.stack([hot_ok.sum().astype(jnp.int32), retired,
                           (~finite).sum().astype(jnp.int32)])])
            fin = jnp.where(finite, folded, 0.0)
            row_f = jnp.stack([fin.sum(), fin.max()])
            idx = it - it0
            hist_i = lax.dynamic_update_slice(hist_i, row_i[None, :],
                                              (idx, 0))
            hist_f = lax.dynamic_update_slice(hist_f, row_f[None, :],
                                              (idx, 0))
            return (values, psd, dmax, calm, counts, hslots, sbacc,
                    hist_i, hist_f, scheduled)

        def chunk_traced(ed, coupling, values, psd, dmax, calm, counts,
                         hslots, sbacc, it0, it_end, is_hot, i2, acct,
                         hist_i, hist_f):
            def cond(carry):
                return (carry[0] < it_end) & jnp.logical_not(carry[-1])

            def body(carry):
                (it, values, psd, dmax, calm, counts, hslots, sbacc,
                 hist_i, hist_f, _) = carry
                (values, psd, dmax, calm, counts, hslots, sbacc, hist_i,
                 hist_f, scheduled) = superstep_traced(
                    it, it0, i2, ed, coupling, values, psd, dmax, calm,
                    counts, hslots, sbacc, hist_i, hist_f, is_hot, acct)
                conv = state_lib.converged_device(psd, t2)
                it = it + jnp.where(scheduled, 1, 0).astype(it.dtype)
                done = conv | jnp.logical_not(scheduled)
                return (it, values, psd, dmax, calm, counts, hslots,
                        sbacc, hist_i, hist_f, done)

            (it, values, psd, dmax, calm, counts, hslots, sbacc, hist_i,
             hist_f, _) = lax.while_loop(
                cond, body,
                (it0, values, psd, dmax, calm, counts, hslots, sbacc,
                 hist_i, hist_f, jnp.bool_(False)))
            return (it, values, psd, dmax, calm, counts, hslots, sbacc,
                    hist_i, hist_f, state_lib.converged_device(psd, t2))

        fn = jax.jit(chunk_traced,
                     donate_argnums=(2, 3, 4, 5, 6, 7, 8, 14, 15))
        self._fns[key] = fn
        return fn

    def _idle_chunk_args(self, wb: int) -> tuple:
        """Arguments of a zero-length chunk call (it_end == it0: the
        while_loop body never fires) at dispatch width ``wb``."""
        p = self.plan
        ps = (p.num_blocks, self.config.subblocks)
        return (self._ed, self._coupling_dev,
                jnp.zeros(self._values_len, jnp.float32),
                jnp.zeros(ps, jnp.float32),
                jnp.zeros(ps, jnp.float32),
                jnp.zeros(ps, jnp.int32),
                jnp.zeros(p.num_blocks, jnp.int32),
                jnp.zeros(wb, jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.int32(0),
                jnp.zeros(p.num_blocks, dtype=bool),
                jnp.int32(self.config.i2))

    def prewarm_buckets(self) -> list[int]:
        """Compile the fused chunk for every dispatch-width bucket with a
        zero-length run, so a long-lived caller (streaming, benchmarks)
        never pays a bucket compile inside a measured batch/run. Returns
        the widths warmed."""
        for wb in self._ladder:
            self._get_chunk(wb)(*self._idle_chunk_args(wb))
        return list(self._ladder)

    def compiled_chunk_text(self) -> str:
        """The compiled fused chunk's program text at the widest dispatch
        bucket — what a caller inspects to see which kernels the backend
        runs (``tpu_custom_call`` for Pallas)."""
        wb = self._ladder[0]
        return self._get_chunk(wb).lower(
            *self._idle_chunk_args(wb)).compile().as_text()

    # -- main loop ----------------------------------------------------------
    def run(self, max_iterations: int | None = None,
            fused: bool | None = None,
            warm: WarmStart | None = None,
            trace: bool | None = None) -> RunResult:
        """Run to convergence. ``fused`` overrides ``config.fused``:
        True = device-resident chunked loop (host syncs only at repartition
        boundaries), False = reference host-driven loop (one sync per
        iteration, per-iteration history). ``warm`` re-enters from a
        previous fixpoint with only the dirty blocks re-heated.

        ``trace`` captures the per-superstep timeline
        (``RunResult.timeline``) and emits run/chunk/repartition spans +
        superstep counters into the installed :mod:`repro.obs` recorder.
        ``None`` (default) auto-enables tracing exactly when a recorder
        is installed, so long-lived callers (streaming reconvergence,
        serve lanes' sibling engines) inherit the capture without
        plumbing. Values and every algorithmic counter of a traced run
        are bitwise identical to the untraced one (property-tested)."""
        fused = self.config.fused if fused is None else fused
        if trace is None:
            trace = obs_trace.current() is not None
        with obs_trace.span("run", cat="engine", fused=bool(fused),
                            warm=warm is not None) as sp:
            res = (self._run_fused(max_iterations, warm, trace=trace)
                   if fused
                   else self._run_host(max_iterations, warm, trace=trace))
            sp.set(iterations=res.metrics.iterations,
                   converged=res.metrics.converged)
        return res

    def _sub2d(self, a: np.ndarray) -> np.ndarray:
        """Normalize a per-block (P,) state vector to the engine's (P, S)
        layout by replicating across sub-blocks (identity content at
        S = 1; for S > 1 a block-granular seed arms/retires all of the
        block's sub-ranges — the sound reading of a flat input)."""
        a = np.asarray(a)
        if a.ndim == 2:
            return a
        return np.repeat(a[:, None], self.config.subblocks, axis=1)

    def _start_state(self, warm: WarmStart | None):
        """(values, psd, rep, calm, i2): the start state of a run. Cold
        runs start fully active (calm 0 everywhere, configured cadence);
        warm runs may seed retired calm counters and a delta-scaled
        cadence (ignored when adaptive is off). psd/calm are (P, S)
        device state; flat (P,) warm seeds are replicated per sub-block."""
        cfg, p = self.config, self.plan
        calm0 = np.zeros((p.num_blocks, cfg.subblocks), dtype=np.int32)
        if warm is None:
            mode = ("barrier" if self.program.monotone_cooling
                    else "universal")
            rep = RepartitionState.create(
                p.num_blocks, p.barrier_block, mode,
                interval=cfg.repartition_interval,
                growth=cfg.repartition_growth)
            return (jnp.asarray(self.values0),
                    jnp.asarray(state_lib.init_psd(p.num_blocks,
                                                   cfg.subblocks)), rep,
                    calm0, cfg.i2)
        if warm.values.shape[0] != self._values_len:
            raise ValueError("warm values must be permuted + padded "
                             f"({warm.values.shape[0]} != {self._values_len})")
        rep = RepartitionState.warm(
            warm.is_hot, interval=cfg.repartition_interval,
            growth=cfg.repartition_growth)
        if cfg.adaptive and warm.calm is not None:
            calm0 = self._sub2d(warm.calm).astype(np.int32)
        i2 = (warm.i2 if cfg.adaptive and warm.i2 is not None
              else cfg.i2)
        psd0 = self._sub2d(np.asarray(warm.psd, dtype=np.float32))
        return (jnp.asarray(np.asarray(warm.values, dtype=np.float32)),
                jnp.asarray(psd0.astype(np.float32)), rep,
                calm0, int(i2))

    def _run_fused(self, max_iterations: int | None = None,
                   warm: WarmStart | None = None,
                   trace: bool = False) -> RunResult:
        cfg, p = self.config, self.plan
        max_it = max_iterations or cfg.max_iterations

        values, psd, rep, calm_host, i2 = self._start_state(warm)
        calm = jnp.asarray(calm_host)
        # host-side decisions (repartition, dispatch bucket, history) are
        # block-granular: fold the (P, S) sub-block psd to block priority
        psd_sub_host = np.asarray(psd)
        psd_host = state_lib.fold_subblock_psd(psd_sub_host)
        active = self._active_count(calm_host)
        dmax = jnp.zeros((p.num_blocks, cfg.subblocks), jnp.float32)
        acct = self._acct_table()
        metrics = Metrics()
        history = []
        depth_hist: dict[int, int] = {}
        width_iters = 0
        sb_total = 0
        # tracing: spans/counters go to the installed recorder (if any);
        # the device timeline needs only the traced chunk variant. The
        # acct table rides as a TRACED int32 arg so the device can expand
        # per-superstep schedule picks into counter deltas itself.
        rec = obs_trace.current() if trace else None
        timeline: list | None = [] if trace else None
        acct_dev = jnp.asarray(acct.astype(np.int32)) if trace else None
        # out-of-core paging: the host scheduler twin (decision-identical
        # to the fused device select, property-tested) predicts each
        # superstep's block demand so it can be paged in BEFORE the sweep
        # reads it — residency never changes the schedule, which is what
        # makes a budget-constrained run bitwise-identical to the fully
        # resident one. Paged chunks run one superstep at a time (the
        # demand set changes per superstep); the dispatch bucket is still
        # retargeted only at repartition boundaries, exactly the resident
        # cadence, so the trajectory cannot diverge.
        spill = self.spill
        pred = None
        if spill is not None:
            from repro.ooc import prefetch as ooc_policy
            spill.begin_run()
            pred = schedule_predictor(self._ladder[0], i2, cfg.cold_frac,
                                      self._psd_floor())
        wb = self._pick_width(active, psd_host)

        with Timer() as t:
            it = 0
            while it < max_it:
                if spill is None:
                    it_end = rep.chunk_end(max_it)
                else:
                    pred.width = wb
                    sel = pred.select(it, psd_sub_host, rep.is_hot)
                    spill.admit(ooc_policy.demand_blocks(sel, self.pad_id),
                                psd_host, calm_host)
                    it_end = it + 1
                # the device counts schedules per block (exact chunk-sized
                # int32s, zeroed each chunk); the host expands them through
                # the int64 accounting table at the boundary. The chunk
                # span (dispatch -> the boundary sync that realizes the
                # async device work) is the trace's wall window for the
                # chunk's supersteps.
                with obs_trace.span("chunk", cat="engine", it0=it,
                                    width=wb) as csp:
                    if trace:
                        cap = _hist_cap(it_end - it)
                        chunk = self._get_chunk(wb, cap)
                        (it_dev, values, psd, dmax, calm, counts, hslots,
                         sbacc, hist_i, hist_f, conv) = chunk(
                            self._ed, self._coupling_dev, values, psd,
                            dmax, calm,
                            jnp.zeros(p.num_blocks, jnp.int32),
                            jnp.zeros(wb, jnp.int32), jnp.int32(0),
                            jnp.int32(it), jnp.int32(it_end),
                            jnp.asarray(rep.is_hot), jnp.int32(i2),
                            acct_dev,
                            jnp.zeros((cap, len(TIMELINE_INT_COLS)),
                                      jnp.int32),
                            jnp.zeros((cap, len(TIMELINE_FLOAT_COLS)),
                                      jnp.float32))
                    else:
                        chunk = self._get_chunk(wb)
                        (it_dev, values, psd, dmax, calm, counts, hslots,
                         sbacc, conv) = chunk(
                            self._ed, self._coupling_dev, values, psd,
                            dmax, calm,
                            jnp.zeros(p.num_blocks, jnp.int32),
                            jnp.zeros(wb, jnp.int32), jnp.int32(0),
                            jnp.int32(it), jnp.int32(it_end),
                            jnp.asarray(rep.is_hot), jnp.int32(i2))
                    # the chunk's single host sync point
                    it_new = int(it_dev)
                    psd_sub_host = np.asarray(psd)
                    psd_host = state_lib.fold_subblock_psd(psd_sub_host)
                    calm_host = np.asarray(calm)
                    counts_host = np.asarray(counts, dtype=np.int64)
                    if trace:
                        # history buffers flush in the SAME sync — the
                        # per-superstep resolution is free of extra host
                        # round-trips
                        hi = np.asarray(hist_i)[:it_new - it]
                        hf = np.asarray(hist_f)[:it_new - it]
                        rows = []
                        for k in range(it_new - it):
                            row = {"superstep": it + k, "width": wb}
                            row.update(zip(TIMELINE_INT_COLS,
                                           (int(v) for v in hi[k])))
                            row.update(zip(TIMELINE_FLOAT_COLS,
                                           (float(v) for v in hf[k])))
                            rows.append(row)
                        timeline.extend(rows)
                    csp.set(it_end=it_new)
                if rec is not None and trace and rows:
                    rec.counter_rows("superstep", rows, csp.t0, csp.t1)
                delta = counts_host @ acct
                metrics.absorb_counters(delta)
                sb_total += int(sbacc)
                span = it_new - it
                width_iters += wb * span
                for d, cnt in zip(self._inner_depths(wb).tolist(),
                                  np.asarray(hslots).tolist()):
                    if cnt:
                        depth_hist[int(d)] = depth_hist.get(int(d), 0) + \
                            int(cnt)
                history.append({
                    "iteration": max(it_new - 1, 0),
                    "span": span,  # iterations covered by this entry
                    "psd_sum": float(psd_host[psd_host <
                                              state_lib.UNSEEN].sum()),
                    "unseen": int((psd_host >= state_lib.UNSEEN).sum()),
                    "hot_blocks": int(rep.is_hot.sum()),
                    "scheduled": int(delta[2]),  # block loads
                    "width": wb,
                    "retired": p.num_blocks - self._active_count(calm_host),
                })
                if bool(conv):
                    metrics.converged = True
                    it = it_new
                    break
                if it_new == it:  # schedule went empty: nothing left to do
                    break
                it = it_new
                # a no-op until it - 1 reaches the boundary, so the paged
                # per-superstep calls fire on exactly the resident cadence
                with obs_trace.span("repartition", cat="engine",
                                    iteration=it - 1) as rsp:
                    fired = rep.maybe_repartition(it - 1, psd_host,
                                                  cfg.hot_ratio)
                    rsp.set(fired=fired)
                # next chunk's bucket follows the live active set, exactly
                # like the host loop's boundary retarget. In paged mode the
                # bucket changes ONLY at fired boundaries (the resident
                # path's chunks always end at boundaries, so this is the
                # same retarget cadence — a per-superstep retarget would
                # change the cold quota and fork the trajectory).
                active = self._active_count(calm_host)
                if spill is None or fired:
                    wb = self._pick_width(active, psd_host)
                if spill is not None and fired:
                    # activity-directed prefetch at the boundary: stage the
                    # predicted next-superstep demand plus the hottest
                    # non-resident blocks, swapping out retired ones only
                    pred.width = wb
                    nsel = pred.select(it, psd_sub_host, rep.is_hot)
                    spill.prefetch_boundary(
                        ooc_policy.demand_blocks(nsel, self.pad_id),
                        psd_host, calm_host)
        metrics.iterations = it
        metrics.wall_time_s = t.elapsed
        metrics.mean_dispatch_width = width_iters / max(it, 1)
        metrics.blocks_retired = p.num_blocks - self._active_count(calm_host)
        metrics.inner_depth_hist = depth_hist
        metrics.subblocks_retired = self._subblocks_retired(calm_host)
        metrics.mean_subblock_dispatch = sb_total / \
            max(metrics.block_loads, 1)
        if spill is not None:
            spill.flush_metrics(metrics)
        self.last_psd = psd_sub_host
        self.last_calm = np.asarray(calm_host)
        out = np.asarray(values)[self.plan.inv]  # back to original ids
        return RunResult(values=out, metrics=metrics, history=history,
                         timeline=timeline)

    def _run_host(self, max_iterations: int | None = None,
                  warm: WarmStart | None = None,
                  trace: bool = False) -> RunResult:
        cfg, p = self.config, self.plan
        max_it = max_iterations or cfg.max_iterations

        values, psd, rep, calm_host, i2 = self._start_state(warm)
        # psd_sub is the raw (P, S) sub-block state (sb-dispatch accounting
        # + the scheduler folds it internally); psd_host its block fold for
        # the host-side block-granular decisions
        psd_sub = np.asarray(psd)
        psd_host = state_lib.fold_subblock_psd(psd_sub)
        sched = Scheduler(width=self._pick_width(
                              self._active_count(calm_host), psd_host),
                          i2=i2, cold_frac=cfg.cold_frac,
                          min_psd=self._psd_floor())
        calm = jnp.asarray(calm_host)
        dmax = jnp.zeros((p.num_blocks, cfg.subblocks), jnp.float32)
        floor = self._psd_floor()
        metrics = Metrics()
        history = []
        depth_hist: dict[int, int] = {}
        hslots = np.zeros(cfg.width, dtype=np.int64)
        width_iters = 0
        sb_total = 0
        # host-path timeline: computed per iteration from the same acct
        # table and post-superstep state the fused history buffers record
        timeline: list | None = [] if trace else None
        acct = self._acct_table() if trace else None
        spill = self.spill
        if spill is not None:
            from repro.ooc import prefetch as ooc_policy
            spill.begin_run()

        with Timer() as t:
            it = 0
            while it < max_it:
                sel: Selection = sched.select(it, psd_sub, rep.is_hot)
                if sel.hot_ids.size == 0 and sel.cold_ids.size == 0:
                    break
                if spill is not None:
                    # page the selected slate in before dispatch touches it
                    # (block 0 — the host dispatch's row padding — is
                    # pinned resident by the store)
                    spill.admit(ooc_policy.demand_blocks(sel, self.pad_id),
                                psd_host, np.asarray(calm))
                processed = np.concatenate([sel.hot_ids, sel.cold_ids])
                w_used = sched.width  # this iteration's bucket (the
                # boundary retarget below may change it before history)
                # live sub-blocks actually swept this iteration, from the
                # same pre-sweep psd the device masks derive from
                sb_total += int((psd_sub[processed] >= floor).sum())
                values, psd, dmax = self._dispatch(
                    values, psd, dmax, sel.hot_ids, sequential=True,
                    width=sched.width)
                values, psd, dmax = self._dispatch(
                    values, psd, dmax, sel.cold_ids, sequential=False,
                    width=sched.width)
                self._account(metrics, processed)
                hslots[:sel.hot_ids.size] += 1
                width_iters += sched.width
                # staleness propagation (device-side max-product matvec):
                # a max per-vertex delta v in block j can move block b's
                # mean-PSD by at most decay * v * coupling(j->b); the post
                # also advances the calm/retire counters.
                psd, dmax, calm = self._post(self._coupling_dev, psd, dmax,
                                             calm)
                psd_sub = np.asarray(psd)
                psd_host = state_lib.fold_subblock_psd(psd_sub)
                with obs_trace.span("repartition", cat="engine",
                                    iteration=it) as rsp:
                    fired = rep.maybe_repartition(it, psd_host,
                                                  cfg.hot_ratio)
                    rsp.set(fired=fired)
                if fired and cfg.adaptive:
                    # boundary retarget: same cadence as the fused path's
                    # per-chunk bucket pick
                    calm_host = np.asarray(calm)
                    sched.width = self._pick_width(
                        self._active_count(calm_host), psd_host)
                if fired and spill is not None:
                    # boundary prefetch: stage the predicted next-iteration
                    # demand + the hottest non-resident blocks
                    nsel = sched.select(it + 1, psd_sub, rep.is_hot)
                    spill.prefetch_boundary(
                        ooc_policy.demand_blocks(nsel, self.pad_id),
                        psd_host, np.asarray(calm))
                history.append({
                    "iteration": it,
                    "psd_sum": float(psd_host[psd_host <
                                              state_lib.UNSEEN].sum()),
                    "unseen": int((psd_host >= state_lib.UNSEEN).sum()),
                    "hot_blocks": int(rep.is_hot.sum()),
                    "scheduled": int(processed.size),
                    "width": sched.width,
                })
                if trace:
                    # same columns/definitions as the fused history
                    # buffers: counter deltas via the acct table, retired/
                    # PSD stats from the post-superstep state
                    d = acct[processed].sum(axis=0) if processed.size \
                        else np.zeros(4, dtype=np.int64)
                    finite = psd_host < state_lib.UNSEEN
                    row = {"superstep": it, "width": w_used,
                           "hot_loads": int(sel.hot_ids.size),
                           "retired": p.num_blocks
                           - self._active_count(np.asarray(calm)),
                           "unseen": int((~finite).sum()),
                           "psd_sum": float(
                               psd_host[finite].astype(np.float32).sum()),
                           "psd_max": float(
                               psd_host[finite].max()) if finite.any()
                           else 0.0}
                    row.update(zip(COUNTER_FIELDS,
                                   (int(v) for v in d)))
                    timeline.append(row)
                it += 1
                if state_lib.converged(psd_sub, cfg.t2):
                    metrics.converged = True
                    break
        calm_host = np.asarray(calm)
        depths = self._inner_depths(cfg.width)
        for d, cnt in zip(depths.tolist(), hslots.tolist()):
            if cnt:
                depth_hist[int(d)] = depth_hist.get(int(d), 0) + int(cnt)
        metrics.iterations = it
        metrics.wall_time_s = t.elapsed
        metrics.mean_dispatch_width = width_iters / max(it, 1)
        metrics.blocks_retired = p.num_blocks - self._active_count(calm_host)
        metrics.inner_depth_hist = depth_hist
        metrics.subblocks_retired = self._subblocks_retired(calm_host)
        metrics.mean_subblock_dispatch = sb_total / \
            max(metrics.block_loads, 1)
        if spill is not None:
            spill.flush_metrics(metrics)
        self.last_psd = psd_sub
        self.last_calm = calm_host
        out = np.asarray(values)[self.plan.inv]  # back to original ids
        return RunResult(values=out, metrics=metrics, history=history,
                         timeline=timeline)


def coupling_from_counts(block_edge_counts: np.ndarray,
                         program: VertexProgram,
                         block_size: int) -> np.ndarray:
    """(P, P) staleness-coupling matrix from the block->block edge-count
    matrix W_jb (number of edges from block j's vertices into block b).
    Factored out of the engine so the streaming subsystem can maintain W
    incrementally under edge deltas and refresh K without an O(m) rescan.
    """
    w = block_edge_counts
    if program.combine == "sum":
        k = (np.minimum(w, block_size) / block_size).astype(np.float32)
        return k * np.float32(program.damping)
    return (w > 0).astype(np.float32)


# -- Betweenness centrality (Brandes, sampled sources) -----------------------
def betweenness(graph: Graph, sources: list[int],
                config: EngineConfig = EngineConfig(),
                structure_aware: bool = True) -> tuple[np.ndarray, Metrics]:
    """BC per paper's algorithm set: the forward BFS waves run through the
    structure-aware engine (or the baseline when structure_aware=False); the
    path-counting and dependency accumulation are level-synchronous dense
    sweeps (they are single passes, not iterative-convergent phases)."""
    from repro.core import algorithms as algos
    from repro.core.baseline import BaselineEngine

    n = graph.n
    bc = np.zeros(n, dtype=np.float64)
    total = Metrics()
    s_arr, d_arr, _ = _coo(graph)
    for s in sources:
        prog = algos.bfs(source=s)
        eng = (StructureAwareEngine(graph, prog, config) if structure_aware
               else BaselineEngine(graph, prog, config))
        res = eng.run()
        dist = res.values
        for k, v in res.metrics.as_dict().items():
            # skip non-summable entries: converged, and derived rates that
            # as_dict computes from counters (read-only properties)
            if (isinstance(v, (int, float)) and k != "converged"
                    and not isinstance(getattr(type(total), k, None),
                                       property)):
                setattr(total, k, getattr(total, k) + v)
        # sigma: #shortest paths, level-synchronous accumulation
        finite = dist < algos.INF / 2
        max_lvl = int(dist[finite].max()) if finite.any() else 0
        sigma = np.zeros(n, dtype=np.float64)
        sigma[s] = 1.0
        on_sp = dist[d_arr] == dist[s_arr] + 1
        for lvl in range(1, max_lvl + 1):
            e = on_sp & (dist[d_arr] == lvl)
            np.add.at(sigma, d_arr[e], sigma[s_arr[e]])
        # delta: backward dependency accumulation
        delta = np.zeros(n, dtype=np.float64)
        for lvl in range(max_lvl, 0, -1):
            e = on_sp & (dist[d_arr] == lvl)
            contrib = sigma[s_arr[e]] / np.maximum(sigma[d_arr[e]], 1.0) * \
                (1.0 + delta[d_arr[e]])
            np.add.at(delta, s_arr[e], contrib)
        delta[s] = 0.0
        bc += delta
    return bc, total


def _coo(g: Graph):
    dst = np.repeat(np.arange(g.n, dtype=np.int64), g.in_deg)
    return g.in_src.astype(np.int64), dst, g.in_w
