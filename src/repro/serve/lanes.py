"""Multi-lane fused execution: L independent queries per superstep sweep.

The :class:`LaneEngine` is the query-serving generalization of
``StructureAwareEngine._run_fused``: vertex values carry a lane axis
``(values_len, L)``, one jitted ``lax.while_loop`` advances every lane per
superstep, and the paper's whole scheduling stack prices the **union** of
the lane frontiers:

  * the per-block scheduling priority is the max over live lanes of the
    per-lane PSD (``state.fold_lane_psd``) — a block hot in ANY running
    lane is schedulable, so one hot dispatch serves every lane that needs
    the block;
  * **per-lane convergence masks** retire finished lanes: lane l is done
    when SUM_b PSD[b, l] < T2 (the paper's test, per lane). A retired
    lane stops contributing to block priority, so the active set — and
    with it the adaptive dispatch width — shrinks as lanes finish;
  * the adaptive active-set machinery (calm/retire counters, PSD-rank
    depth ladder, dispatch-width buckets) is REUSED via the engine's
    module-level decision helpers, not reimplemented — with a single
    admitted lane the schedule decisions are identical to the
    single-program engine, which is what makes serving a strict superset
    of the engine rather than a fork (property tested);
  * hierarchical partitions (``EngineConfig.subblocks = S > 1``) carry
    through: lane PSD/dmax are (P, S, L), calm is (P, S), and each
    scheduled block applies ONE shared (S,) sub-block mask — the
    lane-folded sub priority over the pruning floor
    (:func:`repro.core.state.lane_sub_psd_device`) — so a narrow query
    frontier sweeps only the sub-ranges some live lane actually prices,
    instead of paying whole-block sweeps. ``subblocks = 1`` traces the
    exact flat path.

Why lanes beat sequential runs: each scheduled block's edge tiles are
gathered once per superstep and the message/combine/apply math vectorizes
over the lane axis, so L queries share every partition load, every
schedule decision, and every while-loop step. Partition loads and bytes
are billed once per block schedule (the load IS shared); ``updates`` and
``edges_processed`` are billed per admitted lane (the arithmetic is not).

Everything per-epoch (edge tiles, aux, coupling) and per-batch (init
values, personalization vconst) arrives as TRACED ARGUMENTS, so one
compiled executable per (family, lane width, dispatch bucket) serves
every batch and every streaming epoch of the same tile geometry.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis.contracts import one_executable_per
from repro.core import state as state_lib
from repro.core.algorithms import LaneProgram
from repro.core.engine import (EdgeData, StructureAwareEngine, acct_table,
                               dispatch_width, inner_depths,
                               make_lane_processor)
from repro.core.metrics import Metrics, Timer
from repro.core.repartition import RepartitionState
from repro.core.schedule import make_device_select


@dataclasses.dataclass
class LaneResult:
    values: np.ndarray  # (n, L), original vertex ids
    metrics: Metrics  # batch-level accounting (see module docstring)
    lane_iterations: np.ndarray  # (L,) supersteps until each lane converged
    lane_converged: np.ndarray  # (L,) bool


class LaneEngine:
    """Fused multi-lane runner over one engine epoch's tile geometry.

    Borrows plan, config, and the compiled-decision helpers from a
    :class:`StructureAwareEngine` (the geometry owner); edge state and
    coupling arrive per run, so the same LaneEngine serves every epoch
    that keeps the geometry (a plan rebuild needs a new one, exactly like
    the engine's own compiled functions)."""

    def __init__(self, engine: StructureAwareEngine, program: LaneProgram,
                 use_pallas: bool | None = None):
        self.engine = engine
        self.program = program
        # None inherits the geometry owner's flag, so a Pallas engine
        # serves Pallas lanes without the caller re-stating it
        self.use_pallas = (engine.config.use_pallas if use_pallas is None
                           else use_pallas)
        p = engine.plan
        self._proc = make_lane_processor(program, p.unified, p.block_size,
                                         p.n_live, p.graph.n,
                                         subblocks=engine.config.subblocks,
                                         use_pallas=self.use_pallas)
        self._fns: dict = {}

    # -- traced pieces (mirrors of the engine's, with a lane axis) -----------
    def _sweeps(self, width: int):
        eng = self.engine
        c = eng.plan.block_size
        subblocks = eng.config.subblocks
        floor = jnp.float32(eng._psd_floor())
        depths = jnp.asarray(inner_depths(eng.config, width))
        process_one, process_iterated, gids = self._proc

        def write_one(values, psd, dmax, base, new, psd_vec, dmax_vec, gid,
                      ok, sub_act=None):
            nl = values.shape[1]
            cur = lax.dynamic_slice(values, (base, 0), (c, nl))
            values = lax.dynamic_update_slice(
                values, jnp.where(ok, new, cur), (base, 0))
            if sub_act is not None:
                # masked sub-blocks keep their prior per-lane PSD/dmax —
                # they were not swept, so their staleness is unchanged
                psd_vec = jnp.where(sub_act[:, None], psd_vec, psd[gid])
                dmax_vec = jnp.where(sub_act[:, None], dmax_vec, dmax[gid])
            psd = jnp.where(ok, psd.at[gid].set(psd_vec), psd)
            dmax = jnp.where(ok, dmax.at[gid].set(dmax_vec), dmax)
            return values, psd, dmax

        def row_sub_act(psd, lane_done, gid):
            """(S,) shared sub-block mask for one scheduled row: the
            lane-folded sub priority over the floor. Rows scheduled in a
            superstep are distinct and each sweep writes only its own
            row, so reading ``psd[gid]`` mid-sweep equals the
            pre-superstep fold the sb accounting uses."""
            live = jnp.max(jnp.where(lane_done, jnp.float32(0.0),
                                     psd[gid]), axis=-1)
            return live >= floor

        def hot_sweep(ed, vconst, values, psd, dmax, rows, ok, lane_done):
            def body(i, carry):
                values, psd, dmax = carry
                row = rows[i]
                sub_act = (None if subblocks == 1
                           else row_sub_act(psd, lane_done, gids[row]))
                base, new, pv, dv = process_iterated(ed, values, vconst,
                                                     row, depths[i],
                                                     sub_act)
                return write_one(values, psd, dmax, base, new, pv, dv,
                                 gids[row], ok[i], sub_act)
            return lax.fori_loop(0, width, body, (values, psd, dmax))

        def cold_sweep(ed, vconst, values, psd, dmax, rows, ok, lane_done):
            if subblocks == 1:
                bases, news, pvs, dvs = jax.vmap(
                    lambda r: process_one(ed, values, vconst, r))(rows)
                sub_acts = [None] * width
            else:
                sub_acts = jax.vmap(
                    lambda r: row_sub_act(psd, lane_done, gids[r]))(rows)
                bases, news, pvs, dvs = jax.vmap(
                    lambda r, sa: process_one(ed, values, vconst, r, sa))(
                        rows, sub_acts)

            def body(i, carry):
                values, psd, dmax = carry
                sa = None if subblocks == 1 else sub_acts[i]
                return write_one(values, psd, dmax, bases[i], news[i],
                                 pvs[i], dvs[i], gids[rows[i]], ok[i], sa)
            return lax.fori_loop(0, width, body, (values, psd, dmax))

        return hot_sweep, cold_sweep

    def _make_post(self):
        eng = self.engine
        eps = eng.config.stale_eps
        floor = eng._psd_floor()

        def post(coupling, psd, dmax, calm, lane_done):
            """Per-lane staleness propagation + the SHARED calm counters:
            the bump is applied lane-by-lane (a delta in lane l re-arms
            downstream blocks for lane l only), while retirement hysteresis
            tracks the folded (lane-union) priority — a block retires only
            when quiet in every live lane, which keeps the active set sound
            for the whole batch. With a sub-block axis ((P, S, L) state)
            the coupling is destination-sub-resolved ((P, P, S), same as
            the engine's post): the outgoing signal is the block's max
            sub-delta per lane and an incoming bump re-arms only the
            sub-ranges that block actually feeds, per lane; calm then
            advances per sub-block on the lane-folded sub priority."""
            d = jnp.where(dmax > eps, dmax, 0.0)  # (P, L) or (P, S, L)
            if psd.ndim == 3:
                dblk = d.max(axis=1)  # (P, L)
                bump = jnp.max(dblk[:, None, None, :]
                               * coupling[:, :, :, None], axis=0)  # (P,S,L)
                psd = jnp.maximum(psd, jnp.minimum(bump, 1e29))
                quiet = state_lib.lane_sub_psd_device(psd, lane_done)
            else:
                bump = jnp.max(d[:, None, :] * coupling[:, :, None], axis=0)
                psd = jnp.maximum(psd, jnp.minimum(bump, 1e29))
                quiet = state_lib.fold_lane_psd_device(psd, lane_done)
            calm = jnp.where(quiet < floor, calm + 1, 0).astype(jnp.int32)
            return psd, jnp.zeros_like(dmax), calm
        return post

    @one_executable_per("width")
    def _get_chunk(self, width: int):
        key = ("lane_chunk", width)
        if key in self._fns:
            return self._fns[key]
        eng = self.engine
        cfg, plan = eng.config, eng.plan
        t2 = cfg.t2
        hot_sweep, cold_sweep = self._sweeps(width)
        post = self._make_post()
        tile_cnt = plan.unified.tile_cnt
        select = make_device_select(
            width=width, cold_frac=cfg.cold_frac, min_psd=eng._psd_floor(),
            pad_id=int(np.argmin(tile_cnt)) if tile_cnt.size else 0)

        floor = jnp.float32(eng._psd_floor())

        def chunk(ed, coupling, vconst, values, psd, dmax, calm, counts,
                  hslots, sbacc, lane_done, lane_it, it0, it_end, is_hot,
                  i2):
            def cond(carry):
                it = carry[0]
                done = carry[-1]
                return (it < it_end) & jnp.logical_not(done)

            def body(carry):
                (it, values, psd, dmax, calm, counts, hslots, sbacc,
                 lane_done, lane_it, _) = carry
                block_psd = state_lib.fold_lane_psd_device(psd, lane_done)
                hot_rows, hot_ok, cold_rows, cold_ok = select(
                    it, i2, block_psd, is_hot)
                # sub-block dispatch accounting from the PRE-superstep
                # priorities — identical to the masks the sweeps apply
                # (scheduled rows are distinct within a superstep)
                if psd.ndim == 3:
                    live = (state_lib.lane_sub_psd_device(psd, lane_done)
                            >= floor).sum(axis=-1).astype(jnp.int32)
                else:
                    live = (block_psd >= floor).astype(jnp.int32)
                sbacc = sbacc + \
                    jnp.where(hot_ok, live[hot_rows], 0).sum() + \
                    jnp.where(cold_ok, live[cold_rows], 0).sum()
                values, psd, dmax = hot_sweep(ed, vconst, values, psd,
                                              dmax, hot_rows, hot_ok,
                                              lane_done)
                values, psd, dmax = cold_sweep(ed, vconst, values, psd,
                                               dmax, cold_rows, cold_ok,
                                               lane_done)
                counts = counts.at[hot_rows].add(hot_ok.astype(jnp.int32))
                counts = counts.at[cold_rows].add(cold_ok.astype(jnp.int32))
                hslots = hslots + hot_ok.astype(jnp.int32)
                psd, dmax, calm = post(coupling, psd, dmax, calm, lane_done)
                lane_conv = state_lib.lane_converged_device(psd, t2)
                scheduled = hot_ok.any() | cold_ok.any()
                it = it + jnp.where(scheduled, 1, 0).astype(it.dtype)
                newly = lane_conv & jnp.logical_not(lane_done)
                lane_it = jnp.where(newly, it, lane_it)
                lane_done = lane_done | lane_conv
                done = lane_done.all() | jnp.logical_not(scheduled)
                return (it, values, psd, dmax, calm, counts, hslots, sbacc,
                        lane_done, lane_it, done)

            (it, values, psd, dmax, calm, counts, hslots, sbacc, lane_done,
             lane_it, _) = lax.while_loop(
                cond, body,
                (it0, values, psd, dmax, calm, counts, hslots, sbacc,
                 lane_done, lane_it, jnp.bool_(False)))
            return (it, values, psd, dmax, calm, counts, hslots, sbacc,
                    lane_done, lane_it, lane_done.all())

        fn = jax.jit(chunk, donate_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
        self._fns[key] = fn
        return fn

    # -- host side -----------------------------------------------------------
    def _pad_lane_values(self, arr: np.ndarray) -> np.ndarray:
        pad = self.engine._values_len - arr.shape[0]
        if pad:
            return np.concatenate(
                [arr, np.zeros((pad, arr.shape[1]), dtype=arr.dtype)])
        return arr

    def _init_dead(self, values0: np.ndarray, vconst: np.ndarray):
        """Dead partition one-shot (engine parity): apply() with the
        identity aggregate, per lane. Streaming plans keep zero dead
        vertices; this covers LaneEngines over plain engines."""
        p = self.engine.plan
        if p.n_dead == 0:
            return values0
        dead = slice(p.n_live, p.graph.n)
        nl = values0.shape[1]
        agg = jnp.full((p.n_dead, nl),
                       0.0 if self.program.combine == "sum"
                       else self.program.identity, jnp.float32)
        values0 = values0.copy()
        values0[dead] = np.asarray(self.program.apply(
            jnp.asarray(values0[dead]), agg, jnp.asarray(vconst[dead]),
            p.graph.n))
        return values0

    def prewarm(self, n_lanes: int) -> list[int]:
        """Compile the lane chunk for every dispatch-width bucket at this
        lane width with a zero-length run, so no query batch pays a
        compile inside its measured latency (streaming-prewarm parity)."""
        eng = self.engine
        p = eng.plan
        vl = eng._values_len
        sb = eng.config.subblocks
        lane_shape = ((p.num_blocks, n_lanes) if sb == 1
                      else (p.num_blocks, sb, n_lanes))
        calm_shape = (p.num_blocks,) if sb == 1 else (p.num_blocks, sb)
        # under an out-of-core budget the lanes read pinned whole-plan
        # copies, not the live pool: compile for that shape
        ed = (eng.edge_snapshot() if eng.spill is not None
              else eng.edge_state)
        for wb in eng._ladder:
            fn = self._get_chunk(wb)
            fn(ed, jnp.zeros(eng._coupling.shape, jnp.float32),
               jnp.zeros((vl, n_lanes), jnp.float32),
               jnp.zeros((vl, n_lanes), jnp.float32),
               jnp.zeros(lane_shape, jnp.float32),
               jnp.zeros(lane_shape, jnp.float32),
               jnp.zeros(calm_shape, jnp.int32),
               jnp.zeros(p.num_blocks, jnp.int32),
               jnp.zeros(wb, jnp.int32),
               jnp.int32(0),
               jnp.zeros(n_lanes, dtype=bool),
               jnp.zeros(n_lanes, jnp.int32),
               jnp.int32(0), jnp.int32(0),
               jnp.zeros(p.num_blocks, dtype=bool),
               jnp.int32(eng.config.i2))
        return list(eng._ladder)

    def run(self, *, ed: EdgeData, coupling: np.ndarray,
            values0: np.ndarray, vconst: np.ndarray | None,
            lane_active: np.ndarray, edge_counts: np.ndarray,
            max_iterations: int | None = None) -> LaneResult:
        """Run every active lane to convergence over the given epoch state.

        ``values0``/``vconst`` are (n, L) in ORIGINAL vertex ids;
        ``lane_active`` marks admitted lanes (padding lanes start
        individually converged and never price a block); ``edge_counts``
        is the pinned epoch's per-block live edge counts (metric truth).
        """
        eng = self.engine
        cfg, p = eng.config, eng.plan
        max_it = max_iterations or cfg.max_iterations
        lane_active = np.asarray(lane_active, dtype=bool)
        nl = values0.shape[1]
        n_adm = int(lane_active.sum())

        vals = np.asarray(values0, dtype=np.float32)[p.order]
        vc = (np.asarray(vconst, dtype=np.float32)[p.order]
              if vconst is not None
              else np.zeros_like(vals))
        vals = self._init_dead(vals, vc)
        values = jnp.asarray(self._pad_lane_values(vals))
        vconst_dev = jnp.asarray(self._pad_lane_values(vc))

        sb = cfg.subblocks
        psd_host = state_lib.init_lane_psd(p.num_blocks, lane_active,
                                           None if sb == 1 else sb)
        psd = jnp.asarray(psd_host)
        lane_done_host = ~lane_active
        lane_done = jnp.asarray(lane_done_host)
        lane_it = jnp.zeros(nl, jnp.int32)
        folded = state_lib.fold_lane_psd(psd_host, lane_done_host)
        mode = ("barrier" if self.program.monotone_cooling else "universal")
        rep = RepartitionState.create(
            p.num_blocks, p.barrier_block, mode,
            interval=cfg.repartition_interval,
            growth=cfg.repartition_growth)
        calm_host = np.zeros(p.num_blocks if sb == 1 else (p.num_blocks, sb),
                             dtype=np.int32)
        calm = jnp.asarray(calm_host)
        dmax = jnp.zeros((p.num_blocks, nl) if sb == 1
                         else (p.num_blocks, sb, nl), jnp.float32)
        active = eng._active_count(calm_host)
        # loads/bytes are billed once per block schedule (shared by the
        # lanes — that is the batching win); updates/edges per admitted
        # lane (the arithmetic really runs per lane)
        acct = acct_table(p, edge_counts)
        acct[:, 0] *= max(n_adm, 1)
        acct[:, 1] *= max(n_adm, 1)
        coupling_dev = jnp.asarray(np.asarray(coupling, dtype=np.float32))
        metrics = Metrics()
        depth_hist: dict[int, int] = {}
        width_iters = 0
        sb_total = 0
        loads_total = 0
        conv = jnp.bool_(False)

        with Timer() as t:
            it = 0
            while it < max_it and n_adm:
                wb = dispatch_width(cfg, eng._ladder, active, folded)
                chunk = self._get_chunk(wb)
                it_end = rep.chunk_end(max_it)
                (it_dev, values, psd, dmax, calm, counts, hslots, sbacc,
                 lane_done, lane_it, conv) = chunk(
                    ed, coupling_dev, vconst_dev, values, psd, dmax, calm,
                    jnp.zeros(p.num_blocks, jnp.int32),
                    jnp.zeros(wb, jnp.int32),
                    jnp.int32(0),
                    lane_done, lane_it,
                    jnp.int32(it), jnp.int32(it_end),
                    jnp.asarray(rep.is_hot), jnp.int32(cfg.i2))
                it_new = int(it_dev)
                psd_host = np.asarray(psd)
                lane_done_host = np.asarray(lane_done)
                calm_host = np.asarray(calm)
                # ONE active-set read per chunk boundary: both the next
                # dispatch-width pick and the end-of-run retirement metric
                # reuse it (this used to be recomputed at every use site)
                active = eng._active_count(calm_host)
                folded = state_lib.fold_lane_psd(psd_host, lane_done_host)
                counts_host = np.asarray(counts, dtype=np.int64)
                metrics.absorb_counters(counts_host @ acct)
                sb_total += int(sbacc)
                loads_total += int(counts_host.sum())
                span = it_new - it
                width_iters += wb * span
                for d, cnt in zip(inner_depths(cfg, wb).tolist(),
                                  np.asarray(hslots).tolist()):
                    if cnt:
                        depth_hist[int(d)] = depth_hist.get(int(d), 0) + \
                            int(cnt)
                if bool(conv):
                    metrics.converged = True
                    it = it_new
                    break
                if it_new == it:  # schedule went empty
                    break
                it = it_new
                rep.maybe_repartition(it - 1, folded, cfg.hot_ratio)
        metrics.iterations = it
        metrics.wall_time_s = t.elapsed
        metrics.mean_dispatch_width = width_iters / max(it, 1)
        metrics.blocks_retired = p.num_blocks - active
        metrics.subblocks_retired = eng._subblocks_retired(calm_host)
        metrics.mean_subblock_dispatch = sb_total / max(loads_total, 1)
        metrics.inner_depth_hist = depth_hist
        lane_it_host = np.asarray(lane_it, dtype=np.int64)
        lane_conv_host = np.asarray(lane_done) & lane_active
        lane_iters = np.where(lane_conv_host, lane_it_host, it)
        out = np.asarray(values)[p.inv]  # (n, L), original ids
        return LaneResult(values=out, metrics=metrics,
                          lane_iterations=lane_iters,
                          lane_converged=lane_conv_host)
