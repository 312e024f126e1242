"""SpillStore: a device pool of edge tile rows, and the spill tier behind it.

Under an out-of-core budget (``EngineConfig.resident_rows``) the engine's
row arrays (``EdgeData.src / dstl / w / valid / cov``) hold
``resident_rows`` tile rows, not the plan's: the device memory the edges
get is the budget. Each resident block's run of tile rows sits
contiguously in that pool, at ``EdgeData.start[b]``, and
``EdgeData.resident`` marks the blocks whose run is there. A host
allocator places each admitted block's run in a free contiguous range of
the pool (first fit) and writes its start; evicting a block only frees
its range. When no free range fits, the resident runs are compacted
towards the front with donated in-place row moves, so the device never
holds two pools at once. The device writes run under the named scope
``ooc_place``.

The engine demand-fetches every block its predicted schedule needs
*before* entering the superstep, so the schedule itself never changes: a
budget-constrained run is bitwise-identical (values and algorithmic
counters) to the fully resident one — the property the OOC tests pin.
The prediction is the host scheduler twin's; each fused chunk also counts
the sweeps of blocks the device mask calls non-resident, and a run whose
count is not 0 raises (:meth:`SpillStore.check_sweeps`) instead of
returning values computed from another block's rows.

What spills: the per-block EDGE tile rows (src / dst_local / w / valid —
the O(m) state). Vertex values, PSD/calm activity and aux stay resident:
the sweeps are pull-mode (any scheduled block gathers ``values[e_src]``
graph-wide), so value slices of unscheduled blocks are still read every
superstep, and the activity state is exactly what the prefetch policy
steers by. Those are O(n) and O(P*S); the edge tiles are the memory
story.

Payload source of truth, in priority order:

  1. ``row_source`` — a host-side truth oracle (the streaming engine
     wires ``MutableTiledState.rows2d`` here), always current under
     ingest mutation;
  2. the host payload cache: every block's plan rows at construction
     (views, no copy), dropped for a block whose device rows a commit
     rewrites and captured again from the device at its eviction;
  3. the npz disk segment (``keep_host=False`` keeps no cache: the
     segments are the tier).

``on_evict`` fires before a block's range is freed so the serve layer
can preserve pinned epoch snapshots (see ``StreamingEngine.snapshot``);
``materialize`` builds a whole-plan :class:`EdgeData` copy, addressed by
the plan's own starts, for such pins without changing residency.
"""
from __future__ import annotations

import os
import queue
import threading

import jax.numpy as jnp
import numpy as np

from repro.core.engine import EdgeData, tile_coverage
from repro.obs import trace as obs_trace
from repro.ooc import prefetch as policy


class _AsyncSegmentWriter:
    """Single daemon writer draining (block, payload) jobs to atomic npz
    segments — the ckpt-manager write discipline (tmp + rename) applied
    per block. ``wait`` drains the queue; readers call it before touching
    a segment that might still be in flight."""

    def __init__(self, directory: str):
        os.makedirs(directory, exist_ok=True)
        self.dir = directory
        self._q: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def path(self, block: int) -> str:
        return os.path.join(self.dir, f"blk_{block:06d}.npz")

    def submit(self, block: int, payload: dict) -> None:
        self._q.put((block, payload))

    def _loop(self) -> None:
        while True:
            block, payload = self._q.get()
            try:
                final = self.path(block)
                tmp = final + ".tmp.npz"
                np.savez(tmp, **payload)
                os.replace(tmp, final)  # atomic publish
            finally:
                self._q.task_done()

    def wait(self) -> None:
        self._q.join()


class SpillStore:
    """Device row pool, its allocator and the spill tier for one engine
    epoch."""

    PAYLOAD_FIELDS = ("src", "dst_local", "w", "valid")

    def __init__(self, engine, rows: int, directory: str | None = None,
                 keep_host: bool | None = None):
        plan = engine.plan
        u = plan.unified
        self.engine = engine
        self.num_blocks = int(plan.num_blocks)
        self.rows = int(rows)  # the pool's size in tile rows
        self.cnt = u.tile_cnt.astype(np.int64)
        self.plan_start = u.tile_start.astype(np.int64)
        width = int(engine.config.width)
        need = int(np.sort(self.cnt)[::-1][:width + 2].sum())
        if self.rows < need:
            raise ValueError(
                f"resident_rows={self.rows} cannot hold one superstep's "
                f"demand: need >= {need} rows, the {width + 2} largest "
                "block runs (width + 2: the scheduled slate plus the "
                "pinned pad blocks)")
        # the pad block fills every non-ok dispatch slot (the sweeps still
        # compute it) and the host loop pads its chunks with block 0 —
        # both must always be resident
        self.pinned = np.zeros(self.num_blocks, dtype=bool)
        self.pinned[[0, engine.pad_id]] = True
        self.floor = engine._psd_floor()
        self.retire_after = int(engine.config.retire_after)
        # plan row -> its block (commits arrive in plan rows)
        self._row_block = np.repeat(np.arange(self.num_blocks), self.cnt)
        # where each resident run starts in the pool; a block of no rows
        # is always resident
        self.start = np.zeros(self.num_blocks, dtype=np.int64)
        self.resident = self.cnt == 0
        self._table_stale = False  # start/resident changed since the upload
        self.row_source = None  # callable(rows) -> payload dict, or None
        self.on_evict = None  # pre-eviction hook (epoch-pin preservation)
        self._writer = (_AsyncSegmentWriter(directory)
                        if directory is not None else None)
        self.keep_host = (self._writer is None if keep_host is None
                          else bool(keep_host))
        self._cache: dict[int, dict] = {}
        if self.keep_host:
            for b in range(self.num_blocks):
                self._cache[b] = self._plan_payload(b)
        self._zero_counters()

    # -- accounting ----------------------------------------------------------
    def _zero_counters(self) -> None:
        self.spill_evictions = 0
        self.bytes_spilled = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.bytes_fetched = 0
        self.compactions = 0
        self.rows_moved = 0

    def begin_run(self) -> None:
        """Reset the per-run counters (residency itself persists across
        runs — the out-of-core steady state)."""
        self._zero_counters()

    def flush_metrics(self, metrics) -> None:
        metrics.spill_evictions += self.spill_evictions
        metrics.bytes_spilled += self.bytes_spilled
        metrics.prefetch_hits += self.prefetch_hits
        metrics.prefetch_misses += self.prefetch_misses
        metrics.bytes_fetched += self.bytes_fetched
        metrics.compactions += self.compactions
        metrics.rows_moved += self.rows_moved

    @property
    def spilled_blocks(self) -> np.ndarray:
        return np.flatnonzero(~self.resident)

    @property
    def free_rows(self) -> int:
        return self.rows - int(self.cnt[self.resident].sum())

    def block_rows(self, block: int) -> np.ndarray:
        """Block ``block``'s rows in the plan's layout."""
        s = int(self.plan_start[block])
        return np.arange(s, s + int(self.cnt[block]), dtype=np.int64)

    def _pool_rows_of(self, block: int) -> np.ndarray:
        s = int(self.start[block])
        return np.arange(s, s + int(self.cnt[block]), dtype=np.int64)

    def _payload_bytes(self, rows: int) -> int:
        # 4B src + 4B dst offset + 4B w + 1B valid per slot
        tile = int(self.engine.plan.unified.src.shape[1])
        return rows * tile * 13

    def check_sweeps(self, miss: int, superstep: int | None) -> None:
        """Raise if ``miss`` dispatch slots swept a non-resident block:
        their rows in the pool belong to another block, so the values
        would be wrong."""
        if miss:
            raise RuntimeError(
                f"out-of-core paging: {miss} dispatch slots swept a block "
                f"whose rows are not resident (superstep {superstep}); the "
                "paged-in set missed the schedule")

    # -- payload plumbing ----------------------------------------------------
    def _plan_payload(self, block: int) -> dict:
        u = self.engine.plan.unified
        s, n = int(self.plan_start[block]), int(self.cnt[block])
        return {"src": u.src[s:s + n], "dst_local": u.dst_local[s:s + n],
                "w": u.w[s:s + n], "valid": u.valid[s:s + n]}

    def _gather_device(self, rows: np.ndarray) -> dict:
        """Read pool rows back off the device (a block whose device rows
        a commit rewrote, with no host truth to read instead)."""
        ed = self.engine.edge_state
        r = jnp.asarray(rows)
        return {"src": np.asarray(ed.src[r]),
                "dst_local": np.asarray(ed.dstl[r]),
                "w": np.asarray(ed.w[r]),
                "valid": np.asarray(ed.valid[r])}

    def _payload_of(self, block: int) -> dict:
        """A block's tile rows, from truth > cache > disk segment."""
        if self.row_source is not None:
            return self.row_source(self.block_rows(block))
        payload = self._cache.get(block)
        if payload is not None:
            return payload
        if self._writer is None:
            raise KeyError(f"no spill payload for block {block}")
        self._writer.wait()  # the segment may still be in flight
        with np.load(self._writer.path(block)) as z:
            return {k: z[k] for k in self.PAYLOAD_FIELDS}

    def pool_rows(self, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Plan rows -> (mask of the rows whose block is resident, their
        pool rows): where a commit writes. The cache of every resident
        block written to is dropped (the device now holds its truth);
        without a host truth a spilled block's rows cannot be written."""
        rows = np.asarray(rows, dtype=np.int64)
        blk = self._row_block[rows]
        keep = self.resident[blk]
        if self.row_source is None and not keep.all():
            raise ValueError("rows of spilled blocks need a host truth "
                             "(SpillStore.row_source) to be written")
        for b in np.unique(blk[keep]):
            self._cache.pop(int(b), None)
        kb = blk[keep]
        return keep, self.start[kb] + rows[keep] - self.plan_start[kb]

    # -- the pool ------------------------------------------------------------
    def initial_edge_data(self, aux) -> EdgeData:
        """The engine's first :class:`EdgeData`: the pinned blocks, then
        the others in block order, each placed while its run fits, in a
        pool of ``rows`` rows built on the host and uploaded once."""
        u = self.engine.plan.unified
        order = np.concatenate([np.flatnonzero(self.pinned),
                                np.flatnonzero(~self.pinned)])
        at = 0
        for b in order:
            n = int(self.cnt[b])
            if n and at + n <= self.rows:
                self.start[b] = at
                self.resident[b] = True
                at += n
        tile = int(u.src.shape[1])
        pool = {"src": np.zeros((self.rows, tile), np.int32),
                "dst_local": np.zeros((self.rows, tile), np.int32),
                "w": np.zeros((self.rows, tile), np.float32),
                "valid": np.zeros((self.rows, tile), bool)}
        for b in np.flatnonzero(self.resident & (self.cnt > 0)):
            s, n = int(self.start[b]), int(self.cnt[b])
            for f, a in self._plan_payload(b).items():
                pool[f][s:s + n] = a
        if self._writer is not None:
            for b in np.flatnonzero(~self.resident):
                self._writer.submit(int(b), self._plan_payload(int(b)))
        cfg = self.engine.config
        return EdgeData(
            src=jnp.asarray(pool["src"]), dstl=jnp.asarray(pool["dst_local"]),
            w=jnp.asarray(pool["w"]), valid=jnp.asarray(pool["valid"]),
            cov=jnp.asarray(tile_coverage(pool["dst_local"], pool["valid"],
                                          cfg.subblocks,
                                          self.engine.plan.block_size)),
            aux=jnp.asarray(aux),
            start=jnp.asarray(self.start.astype(np.int32)),
            resident=jnp.asarray(self.resident))

    def _sync_table(self) -> None:
        """Hand the device the row-start table and residency mask, when
        they changed."""
        if not self._table_stale:
            return
        self._table_stale = False
        ed = self.engine.edge_state
        self.engine._ed = ed._replace(
            start=jnp.asarray(self.start.astype(np.int32)),
            resident=jnp.asarray(self.resident))

    def _gaps(self) -> list[list[int]]:
        """Free ranges ``[lo, hi)`` of the pool, in order."""
        occ = np.flatnonzero(self.resident & (self.cnt > 0))
        occ = occ[np.argsort(self.start[occ], kind="stable")]
        gaps, at = [], 0
        for b in occ:
            s = int(self.start[b])
            if s > at:
                gaps.append([at, s])
            at = s + int(self.cnt[b])
        if at < self.rows:
            gaps.append([at, self.rows])
        return gaps

    def _fit(self, blocks: np.ndarray) -> dict[int, int] | None:
        """First-fit starts for ``blocks`` (in the given order), or None
        when one finds no free range."""
        gaps = self._gaps()
        out = {}
        for b in blocks:
            n = int(self.cnt[b])
            for g in gaps:
                if g[1] - g[0] >= n:
                    out[int(b)] = g[0]
                    g[0] += n
                    break
            else:
                return None
        return out

    def compact(self) -> None:
        """Move the resident runs to the front of the pool, in the order
        they sit, so that the free rows are one range at the end."""
        occ = np.flatnonzero(self.resident & (self.cnt > 0))
        occ = occ[np.argsort(self.start[occ], kind="stable")]
        new = np.concatenate([[0], np.cumsum(self.cnt[occ])[:-1]]) \
            if occ.size else occ
        moved = occ[new != self.start[occ]]
        with obs_trace.span("compact", cat="ooc",
                            blocks=int(moved.size)) as sp:
            src = [self._pool_rows_of(int(b)) for b in moved]
            self.start[occ] = new
            self._table_stale = True
            dst = [self._pool_rows_of(int(b)) for b in moved]
            if moved.size:
                src, dst = np.concatenate(src), np.concatenate(dst)
                self.engine.move_pool_rows(src, dst)
                self.rows_moved += int(src.size)
            self.compactions += 1
            sp.set(rows=int(sum(self.cnt[moved])))

    def _place(self, blocks: np.ndarray) -> int:
        """Place non-resident blocks' runs in free ranges of the pool
        (largest first; compacting first when one does not fit) and write
        their rows. Returns the bytes written."""
        blocks = np.asarray(blocks, dtype=np.int64)
        blocks = blocks[~self.resident[blocks]]
        if blocks.size == 0:
            return 0
        blocks = blocks[np.lexsort((blocks, -self.cnt[blocks]))]
        starts = self._fit(blocks)
        if starts is None:
            self.compact()
            starts = self._fit(blocks)
        if starts is None:
            raise RuntimeError(
                f"out-of-core pool of {self.rows} rows has "
                f"{self.free_rows} free, cannot place "
                f"{int(self.cnt[blocks].sum())}")
        rows_l, parts = [], []
        for b in blocks:
            b = int(b)
            parts.append(self._payload_of(b))
            self.start[b] = starts[b]
            self.resident[b] = True
            self._table_stale = True
            rows_l.append(self._pool_rows_of(b))
        payload = {f: np.concatenate([p[f] for p in parts])
                   for f in self.PAYLOAD_FIELDS}
        written = self.engine.write_pool_rows(np.concatenate(rows_l),
                                              payload)
        self.bytes_fetched += written
        return written

    def prewarm(self) -> None:
        """Compile the placement and compaction calls with writes that
        change nothing (pool row 0 onto itself), so no run pays their
        compile."""
        eng = self.engine
        zero = np.zeros(1, dtype=np.int64)
        eng.move_pool_rows(zero, zero)
        ed = eng.edge_state
        row0 = {"src": np.asarray(ed.src[:1]),
                "dst_local": np.asarray(ed.dstl[:1]),
                "w": np.asarray(ed.w[:1]), "valid": np.asarray(ed.valid[:1])}
        big = eng._PLACE_CHUNKS[0]
        eng.write_pool_rows(np.zeros(big + 1, dtype=np.int64),
                            {f: np.repeat(a, big + 1, axis=0)
                             for f, a in row0.items()})

    # -- residency transitions ----------------------------------------------
    def evict(self, blocks: np.ndarray) -> None:
        """Free blocks' ranges of the pool. Their rows stay in the spill
        tier: the host truth or cache, or — read back from the device
        once a commit rewrote them — a fresh cache entry or disk
        segment."""
        blocks = np.asarray(blocks, dtype=np.int64)
        blocks = blocks[self.resident[blocks] & ~self.pinned[blocks]
                        & (self.cnt[blocks] > 0)]
        if blocks.size == 0:
            return
        with obs_trace.span("spill_evict", cat="ooc",
                            blocks=int(blocks.size)) as sp:
            if self.on_evict is not None:
                self.on_evict()  # pins must copy the epoch before it goes
            spilled0 = self.bytes_spilled
            for b in blocks:
                b = int(b)
                if self.row_source is None or self._writer is not None:
                    payload = (self.row_source(self.block_rows(b))
                               if self.row_source is not None
                               else self._cache.get(b))
                    if payload is None:
                        payload = self._gather_device(self._pool_rows_of(b))
                    if self.keep_host:
                        self._cache[b] = payload
                    if self._writer is not None:
                        self._writer.submit(b, payload)
                self.resident[b] = False
                self._table_stale = True
                self.bytes_spilled += self._payload_bytes(int(self.cnt[b]))
            self.spill_evictions += int(blocks.size)
            sp.set(bytes=int(self.bytes_spilled - spilled0))

    # -- the per-superstep / per-boundary driver entry points ---------------
    def admit(self, need: np.ndarray, psd_blk: np.ndarray,
              calm_blk: np.ndarray | None) -> None:
        """Make the demand set resident before the superstep runs,
        evicting the calmest unprotected residents until its rows fit.
        Counts hits (needed and already resident) vs misses (demand
        fetches the prefetcher failed to stage)."""
        need = np.asarray(need, dtype=np.int64)
        have = self.resident[need]
        self.prefetch_hits += int(have.sum())
        self.prefetch_misses += int(need.size - have.sum())
        missing = need[~have]
        with obs_trace.span("admit", cat="ooc",
                            blocks=int(missing.size)) as sp:
            rows = int(self.cnt[missing].sum())
            short = rows - self.free_rows
            evicted = 0
            if short > 0:
                protect = self.pinned.copy()
                protect[need] = True
                victims = policy.rank_victims(
                    psd_blk, policy.fold_calm(calm_blk),
                    self.resident & (self.cnt > 0), protect,
                    self.retire_after, retired_only=False)
                freed = np.cumsum(self.cnt[victims])
                evicted = int(np.searchsorted(freed, short)) + 1
                self.evict(victims[:evicted])
            placed = self._place(missing)
            self._sync_table()
            sp.set(rows=rows, bytes=int(placed), evicted=evicted)

    def prefetch_boundary(self, need_next: np.ndarray, psd_blk: np.ndarray,
                          calm_blk: np.ndarray | None) -> int:
        """Repartition-boundary prefetch: stage the predicted next demand
        plus the hottest non-resident blocks beyond it, filling free rows
        first and then swapping out RETIRED residents only (a speculative
        fetch must never evict the live active set). Returns the number of
        blocks staged."""
        need_next = np.asarray(need_next, dtype=np.int64)
        with obs_trace.span("prefetch", cat="ooc") as sp:
            protect = self.pinned.copy()
            protect[need_next] = True
            cand = policy.rank_fetch_candidates(psd_blk, self.resident,
                                                self.floor)
            # demand first (free, exact), then speculation by PSD rank
            cand = np.concatenate(
                [need_next[~self.resident[need_next]],
                 cand[~np.isin(cand, need_next)]])
            victims = policy.rank_victims(
                psd_blk, policy.fold_calm(calm_blk),
                self.resident & (self.cnt > 0), protect, self.retire_after,
                retired_only=True)
            free, vi = self.free_rows, 0
            staged: list[int] = []
            for b in cand:
                n = int(self.cnt[b])
                while free < n and vi < victims.size:
                    free += int(self.cnt[victims[vi]])
                    vi += 1
                if free < n:
                    break
                free -= n
                staged.append(int(b))
            self.evict(victims[:vi])
            placed = self._place(np.asarray(staged, dtype=np.int64))
            self._sync_table()
            sp.set(blocks=len(staged), bytes=int(placed), evicted=vi)
        return len(staged)

    # -- epoch-pin support ---------------------------------------------------
    def materialize(self) -> EdgeData:
        """A whole-plan :class:`EdgeData` copy, each block's run at its
        plan start: the pool's resident runs and the spill tier's truth
        for the rest — what ``edge_snapshot`` hands a pinned epoch so
        snapshot isolation survives eviction. Residency is unchanged."""
        u = self.engine.plan.unified
        if self.row_source is not None:
            full = self.row_source(np.arange(u.src.shape[0]))
        else:
            full = {f: getattr(u, f).copy() for f in self.PAYLOAD_FIELDS}
            live = np.flatnonzero(self.resident & (self.cnt > 0))
            read = [b for b in live if int(b) not in self._cache]
            if read:
                got = self._gather_device(np.concatenate(
                    [self._pool_rows_of(int(b)) for b in read]))
                at = 0
                for b in read:
                    n, s = int(self.cnt[b]), int(self.plan_start[b])
                    for f in self.PAYLOAD_FIELDS:
                        full[f][s:s + n] = got[f][at:at + n]
                    at += n
            for b in range(self.num_blocks):
                if self.cnt[b] and b not in read:
                    s, n = int(self.plan_start[b]), int(self.cnt[b])
                    for f, a in self._payload_of(b).items():
                        full[f][s:s + n] = a
        ed = self.engine.edge_state
        cfg = self.engine.config
        return EdgeData(
            src=jnp.asarray(full["src"], jnp.int32),
            dstl=jnp.asarray(full["dst_local"], jnp.int32),
            w=jnp.asarray(full["w"], jnp.float32),
            valid=jnp.asarray(full["valid"], bool),
            cov=jnp.asarray(tile_coverage(full["dst_local"], full["valid"],
                                          cfg.subblocks,
                                          self.engine.plan.block_size)),
            aux=jnp.array(ed.aux),
            start=jnp.asarray(u.tile_start, dtype=jnp.int32),
            resident=jnp.ones(self.num_blocks, dtype=bool))

    def wait(self) -> None:
        """Drain the async segment writer (tests / clean shutdown)."""
        if self._writer is not None:
            self._writer.wait()
