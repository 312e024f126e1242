"""shard_map execution of the structure-aware engine (paper Alg. 3's
master/mirror update).

Topology: the schedule width W = (devices on the data axis) x
(blocks-per-device). Each device runs its assigned blocks *sequentially*
(async semantics within the device, the paper's hot mode), then replicas are
reconciled once per call:

  * sum-combine programs (PageRank): blocks are disjoint across devices, so
    the update is an additive delta -> ``psum(values_local - values_in)``
    (Alg. 3 ``master <- mirror vertex update``);
  * min/max programs (SSSP/BFS/CC): ``pmin``/``pmax`` over replicas is exact
    because the combine is idempotent (``mirror <- master``).

PSDs are reconciled by masked ``pmax`` (each block is processed by at most
one device per call).

Cross-device visibility of hot updates happens at call boundaries — the same
relaxation PowerSwitch makes when it distributes its async mode. Vertex state
is replicated per device here (it is O(n) floats); graphs whose state
exceeds a device need a sharded-state variant (boundary deltas only).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.algorithms import VertexProgram
from repro.core.engine import (EngineConfig, StructureAwareEngine,
                               make_block_processor)
from repro.core.graph import Graph
from repro.core.partition import EdgeStorage

_NEG = np.float32(-1e38)


def default_mesh(axis: str = "data") -> Mesh:
    devs = np.array(jax.devices())
    return Mesh(devs, (axis,))


class DistributedEngine(StructureAwareEngine):
    """Drop-in engine with shard_map block processing over a mesh axis."""

    def __init__(self, graph: Graph, program: VertexProgram,
                 config: EngineConfig = EngineConfig(),
                 mesh: Mesh | None = None, axis: str = "data",
                 blocks_per_device: int | None = None):
        self.mesh = mesh or default_mesh(axis)
        self.axis = axis
        self.ndev = self.mesh.shape[axis]
        bpd = blocks_per_device or max(1, config.width // self.ndev)
        # shard_map dispatch is host-driven (fused=False): the mesh routing
        # happens per call, not inside a device-resident while_loop. The
        # adaptive active-set model is disabled: the dispatch width IS the
        # mesh (devices x blocks-per-device) — shrinking it would idle
        # devices, and the per-rank depth ladder would skew the round-robin
        # load balance this engine relies on. Sub-block tracking is pinned
        # flat too: the group-padded storages this engine dispatches over
        # have no masked sweep path (per-shard sub-block balance is the
        # follow-up the hierarchical plan sets up, not this engine).
        config = dataclasses.replace(config, width=self.ndev * bpd,
                                     fused=False, adaptive=False,
                                     subblocks=1)
        self.bpd = bpd
        super().__init__(graph, program, config)

    def run(self, max_iterations: int | None = None,
            fused: bool | None = None, warm=None):
        """shard_map dispatch is host-driven; the single-device fused chunk
        would silently ignore the mesh, so asking for it is an error (and
        warm streaming restarts are not distributed yet)."""
        if fused:
            raise ValueError(
                "DistributedEngine does not support the fused loop: "
                "dispatch is routed through shard_map per host call")
        if warm is not None:
            raise ValueError(
                "DistributedEngine does not support warm restarts yet")
        return super().run(max_iterations, fused=False)

    def _get_fn(self, store_key: str, sequential: bool):
        key = (store_key, sequential, "dist")
        if key in self._fns:
            return self._fns[key]
        store: EdgeStorage = getattr(self.plan, store_key)
        program, plan = self.program, self.plan
        c = plan.block_size
        process_one, process_iterated, gids = make_block_processor(
            program, store, self.aux, c, plan.n_live, plan.graph.n,
            self.config.use_pallas)
        t_inner = max(self.config.hot_inner_iters, 1) if sequential else 1
        bpd, axis, nblocks = self.bpd, self.axis, plan.num_blocks

        def device_run(values, psd, dmax, rows, ok):
            # local shapes: values (n,), psd/dmax (P,), rows (bpd,), ok (bpd,)
            values_in = values
            psd_in, dmax_in = psd, dmax

            def body(i, carry):
                values, psd, dmax, bmask = carry
                row = rows[i]
                base, new, psd_val, dmax_val = process_iterated(
                    values, row, t_inner)
                cur = lax.dynamic_slice(values, (base,), (c,))
                values = lax.dynamic_update_slice(
                    values, jnp.where(ok[i], new, cur), (base,))
                gid = gids[row]
                psd = jnp.where(ok[i], psd.at[gid].set(psd_val), psd)
                dmax = jnp.where(ok[i], dmax.at[gid].set(dmax_val), dmax)
                bmask = jnp.where(ok[i], bmask.at[gid].set(True), bmask)
                return values, psd, dmax, bmask

            bmask0 = jnp.zeros((nblocks,), bool)
            values_l, psd_l, dmax_l, bmask = lax.fori_loop(
                0, bpd, body, (values, psd, dmax, bmask0))

            if program.combine == "sum":
                values_out = values_in + lax.psum(values_l - values_in, axis)
            elif program.combine == "min":
                values_out = lax.pmin(values_l, axis)
            else:
                values_out = lax.pmax(values_l, axis)

            def reconcile(local, base_in):
                # psd/dmax carry a trailing (singleton) sub-block axis
                m = bmask[:, None] if local.ndim == 2 else bmask
                masked = jnp.where(m, local, _NEG)
                mx = lax.pmax(masked, axis)
                return jnp.where(mx > _NEG / 2, mx, base_in)

            return values_out, reconcile(psd_l, psd_in), \
                reconcile(dmax_l, dmax_in)

        smapped = jax.shard_map(
            device_run, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(self.axis), P(self.axis)),
            out_specs=(P(), P(), P()), check_vma=False)
        fn = jax.jit(smapped, donate_argnums=(0, 1, 2))
        self._fns[key] = fn
        return fn

    def compiled_hot_dispatch(self) -> jax.stages.Compiled:
        """The compiled program of one hot-group dispatch — what a caller
        inspects to see how the work is laid out over the mesh
        (``input_shardings`` of the block rows, collectives in
        ``as_text()``)."""
        ps = (self.plan.num_blocks, self.config.subblocks)
        w = self.ndev * self.bpd
        return self._get_fn("hot", True).lower(
            jnp.zeros(self._values_len, jnp.float32),
            jnp.zeros(ps, jnp.float32), jnp.zeros(ps, jnp.float32),
            jnp.zeros(w, jnp.int32), jnp.zeros(w, bool)).compile()

    def _dispatch(self, values, psd, dmax, block_ids: np.ndarray,
                  sequential: bool, width: int | None = None):
        """Pad selection to (ndev * bpd) slots, round-robin across devices.
        ``width`` is accepted for base-class compatibility and ignored —
        the mesh fixes this engine's dispatch width (adaptive is pinned
        off in __init__)."""
        p, w = self.plan, self.ndev * self.bpd
        for store_key, cond in (("hot", block_ids < p.barrier_block),
                                ("cold", block_ids >= p.barrier_block)):
            ids = block_ids[cond]
            if ids.size == 0:
                continue
            offset = 0 if store_key == "hot" else p.barrier_block
            for at in range(0, ids.size, w):
                chunk = ids[at:at + w]
                rows = np.zeros(w, dtype=np.int32)
                ok = np.zeros(w, dtype=bool)
                # round-robin so each device's sequential sweep covers a
                # spread of priorities (straggler-friendly: equal bpd each)
                idx = np.arange(chunk.size)
                slot = (idx % self.ndev) * self.bpd + idx // self.ndev
                rows[slot] = (chunk - offset).astype(np.int32)
                ok[slot] = True
                fn = self._get_fn(store_key, sequential)
                with self.mesh:
                    values, psd, dmax = fn(values, psd, dmax,
                                           jnp.asarray(rows),
                                           jnp.asarray(ok))
        return values, psd, dmax
