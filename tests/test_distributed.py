"""Distributed tests run in subprocesses with forced host device counts
(so the main pytest process keeps its single real device)."""
import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str, devices: int = 8, timeout: int = 900):
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd="/root/repo")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_graph_engine_parity():
    run_sub("""
        import numpy as np
        from repro.core import algorithms as A, graph as G
        from repro.core.distributed import DistributedEngine
        from repro.core.engine import EngineConfig, StructureAwareEngine
        g = G.core_periphery_graph(6000, avg_deg=8, seed=1, chords=1)
        cfg = EngineConfig(t2=1e-9, width=8, block_size=256,
                           hot_inner_iters=4)
        local = StructureAwareEngine(g, A.pagerank(), cfg).run()
        dist = DistributedEngine(g, A.pagerank(), cfg,
                                 blocks_per_device=1).run()
        assert dist.metrics.converged
        assert np.allclose(local.values, dist.values, rtol=1e-4, atol=1e-8)
        # min-combine (SSSP) through pmin reconciliation
        g2 = G.powerlaw_graph(3000, 6, seed=3, weighted=True)
        l2 = StructureAwareEngine(g2, A.sssp(0), cfg).run()
        d2 = DistributedEngine(g2, A.sssp(0), cfg, blocks_per_device=1).run()
        assert np.allclose(np.minimum(l2.values, 1e18),
                           np.minimum(d2.values, 1e18), rtol=1e-5, atol=1e-3)
        print('PARITY OK')
    """)


def test_distributed_hot_dispatch_spans_mesh():
    run_sub("""
        import numpy as np
        from repro.core import algorithms as A, graph as G
        from repro.core.distributed import DistributedEngine
        from repro.core.engine import EngineConfig
        g = G.powerlaw_graph(3000, 6, seed=3)
        dist = DistributedEngine(g, A.cc(), EngineConfig(block_size=256))
        compiled = dist.compiled_hot_dispatch()
        rows = compiled.input_shardings[0][3]
        assert len(rows.device_set) == 4, rows
        assert "all-reduce" in compiled.as_text()
        print('SPREAD OK')
    """, devices=4)


def test_sharded_train_step_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.data import SyntheticLM
        from repro.launch import sharding as shard_lib
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as M
        from repro.optim import AdamWConfig, adamw_init
        from repro.train.step import make_train_step

        cfg = configs.reduced(configs.get('qwen3_14b'))
        data = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
        opt = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
        step = make_train_step(cfg, opt)

        params = M.init_params(cfg, jax.random.PRNGKey(0))
        state0 = {'params': params, 'opt': adamw_init(params)}
        s_ref, m_ref = jax.jit(step)(jax.tree.map(jnp.copy, state0),
                                     data.batch(0))

        mesh = make_host_mesh(model=2)  # (4, 2) data x model
        sspecs = shard_lib.state_specs(
            jax.eval_shape(lambda: state0), mesh)
        bspec = {'tokens': NamedSharding(mesh, P('data', None)),
                 'targets': NamedSharding(mesh, P('data', None))}
        state = jax.device_put(state0, sspecs)
        batch = jax.device_put(data.batch(0), bspec)
        jstep = jax.jit(step, in_shardings=(sspecs, bspec),
                        out_shardings=(sspecs, None))
        s_sh, m_sh = jstep(state, batch)
        np.testing.assert_allclose(float(m_ref['loss']),
                                   float(m_sh['loss']), rtol=1e-4)
        # Adam's first step is ~sign(g)*lr; sharded bf16 reductions can
        # flip signs of near-zero grads, so tolerate a few lr units.
        for a, b in zip(jax.tree.leaves(s_ref['params']),
                        jax.tree.leaves(s_sh['params'])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-2, atol=5e-3)
        print('SHARDED TRAIN OK')
    """)


def test_elastic_reshard_checkpoint():
    """Save under an 8-device mesh, restore under a 4-device mesh."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.ckpt import CheckpointManager

        devs = np.array(jax.devices())
        mesh8 = Mesh(devs.reshape(4, 2), ('data', 'model'))
        mesh4 = Mesh(devs[:4].reshape(2, 2), ('data', 'model'))
        tree = {'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        sh8 = {'w': NamedSharding(mesh8, P('data', 'model'))}
        sh4 = {'w': NamedSharding(mesh4, P('data', 'model'))}
        t8 = jax.device_put(tree, sh8)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_write=False)
            mgr.save(1, t8)
            restored, _ = mgr.restore(shardings=sh4)
            np.testing.assert_array_equal(np.asarray(restored['w']),
                                          np.asarray(tree['w']))
            assert restored['w'].sharding == sh4['w']
        print('RESHARD OK')
    """)


def test_ef_compressed_psum_in_shard_map():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim import ef_compress_psum

        mesh = Mesh(np.array(jax.devices()), ('pod',))
        def f(g, r):
            return ef_compress_psum(g, r, 'pod')
        g = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 100.
        r = jnp.zeros((8, 16))
        sm = jax.shard_map(f, mesh=mesh, in_specs=(P('pod'), P('pod')),
                           out_specs=(P('pod'), P('pod')), check_vma=False)
        out, resid = jax.jit(sm)(g, r)
        want = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-3)
        print('EF PSUM OK')
    """)


def test_dryrun_plumbing_small_mesh():
    """The dry-run machinery end-to-end on a (2,2,2) toy pod mesh."""
    run_sub("""
        import jax
        from repro.launch import dryrun as dr
        from repro import configs
        from repro.models.config import ShapeConfig, SHAPES
        import dataclasses
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        # tiny shape grid against the reduced config
        SHAPES['t_train'] = ShapeConfig('t_train', 64, 8, 'train')
        SHAPES['t_dec'] = ShapeConfig('t_dec', 64, 8, 'decode')
        cfg = configs.reduced(configs.get('granite_moe_3b_a800m'))
        import repro.configs as C
        orig = C.get
        C.get = lambda name: cfg
        try:
            for shp in ('t_train', 't_dec'):
                r = dr.lower_cell('granite_moe_3b_a800m', shp, mesh, 'toy')
                assert r['status'] == 'ok', r
                assert r['flops'] > 0
                assert r['peak_bytes'] > 0
        finally:
            C.get = orig
        g = dr.lower_graph_cell(mesh, 'toy', n=65536, block_size=4096,
                                e_cap=8192)
        assert g['status'] == 'ok' and g['collective_bytes'] > 0
        print('DRYRUN PLUMBING OK')
    """, devices=8)
