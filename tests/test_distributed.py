"""Distributed behavior on 8 virtual CPU devices. Each test runs in a
subprocess because the device count must be fixed before jax initializes
(the main test process keeps the default single device)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    prog = textwrap.dedent(body)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_lm_train_step_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import transformer as T
        from repro.train import optim as O
        from repro.train.loop import make_train_step
        cfg = get_arch('llama3-8b').smoke_config()
        params = T.init_params(cfg, jax.random.key(0))
        ocfg = O.OptimizerConfig(lr=1e-3)
        opt = O.init_opt_state(ocfg, params)
        toks = np.random.default_rng(0).integers(0, cfg.vocab, (8, 32)).astype(np.int32)
        batch = {'tokens': jnp.asarray(toks), 'labels': jnp.asarray(toks)}
        step = make_train_step(lambda p, b: T.loss_fn(p, cfg, b), ocfg)
        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, batch)
        # 4x2 mesh, batch sharded over data
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        bspec = {'tokens': P('data', None), 'labels': P('data', None)}
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(step, in_shardings=(None, None, bspec))(
                params, opt, batch)
        assert np.allclose(float(m1['loss']), float(m2['loss']), rtol=1e-4), \
            (float(m1['loss']), float(m2['loss']))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-5)
        print('OK sharded == single')
    """)
    assert "OK sharded == single" in out


def test_compressed_psum_shard_map():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.grad_compress import compressed_psum
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
        f = jax.shard_map(lambda v: compressed_psum(v[0], 'data'),
                          mesh=mesh, in_specs=P('data', None),
                          out_specs=P(None), check_vma=False)
        got = np.asarray(f(jnp.asarray(x)))
        exp = x.sum(0)
        rel = np.abs(got - exp).max() / np.abs(exp).max()
        assert rel < 0.02, rel
        print('OK compressed psum rel', rel)
    """)
    assert "OK compressed psum" in out


def test_pipeline_stage_permute():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import gpipe_forward
        mesh = jax.make_mesh((4, 2), ('pod', 'data'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        # 4 pipeline stages, each a linear layer
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.standard_normal((4, 16, 16)).astype(np.float32) * 0.3)
        x = jnp.asarray(rng.standard_normal((8, 16, 16)).astype(np.float32))
        y = gpipe_forward(mesh, ws, x, n_microbatches=8)
        # reference: sequential application
        ref = x
        for i in range(4):
            ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        print('OK pipeline')
    """)
    assert "OK pipeline" in out


def test_wcsd_query_engine_sharded_batch():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.generators import scale_free, random_queries
        from repro.core.wc_index import build_wc_index
        from repro.core.query import query_batch_jnp
        g = scale_free(100, 3, num_levels=3, seed=1)
        idx = build_wc_index(g)
        h, d, w, c = idx.padded_device_arrays()
        s, t, wl = random_queries(g, 64, seed=2)
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        with jax.set_mesh(mesh):
            f = jax.jit(query_batch_jnp,
                        in_shardings=(None, None, None, None,
                                      P('data'), P('data'), P('data')))
            got = np.asarray(f(jnp.asarray(h), jnp.asarray(d), jnp.asarray(w),
                               jnp.asarray(c), jnp.asarray(s), jnp.asarray(t),
                               jnp.asarray(wl)))
        exp = idx.query_batch(s, t, wl)
        assert np.array_equal(got, exp)
        print('OK sharded queries')
    """)
    assert "OK sharded queries" in out
