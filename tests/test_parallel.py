"""Multi-device (8 fake CPU devices) distribution tests.

Each case runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 so the main pytest process keeps seeing exactly one device
(required by the dry-run isolation policy)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str, timeout: int = 900) -> str:
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pjit_sharded_train_step_matches_single_device():
    run_in_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.configs.base import ShapeConfig
        from repro.models import registry
        from repro.parallel.sharding import build_param_specs
        from repro.train.optimizer import AdamWConfig, init_state

        cfg = dataclasses.replace(get_arch('qwen3-8b').reduced(),
                                  n_layers=2, d_model=64, vocab=128,
                                  n_heads=4, n_kv_heads=2, head_dim=16)
        bundle = registry.build(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=100)
        opt = init_state(opt_cfg, params)
        step = bundle.make_train_step(opt_cfg)
        shape = ShapeConfig('t', 32, 4, 'train')
        batch = registry.make_batch(cfg, shape)

        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        # sharded: mesh (data=2, model=4), Auto axes as the model's
        # GSPMD sharding expects (make_mesh defaults to Explicit)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pspecs = build_param_specs(
            jax.eval_shape(bundle.init, jax.random.PRNGKey(0)),
            model_axis_size=4)
        with jax.set_mesh(mesh):
            sh = lambda spec: NamedSharding(mesh, spec)
            params_s = jax.tree.map(
                lambda x, s: jax.device_put(x, sh(s)), params, pspecs)
            batch_s = {k: jax.device_put(v, sh(P('data', None)))
                       for k, v in batch.items()}
            opt_s = jax.device_put(opt, None)
            p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-4, \
            (float(m1['loss']), float(m2['loss']))
        a = jax.tree.leaves(p1)[0]; b = jax.tree.leaves(p2)[0]
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-4)
        print('pjit OK')
    """)


def test_explicit_mesh_is_refused_with_a_clear_error():
    """The LM tier shards through GSPMD constraints. Arrays placed on
    ``jax.make_mesh``'s default Explicit axes carry their sharding in
    their types; the forward pass refuses them up front, naming the
    remedy, rather than failing inside a gather or reshape. The same
    arrays on Auto axes, and unplaced arrays under an Explicit mesh (the
    dry-run's ``jit(in_shardings=...)`` path), run."""
    import dataclasses

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.models import registry
    from repro.models.transformer import forward

    cfg = dataclasses.replace(get_arch('qwen3-8b').reduced(),
                              n_layers=1, d_model=32, vocab=64,
                              n_heads=2, n_kv_heads=1, head_dim=16)
    bundle = registry.build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    tokens = registry.make_batch(cfg, ShapeConfig('t', 8, 2, 'train'))[
        "tokens"]
    fwd = jax.jit(lambda p, t: forward(p, cfg, t))

    def placed(mesh):
        put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
        return ({**params, "embed": put(params["embed"], P(None, 'model'))},
                put(tokens, P('data', None)))

    explicit = jax.make_mesh((1, 1), ('data', 'model'))
    auto = jax.make_mesh((1, 1), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(explicit):
        with pytest.raises(ValueError, match="AxisType.Auto"):
            fwd(*placed(explicit))
        assert fwd(params, tokens).shape == (2, 8, 64)
    with jax.set_mesh(auto):
        assert fwd(*placed(auto)).shape == (2, 8, 64)


def test_pipeline_parallel_matches_sequential():
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import (pipelined_forward,
            stack_stage_params, pipeline_utilization)

        mesh = jax.make_mesh((8,), ('stage',))
        L, D, M, MB = 16, 32, 6, 4
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, D, D)) * (D ** -0.5)

        def layer(wl, x):
            return jnp.tanh(x @ wl)

        def stage_fn(stage_w, x):
            def body(c, wl):
                return layer(wl, c), None
            y, _ = jax.lax.scan(body, x, stage_w)
            return y

        micro = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
        stage_w = stack_stage_params(w, 8)
        run = pipelined_forward(mesh, stage_fn)
        got = run(stage_w, micro)

        want = micro
        for l in range(L):
            want = jax.vmap(lambda x: layer(w[l], x))(want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        assert abs(pipeline_utilization(6, 8) - 6/13) < 1e-9
        print('pipeline OK')
    """)


def test_compressed_psum_across_devices():
    run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compression import (CompressionConfig,
            compressed_psum, init_residuals)

        mesh = jax.make_mesh((8,), ('data',))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.1
        grads = {'w': g}
        res = {'w': jnp.zeros((8, 64))}

        def body(gs, rs):
            return compressed_psum(gs, rs, 'data',
                                   CompressionConfig('int8_ef'))

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P('data', None), P('data', None)),
                    out_specs=(P(None), P('data', None))))
        # shard_map splits axis0; each worker sees (1, 64)
        mean_c, new_r = f(grads, res)
        want = np.asarray(g, np.float32).mean(axis=0, keepdims=True)
        got = np.asarray(mean_c['w'], np.float32)
        np.testing.assert_allclose(got, want, atol=2e-3)
        # error feedback residual = local grad - local dequantized
        assert float(np.abs(np.asarray(new_r['w'])).max()) < 2e-3
        # exact scheme is exact
        f0 = jax.jit(jax.shard_map(
            lambda gs, rs: compressed_psum(gs, rs, 'data',
                                           CompressionConfig('none')),
            mesh=mesh, in_specs=(P('data', None), P('data', None)),
            out_specs=(P(None), P('data', None))))
        mean_e, _ = f0(grads, res)
        np.testing.assert_allclose(np.asarray(mean_e['w'], np.float32),
                                   want, rtol=1e-6)
        print('compression OK')
    """)


def test_dryrun_machinery_small_mesh():
    """De-risks the production dry-run: AOT lower/compile + cost analysis
    on an 8-device mesh for a reduced arch."""
    run_in_subprocess("""
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.configs.base import ShapeConfig
        from repro.models import registry
        from repro.parallel.sharding import build_param_specs
        from repro.train.optimizer import AdamWConfig, init_state

        cfg = dataclasses.replace(get_arch('mixtral-8x7b').reduced(),
                                  n_layers=2)
        bundle = registry.build(cfg)
        opt_cfg = AdamWConfig()
        step = bundle.make_train_step(opt_cfg)
        shape = ShapeConfig('t', 32, 8, 'train')

        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        params_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        opt_shape = jax.eval_shape(lambda p: init_state(opt_cfg, p),
                                   params_shape)
        pspecs = build_param_specs(params_shape, n_experts=4,
                                   model_axis_size=4)
        ospecs = {'m': pspecs, 'v': pspecs, 'step': P()}
        from repro.models.registry import input_specs
        batch = input_specs(cfg, shape)
        sh = lambda s: NamedSharding(mesh, s)
        in_sh = (
            jax.tree.map(sh, pspecs),
            jax.tree.map(sh, ospecs),
            {k: sh(P('data', None)) for k in batch},
        )
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=in_sh).lower(
                params_shape, opt_shape, batch)
            compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        assert ca.get('flops', 0) > 0
        txt = compiled.as_text()
        assert 'all-reduce' in txt or 'all-gather' in txt
        print('dryrun-small OK, flops=%.3e' % ca['flops'])
    """)
