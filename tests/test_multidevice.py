"""Real multi-device execution (subprocess: 8 fake CPU devices).

The main test process keeps jax at 1 device (per the dry-run rule), so the
sharded numeric checks run in a subprocess with
``--xla_force_host_platform_device_count=8``:

  * a reduced llama3 train step under a (4, 2) mesh with the production
    sharding rules must match the single-device step numerically;
  * the production-mesh dry-run lowering path (scaled mesh) compiles.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_config
        from repro.models.transformer import Model
        from repro.optim import adamw_init
        from repro.runtime.shard_plan import (Strategy, batch_specs, named,
                                              opt_specs, param_specs)
        from repro.runtime.steps import make_train_step

        assert len(jax.devices()) == 8
        cfg = dataclasses.replace(get_config('llama3-8b').reduced(),
                                  dtype='float32')
        model = Model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key)
        opt = adamw_init(params)
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab)
        batch = {'tokens': toks, 'labels': toks}
        step = make_train_step(model)

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((4, 2), ('data', 'model'))
        st = Strategy(attn='tp', ffn='tp')
        p_spec = param_specs(jax.eval_shape(lambda: params), mesh, st,
                             'train')
        p_sh = named(p_spec, mesh)
        o_sh = named(opt_specs(p_spec, None), mesh)
        b_sh = named(batch_specs(jax.eval_shape(lambda: batch), mesh), mesh)
        with mesh:
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                                 out_shardings=(p_sh, o_sh,
                                                NamedSharding(mesh, P()))
                                 )(params, opt, batch)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-4, (
            float(m1['loss']), float(m2['loss']))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)
        print('SHARDED_MATCH_OK')
    """)
    assert "SHARDED_MATCH_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_decode_step_sharded_compiles_and_runs():
    r = _run("""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.models.transformer import Model
        from repro.runtime.shard_plan import (Strategy, cache_specs, named,
                                              param_specs)
        cfg = dataclasses.replace(get_config('zamba2-1.2b').reduced(),
                                  dtype='float32')
        model = Model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key)
        cache = model.cache_init(8, 16)
        mesh = jax.make_mesh((4, 2), ('data', 'model'))
        st = Strategy()
        p_sh = named(param_specs(jax.eval_shape(lambda: params), mesh, st,
                                 'decode'), mesh)
        c_sh = named(cache_specs(jax.eval_shape(lambda: cache), mesh, st),
                     mesh)
        tok = jnp.zeros((8, 1), jnp.int32)
        with mesh:
            fn = jax.jit(model.decode_step, in_shardings=(p_sh, c_sh, None,
                                                          None))
            logits, cache2 = fn(params, cache, tok, jnp.int32(0))
        assert logits.shape == (8, 1, cfg.vocab)
        assert bool(jnp.isfinite(logits).all())
        print('DECODE_SHARDED_OK')
    """)
    assert "DECODE_SHARDED_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_shard_map_cnn_halo_exchange():
    """FlexPie InH partition as a REAL shard_map program: per-device conv
    shards with explicit collective_permute halo exchange reproduce the
    full conv."""
    r = _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()[:4]
        mesh = jax.make_mesh((4,), ('rows',), devices=devs)
        H, W, C = 32, 16, 8
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (H, W, C))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, C, C)) * 0.1

        def ref(x):
            return jax.lax.conv_general_dilated(
                x[None], w, (1, 1), [(1, 1), (1, 1)],
                dimension_numbers=('NHWC', 'HWIO', 'NHWC'))[0]

        @partial(jax.shard_map, mesh=mesh, in_specs=(P('rows', None, None),),
                 out_specs=P('rows', None, None))
        def sharded_conv(xs):
            # halo exchange: one boundary row from each neighbour
            up = jax.lax.ppermute(xs[-1:], 'rows',
                                  [(i, (i + 1) % 4) for i in range(4)])
            dn = jax.lax.ppermute(xs[:1], 'rows',
                                  [(i, (i - 1) % 4) for i in range(4)])
            idx = jax.lax.axis_index('rows')
            up = jnp.where(idx == 0, 0.0, up)      # top border: zero pad
            dn = jnp.where(idx == 3, 0.0, dn)
            xh = jnp.concatenate([up, xs, dn], axis=0)
            out = jax.lax.conv_general_dilated(
                xh[None], w, (1, 1), [(0, 0), (1, 1)],
                dimension_numbers=('NHWC', 'HWIO', 'NHWC'))[0]
            return out

        out = sharded_conv(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x)),
                                   atol=1e-4)
        print('SHARD_MAP_HALO_OK')
    """)
    assert "SHARD_MAP_HALO_OK" in r.stdout, r.stdout + r.stderr
