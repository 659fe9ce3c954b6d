"""Distribution tests in subprocesses with forced device counts: real
multi-device train step, FSDP spec assignment, elastic 8->4 rescale
(DESIGN.md §7.8/elastic), 1-bit all-reduce under shard_map."""
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env


def _run(n_devices: int, code: str) -> str:
    script = ("import os\n"
              f"os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS','') + "
              f"' --xla_force_host_platform_device_count={n_devices}'\n"
              + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", script],
                         env=subprocess_env(n_devices),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_step_on_8_devices():
    out = _run(8, """
        import jax, numpy as np
        from repro.configs import base
        from repro.models.lm import build_model
        from repro.data.synthetic import SyntheticStream
        from repro.optim.adamw import AdamW
        from repro.train.trainer import Trainer, TrainerConfig
        from repro.launch import mesh as mesh_lib

        cfg = base.get_smoke_config('smollm-135m')
        model = build_model(cfg)
        mesh = mesh_lib.make_mesh((4, 2), ('data', 'model'))
        tr = Trainer(model, AdamW(lr=1e-3), mesh, TrainerConfig())
        stream = SyntheticStream(cfg, 16, 8, seed=0)
        state = tr.init_state()
        for step in range(3):
            state, m = tr.train_step(state, stream.batch_at(step))
        print('LOSS', float(m['loss']))
    """)
    assert "LOSS" in out


def test_fsdp_specs_assignment():
    out = _run(8, """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_mesh((4, 2), ('data', 'model'))
        specs = {'w': P(None, 'model'), 'small': P(None), 'odd': P(None, None)}
        shapes = {'w': jax.ShapeDtypeStruct((16, 6), jnp.float32),
                  'small': jax.ShapeDtypeStruct((7,), jnp.float32),
                  'odd': jax.ShapeDtypeStruct((5, 3), jnp.float32)}
        out = mesh_lib.fsdp_specs(specs, shapes, mesh)
        assert out['w'] == P('data', 'model'), out['w']
        assert out['small'] == P(None)
        assert out['odd'] == P(None, None)
        print('FSDP OK')
    """)
    assert "FSDP OK" in out


def test_elastic_rescale_8_to_4():
    out = _run(8, """
        import jax, numpy as np, tempfile
        from repro.configs import base
        from repro.models.lm import build_model
        from repro.data.synthetic import SyntheticStream
        from repro.optim.adamw import AdamW
        from repro.train.trainer import Trainer, TrainerConfig
        from repro.train import ft
        from repro.checkpoint.ckpt import Checkpointer
        from repro.launch import mesh as mesh_lib

        cfg = base.get_smoke_config('smollm-135m')
        model = build_model(cfg)
        stream = SyntheticStream(cfg, 16, 8, seed=0)
        mesh8 = mesh_lib.make_mesh((4, 2), ('data', 'model'))
        tr8 = Trainer(model, AdamW(lr=1e-3), mesh8, TrainerConfig())
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            state = ft.run(tr8, stream, ck, steps=2, ckpt_every=0,
                           log_every=100, log_fn=lambda s: None)
            # rescale: same checkpoint, (2,2) mesh of 4 devices
            mesh4 = mesh_lib.make_mesh((2, 2), ('data', 'model'))
            tr4 = Trainer(model, AdamW(lr=1e-3), mesh4, TrainerConfig())
            st4, dstep, _ = ft.elastic_restore(ck, tr4)
            for x, y in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(st4.params)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            st4, m = tr4.train_step(st4, stream.batch_at(dstep))
            print('ELASTIC OK', float(m['loss']))
    """)
    assert "ELASTIC OK" in out


def test_allreduce_1bit_shard_map():
    out = _run(4, """
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.launch import mesh as mesh_lib
        from repro.optim.compress import allreduce_1bit

        mesh = mesh_lib.make_mesh((4,), ('data',))
        g = jnp.asarray(np.random.default_rng(0)
                        .normal(size=(4, 64)).astype(np.float32))

        @partial(jax.shard_map, mesh=mesh, in_specs=P('data', None),
                 out_specs=P('data', None))
        def reduce(local):
            return allreduce_1bit(local[0], 'data')[None]

        got = reduce(g)
        # every shard sees the same averaged sign aggregate
        want = np.mean([np.sign(np.asarray(g[i])) *
                        np.abs(np.asarray(g[i])).mean()
                        for i in range(4)], axis=0)
        np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got[1]), want, rtol=1e-5)
        print('1BIT OK')
    """)
    assert "1BIT OK" in out


def test_activation_sharding_context():
    out = _run(4, """
        import jax, jax.numpy as jnp
        from repro.launch import mesh as mesh_lib
        from repro.models.sharding import activation_sharding, constrain
        mesh = mesh_lib.make_mesh((2, 2), ('data', 'model'))
        x = jnp.ones((4, 8))
        # no-op outside the context
        assert constrain(x, 'batch', None) is x
        with activation_sharding(mesh, ('data',)):
            with mesh:
                y = jax.jit(lambda t: constrain(t, 'batch', 'model'))(x)
        print('CTX OK', y.sharding.spec)
    """)
    assert "CTX OK" in out
