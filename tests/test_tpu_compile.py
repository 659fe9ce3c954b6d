"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Interpret mode cannot see what the TPU compiler refuses: block shapes off
the (8, 128) tiling, unsigned reductions, primitives Mosaic does not
lower.  These tests compile the serve path's Pallas kernel, and the
granite-3-2b deploy-parameter build, for one chip of a described
``v5e:2x2`` topology.

Only one process may load the TPU library, so the topology is described
inside a module fixture, never at import: every xdist worker then
collects the same tests, and only the worker given this file loads it.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.kernels.paged_attn import kernel as paged_kernel

HBM_BYTES = 16 * 1024**3  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can describe it here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("page", [32, 128])
@pytest.mark.parametrize("groups", [4, 8])
@pytest.mark.parametrize("d_h", [64, 128])
def test_paged_gather_decode_compiles_for_v5e(one_chip, d_h, groups, page):
    b, hkv, nblk, pages = 4, 8, 8, 64
    h, dhp = hkv * groups, packing.packed_len(d_h)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = (arg((b, h, dhp), jnp.uint32),
            arg((pages + 1, hkv, page, dhp), jnp.uint32),
            arg((pages + 1, hkv, d_h, page // packing.WORD), jnp.uint32),
            arg((b, nblk), jnp.int32), arg((b,), jnp.int32),
            arg((1,), jnp.int32), arg((b, h), jnp.int32))
    fn = jax.jit(lambda *a: paged_kernel.paged_gather_decode(
        *a, d_h=d_h, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_deploy_params_fit_one_chip(one_chip):
    from repro.configs import base
    from repro.models.lm import build_model
    model = build_model(base.get_config("granite-3-2b"))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lambda k: model.convert(model.init(k))).lower(
        key).compile()
    mem = compiled.memory_analysis()
    out, temp = mem.output_size_in_bytes, mem.temp_size_in_bytes
    # the packed params are a small share of the 10 GB fp32 latents
    assert out < 1e9, out
    assert out + temp < HBM_BYTES, (out, temp)
