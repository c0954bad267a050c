"""Ahead-of-time compiles for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  These tests guard what interpret-mode and
XLA-path tests cannot see — Mosaic's block-shape and VMEM rules, and
whether a real-width step program compiles at all:

  * the three Pallas kernels at real widths (qwen3-1.7b decode geometry,
    mamba2-130m chunk geometry, qwen3's embedding table), each lowered
    with Mosaic (``tpu_custom_call`` in the HLO);
  * the qwen3-1.7b full-width decode step on one chip.

The topology is described inside a module fixture (only the pytest
worker that runs this file loads the TPU compiler), and the persistent
compilation cache is off around the compiles.
"""
import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_kv_decode_compiles_with_mosaic(one_chip):
    from repro.kernels import kv_decode

    b, hq, hkv, s, d = 8, 16, 8, 4096, 128
    kv = _shape(one_chip, (b, hkv, s, d), jnp.bfloat16)
    c = _compile(lambda q, k, v, lens: kv_decode(q, k, v, lens, n_banks=8,
                                                 mode="pallas"),
                 _shape(one_chip, (b, hq, d), jnp.bfloat16), kv, kv,
                 _shape(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_chunk_compiles_with_mosaic(one_chip):
    from repro.kernels import ssd_chunk

    bt, h, q, p, n = 2, 24, 256, 64, 128
    f32 = jnp.float32
    c = _compile(lambda *a: ssd_chunk(*a, mode="pallas"),
                 _shape(one_chip, (bt, h, q, p), f32),
                 _shape(one_chip, (bt, h, q), f32),
                 _shape(one_chip, (bt, h, q), f32),
                 _shape(one_chip, (bt, q, n), f32),
                 _shape(one_chip, (bt, q, n), f32),
                 _shape(one_chip, (bt, h, p, n), f32))
    assert "tpu_custom_call" in c.as_text()


def test_amm_gather_compiles_with_mosaic(one_chip):
    from repro.kernels import amm_gather

    c = _compile(lambda t, i: amm_gather(t, i, n_banks=4, mode="pallas"),
                 _shape(one_chip, (151936, 2048), jnp.bfloat16),
                 _shape(one_chip, (512,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_qwen3_decode_step_compiles_full_width(one_chip):
    from repro.configs import get_arch
    from repro.configs.base import RuntimeConfig
    from repro.launch.steps import make_decode_step
    from repro.models import DTypePolicy, init_model, make_cache

    arch = get_arch("qwen3-1.7b")
    policy = DTypePolicy.standard()

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda k: init_model(k, arch, policy), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: make_cache(arch, 1152, 8, policy)))
    c = _compile(make_decode_step(arch, RuntimeConfig(remat="none"), policy),
                 params, cache, _shape(one_chip, (8, 1), jnp.int32))
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, f"decode step needs {used / 2**30:.1f} GiB"
