"""Ahead-of-time compiles for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  These tests guard what interpret-mode and
XLA-path tests cannot see — Mosaic's block-shape and VMEM rules, and
whether a real-width step program compiles at all:

  * the four Pallas kernels at real widths (qwen3-1.7b decode geometry,
    mamba2-130m chunk geometry, qwen3's embedding table, qwen3's layer
    matrices), each lowered with Mosaic (``tpu_custom_call`` in the HLO);
  * the weight-streaming kernel at minicpm3-4b's widths;
  * the prefill attention kernel at qwen3-1.7b's and minicpm3-4b's
    prefill shapes, and qwen3-1.7b's prefill step at the benchmark's
    batch, which holds no float32 score block;
  * the qwen3-1.7b full-width decode step on one chip, and minicpm3-4b's
    at its published widths and the benchmark's 31 layers, whose layer
    matrices are read by the weight-streaming kernel (all of qwen3's;
    all of minicpm3's but the two that its latent attention applies per
    head), with no bfloat16 copy of a stack.

The topology is described inside a module fixture (only the pytest
worker that runs this file loads the TPU compiler), and the persistent
compilation cache is off around the compiles.
"""
import os
import re
from collections import Counter

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


@pytest.mark.parametrize("k,widths", [
    (2048, (2048, 1024, 1024)),     # q, k, v
    (2048, (2048,)),                # o
    (2048, (6144, 6144)),           # gate, up
    (6144, (2048,)),                # down
    (14336, (4096,)),               # llama-3-8b's down: K-tiled
    (2560, (768, 288)),             # minicpm3-4b's q_a and kv_a: K-tiled
    (768, (3840,)),                 # minicpm3-4b's q_b
    (2560, (2560,)),                # minicpm3-4b's o
    (2560, (6400, 6400)),           # minicpm3-4b's gate, up
    (6400, (2560,)),                # minicpm3-4b's down
])
def test_weight_stream_compiles_with_mosaic(one_chip, k, widths):
    from repro.kernels import weight_stream

    c = _compile(lambda x, l, *ws: weight_stream(x, ws, l, mode="pallas"),
                 _shape(one_chip, (8, 1, k), jnp.bfloat16),
                 _shape(one_chip, (), jnp.int32),
                 *[_shape(one_chip, (28, k, n), jnp.float32) for n in widths])
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (4, 16, 8, 2048, 128),          # qwen3-1.7b's prefill
    (1, 40, 40, 16384, 96),         # minicpm3-4b's 16k document (MLA)
])
def test_flash_prefill_compiles_with_mosaic(one_chip, b, hq, hkv, s, d):
    from repro.kernels import flash_prefill

    kv = _shape(one_chip, (b, hkv, s, d), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_prefill(q, k, v, mode="pallas"),
                 _shape(one_chip, (b, hq, s, d), jnp.bfloat16), kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_qwen3_prefill_step_holds_no_score_block(one_chip, monkeypatch):
    """qwen3-1.7b's prefill step at the prefill cell's batch of 4 x 2048:
    attention runs in the kernel, so the program holds none of the XLA
    scan's f32[4,8,2,2048,1024] score blocks, and fewer temporaries
    than the scan's 4,330,360,832 bytes."""
    from repro.configs import get_arch
    from repro.configs.base import RuntimeConfig
    from repro.launch.steps import make_prefill_step
    from repro.models import DTypePolicy, init_model

    arch, policy = get_arch("qwen3-1.7b"), DTypePolicy.standard()
    params = jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: init_model(k, arch, policy),
                       jax.random.PRNGKey(0)))
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    c = _compile(make_prefill_step(arch, RuntimeConfig(remat="none"),
                                   policy, 2049),
                 params, {"tokens": _shape(one_chip, (4, 2048), jnp.int32)})
    hlo = c.as_text()
    assert "flash_prefill" in hlo and "tpu_custom_call" in hlo
    assert "f32[4,8,2,2048,1024]" not in hlo
    assert c.memory_analysis().temp_size_in_bytes < 4_330_360_832


def _decode_step_streams_every_stack(one_chip, monkeypatch, arch, seq_len,
                                     streamed):
    """Compile ``arch``'s decode step for one described v5e and check
    that every float32 layer stack named in ``streamed`` is read whole by
    a kernel and that no stack is cast to bfloat16."""
    from repro.configs.base import RuntimeConfig
    from repro.launch.steps import make_decode_step
    from repro.models import DTypePolicy, init_model, make_cache

    policy = DTypePolicy.standard()

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda k: init_model(k, arch, policy), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: make_cache(arch, seq_len, 8,
                                                    policy)))
    # the model asks the backend for its kernel mode, and sees the CPU
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    c = _compile(make_decode_step(arch, RuntimeConfig(remat="none"), policy),
                 params, cache, _shape(one_chip, (8, 1), jnp.int32))
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, f"decode step needs {used / 2**30:.1f} GiB"
    # every layer matrix is read as its f32 stack by a kernel, and no
    # stack is cast: the program holds no bf16 copy of the stacks (2.8 GB)
    hlo = c.as_text()
    blocks = params["blocks"]
    stacks = [x for x in jax.tree.leaves(blocks) if x.ndim == 3]
    shape = lambda x: f"[{','.join(map(str, x.shape))}]"     # noqa: E731
    assert not [ln for ln in hlo.splitlines()
                if any(f"= bf16{shape(x)}" in ln and " convert(" in ln
                       for x in stacks)]
    read = Counter(m for ln in hlo.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln
                   for m in re.findall(r"f32(\[\d+,\d+,\d+\])", ln))
    assert read == Counter(shape(blocks[g][n]) for g, n in streamed)
    bf16_stacks = sum(x.size * 2 for x in stacks)
    assert mem.temp_size_in_bytes < bf16_stacks
    return mem


def test_qwen3_decode_step_compiles_full_width(one_chip, monkeypatch):
    from repro.configs import get_arch

    streamed = [("attn", n) for n in ("wq", "wk", "wv", "wo")] + [
        ("mlp", n) for n in ("w_gate", "w_up", "w_down")]
    _decode_step_streams_every_stack(one_chip, monkeypatch,
                                     get_arch("qwen3-1.7b"), 1152, streamed)


def test_minicpm3_decode_step_compiles_full_width(one_chip, monkeypatch):
    """minicpm3-4b at its published widths, cut to the benchmark's 31
    layers, with the benchmark cell's batch and cache: the MLA and MLP
    stacks are each read whole by the kernel; W_UK and W_UV, which the
    latent attention applies per head, are sliced per layer in float32
    and cast as no stack; and the step fits one chip."""
    import dataclasses

    from repro.configs import get_arch

    arch = dataclasses.replace(get_arch("minicpm3-4b"), n_layers=31)
    streamed = [("attn", n) for n in ("wq_a", "wkv_a", "wq_b", "wo")] + [
        ("mlp", n) for n in ("w_gate", "w_up", "w_down")]
    mem = _decode_step_streams_every_stack(one_chip, monkeypatch, arch,
                                           16896, streamed)
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held < 16 * 2**30, f"decode step holds {held / 2**30:.1f} GiB"
