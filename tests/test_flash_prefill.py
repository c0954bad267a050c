"""The prefill attention kernel (``kernels/flash_prefill.py``) against a
dense float32 oracle (``kernels/ref.py``) and the XLA block scan it
replaces in the prefill programs, and the dispatch that picks it.

The scan's compiled program on the TPU multiplies its float32 operands
in one bfloat16 pass (the default precision there); on the CPU the same
einsums are exact.  So the scan is held to the oracle as the chip runs
it (its einsums' operands rounded to bfloat16, with float32 results),
and the kernel to the scan's own distance from the oracle."""
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, tiny_variant
from repro.configs.base import RuntimeConfig
from repro.kernels.ref import causal_attention_ref
from repro.launch.sharding import activation_sharding
from repro.models import attention, init_model, loss_fn
from repro.models.attention import (AttnConfig, gqa_init, gqa_prefill,
                                    mla_init, mla_prefill)

# the package exports the function under the module's name
kernel_module = sys.modules["repro.kernels.flash_prefill"]

RT = RuntimeConfig(remat="none")
BF16 = jnp.bfloat16


def _chip_scan(monkeypatch, q, k, v):
    """``_flash_block_scan`` with its einsums at the TPU's default
    precision: each float32 operand rounded to bfloat16, float32 out."""
    chip = types.SimpleNamespace(**{n: getattr(jnp, n) for n in dir(jnp)
                                    if not n.startswith("__")})
    chip.einsum = lambda spec, *ops, **kw: jnp.einsum(
        spec, *(o.astype(BF16) for o in ops),
        preferred_element_type=jnp.float32)
    with monkeypatch.context() as m:
        m.setattr(attention, "jnp", chip)
        return attention._flash_block_scan(q, k, v, True, 0, 1024)


def _qkv(b, hq, hkv, s, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, s, d)).astype(BF16),
            jax.random.normal(ks[1], (b, hkv, s, d)).astype(BF16),
            jax.random.normal(ks[2], (b, hkv, s, d)).astype(BF16))


def _distance(x, ref):
    err = jnp.abs(x.astype(jnp.float32) - ref)
    return float(err.max()), float(err.mean())


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("d", [128, 96])
@pytest.mark.parametrize("b,s,rows,keys", [
    (2, 64, 1024, 1024), (1, 64, 32, 16), (2, 64, 16, 32), (2, 40, 32, 16)],
    ids=["one-block", "more-queries-than-keys", "more-keys-than-queries",
         "padded-tail"])
def test_kernel_holds_the_scans_distance_from_the_oracle(
        monkeypatch, g, d, b, s, rows, keys):
    hkv = 2
    q, k, v = _qkv(b, g * hkv, hkv, s, d)
    ref = causal_attention_ref(q, k, v)
    scan = _chip_scan(monkeypatch, q, k, v)
    s_max, s_mean = _distance(scan, ref)
    for mode in ("xla", "interpret"):
        out = kernel_module.flash_prefill(q, k, v, mode=mode, rows=rows,
                                          keys=keys)
        assert out.shape == q.shape and out.dtype == q.dtype
        k_max, k_mean = _distance(out, ref)
        # both round the output to bfloat16 (the largest error) and the
        # probabilities to bfloat16; their blocks rescale at other keys
        assert k_max <= s_max * 1.05 + 1e-6, (mode, k_max, s_max)
        assert k_mean <= s_mean * 1.05 + 1e-7, (mode, k_mean, s_mean)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(scan, np.float32), atol=2e-2)


def test_blocks_and_the_pairs_the_grid_visits():
    sizes = kernel_module.block_sizes
    assert sizes(2048, 2) == (512, 1024, 2048)      # qwen3-1.7b
    assert sizes(16384, 1) == (1024, 1024, 16384)   # minicpm3-4b
    assert sizes(2048, 3) == (256, 1024, 2048)
    assert sizes(600, 2) == (512, 1024, 1024)
    assert sizes(40, 2) == (64, 64, 64)
    assert sizes(40, 2, 32, 16) == (16, 16, 48)
    # four query blocks of 512 against key blocks of 1024: the first two
    # see key block 0 only, the last two both; 6 of 8 pairs
    q_of, k_of = kernel_module._pairs(4, 512, 1024)
    assert q_of.tolist() == [0, 1, 2, 2, 3, 3]
    assert k_of.tolist() == [0, 0, 0, 1, 0, 1]
    q_of, k_of = kernel_module._pairs(3, 16, 16)
    assert list(zip(q_of.tolist(), k_of.tolist())) == [
        (0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2)]


def _gqa():
    cfg = AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, head_dim=32,
                     qk_norm=True)
    return cfg, gqa_init(jax.random.PRNGKey(1), cfg), gqa_prefill


def _mla():
    cfg = AttnConfig(d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                     attn_type="mla", q_lora_rank=32, kv_lora_rank=16,
                     rope_head_dim=8)
    return cfg, mla_init(jax.random.PRNGKey(1), cfg), mla_prefill


def _one_device_mesh():
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    return jax.sharding.Mesh(devs, ("data", "model"))


@pytest.mark.parametrize("build", [_gqa, _mla], ids=["gqa", "mla"])
def test_prefill_takes_the_kernel_unless_partitioned(monkeypatch, build):
    """Unpartitioned, ``gqa_prefill`` and ``mla_prefill`` run the kernel
    (a ``pallas_call`` named ``flash_prefill`` in interpret mode); under
    a launcher's activation sharder they run the scan, to the same
    output within bfloat16 rounding."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg, params, prefill_fn = build()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64)).astype(BF16)
    params = jax.tree.map(lambda w: w.astype(BF16), params)
    run = lambda p, xx: prefill_fn(p, cfg, xx)        # noqa: E731
    kernel_jaxpr = str(jax.make_jaxpr(run)(params, x))
    assert "pallas_call" in kernel_jaxpr and "flash_prefill" in kernel_jaxpr
    out, cache = run(params, x)
    with activation_sharding(_one_device_mesh(), ("data",)):
        # a new function: JAX would reuse the trace made outside
        scan_run = lambda p, xx: prefill_fn(p, cfg, xx)   # noqa: E731
        assert "pallas_call" not in str(jax.make_jaxpr(scan_run)(params, x))
        out_scan, cache_scan = scan_run(params, x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_scan, np.float32),
                               atol=3e-2, rtol=3e-2)
    for c, cs in zip(cache, cache_scan):
        np.testing.assert_array_equal(np.asarray(c, np.float32),
                                      np.asarray(cs, np.float32))


@pytest.mark.parametrize("name", ["qwen3-1.7b", "minicpm3-4b"])
def test_training_gradients_go_through_the_scan(monkeypatch, name):
    """``jax.grad`` through ``forward`` never reaches the kernel, which
    has no VJP: with every kernel entry point made to raise, the
    gradients are the same to the bit."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    arch = tiny_variant(get_arch(name))
    params = init_model(jax.random.PRNGKey(0), arch)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, arch.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    grad = jax.jit(jax.grad(lambda p: loss_fn(p, arch, batch, RT)[0]))
    want = grad(params)

    def refuse(*a, **kw):
        raise AssertionError("the training path reached the prefill kernel")

    monkeypatch.setattr(attention, "flash_prefill", refuse)
    monkeypatch.setattr(attention, "prefill_attention", refuse)
    got = jax.jit(jax.grad(lambda p: loss_fn(p, arch, batch, RT)[0]))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
