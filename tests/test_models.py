"""Model-zoo tests: per-arch reduced-config smoke tests (deliverable f),
attention equivalences, SSD oracle, decode-vs-forward consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_arch, tiny_variant
from repro.configs.base import RuntimeConfig
from repro.models import (decode_step, forward, init_model, loss_fn,
                          make_cache, prefill)
from repro.models.attention import (AttnConfig, _mla_qkv_full,
                                    flash_attention, gqa_apply, gqa_init,
                                    mla_decode, mla_init, mla_prefill)
from repro.models.ssm import ssd_chunked, ssd_reference

RT = RuntimeConfig(remat="none")
KEY = jax.random.PRNGKey(0)


def make_batch(arch, b=2, s=32):
    batch = {"tokens": jnp.ones((b, s), jnp.int32) * 3,
             "labels": jnp.ones((b, s), jnp.int32) * 5}
    if arch.family == "vlm":
        batch["patches"] = jnp.ones((b, arch.n_patches, arch.vit_dim),
                                    jnp.float32)
    if arch.is_encdec:
        batch["frames"] = jnp.ones((b, s, arch.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_arch_smoke_train_step(name):
    """Reduced config of the same family: one forward/train step on CPU,
    output shapes + no NaNs (assignment requirement)."""
    arch = tiny_variant(get_arch(name))
    params = init_model(KEY, arch)
    batch = make_batch(arch)
    logits, aux = jax.jit(lambda p, b: forward(p, arch, b, RT))(params, batch)
    exp_s = batch["tokens"].shape[1] + (
        arch.n_patches if arch.family == "vlm" else 0)
    assert logits.shape == (2, exp_s, arch.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), f"{name} logits NaN"
    grads = jax.jit(jax.grad(lambda p, b: loss_fn(p, arch, b, RT)[0]))(
        params, batch)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat), f"{name} grad NaN"


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_arch_smoke_decode_step(name):
    arch = tiny_variant(get_arch(name))
    params = init_model(KEY, arch)
    cache = make_cache(arch, 16, 2)
    toks = jnp.ones((2, 1), jnp.int32)
    logits, cache2 = jax.jit(
        lambda p, c, t: decode_step(p, arch, c, t, RT))(params, cache, toks)
    assert logits.shape == (2, 1, arch.padded_vocab)
    assert bool(jnp.isfinite(logits).all())
    assert int(cache2["len"]) == 1


def test_param_count_estimates_match_published():
    targets = {
        "nemotron-4-340b": 340e9, "mistral-large-123b": 123e9,
        "minicpm3-4b": 4e9, "qwen3-1.7b": 1.7e9, "dbrx-132b": 132e9,
        "mamba2-130m": 130e6, "zamba2-2.7b": 2.7e9,
    }
    for name, want in targets.items():
        est = get_arch(name).param_count_estimate()
        assert 0.8 * want < est < 1.25 * want, (name, est, want)


def test_flash_attention_matches_naive():
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 4, 96, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_kv=32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_kv_block_shrinks_to_fit_its_scores(monkeypatch):
    """A query whose scores over one KV block would pass the budget
    halves the block, here from 512 to its floor of 128 keys: the same
    attention, in three blocks where there was one."""
    from repro.models import attention

    rng = np.random.default_rng(5)
    b, h, s, d = 1, 2, 300, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
               for _ in range(3))
    want = flash_attention(q, k, v, causal=True, block_kv=512)
    monkeypatch.setattr(attention, "SCORE_BLOCK_BYTES", b * h * s * 64 * 4)
    monkeypatch.setattr(attention.jax.lax, "scan", _counting_scan(
        attention.jax.lax.scan, seen := []))
    got = flash_attention(q, k, v, causal=True, block_kv=512)
    assert seen == [3]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _counting_scan(scan, seen):
    def counted(f, init, xs, *args, **kwargs):
        seen.append(jax.tree.leaves(xs)[0].shape[0])
        return scan(f, init, xs, *args, **kwargs)
    return counted


def test_gqa_kv_replication_equivalence():
    """kv_repeat must not change the math (Megatron kv replication)."""
    cfg1 = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
    cfg2 = dataclasses.replace(cfg1, kv_repeat=2)
    params = gqa_init(KEY, cfg1)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 16, 32)),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(gqa_apply(params, cfg1, x)),
        np.asarray(gqa_apply(params, cfg2, x)), atol=1e-5)


def test_mla_absorb_equivalence():
    """The decode's absorbed (latent-space) attention equals the
    expanded attention, written out here: the latent cache up-projected
    to per-head K and V, scored with the rope key, softmax over the
    live positions, then the output projection."""
    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=4, head_dim=8,
                     attn_type="mla", q_lora_rank=16, kv_lora_rank=8,
                     rope_head_dim=4)
    params = mla_init(KEY, cfg)
    rng = np.random.default_rng(2)
    x_ctx = jnp.asarray(rng.standard_normal((2, 6, 32)), jnp.float32)
    _, (c_kv, k_rope) = mla_prefill(params, cfg, x_ctx)
    pad = 10 - 6
    cache = (jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
             jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))))
    x_new = jnp.asarray(rng.standard_normal((2, 1, 32)), jnp.float32)
    got, (c, r) = mla_decode(params, cfg, x_new, cache, jnp.int32(6))

    h, hd, rd = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    q_nope, q_rope, c_new, _ = _mla_qkv_full(params, cfg, x_new,
                                             jnp.full((1,), 6))
    np.testing.assert_allclose(np.asarray(c[:, 6]), np.asarray(c_new[:, 0]))
    k = (c[:, :7] @ params["wk_b"]).reshape(2, 7, h, hd)
    v = (c[:, :7] @ params["wv_b"]).reshape(2, 7, h, hd)
    scores = (jnp.einsum("bhd,bshd->bhs", q_nope[:, 0], k)
              + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], r[:, :7])
              ) / np.sqrt(hd + rd)
    o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores, -1), v)
    want = o.reshape(2, 1, h * hd) @ params["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_ssd_chunked_matches_reference():
    rng = np.random.default_rng(3)
    b, s, h, p, n = 2, 40, 2, 8, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.4, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, h), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    y1, h1 = ssd_reference(x, dt, A, B, C)
    y2, h2 = ssd_chunked(x, dt, A, B, C, chunk=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "minicpm3-4b", "mamba2-130m"])
def test_prefill_decode_matches_forward(name):
    """prefill(ctx) then decode(tok) must reproduce forward(ctx+tok)."""
    arch = tiny_variant(get_arch(name))
    params = init_model(KEY, arch)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(1, arch.vocab - 1, (2, 12)), jnp.int32)
    # full forward over all 12 tokens
    logits_full, _ = forward(params, arch, {"tokens": toks}, RT)
    # prefill on 11, decode token 12
    logits_p, cache = prefill(params, arch, {"tokens": toks[:, :11]}, 16, RT)
    logits_d, _ = decode_step(params, arch, cache, toks[:, 11:12], RT)
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(logits_full[:, 11]),
                               atol=2e-2, rtol=2e-2)


def test_loss_decreases_on_repeated_batch():
    arch = tiny_variant(get_arch("qwen3-1.7b"))
    from repro.optim import adamw
    params = init_model(KEY, arch)
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=50)
    batch = make_batch(arch)

    @jax.jit
    def step(p, o, b):
        (l, _), g = jax.value_and_grad(
            lambda pp: loss_fn(pp, arch, b, RT), has_aux=True)(p)
        p2, o2, _ = adamw.update(g, o, p, cfg)
        return p2, o2, l

    losses = []
    for _ in range(8):
        params, opt, l = step(params, opt, batch)
        losses.append(float(l))
    assert losses[-1] < losses[0] - 0.5, losses
