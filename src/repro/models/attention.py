"""Attention variants: GQA (w/ optional qk-norm) and MLA (multi-head
latent attention, MiniCPM3/DeepSeek-V2 style).

Full-sequence attention is computed *blockwise* over KV chunks with an
online-softmax accumulator (``flash_attention``: the flash-attention
recurrence in pure JAX via ``lax.scan``) so the [S, S] score matrix is
never materialized — at prefill_32k a materialized score tensor would
be O(S^2) HBM and the dry-run would not fit.  ``gqa_apply`` and
``mla_apply`` take the function that runs a causal self-attention:
training passes the scan (it is differentiable); a prefill passes
``prefill_attention``, the Pallas kernel ``kernels/flash_prefill.py``,
which keeps each score block on-chip and skips the key blocks past the
diagonal, unless a launcher partitions the program (XLA cannot
partition a kernel), where it falls back to the scan.  Both return the
layer's cache entries beside the output.

Decode (one new token against a cached KV of length S) scores the whole
cache in XLA; a launcher that shards the cache's length axis over the
"model" mesh axis gets XLA's partial-softmax reduction.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import flash_prefill
from repro.launch.sharding import partitioned
from repro.models.common import (Params, apply_rope, cast_matmul, dense_init,
                                 norm_init, rms_norm)

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    # MLA (attn_type == "mla")
    attn_type: str = "gqa"            # "gqa" | "mla"
    q_lora_rank: int = 0              # 0 = full-rank q projection
    kv_lora_rank: int = 0
    rope_head_dim: int = 0            # decoupled rope dims (MLA)
    block_q: int = 512
    block_kv: int = 1024
    # kv replication factor: full-seq paths repeat kv heads so that the
    # head axis divides the TP degree exactly (Megatron kv replication)
    kv_repeat: int = 1


# ======================================================================
# Blockwise (flash-style) attention core
# ======================================================================
# one KV block's scores, [B, Hq, Sq, block_kv] in float32, are the scan's
# largest temporary: a long or wide query halves the block (to 128 at
# least) until they fit this many bytes
SCORE_BLOCK_BYTES = 2**29


def flash_attention(q, k, v, causal: bool = True, q_offset=0,
                    block_kv: int = 1024):
    """Online-softmax attention.

    q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D]; returns [B, Hq, Sq, D].
    Group-query: Hq is a multiple of Hkv; handled by reshaping q into
    [B, Hkv, G, Sq, D] so each KV head serves G query heads.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    while block_kv > 128 and b * hq * sq * block_kv * 4 > SCORE_BLOCK_BYTES:
        block_kv //= 2

    n_blocks = -(-skv // block_kv)
    pad = n_blocks * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, hkv, n_blocks, block_kv, d)
    vb = v.reshape(b, hkv, n_blocks, block_kv, d)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        kv_i, k_i, v_i = xs
        kv_pos = kv_i * block_kv + jnp.arange(block_kv)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                       k_i.astype(jnp.float32)) * scale
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
            jnp.ones((sq, block_kv), bool)
        mask = jnp.logical_and(mask, (kv_pos < skv)[None, :])
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    kb_t = jnp.moveaxis(kb, 2, 0)   # [n_blocks, B, Hkv, bk, D]
    vb_t = jnp.moveaxis(vb, 2, 0)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_blocks), kb_t, vb_t))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def prefill_attention(q, k, v, causal: bool, block_kv: int = 1024):
    """A prefill's self-attention of a prompt to itself (q: [B, Hq, S,
    D]; k/v: [B, Hkv, S, D]): causal attention takes the Pallas kernel,
    which has no VJP, unless the program is partitioned over a mesh;
    anything else takes the scan with key blocks of ``block_kv``."""
    if causal and not partitioned():
        return flash_prefill(q, k, v)
    return flash_attention(q, k, v, causal, 0, block_kv)


# ======================================================================
# GQA
# ======================================================================
def gqa_init(key: jax.Array, cfg: AttnConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, hd = cfg.d_model, cfg.head_dim
    p: Params = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd)
        p["k_norm"] = norm_init(hd)
    return p


def _project_qkv(params: Params, cfg: AttnConfig, x: jax.Array,
                 positions: jax.Array, matmul=cast_matmul):
    b, s, _ = x.shape
    hd = cfg.head_dim
    q, k, v = matmul(x, params["wq"], params["wk"], params["wv"])
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"])
        k = rms_norm(k, params["k_norm"]["scale"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _replicate_kv(cfg: AttnConfig, k: jax.Array, v: jax.Array):
    """Repeat kv heads so the head axis divides TP exactly (Megatron kv
    replication).  GQA math is unchanged — property-tested."""
    if cfg.kv_repeat > 1:
        k = jnp.repeat(k, cfg.kv_repeat, axis=2)
        v = jnp.repeat(v, cfg.kv_repeat, axis=2)
    return k, v


@jax.named_scope("attn")
def gqa_apply(params: Params, cfg: AttnConfig, x: jax.Array,
              attend=flash_attention):
    """Full-sequence GQA.  ``attend(q, k, v, causal=, block_kv=)`` runs
    the attention itself: ``flash_attention`` for training and scoring,
    ``prefill_attention`` in a prefill.  Returns the output and the
    layer's cache entries, K and V of the real heads [B, Hkv, S, D]."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, jnp.arange(s))
    kc, vc = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # cache: real heads
    kr, vr = _replicate_kv(cfg, k, v)
    out = attend(jnp.swapaxes(q, 1, 2), jnp.swapaxes(kr, 1, 2),
                 jnp.swapaxes(vr, 1, 2), causal=cfg.causal,
                 block_kv=cfg.block_kv)
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(x.dtype), (kc, vc)


def _attend_cache(q: jax.Array, kc: jax.Array, vc: jax.Array,
                  length: jax.Array | None = None) -> jax.Array:
    """One query a row against a cache: q [B, 1, Hq, D]; kc/vc [B, Hkv,
    S, D]; positions past ``length`` masked (none without it).  Returns
    the float32 context [B, 1, Hq * D]."""
    b, _, hq, hd = q.shape
    hkv = kc.shape[1]
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, hq // hkv, hd)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / jnp.sqrt(hd)
    if length is not None:
        valid = jnp.arange(kc.shape[2])[None, None, None, :] <= length
        scores = jnp.where(valid, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, vc.astype(jnp.float32))
    return out.reshape(b, 1, hq * hd)


@jax.named_scope("attn")
def gqa_decode(params: Params, cfg: AttnConfig, x: jax.Array,
               cache: tuple[jax.Array, jax.Array], cache_len: jax.Array,
               matmul=cast_matmul):
    """One-token decode. x: [B, 1, D_model]; cache [B, Hkv, S_max, D].
    ``matmul`` multiplies by the projections (``common.cast_matmul``)."""
    positions = jnp.full((1,), cache_len, jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions, matmul)
    kc, vc = cache
    kc = jax.lax.dynamic_update_slice_in_dim(
        kc, jnp.swapaxes(k, 1, 2).astype(kc.dtype), cache_len, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(
        vc, jnp.swapaxes(v, 1, 2).astype(vc.dtype), cache_len, axis=2)
    out = _attend_cache(q, kc, vc, cache_len).astype(x.dtype)
    (out,) = matmul(out, params["wo"])
    return out, (kc, vc)


@jax.named_scope("attn")
def cross_apply(params: Params, cfg: AttnConfig, x: jax.Array,
                enc: jax.Array) -> tuple[jax.Array, tuple]:
    """Full-sequence cross attention: queries from ``x`` [B, S, D], keys
    and values from the encoder's output ``enc`` [B, S_enc, D], no rope
    and no mask.  Returns the output and the layer's cache entries, the
    cross K and V [B, Hkv, S_enc, D]."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, cfg.n_heads, hd)
    enc = enc.astype(x.dtype)
    k = (enc @ params["wk"].astype(x.dtype)).reshape(b, -1, cfg.n_kv_heads, hd)
    v = (enc @ params["wv"].astype(x.dtype)).reshape(b, -1, cfg.n_kv_heads, hd)
    k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    o = flash_attention(jnp.swapaxes(q, 1, 2), k, v, causal=False)
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, cfg.n_heads * hd)
    return o @ params["wo"].astype(x.dtype), (k, v)


@jax.named_scope("attn")
def cross_decode(params: Params, cfg: AttnConfig, x: jax.Array,
                 cache: tuple[jax.Array, jax.Array]) -> tuple[jax.Array, tuple]:
    """One-token cross attention against the cross cache (K and V
    [B, Hkv, S_enc, D], which decode leaves as they are)."""
    b = x.shape[0]
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, 1, cfg.n_heads,
                                                   cfg.head_dim)
    out = _attend_cache(q, *cache).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), cache


# ======================================================================
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2)
# ======================================================================
def mla_init(key: jax.Array, cfg: AttnConfig) -> Params:
    ks = jax.random.split(key, 8)
    d, hd, r = cfg.d_model, cfg.head_dim, cfg.rope_head_dim
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    p: Params = {
        # q: d -> q_lora -> heads*(nope+rope)
        "wq_a": dense_init(ks[0], d, qr),
        "q_a_norm": norm_init(qr),
        "wq_b": dense_init(ks[1], qr, cfg.n_heads * (hd + r)),
        # kv: d -> kv_lora (+ shared k_rope)
        "wkv_a": dense_init(ks[2], d, kvr + r),
        "kv_a_norm": norm_init(kvr),
        # up-projections from the latent
        "wk_b": dense_init(ks[3], kvr, cfg.n_heads * hd),
        "wv_b": dense_init(ks[4], kvr, cfg.n_heads * hd),
        "wo": dense_init(ks[5], cfg.n_heads * hd, d),
    }
    return p


def _mla_qkv_full(params: Params, cfg: AttnConfig, x: jax.Array,
                  positions: jax.Array, matmul=cast_matmul):
    """The queries (no-rope and rope parts, per head), the normed latent
    ``c_kv`` and the shared rope key of ``x``.  ``matmul`` multiplies by
    the projections (``common.cast_matmul``); q's and kv's down
    projections share one call, one pass over ``x``."""
    b, s, _ = x.shape
    hd, r, kvr = cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    qa, kv = matmul(x, params["wq_a"], params["wkv_a"])      # kv: [B,S,kvr+r]
    qa = rms_norm(qa, params["q_a_norm"]["scale"])
    (q,) = matmul(qa, params["wq_b"])
    q = q.reshape(b, s, cfg.n_heads, hd + r)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = rms_norm(kv[..., :kvr], params["kv_a_norm"]["scale"])
    k_rope = apply_rope(kv[..., kvr:][:, :, None, :], positions,
                        cfg.rope_theta)                        # [B,S,1,r]
    return q_nope, q_rope, c_kv, k_rope


@jax.named_scope("attn")
def mla_apply(params: Params, cfg: AttnConfig, x: jax.Array,
              attend=flash_attention):
    """Full-sequence MLA in the expanded form: the latent expanded to
    per-head K/V, then ``attend(q, k, v, causal=, block_kv=)``
    (``flash_attention`` or ``prefill_attention``).  Returns the output
    and the layer's cache entries: only the latent ``c_kv`` [B, S, kvr]
    and the shared rope key [B, S, r], MLA's memory win."""
    b, s, _ = x.shape
    hd, r = cfg.head_dim, cfg.rope_head_dim
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_full(params, cfg, x,
                                                 jnp.arange(s))
    k_nope = (c_kv @ params["wk_b"].astype(x.dtype)).reshape(
        b, s, cfg.n_heads, hd)
    v = (c_kv @ params["wv_b"].astype(x.dtype)).reshape(b, s, cfg.n_heads, hd)
    # fold the decoupled rope part into the head dim (shared k_rope per head)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, s, cfg.n_heads, r))], axis=-1)
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, r)))
    out = attend(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v_pad, 1, 2), causal=cfg.causal, block_kv=cfg.block_kv)
    out = jnp.swapaxes(out, 1, 2)[..., :hd].reshape(b, s, cfg.n_heads * hd)
    return out @ params["wo"].astype(x.dtype), (c_kv, k_rope[:, :, 0, :])


@jax.named_scope("attn")
def mla_decode(params: Params, cfg: AttnConfig, x: jax.Array,
               cache: tuple[jax.Array, jax.Array], cache_len: jax.Array,
               matmul=cast_matmul, layer: jax.Array | None = None):
    """One-token MLA decode against the latent cache (c_kv [B,S,kvr],
    k_rope [B,S,r]; with ``layer``, the stacks [L,B,S,...] of every
    layer, of which layer ``layer``'s is read), in the absorbed form:
    W_UK takes the query into the latent space, where it is scored
    against ``c_kv`` itself, and W_UV takes the latent context out, so a
    step reads the cache's S*(kvr+r) numbers a row and never expands it
    to per-head K and V.  Returns the output and this layer's cache.
    Under the scope ``latent``: the cache's read (the layer's slice of
    the stacks) and write, the query's absorption, the scores against
    ``c_kv`` and the rope key, the mask, the softmax, the latent context
    and its up-projection; the cache is read in its own dtype with
    float32 accumulation.  ``matmul`` multiplies by the other
    projections (``common.cast_matmul``)."""
    b = x.shape[0]
    hd, r, kvr = cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    f32 = jnp.float32
    positions = jnp.full((1,), cache_len, jnp.int32)
    q_nope, q_rope, c_new, k_rope_new = _mla_qkv_full(params, cfg, x,
                                                      positions, matmul)
    with jax.named_scope("latent"):
        c_cache, r_cache = cache if layer is None else (
            jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
            for c in cache)
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            c_cache, c_new.astype(c_cache.dtype), cache_len, axis=1)
        r_cache = jax.lax.dynamic_update_slice_in_dim(
            r_cache, k_rope_new[:, :, 0, :].astype(r_cache.dtype), cache_len,
            axis=1)
        cdt = c_cache.dtype
        wk = params["wk_b"].reshape(kvr, cfg.n_heads, hd)
        # W_UK and W_UV are float32 slices of their stacks: at full
        # precision XLA keeps them so, where the default precision would
        # have it write a bfloat16 copy of both stacks every step
        q_lat = jnp.einsum("bhd,khd->bhk", q_nope[:, 0].astype(f32),
                           wk.astype(f32), precision=HIGHEST)  # [B,H,kvr]
        s_lat = jnp.einsum("bhk,bsk->bhs", q_lat.astype(cdt), c_cache,
                           preferred_element_type=f32)
        s_rope = jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(cdt),
                            r_cache, preferred_element_type=f32)
        scores = (s_lat + s_rope) / jnp.sqrt(f32(hd + r))
        valid = jnp.arange(c_cache.shape[1])[None, None, :] <= cache_len
        w = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhs,bsk->bhk", w.astype(cdt), c_cache,
                         preferred_element_type=f32)          # [B,H,kvr]
        wv = params["wv_b"].reshape(kvr, cfg.n_heads, hd)
        out = jnp.einsum("bhk,khd->bhd", ctx, wv.astype(f32),
                         precision=HIGHEST)
    out = out.reshape(b, 1, cfg.n_heads * hd).astype(x.dtype)
    (out,) = matmul(out, params["wo"])
    return out, (c_cache, r_cache)
