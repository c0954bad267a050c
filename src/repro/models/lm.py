"""Unified language-model assembly for all assigned architectures.

One functional model covering the six families (dense / moe / ssm /
hybrid / vlm / audio-encdec).  Layers are pre-stacked and consumed with
``lax.scan`` (+ per-layer remat), so HLO size and compile time are O(1)
in depth — required for 96-layer, 340B-parameter dry-runs.

Public entry points:
  init_model(key, arch, policy)                  -> params
  forward(params, arch, batch, rt)               -> logits (train/prefill)
  loss_fn(params, arch, batch, rt)               -> (loss, metrics)
  make_cache(arch, shape, batch, policy)         -> decode cache pytree
  prefill(params, arch, batch, rt)               -> (logits, cache)
  decode_step(params, arch, cache, tokens, rt)   -> (logits, cache)

Activation-sharding hooks go through ``repro.launch.sharding.constrain``
so the model code stays mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RuntimeConfig, ShapeConfig
from repro.kernels import weight_stream
from repro.launch.sharding import constrain, partitioned
from repro.models.attention import (AttnConfig, flash_attention, gqa_apply,
                                    gqa_decode, gqa_init, gqa_prefill,
                                    mla_apply, mla_decode, mla_init,
                                    mla_prefill)
from repro.models.common import (DTypePolicy, Params, cast_matmul, dense_init,
                                 norm_init, rms_norm, truncated_normal_init)
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import MoEConfig, aux_load_balance_loss, moe_apply, moe_init
from repro.models.ssm import SSMConfig, mamba2_apply, mamba2_decode, mamba2_init

# ======================================================================
# Config adapters
# ======================================================================
def attn_config(arch: ArchConfig, causal: bool = True) -> AttnConfig:
    from repro.launch.sharding import tp_hint
    tp = tp_hint()
    rep = 1
    if tp > 1 and arch.n_kv_heads < tp and tp % arch.n_kv_heads == 0 \
            and arch.n_heads % tp == 0:
        rep = tp // arch.n_kv_heads        # Megatron kv replication
    return AttnConfig(
        d_model=arch.d_model,
        n_heads=arch.n_heads,
        n_kv_heads=arch.n_kv_heads,
        head_dim=arch.resolved_head_dim,
        qk_norm=arch.qk_norm,
        rope_theta=arch.rope_theta,
        causal=causal,
        attn_type=arch.attn_type,
        q_lora_rank=arch.q_lora_rank,
        kv_lora_rank=arch.kv_lora_rank,
        rope_head_dim=arch.rope_head_dim,
        kv_repeat=rep,
    )


def moe_config(arch: ArchConfig) -> MoEConfig:
    return MoEConfig(
        d_model=arch.d_model, d_ff_expert=arch.d_ff,
        n_experts=arch.n_experts, top_k=arch.top_k,
        capacity_factor=arch.moe_capacity_factor,
        act=arch.act, gated=arch.gated_mlp,
    )


def ssm_config(arch: ArchConfig) -> SSMConfig:
    return SSMConfig(
        d_model=arch.d_model, d_state=arch.ssm_state,
        head_dim=arch.ssm_head_dim, expand=arch.ssm_expand,
        chunk=arch.ssm_chunk,
    )


# ======================================================================
# Per-layer blocks
# ======================================================================
def _attn_block_init(key, arch: ArchConfig) -> Params:
    acfg = attn_config(arch)
    init = mla_init if arch.attn_type == "mla" else gqa_init
    return {"attn": init(key, acfg), "ln": norm_init(arch.d_model)}


def _decoder_layer_init(key, arch: ArchConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = _attn_block_init(k1, arch)
    p["ln2"] = norm_init(arch.d_model)
    if arch.family == "moe":
        p["moe"] = moe_init(k2, moe_config(arch))
    else:
        p["mlp"] = mlp_init(k2, arch.d_model, arch.d_ff, arch.gated_mlp)
    return p


def _ssm_layer_init(key, arch: ArchConfig) -> Params:
    return {"mamba": mamba2_init(key, ssm_config(arch)),
            "ln": norm_init(arch.d_model)}


def _encoder_layer_init(key, arch: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    acfg = attn_config(arch, causal=False)
    return {"attn": gqa_init(k1, acfg), "ln": norm_init(arch.d_model),
            "mlp": mlp_init(k2, arch.d_model, arch.d_ff, arch.gated_mlp),
            "ln2": norm_init(arch.d_model)}


def _cross_decoder_layer_init(key, arch: ArchConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = _decoder_layer_init(jax.random.fold_in(k1, 0), arch)
    p["cross"] = gqa_init(k2, attn_config(arch, causal=False))
    p["ln_cross"] = norm_init(arch.d_model)
    return p


def _shared_block_init(key, arch: ArchConfig) -> Params:
    """zamba2-style shared attention block, fed concat(h, emb0)."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = _decoder_layer_init(k1, arch)
    p["w_cat"] = dense_init(k2, 2 * arch.d_model, arch.d_model)
    return p


def _residual(arch: ArchConfig, h: jax.Array, branch: jax.Array) -> jax.Array:
    """``h`` plus a residual branch scaled by ``arch.residual_scale``; the
    scale is applied only where it is not 1, so the default adds no op."""
    if arch.residual_scale != 1.0:
        branch = branch * jnp.asarray(arch.residual_scale, branch.dtype)
    return h + branch


def _layer_apply_full(p: Params, arch: ArchConfig, h: jax.Array,
                      rt: RuntimeConfig) -> tuple[jax.Array, jax.Array]:
    """Full-sequence decoder layer (train / prefill w/o cache).
    Returns (h, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    # Megatron-SP: sub-block outputs are constrained to the seq-sharded
    # "hidden" layout BEFORE the residual add, so the TP partial-sum
    # lowers to a reduce-scatter and the residual add stays local
    # (otherwise GSPMD all-gathers the residual at every add —
    # measured ~7 hidden-sized gathers/layer on mistral, §Perf it.4).
    if arch.family in ("ssm", "hybrid"):
        with jax.named_scope("mlp"):
            x = constrain(rms_norm(h, p["ln"]["scale"]), "tp_in", rt)
            h = _residual(arch, h, constrain(
                mamba2_apply(p["mamba"], ssm_config(arch), x), "hidden", rt))
        return constrain(h, "hidden", rt), aux
    acfg = attn_config(arch)
    with jax.named_scope("attn"):
        x = constrain(rms_norm(h, p["ln"]["scale"]), "tp_in", rt)
        attn = mla_apply if arch.attn_type == "mla" else gqa_apply
        h = _residual(arch, h, constrain(attn(p["attn"], acfg, x), "hidden",
                                         rt))
    with jax.named_scope("mlp"):
        x2 = constrain(rms_norm(h, p["ln2"]["scale"]), "tp_in", rt)
        if arch.family == "moe":
            h = _residual(arch, h, constrain(
                moe_apply(p["moe"], moe_config(arch), x2), "hidden", rt))
            aux = aux_load_balance_loss(p["moe"], moe_config(arch), x2)
        else:
            h = _residual(arch, h, constrain(mlp_apply(p["mlp"], x2, arch.act),
                                             "hidden", rt))
    return constrain(h, "hidden", rt), aux


def _shared_block_apply(p: Params, arch: ArchConfig, h: jax.Array,
                        emb0: jax.Array, rt: RuntimeConfig) -> jax.Array:
    acfg = attn_config(arch)
    with jax.named_scope("attn"):
        z = jnp.concatenate([h, emb0.astype(h.dtype)], axis=-1)
        z = z @ p["w_cat"].astype(h.dtype)
        x = rms_norm(z, p["ln"]["scale"])
        z = z + gqa_apply(p["attn"], acfg, x)
    with jax.named_scope("mlp"):
        x2 = rms_norm(z, p["ln2"]["scale"])
        z = z + mlp_apply(p["mlp"], x2, arch.act)
    return h + z


# ======================================================================
# Model init
# ======================================================================
def init_model(key: jax.Array, arch: ArchConfig,
               policy: DTypePolicy | None = None) -> Params:
    policy = policy or DTypePolicy.standard()
    ks = jax.random.split(key, 8)
    d = arch.d_model
    params: Params = {
        # vocab padded to a multiple of 128 (TPU lanes + mesh divisibility)
        "embed": truncated_normal_init(ks[0], (arch.padded_vocab, d), 1.0),
        "final_norm": norm_init(d),
    }
    if not arch.tie_embeddings:
        params["head"] = dense_init(ks[1], d, arch.padded_vocab)

    if arch.family in ("ssm", "hybrid"):
        layer_init = partial(_ssm_layer_init, arch=arch)
    elif arch.is_encdec:
        layer_init = partial(_cross_decoder_layer_init, arch=arch)
    else:
        layer_init = partial(_decoder_layer_init, arch=arch)
    params["blocks"] = jax.vmap(lambda k: layer_init(k))(
        jax.random.split(ks[2], arch.n_layers))

    if arch.family == "hybrid" and arch.shared_attn_every:
        params["shared"] = _shared_block_init(ks[3], arch)
    if arch.is_encdec:
        params["enc_blocks"] = jax.vmap(
            lambda k: _encoder_layer_init(k, arch))(
            jax.random.split(ks[4], arch.enc_layers))
        params["enc_norm"] = norm_init(d)
    if arch.family == "vlm":
        params["patch_proj"] = dense_init(ks[5], arch.vit_dim, d)

    return jax.tree.map(
        lambda x: x.astype(policy.params)
        if x.dtype == jnp.float32 else x, params)


# ======================================================================
# Forward (train / prefill), scan over stacked layers
# ======================================================================
@jax.named_scope("weight_cast")
def _cast_blocks(blocks: Params, dtype) -> Params:
    """Cast the stacked layer matrices to the compute dtype ONCE, outside
    the layer scan, so FSDP all-gathers move bf16 (not f32) bytes.  A
    layer's matrices are rank >= 3 once stacked; its vectors (norm
    scales etc.) are rank 2 and stay f32 (rms_norm computes in f32).
    The layers cast each matrix to the compute dtype where they use it,
    so casting the stack and then slicing gives the same bits as slicing
    and then casting.  Training and prefill cast every stack here; the
    decode step only what its weight-streaming matmul does not read (MoE
    experts, cross attention; not MLA's W_UK and W_UV, used in float32):
    it writes no copy of the rest.

    The barrier changes no value and adds no op.  Where the layers want
    a stack in another layout, XLA folds the layout change and the cast
    into one copy named after the copy's input: through the barrier that
    input is this scope's, not the bare parameter, so the trace counts
    the copy under ``weight_cast``."""
    blocks = jax.lax.optimization_barrier(blocks)
    return jax.tree.map(
        lambda x: x.astype(dtype) if (x.ndim >= 3 and
                                      x.dtype == jnp.float32) else x,
        blocks)


def _scan_layers(params: Params, arch: ArchConfig, h: jax.Array,
                 rt: RuntimeConfig) -> tuple[jax.Array, jax.Array]:
    emb0 = h
    every = arch.shared_attn_every

    def one_layer(carry, xs):
        hh = carry
        bp, idx = xs
        hh, aux = _layer_apply_full(bp, arch, hh, rt)
        if arch.family == "hybrid" and every:
            hh = jax.lax.cond(
                (idx % every) == 0,
                lambda v: _shared_block_apply(params["shared"], arch, v,
                                              emb0, rt),
                lambda v: v,
                hh,
            )
        return hh, aux

    layer = one_layer
    if rt.remat == "full":
        layer = jax.checkpoint(
            one_layer, policy=jax.checkpoint_policies.nothing_saveable)
    blocks = _cast_blocks(params["blocks"], h.dtype)
    with jax.named_scope("layers"):
        h, auxs = jax.lax.scan(
            layer, h, (blocks, jnp.arange(arch.n_layers)))
    return h, jnp.sum(auxs)


def _encoder_forward(params: Params, arch: ArchConfig, frames: jax.Array,
                     rt: RuntimeConfig) -> jax.Array:
    acfg = attn_config(arch, causal=False)

    def one_layer(h, bp):
        with jax.named_scope("attn"):
            x = rms_norm(h, bp["ln"]["scale"])
            h = h + gqa_apply(bp["attn"], acfg, x)
        with jax.named_scope("mlp"):
            x2 = rms_norm(h, bp["ln2"]["scale"])
            h = h + mlp_apply(bp["mlp"], x2, arch.act)
        return constrain(h, "hidden", rt), None

    layer = one_layer
    if rt.remat == "full":
        layer = jax.checkpoint(
            one_layer, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        h, _ = jax.lax.scan(layer, frames, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"]["scale"])


def _cross_decoder_forward(params: Params, arch: ArchConfig, h: jax.Array,
                           enc_out: jax.Array, rt: RuntimeConfig
                           ) -> tuple[jax.Array, jax.Array]:
    acfg = attn_config(arch)
    xcfg = attn_config(arch, causal=False)

    def one_layer(hh, bp):
        with jax.named_scope("attn"):
            x = rms_norm(hh, bp["ln"]["scale"])
            hh = _residual(arch, hh, gqa_apply(bp["attn"], acfg, x))
            xc = rms_norm(hh, bp["ln_cross"]["scale"])
            # cross attention: q from decoder, k/v from encoder output
            b, s, _ = xc.shape
            hd = xcfg.head_dim
            q = (xc @ bp["cross"]["wq"].astype(xc.dtype)).reshape(
                b, s, xcfg.n_heads, hd)
            k = (enc_out.astype(xc.dtype)
                 @ bp["cross"]["wk"].astype(xc.dtype)
                 ).reshape(b, -1, xcfg.n_kv_heads, hd)
            v = (enc_out.astype(xc.dtype)
                 @ bp["cross"]["wv"].astype(xc.dtype)
                 ).reshape(b, -1, xcfg.n_kv_heads, hd)
            o = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                                jnp.swapaxes(v, 1, 2), causal=False)
            o = jnp.swapaxes(o, 1, 2).reshape(b, s, xcfg.n_heads * hd)
            hh = _residual(arch, hh, o @ bp["cross"]["wo"].astype(xc.dtype))
        with jax.named_scope("mlp"):
            x2 = rms_norm(hh, bp["ln2"]["scale"])
            hh = _residual(arch, hh, mlp_apply(bp["mlp"], x2, arch.act))
        return constrain(hh, "hidden", rt), jnp.zeros((), jnp.float32)

    layer = one_layer
    if rt.remat == "full":
        layer = jax.checkpoint(
            one_layer, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        h, auxs = jax.lax.scan(layer, h, params["blocks"])
    return h, jnp.sum(auxs)


@jax.named_scope("embed")
def embed_tokens(params: Params, arch: ArchConfig, tokens: jax.Array,
                 rt: RuntimeConfig, compute_dtype) -> jax.Array:
    e = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    scale = (jnp.asarray(arch.embed_scale, compute_dtype) if arch.embed_scale
             else jnp.sqrt(arch.d_model).astype(compute_dtype))
    return constrain(e * scale, "hidden", rt)


@jax.named_scope("head")
def _head(params: Params, arch: ArchConfig, h: jax.Array, compute_dtype,
          last_only: bool = False) -> jax.Array:
    """Final norm, division by ``arch.head_divisor`` (where it is not 1)
    and logits, of the last position alone with ``last_only``; the head
    is the embedding's transpose when tied."""
    h = rms_norm(h, params["final_norm"]["scale"])
    if last_only:
        h = h[:, -1:, :]
    if arch.head_divisor != 1.0:
        h = h / jnp.asarray(arch.head_divisor, h.dtype)
    head = params.get("head", None)
    w = (params["embed"].T if head is None else head).astype(compute_dtype)
    return h @ w


def forward(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
            rt: RuntimeConfig | None = None,
            policy: DTypePolicy | None = None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits, aux_loss).

    batch keys: "tokens" [B,S]; vlm: + "patches" [B,P,vit_dim];
    audio: + "frames" [B,S_enc,d_model]."""
    rt = rt or RuntimeConfig()
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    tokens = batch["tokens"]
    h = embed_tokens(params, arch, tokens, rt, cd)

    if arch.family == "vlm":
        with jax.named_scope("embed"):
            prefix = (batch["patches"].astype(cd)
                      @ params["patch_proj"].astype(cd))
            h = jnp.concatenate([prefix, h], axis=1)

    if arch.is_encdec:
        enc_out = _encoder_forward(params, arch,
                                   batch["frames"].astype(cd), rt)
        h, aux = _cross_decoder_forward(params, arch, h, enc_out, rt)
    else:
        h, aux = _scan_layers(params, arch, h, rt)

    logits = _head(params, arch, h, cd)
    return constrain(logits, "logits", rt), aux


def loss_fn(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
            rt: RuntimeConfig | None = None,
            policy: DTypePolicy | None = None) -> tuple[jax.Array, dict]:
    """Next-token cross entropy (+ MoE aux + z-loss)."""
    logits, aux = forward(params, arch, batch, rt, policy)
    labels = batch["labels"]
    if arch.family == "vlm":  # logits cover [patches + tokens]
        logits = logits[:, -labels.shape[1]:, :]
    lg = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    shifted = lg - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    # gold logit via masked reduce (fuses under SPMD; take_along_axis over
    # the vocab-sharded axis would all-gather the full logits tensor)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == jnp.maximum(labels, 0)[..., None], lg, 0.0),
        axis=-1)
    nll = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    z_loss = 1e-4 * jnp.sum(jnp.square(lse) * mask) / denom
    aux_w = 0.01 * aux
    loss = ce + z_loss + aux_w
    return loss, {"ce": ce, "z_loss": z_loss, "aux": aux_w,
                  "tokens": jnp.sum(mask)}


# ======================================================================
# Decode caches
# ======================================================================
def make_cache(arch: ArchConfig, seq_len: int, batch: int,
               policy: DTypePolicy | None = None) -> Params:
    """Allocate (or shape-spec, under eval_shape) the decode cache."""
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    hd = arch.resolved_head_dim
    L, B = arch.n_layers, batch
    cache: Params = {"len": jnp.zeros((), jnp.int32)}
    if arch.family in ("dense", "moe", "vlm", "audio"):
        if arch.attn_type == "mla":
            cache["c_kv"] = jnp.zeros((L, B, seq_len, arch.kv_lora_rank), cd)
            cache["k_rope"] = jnp.zeros((L, B, seq_len, arch.rope_head_dim), cd)
        else:
            cache["k"] = jnp.zeros((L, B, arch.n_kv_heads, seq_len, hd), cd)
            cache["v"] = jnp.zeros((L, B, arch.n_kv_heads, seq_len, hd), cd)
    if arch.is_encdec:
        s_enc = max(seq_len // arch.cross_len_frac, 16)
        cache["cross_k"] = jnp.zeros((L, B, arch.n_kv_heads, s_enc, hd), cd)
        cache["cross_v"] = jnp.zeros((L, B, arch.n_kv_heads, s_enc, hd), cd)
    if arch.family in ("ssm", "hybrid"):
        scfg = ssm_config(arch)
        cache["ssm_h"] = jnp.zeros(
            (L, B, scfg.n_heads, scfg.head_dim, scfg.d_state), jnp.float32)
        cache["ssm_conv"] = jnp.zeros(
            (L, B, scfg.conv_width - 1, scfg.conv_channels), cd)
    if arch.family == "hybrid" and arch.shared_attn_every:
        n_uses = -(-arch.n_layers // arch.shared_attn_every)
        cache["shared_k"] = jnp.zeros(
            (n_uses, B, arch.n_kv_heads, seq_len, hd), cd)
        cache["shared_v"] = jnp.zeros(
            (n_uses, B, arch.n_kv_heads, seq_len, hd), cd)
    return cache


# ======================================================================
# Decode step
# ======================================================================
def _cross_attn_decode(bp: Params, arch: ArchConfig, x: jax.Array,
                       ck: jax.Array, cv: jax.Array) -> jax.Array:
    b = x.shape[0]
    hd = arch.resolved_head_dim
    q = (x @ bp["cross"]["wq"].astype(x.dtype)).reshape(
        b, arch.n_heads, hd)
    g = arch.n_heads // arch.n_kv_heads
    qg = q.reshape(b, arch.n_kv_heads, g, hd)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) / jnp.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", w, cv.astype(jnp.float32))
    o = o.reshape(b, 1, arch.n_heads * hd).astype(x.dtype)
    return o @ bp["cross"]["wo"].astype(x.dtype)


def _ffn(bp: Params, arch: ArchConfig, h: jax.Array,
         matmul=cast_matmul) -> jax.Array:
    """The feed-forward half of a decoder layer: norm, then the MLP (the
    experts under ``moe``), added to the residual ``h``.  ``matmul`` is
    the dense MLP's (``common.cast_matmul``)."""
    with jax.named_scope("mlp"):
        x = rms_norm(h, bp["ln2"]["scale"])
        if arch.family == "moe":
            return _residual(arch, h, moe_apply(bp["moe"], moe_config(arch), x))
        return _residual(arch, h, mlp_apply(bp["mlp"], x, arch.act, matmul))


def _split_stacks(blocks: Params, names: tuple[str, ...]
                  ) -> tuple[Params, Params]:
    """The matrix stacks ``[L, in, out]`` right under ``blocks[name]``
    for each of ``names`` (the norms there are dicts), and the rest of
    ``blocks``."""
    mats, rest = {}, dict(blocks)
    for name in names:
        if name in blocks:
            sub = blocks[name]
            mats[name] = {k: w for k, w in sub.items()
                          if not isinstance(w, dict)}
            rest[name] = {k: w for k, w in sub.items() if isinstance(w, dict)}
    return mats, rest


def _stack_matmul(layer: jax.Array):
    """The matmul of a decode layer whose weights are whole layer stacks
    ``[L, in, out]``: ``x @ w[layer]`` in ``x``'s dtype.  The
    weight-streaming kernel reads each float32 block of the layer once
    and rounds it in VMEM, so no bfloat16 copy of a stack is written.
    A program that a launcher partitions over a mesh slices and casts in
    XLA instead, which partitions the dot; it cannot partition a kernel."""
    if partitioned():
        return lambda x, *ws: cast_matmul(x, *(w[layer] for w in ws))
    return lambda x, *ws: weight_stream(x, ws, layer)


def decode_step(params: Params, arch: ArchConfig, cache: Params,
                tokens: jax.Array, rt: RuntimeConfig | None = None,
                policy: DTypePolicy | None = None
                ) -> tuple[jax.Array, Params]:
    """One decode step.  tokens: [B, 1] new token ids."""
    rt = rt or RuntimeConfig()
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    h = embed_tokens(params, arch, tokens, rt, cd)
    pos = cache["len"]
    acfg = attn_config(arch)
    emb0 = h

    if arch.family in ("dense", "moe", "vlm") or arch.is_encdec:
        mla = arch.attn_type == "mla"
        keys = ("c_kv", "k_rope") if mla else ("k", "v")
        # attention's and the dense MLP's matrices stay whole float32
        # stacks, closed over by the scan (scanning them would slice
        # each layer out), and are read by the layer's matmul
        # (_stack_matmul); MLA's up-projections, which the absorbed
        # attention applies per head, are sliced per layer and stay
        # float32; the rest that is cast (MoE experts, cross attention)
        # is cast once, under its scope, where XLA would otherwise hoist
        # each layer's cast out of the scan with no name
        mats, rest = _split_stacks(params["blocks"], ("attn", "mlp"))
        per_head = {k: mats["attn"].pop(k) for k in ("wk_b", "wv_b") if mla}
        # GQA's cache is scanned; MLA's stacks are closed over, and the
        # latent attention reads its layer's slice itself, so that its
        # scope (`latent`) holds all of its reading of the cache
        stacks = (cache[keys[0]], cache[keys[1]])
        xs = (_cast_blocks(rest, cd), per_head, jnp.arange(arch.n_layers),
              () if mla else stacks)
        if arch.is_encdec:
            xs += (cache["cross_k"], cache["cross_v"])

        def layer(carry, x):
            hh = carry
            bp, ph, idx, kv = x[:4]
            bp = {**bp, **{k: {**bp[k], **m} for k, m in mats.items()}}
            bp["attn"] = {**bp["attn"], **ph}
            matmul = _stack_matmul(idx)
            with jax.named_scope("attn"):
                xn = rms_norm(hh, bp["ln"]["scale"])
                if mla:
                    o, kv = mla_decode(bp["attn"], acfg, xn, stacks, pos,
                                       matmul, layer=idx)
                else:
                    o, kv = gqa_decode(bp["attn"], acfg, xn, kv, pos, matmul)
                hh = _residual(arch, hh, o)
                if arch.is_encdec:
                    xc = rms_norm(hh, bp["ln_cross"]["scale"])
                    hh = _residual(arch, hh, _cross_attn_decode(
                        bp, arch, xc[:, 0], *x[4:]))
            return _ffn(bp, arch, hh, matmul), kv

        with jax.named_scope("layers"):
            h, (c0, c1) = jax.lax.scan(layer, h, xs)
        cache = {**cache, keys[0]: c0, keys[1]: c1}
    else:  # ssm / hybrid
        scfg = ssm_config(arch)
        every = arch.shared_attn_every
        sk = cache.get("shared_k")
        sv = cache.get("shared_v")

        def layer(carry, x):
            hh, sk, sv = carry
            bp, hc, cc, idx = x
            with jax.named_scope("mlp"):
                xn = rms_norm(hh, bp["ln"]["scale"])
                o, (hc, cc) = mamba2_decode(bp["mamba"], scfg, xn, (hc, cc))
                hh = _residual(arch, hh, o)

            if arch.family == "hybrid" and every:
                u = idx // every

                def do_shared(args):
                    hh, sk, sv = args
                    sp = params["shared"]
                    with jax.named_scope("attn"):
                        z = jnp.concatenate([hh, emb0.astype(hh.dtype)], -1)
                        z = z @ sp["w_cat"].astype(hh.dtype)
                        xn2 = rms_norm(z, sp["ln"]["scale"])
                        ku, vu = sk[u], sv[u]
                        o2, (ku, vu) = gqa_decode(sp["attn"], acfg, xn2,
                                                  (ku, vu), pos)
                        z = z + o2
                        sk = jax.lax.dynamic_update_index_in_dim(sk, ku, u, 0)
                        sv = jax.lax.dynamic_update_index_in_dim(sv, vu, u, 0)
                    with jax.named_scope("mlp"):
                        x2 = rms_norm(z, sp["ln2"]["scale"])
                        z = z + mlp_apply(sp["mlp"], x2, arch.act)
                    return hh + z, sk, sv

                hh, sk, sv = jax.lax.cond(
                    (idx % every) == 0, do_shared, lambda a: a, (hh, sk, sv))
            return (hh, sk, sv), (hc, cc)

        if sk is None:
            sk = jnp.zeros((1,), jnp.float32)
            sv = jnp.zeros((1,), jnp.float32)
        with jax.named_scope("layers"):
            (h, sk, sv), (hc, cc) = jax.lax.scan(
                layer, (h, sk, sv),
                (params["blocks"], cache["ssm_h"], cache["ssm_conv"],
                 jnp.arange(arch.n_layers)))
        cache = {**cache, "ssm_h": hc, "ssm_conv": cc}
        if arch.family == "hybrid" and every:
            cache = {**cache, "shared_k": sk, "shared_v": sv}

    logits = _head(params, arch, h, cd)
    cache = {**cache, "len": cache["len"] + 1}
    return constrain(logits, "logits", rt), cache


def prefill(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
            cache_len: int, rt: RuntimeConfig | None = None,
            policy: DTypePolicy | None = None) -> tuple[jax.Array, Params]:
    """Run the full-sequence forward and populate a decode cache of
    capacity ``cache_len`` (>= prompt length)."""
    rt = rt or RuntimeConfig()
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = make_cache(arch, cache_len, b, policy)
    h = embed_tokens(params, arch, tokens, rt, cd)
    acfg = attn_config(arch)
    # the attention families use every layer weight in the compute dtype
    # (the SSM mixer keeps some in f32): cast the stack once, as in decode
    blocks = params["blocks"]
    if arch.family in ("dense", "moe", "vlm") or arch.is_encdec:
        blocks = _cast_blocks(blocks, cd)

    if arch.is_encdec:
        # encoder once; decoder prefill caches self-KV + per-layer cross-KV
        enc_out = _encoder_forward(params, arch,
                                   batch["frames"].astype(cd), rt)
        hd = arch.resolved_head_dim
        xcfg = attn_config(arch, causal=False)

        def layer(hh, bp):
            with jax.named_scope("attn"):
                xn = rms_norm(hh, bp["ln"]["scale"])
                o, (kc, vc) = gqa_prefill(bp["attn"], acfg, xn)
                hh = _residual(arch, hh, o)
                xc = rms_norm(hh, bp["ln_cross"]["scale"])
                be, se, _ = enc_out.shape
                q = (xc @ bp["cross"]["wq"].astype(cd)).reshape(
                    be, -1, xcfg.n_heads, hd)
                xk = (enc_out.astype(cd) @ bp["cross"]["wk"].astype(cd)
                      ).reshape(be, se, xcfg.n_kv_heads, hd)
                xv = (enc_out.astype(cd) @ bp["cross"]["wv"].astype(cd)
                      ).reshape(be, se, xcfg.n_kv_heads, hd)
                o2 = flash_attention(jnp.swapaxes(q, 1, 2),
                                     jnp.swapaxes(xk, 1, 2),
                                     jnp.swapaxes(xv, 1, 2), causal=False)
                o2 = o2.swapaxes(1, 2).reshape(be, -1, xcfg.n_heads * hd)
                hh = _residual(arch, hh, o2 @ bp["cross"]["wo"].astype(cd))
            hh = _ffn(bp, arch, hh)
            return constrain(hh, "hidden", rt), (
                kc, vc, jnp.swapaxes(xk, 1, 2), jnp.swapaxes(xv, 1, 2))

        with jax.named_scope("layers"):
            h, (kc, vc, xk, xv) = jax.lax.scan(layer, h, blocks)
            pad = ((0, 0), (0, 0), (0, 0), (0, cache_len - s), (0, 0))
            cache["k"] = jnp.pad(kc.astype(cd), pad)
            cache["v"] = jnp.pad(vc.astype(cd), pad)
            s_enc = cache["cross_k"].shape[3]
            cache["cross_k"] = xk[:, :, :, :s_enc].astype(cd)
            cache["cross_v"] = xv[:, :, :, :s_enc].astype(cd)
    elif arch.family in ("dense", "moe", "vlm"):
        mla = arch.attn_type == "mla"
        attend = mla_prefill if mla else gqa_prefill
        keys = ("c_kv", "k_rope") if mla else ("k", "v")

        def layer(hh, bp):
            with jax.named_scope("attn"):
                xn = rms_norm(hh, bp["ln"]["scale"])
                o, (c0, c1) = attend(bp["attn"], acfg, xn)
                hh = _residual(arch, hh, o)
            return constrain(_ffn(bp, arch, hh), "hidden", rt), (c0, c1)

        with jax.named_scope("layers"):
            h, (c0, c1) = jax.lax.scan(layer, h, blocks)
            for key, c in zip(keys, (c0, c1)):
                # [L, B, (heads,) S, D]: pad the positions to cache_len
                pad = [(0, 0)] * c.ndim
                pad[-2] = (0, cache_len - s)
                cache[key] = jnp.pad(c.astype(cd), pad)
    elif arch.family in ("ssm", "hybrid"):
        def layer(hh, bp):
            with jax.named_scope("mlp"):
                xn = rms_norm(hh, bp["ln"]["scale"])
                o, (hf, conv_tail) = mamba2_apply(
                    bp["mamba"], ssm_config(arch), xn, return_state=True)
                hh = _residual(arch, hh, o)
            return constrain(hh, "hidden", rt), (hf, conv_tail)

        # Note: prefill for hybrid ignores the shared attention block's
        # cache population here for brevity of the driver; serving tests
        # exercise decode_step from a zero cache instead.
        with jax.named_scope("layers"):
            h, (hf, conv_tail) = jax.lax.scan(layer, h, blocks)
        cache["ssm_h"] = hf
        cache["ssm_conv"] = conv_tail.astype(cd)
    logits = _head(params, arch, h, cd, last_only=True)
    cache = {**cache, "len": jnp.asarray(s, jnp.int32)}
    return logits, cache
