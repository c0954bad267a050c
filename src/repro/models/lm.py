"""Unified language-model assembly for all assigned architectures.

One functional model covering the six families (dense / moe / ssm /
hybrid / vlm / audio-encdec).  ``layer_parts`` reads the family from the
``ArchConfig`` once and names a layer's parts: its mixer (GQA, MLA or
Mamba2), cross attention or none, MLP, experts or none, and the shared
attention block or none; everything below follows it.  Layers are
pre-stacked and consumed with ``lax.scan`` (+ per-layer remat), so HLO
size and compile time are O(1) in depth — required for 96-layer,
340B-parameter dry-runs.  One full-sequence layer (``_layer``) serves
training, scoring and prefill, which differ only in the function that
runs a causal self-attention; the decode step scans one body that runs
the same layer with each mixer's one-token form.

Public entry points:
  init_model(key, arch, policy)                  -> params
  forward(params, arch, batch, rt)               -> (logits, aux_loss)
  loss_fn(params, arch, batch, rt)               -> (loss, metrics)
  make_cache(arch, seq_len, batch, policy)       -> decode cache pytree
  prefill(params, arch, batch, cache_len, rt)    -> (last logits, cache)
  decode_step(params, arch, cache, tokens, rt)   -> (logits, cache)

Activation-sharding hooks go through ``repro.launch.sharding.constrain``
so the model code stays mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RuntimeConfig
from repro.kernels import weight_stream
from repro.launch.sharding import constrain, partitioned
from repro.models import attention
from repro.models.attention import (AttnConfig, cross_apply, cross_decode,
                                    gqa_apply, gqa_decode, gqa_init,
                                    mla_apply, mla_decode, mla_init)
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
# The layer's parts: the one place that reads the family
# ======================================================================
@dataclasses.dataclass(frozen=True)
class Parts:
    mixer: str                 # "gqa" | "mla" | "mamba"
    ffn: str | None            # "mlp" | "moe" | None
    cross: bool = False        # cross attention to an encoder's output
    shared_every: int = 0      # zamba2's shared block after every n-th layer
    patches: bool = False      # a projected image prefix before the tokens
    causal: bool = True        # the mixer's attention (False: an encoder's)


def layer_parts(arch: ArchConfig) -> Parts:
    """The parts of ``arch``'s decoder layer, and the model's extras: an
    encoder (which cross attention reads) and an image prefix."""
    ssm = arch.family in ("ssm", "hybrid")
    return Parts(
        mixer="mamba" if ssm else arch.attn_type,
        ffn=None if ssm else ("moe" if arch.family == "moe" else "mlp"),
        cross=arch.is_encdec,
        shared_every=arch.shared_attn_every if arch.family == "hybrid" else 0,
        patches=arch.family == "vlm")


# the shared block's layer, and the encoder's
_GQA_MLP = Parts("gqa", "mlp")
_ENCODER = Parts("gqa", "mlp", causal=False)
# each mixer's decode-cache entries, by their cache keys
_CACHE_KEYS = {"gqa": ("k", "v"), "mla": ("c_kv", "k_rope"),
               "mamba": ("ssm_h", "ssm_conv")}
# the entries that hold a row per position, [..., S, D]: prefill pads
# them to the cache's length (the cross K/V keep the encoder's length)
_PER_POSITION = ("k", "v", "c_kv", "k_rope", "shared_k", "shared_v")


def _layer_init(key, arch: ArchConfig, parts: Parts) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    d = arch.d_model
    p: Params = {"ln": norm_init(d)}
    if parts.mixer == "mamba":
        p["mamba"] = mamba2_init(k1, ssm_config(arch))
    else:
        init = mla_init if parts.mixer == "mla" else gqa_init
        p["attn"] = init(k1, attn_config(arch))
    if parts.cross:
        p["cross"] = gqa_init(k3, attn_config(arch, causal=False))
        p["ln_cross"] = norm_init(d)
    if parts.ffn:
        p["ln2"] = norm_init(d)
    if parts.ffn == "moe":
        p["moe"] = moe_init(k2, moe_config(arch))
    elif parts.ffn == "mlp":
        p["mlp"] = mlp_init(k2, d, arch.d_ff, arch.gated_mlp)
    return p


def _residual(arch: ArchConfig, h: jax.Array, branch: jax.Array) -> jax.Array:
    """``h`` plus a residual branch scaled by ``arch.residual_scale``; the
    scale is applied only where it is not 1, so the default adds no op."""
    if arch.residual_scale != 1.0:
        branch = branch * jnp.asarray(arch.residual_scale, branch.dtype)
    return h + branch


Mix = Callable[[Params, jax.Array], tuple[jax.Array, tuple]]


def _layer(p: Params, arch: ArchConfig, parts: Parts, h: jax.Array,
           rt: RuntimeConfig, mix: Mix, cross: Mix | None = None,
           matmul=cast_matmul) -> tuple[jax.Array, jax.Array | None, dict]:
    """One decoder layer of ``parts``: the mixer, then cross attention,
    then the MLP or the experts, each normed first and added to the
    residual.  ``mix(params, x)`` and ``cross(params, x)`` return their
    output and their cache entries; the full-sequence layer (forward,
    prefill) and the decode step's differ only in them and in
    ``matmul``, the MLP's (``common.cast_matmul``).  Returns (h, the
    experts' load-balance loss or None, the cache entries by key)."""
    # Megatron-SP: sub-block outputs are constrained to the seq-sharded
    # "hidden" layout BEFORE the residual add, so the TP partial-sum
    # lowers to a reduce-scatter and the residual add stays local
    # (otherwise GSPMD all-gathers the residual at every add —
    # measured ~7 hidden-sized gathers/layer on mistral, §Perf it.4).
    ssm = parts.mixer == "mamba"
    # the Mamba2 mixer is counted with the MLPs
    with jax.named_scope("mlp" if ssm else "attn"):
        x = constrain(rms_norm(h, p["ln"]["scale"]), "tp_in", rt)
        o, entries = mix(p["mamba" if ssm else "attn"], x)
        h = _residual(arch, h, constrain(o, "hidden", rt))
        entries = dict(zip(_CACHE_KEYS[parts.mixer], entries))
        if parts.cross:
            x = rms_norm(h, p["ln_cross"]["scale"])
            o, (entries["cross_k"], entries["cross_v"]) = cross(p["cross"], x)
            h = _residual(arch, h, o)
    aux = None
    if parts.ffn:
        with jax.named_scope("mlp"):
            x = constrain(rms_norm(h, p["ln2"]["scale"]), "tp_in", rt)
            if parts.ffn == "moe":
                o = moe_apply(p["moe"], moe_config(arch), x)
                aux = aux_load_balance_loss(p["moe"], moe_config(arch), x)
            else:
                o = mlp_apply(p["mlp"], x, arch.act, matmul)
            h = _residual(arch, h, constrain(o, "hidden", rt))
    return constrain(h, "hidden", rt), aux, entries


def _shared_block(p: Params, arch: ArchConfig, h: jax.Array,
                  emb0: jax.Array, rt: RuntimeConfig, mix: Mix
                  ) -> tuple[jax.Array, dict]:
    """zamba2-style shared block: a GQA and MLP layer over concat(h,
    emb0) projected by ``w_cat``, added to ``h``.  Returns h and the
    block's K/V entries."""
    with jax.named_scope("attn"):
        z = jnp.concatenate([h, emb0.astype(h.dtype)], axis=-1)
        z = z @ p["w_cat"].astype(h.dtype)
    z, _, kv = _layer(p, arch, _GQA_MLP, z, rt, mix)
    return h + z, kv


# ======================================================================
# Model init
# ======================================================================
def init_model(key: jax.Array, arch: ArchConfig,
               policy: DTypePolicy | None = None) -> Params:
    policy = policy or DTypePolicy.standard()
    parts = layer_parts(arch)
    ks = jax.random.split(key, 8)
    d = arch.d_model
    params: Params = {
        # vocab padded to a multiple of 128 (TPU lanes + mesh divisibility)
        "embed": truncated_normal_init(ks[0], (arch.padded_vocab, d), 1.0),
        "final_norm": norm_init(d),
    }
    if not arch.tie_embeddings:
        params["head"] = dense_init(ks[1], d, arch.padded_vocab)
    params["blocks"] = jax.vmap(lambda k: _layer_init(k, arch, parts))(
        jax.random.split(ks[2], arch.n_layers))
    if parts.shared_every:
        k1, k2, _ = jax.random.split(ks[3], 3)
        params["shared"] = {**_layer_init(k1, arch, _GQA_MLP),
                            "w_cat": dense_init(k2, 2 * d, d)}
    if parts.cross:
        params["enc_blocks"] = jax.vmap(
            lambda k: _layer_init(k, arch, _ENCODER))(
            jax.random.split(ks[4], arch.enc_layers))
        params["enc_norm"] = norm_init(d)
    if parts.patches:
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
    scales etc.) are rank 2 and stay f32 (rms_norm computes in f32), as
    do the Mamba2 mixer's stacks (its convolution runs in f32).
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
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(dtype) if (
            x.ndim >= 3 and x.dtype == jnp.float32
            and path[0].key != "mamba") else x,
        blocks)


def _full_mix(arch: ArchConfig, parts: Parts, attend) -> Mix:
    """The full-sequence mixer of ``parts``; ``attend`` runs a causal
    self-attention (see ``attention.gqa_apply``)."""
    if parts.mixer == "mamba":
        scfg = ssm_config(arch)
        return lambda p, x: mamba2_apply(p, scfg, x, return_state=True)
    apply = mla_apply if parts.mixer == "mla" else gqa_apply
    acfg = attn_config(arch, parts.causal)
    return lambda p, x: apply(p, acfg, x, attend)


def _layers(params: Params, blocks: Params, arch: ArchConfig, parts: Parts,
            h: jax.Array, rt: RuntimeConfig, attend, cross: Mix | None = None
            ) -> tuple[jax.Array, jax.Array, dict]:
    """The full-sequence layer scan of forward and prefill over
    ``blocks``.  Returns h, the summed aux loss and every cache entry
    stacked over the layers (the shared block's over its uses)."""
    emb0 = h
    mix = _full_mix(arch, parts, attend)
    every = parts.shared_every

    def one_layer(hh, xs):
        bp, idx = xs
        hh, aux, entries = _layer(bp, arch, parts, hh, rt, mix, cross)
        if every:
            shared = lambda v: _shared_block(    # noqa: E731
                params["shared"], arch, v, emb0, rt,
                _full_mix(arch, _GQA_MLP, attend))
            # where the cond skips the block its entries are zeros,
            # dropped after the scan
            skip = lambda v: (v, jax.tree.map(   # noqa: E731
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(shared, v)[1]))
            hh, kv = jax.lax.cond(idx % every == 0, shared, skip, hh)
            entries.update(shared_k=kv["k"], shared_v=kv["v"])
        return hh, (aux, entries)

    layer = one_layer
    if rt.remat == "full":
        layer = jax.checkpoint(
            one_layer, policy=jax.checkpoint_policies.nothing_saveable)
    blocks = _cast_blocks(blocks, h.dtype)
    # the layer index is read by the shared block's cond alone
    idx = jnp.arange(arch.n_layers) if every else None
    with jax.named_scope("layers"):
        h, (auxs, entries) = jax.lax.scan(layer, h, (blocks, idx))
    if every:
        # the layers that ran the shared block
        entries = {k: c[::every] if k.startswith("shared_") else c
                   for k, c in entries.items()}
    aux = jnp.zeros((), jnp.float32) if auxs is None else jnp.sum(auxs)
    return h, aux, entries


def _decoder(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
             rt: RuntimeConfig, cd, attend
             ) -> tuple[jax.Array, jax.Array, dict]:
    """Forward's and prefill's common part: embed the tokens (after the
    image prefix where the model has one), run the encoder where it has
    one, then the decoder layers.  Returns ``_layers``'s answer."""
    parts = layer_parts(arch)
    h = embed_tokens(params, arch, batch["tokens"], rt, cd)
    if parts.patches:
        with jax.named_scope("embed"):
            prefix = (batch["patches"].astype(cd)
                      @ params["patch_proj"].astype(cd))
            h = jnp.concatenate([prefix, h], axis=1)
    cross = None
    if parts.cross:
        enc = _layers(params, params["enc_blocks"], arch, _ENCODER,
                      batch["frames"].astype(cd), rt,
                      attention.flash_attention)[0]
        enc = rms_norm(enc, params["enc_norm"]["scale"])
        xcfg = attn_config(arch, causal=False)
        cross = lambda p, x: cross_apply(p, xcfg, x, enc)   # noqa: E731
    return _layers(params, params["blocks"], arch, parts, h, rt, attend,
                   cross)


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
    h, aux, _ = _decoder(params, arch, batch, rt, policy.compute,
                         attention.flash_attention)
    logits = _head(params, arch, h, policy.compute)
    return constrain(logits, "logits", rt), aux


def loss_fn(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
            rt: RuntimeConfig | None = None,
            policy: DTypePolicy | None = None) -> tuple[jax.Array, dict]:
    """Next-token cross entropy (+ MoE aux + z-loss)."""
    logits, aux = forward(params, arch, batch, rt, policy)
    labels = batch["labels"]
    # logits cover an image prefix, where there is one, then the tokens
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


def prefill(params: Params, arch: ArchConfig, batch: dict[str, jax.Array],
            cache_len: int, rt: RuntimeConfig | None = None,
            policy: DTypePolicy | None = None) -> tuple[jax.Array, Params]:
    """The full-sequence forward, whose causal self-attention runs
    ``attention.prefill_attention``, and the decode cache it fills, of
    capacity ``cache_len`` (>= the prompt with its image prefix).
    Returns the last position's logits and the cache."""
    rt = rt or RuntimeConfig()
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    h, _, entries = _decoder(params, arch, batch, rt, cd,
                             attention.prefill_attention)
    s = h.shape[1]
    cache = {}
    with jax.named_scope("layers"):
        for key, c in entries.items():
            if key in _PER_POSITION:
                pad = [(0, 0)] * c.ndim
                pad[-2] = (0, cache_len - s)
                c = jnp.pad(c, pad)
            cache[key] = c
    logits = _head(params, arch, h, cd, last_only=True)
    cache["len"] = jnp.asarray(s, jnp.int32)
    return logits, cache


# ======================================================================
# Decode
# ======================================================================
def make_cache(arch: ArchConfig, seq_len: int, batch: int,
               policy: DTypePolicy | None = None) -> Params:
    """Allocate (or shape-spec, under eval_shape) the decode cache."""
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    parts = layer_parts(arch)
    L, B = arch.n_layers, batch
    kv = (arch.n_kv_heads, seq_len, arch.resolved_head_dim)
    cache: Params = {"len": jnp.zeros((), jnp.int32)}
    if parts.mixer == "gqa":
        cache["k"] = cache["v"] = jnp.zeros((L, B, *kv), cd)
    elif parts.mixer == "mla":
        cache["c_kv"] = jnp.zeros((L, B, seq_len, arch.kv_lora_rank), cd)
        cache["k_rope"] = jnp.zeros((L, B, seq_len, arch.rope_head_dim), cd)
    else:
        scfg = ssm_config(arch)
        cache["ssm_h"] = jnp.zeros(
            (L, B, scfg.n_heads, scfg.head_dim, scfg.d_state), jnp.float32)
        cache["ssm_conv"] = jnp.zeros(
            (L, B, scfg.conv_width - 1, scfg.conv_channels), cd)
    if parts.cross:
        s_enc = max(seq_len // arch.cross_len_frac, 16)
        cache["cross_k"] = cache["cross_v"] = jnp.zeros(
            (L, B, arch.n_kv_heads, s_enc, arch.resolved_head_dim), cd)
    if parts.shared_every:
        n_uses = -(-arch.n_layers // parts.shared_every)
        cache["shared_k"] = cache["shared_v"] = jnp.zeros((n_uses, B, *kv), cd)
    return cache


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
    """One decode step.  tokens: [B, 1] new token ids.  Each layer runs
    ``_layer`` with its mixer's and cross attention's one-token forms,
    then, where the model has one, the shared block."""
    rt = rt or RuntimeConfig()
    policy = policy or DTypePolicy.standard()
    cd = policy.compute
    parts = layer_parts(arch)
    h = embed_tokens(params, arch, tokens, rt, cd)
    pos = cache["len"]
    acfg, xcfg = attn_config(arch), attn_config(arch, causal=False)
    emb0 = h
    scfg = ssm_config(arch)
    mla = parts.mixer == "mla"
    keys = _CACHE_KEYS[parts.mixer]
    # attention's and the dense MLP's matrices stay whole float32
    # stacks, closed over by the scan (scanning them would slice
    # each layer out), and are read by the layer's matmul
    # (_stack_matmul); MLA's up-projections, which the absorbed
    # attention applies per head, are sliced per layer and stay
    # float32; the rest that is cast (MoE experts, cross attention)
    # is cast once, under its scope, where XLA would otherwise hoist
    # each layer's cast out of the scan with no name
    mats, rest = _split_stacks(params["blocks"], ("attn", "mlp"))
    per_head = {"attn": {k: mats["attn"].pop(k)
                         for k in ("wk_b", "wv_b")}} if mla else {}
    # GQA's and Mamba2's caches are scanned; MLA's stacks are closed
    # over, and the latent attention reads its layer's slice itself, so
    # that its scope (`latent`) holds all of its reading of the cache
    stacks = tuple(cache[k] for k in keys)
    cross = tuple(cache[k] for k in ("cross_k", "cross_v") if parts.cross)
    xs = (_cast_blocks(rest, cd), per_head, jnp.arange(arch.n_layers),
          () if mla else stacks, cross)
    every = parts.shared_every
    shared = {k: cache["shared_" + k] for k in ("k", "v") if every}

    def layer(carry, x):
        hh, sh = carry
        bp, ph, idx, kv, cross_kv = x
        for group, m in (*mats.items(), *ph.items()):
            bp = {**bp, group: {**bp[group], **m}}
        matmul = _stack_matmul(idx)
        mix = {
            "gqa": lambda p, xn: gqa_decode(p, acfg, xn, kv, pos, matmul),
            "mla": lambda p, xn: mla_decode(p, acfg, xn, stacks, pos, matmul,
                                            layer=idx),
            "mamba": lambda p, xn: mamba2_decode(p, scfg, xn, kv),
        }[parts.mixer]
        hh, _, entries = _layer(
            bp, arch, parts, hh, rt, mix,
            lambda p, xn: cross_decode(p, xcfg, xn, cross_kv), matmul)
        if every:
            def use_shared(args):
                hh, sh = args
                u = idx // every
                hh, kv = _shared_block(
                    params["shared"], arch, hh, emb0, rt,
                    lambda p, xn: gqa_decode(p, acfg, xn,
                                             (sh["k"][u], sh["v"][u]), pos))
                return hh, {k: jax.lax.dynamic_update_index_in_dim(
                    sh[k], kv[k], u, 0) for k in sh}

            hh, sh = jax.lax.cond(idx % every == 0, use_shared,
                                  lambda a: a, (hh, sh))
        return (hh, sh), {k: entries[k] for k in keys}

    with jax.named_scope("layers"):
        (h, shared), new = jax.lax.scan(layer, (h, shared), xs)
    cache = {**cache, **new, **{"shared_" + k: c for k, c in shared.items()}}
    logits = _head(params, arch, h, cd)
    cache = {**cache, "len": cache["len"] + 1}
    return constrain(logits, "logits", rt), cache
