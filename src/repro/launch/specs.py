"""ShapeDtypeStruct input stand-ins for every (arch x shape) cell —
weak-type-correct, shardable, never allocated (dry-run pattern).

Also resolves the per-cell RuntimeConfig (dtype preset, accumulation,
activation sequence-sharding, kv sharding) — the launcher-side knobs
that make the big cells fit 16 GB/chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, RuntimeConfig, ShapeConfig)
from repro.models.common import DTypePolicy
from repro.models.lm import make_cache

I32 = jnp.int32
BF16 = jnp.bfloat16


def resolve_runtime(arch: ArchConfig, shape: ShapeConfig,
                    n_data_shards: int = 16,
                    profile: str = "baseline") -> RuntimeConfig:
    """Per-cell runtime knobs (see DESIGN.md §4).

    profile="baseline": paper-faithful uniform Megatron TP-16 + blanket
    accumulation rules — the §Roofline baseline.
    profile="opt": the §Perf hillclimbed configuration — accumulation
    chosen by activation-budget math (in-scan collective traffic scales
    linearly with accum, so accum is minimized subject to HBM), and
    small archs trade TP for pure-FSDP over all chips (their TP psum
    cost exceeds their compute).
    """
    n = arch.param_count_estimate()
    big = n >= 60e9
    huge = n >= 200e9
    accum = 1
    if shape.kind == "train":
        # n_data_shards should be the product of ALL batch axes (incl. pod)
        per_dev_seqs = max(shape.global_batch // n_data_shards, 1)
        if profile == "opt":
            # boundary activations (post-SP) must fit ~6 GB HBM:
            # act_bytes = L * S * d_model * 2 / TP16 per sequence
            act_per_seq = arch.n_layers * shape.seq_len * arch.d_model * 2 / 16
            budget = 6e9
            need = act_per_seq * per_dev_seqs / budget
            accum = 1
            while accum < per_dev_seqs and need > accum:
                accum *= 2
        else:
            if huge:
                accum = per_dev_seqs
            elif big:
                accum = max(per_dev_seqs // 2, 1)
            elif arch.d_model >= 2048:
                accum = max(per_dev_seqs // 8, 1)
    preset = "standard"
    if big:
        preset = "lean"
    if huge:
        preset = "ultra_lean" if shape.kind != "train" else "lean"
    axis_profile = "tp"
    # dp profile: small archs trade TP for pure FSDP; _fit_spec degrades
    # weight sharding gracefully when dims don't divide 256 (replication
    # is affordable exactly because these models are small)
    if profile == "opt" and shape.kind == "train" and n < 8e9:
        axis_profile = "dp"
    return RuntimeConfig(
        dtype_preset=preset,
        accum_steps=accum,
        seq_shard_acts=(arch.d_model >= 6144 or shape.seq_len >= 32768)
        and axis_profile == "tp",
        kv_shard="auto",
        remat="full" if shape.kind == "train" else "none",
        axis_profile=axis_profile,
    )


def policy_for(rt: RuntimeConfig) -> DTypePolicy:
    return {"standard": DTypePolicy.standard(),
            "lean": DTypePolicy.lean(),
            "ultra_lean": DTypePolicy.ultra_lean()}[rt.dtype_preset]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(arch: ArchConfig, shape: ShapeConfig,
                rt: RuntimeConfig | None = None) -> dict:
    """Step inputs for the cell.

    train/prefill: token batch (+ modality stubs).  decode: one new
    token per sequence (+ the cache spec via ``cache_specs``)."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"tokens": _sds((b, 1), I32)}
    batch: dict = {}
    if arch.family == "vlm":
        s_text = s - arch.n_patches
        batch["patches"] = _sds((b, arch.n_patches, arch.vit_dim), BF16)
        batch["tokens"] = _sds((b, s_text), I32)
        if shape.kind == "train":
            batch["labels"] = _sds((b, s_text), I32)
        return batch
    if arch.is_encdec:
        batch["frames"] = _sds((b, s, arch.d_model), BF16)
    batch["tokens"] = _sds((b, s), I32)
    if shape.kind == "train":
        batch["labels"] = _sds((b, s), I32)
    return batch


def cache_specs(arch: ArchConfig, shape: ShapeConfig,
                rt: RuntimeConfig | None = None) -> dict:
    rt = rt or resolve_runtime(arch, shape)
    policy = policy_for(rt)
    return jax.eval_shape(
        lambda: make_cache(arch, shape.seq_len, shape.global_batch, policy))


def abstract_params(arch: ArchConfig, rt: RuntimeConfig | None = None):
    from repro.models.lm import init_model
    rt = rt or RuntimeConfig()
    policy = policy_for(rt)
    return jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), arch, policy))


def abstract_opt_state(params_spec, rt: RuntimeConfig | None = None):
    from repro.optim import adamw
    rt = rt or RuntimeConfig()
    policy = policy_for(rt)
    return jax.eval_shape(lambda: adamw.init(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_spec),
        policy))
