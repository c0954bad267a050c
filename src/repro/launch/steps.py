"""Step builders: the jitted SPMD programs the launcher lowers.

``make_train_step`` builds loss -> grad -> AdamW update with optional
microbatch gradient accumulation (lax.scan over the split batch, grads
accumulated in the policy's moment dtype to bound HBM).  ``make_*_step``
variants for serving build prefill and single-token decode.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RuntimeConfig
from repro.models.common import DTypePolicy
from repro.models.lm import decode_step, loss_fn, prefill
from repro.optim import adamw


def make_train_step(arch: ArchConfig, rt: RuntimeConfig,
                    policy: DTypePolicy,
                    opt_cfg: adamw.AdamWConfig | None = None) -> Callable:
    opt_cfg = opt_cfg or adamw.AdamWConfig()

    def micro_loss(params, mb):
        loss, metrics = loss_fn(params, arch, mb, rt, policy)
        return loss, metrics

    grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

    def train_step(params, opt_state, batch):
        a = rt.accum_steps
        if a <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape(a, x.shape[0] // a, *x.shape[1:])

            mbs = jax.tree.map(split, batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, policy.moments), params)

            def acc(carry, mb):
                g_sum, l_sum = carry
                (l, _), g = grad_fn(params, mb)
                g_sum = jax.tree.map(
                    lambda s, gi: s + gi.astype(policy.moments), g_sum, g)
                return (g_sum, l_sum + l), None

            (g_sum, l_sum), _ = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / a, g_sum)
            loss = l_sum / a
            metrics = {}
        new_params, new_opt, stats = adamw.update(
            grads, opt_state, params, opt_cfg, policy)
        return new_params, new_opt, {"loss": loss, **stats}

    return train_step


def make_prefill_step(arch: ArchConfig, rt: RuntimeConfig,
                      policy: DTypePolicy, cache_len: int) -> Callable:
    def prefill_step(params, batch):
        return prefill(params, arch, batch, cache_len, rt, policy)

    return prefill_step


def make_decode_step(arch: ArchConfig, rt: RuntimeConfig,
                     policy: DTypePolicy) -> Callable:
    def serve_step(params, cache, tokens):
        logits, cache = decode_step(params, arch, cache, tokens, rt, policy)
        with jax.named_scope("head"):
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        return next_tok.astype(jnp.int32), logits, cache

    return serve_step
