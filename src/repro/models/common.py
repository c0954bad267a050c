"""Shared model primitives: norms, RoPE, initializers, dtype policy.

All models are pure-functional: ``init(key, cfg) -> params`` (nested
dicts of jnp arrays) and ``apply(params, ...) -> out``.  Layer stacks
are created pre-stacked on a leading [L, ...] axis and consumed with
``lax.scan`` so that compile time and HLO size stay O(1) in depth —
essential for the 96-layer dry-runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DTypePolicy:
    """Mixed-precision policy. ``lean`` presets drop the fp32 master copy
    for >=100B-param archs so optimizer state fits 16 GB/chip HBM."""
    params: Any = jnp.float32
    compute: Any = jnp.bfloat16
    moments: Any = jnp.float32

    @staticmethod
    def standard() -> "DTypePolicy":
        return DTypePolicy(jnp.float32, jnp.bfloat16, jnp.float32)

    @staticmethod
    def lean() -> "DTypePolicy":
        return DTypePolicy(jnp.float32, jnp.bfloat16, jnp.bfloat16)

    @staticmethod
    def ultra_lean() -> "DTypePolicy":
        """bf16 params + bf16 moments: 6 bytes/param optimizer footprint."""
        return DTypePolicy(jnp.bfloat16, jnp.bfloat16, jnp.bfloat16)


def truncated_normal_init(key: jax.Array, shape: tuple[int, ...],
                          scale: float, dtype=jnp.float32) -> jax.Array:
    stddev = scale / max(1.0, (shape[0] if shape else 1)) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


def dense_init(key: jax.Array, d_in: int, d_out: int,
               dtype=jnp.float32) -> jax.Array:
    return truncated_normal_init(key, (d_in, d_out), 1.0, dtype)


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_init(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                          # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Matmuls
# ----------------------------------------------------------------------
def cast_matmul(x: jax.Array, *ws: jax.Array) -> tuple[jax.Array, ...]:
    """``x @ w`` for each weight, each cast to ``x``'s dtype where it is
    used.  The layers take their matmul as an argument with this as the
    default; the decode step passes one that streams the weights from
    their layer stacks (``models/lm.py``)."""
    return tuple(x @ w.astype(x.dtype) for w in ws)


# ----------------------------------------------------------------------
# Activations
# ----------------------------------------------------------------------
def squared_relu(x: jax.Array) -> jax.Array:
    """Nemotron-4's squared ReLU."""
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": squared_relu,
    "relu": jax.nn.relu,
}


def stack_layer_init(layer_init: Callable[[jax.Array], Params],
                     key: jax.Array, n_layers: int) -> Params:
    """Initialize L layers pre-stacked on axis 0 (for lax.scan)."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(layer_init)(keys)


def count_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
