"""Feed-forward variants: gated (SwiGLU) and plain (squared-ReLU etc.)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ACTIVATIONS, Params, cast_matmul, dense_init


def mlp_init(key: jax.Array, d_model: int, d_ff: int, gated: bool) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {
        "w_up": dense_init(ks[0], d_model, d_ff),
        "w_down": dense_init(ks[1], d_ff, d_model),
    }
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff)
    return p


@jax.named_scope("mlp")
def mlp_apply(params: Params, x: jax.Array, act: str = "silu",
              matmul=cast_matmul) -> jax.Array:
    """The gated (or plain) MLP; ``matmul(x, *ws)`` multiplies ``x`` by
    each weight (see ``common.cast_matmul``), gate and up in one call."""
    f = ACTIVATIONS[act]
    if "w_gate" in params:
        up, gate = matmul(x, params["w_up"], params["w_gate"])
        up = f(gate) * up
    else:
        (up,) = matmul(x, params["w_up"])
        up = f(up)
    (out,) = matmul(up, params["w_down"])
    return out
