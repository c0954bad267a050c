"""GPipe-style pipeline parallelism over a mesh axis.

The layer stack is split into P contiguous stages; each device along the
pipeline axis holds one stage's parameters.  Microbatches stream through
with the classic (M + P - 1)-tick schedule; boundary activations move
between neighbouring stages with ``jax.lax.ppermute`` inside
``shard_map``.  Intended for the "pod" axis of the production mesh
(cross-pod ICI is the slow link, and PP moves only boundary activations
across it — DESIGN.md §4); the dry-run default keeps pod as pure DP.

``pipeline_apply`` is deterministic, jit-able, and validated against the
equivalent sequential stack in tests/test_pipeline.py.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape [L, ...] stacked layer params to [P, L/P, ...]."""

    def resh(x):
        l = x.shape[0]
        assert l % n_stages == 0, "layers must divide stages"
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(resh, stacked_params)


def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    staged_params: Any,          # [P, L/P, ...] pytree
    microbatches: jax.Array,     # [M, mb, ...] inputs
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the staged stack over microbatches; returns [M, mb, ...]."""
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = m + n_stages - 1

    def stage_fwd(params_local, h):
        """Apply this device's L/P layers (params_local: [L/P, ...])."""

        def body(carry, lp):
            return layer_fn(lp, carry), None

        out, _ = jax.lax.scan(body, h, params_local)
        return out

    def shard_fn(staged_local, mbs):
        # staged_local: [1, L/P, ...] (this stage's params)
        # mbs: full [M, mb, ...] (replicated along the pipe axis)
        stage_id = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda x: x[0], staged_local)
        mb_shape = mbs.shape[1:]
        # carriers must be marked device-varying along the pipe axis
        h = jax.lax.pcast(jnp.zeros(mb_shape, mbs.dtype), (axis,),
                          to="varying")
        outs = jax.lax.pcast(jnp.zeros((m,) + mb_shape, mbs.dtype), (axis,),
                             to="varying")
        mbs = jax.lax.pcast(mbs, (axis,), to="varying")

        def tick(carry, t):
            h, outs = carry
            # first stage ingests microbatch t (while valid)
            mb_in = mbs[jnp.clip(t, 0, m - 1)]
            h = jnp.where(stage_id == 0, mb_in, h)
            h = stage_fwd(params_local, h)
            # last stage retires microbatch (t - P + 1)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(out_idx >= 0, stage_id == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h, jnp.maximum(out_idx, 0), 0),
                lambda o: o,
                outs,
            )
            # shift boundary activations to the next stage
            h = jax.lax.ppermute(
                h, axis,
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (h, outs), None

        (h, outs), _ = jax.lax.scan(tick, (h, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them back
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, 0.0), axis)
        return outs

    pspec_params = jax.tree.map(lambda _: P(axis), staged_params)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(pspec_params, P()),
        out_specs=P(),
    )
    return fn(staged_params, microbatches)
