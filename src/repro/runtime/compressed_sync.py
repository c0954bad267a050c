"""Compressed cross-pod gradient synchronization (shard_map).

Replaces the cross-pod bf16 all-reduce of gradients with:
  quantize int8 (per-tensor scale) -> all-gather over "pod" ->
  dequantize + mean locally.

Ring all-reduce moves ~2(n-1)/n x 2 bytes/elem; int8 all-gather moves
(n-1)/n x 1 byte/elem (+ one f32 scale per tensor) — a ~4x cut of the
cross-pod wire traffic, at the cost of n_pods x receive buffers and the
quantization error (error feedback in ``repro.runtime.ft`` keeps the
optimizer unbiased over steps; exactness bounds tested).

Integration point: pods compute *local* gradients (grads sharded with a
pod-local psum via shard_map over "pod"), then this sync produces the
global mean.  ``benchmarks/run.py grad_sync_bench`` lowers both variants
on the 2x16x16 mesh and reports HLO collective bytes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _q_int8(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(g.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def compressed_pod_mean(grads: Any, mesh: Mesh, axis: str = "pod") -> Any:
    """Mean of per-pod gradient pytrees across the pod axis, int8 wire
    format.  Input/output: pytree sharded P() along `axis` (replicated
    within a pod, distinct across pods -> mean across pods)."""
    n = mesh.shape[axis]

    def sync_leaf(g):
        def inner(gl):
            q, s = _q_int8(gl)
            # int8 across the wire; one f32 scale per tensor
            q_all = jax.lax.all_gather(q, axis)           # [n, ...] int8
            s_all = jax.lax.all_gather(s, axis)           # [n] f32
            deq = q_all.astype(jnp.float32) * s_all.reshape(
                (n,) + (1,) * gl.ndim)
            return jnp.mean(deq, axis=0).astype(gl.dtype)

        # in reality the grads VARY across pods (per-pod local grads) but
        # are replicated within a pod; P() can't express that, so the
        # static replication check is disabled.
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False,
        )(g)

    return jax.tree.map(sync_leaf, grads)


def uncompressed_pod_mean(grads: Any, mesh: Mesh, axis: str = "pod") -> Any:
    """Baseline: bf16 psum-mean across pods (what XLA inserts)."""
    n = mesh.shape[axis]

    def sync_leaf(g):
        def inner(gl):
            return (jax.lax.psum(gl.astype(jnp.bfloat16), axis)
                    / n).astype(gl.dtype)

        return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(g)

    return jax.tree.map(sync_leaf, grads)
