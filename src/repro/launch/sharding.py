"""Sharding rules engine.

Maps every parameter / activation / cache tensor to a PartitionSpec over
the production mesh axes ("pod", "data", "model"):

  * TP (Megatron): attention heads, FFN hidden, experts, vocab -> "model"
  * FSDP/ZeRO: the other matrix dim of every weight        -> "data"
  * DP: batch -> ("pod", "data")   (pod is pure DP; grads all-reduce)
  * SP (optional, rt.seq_shard_acts): boundary activations' sequence
    axis -> "model" (Megatron sequence parallelism)

Model code calls :func:`constrain` with a *role* string; outside a
launcher context it is the identity, so models stay mesh-agnostic and
unit tests see no sharding machinery.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# ----------------------------------------------------------------------
# Activation-sharding context
# ----------------------------------------------------------------------
_SHARDER: contextvars.ContextVar[Callable | None] = contextvars.ContextVar(
    "activation_sharder", default=None)
_TP_HINT: contextvars.ContextVar[int] = contextvars.ContextVar(
    "tp_hint", default=1)


def tp_hint() -> int:
    """Tensor-parallel degree the launcher is lowering for (1 = none).
    Models use it to replicate GQA kv heads up to a multiple of TP so
    the head axis shards exactly (kv replication, standard Megatron)."""
    return _TP_HINT.get()


def partitioned() -> bool:
    """True while a launcher lowers for a mesh (its activation sharder
    is installed): the program is partitioned by XLA, which cannot
    partition a custom kernel, so models keep such kernels off it."""
    return _SHARDER.get() is not None


def constrain(x: jax.Array, role: str, rt: Any = None) -> jax.Array:
    fn = _SHARDER.get()
    if fn is None:
        return x
    return fn(x, role, rt)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, batch_axes: tuple[str, ...],
                        seq_shard_acts: bool = False,
                        axis_profile: str = "tp"):
    """Install the launcher's activation sharder."""
    vocab_axis = "model" if axis_profile == "tp" else None

    def sharder(x: jax.Array, role: str, rt: Any = None) -> jax.Array:
        if x.ndim < 2:
            return x
        bspec = batch_axes if batch_axes else None
        seq = None
        if role == "hidden":
            if (seq_shard_acts and x.ndim == 3
                    and x.shape[1] % mesh.shape["model"] == 0
                    and x.shape[1] > 1):
                seq = "model"
            spec = P(bspec, seq, *([None] * (x.ndim - 2)))
        elif role == "tp_in":
            # explicit SP -> TP transition: activations enter the
            # tensor-parallel matmuls seq-UNsharded, so the weights'
            # "model" sharding survives (otherwise GSPMD all-gathers
            # full weight matrices per layer — measured 48x collective
            # blow-up on mistral-123b, see EXPERIMENTS.md §Perf)
            spec = P(bspec, *([None] * (x.ndim - 1)))
        elif role == "logits":
            spec = P(bspec, None, vocab_axis)
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    tok = _SHARDER.set(sharder)
    tok2 = _TP_HINT.set(int(mesh.shape.get("model", 1))
                        if axis_profile == "tp" else 1)
    try:
        yield
    finally:
        _SHARDER.reset(tok)
        _TP_HINT.reset(tok2)


# ----------------------------------------------------------------------
# Batch axes
# ----------------------------------------------------------------------
def batch_axes_for(mesh: Mesh, global_batch: int,
                   include_model: bool = False) -> tuple[str, ...]:
    """Largest prefix of (pod, data[, model]) whose product divides the
    batch.  include_model=True is the pure-DP profile (no TP): the model
    axis becomes extra data parallelism."""
    names = ("pod", "data", "model") if include_model else ("pod", "data")
    axes: list[str] = []
    prod = 1
    for name in names:
        if name in mesh.shape:
            n = mesh.shape[name]
            if global_batch % (prod * n) == 0:
                axes.append(name)
                prod *= n
    # prefer ("data",) alone if pod doesn't fit but data does
    if not axes and "data" in mesh.shape and \
            global_batch % mesh.shape["data"] == 0:
        axes = ["data"]
    return tuple(axes)


# ----------------------------------------------------------------------
# Parameter rules: leaf-name -> PartitionSpec of the *unstacked* tensor.
# A leading layer-stack axis (rank == len(spec)+1) gets None prepended.
# ----------------------------------------------------------------------
_PARAM_RULES: dict[str, P] = {
    # embeddings / head
    "embed": P("model", "data"),
    "head": P("data", "model"),
    "patch_proj": P(None, "data"),
    # attention (gqa)
    "wq": P("data", "model"),
    "wk": P("data", "model"),
    "wv": P("data", "model"),
    "wo": P("model", "data"),
    # attention (mla)
    "wq_a": P("data", None),
    "wq_b": P(None, "model"),
    "wkv_a": P("data", None),
    "wk_b": P(None, "model"),
    "wv_b": P(None, "model"),
    # mlp
    "w_up": P("data", "model"),
    "w_gate": P("data", "model"),
    "w_down": P("model", "data"),
    # moe (expert-stacked: E D F / E F D)
    "router": P("data", None),
    # mamba2
    "in_proj": P("data", "model"),
    "out_proj": P("model", "data"),
    "conv_w": P(None, "model"),
    # hybrid shared block
    "w_cat": P("data", "model"),
}

# expert-stacked MoE weights carry an [E, ...] axis -> experts on "model"
_MOE_EXPERT_RULES: dict[str, P] = {
    "w_up": P("model", "data", None),
    "w_gate": P("model", "data", None),
    "w_down": P("model", None, "data"),
}


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh | None) -> P:
    """Drop axes whose size does not divide the dimension (e.g. mamba
    in_proj's 2*d_inner + 2*state + H tail dim)."""
    if mesh is None:
        return spec
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * len(shape)):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        prod = 1
        for a in axes:
            prod *= mesh.shape.get(a, 1)
        out.append(axis if dim % prod == 0 else None)
    return P(*out)


def _to_dp_profile(spec: P) -> P:
    """Pure-FSDP profile: no tensor parallelism — the 'data' dim of each
    weight is sharded over BOTH mesh axes, 'model' dims replicate."""
    out = []
    for axis in spec:
        if axis == "data":
            out.append(("data", "model"))
        elif axis == "model":
            out.append(None)
        else:
            out.append(axis)
    return P(*out)


def _spec_for_path(path: tuple, leaf: Any, mesh: Mesh | None,
                   axis_profile: str) -> P:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    stacked = names[0] in ("blocks", "enc_blocks")
    in_moe = "moe" in names
    if in_moe and name in _MOE_EXPERT_RULES:
        spec = _MOE_EXPERT_RULES[name]
    elif name in _PARAM_RULES:
        spec = _PARAM_RULES[name]
    else:
        spec = None  # norms, biases, A_log, scales... -> replicated
    rank = len(leaf.shape)
    if spec is None:
        return P(*([None] * rank))
    if axis_profile == "dp" and not in_moe:
        spec = _to_dp_profile(spec)
    if stacked and rank == len(spec) + 1:
        spec = P(None, *spec)
    elif rank != len(spec):
        # rank mismatch (e.g. tiny test config) -> replicate
        return P(*([None] * rank))
    return _fit_spec(spec, leaf.shape, mesh)


def param_pspecs(params_shape: Any, mesh: Mesh | None = None,
                 axis_profile: str = "tp") -> Any:
    """PartitionSpec pytree matching a params (shape) pytree.  With a
    mesh, axes that don't divide the dim are dropped (replicated)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _spec_for_path(p, l, mesh, axis_profile), params_shape)


def cache_pspecs(cache_shape: Any, mesh: Mesh, global_batch: int,
                 kv_shard: str = "auto") -> Any:
    """Decode-cache specs.  KV caches [L, B, Hkv, S, D]: batch on
    (pod,data) when divisible; heads on "model" when divisible, else the
    sequence axis (flash-decode over sharded KV length)."""
    baxes = batch_axes_for(mesh, global_batch)
    bspec = baxes if baxes else None
    m = mesh.shape.get("model", 1)

    def spec(path, leaf) -> P:
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        name = names[-1]
        rank = len(leaf.shape)
        if name in ("k", "v", "cross_k", "cross_v", "shared_k", "shared_v"):
            L, B, H, S, D = leaf.shape
            if kv_shard == "heads" or (kv_shard == "auto" and H % m == 0):
                return P(None, bspec if B % _prod(mesh, baxes) == 0 else None,
                         "model" if H % m == 0 else None, None, None)
            return P(None, bspec if B % _prod(mesh, baxes) == 0 else None,
                     None, "model" if S % m == 0 else None, None)
        if name in ("c_kv", "k_rope"):
            L, B, S, D = leaf.shape
            return P(None, bspec if B % _prod(mesh, baxes) == 0 else None,
                     "model" if S % m == 0 else None, None)
        if name == "ssm_h":
            L, B, H, Pd, N = leaf.shape
            return P(None, bspec if B % _prod(mesh, baxes) == 0 else None,
                     "model" if H % m == 0 else None, None, None)
        if name == "ssm_conv":
            L, B, W, C = leaf.shape
            return P(None, bspec if B % _prod(mesh, baxes) == 0 else None,
                     None, "model" if C % m == 0 else None)
        return P(*([None] * rank))

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def _prod(mesh: Mesh, axes: tuple[str, ...]) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return max(out, 1)


def input_pspecs(batch_shape: Any, mesh: Mesh, global_batch: int,
                 batch_axes: tuple[str, ...] | None = None) -> Any:
    baxes = batch_axes_for(mesh, global_batch) if batch_axes is None \
        else batch_axes
    bspec = baxes if baxes else None

    def spec(path, leaf) -> P:
        rank = len(leaf.shape)
        if rank == 0:
            return P()
        if leaf.shape[0] == global_batch and global_batch % _prod(mesh, baxes) == 0:
            return P(bspec, *([None] * (rank - 1)))
        return P(*([None] * rank))

    return jax.tree_util.tree_map_with_path(spec, batch_shape)


def to_named(tree_spec: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_spec,
                        is_leaf=lambda x: isinstance(x, P))
