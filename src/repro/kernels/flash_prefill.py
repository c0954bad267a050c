"""Causal flash attention for a prefill: the score blocks stay on-chip.

A prefill attends every query of a prompt to every key at or before it.
Scanning the keys in blocks in XLA still writes each block's scores,
``[rows, block]`` in float32, to HBM and reads them back for the
softmax and the value product; at a 2048-token prompt that traffic, not
the arithmetic, bounds the layer.  This kernel keeps a score block in
VMEM from the QK^T product to the PV product: HBM sees the queries,
keys, values and the output, each once per (query block, key block).

The ``G = Hq / Hkv`` query heads of one KV head are folded into the
rows of one query block (``[G * bq, D]``), so each key and value block
is read once per group.  The running max, sum and float32 accumulator
are VMEM scratch, reset at a query block's first key block; the output
block is written at every step and leaves VMEM when the query block
changes, after its last key block (the diagonal's).

Causal skip: the grid is ``(B, Hkv, T)``, where ``T`` walks only the
(query block, key block) pairs with a key at or before one of the
query block's positions, query blocks in order and key blocks in order
within each; two scalar-prefetched tables give a step's pair.  A key
block wholly past the diagonal is never visited: no step, no copy.
The pairs that straddle the diagonal are masked.  A prompt that is not
a multiple of the blocks is padded to one; its padded keys lie past
every real query, so the causal mask hides them.

Blocks: a score block of ``ROWS`` query rows by ``KEYS`` keys, 4 MiB in
float32.  On one v5e, at qwen3-1.7b's prefill shape (G 2, D 128), 512
queries by 1024 keys took 0.82 ms a layer, 512 by 512 1.35 ms and 256
by 1024 0.92 ms; at minicpm3-4b's 16k prompt (G 1, D 96), 1024 by 1024
took 24.2 ms and 512 by 1024 26.8 ms: fewer, larger steps win.

Precision, as the XLA scan's compiled program on the TPU: the queries
and keys (bfloat16 in the serve programs) go to the MXU in their dtype
with float32 results, the ``1/sqrt(D)`` scale is applied to the float32
scores, the softmax state is float32, and the probabilities are
rounded to the values' dtype for the PV product (the one bfloat16 pass
that the TPU's default precision gives the scan's float32 operand),
accumulated in float32.

The block body is values in, values out and lowers through every
``lowering.py`` mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.lowering import Spec, grid_call

ROWS = KEYS = 1024


def block_sizes(s: int, g: int, rows: int = ROWS, keys: int = KEYS
                ) -> tuple[int, int, int]:
    """``(bq, bk, padded length)`` for a prompt of ``s`` tokens and ``g``
    query heads per KV head: powers of two, ``bq`` the largest that
    fits ``rows / g`` and ``bk`` = ``keys``, each cut to the prompt's
    length rounded up to a power of two (at least 16, a bfloat16 tile's
    sublanes); the prompt is padded to a multiple of the larger."""
    short = max(16, 1 << (s - 1).bit_length())
    bq = min(max(16, 1 << ((rows // g).bit_length() - 1)), short)
    bk = min(keys, short)
    step = max(bq, bk)
    return bq, bk, -(-s // step) * step


def _pairs(n_q: int, bq: int, bk: int) -> tuple[np.ndarray, np.ndarray]:
    """The (query block, key block) pairs a causal prefill visits, in
    grid order: each query block's key blocks up to its last position."""
    qs, ks = zip(*[(qi, ki) for qi in range(n_q)
                   for ki in range((qi * bq + bq - 1) // bk + 1)])
    return np.asarray(qs, np.int32), np.asarray(ks, np.int32)


def _flash_block(coords, q_of, k_of, q_blk, k_blk, v_blk, m, l, acc, *,
                 scale: float):
    """q_blk: [1, 1, G, bq, D]; k/v_blk: [1, 1, bk, D]; scratch m, l:
    [G * bq, 1], acc: [G * bq, D], float32.  One key block of the online
    softmax; returns the output block (q_blk's shape) and the new
    scratch."""
    qi, ki = q_of[coords[2]], k_of[coords[2]]
    _, _, g, bq, d = q_blk.shape
    bk = k_blk.shape[2]
    rows = g * bq
    q = q_blk.reshape(rows, d)
    k, v = k_blk[0, 0], v_blk[0, 0]
    first = ki == 0
    m = jnp.where(first, -jnp.inf, m)
    l = jnp.where(first, 0.0, l)
    acc = jnp.where(first, 0.0, acc)

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1
                                           ).reshape(rows, bk)
    k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
    s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
    # key block 0 holds a visible key for every row: m_new is finite
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
    out = acc * (1.0 / l)
    return out.reshape(q_blk.shape).astype(q_blk.dtype), m_new, l, acc


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  mode: str = "interpret", rows: int = ROWS,
                  keys: int = KEYS) -> jax.Array:
    """Causal attention of every position of a prompt to those at or
    before it.  q: [B, Hq, S, D]; k/v: [B, Hkv, S, D], Hq a multiple of
    Hkv; returns [B, Hq, S, D] in q's dtype.  A score block holds up to
    ``rows`` query rows (positions of each of G heads) and ``keys``
    keys, powers of two (see ``block_sizes``).  ``mode`` must be
    resolved, see ``lowering.resolve_mode``."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    bq, bk, padded = block_sizes(s, g, rows, keys)
    if padded != s:
        pad = ((0, 0), (0, 0), (0, padded - s), (0, 0))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    q_of, k_of = _pairs(padded // bq, bq, bk)
    q_spec = Spec((1, 1, g, bq, d),
                  lambda bi, h, t, qo, ko: (bi, h, 0, qo[t], 0))
    kv_spec = Spec((1, 1, bk, d), lambda bi, h, t, qo, ko: (bi, h, ko[t], 0))
    f32 = jnp.float32
    call = grid_call(
        functools.partial(_flash_block, scale=1.0 / d ** 0.5),
        grid=(b, hkv, len(q_of)),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec],
        out_shapes=[jax.ShapeDtypeStruct((b, hkv, g, padded, d), q.dtype)],
        mode=mode,
        num_scalar_prefetch=2,
        scratch_shapes=[jax.ShapeDtypeStruct((g * bq, 1), f32),
                        jax.ShapeDtypeStruct((g * bq, 1), f32),
                        jax.ShapeDtypeStruct((g * bq, d), f32)],
        name="flash_prefill",
    )
    out = call(jnp.asarray(q_of), jnp.asarray(k_of),
               q.reshape(b, hkv, g, padded, d), k, v)
    out = out.reshape(b, hq, padded, d)
    return out[:, :, :s] if padded != s else out
