"""Banked KV-cache flash-decode — the paper's banking idea applied to
the decode-attention hot loop.

The KV cache of one (batch, kv-head) is partitioned into ``n_banks``
sequence banks (independent tiles).  A decode step is a multi-port read
burst over those banks; the kernel streams the banks with the
online-softmax (flash) recurrence, so each bank is read exactly once
per step and never materializes an [S] score vector in HBM.

Grid: (batch, kv_heads, group / block_h), with the query viewed as
[B, Hkv, G, D] (G = Hq // Hkv).  ``block_h`` query heads of one GQA
group are served per grid cell, so the whole block shares one kv head
and the bank stream (the expensive loads) is amortized across the block
instead of re-read per head.  On the TPU the query block's last two
dimensions must be (8, 128)-aligned or whole, so ``block_h`` is the
group or a multiple of 8 there.  Ragged batches: ``lengths`` is
scalar-prefetched (SMEM on the TPU) and ``lengths[b]`` masks each
row's positions ``>= seq_len`` out of both the max and the weight sum
(padded K/V content never reaches the output), and a fully-empty row
(``seq_len == 0``) returns zeros rather than NaN — the shape class
mixed-length serving batches need.

The block body is backend-agnostic (values in, values out) and lowers
through every ``lowering.py`` mode: Pallas interpreter, real
``pallas_call``, and the compiled XLA grid path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.lowering import Spec, grid_call

# float32 contractions at full precision: the TPU's default multiplies
# f32 operands in one bfloat16 pass
_HI = lax.Precision.HIGHEST


def _decode_block(coords, lengths, q_blk, k_blk, v_blk, *, n_banks: int,
                  bank_len: int, scale: float):
    """q_blk: [1, 1, BH, D]; k/v_blk: [1, 1, NB, SB, D] -> [1, 1, BH, D].
    Flash recurrence over banks, vectorized over the BH-head block."""
    q = q_blk[0, 0].astype(jnp.float32)                    # [BH, D]
    kv_len = lengths[coords[0]]
    bh, d = q.shape

    def bank_body(j, carry):
        m, l, acc = carry                          # [BH,1] [BH,1] [BH,D]
        k = k_blk[0, 0, j].astype(jnp.float32)             # [SB, D]
        v = v_blk[0, 0, j].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=_HI,
                            preferred_element_type=jnp.float32) * scale
        pos = j * bank_len + lax.broadcasted_iota(jnp.int32, (1, bank_len), 1)
        valid = pos < kv_len                               # [1, SB]
        s = jnp.where(valid, s, -1e30)                     # [BH, SB]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)                       # empty-bank exp(0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p, v, precision=_HI, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bh, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((bh, 1), jnp.float32)
    a0 = jnp.zeros((bh, d), jnp.float32)
    carry = (m0, l0, a0)
    for j in range(n_banks):       # static unroll: NB is a compile-time
        carry = bank_body(j, carry)  # constant, loop overhead vanishes
    m, l, acc = carry
    # seq_len == 0 leaves l == 0: define the row as zeros, not NaN
    out = acc / jnp.maximum(l, 1e-30)
    return out[None, None].astype(q_blk.dtype)


def banked_kv_decode(q: jax.Array, k_banks: jax.Array, v_banks: jax.Array,
                     lengths: jax.Array, block_h: int = 1,
                     mode: str = "interpret") -> jax.Array:
    """q: [B, Hq, D]; k/v_banks: [B, Hkv, NB, SB, D]; lengths: [B] int32.
    Returns [B, Hq, D].  ``block_h`` must divide the GQA group
    (Hq // Hkv); ``mode`` must be resolved, see ``lowering.resolve_mode``."""
    b, hq, d = q.shape
    _, hkv, nb, sb, _ = k_banks.shape
    group = hq // hkv
    block_h = min(block_h, group)
    assert group % block_h == 0, "head block must divide the GQA group"
    scale = 1.0 / (d ** 0.5)
    kv_spec = Spec((1, 1, nb, sb, d), lambda i, h, g, lens: (i, h, 0, 0, 0))
    q_spec = Spec((1, 1, block_h, d), lambda i, h, g, lens: (i, h, g, 0))
    call = grid_call(
        functools.partial(_decode_block, n_banks=nb, bank_len=sb,
                          scale=scale),
        grid=(b, hkv, group // block_h),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec],
        out_shapes=[jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype)],
        mode=mode,
        num_scalar_prefetch=1,
    )
    out = call(lengths, q.reshape(b, hkv, group, d), k_banks, v_banks)
    return out.reshape(b, hq, d)
