"""Mamba2 SSD chunk kernel — one chunk step of the state-space dual form
as two MXU matmuls plus a decay mask.

Grid: (batch, heads / block_h).  Per grid cell, for a chunk of Q tokens
and a block of ``block_h`` heads:
  inputs : x [BH, Q, P], dt [BH, Q], cum [BH, Q] (cumulative log-decay),
           B [Q, N], C [Q, N], h_in [BH, P, N]
  outputs: y [BH, Q, P], h_out [BH, P, N]

  L[i,j]  = exp(cum_i - cum_j)        for j <= i, else 0
  y       = ((C B^T) * L) @ (dt * x)  +  (C * exp(cum)) @ h_in^T
  h_out   = exp(cum_Q) h_in + (exp(cum_Q - cum) * dt * x)^T @ B

The [Q,N]x[N,Q] and [Q,Q]x[Q,P] contractions are MXU-shaped when
Q, N, P are multiples of 128/8; the inter-chunk recurrence stays a
lax.scan in repro.models.ssm (sequential by nature).  ``block_h``
batches heads through one grid cell so the shared B/C projections are
loaded once per block.  The wrapper hands dt and cum in as columns
([.., Q, 1]) and cum once more as a row ([.., 1, Q]): every block's
last two dimensions are then whole array dimensions, which Mosaic's
(8, 128) tiling rule accepts for any ``block_h``, and the body needs no
lane-to-sublane transpose and no dynamic slice.  The body is
backend-agnostic and lowers through every ``lowering.py`` mode (Pallas
interpreter, real ``pallas_call``, compiled XLA grid path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lowering import Spec, grid_call

# float32 contractions at full precision: the TPU's default multiplies
# f32 operands in one bfloat16 pass
_HI = jax.lax.Precision.HIGHEST


def _ssd_block(x_blk, dt_blk, cumc_blk, cumr_blk, b_blk, c_blk, h_blk, *,
               q: int):
    x = x_blk[0].astype(jnp.float32)           # [BH, Q, P]
    dt = dt_blk[0].astype(jnp.float32)         # [BH, Q, 1]
    cum = cumc_blk[0].astype(jnp.float32)      # [BH, Q, 1]
    cum_row = cumr_blk[0].astype(jnp.float32)  # [BH, 1, Q]
    B = b_blk[0].astype(jnp.float32)           # [Q, N]
    C = c_blk[0].astype(jnp.float32)           # [Q, N]
    h = h_blk[0].astype(jnp.float32)           # [BH, P, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    diff = jnp.where(jj[None] <= ii[None], cum - cum_row, -1e30)
    decay = jnp.exp(diff)                                     # [BH, Q, Q]
    cb = jnp.einsum("in,jn->ij", C, B, precision=_HI,
                    preferred_element_type=jnp.float32)       # [Q, Q]
    scores = cb[None] * decay
    y = jnp.einsum("hij,hjp->hip", scores, dt * x, precision=_HI,
                   preferred_element_type=jnp.float32)        # [BH, Q, P]
    y = y + jnp.einsum("hin,hpn->hip", C[None] * jnp.exp(cum), h,
                       precision=_HI, preferred_element_type=jnp.float32)
    last = cum[:, q - 1:, :]                                  # [BH, 1, 1]
    tail = jnp.exp(last - cum) * dt                           # [BH, Q, 1]
    # widen along lanes first: Mosaic broadcasts one tile axis at a time
    h_decay = jnp.exp(jnp.broadcast_to(last, (h.shape[0], 1, h.shape[2])))
    h_out = h_decay * h + jnp.einsum(
        "hjp,jn->hpn", tail * x, B, precision=_HI,
        preferred_element_type=jnp.float32)
    return y[None].astype(x_blk.dtype), h_out[None].astype(h_blk.dtype)


def ssd_chunk_step(x: jax.Array, dt: jax.Array, cum: jax.Array,
                   B: jax.Array, C: jax.Array, h_in: jax.Array,
                   block_h: int = 1, mode: str = "interpret"
                   ) -> tuple[jax.Array, jax.Array]:
    """x: [Bt, H, Q, P]; dt/cum: [Bt, H, Q]; B/C: [Bt, Q, N];
    h_in: [Bt, H, P, N] -> (y [Bt,H,Q,P], h_out [Bt,H,P,N]).
    ``block_h`` must divide H; ``mode`` must be resolved."""
    bt, h, q, p = x.shape
    n = B.shape[-1]
    block_h = min(block_h, h)
    assert h % block_h == 0, "head block must divide the head count"
    col = Spec((1, block_h, q, 1), lambda i, j: (i, j, 0, 0))
    call = grid_call(
        functools.partial(_ssd_block, q=q),
        grid=(bt, h // block_h),
        in_specs=[
            Spec((1, block_h, q, p), lambda i, j: (i, j, 0, 0)),
            col,
            col,
            Spec((1, block_h, 1, q), lambda i, j: (i, j, 0, 0)),
            Spec((1, q, n), lambda i, j: (i, 0, 0)),
            Spec((1, q, n), lambda i, j: (i, 0, 0)),
            Spec((1, block_h, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            Spec((1, block_h, q, p), lambda i, j: (i, j, 0, 0)),
            Spec((1, block_h, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shapes=[
            jax.ShapeDtypeStruct((bt, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        mode=mode,
    )
    return call(x, dt[..., None], cum[..., None], cum[..., None, :], B, C,
                h_in)
