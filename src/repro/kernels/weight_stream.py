"""Weight-streaming matmul: ``x @ W[l]`` read straight from a layer stack.

A decode step multiplies one token per row, so each layer matrix feeds
only a few rows and the step is bound by the bytes of its weights.  The
weights are kept as float32 stacks ``[L, K, N]``; casting a stack to the
compute dtype before the layer loop reads the float32 bytes, writes a
bfloat16 copy and then reads the copy again.  This kernel reads each
float32 block once: its operand is the whole stack, left in HBM, and the
layer index ``l`` is scalar-prefetched so that the stack's block index
map picks ``(l, k, n)`` — no slice of the stack is ever materialised.
Each block is rounded to the compute dtype in VMEM (as ``astype`` does)
and multiplied on the MXU with float32 accumulation.

Several stacks with the same ``K`` share one pass over ``x`` (the gate
and up projections; q, k and v): one call, one output each.  The grid
runs over ``g`` column steps, weight ``i`` taking ``N_i / g`` columns a
step (a multiple of 128, or the whole width), and over ``K`` tiles only
where one step's block columns would not fit ``BLOCK_BYTES`` (at a K of
8192 and more for float32); the K-tiled program accumulates in float32
output blocks, cast afterwards.

The block body is values in, values out and lowers through every
``lowering.py`` mode.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.lowering import Spec, grid_call

# weight bytes moved per grid step: two buffers of it, and the block
# rounded to bfloat16, fit the TPU's default 16 MiB of scoped VMEM
BLOCK_BYTES = 4 * 2**20
_LANE = 128


def _tiles(k: int, widths: Sequence[int], itemsize: int,
           block_bytes: int) -> tuple[int, int]:
    """``(g, tk)``: the fewest column steps ``g`` whose blocks fit
    ``block_bytes`` (each width split into ``g`` lane-aligned columns),
    and the K tile, ``k`` itself unless the most column steps still
    leave a block too large."""
    steps = [g for g in range(1, max(widths) + 1)
             if all(n % g == 0 and (g == 1 or (n // g) % _LANE == 0)
                    for n in widths)]
    cols = lambda g: sum(n // g for n in widths)     # noqa: E731
    for g in steps:
        if k * cols(g) * itemsize <= block_bytes:
            return g, k
    g = steps[-1]
    tiles = [t for t in range(_LANE, k, _LANE) if k % t == 0] or [k]
    fits = [t for t in tiles if t * cols(g) * itemsize <= block_bytes]
    return g, max(fits or tiles[:1])


def _stream_block(coords, layer, x, *blocks, n_w: int, k_tiled: bool):
    """x: [B, tk]; blocks: ``n_w`` weight blocks [1, tk, tn_i], then (when
    K-tiled) the float32 output blocks [B, tn_i] as they stand."""
    ws, accs = blocks[:n_w], blocks[n_w:]
    outs = []
    for i, w in enumerate(ws):
        part = jnp.dot(x, w[0].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        if k_tiled:
            part = jnp.where(coords[1] == 0, 0.0, accs[i]) + part
        outs.append(part)
    return outs


def weight_stream_matmul(x: jax.Array, ws: Sequence[jax.Array],
                         layer: jax.Array, mode: str = "interpret"
                         ) -> tuple[jax.Array, ...]:
    """``tuple(x @ w[layer].astype(x.dtype) for w in ws)``, accumulated
    in float32 and returned in ``x``'s dtype.  x: [B, K]; each w:
    [L, K, N_i] (one L and K for all); layer: int32 scalar.  ``mode``
    must be resolved, see ``lowering.resolve_mode``."""
    b, k = x.shape
    widths = [w.shape[2] for w in ws]
    g, tk = _tiles(k, widths, ws[0].dtype.itemsize, BLOCK_BYTES)
    k_tiled = tk < k
    acc_dtype = jnp.float32 if k_tiled else x.dtype
    call = grid_call(
        functools.partial(_stream_block, n_w=len(ws), k_tiled=k_tiled),
        grid=(g, k // tk),
        in_specs=[Spec((b, tk), lambda n, kk, lyr: (0, kk))] + [
            Spec((1, tk, w // g), lambda n, kk, lyr: (lyr[0], kk, n))
            for w in widths],
        out_specs=[Spec((b, w // g), lambda n, kk, lyr: (0, n))
                   for w in widths],
        out_shapes=[jax.ShapeDtypeStruct((b, w), acc_dtype) for w in widths],
        mode=mode,
        num_scalar_prefetch=1,
        unpack=False,
        accumulate=k_tiled,
        name="weight_stream",
    )
    outs = call(jnp.reshape(layer, (1,)).astype(jnp.int32), x, *ws)
    return tuple(o.astype(x.dtype) for o in outs)
