"""AMM XOR-banked gather — the paper's H-NTX-Rd read path as a blocked
kernel.

The logical table (an embedding shard, an expert bank, a KV page table)
is depth-partitioned into ``n_banks`` banks plus one XOR parity bank
(parity[o] = XOR_b bank_b[o]).  Requests are served two per cycle (2
read ports): even request slots read the *direct* path, odd slots read
the *reconstruction* path — parity XOR all other banks — which is what
the hardware does when both requests of a cycle hit the same bank.
Either path returns the same word (the H-NTX-Rd invariant), so the
kernel is conflict-free by construction, independent of the request
pattern's spatial locality.

The request ids are scalar-prefetched: grid step ``i`` serves request
``i``, and its index maps pick the row offset ``idx[i] % rows`` of every
bank and of the parity bank, so the table stays in HBM and only
``n_banks + 1`` rows per request move into VMEM (the Pallas pipeline
fetches step ``i + 1``'s rows while step ``i`` computes).  Rows are
carried as ``[.., 1, D]`` so each block's last two dimensions equal the
array's, which Mosaic's (8, 128) tiling rule needs for a one-row block.
The body is values in, values out and lowers through every
``lowering.py`` mode.  Payloads are bitcast to unsigned ints for XOR;
ops.py handles fp views.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.lowering import Spec, grid_call


def _gather_block(coords, idx, banks, parity, *, n_banks: int, rows: int):
    """banks: [NB, 1, 1, D] uint (row ``idx[i] % rows`` of every bank);
    parity: [1, 1, D] uint -> [1, 1, D].  Even request slots take the
    direct bank read, odd slots the XOR-reconstruction (parity) path."""
    i = coords[0]
    bank = idx[i] // rows
    direct = jnp.zeros_like(parity)
    acc = parity          # reconstruction: parity ^ XOR_{j != bank} bank_j
    for j in range(n_banks):                      # static unroll, NB small
        row = lax.index_in_dim(banks, j, 0, keepdims=False)
        direct = jnp.where(bank == j, row, direct)
        acc = jnp.where(bank == j, acc, acc ^ row)
    return jnp.where(i % 2 == 1, acc, direct)     # odd slot = second port


def amm_gather_u32(banks: jax.Array, parity: jax.Array, idx: jax.Array,
                   mode: str = "interpret") -> jax.Array:
    """banks: [NB, R, D] uint; parity: [R, D] uint; idx: [N] int32.
    Returns [N, D] uint gathered rows.  ``mode`` must be resolved
    ('pallas'|'interpret'|'xla'), see ``lowering.resolve_mode``."""
    nb, rows, d = banks.shape
    n = idx.shape[0]
    call = grid_call(
        functools.partial(_gather_block, n_banks=nb, rows=rows),
        grid=(n,),
        in_specs=[
            Spec((nb, 1, 1, d), lambda i, ids: (0, ids[i] % rows, 0, 0)),
            Spec((1, 1, d), lambda i, ids: (ids[i] % rows, 0, 0)),
        ],
        out_specs=[Spec((1, 1, d), lambda i, ids: (i, 0, 0))],
        out_shapes=[jax.ShapeDtypeStruct((n, 1, d), banks.dtype)],
        mode=mode,
        num_scalar_prefetch=1,
    )
    out = call(idx, banks.reshape(nb, rows, 1, d), parity.reshape(rows, 1, d))
    return out.reshape(n, d)
