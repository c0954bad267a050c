"""Public wrappers around the blocked kernels.

Dispatch: every wrapper resolves an execution mode (see
``lowering.resolve_mode``); ``kv_decode`` and ``ssd_chunk`` also
resolve a block configuration (explicit argument > autotuned table in
``_autotune_cache.json`` > kernel default).  ``amm_gather`` serves one
request per grid step and has no block size to tune.  The
default mode is *compiled* — real ``pallas_call`` lowering on TPU/GPU,
the XLA grid path on CPU — and runs through a jit'd implementation
with mode and blocks held static.

``mode="interpret"`` (or ``interpret=True``) is the conformance and
debugging anchor, and is dispatched *eagerly*: the Pallas interpreter
actually walks the grid in Python per call, so refs stay inspectable
and prints/breakpoints work.  (Inside an outer ``jax.jit`` the call
traces like any JAX code, so library users embedding these ops in a
jitted model keep compiled performance regardless of mode.)  The
seed wrapped the interpreter in ``jax.jit``, which traces it into
near-identical XLA — neither real interpretation nor a real lowering;
the two roles are now genuinely distinct, which is exactly what the
``kernel.* `` vs ``kernel.*_compiled`` BENCH rows measure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.amm_gather import amm_gather_u32
from repro.kernels.banked_kv_decode import banked_kv_decode
from repro.kernels.lowering import resolve_mode
from repro.kernels.ssd_scan import ssd_chunk_step

_UINT_FOR = {2: jnp.uint16, 4: jnp.uint32}


def _pick_block(target: int, n: int) -> int:
    """Largest block <= target that divides n (re-legalizes a bucketed
    autotune winner against the actual shape)."""
    for b in range(min(target, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def _config(kernel: str, mode: str, **dims: int) -> dict[str, int]:
    return autotune.get_config(kernel, jax.default_backend(), mode, **dims)


def pack_amm_banks(table: jax.Array, n_banks: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Depth-partition [V, D] into XOR banks [NB, V/NB, D] + parity."""
    v, d = table.shape
    assert v % n_banks == 0, "table depth must divide into banks"
    u = _UINT_FOR[table.dtype.itemsize]
    banks = jax.lax.bitcast_convert_type(table, u).reshape(
        n_banks, v // n_banks, d)
    parity = banks[0]
    for j in range(1, n_banks):
        parity = parity ^ banks[j]
    return banks, parity


def _amm_gather_impl(table, idx, n_banks, mode):
    banks, parity = pack_amm_banks(table, n_banks)
    out = amm_gather_u32(banks, parity, idx.astype(jnp.int32), mode=mode)
    return jax.lax.bitcast_convert_type(out, table.dtype)


_amm_gather = jax.jit(_amm_gather_impl, static_argnames=("n_banks", "mode"))


def amm_gather(table: jax.Array, idx: jax.Array, n_banks: int = 4,
               interpret: bool | None = None, mode: str | None = None
               ) -> jax.Array:
    """Conflict-free XOR-banked gather.  table: [V, D]; idx: [N]."""
    mode = resolve_mode(interpret, mode)
    fn = _amm_gather_impl if mode == "interpret" else _amm_gather
    return fn(table, idx, n_banks, mode)


def _kv_decode_impl(q, k, v, lengths, n_banks, mode, block_h):
    b, hkv, s, d = k.shape
    kb = k.reshape(b, hkv, n_banks, s // n_banks, d)
    vb = v.reshape(b, hkv, n_banks, s // n_banks, d)
    return banked_kv_decode(q, kb, vb, lengths.astype(jnp.int32),
                            block_h=block_h, mode=mode)


_kv_decode = jax.jit(_kv_decode_impl,
                     static_argnames=("n_banks", "mode", "block_h"))


def kv_decode(q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
              n_banks: int = 8, interpret: bool | None = None,
              mode: str | None = None, block_h: int | None = None
              ) -> jax.Array:
    """Flash-decode over a bank-partitioned KV cache.
    q: [B, Hq, D]; k/v: [B, Hkv, S, D]; lengths: [B] (per-row valid
    sequence lengths; rows with length 0 decode to zeros)."""
    mode = resolve_mode(interpret, mode)
    b, hkv, s, d = k.shape
    hq = q.shape[1]
    assert s % n_banks == 0
    group = max(hq // hkv, 1)
    if block_h is None:
        block_h = _config("kv_decode", mode, b=b, hq=hq, hkv=hkv, s=s,
                          d=d, nb=n_banks)["block_h"]
    block_h = _pick_block(block_h, group)
    if mode == "pallas" and block_h % 8:
        block_h = group   # Mosaic: the query block is 8-row aligned or whole
    fn = _kv_decode_impl if mode == "interpret" else _kv_decode
    return fn(q, k, v, lengths, n_banks, mode, block_h)


def _ssd_chunk_impl(x, dt, cum, B, C, h_in, mode, block_h):
    return ssd_chunk_step(x, dt, cum, B, C, h_in, block_h=block_h,
                          mode=mode)


_ssd_chunk = jax.jit(_ssd_chunk_impl, static_argnames=("mode", "block_h"))


def ssd_chunk(x, dt, cum, B, C, h_in, interpret: bool | None = None,
              mode: str | None = None, block_h: int | None = None):
    """One SSD chunk step (see ssd_scan.py for the contract)."""
    mode = resolve_mode(interpret, mode)
    bt, h, q, p = x.shape
    if block_h is None:
        block_h = _config("ssd_chunk", mode, bt=bt, h=h, q=q, p=p,
                          n=B.shape[-1])["block_h"]
    fn = _ssd_chunk_impl if mode == "interpret" else _ssd_chunk
    return fn(x, dt, cum, B, C, h_in, mode, _pick_block(block_h, h))
