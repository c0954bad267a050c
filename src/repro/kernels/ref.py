"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose
kernel-vs-ref across shape/dtype sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def amm_gather_ref(table: jax.Array, idx: jax.Array) -> jax.Array:
    """table: [V, D]; idx: [N] -> [N, D]."""
    return jnp.take(table, idx, axis=0)


_UINT_FOR = {2: jnp.uint16, 4: jnp.uint32}


def amm_gather_replay_ref(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Replay-backed oracle for ``amm_gather``: the gather is an op trace
    on the H-NTX-Rd *functional model* (``repro.core.amm.replay``),
    batched with vmap across the payload columns — one AMM instance per
    column, all replaying the same request stream in a single scan.

    Requests are paired two per cycle (the kernel's 2 read ports): even
    slots decode through the direct path, odd slots through the
    XOR-reconstruction (parity) path, exactly like the kernel's
    conflict-free second port.  table: [V, D]; idx: [N] -> [N, D].
    """
    from repro.core.amm.replay import init_flat, replay_batched
    from repro.core.amm.spec import AMMSpec

    v, d = table.shape
    u = _UINT_FOR[table.dtype.itemsize]
    cols = jax.lax.bitcast_convert_type(table, u).astype(jnp.uint32).T  # [D,V]
    spec = AMMSpec("h_ntx_rd", n_read=2, n_write=1, depth=v)
    states = jax.vmap(lambda c: init_flat(spec, c))(cols)

    n = idx.shape[0]
    padded = jnp.concatenate([idx.astype(jnp.int32),
                              jnp.zeros((n % 2,), jnp.int32)])
    cycles = padded.shape[0] // 2
    ra = padded.reshape(cycles, 2)
    wa = jnp.zeros((cycles, 1), jnp.int32)
    wv = jnp.zeros((cycles, 1), jnp.uint32)
    wm = jnp.zeros((cycles, 1), bool)
    _, result = replay_batched(spec, states, ra, wa, wv, wm, share_trace=True)

    # [D, T, 2]: keep direct reads from even slots, parity from odd slots
    slots = jnp.stack([result.read_vals[..., 0], result.parity_vals[..., 1]],
                      axis=-1)
    flat = slots.reshape(d, cycles * 2)[:, :n].T            # [N, D]
    return jax.lax.bitcast_convert_type(flat.astype(u), table.dtype)


def kv_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  lengths: jax.Array) -> jax.Array:
    """Masked dense reference.  q: [B, Hq, D]; k/v: [B, Hkv, S, D];
    lengths: [B] per-row valid lengths -> [B, Hq, D].

    Positions ``>= lengths[b]`` are excluded from the softmax, so padded
    K/V content never reaches the output; a fully-empty row
    (``lengths[b] == 0``) decodes to zeros — the same ragged-batch
    semantics the banked kernel implements (softmax over -inf would
    otherwise be NaN)."""
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(d)
    valid = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0)),
                  0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    w = p / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def causal_attention_ref(q: jax.Array, k: jax.Array,
                         v: jax.Array) -> jax.Array:
    """Dense causal softmax attention in float32 at full precision, the
    oracle of ``flash_prefill``.  q: [B, Hq, S, D]; k/v: [B, Hkv, S, D]
    -> [B, Hq, S, D] float32."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(b, hkv, hq // hkv, s, d).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32),
                        precision=hi) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w, v.astype(jnp.float32),
                     precision=hi)
    return out.reshape(b, hq, s, d)


def ssd_chunk_ref(x, dt, cum, B, C, h_in):
    """Same contract as ssd_scan.ssd_chunk_step, dense einsums."""
    la = jnp.where(
        jnp.arange(cum.shape[-1])[None, None, :, None]
        >= jnp.arange(cum.shape[-1])[None, None, None, :],
        cum[..., :, None] - cum[..., None, :], -1e30)
    decay = jnp.exp(la)                                        # [b,h,i,j]
    scores = jnp.einsum("bin,bjn->bij", C.astype(jnp.float32),
                        B.astype(jnp.float32))[:, None] * decay
    y = jnp.einsum("bhij,bhj,bhjp->bhip", scores, dt.astype(jnp.float32),
                   x.astype(jnp.float32))
    y = y + jnp.einsum("bin,bhi,bhpn->bhip", C.astype(jnp.float32),
                       jnp.exp(cum), h_in.astype(jnp.float32))
    tail = jnp.exp(cum[..., -1:] - cum) * dt                   # [b,h,q]
    h_out = jnp.exp(cum[..., -1])[..., None, None] * h_in + jnp.einsum(
        "bhj,bhjp,bjn->bhpn", tail, x.astype(jnp.float32),
        B.astype(jnp.float32))
    return y, h_out
