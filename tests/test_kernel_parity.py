"""Compiled-vs-interpret-vs-oracle parity harness (ISSUE 8).

Every kernel runs the *same* blocked program through two executors —
the Pallas interpreter and the compiled XLA grid path (`mode="xla"`,
what `mode="compiled"` resolves to on CPU) — and both must agree with
the pure-jnp / replay-backed oracles in ``kernels/ref.py``:

  * integer paths (amm_gather XOR reconstruction) are bit-exact,
  * float accumulation paths (kv_decode, ssd_chunk) are bit-exact
    between executors (identical op sequence per block) and tight
    allclose against the dense oracles (different reduction order).

The grid covers shape classes, bank counts (odd / non-pow2 / single),
ragged sequence lengths (incl. empty rows), and both parity paths of
the XOR gather.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import amm_gather, kv_decode, ref, ssd_chunk
from repro.kernels.lowering import resolve_mode, supports_pallas_lowering

RNG = np.random.default_rng(42)
MODES = ("interpret", "xla")


# ----------------------------------------------------------------- amm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("v,d,nb,n", [
    (64, 8, 2, 16), (128, 16, 4, 64), (256, 32, 8, 128),
    (96, 8, 3, 48),              # odd bank count
    (250, 8, 5, 49),             # non-pow2 depth and banks, odd request count
    (64, 8, 1, 32),              # single-bank degenerate geometry
])
def test_amm_gather_parity(dtype, v, d, nb, n):
    table = jnp.asarray(RNG.standard_normal((v, d)), dtype)
    idx = jnp.asarray(RNG.integers(0, v, n), jnp.int32)
    want = ref.amm_gather_ref(table, idx)
    outs = {m: amm_gather(table, idx, n_banks=nb, mode=m) for m in MODES}
    for m, got in outs.items():
        assert jnp.array_equal(got, want), f"{m} != oracle"
    assert jnp.array_equal(outs["interpret"], outs["xla"])


def test_amm_gather_replay_oracle_parity():
    """Both executors must match the replay-backed functional-model
    oracle (H-NTX-Rd direct/parity paths) bit-for-bit."""
    table = jnp.asarray(RNG.standard_normal((128, 16)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 128, 64), jnp.int32)
    want = ref.amm_gather_replay_ref(table, idx)
    for m in MODES:
        got = amm_gather(table, idx, n_banks=4, mode=m)
        assert jnp.array_equal(got, want), f"{m} != replay oracle"


def test_amm_gather_block_autoselect():
    """Any request count runs (incl. primes): one request per grid step,
    so no block size has to divide the request count."""
    table = jnp.asarray(RNG.standard_normal((64, 8)), jnp.float32)
    for n in (1, 7, 63, 97, 128):
        idx = jnp.asarray(RNG.integers(0, 64, n), jnp.int32)
        for m in MODES:
            got = amm_gather(table, idx, n_banks=4, mode=m)
            assert jnp.array_equal(got, ref.amm_gather_ref(table, idx))


# ------------------------------------------------------------------ kv
_KV_SHAPES = [
    # b, hq, hkv, s, d, nb
    (2, 4, 2, 64, 16, 4),
    (1, 8, 8, 128, 32, 8),
    (3, 6, 2, 96, 8, 3),         # odd bank count
    (4, 8, 4, 64, 16, 1),        # single bank
]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("b,hq,hkv,s,d,nb", _KV_SHAPES)
def test_kv_decode_parity(dtype, tol, b, hq, hkv, s, d, nb):
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, b), jnp.int32)
    want = ref.kv_decode_ref(q, k, v, lens)
    outs = {}
    group = hq // hkv
    for m in MODES:
        for bh in sorted({1, group}):
            got = kv_decode(q, k, v, lens, n_banks=nb, mode=m, block_h=bh)
            outs[(m, bh)] = np.asarray(got, np.float32)
            np.testing.assert_allclose(outs[(m, bh)],
                                       np.asarray(want, np.float32),
                                       atol=tol, rtol=tol,
                                       err_msg=f"{m} bh={bh}")
    # same block program, same ops: executors agree bit-exactly
    for bh in sorted({1, group}):
        np.testing.assert_array_equal(outs[("interpret", bh)],
                                      outs[("xla", bh)])


@pytest.mark.parametrize("lens", [
    [0, 5, 33, 64],              # empty row + mid-bank + bank boundary + full
    [1, 1, 16, 17],              # bank-boundary straddle (SB=16 at nb=4)
    [0, 0, 0, 0],                # fully-empty batch
])
def test_kv_decode_ragged_masking(lens):
    """Per-row seq_len < padded S: masked reference equality, empty rows
    decode to zeros, and padded K/V content never leaks into outputs."""
    b, hq, hkv, s, d, nb = 4, 4, 2, 64, 16, 4
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    L = jnp.asarray(lens, jnp.int32)
    want = np.asarray(ref.kv_decode_ref(q, k, v, L))
    assert not np.isnan(want).any(), "masked reference must be NaN-free"
    kp, vp = k, v
    for i, n in enumerate(lens):     # poison the padded tail of each row
        kp = kp.at[i, :, n:, :].set(1e4)
        vp = vp.at[i, :, n:, :].set(-1e4)
    for m in MODES:
        got = np.asarray(kv_decode(q, k, v, L, n_banks=nb, mode=m))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for i, n in enumerate(lens):
            if n == 0:
                assert np.all(got[i] == 0.0), "empty row must decode to 0"
        # padded content must never leak into the output
        got2 = np.asarray(kv_decode(q, kp, vp, L, n_banks=nb, mode=m))
        np.testing.assert_allclose(got2, got, atol=1e-6)


# ----------------------------------------------------------------- ssd
@pytest.mark.parametrize("bt,h,q,p,n", [(1, 2, 8, 4, 4), (2, 4, 16, 8, 8),
                                        (2, 3, 12, 8, 6)])
def test_ssd_chunk_parity(bt, h, q, p, n):
    x = jnp.asarray(RNG.standard_normal((bt, h, q, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, (bt, h, q)), jnp.float32)
    la = -dt * jnp.asarray(RNG.uniform(0.5, 2.0, (1, h, 1)), jnp.float32)
    cum = jnp.cumsum(la, axis=-1)
    B = jnp.asarray(RNG.standard_normal((bt, q, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((bt, q, n)), jnp.float32)
    h_in = jnp.asarray(RNG.standard_normal((bt, h, p, n)), jnp.float32)
    y_ref, h_ref = ref.ssd_chunk_ref(x, dt, cum, B, C, h_in)
    outs = {}
    for m in MODES:
        for bh in sorted({1, h}):
            y, hout = ssd_chunk(x, dt, cum, B, C, h_in, mode=m, block_h=bh)
            outs[(m, bh)] = (np.asarray(y), np.asarray(hout))
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       atol=1e-4, err_msg=f"{m} bh={bh}")
            np.testing.assert_allclose(np.asarray(hout), np.asarray(h_ref),
                                       atol=1e-4, err_msg=f"{m} bh={bh}")
    for bh in sorted({1, h}):
        np.testing.assert_array_equal(outs[("interpret", bh)][0],
                                      outs[("xla", bh)][0])
        np.testing.assert_array_equal(outs[("interpret", bh)][1],
                                      outs[("xla", bh)][1])


# ------------------------------------------------------ mode dispatch
def test_resolve_mode_defaults():
    assert resolve_mode(True, None) == "interpret"
    compiled = resolve_mode(False, None)
    assert compiled == ("pallas" if supports_pallas_lowering() else "xla")
    assert resolve_mode(None, None) == compiled
    assert resolve_mode(None, "compiled") == compiled
    assert resolve_mode(True, "xla") == "xla"   # explicit mode wins
    with pytest.raises(ValueError):
        resolve_mode(None, "nope")


def test_env_override_is_default_only(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert resolve_mode(None, None) == "interpret"
    assert resolve_mode(False, None) == "interpret"
    assert resolve_mode(None, "xla") == "xla"   # explicit mode still wins


def test_env_override_refused_on_tpu(monkeypatch):
    """On a TPU the override may not move default calls off Mosaic; an
    explicit ``mode=`` is still honoured, and the CPU keeps the override."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        resolve_mode(None, None, backend="tpu")
    assert resolve_mode(None, "xla", backend="tpu") == "xla"
    assert resolve_mode(None, None, backend="cpu") == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    assert resolve_mode(None, None, backend="tpu") == "pallas"


def test_compiled_executes_with_interpret_false():
    """The acceptance bullet: kernels execute with interpret=False on
    CPU — resolved through the interpreter-bypass path."""
    table = jnp.asarray(RNG.standard_normal((64, 8)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 64, 32), jnp.int32)
    got = amm_gather(table, idx, n_banks=4, interpret=False)
    assert jnp.array_equal(got, ref.amm_gather_ref(table, idx))
