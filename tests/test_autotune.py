"""Autotune harness tests (ISSUE 8): candidate legality, shape-bucket
keys, table round-trip, dispatch fallbacks, and a tiny end-to-end tune."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.ops import _pick_block, amm_gather, kv_decode

RNG = np.random.default_rng(5)


def test_candidates_are_legal():
    for cfg in autotune.candidates("kv_decode", b=2, hq=8, hkv=2, s=64,
                                   d=16, nb=4):
        assert (8 // 2) % cfg["block_h"] == 0
    for cfg in autotune.candidates("ssd_chunk", bt=1, h=6, q=16, p=8, n=4):
        assert 6 % cfg["block_h"] == 0
    with pytest.raises(KeyError):
        autotune.candidates("nope")


def test_shape_key_pow2_bucketing():
    k1 = autotune.shape_key("kv_decode", "cpu", "xla", s=1000, b=200)
    k2 = autotune.shape_key("kv_decode", "cpu", "xla", s=1024, b=256)
    k3 = autotune.shape_key("kv_decode", "cpu", "xla", s=1025, b=256)
    assert k1 == k2 != k3


def test_pick_block_relegalizes():
    assert _pick_block(128, 256) == 128
    assert _pick_block(128, 96) == 96
    assert _pick_block(128, 97) == 97      # prime: whole-shape block
    assert _pick_block(4, 6) == 3
    assert _pick_block(1, 5) == 1


def test_table_roundtrip_and_fallback(tmp_path):
    path = tmp_path / "cache.json"
    entries = {
        autotune.shape_key("ssd_chunk", "cpu", "xla", h=64, q=32):
            {"config": {"block_h": 16}, "us": 1.0},
    }
    autotune.save_table(entries, str(path))
    loaded = autotune.load_table(str(path), refresh=True)
    assert loaded == json.loads(path.read_text())["entries"]
    try:
        got = autotune.get_config("ssd_chunk", "cpu", "xla", h=64, q=32)
        assert got == {"block_h": 16}
        # miss -> kernel default
        miss = autotune.get_config("ssd_chunk", "cpu", "xla", h=8192, q=8192)
        assert miss == autotune.DEFAULTS["ssd_chunk"]
    finally:
        autotune.load_table(refresh=True)      # restore the real table


def test_corrupt_table_reads_as_empty(tmp_path):
    path = tmp_path / "junk.json"
    path.write_text("{not json")
    try:
        assert autotune.load_table(str(path), refresh=True) == {}
    finally:
        autotune.load_table(refresh=True)


def test_tune_end_to_end_records_winner():
    q = jnp.asarray(RNG.standard_normal((2, 8, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 2, 64, 16)), jnp.float32)
    lens = jnp.asarray([64, 9], jnp.int32)
    entries = {}
    entry = autotune.tune("kv_decode", (q, k, k, lens, 4),
                          dict(b=2, hq=8, hkv=2, s=64, d=16, nb=4), repeat=2,
                          entries=entries)
    assert entry["config"] in [r["config"] for r in entry["swept"]]
    assert entry["us"] == min(r["us"] for r in entry["swept"])
    assert len(entries) == 1
    key = next(iter(entries))
    assert key.startswith(f"kv_decode|{jax.default_backend()}|")


def test_tuned_config_changes_nothing_numerically():
    """Whatever block the table picks, results must equal the oracle —
    dispatch through the real checked-in table (amm_gather has no
    block to pick and must still match)."""
    table = jnp.asarray(RNG.standard_normal((1024, 128)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 1024, 256), jnp.int32)
    got = amm_gather(table, idx, n_banks=4)        # tuned dispatch
    assert jnp.array_equal(got, ref.amm_gather_ref(table, idx))
    q = jnp.asarray(RNG.standard_normal((2, 8, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 4, 64, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 4, 64, 32)), jnp.float32)
    lens = jnp.asarray([64, 17], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(kv_decode(q, k, v, lens, n_banks=4)),
        np.asarray(ref.kv_decode_ref(q, k, v, lens)), atol=2e-5, rtol=2e-5)
