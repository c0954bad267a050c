"""MiniCPM3 (multi-head latent attention with muP scalings) through the
program's serve steps against the benchmark's plain float32 reference
(``bench/configs/minicpm3-4b.reference.py``), on seeded weights at tiny
MLA widths with the scalings set to values that are not 1; the
configuration the benchmark builds from its file against the
registry's; and the MLA decode step's matmuls through the
weight-streaming kernel."""
import dataclasses
import importlib.util
import json
import sys
from contextlib import nullcontext
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import RuntimeConfig
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import DTypePolicy

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "bench" / "configs" / "minicpm3-4b.json"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, vocab_size=256, dim_model_base=16)
RT = RuntimeConfig(remat="none")
POLICY = DTypePolicy.standard()
# the program computes in bfloat16 over float32 weights, the reference
# in float32 at HIGHEST: over two tiny layers that moves the logits by
# 1.0-1.9% of their norm (seeds 0-5, CPU); the fp8 (e4m3) control moved
# them by 20% at the published widths (4 layers, CPU), and a depth-cut
# model scaled for its own depth by 22% (the test below)
REL_TOL = 0.05


def _load(path: Path, name: str):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))      # the driver imports bench.*
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load(ROOT / "bench" / "configs" / "minicpm3-4b.reference.py",
                 "minicpm3_reference_under_test")


@pytest.fixture(scope="module")
def driver():
    return _load(ROOT / "bench" / "drivers" / "sessions.py",
                 "minicpm3_sessions_driver_under_test")


def _config(**shapes) -> dict:
    config = json.loads(CONFIG.read_text())
    config["shapes"].update(TINY, **shapes)
    return config


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_driver_arch_is_the_registry_arch_cut_in_depth(driver):
    config = json.loads(CONFIG.read_text())
    arch = driver.arch_from(config)
    assert arch == dataclasses.replace(get_arch("minicpm3-4b"), n_layers=31)
    # the residual branches keep the published depth's scale
    assert arch.residual_scale == pytest.approx(1.4 / np.sqrt(62))
    assert arch.embed_scale == 12.0 and arch.head_divisor == 10.0


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_and_decode_through_the_cache_match_reference(
        reference, driver, seed):
    """Prefill of a prompt, decode of known tokens through the cache,
    then the cache's length set back to the prompt's end and another
    token decoded there: every logit against the reference's full
    forward pass over the same sequence."""
    config = _config()
    shapes, arch = config["shapes"], driver.arch_from(config)
    assert arch.residual_scale != 1 and arch.head_divisor != 1
    params = jax.jit(lambda k: reference.init_weights(shapes, k))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    b, p, g = 2, 12, 5
    prompt = rng.integers(0, arch.vocab, (b, p)).astype(np.int32)
    fed = rng.integers(0, arch.vocab, (b, g)).astype(np.int32)
    other = rng.integers(0, arch.vocab, (b, 1)).astype(np.int32)

    prefill = jax.jit(make_prefill_step(arch, RT, POLICY, p + g))
    decode = jax.jit(make_decode_step(arch, RT, POLICY))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)})
    got = [logits[:, -1]]
    start = cache
    for t in range(g):
        _, lg, cache = decode(params, cache, jnp.asarray(fed[:, t:t + 1]))
        got.append(lg[:, 0])
    rewound = {**cache, "len": jnp.asarray(p, jnp.int32)}
    _, again, cache = decode(params, rewound, jnp.asarray(other))
    assert int(cache["len"]) == p + 1 and int(start["len"]) == p

    seq = np.concatenate([prompt, fed], axis=1)
    want = reference.logits_at(params, shapes, jnp.asarray(seq),
                               jnp.asarray(np.broadcast_to(
                                   np.arange(p - 1, p + g), (b, g + 1))))
    assert _rel(jnp.stack(got, 1), want) < REL_TOL
    want_again = reference.logits_at(
        params, shapes, jnp.asarray(np.concatenate([prompt, other], 1)),
        jnp.asarray(np.full((b, 1), p)))
    assert _rel(again, want_again) < REL_TOL


def test_depth_cut_model_keeps_the_published_residual_scale(reference,
                                                            driver):
    """A model holding 2 of 62 published layers scales its residual
    branches by scale_depth / sqrt(62), as the reference does; the
    scale of its own depth gives other logits."""
    config = _config(published_num_hidden_layers=62)
    shapes, arch = config["shapes"], driver.arch_from(config)
    params = jax.jit(lambda k: reference.init_weights(shapes, k))(
        jax.random.PRNGKey(3))
    prompt = np.random.default_rng(3).integers(0, arch.vocab, (2, 16))
    prompt = jnp.asarray(prompt, jnp.int32)
    at = jnp.full((2, 1), 15)
    want = reference.logits_at(params, shapes, prompt, at)
    own = reference.logits_at(params, dict(shapes,
                                           published_num_hidden_layers=2),
                              prompt, at)

    def program(a):
        return jax.jit(make_prefill_step(a, RT, POLICY, 16))(
            params, {"tokens": prompt})[0]

    assert _rel(program(arch), want) < REL_TOL
    assert _rel(own, want) > 3 * REL_TOL
    cut_own = dataclasses.replace(arch, residual_scale=1.4 / 2 ** 0.5)
    assert _rel(program(cut_own), own) < REL_TOL


@pytest.mark.parametrize("partitioned", [False, True])
def test_mla_decode_streams_five_calls_a_layer_unless_partitioned(
        reference, driver, partitioned, monkeypatch):
    """The MLA decode step multiplies by its layer stacks through the
    weight-streaming kernel, five calls a layer (q_a with kv_a; q_b; o;
    gate with up; down); lowered for a mesh it keeps the kernel off, and
    its logits agree."""
    from jax.sharding import Mesh

    from repro.launch.sharding import activation_sharding

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    config = _config()
    shapes, arch = config["shapes"], driver.arch_from(config)
    params = jax.jit(lambda k: reference.init_weights(shapes, k))(
        jax.random.PRNGKey(4))
    prompt = jnp.asarray(np.random.default_rng(4).integers(
        0, arch.vocab, (2, 8)), jnp.int32)
    _, cache = make_prefill_step(arch, RT, POLICY, 16)(
        params, {"tokens": prompt})
    tokens = jnp.array([[3], [5]], jnp.int32)

    def run():
        # a step function of its own: a traced program is reused by JAX
        # whatever launcher context it is called in
        step = make_decode_step(arch, RT, POLICY)
        return (str(jax.make_jaxpr(step)(params, cache, tokens)),
                jax.jit(step)(params, cache, tokens)[1])

    _, streamed = run()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with (activation_sharding(mesh, ("data",)) if partitioned
          else nullcontext()):
        jaxpr, logits = run()
    assert jaxpr.count("pallas_call[") == (0 if partitioned else 5)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(streamed, np.float32),
                               rtol=2**-7, atol=1e-6)
