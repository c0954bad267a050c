"""The weight-streaming matmul (``kernels/weight_stream.py``) against
``x @ W[l].astype(bf16)`` with float32 accumulation, in the Pallas
interpreter and on the XLA grid path: batches of 1 to 64 rows, tiny
widths (every layer of a small stack), qwen3-1.7b's (2048 x 6144,
6144 x 2048, 2048 x 1024, and q, k, v in one call), minicpm3-4b's q_a
with kv_a (2560 x (768 + 288), tiled in K) and q_b (768 x 3840), and a K
too deep for one block column (llama-3-8b's down projection, 14336),
which is tiled.  Mosaic's compile of it is in ``test_tpu_compile.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import weight_stream
from repro.kernels.weight_stream import BLOCK_BYTES, _tiles

BF16 = jnp.bfloat16
# (rows, K, widths of the stacks, layers in the stack)
SHAPES = {
    "tiny.b1": (1, 64, (32,), 3),
    "tiny.b3.two": (3, 128, (64, 256), 3),
    "tiny.b8.qkv": (8, 256, (384, 128, 128), 3),
    "qwen3.gate_up": (8, 2048, (6144, 6144), 2),
    "qwen3.down": (64, 6144, (2048,), 2),
    "qwen3.k": (3, 2048, (1024,), 2),
    "qwen3.qkv": (1, 2048, (2048, 1024, 1024), 2),
    "deep.k_tiled": (8, 14336, (128, 256), 2),
    # minicpm3-4b: q_a with kv_a (288 columns, K-tiled in f32), and q_b
    "minicpm3.q_a_kv_a.k_tiled": (8, 2560, (768, 288), 2),
    "minicpm3.q_b": (8, 768, (3840,), 2),
}
CASES = [(name, layer) for name, (_, _, _, n_layers) in SHAPES.items()
         for layer in range(n_layers)
         if name.startswith("tiny") or layer == n_layers - 1]


@pytest.mark.parametrize("mode", ["interpret", "xla"])
@pytest.mark.parametrize("name,layer", CASES)
def test_weight_stream_matches_cast_matmul(name, layer, mode):
    b, k, widths, n_layers = SHAPES[name]
    k_tiled = _tiles(k, widths, 4, BLOCK_BYTES)[1] < k
    assert k_tiled == name.endswith("k_tiled")
    keys = jax.random.split(jax.random.PRNGKey(b * 7 + k), len(widths) + 1)
    x = jax.random.normal(keys[0], (b, 1, k), jnp.float32).astype(BF16)
    ws = [jax.random.normal(kw, (n_layers, k, n), jnp.float32) / k ** 0.5
          for kw, n in zip(keys[1:], widths)]
    got = weight_stream(x, ws, jnp.int32(layer), mode=mode)
    assert len(got) == len(ws)
    for out, w in zip(got, ws):
        want = jnp.dot(x, w[layer].astype(BF16),
                       preferred_element_type=jnp.float32).astype(BF16)
        assert out.dtype == BF16 and out.shape == (b, 1, w.shape[2])
        # both accumulate the same bf16 products in f32; the order may
        # differ, which moves a result by at most one bf16 rounding step
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2**-7, atol=1e-6)


@pytest.mark.parametrize("partitioned", [False, True])
def test_decode_streams_its_matrices_unless_partitioned(partitioned,
                                                        monkeypatch):
    """The decode step of a dense GQA model multiplies by every layer
    matrix through the kernel, four calls a layer (q, k and v; o; gate
    and up; down), and casts no stack.  Lowered for a mesh, it keeps the
    kernel off (XLA cannot partition it), and its logits agree."""
    from contextlib import nullcontext

    from jax.sharding import Mesh

    from repro.configs import get_arch, tiny_variant
    from repro.configs.base import RuntimeConfig
    from repro.launch.sharding import activation_sharding
    from repro.launch.steps import make_decode_step
    from repro.models import DTypePolicy, init_model, make_cache

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    arch = tiny_variant(get_arch("qwen3-1.7b"))
    policy = DTypePolicy.standard()
    params = init_model(jax.random.PRNGKey(0), arch, policy)
    cache = make_cache(arch, 16, 2, policy)
    tokens = jnp.array([[3], [5]], jnp.int32)

    def run():
        # a step function of its own: a traced program is reused by JAX
        # whatever launcher context it is called in
        step = make_decode_step(arch, RuntimeConfig(remat="none"), policy)
        return (str(jax.make_jaxpr(step)(params, cache, tokens)),
                jax.jit(step)(params, cache, tokens)[1])

    _, streamed = run()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with (activation_sharding(mesh, ("data",)) if partitioned
          else nullcontext()):
        jaxpr, logits = run()
    assert jaxpr.count("pallas_call[") == (0 if partitioned else 4)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(streamed, np.float32),
                               rtol=2**-7, atol=1e-6)
