"""The serve steps' named scopes, checked in the optimized HLO of the
tiny qwen3 on the CPU: every matmul lies under ``attn``, ``mlp`` or
``head``, and the whole-stack weight casts under ``weight_cast``.  A
refactor that drops a scope fails here, not in a chip run.  Also: the
stack cast before prefill's layer scan gives the same bits as each layer
casting its own slice, and decode, which casts no stack (its matmuls
stream the float32 stacks), gives the same bits as a decode of weights
cast to bfloat16 beforehand."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, tiny_variant
from repro.configs.base import RuntimeConfig
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import DTypePolicy, init_model, lm, make_cache

SCOPES = ("embed", "weight_cast", "layers", "attn", "mlp", "head")
ARCH = tiny_variant(get_arch("qwen3-1.7b"))
RT = RuntimeConfig(remat="none")
POLICY = DTypePolicy.standard()
BATCH, PROMPT, CACHE = 2, 12, 16
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def params():
    # norm scales start at zero, which every dtype holds exactly: move
    # every weight off its initial value so a cast of them would show
    params = init_model(jax.random.PRNGKey(0), ARCH, POLICY)
    key = jax.random.PRNGKey(2)
    return jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(key, x.shape, x.dtype), params)


def _programs(params):
    """The two serve steps and their arguments, as the serve entry
    builds them."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PROMPT), 0,
                                ARCH.vocab)
    cache = make_cache(ARCH, CACHE, BATCH, POLICY)
    return {
        "prefill": (make_prefill_step(ARCH, RT, POLICY, CACHE),
                    (params, {"tokens": tokens})),
        "decode": (make_decode_step(ARCH, RT, POLICY),
                   (params, cache, tokens[:, :1])),
    }


def _hlo(params, which: str) -> str:
    fn, args = _programs(params)[which]
    return jax.jit(fn).lower(*args).compile().as_text()


def _innermost(line: str) -> str | None:
    found = _OP_NAME.search(line)
    if not found:
        return None
    parts = [p for p in found.group(1).split("/")[:-1] if p in SCOPES]
    return parts[-1] if parts else None


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_every_matmul_is_under_attn_mlp_or_head(params, which):
    dots = [ln for ln in _hlo(params, which).splitlines()
            if re.search(r"\b(dot|convolution)\(", ln)]
    assert dots
    scopes = {_innermost(ln) for ln in dots}
    assert scopes == {"attn", "mlp", "head"}, [
        ln.strip()[:160] for ln in dots if _innermost(ln) not in
        ("attn", "mlp", "head")]


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_whole_stack_weight_casts_are_under_weight_cast(params, which):
    # the layer matrices, stacked over the layers: [L, in, out] f32
    stacked = {tuple(x.shape) for x in jax.tree.leaves(params["blocks"])
               if x.ndim == 3}
    casts = {}
    for ln in _hlo(params, which).splitlines():
        found = re.search(r"= bf16\[(\d+),(\d+),(\d+)\]\S* convert\(", ln)
        shape = found and tuple(int(d) for d in found.groups())
        if shape in stacked:
            casts.setdefault(shape, []).append(ln)
    if which == "decode":
        # the decode step's matmuls read the f32 stacks themselves
        assert casts == {}
        return
    assert set(casts) == stacked
    assert {_innermost(ln) for lns in casts.values() for ln in lns} == \
        {"weight_cast"}


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_stack_cast_is_bit_identical_to_per_layer_cast(params, which,
                                                       monkeypatch):
    fn, args = _programs(params)[which]
    if which == "decode":
        # a decode step from a prefilled cache, so attention reads it
        pre, pre_args = _programs(params)["prefill"]
        logits, cache = jax.jit(pre)(*pre_args)
        args = (params, cache,
                jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
    stack = jax.jit(fn)(*args)
    if which == "decode":
        # the kernel rounds each f32 block as astype does; at these widths
        # it takes no K tiles, so it sums in the same order either way
        cast = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.ndim >= 3 else x,
            params["blocks"])
        per_layer = jax.jit(fn)({**params, "blocks": cast}, *args[1:])
    else:
        # each layer then casts its own slice where it uses it
        monkeypatch.setattr(lm, "_cast_blocks", lambda blocks, dtype: blocks)
        per_layer = jax.jit(lambda *a: fn(*a))(*args)
    for a, b in zip(jax.tree.leaves(stack), jax.tree.leaves(per_layer)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
