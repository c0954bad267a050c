"""Serving entry point: prefill a batch of prompts, then decode greedily from
the model's dense KV cache (example application for the inference
shapes).  The decode attention is the plain jnp path in
``models/attention.py``; the banked Pallas decode kernel is not on it.
The decode step's layer matmuls stream the float32 weights through
``kernels/weight_stream.py``; MLA models decode in the absorbed latent
form.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --preset tiny --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_arch, tiny_variant
from repro.configs.base import RuntimeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.memory import plan_memory
from repro.configs.base import SHAPES
from repro.models import DTypePolicy, init_model, make_cache


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_NAMES))
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = get_arch(args.arch)
    if args.preset == "tiny":
        arch = tiny_variant(arch)
    rt = RuntimeConfig(remat="none")
    policy = DTypePolicy.standard()

    # the paper's planner: pick the memory layout for this serving shape
    plan = plan_memory(arch, SHAPES["decode_32k"])
    print("memory plan:")
    for s in plan.streams:
        print(f"  {s.stream:12s} L={s.locality:5.3f} "
              f"{'AMM' if s.use_amm else 'banked'} banks={s.n_banks}  ({s.note})")

    params = init_model(jax.random.PRNGKey(0), arch, policy)
    cache_len = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, arch.vocab, (args.batch, args.prompt_len)), jnp.int32)
    batch = {"tokens": tokens}
    if arch.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((args.batch, arch.n_patches, arch.vit_dim)),
            jnp.float32)

    from_empty = arch.family in ("hybrid",) or arch.is_encdec
    decode = jax.jit(make_decode_step(arch, rt, policy))
    # compile both steps before any timer starts, so the times below are
    # of the steps alone
    t0 = time.perf_counter()
    if from_empty:
        cache_spec = jax.eval_shape(
            lambda: make_cache(arch, cache_len, args.batch, policy))
    else:
        prefill_jit = jax.jit(make_prefill_step(arch, rt, policy, cache_len))
        prefill_step = prefill_jit.lower(params, batch).compile()
        cache_spec = jax.eval_shape(prefill_jit, params, batch)[1]
    decode_step = decode.lower(
        params, cache_spec,
        jax.ShapeDtypeStruct((args.batch, 1), jnp.int32)).compile()
    print(f"compile (prefill and decode steps): "
          f"{time.perf_counter() - t0:.3f}s")

    if from_empty:
        # drivers for these families decode from an empty cache
        cache = make_cache(arch, cache_len, args.batch, policy)
        if arch.is_encdec:
            print("enc-dec: decoding against zero cross-cache (driver demo)")
        last = tokens[:, :1]
        logits = None
    else:
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(prefill_step(params, batch))
        t_prefill = time.perf_counter() - t0
        print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill:.3f}s")
        last = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    prefill_logits = logits

    outs = []
    t0 = time.perf_counter()
    for i in range(args.gen):
        last, logits, cache = decode_step(params, cache, last)
        outs.append(np.asarray(last))
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    toks = args.gen * args.batch
    print(f"decode: {toks} tokens in {dt:.3f}s -> {toks/dt:.1f} tok/s")
    gen = np.concatenate(outs, axis=1)
    print("sample continuation ids:", gen[0, :16].tolist())
    return {"tok_per_s": toks / dt, "generated": gen, "prompt": tokens,
            "prefill_logits": prefill_logits, "decode_logits": logits}


if __name__ == "__main__":
    main()
