"""End-to-end training driver (example application + FT demonstration).

Trains a reduced-config model on the synthetic corpus with the full
production substrate: jitted train step (grad accum + AdamW), periodic
checkpoints, straggler monitoring, optional int8 gradient compression
(error feedback), and crash-restart recovery (--simulate-failure).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --preset tiny --steps 50
  PYTHONPATH=src python -m repro.launch.train --preset m100 --steps 300
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_NAMES, get_arch, tiny_variant
from repro.configs.base import ArchConfig, RuntimeConfig
from repro.data import DataConfig, PrefetchLoader, SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import DTypePolicy, count_params, init_model
from repro.optim import adamw
from repro.runtime import (HeartbeatMonitor, compressed_grad_tree)

M100 = ArchConfig(
    name="m100", family="dense", n_layers=12, d_model=640, n_heads=10,
    n_kv_heads=5, d_ff=2560, vocab=16384, head_dim=64, qk_norm=True,
    act="silu", gated_mlp=True, tie_embeddings=True)


def build_arch(args) -> ArchConfig:
    if args.preset == "m100":
        return M100
    base = get_arch(args.arch)
    if args.preset == "tiny":
        return tiny_variant(base)
    return base


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_NAMES))
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "m100", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="crash (and auto-restart once) at this step")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = build_arch(args)
    rt = RuntimeConfig(accum_steps=args.accum, remat="none")
    policy = DTypePolicy.standard()
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=20,
                                total_steps=args.steps)

    key = jax.random.PRNGKey(0)
    params = init_model(key, arch, policy)
    opt_state = adamw.init(params, policy)
    print(f"arch={arch.name} params={count_params(params)/1e6:.1f}M "
          f"batch={args.batch}x{args.seq}")

    corpus = SyntheticCorpus(DataConfig(
        vocab=arch.vocab, seq_len=args.seq, global_batch=args.batch))
    loader = PrefetchLoader(corpus)

    ckpt = CheckpointManager(args.ckpt_dir, keep_last=2, async_save=True)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        state = ckpt.restore({"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start = ckpt.latest_step()
        print(f"resumed from step {start}")

    base_step = make_train_step(arch, rt, policy, opt_cfg)

    if args.compress_grads:
        # wrap: grads quantized int8 with error feedback before the update
        def step_fn(params, opt_state, err, batch):
            def micro(p, b):
                from repro.models.lm import loss_fn
                return loss_fn(p, arch, b, rt, policy)
            (loss, _), grads = jax.value_and_grad(micro, has_aux=True)(
                params, batch)
            grads, err = compressed_grad_tree(grads, err)
            new_p, new_o, stats = adamw.update(grads, opt_state, params,
                                               opt_cfg, policy)
            return new_p, new_o, err, {"loss": loss, **stats}
        step = jax.jit(step_fn)
        err_state = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                                 params)
    else:
        step = jax.jit(base_step)
        err_state = None

    monitor = HeartbeatMonitor(n_workers=1)
    losses = []
    crashed = False
    i = start
    while i < args.steps:
        batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
        t0 = time.time()
        if args.compress_grads:
            params, opt_state, err_state, stats = step(
                params, opt_state, err_state, batch)
        else:
            params, opt_state, stats = step(params, opt_state, batch)
        stats["loss"].block_until_ready()
        monitor.report(0, time.time() - t0)
        losses.append(float(stats["loss"]))
        i += 1
        if args.simulate_failure and i == args.simulate_failure and not crashed:
            print(f"!! simulated node failure at step {i}; restoring")
            crashed = True
            ckpt.save(i, {"params": params, "opt": opt_state}, blocking=True)
            # crash: lose live state
            params = opt_state = None
            state = ckpt.restore(
                {"params": jax.eval_shape(lambda: init_model(key, arch, policy)),
                 "opt": None} if False else
                {"params": init_model(key, arch, policy),
                 "opt": adamw.init(init_model(key, arch, policy), policy)})
            params, opt_state = state["params"], state["opt"]
            i = ckpt.latest_step()
            print(f"recovered at step {i}")
        if i % args.ckpt_every == 0:
            ckpt.save(i, {"params": params, "opt": opt_state})
        if i % args.log_every == 0 or i == args.steps:
            print(f"step {i:5d} loss={losses[-1]:.4f} "
                  f"lr={float(stats['lr']):.2e} "
                  f"gnorm={float(stats['grad_norm']):.2f} "
                  f"dt={time.time()-t0:.3f}s")
    ckpt.wait()
    loader.close()
    out = {"first_loss": losses[0], "final_loss": losses[-1],
           "steps": len(losses)}
    print(f"done: loss {out['first_loss']:.3f} -> {out['final_loss']:.3f}")
    assert out["final_loss"] < out["first_loss"], "training failed to learn"
    return out


if __name__ == "__main__":
    main()
