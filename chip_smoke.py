#!/usr/bin/env python3
"""Chip smoke test: the system's main paths, once, on a TPU.

    python chip_smoke.py              # one chip: serve + explorer + kernels
    python chip_smoke.py --chips 4    # four chips: sharded serve only

One chip runs three phases in this one process (a second process could
not reach a chip this one holds):

  serve     ``repro.launch.serve.main`` with qwen3-1.7b at full width
            (batch 8, prompt 1024, 32 generated tokens, random weights
            from seed 0).  Generated ids must be in the vocabulary and
            every logit finite; the prefill's last-position logits of
            the first prompts are compared with a float32 reference
            (float32 compute, "highest" matmul precision) on this chip.
  explorer  the device engines: ``schedule_batched`` over every golden
            design row of every bench (tests/golden_schedule.json),
            ``run_sweep(backend="jax", jobs=1)`` against the C loop on
            one bench, and the 8 pinned fault campaigns
            (tests/golden_faults.json).  All are integer results and
            must match bit for bit.
  kernels   ``amm_gather``, ``kv_decode`` and ``ssd_chunk`` through
            ``repro.kernels`` in their default mode, which must resolve
            to Mosaic (``pallas``): the compiled HLO must hold a
            ``tpu_custom_call``, and each result is compared with its
            oracle in ``kernels/ref.py``.

``--chips 4`` builds a 2x2 ("data", "model") mesh, places qwen3-1.7b's
parameters and KV cache with ``launch/sharding.py``, runs prefill and a
few decode steps, checks that the parameters span all four devices, and
compares the logits with the same steps run on one device.

Each phase prints one line: what ran, its check, and its host wall time.
Any failed check raises, and the script exits non-zero; off a TPU it
refuses to run.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"
SERVE = dict(batch=8, prompt_len=1024, gen=32)
N_REF_PROMPTS = 2          # float32 reference rows (HBM bound)
# relative L2 of bf16-compute logits against the float32 reference and of
# 2x2-sharded against one-device logits: bf16 rounding through 28 layers
# stays near 0.02, a wrong weight or head placement is O(1)
REL_L2_TOL = 5e-2
SHARD_DECODE_STEPS = 4
KV = dict(b=8, hq=16, hkv=8, d=128, s=4096, n_banks=8)   # qwen3-1.7b decode
SSD = dict(bt=2, h=24, q=256, p=64, n=128)               # mamba2-130m chunk
AMM = dict(v=151936, d=2048, n_banks=4, n=512)           # qwen3 embedding
KV_ATOL = 4e-2             # bf16 output vs float32 oracle
SSD_REL_TOL = 1e-3         # max |err| / max |ref|
SWEEP_BENCH = "gemm_ncubed"


def _line(phase: str, t0: float, **fields) -> None:
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {kv} host_wall_s={time.perf_counter() - t0:.3f}",
          flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _rel_l2(got, want) -> tuple[float, float]:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = got - want
    return (float(np.abs(err).max()),
            float(np.linalg.norm(err) / np.linalg.norm(want)))


# ---------------------------------------------------------------- serve
def _reference_prefill(arch, tokens, cache_len: int):
    """float32 prefill logits of ``tokens`` with the serve entry point's
    seed-0 weights."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RuntimeConfig
    from repro.launch.steps import make_prefill_step
    from repro.models import DTypePolicy, init_model

    f32 = DTypePolicy(jnp.float32, jnp.float32, jnp.float32)
    params = init_model(jax.random.PRNGKey(0), arch, DTypePolicy.standard())
    step = jax.jit(make_prefill_step(arch, RuntimeConfig(remat="none"), f32,
                                     cache_len))
    with jax.default_matmul_precision("highest"):
        logits, _ = step(params, {"tokens": tokens})
    return jax.device_get(logits[:, -1, :])


def phase_serve(preset: str = "full", batch: int = SERVE["batch"],
                prompt_len: int = SERVE["prompt_len"],
                gen: int = SERVE["gen"]) -> None:
    import numpy as np

    from repro.configs import get_arch, tiny_variant
    from repro.launch import serve

    t0 = time.perf_counter()
    out = serve.main(["--arch", ARCH, "--preset", preset,
                      "--batch", str(batch), "--prompt-len", str(prompt_len),
                      "--gen", str(gen)])
    arch = get_arch(ARCH)
    if preset == "tiny":
        arch = tiny_variant(arch)
    ids = out["generated"]
    _check(ids.shape == (batch, gen), f"generated shape {ids.shape}")
    _check(bool((ids >= 0).all() and (ids < arch.vocab).all()),
           "generated id outside the vocabulary")
    prefill = np.asarray(out["prefill_logits"], np.float32)
    decode = np.asarray(out["decode_logits"], np.float32)
    _check(bool(np.isfinite(prefill).all() and np.isfinite(decode).all()),
           "non-finite logits")
    n_ref = min(N_REF_PROMPTS, batch)
    want = _reference_prefill(arch, out["prompt"][:n_ref],
                              prompt_len + gen)
    max_abs, rel = _rel_l2(prefill[:n_ref, -1, :], want)
    _line("serve", t0, arch=ARCH, preset=preset,
          shape=f"b{batch}xp{prompt_len}+g{gen}",
          ids_in_vocab=True, logits_finite=True, ref_rows=n_ref,
          tol_rel_l2=REL_L2_TOL, max_abs_err=f"{max_abs:.6g}",
          rel_l2_err=f"{rel:.6g}")
    _check(rel <= REL_L2_TOL,
           f"prefill logits rel-L2 {rel:.4g} > {REL_L2_TOL}")


# ------------------------------------------------------------- explorer
def phase_explorer(benches: list[str] | None = None) -> None:
    """The golden checks of tests/test_golden_schedule.py and
    tests/test_fault.py, called directly so that the chip is held to the
    very assertions the CPU suite makes."""
    sys.path.insert(0, str(ROOT / "tests"))
    import test_fault
    import test_golden_schedule as golden

    from repro.core.bench import get_trace
    from repro.core.dse.runner import run_sweep
    from repro.core.dse.sweep import DEFAULT_DESIGNS
    from repro.core.sim import prepare_trace

    t0 = time.perf_counter()
    rows = [(b, r) for b, r in golden._BENCH_ROWS
            if benches is None or b in benches]
    for bench, bench_rows in rows:
        golden.test_jax_grid_matches_golden(bench, bench_rows)
    _line("explorer.schedule_batched", t0,
          benches=",".join(b for b, _ in rows),
          rows=sum(len(r) for _, r in rows), bit_exact=True)

    t0 = time.perf_counter()
    pt = prepare_trace(get_trace(SWEEP_BENCH))
    grid = DEFAULT_DESIGNS[::4]
    pts_jax = run_sweep(pt, grid, (1, 4), backend="jax", jobs=1)
    pts_c = run_sweep(pt, grid, (1, 4), backend="c", jobs=1)
    _check(pts_jax == pts_c, "run_sweep jax points differ from the C loop")
    _line("explorer.run_sweep", t0, bench=SWEEP_BENCH, points=len(pts_jax),
          equal_to_c=True)

    t0 = time.perf_counter()
    for row in test_fault.GOLDEN:
        test_fault.test_golden_campaigns_pinned(row)
    _line("explorer.fault_campaigns", t0, campaigns=len(test_fault.GOLDEN),
          bit_exact=True)


# -------------------------------------------------------------- kernels
def _run_compiled(fn, *args, require_mosaic: bool):
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    _check(mosaic or not require_mosaic, "no tpu_custom_call in the HLO")
    return jax.block_until_ready(compiled(*args)), mosaic


def phase_kernels(kv: dict = KV, ssd: dict = SSD, amm: dict = AMM,
                  require_mosaic: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import amm_gather, kv_decode, ref, ssd_chunk
    from repro.kernels.lowering import resolve_mode

    mode = resolve_mode()
    _check(mode == "pallas" or not require_mosaic,
           f"default kernel mode resolves to {mode!r}, not 'pallas'")
    rng = np.random.default_rng(0)
    hp = jax.default_matmul_precision("highest")

    t0 = time.perf_counter()
    b, hq, hkv, d, s = kv["b"], kv["hq"], kv["hkv"], kv["d"], kv["s"]
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    lens = jnp.asarray(np.r_[0, s, rng.integers(1, s, b - 2)], jnp.int32)
    got, mosaic = _run_compiled(
        lambda *a: kv_decode(*a, n_banks=kv["n_banks"]), q, k, v, lens,
        require_mosaic=require_mosaic)
    with hp:
        want = ref.kv_decode_ref(q, k, v, lens)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    _line("kernels.kv_decode", t0, mode=mode, tpu_custom_call=mosaic,
          shape=f"B{b}.Hq{hq}.Hkv{hkv}.D{d}.S{s}.NB{kv['n_banks']}.bf16",
          max_abs_err=f"{err:.6g}", tol=KV_ATOL)
    _check(err <= KV_ATOL, f"kv_decode max |err| {err:.4g} > {KV_ATOL}")

    t0 = time.perf_counter()
    bt, h, qq, p, n = ssd["bt"], ssd["h"], ssd["q"], ssd["p"], ssd["n"]
    x = jnp.asarray(rng.standard_normal((bt, h, qq, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (bt, h, qq)), jnp.float32)
    cum = jnp.cumsum(-dt * jnp.asarray(rng.uniform(0.5, 2.0, (1, h, 1)),
                                       jnp.float32), axis=-1)
    B = jnp.asarray(rng.standard_normal((bt, qq, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((bt, qq, n)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((bt, h, p, n)), jnp.float32)
    (y, h1), mosaic = _run_compiled(ssd_chunk, x, dt, cum, B, C, h0,
                                    require_mosaic=require_mosaic)
    with hp:
        y_ref, h_ref = ref.ssd_chunk_ref(x, dt, cum, B, C, h0)
    rel = max(float(jnp.abs(a - w).max() / jnp.abs(w).max())
              for a, w in ((y, y_ref), (h1, h_ref)))
    _line("kernels.ssd_chunk", t0, mode=mode, tpu_custom_call=mosaic,
          shape=f"Bt{bt}.H{h}.Q{qq}.P{p}.N{n}.f32",
          max_err_over_max_ref=f"{rel:.6g}", tol=SSD_REL_TOL)
    _check(rel <= SSD_REL_TOL, f"ssd_chunk error {rel:.4g} > {SSD_REL_TOL}")

    t0 = time.perf_counter()
    table = jax.random.normal(jax.random.PRNGKey(1), (amm["v"], amm["d"]),
                              jnp.bfloat16)
    idx = jnp.asarray(rng.integers(0, amm["v"], amm["n"]), jnp.int32)
    got, mosaic = _run_compiled(
        lambda t, i: amm_gather(t, i, n_banks=amm["n_banks"]), table, idx,
        require_mosaic=require_mosaic)
    exact = bool(jnp.array_equal(got, ref.amm_gather_ref(table, idx)))
    _line("kernels.amm_gather", t0, mode=mode, tpu_custom_call=mosaic,
          shape=f"V{amm['v']}.D{amm['d']}.NB{amm['n_banks']}.N{amm['n']}"
                ".bf16", bit_exact=exact)
    _check(exact, "amm_gather differs from jnp.take")


# ------------------------------------------------------------ 4 chips
def phase_sharded(preset: str = "full", batch: int = SERVE["batch"],
                  prompt_len: int = SERVE["prompt_len"],
                  steps: int = SHARD_DECODE_STEPS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch, tiny_variant
    from repro.configs.base import RuntimeConfig
    from repro.launch import sharding as shd
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import DTypePolicy, init_model

    t0 = time.perf_counter()
    devs = jax.devices()
    _check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
    arch = get_arch(ARCH)
    if preset == "tiny":
        arch = tiny_variant(arch)
    rt = RuntimeConfig(remat="none")
    policy = DTypePolicy.standard()
    cache_len = prompt_len + steps
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, arch.vocab, (batch, prompt_len)),
                         jnp.int32)
    prefill = make_prefill_step(arch, rt, policy, cache_len)
    decode = make_decode_step(arch, rt, policy)

    # one device: the comparison
    params = init_model(jax.random.PRNGKey(0), arch, policy)
    logits1, cache = jax.jit(prefill)(params, {"tokens": tokens})
    dec1 = jax.jit(decode)
    fed = [jnp.argmax(logits1[:, -1:, :], axis=-1).astype(jnp.int32)]
    ref_logits = [np.asarray(logits1[:, -1, :], np.float32)]
    for _ in range(steps):
        nxt, lg, cache = dec1(params, cache, fed[-1])
        fed.append(nxt)
        ref_logits.append(np.asarray(lg[:, -1, :], np.float32))
    del cache

    # 2x2 mesh: params and cache placed by the sharding rules
    param_sh = shd.to_named(shd.param_pspecs(params, mesh), mesh)
    sparams = jax.device_put(params, param_sh)
    del params
    total = sum(x.nbytes for x in jax.tree.leaves(sparams))
    per_dev = {d.id: 0 for d in mesh.devices.flat}
    for x in jax.tree.leaves(sparams):
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    _check(all(0 < b < 0.6 * total for b in per_dev.values()),
           f"params not spread over 4 devices: {per_dev} of {total}")
    baxes = shd.batch_axes_for(mesh, batch)
    tok_sh = NamedSharding(mesh, P(baxes, None))
    with shd.activation_sharding(mesh, baxes, False, "tp"):
        logits4, cache4 = jax.jit(prefill)(
            sparams, {"tokens": jax.device_put(tokens, tok_sh)})
        cache_sh = shd.to_named(shd.cache_pspecs(cache4, mesh, batch), mesh)
        cache4 = jax.device_put(cache4, cache_sh)
        dec4 = jax.jit(decode, in_shardings=(param_sh, cache_sh, tok_sh))
        got = [np.asarray(logits4[:, -1, :], np.float32)]
        for t in range(steps):     # teacher-forced with the one-device ids
            _, lg, cache4 = dec4(sparams, cache4,
                                 jax.device_put(fed[t], tok_sh))
            got.append(np.asarray(lg[:, -1, :], np.float32))
    errs = [_rel_l2(g, w) for g, w in zip(got, ref_logits)]
    worst = max(e[1] for e in errs)
    _line("sharded.serve", t0, arch=ARCH, preset=preset, mesh="2x2",
          axes="data,model", param_bytes_total=total,
          param_bytes_per_device=",".join(
              str(per_dev[k]) for k in sorted(per_dev)),
          prefill_plus_decode_steps=f"1+{steps}",
          max_abs_err=f"{max(e[0] for e in errs):.6g}",
          worst_rel_l2=f"{worst:.6g}", tol_rel_l2=REL_L2_TOL)
    _check(worst <= REL_L2_TOL,
           f"sharded logits rel-L2 {worst:.4g} > {REL_L2_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serve path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}; "
              "refusing to run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_sharded()
    else:
        phase_serve()
        phase_explorer()
        phase_kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
