"""Benchmark harness — one function per paper table/figure + framework
microbenchmarks.  Prints ``name,us_per_call,derived`` CSV rows.

  fig4_dse          — area-cycles / power-cycles DSE per benchmark (Fig 4)
  fig5_locality     — spatial locality + performance ratio (Fig 5)
  serving_dse       — LLM-serving traces (KV decode / paged KV / MoE
                      routing): full-grid sweep + AMM kind ranking
  tab_synthesis     — AMM design cost table (Sec III-A synthesis results)
  kernel_microbench — blocked kernels: interpret vs compiled rows
                      (--interpret/--compiled restrict to one mode)
  scheduler_microbench — C cycle loop vs pure-Python fallback (large trace)
  scheduler_batched — batched JAX grid vs per-point C / python loops
  dse_matrix        — full 15x13 DSE matrix: exhaustive C vs
                      surrogate-pruned batched-C vs warm cache
  fault_campaign    — seeded fault-injection campaigns per design kind
                      (SDC rate / corrected / detected fractions)
  lm_smoke_bench    — tiny-arch train/decode step wall times (CPU)

Full-size runs: ``python -m benchmarks.run --full`` (minutes).
DSE tables run on the parallel sweep runner; control worker processes
with ``--jobs N`` and enable the incremental on-disk result cache with
``--cache-dir DIR``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# set by main() from argparse; module-level so the table functions and
# ad-hoc imports (e.g. REPL use) see consistent defaults
FULL = False
JOBS = os.cpu_count() or 1
CACHE_DIR = None
BACKEND = "auto"  # scheduler cycle-loop backend for the DSE tables
ARTIFACT_DIR = None  # where fig5_locality drops fig5.csv (None = don't)
KERNEL_MODES = ("interpret", "compiled")  # kernel_microbench legs
KERNEL_REPEAT = 20  # timed iterations per kernel row (after warm-up)
# the interpret legs run the *eager* Pallas interpreter (per-call Python
# grid walk — the point of the row pair); a few iterations suffice
KERNEL_REPEAT_INTERPRET = 3
ROWS: list[dict] = []  # every _row() call, for --json


def _t(fn, *args, repeat=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn(*args, **kw)
    return (time.perf_counter() - t0) / repeat * 1e6


def _row(name: str, us: float, derived: str = "") -> None:
    ROWS.append({"name": name, "us": round(us, 1), "derived": derived})
    print(f"{name},{us:.1f},{derived}")


# ======================================================================
def fig4_dse() -> None:
    """Paper Fig 4: design-space exploration per benchmark."""
    from repro.core.bench import PAPER_FIG4, get_trace
    from repro.core.dse import (DEFAULT_DESIGNS, design_space_expansion,
                                pareto_front, run_sweep)
    from repro.core.sim import prepare_trace

    unrolls = (1, 2, 4, 8) if FULL else (2, 8)
    designs = DEFAULT_DESIGNS if FULL else DEFAULT_DESIGNS[::2]
    for name in PAPER_FIG4:
        tr = get_trace(name, full=FULL)
        t0 = time.perf_counter()
        pts = run_sweep(prepare_trace(tr), designs, unrolls,
                        jobs=JOBS, cache_dir=CACHE_DIR, backend=BACKEND)
        dt = (time.perf_counter() - t0) * 1e6
        banking = [p for p in pts if not p.is_amm]
        amm = [p for p in pts if p.is_amm]
        exp = design_space_expansion(banking, amm)
        fb = pareto_front(banking)
        fa = pareto_front(amm)
        best_b = min(p.time_us for p in banking)
        best_a = min(p.time_us for p in amm)
        # stall aggregates per kind-family: `bank_conflict_stalls` means
        # leaf sub-banking conflicts on NTX points but steering misses on
        # remap points — summing them across all AMM points (the old
        # `amm_steer_stalls` column) conflated the two mechanisms.
        ntx = [p for p in amm if p.design.split("-")[0]
               in ("h_ntx_rd", "b_ntx_wr", "hb_ntx")]
        remap = [p for p in amm if p.design.startswith("remap")]
        _row(f"fig4_dse.{name}", dt,
             f"points={len(pts)};expansion={exp:.2f};"
             f"fastest_banked_us={best_b:.2f};fastest_amm_us={best_a:.2f};"
             f"pareto_banked={len(fb)};pareto_amm={len(fa)};"
             f"bank_stalls={sum(p.bank_conflict_stalls for p in banking)};"
             f"ntx_parity_stalls={sum(p.parity_fanout_stalls for p in ntx)};"
             f"ntx_pair_stalls={sum(p.write_pair_stalls for p in ntx)};"
             f"ntx_leaf_stalls={sum(p.bank_conflict_stalls for p in ntx)};"
             f"remap_steer_stalls="
             f"{sum(p.bank_conflict_stalls for p in remap)}")


def fig5_locality() -> None:
    """Paper Fig 5: spatial locality vs AMM performance ratio over the
    full 15-benchmark suite (12 MachSuite-style kernels + the 3
    LLM-serving traces), summarized by Spearman rank correlation
    (the paper's claim holds when the ratio *decreases* with locality,
    i.e. rho < 0).  Writes ``fig5.csv`` under ``--artifact-dir``.

    Locality is a property of the workload, so this table always
    characterizes the *full-size* traces (TINY traces are dependence-
    bound and flatten the banking-vs-AMM timing signal the ratio
    measures); ``--full`` widens the design grid instead.
    """
    from repro.core.bench import BENCHMARKS, get_trace
    from repro.core.dse import (DEFAULT_DESIGNS, design_space_expansion,
                                performance_ratio, run_sweep, spearman_rho)
    from repro.core.sim import prepare_trace

    unrolls = (1, 2, 4, 8) if FULL else (2, 8)
    designs = DEFAULT_DESIGNS if FULL else DEFAULT_DESIGNS[::2]
    out = []
    for name in sorted(BENCHMARKS):
        tr = get_trace(name, full=True)
        t0 = time.perf_counter()
        pt = prepare_trace(tr)
        L = pt.locality
        pts = run_sweep(pt, designs, unrolls, jobs=JOBS,
                        cache_dir=CACHE_DIR, backend=BACKEND)
        ratio = performance_ratio(pts)
        exp = design_space_expansion([p for p in pts if not p.is_amm],
                                     [p for p in pts if p.is_amm])
        dt = (time.perf_counter() - t0) * 1e6
        out.append({"bench": name, "nodes": pt.n_nodes,
                    "mem_ops": pt.trace.n_mem, "L_spatial": L,
                    "perf_ratio": ratio, "expansion": exp,
                    "sweep_points": len(pts)})
        _row(f"fig5_locality.{name}", dt,
             f"L_spatial={L:.3f};perf_ratio={ratio:.3f};"
             f"expansion={exp:.3f}")
    rho = spearman_rho([r["L_spatial"] for r in out],
                       [r["perf_ratio"] for r in out])
    rho_exp = spearman_rho([r["L_spatial"] for r in out],
                           [r["expansion"] for r in out])
    n_ok = sum(np.isfinite(r["perf_ratio"]) for r in out)
    claim = "indeterminate" if not np.isfinite(rho) else rho < 0
    _row("fig5_locality.summary", 0.0,
         f"benchmarks={len(out)};finite_ratios={n_ok};"
         f"spearman_rho={rho:.3f};spearman_rho_expansion={rho_exp:.3f};"
         f"paper_claim_holds={claim}")
    if ARTIFACT_DIR:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(ARTIFACT_DIR, "fig5.csv")
        with open(path, "w") as f:
            f.write("bench,nodes,mem_ops,L_spatial,perf_ratio,expansion,"
                    "sweep_points\n")
            for r in sorted(out, key=lambda r: r["L_spatial"]):
                f.write(f"{r['bench']},{r['nodes']},{r['mem_ops']},"
                        f"{r['L_spatial']:.4f},{r['perf_ratio']:.4f},"
                        f"{r['expansion']:.4f},{r['sweep_points']}\n")
        # keep the artifact strictly tabular (no comment footer: CSV
        # readers would ingest it as a row); the rho summary lives in
        # the stdout rows / --json output
        print(f"# wrote {path} (spearman_rho={rho:.4f})", file=sys.stderr)


def serving_dse() -> None:
    """LLM-serving workload family: full-grid DSE over the three
    serving traces (batched mixed-length KV decode, paged-KV gather
    with block-table indirection, MoE top-k routing) and a ranking of
    every AMM kind family by its fastest point on each bench.

    Unlike the other DSE tables this one always sweeps the *full*
    20-design grid — the smoke stride would drop the ``b_ntx_wr`` kind
    and the sub-banked ``*-b4`` points, and the whole point of the
    table is a complete kind ranking (smoke runs thin the unroll axis
    instead; ``--full`` also switches to full-size traces).
    """
    from repro.core.bench import SERVING, get_trace
    from repro.core.dse import (DEFAULT_DESIGNS, design_space_expansion,
                                pareto_front, run_sweep)
    from repro.core.sim import prepare_trace

    unrolls = (1, 2, 4, 8) if FULL else (2, 8)
    kind_of = {d.label: d.kind for d in DEFAULT_DESIGNS}
    for name in SERVING:
        tr = get_trace(name, full=FULL)
        pt = prepare_trace(tr)
        t0 = time.perf_counter()
        pts = run_sweep(pt, DEFAULT_DESIGNS, unrolls, jobs=JOBS,
                        cache_dir=CACHE_DIR, backend=BACKEND)
        dt = (time.perf_counter() - t0) * 1e6
        banking = [p for p in pts if not p.is_amm]
        amm = [p for p in pts if p.is_amm]
        fastest: dict[str, float] = {}
        for p in pts:
            k = kind_of[p.design]
            fastest[k] = min(fastest.get(k, float("inf")), p.time_us)
        ranking = ">".join(sorted(fastest, key=fastest.get))
        exp = design_space_expansion(banking, amm)
        _row(f"serving_dse.{name}", dt,
             f"L_spatial={pt.locality:.3f};points={len(pts)};"
             f"kinds={len(fastest)};ranking={ranking};"
             f"winner={min(pts, key=lambda p: p.time_us).design};"
             f"fastest_banked_us={min(p.time_us for p in banking):.2f};"
             f"fastest_amm_us={min(p.time_us for p in amm):.2f};"
             f"expansion={exp:.2f};"
             f"pareto_amm={len(pareto_front(amm))}")


def tab_synthesis() -> None:
    """Sec III-A: synthesized cost of each AMM design point."""
    from repro.core.amm.spec import AMMSpec
    from repro.core.cost import memory_cost

    specs = [
        AMMSpec("banked", 8, 8, 4096, n_banks=4),
        AMMSpec("banked", 32, 32, 4096, n_banks=16),
        AMMSpec("multipump", 2, 2, 4096),
        AMMSpec("h_ntx_rd", 2, 1, 4096),
        AMMSpec("h_ntx_rd", 4, 1, 4096),
        AMMSpec("b_ntx_wr", 1, 2, 4096),
        AMMSpec("hb_ntx", 2, 2, 4096),
        AMMSpec("hb_ntx", 4, 2, 4096),
        AMMSpec("lvt", 2, 2, 4096),
        AMMSpec("lvt", 4, 2, 4096),
        AMMSpec("remap", 2, 2, 4096),
    ]
    for s in specs:
        us = _t(memory_cost, s, repeat=10)
        c = memory_cost(s)
        _row(f"tab_synthesis.{s.describe()}", us,
             f"area_mm2={c.area_mm2:.4f};rd_pj={c.read_energy_pj:.2f};"
             f"ns={c.access_ns:.3f};fmax_ghz={c.max_freq_ghz:.2f}")


def kernel_microbench() -> None:
    """The blocked kernel surface, interpret mode (the conformance
    anchor — dispatched *eagerly*, the Pallas interpreter walks the
    grid in Python per call) vs the compiled path (real Pallas lowering
    on TPU/GPU, the XLA grid executor on CPU).  Methodology: warm-up +
    ``block_until_ready`` keep trace/compile out of the timed loop;
    ``compile_ms`` is reported separately in ``derived`` along with the
    autotuned block sizes.  ``--interpret`` / ``--compiled`` restrict
    the run to one mode (default: both, so every ``kernel.X`` row gets
    a ``kernel.X_compiled`` twin recording the speedup)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import amm_gather, kv_decode, ssd_chunk
    from repro.kernels.autotune import get_config, time_compiled
    from repro.kernels.lowering import resolve_mode

    backend = jax.default_backend()
    rng = np.random.default_rng(0)

    def both_modes(name, make_call, extra, tuned):
        us_int = None
        if "interpret" in KERNEL_MODES:
            us_int, cms = time_compiled(make_call("interpret"),
                                        repeat=KERNEL_REPEAT_INTERPRET,
                                        warmup=1)
            _row(f"kernel.{name}", us_int,
                 f"{extra};interpret=True;eager=True;compile_ms={cms:.0f}")
        if "compiled" in KERNEL_MODES:
            mode = resolve_mode(mode="compiled")
            us, cms = time_compiled(make_call("compiled"),
                                    repeat=KERNEL_REPEAT)
            blocks = ";".join(f"{k}={v}" for k, v in sorted(tuned.items()))
            d = f"{extra};mode={mode};{blocks};compile_ms={cms:.0f}"
            if us_int is not None:
                d += f";speedup_vs_interpret={us_int / us:.1f}x"
            _row(f"kernel.{name}_compiled", us, d)

    table = jnp.asarray(rng.standard_normal((1024, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 1024, 256), jnp.int32)
    both_modes(
        "amm_gather_1024x128_n256",
        lambda m: lambda: amm_gather(table, idx, n_banks=4, mode=m),
        "banks=4", {})

    q = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 4, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 4, 512, 64)), jnp.float32)
    lens = jnp.asarray([512, 300, 100, 512], jnp.int32)
    both_modes(
        "kv_decode_b4_s512",
        lambda m: lambda: kv_decode(q, k, v, lens, n_banks=8, mode=m),
        "banks=8",
        get_config("kv_decode", backend, resolve_mode(mode="compiled"),
                   b=4, hq=8, hkv=4, s=512, d=64, nb=8))

    x = jnp.asarray(rng.standard_normal((2, 4, 64, 32)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.4, (2, 4, 64)), jnp.float32)
    cum = jnp.cumsum(-dt, axis=-1)
    B = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    h0 = jnp.zeros((2, 4, 32, 16), jnp.float32)
    both_modes(
        "ssd_chunk_q64",
        lambda m: lambda: ssd_chunk(x, dt, cum, B, C, h0, mode=m)[0],
        "bt2xh4",
        get_config("ssd_chunk", backend, resolve_mode(mode="compiled"),
                   bt=2, h=4, q=64, p=32, n=16))

    # serving-scale decode: the ROADMAP's LLM-workload shape class
    # (large batch, long context, mixed request lengths)
    bs, hqs, hkvs, ss, ds = 8, 16, 4, 1024, 64
    q2 = jnp.asarray(rng.standard_normal((bs, hqs, ds)), jnp.float32)
    k2 = jnp.asarray(rng.standard_normal((bs, hkvs, ss, ds)), jnp.float32)
    v2 = jnp.asarray(rng.standard_normal((bs, hkvs, ss, ds)), jnp.float32)
    lens2 = jnp.asarray(rng.integers(0, ss + 1, bs), jnp.int32)
    both_modes(
        "kv_decode_serving_b8_s1024",
        lambda m: lambda: kv_decode(q2, k2, v2, lens2, n_banks=8, mode=m),
        "banks=8;ragged=True",
        get_config("kv_decode", backend, resolve_mode(mode="compiled"),
                   b=bs, hq=hqs, hkv=hkvs, s=ss, d=ds, nb=8))


def amm_replay() -> None:
    """Whole-trace functional-sim replay (lax.scan) vs the per-step
    Python loop, plus vmap-batched replay across seeds."""
    import jax
    import jax.numpy as jnp

    from repro.core.amm import AMMSpec, make_amm
    from repro.core.amm import replay as rp

    depth = 1024 if FULL else 256
    n_cycles = 2048 if FULL else 512
    n_seeds = 8
    rng = np.random.default_rng(0)
    for spec in (AMMSpec("hb_ntx", 4, 2, depth),
                 AMMSpec("lvt", 4, 2, depth),
                 AMMSpec("remap", 2, 3, depth)):
        init = jnp.asarray(rng.integers(0, 2**32, depth, dtype=np.uint32))
        ra, wa, wv, wm = (jnp.asarray(x)
                          for x in rp.make_trace(spec, n_cycles, seed=1))
        sim = make_amm(spec, init)

        def step_loop():
            st = sim.state
            for t in range(n_cycles):
                st, vals = sim.step(st, ra[t], wa[t], wv[t], wm[t])
            return jax.block_until_ready(vals)

        def replay_once():
            _, res = rp.replay(spec, rp.init_flat(spec, init),
                               ra, wa, wv, wm)
            return jax.block_until_ready(res.read_vals)

        step_us = _t(step_loop, repeat=1)
        replay_us = _t(replay_once)
        _row(f"amm_replay.{spec.kind}", replay_us,
             f"T={n_cycles};depth={depth};step_loop_us={step_us:.1f};"
             f"speedup={step_us / replay_us:.1f}x")

        # vmap across seeds: batched oracle verification throughput
        states = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[rp.init_flat(spec, init) for _ in range(n_seeds)])
        traces = [rp.make_trace(spec, n_cycles, seed=s)
                  for s in range(n_seeds)]
        bra, bwa, bwv, bwm = (jnp.asarray(np.stack([tr[i] for tr in traces]))
                              for i in range(4))

        def replay_vmapped():
            _, res = rp.replay_batched(spec, states, bra, bwa, bwv, bwm)
            return jax.block_until_ready(res.read_vals)

        us = _t(replay_vmapped)
        _row(f"amm_replay.{spec.kind}_vmap{n_seeds}", us,
             f"T={n_cycles};per_trace_us={us / n_seeds:.1f}")


def scheduler_microbench() -> None:
    """Compiled C cycle loop vs the pure-Python reference loop on a
    large prepared trace, across arbitration-heavy memory kinds."""
    from repro.core.bench import BENCHMARKS, get_trace
    from repro.core.dse.sweep import _BASE_FU, DesignPoint, _spec_for
    from repro.core.sim import _cycle_ext, prepare_trace
    from repro.core.sim.scheduler import (ScheduleConfig, _schedule_c,
                                          _schedule_py)

    # ~7k nodes in smoke runs, the full 56k-node trace with --full
    params = BENCHMARKS["gemm_ncubed"].Params() if FULL \
        else BENCHMARKS["gemm_ncubed"].Params(n=12)
    pt = prepare_trace(get_trace("gemm_ncubed", params))
    fast = _cycle_ext.load()
    for dp in (DesignPoint("banked", n_banks=8),
               DesignPoint("hb_ntx", 4, 2),
               DesignPoint("remap", 4, 2)):
        specs = {aid: _spec_for(dp, pt.array_depths[aid],
                                pt.trace.word_bytes[aid] * 8)
                 for aid in pt.trace.array_names}
        cfg = ScheduleConfig(
            mem=specs,
            fu_counts={k: v * 4 for k, v in _BASE_FU.items()})
        t0 = time.perf_counter()
        res = _schedule_py(pt, cfg)             # one timed run, result kept
        py_us = (time.perf_counter() - t0) * 1e6
        if fast is None:
            _row(f"scheduler.{dp.label}_py_only", py_us,
                 f"nodes={pt.n_nodes};cycles={res.cycles};no C compiler")
            continue
        c_res = _schedule_c(fast, pt, cfg)
        if c_res != res:
            raise RuntimeError(f"C/python loops diverged on {dp.label}")
        c_us = _t(_schedule_c, fast, pt, cfg, repeat=5)
        _row(f"scheduler.{dp.label}_c_loop", c_us,
             f"nodes={pt.n_nodes};cycles={res.cycles};"
             f"py_loop_us={py_us:.0f};speedup={py_us / c_us:.1f}x")


def scheduler_batched() -> None:
    """Batched JAX grid evaluation vs the per-point C and pure-Python
    loops on full gemm Fig-4 design grids.

    One ``schedule_batched`` jit call evaluates the whole 20-design x
    4-unroll composition grid; the per-point loops evaluate the same
    configs one call at a time.  Rows record the measured grid-vs-point
    ratios both ways, and on host CPUs they are a *loss* for the jax
    engine at every practical size: the deferral scan is sequential
    (~60-180 pops/cycle) and each XLA while-loop step carries
    microseconds of overhead vs nanoseconds per C pop, which vmap
    amortizes across lanes but cannot eliminate.  The rows exist to
    keep that trade-off measured and honest across PRs; the jax path's
    value is the three-way conformance matrix + accelerator scale-out,
    not host-CPU wall time.  See README "Execution backends".
    """
    from repro.core.bench import BENCHMARKS, get_trace
    from repro.core.dse.sweep import (DEFAULT_DESIGNS, DEFAULT_UNROLLS,
                                      schedule_config_for)
    from repro.core.sim import _cycle_ext, prepare_trace
    from repro.core.sim.jax_cycle import schedule_batched
    from repro.core.sim.scheduler import _schedule_c, _schedule_py

    # TINY-size trace: the batched engine's sequential deferral scan
    # makes larger traces impractically slow on host CPUs (the point of
    # this table is to measure that honestly, not to hide it)
    params = BENCHMARKS["gemm_ncubed"].Params(n=8) if FULL \
        else BENCHMARKS["gemm_ncubed"].Params(n=6)
    pt = prepare_trace(get_trace("gemm_ncubed", params))
    grid = [(dp, u) for dp in DEFAULT_DESIGNS for u in DEFAULT_UNROLLS]
    cfgs = [schedule_config_for(pt, dp, u) for dp, u in grid]

    t0 = time.perf_counter()
    res = schedule_batched(pt, cfgs)          # compile + first run
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = schedule_batched(pt, cfgs)
    jax_us = (time.perf_counter() - t0) * 1e6

    fast = _cycle_ext.load()
    c_us = float("nan")
    if fast is not None:
        t0 = time.perf_counter()
        c_res = [_schedule_c(fast, pt, cfg) for cfg in cfgs]
        c_us = (time.perf_counter() - t0) * 1e6
        if c_res != res:
            raise RuntimeError("jax grid diverged from the C loop")
    _row("scheduler_batched.grid_vs_c", jax_us,
         f"nodes={pt.n_nodes};points={len(cfgs)};c_loop_us={c_us:.0f};"
         f"jax_vs_c={c_us / jax_us:.3f}x;compile_s={compile_s:.1f}")

    # pure-Python comparison on a subset (the reference loop is slow)
    sub = [(dp, u) for dp in DEFAULT_DESIGNS[::4] for u in (2, 8)]
    sub_cfgs = [schedule_config_for(pt, dp, u) for dp, u in sub]
    jr = schedule_batched(pt, sub_cfgs)
    t0 = time.perf_counter()
    jr = schedule_batched(pt, sub_cfgs)
    jax_sub_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    py_res = [_schedule_py(pt, cfg) for cfg in sub_cfgs]
    py_us = (time.perf_counter() - t0) * 1e6
    if py_res != jr:
        raise RuntimeError("jax grid diverged from the python loop")
    _row("scheduler_batched.grid_vs_py", jax_sub_us,
         f"nodes={pt.n_nodes};points={len(sub_cfgs)};"
         f"py_loop_us={py_us:.0f};jax_vs_py={py_us / jax_sub_us:.1f}x")


def dse_matrix() -> None:
    """Full 15-bench x 13-design x 4-unroll DSE matrix three ways:
    exhaustive per-point C sweep, surrogate-pruned batched-C sweep
    (band prune + in-C Pareto front caps; uncalibrated serving traces
    fall back to exhaustive inside run_sweep) and the fully-warm
    on-disk cache (manifest fast path, trace generation skipped).  The
    unroll axis is the default sweep grid (1/2/4/8), the design axis
    the 13-design calibration matrix.

    Traces are generated and prepared in a prepass so the timed legs
    measure sweep compute only; the surrogate leg *does* pay for its
    own feature extraction (it is part of the pruned-sweep cost).
    Derived fields pin the headline claims: pruned-vs-exhaustive
    speedup and Pareto-front identity on every bench.
    """
    import tempfile

    from repro.core.bench import BENCHMARKS, get_trace, trace_cache_key
    from repro.core.dse.pareto import pareto_front
    from repro.core.dse.runner import (SweepCache, point_key, run_sweep,
                                       run_sweep_bench)
    from repro.core.dse.surrogate import CALIBRATION_DESIGNS
    from repro.core.dse.sweep import DEFAULT_UNROLLS, evaluate_point
    from repro.core.sim import prepare_trace

    designs = list(CALIBRATION_DESIGNS.values())
    unrolls = DEFAULT_UNROLLS
    grid = [(dp, u) for dp in designs for u in unrolls]
    names = sorted(BENCHMARKS)
    prepared = {n: prepare_trace(get_trace(n, full=FULL)) for n in names}
    n_pts = len(names) * len(grid)

    # leg 1: exhaustive — every grid point through the per-point C loop
    t0 = time.perf_counter()
    full_res = {n: [evaluate_point(prepared[n], dp, u) for dp, u in grid]
                for n in names}
    t_exh = time.perf_counter() - t0

    # leg 2: surrogate-pruned (analytic ranking + batched C + front caps)
    t0 = time.perf_counter()
    pruned_res = {n: run_sweep(prepared[n], designs, unrolls,
                               prune="surrogate") for n in names}
    t_prn = time.perf_counter() - t0

    fronts_ok = 0
    n_kept = 0
    for n in names:
        n_kept += len(pruned_res[n])
        ff = {(p.design, p.unroll) for p in pareto_front(full_res[n])}
        fp = {(p.design, p.unroll) for p in pareto_front(pruned_res[n])}
        fronts_ok += ff == fp

    # leg 3: warm cache — manifest fast path, trace generation skipped
    with tempfile.TemporaryDirectory() as d:
        cache = SweepCache(d)
        for n in names:
            fp_ = prepared[n].fingerprint
            for (dp, u), p in zip(grid, full_res[n]):
                cache.put(point_key(fp_, dp, u, 2), p)
            cache.manifest_put(trace_cache_key(n, full=FULL), fp_)
        t0 = time.perf_counter()
        for n in names:
            run_sweep_bench(n, designs, unrolls, full=FULL, cache=cache)
        t_warm = time.perf_counter() - t0

    _row("dse_matrix.exhaustive_c", t_exh * 1e6,
         f"benches={len(names)};points={n_pts}")
    _row("dse_matrix.surrogate_pruned", t_prn * 1e6,
         f"kept={n_kept}/{n_pts};speedup={t_exh / t_prn:.2f}x;"
         f"fronts_identical={fronts_ok}/{len(names)}")
    _row("dse_matrix.warm_cache", t_warm * 1e6,
         f"points={n_pts};speedup={t_exh / t_warm:.1f}x")


def fault_campaign() -> None:
    """Seeded fault-injection campaigns per design kind (ISSUE 7): wall
    time of one batched campaign plus the resilience record — SDC rate,
    corrected/detected fractions of affected reads, mean detection
    latency.  Smoke runs use the golden campaign shape
    (32 faults x 96 cycles, seed 7) so rows are directly comparable to
    ``tests/golden_faults.json``; ``--full`` widens the population."""
    from repro.core.dse.sweep import DEFAULT_DESIGNS, _spec_for
    from repro.core.fault import FaultConfig, run_campaign

    labels = ("banked8", "multipump-2R2W", "h_ntx_rd-4R1W", "b_ntx_wr-1R2W",
              "hb_ntx-4R2W", "lvt-2R2W", "lvt-4R2W", "remap-4R2W")
    cfg = FaultConfig(n_faults=128, n_cycles=256, seed=7) if FULL \
        else FaultConfig(n_faults=32, n_cycles=96, seed=7)
    by_label = {d.label: d for d in DEFAULT_DESIGNS}
    for label in labels:
        spec = _spec_for(by_label[label], 256, 32)
        t0 = time.perf_counter()
        res = run_campaign(spec, cfg)
        us = (time.perf_counter() - t0) * 1e6
        r = res.resilience
        _row(f"fault_campaign.{label}", us,
             f"cover={r.cover};faults={r.n_faults};"
             f"sdc_rate={r.sdc_rate:.4f};corrected={r.corrected_frac:.3f};"
             f"detected={r.detected_frac:.3f};latency={r.det_latency:.2f}")


def lm_smoke_bench() -> None:
    """Tiny-config train/decode step wall time per assigned arch."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCH_NAMES, get_arch, tiny_variant
    from repro.configs.base import RuntimeConfig
    from repro.launch.steps import make_decode_step, make_train_step
    from repro.models import DTypePolicy, init_model, make_cache
    from repro.optim import adamw

    rt = RuntimeConfig(remat="none")
    policy = DTypePolicy.standard()
    names = ARCH_NAMES if FULL else ARCH_NAMES[:4]
    for name in names:
        arch = tiny_variant(get_arch(name))
        params = init_model(jax.random.PRNGKey(0), arch, policy)
        opt = adamw.init(params, policy)
        batch = {"tokens": jnp.ones((2, 64), jnp.int32),
                 "labels": jnp.ones((2, 64), jnp.int32)}
        if arch.family == "vlm":
            batch["patches"] = jnp.ones((2, arch.n_patches, arch.vit_dim),
                                        jnp.float32)
        if arch.is_encdec:
            batch["frames"] = jnp.ones((2, 64, arch.d_model), jnp.float32)
        step = jax.jit(make_train_step(arch, rt, policy))
        us = _t(lambda: jax.block_until_ready(step(params, opt, batch)))
        _row(f"lm_train_tiny.{name}", us, "b2xs64")
        cache = make_cache(arch, 32, 2)
        dec = jax.jit(make_decode_step(arch, rt, policy))
        tok = jnp.ones((2, 1), jnp.int32)
        us = _t(lambda: jax.block_until_ready(dec(params, cache, tok)))
        _row(f"lm_decode_tiny.{name}", us, "cache32")


def grad_sync_bench() -> None:
    """Cross-pod grad sync: bf16 all-reduce vs int8 compressed
    (collective wire bytes from the compiled HLO, 2-pod test mesh)."""
    import subprocess
    import sys as _sys
    import textwrap

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from repro.launch.roofline import analyze_hlo
        from repro.runtime.compressed_sync import (compressed_pod_mean,
                                                   uncompressed_pod_mean)
        mesh = make_test_mesh((2, 4), ("pod", "data"))
        g = {"w": jnp.zeros((4096, 1024), jnp.float32)}
        ref = jax.jit(lambda x: uncompressed_pod_mean(x, mesh)).lower(g).compile()
        cmp_ = jax.jit(lambda x: compressed_pod_mean(x, mesh)).lower(g).compile()
        b0 = analyze_hlo(ref.as_text())["collective_bytes"]
        b1 = analyze_hlo(cmp_.as_text())["collective_bytes"]
        print(f"{b0},{b1},{b1/b0:.3f}")
    """)
    # the child only counts HLO bytes on 8 fake host devices: it must
    # never reach for an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([_sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        raise RuntimeError(f"grad_sync child failed:\n{out.stderr[-2000:]}")
    b0, b1, ratio = out.stdout.strip().splitlines()[-1].split(",")
    _row("grad_sync.bf16_allreduce_bytes", float(b0), "16M grads")
    _row("grad_sync.int8_compressed_bytes", float(b1),
         f"ratio={ratio};error_feedback=repro.runtime.ft")


# ======================================================================
TABLES = {
    "fig4_dse": fig4_dse,
    "fig5_locality": fig5_locality,
    "serving_dse": serving_dse,
    "tab_synthesis": tab_synthesis,
    "kernel_microbench": kernel_microbench,
    "amm_replay": amm_replay,
    "scheduler_microbench": scheduler_microbench,
    "scheduler_batched": scheduler_batched,
    "dse_matrix": dse_matrix,
    "fault_campaign": fault_campaign,
    "lm_smoke_bench": lm_smoke_bench,
    "grad_sync_bench": grad_sync_bench,
}


def _only_list(arg: str | None) -> list[str] | None:
    if arg is None:
        return None
    names = [n.strip() for n in arg.split(",") if n.strip()]
    unknown = sorted(set(names) - set(TABLES))
    if unknown:
        raise SystemExit(f"unknown table(s) {unknown}; "
                         f"choose from {sorted(TABLES)}")
    return names


def main(argv=None) -> None:
    global FULL, JOBS, CACHE_DIR, BACKEND, ARTIFACT_DIR
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description="Paper table/figure benchmark harness (CSV to stdout).")
    ap.add_argument("--full", action="store_true",
                    help="full-size traces/archs (minutes)")
    ap.add_argument("--only", default=None, metavar="TABLE[,TABLE...]",
                    help=f"run a subset of {sorted(TABLES)}")
    ap.add_argument("--jobs", type=int, default=os.cpu_count() or 1,
                    help="worker processes for DSE sweeps (1 = serial)")
    ap.add_argument("--backend", choices=("auto", "c", "py", "jax"),
                    default="auto",
                    help="scheduler cycle-loop backend for DSE tables")
    ap.add_argument("--cache-dir", default=None,
                    help="on-disk DSE result cache for incremental re-runs")
    ap.add_argument("--artifact-dir", default=None, metavar="DIR",
                    help="directory for table CSV artifacts "
                         "(fig5_locality writes fig5.csv there)")
    mode_grp = ap.add_mutually_exclusive_group()
    mode_grp.add_argument("--interpret", action="store_true",
                          help="kernel_microbench: interpret rows only")
    mode_grp.add_argument("--compiled", action="store_true",
                          help="kernel_microbench: compiled rows only")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON "
                         "(e.g. BENCH.json) for cross-PR perf tracking")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = _only_list(args.only)
    FULL, JOBS, CACHE_DIR = args.full, args.jobs, args.cache_dir
    BACKEND = args.backend
    ARTIFACT_DIR = args.artifact_dir
    global KERNEL_MODES
    if args.interpret:
        KERNEL_MODES = ("interpret",)
    elif args.compiled:
        KERNEL_MODES = ("compiled",)

    print("name,us_per_call,derived")
    for name, fn in TABLES.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        fn()
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"full": FULL, "rows": ROWS}, f, indent=1)
        print(f"# wrote {len(ROWS)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
