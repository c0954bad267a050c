"""Block-size autotuning for the compiled kernel surface.

For each kernel + shape class + backend, ``tune()`` sweeps the legal
block/grid candidates, times the *compiled* executable (warm-up
iterations absorb trace+compile, ``block_until_ready`` fences every
measurement), and records the winner.  Winners are cached in the
checked-in table ``_autotune_cache.json`` keyed by
``kernel|backend|mode|shape-bucket`` — ``kernels.ops`` consults it on
every call, so callers transparently get tuned configurations; a miss
falls back to ``DEFAULTS``.

Shape buckets round every dimension up to a power of two: a tuned
winner for (s=1024, b=8) also serves (s=700, b=6), which keeps the
table small while the candidates themselves are re-legalized against
the *actual* shape at dispatch time (``ops._pick_block``).

Re-tune (e.g. on new hardware or after a kernel change) with::

    PYTHONPATH=src python -m repro.kernels.autotune [--repeat N] [--write]

which sweeps the standard shape classes below and rewrites the table.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Callable, Iterable

import numpy as np

_CACHE_FILE = os.path.join(os.path.dirname(__file__), "_autotune_cache.json")
_TABLE: dict[str, dict] | None = None

DEFAULTS: dict[str, dict[str, int]] = {
    "kv_decode": {"block_h": 1},
    "ssd_chunk": {"block_h": 1},
}


# -- shape bucketing / cache table -------------------------------------
def _pow2_bucket(x: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(x, 1)))), 0)


def shape_key(kernel: str, backend: str, mode: str, **dims: int) -> str:
    parts = ";".join(f"{k}={_pow2_bucket(v)}" for k, v in sorted(dims.items()))
    return f"{kernel}|{backend}|{mode}|{parts}"


def load_table(path: str = _CACHE_FILE, refresh: bool = False) -> dict:
    global _TABLE
    if _TABLE is None or refresh:
        try:
            with open(path) as f:
                _TABLE = json.load(f).get("entries", {})
        except (OSError, ValueError):
            _TABLE = {}
    return _TABLE


def save_table(entries: dict, path: str = _CACHE_FILE) -> None:
    global _TABLE
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": dict(sorted(entries.items()))},
                  f, indent=1)
        f.write("\n")
    _TABLE = entries


def get_config(kernel: str, backend: str, mode: str, **dims: int
               ) -> dict[str, int]:
    """Tuned config for this call site, or the kernel default on a miss."""
    hit = load_table().get(shape_key(kernel, backend, mode, **dims))
    if hit:
        return dict(hit["config"])
    return dict(DEFAULTS[kernel])


# -- candidate enumeration ---------------------------------------------
def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def candidates(kernel: str, **dims: int) -> list[dict[str, int]]:
    """Legal block configs for one kernel at one (actual) shape."""
    if kernel == "kv_decode":
        group = max(dims["hq"] // dims["hkv"], 1)
        return [{"block_h": b} for b in _divisors(group)]
    if kernel == "ssd_chunk":
        return [{"block_h": b} for b in _divisors(dims["h"])]
    raise KeyError(f"unknown kernel {kernel!r}")


# -- timing ------------------------------------------------------------
def time_compiled(fn: Callable[[], Any], repeat: int = 30,
                  warmup: int = 2) -> tuple[float, float]:
    """(steady-state us/call, compile_ms).  The first call pays
    trace+compile; ``warmup`` more calls settle caches before timing."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn())
    us = (time.perf_counter() - t0) / repeat * 1e6
    return us, max(cold_ms - us / 1e3, 0.0)


# -- the tuner ---------------------------------------------------------
def _make_call(kernel: str, args: tuple, cfg: dict[str, int], mode: str
               ) -> Callable[[], Any]:
    from repro.kernels import ops

    if kernel == "kv_decode":
        q, k, v, lens, nb = args
        return lambda: ops.kv_decode(q, k, v, lens, n_banks=nb, mode=mode,
                                     **cfg)
    if kernel == "ssd_chunk":
        return lambda: ops.ssd_chunk(*args, mode=mode, **cfg)[0]
    raise KeyError(kernel)


def tune(kernel: str, args: tuple, dims: dict[str, int],
         mode: str = "compiled", repeat: int = 30,
         entries: dict | None = None) -> dict:
    """Sweep candidates for one kernel/shape, return the winning entry
    (and record it into ``entries`` when given)."""
    import jax

    from repro.kernels.lowering import resolve_mode

    resolved = resolve_mode(mode=mode)
    backend = jax.default_backend()
    rows = []
    for cfg in candidates(kernel, **dims):
        us, compile_ms = time_compiled(
            _make_call(kernel, args, cfg, resolved), repeat=repeat)
        rows.append({"config": cfg, "us": round(us, 2),
                     "compile_ms": round(compile_ms, 1)})
    best = min(rows, key=lambda r: r["us"])
    entry = {"config": best["config"], "us": best["us"],
             "compile_ms": best["compile_ms"], "mode": resolved,
             "swept": rows}
    if entries is not None:
        entries[shape_key(kernel, backend, resolved, **dims)] = entry
    return entry


# -- standard shape classes (the bench + serving shapes) ---------------
def _standard_problems() -> Iterable[tuple[str, tuple, dict[str, int]]]:
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for b, hq, hkv, s, d, nb in ((4, 8, 4, 512, 64, 8),
                                 (8, 16, 2, 1024, 64, 8)):
        q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        v_ = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
        lens = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
        yield "kv_decode", (q, k, v_, lens, nb), dict(
            b=b, hq=hq, hkv=hkv, s=s, d=d, nb=nb)
    for bt, h, qq, p, n in ((2, 4, 64, 32, 16), (2, 8, 128, 64, 32)):
        x = jnp.asarray(rng.standard_normal((bt, h, qq, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.4, (bt, h, qq)), jnp.float32)
        cum = jnp.cumsum(-dt, axis=-1)
        B = jnp.asarray(rng.standard_normal((bt, qq, n)), jnp.float32)
        C = jnp.asarray(rng.standard_normal((bt, qq, n)), jnp.float32)
        h0 = jnp.zeros((bt, h, p, n), jnp.float32)
        yield "ssd_chunk", (x, dt, cum, B, C, h0), dict(
            bt=bt, h=h, q=qq, p=p, n=n)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.kernels.autotune",
        description="Re-tune kernel block sizes and rewrite the cache.")
    ap.add_argument("--repeat", type=int, default=30,
                    help="timed iterations per candidate")
    ap.add_argument("--mode", default="compiled",
                    choices=("compiled", "interpret", "xla", "pallas"))
    ap.add_argument("--dry-run", action="store_true",
                    help="print winners without rewriting the table")
    args = ap.parse_args(argv)

    entries = dict(load_table())
    for kernel, call_args, dims in _standard_problems():
        entry = tune(kernel, call_args, dims, mode=args.mode,
                     repeat=args.repeat, entries=entries)
        print(f"{kernel} {dims}: {entry['config']} "
              f"({entry['us']:.1f} us, compile {entry['compile_ms']:.0f} ms)")
    if not args.dry_run:
        save_table(entries)
        print(f"wrote {len(entries)} entries to {_CACHE_FILE}")


if __name__ == "__main__":
    main()
