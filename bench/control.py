#!/usr/bin/env python3
"""Readings the check's limits are set from, for a served-model cell.

    python3 bench/control.py --workload qwen3-1.7b.decode --seeds 1-12

For each seed, in one process: weights from the seed, then the cell's
own batches through the same driver code and programs as a benchmark
run, until as many requests have finished as a run's check samples.
On those requests it reads the numbers a run's check compares, and
judges them by the same comparison (``judge`` of the cell's driver)
under the committed limits (``bench/limits/<cell>.json``), printed one
line per seed:

  program  ``served_logit_gap``: the widest gap between the float32
           reference's best logit and the logit of a token the program
           served; ``logit_rel_err``: the widest relative L2 distance
           of the program's kept logits from the reference's;
           ``program_correct``: both within their limits;
  control  the same for the reference computed in fp8 (e4m3, the
           precision below the configuration's bfloat16) in the
           program's place, at the same positions of the same sequences;
           ``control_correct`` has to read false on every seed.

The last line gives the largest program reading and the smallest
control reading of each number, the limits are set between (PERF.md
gives the readings).  Not run by the benchmark's runs; it needs the
chip the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def seed_list(text: str) -> list[int]:
    """``1-12`` or ``3,7,9`` -> seeds."""
    if "-" in text and "," not in text:
        lo, hi = (int(x) for x in text.split("-"))
        return list(range(lo, hi + 1))
    return [int(x) for x in text.split(",")]


def readings(found: dict, seed: int, spans) -> dict:
    """Program and control readings of one seed, each judged by the
    run's check (see module doc)."""
    from bench.run import load_file

    driver = load_file(found["bench"] / "drivers"
                       / f"{found['config']['driver']}.py")
    reference = load_file(found["reference"])
    server = driver.Server(found["config"], found["traffic"], seed,
                           reference)
    limits = found["limits"]
    want = limits["sample_requests"]
    batches = [server.serve(i + 1, spans,
                            keep_every=limits["keep_logits_every"])
               for i in range(math.ceil(want / server.batch))]
    prompts, served, kept, logits = driver.sample(batches, server.batch,
                                                  want, seed)
    del batches
    params, server.params = server.params, None
    out = {"seed": seed}
    for side, got in (
            ("program", reference.compare(params, server.shapes, prompts,
                                          served, kept, logits)),
            ("control", reference.compare(params, server.shapes, prompts,
                                          served, kept, control=True))):
        checks = driver.judge(got, limits)
        out[side] = {c["name"]: c["value"] for c in checks}
        out[f"{side}_correct"] = all(c["ok"] for c in checks)
    out["limits"] = {c["name"]: c["limit"] for c in checks}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seed_list)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench.run import JAX_CACHE, find_cell
    from bench.spans import Spans

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(JAX_CACHE))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    found = find_cell(args.workload)
    out = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(found, seed, Spans())
        r["seconds"] = time.perf_counter() - t0
        out.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({"workload": args.workload, **{
        f"{k}_{side}": f([r[side][k] for r in out])
        for k in out[0]["program"]
        for side, f in (("program", max), ("control", min))},
        "program_correct": all(r["program_correct"] for r in out),
        "control_failed_every_seed": not any(r["control_correct"]
                                             for r in out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
