#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip the process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
it needs is found by name, from files alone:

  bench/configs/<config>.json            sizes, source, driver, guarantees
  bench/configs/<config>.reference.py    the plain reference
  bench/traffic/<traffic>.json           the traffic mix's parameters
  bench/limits/<cell>.json               the check's limits and sample
  bench/drivers/<driver>.py              the code that drives the program
  bench/metrics/<metric>.py              one reader per per-layer metric

The run sets up (weights or inputs from ``--seed``, one warm-up of the
cell's own shapes), measures for ``--seconds``, checks what the timed
path produced against the reference, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``checks``: each
number compared beside its limit.  With ``--trace 1`` the profiler
records part of the window and the metrics are the cell's per-layer
ones.  Off a TPU, or with fewer chips than the cell asks for, it prints
no result and exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
# fixed paths inside the checkout: the compile cache's path is part of
# its key, and the TPU runtime's logs stay out of a fixed /tmp path
JAX_CACHE = ROOT / ".jax_cache"
TPU_LOGS = ROOT / ".bench_cache" / "tpu_logs"
TRACE_DIR = ROOT / ".bench_trace"


def load_file(path: Path, name: str | None = None):
    """Import the Python file ``path`` as a module."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a cell needs, found by its name: the workload entry,
    its configuration, traffic mix, limits and metric lists."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    bench = root / "bench"

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": _json(root / entry["file"]),
        "reference": bench / "configs" / f"{cell['config']}.reference.py",
        "traffic": _json(bench / "traffic" / f"{cell['traffic']}.json"),
        "limits": _json(bench / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "bench": bench,
    }


class CompileCounter:
    """Counts the compilations JAX requests, with the host time of each,
    and sums the seconds JAX spends tracing, lowering, compiling and
    reading its compile cache, through ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/compile_requests_use_cache")
    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                 "/jax/core/compile/backend_compile_duration": "compile",
                 "/jax/compilation_cache/cache_retrieval_time_sec":
                     "cache_read"}

    def __init__(self) -> None:
        self.times: list[float] = []
        self.durations: list[tuple[str, float, float]] = []
        self.cache_hits = 0

    def install(self) -> None:
        import jax

        def on_event(name, *args, **kwargs):
            if name in self.EVENTS:
                self.times.append(time.perf_counter())
            elif name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_duration(name, secs, *args, **kwargs):
            on_event(name)
            if name in self.DURATIONS:
                self.durations.append((self.DURATIONS[name],
                                       time.perf_counter(), secs))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)

    def seconds_before(self, t: float) -> dict[str, float]:
        """Seconds of each kind of JAX compile work done before ``t``."""
        out = dict.fromkeys(self.DURATIONS.values(), 0.0)
        for kind, at, secs in self.durations:
            if at <= t:
                out[kind] += secs
        return out


class Tracer:
    """The profiler over the first ``units`` units of work of the
    window (the mix's batches), or none."""

    def __init__(self, enabled: bool, units: int, directory: Path,
                 spans) -> None:
        self.enabled = enabled
        self.units = units
        self.directory = directory
        self.spans = spans
        self.on = False
        self.done = 0
        self._window = None

    def start(self) -> None:
        if not self.enabled:
            return
        import shutil

        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # host spans are TraceAnnotations, which the host tracer keeps;
        # the Python tracer would add an event per Python call
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.directory),
                                 profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("traced_window")
        self._window.__enter__()
        self.spans.annotate = True
        self.on = True

    def unit_done(self) -> None:
        if self.on:
            self.done += 1
            if self.done >= self.units:
                self.stop()

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        self._window.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.on = False


def device_summary() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def execute(found: dict, seed: int, seconds: float, trace: bool,
            t_start: float = T_START, trace_dir: Path = TRACE_DIR,
            setup: dict[str, float] | None = None,
            control: bool = False) -> dict:
    """Drive one run of the cell ``found`` (see :func:`find_cell`) and
    return its result line.  Does not look for a chip: ``main`` does.
    ``setup`` holds the seconds of set-up phases before this call.
    With ``control`` the check judges the reference's lower-precision
    control in the program's place, which has to read ``correct``
    false; the benchmark's own runs never set it."""
    sys.path.insert(0, str(found["bench"].parent))
    from bench.spans import Spans
    from bench.trace_reduce import reduce_trace

    counter = CompileCounter()
    counter.install()
    spans = Spans()
    traffic = found["traffic"]
    tracer = Tracer(trace, traffic.get("trace_units", 1), trace_dir, spans)
    t_load = time.perf_counter()
    driver = load_file(found["bench"] / "drivers"
                       / f"{found['config']['driver']}.py")
    reference = load_file(found["reference"])
    setup = dict(setup or {}, program_import=time.perf_counter() - t_load)
    out = driver.run(found["config"], traffic, seed, seconds, spans=spans,
                     tracer=tracer, reference=reference,
                     limits=found["limits"], t_start=t_start,
                     control=control)
    tracer.stop()
    w0, w1 = out["window"]
    compiles = counter.between(w0, w1)
    checks = list(out["checks"])
    checks.append({"name": "compiles_in_window", "value": compiles,
                   "limit": 0, "ok": compiles == 0})
    device = device_summary()
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result: dict = {
        "correct": all(c["ok"] for c in checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
    }
    run = {"found": found, "spans": spans, "counts": out["counts"],
           "context": out["context"], "device_kind": device["kind"],
           "trace": None}
    if trace:
        run["trace"] = reduce_trace(trace_dir, "traced_window",
                                    {n for n, _, _ in spans.records})
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        metrics = {}
        for m in found["per_layer"]:
            reader = load_file(found["bench"] / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        traced = {"op_events": run["trace"]["op_events"],
                  "program_runs": {k: v[1] for k, v in
                                   run["trace"]["programs"].items()},
                  **{k: len(v) for k, v in run["context"].items()
                     if isinstance(v, list)}}
        result["breakdown"] = {
            "device_ops": run["trace"]["ops"][:10],
            "idle_gaps": run["trace"]["idle_gaps"][:10]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in found["end_to_end"] if m["name"] in out["e2e"]}
    result["metrics"] = metrics
    result["device"] = device
    setup.update(out["setup"])
    setup.update({f"jax.{k}": v
                  for k, v in counter.seconds_before(w0).items()})
    result["diagnostics"] = {"counts": out["counts"],
                             "traced": traced if trace else None,
                             "cache_hits": counter.cache_hits,
                             "setup_phases": setup,
                             "check_s": out["check_s"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program beside {BENCH}: src/repro is missing",
              file=sys.stderr)
        return 2
    found = find_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(JAX_CACHE))
    os.environ.setdefault("TPU_LOG_DIR", str(TPU_LOGS))
    Path(os.environ["TPU_LOG_DIR"]).mkdir(parents=True, exist_ok=True)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    t_jax = time.perf_counter()
    devs = jax.devices()
    chips = found["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: cell {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s); refusing "
              "to run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, is found in the cache by
    # the cell's later runs, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup = {"jax_import": t_jax - T_START,
             "backend_init": time.perf_counter() - t_jax}
    result = execute(found, args.seed, args.seconds, bool(args.trace),
                     setup=setup)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
