"""decode_weight_stream_roofline: the weight-streaming kernel's share of
the HBM roofline in the decode step program (``jit_serve_step``), in %.

Bytes: the layer matrices the step reads through the kernel, in float32
as the configuration keeps them (``departures.params_dtype``), so
``4 * bench/flops.block_params``, plus the bfloat16 rows each of a
layer's four kernel calls reads and writes (q, k and v from the
attention's input; o; gate and up from the MLP's input; down): a floor
of what the kernel moves.  Time: the device time of the kernel's ops
(HLO name ``weight_stream.<n>``) inside runs of the program, per run,
mean over the traced runs and the chips, from the trace that
``bench/run.py``'s tracer leaves in ``.bench_trace`` (decoded by
``bench/trace_scopes.py``).  The share is the bytes over that time
times ``bench/peaks.py``'s HBM bytes per second.  None where the trace
holds no run of the program or no op of the kernel (a program without
it), or is not the run's own."""
from __future__ import annotations

import bisect

from bench import flops, peaks
from bench.metrics._program import program_seconds
from bench.trace_reduce import _clip, find_xplane, program_name
from bench.trace_scopes import read_scoped_events

PROGRAM = "jit_serve_step"
KERNEL = "weight_stream"
TRACE_DIR = ".bench_trace"
WINDOW = "traced_window"
WEIGHT_BYTES = 4          # float32
ROW_BYTES = 2             # bfloat16 activations


def step_bytes(cfg: dict, batch: int) -> float:
    """Bytes the kernel moves in one decode step of ``batch`` rows."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    rows_in = d + hq * hd + d + ff
    rows_out = (hq + 2 * hkv) * hd + d + 2 * ff + d
    rows = cfg["num_hidden_layers"] * batch * (rows_in + rows_out)
    return WEIGHT_BYTES * flops.block_params(cfg) + ROW_BYTES * rows


def kernel_seconds(chips, window: tuple[float, float], program: str,
                   kernel: str) -> float:
    """Device seconds of the ops named ``kernel.<n>`` inside runs of
    ``program``, clipped to ``window``, mean over chips."""
    lo, hi = window
    total = 0.0
    for chip in chips.values():
        runs = sorted((s, e, program_name(n)) for n, s, e in chip["modules"])
        starts = [s for s, _, _ in runs]
        for name, _, s, e in chip["ops"]:
            if name.split(".")[0] != kernel:
                continue
            k = bisect.bisect_right(starts, s) - 1
            iv = _clip(s, e, lo, hi)
            if iv and k >= 0 and runs[k][1] >= e and runs[k][2] == program:
                total += iv[1] - iv[0]
    return total / max(len(chips), 1)


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    if found is None:
        return None
    try:
        path = find_xplane(run["found"]["bench"].parent / TRACE_DIR)
    except FileNotFoundError:
        return None
    window, chips = read_scoped_events(path, WINDOW)
    # the trace on disk is this run's when its window is the one reduced
    if window is None or abs(window[1] - window[0]
                             - run["trace"]["window_s"]) > 1e-6:
        return None
    seconds = kernel_seconds(chips, window, PROGRAM, KERNEL)
    if seconds <= 0:
        return None
    per_run = seconds / found[1]
    moved = step_bytes(run["found"]["config"]["shapes"],
                       run["context"]["batch"])
    bandwidth = peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (per_run * bandwidth)
