"""prefill_flash_roofline: the prefill attention kernel's share of the
bf16 peak in the prefill program (``jit_prefill_step``), in %.

Work: the causal attention term of ``bench/flops.prefill_flops`` (the
scores and the value sum of every query over the keys at or before it,
all layers, every prompt of the batch): the model's count, not the
blocks the kernel runs.  Time: the device time of the kernel's ops (HLO
name ``flash_prefill.<n>``) inside runs of the program, per run, mean
over the traced runs and the chips, from the trace that
``bench/run.py``'s tracer leaves in ``.bench_trace``.  The share is the
work over that time times ``bench/peaks.py``'s bf16 FLOP/s.  None where
the trace holds no run of the program or no op of the kernel (a
prefill that fell back to the XLA block scan), or is not the run's
own."""
from __future__ import annotations

from bench import flops, peaks
from bench.metrics._events import scoped_events
from bench.metrics._program import program_seconds
from bench.metrics.decode_weight_stream_roofline import kernel_seconds

PROGRAM = "jit_prefill_step"
KERNEL = "flash_prefill"


def attention_work(cfg: dict, batch: int, prompt_len: int) -> float:
    """FLOPs of causal attention in a prefill of ``batch`` prompts of
    ``prompt_len`` tokens: query i attends to i + 1 keys."""
    s = prompt_len
    return batch * flops.attention_flops(cfg, 1) * s * (s + 1) / 2


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    events = scoped_events(run) if found is not None else None
    if events is None:
        return None
    window, chips = events
    seconds = kernel_seconds(chips, window, PROGRAM, KERNEL)
    if seconds <= 0:
        return None
    ctx = run["context"]
    work = attention_work(run["found"]["config"]["shapes"], ctx["batch"],
                          ctx["prompt_len"])
    peak = peaks.peak(run["device_kind"])["bf16_flops"]
    return 100.0 * work / (seconds / found[1] * peak)
