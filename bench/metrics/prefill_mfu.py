"""prefill_mfu: model FLOPs of the traced prefills (``bench/flops.py``)
over their device time times the chip's bf16 peak
(``bench/peaks.py``), in %."""
from bench import flops, peaks
from bench.metrics._program import program_seconds

PROGRAM = "jit_prefill_step"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    if found is None:
        return None
    shapes = run["found"]["config"]["shapes"]
    ctx = run["context"]
    work = found[1] * flops.prefill_flops(shapes, ctx["batch"],
                                          ctx["prompt_len"])
    peak = peaks.peak(run["device_kind"])["bf16_flops"]
    return 100.0 * work / (found[0] * peak)
