"""decode_mfu: model FLOPs of a traced decode step (``bench/flops.py``,
mean over the traced steps, each at its live context) over the mean
device time of one run of the step program, times the chip's bf16 peak
(``bench/peaks.py``), in %.  Means on both sides, so that the reading
does not hang on the trace holding an event for every step the host
counted."""
from bench import flops, peaks
from bench.metrics._program import program_seconds

PROGRAM = "jit_serve_step"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    contexts = run["context"].get("decode_contexts", [])
    if found is None or not contexts:
        return None
    shapes = run["found"]["config"]["shapes"]
    batch = run["context"]["batch"]
    work = sum(flops.decode_step_flops(shapes, batch, c)
               for c in contexts) / len(contexts)
    peak = peaks.peak(run["device_kind"])["bf16_flops"]
    return 100.0 * work / (found[0] / found[1] * peak)
