"""mla_decode_mfu: model FLOPs of a traced decode step of an MLA model
(``bench/flops_mla.py``: two per weight of every MLA and MLP matrix and
the head, and attention as the model defines it over each step's live
context; mean over the traced steps) over the mean device time of one
run of the step program, times the chip's bf16 peak
(``bench/peaks.py``), in %.  Means on both sides, as ``decode_mfu``."""
from bench import flops_mla, peaks
from bench.metrics._program import program_seconds

PROGRAM = "jit_serve_step"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    contexts = run["context"].get("decode_contexts", [])
    if found is None or not contexts:
        return None
    shapes = run["found"]["config"]["shapes"]
    batch = run["context"]["batch"]
    work = sum(flops_mla.decode_step_flops(shapes, batch, c)
               for c in contexts) / len(contexts)
    peak = peaks.peak(run["device_kind"])["bf16_flops"]
    return 100.0 * work / (found[0] / found[1] * peak)
