"""prefill_ms: device time of one run of the prefill program
(``launch/steps.py:make_prefill_step`` under ``jax.jit``), mean over the
runs in the traced window, in ms."""
from bench.metrics._program import program_seconds

PROGRAM = "jit_prefill_step"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    return None if found is None else 1e3 * found[0] / found[1]
