"""decode_weight_cast_ms: device time of the ``weight_cast`` scope in one
run of the decode step program (``jit_serve_step``), mean over the runs
in the traced window, in ms.  The scope covers the cast of the stacked
block weights to the compute dtype, before the layer scan."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_serve_step", "weight_cast")
