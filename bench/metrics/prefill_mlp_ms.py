"""prefill_mlp_ms: device time of the ``mlp`` scope in one run of the
prefill program (``jit_prefill_step``), mean over the runs in the traced
window, in ms.  The scope covers the MLP in every layer: norm and the
gated feed-forward."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_prefill_step", "mlp")
