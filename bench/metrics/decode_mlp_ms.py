"""decode_mlp_ms: device time of the ``mlp`` scope in one run of the decode
step program (``jit_serve_step``), mean over the runs in the traced
window, in ms.  The scope covers the MLP in every layer: norm and the
gated feed-forward."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_serve_step", "mlp")
