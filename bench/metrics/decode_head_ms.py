"""decode_head_ms: device time of the ``head`` scope in one run of the
decode step program (``jit_serve_step``), mean over the runs in the
traced window, in ms.  The scope covers the head: final norm, logits and
the greedy argmax."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_serve_step", "head")
