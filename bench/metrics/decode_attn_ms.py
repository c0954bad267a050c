"""decode_attn_ms: device time of the ``attn`` scope in one run of the
decode step program (``jit_serve_step``), mean over the runs in the
traced window, in ms.  The scope covers attention in every layer: norm,
QKV projection, qk-norm and RoPE, the cache write, scores, softmax and
output projection."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_serve_step", "attn")
