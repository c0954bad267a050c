"""prefill_attn_ms: device time of the ``attn`` scope in one run of the
prefill program (``jit_prefill_step``), mean over the runs in the traced
window, in ms.  The scope covers attention in every layer: norm, QKV
projection, qk-norm and RoPE, the block-scan attention and output
projection."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_prefill_step", "attn")
