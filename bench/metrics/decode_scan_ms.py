"""decode_scan_ms: device time of the ``layers`` scope in one run of the
decode step program (``jit_serve_step``), mean over the runs in the
traced window, in ms.  The scope covers the layer scan's own work
outside attention and the MLP: slicing each layer's weights and cache
out of the stacks and stacking the cache again (self time)."""
from bench.metrics._scopes import scope_ms


def read(run: dict) -> float | None:
    return scope_ms(run, "jit_serve_step", "layers")
