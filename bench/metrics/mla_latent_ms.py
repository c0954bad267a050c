"""mla_latent_ms: device time of the MLA decode's latent attention in
one run of the decode step program (``jit_serve_step``), mean over the
runs in the traced window, in ms: the ops whose ``op_name`` path holds
the scope ``latent`` (``models/attention.py:mla_decode``: the query's
absorption, the scores against the latent cache and the rope key, the
mask, the softmax, the latent context and its up-projection).  The
scope lies inside ``attn``, so ``decode_attn_ms`` counts this time too.
None where the trace holds no run of the program or no op in the scope
(a program without it), or is not the run's own."""
from __future__ import annotations

from bench.metrics._events import scoped_events, scoped_op_seconds
from bench.metrics._program import program_seconds

PROGRAM = "jit_serve_step"
SCOPE = "latent"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    events = None if found is None else scoped_events(run)
    if events is None:
        return None
    seconds = scoped_op_seconds(events[1], events[0], PROGRAM, SCOPE)
    return 1e3 * seconds / found[1] if seconds > 0 else None
