"""The lookup the per-scope readers share: device time of one named
scope of one jitted program, per run of the program, in ms.

The split comes from the traced run's profiler trace, which
``bench/run.py``'s tracer leaves in ``.bench_trace`` beside ``bench/``,
reduced by ``bench/trace_scopes.py`` once per run.  A reader returns
None where the trace holds no run of the program, or no time in the
scope (a program built without the scope)."""
from __future__ import annotations

from bench.metrics._program import program_seconds
from bench.trace_scopes import scope_seconds

TRACE_DIR = ".bench_trace"
WINDOW = "traced_window"


def _split(run: dict) -> dict | None:
    found = scope_seconds(run["found"]["bench"].parent / TRACE_DIR, WINDOW)
    if found is None:
        return None
    window_s, split = found
    # the trace on disk is this run's when its window is the one reduced
    if abs(window_s - run["trace"]["window_s"]) > 1e-6:
        return None
    return split


def scope_ms(run: dict, program: str, scope: str) -> float | None:
    runs = program_seconds(run, program)
    if runs is None:
        return None
    if "scopes" not in run:
        run["scopes"] = _split(run)
    seconds = (run["scopes"] or {}).get(program, {}).get(scope, 0.0)
    return 1e3 * seconds / runs[1] if seconds > 0 else None
