"""The traced run's device operations with their ``op_name`` paths
(``bench/trace_scopes.read_scoped_events``), read once per run from the
trace that ``bench/run.py``'s tracer leaves in ``.bench_trace`` beside
``bench/``, and the device time of the ops of one named scope inside
runs of one program."""
from __future__ import annotations

import bisect

from bench.trace_reduce import CONTAINERS, _clip, find_xplane, program_name
from bench.trace_scopes import read_scoped_events

TRACE_DIR = ".bench_trace"
WINDOW = "traced_window"


def scoped_events(run: dict):
    """``(window, chips)`` of :func:`read_scoped_events`, or None where
    the directory holds no trace or not the run's own."""
    if "scoped_events" not in run:
        run["scoped_events"] = None
        try:
            path = find_xplane(run["found"]["bench"].parent / TRACE_DIR)
        except FileNotFoundError:
            return None
        window, chips = read_scoped_events(path, WINDOW)
        # the trace on disk is this run's when its window is the one reduced
        if window is not None and abs(window[1] - window[0]
                                      - run["trace"]["window_s"]) <= 1e-6:
            run["scoped_events"] = (window, chips)
    return run["scoped_events"]


def scoped_op_seconds(chips, window: tuple[float, float], program: str,
                      scope: str) -> float:
    """Device seconds of the ops with ``scope`` among the components of
    their ``op_name`` path (before the op's own name), inside runs of
    ``program``, clipped to ``window``, mean over chips; the ``while``,
    ``conditional`` and ``call`` ops that hold others are left out."""
    lo, hi = window
    total = 0.0
    for chip in chips.values():
        runs = sorted((s, e, program_name(n)) for n, s, e in chip["modules"])
        starts = [s for s, _, _ in runs]
        for name, path, s, e in chip["ops"]:
            if (name.startswith(CONTAINERS) or not path
                    or scope not in path.split("/")[:-1]):
                continue
            k = bisect.bisect_right(starts, s) - 1
            iv = _clip(s, e, lo, hi)
            if iv and k >= 0 and runs[k][1] >= e and runs[k][2] == program:
                total += iv[1] - iv[0]
    return total / max(len(chips), 1)
