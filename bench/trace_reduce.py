"""Reduce a profiler trace of a ``--trace 1`` run to the numbers the
per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes
under ``<dir>/plugins/profile/<time>/``, read with
``jax.profiler.ProfileData``.  Its planes:

- ``/device:TPU:<n>``: one per chip.  The line ``XLA Modules`` holds one
  event per run of a jitted program, named after it
  (``jit_serve_step(<id>)``); the line ``XLA Ops`` one event per device
  operation, named by its HLO text.
- ``/host:CPU``: one line per host thread; the benchmark's spans
  (``jax.profiler.TraceAnnotation``) are events on the Python thread's
  line, on the same clock as the device's events.

The traced window is the host span named by ``window``.  Within it:
``busy_s`` is the length of the union of the intervals in which an
operation ran on a chip, averaged over the chips; ``programs`` the
device time and count of each jitted program; ``op_events`` the count
of operation events; ``ops`` the device time of each operation by its
HLO name, largest first, leaving out the ``while``, ``conditional`` and
``call`` ops that hold other ops; and ``idle_gaps`` the idle device
time in each host span, largest first: each stretch with no operation
running goes to the innermost benchmark span that covers its middle,
or to ``outside_spans``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
CONTAINERS = ("while", "conditional", "call")


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def program_name(event_name: str) -> str:
    """``jit_serve_step(1234)`` -> ``jit_serve_step``."""
    return _PROGRAM_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction text:
    ``%fusion.12 = bf16[8,2048] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


class SpanIndex:
    """The host spans, to ask which one the host was in at a time."""

    def __init__(self, spans) -> None:
        self.spans = sorted((s, e, n) for n, s, e in spans)
        self.starts = [s for s, _, _ in self.spans]

    def innermost(self, t: float) -> str:
        """The latest-starting span that covers ``t``."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s, e, n = self.spans[i]
            if e >= t:
                return n
            i -= 1
        return "outside_spans"


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_stretches(intervals: list[tuple[float, float]], lo: float,
                   hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def read_events(path: Path, window: str, span_names):
    """Host spans and per-chip device events of the trace at ``path``,
    in seconds on the trace's clock."""
    import jax

    names = set(span_names) | {window}
    spans: list[tuple[str, float, float]] = []
    chips: dict[str, dict[str, list]] = {}
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(ev.name, ev.start_ns * 1e-9,
                                         ev.end_ns * 1e-9)
                                        for ev in line.events]
            if lines:
                chips[plane.name] = lines
    return spans, chips


def reduce_events(spans, chips, window: str) -> dict:
    """The reduction of :func:`read_events`' output (see module doc)."""
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins:
        raise ValueError(f"no span {window!r} in the trace")
    lo, hi = wins[0]
    index = SpanIndex([sp for sp in spans if sp[0] != window])
    busy_total = 0.0
    programs: dict[str, list] = defaultdict(lambda: [0.0, 0])
    ops: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    n_ops = 0
    for lines in chips.values():
        busy = []
        for name, s, e in lines.get(OPS_LINE, []):
            iv = _clip(s, e, lo, hi)
            if iv:
                busy.append(iv)
                n_ops += 1
                name = op_name(name)
                # a while, conditional or call op spans the ops inside it
                if not name.startswith(CONTAINERS):
                    ops[name] += iv[1] - iv[0]
        for name, s, e in lines.get(MODULES_LINE, []):
            iv = _clip(s, e, lo, hi)
            if iv:
                p = programs[program_name(name)]
                p[0] += iv[1] - iv[0]
                p[1] += 1
        busy_total += union_length(busy)
        for s, e in idle_stretches(busy, lo, hi):
            gaps[index.innermost(0.5 * (s + e))] += e - s
    n = max(len(chips), 1)
    return {
        "window_s": hi - lo,
        "busy_s": busy_total / n,
        "chips": len(chips),
        "op_events": n_ops,
        "programs": {k: [v[0] / n, v[1] / n] for k, v in programs.items()},
        "ops": [[k, v / n] for k, v in
                sorted(ops.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
    }


def reduce_trace(directory: Path, window: str, span_names) -> dict:
    """Reduce the trace written under ``directory``."""
    spans, chips = read_events(find_xplane(directory), window, span_names)
    return reduce_events(spans, chips, window)
