"""Host spans of the benchmark's own calls into the program.

Each span is kept in memory as ``(name, start, end)`` on the host's
``perf_counter`` clock.  While the profiler runs, the same span is also
a ``jax.profiler.TraceAnnotation``, so the trace shows what the host was
doing beside the device's timeline and ``trace_reduce`` can attribute
idle gaps to it.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator


class Spans:
    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> tuple[float, int]:
        """Seconds spent in spans called ``name`` and their count."""
        durations = [t1 - t0 for n, t0, t1 in self.records if n == name]
        return sum(durations), len(durations)
