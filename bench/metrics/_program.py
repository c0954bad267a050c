"""Helpers the per-layer readers share: device time of one jitted
program, and the device's idle share, from the reduced trace."""
from __future__ import annotations


def program_seconds(run: dict, name: str) -> tuple[float, int] | None:
    """Device seconds and runs of the program ``name`` in the traced
    window, or None where the trace holds no run of it."""
    trace = run["trace"]
    entry = trace["programs"].get(name) if trace else None
    if not entry or entry[1] < 1:
        return None
    return entry[0], round(entry[1])


def idle_percent(run: dict) -> float | None:
    """Share of the traced window in which no operation ran on a chip."""
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0 or trace["chips"] < 1:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
