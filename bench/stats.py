"""Statistics the end-to-end metrics are taken with.

A tail is taken over every sample of the window, never over a subset
or a per-batch summary.
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q < 100) of all ``values``, by the
    nearest-rank rule: the smallest value with at least q% of the
    samples at or below it.  Raises ``ValueError`` on no samples."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < q < 100:
        raise ValueError(f"percentile must lie in (0, 100), got {q}")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return float(ordered[max(rank, 1) - 1])
