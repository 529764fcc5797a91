"""Percentile and rate arithmetic shared by every cell."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least ``p``
    percent of the samples at or below it.  None for no samples."""
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def rate(count: float, seconds: float) -> float:
    """Work per second over a window; the window must have a length."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s has no length")
    return count / seconds

