"""Order statistics of the benchmark's samples. Every sample counts: a
percentile is of all requests of the window, never of a trimmed set."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``p``
    percent of all samples at or below it."""
    if not len(values):
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError("p must be in (0, 100]")
    s = sorted(float(v) for v in values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def median(values: Sequence[float]) -> float:
    return percentile(values, 50)
