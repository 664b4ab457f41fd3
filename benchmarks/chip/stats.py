"""Percentile and rate arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (``0 < q <= 100``): the smallest
    value with at least ``q`` percent of the sample at or below it."""
    ordered = sorted(float(v) for v in values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile q must be in (0, 100], got {q}")
    return ordered[max(1, math.ceil(q / 100 * len(ordered))) - 1]


def rate(count: int, seconds: float) -> float:
    """Events per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds
