"""The arithmetic behind every reported number: percentiles and rates."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default method), ``None``
    for no samples — a reader that finds nothing reports nothing."""
    if not len(values):
        return None
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def rate_per_chip(records: int, window_s: float, chips: int) -> float:
    """Records applied in the window over ALL of its seconds, per chip."""
    if window_s <= 0 or chips < 1:
        raise ValueError(f"window_s={window_s}, chips={chips}")
    return records / window_s / chips
