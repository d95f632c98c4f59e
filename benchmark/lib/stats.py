"""Percentile arithmetic, kept with the benchmark so no PR of the program
can change it."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics (numpy's default, written out). NaN for no samples."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
