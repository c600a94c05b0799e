"""Percentiles, linear between closest ranks (numpy's default definition)."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """q-th percentile (0..100) of ``values``; NaN when there are none."""
    s = sorted(values)
    if not s:
        return float("nan")
    pos = (len(s) - 1) * (q / 100.0)
    lo, hi = math.floor(pos), math.ceil(pos)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))
