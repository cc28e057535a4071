"""Percentiles over all samples (no binning, no sampling)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile of every value, linearly interpolated between
    order statistics (numpy's default rule).  Raises on no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"q must be in [0, 100], got {q}")
    r = q / 100 * (len(xs) - 1)
    lo = math.floor(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)
