"""Percentile arithmetic, kept with the benchmark so that no change
to ``serve/metrics.py`` or ``obs`` can move a reported tail."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0..100), linear interpolation between closest
    ranks (numpy's default).  None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values) -> float | None:
    return percentile(values, 50.0)
