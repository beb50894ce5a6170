"""The statistics the readings are made with."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all the values (numpy's default)."""
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)
