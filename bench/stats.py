"""Percentiles with their sample counts.

A percentile is nearest-rank: the p-th percentile of n samples is the
ceil(p * n)-th smallest, so `beyond` = n - ceil(p * n) samples lie above
it. A missing sample (a request that never produced its first token in the
window) counts as +inf: it misses every limit.
"""
from __future__ import annotations

import math


def percentile(samples, p: float) -> dict:
    """{'value', 'n', 'beyond'}; value is None for no samples."""
    xs = sorted(samples)
    n = len(xs)
    if n == 0:
        return {"value": None, "n": 0, "beyond": 0}
    rank = max(1, math.ceil(p * n))
    return {"value": xs[rank - 1], "n": n, "beyond": n - rank}
