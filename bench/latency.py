"""Exact latency and rate arithmetic over every request of a window."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``: the
    smallest value with at least ``q`` percent of the values at or below
    it. A failed request enters as ``math.inf``, beyond every limit."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100]: {q}")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return ordered[max(rank, 1) - 1]


def beyond(values, limit: float) -> int:
    """How many values lie above ``limit`` (the samples a tail rests on)."""
    return sum(1 for v in values if v > limit)
