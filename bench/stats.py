"""Window arithmetic: rates over elapsed time, and percentiles."""
from __future__ import annotations

import statistics


def rate(count: int, start: float, last: float) -> float:
    """``count`` answers over the time from the window's start to the
    last answer."""
    if count < 1 or last <= start:
        raise ValueError("a rate needs at least one answer after the start")
    return count / (last - start)


def percentile(values, q: int) -> float:
    """The ``q``-th percentile (1..99) by ``statistics.quantiles``'
    exclusive method; needs two values or more."""
    if len(values) < 2:
        raise ValueError("a percentile needs two values or more")
    return statistics.quantiles(values, n=100)[q - 1]

