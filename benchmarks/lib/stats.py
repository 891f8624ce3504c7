"""Percentiles and open-loop lateness: the arithmetic behind the tails.
Pure Python, checked by ``checks/test_yardstick.py``."""

from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0..1) by linear interpolation between order
    statistics (numpy's default): p95 of 1..100 is 95.05."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def percentile_with_misses(values: Sequence[float], q: float,
                            misses: int) -> float:
    """The ``q``-quantile over ``values`` plus ``misses`` requests that never
    answered and so rank above every answer. Where the quantile falls among
    them the longest wait seen stands in: a lower bound, and the run's
    ``failed`` count says so."""
    s = sorted(values)
    pos = q * (len(s) + misses - 1)
    lo = int(pos)
    if lo + 1 >= len(s):
        return s[-1]
    return s[lo] + (s[lo + 1] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def open_loop_latencies(due: Sequence[float], sent: Sequence[float],
                        first: Sequence[float]) -> Dict[str, List[float]]:
    """Per request, on one clock: ``ttft`` counts from when the request was
    DUE, so a stall that delays later sends is charged to the system;
    ``lateness`` is how far behind its schedule the generator sent it (a
    starved generator shows here, not as a fast server)."""
    ttft = [f - d for d, f in zip(due, first)]
    lateness = [max(0.0, s - d) for d, s in zip(due, sent)]
    return {"ttft": ttft, "lateness": lateness}
