"""Arithmetic the metrics share: tails over requests and run spreads."""
from __future__ import annotations

import math
import statistics
from typing import List


def percentile_rank(values: List[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]). ``inf`` entries (a
    request that failed or never finished) rank last, so a missing request
    can only raise a tail."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
