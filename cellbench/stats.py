"""The few statistics the benchmark reports, each over all its samples."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile. ``+inf`` samples (requests that failed or
    never ended) are ordinary samples that sort last, so enough of them
    make the tail infinite: a miss is never dropped."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between first and third quartile as a share of the median
    (``statistics.quantiles(n=4)``): the spread bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
