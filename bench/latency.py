"""The end-to-end arithmetic: latency percentiles and completed rate.

A request that was shed, failed or never answered has no latency: it counts
as +inf, so it lands at the top of every percentile. A rate is all the
work completed inside the window over the whole window.
"""

from __future__ import annotations

import math
from collections.abc import Sequence


def percentile(latencies: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of all requests,
    +inf included: the smallest value with at least ``q``% at or below it."""
    if not latencies:
        raise ValueError("no requests to take a percentile of")
    ordered = sorted(latencies)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def completed_rate(done_at: Sequence[float], start: float,
                   end: float) -> float:
    """Requests completed in ``[start, end)`` per second of that window."""
    if end <= start:
        raise ValueError("empty window")
    return sum(start <= t < end for t in done_at) / (end - start)
