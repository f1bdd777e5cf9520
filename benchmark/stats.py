"""Percentile and spread arithmetic, the benchmark's own.

``ServingMetrics`` keeps sampled reservoirs and per-request means; no
end-to-end number is read from them.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float, misses: int = 0) -> float:
    """The ``q``-th percentile (0..100, linear interpolation between
    order statistics) of ``values`` plus ``misses`` samples of +inf: a
    request that failed missed every limit. ``inf`` when the percentile
    falls among the misses; ``nan`` without samples."""
    xs = sorted(float(v) for v in values) + [math.inf] * int(misses)
    if not xs:
        return math.nan
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if xs[hi] == math.inf:
        return math.inf if hi != lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the driver reads a bound against."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
