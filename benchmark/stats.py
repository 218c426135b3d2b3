"""Sample arithmetic of the benchmark: percentiles, trimmed mean and the
spread the bounds are set from. Pure Python."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence

INF = float("inf")


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default). +inf samples (failed requests) sort
    last, so a tail that reaches them is +inf. None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return xs[lo]
    if math.isinf(xs[hi]):
        return INF
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def trimmed_mean(values: Iterable[float], trim: float = 0.1) -> Optional[float]:
    """Mean of what is left after dropping floor(trim * n) samples at each
    end (the 'mid80' of the issue for trim=0.1)."""
    xs = sorted(values)
    if not xs:
        return None
    k = int(math.floor(trim * len(xs)))
    kept = xs[k:len(xs) - k] or xs
    if any(math.isinf(x) for x in kept):
        return INF
    return sum(kept) / len(kept)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, the way the
    builder's contract measures it (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
