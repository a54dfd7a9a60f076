"""Percentiles and spreads, as the benchmark's contract defines them."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100) of the sample; None when
    the sample is empty. A tail needs samples beyond it: ``supports`` says
    whether this sample has ten."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def supports(n: int, q: float, beyond: int = 10) -> bool:
    """Does a sample of n have at least ``beyond`` values above its q-th
    percentile? (choosing-metrics: report the highest percentile that has
    ten samples beyond it)."""
    return n * (100.0 - q) / 100.0 >= beyond


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return abs(q3 - q1) / abs(med) if med else None
