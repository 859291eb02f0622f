"""Percentile arithmetic: the benchmark's own copy of the exact
(value-retaining, linearly interpolated) percentile the program's
``observability.metrics.percentiles`` computes, so the yardstick does not
change when the program does."""

from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest order statistics (numpy's default); None for no values. A value
    of ``inf`` (a request that missed) sorts last and comes back as inf."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) or math.isinf(xs[lo]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
