"""Statistics the benchmark uses, in one place."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear interpolation; infinities (lines
    that never settled) sort last and come out as infinity."""
    a = np.sort(np.asarray(values, np.float64))
    if a.size == 0:
        raise ValueError("percentile of nothing")
    pos = (a.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, a.size - 1)
    if np.isinf(a[hi]):
        return float(a[hi] if pos > lo else a[lo])
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def histogram_quantile(buckets: dict, q: float):
    """Quantile of a cumulative histogram ``{upper_bound: count}`` (the
    program's log2 buckets): the upper bound of the bucket that holds it.
    None when the histogram is empty."""
    items = sorted((float(b), c) for b, c in buckets.items()
                   if b not in ("+Inf", "inf"))
    total = max((c for _, c in items), default=0)
    if total <= 0:
        return None
    want = q * total
    for bound, count in items:
        if count >= want:
            return bound
    return items[-1][0]
