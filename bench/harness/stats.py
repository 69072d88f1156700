"""Arithmetic of the metrics: over every sample of the window, never over
medians of chunks."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile of all samples (linear interpolation between
    order statistics); None when there is no sample."""
    v = np.asarray(list(values), dtype=np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def mean(values) -> float | None:
    v = np.asarray(list(values), dtype=np.float64)
    return float(v.mean()) if v.size else None


def rate(amount: float, seconds: float) -> float | None:
    """Amount per second over the whole window."""
    return amount / seconds if seconds > 0 else None


def union_seconds(intervals) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
