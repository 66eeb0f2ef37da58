"""Window arithmetic shared by the metric readers: quantiles and rates."""

from __future__ import annotations

import math

import numpy as np


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the ceil(q * n)-th smallest value. Values may
    be +inf (an op never acknowledged counts as missing every limit), so
    a tail never drops a request."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("quantile of no values")
    k = max(1, math.ceil(q * v.size))
    return float(v[k - 1])


def window_rate(times, t0: float, t1: float) -> float:
    """Events per second whose time lies in [t0, t1)."""
    t = np.asarray(times, dtype=np.float64)
    return float(np.count_nonzero((t >= t0) & (t < t1))) / (t1 - t0)
