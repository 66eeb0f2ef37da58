"""99th percentile, nearest rank, of how late the replicas' event loops
woke a 10 ms sleep in the window, pooled over the replicas (``loop_lag``
rows of the traced replicas)."""

import numpy as np

from hostrows import window_rows
from winstats import quantile


def read(run):
    rows = window_rows(run.node_stats, "loop_lag", run.t0, run.t1)
    if not rows:
        return None
    return quantile(np.concatenate([r[:, 1] for r in rows]), 0.99) * 1e3
