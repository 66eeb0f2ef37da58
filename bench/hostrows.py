"""The replicas' host samples in a window: the ``loop_lag`` and ``host``
rows a traced replica writes into its ``node-<id>.stats.json``, each
stamped first with the cluster clock (``repro.transport.node_runner``)."""

from __future__ import annotations

import numpy as np


def window_rows(node_stats, key: str, t0: float, t1: float):
    """Each replica's rows of ``key`` whose time lies in [t0, t1), as one
    2-D array per replica; replicas that recorded none in the window are
    left out (an untraced replica, or a program that samples nothing)."""
    out = []
    for s in node_stats:
        rows = np.asarray(s.get(key) or [], dtype=np.float64)
        if rows.size:
            rows = rows[(rows[:, 0] >= t0) & (rows[:, 0] < t1)]
        if len(rows):
            out.append(rows)
    return out
