"""Share of the window's acknowledged ops that the replicas report as
committed on the fast path (the commit path tag on each ack)."""

import numpy as np


def read(run):
    acked = np.isfinite(run.ack)
    if not acked.any():
        return None
    return float(np.count_nonzero(run.path[acked] == "fast")) \
        / float(np.count_nonzero(acked))
