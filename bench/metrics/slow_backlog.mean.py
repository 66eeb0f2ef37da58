"""Client batches still queued behind each slow-path instance when it is
proposed: the window's growth of the replicas' summed backlog over that of
their slow instances (``slow`` rows of the traced replicas,
``SlowCounters``). Silent where no replica recorded ``slow`` rows, or none
proposed an instance in the window."""

from hostrows import window_rows


def read(run):
    rows = window_rows(run.node_stats, "slow", run.t0, run.t1)
    instances = sum(r[-1, 1] - r[0, 1] for r in rows)
    if not instances:
        return None
    return sum(r[-1, 4] - r[0, 4] for r in rows) / instances
