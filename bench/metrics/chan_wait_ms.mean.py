"""Mean wait of a frame in a replica's outbound channel queue, from being
queued to being written to the socket: the window's growth of the
replicas' summed queue wait over that of their frames written (``host``
rows of the traced replicas)."""

from hostrows import window_rows


def read(run):
    rows = window_rows(run.node_stats, "host", run.t0, run.t1)
    frames = sum(r[-1, 2] - r[0, 2] for r in rows)
    if not frames:
        return None
    return sum(r[-1, 3] - r[0, 3] for r in rows) / frames * 1e3
