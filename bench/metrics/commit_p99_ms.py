"""99th percentile commit latency of all ops due in the window: ack
receipt minus due time, on the client's clock; an op never acked counts
as +inf."""

from winstats import quantile


def read(run):
    v = quantile(run.ack - run.due, 0.99) * 1e3
    return v if v != float("inf") else None
