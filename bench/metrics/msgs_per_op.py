"""Messages the replicas posted (their ``messages`` counters, summed:
protocol messages, client replies and heartbeats) per op acknowledged in
the whole run."""


def read(run):
    if not run.node_stats or not run.acked_total:
        return None
    return sum(s["messages"] for s in run.node_stats) / run.acked_total
