"""Frame bodies the replicas encoded (their ``encodes`` counters, summed:
one per message encoded alone, one per broadcast encoded for all its
destinations) per message they posted (``messages``), over the whole run.
Silent where a replica's stats carry no ``encodes``."""


def read(run):
    stats = run.node_stats
    if not stats or any("encodes" not in s for s in stats):
        return None
    messages = sum(s["messages"] for s in stats)
    if not messages:
        return None
    return sum(s["encodes"] for s in stats) / messages
