"""Frames the replicas' outbound channels wrote (their ``sent`` counters,
summed) per socket write (``writes``: one per flush, which writes every
frame queued in a loop turn), over every channel of every replica and the
whole run. Silent where a channel's stats carry no ``writes``."""


def read(run):
    chans = [c for s in run.node_stats for c in s.get("channels", ())]
    if not chans or any("writes" not in c for c in chans):
        return None
    writes = sum(c["writes"] for c in chans)
    if not writes:
        return None
    return sum(c["sent"] for c in chans) / writes
