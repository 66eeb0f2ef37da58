"""Mean quorum wait of the window's committed ops with a full span, fast
and slow path together: propose to first accept (quorum link) plus first
accept to the deciding one (straggler), from the program's critical-path
analysis of the replicas' merged spans."""


def read(run):
    r = run.report
    if r is None:
        return None
    count = r.fast.count + r.slow.count
    if not count:
        return None
    total = (r.fast.quorum_link_s + r.fast.straggler_s
             + r.slow.quorum_link_s + r.slow.straggler_s)
    return total / count * 1e3
