"""Mean wait of the window's slow-path commits in the leader's queue
(enqueue to the proposal of their instance), from the program's
critical-path analysis of the replicas' merged spans."""


def read(run):
    r = run.report
    if r is None or not r.slow.count:
        return None
    return r.slow.queue_s / r.slow.count * 1e3
