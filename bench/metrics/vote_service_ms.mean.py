"""Mean time a responder spends in its handler of a proposal (start to
end; the accept is posted inside it), over the votes the window's quorums
waited on, from the program's critical-path analysis (``vote`` spans)."""


def read(run):
    legs = getattr(run.report, "votes", None)
    if legs is None or not legs.count:
        return None
    return legs.service_s / legs.count * 1e3
