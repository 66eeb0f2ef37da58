"""Mean wire leg of the votes the window's quorums waited on: the
outbound leg (proposal to the responder's handler) and the return leg
(the responder's handler end to the coordinator handling the accept),
averaged over both legs of every counted vote, from the program's
critical-path analysis of the replicas' merged spans (``vote`` spans)."""


def read(run):
    legs = getattr(run.report, "votes", None)
    if legs is None or not legs.count:
        return None
    return (legs.out_s + legs.back_s) / (2 * legs.count) * 1e3
