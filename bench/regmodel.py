"""The plain reference: a sequential read/write register per object.

A served history is correct when every object's operations can be put in
one sequence that respects real time (an operation that finished before
another started comes first) and in which every read returns the value of
the last write before it, or ``None`` where there was none. Write values are
unique (the client writes each op's id, masked by the seed), so each read
names the one write it saw.

For such histories the check is the zone test of Gibbons and Korach
("Testing shared memories", SIAM J. Comput. 1997): group each write with the
reads that returned its value (a *cluster*); with ``f`` the earliest
response and ``s`` the latest invocation in a cluster, the cluster's zone
is *forward* ``(f, s)`` where ``f < s`` and *backward* ``(s, f)`` otherwise.
The history is linearizable iff no read returns a value whose write began
after the read ended, no two forward zones overlap, and no backward zone
lies inside a forward zone. That is O(n log n) per object, so every
acknowledged write of every object read back is checked.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import bisect
import math
from typing import Dict, Iterable, List, Optional, Tuple

INF = math.inf

# (invoke, response) of one write; response is +inf for a write never
# acknowledged, which may or may not have taken effect
Interval = Tuple[float, float]
# (invoke, response, value returned)
Read = Tuple[float, float, Optional[int]]


def check_object(writes: Dict[int, Interval], reads: Iterable[Read]
                 ) -> Optional[str]:
    """None if one object's history fits the register model, else why not."""
    clusters: Dict[object, List[float]] = {}      # value -> [s, f]
    for value, (inv, resp) in writes.items():
        clusters[value] = [inv, resp]
    read_seen = set()
    for inv, resp, value in reads:
        if value is None:
            c = clusters.setdefault(None, [-INF, -INF])   # the initial value
        else:
            c = clusters.get(value)
            if c is None:
                return f"a read returned {value!r}, which no client wrote"
            if resp < writes[value][0]:
                return f"a read returned {value!r} before its write began"
        read_seen.add(value)
        c[0] = max(c[0], inv)
        c[1] = min(c[1], resp)
    forward, backward = [], []
    for value, (s, f) in clusters.items():
        if f == INF and value not in read_seen:
            continue          # never acknowledged and never read: may be void
        (forward if f < s else backward).append((min(s, f), max(s, f)))
    forward.sort()
    for (a0, b0), (a1, _) in zip(forward, forward[1:]):
        if a1 < b0:
            return (f"two writes were each the last one seen by a read in "
                    f"overlapping spans ({a0:.6f}, {b0:.6f}) and from "
                    f"{a1:.6f}")
    starts = [a for a, _ in forward]
    for s, f in backward:
        i = bisect.bisect_left(starts, s) - 1
        if i >= 0 and forward[i][1] > f:
            return (f"a write in ({s:.6f}, {f:.6f}) was overwritten unseen: "
                    f"a later read returned an older value")
    return None


def check_history(ops: Iterable[tuple]) -> Dict[int, str]:
    """Check every object of a history of ``(obj, kind, value, invoke,
    response)`` rows, where ``kind`` is "w" or "r" and a read's ``value``
    is what it returned. Returns {obj: why} for each object that fails."""
    writes: Dict[int, Dict[int, Interval]] = {}
    reads: Dict[int, List[Read]] = {}
    for obj, kind, value, inv, resp in ops:
        if kind == "w":
            writes.setdefault(obj, {})[value] = (inv, resp)
        else:
            reads.setdefault(obj, []).append((inv, resp, value))
    bad = {}
    for obj, rs in reads.items():
        why = check_object(writes.get(obj, {}), rs)
        if why is not None:
            bad[obj] = why
    return bad
