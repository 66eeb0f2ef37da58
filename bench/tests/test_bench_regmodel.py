"""The register model that decides ``correct``: recorded served histories,
and agreement with a brute-force search over small random histories."""

import itertools
import json
import random
from pathlib import Path

import pytest

import regmodel

DATA = Path(__file__).resolve().parent / "data"


def _rows(name):
    return [tuple(r) for r in json.loads((DATA / name).read_text())["ops"]]


def test_reorder_twin_history_is_rejected():
    """A history served with the frame-reorder mutation twin (reads on one
    hot object) returns a value rolled back past later writes."""
    bad = regmodel.check_history(_rows("reorder_history.json"))
    assert bad, "the reorder twin's history passed the register model"


def test_clean_history_is_accepted():
    assert regmodel.check_history(_rows("clean_history.json")) == {}


def _brute_force(writes, reads) -> bool:
    """Search every order of the ops for one that respects real time and in
    which each read returns the last write before it."""
    ops = [("w", v, a, b) for v, (a, b) in writes.items()] + \
          [("r", v, a, b) for a, b, v in reads]
    for order in itertools.permutations(range(len(ops))):
        pos = {k: i for i, k in enumerate(order)}
        if any(ops[x][3] < ops[y][2] and pos[x] > pos[y]
               for x in range(len(ops)) for y in range(len(ops))):
            continue
        last, ok = None, True
        for k in order:
            kind, v, _, _ = ops[k]
            if kind == "w":
                last = v
            elif v != last:
                ok = False
                break
        if ok:
            return True
    return False


@pytest.mark.parametrize("seed", range(40))
def test_zone_check_matches_brute_force(seed):
    rng = random.Random(seed)
    writes = {}
    for v in range(rng.randint(1, 3)):
        a = rng.uniform(0, 10)
        writes[100 + v] = (a, a + rng.uniform(0.1, 4))
    reads = []
    for _ in range(rng.randint(1, 3)):
        a = rng.uniform(0, 12)
        v = rng.choice(list(writes) + [None])
        reads.append((a, a + rng.uniform(0.1, 4), v))
    got = regmodel.check_object(writes, reads) is None
    assert got == _brute_force(writes, reads), (writes, reads)


def test_read_of_unwritten_value_and_read_before_write():
    assert regmodel.check_object({1: (0.0, 1.0)}, [(2.0, 3.0, 7)])
    assert regmodel.check_object({1: (5.0, 6.0)}, [(1.0, 2.0, 1)])
    # an unacknowledged write may have taken effect, or not
    assert regmodel.check_object({1: (0.0, regmodel.INF)},
                                 [(2.0, 3.0, 1)]) is None
    assert regmodel.check_object({1: (0.0, 1.0), 2: (2.0, regmodel.INF)},
                                 [(3.0, 4.0, 1)]) is None
