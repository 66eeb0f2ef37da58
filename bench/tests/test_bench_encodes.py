"""The reader of the transport's encode counter: the ratio of frame bodies
encoded to messages posted on hand-made replica stats, and silent (None)
where the program counts no encodes, as a program without the counter
does."""

import numpy as np
import pytest

import run


def _run(node_stats=()):
    return run.Run(t0=10.0, t1=20.0, setup_s=1.0, due=np.zeros(1),
                   ack=np.ones(1), path=np.array(["fast"]), acks=np.ones(1),
                   acked_total=1, node_stats=list(node_stats))


@pytest.mark.parametrize("stats, want", [
    # every message encoded alone
    ([{"node": 0, "messages": 40, "encodes": 40}], 1.0),
    # a leader's 8-way broadcasts and a follower's single replies:
    # (15 + 30) bodies over (120 + 30) messages
    ([{"node": 0, "messages": 120, "encodes": 15},
      {"node": 1, "messages": 30, "encodes": 30}], 45 / 150),
])
def test_reader_on_hand_made_stats(stats, want):
    assert run.metric_reader("encodes_per_msg")(_run(stats)) == \
        pytest.approx(want)


@pytest.mark.parametrize("stats", [
    [],                                                    # no stats
    [{"node": 0, "messages": 5, "channels": []}],          # parent program
    [{"node": 0, "messages": 5, "encodes": 2},             # one replica
     {"node": 1, "messages": 5, "channels": []}],          # without it
    [{"node": 0, "messages": 0, "encodes": 0}],            # nothing posted
], ids=["nothing", "parent_program", "partly", "no_messages"])
def test_reader_is_silent_without_the_counter(stats):
    assert run.metric_reader("encodes_per_msg")(_run(stats)) is None
