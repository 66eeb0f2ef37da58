"""The reader of the transport's write counter: frames written per socket
write on hand-made replica stats, and silent (None) where the channels
count no writes, as a program without the counter does."""

import numpy as np
import pytest

import run


def _run(node_stats=()):
    return run.Run(t0=10.0, t1=20.0, setup_s=1.0, due=np.zeros(1),
                   ack=np.ones(1), path=np.array(["fast"]), acks=np.ones(1),
                   acked_total=1, node_stats=list(node_stats))


def _chan(dst, sent, writes=None):
    c = {"dst": dst, "sent": sent, "dropped": 0}
    if writes is not None:
        c["writes"] = writes
    return c


@pytest.mark.parametrize("stats, want", [
    # one frame per write on every link
    ([{"node": 0, "channels": [_chan(1, 40, 40), _chan(2, 40, 40)]}], 1.0),
    # a leader flushing long turns and a follower flushing short ones:
    # (300 + 300 + 90) frames over (100 + 120 + 80) writes
    ([{"node": 0, "channels": [_chan(1, 300, 100), _chan(2, 300, 120)]},
      {"node": 1, "channels": [_chan(0, 90, 80)]}], 690 / 300),
])
def test_reader_on_hand_made_stats(stats, want):
    assert run.metric_reader("frames_per_write")(_run(stats)) == \
        pytest.approx(want)


@pytest.mark.parametrize("stats", [
    [],                                                    # no stats
    [{"node": 0, "channels": [_chan(1, 40)]}],             # parent program
    [{"node": 0, "channels": [_chan(1, 40, 10)]},          # one replica
     {"node": 1, "channels": [_chan(0, 40)]}],             # without it
    [{"node": 0, "channels": []}],                         # no channels
    [{"node": 0, "channels": [_chan(1, 0, 0)]}],           # nothing written
], ids=["nothing", "parent_program", "partly", "no_channels",
        "no_writes"])
def test_reader_is_silent_without_the_counter(stats):
    assert run.metric_reader("frames_per_write")(_run(stats)) is None
