"""The readers of the served path's spans and host samples: each on a
synthetic run, cut to the window [t0, t1), and silent (None) where the
program recorded nothing, as a program without these spans and counters
does."""

import types

import numpy as np
import pytest

import run
from repro.obs.critical_path import VoteLegs

T0, T1 = 10.0, 20.0


def _run(node_stats=(), report=None):
    return run.Run(t0=T0, t1=T1, setup_s=1.0, due=np.zeros(1),
                   ack=np.ones(1), path=np.array(["fast"]), acks=np.ones(1),
                   acked_total=1, node_stats=list(node_stats), report=report)


def _host_stats():
    """Two replicas; rows at t0 - 1 and at t1 lie outside the window and
    would change every reading if they were counted."""
    lag0 = ([[T0 - 0.01, 5.0]] + [[T0 + 0.05 * i, 0.001] for i in range(99)]
            + [[T1 - 0.01, 0.2], [T1, 5.0]])
    lag1 = [[T0 + 0.05 * i, 0.002] for i in range(100)]
    host0 = [[T0 - 1.0, 100.0, 0, 0.0], [T0, 101.0, 1000, 1.0],
             [15.0, 103.0, 2000, 2.0], [19.9, 105.0, 3000, 4.0],
             [T1, 200.0, 9999, 99.0]]
    host1 = [[10.5, 50.0, 500, 0.5], [19.5, 51.0, 1500, 1.5]]
    return [{"node": 0, "loop_lag": lag0, "host": host0},
            {"node": 1, "loop_lag": lag1, "host": host1}]


LEGS = VoteLegs(count=4, out_s=0.004, service_s=0.0008, back_s=0.002)


@pytest.mark.parametrize("name, fake, want", [
    # (out + back) over both legs of each of 4 votes
    ("vote_wire_ms.mean", _run(report=types.SimpleNamespace(votes=LEGS)),
     0.75),
    ("vote_service_ms.mean", _run(report=types.SimpleNamespace(votes=LEGS)),
     0.2),
    # (3 s + 1 s of queue wait) over (2000 + 1000) frames written
    ("chan_wait_ms.mean", _run(_host_stats()), 4.0 / 3000 * 1e3),
    # 200 pooled samples: 99 of 1 ms, 100 of 2 ms, one of 200 ms
    ("loop_lag_ms.p99", _run(_host_stats()), 2.0),
    # replica 0: 4 s of CPU over 9.9 s; replica 1: 1 s over 9 s
    ("replica_cpu_share.max", _run(_host_stats()), 4.0 / 9.9),
])
def test_reader_on_a_synthetic_run(name, fake, want):
    assert run.metric_reader(name)(fake) == pytest.approx(want)


def _stats_outside_window():
    return [{"node": 0, "loop_lag": [[T1 + 1.0, 0.1]],
             "host": [[T0 - 2.0, 1.0, 0, 0.0], [T1, 2.0, 10, 1.0]]}]


def _one_row_each():
    return [{"node": 0, "loop_lag": [], "host": [[12.0, 1.0, 5, 0.1]]},
            {"node": 1, "loop_lag": [], "host": [[13.0, 1.0, 5, 0.1]]}]


@pytest.mark.parametrize("name", ["vote_wire_ms.mean", "vote_service_ms.mean",
                                  "chan_wait_ms.mean", "loop_lag_ms.p99",
                                  "replica_cpu_share.max"])
@pytest.mark.parametrize("fake", [
    _run(),                                        # no report, no stats
    # a program without these spans and samples: its report has no vote
    # legs and its replicas' stats no host rows
    _run([{"node": 0, "messages": 5, "channels": []}],
         types.SimpleNamespace(fast=None, slow=None)),
    _run(_stats_outside_window(), types.SimpleNamespace(votes=VoteLegs())),
    _run(_one_row_each()),
], ids=["nothing", "parent_program", "outside_window", "one_row_each"])
def test_reader_is_silent_without_data(name, fake):
    assert run.metric_reader(name)(fake) is None
