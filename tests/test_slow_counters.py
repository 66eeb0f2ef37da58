"""The slow path's instance counters (``repro.core.slowpath.SlowCounters``)
on simulated runs at full contention: the leader counts every op it
committed on the slow path, each instance holds at most ``group_cap`` ops,
one client batch, and the mutex's holds never overlap. The counters take
no simulated time and no rng draw; the golden pins of ``test_scenario.py``
hold them to that."""

from collections import Counter

import pytest

from repro.core.simulator import Workload
from repro.core.slowpath import SlowCounters
from repro.scenario import Observability, Scenario, run_scenario


@pytest.mark.parametrize("protocol", ["woc", "cabinet"])
def test_counters_count_the_leaders_slow_instances(protocol):
    sc = Scenario(protocol=protocol, n_replicas=5, total_ops=2000, seed=7,
                  workload=Workload(p_independent=0.0, p_common=0.0,
                                    p_hot=1.0),
                  obs=Observability(trace=True))
    art = run_scenario(sc)
    r = art.result
    assert r.committed_ops == sc.total_ops and r.fast_path_frac == 0.0

    leader, *followers = [rep.slow_counters for rep in art.replicas]
    assert followers == [SlowCounters()] * len(followers)
    assert leader.ops == r.committed_ops
    # every instance the leader proposed, from its traced proposes
    sizes = Counter(ev[3] for ev in r.trace if ev[1] == "slow_propose")
    assert len(sizes) == leader.instances
    assert sum(sizes.values()) == leader.ops
    assert max(sizes.values()) <= art.replicas[0].group_cap
    # simulated runs keep one client batch per instance (the served
    # replicas alone merge queued batches: tests/test_slow_group_commit.py)
    assert art.replicas[0].group_cap == sc.batch_size
    assert max(sizes.values()) <= sc.batch_size
    # one instance at a time: the holds fit inside the run, end to end
    assert 0.0 < leader.hold_s <= art.sim.now
    assert leader.backlog >= 0
