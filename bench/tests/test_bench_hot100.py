"""The full-contention cell ``w5.hot100.cap``: the repo's benchmark loads it
with its slow-path metrics, a short run of it on the CPU is correct with
every acknowledged op on the slow path, the control is caught on it, and
its readers read the ``slow`` rows of the replicas' stats, cut to the
window, and are silent (None) where the program records none, as a
program without the slow path's counters does."""

import time

import numpy as np
import pytest

import run

CELL = "w5.hot100.cap"
SLOW_METRICS = ["slow_group_ops.mean", "slow_hold_ms.mean",
                "slow_backlog.mean"]
T0, T1 = 10.0, 20.0


def _cpu_run(seed, **kw):
    return run.run_once(CELL, seed, 1.5, False, require_tpu=False,
                        t_start=time.time(), log=lambda *a, **k: None, **kw)


def test_the_cell_loads_with_its_slow_path_metrics():
    cell = run.load_cell(CELL)
    assert cell["config"]["n_replicas"] == 5
    assert cell["traffic"]["p_hot"] == 1.0
    assert cell["end_to_end"] == ["commit_p99_ms", "throughput_ops",
                                  "setup_s"]
    assert cell["per_layer"] == SLOW_METRICS
    assert not set(SLOW_METRICS) & set(run.load_cell("w9.mix90.cap")
                                       ["per_layer"])


def test_a_short_run_is_correct_and_all_slow():
    result, r = _cpu_run(2**31 + 16)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["failed"] == 0
    acked = np.isfinite(r.ack)
    assert acked.all() and acked.sum() > 1000
    assert set(r.path[acked]) == {"slow"}


def test_reorder_control_is_not_correct():
    results = []
    for seed in range(41, 45):
        result, _ = _cpu_run(seed, reorder=True)
        results.append(result["checks"])
        if not result["correct"]:
            return
    pytest.fail(f"the reorder twin passed on every seed: {results}")


def _run(node_stats=()):
    return run.Run(t0=T0, t1=T1, setup_s=1.0, due=np.zeros(1),
                   ack=np.ones(1), path=np.array(["slow"]), acks=np.ones(1),
                   acked_total=1, node_stats=list(node_stats))


def _slow_stats():
    """The leader and a follower; the leader's rows at t0 - 1 and at t1
    lie outside the window and would change every reading if counted."""
    leader = [[T0 - 1.0, 0, 0, 0.0, 0], [T0, 100, 1000, 0.5, 300],
              [15.0, 300, 3000, 1.5, 1100], [19.9, 500, 5000, 2.5, 1900],
              [T1, 9000, 90000, 99.0, 50000]]
    follower = [[10.5, 0, 0, 0.0, 0], [19.5, 0, 0, 0.0, 0]]
    return [{"node": 0, "slow": leader}, {"node": 1, "slow": follower}]


@pytest.mark.parametrize("name, want", [
    # 4000 ops over 400 instances in the window
    ("slow_group_ops.mean", 10.0),
    # 2.0 s of mutex hold over 400 instances
    ("slow_hold_ms.mean", 5.0),
    # 1600 batches left queued over 400 proposes
    ("slow_backlog.mean", 4.0),
])
def test_reader_on_a_synthetic_run(name, want):
    assert run.metric_reader(name)(_run(_slow_stats())) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", SLOW_METRICS)
@pytest.mark.parametrize("stats", [
    [],                                                    # no stats
    [{"node": 0, "host": [[T0, 1.0, 0, 0.0]]}],            # parent program
    [{"node": 0, "slow": [[T0 + 1, 7, 70, 0.1, 3],         # no instance
                          [T0 + 2, 7, 70, 0.1, 3]]}],      # in the window
    [{"node": 0, "slow": [[T1 + 1, 0, 0, 0.0, 0]]}],       # outside it
], ids=["nothing", "parent_program", "idle", "outside"])
def test_reader_is_silent_without_slow_rows(name, stats):
    assert run.metric_reader(name)(_run(stats)) is None
