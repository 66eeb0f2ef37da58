"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests call its
phase functions directly, with the quorum kernel forced into Pallas
interpret mode here (on the chip it compiles).
"""

import functools
import importlib.util
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def short_run(smoke):
    return smoke.deployment_phase(total_ops=2000)


@pytest.fixture
def interpret_kernel(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "quorum_commit", functools.partial(
        ops.quorum_commit, force_pallas=True, interpret=True))


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_deployment_commits_every_op(short_run):
    r = short_run.result
    assert r.committed_ops == 2000
    assert len(r.history) == 2000
    assert any(ev[1] == "fast_accept" for ev in r.trace)


def test_quorum_phase_matches_numpy_in_interpret_mode(smoke, short_run,
                                                      interpret_kernel):
    info = smoke.quorum_phase(short_run)
    fast = sum(1 for ev in short_run.result.trace if ev[1] == "fast_propose")
    assert info["instances"] == fast
    assert info["tick_batches"] == -(-fast // smoke.TICK_BATCH)
    assert 0 < info["committed"] <= fast
    assert not info["tpu_custom_call"]          # interpreted, not compiled
    assert info["host_fast_commits_reproduced"] <= info["host_fast_commits"]


def test_vote_matrix_rows_follow_trace(smoke):
    """Self-vote at propose time, accepts by batch, +inf elsewhere;
    weights follow the coordinator's last ema ranking."""
    base = np.array([4.0, 2.0, 1.0], np.float32)
    trace = [
        (0.0, "fast_propose", 1, 7, 100),        # before any ema span
        (0.5, "ema", 1, 0, 0.3),
        (0.5, "ema", 1, 2, 0.1),
        (1.0, "fast_accept", 1, 7, 2, 0),
        (1.0, "fast_commit", 1, 7, 100),
        (2.0, "fast_propose", 1, 9, 101),
        (3.0, "fast_accept", 1, 9, 0, 1),
    ]
    arr, w, stamps = smoke.vote_matrix(trace, 3, base)
    np.testing.assert_array_equal(arr, [[np.inf, 0.0, 1.0],
                                        [3.0, 2.0, np.inf]])
    np.testing.assert_array_equal(w, [[2.0, 4.0, 1.0],     # self, then ids
                                      [1.0, 4.0, 2.0]])    # self, 2, 0
    np.testing.assert_array_equal(stamps, [1.0, np.inf])


def test_numpy_quorum_strict_crossing_and_ties(smoke):
    arrivals = np.array([[1.0, 1.0, np.inf, 2.0],      # tie at t=1
                         [1.0, 2.0, 3.0, 4.0],         # exactly half: no
                         [np.inf] * 4], np.float32)
    weights = np.array([[1.0, 3.0, 1.0, 1.0],
                        [1.0, 1.0, 1.0, 1.0],
                        [1.0, 1.0, 1.0, 1.0]], np.float32)
    commit_t, qsize, committed = smoke.numpy_quorum(arrivals, weights)
    np.testing.assert_array_equal(committed, [True, True, False])
    np.testing.assert_array_equal(qsize, [2, 3, 0])
    np.testing.assert_array_equal(commit_t, [1.0, 3.0, np.inf])


def test_weights_phase_small_table(smoke):
    info = smoke.weights_phase(r=1.8, num_objects=4096)
    assert info["objects_checked"] == 4096


def test_served_phase_no_child_starts_jax(smoke):
    info = smoke.served_phase(total_ops=240)
    assert info["linearizable"]
    assert info["processes_without_jax_backend"] == 7
