"""``correct`` on whole runs at a small size on the CPU: a clean run of a
cell made of new files passes, and the control (the program's frame-reorder
mutation twin) and each planted fault make it false. Everything but the
look for the chip runs as on the chip: the replicas through the program's
launcher, the benchmark's clients, the read-back and the register model."""

import subprocess
import time
from pathlib import Path

import pytest

import run
from test_bench_layout import add_cell

PLANT = Path(__file__).resolve().parent / "plant.py"
CELL = "w3.test"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return add_cell(tmp_path_factory.mktemp("cell"))


def _run(root, seed, cell=CELL, **kw):
    return run.run_once(cell, seed, 1.5, False, require_tpu=False,
                        root=root, t_start=time.time(),
                        log=lambda *a, **k: None, **kw)


def test_clean_run_is_correct_and_reports_its_metrics(root):
    result, r = _run(root, 2**31 + 11)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"commit_p99_ms", "throughput_ops",
                                      "setup_s"}
    assert result["attempted"] > 2000 and result["failed"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert list(result)[-1] == "checks"


def test_reorder_control_is_not_correct(root):
    """The twin rolls a follower's copy back only where a displaced commit
    was the object's last write; at this size a cell whose mix puts a
    third of its writes on the hot objects keeps that likely (two seeds in
    three on the CPU), so a few seeds are tried."""
    results = []
    for seed in range(21, 29):
        result, _ = _run(root, seed, cell="w3.hot", reorder=True)
        results.append(result["checks"])
        if not result["correct"]:
            return
    pytest.fail(f"the reorder twin passed on every seed: {results}")


@pytest.fixture
def planted(monkeypatch):
    """Start every replica through plant.py with the given fault."""
    real = subprocess.Popen

    def use(fault):
        def popen(cmd, *a, **kw):
            if "repro.transport.node_runner" in cmd:
                i = cmd.index("-m")
                cmd = cmd[:i] + [str(PLANT), fault] + cmd[i + 2:]
            return real(cmd, *a, **kw)
        monkeypatch.setattr(subprocess, "Popen", popen)
        monkeypatch.setattr(run, "DRAIN_S", 3.0)
    return use


@pytest.mark.parametrize("fault, caught_by", [
    ("state_unchanged", "stale_objects"),
    ("half_batch", "stale_objects"),
    ("no_exchange", "stale_objects"),
    ("altered_answer", "stale_objects"),
])
def test_each_planted_fault_is_not_correct(root, planted, fault, caught_by):
    planted(fault)
    result, _ = _run(root, 31)
    assert not result["correct"]
    assert result["checks"][caught_by]["value"] > 0, result["checks"]
