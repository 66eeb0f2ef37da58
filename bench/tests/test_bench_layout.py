"""The harness finds a cell's configuration, traffic mix and metrics by
name, so a new cell is new files and new entries only; and the command
refuses to run without the chip."""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import run

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _digest(paths):
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in paths if p.is_file()}


def add_cell(tmp_path: Path) -> Path:
    """A copy of the benchmark with cells made of new files only: a
    configuration, two traffic mixes and a per-layer metric. ``w3.test``
    writes the paper's mix; ``w3.hot`` puts 30 % of its writes on the hot
    objects, where the reorder control bites at this small size."""
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics"):
        (root / "bench" / sub).mkdir()
        for f in (BENCH / sub).iterdir():
            if f.is_file():
                (root / "bench" / sub / f.name).write_bytes(f.read_bytes())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "woc9_paper.json").read_text())
    cfg["n_replicas"], cfg["t_fail"] = 3, 1
    (root / "bench" / "configs" / "woc3_small.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "mix90.cap.json").read_text())
    del traffic["inflight_batches"]
    traffic.update(arrival="open", rate_ops_s=2000, warmup_s=0.5,
                   readback_independent=100)
    (root / "bench" / "traffic" / "mix90.slow.json").write_text(
        json.dumps(traffic))
    traffic.update(p_hot=0.3, p_common=0.1)
    (root / "bench" / "traffic" / "hot30.slow.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "ops_in_window.py").write_text(
        "def read(run):\n    return float(len(run.due))\n")
    spec["configs"].append({"name": "woc3_small", "source": "a test",
                            "file": "bench/configs/woc3_small.json",
                            "reduced": ["n_replicas"], "why": "a test"})
    spec["workloads"].append({"name": "w3.test", "config": "woc3_small",
                              "traffic": "mix90.slow", "chips": 1,
                              "why": "a test"})
    spec["workloads"].append({"name": "w3.hot", "config": "woc3_small",
                              "traffic": "hot30.slow", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "ops_in_window", "unit": "ops",
                              "better": "higher", "source": "host_clock",
                              "layer": "routing", "moves": "commit_p99_ms",
                              "workloads": ["w3.test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_cell_of_new_files_loads_without_editing_any(tmp_path):
    before = _digest(list(BENCH.rglob("*")) + [ROOT / "BENCHMARK.json"])
    root = add_cell(tmp_path)
    cell = run.load_cell("w3.test", root)
    assert cell["config"]["n_replicas"] == 3
    assert cell["traffic"]["rate_ops_s"] == 2000
    assert cell["end_to_end"] == ["commit_p99_ms", "throughput_ops", "setup_s"]
    assert cell["per_layer"] == ["ops_in_window"]
    fake = run.Run(t0=0.0, t1=1.0, setup_s=1.0, due=np.zeros(7),
                   ack=np.ones(7), path=np.array(["fast"] * 7),
                   acks=np.ones(7), acked_total=7)
    assert run.metric_reader("ops_in_window", root)(fake) == 7.0
    assert run.metric_reader("commit_p99_ms", root)(fake) == 1000.0
    # the existing cells load as before
    old, new = run.load_cell("w9.mix90.cap"), run.load_cell(
        "w9.mix90.cap", root)
    assert new.pop("units").items() >= old.pop("units").items()
    assert new == old
    assert _digest(list(BENCH.rglob("*"))
                   + [ROOT / "BENCHMARK.json"]) == before


def test_every_cell_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        for name in cell["end_to_end"] + cell["per_layer"]:
            assert (BENCH / "metrics" / f"{name}.py").is_file(), name
        assert cell["end_to_end"][-1] == "setup_s" or \
            "setup_s" in cell["end_to_end"]


def test_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "w9.mix90.cap", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        run.load_cell("no.such.cell")
