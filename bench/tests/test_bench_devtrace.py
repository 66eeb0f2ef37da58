"""The reduction from a profiler trace to device busy time, time per
device operation and named idle gaps."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.load", 0, 1000), _ev("bench.fold", 1000, 500),
        _ev("other", 0, 5000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_x", 0, 5000)]),
        NS(name="XLA Ops", events=[
            _ev("fusion", 1100, 100), _ev("kernel", 1150, 100),
            _ev("kernel", 1300, 50), _ev("late", 1490, 100)])])
    return [host, dev]


def test_busy_is_the_union_of_op_intervals_inside_the_phases():
    r = devtrace.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(1500e-9)
    # [1100, 1250) + [1300, 1350) + [1490, 1500)
    assert r["busy_s"] == pytest.approx(210e-9)
    ops = dict(r["device_ops"])
    assert ops["kernel"] == pytest.approx(150e-9)
    assert ops["late"] == pytest.approx(10e-9)        # clipped to the span
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.load", pytest.approx(1000e-9)]
    assert sum(g for _, g in gaps) + r["busy_s"] == pytest.approx(1500e-9)
    assert {name for name, _ in gaps} == {"bench.load", "bench.fold"}


def test_each_phase_has_its_own_busy_time_and_span():
    r = devtrace.reduce_planes(_planes())
    assert r["phases"]["bench.load"] == [0.0, pytest.approx(1000e-9)]
    assert r["phases"]["bench.fold"] == [pytest.approx(210e-9),
                                         pytest.approx(500e-9)]


def test_device_idle_reads_the_load_phase_alone():
    """The fold after the window is the harness's work, not the
    program's: it leaves ``device_idle`` at 1 while no program path calls
    the device during the load, and device work inside the load lowers it."""
    import run
    read = run.metric_reader("device_idle")
    r = devtrace.reduce_planes(_planes())
    assert read(NS(device=r)) == 1.0
    host, dev = _planes()
    dev.lines[1].events.append(_ev("program_op", 200, 250))
    assert read(NS(device=devtrace.reduce_planes([host, dev]))) == \
        pytest.approx(0.75)
    assert read(NS(device=None)) is None


def test_a_trace_without_phases_or_device_is_refused():
    host, dev = _planes()
    with pytest.raises(ValueError, match="phase"):
        devtrace.reduce_planes([dev])
    with pytest.raises(ValueError, match="device"):
        devtrace.reduce_planes([host])


def test_recorded_tpu_trace():
    """A profiler trace recorded on one TPU v5e: three chunks of the fold
    (20 000 instances x 5 replicas) between a 0.2 s ``bench.load`` and a
    0.05 s ``bench.stop``. Its ``/device:CUSTOM`` plane is not a chip."""
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(DATA / "tpu_fold.xplane.pb")).planes
    r = devtrace.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(0.261165994)
    assert r["busy_s"] == pytest.approx(10.2e-6)
    name, seconds = r["device_ops"][0]
    assert "quorum_commit_pallas" in name and "tpu_custom_call" in name
    assert seconds == pytest.approx(5.81e-6)
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(10.2e-6)
    assert r["idle_gaps"][0] == ["bench.load", pytest.approx(0.199023263)]
    assert r["idle_gaps"][1] == ["bench.stop", pytest.approx(0.050158715)]
    busy, span = r["phases"]["bench.load"]
    assert busy == pytest.approx(3.401e-6) and span == pytest.approx(0.20106128)
