"""Observability contracts: zero-overhead tracing, byte-determinism,
serial<->parallel span equality, exact path mix under sampling.

The pinned contracts of the observability PR:

  * tracing OFF is the default and costs one attribute read per hook —
    every golden pin in tests/test_scenario.py runs with it off;
  * tracing ON never changes simulated time: a traced run's result is
    bit-identical to the untraced run (minus the trace itself);
  * same seed + schedule => byte-identical trace export;
  * parallel sharded workers ship truncated traces that canonicalize to
    EXACTLY the serial oracle's span set;
  * the critical-path analyzer's ``fast_frac`` is computed from the
    always-recorded commit stamps, so it equals the engine's
    ``fast_path_frac`` exactly — even with per-op span sampling on.
"""

import dataclasses
import json

import pytest

from repro.obs import (MetricsRegistry, Tracer, analyze_events,
                       canonical_events, chrome_trace_json, export_trace,
                       metrics_from_trace, to_chrome_trace,
                       validate_chrome_trace)
from repro.obs.spans import MappedTracer
from repro.scenario import Observability, Scenario, Sharding, run_scenario
from repro.shard import non_telemetry_metrics

# wall-clock-only fields; "trace" differs by construction (off => [])
_TELEMETRY = {"events_per_sec", "wall_s", "trace"}


def _metrics(result):
    d = dataclasses.asdict(result)
    for k in _TELEMETRY:
        d.pop(k, None)
    return d


def _flat(trace=True, sample_every=1, **kw):
    obs = Observability(trace=True, sample_every=sample_every) \
        if trace else None
    kw.setdefault("protocol", "woc")
    kw.setdefault("total_ops", 2000)
    kw.setdefault("batch_size", 10)
    kw.setdefault("seed", 3)
    return run_scenario(Scenario(obs=obs, **kw))


def _sharded(workers, trace=True):
    return run_scenario(Scenario(
        protocol="woc", n_replicas=3, total_ops=2000, batch_size=10,
        seed=5,
        sharding=Sharding(n_groups=2, locality="drift", working_set=8,
                          p_working=0.9, steal_threshold=2,
                          workers=workers),
        obs=Observability(trace=True) if trace else None)).result


# ---------------------------------------------------------------------------
# Zero overhead in simulated time
# ---------------------------------------------------------------------------

def test_tracing_on_is_bit_identical_to_tracing_off_flat():
    off = _flat(trace=False)
    on = _flat(trace=True)
    assert _metrics(off.result) == _metrics(on.result)
    assert off.result.trace == []
    assert len(on.result.trace) > 0


def test_tracing_on_is_bit_identical_sharded_serial():
    off = _sharded(workers=1, trace=False)
    on = _sharded(workers=1, trace=True)
    assert non_telemetry_metrics(off) == non_telemetry_metrics(on)
    assert off.trace == [] and len(on.trace) > 0


# ---------------------------------------------------------------------------
# Byte-deterministic export
# ---------------------------------------------------------------------------

def test_same_seed_exports_byte_identical_trace():
    a = _flat().result.trace
    b = _flat().result.trace
    assert a == b
    for fmt in ("chrome", "jsonl"):
        assert export_trace(a, fmt) == export_trace(b, fmt)


def test_chrome_trace_validates_and_reconstructs_commit_latency():
    art = _flat()
    doc = json.loads(chrome_trace_json(art.result.trace))
    assert validate_chrome_trace(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == art.result.committed_ops
    # span durations are the engine's own commit latencies (us of sim
    # time): their mean must agree with the pinned latency average
    avg_ms = sum(s["dur"] for s in spans) / len(spans) / 1e3
    assert avg_ms == pytest.approx(art.result.latency_avg_ms, rel=1e-9)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"displayTimeUnit": "ms"})
    with pytest.raises(ValueError, match="ph invalid"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "Z", "ts": 0, "pid": 0, "tid": 0}]})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0, "pid": 0, "tid": 0}]})


# ---------------------------------------------------------------------------
# Serial <-> parallel sharded span equality
# ---------------------------------------------------------------------------

def test_parallel_sharded_trace_equals_serial_oracle():
    serial = _sharded(workers=1)
    parallel = _sharded(workers=2)
    assert non_telemetry_metrics(serial) == non_telemetry_metrics(parallel)
    assert serial.trace == parallel.trace
    assert len(serial.trace) > 0
    assert serial.commit_log_residual == parallel.commit_log_residual == 0
    # every node id in the merged trace lives in the GLOBAL namespace:
    # replica ids cover both groups' blocks (0..5), not one group's 0..2
    nodes = {e[2] for e in serial.trace if e[1] == "commit"}
    assert max(nodes) >= 3


# ---------------------------------------------------------------------------
# Exact path mix, with and without per-op sampling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sample_every", [1, 4])
def test_critical_path_fast_frac_matches_engine_exactly(sample_every):
    r = _flat(sample_every=sample_every).result
    rep = analyze_events(r.trace)
    assert rep.committed == r.committed_ops
    assert rep.fast_frac == r.fast_path_frac          # exact, not approx
    if sample_every > 1:
        assert 0 < rep.analyzed < rep.committed       # sampling engaged
    else:
        assert rep.analyzed == rep.committed
    # the additive decomposition covers each path's total by construction
    for bd in (rep.fast, rep.slow):
        if bd.count:
            parts = (bd.ingress_s + bd.coord_s + bd.queue_s
                     + bd.quorum_link_s + bd.straggler_s + bd.dep_stall_s
                     + bd.other_s)
            assert parts == pytest.approx(bd.total_s, rel=1e-9)


def test_analyze_window_partitions_commits():
    r = _flat().result
    full = analyze_events(r.trace)
    mid = r.makespan_s / 2
    lo = analyze_events(r.trace, window=(0.0, mid))
    hi = analyze_events(r.trace, window=(mid, float("inf")))
    assert lo.committed + hi.committed == full.committed
    assert lo.fast_committed + hi.fast_committed == full.fast_committed


# A served trace by hand: a fast round (batch 7, proposer 0) whose votes
# from replicas 1 and 2 arrive before its decision and whose vote from 3
# arrives after it, and a slow round (instance 9, leader 1) whose one vote
# reads its proposal before it was sent (a clock fault).
_SERVED = [
    (0.9980, "ingress", 0, 100, 5, 0.9975, 20),
    (1.0000, "fast_propose", 0, 7, 100),
    (1.0010, "vote", 1, "fast", 7, 0, 1.0012),
    (1.0015, "vote", 2, "fast", 7, 0, 1.0016),
    (1.0020, "fast_accept", 0, 7, 1, 0),
    (1.0030, "fast_accept", 0, 7, 2, 1),
    (1.0030, "fast_commit", 0, 7, 100),
    (1.0030, "commit", 0, 100, "fast"),
    (1.0040, "vote", 3, "fast", 7, 0, 1.0041),
    (1.0050, "fast_accept", 0, 7, 3, 0),
    (1.9900, "ingress", 1, 200, 6, 1.9890, 21),
    (1.9950, "slow_enqueue", 1, 200),
    (1.9000, "vote", 2, "slow", 9, 1, 1.9001),
    (2.0000, "slow_propose", 1, 9, 200),
    (2.0020, "slow_accept", 1, 9, 2, 0.7),
    (2.0020, "slow_commit", 1, 9, 200),
    (2.0020, "commit", 1, 200, "slow"),
]


def test_critical_path_splits_counted_votes_into_legs():
    events = canonical_events(_SERVED)
    rep = analyze_events(events)
    legs = rep.votes
    # the vote from 3 came after the decision: not counted
    assert legs.count == 3
    assert legs.out_s == pytest.approx(0.0010 + 0.0015 - 0.1000)
    assert legs.service_s == pytest.approx(0.0002 + 0.0001 + 0.0001)
    assert legs.back_s == pytest.approx(0.0008 + 0.0014 + 0.1019)
    assert legs.clock_faults == 1
    assert rep.to_dict()["votes"] == dataclasses.asdict(legs)
    # the vote spans change no other field of the report
    bare = analyze_events([e for e in events if e[1] != "vote"])
    assert bare.votes.count == 0
    with_votes = rep.to_dict()
    with_votes.pop("votes")
    without = bare.to_dict()
    without.pop("votes")
    assert with_votes == without
    # a window that leaves the fast commit out leaves its votes out too
    assert analyze_events(events, window=(1.5, 3.0)).votes.count == 1


# ---------------------------------------------------------------------------
# commit_log release (satellite: unbounded growth fix)
# ---------------------------------------------------------------------------

def test_commit_log_cleared_and_residual_exposed():
    art = _flat(trace=False)
    assert art.result.commit_log_residual == 0
    assert len(art.sim.commit_log) == 0               # released at run end


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_labels_and_canonical_dict():
    reg = MetricsRegistry()
    reg.counter("ops", path="fast").inc()
    reg.counter("ops", path="fast").inc(2)
    reg.counter("ops", path="slow").inc()
    reg.gauge("w", node=1).set(0.5)
    h = reg.histogram("lat")
    h.observe(2e-6)
    h.observe(1.5e-6)
    d = reg.to_dict()
    assert d["counters"] == {"ops{path=fast}": 3.0, "ops{path=slow}": 1.0}
    assert d["gauges"] == {"w{node=1}": 0.5}
    assert d["histograms"]["lat"]["count"] == 2
    assert d["histograms"]["lat"]["sum"] == pytest.approx(3.5e-6)


def test_metrics_from_trace_path_mix_matches_engine():
    r = _flat().result
    d = metrics_from_trace(r.trace,
                           commit_log_residual=r.commit_log_residual
                           ).to_dict()
    fast = d["counters"].get("ops_committed_total{path=fast}", 0)
    slow = d["counters"].get("ops_committed_total{path=slow}", 0)
    assert fast + slow == r.committed_ops
    assert fast / (fast + slow) == r.fast_path_frac
    assert d["counters"]["commit_log_residual"] == 0
    assert d["histograms"]["quorum_wait_s{path=fast}"]["count"] > 0


# ---------------------------------------------------------------------------
# Span primitives
# ---------------------------------------------------------------------------

def test_tracer_sampling_is_deterministic_pure_hash():
    a = Tracer(sample_every=4)
    b = Tracer(sample_every=4)
    picks = [op for op in range(1000) if a.sampled(op)]
    assert picks == [op for op in range(1000) if b.sampled(op)]
    assert 0 < len(picks) < 1000
    assert Tracer(sample_every=1).sampled(12345)


def test_mapped_tracer_translates_node_and_replica_args():
    root = Tracer()
    mt = MappedTracer(root, lambda n: n + 10 if n < 3 else n)
    mt.ev("fast_accept", 1.0, 1, 7, 2, 1)     # src arg (idx 1) is local
    mt.ev("ingress", 2.0, 0, 42, 9, 1.5, 100)  # client id untouched
    mt.ev("vote", 3.0, 1, "fast", 7, 2, 3.5)   # proposer arg (idx 2)
    assert root.events[0] == (1.0, "fast_accept", 11, 7, 12, 1)
    assert root.events[1] == (2.0, "ingress", 10, 42, 9, 1.5, 100)
    assert root.events[2] == (3.0, "vote", 11, "fast", 7, 12, 3.5)


def test_canonical_events_dedupes_commits_keeping_earliest():
    evs = [(2.0, "commit", 1, 7, "slow"), (1.0, "commit", 0, 7, "fast"),
           (0.5, "ingress", 0, 7, 3, 0.4, 9)]
    out = canonical_events(evs)
    assert out == [(0.5, "ingress", 0, 7, 3, 0.4, 9),
                   (1.0, "commit", 0, 7, "fast")]


def test_chrome_trace_skips_unsampled_ops():
    # commit stamp without ingress (op sampled out) draws no X span
    doc = to_chrome_trace([(1.0, "commit", 0, 7, "fast")])
    assert [e["ph"] for e in doc["traceEvents"]] == ["i"]


# ---------------------------------------------------------------------------
# Scenario spec integration
# ---------------------------------------------------------------------------

def test_obs_round_trips_through_dict_and_json():
    sc = Scenario(obs=Observability(trace=True, sample_every=8,
                                    export="/tmp/t.json",
                                    export_format="jsonl"))
    assert Scenario.from_dict(sc.to_dict()) == sc
    assert Scenario.from_json(sc.to_json()) == sc
    # default stays None (and serializes as such)
    assert Scenario().to_dict()["obs"] is None
    assert Scenario.from_dict({"protocol": "woc"}).obs is None


def test_obs_validation():
    with pytest.raises(ValueError, match="export requires"):
        Scenario(obs=Observability(export="/tmp/t.json"))
    with pytest.raises(ValueError, match="sample_every"):
        Scenario(obs=Observability(trace=True, sample_every=0))
    with pytest.raises(ValueError, match="export_format"):
        Scenario(obs=Observability(trace=True, export="/tmp/t.json",
                                   export_format="protobuf"))


def test_scenario_export_writes_loadable_chrome_trace(tmp_path):
    path = tmp_path / "trace.json"
    run_scenario(Scenario(protocol="woc", total_ops=400, batch_size=10,
                          seed=3,
                          obs=Observability(trace=True,
                                            export=str(path))))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc)
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
