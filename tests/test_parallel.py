"""Serial <-> parallel sharded-simulation equivalence (PR 3 tentpole).

The contract: ``run_sharded`` with ``workers>=2`` (per-group event
engines over worker processes, conservative time-window sync) produces
**bit-identical** ``ShardedRunResult`` metrics to ``workers=1`` (the
single-heap serial oracle) — every field except the wall-clock telemetry
in ``TELEMETRY_FIELDS``. This holds because simulated timing is a pure
function of per-link message history (per-link jitter sequences, FIFO
floors, per-node busy-until), not of how engines' events interleave in
one heap; see repro/shard/parallel.py for the full argument.

Runs here are sized small: the point is schedule equivalence across
locality modes and active object stealing, not load.
"""

import pytest

from repro.shard import (ShardedRunConfig, lookahead_of,
                         non_telemetry_metrics as _metrics, run_sharded)
from repro.core.simulator import CostModel


def _pair(**kw):
    serial = run_sharded(ShardedRunConfig(**kw, workers=1))
    parallel = run_sharded(ShardedRunConfig(**kw, workers=2))
    return serial, parallel


@pytest.mark.parametrize("n_groups", [2, 4])
@pytest.mark.parametrize("locality", ["uniform", "mixed", "drift"])
def test_parallel_matches_serial_bit_identical(n_groups, locality):
    serial, parallel = _pair(
        n_groups=n_groups, n_replicas_per_group=3, total_ops=1200,
        batch_size=10, locality=locality, seed=3)
    assert _metrics(serial.result) == _metrics(parallel.result)
    assert parallel.result.workers == 2
    assert parallel.result.barriers > 0


def test_parallel_matches_serial_reference_group_size():
    """The G=4 reference geometry (5 replicas per group, stealing
    enabled, drift locality — the hardest of the three modes): acceptance
    configuration of the PR 3 tentpole."""
    serial, parallel = _pair(
        n_groups=4, n_replicas_per_group=5, n_clients_per_group=2,
        total_ops=2000, batch_size=10, locality="drift",
        steal_threshold=3, seed=3)
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_parallel_matches_serial_with_active_stealing():
    """Stealing-heavy drift workload: fences, drains, grants, installs and
    fenced-op replays all cross engine boundaries mid-run."""
    serial, parallel = _pair(
        n_groups=2, n_replicas_per_group=3, total_ops=2500, batch_size=10,
        locality="drift", working_set=8, p_working=0.9, steal_threshold=2,
        seed=5)
    assert serial.result.migrations >= 1, "workload must exercise stealing"
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_parallel_matches_serial_sparse_traffic():
    """Sparse regression (code-review finding): with one client per group
    and small batches the event heaps go idle between batches, so window
    bounds computed from heap tops alone would let an early-arriving
    boundary message's consequences cross back within the same window —
    a causality violation that diverged `messages` before the bound also
    counted in-flight arrivals. EventEngine.inject now hard-fails on any
    delivery into an engine's past."""
    serial, parallel = _pair(
        n_groups=2, n_replicas_per_group=3, n_clients_per_group=1,
        total_ops=400, batch_size=5, locality="uniform",
        steal_threshold=0, seed=3)
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_workers_exceeding_groups_degenerate():
    """workers > G clamps to one engine per group and stays bit-identical
    (worker count may never affect simulated behaviour)."""
    cfg = dict(n_groups=2, n_replicas_per_group=3, total_ops=1200,
               batch_size=10, locality="mixed", seed=3)
    serial = run_sharded(ShardedRunConfig(**cfg, workers=1))
    parallel = run_sharded(ShardedRunConfig(**cfg, workers=6))
    assert parallel.result.workers == 2          # clamped to n_groups
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_workers_never_fork_a_process_holding_jax(monkeypatch):
    """The caller may hold the chip: a jax backend does not survive
    fork, and a forked child would inherit the device client. Workers
    must start without forking the calling process."""
    import os

    import jax
    jax.devices()                     # the caller holds a backend

    def refuse_fork():
        raise AssertionError("parallel runner forked its caller")

    monkeypatch.setattr(os, "fork", refuse_fork)
    cfg = dict(n_groups=2, n_replicas_per_group=3, total_ops=600,
               batch_size=10, locality="mixed", seed=5)
    parallel = run_sharded(ShardedRunConfig(**cfg, workers=2))
    assert parallel.result.workers == 2
    monkeypatch.undo()
    serial = run_sharded(ShardedRunConfig(**cfg, workers=1))
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_workers_auto_and_g1_fall_back_to_serial():
    """G=1 has nothing to parallelize: any workers value runs the serial
    engine (artifacts keep live sim/replica state)."""
    art = run_sharded(ShardedRunConfig(
        n_groups=1, n_replicas_per_group=3, total_ops=600, batch_size=10,
        seed=2, workers=4))
    assert art.result.workers == 1
    assert art.sim is not None and art.clients


def test_parallel_run_is_reproducible():
    """Same seed, same workers => identical result across parallel runs
    (barrier routing and injection order are deterministic)."""
    cfg = dict(n_groups=4, n_replicas_per_group=3, total_ops=1200,
               batch_size=10, locality="drift", seed=7)
    a = run_sharded(ShardedRunConfig(**cfg, workers=2))
    b = run_sharded(ShardedRunConfig(**cfg, workers=2))
    assert _metrics(a.result) == _metrics(b.result)


def test_lookahead_is_min_cross_group_delay():
    c = CostModel()
    la = lookahead_of(c)
    assert la == min(c.net_base + c.net_cross,
                     c.net_client + c.net_remote_client)
    assert la > 0
    # stealing disabled: replica<->replica never crosses groups, so the
    # window widens to the client WAN hop
    assert lookahead_of(c, allow_steal=False) \
        == c.net_client + c.net_remote_client
    # adversarial cost models shrink but never zero the window
    tight = CostModel(net_client=1e-6, net_base=2e-3)
    assert lookahead_of(tight) > 0


def test_lookahead_is_zero_byte_conservative():
    """PDES safety pin for the payload-size axis (repro.coding): the
    per-byte cost terms (c_byte_wire x size_bytes, bandwidth serialization)
    only ADD delay on top of a message's base latency — a zero-byte
    (metadata-only) message pays none of them. The conservative window
    must therefore remain the zero-byte minimum: a cost model with byte
    terms configured yields EXACTLY the same lookahead as one without,
    anything larger could admit a small cross-group frame early."""
    plain = CostModel()
    heavy = CostModel(c_byte_wire=2e-9, c_byte_parse=1e-9,
                      link_bw=(1.0, 10.0))
    assert lookahead_of(heavy) == lookahead_of(plain)
    assert lookahead_of(heavy, allow_steal=False) \
        == lookahead_of(plain, allow_steal=False)
    # and it is still the documented closed form of the base terms only
    assert lookahead_of(heavy) == min(heavy.net_base + heavy.net_cross,
                                      heavy.net_client
                                      + heavy.net_remote_client)


def test_parallel_matches_serial_mixed_value_sizes():
    """Serial <-> parallel bit-identity with the value-size workload axis
    and per-byte costs live: big frames serialize onto links and charge
    wire/parse time, yet every boundary message still respects the
    zero-byte lookahead, so window sync stays conservative. (The Coding
    knob itself is serial-only by validation; what must hold here is
    that SIZED traffic — the data-heavy regime coding decides over —
    cannot break the parallel contract.)"""
    from repro.scenario import ValueSizesWorkload
    wl = ValueSizesWorkload(size_dist="bimodal", size_small=256,
                            size_large=1 << 20, p_large=0.15)
    serial, parallel = _pair(
        n_groups=2, n_replicas_per_group=3, total_ops=1200, batch_size=10,
        locality="mixed", seed=11, workload=wl,
        costs=CostModel(c_byte_wire=4e-10, c_byte_parse=2e-10,
                        link_bw=(1.0, 1.5, 2.0)))
    assert serial.result.makespan_s > 0
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_parallel_matches_serial_stealing_disabled_wide_window():
    """steal_threshold=0 runs with the wider client-WAN lookahead; the
    contract must hold there too (fewer, larger windows)."""
    serial, parallel = _pair(
        n_groups=2, n_replicas_per_group=3, total_ops=1200, batch_size=10,
        locality="mixed", steal_threshold=0, seed=3)
    assert _metrics(serial.result) == _metrics(parallel.result)


def test_parallel_telemetry_populated():
    art = run_sharded(ShardedRunConfig(
        n_groups=2, n_replicas_per_group=3, total_ops=1200, batch_size=10,
        locality="uniform", seed=3, workers=2))
    r = art.result
    assert r.barriers > 0
    assert 0.0 <= r.idle_wait_frac <= 1.0
    assert len(r.per_engine) == 2
    for es in r.per_engine:
        assert es.events > 0
        assert es.wall_s >= 0.0
        assert es.messages > 0
