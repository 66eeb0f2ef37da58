"""The open-loop generator times each op from its due time and sends on
its schedule whatever is in flight, so a replica that stalls raises the
tail; the capped (closed-loop) client sends only as acks free its slots.
The clients run over the program's wire path into a fake replica, on a
clock the test sets."""

import asyncio

import numpy as np
import pytest

import loadgen
from repro.core.simulator import Msg, Workload
from repro.transport.codec import decode_body, split_frames
from repro.transport.net import NetContext


class Clock(NetContext):
    t = 0.0

    @property
    def now(self):
        return self.t


def _client(inflight=None):
    ctx = Clock(2, 1, epoch=0.0, seed=3)
    client = loadgen.BenchClient(ctx, n=1, batch_size=10, workload=Workload(),
                                 seed=3, inflight=inflight)
    ctx.add_node(client)
    held = []                                  # requests the replica holds

    def replica(data):
        (body,), _ = split_frames(data)
        held.append(decode_body(body).payload)
    ctx.register_peer(0, replica)
    return ctx, client, held


def _answer(ctx, client, held, t):
    """The replica answers every request it holds at time ``t``."""
    ctx.t = t
    for req in held:
        client.on_message(Msg("client_reply", 0, client.node_id, {
            "batch_id": req["batch_id"],
            "op_ids": [op.op_id for op in req["ops"]]}), t)
    held.clear()


def test_open_loop_keeps_sending_and_times_from_due():
    async def drive():
        ctx, client, held = _client()
        for i in range(10):                    # due every 10 ms
            ctx.t = 0.01 * i + 0.002           # sent 2 ms late
            client.send_due(0.01 * i)
            if i < 3:
                _answer(ctx, client, held, ctx.t + 0.001)
        # the replica stalls from 30 ms: nothing acked, sending goes on
        assert len(held) == 7 and client.pending() == 70
        _answer(ctx, client, held, 0.5)
        return client.records(), client.lateness
    rec, late = asyncio.run(drive())
    lat = rec["ack"] - rec["due"]
    np.testing.assert_allclose(lat[:30], 0.003)
    np.testing.assert_allclose(lat[30:40], 0.5 - 0.03)      # from due time
    np.testing.assert_allclose(late, 0.002)
    assert np.quantile(lat, 0.99) > 0.4


def test_acks_do_not_send_in_open_loop():
    async def drive():
        ctx, client, held = _client()
        client.send_due(0.0)
        _answer(ctx, client, held, 0.001)
        return len(client.ops)
    assert asyncio.run(drive()) == 10


def test_capped_client_sends_only_when_an_ack_frees_a_slot():
    async def drive():
        ctx, client, held = _client(inflight=5)
        client.start()
        assert len(held) == 5 and client.pending() == 50
        ctx.t = 0.2                            # the replica stalls
        assert len(held) == 5                  # nothing more is sent
        first = held.pop(0)
        client.on_message(Msg("client_reply", 0, client.node_id, {
            "batch_id": first["batch_id"],
            "op_ids": [op.op_id for op in first["ops"]]}), 0.2)
        assert len(held) == 5                  # one slot freed, one sent
        return client.records()
    rec = asyncio.run(drive())
    assert rec["ack"][0] - rec["due"][0] == pytest.approx(0.2)
    assert rec["due"][-1] == pytest.approx(0.2)  # timed from its send


def test_run_open_sends_every_due_batch():
    import time

    async def drive():
        ctx = NetContext(2, 1, epoch=time.time(), seed=3)
        client = loadgen.BenchClient(ctx, n=1, batch_size=10,
                                     workload=Workload(), seed=3)
        ctx.add_node(client)
        ctx.register_peer(0, lambda data: None)

        class One:
            clients = [client]
            now = property(lambda self: ctx.now)
        t0 = ctx.now
        times = t0 + np.arange(50) / 1000.0
        await loadgen.run_open(One(), [times], t0 + 0.05)
        return client
    client = asyncio.run(drive())
    assert len(client.ops) == 500 and min(client.lateness) >= 0.0


def test_loop_lag_sees_a_task_that_holds_the_loop():
    import time

    class Wall:
        now = property(lambda self: time.monotonic())

    async def drive():
        clock = Wall()
        probe = asyncio.ensure_future(
            loadgen.loop_lag(clock, clock.now + 0.2))
        await asyncio.sleep(0.05)
        time.sleep(0.06)                       # holds the loop 60 ms
        return await probe
    lags = asyncio.run(drive())
    assert lags.size > 5 and lags.max() >= 0.04 and lags.min() >= 0.0


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**33 + 1])
def test_every_seed_offers_the_same_number_of_batches(seed):
    a = loadgen.batch_schedule(seed, 0, 250.0, 10.0, 30.0)
    assert a.size == 5000 and (np.diff(a) >= 0).all()
    assert a[0] >= 10.0 and a[-1] < 30.0
