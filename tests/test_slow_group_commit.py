"""Group commit on the served slow path (``repro.core.slowpath``): a served
leader merges every whole batch queued behind its mutex into the next
instance, in queue order, up to the largest instance whose SLOW_PROPOSE and
SLOW_COMMIT frames fit under the transport's frame limit
(``codec.slow_instance_fits``). The leader runs over the served engine with
stub senders, no sockets, on a clock it is handed (every peer's heartbeat
fresh at 0)."""

import asyncio
import time

import pytest

from repro.core.simulator import Msg, Op
from repro.transport import codec
from repro.transport.net import NetContext
from repro.transport.node_runner import served_replica

N = 5
BATCH = 10


def _batch(b):
    return [Op(100 * b + i, 9, i % 4, "w", 100 * b + i) for i in range(BATCH)]


def _drive(protocol, n_queued):
    """Replica 0 proposes batch 0, ``n_queued`` more batches queue behind
    its mutex, then every follower accepts every instance it is sent until
    the queue is empty. The leader's slow counters, and each frame it sent
    to replica 1: (kind, payload, frame length)."""
    wire = []

    async def go():
        ctx = NetContext(0, N, epoch=time.time())
        rep = served_replica(protocol, 0, ctx, t_fail=1)
        ctx.add_node(rep)
        ctx.register_peer(1, wire.append)
        for j in range(2, N):
            ctx.register_peer(j, lambda frame: None)
        rep._enqueue_slow(_batch(0), 0.0)
        assert rep.slow_mutex
        for b in range(1, n_queued + 1):
            rep._enqueue_slow(_batch(b), 0.0)
        while rep.slow_inst is not None:
            inst = rep.slow_inst.inst_id
            for j in range(1, N):
                rep.on_message(Msg("slow_accept", j, 0, {"inst": inst}), 0.0)
        assert not rep.slow_queue and not rep.slow_mutex
        return rep.slow_counters

    counters = asyncio.run(go())
    frames = []
    for frame in wire:
        (body,), rest = codec.split_frames(frame)
        assert rest == b""
        msg = codec.decode_body(body)
        frames.append((msg.kind, msg.payload, len(frame)))
    return counters, frames


def _instances(frames, kind):
    return [[op.op_id for op in p["ops"]] for k, p, _ in frames if k == kind]


@pytest.mark.parametrize("protocol", ["woc", "cabinet"])
def test_served_leader_merges_every_queued_batch(protocol):
    """Three batches queued behind an instance go out as the next
    instance, whole and in queue order, and the counters count two
    instances of one and three batches."""
    counters, frames = _drive(protocol, 3)
    ids = [[op.op_id for op in _batch(b)] for b in range(4)]
    merged = ids[1] + ids[2] + ids[3]
    assert _instances(frames, "slow_propose") == [ids[0], merged]
    assert _instances(frames, "slow_commit") == [ids[0], merged]
    assert (counters.instances, counters.ops) == (2, 4 * BATCH)
    # batch 0 was proposed with nothing queued, the merge left nothing
    assert counters.backlog == 0


@pytest.mark.parametrize("protocol", ["woc", "cabinet"])
def test_frame_limit_splits_the_queue(protocol, monkeypatch):
    """Under a frame limit that holds two and a half batches, five queued
    batches go out as instances of two, two and one batch, each whole and
    in queue order, and every propose and commit frame fits the limit."""
    limit = codec.SLOW_BODY_BYTES + (5 * BATCH // 2) * codec.SLOW_OP_BYTES
    monkeypatch.setattr(codec, "MAX_FRAME", limit)
    counters, frames = _drive(protocol, 5)
    ids = [[op.op_id for op in _batch(b)] for b in range(6)]
    want = [ids[0], ids[1] + ids[2], ids[3] + ids[4], ids[5]]
    assert _instances(frames, "slow_propose") == want
    assert _instances(frames, "slow_commit") == want
    assert (counters.instances, counters.ops) == (4, 6 * BATCH)
    # batches left queued at each propose: none, then 3, 1 and 0
    assert counters.backlog == 4
    assert all(n - codec.HEADER.size <= limit for k, _, n in frames
               if k in ("slow_propose", "slow_commit"))
