"""Load on a served WOC cluster, timed on the benchmark's own clock.

The clients are the program's own :class:`repro.core.simulator.Client`
(batching, retry to another replica after ``RETRY``, suspicion and ack
dedup) over the program's wire path (:class:`NetContext`,
:class:`PeerChannel`), run as asyncio tasks in the benchmark's process. What
differs is when a batch is sent and how an op is timed:

* ``open``: batches are sent when they fall due on a schedule drawn from
  the seed, whatever is still in flight. An op's latency runs from its due
  time to the receipt of its ack here, so a stall that delays sending shows
  in the tail instead of lowering the offered load.
* ``cap``: the paper's client model. Each client keeps at most
  ``inflight`` batches outstanding and sends the next when an ack frees a
  slot; an op is timed from its send.

Every time is ``NetContext.now``: seconds since the cluster's epoch, the
clock the replicas stamp their spans with.
"""

from __future__ import annotations

import asyncio
from typing import Dict, List, Optional

import numpy as np

from repro.core.runner import client_target_fn
from repro.core.simulator import Client, Op, Workload
from repro.transport.codec import decode_body
from repro.transport.net import NetContext, PeerChannel
from repro.transport.node_runner import read_addr

SEQ_BITS = 40          # an op id is (client id << 40) | sequence number
SEQ_MASK = (1 << SEQ_BITS) - 1
TICK_S = 0.0005        # the open-loop sender's longest sleep


def object_class(obj: int) -> int:
    """0 independent, 1 common, 2 hot: the bits the paper mix sets."""
    return 2 if obj >> 61 & 1 else 1 if obj >> 60 & 1 else 0


def batch_schedule(seed: int, client: int, rate_batches: float, t_from: float,
                   t_to: float) -> np.ndarray:
    """Due times of one client's batches in [t_from, t_to): a Poisson
    stream conditioned on its count, so every seed offers the same number
    of batches at different moments."""
    count = int(round(rate_batches * (t_to - t_from)))
    rng = np.random.default_rng([seed, 0x5EED, client])
    return np.sort(rng.uniform(t_from, t_to, count))


class BenchClient(Client):
    """:class:`Client` that records, per op, when it was due, when its ack
    arrived, the commit path the replica reported and a read's value."""

    def __init__(self, ctx: NetContext, *, n: int, batch_size: int,
                 workload: Workload, seed: int,
                 inflight: Optional[int] = None):
        index = ctx.local_id - n
        super().__init__(ctx.local_id, ctx, batch_size=batch_size,
                         max_inflight=inflight or 1 << 30, workload=workload,
                         target_fn=client_target_fn("woc", index, n),
                         total_batches=1 << 62, value_seed=seed)
        self.capped = inflight is not None
        self.sending = True
        self.due: List[float] = []             # by sequence number
        self.sent: List[float] = []
        self.ack: Dict[int, float] = {}        # sequence number -> time
        self.path: Dict[int, str] = {}
        self.result: Dict[int, object] = {}
        self.lateness: List[float] = []        # open loop: send - due

    # -- sending -------------------------------------------------------------

    def _maybe_submit(self) -> None:
        if not (self.capped and self.sending):
            return
        before = len(self.ops)
        super()._maybe_submit()
        now = self.sim.now
        grown = len(self.ops) - before
        self.due.extend([now] * grown)
        self.sent.extend([now] * grown)

    def send_due(self, due: float) -> None:
        """Open loop: make and send the batch that fell due at ``due``."""
        ops = self._make_batch()
        self._submit(ops, due)
        self.lateness.append(self.sim.now - due)

    def send_reads(self, objs, target: int) -> None:
        """Read each object once through replica ``target``."""
        now = self.sim.now
        for i in range(0, len(objs), self.batch_size):
            ops = []
            for obj in objs[i:i + self.batch_size]:
                oid = (self.node_id << SEQ_BITS) | self._next_op
                self._next_op += 1
                ops.append(Op(oid, self.node_id, int(obj), "r", 0, now))
            saved, self.target_fn = self.target_fn, lambda k: target
            try:
                self._submit(ops, now)
            finally:
                self.target_fn = saved

    def _submit(self, ops: List[Op], due: float) -> None:
        self.ops.extend(ops)
        self.submitted += 1
        self.inflight_ops += len(ops)
        now = self.sim.now
        self.due.extend([due] * len(ops))
        self.sent.extend([now] * len(ops))
        self._dispatch(ops)

    # -- acks ----------------------------------------------------------------

    def on_client_reply(self, msg, now: float) -> None:
        payload = msg.payload
        paths = payload.get("paths") or {}
        results = payload.get("results") or {}
        t = self.sim.now
        for op_id in payload.get("op_ids", ()):
            seq = op_id & SEQ_MASK
            if seq in self.ack:
                continue                       # a retry's second ack
            self.ack[seq] = t
            stamp = paths.get(op_id)
            self.path[seq] = stamp[1] if stamp is not None else "ack"
            if op_id in results:
                self.result[seq] = results[op_id]
        super().on_client_reply(msg, now)

    def pending(self) -> int:
        return len(self.ops) - len(self.ack)

    def records(self) -> dict:
        """Per-op arrays in sequence order; ``ack`` is +inf where none came."""
        n = len(self.ops)
        ack = np.full(n, np.inf)
        for seq, t in self.ack.items():
            ack[seq] = t
        return {
            "obj": np.array([op.obj for op in self.ops], dtype=np.uint64),
            "kind": np.array([op.kind for op in self.ops]),
            "value": [op.value for op in self.ops],
            "due": np.asarray(self.due, dtype=np.float64),
            "sent": np.asarray(self.sent, dtype=np.float64),
            "ack": ack,
            "path": np.array([self.path.get(i, "") for i in range(n)]),
            "result": [self.result.get(i) for i in range(n)],
            "answered": np.array([i in self.result for i in range(n)]),
        }


class ClientSet:
    """Clients with one channel to every replica each, on the running loop."""

    def __init__(self, *, n: int, first_gid: int, count: int, epoch: float,
                 seed: int, run_dir, batch_size: int, workload: Workload,
                 inflight: Optional[int] = None):
        self.clients: List[BenchClient] = []
        self.channels: List[PeerChannel] = []
        for gid in range(first_gid, first_gid + count):
            ctx = NetContext(gid, n, epoch=epoch, seed=seed)
            client = BenchClient(ctx, n=n, batch_size=batch_size,
                                 workload=workload, seed=seed,
                                 inflight=inflight)
            ctx.add_node(client)
            for j in range(n):
                chan = PeerChannel(
                    gid, j, lambda j=j: read_addr(run_dir, j),
                    on_frame=lambda body, c=client, x=ctx:
                        c.on_message(decode_body(body), x.now))
                ctx.register_peer(j, chan.send)
                self.channels.append(chan)
            self.clients.append(client)

    @property
    def now(self) -> float:
        return self.clients[0].sim.now

    def pending(self) -> int:
        return sum(c.pending() for c in self.clients)

    async def drain(self, deadline: float) -> None:
        """Wait until every op sent has its ack, or until ``deadline``."""
        while self.pending() and self.now < deadline:
            await asyncio.sleep(0.005)

    async def close(self) -> None:
        for chan in self.channels:
            await chan.close()


async def sleep_until(clock: ClientSet, t: float) -> None:
    while True:
        left = t - clock.now
        if left <= 0:
            return
        await asyncio.sleep(min(left, 0.05))


async def loop_lag(clock, t_end: float, period: float = 0.01) -> np.ndarray:
    """How late the running loop wakes a task that sleeps ``period``, at
    each wake until ``t_end``: the loop's own delay to every client on it."""
    lags = []
    while clock.now < t_end:
        t = clock.now
        await asyncio.sleep(period)
        lags.append(clock.now - t - period)
    return np.asarray(lags or [0.0])


async def run_open(cs: ClientSet, schedules: List[np.ndarray],
                   t_end: float) -> None:
    """Send each client's batches as they fall due, until ``t_end``."""
    heads = [0] * len(schedules)
    while True:
        now = cs.now
        nxt = t_end
        for c, (client, times) in enumerate(zip(cs.clients, schedules)):
            i = heads[c]
            while i < len(times) and times[i] <= now:
                client.send_due(float(times[i]))
                i += 1
            heads[c] = i
            if i < len(times):
                nxt = min(nxt, float(times[i]))
        if now >= t_end and all(h == len(t) for h, t in zip(heads, schedules)):
            return
        await asyncio.sleep(min(TICK_S, max(0.0, nxt - cs.now)))


async def run_capped(cs: ClientSet, t_end: float) -> None:
    for client in cs.clients:
        client.start()
    await sleep_until(cs, t_end)
    for client in cs.clients:
        client.sending = False
