"""Asyncio transport core: the engine facade + per-peer outbound channels.

:class:`NetContext` implements the slice of the
:class:`repro.core.simulator.EventEngine` surface that replicas actually
touch (``post`` / ``set_timer`` / ``busy`` / ``now`` / ``n`` / ``costs``
/ ``tracer`` / ``commit_log`` / ...), so the protocol classes run over
real sockets **unmodified** — the same post/deliver contract, a
different substrate:

  * ``now`` is wall-clock seconds since a cluster-wide epoch the
    launcher hands every process (same host, same ``time.time`` domain),
    so spans and histories from different processes share one timeline;
  * timers are ``loop.call_later`` (monotonic) behind the same
    :class:`TimerHandle` interface (``cancel()`` / ``alive``) the
    simulator returns;
  * ``post`` routes by destination id: loopback via ``call_soon`` (a
    handler's sends must not recurse into handlers, exactly like the
    simulator's event queue), replicas via their :class:`PeerChannel`,
    clients via the inbound socket they dialed in on;
  * ``post_many`` is a broadcast: its payload is encoded once and each
    destination's frame spliced from those bytes; the simulator engines
    have no bytes to share and do not offer it;
  * ``busy`` is a no-op — real CPU time charges itself.

Frames cross between the event loop and the sockets in bulk. A send
only queues its frame; the first one of a loop turn schedules its link's
flush (``loop.call_soon``), which writes every frame queued by then with
one ``transport.write``, so a busy loop pays one wake and one syscall for
the many frames a long turn queues (:class:`PeerChannel`, and the reply
route of :meth:`NetContext.register_client_writer`). Reads are
``asyncio.Protocol`` callbacks (:class:`FrameProtocol`): each one cuts
every complete frame out of the connection's buffer and hands them on in
arrival order, with no task woken per frame.

Clock-domain caveat: ``time.time`` can step (NTP); on a single host the
histories this transport records are causally ordered by the sockets
themselves, and the linearizability checker consumes invoke/response
*intervals*, which only widen under small steps. Cross-host deployments
would need a real clock-sync story; this transport targets loopback.

Long-run memory contract (the soak assertions in tests/test_transport.py
pin this): every per-peer table in this module is bounded —
``PeerChannel`` queues cap at ``max_queue`` frames (drop-oldest; the
protocol's retransmit/retry layers re-drive), a client's reply route
holds one loop turn's replies, a connection's read buffer at most one
partial frame after each read, reconnect backoff is capped, and the
``read_results`` / ``commit_log`` reply-enrichment tables prune FIFO
above a fixed cap (a retried op older than 64k credits would lose its
path stamp in the reply — it keeps its ack).
Nothing here grows with the op count of the run except the tracer,
which is explicitly sampled (the ``vote`` spans below are tracer
events); a timed channel's queue stamps ride its bounded queue.

Served-path instrumentation, on only in a traced replica (off, each
costs one ``None`` or flag test per frame):

  * ``vote`` — ``(t_recv, "vote", responder, path, id, proposer,
    t_post)`` for every ``fast_propose`` / ``slow_propose`` frame that
    carries a sampled op: the responder's handler start and end (the
    accept is posted inside it). Joined with the coordinator's proposal
    and accept events, it splits each vote into outbound leg, responder
    service and return leg (``repro.obs.critical_path``). It is a span
    of the tracer and shares its sampling.
  * ``PeerChannel(timed=True)`` stamps each frame as it is queued and
    sums the time until its flush's ``transport.write`` into ``wait_s``:
    one float per channel, whatever the op count.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

from repro.core.simulator import CostModel, Msg
from repro.transport.codec import (encode_fanout, encode_hello, encode_msg,
                                   split_frames)

READ_RESULTS_CAP = 65536      # reply-enrichment table bound (FIFO prune)
WRITE_BUF_LIMIT = 8 * 1024 * 1024   # per-client-socket backpressure bound
# proposal kinds a responder votes on: kind -> (path, payload key of the
# round's id)
VOTE_KINDS = {"fast_propose": ("fast", "fb"), "slow_propose": ("slow", "inst")}


class TransportTimer:
    """``TimerHandle``-compatible wrapper over ``loop.call_later``."""

    __slots__ = ("alive", "_handle")

    def __init__(self):
        self.alive = True
        self._handle = None

    def cancel(self) -> None:
        self.alive = False
        if self._handle is not None:
            self._handle.cancel()


class NetContext:
    """One node process's engine facade (see module docstring)."""

    def __init__(self, node_id: int, n: int, *, epoch: float,
                 costs: Optional[CostModel] = None, seed: int = 0):
        self.local_id = node_id
        self.n = n
        self.costs = costs or CostModel()
        self.seed = seed
        self._epoch = epoch
        # engine-surface state the protocol layer reads
        self.crashed: set = set()
        self.clients_done = 0
        self.commit_log: Dict[int, tuple] = {}
        self.tracer = None
        self.weight_view: tuple = (0, None)
        self.weight_installs: List[tuple] = []
        # transport-only: read results recorded at apply time (the sim
        # shares Op objects by reference so the client sees the result
        # for free; over sockets ops are copies and the value must ride
        # the client_reply explicitly — see protocol_base apply sites)
        self.read_results: Dict[int, object] = {}
        self._node = None
        self._senders: Dict[int, Callable[[bytes], None]] = {}
        self.stats_messages = 0
        self.encodes = 0               # frame bodies encoded: one per
                                       # frame ``post`` encodes alone,
                                       # one per ``post_many``
        self._frames: Optional[dict] = None    # post_many: dst -> frame
        self.dropped_no_route = 0      # sends with no live route (peer
                                       # down / client gone): the
                                       # transport twin of a cut link

    # -- engine surface ------------------------------------------------------

    @property
    def now(self) -> float:
        return time.time() - self._epoch

    def add_node(self, node) -> None:
        assert node.node_id == self.local_id
        self._node = node

    def replicas(self) -> List[int]:
        return list(range(self.n))

    def busy(self, node_id: int, seconds: float) -> None:
        pass                           # real CPU time charges itself

    def set_timer(self, node_id: int, delay: float, name: str,
                  payload: dict) -> TransportTimer:
        handle = TransportTimer()

        def fire() -> None:
            if handle.alive:
                handle.alive = False
                self._node.on_timer(name, payload, self.now)

        handle._handle = asyncio.get_running_loop().call_later(delay, fire)
        return handle

    def note_weight_install(self, t: float, epoch: int, ranking: list,
                            by: int) -> None:
        if epoch > self.weight_view[0]:
            self.weight_view = (epoch, list(ranking))
        self.weight_installs.append((t, epoch, tuple(ranking), by))
        tr = self.tracer
        if tr is not None:
            tr.ev("weight_install", t, by, epoch,
                  ",".join(map(str, ranking)))

    def post(self, msg: Msg) -> None:
        self.stats_messages += 1
        if msg.dst == self.local_id:
            # loopback: defer like the simulator's event queue — a
            # handler's sends to self must not reenter handlers inline
            asyncio.get_running_loop().call_soon(self._deliver_local, msg)
            return
        if msg.kind == "client_reply":
            self._enrich_reply(msg.payload)
        sender = self._senders.get(msg.dst)
        if sender is None:
            self.dropped_no_route += 1
            return
        frames = self._frames
        if frames is None:
            self.encodes += 1
            sender(encode_msg(msg))
        else:
            sender(frames[msg.dst])

    def post_many(self, msg: Msg, dsts: Sequence[int]) -> None:
        """A broadcast: ``post`` of ``msg`` to each id of ``dsts`` (its
        own ``dst`` is not read), with the payload encoded once for all
        of them (``encode_fanout``). Each destination still goes through
        ``post``, which takes its frame from the broadcast's, so
        counting, loopback and routing are those of single sends, and a
        wrapper of ``post`` sees every destination. A ``client_reply``
        is enriched per destination and so encoded per destination."""
        if msg.kind != "client_reply" and dsts:
            self._frames = dict(zip(dsts, encode_fanout(msg, dsts)))
            self.encodes += 1
        try:
            for d in dsts:
                self.post(Msg(msg.kind, msg.src, d, msg.payload,
                              msg.size_ops, msg.size_bytes))
        finally:
            self._frames = None

    # -- transport plumbing --------------------------------------------------

    def _deliver_local(self, msg: Msg) -> None:
        self._node.on_message(msg, self.now)

    def deliver(self, msg: Msg) -> None:
        """Inbound frame -> protocol handler (called by the node
        runner's inbound connections, in each read's frame order). A
        traced replica records a ``vote`` span around its handling of a
        proposal (module docstring)."""
        t_recv = self.now
        self._node.on_message(msg, t_recv)
        tr = self.tracer
        if tr is not None:
            vote = VOTE_KINDS.get(msg.kind)
            if vote is not None and any(tr.sampled(op.op_id)
                                        for op in msg.payload["ops"]):
                path, key = vote
                tr.ev("vote", t_recv, self.local_id, path,
                      msg.payload[key], msg.src, self.now)

    def _enrich_reply(self, payload: dict) -> None:
        """Attach read results + commit paths to an outgoing credit
        message. Values are looked up (not popped): a retried op may be
        credited twice and both replies should carry the answer; the
        table is FIFO-pruned above a fixed cap instead."""
        rr = self.read_results
        commit_log = self.commit_log
        results = {}
        paths = {}
        for op_id in payload.get("op_ids", ()):
            if op_id in rr:
                results[op_id] = rr[op_id]
            stamp = commit_log.get(op_id)
            if stamp is not None:
                paths[op_id] = [stamp[0], stamp[1]]   # (commit_time, path)
        if results:
            payload["results"] = results
        if paths:
            payload["paths"] = paths
        if len(rr) > READ_RESULTS_CAP:
            drop = len(rr) - READ_RESULTS_CAP
            for k in list(rr)[:drop]:
                del rr[k]
        if len(commit_log) > READ_RESULTS_CAP:
            drop = len(commit_log) - READ_RESULTS_CAP
            for k in list(commit_log)[:drop]:
                del commit_log[k]

    def register_peer(self, peer_id: int,
                      sender: Callable[[bytes], None]) -> None:
        self._senders[peer_id] = sender

    def register_client_writer(self, client_id: int, transport) -> None:
        """Replies to a client go back over the socket it dialed in on
        (its server-side ``transport``), flushed as a peer channel's
        frames are: the replies of one loop turn leave in one write.
        Writes are bounded by the transport's write-buffer size: a stuck
        client drops replies (its retries re-drive) instead of growing
        the buffer without limit."""
        loop = asyncio.get_running_loop()
        pending: List[bytes] = []

        def flush() -> None:
            data = b"".join(pending)
            pending.clear()
            if not transport.is_closing():
                transport.write(data)

        def send(data: bytes) -> None:
            if transport.is_closing():
                self._senders.pop(client_id, None)
                self.dropped_no_route += 1
                return
            if transport.get_write_buffer_size() > WRITE_BUF_LIMIT:
                self.dropped_no_route += 1
                return
            if not pending:
                loop.call_soon(flush)
            pending.append(data)

        self._senders[client_id] = send

    def unregister(self, peer_id: int) -> None:
        self._senders.pop(peer_id, None)


class FrameProtocol(asyncio.Protocol):
    """A connection's read side: every complete frame a read brings is cut
    from the buffer (``codec.split_frames``) and handed to ``on_body``,
    in arrival order, inside that one ``data_received``; a partial frame
    waits in the buffer for the next read. A length over ``MAX_FRAME``,
    or a ``ValueError`` from ``on_body`` (a body that does not decode),
    aborts the connection. ``on_body`` may be replaced while a read's
    frames are handed on: the next frame goes to the new one."""

    def __init__(self, on_body: Callable[[bytes], None]):
        self.on_body = on_body
        self.transport = None
        self._buf = bytearray()

    def connection_made(self, transport) -> None:
        self.transport = transport

    def data_received(self, data: bytes) -> None:
        buf = self._buf
        buf += data
        try:
            bodies, _ = split_frames(buf)
            for body in bodies:
                self.on_body(body)
        except ValueError:
            self.transport.abort()


def _ignore(body: bytes) -> None:
    pass


class _ChannelLink(FrameProtocol):
    """The protocol of a :class:`PeerChannel`'s live connection: inbound
    frames go to the channel's ``on_frame``; the transport's write
    pauses and its loss go to the channel."""

    def __init__(self, chan: "PeerChannel"):
        super().__init__(chan.on_frame or _ignore)
        self.chan = chan

    def connection_made(self, transport) -> None:
        super().connection_made(transport)
        self.chan._up(transport)

    def connection_lost(self, exc) -> None:
        self.chan._down(self.transport)

    def pause_writing(self) -> None:
        self.chan._paused = True

    def resume_writing(self) -> None:
        self.chan._paused = False
        self.chan._schedule()


class PeerChannel:
    """One outbound connection to a replica: bounded queue, one flush per
    loop turn, dial + reconnect with capped exponential backoff, optional
    frame-reorder mutation.

    ``send`` queues the frame (drop-oldest at ``max_queue``) and, if no
    flush is due and the link is writable, schedules one with
    ``loop.call_soon``. The flush writes every frame queued by then with
    one ``transport.write`` (``writes`` counts them beside ``sent``).
    While the transport has paused writing (its buffer over asyncio's
    high-water mark) or the link is down, frames wait in the bounded
    queue; resuming, or the next connection, flushes them.

    The address is re-resolved through ``addr_fn`` on every dial so a
    peer that restarts on a fresh port is picked up without any control
    plane (the node runner's port files are the discovery mechanism).
    The dial runs at construction and again whenever the connection is
    lost; frames already handed to a transport that dies are lost (drop
    semantics: the protocol's retries re-drive them).

    ``reorder=True`` is the MUTATION TWIN for tests: every
    ``REORDER_EVERY``-th frame on this channel is held back and released
    only after ``REORDER_SKIP`` later frames have been sent, breaking
    the per-link FIFO property real TCP gives. Displacement (not a mere
    adjacent swap) is required to hurt: the slow path's wire stream
    strictly alternates commit(k), propose(k+1), commit(k+1), so
    distance-1 swaps can never invert two commits of the same object —
    a held frame skipping many successors can. Consecutive slow
    instances carry no dependency edges between their own ops (deps
    only cover live fast ops), so a displaced commit applies out of
    order at the receiving replica and a read coordinated there returns
    a stale value. The displacement must also exceed the client
    concurrency width: a one-generation inversion swaps writes that
    were concurrently in flight — whose client intervals overlap — and
    the checker may legally reorder those; rolling the store back past
    a dozen frames (several committed generations) makes the stale
    value's overwriters strictly real-time-before any witnessing read.
    A frame held through ``REORDER_QUIET_S`` without a send is released
    (liveness); releasing only after a quiet period, not the moment the
    queue drains, is what lets the displacement straddle later frames on
    a fast loopback link. A transport with this bug must fail the
    linearizability checker — that is what makes the
    checker-on-real-histories pipeline trustworthy.

    ``timed=True`` (a traced replica) queues each frame with the
    ``perf_counter`` time it was queued and adds its wait until the
    flush's ``transport.write`` to ``wait_s``; otherwise the queue holds
    bare frames.
    """

    REORDER_EVERY = 4     # hold every 4th frame ...
    REORDER_SKIP = 12     # ... until 12 later frames have been sent
    REORDER_QUIET_S = 0.25   # ... or no frame for this long

    def __init__(self, src: int, dst: int,
                 addr_fn: Callable[[], Optional[tuple]], *,
                 max_queue: int = 512, reorder: bool = False,
                 timed: bool = False,
                 on_frame: Optional[Callable[[bytes], None]] = None):
        self.src = src
        self.dst = dst
        self.addr_fn = addr_fn
        self.max_queue = max_queue
        self.reorder = reorder
        self.timed = timed
        self.on_frame = on_frame       # clients: replies ride this socket
        self._loop = asyncio.get_running_loop()
        self._q: deque = deque()
        self._held: Optional[bytes] = None     # reorder twin: displaced frame
        self._held_skip = 0                    # frames left to jump over
        self._sent_ctr = 0                     # selects every Nth frame
        self._release = None                   # ... and its quiet timer
        self._transport = None         # the live connection's, once dialed
        self._paused = False           # its buffer is over the high-water
        self._flush_due = False        # a flush is scheduled
        self._closed = False
        # soak-visible stats: every one of these is bounded per the
        # module contract; queue_hwm <= max_queue is asserted in tests
        self.sent = 0
        self.writes = 0                        # transport.write calls
        self.wait_s = 0.0                      # timed: queued -> written
        self.dropped = 0
        self.reconnects = 0
        self.queue_hwm = 0
        self._dial = self._loop.create_task(self._connect())

    # -- send side (sync, called from protocol handlers) ---------------------

    def send(self, data: bytes) -> None:
        if self._closed:
            return
        if self.reorder:
            if self._held is not None:
                self._push(data)
                self._held_skip -= 1
                if self._held_skip <= 0:
                    self._release_held()   # displaced frame lands late
                else:
                    self._hold_quietly()
                return
            self._sent_ctr += 1
            if self._sent_ctr % self.REORDER_EVERY == 0:
                self._held = data
                self._held_skip = self.REORDER_SKIP
                self._hold_quietly()
                return
        self._push(data)

    def _hold_quietly(self) -> None:
        """(Re)arm the reorder twin's quiet-period release."""
        if self._release is not None:
            self._release.cancel()
        self._release = self._loop.call_later(self.REORDER_QUIET_S,
                                              self._release_held)

    def _release_held(self) -> None:
        if self._release is not None:
            self._release.cancel()
            self._release = None
        held, self._held = self._held, None
        if held is not None and not self._closed:
            self._push(held)

    def _push(self, data: bytes) -> None:
        q = self._q
        if len(q) >= self.max_queue:
            q.popleft()                    # drop-oldest: retransmit
            self.dropped += 1              # timers / client retries
        if self.timed:                     # re-drive consensus traffic
            data = (time.perf_counter(), data)
        q.append(data)
        if len(q) > self.queue_hwm:
            self.queue_hwm = len(q)
        self._schedule()

    def _schedule(self) -> None:
        if (not self._flush_due and self._q and not self._paused
                and self._transport is not None):
            self._flush_due = True
            self._loop.call_soon(self._flush)

    def _flush(self) -> None:
        """Write every queued frame with one ``transport.write``."""
        self._flush_due = False
        q = self._q
        transport = self._transport
        if transport is None or self._paused or not q:
            return
        if self.timed:
            now = time.perf_counter()
            self.wait_s += sum(now - queued for queued, _ in q)
            data = b"".join([frame for _, frame in q])
        else:
            data = b"".join(q)
        self.sent += len(q)
        self.writes += 1
        q.clear()
        transport.write(data)

    # -- connection ----------------------------------------------------------

    async def _connect(self) -> None:
        """Dial until connected, re-reading the address on every attempt."""
        backoff = 0.05
        while True:
            addr = self.addr_fn()
            if addr is not None:
                try:
                    await self._loop.create_connection(
                        lambda: _ChannelLink(self), *addr)
                    return
                except OSError:
                    self.reconnects += 1
            await asyncio.sleep(backoff)
            backoff = min(backoff * 2, 1.0)

    def _up(self, transport) -> None:
        if self._closed:
            transport.close()
            return
        transport.write(encode_hello(self.src))
        self._transport = transport
        self._schedule()

    def _down(self, transport) -> None:
        if transport is not self._transport:
            return
        self._transport = None
        self._paused = False
        if not self._closed:
            self.reconnects += 1
            self._dial = self._loop.create_task(self._connect())

    async def close(self) -> None:
        self._closed = True
        if self._release is not None:
            self._release.cancel()
        self._dial.cancel()
        try:
            await self._dial
        except asyncio.CancelledError:
            pass
        if self._transport is not None:
            self._transport.close()
            self._transport = None

    def stats(self) -> dict:
        return {"dst": self.dst, "sent": self.sent, "writes": self.writes,
                "wait_s": self.wait_s, "dropped": self.dropped,
                "reconnects": self.reconnects, "queue_hwm": self.queue_hwm,
                "queue_len": len(self._q), "max_queue": self.max_queue}
