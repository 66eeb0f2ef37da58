"""Served transport (repro.transport): codec round-trips, loopback
clusters with real client processes, crash/recovery over sockets,
bounded per-peer state, and the frame-reorder mutation twin.

The cluster tests spawn real subprocesses and take wall-clock seconds
each; they are deliberately small (hundreds of ops) — the simulator
remains the scale/determinism oracle, these prove the same replica code
serves real concurrent clients and that the capture pipeline feeds the
checker honestly (including failing when the transport is broken).
"""

import time

import pytest

from repro.core.simulator import Msg, Op
from repro.obs.critical_path import analyze_events
from repro.transport import ClusterConfig, ClusterLauncher, run_served
from repro.transport.codec import (decode_body, decode_hello, encode_hello,
                                   encode_msg, split_frames)
from repro.transport.net import READ_RESULTS_CAP
from repro.verify import check_history_linearizable, verify_artifacts


# ---------------------------------------------------------------------------
# codec (no sockets)
# ---------------------------------------------------------------------------

def test_codec_roundtrips_protocol_shapes():
    """The tag space must restore the exact in-memory shapes protocol
    handlers expect: Op records, sets, tuples, int-keyed dicts."""
    op = Op(7, 5, 0x2000000000000000, "w", 1234, 0.5, -1.0, "", None)
    msg = Msg("slow_commit", 1, 3,
              {"ops": [op], "deps": {7: [3, 4]}, "applied": {1, 2},
               "buf": [(op, None, "slow")], "store": {9: 42}}, 1)
    frames, tail = split_frames(encode_msg(msg))
    assert tail == b"" and len(frames) == 1
    out = decode_body(frames[0])
    assert (out.kind, out.src, out.dst, out.size_ops) == \
        ("slow_commit", 1, 3, 1)
    op2 = out.payload["ops"][0]
    assert isinstance(op2, Op)
    assert (op2.op_id, op2.obj, op2.kind, op2.value) == \
        (op.op_id, op.obj, op.kind, op.value)
    assert out.payload["deps"] == {7: [3, 4]}          # int keys survive
    assert out.payload["applied"] == {1, 2}            # set survives
    assert out.payload["buf"][0][2] == "slow"          # tuple survives
    assert out.payload["store"] == {9: 42}


def test_codec_partial_frames_and_hello():
    a = encode_msg(Msg("hb", 0, 1, {"t": 0.25}, 0))
    b = encode_hello(4)
    frames, tail = split_frames(a + b[:3])             # split mid-header
    assert len(frames) == 1 and tail == b[:3]
    frames2, tail2 = split_frames(tail + b[3:])
    assert tail2 == b"" and decode_hello(frames2[0]) == 4


def test_codec_op_size_and_msg_bytes_roundtrip():
    """The payload-size axis rides the wire: Op.size survives encode/
    decode, Msg.size_bytes rides the optional "b" key, and frames from
    peers on the pre-size format (9-field __op__, no "b") decode as
    sizeless rather than crashing — a mixed-version cluster must not
    partition on codec shape."""
    op = Op(7, 5, 0x2000000000000000, "w", 1234, 0.5, -1.0, "", None,
            1 << 20)
    frames, _ = split_frames(encode_msg(
        Msg("fast_propose", 1, 3, {"ops": [op]}, 1, 1 << 20)))
    out = decode_body(frames[0])
    assert out.size_bytes == 1 << 20
    assert out.payload["ops"][0].size == 1 << 20
    # sizeless messages must not grow a "b" key (byte-identical frames)
    plain = encode_msg(Msg("hb", 0, 1, {"t": 0.25}, 0))
    assert b'"b"' not in plain and b"\xa1b" not in plain
    # old-format frame: hand-build a 9-field __op__ body without "b"
    import json as _json
    legacy = _json.dumps(
        {"k": "fast_propose", "s": 1, "d": 3, "z": 1,
         "p": {"ops": [{"__op__": [7, 5, 9, "w", 1234, 0.5, -1.0, "",
                                   None]}]}},
        separators=(",", ":")).encode()
    from repro.transport import codec as _codec
    saved = _codec.msgpack
    _codec.msgpack = None          # force the JSON path the frame is in
    try:
        old = decode_body(legacy)
    finally:
        _codec.msgpack = saved
    assert old.size_bytes == 0 and old.payload["ops"][0].size == 0


def test_codec_oversize_frames_rejected_both_ends():
    """A corrupt (or hostile) length prefix must die at the header, even
    when the body bytes never arrive (streaming-safe), and the encoder
    must refuse to emit a frame larger than every receiver's bound."""
    from repro.transport.codec import HEADER, MAX_FRAME
    # decode side: header alone, no body — the length check cannot wait
    # for MAX_FRAME bytes that will never come
    with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
        split_frames(HEADER.pack(MAX_FRAME + 1))
    # encode side: a payload whose encoded body crosses the bound
    big = "x" * (MAX_FRAME + 16)
    with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
        encode_msg(Msg("blob", 0, 1, {"v": big}, 1))


def test_codec_slow_instance_bound_covers_the_widest_op(body_format):
    """``slow_instance_fits`` bounds the bodies of the widest slow
    instance it admits: every number of each op and of the envelope at
    64 bits, and the commit's deps listing every id."""
    from repro.transport import codec
    big = -(1 << 63)
    wide = Op(big, big, big, "w", big, -1.2345678901234567e+300,
              -1.2345678901234567e-300, "slow", big, big)
    n_ops, n_ids = 40, 60
    ops = [wide] * n_ops
    deps = {big + i: [big] * (n_ids // n_ops + (i < n_ids % n_ops))
            for i in range(n_ops)}
    bodies = [
        Msg("slow_propose", big, big, {"inst": big, "ops": ops,
                                        "epoch": big}, big, big),
        Msg("slow_commit", big, big, {"ops": ops, "deps": deps}, big, big),
    ]
    need = max(len(encode_msg(m)) - codec.HEADER.size for m in bodies)
    bound = (codec.SLOW_BODY_BYTES + n_ops * codec.SLOW_OP_BYTES
             + n_ids * codec.SLOW_ID_BYTES)
    assert need <= bound
    assert codec.slow_instance_fits(n_ops, n_ids) == \
        (bound <= codec.MAX_FRAME)
    assert not codec.slow_instance_fits(codec.MAX_FRAME, 0)


def _op(i, size=0):
    return Op(i, 5, 0x2000000000000000 + i, "w", 1234 + i, 0.5, -1.0, "",
              None, size)


FANOUT_SHAPES = {
    "ops": lambda: Msg("fast_propose", 0, -1,
                       {"ops": [_op(i) for i in range(10)], "fb": 17}, 10),
    "dep_map": lambda: Msg("fast_commit", 2, -1,
                           {"fb": 3, "deps": {7: [3, 4], 9: []},
                            "ok": True}, 0),
    "set_tuple": lambda: Msg("slow_commit", 1, -1,
                             {"applied": {1, 2, 3},
                              "buf": [(_op(1), None, "slow")],
                              "store": {9: 42}, "t": 0.25}, 1),
    "size_bytes": lambda: Msg("fast_propose", 4, -1,
                              {"ops": [_op(1, 1 << 20)]}, 1, 1 << 20),
}


@pytest.fixture(params=["msgpack", "json"])
def body_format(request, monkeypatch):
    """Run a codec test on msgpack bodies and on the JSON fallback."""
    from repro.transport import codec
    if request.param == "json":
        monkeypatch.setattr(codec, "msgpack", None)
    elif codec.msgpack is None:
        pytest.fail("msgpack is not installed")
    return request.param


@pytest.mark.parametrize("shape", sorted(FANOUT_SHAPES))
def test_codec_fanout_frames_are_encode_msg_frames(shape, body_format):
    """A broadcast's frames, encoded once for every destination, are byte
    for byte the frames ``encode_msg`` gives each destination alone."""
    from repro.transport.codec import encode_fanout
    msg = FANOUT_SHAPES[shape]()
    dsts = [1, 3, 8, 0, 127, 128, 70000]
    frames = encode_fanout(msg, dsts)
    assert frames == [encode_msg(Msg(msg.kind, msg.src, d, msg.payload,
                                     msg.size_ops, msg.size_bytes))
                      for d in dsts]
    assert decode_body(split_frames(frames[2])[0][0]).dst == 8


def test_codec_fanout_refuses_oversize_body(body_format):
    from repro.transport.codec import MAX_FRAME, encode_fanout
    big = "x" * (MAX_FRAME + 16)
    with pytest.raises(ValueError, match="exceeds MAX_FRAME"):
        encode_fanout(Msg("blob", 0, -1, {"v": big}, 1), [1, 2])


# ---------------------------------------------------------------------------
# engine facade (stub senders, no sockets)
# ---------------------------------------------------------------------------

class _Sink:
    """A replica that records what is delivered to it."""

    def __init__(self, node_id):
        self.node_id = node_id
        self.got = []

    def on_message(self, msg, now):
        self.got.append((msg.kind, msg.src, msg.dst, msg.payload))


def _facade(broadcast: bool, kind: str):
    """Send one message to replicas 1-3 (2 has no route), to itself and to
    client 9, as one broadcast or as single sends, from replica 0 of a
    4-replica facade; what each stub sender and the local replica got.
    The local replica gets the payload object itself."""
    import asyncio

    from repro.core.simulator import Node
    from repro.transport.net import NetContext

    async def go():
        ctx = NetContext(0, 4, epoch=time.time())
        sink = _Sink(0)
        ctx.add_node(sink)
        ctx.commit_log[5] = (0.5, "fast")
        wire = {d: [] for d in (1, 3, 9)}
        for d, got in wire.items():
            ctx.register_peer(d, got.append)
        node = Node(0, ctx)
        payload = {"ops": [_op(1), _op(2)], "fb": 4, "op_ids": [5]}
        dsts = [1, 0, 2, 3, 9]
        if broadcast:
            node.broadcast(dsts, kind, payload, 2)
        else:
            for d in dsts:
                node.send(d, kind, payload, 2)
        await asyncio.sleep(0)             # the deferred loopback
        assert [m[3] for m in sink.got] == [payload]
        return ctx, [m[:3] for m in sink.got], wire

    return asyncio.run(go())


@pytest.mark.parametrize("kind", ["fast_commit", "client_reply"])
def test_facade_broadcast_is_its_single_posts(kind):
    """One broadcast through the served engine counts, routes, loops back
    and writes the same bytes per peer as one post per destination, and
    encodes its body once (a client reply is enriched per destination, so
    it is encoded per destination)."""
    one, looped1, wire1 = _facade(False, kind)
    many, looped2, wire2 = _facade(True, kind)
    assert wire2 == wire1 and all(len(w) == 1 for w in wire1.values())
    assert looped2 == looped1 == [(kind, 0, 0)]
    assert (many.stats_messages, many.dropped_no_route) == \
        (one.stats_messages, one.dropped_no_route) == (5, 1)
    assert one.encodes == 3
    assert many.encodes == (3 if kind == "client_reply" else 1)


# ---------------------------------------------------------------------------
# the bulk path: one flush per link per loop turn, one parse per read
# ---------------------------------------------------------------------------

async def _until(cond, timeout=10.0):
    import asyncio
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        await asyncio.sleep(0.005)


class _Peer:
    """A stub replica's listening socket: records the bytes each inbound
    connection brings, and can stop reading them."""

    def __init__(self, read=True):
        self.read = read
        self.conns = []

    async def start(self):
        import asyncio

        peer = self

        class Conn(asyncio.Protocol):
            def connection_made(self, transport):
                self.transport, self.data = transport, bytearray()
                peer.conns.append(self)
                if not peer.read:
                    transport.pause_reading()

            def data_received(self, data):
                self.data += data

        self.server = await asyncio.get_running_loop().create_server(
            Conn, "127.0.0.1", 0)
        self.addr = self.server.sockets[0].getsockname()[:2]
        return self

    def received(self):
        return b"".join(bytes(c.data) for c in self.conns)

    def kill(self):
        self.server.close()
        for c in self.conns:
            c.transport.abort()


def _frames(n, size=0):
    return [encode_msg(Msg("fast_propose", 0, 1,
                           {"ops": [_op(i, size)], "fb": i, "pad": "x" * size},
                           1)) for i in range(n)]


@pytest.mark.parametrize("timed", [False, True], ids=["untimed", "timed"])
def test_frames_of_one_turn_leave_in_one_write(timed):
    """Frames sent in one loop turn reach the peer as one write of their
    concatenation, in order; an idle link flushes a lone frame at once."""
    import asyncio

    from repro.transport.net import PeerChannel

    async def go():
        peer = await _Peer().start()
        chan = PeerChannel(7, 1, lambda: peer.addr, timed=timed)
        await _until(lambda: chan._transport is not None)
        hello = encode_hello(7)
        frames = _frames(3)
        for f in frames:
            chan.send(f)
        await _until(lambda: len(peer.received()) >= len(hello) + sum(
            map(len, frames)))
        assert peer.received() == hello + b"".join(frames)
        assert (chan.sent, chan.writes, chan.dropped) == (3, 1, 0)
        assert (chan.wait_s > 0) == timed
        chan.send(frames[0])               # alone in its turn
        await _until(lambda: chan.sent == 4)
        assert chan.writes == 2
        stats = chan.stats()
        await chan.close()
        peer.kill()
        return stats

    stats = asyncio.run(go())
    assert stats["writes"] == 2 and stats["sent"] == 4
    assert stats["queue_len"] == 0 and stats["reconnects"] == 0


def _inbound_stream():
    """A replica's hello and then frames of several sizes, and the
    messages they carry."""
    msgs = [Msg("hb", 2, 0, {"t": 0.25}, 0),
            Msg("fast_propose", 2, 0,
                {"ops": [_op(i) for i in range(10)], "fb": 5}, 10),
            Msg("slow_commit", 2, 0, {"ops": [_op(1)], "deps": {1: [2]}}, 1),
            Msg("fast_accept", 2, 0, {"fb": 5, "ok": True}, 0)]
    frames = [encode_msg(m) for m in msgs]
    return encode_hello(2) + b"".join(frames), frames


class _Transport:
    """What an inbound protocol and the reply route use of a transport."""

    def __init__(self, buffered=0):
        self.writes = []
        self.buffered = buffered
        self.aborted = self.closing = False

    def write(self, data):
        self.writes.append(data)

    def abort(self):
        self.aborted = True

    def is_closing(self):
        return self.closing or self.aborted

    def get_write_buffer_size(self):
        return self.buffered


def _inbound(n=5):
    from repro.transport.net import NetContext
    from repro.transport.node_runner import Inbound

    ctx = NetContext(0, n, epoch=time.time())
    sink = _Sink(0)
    sink.on_message = lambda msg, now: sink.got.append(encode_msg(msg))
    ctx.add_node(sink)
    proto = Inbound(ctx)
    transport = _Transport()
    proto.connection_made(transport)
    return proto, transport, sink, ctx


@pytest.mark.parametrize("pieces", ["two", "bytewise"])
def test_inbound_frames_survive_every_cut(pieces, body_format):
    """Cut anywhere, mid-header and mid-body included, the inbound
    connection delivers the same messages in the same order (each
    delivered message encodes to the frame it came in)."""
    stream, want = _inbound_stream()
    if pieces == "two":
        cuts = [[stream[:k], stream[k:]] for k in range(len(stream) + 1)]
    else:
        cuts = [[stream[i:i + 1] for i in range(len(stream))]]
    for chunks in cuts:
        proto, transport, sink, _ = _inbound()
        for chunk in chunks:
            proto.data_received(chunk)
        assert sink.got == want
        assert not transport.aborted and not proto._buf


@pytest.mark.parametrize("bad", ["oversize", "not_a_hello", "oversize_hello"])
def test_inbound_rejects_a_corrupt_stream(bad):
    """A length over ``MAX_FRAME`` closes the connection as soon as its
    header is in, and so does a first frame that is not a hello; nothing
    after it is delivered."""
    from repro.transport.codec import HEADER, MAX_FRAME
    stream, _ = _inbound_stream()
    over = HEADER.pack(MAX_FRAME + 1)
    data = {"oversize": encode_hello(2) + over + stream[len(encode_hello(2)):],
            "not_a_hello": encode_msg(Msg("hb", 2, 0, {"t": 0.25}, 0)),
            "oversize_hello": over}[bad]
    proto, transport, sink, _ = _inbound()
    proto.data_received(data)
    assert transport.aborted and sink.got == []


@pytest.mark.parametrize("case", ["batched", "over_limit", "closing"])
def test_client_replies_of_one_turn_leave_in_one_write(case):
    """A client's replies posted in one loop turn leave in one write of
    their concatenation; a client whose socket buffer is over
    ``WRITE_BUF_LIMIT`` loses replies, and a closed one loses its route."""
    import asyncio

    from repro.transport.net import WRITE_BUF_LIMIT

    async def go():
        proto, transport, _, ctx = _inbound()
        proto.data_received(encode_hello(9)[:5])
        proto.data_received(encode_hello(9)[5:])    # client 9 dialed in
        transport.buffered = {"over_limit": WRITE_BUF_LIMIT + 1}.get(case, 0)
        transport.closing = case == "closing"
        frames = [encode_msg(Msg("client_reply", 0, 9, {"op_ids": [i]}, 0))
                  for i in range(3)]
        for i in range(3):
            ctx.post(Msg("client_reply", 0, 9, {"op_ids": [i]}, 0))
        assert transport.writes == []        # nothing before the turn ends
        await asyncio.sleep(0)
        return transport.writes, frames, ctx

    writes, frames, ctx = asyncio.run(go())
    if case == "batched":
        assert writes == [b"".join(frames)] and ctx.dropped_no_route == 0
    else:
        assert writes == [] and ctx.dropped_no_route == 3
    assert (9 in ctx._senders) == (case != "closing")


def test_channel_to_a_stalled_peer_stays_bounded():
    """A peer that stops reading: the transport pauses writing, frames
    wait in the bounded queue (drop-oldest, ``queue_hwm <= max_queue``,
    ``dropped`` counting the overflow), and when the peer reads again the
    queue drains through the resumed flush."""
    import asyncio

    from repro.transport.net import PeerChannel

    async def go():
        peer = await _Peer(read=False).start()
        chan = PeerChannel(0, 1, lambda: peer.addr, max_queue=8)
        await _until(lambda: peer.conns)
        frames = _frames(4, size=64 * 1024)
        n = 0
        while not (chan._paused and chan.dropped):
            for f in frames:
                chan.send(f)
                n += 1
            await asyncio.sleep(0)
            assert n < 4000, "the transport never paused"
        assert chan.queue_hwm <= chan.max_queue
        assert chan.sent + chan.dropped + len(chan._q) == n
        peer.conns[0].transport.resume_reading()
        await _until(lambda: not chan._q)
        hello = encode_hello(0)
        await _until(lambda: len(peer.received()) >= len(hello)
                     + chan.sent * len(frames[0]))
        assert chan.sent + chan.dropped == n and chan.writes < chan.sent
        await chan.close()
        peer.kill()

    asyncio.run(go())


def test_channel_redials_a_restarted_peer_on_its_new_port():
    """When the peer dies and comes back on a fresh port, the channel
    notices the lost connection, re-resolves ``addr_fn`` and delivers
    what is sent next over the new connection, hello first."""
    import asyncio

    from repro.transport.net import PeerChannel

    async def go():
        old = await _Peer().start()
        where = [old.addr]
        chan = PeerChannel(3, 1, lambda: where[0])
        a, b = _frames(2)
        chan.send(a)
        await _until(lambda: old.received() == encode_hello(3) + a)
        new = await _Peer().start()
        assert new.addr != old.addr
        where[0] = new.addr
        old.kill()
        await _until(lambda: chan.reconnects >= 1)
        chan.send(b)
        await _until(lambda: new.received() == encode_hello(3) + b)
        assert (chan.sent, chan.dropped) == (2, 0)
        await chan.close()
        new.kill()

    asyncio.run(go())


def test_reorder_twin_displaces_and_releases_when_quiet():
    """The mutation twin holds every ``REORDER_EVERY``-th frame until
    ``REORDER_SKIP`` later frames are queued, and releases a held frame
    that no later frame displaces after ``REORDER_QUIET_S``."""
    import asyncio

    from repro.transport.net import PeerChannel

    async def go():
        chan = PeerChannel(0, 1, lambda: None, reorder=True)
        for i in range(1, 21):
            chan.send(i)
        order = list(chan._q)
        await asyncio.sleep(chan.REORDER_QUIET_S * 2)
        late = list(chan._q)[len(order):]
        await chan.close()
        return order, late

    order, late = asyncio.run(go())
    assert order == [1, 2, 3, *range(5, 17), 4, 17, 18, 19]
    assert late == [20]


# ---------------------------------------------------------------------------
# loopback cluster: real histories through the real checker
# ---------------------------------------------------------------------------

def test_served_cluster_history_linearizable_and_bounded():
    """5 replicas + 2 client processes over localhost sockets: every op
    commits, the captured history passes the linearizability checker,
    obs metrics aggregate from the merged real trace, and all per-peer
    transport state stays bounded (the soak contract)."""
    cfg = ClusterConfig(n_replicas=5, n_clients=2, total_ops=400,
                        batch_size=8, seed=11, time_limit_s=45)
    art = run_served(cfg)
    r = art.result

    assert r.clients_done == cfg.n_clients
    assert r.committed_ops == cfg.total_ops
    ok, why = check_history_linearizable(r.history)
    assert ok, why
    ok, why = verify_artifacts(art, check_rsm=False)
    assert ok, why

    # obs wiring: real wall-clock spans aggregate exactly like sim spans
    counters = r.metrics["counters"]
    committed_by_path = sum(v for k, v in counters.items()
                            if k.startswith("ops_committed_total"))
    assert committed_by_path == cfg.total_ops

    # no replica or client process starts a jax backend: the chip
    # belongs to the process that launched them
    assert len(r.client_stats) == cfg.n_clients
    assert not any(s["jax_backend"] for s in r.node_stats + r.client_stats)

    # soak bounds: queues respect their cap and drain at shutdown,
    # nothing reconnected on a healthy cluster, read-result capture
    # stays under its FIFO cap, and every replica applied every op
    assert len(r.node_stats) == cfg.n_replicas
    for ns in r.node_stats:
        assert ns["applied"] == cfg.total_ops
        assert not ns["recovering"] and not ns["isolated"]
        assert ns["read_results"] <= READ_RESULTS_CAP
        # broadcasts are encoded once for all their destinations
        assert 0 < ns["encodes"] < ns["messages"]
        assert ns["commit_log"] <= READ_RESULTS_CAP
        for ch in ns["channels"]:
            assert ch["queue_hwm"] <= ch["max_queue"]
            assert ch["dropped"] == 0
            # (queue_len may hold a trailing heartbeat enqueued between
            # the last drain and the SIGTERM dump — bounded, not empty)
            assert ch["queue_len"] <= ch["max_queue"]
            assert ch["reconnects"] == 0

    # served-path tracing: responders record a vote span per sampled
    # proposal, which joins to the coordinator's rounds, and every
    # replica samples its host on the cluster clock
    votes = [e for e in r.trace if e[1] == "vote"]
    assert votes
    for t_recv, _, responder, path, _, proposer, t_post in votes:
        assert path in ("fast", "slow")
        assert responder != proposer and t_post >= t_recv
    legs = analyze_events(r.trace).votes
    assert 0 < legs.count <= len(votes)
    assert legs.clock_faults == 0
    for ns in r.node_stats:
        lag, host = ns["loop_lag"], ns["host"]
        assert lag and all(len(row) == 2 for row in lag)
        assert len(host) >= 2 and all(len(row) == 4 for row in host)
        for rows in (lag, host):
            assert [row[0] for row in rows] == sorted(row[0] for row in rows)
        assert host[-1][1] >= host[0][1] and host[-1][2] >= host[0][2]
        assert host[-1][2] <= sum(ch["sent"] for ch in ns["channels"])
        assert sum(ch["wait_s"] for ch in ns["channels"]) > 0

    # the slow path's counters: whole-run totals in every replica's
    # stats, and rows sampled with the host rows on the cluster clock;
    # the leader counts every op committed on the slow path
    slow_committed = sum(v for k, v in counters.items()
                         if k.startswith("ops_committed_total")
                         and "slow" in k)
    assert slow_committed > 0
    assert sum(ns["slow_ops"] for ns in r.node_stats) == slow_committed
    for ns in r.node_stats:
        rows = ns["slow"]
        assert [row[0] for row in rows] == [row[0] for row in ns["host"]]
        assert all(len(row) == 5 for row in rows)
        totals = [ns["slow_instances"], ns["slow_ops"], ns["slow_hold_s"],
                  ns["slow_backlog"]]
        assert all(x <= total for x, total in zip(rows[-1][1:], totals))
        assert ns["slow_ops"] <= ns["slow_instances"] * cfg.batch_size
        assert ns["slow_hold_s"] >= 0 and ns["slow_backlog"] >= 0


def test_served_full_contention_merges_slow_instances():
    """Every op on one of 4 hot objects, so the leader's slow path does all
    the work with many batches queued behind its mutex: the history is
    linearizable, every replica applied each object's writes in the same
    order, and the leader's instances carry more than one client batch
    on average, so the served group commit engaged."""
    cfg = ClusterConfig(n_replicas=5, n_clients=3, total_ops=1200,
                        batch_size=4, max_inflight=8, p_hot=1.0,
                        p_common=0.0, seed=17, time_limit_s=45)
    r = run_served(cfg).result

    assert r.clients_done == cfg.n_clients
    assert r.committed_ops == cfg.total_ops
    ok, why = check_history_linearizable(r.history)
    assert ok, why

    assert len(r.node_stats) == cfg.n_replicas
    assert all(ns["applied"] == cfg.total_ops for ns in r.node_stats)
    assert len({ns["apply_digest"] for ns in r.node_stats}) == 1

    leaders = [ns for ns in r.node_stats if ns["slow_instances"]]
    assert len(leaders) == 1
    leader = leaders[0]
    assert leader["slow_ops"] > cfg.total_ops // 2
    assert leader["slow_ops"] > leader["slow_instances"] * cfg.batch_size


# ---------------------------------------------------------------------------
# crash + recovery over sockets
# ---------------------------------------------------------------------------

def test_served_crash_restart_recovers_over_sockets():
    """SIGKILL replica 0 mid-workload, restart it with --recover: the
    survivors reconnect (fresh port via the port file), state transfer
    catches the restarted replica up, and the client-observed history
    stays linearizable throughout."""
    cfg = ClusterConfig(n_replicas=5, n_clients=2, total_ops=2400,
                        batch_size=8, seed=13, time_limit_s=60,
                        trace=False)
    launcher = ClusterLauncher(cfg)
    launcher.start()
    try:
        launcher.start_clients()
        time.sleep(0.7)                    # let the workload get going
        launcher.kill_node(0)
        time.sleep(0.3)                    # clients retry around the hole
        launcher.restart_node(0)
        done = launcher.wait_clients()
        time.sleep(1.0)                    # grace: state transfer completes
    finally:
        launcher.stop()
    art = launcher.collect(done)
    r = art.result

    assert r.clients_done == cfg.n_clients
    assert r.committed_ops == cfg.total_ops
    ok, why = check_history_linearizable(r.history)
    assert ok, why

    stats = {ns["node"]: ns for ns in r.node_stats}
    assert set(stats) == set(range(cfg.n_replicas))
    # untraced: no spans, no host rows, no queue stamps
    assert r.trace == []
    for ns in stats.values():
        assert "loop_lag" not in ns and "host" not in ns
        assert all(ch["wait_s"] == 0.0 for ch in ns["channels"])
    # the restarted replica finished recovery and holds real state
    assert not stats[0]["recovering"]
    assert stats[0]["applied"] > 0
    # every survivor redialed node 0 after the crash
    for i in range(1, cfg.n_replicas):
        chan0 = next(c for c in stats[i]["channels"] if c["dst"] == 0)
        assert chan0["reconnects"] >= 1, (i, chan0)


# ---------------------------------------------------------------------------
# the mutation twin: reordering frames must fail the checker
# ---------------------------------------------------------------------------

def test_reorder_twin_fails_the_checker():
    """A transport that displaces frames past later ones on a peer link
    (breaking TCP's per-link FIFO) lets consecutive slow commits apply
    inverted at a follower, whose coordinated reads then return values
    rolled back several generations — a real-time cycle the checker
    must reject. If this ever starts passing, the capture pipeline has
    stopped seeing what replicas actually serve and cannot be trusted
    to validate the honest transport."""
    failed = False
    for seed in (1, 2, 3):
        cfg = ClusterConfig(n_replicas=5, n_clients=3, total_ops=600,
                            batch_size=1, max_inflight=1,
                            reads_fraction=0.35, p_hot=0.9, p_common=0.02,
                            n_hot=1, seed=seed, time_limit_s=60,
                            reorder=True, trace=False)
        r = run_served(cfg).result
        assert r.committed_ops == cfg.total_ops   # liveness holds: the
        # twin delays frames, it never drops them — only ordering breaks
        ok, _ = check_history_linearizable(r.history)
        if not ok:
            failed = True
            break
    assert failed, "reorder twin produced linearizable histories on " \
        "every seed — the mutation no longer bites; re-tune it"
