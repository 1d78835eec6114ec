"""Wire-layer tests: encoding round-trips, message framing + crc, loopback and
TCP messengers with policies, map codec round-trips (the dencoder analog)."""

import threading
import time

import pytest

from ceph_tpu.crush import build_two_level_map
from ceph_tpu.messages import (
    MOSDOp, MOSDOpReply, MOSDPing, MOSDECSubOpWrite, OSDOpField)
from ceph_tpu.messages.osd_msgs import OP_WRITE
from ceph_tpu.msg import Decoder, Encoder, EntityName, Message, Messenger
from ceph_tpu.msg.encoding import DecodeError
from ceph_tpu.msg.messenger import ConnectionPolicy, Dispatcher
from ceph_tpu.osd import OSDMap, PGPool
from ceph_tpu.osd.map_codec import decode_osdmap, encode_osdmap


def test_encoding_primitives_roundtrip():
    e = (Encoder().u8(255).u16(65535).u32(2**32 - 1).u64(2**64 - 1)
         .s32(-5).s64(-(2**62)).f64(1.5).str("héllo").bytes(b"\x00\x01")
         .list([1, 2, 3], lambda en, v: en.u32(v))
         .map({"a": 1, "b": 2}, lambda en, k: en.str(k),
              lambda en, v: en.u32(v)))
    d = Decoder(e.tobytes())
    assert d.u8() == 255 and d.u16() == 65535
    assert d.u32() == 2**32 - 1 and d.u64() == 2**64 - 1
    assert d.s32() == -5 and d.s64() == -(2**62)
    assert d.f64() == 1.5 and d.str() == "héllo" and d.bytes() == b"\x00\x01"
    assert d.list(lambda dd: dd.u32()) == [1, 2, 3]
    assert d.map(lambda dd: dd.str(), lambda dd: dd.u32()) == {"a": 1, "b": 2}
    assert d.remaining() == 0


def test_versioned_section_skips_future_fields():
    # a v2 encoder appends a field; a v1 decoder must skip it cleanly
    e = Encoder()
    e.versioned(2, 1, lambda b: (b.u32(7), b.str("future-field")))
    e.u32(99)  # data after the section

    d = Decoder(e.tobytes())
    val = d.versioned(1, lambda b, v: b.u32())
    assert val == 7
    assert d.u32() == 99

    # compat above ours must fail
    e2 = Encoder()
    e2.versioned(3, 3, lambda b: b.u32(1))
    with pytest.raises(DecodeError):
        Decoder(e2.tobytes()).versioned(1, lambda b, v: b.u32())


def test_message_frame_roundtrip_and_crc():
    op = MOSDOp(client_id=7, tid=42, pgid=(1, 9), oid="obj-1",
                ops=[OSDOpField(OP_WRITE, 0, 5, b"hello")], epoch=3)
    op.seq = 11
    data = op.encode()
    back = Message.decode(data)
    assert isinstance(back, MOSDOp)
    assert (back.client_id, back.tid, back.pgid, back.oid, back.epoch,
            back.seq) == (7, 42, (1, 9), "obj-1", 3, 11)
    assert back.ops[0].data == b"hello"
    # corrupt one payload byte -> crc failure
    bad = bytearray(data)
    bad[25] ^= 0xFF
    with pytest.raises(DecodeError):
        Message.decode(bytes(bad))


class _Collector(Dispatcher):
    def __init__(self):
        self.got = []
        self.resets = []
        self.event = threading.Event()

    def ms_dispatch(self, msg):
        self.got.append(msg)
        self.event.set()
        return True

    def ms_handle_reset(self, con):
        self.resets.append(con)


def test_loopback_messenger_roundtrip():
    a = Messenger.create(EntityName("client", 1), "loopback")
    b = Messenger.create(EntityName("osd", 0), "loopback")
    coll = _Collector()
    b.add_dispatcher_tail(coll)
    a.bind("a")
    b.bind("b")
    a.start()
    b.start()
    try:
        con = a.connect_to("b", EntityName("osd", 0))
        con.send_message(MOSDPing(from_osd=-1, op=MOSDPing.PING, stamp=1.0))
        assert coll.event.wait(2)
        msg = coll.got[0]
        assert isinstance(msg, MOSDPing)
        assert msg.connection.peer_name == EntityName("client", 1)
    finally:
        a.shutdown()
        b.shutdown()


def test_tcp_messenger_request_reply():
    server = Messenger.create(EntityName("osd", 3), "async")
    client = Messenger.create(EntityName("client", 9), "async")
    got_reply = _Collector()

    class Echo(Dispatcher):
        def ms_dispatch(self, msg):
            if isinstance(msg, MOSDOp):
                msg.connection.send_message(
                    MOSDOpReply(tid=msg.tid, result=0, epoch=msg.epoch))
                return True
            return False

    server.set_policy("client", ConnectionPolicy.lossy_client())
    server.add_dispatcher_tail(Echo())
    client.add_dispatcher_tail(got_reply)
    server.bind("127.0.0.1:0")
    server.start()
    client.start()
    try:
        con = client.connect_to(server.my_addr, EntityName("osd", 3))
        con.send_message(MOSDOp(client_id=9, tid=77, pgid=(1, 2), oid="x",
                                epoch=5))
        assert got_reply.event.wait(5)
        reply = got_reply.got[0]
        assert isinstance(reply, MOSDOpReply) and reply.tid == 77
    finally:
        client.shutdown()
        server.shutdown()


def test_tcp_many_messages_ordered():
    server = Messenger.create(EntityName("osd", 4), "async")
    client = Messenger.create(EntityName("client", 2), "async")
    coll = _Collector()
    server.add_dispatcher_tail(coll)
    server.bind("127.0.0.1:0")
    server.start()
    client.start()
    try:
        con = client.connect_to(server.my_addr, EntityName("osd", 4))
        n = 200
        for i in range(n):
            con.send_message(MOSDECSubOpWrite(
                reqid=(2, i), pgid=(1, 0), oid=f"o{i}", shard=i % 12,
                chunk=bytes([i % 256]) * 128))
        deadline = time.time() + 10
        while len(coll.got) < n and time.time() < deadline:
            time.sleep(0.01)
        assert len(coll.got) == n
        assert [m.reqid[1] for m in coll.got] == list(range(n))  # ordered
    finally:
        client.shutdown()
        server.shutdown()


def test_osdmap_codec_roundtrip():
    crush, _root, rule = build_two_level_map(4, 3)
    m = OSDMap(crush=crush)
    m.set_max_osd(12)
    for o in range(12):
        m.mark_up(o)
    m.mark_down(5)
    m.osd_primary_affinity[2] = 0x8000
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rule, pg_num=32)
    m.pools[2] = PGPool(pool_id=2, type=3, size=4, crush_rule=0, pg_num=16)
    m.pg_upmap[(1, 3)] = [0, 1, 2]
    m.pg_upmap_items[(1, 4)] = [(0, 7)]
    m.pg_temp[(1, 5)] = [2, 3, 4]
    m.primary_temp[(1, 5)] = 3
    m.epoch = 42

    back = decode_osdmap(encode_osdmap(m))
    assert back.epoch == 42 and back.max_osd == 12
    assert back.pools[1].pg_num == 32 and back.pools[2].is_erasure()
    assert back.pg_upmap[(1, 3)] == [0, 1, 2]
    assert back.pg_upmap_items[(1, 4)] == [(0, 7)]
    assert back.pg_temp[(1, 5)] == [2, 3, 4]
    assert back.primary_temp[(1, 5)] == 3
    # placement identical through the codec
    for pg in range(32):
        assert back.pg_to_up_acting_osds(1, pg) == m.pg_to_up_acting_osds(1, pg)


def test_event_stack_thread_count():
    """The event-driven stack costs 2 messenger threads per daemon
    regardless of connection count (the epoll-AsyncMessenger property
    the threaded stack lacks: it spawns ~2 threads per connection)."""
    import threading

    from ceph_tpu.tools.vstart import MiniCluster

    before = {t.name for t in threading.enumerate()}
    c = MiniCluster(n_osds=10, ms_type="async", heartbeats=True).start()
    try:
        c.wait_for_osd_count(10)
        client = c.client()
        pool = c.create_pool(client, pg_num=16, size=3)
        io = client.open_ioctx(pool)
        for i in range(10):
            io.write_full(f"o{i}", b"x" * 512)
        # 10 osds + 1 mon + 1 client = 12 messengers; heartbeats mesh
        # the osds all-to-all, so connections >> messengers
        ms_threads = [t.name for t in threading.enumerate()
                      if t.name.startswith("ms-") and t.name not in before]
        n_daemons = 12
        assert len(ms_threads) <= 2 * n_daemons, ms_threads
        conns = sum(len(o.msgr._conns) for o in c.osds.values())
        assert conns > 2 * 10, f"expected a meshed cluster, got {conns}"
    finally:
        c.stop()


def test_event_and_threaded_stacks_interoperate():
    """Same v1-lite wire protocol: a threaded-stack client talks to an
    event-stack server and vice versa."""
    import time as _t

    from ceph_tpu.messages import MOSDPing
    from ceph_tpu.msg.messenger import Dispatcher, EntityName, Messenger

    for srv_type, cli_type in (("async", "threaded"),
                               ("threaded", "async")):
        got = []

        class D(Dispatcher):
            def ms_dispatch(self, msg):
                got.append(msg)
                return True

        srv = Messenger.create(EntityName("osd", 7), srv_type)
        srv.set_auth(b"sharedkey")
        srv.add_dispatcher_tail(D())
        srv.bind("127.0.0.1:0")
        srv.start()
        cli = Messenger.create(EntityName("client", 8), cli_type)
        cli.set_auth(b"sharedkey")
        cli.start()
        con = cli.connect_to(srv.my_addr, EntityName("osd", 7))
        for _ in range(3):
            con.send_message(MOSDPing(from_osd=8, stamp=_t.time()))
        deadline = _t.time() + 5
        while len(got) < 3 and _t.time() < deadline:
            _t.sleep(0.02)
        assert len(got) == 3, f"{srv_type}<-{cli_type}: got {len(got)}"
        cli.shutdown()
        srv.shutdown()


# -- which thread writes a frame (event stack) --------------------------------

def _wait_for(cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.005)
    return cond()


def _subwrite(sender: int, i: int, chunk: bytes = b"c" * 100):
    return MOSDECSubOpWrite(reqid=(sender, i), pgid=(1, 0),
                            oid=f"o{sender}.{i}", shard=i % 12, chunk=chunk)


class _EventPair:
    """An event-stack server and a client dialing it, both collecting."""

    def __init__(self, client_policy: ConnectionPolicy | None = None,
                 prepare=None):
        self.server = Messenger.create(EntityName("osd", 5), "async")
        self.client = Messenger.create(EntityName("client", 6), "async")
        if prepare is not None:
            prepare(self.server)
            prepare(self.client)
        if client_policy is not None:
            self.client.set_policy("osd", client_policy)
        self.got, self.client_got = _Collector(), _Collector()
        self.server.add_dispatcher_tail(self.got)
        self.client.add_dispatcher_tail(self.client_got)
        self.server.bind("127.0.0.1:0")
        self.server.start()
        self.client.start()
        self.con = None

    def connect(self):
        self.con = self.client.connect_to(self.server.my_addr,
                                          EntityName("osd", 5))
        return self.con

    def settle(self):
        """Dial, and send until one message has gone all the way: the
        handshake's bytes are flushed and both queues are empty."""
        con = self.con or self.connect()
        con.send_message(_subwrite(0, 0))
        assert _wait_for(lambda: self.sent("msg_send") == 1
                         and len(self.got.got) == 1)
        assert not con.out_frames and not con.backlog
        return con

    def sent(self, key: str) -> int:
        return self.client.perf.value(key)

    def ids(self):
        return [m.reqid for m in self.got.got]

    def close(self):
        self.client.shutdown()
        self.server.shutdown()


def test_event_settled_connection_is_written_by_the_sender():
    p = _EventPair()
    try:
        con = p.settle()
        base = {k: p.sent(k) for k in ("msg_send", "msg_send_inline",
                                       "msg_send_queued")}
        n = 64
        for i in range(1, n + 1):
            con.send_message(_subwrite(1, i))
        assert _wait_for(lambda: len(p.got.got) == n + 1)
        assert p.ids()[1:] == [(1, i) for i in range(1, n + 1)]
        assert p.sent("msg_send_inline") - base["msg_send_inline"] == n
        assert p.sent("msg_send_queued") == base["msg_send_queued"]
        assert p.sent("msg_send") - base["msg_send"] == n
    finally:
        p.close()


def test_event_senders_interleave_large_and_small_frames():
    """Frames larger than the socket buffer take the partial-write
    road (the sender writes what fits, the loop the rest) while other
    threads keep sending on the same connection: nothing overtakes
    within a sender and every byte arrives."""
    import socket
    import sys

    p = _EventPair()
    old_switch = sys.getswitchinterval()
    try:
        con = p.settle()
        con.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 64 << 10)
        senders, per = 6, 12
        sys.setswitchinterval(1e-5)

        def chunk(s: int, i: int) -> bytes:
            size = (1 << 20) if i % 2 == 0 else 100
            return bytes([(s * per + i) % 251]) * size

        def run(s: int):
            for i in range(per):
                con.send_message(_subwrite(s, i, chunk(s, i)))

        threads = [threading.Thread(target=run, args=(s,))
                   for s in range(1, senders + 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        assert _wait_for(lambda: len(p.got.got) == senders * per + 1, 60)
        for s in range(1, senders + 1):
            mine = [m for m in p.got.got if m.reqid[0] == s]
            assert [m.reqid[1] for m in mine] == list(range(per))
            assert all(m.chunk == chunk(s, m.reqid[1]) for m in mine)
        assert p.sent("msg_send_queued") > 1        # 1 = the dial's own
        assert (p.sent("msg_send_inline") + p.sent("msg_send_queued")
                == p.sent("msg_send") == senders * per + 1)
    finally:
        sys.setswitchinterval(old_switch)
        p.close()


@pytest.mark.parametrize("road", ["fast", "slow"])
@pytest.mark.parametrize("lossy", [False, True],
                         ids=["stateful", "lossy"])
def test_event_peer_socket_closed_mid_stream(lossy, road):
    """The peer's socket dies while the client keeps sending.  On the
    fast road the sender's own send() meets the dead socket, on the slow
    road the loop thread's does; either way a stateful dialing
    connection redials and delivers the unwritten tail in order, once,
    and a lossy one resets once and delivers nothing more."""
    policy = (ConnectionPolicy(lossy=True, resend_on_reconnect=False)
              if lossy else None)
    p = _EventPair(client_policy=policy)
    gate, stalled = threading.Event(), threading.Event()
    try:
        con = p.settle()
        # hold the client's loop thread so that only senders touch the
        # socket until the gate opens
        p.client.defer(lambda: (stalled.set(), gate.wait(20)))
        assert stalled.wait(5)
        acc = p.server._conns[f"accepted:{p.client.my_name}"]
        acc.mark_down()
        assert _wait_for(lambda: acc.sock is None)
        # TCP takes one write after the peer's close and answers it with
        # a reset; from then on a write fails
        con.send_message(_subwrite(9, 0))
        time.sleep(0.1)
        inline0 = p.sent("msg_send_inline")
        tail = [(2, i) for i in range(8)]

        def send_tail():
            for r in tail:
                con.send_message(_subwrite(*r))

        if road == "fast":
            send_tail()
        else:
            # another writer holds the connection: senders queue
            with con._wlock:
                t = threading.Thread(target=send_tail)
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
        assert [m.reqid for m in con.backlog] == tail
        assert p.sent("msg_send_inline") == inline0
        gate.set()
        if lossy:
            assert _wait_for(lambda: len(p.client_got.resets) == 1)
            assert not con.is_connected()
            con.send_message(_subwrite(2, 99))
            time.sleep(0.3)
            assert p.client_got.resets == [con]
            assert not [r for r in p.ids() if r[0] == 2]
        else:
            assert _wait_for(
                lambda: [r for r in p.ids() if r[0] == 2] == tail), p.ids()
            assert con.is_connected() and not p.client_got.resets
            # the redialed connection settles back onto the fast road
            assert _wait_for(lambda: not con.out_frames and not con.backlog)
            inline1 = p.sent("msg_send_inline")
            con.send_message(_subwrite(3, 0))
            assert _wait_for(lambda: p.ids()[-1] == (3, 0))
            assert p.sent("msg_send_inline") == inline1 + 1
    finally:
        gate.set()
        p.close()


def test_event_send_before_handshake_takes_the_loop():
    p = _EventPair()
    try:
        con = p.connect()
        con.send_message(_subwrite(1, 0))     # still dialing
        assert _wait_for(lambda: p.ids() == [(1, 0)])
        assert _wait_for(lambda: p.sent("msg_send_queued") == 1)
        assert p.sent("msg_send_inline") == 0
    finally:
        p.close()


def test_event_ici_token_connection_frames_on_the_loop(monkeypatch):
    """Framing for a peer that negotiated FEATURE_ICI_TOKENS may stage
    a device buffer, which can wait on the device: never on the
    sender's thread."""
    from ceph_tpu.msg.event_tcp import EventConnection
    from ceph_tpu.msg.features import FEATURE_ICI_TOKENS

    framed_on = []
    frame = EventConnection._frame

    def spy(self, msg):
        framed_on.append(threading.current_thread().name)
        return frame(self, msg)

    monkeypatch.setattr(EventConnection, "_frame", spy)

    def as_ici_wire(m):
        m.ici_wire = True
        m.local_features |= FEATURE_ICI_TOKENS

    p = _EventPair(prepare=as_ici_wire)
    try:
        con = p.settle()
        assert con.features & FEATURE_ICI_TOKENS
        for i in range(1, 9):
            con.send_message(_subwrite(1, i))
        assert _wait_for(lambda: len(p.got.got) == 9)
        assert p.ids()[1:] == [(1, i) for i in range(1, 9)]
        assert p.sent("msg_send_inline") == 0
        assert p.sent("msg_send_queued") == 9
        assert set(framed_on) == {f"ms-ev:{p.client.my_name}"}
    finally:
        p.close()
