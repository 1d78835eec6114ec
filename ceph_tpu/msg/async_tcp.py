"""TCP messenger stack.

API-equivalent to the reference's default AsyncMessenger (src/msg/async/);
internally thread-per-connection like its SimpleMessenger sibling — the
portable structure for a multi-process vstart harness.  Protocol v1-lite
(async/Protocol.h:103 analog):

    banner          b"ceph_tpu v1\\n" both ways
    announce        length-prefixed str(entity_name) both ways
    auth            [u8 mode][16B nonce] both ways, then an HMAC-SHA256
                    proof over the peer's fresh nonce (cephx-lite: the
                    src/auth/cephx challenge shape with a shared cluster
                    key standing in for the ticket infrastructure; fresh
                    nonces per connection give replay protection)
    compression     [u8 offered-mode] both ways; effective mode is the
                    min (0=off, 1=zlib) — msgr2 on-wire compression
                    negotiation (src/msg/async/compression_*)
    frames          [u32 length][u8 comp][Message.encode() bytes or its
                    zlib stream]   (crc inside the message)

Stateful policies reconnect on send failure and resend the queued backlog;
lossy connections drop and notify ms_handle_reset (msg/Policy.h semantics).
Hardening: frames above the policy byte cap are rejected, total in-dispatch
bytes ride a Throttle (msg/Policy.h throttler analog), and dead accepted
connections are reaped instead of leaking on reconnect storms.
"""

from __future__ import annotations

import hashlib
import hmac
import os
import queue
import socket
import struct
import threading
import time
import zlib

from ceph_tpu.common import lockdep, tracing

from .message import Message
from .messenger import Connection, ConnectionPolicy, EntityName, Messenger

BANNER = b"ceph_tpu v1\n"
_LEN = struct.Struct("<I")

AUTH_NONE = 0
AUTH_CEPHX = 1

#: largest acceptable frame (DoS guard; the reference uses policy
#: throttles plus osd_max_write_size-scale caps)
MAX_FRAME = 256 << 20


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


from ceph_tpu.msg.features import FEAT_FRAME as _FEAT

#: on-wire compression modes (msgr2 compression negotiation analog)
COMP_NONE = 0
COMP_ZLIB = 1

#: frames below this many bytes ride uncompressed (header-dominated)
COMP_THRESHOLD = 1024


def _handshake(sock: socket.socket, my_name: EntityName,
               auth_key: bytes | None,
               auth_required: bool,
               comp_mode: int = COMP_NONE,
               cephx=None, accepted: bool = False,
               peer_type: str = "",
               features: int | None = None,
               required_fn=None,
               ) -> tuple[EntityName, int, str | None, int]:
    from ceph_tpu.auth.handshake import (
        AUTH_CEPHX_ENTITY, AUTH_CEPHX_TICKET, accept_ticket,
        entity_proof, proof as sess_proof, ticket_for)
    from ceph_tpu.msg.features import (
        FEATURE_WIRE_COMPRESSION, REQUIRED_DEFAULT, SUPPORTED_FEATURES,
        check_compat)
    if features is None:
        features = SUPPORTED_FEATURES
    sock.sendall(BANNER)
    got = _read_exact(sock, len(BANNER))
    if got != BANNER:
        raise ConnectionError(f"bad banner {got!r}")
    me = str(my_name).encode()
    sock.sendall(_LEN.pack(len(me)) + me)
    plen = _LEN.unpack(_read_exact(sock, _LEN.size))[0]
    if plen > 256:
        raise ConnectionError("oversized name frame")
    peer = EntityName.parse(_read_exact(sock, plen).decode())

    # feature negotiation (ceph_features.h / Policy::features_required):
    # both advertise (supported, required-of-this-peer-type); unmet
    # requirements reject the session here, before auth or any message
    my_req = (required_fn(peer.type) if required_fn
              else REQUIRED_DEFAULT)
    sock.sendall(_FEAT.pack(features, my_req))
    pf, pr = _FEAT.unpack(_read_exact(sock, _FEAT.size))
    common = check_compat(str(peer), features, my_req, pf, pr)

    # auth phase: mode + fresh nonce both ways, then mutual proofs
    if cephx is not None:
        my_mode = (cephx.acceptor_mode() if accepted
                   else cephx.initiator_mode(peer_type or peer.type))
    else:
        my_mode = AUTH_CEPHX if auth_key else AUTH_NONE
    my_nonce = os.urandom(16)
    sock.sendall(bytes([my_mode]) + my_nonce)
    hdr = _read_exact(sock, 17)
    peer_mode, peer_nonce = hdr[0], hdr[1:]
    auth_entity: str | None = None
    if cephx is not None:
        if not accepted:
            if my_mode == AUTH_CEPHX_TICKET:
                t = ticket_for(cephx, peer_type or peer.type)
                if t is None:
                    raise ConnectionError(
                        f"no ticket for {peer_type or peer.type}")
                blob = t.blob()
                sock.sendall(_LEN.pack(len(blob)) + blob
                             + sess_proof(t.session_key, peer_nonce,
                                          t.entity))
                skey = t.session_key
            elif my_mode == AUTH_CEPHX_ENTITY:
                ent = cephx.entity.encode()
                sock.sendall(_LEN.pack(len(ent)) + ent
                             + entity_proof(cephx.key, peer_nonce,
                                            cephx.entity))
                skey = cephx.key.encode()
            else:
                skey = None
            if skey is not None:
                peer_proof = _read_exact(sock, 32)
                want = hmac.new(skey, my_nonce + str(peer).encode(),
                                hashlib.sha256).digest()
                if not hmac.compare_digest(peer_proof, want):
                    raise ConnectionError(
                        f"peer {peer} failed cephx proof")
        else:
            if peer_mode in (AUTH_CEPHX_TICKET, AUTH_CEPHX_ENTITY):
                clen = _LEN.unpack(_read_exact(sock, _LEN.size))[0]
                if clen > 4096:
                    raise ConnectionError("oversized auth credential")
                cred = _read_exact(sock, clen)
                if peer_mode == AUTH_CEPHX_TICKET:
                    got2 = accept_ticket(cephx, cred)
                    if got2 is None:
                        raise ConnectionError(
                            f"peer {peer} invalid/expired ticket")
                    auth_entity, skey = got2
                else:
                    auth_entity = cred.decode()
                    key = (cephx.auth_lookup(auth_entity)
                           if cephx.auth_lookup else
                           (cephx.key if auth_entity == cephx.entity
                            else None))
                    if key is None:
                        raise ConnectionError(
                            f"unknown or revoked entity {auth_entity!r}")
                    skey = key.encode()
                peer_proof = _read_exact(sock, 32)
                want = hmac.new(skey,
                                my_nonce + auth_entity.encode(),
                                hashlib.sha256).digest()
                if not hmac.compare_digest(peer_proof, want):
                    raise ConnectionError(
                        f"peer {peer} failed cephx proof")
                sock.sendall(hmac.new(skey, peer_nonce + me,
                                      hashlib.sha256).digest())
            elif cephx.required:
                raise ConnectionError(
                    f"peer {peer} auth mode {peer_mode} not acceptable")
    else:
        if auth_required and peer_mode != AUTH_CEPHX:
            raise ConnectionError(f"peer {peer} refused authentication")
        if my_mode == AUTH_CEPHX and peer_mode == AUTH_CEPHX:
            # prove I hold the key over the PEER's nonce (never my own:
            # fresh peer nonces are the replay protection)
            proof = hmac.new(auth_key, peer_nonce + me,
                             hashlib.sha256).digest()
            sock.sendall(proof)
            peer_proof = _read_exact(sock, 32)
            want = hmac.new(auth_key, my_nonce + str(peer).encode(),
                            hashlib.sha256).digest()
            if not hmac.compare_digest(peer_proof, want):
                raise ConnectionError(
                    f"peer {peer} failed authentication")
    # compression negotiation: both offer; min wins (off beats on).
    # DEGRADE path: a peer without the wire-compression feature gets
    # uncompressed frames regardless of offers
    if not common & FEATURE_WIRE_COMPRESSION:
        comp_mode = COMP_NONE
    sock.sendall(bytes([comp_mode]))
    peer_comp = _read_exact(sock, 1)[0]
    return peer, min(comp_mode, peer_comp), auth_entity, common


class TcpConnection(Connection):
    def __init__(self, messenger: "AsyncMessenger", peer_addr: str,
                 peer_name: EntityName | None, policy: ConnectionPolicy,
                 sock: socket.socket | None = None, accepted: bool = False,
                 comp: int = COMP_NONE):
        super().__init__(messenger, peer_addr)
        self.peer_name = peer_name
        self.policy = policy
        # accepted sessions cannot dial the peer back; on failure they drop
        # and wait for the initiator to reconnect (the reference server side
        # replaces the Connection on re-accept)
        self.accepted = accepted
        #: negotiated on-wire compression mode for this session
        self.comp = comp
        self._sock = sock
        self._sendq: queue.Queue = queue.Queue()
        self._down = False
        self._lock = lockdep.make_lock("TcpConnection::lock")
        self._writer = threading.Thread(target=self._write_loop, daemon=True)
        self._writer.start()
        if sock is not None:
            self._start_reader()

    # -- public ---------------------------------------------------------------

    def send_message(self, msg: Message) -> None:
        if self._down:
            return
        from ceph_tpu.msg.features import FEATURE_TRACE, FEATURE_TRACE_SPANS
        if self.features & FEATURE_TRACE:
            # NEVER emit the trace header extension against a peer
            # that did not negotiate it (features.py's invariant)
            tracing.stamp(msg, str(self.messenger.my_name))
            if not self.features & FEATURE_TRACE_SPANS:
                # peer predates the v2 (trace_id, parent_span_id)
                # extension: fall back to the v1 bare-u64 frame
                msg.parent_span_id = 0
        self._sendq.put(msg)

    def mark_down(self) -> None:
        self._down = True
        self._sendq.put(None)
        with self._lock:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None

    def is_connected(self) -> bool:
        with self._lock:
            return self._sock is not None and not self._down

    # -- internals ------------------------------------------------------------

    def _start_reader(self) -> None:
        threading.Thread(target=self._read_loop, daemon=True).start()

    def _connect(self) -> None:
        host, port = self.peer_addr.rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=10)
        m = self.messenger
        # keep the dial timeout through the handshake: a stalled or
        # malicious peer must not wedge the writer thread forever
        peer, self.comp, _ent, self.features = _handshake(
            s, m.my_name, m.auth_key, m.auth_required, m.comp_mode,
            cephx=m.cephx, accepted=False,
            peer_type=self.peer_name.type if self.peer_name else "",
            features=m.local_features, required_fn=m.required_for)
        s.settimeout(None)
        with self._lock:
            self._sock = s
        if self.peer_name is None:
            self.peer_name = peer
        self._start_reader()

    def _frame(self, msg: Message) -> bytes:
        """Encode + (maybe) compress one message into a wire frame."""
        payload = msg.encode()
        comp = COMP_NONE
        if self.comp == COMP_ZLIB and len(payload) >= COMP_THRESHOLD:
            z = zlib.compress(payload, 1)
            if len(z) < len(payload):
                comp, payload = COMP_ZLIB, z
        return _LEN.pack(len(payload)) + bytes([comp]) + payload

    def _write_loop(self) -> None:
        backlog: list[Message] = []
        while not self._down:
            item = self._sendq.get()
            if item is None:
                return
            backlog.append(item)
            while backlog and not self._down:
                try:
                    with self._lock:
                        sock = self._sock
                    if sock is None:
                        self._connect()
                        with self._lock:
                            sock = self._sock
                    if sock is None:
                        # the reader nulled it already (e.g. the peer
                        # rejected us right after the handshake)
                        raise OSError("connection lost before write")
                    # frame at send time: the negotiated compression can
                    # change across a reconnect
                    frame = self._frame(backlog[0])
                    sock.sendall(frame)
                    self.messenger.count_sent(len(frame))
                    tracing.sent(backlog.pop(0))
                except OSError:
                    with self._lock:
                        if self._sock is not None:
                            try:
                                self._sock.close()
                            except OSError:
                                pass
                            self._sock = None
                    if self.policy.lossy or self.accepted:
                        self._down = True
                        self.messenger.notify_reset(self)
                        return
                    if not self.policy.resend_on_reconnect:
                        backlog.clear()
                    time.sleep(0.1)  # reconnect backoff

    def _read_loop(self) -> None:
        from ceph_tpu.common.logging import get_logger
        throttle = self.messenger.dispatch_throttle
        try:
            while not self._down:
                with self._lock:
                    sock = self._sock
                if sock is None:
                    return
                frame_len = _LEN.unpack(_read_exact(sock, _LEN.size))[0]
                if frame_len > MAX_FRAME:
                    raise ConnectionError(
                        f"oversized frame ({frame_len} bytes) from "
                        f"{self.peer_name}")
                comp = _read_exact(sock, 1)[0]
                # policy byte throttle BEFORE buffering the payload:
                # acquiring after the read would leave buffered bytes
                # unbounded (msg/Policy.h reads under the throttle)
                charged = min(frame_len, throttle.max_amount)
                throttled = throttle.get(charged)
                data = _read_exact(sock, frame_len)
                if comp == COMP_ZLIB:
                    # bounded inflate: a hostile stream must not balloon
                    # past the frame cap (zlib-bomb guard)
                    d = zlib.decompressobj()
                    data = d.decompress(data, MAX_FRAME)
                    if d.unconsumed_tail:
                        raise ConnectionError(
                            f"decompressed frame exceeds cap from "
                            f"{self.peer_name}")
                    # the buffered-bytes bound must cover the INFLATED
                    # size, not the wire size, or zlib frames bypass it
                    # by the compression ratio
                    if throttled and len(data) > frame_len:
                        extra = min(len(data) - frame_len,
                                    throttle.max_amount - charged)
                        throttle.get(extra)
                        charged += extra
                try:
                    # a bad frame or handler bug must not kill the reader
                    try:
                        msg = Message.decode(data)
                        # on-wire size (header + possibly-compressed
                        # payload): matches the sender's count_sent
                        msg.wire_bytes = _LEN.size + 1 + frame_len
                        msg.connection = self
                        self.messenger.deliver(msg)
                    except Exception:
                        get_logger("ms").exception(
                            "%s: dispatch failed for frame from %s",
                            self.messenger.my_name, self.peer_name)
                finally:
                    if throttled:
                        throttle.put(charged)
        except (ConnectionError, OSError):
            with self._lock:
                self._sock = None
            if not self._down:
                if self.policy.lossy:
                    self._down = True
                self.messenger.notify_reset(self)
            self.messenger.reap(self)


class AsyncMessenger(Messenger):
    is_wire = True

    #: cap on bytes concurrently in dispatch (policy throttler analog)
    DISPATCH_THROTTLE_BYTES = 512 << 20

    def __init__(self, name: EntityName):
        super().__init__(name)
        self._listener: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._conns: dict[str, TcpConnection] = {}
        self._stop = False
        self.auth_key: bytes | None = None
        self.auth_required = False
        #: per-entity cephx config; supersedes the shared-key handshake
        self.cephx = None
        self.comp_mode = COMP_NONE
        from ceph_tpu.common.throttle import Throttle
        self.dispatch_throttle = Throttle(
            f"msgr-dispatch:{name}", self.DISPATCH_THROTTLE_BYTES)

    def set_compression(self, mode: str | int) -> None:
        """Offer on-wire compression (both peers must offer; min wins):
        "zlib" or "none" (ms_compress_mode analog)."""
        if isinstance(mode, str):
            mode = {"none": COMP_NONE, "zlib": COMP_ZLIB}[mode]
        self.comp_mode = int(mode)

    def set_auth(self, key: bytes | str | None,
                 required: bool = True) -> None:
        """Enable cephx-lite: all connections prove possession of the
        shared cluster key during the handshake; with required=True an
        un-keyed peer is rejected."""
        if isinstance(key, str):
            key = key.encode()
        self.auth_key = key
        self.auth_required = bool(key) and required

    def set_auth_cephx(self, config) -> None:
        self.cephx = config

    def reap(self, con: "TcpConnection") -> None:
        """Drop a dead connection from the table (reconnect storms must
        not accumulate dead accepted sessions)."""
        if not con._down and not con.accepted:
            return   # dialing connections self-heal; keep them
        with self._lock:
            for key, c in list(self._conns.items()):
                if c is con:
                    del self._conns[key]

    def bind(self, addr: str) -> None:
        host, port = addr.rsplit(":", 1)
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, int(port)))
        s.listen(64)
        self.my_addr = f"{host}:{s.getsockname()[1]}"  # resolves port 0
        self._listener = s

    def start(self) -> None:
        if self._listener is None:
            return

        def accept_loop():
            while not self._stop:
                try:
                    sock, _ = self._listener.accept()
                except OSError:
                    return
                threading.Thread(target=self._accept_one, args=(sock,),
                                 daemon=True).start()

        self._accept_thread = threading.Thread(target=accept_loop, daemon=True)
        self._accept_thread.start()

    def _accept_one(self, sock: socket.socket) -> None:
        if self._stop:
            sock.close()
            return
        try:
            # handshake-phase timeout: an unauthenticated peer that
            # stalls mid-handshake must not leak a thread + fd
            sock.settimeout(10)
            peer, comp, auth_entity, feat = _handshake(
                sock, self.my_name, self.auth_key, self.auth_required,
                self.comp_mode, cephx=self.cephx, accepted=True,
                features=self.local_features,
                required_fn=self.required_for)
            sock.settimeout(None)
        except (ConnectionError, OSError):
            sock.close()
            return
        policy = self.policy_for(peer.type)
        con = TcpConnection(self, f"{sock.getpeername()[0]}:0", peer,
                            policy, sock=sock, accepted=True, comp=comp)
        con.auth_entity = auth_entity
        con.features = feat
        with self._lock:
            if self._stop:
                # raced shutdown(): it already swept _conns — a session
                # registered now would live on as a zombie responder
                stop = True
            else:
                stop = False
                old = self._conns.get(f"accepted:{peer}")
                self._conns[f"accepted:{peer}"] = con
        if stop:
            con.mark_down()
            return
        if old is not None:
            old.mark_down()   # reap the replaced session

    def shutdown(self) -> None:
        self._stop = True
        if self._listener is not None:
            self._listener.close()
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:
            c.mark_down()

    def connect_to(self, addr: str, peer_name: EntityName) -> Connection:
        key = f"{addr}/{peer_name}"
        with self._lock:
            con = self._conns.get(key)
            # keep a live-or-dialing connection: its writer thread owns a
            # backlog and self-heals stateful sessions.  Replacing a con
            # that is merely mid-dial would orphan that backlog — queued
            # messages black-hole while the caller talks to the new con
            # (and each redial storms the peer's accepted-session table)
            if con is not None and not con._down:
                return con
            policy = self.policy_for(peer_name.type)
            con = TcpConnection(self, addr, peer_name, policy)
            self._conns[key] = con
            return con
