"""Concrete message types (see package docstring for the reference mapping).

Type ids follow the reference's include/msgr.h numbering where one exists
(MSG_OSD_OP=42, MSG_OSD_OPREPLY=43, MSG_OSD_PING=70, ...), so a wire dump is
recognizable to someone who knows the reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ceph_tpu.msg.encoding import Decoder, Encoder
from ceph_tpu.msg.message import Message, register_message

# op codes (rados op subset; include/rados.h CEPH_OSD_OP_*)
OP_READ = 1
OP_WRITE = 2
OP_WRITEFULL = 3
OP_DELETE = 4
OP_STAT = 5
OP_OMAP_GET = 6
OP_OMAP_SET = 7
OP_WATCH = 8          # register this client for notifies on the object
OP_UNWATCH = 9
OP_NOTIFY = 10        # fan a payload out to every watcher, wait for acks
OP_CALL = 11          # in-OSD object class method (cls\0method\0input)
OP_OMAP_RMKEYS = 12   # remove omap keys (Encoder str list in data)
OP_PGLS = 13          # list a PG's logical objects (rados ls / pgls)


@dataclass
class OSDOpField:
    """One sub-op of a client op (OSDOp in osd_types.h)."""

    op: int
    offset: int = 0
    length: int = 0
    data: bytes = b""

    def encode(self, enc: Encoder) -> None:
        enc.u8(self.op).u64(self.offset).u64(self.length).bytes(self.data)

    @staticmethod
    def decode(dec: Decoder) -> "OSDOpField":
        return OSDOpField(op=dec.u8(), offset=dec.u64(), length=dec.u64(),
                          data=dec.bytes())


def _enc_pgid(enc: Encoder, pgid: tuple[int, int]) -> None:
    enc.s64(pgid[0]).u32(pgid[1])


def _dec_pgid(dec: Decoder) -> tuple[int, int]:
    return (dec.s64(), dec.u32())


@register_message
class MOSDOp(Message):
    TYPE = 42  # MSG_OSD_OP
    HEAD_VERSION = 4       # v4: dmclock QoS tags (FEATURE_QOS_TAGS)

    def __init__(self, client_id: int = 0, tid: int = 0,
                 pgid: tuple[int, int] = (0, 0), oid: str = "",
                 ops: list[OSDOpField] | None = None, epoch: int = 0,
                 snapid: int = 0, write_snapc: int = 0,
                 qos_tenant: str = "", qos_delta: int = 1,
                 qos_rho: int = 1):
        super().__init__()
        self.client_id = client_id
        self.tid = tid
        self.pgid = pgid
        self.oid = oid
        self.ops = ops or []
        self.epoch = epoch
        self.snapid = snapid    # v2: read as-of this pool snapshot
        #: v3: pool snap_seq in the WRITER's osdmap (the SnapContext the
        #: reference carries in every MOSDOp, src/messages/MOSDOp.h
        #: snapc) — the OSD clones against max(this, its own map), so a
        #: writer that learned of a snapshot before the serving OSD did
        #: still gets copy-on-write
        self.write_snapc = write_snapc
        #: v4 QoS extension (behind FEATURE_QOS_TAGS; old peers skip
        #: the trailing fields and schedule untagged): the tenant lane
        #: this op bills to (RGW stamps the authenticated tenant; empty
        #: = per-client lane), and the dmClock (delta, rho) pair from
        #: the client's ServiceTracker — completions anywhere / in
        #: reservation phase since the last op to THIS osd — that make
        #: reservations and limits hold cluster-wide
        self.qos_tenant = qos_tenant
        self.qos_delta = qos_delta
        self.qos_rho = qos_rho

    def encode_payload(self, enc):
        enc.versioned(4, 1, lambda e: (
            e.u64(self.client_id), e.u64(self.tid), _enc_pgid(e, self.pgid),
            e.str(self.oid), e.u32(self.epoch),
            e.list(self.ops, lambda e2, op: op.encode(e2)),
            e.u64(self.snapid), e.u64(self.write_snapc),
            e.str(self.qos_tenant), e.u32(self.qos_delta),
            e.u32(self.qos_rho)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.client_id = d.u64()
            self.tid = d.u64()
            self.pgid = _dec_pgid(d)
            self.oid = d.str()
            self.epoch = d.u32()
            self.ops = d.list(OSDOpField.decode)
            self.snapid = d.u64() if v >= 2 else 0
            self.write_snapc = d.u64() if v >= 3 else 0
            if v >= 4:
                self.qos_tenant = d.str()
                self.qos_delta = d.u32()
                self.qos_rho = d.u32()
            else:   # old peer: untagged mClock increments
                self.qos_tenant = ""
                self.qos_delta = 1
                self.qos_rho = 1
        dec.versioned(4, body)


@register_message
class MOSDOpReply(Message):
    TYPE = 43  # MSG_OSD_OPREPLY
    HEAD_VERSION = 2       # v2: dmclock phase-served echo

    def __init__(self, tid: int = 0, result: int = 0, epoch: int = 0,
                 ops: list[OSDOpField] | None = None,
                 qos_phase: int = 0):
        super().__init__()
        self.tid = tid
        self.result = result
        self.epoch = epoch
        self.ops = ops or []   # read results travel back in op fields
        #: v2: which dmclock phase served the op (qos.dmclock.PHASE_*;
        #: 0 = unscheduled/old peer) — the client's ServiceTracker
        #: counts reservation-phase completions (rho) from this
        self.qos_phase = qos_phase

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            e.u64(self.tid), e.s32(self.result), e.u32(self.epoch),
            e.list(self.ops, lambda e2, op: op.encode(e2)),
            e.u8(self.qos_phase)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.tid = d.u64()
            self.result = d.s32()
            self.epoch = d.u32()
            self.ops = d.list(OSDOpField.decode)
            self.qos_phase = d.u8() if v >= 2 else 0
        dec.versioned(2, body)


@register_message
class MOSDRepOp(Message):
    TYPE = 112  # MSG_OSD_REPOP

    def __init__(self, reqid: tuple[int, int] = (0, 0),
                 pgid: tuple[int, int] = (0, 0), oid: str = "",
                 txn: bytes = b"", pg_version: tuple[int, int] = (0, 0),
                 entry: bytes = b""):
        super().__init__()
        self.reqid = reqid          # (client_id, tid)
        self.pgid = pgid
        self.oid = oid
        self.txn = txn              # encoded ObjectStore transaction
        self.pg_version = pg_version
        self.entry = entry          # encoded pg LogEntry (v2+)

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]),
            _enc_pgid(e, self.pgid), e.str(self.oid), e.bytes(self.txn),
            e.u32(self.pg_version[0]), e.u64(self.pg_version[1]),
            e.bytes(self.entry)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.pgid = _dec_pgid(d)
            self.oid = d.str()
            self.txn = d.bytes()
            self.pg_version = (d.u32(), d.u64())
            if v >= 2:
                self.entry = d.bytes()
        dec.versioned(2, body)


@register_message
class MOSDRepOpReply(Message):
    TYPE = 113  # MSG_OSD_REPOPREPLY

    def __init__(self, reqid: tuple[int, int] = (0, 0),
                 pgid: tuple[int, int] = (0, 0), from_osd: int = 0,
                 result: int = 0):
        super().__init__()
        self.reqid = reqid
        self.pgid = pgid
        self.from_osd = from_osd
        self.result = result

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]),
            _enc_pgid(e, self.pgid), e.s32(self.from_osd),
            e.s32(self.result)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.pgid = _dec_pgid(d)
            self.from_osd = d.s32()
            self.result = d.s32()
        dec.versioned(1, body)


@register_message
class MOSDECSubOpWrite(Message):
    TYPE = 108  # MSG_OSD_EC_WRITE

    def __init__(self, reqid: tuple[int, int] = (0, 0),
                 pgid: tuple[int, int] = (0, 0), oid: str = "",
                 shard: int = 0, chunk: bytes = b"", epoch: int = 0,
                 obj_size: int = 0, entry: bytes = b"",
                 offset: int = 0, shard_len: int = 0,
                 truncate: bool = True):
        super().__init__()
        self.reqid = reqid
        self.pgid = pgid
        self.oid = oid
        self.shard = shard
        self.chunk = chunk
        self.epoch = epoch
        self.obj_size = obj_size  # full (pre-encode) object size
        self.entry = entry        # encoded pg LogEntry (v3+)
        # v4: ranged stripe writes (ECBackend rmw pipeline)
        self.offset = offset      # byte offset within the shard object
        self.shard_len = shard_len  # full shard length after this write
        self.truncate = truncate  # True = replace the shard wholesale

    def encode_payload(self, enc):
        enc.versioned(4, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]),
            _enc_pgid(e, self.pgid), e.str(self.oid), e.u8(self.shard),
            e.bytes(self.chunk), e.u32(self.epoch), e.u64(self.obj_size),
            e.bytes(self.entry),
            e.u64(self.offset), e.u64(self.shard_len),
            e.u8(1 if self.truncate else 0)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.pgid = _dec_pgid(d)
            self.oid = d.str()
            self.shard = d.u8()
            self.chunk = d.bytes()
            self.epoch = d.u32()
            if v >= 2:  # v1 smuggled the size in the oid
                self.obj_size = d.u64()
            if v >= 3:
                self.entry = d.bytes()
            if v >= 4:
                self.offset = d.u64()
                self.shard_len = d.u64()
                self.truncate = d.u8() != 0
        dec.versioned(4, body)


@register_message
class MOSDECSubOpWriteReply(Message):
    TYPE = 109

    def __init__(self, reqid: tuple[int, int] = (0, 0), shard: int = 0,
                 from_osd: int = 0, result: int = 0):
        super().__init__()
        self.reqid = reqid
        self.shard = shard
        self.from_osd = from_osd
        self.result = result

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]), e.u8(self.shard),
            e.s32(self.from_osd), e.s32(self.result)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.shard = d.u8()
            self.from_osd = d.s32()
            self.result = d.s32()
        dec.versioned(1, body)


@register_message
class MOSDECSubOpRead(Message):
    """primary -> shard holder: read shard `shard` of `oid`.  v2 carries
    the shard extent ``(offset, length)`` (ECSubRead::to_read); length 0
    is the whole shard, as every v1 read was."""

    TYPE = 110
    HEAD_VERSION = 2       # v2: the shard extent

    def __init__(self, reqid: tuple[int, int] = (0, 0),
                 pgid: tuple[int, int] = (0, 0), oid: str = "",
                 shard: int = 0, offset: int = 0, length: int = 0):
        super().__init__()
        self.reqid = reqid
        self.pgid = pgid
        self.oid = oid
        self.shard = shard
        self.offset = offset
        self.length = length

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]),
            _enc_pgid(e, self.pgid), e.str(self.oid), e.u8(self.shard),
            e.u64(self.offset), e.u64(self.length)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.pgid = _dec_pgid(d)
            self.oid = d.str()
            self.shard = d.u8()
            self.offset = d.u64() if v >= 2 else 0
            self.length = d.u64() if v >= 2 else 0
        dec.versioned(2, body)


@register_message
class MOSDECSubOpReadReply(Message):
    """v3 returns the extent the read asked for (ECSubReadReply's
    buffers_read offsets); length 0 = the whole shard."""

    TYPE = 111
    HEAD_VERSION = 2       # payload v3: the extent read

    def __init__(self, reqid: tuple[int, int] = (0, 0), shard: int = 0,
                 from_osd: int = 0, result: int = 0, chunk: bytes = b"",
                 ver: tuple[int, int] = (0, 0), offset: int = 0,
                 length: int = 0):
        super().__init__()
        self.reqid = reqid
        self.shard = shard
        self.from_osd = from_osd
        self.result = result
        self.chunk = chunk
        self.ver = ver          # shard's object version (v2+; recovery reads)
        self.offset = offset
        self.length = length

    def encode_payload(self, enc):
        enc.versioned(3, 1, lambda e: (
            e.u64(self.reqid[0]), e.u64(self.reqid[1]), e.u8(self.shard),
            e.s32(self.from_osd), e.s32(self.result), e.bytes(self.chunk),
            e.u32(self.ver[0]), e.u64(self.ver[1]),
            e.u64(self.offset), e.u64(self.length)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reqid = (d.u64(), d.u64())
            self.shard = d.u8()
            self.from_osd = d.s32()
            self.result = d.s32()
            self.chunk = d.bytes()
            if v >= 2:
                self.ver = (d.u32(), d.u64())
            self.offset = d.u64() if v >= 3 else 0
            self.length = d.u64() if v >= 3 else 0
        dec.versioned(3, body)


@register_message
class MOSDPing(Message):
    TYPE = 70  # MSG_OSD_PING

    PING = 0
    PING_REPLY = 1

    def __init__(self, from_osd: int = 0, op: int = 0, stamp: float = 0.0,
                 epoch: int = 0):
        super().__init__()
        self.from_osd = from_osd
        self.op = op
        self.stamp = stamp
        self.epoch = epoch

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.s32(self.from_osd), e.u8(self.op), e.f64(self.stamp),
            e.u32(self.epoch)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.from_osd = d.s32()
            self.op = d.u8()
            self.stamp = d.f64()
            self.epoch = d.u32()
        dec.versioned(1, body)


@register_message
class MOSDFailure(Message):
    TYPE = 51  # MSG_OSD_FAILURE

    def __init__(self, reporter: int = 0, failed_osd: int = 0,
                 failed_for: float = 0.0, epoch: int = 0,
                 alive: bool = False):
        super().__init__()
        self.reporter = reporter
        self.failed_osd = failed_osd
        self.failed_for = failed_for
        self.epoch = epoch
        #: v2: FLAG_ALIVE cancellation (messages/MOSDFailure.h if_osd_alive)
        #: — the reporter heard from the peer again; retract my report
        self.alive = alive

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            e.s32(self.reporter), e.s32(self.failed_osd),
            e.f64(self.failed_for), e.u32(self.epoch),
            e.u8(1 if self.alive else 0)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.reporter = d.s32()
            self.failed_osd = d.s32()
            self.failed_for = d.f64()
            self.epoch = d.u32()
            self.alive = bool(d.u8()) if v >= 2 else False
        dec.versioned(2, body)


@register_message
class MOSDMapMsg(Message):
    """Map distribution (messages/MOSDMap.h): carries EITHER a full map
    blob OR a contiguous run of incremental blobs [(epoch, inc)] — the
    reference's maps/incremental_maps pair, reduced to one-or-the-other
    (full maps only on backfill/gap, deltas for normal churn)."""

    TYPE = 41  # MSG_OSD_MAP
    HEAD_VERSION = 2       # v2: incremental blobs ride along

    def __init__(self, epoch: int = 0, map_blob: bytes = b"",
                 incs: list | None = None):
        super().__init__()
        self.epoch = epoch
        self.map_blob = map_blob  # OSDMap encoded via osd.map_codec
        #: [(epoch, inc_blob)] ascending, contiguous; applies to a map
        #: at incs[0][0] - 1
        self.incs = incs or []

    def encode_payload(self, enc):
        def body(e):
            e.u32(self.epoch)
            e.bytes(self.map_blob)
            e.list(self.incs, lambda e2, p: (e2.u32(p[0]),
                                             e2.bytes(p[1])))
        enc.versioned(2, 1, body)

    def decode_payload(self, dec, version):
        def body(d, v):
            self.epoch = d.u32()
            self.map_blob = d.bytes()
            self.incs = (d.list(lambda d2: (d2.u32(), d2.bytes()))
                         if v >= 2 else [])
        dec.versioned(2, body)


@register_message
class MPGStats(Message):
    """Per-OSD PG state summary for mon health (the pre-luminous
    MPGStats / PGMonitor flow: primaries report, the mon aggregates
    PG_DEGRADED-class checks from it)."""

    TYPE = 87  # MSG_PGSTATS

    def __init__(self, osd_id: int = 0, states: dict | None = None,
                 degraded_objects: int = 0, stamp: float = 0.0):
        super().__init__()
        self.osd_id = osd_id
        self.states = states or {}      # pg state -> count (primary pgs)
        self.degraded_objects = degraded_objects
        self.stamp = stamp

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.u32(self.osd_id),
            e.map(self.states, lambda e2, k: e2.str(k),
                  lambda e2, v: e2.u32(v)),
            e.u64(self.degraded_objects), e.f64(self.stamp)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.osd_id = d.u32()
            self.states = d.map(lambda d2: d2.str(), lambda d2: d2.u32())
            self.degraded_objects = d.u64()
            self.stamp = d.f64()
        dec.versioned(1, body)


@register_message
class MMonCommand(Message):
    TYPE = 50  # MSG_MON_COMMAND

    def __init__(self, tid: int = 0, cmd: dict | None = None):
        super().__init__()
        self.tid = tid
        self.cmd = cmd or {}

    def encode_payload(self, enc):
        import json
        enc.versioned(1, 1, lambda e: (e.u64(self.tid),
                                       e.str(json.dumps(self.cmd))))

    def decode_payload(self, dec, version):
        import json

        def body(d, v):
            self.tid = d.u64()
            self.cmd = json.loads(d.str())
        dec.versioned(1, body)


@register_message
class MMonCommandAck(Message):
    TYPE = 52  # MSG_MON_COMMAND_ACK

    def __init__(self, tid: int = 0, result: int = 0, output: str = ""):
        super().__init__()
        self.tid = tid
        self.result = result
        self.output = output

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (e.u64(self.tid), e.s32(self.result),
                                       e.str(self.output)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.tid = d.u64()
            self.result = d.s32()
            self.output = d.str()
        dec.versioned(1, body)


@register_message
class MWatchNotify(Message):
    """osd -> watching client: a notify fired on an object
    (messages/MWatchNotify.h; CEPH_MSG_WATCH_NOTIFY)."""

    TYPE = 44

    def __init__(self, pool: int = 0, oid: str = "", notify_id: int = 0,
                 payload: bytes = b""):
        super().__init__()
        self.pool = pool
        self.oid = oid
        self.notify_id = notify_id
        self.payload = payload

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.s64(self.pool), e.str(self.oid), e.u64(self.notify_id),
            e.bytes(self.payload)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.pool = d.s64()
            self.oid = d.str()
            self.notify_id = d.u64()
            self.payload = d.bytes()
        dec.versioned(1, body)


@register_message
class MWatchNotifyAck(Message):
    TYPE = 45

    def __init__(self, pool: int = 0, oid: str = "", notify_id: int = 0):
        super().__init__()
        self.pool = pool
        self.oid = oid
        self.notify_id = notify_id

    def encode_payload(self, enc):
        enc.versioned(1, 1, lambda e: (
            e.s64(self.pool), e.str(self.oid), e.u64(self.notify_id)))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.pool = d.s64()
            self.oid = d.str()
            self.notify_id = d.u64()
        dec.versioned(1, body)


@register_message
class MOSDScrub(Message):
    """primary -> replica: send your scrub map for this PG
    (MOSDRepScrub analog).  v2 adds an optional oid filter so the
    verified-repair pass can re-fetch JUST the repaired objects'
    digests instead of re-scrubbing the whole collection; old peers
    (compat 1) skip the field and reply with the full map, which the
    primary filters — correct either way."""

    TYPE = 120
    HEAD_VERSION = 2

    def __init__(self, pgid: tuple[int, int] = (0, 0), scrub_id: int = 0,
                 from_osd: int = 0, oids: list[str] | None = None):
        super().__init__()
        self.pgid = pgid
        self.scrub_id = scrub_id
        self.from_osd = from_osd
        #: None = map the whole collection; a list restricts the map
        #: to exactly these store oids (repair verification)
        self.oids = oids

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            _enc_pgid(e, self.pgid), e.u64(self.scrub_id),
            e.s32(self.from_osd),
            e.u8(0 if self.oids is None else 1),
            e.list(self.oids or [], lambda e2, o: e2.str(o))))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.pgid = _dec_pgid(d)
            self.scrub_id = d.u64()
            self.from_osd = d.s32()
            self.oids = None
            if v >= 2:
                has = d.u8()
                lst = d.list(lambda d2: d2.str())
                self.oids = lst if has else None
        dec.versioned(2, body)


@register_message
class MOSDScrubReply(Message):
    """replica -> primary: {oid: (size, data_crc, omap_crc)}.  v2 adds
    the per-oid version blobs ("_v" attrs): scrub maps are gathered
    seconds apart under load, so the primary must distinguish
    SAME-VERSION divergence (corruption — repair it) from
    version-skewed divergence (an in-flight write or recovery — the
    replication machinery owns it; a scrub repair there would push a
    stale copy over an acked newer write)."""

    TYPE = 121
    HEAD_VERSION = 2

    def __init__(self, pgid: tuple[int, int] = (0, 0), scrub_id: int = 0,
                 from_osd: int = 0, scrub_map: dict | None = None,
                 versions: dict | None = None):
        super().__init__()
        self.pgid = pgid
        self.scrub_id = scrub_id
        self.from_osd = from_osd
        self.scrub_map = scrub_map or {}
        #: oid -> raw "_v" blob (b"" for objects without one)
        self.versions = versions or {}

    def encode_payload(self, enc):
        enc.versioned(2, 1, lambda e: (
            _enc_pgid(e, self.pgid), e.u64(self.scrub_id),
            e.s32(self.from_osd),
            e.map(self.scrub_map, lambda e2, k: e2.str(k),
                  lambda e2, t: (e2.u64(t[0]), e2.u32(t[1]),
                                 e2.u32(t[2]))),
            e.map(self.versions, lambda e2, k: e2.str(k),
                  lambda e2, v: e2.bytes(v))))

    def decode_payload(self, dec, version):
        def body(d, v):
            self.pgid = _dec_pgid(d)
            self.scrub_id = d.u64()
            self.from_osd = d.s32()
            self.scrub_map = d.map(
                lambda d2: d2.str(),
                lambda d2: (d2.u64(), d2.u32(), d2.u32()))
            self.versions = {}
            if v >= 2:
                self.versions = d.map(lambda d2: d2.str(),
                                      lambda d2: d2.bytes())
        dec.versioned(2, body)
