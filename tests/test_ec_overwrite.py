"""Overwrites on an erasure-coded pool with allow_ec_overwrites
(pg_pool_t::FLAG_EC_OVERWRITES): a partial write reads only the stripes
it touches, k chunks of each (ECTransaction::get_write_plan), re-encodes
them and writes each shard's extent.  Every result is held to a plain
model of the object and its stored shards to the plain reed_sol_van of
perfbench/reference/rs_plain.py, healthy and degraded.  Without the
flag an erasure pool refuses an unaligned overwrite (-EOPNOTSUPP)."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.messages.osd_msgs import (OP_WRITE, MOSDECSubOpRead,
                                        MOSDECSubOpReadReply, OSDOpField)
from ceph_tpu.msg.encoding import Decoder, Encoder
from ceph_tpu.osd.map_codec import (decode_incremental, decode_osdmap,
                                    encode_osdmap)
from ceph_tpu.osd.osdmap import FLAG_EC_OVERWRITES, OSDMap, PGPool
from ceph_tpu.tools.vstart import MiniCluster
from perfbench.reference import rs_plain

K, M, SU = 4, 2, 4096
WIDTH = K * SU
BASE = 3 * WIDTH + 1000          # three whole stripes and a partial one

#: (offset, length) of each overwrite case, into an object of BASE bytes
CASES = {
    "one_byte": (WIDTH + 7, 1),
    "one_block": (2 * SU, SU),
    "chunk_crossing": (SU - 100, 300),
    "stripe_crossing": (WIDTH - 1000, 3000),
    "at_zero": (0, 5000),
    "growing": (BASE - 100, 2 * WIDTH + 50),
}


def _rng(*key) -> np.random.Generator:
    return np.random.default_rng((0x0ec0, *key))


def _perf(cluster, key: str) -> int:
    return sum(d.perf.value(key) for d in cluster.osds.values())


def _stored_shards(cluster, oid: str) -> dict[int, bytes]:
    out = {}
    for d in cluster.osds.values():
        for cid in d.store.list_collections():
            for soid in d.store.list_objects(cid):
                name, _, s = soid.rpartition(":")
                if name == oid:
                    out[int(s)] = d.store.read(cid, soid)
    return out


def _stripes(off: int, length: int) -> int:
    return -(-(off + length) // WIDTH) - off // WIDTH


def _start(tmp_path_factory, n_osds: int = K + M) -> MiniCluster:
    c = MiniCluster(n_osds=n_osds, ms_type="loopback",
                    store_type="bluestore",
                    base_path=str(tmp_path_factory.mktemp("ecow"))).start()
    c.wait_for_osd_count(n_osds)
    return c


@pytest.fixture(scope="module")
def healthy(tmp_path_factory):
    c = _start(tmp_path_factory)
    client = c.client()
    pool = c.create_pool(client, pg_num=4, pool_type="erasure", k=K, m=M,
                         ec_overwrites=True)
    yield c, client, client.open_ioctx(pool)
    c.stop()


@pytest.mark.parametrize("case", sorted(CASES))
def test_overwrite_is_the_models_and_reads_only_its_stripes(healthy, case):
    cluster, _client, io = healthy
    off, length = CASES[case]
    oid = f"ow-{case}"
    model = bytearray(_rng(1).bytes(BASE))
    io.write_full(oid, bytes(model))
    data = _rng(2, len(case)).bytes(length)
    before = {k: _perf(cluster, k) for k in
              ("ec_rmw_writes", "ec_rmw_read_bytes", "ec_rmw_gather")}
    io.write(oid, data, offset=off)
    model.extend(bytes(max(0, off + length - len(model))))
    model[off:off + length] = data
    assert io.read(oid) == bytes(model)
    assert io.stat(oid)["size"] == len(model)
    assert _stored_shards(cluster, oid) == dict(enumerate(
        rs_plain.shards_of(bytes(model), K, M, SU)))
    delta = {k: _perf(cluster, k) - v for k, v in before.items()}
    assert delta["ec_rmw_writes"] == 1 and delta["ec_rmw_gather"] == 1
    # k chunks of each stripe the write touches, and nothing else; past
    # the old end a shard has less to give
    full = K * SU * _stripes(off, length)
    if case == "growing":
        assert 0 < delta["ec_rmw_read_bytes"] < full
    else:
        assert delta["ec_rmw_read_bytes"] == full


def test_a_ranged_write_commits_one_extent_a_shard(healthy):
    """No whole-shard read or rewrite: a 4 KiB overwrite inside one
    chunk writes one block on each of the k + m shards."""
    from ceph_tpu.ops import telemetry
    _cluster, _client, io = healthy
    io.write_full("ow-blocks", _rng(3).bytes(8 * WIDTH))
    b0 = telemetry.bluestore_summary()["write_run_blocks"]
    io.write("ow-blocks", _rng(4).bytes(SU), offset=5 * WIDTH + 2 * SU)
    assert telemetry.bluestore_summary()["write_run_blocks"] - b0 == K + M


def test_a_fresh_object_written_past_zero_reads_zeros_before(healthy):
    _cluster, _client, io = healthy
    io.write("ow-fresh", b"D" * 100, offset=9000)
    got = io.read("ow-fresh")
    assert got[:9000] == bytes(9000) and got[9000:] == b"D" * 100


def test_a_burst_rides_one_gather(healthy):
    """Writes behind a gather overlay the stripes in flight in arrival
    order; only one that needs a stripe nobody holds reads again."""
    cluster, client, io = healthy
    model = bytearray(_rng(5).bytes(4 * WIDTH))
    io.write_full("ow-burst", bytes(model))
    g0 = _perf(cluster, "ec_rmw_gather")
    writes = [(WIDTH + i * 512, bytes([i + 1]) * 1024) for i in range(8)]
    writes.append((3 * WIDTH + 5, b"far" * 100))    # a stripe of its own
    comps = []
    for off, data in writes:
        model[off:off + len(data)] = data
        comps.append(client.aio_operate(
            io.pool_id, "ow-burst",
            [OSDOpField(OP_WRITE, off, len(data), data)]))
    for c in comps:
        assert c.wait_for_complete(30) and c.get_return_value() == 0
    assert io.read("ow-burst") == bytes(model)
    assert _stored_shards(cluster, "ow-burst") == dict(enumerate(
        rs_plain.shards_of(bytes(model), K, M, SU)))
    assert _perf(cluster, "ec_rmw_gather") - g0 < len(writes)


@pytest.fixture(scope="module", params=[1, M], ids=["one_data_down",
                                                   "m_down"])
def degraded(request, tmp_path_factory):
    """One PG whose data shards 1 (and 2) are on OSDs that are down:
    the primary (shard 0) gathers parity and decodes."""
    c = _start(tmp_path_factory)
    client = c.client()
    pool = c.create_pool(client, pg_num=1, pool_type="erasure", k=K, m=M,
                         min_size=K, ec_overwrites=True)
    io = client.open_ioctx(pool)
    acting = c.mon.osdmap.pg_to_up_acting_osds(pool, 0)[2]
    objs = {i: bytearray(_rng(6, i).bytes(BASE)) for i in range(len(CASES))}
    for i, data in objs.items():
        io.write_full(f"dg-{i}", bytes(data))
    for osd in acting[1:1 + request.param]:
        c.kill_osd(osd)
        rc, out = client.mon_command({"prefix": "osd down",
                                      "id": str(osd)})
        assert rc == 0, out
    epoch = c.mon.osdmap.epoch
    c.wait_for_epoch(epoch, timeout=30)
    client.wait_for_epoch(epoch)
    yield c, io, objs
    c.stop()


def test_degraded_overwrites_decode_and_stay_exact(degraded):
    cluster, io, objs = degraded
    d0 = _perf(cluster, "ec_rmw_decodes")
    for i, case in enumerate(sorted(CASES)):
        off, length = CASES[case]
        data = _rng(7, i).bytes(length)
        io.write(f"dg-{i}", data, offset=off)
        model = objs[i]
        model.extend(bytes(max(0, off + length - len(model))))
        model[off:off + length] = data
        assert io.read(f"dg-{i}") == bytes(model), case
        # the shards still stored are the plain code's of the model
        want = rs_plain.shards_of(bytes(model), K, M, SU)
        for s, got in _stored_shards(cluster, f"dg-{i}").items():
            assert got == want[s], (case, s)
    assert _perf(cluster, "ec_rmw_decodes") - d0 == len(CASES)


@pytest.mark.parametrize("v", [1, 2])
def test_sub_read_round_trips(v):
    m = MOSDECSubOpRead(reqid=(7, 9), pgid=(3, 1), oid="o:2", shard=2,
                        offset=8192 if v == 2 else 0,
                        length=4096 if v == 2 else 0)
    enc = Encoder()
    if v == 1:     # the parent's layout: no extent
        enc.versioned(1, 1, lambda e: (
            e.u64(7), e.u64(9), e.s64(3), e.u32(1), e.str("o:2"), e.u8(2)))
    else:
        m.encode_payload(enc)
    got = MOSDECSubOpRead()
    got.decode_payload(Decoder(enc.tobytes()), 0)
    assert (got.reqid, got.pgid, got.oid, got.shard, got.offset,
            got.length) == (m.reqid, m.pgid, m.oid, m.shard, m.offset,
                            m.length)


def test_sub_read_reply_returns_its_extent():
    m = MOSDECSubOpReadReply(reqid=(1, 2), shard=3, from_osd=4, result=0,
                             chunk=b"abc", ver=(5, 6), offset=4096,
                             length=8192)
    enc = Encoder()
    m.encode_payload(enc)
    got = MOSDECSubOpReadReply()
    got.decode_payload(Decoder(enc.tobytes()), 0)
    assert (got.chunk, got.ver, got.offset, got.length) == (
        b"abc", (5, 6), 4096, 8192)


def test_the_flag_rides_the_map_and_old_maps_decode_without_it(
        monkeypatch):
    from ceph_tpu.osd import map_codec
    m = OSDMap()
    m.pools[3] = PGPool(pool_id=3, type=3, flags=FLAG_EC_OVERWRITES)
    assert decode_osdmap(encode_osdmap(m)).pools[3].allows_ecoverwrites()
    real = map_codec._enc_pool

    def v13(e2, p):             # the parent's pool: no flags word
        real(e2, p)
        e2._parts.pop()
    monkeypatch.setattr(map_codec, "_enc_pool", v13)
    old = bytearray(encode_osdmap(m))
    assert old[0] == 14
    old[0] = 13
    assert decode_osdmap(bytes(old)).pools[3].flags == 0
    # an incremental of the parent's (v4) carries its pools the same way
    inc = bytearray(map_codec.encode_incremental(
        {"epoch": 2, "pools": {3: m.pools[3]}}))
    assert inc[0] == 5
    inc[0] = 4
    assert decode_incremental(bytes(inc))["pools"][3].flags == 0


def test_the_flag_is_set_once_and_never_unset(healthy):
    cluster, client, io = healthy
    pool = cluster.mon.osdmap.pools[io.pool_id]
    assert pool.allows_ecoverwrites()
    rc, out = client.mon_command({
        "prefix": "osd pool set", "pool": str(io.pool_id),
        "var": "allow_ec_overwrites", "val": "false"})
    assert rc == -22 and "cannot be disabled" in out
    assert cluster.mon.osdmap.pools[io.pool_id].allows_ecoverwrites()


def test_the_flag_needs_an_erasure_pool(healthy):
    cluster, client, _io = healthy
    rep = cluster.create_pool(client, pg_num=1)
    rc, out = client.mon_command({
        "prefix": "osd pool set", "pool": str(rep),
        "var": "allow_ec_overwrites", "val": "true"})
    assert rc == -22 and "erasure" in out


@pytest.fixture(scope="module")
def memstore():
    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    c.wait_for_osd_count(3)
    client = c.client()
    yield c, client
    c.stop()


def test_the_flag_is_refused_without_bluestore(memstore):
    cluster, client = memstore
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1)
    rc, out = client.mon_command({
        "prefix": "osd pool set", "pool": str(pool),
        "var": "allow_ec_overwrites", "val": "true"})
    assert rc == -22 and "bluestore" in out
    assert not cluster.mon.osdmap.pools[pool].allows_ecoverwrites()


def test_an_unflagged_pool_refuses_overwrites_and_takes_aligned_appends(
        memstore):
    """PrimaryLogPG::do_osd_ops, requires_aligned_append: without the
    flag a write goes at the object's stripe-aligned end or not at all."""
    cluster, client = memstore
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    width = 2 * SU
    io.write_full("app", b"A" * width)
    for off, n in ((100, 10), (0, 10), (width + 1, 10), (width // 2, 10)):
        with pytest.raises(OSError) as err:
            io.write("app", b"x" * n, offset=off)
        assert err.value.errno == 95
    io.write("app", b"B" * 5000, offset=width)        # at the aligned end
    assert io.read("app") == b"A" * width + b"B" * 5000
    with pytest.raises(OSError) as err:    # 5000 more is no aligned end
        io.write("app", b"C", offset=width + 5000)
    assert err.value.errno == 95
    with pytest.raises(OSError) as err:    # a fresh object starts at 0
        io.write("fresh", b"C", offset=width)
    assert err.value.errno == 95
    io.write("fresh", b"C" * 10)
    assert io.read("fresh") == b"C" * 10
