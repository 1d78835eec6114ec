"""End-to-end cluster tests on the vstart-style MiniCluster: replicated and
EC pool I/O, failure detection, remap, recovery — the standalone QA tier
(qa/standalone/ analog) over the loopback stack."""

import time

import numpy as np
import pytest

from ceph_tpu.tools.vstart import MiniCluster


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    c.wait_for_osd_count(3)
    yield c
    c.stop()


@pytest.fixture
def blue_cluster(tmp_path):
    """BlueStore OSDs: an erasure pool takes overwrites only there
    (allow_ec_overwrites)."""
    c = MiniCluster(n_osds=3, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path)).start()
    c.wait_for_osd_count(3)
    yield c
    c.stop()


def test_cluster_forms(cluster):
    st = cluster.mon.status()
    assert st["num_up_osds"] == 3
    assert st["num_osds"] == 3


def test_replicated_write_read_roundtrip(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=8, size=3)
    io = client.open_ioctx(pool)
    io.write_full("obj-a", b"hello rados")
    assert io.read("obj-a") == b"hello rados"
    io.write("obj-a", b"HELLO", 0)
    assert io.read("obj-a") == b"HELLO rados"
    assert io.stat("obj-a")["size"] == 11
    io.set_omap("obj-a", {"k": b"v"})
    assert io.get_omap("obj-a") == {"k": b"v"}
    io.remove("obj-a")
    with pytest.raises(OSError):
        io.read("obj-a")


def test_replication_reaches_all_members(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=8, size=3)
    io = client.open_ioctx(pool)
    for i in range(10):
        io.write_full(f"o{i}", f"data{i}".encode() * 20)
    time.sleep(0.2)
    # every object's pg members all hold the object
    m = cluster.mon.osdmap
    for i in range(10):
        from ceph_tpu.client.rados import ceph_str_hash_rjenkins
        from ceph_tpu.osd.osdmap import pg_to_pgid
        ps = ceph_str_hash_rjenkins(f"o{i}")
        pg = pg_to_pgid(ps, m.pools[pool].pg_num)
        up, *_ = m.pg_to_up_acting_osds(pool, pg)
        assert len(up) == 3
        for osd_id in up:
            store = cluster.osds[osd_id].store
            assert store.read(f"{pool}.{pg}", f"o{i}") == \
                f"data{i}".encode() * 20, (i, osd_id)


def test_objects_spread_across_pgs(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=16, size=2)
    io = client.open_ioctx(pool)
    for i in range(40):
        io.write_full(f"spread-{i}", b"x")
    time.sleep(0.2)
    used_pgs = set()
    for osd in cluster.osds.values():
        for cid in osd.store.list_collections():
            if cid.startswith(f"{pool}.") and osd.store.list_objects(cid):
                used_pgs.add(cid)
    assert len(used_pgs) > 4  # hash spread over many pgs


def test_ec_pool_write_read_with_tpu_kernels(cluster):
    # 3 osds can hold k=2 m=1
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, 3000, dtype=np.uint8).tobytes()
    io.write_full("ec-obj", payload)
    got = io.read("ec-obj")
    assert got == payload
    # chunks actually live as shards on distinct osds
    time.sleep(0.2)
    shard_count = 0
    for osd in cluster.osds.values():
        for cid in osd.store.list_collections():
            for oid in (osd.store.list_objects(cid)
                        if cid.startswith(f"{pool}.") else []):
                if oid.startswith("ec-obj:"):
                    shard_count += 1
    assert shard_count == 3  # k+m shards


def test_ec_overwrite_with_smaller_data(cluster):
    """Shrinking WRITEFULL must truncate stale shard tails (advisor finding:
    stale chunk tails corrupted the re-read)."""
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=2, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    big = bytes(range(256)) * 40          # 10240 B
    small = b"tiny payload"               # much smaller rewrite
    io.write_full("shrink", big)
    assert io.read("shrink") == big
    io.write_full("shrink", small)
    assert io.read("shrink") == small


def test_ec_read_survives_shard_loss(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    payload = b"erasure coded payload " * 100
    io.write_full("victim", payload)
    time.sleep(0.2)
    # remove one shard object directly from its store (EIO injection analog,
    # test-erasure-eio.sh)
    removed = 0
    for osd in cluster.osds.values():
        for cid in list(osd.store.list_collections()):
            if not cid.startswith(f"{pool}."):
                continue
            for oid in list(osd.store.list_objects(cid)):
                if oid.startswith("victim:") and removed == 0:
                    from ceph_tpu.objectstore import Transaction
                    osd.store.apply_transaction(
                        Transaction().remove(cid, oid))
                    removed = 1
    assert removed == 1
    assert io.read("victim") == payload  # decode path reconstructs


def test_osd_down_triggers_remap_and_resend(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=8, size=2)
    io = client.open_ioctx(pool)
    io.write_full("before", b"pre-failure")
    # mark an osd down via mon command (admin path; heartbeats tested apart)
    victim = 0
    cluster.kill_osd(victim)
    res, _ = client.mon_command({"prefix": "osd down", "id": str(victim)})
    assert res == 0
    epoch = cluster.mon.osdmap.epoch
    cluster.wait_for_epoch(epoch)
    client.wait_for_epoch(epoch)
    # i/o continues against the new primaries
    io.write_full("after", b"post-failure")
    assert io.read("after") == b"post-failure"
    assert io.read("before") == b"pre-failure"


def test_recovery_pulls_missing_objects(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, size=3)
    io = client.open_ioctx(pool)
    for i in range(8):
        io.write_full(f"r{i}", f"recover-{i}".encode())
    time.sleep(0.3)
    # start a brand-new osd; nothing on it yet
    cluster.run_osd(3)
    cluster.wait_for_osd_count(4)
    epoch = cluster.mon.osdmap.epoch
    cluster.wait_for_epoch(epoch)
    # out osd.1 so placements shift toward osd.3
    res, _ = client.mon_command({"prefix": "osd out", "id": "1"})
    assert res == 0
    cluster.wait_for_epoch(cluster.mon.osdmap.epoch)
    time.sleep(0.5)  # scan/pull cycle
    m = cluster.mon.osdmap
    from ceph_tpu.client.rados import ceph_str_hash_rjenkins
    from ceph_tpu.osd.osdmap import pg_to_pgid
    missing = 0
    for i in range(8):
        ps = ceph_str_hash_rjenkins(f"r{i}")
        pg = pg_to_pgid(ps, m.pools[pool].pg_num)
        up, primary, _a, _ap = m.pg_to_up_acting_osds(pool, pg)
        store = cluster.osds[primary].store
        try:
            assert store.read(f"{pool}.{pg}", f"r{i}") == \
                f"recover-{i}".encode()
        except KeyError:
            missing += 1
    assert missing == 0, f"{missing}/8 objects not recovered to new primaries"


def test_filestore_osd_restart_keeps_data(tmp_path):
    c = MiniCluster(n_osds=2, ms_type="loopback", store_type="filestore",
                    base_path=str(tmp_path)).start()
    try:
        c.wait_for_osd_count(2)
        client = c.client()
        pool = c.create_pool(client, pg_num=4, size=2)
        io = client.open_ioctx(pool)
        io.write_full("durable", b"survives restart")
        time.sleep(0.2)
        # hard-kill and restart an osd: journal replay must restore its state
        c.kill_osd(1)
        c.run_osd(1)
        c.wait_for_osd_count(2)
        store = c.osds[1].store
        found = any(
            store.exists(cid, "durable")
            for cid in store.list_collections())
        assert found, "restarted filestore osd lost its objects (mkfs wipe?)"
    finally:
        c.stop()


def test_cluster_over_real_tcp_sockets():
    c = MiniCluster(n_osds=3, ms_type="async").start()
    try:
        c.wait_for_osd_count(3)
        client = c.client()
        pool = c.create_pool(client, pg_num=4, size=3)
        io = client.open_ioctx(pool)
        io.write_full("tcp-obj", b"over real sockets")
        assert io.read("tcp-obj") == b"over real sockets"
        ec_pool = c.create_pool(client, pg_num=2, pool_type="erasure",
                                k=2, m=1)
        io2 = client.open_ioctx(ec_pool)
        io2.write_full("tcp-ec", b"ec over tcp " * 50)
        assert io2.read("tcp-ec") == b"ec over tcp " * 50
    finally:
        c.stop()


def test_heartbeat_failure_detection():
    c = MiniCluster(n_osds=3, ms_type="loopback", heartbeats=True).start()
    try:
        c.wait_for_osd_count(3)
        for osd in c.osds.values():
            osd.ctx.conf.set("osd_heartbeat_interval", 0.1)
            osd.ctx.conf.set("osd_heartbeat_grace", 0.5)
        time.sleep(0.5)  # peers exchange pings
        victim = 2
        c.kill_osd(victim)
        deadline = time.time() + 8
        while time.time() < deadline:
            if c.mon.status()["num_up_osds"] == 2:
                break
            time.sleep(0.05)
        assert c.mon.status()["num_up_osds"] == 2, \
            "mon never marked the dead osd down from peer reports"
        assert not c.mon.osdmap.is_up(victim)
    finally:
        c.stop()


def test_shec_and_clay_pools_end_to_end():
    """The advanced EC plugins drive the same batched OSD data path."""
    c = MiniCluster(n_osds=7, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(7)
        client = c.client(timeout=20.0)
        shec = c.create_pool(client, pg_num=4, pool_type="erasure",
                             plugin="shec", k=4, m=3, c=2)
        io = client.open_ioctx(shec)
        io.write_full("s1", b"shec-on-the-cluster" * 50)
        assert io.read("s1") == b"shec-on-the-cluster" * 50
        clay = c.create_pool(client, pg_num=4, pool_type="erasure",
                             plugin="clay", k=4, m=2)
        io2 = client.open_ioctx(clay)
        io2.write_full("c1", b"clay-coupled-layers" * 64)
        assert io2.read("c1") == b"clay-coupled-layers" * 64
    finally:
        c.stop()


def test_ec_partial_write_rmw(blue_cluster):
    """OP_WRITE at arbitrary offsets on an EC pool with overwrites
    round-trips through the stripe-ranged read-modify-write pipeline
    (ECBackend start_rmw)."""
    cluster = blue_cluster
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, pool_type="erasure",
                               k=2, m=1, ec_overwrites=True)
    io = client.open_ioctx(pool)
    base = bytearray(b"A" * 20000)
    io.write_full("rmw", bytes(base))
    # overwrite a range crossing stripe boundaries (stripe_unit 4096,
    # width 8192)
    io.write("rmw", b"B" * 5000, offset=6000)
    base[6000:11000] = b"B" * 5000
    assert io.read("rmw") == bytes(base)
    # extend past the end (object grows, new stripes appear)
    io.write("rmw", b"C" * 7000, offset=19000)
    base = base[:19000] + b"C" * 7000
    assert io.read("rmw") == bytes(base)
    # partial write to a fresh object (zero-filled head)
    io.write("rmw2", b"D" * 100, offset=9000)
    got = io.read("rmw2")
    assert got[:9000] == bytes(9000) and got[9000:] == b"D" * 100


def test_ec_range_read(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    payload = bytes(range(256)) * 64          # 16 KiB, 2 stripes
    io.write_full("rr", payload)
    assert io.read("rr", length=100, offset=5000) == payload[5000:5100]
    assert io.read("rr", length=0, offset=9000) == payload[9000:]


def test_ec_corrupt_shard_detected_and_reconstructed(cluster):
    """A flipped byte in a stored shard fails the HashInfo checksum: the
    read reconstructs from the other shards and a repair rewrites the
    bad copy (ECUtil HashInfo semantics)."""
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, pool_type="erasure",
                               k=2, m=1)
    io = client.open_ioctx(pool)
    payload = b"integrity-matters" * 400
    io.write_full("crc", payload)
    time.sleep(0.2)
    # find a stored shard and flip a byte behind the OSD's back
    from ceph_tpu.client.rados import ceph_str_hash_rjenkins
    from ceph_tpu.osd.osdmap import pg_to_pgid
    m = cluster.mon.osdmap
    pg = pg_to_pgid(ceph_str_hash_rjenkins("crc"), m.pools[pool].pg_num)
    up, _p, _a, _ap = m.pg_to_up_acting_osds(pool, pg)
    victim = cluster.osds[up[0]]
    cid = f"{pool}.{pg}"
    blob = bytearray(victim.store.read(cid, "crc:0"))
    blob[7] ^= 0xFF
    from ceph_tpu.objectstore import Transaction
    t = Transaction().truncate(cid, "crc:0", 0).write(cid, "crc:0", 0,
                                                      bytes(blob))
    victim.store.apply_transaction(t)   # corrupt WITHOUT updating hinfo
    # the read must still return correct bytes (reconstructed)
    assert io.read("crc") == payload
    # and the repair eventually rewrites the shard with a valid checksum
    from ceph_tpu.osd.ec_util import HashInfo
    deadline = time.time() + 10
    while time.time() < deadline:
        cur = victim.store.read(cid, "crc:0")
        hinfo = victim.store.getattr(cid, "crc:0", "hinfo")
        if HashInfo.matches(cur, hinfo) and cur != bytes(blob):
            break
        time.sleep(0.1)
    cur = victim.store.read(cid, "crc:0")
    assert HashInfo.matches(cur, victim.store.getattr(cid, "crc:0",
                                                      "hinfo"))
    assert cur != bytes(blob), "corrupt shard never repaired"


def test_ec_bitmatrix_technique_pool(blue_cluster):
    """Bitmatrix techniques need chunk % w == 0: the stripe unit rounds
    up to the codec's alignment quantum (w=7 for liberation)."""
    cluster = blue_cluster
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=4, pool_type="erasure",
                               k=2, m=2, technique="liberation",
                               ec_overwrites=True)
    io = client.open_ioctx(pool)
    payload = b"w-aligned-stripes" * 700
    io.write_full("lb", payload)
    assert io.read("lb") == payload
    io.write("lb", b"Z" * 3000, offset=5000)
    want = payload[:5000] + b"Z" * 3000 + payload[8000:]
    assert io.read("lb") == want


def test_health_command(cluster):
    client = cluster.client()
    import json
    rc, out = client.mon_command({"prefix": "health"})
    assert rc == 0
    h = json.loads(out)
    assert h["status"] == "HEALTH_OK" and h["checks"] == []
    cluster.kill_osd(2)
    rc, _ = client.mon_command({"prefix": "osd down", "id": 2})
    assert rc == 0
    rc, out = client.mon_command({"prefix": "health"})
    h = json.loads(out)
    assert h["status"] == "HEALTH_WARN"
    osd_down = next(c for c in h["checks"] if c["check"] == "OSD_DOWN")
    assert osd_down["osds"] == [2]
    assert "summary" in osd_down
    # the detail variant carries per-item lines
    rc, out = client.mon_command({"prefix": "health detail"})
    h = json.loads(out)
    dd = next(c for c in h["checks"] if c["check"] == "OSD_DOWN")
    assert dd["detail"] == ["osd.2 is down"]
