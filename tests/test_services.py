"""Services tier on RADOS (VERDICT missing item 10): striper, rbd-lite
block images, in-OSD object classes (cls), rgw-lite buckets, and the
compressor plugin registry."""

import json

import pytest

from ceph_tpu.osdc.striper import StripeLayout, StripedObject
from ceph_tpu.tools.vstart import MiniCluster


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    c.wait_for_osd_count(3)
    try:
        yield c
    finally:
        c.stop()


@pytest.fixture(scope="module")
def io(cluster):
    client = cluster.client(timeout=15.0)
    pool = cluster.create_pool(client, pg_num=8, size=3)
    return client.open_ioctx(pool)


class TestStriper:
    def test_extent_math(self):
        lay = StripeLayout(stripe_unit=16, stripe_count=2, object_size=32)
        # 2 su per object; su 0->obj0, su 1->obj1, su 2->obj0(second),
        # su 3->obj1(second), su 4->obj2...
        assert lay.extents(0, 16) == [(0, 0, 16)]
        assert lay.extents(16, 16) == [(1, 0, 16)]
        assert lay.extents(32, 16) == [(0, 16, 16)]
        assert lay.extents(64, 16) == [(2, 0, 16)]
        assert lay.extents(8, 16) == [(0, 8, 8), (1, 0, 8)]

    def test_striped_object_roundtrip(self, io):
        so = StripedObject(io, "big",
                           StripeLayout(stripe_unit=1024,
                                        stripe_count=3,
                                        object_size=4096))
        payload = bytes(range(256)) * 64      # 16 KiB over many objects
        so.write(payload)
        assert so.size() == len(payload)
        assert so.read() == payload
        assert so.read(5000, 1000) == payload[5000:6000]
        so.write(b"#" * 100, offset=2000)
        want = payload[:2000] + b"#" * 100 + payload[2100:]
        assert so.read() == want
        so.remove()
        assert so.size() == 0


class TestRbd:
    def test_image_lifecycle(self, io):
        from ceph_tpu.rbd import Image, list_images
        img = Image.create(io, "disk0", size=1 << 20, order=16)
        assert img.stat()["size"] == 1 << 20
        img.write(b"bootsector" * 51, offset=0)
        img.write(b"data-at-512k", offset=512 * 1024)
        assert img.read(0, 510) == (b"bootsector" * 51)
        assert img.read(512 * 1024, 12) == b"data-at-512k"
        # unwritten space reads as zeros
        assert img.read(900 * 1024, 64) == bytes(64)
        with pytest.raises(ValueError):
            img.write(b"x", offset=1 << 20)
        img.resize(2 << 20)
        img.write(b"grown", offset=(1 << 20) + 5)
        assert img.read((1 << 20) + 5, 5) == b"grown"
        assert list_images(io, ["disk0", "nope"]) == ["disk0"]
        img.remove()
        assert list_images(io, ["disk0"]) == []


class TestCls:
    def test_lock_class(self, io):
        io.write_full("locked", b"x")
        out = io.execute("locked", "lock", "lock",
                         json.dumps({"owner": "alice"}).encode())
        assert out == b"{}"
        info = json.loads(io.execute("locked", "lock", "info"))
        assert info["holder"] == "alice"
        # contention -> EACCES
        with pytest.raises(OSError):
            io.execute("locked", "lock", "lock",
                       json.dumps({"owner": "bob"}).encode())
        io.execute("locked", "lock", "unlock",
                   json.dumps({"owner": "alice"}).encode())
        assert json.loads(io.execute("locked", "lock",
                                     "info"))["holder"] is None

    def test_numops_and_version(self, io):
        io.write_full("ctr", b"")
        for want in (5, 8):
            out = json.loads(io.execute(
                "ctr", "numops", "add",
                json.dumps({"key": "hits", "val": 5 if want == 5
                            else 3}).encode()))
            assert out["value"] == want
        v1 = json.loads(io.execute("ctr", "version", "bump"))["ver"]
        v2 = json.loads(io.execute("ctr", "version", "bump"))["ver"]
        assert (v1, v2) == (1, 2)
        # cls mutations replicate: read the omap through the data path
        omap = io.get_omap("ctr")
        assert omap["hits"] == b"8"

    def test_unknown_class_errors(self, io):
        io.write_full("u", b"x")
        with pytest.raises(OSError):
            io.execute("u", "no_such", "method")


class TestRgw:
    def test_bucket_object_lifecycle(self, io):
        from ceph_tpu.rgw_lite import Bucket
        b = Bucket(io, "photos", compression="zlib").create()
        assert b.exists()
        body = b"jpegjpegjpeg" * 500
        b.put("2026/cat.jpg", body, metadata={"content-type":
                                              "image/jpeg"})
        b.put("2026/dog.jpg", b"woof")
        b.put("notes.txt", b"hello")
        assert b.get("2026/cat.jpg") == body
        head = b.head("2026/cat.jpg")
        assert head["size"] == len(body)
        assert head["stored"] < len(body)      # compression worked
        assert head["meta"]["content-type"] == "image/jpeg"
        assert b.list() == ["2026/cat.jpg", "2026/dog.jpg", "notes.txt"]
        assert b.list(prefix="2026/") == ["2026/cat.jpg", "2026/dog.jpg"]
        b.delete_object("2026/dog.jpg")
        assert b.list(prefix="2026/") == ["2026/cat.jpg"]
        with pytest.raises(OSError):
            b.delete()                         # not empty
        for k in b.list():
            b.delete_object(k)
        b.delete()
        assert not b.exists()


class TestCompressor:
    def test_registry_roundtrip(self):
        from ceph_tpu import compressor
        data = b"compressible " * 1000
        for name in compressor.names():
            c = compressor.create(name)
            assert c.decompress(c.compress(data)) == data
        with pytest.raises(KeyError):
            compressor.create("snappy")

    def test_custom_plugin_registration(self):
        from ceph_tpu import compressor

        class Rot13(compressor.Compressor):
            name = "rot13"

            def compress(self, data):
                return bytes((b + 13) % 256 for b in data)

            def decompress(self, data):
                return bytes((b - 13) % 256 for b in data)

        compressor.register("rot13", Rot13)
        c = compressor.create("rot13")
        assert c.decompress(c.compress(b"abc")) == b"abc"


class TestMgrAndCli:
    def test_mgr_aggregates_reports(self):
        c = MiniCluster(n_osds=3, ms_type="loopback").start()
        try:
            c.run_mgr()
            # restart osds so they pick up the mgr address
            for i in list(c.osds):
                c.kill_osd(i)
                c.run_osd(i)
            c.wait_for_osd_count(3)
            client = c.client(timeout=15.0)
            pool = c.create_pool(client, pg_num=8, size=3)
            io = client.open_ioctx(pool)
            for i in range(6):
                io.write_full(f"m{i}", b"x" * 500)
            import time as _t
            deadline = _t.time() + 10
            while _t.time() < deadline:
                df = c.mgr.df()
                if len(df["per_osd"]) == 3 and df["total_objects"] > 0:
                    break
                _t.sleep(0.2)
            df = c.mgr.df()
            assert len(df["per_osd"]) == 3
            assert df["total_objects"] >= 6   # replicas count per-osd
            assert c.mgr.pg_summary().get("active", 0) > 0
            assert c.mgr.health()["status"] in ("HEALTH_OK",
                                                "HEALTH_WARN")
            ctrs = c.mgr.counters()
            assert any(v.get("op_w", 0) > 0 for v in ctrs.values())
        finally:
            c.stop()

    def test_ceph_cli_parses_and_runs(self):
        from ceph_tpu.tools.ceph_cli import main, parse_command
        cmd = parse_command(["osd", "pool", "create", "pg_num=8",
                             "size=3"])
        assert cmd == {"prefix": "osd pool create", "pg_num": "8",
                       "size": "3"}
        assert parse_command(["osd", "out", "3"]) == {
            "prefix": "osd out", "id": "3"}
        c = MiniCluster(n_osds=3, ms_type="async").start()
        try:
            c.wait_for_osd_count(3)
            rc = main(["-m", c.mon_host, "status"])
            assert rc == 0
            rc = main(["-m", c.mon_host, "osd", "pool", "create",
                       "pg_num=4", "size=2"])
            assert rc == 0
        finally:
            c.stop()


class TestIciStack:
    """The device mesh as a messenger stack (SURVEY §5): EC shard bulk
    payloads ride cross-device placement while the daemons run the same
    code path as on tcp/loopback."""

    def test_ec_over_ici_mesh(self, tmp_path):
        from ceph_tpu.msg.ici import IciTransport
        t = IciTransport.instance()
        before = (t.transfers, t.bytes_staged)
        # BlueStore OSDs: the partial rmw below needs allow_ec_overwrites
        c = MiniCluster(n_osds=4, ms_type="ici", store_type="bluestore",
                        base_path=str(tmp_path)).start()
        try:
            c.wait_for_osd_count(4)
            client = c.client(timeout=15.0)
            pool = c.create_pool(client, pg_num=4,
                                 pool_type="erasure", k=2, m=2,
                                 ec_overwrites=True)
            io = client.open_ioctx(pool)
            payload = bytes(range(256)) * 128     # 32 KiB
            io.write_full("mesh-obj", payload)
            assert io.read("mesh-obj") == payload
            # partial rmw over the mesh too
            io.write("mesh-obj", b"Z" * 5000, offset=3000)
            want = payload[:3000] + b"Z" * 5000 + payload[8000:]
            assert io.read("mesh-obj") == want
            # replicated pool bulk recovery pushes also ride the mesh
            rep = c.create_pool(client, pg_num=4, size=3)
            io2 = client.open_ioctx(rep)
            io2.write_full("r", b"replicated-over-ici" * 200)
            assert io2.read("r") == b"replicated-over-ici" * 200
        finally:
            c.stop()
        after = (t.transfers, t.bytes_staged)
        assert after[0] > before[0], "no payload rode the device mesh"
        assert after[1] > before[1]

    def test_bulk_payload_lands_on_peer_device(self):
        import jax
        from ceph_tpu.msg.ici import IciTransport
        t = IciTransport.instance()
        from ceph_tpu.msg.messenger import EntityName
        if len(jax.devices()) < 2:
            import pytest as _pytest
            _pytest.skip("single-device backend")
        token = t.stage(b"x" * 4096, EntityName("osd", 1))
        entry = t._bufs[int.from_bytes(token[5:], "little")]
        assert entry["buf"].devices() == {jax.devices()[1]}
        assert t.redeem(token) == b"x" * 4096


class TestRbdAdvanced:
    """rbd_directory, exclusive lock, snapshots, clone — the librbd
    feature tier over the lite image."""

    def test_directory_listing(self, io):
        from ceph_tpu.rbd import Image, list_images
        a = Image.create(io, "dir-a", size=1 << 16, order=16)
        b = Image.create(io, "dir-b", size=1 << 16, order=16)
        assert list_images(io) == ["dir-a", "dir-b"]
        a.remove()
        assert list_images(io) == ["dir-b"]
        b.remove()
        assert list_images(io) == []

    def test_exclusive_lock(self, io):
        import pytest
        from ceph_tpu.rbd import Image
        img = Image.create(io, "locked-img", size=1 << 16, order=16)
        img.lock_acquire("writer-1")
        img.write(b"mine", 0)   # owner writes fine
        # a second handle must be refused
        other = Image(io, "locked-img")
        with pytest.raises(OSError) as ei:
            other.write(b"stolen", 0)
        assert ei.value.errno == 16
        with pytest.raises(OSError):
            other.resize(1 << 17)
        # lock break lets the second handle take over
        other.break_lock()
        other.lock_acquire("writer-2")
        other.write(b"taken", 0)
        assert img.read(0, 5) == b"taken"
        other.lock_release()
        img.remove()

    def test_snapshots_and_clone(self, io):
        import pytest
        from ceph_tpu.rbd import Image
        img = Image.create(io, "snappy", size=1 << 16, order=16)
        img.write(b"version-one", 0)
        img.snap_create("v1")
        img.write(b"VERSION-TWO", 0)
        assert img.read(0, 11) == b"VERSION-TWO"
        assert img.read(0, 11, snap="v1") == b"version-one"
        assert "v1" in img.snap_list()
        # COW clone from the protected snapshot sees v1 content; its
        # writes copy-up and never touch the parent
        img.snap_protect("v1")
        c = img.clone("snappy-clone", "v1")
        assert c.read(0, 11) == b"version-one"
        c.write(b"clone-write", 0)
        assert img.read(0, 11, snap="v1") == b"version-one"
        # rollback restores v1 on the source
        img.snap_rollback("v1")
        assert img.read(0, 11) == b"version-one"
        # protected + child: removal refused until flatten + unprotect
        with pytest.raises(OSError):
            img.snap_remove("v1")
        with pytest.raises(OSError):
            img.snap_unprotect("v1")
        c.flatten()
        img.snap_unprotect("v1")
        img.snap_remove("v1")
        with pytest.raises(KeyError):
            img.read(0, 4, snap="v1")
        c.remove()
        img.remove()


class TestRbdReviewRegressions:
    def test_lock_enforced_against_prior_writer(self, io):
        """A handle that wrote before the lock existed must be refused
        after another owner acquires it (no stale positive cache)."""
        import pytest
        from ceph_tpu.rbd import Image
        img = Image.create(io, "cache-img", size=1 << 16, order=16)
        img.write(b"pre-lock", 0)     # writes while unlocked
        other = Image(io, "cache-img")
        other.lock_acquire("B")
        with pytest.raises(OSError):
            img.write(b"post-lock", 0)
        other.lock_release()
        img.write(b"unlocked-again", 0)
        img.remove()

    def test_remove_refuses_with_snapshots(self, io):
        import pytest
        from ceph_tpu.rbd import Image
        img = Image.create(io, "snapped", size=1 << 16, order=16)
        img.write(b"x", 0)
        img.snap_create("keep")
        with pytest.raises(OSError):
            img.remove()
        img.snap_remove("keep")
        img.remove()

    def test_rm_omap_keys_with_newline_in_key(self, io):
        io.write_full("omapped", b"")
        io.set_omap("omapped", {"a\nb": b"1", "a": b"2", "b": b"3"})
        io.rm_omap_keys("omapped", ["a\nb"])
        assert io.get_omap("omapped") == {"a": b"2", "b": b"3"}

    def test_list_images_merges_probe_hits(self, io):
        import json as _json
        from ceph_tpu.rbd import Image, list_images
        # legacy image: header exists, no directory entry
        io.write_full(Image.HEADER_FMT.format(name="legacy"),
                      _json.dumps({"size": 16, "order": 16,
                                   "stripe_unit": 1 << 16,
                                   "stripe_count": 4,
                                   "snaps": {}}).encode())
        img = Image.create(io, "modern", size=1 << 16, order=16)
        assert list_images(io, probe=["legacy"]) == ["legacy", "modern"]
        img.remove()


def test_populate_classes_idempotent():
    from ceph_tpu.crush import build_two_level_map
    from ceph_tpu.crush.classes import populate_classes
    m, _root, _rid = build_two_level_map(4, 2)
    dc = {i: ("ssd" if i % 2 else "hdd") for i in range(8)}
    populate_classes(m, dc)
    n_buckets = sum(1 for b in m.buckets if b is not None)
    table = dict(m.class_bucket)
    populate_classes(m, dc)   # refresh must not clone shadows-of-shadows
    assert sum(1 for b in m.buckets if b is not None) == n_buckets
    assert set(table) == set(m.class_bucket)
