"""ObjectStore test suite run across all backends (ceph_test_objectstore
pattern: one suite, every store), plus FileStore journal-replay/torn-write
crash tests and the KV layer."""

import os

import pytest

from ceph_tpu.objectstore import (
    LogDB, MemDB, Transaction, create_objectstore)
from ceph_tpu.objectstore.kv import KVTransaction


@pytest.fixture(params=["memstore", "filestore", "bluestore"])
def store(request, tmp_path):
    s = create_objectstore(request.param, str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    yield s
    s.umount()


def test_basic_write_read(store):
    t = (Transaction()
         .create_collection("pg1")
         .write("pg1", "obj", 0, b"hello world"))
    store.apply_transaction(t)
    assert store.read("pg1", "obj") == b"hello world"
    assert store.read("pg1", "obj", 6, 5) == b"world"
    assert store.stat("pg1", "obj")["size"] == 11
    assert store.exists("pg1", "obj")
    assert not store.exists("pg1", "nope")


def test_write_extends_with_zeros(store):
    store.apply_transaction(
        Transaction().create_collection("c").write("c", "o", 8, b"xy"))
    assert store.read("c", "o") == b"\x00" * 8 + b"xy"


def test_zero_truncate_remove(store):
    store.apply_transaction(
        Transaction().create_collection("c").write("c", "o", 0, b"a" * 16))
    store.apply_transaction(Transaction().zero("c", "o", 4, 8))
    assert store.read("c", "o") == b"aaaa" + b"\x00" * 8 + b"aaaa"
    store.apply_transaction(Transaction().truncate("c", "o", 4))
    assert store.read("c", "o") == b"aaaa"
    store.apply_transaction(Transaction().remove("c", "o"))
    assert not store.exists("c", "o")


def test_omap_and_attrs(store):
    t = (Transaction().create_collection("c")
         .touch("c", "o")
         .omap_setkeys("c", "o", {"k1": b"v1", "k2": b"v2"})
         .setattr("c", "o", "_", b"objinfo"))
    store.apply_transaction(t)
    assert store.omap_get("c", "o") == {"k1": b"v1", "k2": b"v2"}
    assert store.getattr("c", "o", "_") == b"objinfo"
    store.apply_transaction(Transaction().omap_rmkeys("c", "o", ["k1"]))
    assert store.omap_get("c", "o") == {"k2": b"v2"}


def test_clone_and_listing(store):
    store.apply_transaction(
        Transaction().create_collection("c")
        .write("c", "src", 0, b"data").omap_setkeys("c", "src", {"a": b"1"}))
    store.apply_transaction(Transaction().clone("c", "src", "dst"))
    assert store.read("c", "dst") == b"data"
    assert store.omap_get("c", "dst") == {"a": b"1"}
    assert store.list_objects("c") == ["dst", "src"]
    assert store.list_collections() == ["c"]


def test_missing_collection_raises(store):
    with pytest.raises(KeyError):
        store.read("nope", "o")
    with pytest.raises(KeyError):
        store.apply_transaction(Transaction().write("nope", "o", 0, b"x"))


def test_on_commit_callback(store):
    fired = []
    store.queue_transactions(
        [Transaction().create_collection("c").write("c", "o", 0, b"z")],
        on_commit=lambda: fired.append(True))
    assert fired == [True]


def test_transaction_codec_roundtrip():
    t = (Transaction().create_collection("c").write("c", "o", 8, b"abc")
         .omap_setkeys("c", "o", {"k": b"v"}).truncate("c", "o", 4)
         .clone("c", "o", "o2").setattr("c", "o", "_", b"i"))
    back = Transaction.decode(t.encode())
    assert len(back) == len(t)
    for a, b in zip(t.ops, back.ops):
        assert (a.op, a.cid, a.oid, a.offset, a.length, a.data, a.keys,
                a.rmkeys, a.dest, a.name) == \
               (b.op, b.cid, b.oid, b.offset, b.length, b.data, b.keys,
                b.rmkeys, b.dest, b.name)


# -- FileStore durability ----------------------------------------------------

def test_filestore_journal_replay(tmp_path):
    path = str(tmp_path / "fs")
    s = create_objectstore("filestore", path)
    s.mkfs()
    s.mount()
    s.apply_transaction(
        Transaction().create_collection("pg1").write("pg1", "o", 0, b"abc"))
    # crash without umount: journal must carry the state
    s2 = create_objectstore("filestore", path)
    s2.mount()
    assert s2.read("pg1", "o") == b"abc"
    s2.umount()


def test_filestore_checkpoint_and_replay(tmp_path):
    path = str(tmp_path / "fs")
    s = create_objectstore("filestore", path)
    s.mkfs()
    s.mount()
    s.apply_transaction(
        Transaction().create_collection("c").write("c", "a", 0, b"1"))
    s.checkpoint()
    s.apply_transaction(Transaction().write("c", "b", 0, b"2"))
    s2 = create_objectstore("filestore", path)
    s2.mount()
    assert s2.read("c", "a") == b"1"
    assert s2.read("c", "b") == b"2"
    s2.umount()


def test_filestore_torn_journal_tail_ignored(tmp_path):
    path = str(tmp_path / "fs")
    s = create_objectstore("filestore", path)
    s.mkfs()
    s.mount()
    s.apply_transaction(
        Transaction().create_collection("c").write("c", "good", 0, b"ok"))
    s.umount()
    # simulate a torn write: append garbage half-frame
    with open(os.path.join(path, "journal"), "ab") as f:
        f.write(b"\xff\xff\xff\x7f\x00\x00")
    s2 = create_objectstore("filestore", path)
    s2.mount()   # replay must stop at the torn tail, not crash
    assert s2.read("c", "good") == b"ok"
    s2.umount()


# -- KV ----------------------------------------------------------------------

def test_memdb_transactions():
    db = MemDB()
    t = db.get_transaction().set("p", "k1", b"v1").set("p", "k2", b"v2")
    db.submit_transaction(t)
    db.submit_transaction(db.get_transaction().rmkey("p", "k1"))
    assert db.get("p", "k1") is None
    assert db.get("p", "k2") == b"v2"
    assert db.get_range("p") == {"k2": b"v2"}


def test_logdb_durability_and_compaction(tmp_path):
    path = str(tmp_path / "kv")
    db = LogDB(path)
    db.open()
    db.submit_transaction(db.get_transaction().set("m", "epoch", b"1"))
    db.submit_transaction(db.get_transaction().set("m", "epoch", b"2"))
    db.close()
    db2 = LogDB(path)
    db2.open()
    assert db2.get("m", "epoch") == b"2"
    db2.compact()
    db2.submit_transaction(db2.get_transaction().set("m", "extra", b"x"))
    db2.close()
    db3 = LogDB(path)
    db3.open()
    assert db3.get("m", "epoch") == b"2"
    assert db3.get("m", "extra") == b"x"
    db3.close()


def test_kv_transaction_codec():
    t = KVTransaction().set("a", "b", b"c").rmkey("d", "e")
    back = KVTransaction.decode(t.encode())
    assert back.sets == [("a", "b", b"c")]
    assert back.rms == [("d", "e")]



def test_bluestore_restart_durability(tmp_path):
    """Data lives on the block file, metadata in the KV: a remount sees
    everything, and reads come from disk, not RAM."""
    from ceph_tpu.objectstore import create_objectstore
    path = str(tmp_path / "bs")
    s = create_objectstore("bluestore", path)
    s.mkfs_if_needed()
    s.mount()
    t = (Transaction().create_collection("1.0")
         .write("1.0", "a", 0, b"durable" * 1000)
         .setattr("1.0", "a", "_v", b"7.1")
         .omap_setkeys("1.0", "a", {"k": b"v"}))
    s.apply_transaction(t)
    s.umount()
    s2 = create_objectstore("bluestore", path)
    s2.mkfs_if_needed()   # must NOT wipe an existing store
    s2.mount()
    assert s2.read("1.0", "a") == b"durable" * 1000
    assert s2.getattr("1.0", "a", "_v") == b"7.1"
    assert s2.omap_get("1.0", "a") == {"k": b"v"}
    s2.umount()


def test_bluestore_allocator_reuses_freed_blocks(tmp_path):
    from ceph_tpu.objectstore import create_objectstore
    path = str(tmp_path / "bs2")
    s = create_objectstore("bluestore", path)
    s.mkfs_if_needed()
    s.mount()
    s.apply_transaction(Transaction().create_collection("c"))
    for i in range(8):
        s.apply_transaction(
            Transaction().write("c", f"o{i}", 0, b"x" * 8192))
    import os
    size_before = os.path.getsize(f"{path}/block")
    for i in range(8):
        s.apply_transaction(Transaction().remove("c", f"o{i}"))
    for i in range(8):
        s.apply_transaction(
            Transaction().write("c", f"n{i}", 0, b"y" * 8192))
    s.umount()
    # freed extents were reused: the block file did not double
    assert os.path.getsize(f"{path}/block") <= size_before + 8192


def test_bluestore_cluster_end_to_end(tmp_path):
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=3, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path)).start()
    try:
        c.wait_for_osd_count(3)
        client = c.client(timeout=40.0)  # generous: suite runs fully loaded
        pool = c.create_pool(client, pg_num=4, size=3)
        io = client.open_ioctx(pool)
        io.write_full("b", b"bluestore-backed" * 100)
        assert io.read("b") == b"bluestore-backed" * 100
        ec = c.create_pool(client, pg_num=4, pool_type="erasure",
                           k=2, m=1, ec_overwrites=True)
        io2 = client.open_ioctx(ec)
        io2.write_full("e", b"E" * 9000)
        io2.write("e", b"Z" * 2000, offset=4000)
        want = b"E" * 4000 + b"Z" * 2000 + b"E" * 3000
        assert io2.read("e") == want
    finally:
        c.stop()


def test_bluestore_crash_remount_allocator_safe(tmp_path):
    """Hard-kill crash model: reopen WITHOUT umount.  The rebuilt
    allocator must not hand out live blocks, and committed overwrites
    must be intact (COW + fsck-style free-list rebuild)."""
    from ceph_tpu.objectstore import create_objectstore
    path = str(tmp_path / "bs3")
    s = create_objectstore("bluestore", path)
    s.mkfs_if_needed()
    s.mount()
    s.apply_transaction(Transaction().create_collection("c")
                        .write("c", "a", 0, b"A" * 8192))
    s.apply_transaction(Transaction().write("c", "a", 100, b"patch"))
    # simulate a crash: drop the handles without umount bookkeeping
    s._f.close()
    s._db.close()
    s2 = create_objectstore("bluestore", path)
    s2.mkfs_if_needed()
    s2.mount()
    want = b"A" * 100 + b"patch" + b"A" * (8192 - 105)
    assert s2.read("c", "a") == want
    # new writes after the crash must not corrupt the survivor
    s2.apply_transaction(Transaction().write("c", "b", 0, b"B" * 8192))
    assert s2.read("c", "a") == want
    assert s2.read("c", "b") == b"B" * 8192
    s2.umount()


def test_bluestore_rmcoll_purges_and_zero_punches_holes(tmp_path):
    import os as _os
    from ceph_tpu.objectstore import create_objectstore
    path = str(tmp_path / "bs4")
    s = create_objectstore("bluestore", path)
    s.mkfs_if_needed()
    s.mount()
    s.apply_transaction(Transaction().create_collection("c")
                        .write("c", "o", 0, b"x" * 16384))
    # zero the middle: full blocks become holes, not zero-filled disk
    size_before = _os.path.getsize(f"{path}/block")
    s.apply_transaction(Transaction().zero("c", "o", 4096, 8192))
    assert s.read("c", "o") == b"x" * 4096 + bytes(8192) + b"x" * 4096
    assert _os.path.getsize(f"{path}/block") <= size_before + 2 * 4096
    # rmcoll purges objects; recreating the collection finds it empty
    s.apply_transaction(Transaction().remove_collection("c"))
    s.apply_transaction(Transaction().create_collection("c"))
    assert not s.exists("c", "o")
    assert s.list_objects("c") == []
    s.umount()


def test_bluestore_remove_recreate_one_txn(tmp_path):
    """Recovery's replace-wholesale push removes and rewrites the same
    object in ONE transaction; the KV batch (sets-then-rms) must not
    let the remove eat the recreate.  Same for collections."""
    from ceph_tpu.objectstore import create_objectstore
    path = str(tmp_path / "bs5")
    s = create_objectstore("bluestore", path)
    s.mkfs_if_needed()
    s.mount()
    s.apply_transaction(Transaction().create_collection("c")
                        .write("c", "o", 0, b"old" * 2000))
    s.apply_transaction(Transaction()
                        .remove("c", "o")
                        .write("c", "o", 0, b"new")
                        .setattr("c", "o", "_v", b"9.9"))
    assert s.read("c", "o") == b"new"
    assert s.getattr("c", "o", "_v") == b"9.9"
    s.apply_transaction(Transaction()
                        .remove_collection("c")
                        .create_collection("c")
                        .write("c", "p", 0, b"fresh"))
    assert s.list_objects("c") == ["p"]
    # survives a remount (the KV really holds the final state)
    s.umount()
    s2 = create_objectstore("bluestore", path)
    s2.mkfs_if_needed()
    s2.mount()
    assert not s2.exists("c", "o")
    assert s2.read("c", "p") == b"fresh"
    assert s2.list_objects("c") == ["p"]
    s2.umount()
