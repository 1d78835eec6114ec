"""BlueStore block I/O by extent run.

Whole, block-aligned, uncompressed blocks move between an object and
the block file a run of consecutive blocks at a time: one allocation
and one positioned write per run for a write's body, one positioned
read per run for a wide read.  Held here: the store stays byte-equal to
MemStore over seeded sequences of every op shape (and every committed
checksum equal to zlib's over the block read raw), the copy-on-write
crash shape survives the run path, and the counters that say the path
engages count what the block file saw."""

from __future__ import annotations

import json
import os
import zlib

import numpy as np
import pytest

from ceph_tpu.objectstore import Transaction, create_objectstore
from ceph_tpu.objectstore import bluestore as bluestore_mod
from ceph_tpu.objectstore.bluestore import BLOCK, BlueStoreLite, _runs
from ceph_tpu.ops import telemetry

pytestmark = pytest.mark.filterwarnings("ignore")

CID = "2.0"
SHARD = 128 * BLOCK     # one shard of a 4 MiB object at k = 8


# -- helpers ------------------------------------------------------------------

def _ctx(name: str, **conf):
    from ceph_tpu.common.context import CephTpuContext
    c = CephTpuContext(name)
    for k, v in conf.items():
        c.conf.set(k, str(v), source="cli")
    return c


def _stop_engines(ctx) -> None:
    for attr in ("_decode_dispatch", "_dispatch"):
        e = getattr(ctx, attr, None) if ctx is not None else None
        if e is not None:
            e.stop()


def _mount(path: str, ctx, engine: bool = True) -> BlueStoreLite:
    """``engine=False``: a store that has its context's conf (a
    compression mode) and stages its checksums by the scalar path."""
    s = BlueStoreLite(path, ctx=ctx)
    s.mkfs_if_needed()
    s.mount()
    if not engine:
        s._batch_engine = lambda: None
    return s


def _payload(seed: int, n: int) -> bytes:
    return bytes(np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8))


def _soft_payload(seed: int, n: int) -> bytes:
    """Bytes a compressor takes: 64 seeded ones, repeated."""
    return (_payload(seed, 64) * (n // 64 + 1))[:n]


def _metas(s: BlueStoreLite) -> dict[str, dict]:
    return {k: json.loads(v.decode())
            for k, v in s._db.get_range("obj").items()}


def _raw(s: BlueStoreLite, block: int) -> bytes:
    """A block as the file holds it, read past the store."""
    with open(s._block_path, "rb") as f:
        f.seek(block * BLOCK)
        return f.read(BLOCK).ljust(BLOCK, b"\x00")


def _check_committed(s: BlueStoreLite) -> None:
    """Every stored block's committed csum is zlib's over the block
    read raw; no block belongs to two extents, or to the free set."""
    used: list[int] = []
    for okey, m in _metas(s).items():
        assert len(m["csum"]) == len(m["extents"]) == len(m["comp"]), okey
        for bi, b in enumerate(m["extents"]):
            if b < 0:
                assert m["csum"][bi] is None, (okey, bi)
                continue
            used.append(b)
            raw = _raw(s, b)
            comp = m["comp"][bi]
            stored = raw[:comp[1]] if comp else raw
            assert m["csum"][bi] == zlib.crc32(stored), (okey, bi, b)
    assert len(used) == len(set(used))
    assert not set(used) & s._alloc._free
    assert all(b < s._alloc._next for b in used)


def _same(bs, mem, oids) -> None:
    for oid in oids:
        assert bs.exists(CID, oid) == mem.exists(CID, oid), oid
        if not mem.exists(CID, oid):
            continue
        want = mem.read(CID, oid)
        assert bs.stat(CID, oid)["size"] == mem.stat(CID, oid)["size"], oid
        assert bs.read(CID, oid) == want, oid
        if len(want) > 2 * BLOCK:     # a read that starts and ends inside blocks
            assert bs.read(CID, oid, BLOCK - 7, len(want) - 2 * BLOCK) \
                == want[BLOCK - 7:len(want) - BLOCK - 7], oid


def _random_txn(rng, oids: list[str], sizes: dict[str, int],
                payload=_payload) -> Transaction:
    """One transaction of one to three ops over a few objects: aligned
    and unaligned writes and overwrites, writes past the end, deferred
    small writes, zero, truncate, clone, remove-and-recreate."""
    t = Transaction()
    for _ in range(int(rng.integers(1, 4))):
        oid = oids[int(rng.integers(len(oids)))]
        size = sizes.get(oid, 0)
        kind = rng.choice(["write", "write", "write", "aligned", "aligned",
                           "shard", "small", "zero", "truncate", "clone",
                           "recreate"])
        if kind == "write":
            off = int(rng.integers(0, size + 3 * BLOCK))
            n = int(rng.integers(1, 12 * BLOCK))
        elif kind == "aligned":
            off = int(rng.integers(0, size // BLOCK + 3)) * BLOCK
            n = int(rng.integers(1, 24)) * BLOCK
        elif kind == "shard":
            # the OSD's EC shard write: truncate(0).write(0, whole shard)
            off, n = 0, int(rng.integers(8, 40)) * BLOCK
            t.truncate(CID, oid, 0)
            size = 0
        elif kind == "small":
            off = int(rng.integers(0, max(1, size)))
            n = int(rng.integers(1, BLOCK))
        if kind in ("write", "aligned", "shard", "small"):
            t.write(CID, oid, off, payload(int(rng.integers(1 << 30)), n))
            sizes[oid] = max(size, off + n)
        elif kind == "zero":
            off = int(rng.integers(0, size + BLOCK))
            n = int(rng.integers(1, 6 * BLOCK))
            t.zero(CID, oid, off, n)
            sizes[oid] = max(size, off + n)
        elif kind == "truncate":
            n = int(rng.integers(0, size + 2 * BLOCK))
            t.truncate(CID, oid, n)
            sizes[oid] = n
        elif kind == "clone":
            dest = oids[int(rng.integers(len(oids)))]
            if dest != oid and oid in sizes:
                t.clone(CID, oid, dest)
                sizes[dest] = size
        else:   # remove and recreate: the freed blocks fragment the free set
            n = int(rng.integers(1, 20)) * BLOCK
            t.remove(CID, oid).write(CID, oid, 0, payload(
                int(rng.integers(1 << 30)), n))
            sizes[oid] = n
    return t


# -- byte-equal to MemStore, every csum zlib's --------------------------------

CASES = [pytest.param(seed, with_ctx, "none",
                      id=f"seed{seed}-{'engine' if with_ctx else 'scalar'}")
         for seed in range(5) for with_ctx in (True, False)]
CASES.append(pytest.param(5, True, "force", id="seed5-engine-compress"))
CASES.append(pytest.param(6, False, "force", id="seed6-scalar-compress"))


@pytest.mark.parametrize("seed,with_ctx,compression", CASES)
def test_bluestore_equals_memstore_over_seeded_ops(tmp_path, seed, with_ctx,
                                                   compression):
    ctx = None
    if with_ctx or compression != "none":
        # a floor of one block: every commit with a context rides the
        # engine, so staged views meet the digest batch at every size
        conf = {"bluestore_batched_csum_min": 1,
                "bluestore_compression_mode": compression}
        if compression != "none":
            conf["bluestore_compression_algorithm"] = "zlib"
        ctx = _ctx(f"bs-runs-{seed}", **conf)
    path = str(tmp_path / "bs")
    bs = _mount(path, ctx, engine=with_ctx)
    mem = create_objectstore("memstore", str(tmp_path / "mem"))
    mem.mkfs()
    mem.mount()
    rng = np.random.default_rng(1000 + seed)
    payload = _payload if compression == "none" else _soft_payload
    oids = [f"o{i}" for i in range(4)]
    sizes: dict[str, int] = {}
    try:
        for s in (bs, mem):
            s.apply_transaction(Transaction().create_collection(CID))
        for _step in range(30):
            t = _random_txn(rng, oids, sizes, payload)
            bs.apply_transaction(t)
            mem.apply_transaction(t)
            _same(bs, mem, oids)
            _check_committed(bs)
        assert bs.perf.value("write_runs") > 0
        # the staging each case is for did carry it
        assert (bs.perf.value("csum_batches") > 0) == with_ctx
        assert (bs.perf.value("compress_blocks") > 0) \
            == (compression != "none")
        bs.umount()
        bs = _mount(path, ctx, engine=with_ctx)
        _same(bs, mem, oids)
        _check_committed(bs)
        # and the remounted allocator goes on without touching a live block
        t = Transaction().write(CID, "fresh", 0, _payload(seed, 40 * BLOCK))
        bs.apply_transaction(t)
        mem.apply_transaction(t)
        _same(bs, mem, oids + ["fresh"])
        _check_committed(bs)
    finally:
        bs.umount()
        mem.umount()
        _stop_engines(ctx)


def test_runs_cuts_consecutive_numbers():
    assert list(_runs([])) == []
    assert list(_runs([7])) == [(0, 1)]
    assert list(_runs([3, 4, 5, 9, 10, 12])) == [(0, 3), (3, 5), (5, 6)]
    assert list(_runs([5, 4, 3])) == [(0, 1), (1, 2), (2, 3)]
    assert list(_runs(list(range(128)))) == [(0, 128)]


# -- the crash shape ----------------------------------------------------------

@pytest.mark.parametrize("with_ctx", [True, False], ids=["engine", "scalar"])
def test_failed_kv_commit_leaves_old_extents_untouched(tmp_path, with_ctx):
    """The KV commit of a 128-block overwrite fails after the blocks
    were written: the old bytes read back, the old extents were never
    written to, and a remount's allocator hands out no live block."""
    ctx = _ctx("bs-runs-crash", bluestore_batched_csum_min=1) \
        if with_ctx else None
    path = str(tmp_path / "bs")
    s = _mount(path, ctx)
    old, new = _payload(1, SHARD), _payload(2, SHARD)
    try:
        s.apply_transaction(Transaction().create_collection(CID)
                            .write(CID, "o", 0, old)
                            .write(CID, "other", 0, _payload(3, 9 * BLOCK)))
        live = {b for m in _metas(s).values() for b in m["extents"]}
        old_ext = _metas(s)[f"{CID}\x00o"]["extents"]
        assert len(old_ext) == 128 and len(live) == 137
        size_before = os.path.getsize(s._block_path)

        def boom(kvt):
            raise OSError("kv device gone")

        real_submit = s._db.submit_transaction
        s._db.submit_transaction = boom
        with pytest.raises(OSError, match="kv device gone"):
            s.apply_transaction(Transaction().truncate(CID, "o", 0)
                                .write(CID, "o", 0, new))
        s._db.submit_transaction = real_submit
        # the new blocks did reach the file, beyond every old one
        assert os.path.getsize(s._block_path) >= size_before + SHARD
        assert [_raw(s, b) for b in old_ext] == \
            [old[i * BLOCK:(i + 1) * BLOCK] for i in range(128)]
        assert s.read(CID, "o") == old
        assert not set(s._alloc._free) & live    # nothing released
        # the next batch starts clean and commits
        s.apply_transaction(Transaction().write(CID, "next", 0, new))
        assert s.read(CID, "next") == new and s.read(CID, "o") == old
        _check_committed(s)
        # a hard kill: drop the handles, mount again
        s._f.close()
        s._db.close()
        s = _mount(path, ctx)
        live = {b for m in _metas(s).values() for b in m["extents"]}
        assert len(live) == 137 + 128
        assert not set(s._alloc.allocate(400)) & live
        assert s.read(CID, "o") == old and s.read(CID, "next") == new
    finally:
        s.umount()
        _stop_engines(ctx)


@pytest.mark.parametrize("with_ctx", [True, False], ids=["engine", "scalar"])
def test_failed_apply_resets_the_batch_state(tmp_path, with_ctx):
    """An op after a run-written body raises: the batch's pending
    checksums, displaced blocks and dirty flag are dropped, and the
    object is as it was."""
    ctx = _ctx("bs-runs-abort", bluestore_batched_csum_min=1) \
        if with_ctx else None
    s = _mount(str(tmp_path / "bs"), ctx)
    old, new = _payload(4, SHARD), _payload(5, SHARD)
    try:
        s.apply_transaction(Transaction().create_collection(CID)
                            .write(CID, "o", 0, old))
        seen = {}
        real_apply = s._apply_one

        def spy(op, *a):
            real_apply(op, *a)
            seen["pending"] = len(s._pending_csum)
            seen["freed"] = len(s._freed)
            seen["dirty"] = s._block_dirty

        s._apply_one = spy
        with pytest.raises(KeyError, match="no collection"):
            s.apply_transaction(Transaction().write(CID, "o", 0, new)
                                .write("no.such", "x", 0, b"y"))
        s._apply_one = real_apply
        # the write had been applied before the batch failed ...
        assert seen == {"pending": 128 if with_ctx else 0, "freed": 128,
                        "dirty": True}
        # ... and nothing of it is left
        assert s._pending_csum == {} and s._freed == []
        assert s._block_dirty is False
        assert s.read(CID, "o") == old
        _check_committed(s)
        s.apply_transaction(Transaction().write(CID, "o", 0, new))
        assert s.read(CID, "o") == new
        _check_committed(s)
    finally:
        s.umount()
        _stop_engines(ctx)


# -- the counters that say it engages -----------------------------------------

def _io_spy(monkeypatch):
    """Count the positioned reads and writes the block file sees."""
    calls = {"pwrite": [], "pread": []}
    real_pwrite, real_pread = os.pwrite, os.pread

    def pwrite(fd, data, off):
        calls["pwrite"].append(len(data))
        return real_pwrite(fd, data, off)

    def pread(fd, n, off):
        calls["pread"].append(n)
        return real_pread(fd, n, off)

    monkeypatch.setattr(bluestore_mod.os, "pwrite", pwrite)
    monkeypatch.setattr(bluestore_mod.os, "pread", pread)
    return calls


def _counts(s: BlueStoreLite) -> dict:
    blue = telemetry.bluestore_summary()
    out = {k: s.perf.value(k) for k in ("write_runs", "write_run_blocks",
                                        "read_runs", "read_run_blocks")}
    out.update({f"global_{k}": blue[k] for k in (
        "write_runs", "write_run_blocks", "read_runs", "read_run_blocks",
        "read_verify_batches", "read_verify_blocks")})
    return out


def _delta(s, before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts(s).items()}


@pytest.mark.parametrize("groups", [1, 2, 4, 16, 128])
def test_write_counts_one_run_per_group_of_free_blocks(tmp_path, monkeypatch,
                                                       groups):
    """A fresh 128-block aligned write is one run and one write to the
    block file; over a free set that deletes have cut into g groups of
    consecutive blocks it is g runs, g writes."""
    s = _mount(str(tmp_path / "bs"), None)
    payload = _payload(groups, SHARD)
    try:
        s.apply_transaction(Transaction().create_collection(CID))
        if groups > 1:
            # punch g groups of 128 / g blocks out of a 256-block object:
            # exactly 128 free blocks, none next to another group's
            s.apply_transaction(
                Transaction().write(CID, "base", 0, _payload(99, 2 * SHARD)))
            t = Transaction()
            for g in range(groups):
                t.zero(CID, "base", g * (256 // groups) * BLOCK,
                       (128 // groups) * BLOCK)
            s.apply_transaction(t)
            assert len(s._alloc._free) == 128
        calls = _io_spy(monkeypatch)
        before = _counts(s)
        s.apply_transaction(Transaction().truncate(CID, "o", 0)
                            .write(CID, "o", 0, payload))
        d = _delta(s, before)
        assert d["write_runs"] == d["global_write_runs"] == groups
        assert d["write_run_blocks"] == d["global_write_run_blocks"] == 128
        assert calls["pwrite"] == [SHARD // groups] * groups
        assert calls["pread"] == []
        ext = _metas(s)[f"{CID}\x00o"]["extents"]
        assert len(list(_runs(ext))) == groups
        assert s.read(CID, "o") == payload
        _check_committed(s)
    finally:
        s.umount()


def test_unaligned_write_is_head_body_tail(tmp_path, monkeypatch):
    """An unaligned write patches its head and tail block and moves
    the whole blocks between them as one run."""
    s = _mount(str(tmp_path / "bs"), None)
    base, patch = _payload(7, 20 * BLOCK), _payload(8, 10 * BLOCK + 300)
    try:
        s.apply_transaction(Transaction().create_collection(CID)
                            .write(CID, "o", 0, base))
        calls = _io_spy(monkeypatch)
        before = _counts(s)
        off = 3 * BLOCK + 1000
        s.apply_transaction(Transaction().write(CID, "o", off, patch))
        d = _delta(s, before)
        # head (rest of block 3), blocks 4..12 whole, tail (start of 13)
        assert calls["pwrite"] == [BLOCK, 9 * BLOCK, BLOCK]
        assert d["write_runs"] == 3 and d["write_run_blocks"] == 11
        assert calls["pread"] == [BLOCK, BLOCK]     # the two patched blocks
        assert s.read(CID, "o") == \
            base[:off] + patch + base[off + len(patch):]
        _check_committed(s)
    finally:
        s.umount()


def test_wide_read_is_one_run_one_digest_batch(tmp_path, monkeypatch):
    """A 128-block read through ``_batch_read_verify``: one read of the
    block file, one digest batch of 128 — and a flipped byte in block
    77 of the run still raises the checksum error naming that block."""
    ctx = _ctx("bs-runs-read", bluestore_batched_csum_min=1)
    s = _mount(str(tmp_path / "bs"), ctx)
    payload = _payload(9, SHARD)
    try:
        s.apply_transaction(Transaction().create_collection(CID)
                            .write(CID, "o", 0, payload))
        ext = _metas(s)[f"{CID}\x00o"]["extents"]
        assert ext == list(range(ext[0], ext[0] + 128))
        calls = _io_spy(monkeypatch)
        before = _counts(s)
        assert s.read(CID, "o") == payload
        d = _delta(s, before)
        assert calls["pread"] == [SHARD]
        assert d["read_runs"] == d["global_read_runs"] == 1
        assert d["read_run_blocks"] == d["global_read_run_blocks"] == 128
        assert d["global_read_verify_batches"] == 1
        assert d["global_read_verify_blocks"] == 128
        # two objects' blocks interleaved in the file: runs of one
        s.apply_transaction(Transaction().write(CID, "a", 0, bytes(BLOCK)))
        for i in range(1, 12):
            s.apply_transaction(
                Transaction().write(CID, "b", (i - 1) * BLOCK, bytes(BLOCK)))
            s.apply_transaction(Transaction().write(
                CID, "a", i * BLOCK, _payload(i, BLOCK)))
        before = _counts(s)
        s.read(CID, "a")
        d = _delta(s, before)
        assert d["read_runs"] == 12 and d["read_run_blocks"] == 12
        assert d["global_read_verify_batches"] == 1
        # corruption inside a run is still caught, block by block
        with open(s._block_path, "r+b") as f:
            f.seek(ext[77] * BLOCK + 1234)
            f.write(bytes([payload[77 * BLOCK + 1234] ^ 0x20]))
        with pytest.raises(IOError, match=f"checksum mismatch on block "
                                          f"{ext[77]}:"):
            s.read(CID, "o")
        # the scalar path under the floor names it too
        with pytest.raises(IOError, match=f"block {ext[77]}:"):
            s.read(CID, "o", 77 * BLOCK, BLOCK)
        assert s.read(CID, "o", 0, 77 * BLOCK) == payload[:77 * BLOCK]
    finally:
        s.umount()
        _stop_engines(ctx)
