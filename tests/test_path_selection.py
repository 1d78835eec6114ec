"""The old paths that remain, reached the way the code reaches them.

No option selects between an old and a new path any more: the scalar
scan, the host ``zlib.crc32`` loop, the per-block read verify, the
``shard_crc`` scrub loop and the synchronous EC encode / decode are
taken when the code OBSERVES a reason (an exception from the service, a
store with no context, a caller that is an engine thread, a batch under
a floor, a row over the kernel's width cap, a whole-object codec).  Each
case below produces one such reason and checks that the old path ran
and that its answer is bit-equal to the new path's."""

from __future__ import annotations

import json
import threading
import zlib

import numpy as np
import pytest

from ceph_tpu.objectstore import Transaction
from ceph_tpu.objectstore.bluestore import BLOCK, BlueStoreLite
from ceph_tpu.ops import checksum_kernel as ck
from ceph_tpu.ops.dispatch import submit_bluestore_data
from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.mapping import SharedPGMappingService
from ceph_tpu.tools.vstart import MiniCluster

pytestmark = pytest.mark.filterwarnings("ignore")


# -- helpers ------------------------------------------------------------------

def _ctx(name: str, **conf):
    from ceph_tpu.common.context import CephTpuContext
    c = CephTpuContext(name)
    for k, v in conf.items():
        c.conf.set(k, str(v), source="cli")
    return c


def _stop_engines(ctx) -> None:
    for attr in ("_decode_dispatch", "_dispatch"):
        e = getattr(ctx, attr, None)
        if e is not None:
            e.stop()


def _store(tmp_path, ctx, name: str) -> BlueStoreLite:
    s = BlueStoreLite(str(tmp_path / name), ctx=ctx)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection("2.0"))
    return s


def _csums(store, oid: str) -> list:
    return json.loads(
        store._db.get("obj", f"2.0\x00{oid}").decode())["csum"]


def _write(store, oid: str, payload: bytes) -> None:
    store.apply_transaction(Transaction().write("2.0", oid, 0, payload))


def _payload(seed: int, n: int) -> bytes:
    return bytes(np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8))


def _on_engine_thread(eng, fn):
    """Run ``fn()`` in a continuation on ``eng``'s completion thread
    (where EC-write and recovery continuations commit to the store) and
    return its result."""
    for _ in range(20):
        box: dict = {}
        done = threading.Event()

        def cb(_fut, box=box, done=done):
            try:
                box["on_engine"] = eng.owns_current_thread()
                if box["on_engine"]:
                    box["out"] = fn()
            except BaseException as e:      # _deliver swallows: carry it
                box["exc"] = e
            finally:
                done.set()

        submit_bluestore_data(eng, [b"x" * 64]).add_done_callback(cb)
        assert done.wait(120)
        if "exc" in box:
            raise box["exc"]
        if box["on_engine"]:
            return box["out"]
    raise AssertionError("no continuation ran on the engine's thread")


# -- the cases ----------------------------------------------------------------

def _case_update_to_raises_full_scalar_scan(tmp_path, monkeypatch):
    """The mapping service's ``update_to`` raises inside ``_handle_map``:
    the OSD scans every PG with the scalar pipeline (``_scan_pgs(None)``),
    ``lookup()`` answers from the scalar oracle, and the pool serves."""
    from test_mapping_service import _count_scan_scalar_calls

    def broken(self, *a, **kw):
        raise RuntimeError("mapping service down")

    monkeypatch.setattr(SharedPGMappingService, "update_to", broken)
    c = MiniCluster(n_osds=2, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(2)
        client = c.client()
        calls = _count_scan_scalar_calls(monkeypatch)
        pool = c.create_pool(client, pg_num=16, size=2)
        io = client.open_ioctx(pool)
        io.write_full("obj", b"scalar")
        assert io.read("obj") == b"scalar"
        assert calls["scan"] >= 16, calls       # a full scalar scan
        # nothing was ever published: every answer came from the oracle
        for osd in c.osds.values():
            assert osd.ctx.mapping_service().epoch == -1
    finally:
        c.stop()


def _case_bluestore_without_context_scalar_crc(tmp_path, monkeypatch):
    """A store with no context has no engine: zlib.crc32 settles its
    checksums, the same values the batched store commits."""
    ctx = _ctx("path-sel-noctx", bluestore_batched_csum_min=1)
    payload = _payload(11, 6 * BLOCK + 123)
    batched = _store(tmp_path, ctx, "batched")
    bare = _store(tmp_path, None, "bare")
    try:
        assert bare._batch_engine() is None
        _write(batched, "o", payload)
        _write(bare, "o", payload)
        assert batched.perf.value("csum_batches") == 1
        assert bare.perf.value("csum_batches") == 0
        want = [zlib.crc32(payload[i:i + BLOCK].ljust(BLOCK, b"\0"))
                for i in range(0, len(payload), BLOCK)]
        assert _csums(bare, "o") == want
        assert _csums(batched, "o") == want
        assert bare.read("2.0", "o") == payload
    finally:
        batched.umount()
        bare.umount()
        _stop_engines(ctx)


def _case_bluestore_on_engine_thread_scalar_crc(tmp_path, monkeypatch):
    """A commit made from an engine's own completion thread must not
    wait on that engine: ``_batch_engine()`` is None there and
    zlib.crc32 settles the checksums, equal to the batched ones."""
    ctx = _ctx("path-sel-engthread", bluestore_batched_csum_min=1)
    payload = _payload(12, 5 * BLOCK)
    s = _store(tmp_path, ctx, "s")
    try:
        eng = ctx.decode_dispatch_engine()
        assert s._batch_engine() is eng         # an ordinary caller
        _write(s, "plain", payload)
        assert s.perf.value("csum_batches") == 1

        def commit():
            assert s._batch_engine() is None
            _write(s, "cont", payload)
            return s.perf.value("csum_batches")

        assert _on_engine_thread(eng, commit) == 1   # no second batch
        assert _csums(s, "cont") == _csums(s, "plain")
        assert None not in _csums(s, "cont")
        assert s.read("2.0", "cont") == payload
    finally:
        s.umount()
        _stop_engines(ctx)


def _case_read_under_floor_verifies_per_block(tmp_path, monkeypatch):
    """A read of fewer than ``bluestore_batched_read_min`` blocks is
    verified block by block on the host; a wider one rides the digest
    channel.  Both return the bytes, both catch a flipped byte."""
    ctx = _ctx("path-sel-readfloor", bluestore_batched_csum_min=1)
    floor = int(ctx.conf.get("bluestore_batched_read_min"))
    payload = _payload(13, (floor + 4) * BLOCK)
    s = _store(tmp_path, ctx, "s")
    try:
        _write(s, "r", payload)
        seen = []
        orig = s._batch_read_verify

        def spy(*a, **kw):
            out = orig(*a, **kw)
            seen.append(len(out))
            return out

        monkeypatch.setattr(s, "_batch_read_verify", spy)
        under = (floor - 1) * BLOCK
        assert s.read("2.0", "r", 0, under) == payload[:under]
        assert seen == [0]                      # per-block path
        assert s.read("2.0", "r") == payload
        assert seen == [0, floor + 4]           # one digest batch
        m = json.loads(s._db.get("obj", "2.0\x00r").decode())
        s._f.seek(m["extents"][1] * BLOCK + 99)
        s._f.write(b"\xff" if payload[BLOCK + 99] != 0xFF else b"\x00")
        s._f.flush()
        for length in (under, None):
            with pytest.raises(IOError, match="checksum mismatch"):
                s.read("2.0", "r", 0, length)
    finally:
        s.umount()
        _stop_engines(ctx)


def _case_scrub_row_over_max_width_scalar_loop(tmp_path, monkeypatch):
    """A scrub chunk holding a row wider than the digest kernel's cap
    takes the ``shard_crc`` loop; narrower rows ride the channel, and
    the two fill the same triples."""
    from ceph_tpu.osd.ec_util import shard_crc
    c = MiniCluster(n_osds=1, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(1)
        client = c.client(timeout=30.0)
        pool = c.create_pool(client, pg_num=1, size=1)
        io = client.open_ioctx(pool)
        wide = _payload(14, ck.MAX_WIDTH + 1)
        io.write_full("wide", wide)
        for i in range(3):
            io.write_full(f"narrow{i}", _payload(20 + i, 1000 + i))
        osd = next(iter(c.osds.values()))
        cid = f"{pool}.0"
        _out, rows, _vers = osd._scrub_read_rows(cid)
        blobs = [r[1] for r in rows] + [r[2] for r in rows]
        assert max(len(b) for b in blobs) > ck.MAX_WIDTH
        assert osd._scrub_digest_rows(blobs) is None
        narrow = [r for r in rows if r[0] != "wide"]
        assert len(narrow) == 3
        digs = osd._scrub_digest_rows(
            [r[1] for r in narrow] + [r[2] for r in narrow])
        assert digs is not None
        assert (OSDDaemon._scrub_fill({}, narrow, digs)
                == OSDDaemon._scrub_fill({}, narrow, None))
        smap, _ = osd._scrub_map(cid)
        assert smap["wide"][:2] == (len(wide), shard_crc(wide))
        assert osd.scrub_all_pgs()["clean"]
    finally:
        c.stop()


def _case_whole_object_codec_sync_encode_and_decode(tmp_path,
                                                    monkeypatch):
    """A Clay pool (whole-object layout, no StripeInfo) encodes its
    write synchronously and ``_ec_submit_decode`` declines its degraded
    read; a jerasure pool on the same OSDs submits both.  Either way
    the read returns the acknowledged bytes."""
    answers: dict[int, list] = {}
    orig = OSDDaemon._ec_submit_decode

    def spy(self, reqid, state):
        out = orig(self, reqid, state)
        answers.setdefault(state["pool"].pool_id, []).append(out)
        return out

    monkeypatch.setattr(OSDDaemon, "_ec_submit_decode", spy)
    c = MiniCluster(n_osds=6, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(6)
        client = c.client(timeout=30.0)

        def submits() -> int:
            return sum(o.perf.value("ec_dispatch_submits")
                       for o in c.osds.values())

        def lose_shard(pool: int, oid: str) -> None:
            for osd in c.osds.values():
                for cid in osd.store.list_collections():
                    if cid.startswith(f"{pool}.") and \
                            f"{oid}:0" in osd.store.list_objects(cid):
                        osd.store.apply_transaction(
                            Transaction().remove(cid, f"{oid}:0"))
                        return
            raise AssertionError("shard 0 not found")

        clay = c.create_pool(client, pg_num=1, pool_type="erasure",
                             plugin="clay", k=4, m=2)
        rs = c.create_pool(client, pg_num=1, pool_type="erasure",
                           k=2, m=1)
        payload = _payload(15, 20000)
        s0 = submits()
        io = client.open_ioctx(clay)
        io.write_full("obj", payload)
        assert submits() == s0                  # encoded synchronously
        lose_shard(clay, "obj")
        assert io.read("obj") == payload
        assert answers.get(clay) and not any(answers[clay])

        io2 = client.open_ioctx(rs)
        io2.write_full("obj", payload)
        assert submits() == s0 + 1              # through the engine
        lose_shard(rs, "obj")
        assert io2.read("obj") == payload
        assert answers.get(rs) == [True]
    finally:
        c.stop()


def _case_balancer_warm_fails_scalar_histogram(tmp_path, monkeypatch):
    """When warming the shared cache fails the balancer reads every PG
    from the scalar pipeline, and counts the same placements."""
    from ceph_tpu import balancer
    from test_mapping_service import _base_map
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.warm(m)
    cached = balancer.pool_pg_histogram(m, 1, service=svc)

    def broken(self, osdmap):
        raise RuntimeError("no table build today")

    monkeypatch.setattr(SharedPGMappingService, "warm", broken)
    assert balancer._shared_service(m) is None
    assert balancer.pool_pg_histogram(m, 1) == cached


CASES = {
    "update_to_raises_full_scalar_scan":
        _case_update_to_raises_full_scalar_scan,
    "bluestore_without_context_scalar_crc":
        _case_bluestore_without_context_scalar_crc,
    "bluestore_on_engine_thread_scalar_crc":
        _case_bluestore_on_engine_thread_scalar_crc,
    "read_under_floor_verifies_per_block":
        _case_read_under_floor_verifies_per_block,
    "scrub_row_over_max_width_scalar_loop":
        _case_scrub_row_over_max_width_scalar_loop,
    "whole_object_codec_sync_encode_and_decode":
        _case_whole_object_codec_sync_encode_and_decode,
    "balancer_warm_fails_scalar_histogram":
        _case_balancer_warm_fails_scalar_histogram,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_old_path_taken_for_observed_reason(case, tmp_path, monkeypatch):
    CASES[case](tmp_path, monkeypatch)
