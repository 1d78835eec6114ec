"""The device-resident objectstore write path: the ``bluestore_data``
dispatch channel's bit-exactness and fault ladder, the tpu_bitplane
compressor plugin, the compressor registry's kwargs/typed-error
contract, the KV journal's loud truncation ledger, and BlueStoreLite
end-to-end with batched checksums + block compression."""

from __future__ import annotations

import json
import os
import struct
import time
import zlib

import numpy as np
import pytest

from ceph_tpu import compressor
from ceph_tpu.common import failpoint
from ceph_tpu.objectstore import Transaction
from ceph_tpu.objectstore.bluestore import BLOCK, BlueStoreLite
from ceph_tpu.objectstore.kv import KVTransaction, LogDB
from ceph_tpu.ops import checksum_kernel as ck
from ceph_tpu.ops import compression_kernel as bk
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import (
    DeviceDispatchEngine, submit_bluestore_data)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoint.clear()
    yield
    failpoint.clear()


def _engine(**kw):
    eng = DeviceDispatchEngine(stats=telemetry.DispatchStats(), **kw)
    eng.fault_backoff_ms = 1.0
    eng.fault_backoff_max_ms = 5.0
    eng.probe_interval = 0.05
    return eng


def _wait_breaker(eng, channel, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.breaker_states().get(channel) == state:
            return True
        time.sleep(0.02)
    return False


# -- the bluestore_data digest channel ---------------------------------------

class TestBluestoreDataChannel:
    #: empty, sub-word, odd, and width-bucket-edge sizes: the unpad
    #: epilogue must hold across all of them
    SIZES = [0, 1, 3, 7, 8, 9, 63, 64, 65, 255, 256, 1000,
             ck.MIN_WIDTH - 1, ck.MIN_WIDTH, ck.MIN_WIDTH + 1,
             4095, 4096, 4097]

    def test_bit_exact_property_vs_zlib_crc32(self):
        """The acceptance pin: column 0 of a submit_bluestore_data
        batch (through the engine, padding and Z^-pad unpadding
        included) equals the host zlib.crc32 of every stored payload,
        for sizes 0 / odd / bucket-edge and random patterns."""
        rng = np.random.default_rng(17)
        eng = _engine()
        try:
            for round_ in range(2):
                sizes = list(self.SIZES) + [
                    int(s) for s in rng.integers(0, 6000, 12)]
                blobs = [rng.integers(0, 256, s, dtype=np.uint8)
                         .tobytes() for s in sizes]
                got = np.asarray(
                    submit_bluestore_data(eng, blobs).result(60))
                for i, b in enumerate(blobs):
                    assert int(got[i, 0]) == (zlib.crc32(b)
                                              & 0xFFFFFFFF), (round_, i)
        finally:
            eng.stop()

    #: case -> (blocks per run, bytes before each run's view in its
    #: buffer, bytes cut off the last blob)
    RUN_CASES = {
        "run_of_1": ([1], 0, 0), "run_of_3": ([3], 0, 0),
        "run_of_128": ([128], 0, 0), "run_of_129": ([129], 0, 0),
        "view_at_offset": ([5], 1234, 0), "two_runs": ([3, 2], 7, 0),
        "mixed_short_last": ([4], 0, BLOCK - 100),
    }

    @pytest.mark.parametrize("case", sorted(RUN_CASES))
    def test_runs_passed_as_buffers_bit_exact(self, case):
        """A request whose blobs are whole consecutive slices of the
        buffers passed as ``runs`` is built as a view of them (one
        run: the engine is handed the caller's own bytes) and column
        0 is zlib.crc32 per block; a short last blob takes the padded
        build, with the same answer."""
        from ceph_tpu.ops import dispatch
        rng = np.random.default_rng(23)
        counts, lead, cut = self.RUN_CASES[case]
        runs = []
        for n in counts:
            raw = rng.integers(0, 256, lead + n * BLOCK - cut,
                               dtype=np.uint8).tobytes()
            runs.append(memoryview(raw)[lead:])
        blobs = [r[lo:lo + BLOCK] for r in runs
                 for lo in range(0, len(r), BLOCK)]
        assert len(blobs) == sum(counts)
        eng = _engine()
        seen = []
        real = eng.submit

        def spy(key, fn, data, **kw):
            seen.append(data)
            return real(key, fn, data, **kw)

        eng.submit = spy
        try:
            got = np.asarray(submit_bluestore_data(
                eng, blobs, runs=runs).result(60))
            # plain lists of bytes, no runs: the padded build
            plain = np.asarray(submit_bluestore_data(
                eng, [bytes(b) for b in blobs]).result(60))
        finally:
            eng.stop()
        assert np.array_equal(plain, got)
        assert got.shape == (len(blobs), 2)
        for i, b in enumerate(blobs):
            assert int(got[i, 0]) == zlib.crc32(b) & 0xFFFFFFFF, i
        view = dispatch._whole_block_batch(blobs, runs)
        if cut:
            assert view is None
            assert seen[0].flags.owndata        # the padded build's
        else:
            assert view.shape == (len(blobs), BLOCK)
            assert np.shares_memory(seen[0], np.frombuffer(
                runs[0], dtype=np.uint8)) == (len(runs) == 1)

    @pytest.mark.parametrize("rows,width", [(1, BLOCK), (4, BLOCK),
                                            (128, BLOCK), (8, 64)])
    def test_whole_row_operands_equal_digest_operands(self, rows, width):
        """The resident epilogue operands of a whole-row shape are
        ``digest_operands([w] * n, w)``, dtypes included, and one
        object a shape for the life of the process."""
        mats, invp = ck.whole_row_operands(rows, width)
        want_m, want_p = ck.digest_operands([width] * rows, width)
        assert mats.dtype == np.uint32 and invp.dtype == np.uint8
        assert np.array_equal(np.asarray(mats), want_m)
        assert np.array_equal(np.asarray(invp), want_p)
        again = ck.whole_row_operands(rows, width)
        assert again[0] is mats and again[1] is invp

    def test_second_whole_row_request_uploads_data_alone(self):
        """The second request of a whole-row shape uploads one operand
        (the kernel's ``bytes_in`` grows by the batch's bytes), builds
        no operands and compiles nothing — and the padded build of the
        same rows runs the same executable."""
        rng = np.random.default_rng(29)
        n = 16
        buf = rng.integers(0, 256, n * BLOCK, dtype=np.uint8).tobytes()
        blobs = [buf[i * BLOCK:(i + 1) * BLOCK] for i in range(n)]
        ks = telemetry.registry().kernel("bluestore_data")
        eng = _engine()
        seen = []
        real = eng.submit

        def spy(key, fn, data, **kw):
            seen.append((key, kw.get("aux")))
            return real(key, fn, data, **kw)

        eng.submit = spy
        try:
            first = np.asarray(submit_bluestore_data(
                eng, blobs, runs=[buf]).result(60))
            entries = ck.digest_jit_entries()
            built = ck.whole_row_operands.cache_info().misses
            bytes_in = ks.dump()["bytes_in"]
            second = np.asarray(submit_bluestore_data(
                eng, blobs, runs=[buf]).result(60))
            assert ks.dump()["bytes_in"] - bytes_in == n * BLOCK
            assert ck.whole_row_operands.cache_info().misses == built
            assert ck.digest_jit_entries() == entries
            padded = np.asarray(submit_bluestore_data(
                eng, blobs).result(60))
            assert ck.digest_jit_entries() == entries
        finally:
            eng.stop()
        assert np.array_equal(first, second)
        assert np.array_equal(first, padded)
        # whole-row requests coalesce with their own kind only
        assert [k for k, _aux in seen] == [
            ("bluestore_data", BLOCK, "whole")] * 2 + [
            ("bluestore_data", BLOCK)]
        assert [aux is None for _k, aux in seen] == [True, True, False]

    def test_shares_scrub_jit_executable(self):
        """bluestore_digest_batched delegates to the SAME jitted entry
        point scrub uses: digesting through both names at one width
        must not add a compile cache entry for the second."""
        rng = np.random.default_rng(5)
        batch = rng.integers(0, 256, (4, 64), dtype=np.uint8)
        lengths = [64, 63, 1, 0]
        for i, n in enumerate(lengths):   # rows are ZERO-padded past n
            batch[i, n:] = 0
        mats, invp = ck.digest_operands(lengths, 64)
        ck.scrub_digest_batched(batch, mats, invp)
        before = ck.digest_jit_entries()
        got = np.asarray(
            ck.bluestore_digest_batched(batch, mats, invp))
        assert ck.digest_jit_entries() == before
        ref = ck.scrub_digest_ref(batch, lengths)
        assert np.array_equal(got, np.asarray(ref))

    def test_transient_fault_retries_bit_exact(self):
        eng = _engine()
        try:
            failpoint.set("dispatch.launch:bluestore_data", "nth:1")
            blobs = [b"retry-me" * 40, b"x" * 7]
            got = np.asarray(
                submit_bluestore_data(eng, blobs).result(60))
            for i, b in enumerate(blobs):
                assert int(got[i, 0]) == (zlib.crc32(b) & 0xFFFFFFFF)
            d = eng.stats.fault_dump()
            assert d["retries"] >= 1 and d["retry_successes"] >= 1, d
        finally:
            eng.stop()

    def test_hard_outage_opens_breaker_falls_back_then_recloses(self):
        """The PR 11 fault ladder on the sixth channel: a hard device
        outage opens the bluestore_data breaker, every batch is served
        by the bit-exact scrub_digest_ref oracle, and clearing the
        fault lets the background probe re-close the breaker."""
        eng = _engine()
        eng.breaker_threshold = 2
        try:
            failpoint.set("dispatch.launch:bluestore_data", "always")
            blobs = [b"outage" * 50, b"", b"z" * 129]
            for _ in range(3):
                got = np.asarray(
                    submit_bluestore_data(eng, blobs).result(60))
                for i, b in enumerate(blobs):
                    assert int(got[i, 0]) == (zlib.crc32(b)
                                              & 0xFFFFFFFF)
            d = eng.stats.fault_dump()
            assert d["breaker_opens"] >= 1, d
            assert d["fallback_batches"] >= 1, d
            assert eng.breaker_states()["bluestore_data"] == \
                telemetry.BREAKER_OPEN
            failpoint.clear()
            assert _wait_breaker(eng, "bluestore_data",
                                 telemetry.BREAKER_CLOSED)
            got = np.asarray(submit_bluestore_data(
                eng, [b"healed" * 3]).result(60))
            assert int(got[0, 0]) == (zlib.crc32(b"healed" * 3)
                                      & 0xFFFFFFFF)
        finally:
            eng.stop()


# -- the bitplane compression kernel + plugin ---------------------------------

class TestBitplane:

    def test_planes_device_matches_ref(self):
        rng = np.random.default_rng(9)
        batch = rng.integers(0, 256, (5, 96), dtype=np.uint8)
        ref = bk.bitplane_planes_ref(batch)
        dev = bk.bitplane_planes_batched(batch)
        assert np.array_equal(np.asarray(dev), ref)

    def test_encode_decode_roundtrip_property(self):
        rng = np.random.default_rng(11)
        blobs = [b"", b"\x00" * 100, b"a" * 999,
                 bytes(rng.integers(0, 256, 4096, dtype=np.uint8)),
                 bytes(rng.integers(0, 64, 4097, dtype=np.uint8)),
                 b"the quick brown fox " * 37]
        blobs += [bytes(rng.integers(0, 128, int(s), dtype=np.uint8))
                  for s in rng.integers(1, 3000, 8)]
        planes = bk.pack_planes(blobs)
        for b, p in zip(blobs, planes):
            body = bk.encode_block(b, p)
            assert bk.decode_block(body) == b

    def test_plugin_roundtrip_and_ratio_win_on_structured(self):
        """6-bit data has two provably-zero planes: the plugin must
        round-trip byte-identical AND beat the raw size clearly."""
        rng = np.random.default_rng(13)
        c = compressor.create("tpu_bitplane")
        data = bytes(rng.integers(0, 64, BLOCK, dtype=np.uint8))
        comp = c.compress(data)
        assert c.decompress(comp) == data
        assert len(comp) <= BLOCK * 0.8
        # random data keeps all planes: stored raw-tagged, one byte of
        # overhead, still round-trips
        rnd = bytes(rng.integers(0, 256, BLOCK, dtype=np.uint8))
        comp = c.compress(rnd)
        assert c.decompress(comp) == rnd
        assert len(comp) == BLOCK + 1

    def test_compress_batch_matches_single(self):
        rng = np.random.default_rng(15)
        c = compressor.create("tpu_bitplane")
        blobs = [bytes(rng.integers(0, 64, BLOCK, dtype=np.uint8))
                 for _ in range(4)]
        batch = c.compress_batch(blobs)
        for b, body in zip(blobs, batch):
            assert c.decompress(body) == b

    def test_corrupt_bodies_raise_compression_error(self):
        c = compressor.create("tpu_bitplane")
        good = c.compress(b"hello bitplane world" * 40)
        with pytest.raises(compressor.CompressionError):
            c.decompress(b"")                    # empty payload
        with pytest.raises(compressor.CompressionError):
            c.decompress(b"\x07whatever")        # unknown scheme tag
        with pytest.raises(compressor.CompressionError):
            c.decompress(good[:1])               # chopped header
        if good[:1] == b"\x01":
            with pytest.raises(compressor.CompressionError):
                c.decompress(good[:-3])          # truncated planes
        with pytest.raises(compressor.CompressionError):
            c.decompress(b"\x02not-zlib-data")   # corrupt zlib body


# -- the compressor registry contract ----------------------------------------

class TestCompressorRegistry:

    def test_unknown_kwarg_names_accepted_set(self):
        with pytest.raises(ValueError, match="accepted kwargs"):
            compressor.create("zlib", levle=3)
        with pytest.raises(ValueError, match="tpu_bitplane"):
            compressor.create("tpu_bitplane", mode="fast")
        # valid kwargs still construct
        assert compressor.create("zlib", level=1).level == 1
        assert compressor.create("tpu_bitplane", device=False) \
            .device is False

    def test_lzma_honors_level(self):
        """The seed's LzmaCompressor accepted a level and silently
        ignored it: preset must now follow the kwarg (preset 0 and 9
        produce different streams for compressible data)."""
        data = b"abcdefgh" * 4096
        fast = compressor.create("lzma", level=0).compress(data)
        small = compressor.create("lzma", level=9).compress(data)
        assert fast != small
        assert compressor.create("lzma").decompress(fast) == data
        assert compressor.create("lzma").decompress(small) == data

    def test_corrupt_input_raises_typed_error(self):
        for name in ("zlib", "lzma"):
            with pytest.raises(compressor.CompressionError):
                compressor.create(name).decompress(b"\xff" * 32)


# -- KV journal truncation ledger ---------------------------------------------

class TestKvJournalTruncation:

    def _logdb_with_tail(self, tmp_path, tail: bytes) -> LogDB:
        db = LogDB(str(tmp_path / "kv"))
        db.open()
        for i in range(3):
            db.submit_transaction(
                KVTransaction().set("p", f"k{i}", b"v"))
        db.close()
        with open(db._log_path, "ab") as f:
            f.write(tail)
        return db

    def test_clean_replay_reports_no_truncation(self, tmp_path):
        db = self._logdb_with_tail(tmp_path, b"")
        db.open()
        try:
            assert db.truncated_frames == 0
            assert db.truncated_bytes == 0
            assert db.get("p", "k2") == b"v"
        finally:
            db.close()

    def test_corrupt_tail_counts_frames_and_bytes(self, tmp_path):
        garbage = struct.pack("<II", 40, 0xDEAD) + b"x" * 11
        db = self._logdb_with_tail(tmp_path, garbage)
        db.open()
        try:
            # everything before the stop replayed; the chopped tail is
            # counted loudly instead of presenting a clean mount
            assert db.get("p", "k2") == b"v"
            assert db.truncated_frames == 1
            assert db.truncated_bytes == len(garbage)
        finally:
            db.close()

    def test_reopen_does_not_double_count(self, tmp_path):
        garbage = b"\x01\x02\x03\x04\x05"
        db = self._logdb_with_tail(tmp_path, garbage)
        db.open()
        db.close()
        db.open()
        try:
            assert db.truncated_frames == 1
            assert db.truncated_bytes == len(garbage)
        finally:
            db.close()

    def test_bluestore_mount_surfaces_counter(self, tmp_path):
        s = BlueStoreLite(str(tmp_path))
        s.mkfs()
        s.mount()
        s.apply_transaction(Transaction().create_collection("1.0"))
        s.umount()
        with open(os.path.join(str(tmp_path), "kv", "kv.log"),
                  "ab") as f:
            f.write(b"torn-tail")
        before = telemetry.bluestore_dump()
        s2 = BlueStoreLite(str(tmp_path))
        s2.mount()
        try:
            assert s2.perf.value("kv_journal_truncated") == 1
            after = telemetry.bluestore_dump()
            assert after["kv_journal_truncated"] == \
                before["kv_journal_truncated"] + 1
            assert after["kv_journal_lost_bytes"] == \
                before["kv_journal_lost_bytes"] + len(b"torn-tail")
        finally:
            s2.umount()


# -- BlueStoreLite end-to-end -------------------------------------------------

@pytest.fixture(scope="class")
def ctx():
    from ceph_tpu.common.context import CephTpuContext
    c = CephTpuContext("test-bluestore-data")
    c.conf.set("bluestore_batched_csum_min", "1", source="cli")
    c.conf.set("bluestore_batched_read_min", "1", source="cli")
    try:
        yield c
    finally:
        for attr in ("_decode_dispatch", "_dispatch"):
            e = getattr(c, attr, None)
            if e is not None:
                e.stop()


def _host_csum_audit(store) -> bool:
    """Every committed csum equals host zlib.crc32 of the STORED
    bytes — the bit-exactness gate on whatever path computed it."""
    for blob in store._db.get_range("obj").values():
        meta = json.loads(blob.decode())
        co = meta.get("comp") or []
        for bi, b in enumerate(meta["extents"]):
            if b < 0:
                continue
            comp = co[bi] if bi < len(co) else None
            data = store._read_block(b)
            stored = data[:comp[1]] if comp else data
            if zlib.crc32(stored) != meta["csum"][bi]:
                return False
    return True


class TestBlueStoreBatched:

    def _store(self, tmp_path, ctx, name="s"):
        s = BlueStoreLite(str(tmp_path / name), ctx=ctx)
        s.mkfs()
        s.mount()
        s.apply_transaction(Transaction().create_collection("2.0"))
        return s

    def test_batched_csums_equal_scalar_store(self, tmp_path, ctx):
        """The same writes through a batched store and a bare scalar
        store commit IDENTICAL csum lists (and both satisfy the host
        audit) — the channel changes how checksums are computed, never
        what they are."""
        rng = np.random.default_rng(2)
        payload = bytes(rng.integers(0, 256, 6 * BLOCK + 123,
                                     dtype=np.uint8))
        batched = self._store(tmp_path, ctx, "batched")
        scalar = self._store(tmp_path, None, "scalar")
        try:
            before = telemetry.bluestore_dump()
            for s in (batched, scalar):
                t = Transaction()
                t.write("2.0", "obj", 0, payload)
                t.write("2.0", "obj", 3 * BLOCK + 7, b"patch" * 100)
                s.apply_transaction(t)
            after = telemetry.bluestore_dump()
            assert after["csum_batches"] > before["csum_batches"]
            mb = json.loads(
                batched._db.get("obj", "2.0\x00obj").decode())
            ms = json.loads(
                scalar._db.get("obj", "2.0\x00obj").decode())
            assert mb["csum"] == ms["csum"]
            assert None not in mb["csum"]
            assert _host_csum_audit(batched)
            assert batched.read("2.0", "obj") == \
                scalar.read("2.0", "obj")
        finally:
            batched.umount()
            scalar.umount()

    def test_channel_outage_scalar_oracle_carries_commits(
            self, tmp_path, ctx):
        """Kill the device launch under the channel: commits must keep
        landing with correct csums (engine-level host oracle or the
        store's scalar fallback — either way bit-exact)."""
        rng = np.random.default_rng(3)
        s = self._store(tmp_path, ctx, "outage")
        eng = ctx.decode_dispatch_engine()
        old_thresh = eng.breaker_threshold
        eng.breaker_threshold = 2
        try:
            failpoint.set("dispatch.launch:bluestore_data", "always")
            for i in range(3):
                t = Transaction()
                t.write("2.0", f"o{i}", 0,
                        bytes(rng.integers(0, 256, 3 * BLOCK,
                                           dtype=np.uint8)))
                s.apply_transaction(t)
            assert _host_csum_audit(s)
            assert eng.breaker_states().get("bluestore_data") == \
                telemetry.BREAKER_OPEN
            failpoint.clear()
            assert _wait_breaker(eng, "bluestore_data",
                                 telemetry.BREAKER_CLOSED)
            # channel healed: the next commit rides the device again
            t = Transaction()
            t.write("2.0", "healed", 0, b"h" * BLOCK)
            s.apply_transaction(t)
            assert _host_csum_audit(s)
        finally:
            eng.breaker_threshold = old_thresh
            s.umount()

    def test_compression_force_roundtrip_and_shrink(self, tmp_path,
                                                    ctx):
        rng = np.random.default_rng(4)
        s = self._store(tmp_path, ctx, "comp")
        try:
            s.set_pool_compression(2, "force", "tpu_bitplane")
            payload = bytes(rng.integers(0, 64, 8 * BLOCK,
                                         dtype=np.uint8))
            t = Transaction()
            t.write("2.0", "z", 0, payload)
            s.apply_transaction(t)
            m = json.loads(s._db.get("obj", "2.0\x00z").decode())
            assert all(c is not None and c[0] == "tpu_bitplane"
                       and c[1] < BLOCK for c in m["comp"])
            assert _host_csum_audit(s)
            assert s.read("2.0", "z") == payload
            # partial overwrite of a compressed block round-trips too
            t = Transaction()
            t.write("2.0", "z", BLOCK + 11, b"Y" * 100)
            s.apply_transaction(t)
            exp = bytearray(payload)
            exp[BLOCK + 11:BLOCK + 111] = b"Y" * 100
            assert s.read("2.0", "z") == bytes(exp)
            # clone copies stored (compressed) bytes
            t = Transaction()
            t.clone("2.0", "z", "z2")
            s.apply_transaction(t)
            assert s.read("2.0", "z2") == bytes(exp)
        finally:
            s.umount()

    def test_corrupt_compressed_block_is_eio(self, tmp_path, ctx):
        rng = np.random.default_rng(5)
        s = self._store(tmp_path, ctx, "corrupt")
        try:
            s.set_pool_compression(2, "force", "tpu_bitplane")
            payload = bytes(rng.integers(0, 64, BLOCK,
                                         dtype=np.uint8))
            t = Transaction()
            t.write("2.0", "x", 0, payload)
            s.apply_transaction(t)
            m = json.loads(s._db.get("obj", "2.0\x00x").decode())
            block, clen = m["extents"][0], m["comp"][0][1]
            # flip a stored byte on disk: the crc must catch it before
            # decompression is even attempted
            s._f.seek(block * BLOCK + clen // 2)
            old = s._f.read(1)
            s._f.seek(block * BLOCK + clen // 2)
            s._f.write(bytes([old[0] ^ 0x40]))
            s._f.flush()
            with pytest.raises(IOError, match="checksum mismatch"):
                s.read("2.0", "x")
            # now break the body STRUCTURALLY (unknown scheme tag) and
            # make the crc match it, so only decompression can object
            # -> still EIO, attributed to decompress_errors
            s._f.seek(block * BLOCK)
            s._f.write(b"\x07")
            s._f.flush()
            s._f.seek(block * BLOCK)
            body = s._f.read(clen)
            m["csum"][0] = zlib.crc32(body)
            kvt = s._db.get_transaction()
            kvt.set("obj", "2.0\x00x", json.dumps(m).encode())
            s._db.submit_transaction(kvt)
            before = telemetry.bluestore_dump()
            with pytest.raises(IOError, match="decompress"):
                s.read("2.0", "x")
            after = telemetry.bluestore_dump()
            assert after["decompress_errors"] > \
                before["decompress_errors"]
        finally:
            s.umount()

    def test_batched_read_verify_catches_flip(self, tmp_path, ctx):
        rng = np.random.default_rng(6)
        s = self._store(tmp_path, ctx, "readv")
        try:
            payload = bytes(rng.integers(0, 256, 12 * BLOCK,
                                         dtype=np.uint8))
            t = Transaction()
            t.write("2.0", "r", 0, payload)
            s.apply_transaction(t)
            before = telemetry.bluestore_dump()
            assert s.read("2.0", "r") == payload
            after = telemetry.bluestore_dump()
            assert after["read_verify_batches"] > \
                before["read_verify_batches"]
            m = json.loads(s._db.get("obj", "2.0\x00r").decode())
            s._f.seek(m["extents"][5] * BLOCK + 99)
            s._f.write(b"\xff")
            s._f.flush()
            with pytest.raises(IOError, match="checksum mismatch"):
                s.read("2.0", "r")
        finally:
            s.umount()

    def test_wal_deferred_and_remount_survive_batching(self, tmp_path,
                                                       ctx):
        """Deferred small writes, folds, and a remount all interleave
        with the batched csum path without losing a byte."""
        rng = np.random.default_rng(7)
        path = tmp_path / "wal"
        s = BlueStoreLite(str(path), ctx=ctx)
        s.mkfs()
        s.mount()
        s.apply_transaction(Transaction().create_collection("2.0"))
        base = bytes(rng.integers(0, 256, 4 * BLOCK, dtype=np.uint8))
        t = Transaction()
        t.write("2.0", "w", 0, base)
        s.apply_transaction(t)
        exp = bytearray(base)
        for i in range(20):   # > WAL_MAX forces a fold mid-stream
            off = (i * 37) % (4 * BLOCK - 64)
            t = Transaction()
            t.write("2.0", "w", off, bytes([i]) * 64)
            s.apply_transaction(t)
            exp[off:off + 64] = bytes([i]) * 64
        assert s.read("2.0", "w") == bytes(exp)
        s.umount()
        s2 = BlueStoreLite(str(path), ctx=ctx)
        s2.mount()
        try:
            assert s2.read("2.0", "w") == bytes(exp)
            assert _host_csum_audit(s2)
        finally:
            s2.umount()
