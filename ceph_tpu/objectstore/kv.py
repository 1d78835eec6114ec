"""KeyValueDB (src/kv/KeyValueDB.h analog): ordered KV with batched atomic
transactions, backing the mon store.  MemDB for tests; LogDB is a file-backed
append-log with checkpoint compaction (the RocksDB WAL+SST role collapsed to
its durability essentials)."""

from __future__ import annotations

import os
import struct
import threading
import zlib

from ceph_tpu.common import tracing
from ceph_tpu.msg.encoding import Decoder, Encoder


class KVTransaction:
    def __init__(self):
        self.sets: list[tuple[str, str, bytes]] = []    # (prefix, key, value)
        self.rms: list[tuple[str, str]] = []

    def set(self, prefix: str, key: str, value: bytes) -> "KVTransaction":
        self.sets.append((prefix, key, bytes(value)))
        return self

    def rmkey(self, prefix: str, key: str) -> "KVTransaction":
        self.rms.append((prefix, key))
        return self

    def encode(self) -> bytes:
        e = Encoder()
        e.list(self.sets, lambda e2, s: (e2.str(s[0]), e2.str(s[1]),
                                         e2.bytes(s[2])))
        e.list(self.rms, lambda e2, r: (e2.str(r[0]), e2.str(r[1])))
        return e.tobytes()

    @staticmethod
    def decode(data: bytes) -> "KVTransaction":
        d = Decoder(data)
        t = KVTransaction()
        t.sets = d.list(lambda d2: (d2.str(), d2.str(), d2.bytes()))
        t.rms = d.list(lambda d2: (d2.str(), d2.str()))
        return t


class KeyValueDB:
    def get_transaction(self) -> KVTransaction:
        return KVTransaction()

    def submit_transaction(self, t: KVTransaction) -> None:
        raise NotImplementedError

    def get(self, prefix: str, key: str) -> bytes | None:
        raise NotImplementedError

    def get_range(self, prefix: str) -> dict[str, bytes]:
        """All keys under a prefix, ordered."""
        raise NotImplementedError

    def iterate(self, prefix: str | None = None):
        """Ordered (prefix, key, value) triples — optionally filtered to
        one prefix (KeyValueDB::get_iterator analog)."""
        raise NotImplementedError


class MemDB(KeyValueDB):
    def __init__(self):
        self._data: dict[tuple[str, str], bytes] = {}
        # analysis: allow[bare-lock] -- MemDB map leaf lock
        self._lock = threading.Lock()

    def submit_transaction(self, t: KVTransaction) -> None:
        with self._lock:
            for p, k, v in t.sets:
                self._data[(p, k)] = v
            for p, k in t.rms:
                self._data.pop((p, k), None)

    def get(self, prefix, key):
        with self._lock:
            return self._data.get((prefix, key))

    def get_range(self, prefix):
        with self._lock:
            return {k: v for (p, k), v in sorted(self._data.items())
                    if p == prefix}

    def iterate(self, prefix=None):
        with self._lock:
            return [(p, k, v) for (p, k), v in sorted(self._data.items())
                    if prefix is None or p == prefix]


_FRAME = struct.Struct("<II")


class LogDB(MemDB):
    """Durable MemDB: append-log of encoded transactions + checkpoint."""

    def __init__(self, path: str):
        super().__init__()
        self.path = path
        self._log_path = os.path.join(path, "kv.log")
        self._ckpt_path = os.path.join(path, "kv.ckpt")
        self._f = None
        #: replay truncation found by the LAST ``open()``: whether the
        #: replay stopped at a short/corrupt frame with bytes left
        #: behind, and how many bytes were dropped.  The seed broke
        #: out of the loop SILENTLY: a chopped journal looked like a
        #: clean mount while every later transaction was lost.  The
        #: owning store accumulates these into its
        #: ``kv_journal_truncated`` counter at mount.
        self.truncated_frames = 0
        self.truncated_bytes = 0

    def open(self) -> None:
        os.makedirs(self.path, exist_ok=True)
        self._data.clear()
        self.truncated_frames = 0
        self.truncated_bytes = 0
        if os.path.exists(self._ckpt_path):
            with open(self._ckpt_path, "rb") as f:
                d = Decoder(f.read())
            pairs = d.list(lambda d2: ((d2.str(), d2.str()), d2.bytes()))
            self._data.update(pairs)
        if os.path.exists(self._log_path):
            with open(self._log_path, "rb") as f:
                data = f.read()
            off = 0
            replayed = 0
            while off + _FRAME.size <= len(data):
                length, crc = _FRAME.unpack_from(data, off)
                start = off + _FRAME.size
                blob = data[start:start + length]
                if len(blob) < length or zlib.crc32(blob) != crc:
                    break
                MemDB.submit_transaction(self, KVTransaction.decode(blob))
                off = start + length
                replayed += 1
            leftover = len(data) - off
            if leftover:
                # a torn tail after a crash is one short frame and
                # expected; ANYTHING beyond the stop point is lost
                # either way, so say so loudly instead of presenting a
                # silently shortened history as a clean mount
                self.truncated_frames += 1
                self.truncated_bytes += leftover
                from ceph_tpu.common.logging import dout
                dout("kv", 0,
                     "LogDB %s: replay STOPPED at a short/corrupt "
                     "frame: %d transactions replayed, %d bytes "
                     "unreplayed past offset %d — any transactions "
                     "in those bytes are LOST",
                     self._log_path, replayed, leftover, off)
        self._f = open(self._log_path, "ab")

    def close(self) -> None:
        if self._f:
            self._f.flush()
            os.fsync(self._f.fileno())
            self._f.close()
            self._f = None

    def submit_transaction(self, t: KVTransaction) -> None:
        blob = t.encode()
        with self._lock:
            assert self._f is not None, "LogDB not open"
            self._f.write(_FRAME.pack(len(blob), zlib.crc32(blob)) + blob)
            with tracing.span("bluestore fsync", daemon="bluestore",
                              what="kv", wait=True):
                self._f.flush()
                os.fsync(self._f.fileno())
        MemDB.submit_transaction(self, t)

    def compact(self) -> None:
        e = Encoder()
        with self._lock:
            e.list(sorted(self._data.items()),
                   lambda e2, kv: (e2.str(kv[0][0]), e2.str(kv[0][1]),
                                   e2.bytes(kv[1])))
            tmp = self._ckpt_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(e.tobytes())
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._ckpt_path)
            self._f.close()
            self._f = open(self._log_path, "wb")
