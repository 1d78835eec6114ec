"""Faults planted under the rbd cells' timed path, to show that
`correct` fails: perfbench/faults.py's way, for the calls an image's
writes go through (faults.py is the benchmark's and stays as it is).

  rbd_ec_overwrite  lost_write      every 5th object write goes to
                                    another name, so the image's object
                                    never holds it
                    altered_parity  faults.py's: one byte of the parity
                                    the encode engine hands back is
                                    changed, every 5th write
                    stale_stripe    a ranged sub-read is served from its
                                    shard as it was one write earlier,
                                    under the shard's current version
"""

from __future__ import annotations

import contextlib

from perfbench import faults

FAULTS = {"rbd_ec_overwrite": ("lost_write", "altered_parity",
                               "stale_stripe")}


@contextlib.contextmanager
def plant(name: str):
    with _PLANTERS[name]():
        yield


def _lost_write():
    from ceph_tpu.client.rados import IoCtx
    count = [0]

    def make(real):
        def aio_write(self, oid, data, offset=0):
            count[0] += 1
            if count[0] % 5 == 0:
                oid += ".elsewhere"
            return real(self, oid, data, offset)
        return aio_write
    return faults._patched(IoCtx, "aio_write", make)


def _stale_stripe():
    """Every ranged shard write keeps the bytes it replaces; a later
    ranged read of the same extent gets those bytes back with the
    shard's version as it stands — a shard that kept its old data under
    a new version, which the gather cannot tell from a good one."""
    from ceph_tpu.osd.daemon import OSDDaemon
    older: dict = {}

    def make_write(real):
        def _ec_shard_write(self, t, pool, pgid, oid, shard, chunk,
                            offset, shard_len, truncate,
                            expected_prior=None):
            if not truncate and pool is not None \
                    and pool.allows_ecoverwrites():
                key = (self.osd_id, pgid, f"{oid}:{shard}", offset,
                       len(chunk))
                try:
                    was = self.store.read(f"{pgid[0]}.{pgid[1]}", key[2],
                                          offset, len(chunk))
                except (KeyError, OSError):
                    was = None
                if was is not None and was != chunk:
                    older[key] = was
            return real(self, t, pool, pgid, oid, shard, chunk, offset,
                        shard_len, truncate, expected_prior)
        return _ec_shard_write

    def make_read(real):
        def _read_shard_verified(self, pgid, oid, shard, off=0, length=0):
            got = real(self, pgid, oid, shard, off, length)
            was = older.get((self.osd_id, pgid, f"{oid}:{shard}", off,
                             length)) if length else None
            if got is None or was is None:
                return got
            return (was,) + tuple(got[1:])
        return _read_shard_verified

    @contextlib.contextmanager
    def planted():
        with faults._patched(OSDDaemon, "_ec_shard_write", make_write), \
                faults._patched(OSDDaemon, "_read_shard_verified",
                                make_read):
            yield
    return planted()


_PLANTERS = {"lost_write": _lost_write,
             "altered_parity": faults._altered_parity,
             "stale_stripe": _stale_stripe}
