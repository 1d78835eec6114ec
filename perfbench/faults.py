"""Faults planted under the timed path, to show that `correct` fails.

Each is a context manager that breaks one guarantee a configuration
states, in the program's own classes, while a run goes on above it
unchanged.  The tests under tests/perfbench_tests plant them at toy
size; perfbench/control.py plants them on the chip at a cell's own size.
The benchmark's own runs never do.

  ec_pool       altered_write   one byte of every 5th object is changed
                                on its way to the cluster
                lost_write      every 5th write goes to another name, so
                                the acknowledged object holds nothing
                altered_parity  one byte of the parity that the encode
                                engine hands back is changed, in every
                                5th object
                altered_csum    one checksum of every 5th batch that the
                                block-digest program hands back is changed
  osdmap_churn  stale_state     update_to applies the old map's content
                                under the new epoch (state unchanged)
                altered_answer  lookup() returns one wrong OSD in a row
                half_delta      update_to reports half of the changed PGs
                hidden_rows     every 3rd PG answers a fixed wrong row, in
                                the table and in lookup(), and is never
                                reported as changed: wrong where the
                                program's own table does not look

`mended` is no fault: it puts jerasure's coding matrix, as the plain
reference builds it, in the place of the program's, to show what the
EC cells read on a program whose reed_sol_van is jerasure's (PERF.md,
Open questions).
"""

from __future__ import annotations

import contextlib

import numpy as np

FAULTS = {"ec_pool": ("altered_write", "lost_write", "altered_parity",
                      "altered_csum"),
          "osdmap_churn": ("stale_state", "altered_answer", "half_delta",
                           "hidden_rows")}


@contextlib.contextmanager
def plant(name: str):
    with _PLANTERS[name]():
        yield


@contextlib.contextmanager
def _patched(owner, attr: str, make, static: bool = False):
    """`attr` of a class or module, replaced by ``make(real)``."""
    real = getattr(owner, attr)
    setattr(owner, attr, make(real))
    try:
        yield
    finally:
        setattr(owner, attr, staticmethod(real) if static else real)


def _altered_write():
    from ceph_tpu.client.rados import IoCtx
    count = [0]

    def make(real):
        def aio_write_full(self, oid, data):
            count[0] += 1
            if count[0] % 5 == 0:
                data = bytes([data[0] ^ 1]) + data[1:]
            return real(self, oid, data)
        return aio_write_full
    return _patched(IoCtx, "aio_write_full", make)


def _lost_write():
    from ceph_tpu.client.rados import IoCtx
    count = [0]

    def make(real):
        def aio_write_full(self, oid, data):
            count[0] += 1
            if count[0] % 5 == 0:
                oid += ".elsewhere"
            return real(self, oid, data)
        return aio_write_full
    return _patched(IoCtx, "aio_write_full", make)


def _altered_parity():
    from ceph_tpu.osd.daemon import OSDDaemon
    count = [0]

    def make(real):
        def _ec_shard_columns(si, stripes, parity, n):
            count[0] += 1
            if count[0] % 5 == 0:
                parity = np.array(parity, copy=True)
                parity[0, 0, 0] ^= 1
            return real(si, stripes, parity, n)
        return staticmethod(_ec_shard_columns)
    return _patched(OSDDaemon, "_ec_shard_columns", make, static=True)


def _altered_csum():
    from ceph_tpu.ops import dispatch
    count = [0]

    class Altered:
        def __init__(self, future):
            self._future = future

        def result(self, timeout=None):
            dig = np.array(self._future.result(timeout=timeout), copy=True)
            dig[0, 0] ^= 1
            return dig

    def make(real):
        def submit_bluestore_data(eng, blobs, **kw):
            count[0] += 1
            future = real(eng, blobs, **kw)
            return Altered(future) if count[0] % 5 == 0 else future
        return submit_bluestore_data
    return _patched(dispatch, "submit_bluestore_data", make)


@contextlib.contextmanager
def mended():
    """jerasure's coding matrix under the program's reed_sol_van."""
    from ceph_tpu.ec import jerasure
    from perfbench.reference import rs_plain

    def make(_real):
        def big_vandermonde_distribution_matrix(rows, cols):
            return np.concatenate(
                [np.eye(cols, dtype=np.uint8),
                 rs_plain.coding_matrix(cols, rows - cols)])
        return big_vandermonde_distribution_matrix
    with _patched(jerasure, "big_vandermonde_distribution_matrix", make):
        yield


def _stale_state():
    from ceph_tpu.osd.mapping import SharedPGMappingService
    last = {}

    def make(real):
        def update_to(self, osdmap, from_epoch=None):
            if from_epoch is not None and id(self) in last:
                stale = last[id(self)].copy()
                stale.epoch = osdmap.epoch
                osdmap = stale
            last[id(self)] = osdmap
            return real(self, osdmap, from_epoch)
        return update_to
    return _patched(SharedPGMappingService, "update_to", make)


def _altered_answer():
    from ceph_tpu.osd.mapping import SharedPGMappingService

    def make(real):
        def lookup(self, osdmap, pool_id, pgid):
            up, up_primary, acting, acting_primary = real(
                self, osdmap, pool_id, pgid)
            if pgid % 7 == 0 and up:
                up = [up[0] ^ 1] + list(up[1:])
            return up, up_primary, acting, acting_primary
        return lookup
    return _patched(SharedPGMappingService, "lookup", make)


def _half_delta():
    from ceph_tpu.osd.mapping import MapUpdate, SharedPGMappingService

    def make(real):
        def update_to(self, osdmap, from_epoch=None):
            upd = real(self, osdmap, from_epoch)
            if upd.full:
                return upd
            kept = list(upd.changed)[::2]
            return MapUpdate(upd.epoch_from, upd.epoch_to, kept, False)
        return update_to
    return _patched(SharedPGMappingService, "update_to", make)


def _hidden_rows():
    from ceph_tpu.osd.mapping import MapUpdate, SharedPGMappingService
    wrong = [0, 1, 2]

    def hidden(pgid: int) -> bool:
        return pgid % 3 == 1

    def make_lookup(real):
        def lookup(self, osdmap, pool_id, pgid):
            if hidden(pgid):
                return list(wrong), wrong[0], list(wrong), wrong[0]
            return real(self, osdmap, pool_id, pgid)
        return lookup

    def make_update(real):
        def update_to(self, osdmap, from_epoch=None):
            upd = real(self, osdmap, from_epoch)
            if upd.full:
                return upd
            kept = [c for c in upd.changed if not hidden(c[1])]
            return MapUpdate(upd.epoch_from, upd.epoch_to, kept, False)
        return update_to

    def make_raw(real):
        def get_raw(self, pool_id):
            table = np.array(real(self, pool_id), copy=True)
            table[1::3] = wrong
            return table
        return get_raw

    @contextlib.contextmanager
    def planted():
        from ceph_tpu.osd.mapping import OSDMapMapping
        with _patched(SharedPGMappingService, "lookup", make_lookup), \
                _patched(SharedPGMappingService, "update_to", make_update), \
                _patched(OSDMapMapping, "get_raw", make_raw):
            yield
    return planted()


_PLANTERS = {"altered_write": _altered_write, "lost_write": _lost_write,
             "altered_parity": _altered_parity,
             "altered_csum": _altered_csum, "hidden_rows": _hidden_rows,
             "stale_state": _stale_state, "altered_answer": _altered_answer,
             "half_delta": _half_delta}
