"""glibc's malloc thresholds are fixed by the first OSD or PG mapping
service a process starts (common/allocator.py): whether a process
reuses its object- and table-sized buffers or maps each anew may not
hang on what it happened to free first."""

import ctypes

import pytest

from ceph_tpu.common import allocator


def _glibc() -> bool:
    try:
        ctypes.CDLL("libc.so.6").mallopt
        return True
    except (OSError, AttributeError):
        return False


def test_pin_fixes_both_thresholds_and_is_idempotent(monkeypatch):
    if not _glibc():
        pytest.skip("no glibc mallopt here")
    monkeypatch.setattr(allocator, "_pinned", False)
    assert allocator.pin_malloc_thresholds() is True
    assert allocator._pinned is True
    # again: nothing is loaded or called
    monkeypatch.setattr(ctypes, "CDLL", None)
    assert allocator.pin_malloc_thresholds() is True


def test_a_libc_without_mallopt_is_left_as_it_is(monkeypatch):
    monkeypatch.setattr(allocator, "_pinned", False)

    def no_libc(_name):
        raise OSError("no such library")

    monkeypatch.setattr(ctypes, "CDLL", no_libc)
    assert allocator.pin_malloc_thresholds() is False
    assert allocator._pinned is False


def test_an_osd_pins_before_it_mounts_its_store(monkeypatch, tmp_path):
    from ceph_tpu.osd import daemon
    from ceph_tpu.tools.vstart import MiniCluster
    calls = []
    monkeypatch.setattr(daemon, "pin_malloc_thresholds",
                        lambda: calls.append(1) or True)
    c = MiniCluster(n_osds=2, store_type="memstore").start()
    try:
        assert len(calls) == 2
    finally:
        c.stop()


def test_a_mapping_service_pins_before_its_first_epoch(monkeypatch):
    """The PG mapping service builds and drops tables of the pool's
    size every epoch: it pins, engine-less or behind a context."""
    from ceph_tpu.osd import mapping
    calls = []
    monkeypatch.setattr(mapping, "pin_malloc_thresholds",
                        lambda: calls.append(1) or True)
    mapping.SharedPGMappingService()
    mapping.SharedPGMappingService(backend="scalar", fused=False)
    assert len(calls) == 2
