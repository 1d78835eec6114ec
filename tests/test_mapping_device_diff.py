"""The epoch diff of the shared PG mapping service (osd.mapping):
`_changed_rows` through the named program `mapping_delta_diff` against a
host compare, on both sides of `FUSED_DIFF_HOST_MAX` and on a mesh, and
the three always-on counters that say which path an epoch's delta took
(`delta_device_diffs`, `delta_host_diffs`, `delta_upload_bytes`)."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.crush import build_two_level_map
from ceph_tpu.ops import telemetry
from ceph_tpu.osd import OSDMap, PGPool, SharedPGMappingService
from ceph_tpu.osd import mapping as mapping_mod
from ceph_tpu.osd.mapping import _changed_rows, _Tables

HOST_MAX = SharedPGMappingService.FUSED_DIFF_HOST_MAX
COUNTERS = ("delta_device_diffs", "delta_host_diffs", "delta_upload_bytes")


def counters() -> dict:
    s = telemetry.mapping_summary()
    return {k: s[k] for k in COUNTERS}


def moved(before: dict) -> dict:
    after = counters()
    return {k: after[k] - before[k] for k in COUNTERS}


def tables(rows: int, width: int, seed: int, changed: int):
    """Two packed tables that differ in `changed` seeded rows, one
    element each."""
    rng = np.random.default_rng(seed)
    old = rng.integers(0, 10_000, (rows, width), dtype=np.int32)
    new = old.copy()
    at = np.sort(rng.choice(rows, changed, replace=False))
    new[at, rng.integers(0, width, changed)] ^= 1
    return old, new, at


def test_the_diff_is_a_program_with_a_name_of_its_own():
    prog = mapping_mod._delta_diff_program()
    assert prog is mapping_mod._delta_diff_program()      # built once
    assert prog.__name__ == "mapping_delta_diff"
    old, new, at = tables(64, 10, 1, 5)
    assert "mapping_delta_diff" in prog.lower(old, new).as_text()
    np.testing.assert_array_equal(
        np.flatnonzero(np.asarray(prog(old, new))), at)


@pytest.mark.parametrize("rows,width,changed", [
    (1, 10, 1), (513, 3, 0), (4096, 10, 37), (HOST_MAX // 8 + 8, 8, 301)])
def test_changed_rows_equals_the_host_diff(rows, width, changed):
    old, new, at = tables(rows, width, rows + changed, changed)
    before = counters()
    got = _changed_rows(old, new)
    np.testing.assert_array_equal(got, at)
    np.testing.assert_array_equal(
        got, np.flatnonzero((old != new).any(axis=1)))
    assert moved(before) == {"delta_device_diffs": 1, "delta_host_diffs": 0,
                             "delta_upload_bytes": 2 * old.nbytes}


def test_changed_rows_on_a_mesh_equals_the_host_diff():
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    old, new, at = tables(2048, 10, 9, 41)
    before = counters()
    np.testing.assert_array_equal(_changed_rows(old, new, mesh=mesh), at)
    # rows the mesh does not divide go to one device, still by the program
    old, new, at = tables(2047, 10, 10, 40)
    np.testing.assert_array_equal(_changed_rows(old, new, mesh=mesh), at)
    assert moved(before)["delta_device_diffs"] == 2


def test_changed_rows_without_a_device_is_a_host_diff(monkeypatch):
    def no_device():
        raise RuntimeError("no device")
    monkeypatch.setattr(mapping_mod, "_delta_diff_program", no_device)
    old, new, at = tables(256, 10, 3, 7)
    before = counters()
    np.testing.assert_array_equal(_changed_rows(old, new), at)
    assert moved(before) == {"delta_device_diffs": 0, "delta_host_diffs": 1,
                             "delta_upload_bytes": 0}


def test_tables_of_other_shapes_are_not_diffed():
    before = counters()
    old, new, _at = tables(16, 10, 4, 2)
    np.testing.assert_array_equal(_changed_rows(old, new[:8]), np.arange(8))
    assert _changed_rows(old[:0], new[:0]).size == 0
    assert moved(before) == dict.fromkeys(COUNTERS, 0)


class _Published:
    """What `_fused_delta` reads of a mapping: the map and its packed
    tables."""

    def __init__(self, osdmap, fused, width):
        self.osdmap = osdmap
        self._fused = {1: fused}
        self._fused_w = {1: width}


def _one_pool_map(pg_num: int) -> OSDMap:
    crush, _root, rule = build_two_level_map(3, 3)
    m = OSDMap(crush=crush, epoch=2)
    m.set_max_osd(9)
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rule, pg_num=pg_num)
    return m


@pytest.mark.parametrize("rows,device", [
    (HOST_MAX // 8 - 1, False), (HOST_MAX // 8, False),
    (HOST_MAX // 8 + 1, True), (HOST_MAX // 4, True)])
def test_the_service_takes_the_device_diff_above_the_host_limit(rows, device):
    """Packed tables of 8 columns on both sides of FUSED_DIFF_HOST_MAX
    elements: the same changed PGs either way, and the counters say
    which side computed them."""
    old, new, at = tables(rows, 8, rows, 113)
    m = _one_pool_map(rows)
    svc = SharedPGMappingService(backend="tpu")
    prev = _Tables(m, {}, {}, {}, 2, fused={1: old}, fused_w={1: 2})
    before = counters()
    changed = svc._fused_delta(prev, _Published(m, new, 2))
    assert changed == [(1, int(pg)) for pg in at]
    assert moved(before) == {
        "delta_device_diffs": int(device), "delta_host_diffs": int(not device),
        "delta_upload_bytes": 2 * old.nbytes if device else 0}


def test_a_changed_layout_is_a_host_diff():
    from ceph_tpu.ops.placement_kernel import normalize_packed
    old, new, at = tables(64, 8, 5, 6)              # width 2: 2 * 2 + 4
    m = _one_pool_map(64)
    svc = SharedPGMappingService(backend="tpu")
    prev = _Tables(m, {}, {}, {}, 2, fused={1: old}, fused_w={1: 2})
    before = counters()
    changed = svc._fused_delta(
        prev, _Published(m, normalize_packed(new, 2, 3), 3))
    assert changed == [(1, int(pg)) for pg in at]
    assert moved(before) == {"delta_device_diffs": 0, "delta_host_diffs": 1,
                             "delta_upload_bytes": 0}


def test_the_counters_are_in_the_summary_the_dump_and_the_scrape():
    from test_kernel_telemetry import _scrape, parse_exposition
    for view in (telemetry.mapping_summary(), telemetry.mapping_dump()):
        assert set(COUNTERS) <= set(view)
    fams = parse_exposition(_scrape())
    for key in COUNTERS:
        fam = f"ceph_kernel_mapping_{key}_total"
        assert fam in fams and fams[fam]["type"] == "counter", fam
    stats = telemetry.MappingStats()
    stats.record_delta_diff(device=True, upload_bytes=80)
    stats.record_delta_diff(device=False)
    assert (stats.delta_device_diffs, stats.delta_host_diffs,
            stats.delta_upload_bytes) == (1, 1, 80)
    stats.clear()
    assert (stats.delta_device_diffs, stats.delta_host_diffs,
            stats.delta_upload_bytes) == (0, 0, 0)


# -- the wait for the engine ----------------------------------------------------

class _SlowFuture:
    """A future that answers at the n-th wait."""

    def __init__(self, answers_at: int):
        self.waits, self.answers_at = 0, answers_at

    def result(self, timeout=None):
        self.waits += 1
        if self.waits < self.answers_at:
            raise TimeoutError("dispatch result not ready")
        return [[1, 2, 3]]


class _Engine:
    def __init__(self, launching_for: int):
        self.asked, self.launching_for = 0, launching_for

    def building(self) -> bool:
        self.asked += 1
        return self.asked <= self.launching_for


def test_an_epoch_outwaits_a_launch_that_compiles_and_nothing_else():
    """A first shape compiles inside the engine's launch for longer
    than one wait (CRUSH at 1 Mi lanes): the epoch waits on while the
    engine says it is launching, and gives up when it is not."""
    fut = _SlowFuture(answers_at=3)
    got = mapping_mod._engine_result(_Engine(launching_for=2), fut)
    np.testing.assert_array_equal(got, [[1, 2, 3]])
    assert fut.waits == 3
    with pytest.raises(TimeoutError):
        mapping_mod._engine_result(_Engine(launching_for=1),
                                   _SlowFuture(answers_at=3))
    assert mapping_mod.ENGINE_WAIT_S == 120.0


def test_the_engine_says_when_it_is_launching():
    import threading
    from ceph_tpu.ops.dispatch import DeviceDispatchEngine
    eng = DeviceDispatchEngine()
    gate, seen = threading.Event(), []

    def fn(batch):
        seen.append(eng.building())     # on the dispatch thread, mid-launch
        gate.wait(10.0)
        return batch

    try:
        assert eng.building() is False
        fut = eng.submit(("t", 1), fn, np.zeros((4, 2), dtype=np.uint8),
                         label="test")
        for _ in range(1000):
            if seen:
                break
            threading.Event().wait(0.01)
        assert seen == [True] and eng.building() is True
        gate.set()
        fut.result(timeout=30.0)
        assert eng.flush(10.0) and eng.building() is False
    finally:
        gate.set()
        eng.stop()
