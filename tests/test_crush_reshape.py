"""A CRUSH map that is edited — a host added, a host removed, an item
reweighted, an OSD added to a host, max_osd grown — is served by the
programs the first map of its shape class built: the bucket tables are
operands (crush.fastpath.FastTables), never part of a program.

Every state of a seeded series of edits is held to the scalar oracle
(crush/mapper_ref.py, through OSDMap.pg_to_up_acting_osds) and to the
benchmark's plain reference (perfbench/reference/crush_plain.py), which
gets the same state as plain lists; the changed set of each step is
exact both ways; the jit caches and `crush_program_builds` stand still
inside a class and move by exactly the programs a class boundary needs.
"""

import io

import jax
import numpy as np
import pytest

from ceph_tpu.crush import fastpath, mapper_jax
from ceph_tpu.crush.builder import (add_simple_rule, make_bucket)
from ceph_tpu.crush.types import (CRUSH_BUCKET_STRAW2, CRUSH_ITEM_NONE,
                                  OSD_AXIS_QUANTUM, CrushMap, padded_osds)
from ceph_tpu.ops import placement_kernel, telemetry
from ceph_tpu.osd import OSDMap, PGPool
from ceph_tpu.osd.mapping import (OSDMapMapping, SharedPGMappingService,
                                  scalar_rows)
from perfbench.reference import crush_plain

POOL, SIZE = 1, 3
EDITS = ("host_add", "item_reweight", "osd_add", "host_remove")


def builds() -> int:
    return telemetry.mapping_summary()["crush_program_builds"]


class Cluster:
    """A two-level cluster as plain data — host id -> (OSD ids, crush
    weights), per-OSD reweights — from which both the program's maps
    and the plain reference's are made."""

    def __init__(self, hosts: int, per_host: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.hosts: dict[int, tuple[list[int], list[int]]] = {}
        self.reweight: list[int] = []
        for _ in range(hosts):
            self.host_add(per_host)

    def _weights(self, n: int) -> list[int]:
        return [int(w) for w in self.rng.integers(0x8000, 0x20000, n)]

    @property
    def max_osd(self) -> int:
        return len(self.reweight)

    def _new_osds(self, n: int) -> list[int]:
        ids = list(range(self.max_osd, self.max_osd + n))
        # most in, some reweighted, a few out: the retry ladder fires
        self.reweight += [int(self.rng.choice(
            [0x10000, 0x10000, 0x10000, 0x8000, 0])) for _ in ids]
        return ids

    def host_add(self, per_host: int | None = None) -> None:
        if per_host is None:
            per_host = len(next(iter(self.hosts.values()))[0])
        hid = min(self.hosts, default=-1) - 1
        self.hosts[hid] = (self._new_osds(per_host),
                           self._weights(per_host))

    def host_remove(self) -> None:
        hid = int(self.rng.choice(sorted(self.hosts)))
        for o in self.hosts.pop(hid)[0]:
            self.reweight[o] = 0            # purged: out, not existing

    def item_reweight(self) -> None:
        _ids, weights = self.hosts[int(self.rng.choice(sorted(self.hosts)))]
        weights[int(self.rng.integers(len(weights)))] >>= 1

    def osd_add(self) -> None:
        hid = int(self.rng.choice(sorted(self.hosts)))
        ids, weights = self.hosts[hid]
        ids += self._new_osds(1)
        weights += self._weights(1)

    # -- as the program's maps, and as the reference's -----------------------

    def crush(self) -> tuple[CrushMap, int]:
        m = CrushMap()
        m.max_devices = self.max_osd
        for hid, (ids, weights) in self.hosts.items():
            m.add_bucket(make_bucket(hid, CRUSH_BUCKET_STRAW2, 1,
                                     list(ids), list(weights)))
        order = sorted(self.hosts, reverse=True)
        m.add_bucket(make_bucket(-1, CRUSH_BUCKET_STRAW2, 2, order,
                                 [m.bucket(h).weight for h in order]))
        return m, add_simple_rule(m, -1, 1, "firstn")

    def osdmap(self, epoch: int, pg_num: int) -> OSDMap:
        crush, rid = self.crush()
        m = OSDMap(crush=crush, epoch=epoch)
        m.set_max_osd(self.max_osd)
        live = {o for ids, _w in self.hosts.values() for o in ids}
        for o, w in enumerate(self.reweight):
            m.osd_state[o] = 3 if o in live else 0
            m.osd_weight[o] = w
        m.pools[POOL] = PGPool(pool_id=POOL, size=SIZE, crush_rule=rid,
                               pg_num=pg_num)
        return m

    def plain(self, pg_num: int) -> crush_plain.PlainMap:
        order = sorted(self.hosts, reverse=True)
        hosts = {h: crush_plain.Bucket(
            h, np.array(self.hosts[h][0], dtype=np.int64),
            np.array(self.hosts[h][1], dtype=np.int64)) for h in order}
        root = crush_plain.Bucket(
            -1, np.array(order, dtype=np.int64),
            np.array([sum(self.hosts[h][1]) for h in order],
                     dtype=np.int64))
        live = {o for ids, _w in self.hosts.values() for o in ids}
        return crush_plain.PlainMap(
            root, hosts, list(self.reweight),
            [o in live for o in range(self.max_osd)], POOL, pg_num, SIZE)


def oracle_rows(crush, rid, xs, reweight) -> np.ndarray:
    return scalar_rows(crush, rid, xs, SIZE, reweight)


# -- BatchMapper.do_rule ------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_do_rule_follows_edits_inside_a_class_on_one_program(seed):
    """6 hosts x 4 OSDs pad to 8 x 8 on the XLA path: a 7th host, a 5th
    OSD of a host, reweights and a removal all stay inside the class."""
    c = Cluster(6, 4, seed)
    xs = np.random.default_rng(seed).integers(0, 2**32, 192, dtype=np.uint32)
    shapes, sizes, built = set(), [], []
    for kind in (None,) + EDITS + ("item_reweight",):
        if kind is not None:
            getattr(c, kind)()
        crush, rid = c.crush()
        before = builds()
        bm = mapper_jax.BatchMapper(crush)
        got = np.asarray(bm.do_rule(rid, xs, SIZE, c.reweight))
        np.testing.assert_array_equal(
            got, oracle_rows(crush, rid, xs, c.reweight), err_msg=str(kind))
        ft = bm.fast_tables(rid)
        shapes.add(ft.shape)
        sizes.append(mapper_jax._fast_program(ft.shape, SIZE)._cache_size())
        built.append(builds() - before)
        assert not bm._jit_cache            # no per-content program
    assert len(shapes) == 1
    shape, = shapes
    assert (shape.root_lanes, shape.leaf_lanes, shape.pallas) == (8, 8, False)
    # one entry from the first state on, nothing traced after it
    assert len(set(sizes)) == 1 and built[0] <= 1 and not any(built[1:])


@pytest.mark.parametrize("seed", [4, 5])
def test_a_class_boundary_builds_exactly_the_program_it_needs(seed):
    c = Cluster(8, 8, seed)
    # a batch length no other test uses: a class seen here for the
    # first time is traced here
    xs = np.random.default_rng(seed).integers(
        0, 2**32, 160 + seed, dtype=np.uint32)
    seen = set()

    def run() -> tuple[int, int]:
        crush, rid = c.crush()
        before = builds()
        bm = mapper_jax.BatchMapper(crush)
        got = np.asarray(bm.do_rule(rid, xs, SIZE, c.reweight))
        np.testing.assert_array_equal(
            got, oracle_rows(crush, rid, xs, c.reweight))
        shape = bm.fast_tables(rid).shape
        # exactly one program for a class not seen before, none after
        assert builds() - before == (shape not in seen), shape
        seen.add(shape)
        return shape.root_lanes, shape.leaf_lanes

    assert run() == (8, 8)
    c.item_reweight()
    assert run() == (8, 8)
    c.host_add()                            # 9 hosts: 16 root lanes
    assert run() == (16, 8)
    c.item_reweight()
    assert run() == (16, 8)
    c.osd_add()                             # a host of 9: 16 leaf lanes
    assert run() == (16, 16)
    c.host_remove()                         # back under 8 hosts: a class
    c.item_reweight()                       # of its own, or the first
    assert run()[0] == 8
    assert run()[0] == 8
    assert len(seen) in (3, 4)


def pallas_rows(c, xs):
    """do_rule on the Pallas route in interpret mode (few tries, so a
    short full range: interpret mode takes minutes over the 54 columns
    of the default 51), held to the oracle; returns the shape class."""
    crush, rid = c.crush()
    crush.tunables.choose_total_tries = 7
    bm = mapper_jax.BatchMapper(crush)
    ft = bm._fast_cache[rid] = fastpath.tables_of(
        fastpath.detect(crush, rid), pallas=True, interpret=True)
    got = np.asarray(bm.do_rule(rid, xs, SIZE, c.reweight))
    np.testing.assert_array_equal(
        got, oracle_rows(crush, rid, xs, c.reweight))
    return ft.shape


def test_the_leaf_group_width_holds_a_class_across_host_edits():
    """Hosts of 40 OSDs: the leaf kernel's group is 64 lanes, two
    r-columns a slab.  A host of 40 added, a crush weight halved, a host
    removed — the reshape cell's epochs — run on the first map's
    program; the gauges say how the leaf is laid out."""
    c = Cluster(4, 40, 51)
    xs = np.random.default_rng(51).integers(0, 2**32, 136, dtype=np.uint32)
    shape = pallas_rows(c, xs)
    assert (shape.root_lanes, shape.leaf_lanes) == (128, 64)
    s = telemetry.mapping_summary()
    assert s["crush_leaf_columns_per_slab"] == 2
    assert s["crush_leaf_lane_fill"] == 0.625
    before = builds()
    for kind in ("host_add", "item_reweight", "host_remove"):
        getattr(c, kind)()
        assert pallas_rows(c, xs) == shape, kind
    assert builds() == before


def test_a_host_past_64_crosses_the_leaf_class_once():
    c = Cluster(3, 64, 52)
    xs = np.random.default_rng(52).integers(0, 2**32, 144, dtype=np.uint32)
    assert pallas_rows(c, xs).leaf_lanes == 64
    before = builds()
    c.osd_add()                              # a host of 65
    shape = pallas_rows(c, xs)
    assert shape.leaf_lanes == 128
    assert builds() - before == 1
    s = telemetry.mapping_summary()
    assert s["crush_leaf_columns_per_slab"] == 1
    assert s["crush_leaf_lane_fill"] == pytest.approx(65 / 128, abs=1e-6)
    c.item_reweight()
    assert pallas_rows(c, xs) == shape
    assert builds() - before == 1


def test_the_cells_shape_reports_two_columns_a_slab_filled_to_five_eighths():
    from ceph_tpu.crush import build_skewed_two_level_map
    crush, rid, _rw = build_skewed_two_level_map(250, 40)
    ft = fastpath.tables_of(fastpath.detect(crush, rid), pallas=True,
                            interpret=True)
    assert (ft.shape.root_lanes, ft.shape.leaf_lanes) == (256, 64)
    s = telemetry.mapping_summary()
    assert s["crush_leaf_columns_per_slab"] == 2
    assert s["crush_leaf_lane_fill"] == 0.625
    # the leaf's fields, 8 of 64 lanes a host: 0.52 MB where the
    # 128-lane table with its raw-weight block was 1.18 MB
    assert ft.host[5].nbytes == 256 * 8 * 64 * 4


def test_max_osd_grows_inside_the_quantum_on_one_program_and_across_it_on_two():
    assert OSD_AXIS_QUANTUM % 128 == 0
    assert padded_osds(10000) == padded_osds(10040) == 10240
    assert padded_osds(0) == padded_osds(1) == OSD_AXIS_QUANTUM
    c = Cluster(31, 33, 7)                   # 1,023 OSDs; 32 x 40 lanes
    assert c.max_osd == OSD_AXIS_QUANTUM - 1
    mapping = OSDMapMapping(backend="tpu")
    mapping.update(c.osdmap(2, 64))
    epoch = 3
    for grows, rebuilt in ((1, 0), (1, 2)):  # 1,024 inside; 1,025 across
        for _ in range(grows):
            c.osd_add()
        m = c.osdmap(epoch, 64)
        before = builds()
        mapping.update(m)
        # across the quantum the CRUSH program and the ladder are each
        # traced for the longer OSD axis, and nothing else is
        assert builds() - before == rebuilt, c.max_osd
        for pg in range(64):
            assert mapping.get(POOL, pg) == m.pg_to_up_acting_osds(POOL, pg)
        epoch += 1
    assert c.max_osd == OSD_AXIS_QUANTUM + 1


# -- the mapping service ------------------------------------------------------

def answers(svc, m, pg_num):
    return [svc.lookup(m, POOL, pg) for pg in range(pg_num)]


@pytest.mark.parametrize("seed", [11, 12])
def test_the_service_follows_an_edit_series_as_both_references(seed):
    pg_num = 128
    c = Cluster(6, 4, seed)
    svc = SharedPGMappingService(backend="tpu")
    m = c.osdmap(2, pg_num)
    assert svc.update_to(m).full
    after_first = builds()
    prev = answers(svc, m, pg_num)
    # two of each kind, in a seeded order: at most 8 hosts of at most
    # 6 OSDs, so the series stays inside 8 x 8 lanes
    series = np.random.default_rng(seed).permutation(EDITS * 2)
    for step, kind in enumerate(series):
        getattr(c, str(kind))()
        new = c.osdmap(m.epoch + 1, pg_num)
        upd = svc.update_to(new, from_epoch=m.epoch)
        assert not upd.full
        now = answers(svc, new, pg_num)
        plain = c.plain(pg_num)
        for pg in range(pg_num):
            up, primary = crush_plain.up_of(plain, pg)
            assert now[pg] == new.pg_to_up_acting_osds(POOL, pg)
            assert now[pg] == (up, primary, up, primary), (step, pg)
            assert all(0 <= o < new.max_osd for o in now[pg][0])
        assert sorted(upd.changed) == [
            (POOL, pg) for pg in range(pg_num) if now[pg] != prev[pg]]
        m, prev = new, now
    # 6..8 hosts of 4..6 OSDs stay inside 8 x 8 lanes, max_osd inside
    # the quantum: whatever the series was, nothing was built for it
    shape = svc._mapping._mapper[1].fast_tables(
        m.pools[POOL].crush_rule).shape
    assert (shape.root_lanes, shape.leaf_lanes) == (8, 8)
    assert builds() == after_first
    s = telemetry.mapping_summary()
    assert s["crush_table_builds"] >= 8 and s["crush_table_upload_bytes"] > 0


def test_an_id_at_or_past_max_osd_is_never_placed():
    """The CRUSH map already holds a host whose OSDs the OSDMap does not
    have yet (max_osd not grown): they weigh nothing, in CRUSH's padded
    vector and in the ladder's, whose range checks read max_osd."""
    c = Cluster(6, 4, 21)
    old_max = c.max_osd
    c.host_add()
    m = c.osdmap(2, 128)
    m.max_osd = old_max
    for vec in (m.osd_state, m.osd_weight, m.osd_primary_affinity):
        del vec[old_max:]
    m.pg_upmap_items[(POOL, 3)] = [(0, old_max + 1)]   # to: past max_osd
    m.pg_temp[(POOL, 5)] = [old_max, 1, 2]
    svc = SharedPGMappingService(backend="tpu")
    unfused = telemetry.mapping_summary()["unfused_epochs"]
    svc.update_to(m)
    state, weight, affinity = m.dense_osd_vectors()
    assert len(state) == len(weight) == len(affinity) == padded_osds(old_max)
    raw = svc._mapping.get_raw(POOL)
    assert ((raw < old_max) | (raw == CRUSH_ITEM_NONE)).all()
    for pg in range(128):
        got = svc.lookup(m, POOL, pg)
        assert got == m.pg_to_up_acting_osds(POOL, pg)
        assert all(o < old_max for o in got[0])
    assert telemetry.mapping_summary()["unfused_epochs"] == unfused


def test_the_ladder_reads_max_osd_and_not_the_vectors_length():
    """The same padded vectors under two values of max_osd: an id
    between them exists for one and not for the other."""
    n = 4
    raw = np.array([[1, 5, 2]] * n, dtype=np.int32)
    state = np.full(OSD_AXIS_QUANTUM, 3, dtype=np.int32)
    operands = dict(
        raw=raw, pps=np.arange(n, dtype=np.uint32),
        raw_len=np.full(n, 3, dtype=np.int32),
        up_rows=np.full((n, 3), CRUSH_ITEM_NONE, dtype=np.int32),
        up_len=np.zeros(n, dtype=np.int32),
        items=np.full((n, 1, 2), -1, dtype=np.int32),
        temp_rows=np.full((n, 3), -1, dtype=np.int32),
        temp_len=np.zeros(n, dtype=np.int32),
        ptemp=np.full(n, -1, dtype=np.int32), state=state,
        weight=np.full(OSD_AXIS_QUANTUM, 0x10000, dtype=np.int64),
        affinity=np.full(OSD_AXIS_QUANTUM, 0x10000, dtype=np.int32),
        erasure=False, width=3)
    for max_osd, up in ((8, [1, 5, 2]), (5, [1, 2])):
        ops = placement_kernel.LadderOperands(max_osd=max_osd, **operands)
        packed = placement_kernel.run_ladder(ops)
        assert placement_kernel.unpack_row(packed[0], 3)[0] == up
        np.testing.assert_array_equal(packed, placement_kernel.ladder_ref(
            ops.raw, *ops.aux(), *ops.osd_operands(), erasure=False))
    assert placement_kernel.ladder_cache_entries() >= 1


# -- programs: shared by maps, named as the device trace names them -----------

def test_two_maps_of_one_class_share_the_tools_program():
    """tools/crush_test.py and SharedPGMappingService.place build a
    BatchMapper a map; the compiled program is the class's."""
    from ceph_tpu.tools.crush_test import run_test
    a, b = Cluster(6, 4, 31), Cluster(7, 5, 32)
    assert a.max_osd != b.max_osd
    for i, c in enumerate((a, b, a)):
        crush, rid = c.crush()
        before = builds()
        run_test(crush, [rid], 0, 255, SIZE, backend="tpu",
                 reweight=list(c.reweight), out=io.StringIO())
        assert builds() - before <= (i == 0)
    svc = SharedPGMappingService(backend="tpu")
    xs = np.arange(256, dtype=np.uint32)
    before = builds()
    for c in (a, b):
        crush, rid = c.crush()
        rows = svc.place(crush, rid, xs, SIZE, c.reweight)
        np.testing.assert_array_equal(
            rows, oracle_rows(crush, rid, xs, c.reweight))
    assert builds() == before
    # one map's tables are held, not a history of them
    assert svc._mapping._mapper[1].map is crush


def test_the_mesh_route_takes_the_tables_replicated_and_one_program():
    """Two maps of one class, each batch sharded over the CPU devices:
    the Pallas route (interpret mode) runs one shard_map program with
    each map's tables replicated over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ceph_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device backend")
    mesh = make_mesh(len(jax.devices()))
    spec = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    xs = np.random.default_rng(41).integers(
        0, 2**32, 16 * len(jax.devices()), dtype=np.uint32)
    placed = jax.device_put(jax.numpy.asarray(xs), spec)
    c = Cluster(6, 4, 41)
    keys = set()

    def crush_of(c):
        # few tries, so a short full range: interpret mode takes
        # minutes over the 54 columns of the default 51
        crush, rid = c.crush()
        crush.tunables.choose_total_tries = 7
        return crush, rid

    for kind in (None, "host_add", "item_reweight"):
        if kind:
            getattr(c, kind)()
        crush, rid = crush_of(c)
        bm = mapper_jax.BatchMapper(crush)
        ft = bm._fast_cache[rid] = fastpath.tables_of(
            fastpath.detect(crush, rid), pallas=True, interpret=True)
        got = np.asarray(bm.do_rule(rid, placed, SIZE, c.reweight))
        np.testing.assert_array_equal(
            got, oracle_rows(crush, rid, xs, c.reweight))
        assert ft.placed(mesh) and not ft.placed(None)
        assert all(len(t.sharding.device_set) == mesh.size
                   and t.sharding.is_fully_replicated for t in ft.on(mesh))
        keys.add(next(k for k in mapper_jax._FAST_PROGRAMS
                      if k[0] == ft.shape and k[2] is not None))
    key, = keys
    assert mapper_jax._FAST_PROGRAMS[key]._cache_size() == 1
    # the names a device trace shows (BENCHMARK.json's configurations
    # list them under programs.crush): jit_run on the mesh route ...
    rw = np.zeros(padded_osds(c.max_osd), dtype=np.int64)
    planes = next(v for k, v in mapper_jax._COLUMN_FILLERS.items()
                  if k[0] == key)
    text = mapper_jax._FAST_PROGRAMS[key].lower(
        placed, rw, ft.on(mesh), (np.bool_(False), planes)).as_text()
    assert "module @jit_run " in text
    # ... and jit__unknown on one device, for CRUSH and for the ladder
    one = fastpath.tables_of(fastpath.detect(*crush_of(c)))
    count, R, lanes = fastpath.mapper_for(one.shape).column_shape(
        len(xs), SIZE)
    planes = (np.zeros((R, lanes), dtype=np.int32),) * count
    text = mapper_jax._fast_program(one.shape, SIZE).lower(
        xs, rw, one.on(), (np.bool_(False), planes)).as_text()
    assert "module @jit__unknown " in text
    lowered = placement_kernel._ladder_jit(False).lower(
        *(jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
          for a in ladder_args()))
    assert "module @jit__unknown " in lowered.as_text()


def ladder_args():
    m = Cluster(2, 2, 1).osdmap(2, 8)
    raw = np.zeros((8, SIZE), dtype=np.int32)
    ops = placement_kernel.build_operands(
        m, POOL, m.pools[POOL], raw, np.zeros(8, dtype=np.uint32),
        width=SIZE, pairs=1)
    return (ops.raw, *ops.aux(), *ops.osd_operands())
