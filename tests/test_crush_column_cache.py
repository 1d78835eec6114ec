"""CRUSH's column cache: the stage-1 straw2 winners of a batch are a
function of its seeds and the map's bucket tables alone (mapper.c's
is_out rejects after the descent), so an epoch that changes only
reweights or the up set — out, reweight, down, restore — takes the
winner planes the previous epoch's batch of the same seeds drew on the
same tables, and runs only is_out, the firstn ladder, stage 2 and the
compaction (crush.mapper_jax.BatchMapper._fast_call).

Every epoch here is held to the scalar oracle (crush.mapper_ref,
through ``scalar_rows``) and to the same program with no planes fed
in; ``crush_column_hits`` / ``crush_column_misses`` count what the
cache served, and no program is built after the first map.
"""

import threading

import jax
import numpy as np
import pytest

from ceph_tpu.crush import fastpath, mapper_jax
from ceph_tpu.crush.builder import add_simple_rule, make_bucket
from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2, CrushMap, padded_osds
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine
from ceph_tpu.osd import OSDMap, PGPool
from ceph_tpu.osd.mapping import OSDMapMapping, scalar_rows

SIZE = 3
KINDS = ("out", "reweight", "down", "restore")


def entries() -> dict:
    """The fast-path programs' compiled entries (one per input
    signature, shardings included)."""
    return {k: f._cache_size() for k, f in mapper_jax._FAST_PROGRAMS.items()}


def counters() -> tuple[int, int, int]:
    s = telemetry.mapping_summary()
    return (s["crush_column_hits"], s["crush_column_misses"],
            s["crush_program_builds"])


class Cluster:
    """A two-level cluster as plain data, from which each epoch's
    OSDMap is made: hosts of OSDs with crush weights, per-OSD reweights
    and up states, pools of one rule."""

    def __init__(self, hosts: int, per_host: int, seed: int,
                 pools: dict[int, int] | None = None):
        self.rng = np.random.default_rng(seed)
        self.hosts: list[tuple[list[int], list[int]]] = []
        self.reweight: list[int] = []
        self.up: list[bool] = []
        self.pools = dict(pools or {1: 64})
        self.size = SIZE
        self.epoch = 0
        for _ in range(hosts):
            self.host_add(per_host)

    def host_add(self, per_host: int | None = None) -> None:
        n = per_host if per_host is not None else len(self.hosts[0][0])
        ids = list(range(len(self.reweight), len(self.reweight) + n))
        self.reweight += [0x10000] * n
        self.up += [True] * n
        self.hosts.append(
            (ids, [int(w) for w in self.rng.integers(0x8000, 0x20000, n)]))

    def crush_weight(self) -> None:
        ids, weights = self.hosts[int(self.rng.integers(len(self.hosts)))]
        weights[int(self.rng.integers(len(weights)))] >>= 1

    def churn(self, kind: str) -> None:
        """One epoch of the churn cells' kinds on a drawn OSD."""
        osd = int(self.rng.integers(len(self.reweight)))
        if kind == "out":
            self.reweight[osd] = 0
        elif kind == "reweight":
            self.reweight[osd] = int(self.rng.integers(0x4000, 0x10000))
        elif kind == "down":
            self.up[osd] = False
        else:
            self.reweight = [0x10000] * len(self.reweight)
            self.up = [True] * len(self.up)

    def osdmap(self) -> OSDMap:
        self.epoch += 1
        crush = CrushMap()
        crush.max_devices = len(self.reweight)
        # few tries, so a short full range: interpret mode takes
        # minutes over the 54 columns of the default 51
        crush.tunables.choose_total_tries = 7
        order = []
        for i, (ids, weights) in enumerate(self.hosts):
            crush.add_bucket(make_bucket(-2 - i, CRUSH_BUCKET_STRAW2, 1,
                                         list(ids), list(weights)))
            order.append(-2 - i)
        crush.add_bucket(make_bucket(-1, CRUSH_BUCKET_STRAW2, 2, order,
                                     [sum(w) for _i, w in self.hosts]))
        rid = add_simple_rule(crush, -1, 1, "firstn")
        m = OSDMap(crush=crush, epoch=self.epoch)
        m.set_max_osd(len(self.reweight))
        for o, w in enumerate(self.reweight):
            m.osd_state[o] = 3 if self.up[o] else 1
            m.osd_weight[o] = w
        for pid, pg_num in self.pools.items():
            m.pools[pid] = PGPool(pool_id=pid, size=self.size,
                                  crush_rule=rid, pg_num=pg_num)
        return m


def weights_of(m: OSDMap) -> np.ndarray:
    w = np.zeros(padded_osds(m.max_osd), dtype=np.int64)
    w[:m.max_osd] = m.osd_weight[:m.max_osd]
    return w


def check(mapping: OSDMapMapping, m: OSDMap) -> None:
    """The cached raw table of every pool equals the scalar oracle's
    and the program's with no planes fed in."""
    bm = mapping.mapper_for(m.crush)
    for pid, pool in m.pools.items():
        pps = mapping._pps[pid]
        got = mapping.get_raw(pid)
        want = scalar_rows(m.crush, pool.crush_rule, pps, pool.size,
                           weights_of(m))
        np.testing.assert_array_equal(got, want, err_msg=f"pool {pid}")
        plain = np.asarray(bm.do_rule(pool.crush_rule, pps, pool.size,
                                      weights_of(m)))
        np.testing.assert_array_equal(got, plain, err_msg=f"pool {pid}")


@pytest.fixture(params=["xla", "pallas"])
def route(request, monkeypatch):
    """The XLA route, or the Pallas column kernels in interpret mode."""
    if request.param == "pallas":
        tables_of = fastpath.tables_of
        monkeypatch.setattr(
            fastpath, "tables_of",
            lambda fr, pallas=None, interpret=False: tables_of(
                fr, pallas=True, interpret=True))
    return request.param


def test_churn_epochs_hit_and_answer_exactly(route):
    """Groups of out / reweight / down / restore epochs through
    ``OSDMapMapping.update``: every CRUSH epoch after the first hits,
    the first misses, and nothing is built after the first map."""
    c = Cluster(6, 4, seed=7 if route == "xla" else 8)
    mapping = OSDMapMapping(min_device_pgs=0, fused=False)
    hits0, misses0, _ = counters()
    m = c.osdmap()
    mapping.update(m)
    check(mapping, m)
    built, compiled = counters()[2], entries()
    crush_epochs = 0
    for _group in range(3 if route == "xla" else 2):
        for kind in KINDS:
            c.churn(kind)
            m = c.osdmap()
            info = mapping.update(m)
            crush_epochs += bool(info.recomputed)
            check(mapping, m)
            if info.recomputed:
                assert mapping._seeds[1].columns == "hit"
    hits, misses, builds = counters()
    assert crush_epochs >= 4
    assert (hits - hits0, misses - misses0) == (crush_epochs, 1)
    assert builds == built and entries() == compiled


@pytest.mark.parametrize("edit", ["crush_weight", "host_add", "pg_num",
                                  "size"])
def test_a_changed_map_or_pool_misses_and_answers_exactly(edit):
    """A crush weight halved, a host added, a pool's pg_num changed:
    new tables or new seeds, so the batch draws its own winners — and
    the next churn epoch hits on them.  A pool's size changed keeps its
    seeds but not the columns its ladder consumes: a miss too."""
    c = Cluster(6, 4, seed=11)
    mapping = OSDMapMapping(min_device_pgs=0, fused=False)
    for kind in ("out", "reweight"):
        c.churn(kind)
        m = c.osdmap()
        mapping.update(m)
    ft_before = mapping.mapper_for(m.crush).fast_tables(
        m.pools[1].crush_rule)
    hits0, misses0, _ = counters()
    if edit == "pg_num":
        c.pools[1] = 80
    elif edit == "size":
        c.size = 4
    else:
        getattr(c, edit)()
    c.churn("reweight")
    m = c.osdmap()
    mapping.update(m)
    check(mapping, m)
    assert mapping._seeds[1].columns == "miss"
    assert counters()[:2] == (hits0, misses0 + 1)
    ft = mapping.mapper_for(m.crush).fast_tables(m.pools[1].crush_rule)
    assert (ft is ft_before) == (edit in ("pg_num", "size"))
    c.churn("out")
    m = c.osdmap()
    mapping.update(m)
    check(mapping, m)
    assert mapping._seeds[1].columns == "hit"
    assert counters()[:2] == (hits0 + 1, misses0 + 1)
    # the old seeds' planes went at the first batch after them
    assert len(ft._columns) == 1


def test_a_batch_key_is_its_parts_in_order_and_its_padding():
    a = fastpath.Seeds(np.arange(8, dtype=np.uint32))
    b = fastpath.Seeds(np.arange(8, dtype=np.uint64))   # as placed: uint32
    c = fastpath.Seeds(np.arange(1, 9, dtype=np.uint32))
    assert fastpath.Seeds.key((a,), 0) == fastpath.Seeds.key((b,), 0)
    assert fastpath.Seeds.key((a,), 0) != fastpath.Seeds.key((c,), 0)
    assert fastpath.Seeds.key((a,), 0) != fastpath.Seeds.key((a,), 8)
    assert fastpath.Seeds.key((a, c), 0) != fastpath.Seeds.key((c, a), 0)


def _held(engine, requests: int):
    """Hold ``engine`` busy until ``requests`` more are queued, so that
    they share one device call."""
    entered, release = threading.Event(), threading.Event()

    def slow(a):
        entered.set()
        release.wait(30.0)
        return a

    def watch():
        for _ in range(3000):
            if len(engine._pending) >= requests:
                break
            threading.Event().wait(0.01)
        release.set()

    engine.submit(("held", 0), slow, np.zeros((1,), np.uint8))
    assert entered.wait(10.0)
    threading.Thread(target=watch, daemon=True).start()


def test_coalesced_pools_hit_only_when_both_parts_match():
    """Two pools on one rule and size coalesce into one engine batch:
    a churn epoch hits; an epoch that changes one pool's seeds misses
    though the other's are the same; the next churn epoch hits again."""
    c = Cluster(6, 4, seed=13, pools={1: 64, 2: 32})
    stats = telemetry.DispatchStats()
    eng = DeviceDispatchEngine(stats=stats, max_delay_us=200_000.0)
    mapping = OSDMapMapping(min_device_pgs=0, fused=False)
    try:
        outcomes, keys = [], []
        for step in ("first", "reweight", "pg_num", "out", "restore"):
            if step == "pg_num":
                c.pools[2] = 48
            if step != "first":
                c.churn("reweight" if step == "pg_num" else step)
            m = c.osdmap()
            batches = stats.batches
            _held(eng, 2)
            info = mapping.update(m, engine=eng)
            assert sorted(info.recomputed) == [1, 2]
            # the held call and one CRUSH batch for both pools
            assert stats.batches - batches == 2
            check(mapping, m)
            seeds = mapping._seeds
            assert seeds[1].columns == seeds[2].columns
            outcomes.append(seeds[1].columns)
            ft = mapping.mapper_for(m.crush).fast_tables(
                m.pools[1].crush_rule)
            keys.append(set(ft._used))
        assert outcomes == ["miss", "hit", "miss", "hit", "hit"]
        (_mesh, (_width, (parts, pad))), = keys[0]
        assert len(parts) == 2 and pad == 32          # 96 seeds in 128
        assert keys[2] != keys[1] and keys[3] == keys[2]
    finally:
        eng.stop()


def test_no_program_after_the_first_map_across_an_edit_and_churn():
    """The reshape guarantee: a CRUSH edit (a miss on new tables)
    followed by a churn epoch (a hit) builds no program — the drawing
    and the cached branch are one program."""
    c = Cluster(6, 4, seed=17)
    mapping = OSDMapMapping(min_device_pgs=0, fused=False)
    m = c.osdmap()
    mapping.update(m)
    built, compiled = counters()[2], entries()
    for step in ("crush_weight", "reweight", "host_add", "out",
                 "restore"):
        if step in ("crush_weight", "host_add"):
            getattr(c, step)()
        else:
            c.churn(step)
        m = c.osdmap()
        mapping.update(m)
        check(mapping, m)
    assert counters()[2] == built and entries() == compiled


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_a_mesh_engine_hits_on_its_sharded_planes(route):
    """A 4-device CPU mesh — the XLA route (GSPMD splits the batch) and
    the Pallas route (shard_map, each device its own lanes): the planes
    stay split on the lane axis as the program put them, the churn
    epochs hit, every answer is exact, and the planes fed back build
    and compile nothing."""
    from ceph_tpu.parallel.mesh import make_mesh
    c = Cluster(6, 4, seed=19, pools={1: 256})
    eng = DeviceDispatchEngine(stats=telemetry.DispatchStats(),
                               mesh=make_mesh(4))
    mapping = OSDMapMapping(min_device_pgs=0, fused=False)
    try:
        m = c.osdmap()
        mapping.update(m, engine=eng)
        check(mapping, m)
        assert mapping._seeds[1].columns == "miss"
        built, compiled = counters()[2], entries()
        ft = mapping.mapper_for(m.crush).fast_tables(m.pools[1].crush_rule)
        (mesh, _key), = ft._columns
        assert mesh is not None and mesh.size == 4
        for kind in ("out", "reweight", "restore"):
            c.churn(kind)
            m = c.osdmap()
            mapping.update(m, engine=eng)
            check(mapping, m)
            assert mapping._seeds[1].columns == "hit"
        planes, = ft._columns.values()
        assert all(len(p.sharding.device_set) == 4 for p in planes)
        assert counters()[2] == built and entries() == compiled
    finally:
        eng.stop()
