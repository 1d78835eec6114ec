"""Device-resident placement pipeline (ops.placement_kernel + the
fused mapping-service path): bit-exactness of the fused
raw→up→acting ladder vs the scalar ``pg_to_up_acting_osds`` oracle
under random churn, delta-exactness of the on-device fused diff vs the
scalar diff, the dispatch-engine/mesh channel, the balancer's batched
what-if scoring, the shard_map wrapper that lets pallas kernels ride
sharded batches, and the fused-vs-fallback observability."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.crush import build_two_level_map
from ceph_tpu.ops import telemetry
from ceph_tpu.ops import placement_kernel as pk
from ceph_tpu.osd import OSDMap, PGPool, SharedPGMappingService
from ceph_tpu.osd.mapping import (
    _finish_from, pps_batch_scalar, scalar_rows)
from ceph_tpu.osd.osdmap import (
    OSD_EXISTS, OSD_UP, POOL_TYPE_ERASURE)


def _base_map(hosts=4, per_host=3, epoch=2, pg_num=32):
    crush, _root, rule = build_two_level_map(hosts, per_host)
    n = hosts * per_host
    m = OSDMap(crush=crush, epoch=epoch)
    m.set_max_osd(n)
    for o in range(n):
        m.mark_up(o)
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rule,
                        pg_num=pg_num)
    m.pools[2] = PGPool(pool_id=2, size=4, crush_rule=rule,
                        pg_num=pg_num // 2, type=POOL_TYPE_ERASURE)
    return m, rule


def _full_oracle(m: OSDMap) -> dict:
    return {(pid, pg): m.pg_to_up_acting_osds(pid, pg)
            for pid, pool in m.pools.items()
            for pg in range(pool.pg_num)}


def _churn_once(m: OSDMap, rng, rule: int) -> OSDMap:
    """One epoch of churn spanning EVERY pipeline-tail input: weights,
    state, affinity, pg_temp (incl. empty rows), primary_temp,
    full pg_upmap rows (incl. invalid entries), upmap item pairs, and
    pg growth."""
    new = m.copy()
    new.epoch = m.epoch + 1
    n = new.max_osd
    kind = int(rng.integers(0, 9))
    osd = int(rng.integers(0, n))
    pid = int(rng.choice(list(new.pools)))
    pg = int(rng.integers(0, new.pools[pid].pg_num))
    if kind == 0:
        new.osd_weight[osd] = int(rng.choice(
            (0, 0x4000, 0x8000, 0xC000, 0x10000)))
    elif kind == 1:
        new.osd_state[osd] = new.osd_state[osd] & ~OSD_UP
    elif kind == 2:
        new.osd_state[osd] = OSD_EXISTS | OSD_UP
    elif kind == 3:
        new.osd_primary_affinity[osd] = int(rng.choice(
            (0, 0x4000, 0x8000, 0x10000)))
    elif kind == 4:
        if (pid, pg) in new.pg_temp:
            del new.pg_temp[(pid, pg)]
        else:
            # rows bounded by the max pool size: longer rows only move
            # the shared width W (a fresh jit shape per value — pure
            # suite-runtime cost); the beyond-size width path is
            # pinned by the unit test's 30-churn map instead
            ln = int(rng.integers(0, 5))   # 0: present-but-empty row
            new.pg_temp[(pid, pg)] = [
                int(x) for x in rng.integers(0, n, ln)]
    elif kind == 5:
        if (pid, pg) in new.primary_temp:
            del new.primary_temp[(pid, pg)]
        else:
            new.primary_temp[(pid, pg)] = osd
    elif kind == 6:
        # full upmap row — sometimes invalid (out-of-range / out osd),
        # which the validity gate must reject like the oracle
        if (pid, pg) in new.pg_upmap:
            del new.pg_upmap[(pid, pg)]
        else:
            ln = int(rng.integers(1, 5))
            new.pg_upmap[(pid, pg)] = [
                int(x) for x in rng.integers(0, n + 2, ln)]
    elif kind == 7:
        if (pid, pg) in new.pg_upmap_items:
            del new.pg_upmap_items[(pid, pg)]
        else:
            new.pg_upmap_items[(pid, pg)] = [
                (int(rng.integers(0, n + 2)), int(rng.integers(0, n + 2)))
                for _ in range(int(rng.integers(1, 3)))]
    else:
        old_pool = new.pools[pid]
        new.pools[pid] = PGPool(
            pool_id=pid, size=old_pool.size, crush_rule=rule,
            pg_num=old_pool.pg_num * 2, pgp_num=old_pool.pgp_num,
            type=old_pool.type)
    return new


# -- kernel unit exactness ----------------------------------------------------

def test_ladder_unit_matches_finish_from():
    """Direct run_ladder over dense operands == the host pipeline tail
    for every PG of a replicated AND an erasure pool, across a map
    carrying every override kind (incl. a NONE-frm pair and an empty
    pg_temp row)."""
    rng = np.random.default_rng(7)
    m, rule = _base_map()
    for _ in range(30):
        m = _churn_once(m, rng, rule)
    m.pg_temp[(1, 0)] = []
    m.pg_upmap_items[(2, 0)] = [(0x7FFFFFFF, 1)]
    weights = np.zeros(m.max_osd, dtype=np.int64)
    weights[:len(m.osd_weight)] = m.osd_weight
    raw_tab, pps_tab = {}, {}
    for pid, pool in m.pools.items():
        pgids = np.arange(pool.pg_num, dtype=np.uint32)
        pps_tab[pid] = pps_batch_scalar(pool, pgids)
        raw_tab[pid] = scalar_rows(m.crush, pool.crush_rule,
                                   pps_tab[pid], pool.size, weights)
    width, pairs = pk.pool_widths(m)
    vectors = m.dense_osd_vectors()
    for pid, pool in m.pools.items():
        packed = pk.run_ladder(pk.build_operands(
            m, pid, pool, raw_tab[pid], pps_tab[pid], width=width,
            pairs=pairs, vectors=vectors))
        for pg in range(pool.pg_num):
            assert pk.unpack_row(packed[pg], width) == _finish_from(
                m, pool, pid, pg, raw_tab, pps_tab), (pid, pg)


def test_none_frm_pair_never_pollutes_pad_cells():
    """Regression: on a hole-free erasure row padded to a wider shared
    width, a NONE-frm pair must NOT match a pad cell — writing ``to``
    into the pad would make a later pair's ``to not in raw`` check
    wrongly fail (the scalar list has no cells past the row length)."""
    m, _rule = _base_map()
    pool = m.pools[2]                  # erasure, size 4
    # raw: one full row, no genuine NONE holes; width padded to 6
    raw = np.array([[0, 1, 2, 3]], dtype=np.int32)
    pps = np.array([12345], dtype=np.uint32)
    x = 7                              # valid, absent from the row
    m.pg_upmap_items = {(2, 0): [(0x7FFFFFFF, x), (1, x)]}
    state, weight, affinity = m.dense_osd_vectors()
    width = 6
    up_rows, up_len, items, temp_rows, temp_len, ptemp = \
        m.dense_pool_overrides(2, 1, width, 2)
    packed = pk.run_ladder(pk.LadderOperands(
        raw=pk.pad_raw(raw, width), pps=pps,
        raw_len=np.array([4], dtype=np.int32),
        up_rows=up_rows, up_len=up_len, items=items,
        temp_rows=temp_rows, temp_len=temp_len, ptemp=ptemp,
        state=state, weight=weight, affinity=affinity,
        max_osd=m.max_osd, erasure=True, width=width))
    # oracle: pair 1 (NONE frm) skipped, pair 2 rewrites 1 -> x
    want = m._finish_pg_mapping(pool, (2, 0), [0, 1, 2, 3], 12345)
    assert pk.unpack_row(packed[0], width) == want
    assert x in want[0]                # the rewrite really applied


def test_ladder_bucket_padding_bit_exact():
    """run_ladder's pow2 PG-axis bucketing (all-zero pad rows, sliced
    off) never perturbs live rows: a non-pow2 slice of a pool equals
    the corresponding rows of the full-pool call."""
    rng = np.random.default_rng(11)
    m, rule = _base_map()
    for _ in range(10):
        m = _churn_once(m, rng, rule)
    weights = np.zeros(m.max_osd, dtype=np.int64)
    weights[:len(m.osd_weight)] = m.osd_weight
    width, pairs = pk.pool_widths(m)
    vectors = m.dense_osd_vectors()
    pool = m.pools[1]
    pgids = np.arange(pool.pg_num, dtype=np.uint32)
    pps = pps_batch_scalar(pool, pgids)
    raw = scalar_rows(m.crush, pool.crush_rule, pps, pool.size,
                      weights)
    full = pk.run_ladder(pk.build_operands(
        m, 1, pool, raw, pps, width=width, pairs=pairs,
        vectors=vectors))
    ops = pk.build_operands(m, 1, pool, raw, pps, width=width,
                            pairs=pairs, vectors=vectors)
    cut = 13          # pads 13 -> 16 with zero rows
    for f in ("raw", "pps", "raw_len", "up_rows", "up_len", "items",
              "temp_rows", "temp_len", "ptemp"):
        setattr(ops, f, getattr(ops, f)[:cut])
    np.testing.assert_array_equal(pk.run_ladder(ops), full[:cut])


# -- the plane program against its twin, branch by branch ---------------------

_MAX_OSD = 40           # _branchy_map: 10 hosts x 4


def _branchy_map(rng):
    """A 40-OSD map whose OSDs are down, nonexistent, up without
    existing, out, and of four non-default affinities; the last id
    (max_osd - 1) exists, is up, in and default."""
    m, _rule = _base_map(hosts=10, per_host=4)
    for o in range(_MAX_OSD - 1):
        m.osd_state[o] = int(rng.choice(
            (OSD_EXISTS | OSD_UP, OSD_EXISTS, 0, OSD_UP),
            p=(0.7, 0.1, 0.1, 0.1)))
        m.osd_weight[o] = int(rng.choice((0x10000, 0x8000, 0),
                                         p=(0.6, 0.25, 0.15)))
        m.osd_primary_affinity[o] = int(rng.choice(
            (0x10000, 0, 0x4000, 0x8000, 0xC000),
            p=(0.5, 0.125, 0.125, 0.125, 0.125)))
    return m


def _branchy_operands(m, rng, n, w, pairs, erasure):
    """Dense ladder operands drawn to take every branch: NONE holes,
    valid and invalid pg_upmap rows, chained pairs and a NONE `frm`,
    ids at max_osd - 1, at max_osd, inside the vectors' padding, past
    it and below 0, pg_temp rows with and without primary_temp, and —
    after a first pass — pg_temp rows equal to `up`.  The vectors'
    padding is poisoned (exists, up, in, affinity 0x4000): only the
    max_osd scalar keeps the ladder off it."""
    state, weight, affinity = m.dense_osd_vectors()
    m_pad = len(state)
    state[_MAX_OSD:], weight[_MAX_OSD:] = OSD_EXISTS | OSD_UP, 0x10000
    affinity[_MAX_OSD:] = 0x4000
    good = [o for o in range(_MAX_OSD)
            if m.exists(o) and not m.is_out(o)]
    odd = np.array([_MAX_OSD - 1, _MAX_OSD, _MAX_OSD + 5, m_pad - 1,
                    m_pad, m_pad + 77, -1, -7, pk.NONE], dtype=np.int64)

    def ids(shape, odd_share=0.15):
        out = rng.integers(0, _MAX_OSD, shape)
        return np.where(rng.random(shape) < odd_share,
                        rng.choice(odd, shape), out).astype(np.int32)

    raw_w = max(1, w - w // 4)
    raw = np.full((n, w), pk.NONE, dtype=np.int32)
    raw[:, :raw_w] = np.where(rng.random((n, raw_w)) < 0.1, pk.NONE,
                              ids((n, raw_w), 0.05))
    pps = rng.integers(0, 2 ** 32, n, dtype=np.uint32)
    raw_len = np.full(n, raw_w, dtype=np.int32)

    up_rows = np.full((n, w), pk.NONE, dtype=np.int32)
    up_len = np.where(rng.random(n) < 0.125,
                      rng.integers(1, w + 1, n), 0).astype(np.int32)
    cells = np.arange(w)[None, :] < up_len[:, None]
    valid_rows = rng.random(n) < 0.6          # every entry exists, in
    fill = np.where(valid_rows[:, None], rng.choice(good, (n, w)),
                    ids((n, w), 0.3))
    up_rows[cells] = fill[cells]

    items = np.full((n, pairs, 2), -1, dtype=np.int32)
    n_pairs = np.where(rng.random(n) < 0.25,
                       rng.integers(1, pairs + 1, n), 0)
    col = rng.integers(0, raw_w, n)
    frm = np.where(rng.random(n) < 0.8, raw[np.arange(n), col],
                   rng.choice([pk.NONE, 3, _MAX_OSD - 1], n))
    for p in range(pairs):
        live = n_pairs > p
        to = np.where(rng.random(n) < 0.6, rng.choice(good, n),
                      ids(n, 0.4))
        items[live, p, 0] = frm[live]
        items[live, p, 1] = to[live]
        # the next pair chains on this one's `to`, or starts anew
        frm = np.where(rng.random(n) < 0.5, to,
                       raw[np.arange(n), rng.integers(0, raw_w, n)])

    temp_rows = np.full((n, w), pk.NOSD, dtype=np.int32)
    temp_len = np.where(rng.random(n) < 0.2,
                        rng.integers(1, w + 1, n), 0).astype(np.int32)
    cells = np.arange(w)[None, :] < temp_len[:, None]
    temp_rows[cells] = ids((n, w), 0.2)[cells]
    ptemp = np.where(rng.random(n) < 0.2, ids(n), pk.NOSD).astype(np.int32)

    def operands():
        return (raw, pps, raw_len, up_rows, up_len, items, temp_rows,
                temp_len, ptemp, state, weight, affinity,
                np.int32(_MAX_OSD))

    # acting == up: a third of the pg_temp rows repeat the row's `up`
    first = pk.ladder_ref(*operands(), erasure=erasure)
    same = (temp_len > 0) & (first[:, 2 * w] > 0) & (rng.random(n) < 0.34)
    temp_rows[same] = first[same, :w]
    temp_len[same] = first[same, 2 * w]
    return operands()


def _scalar_rows(m, pool_id, operands, rows):
    """`_finish_from` on `rows`, the dense overrides turned back into
    the map's sparse ones."""
    (raw, pps, raw_len, up_rows, up_len, items, temp_rows, temp_len,
     ptemp) = operands[:9]
    m = m.copy()
    m.pg_upmap, m.pg_upmap_items, m.pg_temp, m.primary_temp = {}, {}, {}, {}
    raw_tab = {pool_id: {}}
    for pg in rows:
        key = (pool_id, pg)
        raw_tab[pool_id][pg] = raw[pg, :raw_len[pg]]
        if up_len[pg]:
            m.pg_upmap[key] = up_rows[pg, :up_len[pg]].tolist()
        prs = [(int(f), int(t)) for f, t in items[pg] if (f, t) != (-1, -1)]
        if prs:
            m.pg_upmap_items[key] = prs
        if temp_len[pg]:
            m.pg_temp[key] = temp_rows[pg, :temp_len[pg]].tolist()
        if ptemp[pg] != pk.NOSD:
            m.primary_temp[key] = int(ptemp[pg])
    return {pg: _finish_from(m, m.pools[pool_id], pool_id, pg, raw_tab,
                             {pool_id: pps}) for pg in rows}


@pytest.mark.parametrize("n", [1, 127, 4096 + 5])
@pytest.mark.parametrize("pairs", [1, 2, 4])
@pytest.mark.parametrize("w", [1, 3, 4, 12, 16])
@pytest.mark.parametrize("erasure", [False, True])
def test_plane_ladder_equals_its_twin_on_every_branch(erasure, w, pairs, n):
    """The jitted program (PGs on the lane axis, one attribute word an
    OSD, compaction by a select network) is bit-equal to `ladder_ref`
    on every row and to the scalar `_finish_from` on sampled rows."""
    rng = np.random.default_rng((n, w, pairs, erasure))
    m = _branchy_map(rng)
    operands = _branchy_operands(m, rng, n, w, pairs, erasure)
    got = np.asarray(pk._ladder_jit(erasure)(*operands))
    assert got.dtype == np.int32 and got.shape == (n, 2 * w + 4)
    np.testing.assert_array_equal(
        got, pk.ladder_ref(*operands, erasure=erasure))
    rows = sorted(set(rng.integers(0, n, 48).tolist()))
    want = _scalar_rows(m, 2 if erasure else 1, operands, rows)
    for pg in rows:
        assert pk.unpack_row(got[pg], w) == want[pg], pg
    if n > 1000:
        # the draw reaches the branches it is meant to reach
        up_len, temp_len, ptemp = operands[4], operands[7], operands[8]
        wcol = got[:, 2 * w:]
        assert (up_len > 0).any() and (operands[5][:, 0, 0] != -1).any()
        assert ((temp_len > 0) & (ptemp != pk.NOSD)).any()
        assert ((temp_len > 0) & (ptemp == pk.NOSD)).any()
        assert ((temp_len > 0) & (got[:, :w] == got[:, w:2 * w]).all(1)
                & (wcol[:, 0] == wcol[:, 2])).any()         # acting == up
        assert (wcol[:, 1] != got[:, 0]).any() or w == 1     # a loser first


def _cell_shapes(n, w, pairs, m_pad):
    """The ladder's operands as shapes only (what `lower` needs)."""
    import jax

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32)

    return (i32(n, w), jax.ShapeDtypeStruct((n,), np.uint32), i32(n),
            i32(n, w), i32(n), i32(n, pairs, 2), i32(n, w), i32(n), i32(n),
            i32(m_pad), jax.ShapeDtypeStruct((m_pad,), np.int64),
            i32(m_pad), i32())


@pytest.mark.parametrize("erasure,w,pairs", [
    (False, 3, 1), (True, 3, 1), (False, 4, 2), (True, 12, 4)])
def test_lowered_ladder_has_no_sort_no_wide_int64_and_few_gathers(
        erasure, w, pairs):
    """What made the old body slow on the chip, held where a CPU can
    hold it: at the 1 Mi cell's shapes (and two wider pools') the
    program as lowered sorts nothing, keeps every tensor of the PG
    axis's length out of 64 bits (only the per-OSD `weight` operand is
    int64, until it is folded into the word), and fetches OSD
    attributes at most once an id table (2 + P; the one-hot product
    needs no gather at all)."""
    import re

    from ceph_tpu.crush.types import padded_osds
    n = 1 << 20
    text = pk._ladder_jit(erasure).lower(
        *_cell_shapes(n, w, pairs, padded_osds(10000))).as_text()
    assert "module @jit__unknown " in text      # the traced name stays
    ops = re.findall(r"=\s*\"?stablehlo\.(\w+)", text)
    assert ops and "sort" not in ops
    wide = [t for t in re.findall(r"tensor<([0-9x]*)x[su]?i64>", text)
            if str(n) in t.split("x")]
    assert not wide, wide[:3]
    assert sum("gather" in op for op in ops) <= 2 + pairs
    assert f"tensor<{n}x{2 * w + 4}xi32>" in text


@pytest.mark.parametrize("erasure", [False, True])
def test_plane_ladder_on_a_mesh_equals_one_device(erasure):
    """GSPMD splits the planes on the PG axis as it split the rows:
    the program over four of the host's eight faked devices answers as
    on one, and its result stays sharded over the four."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ceph_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    n, w, pairs = 4096, 4, 2
    rng = np.random.default_rng((7, erasure))
    operands = _branchy_operands(_branchy_map(rng), rng, n, w, pairs,
                                 erasure)
    mesh = make_mesh(4)
    placed = [
        jax.device_put(a, NamedSharding(mesh, PartitionSpec(
            *((tuple(mesh.axis_names),) + (None,) * (a.ndim - 1))
            if a.ndim and a.shape[0] == n else ())))
        for a in map(np.asarray, operands)]
    fn = pk._ladder_jit(erasure)
    out = fn(*placed)
    assert len(out.sharding.device_set) == 4
    assert not out.sharding.is_fully_replicated
    one = np.asarray(fn(*operands))
    np.testing.assert_array_equal(np.asarray(out), one)
    np.testing.assert_array_equal(
        one, pk.ladder_ref(*operands, erasure=erasure))


# -- service property test ----------------------------------------------------

def test_fused_service_matches_oracle_and_exact_delta():
    """Property test (the PR's bit-exactness contract): a FUSED
    service under random churn serves every lookup identical to the
    scalar oracle, its delta is EXACTLY the scalar old-vs-new diff,
    and the epochs really ran fused (device diff, no host tail)."""
    rng = np.random.default_rng(1234)
    m, rule = _base_map()
    svc = SharedPGMappingService()      # engine-less: fused by default
    st = telemetry.mapping_stats()
    before = st.dump()
    svc.update_to(m)
    oracle = _full_oracle(m)
    for (pid, pg), want in oracle.items():
        assert svc.lookup(m, pid, pg) == want
    for _ in range(12):
        new = _churn_once(m, rng, rule)
        upd = svc.update_to(new, from_epoch=m.epoch)
        new_oracle = _full_oracle(new)
        for (pid, pg), want in new_oracle.items():
            assert svc.lookup(new, pid, pg) == want, (pid, pg)
        exact = sorted(k for k, v in new_oracle.items()
                       if oracle.get(k) != v)
        assert not upd.full
        assert sorted(upd.changed) == exact
        m, oracle = new, new_oracle
    after = st.dump()
    assert after["fused_epochs"] - before["fused_epochs"] == 13
    assert after["unfused_epochs"] == before["unfused_epochs"]
    assert after["fused_lookups"] > before["fused_lookups"]
    # the tail collapsed: fused epochs added zero host-tail seconds
    assert (after["phase_seconds"]["host_tail"]["sum"]
            == before["phase_seconds"]["host_tail"]["sum"])


def test_fused_off_knob_restores_host_tail_path():
    """fused=False (the constructor argument; the fused rows'
    reference) keeps the PR 5 host-tail behavior: identical results,
    unfused counters."""
    rng = np.random.default_rng(5)
    m, rule = _base_map()
    svc = SharedPGMappingService(fused=False)
    st = telemetry.mapping_stats()
    before = st.dump()
    svc.update_to(m)
    new = _churn_once(m, rng, rule)
    upd = svc.update_to(new, from_epoch=m.epoch)
    assert not upd.full
    old_oracle = _full_oracle(m)
    exact = sorted(k for k, v in _full_oracle(new).items()
                   if old_oracle.get(k) != v)
    assert sorted(upd.changed) == exact
    after = st.dump()
    assert after["unfused_epochs"] - before["unfused_epochs"] == 2
    assert after["fused_lookups"] == before["fused_lookups"]


def test_tail_divergent_same_epoch_copy_never_reads_fused_rows():
    """A copy of the service's map at the SAME epoch with equal RAW
    signatures but different tail inputs (an extra pg_temp) binds to
    the cache — but must be served by the host tail against ITS OWN
    map, never the fused rows built from the service's map."""
    m, _rule = _base_map()
    svc = SharedPGMappingService()
    svc.update_to(m)
    twin = m.copy()
    twin.pg_temp = dict(twin.pg_temp)
    twin.pg_temp[(1, 3)] = [1, 2]       # tail diverges, raw sig equal
    st = telemetry.mapping_stats()
    before = st.dump()
    for pg in range(8):
        assert svc.lookup(twin, 1, pg) \
            == twin.pg_to_up_acting_osds(1, pg)
    after = st.dump()
    # served from cache (raw rows), but not one fused read
    assert after["lookups"] - before["lookups"] == 8
    assert after["fused_lookups"] == before["fused_lookups"]
    # an exact copy DOES read fused rows
    exact_twin = m.copy()
    before = st.dump()
    for pg in range(8):
        assert svc.lookup(exact_twin, 1, pg) \
            == exact_twin.pg_to_up_acting_osds(1, pg)
    after = st.dump()
    assert after["fused_lookups"] - before["fused_lookups"] == 8


def test_min_pgs_floor_keeps_toy_maps_unfused():
    """A context-backed service under the default
    osdmap_mapping_min_pgs floor skips the fused build on toy maps
    (compile latency must not land on tiny-cluster map handling)."""
    from ceph_tpu.common.context import CephTpuContext

    ctx = CephTpuContext("fused-floor-test")   # min_pgs default 1024
    svc = ctx.mapping_service()
    m, _rule = _base_map()                     # 48 PGs total
    st = telemetry.mapping_stats()
    before = st.dump()
    svc.update_to(m)
    after = st.dump()
    assert after["unfused_epochs"] - before["unfused_epochs"] == 1
    assert after["fused_epochs"] == before["fused_epochs"]
    for pg in range(4):
        assert svc.lookup(m, 1, pg) == m.pg_to_up_acting_osds(1, pg)
    eng = ctx._dispatch
    if eng is not None:
        eng.stop()


# -- engine / mesh channel ----------------------------------------------------

def test_fused_rides_dispatch_engine_and_mesh():
    """A context-backed fused service submits the ladder through the
    dispatch engine (pg_finish batches appear; on this 8-device test
    env they mesh-shard across all chips) and stays bit-exact,
    including the delta."""
    from ceph_tpu.common.context import CephTpuContext

    ctx = CephTpuContext("fused-engine-test")
    ctx.conf.set("osdmap_mapping_min_pgs", 0)
    m, rule = _base_map(pg_num=64)
    svc = ctx.mapping_service()
    d0 = telemetry.dispatch_stats().dump()
    svc.update_to(m)
    d1 = telemetry.dispatch_stats().dump()
    assert d1["batches"] > d0["batches"]
    oracle = _full_oracle(m)
    for (pid, pg), want in oracle.items():
        assert svc.lookup(m, pid, pg) == want, (pid, pg)
    rng = np.random.default_rng(3)
    for _ in range(4):
        new = _churn_once(m, rng, rule)
        upd = svc.update_to(new, from_epoch=m.epoch)
        new_oracle = _full_oracle(new)
        for (pid, pg), want in new_oracle.items():
            assert svc.lookup(new, pid, pg) == want, (pid, pg)
        assert not upd.full
        assert sorted(upd.changed) == sorted(
            k for k, v in new_oracle.items() if oracle.get(k) != v)
        m, oracle = new, new_oracle
    import jax
    if len(jax.devices()) > 1:
        # the ladder batches really fanned out over the mesh
        assert telemetry.dispatch_stats().dump()["sharded_flushes"] > 0
    st = telemetry.mapping_stats().dump()
    assert st["fused_epochs"] >= 5
    eng = ctx._dispatch
    if eng is not None:
        eng.stop()


# -- balancer what-if ---------------------------------------------------------

def test_what_if_up_matches_host_up_of():
    """Batched what-if scoring == the balancer's per-candidate host
    pipeline (raw + pair rewrites + state filter), including invalid
    pairs that must be rejected."""
    rng = np.random.default_rng(21)
    m, rule = _base_map()
    for _ in range(8):
        m = _churn_once(m, rng, rule)
    svc = SharedPGMappingService()
    svc.update_to(m)
    pool = m.pools[1]
    n = m.max_osd
    cands = []
    for pg in range(pool.pg_num):
        prs = [(int(rng.integers(0, n + 2)), int(rng.integers(0, n + 2)))
               for _ in range(int(rng.integers(0, 3)))]
        cands.append((pg, prs))
    got = svc.what_if_up(m, 1, cands)
    assert got is not None
    for (pg, prs), up in zip(cands, got):
        raw = svc.raw_row(m, 1, pg)
        assert raw is not None
        raw = list(raw)
        for frm, to in prs:
            if frm in raw and to not in raw and m.exists(to) \
                    and not m._is_out(to):
                raw[raw.index(frm)] = to
        want, _ = m._raw_to_up_osds(pool, raw)
        assert up == want, (pg, prs)


def test_balancer_plan_identical_with_and_without_fused_scoring():
    """calc_pg_upmaps produces the SAME plan whether candidate
    scoring runs through the fused batch path or the host fallback."""
    from ceph_tpu import balancer

    crush, _root, rule = build_two_level_map(4, 2)
    m = OSDMap(crush=crush, epoch=2)
    m.set_max_osd(8)
    for o in range(8):
        m.mark_up(o)
    m.pools[1] = PGPool(pool_id=1, size=2, crush_rule=rule, pg_num=64)
    with_fused = balancer.calc_pg_upmaps(m, max_deviation=1)
    orig = balancer._shared_service
    try:
        balancer._shared_service = lambda _m: None
        without = balancer.calc_pg_upmaps(m, max_deviation=1)
    finally:
        balancer._shared_service = orig
    assert with_fused == without


# -- shard_map wrappers -------------------------------------------------------

def test_shard_map_rows_pallas_encode_mesh_bit_exact():
    """The shard_map wrapper runs the fused Pallas encode per shard
    over a mesh-sharded batch, bit-exact vs the numpy oracle, with the
    output still sharded like the input (interpret mode: the TPU
    compile path is covered by the benchmark on TPU hosts)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ceph_tpu.gf.matrix import gen_cauchy1_matrix
    from ceph_tpu.gf.tables import bit_matrix
    from ceph_tpu.ops.gf_kernel import (
        _G, _SB, _blockdiag, _encode_pallas, ec_encode_ref,
        shard_map_rows)
    from ceph_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device backend")
    k, mm, chunk = 4, 2, 512
    coeff = gen_cauchy1_matrix(k, mm)[k:]
    w_blk = jnp.asarray(_blockdiag(bit_matrix(coeff), _G))
    mesh = make_mesh(len(jax.devices()))
    rng = np.random.default_rng(17)
    s = _SB * len(jax.devices())
    data = rng.integers(0, 256, (s, k, chunk), dtype=np.uint8)
    spec = PartitionSpec(tuple(mesh.axis_names), None, None)
    placed = jax.device_put(jnp.asarray(data),
                            NamedSharding(mesh, spec))

    out = shard_map_rows(
        lambda d, w: _encode_pallas(w, d, k=k, m=mm, bc=chunk,
                                    interpret=True),
        placed, w_blk)
    assert len(out.sharding.device_set) == len(jax.devices())
    np.testing.assert_array_equal(np.asarray(out),
                                  ec_encode_ref(coeff, data))


def test_fastpath_pallas_sharded_batch_matches_scalar_oracle():
    """BatchMapper.do_rule routes a mesh-sharded batch through the
    shard_map-wrapped Pallas fastpath (the lifted PR 7 guard) and the
    result equals the scalar rule oracle row for row."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ceph_tpu.crush import mapper_jax
    from ceph_tpu.crush.fastpath import detect, tables_of
    from ceph_tpu.crush.mapper_jax import BatchMapper
    from ceph_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device backend")
    crush_map, _root, rid = build_two_level_map(6, 4)
    fr = detect(crush_map, rid)
    assert fr is not None
    assert not tables_of(fr).shape.pallas    # CPU: not auto-selected
    bm = BatchMapper(crush_map)
    ft = bm._fast_cache[rid] = tables_of(fr, pallas=True, interpret=True)

    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    rng = np.random.default_rng(23)
    n = 16 * n_dev
    xs = rng.integers(0, 2 ** 32, (n,), dtype=np.uint32)
    reweight = np.full(crush_map.max_devices, 0x10000, dtype=np.int64)
    reweight[1] = 0
    reweight[5] = 0x8000
    spec = PartitionSpec(tuple(mesh.axis_names))
    placed = jax.device_put(jnp.asarray(xs), NamedSharding(mesh, spec))
    out = bm.do_rule(rid, placed, 3, reweight)
    # the sharded fastpath entry really compiled, and took the map's
    # tables replicated over the mesh
    assert any(shape == ft.shape and sh is not None
               for shape, _rmax, sh in mapper_jax._FAST_PROGRAMS)
    assert ft.placed(mesh) and not ft.placed(None)
    want = scalar_rows(crush_map, rid, xs, 3, reweight)
    np.testing.assert_array_equal(np.asarray(out), want)


# -- observability ------------------------------------------------------------

def test_fused_families_in_prometheus_scrape():
    from test_kernel_telemetry import _scrape, parse_exposition

    fams = parse_exposition(_scrape())
    for fam, typ in (
            ("ceph_kernel_mapping_fused_epochs_total", "counter"),
            ("ceph_kernel_mapping_unfused_epochs_total", "counter"),
            ("ceph_kernel_mapping_fused_lookups_total", "counter"),
            ("ceph_kernel_mapping_host_tail_share", "gauge")):
        assert fam in fams, fam
        assert fams[fam]["type"] == typ
