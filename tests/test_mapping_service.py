"""Shared PG mapping service (osd.mapping.SharedPGMappingService):
oracle equality under random map churn, exact changed-PG deltas,
epoch-skip burst coalescing, the O(changed + local) OSD scan (scalar
pipeline calls stay flat across an epoch advance), and the
ceph_kernel_mapping_* prometheus families."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ceph_tpu.crush import build_two_level_map
from ceph_tpu.ops import telemetry
from ceph_tpu.osd import OSDMap, PGPool, SharedPGMappingService
from ceph_tpu.osd.mapping import OSDMapMapping
from ceph_tpu.osd.osdmap import OSD_EXISTS, OSD_UP


def _base_map(hosts=3, per_host=3, epoch=2):
    crush, _root, rule = build_two_level_map(hosts, per_host)
    n = hosts * per_host
    m = OSDMap(crush=crush, epoch=epoch)
    m.set_max_osd(n)
    for o in range(n):
        m.mark_up(o)
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rule, pg_num=32)
    m.pools[2] = PGPool(pool_id=2, size=2, crush_rule=rule, pg_num=16)
    return m, rule


def _full_oracle(m: OSDMap) -> dict:
    return {(pid, pg): m.pg_to_up_acting_osds(pid, pg)
            for pid, pool in m.pools.items()
            for pg in range(pool.pg_num)}


def _churn(m: OSDMap, rng, rule: int) -> OSDMap:
    """One random epoch of churn: a NEW map (service contract: maps
    are immutable once published)."""
    new = m.copy()
    new.epoch = m.epoch + 1
    n = new.max_osd
    kind = int(rng.integers(0, 8))
    osd = int(rng.integers(0, n))
    if kind == 0:            # reweight
        new.osd_weight[osd] = int(rng.choice(
            (0, 0x4000, 0x8000, 0xC000, 0x10000)))
    elif kind == 1:          # down (state only)
        new.osd_state[osd] = new.osd_state[osd] & ~OSD_UP
    elif kind == 2:          # back up
        new.osd_state[osd] = OSD_EXISTS | OSD_UP
    elif kind == 3:          # primary affinity
        new.osd_primary_affinity[osd] = int(rng.choice(
            (0, 0x4000, 0x10000)))
    elif kind == 4:          # pg_temp inject / clear
        pid = int(rng.choice(list(new.pools)))
        pg = int(rng.integers(0, new.pools[pid].pg_num))
        if (pid, pg) in new.pg_temp:
            del new.pg_temp[(pid, pg)]
        else:
            new.pg_temp[(pid, pg)] = [osd, (osd + 1) % n]
    elif kind == 5:          # primary_temp inject / clear
        pid = int(rng.choice(list(new.pools)))
        pg = int(rng.integers(0, new.pools[pid].pg_num))
        if (pid, pg) in new.primary_temp:
            del new.primary_temp[(pid, pg)]
        else:
            new.primary_temp[(pid, pg)] = osd
    elif kind == 6:          # upmap pair inject / clear
        pid = int(rng.choice(list(new.pools)))
        pg = int(rng.integers(0, new.pools[pid].pg_num))
        if (pid, pg) in new.pg_upmap_items:
            del new.pg_upmap_items[(pid, pg)]
        else:
            frm = int(rng.integers(0, n))
            new.pg_upmap_items[(pid, pg)] = [(frm, (frm + 2) % n)]
    else:                    # pg_num growth (pool replaced wholesale)
        pid = int(rng.choice(list(new.pools)))
        old_pool = new.pools[pid]
        new.pools[pid] = PGPool(
            pool_id=pid, size=old_pool.size, crush_rule=rule,
            pg_num=old_pool.pg_num * 2, pgp_num=old_pool.pgp_num)
    return new


def test_shared_mapping_matches_oracle_under_churn():
    """Property test: after every random churn epoch (reweights, osd
    down/out, affinity, pg_num growth, upmap/pg_temp/primary_temp
    injection), (a) every get() equals the scalar oracle and (b) the
    changed-PG delta is EXACTLY the set of PGs whose oracle
    (up, up_primary, acting, acting_primary) moved."""
    rng = np.random.default_rng(1234)
    m, rule = _base_map()
    # scalar rebuild backend: identical cache/delta machinery without
    # paying a jit compile in the property loop (the device rebuild
    # path has its own test below)
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(m)
    oracle = _full_oracle(m)
    for (pid, pg), want in oracle.items():
        assert svc.lookup(m, pid, pg) == want
    for _ in range(12):
        new = _churn(m, rng, rule)
        upd = svc.update_to(new, from_epoch=m.epoch)
        new_oracle = _full_oracle(new)
        for (pid, pg), want in new_oracle.items():
            assert svc.lookup(new, pid, pg) == want, (pid, pg)
        exact = sorted(k for k, v in new_oracle.items()
                       if oracle.get(k) != v)
        assert not upd.full
        assert sorted(upd.changed) == exact
        m, oracle = new, new_oracle


def test_incremental_reuse_and_stats():
    """State-only churn reuses every pool table; weight churn
    recomputes; the MappingStats counters tell the story."""
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    st = telemetry.mapping_stats()
    d0 = st.dump()
    svc.update_to(m)
    # state-only epoch: all pools reused
    m2 = m.copy()
    m2.epoch = m.epoch + 1
    m2.osd_state[0] &= ~OSD_UP
    svc.update_to(m2, from_epoch=m.epoch)
    # weight epoch: pools sharing the rule recompute
    m3 = m2.copy()
    m3.epoch = m2.epoch + 1
    m3.osd_weight[1] = 0x8000
    svc.update_to(m3, from_epoch=m2.epoch)
    d = st.dump()
    assert d["epoch_updates"] - d0["epoch_updates"] == 3
    # epoch 2: both pools computed; epoch 3: both reused; epoch 4: both
    # recomputed (shared crush rule -> shared reachable set)
    assert d["pools_reused"] - d0["pools_reused"] == 2
    assert d["pools_recomputed"] - d0["pools_recomputed"] == 4
    assert d["cached_pools"] == 2


def test_epoch_skip_on_concurrent_burst(monkeypatch):
    """While one update computes, a burst of newer maps queues; only
    the NEWEST is ever computed (intermediates are skipped) and every
    waiter returns once the cache passes its epoch."""
    m, rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(m)
    orig = OSDMapMapping.update

    def slow_update(self, osdmap=None, engine=None):
        time.sleep(0.25)
        return orig(self, osdmap, engine)

    monkeypatch.setattr(OSDMapMapping, "update", slow_update)
    maps = [m]
    for _ in range(3):
        nm = maps[-1].copy()
        nm.epoch = maps[-1].epoch + 1
        nm.osd_weight[len(maps) % nm.max_osd] = 0x8000
        maps.append(nm)
    st = telemetry.mapping_stats()
    before = st.dump()
    threads = [threading.Thread(target=svc.update_to, args=(mm,),
                                daemon=True) for mm in maps[1:]]
    threads[0].start()
    time.sleep(0.05)           # let the first update begin computing
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(timeout=30)
    after = st.dump()
    assert svc.epoch == maps[-1].epoch
    # first target computed + the newest; the middle epoch was skipped
    assert after["epoch_updates"] - before["epoch_updates"] == 2
    assert after["epoch_skips"] - before["epoch_skips"] >= 1
    # the skipped epoch's tables were never built
    assert maps[2].epoch not in svc._tables
    # ...but its mappings are still correct (scalar-oracle fallback)
    pid = 1
    assert (svc.lookup(maps[2], pid, 0)
            == maps[2].pg_to_up_acting_osds(pid, 0))


def test_delta_clamped_to_caller_epoch():
    """A reader whose map is OLDER than the cache head must get a
    delta ending at ITS epoch — a change that reverted by the head is
    visible in the reader's map and must not be masked by the
    head-spanning union — and a reader inside a skipped jump gets a
    full rescan, never a wrong delta."""
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(m)
    m2 = m.copy()
    m2.epoch = m.epoch + 1
    m2.osd_weight[0] = 0x8000
    m3 = m2.copy()
    m3.epoch = m2.epoch + 1
    m3.osd_weight[0] = 0x10000        # revert: m3 mappings == m's
    svc.update_to(m2, from_epoch=m.epoch)
    svc.update_to(m3, from_epoch=m2.epoch)
    # reader still at m asking about m2 (cache head is m3)
    upd = svc.update_to(m2, from_epoch=m.epoch)
    assert upd.epoch_to == m2.epoch
    exact = sorted(
        (pid, pg) for pid, pool in m2.pools.items()
        for pg in range(pool.pg_num)
        if m.pg_to_up_acting_osds(pid, pg)
        != m2.pg_to_up_acting_osds(pid, pg))
    assert not upd.full
    assert sorted(upd.changed) == exact
    assert exact        # the revert scenario really changed something
    # reader at an epoch INSIDE a skipped jump: only full is safe
    m5 = m3.copy()
    m5.epoch = m3.epoch + 2           # jump over m3.epoch+1
    m5.osd_weight[1] = 0x8000
    svc.update_to(m5, from_epoch=m3.epoch)
    m4 = m3.copy()
    m4.epoch = m3.epoch + 1
    upd4 = svc.update_to(m4, from_epoch=m3.epoch)
    assert upd4.full


def test_same_epoch_map_copy_binds_to_cache():
    """Another consumer's decode of the same published epoch (equal
    content, different object) binds to the shared tables via the
    signature check — cross-consumer sharing — while a content-
    DIVERGENT map at the same epoch is rejected and served by the
    oracle."""
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(m)
    st = telemetry.mapping_stats()
    twin = m.copy()                   # same epoch, same content
    before = st.dump()
    for pg in range(8):
        assert svc.lookup(twin, 1, pg) == twin.pg_to_up_acting_osds(1, pg)
    after = st.dump()
    assert after["lookups"] - before["lookups"] == 8
    assert after["lookup_fallbacks"] == before["lookup_fallbacks"]
    alien = m.copy()                  # same epoch, DIFFERENT weights
    alien.osd_weight[0] = 0x1234
    before = st.dump()
    for pg in range(8):
        assert svc.lookup(alien, 1, pg) \
            == alien.pg_to_up_acting_osds(1, pg)
    after = st.dump()
    assert after["lookup_fallbacks"] - before["lookup_fallbacks"] == 8


def test_warm_foreign_map_never_poisons_online_deltas():
    """An offline warm() with a foreign map (what-if run at an
    arbitrary epoch number) must not leak wrong deltas to online
    consumers: the chain is invalidated, the published epoch never
    regresses, and the online reader gets a FULL rescan with
    oracle-correct reads."""
    live, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(live)
    foreign = live.copy()
    foreign.epoch = live.epoch + 5
    foreign.osd_weight[2] = 0x2000
    svc.warm(foreign)
    assert svc.epoch == foreign.epoch      # monotonic ratchet
    live2 = live.copy()
    live2.epoch = live.epoch + 1
    live2.osd_state[1] &= ~OSD_UP
    upd = svc.update_to(live2, from_epoch=live.epoch)
    assert upd.full                        # never a garbage delta
    for pid, pool in live2.pools.items():
        for pg in range(pool.pg_num):
            assert svc.lookup(live2, pid, pg) \
                == live2.pg_to_up_acting_osds(pid, pg)


def test_failed_update_recovers_with_exact_delta(monkeypatch):
    """An update that dies mid-compute (device error, future timeout)
    must leave the service consistent: the exception propagates, a
    retry — including from OTHER waiters — makes progress (no
    livelock), and the retry's delta is computed against the REAL old
    tables, not the failed attempt's half-state."""
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.update_to(m)
    orig = OSDMapMapping.update
    boom = {"on": True}

    def flaky(self, osdmap=None, engine=None):
        if boom["on"]:
            boom["on"] = False        # fail exactly once
            raise RuntimeError("device fell over")
        return orig(self, osdmap, engine)

    monkeypatch.setattr(OSDMapMapping, "update", flaky)
    m2 = m.copy()
    m2.epoch = m.epoch + 1
    m2.osd_weight[0] = 0x8000
    m2.osd_state[3] &= ~OSD_UP        # a state change the delta must see
    with pytest.raises(RuntimeError):
        svc.update_to(m2, from_epoch=m.epoch)
    assert svc.epoch == m.epoch       # nothing half-installed
    upd = svc.update_to(m2, from_epoch=m.epoch)   # retry succeeds
    assert svc.epoch == m2.epoch
    assert not upd.full
    exact = sorted(
        (pid, pg) for pid, pool in m2.pools.items()
        for pg in range(pool.pg_num)
        if m.pg_to_up_acting_osds(pid, pg)
        != m2.pg_to_up_acting_osds(pid, pg))
    assert sorted(upd.changed) == exact


def test_device_rebuild_path_rides_dispatch_engine():
    """The tpu backend submits per-pool remaps through the context's
    dispatch engine and the result is bit-identical to the oracle."""
    from ceph_tpu.common.context import CephTpuContext

    ctx = CephTpuContext("mapping-test")
    ctx.conf.set("osdmap_mapping_min_pgs", 0)   # force the device path
    m, _rule = _base_map(hosts=2, per_host=2, epoch=2)
    m.pools = {1: PGPool(pool_id=1, size=2,
                         crush_rule=m.pools[1].crush_rule, pg_num=16)}
    svc = ctx.mapping_service()
    d0 = telemetry.dispatch_stats().dump()
    svc.update_to(m)
    d1 = telemetry.dispatch_stats().dump()
    assert d1["batches"] > d0["batches"]        # remap rode the engine
    for pg in range(16):
        assert svc.lookup(m, 1, pg) == m.pg_to_up_acting_osds(1, pg)
    # weight change: recompute rides the engine again, still exact
    m2 = m.copy()
    m2.epoch = 3
    m2.osd_weight[0] = 0x8000
    upd = svc.update_to(m2, from_epoch=2)
    assert not upd.full
    exact = [(1, pg) for pg in range(16)
             if m.pg_to_up_acting_osds(1, pg)
             != m2.pg_to_up_acting_osds(1, pg)]
    assert sorted(upd.changed) == sorted(exact)
    eng = ctx._dispatch
    if eng is not None:
        eng.stop()


def _count_scan_scalar_calls(monkeypatch):
    """Count scalar pg_to_up_acting_osds calls, attributing those made
    from inside an OSD's _scan_pgs (the map-consumption path the
    shared cache is supposed to eliminate) separately from incidental
    callers (per-second stats ticks hitting the update window)."""
    import sys

    calls = {"scan": 0, "total": 0}
    orig = OSDMap.pg_to_up_acting_osds

    def counting(self, pool_id, ps):
        calls["total"] += 1
        f = sys._getframe(1)
        for _ in range(12):
            if f is None:
                break
            if f.f_code.co_name == "_scan_pgs":
                calls["scan"] += 1
                break
            f = f.f_back
        return orig(self, pool_id, ps)

    monkeypatch.setattr(OSDMap, "pg_to_up_acting_osds", counting)
    return calls


def test_scan_pgs_scalar_calls_stay_flat_across_epoch(monkeypatch):
    """Acceptance gate: an epoch advance over a large pool does NOT
    re-run the scalar pipeline per PG inside
    _scan_pgs — the OSDs consume the map from the shared cache (changed
    + local PGs, served by cached-raw pipeline tails), where the seed
    walked every PG scalar on every OSD (3 x 64 here)."""
    from ceph_tpu.tools.vstart import MiniCluster

    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(3)
        client = c.client()
        pool = c.create_pool(client, pg_num=64, size=3)
        client.open_ioctx(pool).write_full("warm", b"x")
        st = telemetry.mapping_stats()
        before = st.dump()
        calls = _count_scan_scalar_calls(monkeypatch)
        res, _ = client.mon_command(
            {"prefix": "osd reweight", "id": "1", "weight": "0.5"})
        assert res == 0
        epoch = c.mon.osdmap.epoch
        c.wait_for_epoch(epoch)
        # wait_for_epoch returns once daemons SWAPPED the map; the
        # cache update + delta scan run right after — poll for the
        # scans' cache reads to land (1-core hosts need a moment)
        deadline = time.time() + 10
        while (st.dump()["lookups"] <= before["lookups"]
               and time.time() < deadline):
            time.sleep(0.05)
        time.sleep(0.2)
        after = st.dump()
        # seed behavior: every OSD walks every PG scalar in _scan_pgs
        # (>= 3*64 for the big pool alone).  Shared cache: zero — any
        # residual would be a sparse oracle fallback.
        assert calls["scan"] < 32, calls
        # ...and the scans really read the cache (lookup hits grew)
        assert after["lookups"] > before["lookups"]
        # the cluster still works after the delta-driven scan
        io = client.open_ioctx(pool)
        io.write_full("after", b"y")
        assert io.read("after") == b"y"
    finally:
        c.stop()


def test_epoch_burst_e2e_skip_and_peering():
    """A partitioned OSD misses a burst of epochs, then catches up via
    one subscription renewal (the mon ships the whole inc chain in ONE
    message): the shared service jumps straight to the newest epoch —
    the intermediate maps are never computed (epoch-skips) — while
    peering still converges and IO proceeds."""
    from ceph_tpu.tools.vstart import MiniCluster

    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(3)
        client = c.client()
        pool = c.create_pool(client, pg_num=32, size=3)
        victim = c.osds[2]
        orig_handle = victim._handle_map
        dropping = {"on": True}

        def flaky_handle(msg):
            if dropping["on"]:
                return          # partitioned: map pushes are lost
            orig_handle(msg)

        drops = {"n": 0}

        def flaky_counting(msg, _orig=flaky_handle):
            if dropping["on"]:
                drops["n"] += 1
            _orig(msg)

        # install the interceptor BEFORE reading e0: a push landing in
        # between would advance the epoch past the frozen baseline
        victim._handle_map = flaky_counting
        e0 = victim.osdmap.epoch
        for i, w in enumerate(("0.9", "0.8", "0.7", "0.6")):
            res, _ = client.mon_command(
                {"prefix": "osd reweight", "id": str(i % 2),
                 "weight": w})
            assert res == 0
        target = c.mon.osdmap.epoch
        assert target - e0 >= 4
        # drain the in-flight pushes INTO the partition before healing:
        # a push sent during the outage but delivered after the heal
        # would advance the victim piecemeal and shrink the one-jump
        # skip count this test is about (wait for the drop counter to
        # go quiet, not a fixed sleep — lockdep runs are slower)
        quiet = time.time() + 0.5
        deadline = time.time() + 10
        while time.time() < deadline and time.time() < quiet:
            n = drops["n"]
            time.sleep(0.1)
            if drops["n"] != n:
                quiet = time.time() + 0.5
        assert victim.osdmap.epoch == e0
        st = telemetry.mapping_stats()
        before = st.dump()
        # heal the partition; the renewal carries our stale epoch and
        # the mon answers with every missing incremental in one message
        dropping["on"] = False
        victim._renew_map_subscription(time.time(), force=True)
        deadline = time.time() + 10
        while victim.osdmap.epoch < target and time.time() < deadline:
            time.sleep(0.05)
        assert victim.osdmap.epoch >= target
        time.sleep(0.3)
        after = st.dump()
        # the jump e0 -> target computed ONE epoch; the intermediates
        # were skipped, never built
        assert after["epoch_skips"] - before["epoch_skips"] \
            >= target - e0 - 1
        svc = victim.ctx.mapping_service()
        for e in range(e0 + 1, target):
            assert e not in svc._tables
        # peering converged across the jump: IO lands on all members
        io = client.open_ioctx(pool)
        for i in range(8):
            io.write_full(f"burst-{i}", b"z" * 64)
            assert io.read(f"burst-{i}") == b"z" * 64
    finally:
        c.stop()


def test_mapping_families_in_prometheus_scrape():
    """ceph_kernel_mapping_* families appear in the mgr scrape with
    valid exposition structure."""
    from test_kernel_telemetry import _scrape, parse_exposition

    fams = parse_exposition(_scrape())
    for fam in ("ceph_kernel_mapping_epoch_updates_total",
                "ceph_kernel_mapping_epoch_skips_total",
                "ceph_kernel_mapping_pools_recomputed_total",
                "ceph_kernel_mapping_pools_reused_total",
                "ceph_kernel_mapping_lookups_total",
                "ceph_kernel_mapping_lookup_fallbacks_total",
                "ceph_kernel_mapping_cached_pgs"):
        assert fam in fams, fam
        assert fams[fam]["type"] in ("counter", "gauge")
    for fam in ("ceph_kernel_mapping_update_latency_seconds",
                "ceph_kernel_mapping_changed_pgs"):
        assert fam in fams, fam
        assert fams[fam]["type"] == "histogram"


def test_admin_socket_dump_mapping_stats():
    """Every context serves dump_mapping_stats."""
    from ceph_tpu.common.context import CephTpuContext

    ctx = CephTpuContext("mapping-admin-test")
    out = ctx.admin.execute("dump_mapping_stats")
    assert "epoch_updates" in out
    assert "changed_pgs" in out
