"""The background-integrity fairness gate (e2e): continuous deep
scrub of every PG while the 4-tenant front runs at full rate — the
reserved tenant keeps being served from its reservation while the storm
runs and every tenant progresses, the scrub traffic is visibly served
from the background_best_effort class (dump_qos_stats), and corruption
injected mid-run is repaired AND verified while the tenants keep
hammering.  Gold's ops/s with and without the storm are read and put in
the failure messages, not gated: on a CPU shared with five other test
workers a rate over 2.5 s says how busy the host was (the queue-level
attainment property is pinned, on a virtual clock, in
test_qos_dmclock.py).

The data plane is made deterministic the same way test_qos_fairness
does it: a fixed per-op service delay wrapped around the shard
handler, so attainment depends on the dmclock arbitration, not on
host speed.  Tenant lanes ride the same machinery the S3 front stamps
(MOSDOp qos_tenant tags; the gateway-tagged variant is pinned by
test_qos_fairness's S3 scenario) — this gate adds the scrub storm on
top and measures the delta."""

from __future__ import annotations

import threading
import time

import pytest

from ceph_tpu.messages.osd_msgs import OP_WRITEFULL, OSDOpField
from ceph_tpu.objectstore import Transaction
from ceph_tpu.client.rados import ceph_str_hash_rjenkins
from ceph_tpu.osd.osdmap import pg_to_pgid
from ceph_tpu.tools.vstart import MiniCluster

pytestmark = pytest.mark.filterwarnings("ignore")

SERVICE_DELAY = 0.002


def _install_service_delay(osd, delay: float = SERVICE_DELAY) -> None:
    orig = osd.opwq._handler

    def slow(klass, item, served=None):
        time.sleep(delay)
        orig(klass, item, served)
    osd.opwq._handler = slow


def _set_profiles(client, profiles: dict[str, dict]) -> None:
    for tenant, p in profiles.items():
        rc, out = client.mon_command(
            {"prefix": "qos set", "tenant": tenant, **p})
        assert rc == 0, out


def _wait_profiles_applied(cluster, tenants, timeout=10.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(set(o._qos_profiles_applied) >= set(tenants)
               for o in cluster.osds.values()):
            return
        time.sleep(0.05)
    raise TimeoutError("qos_db never reached every osd")


def _gold_served(cluster, phase: str | None = None) -> int:
    """Ops gold's lane was served, cluster-wide; ``phase`` narrows it
    to one dmclock phase ("reservation": served because the floor was
    due, whatever the weights said)."""
    total = 0
    for osd in cluster.osds.values():
        d = osd.ctx.admin.execute("dump_qos_stats")
        row = d["classes"].get("client.gold")
        if row:
            total += (row["served"][phase] if phase
                      else sum(row["served"].values()))
    return total


def _background_served(cluster) -> int:
    total = 0
    for osd in cluster.osds.values():
        d = osd.ctx.admin.execute("dump_qos_stats")
        row = d["classes"].get("background_best_effort")
        if row:
            total += sum(row["served"].values())
    return total


class _Pump:
    def __init__(self, client, pool: int, tenant: str, n_threads: int,
                 payload: bytes = b"x" * 64):
        self.client = client
        self.pool = pool
        self.tenant = tenant
        self.stop = threading.Event()
        self.counts = [0] * n_threads
        self.threads = [
            threading.Thread(target=self._run, args=(i, payload),
                             daemon=True, name=f"pump-{tenant}-{i}")
            for i in range(n_threads)]

    def _run(self, idx: int, payload: bytes) -> None:
        i = 0
        while not self.stop.is_set():
            oid = f"{self.tenant}-{idx}-{i % 4}"
            try:
                self.client.operate(
                    self.pool, oid,
                    [OSDOpField(OP_WRITEFULL, 0, len(payload),
                                payload)],
                    tenant=self.tenant)
            except (OSError, TimeoutError):
                continue
            self.counts[idx] += 1
            i += 1

    def start(self):
        for t in self.threads:
            t.start()
        return self

    def halt(self):
        self.stop.set()

    def join(self):
        for t in self.threads:
            t.join(timeout=15)

    @property
    def total(self) -> int:
        return sum(self.counts)


PROFILES = {
    "hog": {"weight": 8.0},
    "gold": {"reservation": 100.0, "weight": 0.01},
    "silver": {"weight": 2.0},
    "bronze": {"weight": 8.0, "limit": 50.0},
}

#: gold's demand comfortably exceeds its 100 ops/s reservation, so
#: the floor BINDS and attainment measures the scheduler, not the
#: pumps' closed-loop latency
PUMP_THREADS = {"hog": 8, "gold": 5, "silver": 4, "bronze": 4}

GOLD_RESERVATION = 100.0


def _attainment(rate: float) -> float:
    """Reservation attainment: how much of the reserved floor the
    tenant actually drew, capped at 1 — demand above the floor is
    closed-loop noise, not QoS.  Reported, not gated (module doc)."""
    return min(rate, GOLD_RESERVATION) / GOLD_RESERVATION


def test_scrub_storm_keeps_tenant_reservations():
    cluster = MiniCluster(
        n_osds=3, ms_type="loopback",
        osd_conf={"osd_op_num_shards": 2,
                  "osd_scrub_verify_timeout": 10.0}).start()
    scrub_stop = threading.Event()
    scrub_threads = []
    try:
        cluster.wait_for_osd_count(3)
        client = cluster.client(timeout=30.0)
        pool = cluster.create_pool(client, pg_num=8, size=3)
        _set_profiles(client, PROFILES)
        _wait_profiles_applied(cluster, PROFILES)
        for osd in cluster.osds.values():
            _install_service_delay(osd)
        # a victim object with known bytes, corrupted on one replica
        # mid-run: the continuous sweep must find, repair, and VERIFY
        # it while the tenants keep the cluster saturated
        io = client.open_ioctx(pool)
        body = b"gate-truth" * 120
        io.write_full("gate-victim", body)
        for t, n in PUMP_THREADS.items():
            for idx in range(n):
                for i in range(4):
                    io.write_full(f"{t}-{idx}-{i}", b"x" * 64)
        time.sleep(0.3)
        m = cluster.mon.osdmap
        pg = pg_to_pgid(ceph_str_hash_rjenkins("gate-victim"),
                        m.pools[pool].pg_num)
        up, primary, _a, _ap = m.pg_to_up_acting_osds(pool, pg)
        victim_id = next(o for o in up if o != primary)
        cid = f"{pool}.{pg}"

        # warm the digest kernel on every live shape BEFORE anything
        # is measured: the gate is a steady-state arbitration claim,
        # and first-call jit compiles (attributed to the compile
        # ledger in production) would otherwise land inside the scrub
        # measurement window only
        for osd in cluster.osds.values():
            agg = osd.scrub_all_pgs()
            assert agg["clean"], agg
        warm_sweeps = {o: osd.ctx.admin.execute(
            "dump_scrub_stats")["sweeps"]
            for o, osd in cluster.osds.items()}

        pumps = {t: _Pump(client, pool, t, n).start()
                 for t, n in PUMP_THREADS.items()}
        try:
            # -- scrub-off baseline ---------------------------------
            time.sleep(1.0)                       # warmup
            g0 = _gold_served(cluster)
            t0 = time.perf_counter()
            time.sleep(2.5)
            base_rate = (_gold_served(cluster) - g0) \
                / (time.perf_counter() - t0)

            # -- continuous deep scrub of every PG: the production
            # driver (osd_scrub_auto_interval), hot-enabled ---------
            cluster.osds[victim_id].store.apply_transaction(
                Transaction().truncate(cid, "gate-victim", 0)
                .write(cid, "gate-victim", 0, b"gate-lies!" * 120))
            for osd in cluster.osds.values():
                osd.ctx.conf.set("osd_scrub_auto_interval", 0.5)
            time.sleep(1.5)                       # storm settles in
            g1 = _gold_served(cluster)
            r1 = _gold_served(cluster, "reservation")
            t1 = time.perf_counter()
            time.sleep(2.5)
            scrub_rate = (_gold_served(cluster) - g1) \
                / (time.perf_counter() - t1)
            storm_reserved = _gold_served(cluster, "reservation") - r1

            # repaired-and-verified DURING the run: pumps still
            # hammering, sweeps still going — poll the victim's store
            # until the scrub path restored it
            deadline = time.time() + 45.0
            while time.time() < deadline:
                if cluster.osds[victim_id].store.read(
                        cid, "gate-victim") == body:
                    break
                time.sleep(0.5)
            repaired_during_run = cluster.osds[victim_id].store.read(
                cid, "gate-victim") == body
        finally:
            for p in pumps.values():
                p.halt()
            scrub_stop.set()
            for osd in cluster.osds.values():
                try:
                    osd.ctx.conf.set("osd_scrub_auto_interval", 0.0)
                except Exception:
                    pass
            for p in pumps.values():
                p.join()

        # the acceptance gate: with every PG under continuous deep
        # scrub the reserved tenant was still served, and from its
        # reservation (the floor was in play, and honored)
        rates = {"base_rate": base_rate, "scrub_rate": scrub_rate,
                 "base_attainment": _attainment(base_rate),
                 "scrub_attainment": _attainment(scrub_rate)}
        assert base_rate > 0, rates
        assert scrub_rate > 0 and storm_reserved > 0, \
            (rates, storm_reserved)

        # scrub was served from the background class, visibly
        assert _background_served(cluster) > 0
        # and the continuous driver really swept during the storm
        for o, osd in cluster.osds.items():
            st = osd.ctx.admin.execute("dump_scrub_stats")
            assert st["sweeps"] > warm_sweeps[o], (o, st)

        # the injected corruption was repaired AND verified during the
        # run (the victim replica read back as truth while the tenants
        # were still at full rate, and the cluster ledger shows a
        # verified repair with nothing unverified)
        assert repaired_during_run
        repaired = unverified = 0
        for osd in cluster.osds.values():
            st = osd.ctx.admin.execute("dump_scrub_stats")
            repaired += st["repaired"]
            unverified += st["repair_unverified"]
        assert repaired >= 1, (repaired, unverified)
        assert unverified == 0, (repaired, unverified)
        # every tenant progressed under the storm
        assert all(p.total > 0 for p in pumps.values()), {
            t: p.total for t, p in pumps.items()}
    finally:
        scrub_stop.set()
        cluster.stop()
