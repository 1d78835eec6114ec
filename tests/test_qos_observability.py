"""QoS control-plane + observability surfaces: mon qos set/rm/ls,
qos_db map distribution (full + incremental codec), scheduler lane
eviction and O(1) backlog accounting, hot profile re-tagging,
dump_qos_stats, the MMgrReport qos tail, ceph_qos_* prometheus
families, and the qos_wait trace event."""

from __future__ import annotations

import json
import time

from ceph_tpu.msg.encoding import Decoder, Encoder
from ceph_tpu.osd.map_codec import (
    apply_incremental, decode_incremental, decode_osdmap, diff_osdmap,
    encode_incremental, encode_osdmap)
from ceph_tpu.osd.op_queue import ClassInfo, MClockQueue, ShardedOpQueue
from ceph_tpu.osd.osdmap import OSDMap


# -- qos_db distribution ------------------------------------------------------

def test_osdmap_codec_carries_qos_db():
    m = OSDMap(epoch=3)
    m.set_max_osd(2)
    m.qos_db = {"gold": {"reservation": 100.0, "weight": 1.0,
                         "limit": 0.0}}
    got = decode_osdmap(encode_osdmap(m))
    assert got.qos_db == m.qos_db
    # copy() duplicates the db (mon _mutate mutates the copy)
    c = m.copy()
    c.qos_db["silver"] = {"reservation": 0, "weight": 2, "limit": 0}
    assert "silver" not in m.qos_db


def test_incremental_carries_qos_db():
    old = OSDMap(epoch=5)
    old.set_max_osd(2)
    new = old.copy()
    new.epoch = 6
    new.qos_db = {"gold": {"reservation": 50.0, "weight": 1.0,
                           "limit": 0.0}}
    inc = diff_osdmap(old, new)
    assert "qos_db" in inc
    dec = decode_incremental(encode_incremental(inc))
    m = old.copy()
    apply_incremental(m, dec)
    assert m.epoch == 6 and m.qos_db == new.qos_db
    # removal distributes too
    newer = new.copy()
    newer.epoch = 7
    newer.qos_db = {}
    inc2 = decode_incremental(encode_incremental(
        diff_osdmap(new, newer)))
    apply_incremental(m, inc2)
    assert m.qos_db == {}


def test_mon_qos_commands(monkeypatch=None):
    from ceph_tpu.tools.vstart import MiniCluster
    cluster = MiniCluster(n_osds=1, ms_type="loopback").start()
    try:
        cluster.wait_for_osd_count(1)
        client = cluster.client(timeout=15.0)
        rc, out = client.mon_command(
            {"prefix": "qos set", "tenant": "gold",
             "reservation": 100, "weight": 5, "limit": 200})
        assert rc == 0, out
        # validation: weight must be positive, res <= limit
        rc, out = client.mon_command(
            {"prefix": "qos set", "tenant": "bad", "weight": 0})
        assert rc == -22
        rc, out = client.mon_command(
            {"prefix": "qos set", "tenant": "bad",
             "reservation": 10, "weight": 1, "limit": 5})
        assert rc == -22
        rc, out = client.mon_command({"prefix": "qos ls"})
        assert rc == 0
        db = json.loads(out)
        assert db == {"gold": {"reservation": 100.0, "weight": 5.0,
                               "limit": 200.0}}
        # the OSD folds the db into its scheduler on map push
        deadline = time.time() + 10
        osd = cluster.osds[0]
        while time.time() < deadline \
                and "gold" not in osd._qos_profiles_applied:
            time.sleep(0.05)
        assert osd._qos_profiles_applied == db
        d = osd.ctx.admin.execute("dump_qos_stats")
        assert d["profiles"] == db and d["queue"] == "mclock"
        rc, out = client.mon_command({"prefix": "qos rm",
                                      "tenant": "gold"})
        assert rc == 0
        rc, out = client.mon_command({"prefix": "qos rm",
                                      "tenant": "gold"})
        assert rc == -2
        rc, out = client.mon_command({"prefix": "qos ls"})
        assert json.loads(out) == {}
    finally:
        cluster.stop()


# -- scheduler state hygiene --------------------------------------------------

def test_idle_tenant_lane_eviction_and_rollup():
    q = MClockQueue({"client": ClassInfo(weight=100.0)},
                    client_template=ClassInfo(weight=10.0),
                    idle_timeout=5.0)
    for i in range(40):
        q.enqueue(f"client.t{i}", i, now=0.0)
    while q.dequeue(now=1.0) is not None:
        pass
    assert sum(1 for n in q.dump_qos()["classes"]
               if n.startswith("client.")) == 40
    # quiet period passes: the sweep drops every idle dynamic lane and
    # folds its accounting into the rollup
    q.prune(now=100.0)
    d = q.dump_qos()
    assert not any(n.startswith("client.") for n in d["classes"])
    assert d["evicted"]["classes"] == 40
    assert d["evicted"]["enqueued"] == 40
    assert sum(d["evicted"]["served"].values()) == 40
    # static classes never evict
    assert "client" in d["classes"]
    # a busy lane is never evicted: backlogged or recently active
    q.enqueue("client.busy", 1, now=200.0)
    q.prune(now=201.0)
    assert q.exact_backlog("client.busy") == 1


def test_eviction_sweep_triggers_from_enqueue_volume():
    q = MClockQueue(client_template=ClassInfo(weight=1.0),
                    idle_timeout=0.5)
    # one-shot clients arriving over virtual time: the periodic sweep
    # (every 256 dynamic enqueues) must keep the table bounded without
    # anyone calling prune() explicitly
    for i in range(4000):
        now = i * 0.01
        q.enqueue(f"client.one{i}", i, now=now)
        got = q.dequeue(now=now)
        assert got is not None
    lanes = sum(1 for n in q.dump_qos()["classes"]
                if n.startswith("client."))
    assert lanes < 600, lanes


def test_group_backlog_accounting_is_exact():
    q = MClockQueue({"client": ClassInfo(weight=1.0),
                     "subop": ClassInfo(weight=1.0)})
    q.enqueue("client", "a", now=0.0)
    q.enqueue("client.t1", "b", now=0.0)
    q.enqueue("client.t1", "c", now=0.0)
    q.enqueue("client.t2", "d", now=0.0)
    q.enqueue("subop", "e", now=0.0)
    assert q.class_backlog("client") == 4
    assert q.class_backlog("client.t1") == 2
    assert q.exact_backlog("client.t1") == 2
    assert q.class_backlog("subop") == 1
    served = 0
    while q.dequeue(now=10.0) is not None:
        served += 1
    assert served == 5
    assert q.class_backlog("client") == 0
    assert q.exact_backlog("client.t1") == 0
    # eviction keeps the group counters consistent
    q.enqueue("client.t9", "x", now=20.0)
    assert q.class_backlog("client") == 1
    q.dequeue(now=20.0)
    q.prune(now=1000.0)
    assert q.class_backlog("client") == 0


def test_profile_change_retags_existing_backlog():
    """`ceph qos set` on a backlogged tenant applies to the queued
    ops, not just future ones: imposing a limit moves the queued
    requests behind it immediately."""
    q = MClockQueue({"other": ClassInfo(weight=1.0)},
                    client_template=ClassInfo(weight=100.0))
    for i in range(20):
        q.enqueue("client.t", i, now=0.0)
    q.enqueue("other", "o", now=0.0)
    # heavily weighted: the tenant would drain first at frozen now
    name, *_ = q.dequeue(now=0.0)
    assert name == "client.t"
    # cap the tenant hard: remaining backlog re-tags behind the limit
    q.set_client_profiles({"client.t": ClassInfo(weight=100.0,
                                                 limit=1.0)})
    order = [q.dequeue(now=0.0)[0] for _ in range(2)]
    assert order[0] == "other", order


def test_star_args_handler_receives_served():
    """A handler hiding its arity behind *args still gets the dmclock
    (phase, wait) tuple — no silent loss of phase data."""
    got = []
    wq = ShardedOpQueue(lambda *a: got.append(a), n_shards=1, name="t")
    try:
        wq.enqueue(1, "client", "x")
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.01)
        assert got and len(got[0]) == 3, got
        klass, item, (phase, wait) = got[0]
        assert klass == "client" and item == "x" and phase > 0
    finally:
        wq.shutdown()


def test_kwargs_handler_counts_as_two_positional():
    """`def h(klass, item, **kw)` must NOT be classified served-aware:
    calling it with a third positional would TypeError on every op and
    wedge the queue."""
    got = []

    def h(klass, item, **kw):
        got.append((klass, item))
    wq = ShardedOpQueue(h, n_shards=1, name="t")
    try:
        assert not wq._handler_takes_served
        wq.enqueue(1, "client", "x")
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.01)
        assert got == [("client", "x")], got
    finally:
        wq.shutdown()


def test_sharded_dump_merges_and_idle_timeout_reload():
    done = []
    wq = ShardedOpQueue(lambda k, i: done.append(i), n_shards=2,
                        name="t", client_template=ClassInfo(weight=1.0))
    try:
        for i in range(40):
            wq.enqueue(i, f"client.t{i % 4}", i)
        deadline = time.time() + 5
        while len(done) < 40 and time.time() < deadline:
            time.sleep(0.01)
        assert len(done) == 40
        d = wq.dump_qos()
        assert d["shards"] == 2
        total = sum(sum(r["served"].values())
                    for n, r in d["classes"].items()
                    if n.startswith("client."))
        assert total == 40
        wq.set_idle_timeout(123.0)
        assert all(q.idle_timeout == 123.0 for q, _cv in wq._shards)
    finally:
        wq.shutdown()


# -- report + exporter surfaces ----------------------------------------------

def test_mgr_report_qos_tail_roundtrip():
    from ceph_tpu.mgr.daemon import MMgrReport
    qos = {"lanes": {"client.gold": {
        "backlog": 2, "served": {"reservation": 10, "weight": 3,
                                 "limit": 0}, "wait_sum_s": 0.5}},
        "evicted": {"classes": 1, "enqueued": 7, "wait_sum_s": 0.1,
                    "served": {"reservation": 0, "weight": 7,
                               "limit": 0}}}
    m = MMgrReport(osd_id=3, qos=qos)
    enc = Encoder()
    m.encode_payload(enc)
    got = MMgrReport.__new__(MMgrReport)
    got.decode_payload(Decoder(enc.tobytes()), 0)
    assert got.qos == qos and got.osd_id == 3


def test_prometheus_qos_families():
    import sys
    sys.path.insert(0, "tests")
    from test_kernel_telemetry import parse_exposition
    from ceph_tpu.mgr.modules.prometheus import Module

    class _QosMgr:
        class _Map:
            max_osd = 1
            epoch = 1
            osd_weight = [0x10000]

            def is_up(self, o):
                return True

            def exists(self, o):
                return True

        osdmap = _Map()

        def get(self, name):
            return {
                "health": {"status": "HEALTH_OK"},
                "pg_summary": {},
                "df": {"total_objects": 0, "total_bytes_used": 0},
                "counters": {},
                "perf_reports": {},
                "qos_feed": {0: {
                    "lanes": {"client.gold": {
                        "backlog": 4,
                        "served": {"reservation": 11, "weight": 2,
                                   "limit": 1},
                        "wait_sum_s": 1.25}},
                    "evicted": {"classes": 3,
                                "served": {"reservation": 0,
                                           "weight": 40, "limit": 0},
                                "wait_sum_s": 2.5}}},
            }[name]

        def get_store(self, key, default=None):
            return default

    mod = Module.__new__(Module)
    mod.mgr = _QosMgr()
    fams = parse_exposition(mod.scrape_text())
    for fam, typ in (("ceph_qos_served_total", "counter"),
                     ("ceph_qos_backlog", "gauge"),
                     ("ceph_qos_wait_seconds_total", "counter"),
                     ("ceph_qos_evicted_lanes_total", "counter")):
        assert fam in fams and fams[fam]["type"] == typ, fam
    served = {(s[1]["qos_class"], s[1]["phase"]): s[2]
              for s in fams["ceph_qos_served_total"]["samples"]}
    assert served[("client.gold", "reservation")] == 11.0
    # the evicted rollup keeps one-shot tenants' service in the totals
    assert served[("evicted", "weight")] == 40.0
    waits = {s[1]["qos_class"]: s[2]
             for s in fams["ceph_qos_wait_seconds_total"]["samples"]}
    assert waits["evicted"] == 2.5
    backlog = fams["ceph_qos_backlog"]["samples"][0]
    assert backlog[1]["ceph_daemon"] == "osd.0" and backlog[2] == 4.0


def test_qos_wait_trace_event_explains_throttled_op():
    from ceph_tpu.common import tracing
    from ceph_tpu.tools.vstart import MiniCluster
    cluster = MiniCluster(n_osds=1, ms_type="loopback").start()
    try:
        cluster.wait_for_osd_count(1)
        client = cluster.client(timeout=15.0)
        pool = cluster.create_pool(client, pg_num=4, size=1)
        io = client.open_ioctx(pool)
        with tracing.trace_ctx(name="qos write",
                               daemon="client") as tid:
            io.write_full("traced-obj", b"payload")
        rows = tracing.dump(tid)
        waits = [r for r in rows if r.get("event") == "opq wait"]
        assert waits, rows
        # enqueue -> dequeue is a span with its interval; the class and
        # the dmclock phase that served the op are its attributes
        assert waits[0]["kind"] == "span" and waits[0]["dur"] >= 0
        assert waits[0]["layer"] == "OSD op queue"
        assert waits[0]["attrs"]["klass"].startswith("client")
        assert waits[0]["attrs"]["phase"] in ("reservation", "weight",
                                              "limit")
        # the handler runs under it
        assert any(r.get("parent_span_id") == waits[0]["span_id"]
                   and r["event"] == "osd op" for r in rows)
    finally:
        cluster.stop()


def test_service_delay_independent_dump_fields():
    """dump_qos_stats shape: wait/backlog/profile fields present and
    JSON-serializable (the admin-socket contract)."""
    wq = ShardedOpQueue(lambda k, i: None, n_shards=1, name="t",
                        client_template=ClassInfo(weight=1.0))
    try:
        wq.enqueue(1, "client.x", "a")
        time.sleep(0.2)
        d = wq.dump_qos()
        json.dumps(d)
        row = d["classes"]["client.x"]
        assert {"backlog", "enqueued", "served", "wait_sum_s",
                "wait_max_s", "profile", "dynamic"} <= set(row)
    finally:
        wq.shutdown()
