"""Span-structured tracing: hierarchy, wire extension v2, head
sampling + tail retention (exact counts), thread-safe OpTracker
timelines, MMgrReport v4, and the mgr insights/prometheus surface."""

from __future__ import annotations

import json
import threading
import time

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.op_tracker import OpTracker


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.reset()


# -- span model ---------------------------------------------------------------

def test_span_hierarchy_and_attrs():
    with tracing.trace_ctx(name="write", daemon="client.1") as tid:
        root_sid = tracing.current_span()
        with tracing.span("dispatch", daemon="osd.0", pool=3,
                          op_size=4096) as sp:
            assert sp.parent_span_id == root_sid
            with tracing.span("encode", daemon="osd.0") as inner:
                assert inner.parent_span_id == sp.span_id
            tracing.record("osd.0", "sub_op_commit")
    rows = tracing.dump(tid)
    spans = {r["span_id"]: r for r in rows if r["kind"] == "span"}
    assert len(spans) == 3
    roots = [r for r in spans.values() if not r["parent_span_id"]]
    assert len(roots) == 1 and roots[0]["event"] == "write"
    disp = next(r for r in spans.values() if r["event"] == "dispatch")
    assert disp["attrs"] == {"pool": 3, "op_size": 4096}
    assert disp["dur"] is not None and disp["dur"] >= 0
    # the point event attached to the span current when it fired
    ev = next(r for r in rows if r["kind"] == "event"
              and r["event"] == "sub_op_commit")
    assert ev["span_id"] == disp["span_id"]
    # nested tree view agrees
    tree = tracing.span_tree(tid)
    assert len(tree["spans"]) == 1
    top = tree["spans"][0]
    assert top["name"] == "write"
    assert [c["name"] for c in top["children"]] == ["dispatch"]
    assert [c["name"] for c in top["children"][0]["children"]] \
        == ["encode"]


def test_untraced_span_is_noop():
    assert tracing.current() == 0
    with tracing.span("nothing", daemon="x") as sp:
        assert sp is None
    assert tracing.trace_ids() == []


def test_frame_v2_span_extension_roundtrip():
    from ceph_tpu.messages import MOSDOp
    from ceph_tpu.msg.message import Message

    m = MOSDOp(client_id=7, tid=1, oid="spanned")
    m.trace_id = 0xBEEF
    m.parent_span_id = 0xCAFE
    back = Message.decode(m.encode())
    assert back.trace_id == 0xBEEF
    assert back.parent_span_id == 0xCAFE
    # no parent -> v1 bare-u64 extension (8 bytes shorter), old layout
    v1 = MOSDOp(client_id=7, tid=1, oid="spanned")
    v1.trace_id = 0xBEEF
    assert len(v1.encode()) == len(m.encode()) - 8
    b1 = Message.decode(v1.encode())
    assert b1.trace_id == 0xBEEF and b1.parent_span_id == 0
    # untraced stays byte-identical to the pre-tracing format
    plain = MOSDOp(client_id=7, tid=1, oid="spanned")
    assert Message.decode(plain.encode()).trace_id == 0


# -- sampling policy ----------------------------------------------------------

def test_head_sampling_exact_counts():
    tracing.set_sample_rate(0.0)
    assert not tracing.armed()
    for _ in range(20):
        with tracing.root("op", "client.9") as sp:
            assert sp is None and tracing.current() == 0
    assert tracing.trace_ids() == []
    tracing.set_sample_rate(1.0)
    assert tracing.armed()
    for _ in range(5):
        with tracing.root("op", "client.9") as sp:
            assert sp.trace_id != 0 and not sp.parent_span_id
            assert tracing.current() == sp.trace_id
    assert len(tracing.trace_ids()) == 5
    # joining an explicit trace never opens a second one
    with tracing.trace_ctx() as outer:
        with tracing.root("op", "client.9") as sp:
            assert sp.trace_id == outer and sp.parent_span_id
    assert len(tracing.trace_ids()) == 6
    # a cross-thread root (the client's aio path) samples the same way
    root = tracing.begin_root("osd_op x", "client.9")
    assert root is not None and tracing.current() == 0
    tracing.finish_root(root)
    tracing.set_sample_rate(0.0)
    assert tracing.begin_root("osd_op y", "client.9") is None
    assert len(tracing.trace_ids()) == 7


def test_tail_retention_slow_survives_fast_dropped():
    tracing.set_slow_threshold(0.05)
    tracing.set_active_cap(8)
    slow_ids = []
    for _ in range(2):
        with tracing.trace_ctx(name="slow write", daemon="t") as tid:
            time.sleep(0.06)
            slow_ids.append(tid)
    fast_ids = []
    for _ in range(32):
        with tracing.trace_ctx(name="fast", daemon="t") as tid:
            fast_ids.append(tid)
    # EXACTLY the slow traces were promoted, in completion order
    ring = tracing.slow_traces()
    assert [s["trace_id"] for s in ring] == slow_ids
    assert all(s["duration"] >= 0.05 and s["root"] == "slow write"
               for s in ring)
    # fast traces aged out of the bounded active table
    remaining = set(tracing.trace_ids())
    assert set(slow_ids) <= remaining
    assert sum(1 for t in fast_ids if t in remaining) <= 8
    # an evicted slow trace still renders (served from the ring)
    assert tracing.dump(slow_ids[0]), "slow trace lost its rows"
    # the ring itself is bounded
    tracing.set_slow_ring(1)
    assert [s["trace_id"] for s in tracing.slow_traces()] \
        == [slow_ids[1]]
    (kept,) = tracing.slow_traces()
    assert kept["duration"] >= 0.05


def test_evicted_slow_trace_not_shadowed_by_stragglers():
    """A straggler event after promotion+eviction must not resurrect
    an empty ghost that shadows the archived snapshot; the unfiltered
    dump keeps showing ring-only traces."""
    tracing.set_slow_threshold(0.0)
    tracing.set_active_cap(4)
    with tracing.trace_ctx(name="archived", daemon="t") as slow_tid:
        tracing.record("t", "real work")
    for _ in range(16):   # push the archived trace out of the table
        with tracing.trace_ctx(name="churn", daemon="t"):
            pass
    full = tracing.dump(slow_tid)
    assert any(r["event"] == "real work" for r in full)
    # straggler from a thread that still holds the id
    tracing.record("t", "late straggler", trace_id=slow_tid)
    after = tracing.dump(slow_tid)
    assert after == full, "ghost trace shadowed the archived snapshot"
    # the unfiltered view includes ring-only traces too
    assert any(r["trace_id"] == slow_tid for r in tracing.dump())


def test_root_attached_events_render_in_tree():
    with tracing.trace_ctx(name="rooted", daemon="t") as tid:
        pass
    # an event recorded OFF-THREAD (explicit trace id, current() != tid)
    # attaches to the trace root rather than vanishing from the tree
    assert tracing.current() == 0
    tracing.record("other", "off-thread", trace_id=tid)
    tree = tracing.span_tree(tid)
    all_events = []

    def walk(n):
        all_events.extend(e["event"] for e in n["events"])
        for ch in n["children"]:
            walk(ch)
    for root in tree["spans"]:
        walk(root)
    assert "off-thread" in all_events, tree


def test_inflight_trace_survives_churn_and_promotes():
    """Eviction under head-sampling load must prefer COMPLETED traces:
    an in-flight trace may still turn out slow, and dropping it would
    defeat tail retention exactly when it matters."""
    tracing.set_slow_threshold(0.05)
    tracing.set_active_cap(8)
    with tracing.trace_ctx(name="inflight slow", daemon="t") as slow_tid:
        time.sleep(0.06)
        for _ in range(64):   # way past the cap while we're open
            with tracing.trace_ctx(name="churn", daemon="t"):
                pass
    assert any(s["trace_id"] == slow_tid
               for s in tracing.slow_traces()), \
        "in-flight slow trace was evicted before completion"


def test_sampling_knobs_are_config_options():
    from ceph_tpu.common.context import CephTpuContext
    ctx = CephTpuContext("client.sampling")
    ctx.conf.set("tracing_sample_rate", "1.0")
    with tracing.root("op", "c") as sp:
        assert sp is not None
    ctx.conf.set("tracing_sample_rate", "0.0")
    with tracing.root("op", "c") as sp:
        assert sp is None
    ctx.conf.set("tracing_slow_threshold", "0.0")
    with tracing.trace_ctx(name="instant", daemon="c"):
        pass
    assert any(s["root"] == "instant" for s in tracing.slow_traces())


# -- satellite: OpTracker event-list thread safety ----------------------------

def test_tracked_op_events_thread_safe():
    trk = OpTracker(complaint_time=0.001, history_slow_threshold=0.0)
    op = trk.create_request("hammered op")
    errs: list[Exception] = []
    stop = threading.Event()

    def writer():
        try:
            while not stop.is_set():
                op.mark_event("tick")
        except Exception as e:   # pragma: no cover
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                d = op.dump()
                evs = d["type_data"]["events"]
                assert evs[0]["event"] == "initiated"
                trk.dump_ops_in_flight()
                trk.check_ops_in_flight()
        except Exception as e:   # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer),
               threading.Thread(target=reader),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errs, errs[0]
    op.finish()
    assert trk.slow_digests()
    d = trk.slow_digests()[0]
    assert d["description"] == "hammered op"
    assert d["last_event"] == "done"


# -- satellite: admin-socket consolidation ------------------------------------

def test_dump_tracing_alias_and_payload():
    from ceph_tpu.common.context import CephTpuContext
    ctx = CephTpuContext("osd.42")
    with tracing.trace_ctx(name="aliased", daemon="osd.42") as tid:
        tracing.record("osd.42", "probe")
    a = ctx.admin.execute("dump_tracing", trace_id=str(tid))
    b = ctx.admin.execute("dump_traces", trace_id=str(tid))
    assert a == b and a, "alias must serve the identical payload"
    assert all("span_id" in r for r in a), "span-structured rows"
    helps = ctx.admin.execute("help")
    assert "span-structured" in helps["dump_tracing"]
    assert helps["dump_traces"] == "alias for 'dump_tracing'"


# -- MMgrReport v4 ------------------------------------------------------------

def test_mgr_report_v4_roundtrip_and_defaults():
    from ceph_tpu.mgr import MMgrReport
    from ceph_tpu.msg.message import Message

    digest = [{"trace_id": 7, "root": "write", "daemon": "osd.0",
               "duration": 1.25, "completed_at": 123.0, "n_spans": 4,
               "rows": [{"trace_id": 7, "daemon": "osd.0",
                         "event": "write", "t": 121.75, "kind": "span",
                         "span_id": 9, "parent_span_id": 0,
                         "dur": 1.25}]}]
    ops = [{"daemon": "osd.0", "description": "osd_op(...)",
            "initiated_at": 120.0, "duration": 2.0,
            "last_event": "done"}]
    rep = MMgrReport(osd_id=3, counters={"op_w": 5},
                     slow_traces=digest, slow_ops=ops)
    back = Message.decode(rep.encode())
    assert back.osd_id == 3
    assert back.slow_traces == digest
    assert back.slow_ops == ops
    # a report without the tail decodes to empty defaults
    bare = Message.decode(MMgrReport(osd_id=1).encode())
    assert bare.slow_traces == [] and bare.slow_ops == []


# -- mgr health severities ----------------------------------------------------

def _bare_mgr():
    from ceph_tpu.mgr import MgrDaemon
    return MgrDaemon(mon_addr="", ms_type="loopback")


def test_mgr_health_err_on_majority_down_and_failed_module():
    mgr = _bare_mgr()
    m = mgr.osdmap
    m.set_max_osd(4)
    for o in range(4):
        m.mark_up(o)
    assert mgr.health()["status"] == "HEALTH_OK"
    m.mark_down(3)
    h = mgr.health()
    assert h["status"] == "HEALTH_WARN"
    osd_down = next(c for c in h["checks"] if c["check"] == "OSD_DOWN")
    assert osd_down["severity"] == "warn" and osd_down["osds"] == [3]
    m.mark_down(2)   # exactly half down is still WARN (strict majority)
    assert mgr.health()["status"] == "HEALTH_WARN"
    m.mark_down(1)   # 3 of 4: the majority is down
    h = mgr.health()
    assert h["status"] == "HEALTH_ERR"
    assert next(c for c in h["checks"]
                if c["check"] == "OSD_DOWN")["severity"] == "error"
    for o in (1, 2, 3):
        m.mark_up(o)
    mgr.host.failed["badmod"] = "ImportError('nope')"
    h = mgr.health()
    assert h["status"] == "HEALTH_ERR"
    assert next(c for c in h["checks"]
                if c["check"] == "MGR_MODULE_ERROR")["modules"] \
        == {"badmod": "ImportError('nope')"}
    # disabling the broken module is the remediation: unload clears
    # the record, health returns to OK
    mgr.host.unload("badmod")
    assert mgr.health()["status"] == "HEALTH_OK"


def test_prometheus_health_value_mapping():
    from ceph_tpu.mgr.modules.prometheus import Module
    assert Module.HEALTH_VALUES == {"HEALTH_OK": 0, "HEALTH_WARN": 1,
                                    "HEALTH_ERR": 2}


# -- cluster-wide aggregation through the mgr ---------------------------------

def test_insights_module_aggregates_slow_traces_and_ops():
    from ceph_tpu.tools.vstart import MiniCluster

    tracing.set_slow_threshold(0.0)   # every completed trace retained
    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    try:
        c.run_mgr()
        for oid in list(c.osds):       # osds re-report to the mgr
            c.kill_osd(oid)
            c.run_osd(oid)
        c.wait_for_osd_count(3)
        for d in c.osds.values():      # every completed op is "slow"
            d.op_tracker.history_slow_threshold = 0.0
        client = c.client(timeout=20.0)
        pool = c.create_pool(client, pg_num=1, size=3)
        io = client.open_ioctx(pool)
        io.write_full("warm", b"w" * 512)
        with tracing.trace_ctx(name="traced write",
                               daemon="client") as tid:
            io.write_full("slow-traced", b"S" * 4096)

        deadline = time.time() + 20
        mgr = c.mgr
        while time.time() < deadline:
            feed = mgr.insights_feed()
            if feed and any(e["slow_traces"] for e in feed.values()) \
                    and any(e["slow_ops"] for e in feed.values()):
                break
            time.sleep(0.2)

        out, rc = mgr._handle_command({"prefix": "tracing ls"})
        assert rc == 0, out
        ls = json.loads(out)["traces"]
        assert any(tr["trace_id"] == tid for tr in ls), ls
        out, rc = mgr._handle_command({"prefix": "tracing show",
                                       "trace_id": str(tid)})
        assert rc == 0, out
        shown = json.loads(out)
        assert shown["trace_id"] == tid
        names = set()

        def walk(nodes):
            for n in nodes:
                names.add(n["name"])
                walk(n["children"])
        walk(shown["tree"])
        assert "traced write" in names
        assert any(n.startswith("rx MOSDOp") for n in names), names
        out, rc = mgr._handle_command({"prefix": "slow_ops"})
        assert rc == 0, out
        ops = json.loads(out)["ops"]
        assert ops and all("duration" in o and "daemon" in o
                           for o in ops)
        # an unknown trace id is refused, not crashed on
        _out, rc = mgr._handle_command({"prefix": "tracing show",
                                        "trace_id": "12345"})
        assert rc == -2
        # prometheus exports the per-daemon slow-op counts
        body = mgr.prometheus_text()
        assert "ceph_daemon_slow_ops{" in body
        assert "ceph_daemon_slow_traces{" in body
    finally:
        c.stop()


# -- one clock, ids without a syscall ------------------------------------------

def test_spans_run_on_perf_counter_ns_and_ids_are_unique():
    tracing.set_slow_threshold(0.0)
    t_a = time.perf_counter_ns()
    with tracing.trace_ctx(name="b", daemon="bench") as tid:
        time.sleep(0.01)
    t_b = time.perf_counter_ns()
    (snap,) = tracing.slow_traces()
    assert snap["duration"] >= 0.01
    (row,) = [r for r in tracing.dump(tid) if r["kind"] == "span"]
    # the harness's clock: a reader can clip a span to its own stamps
    assert t_a <= row["start_ns"] < row["end_ns"] <= t_b
    assert row["dur"] == (row["end_ns"] - row["start_ns"]) / 1e9
    # `t` is wall-clock, for display, from the trace's one anchor
    assert abs(row["t"] - time.time()) < 5.0
    ids = {tracing.new_span_id() for _ in range(10000)}
    assert len(ids) == 10000 and 0 not in ids
    assert all(i < 1 << 63 for i in ids)        # rides the frame as u64
