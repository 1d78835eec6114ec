"""Pipeline phase profiler: per-batch where-did-the-time-go
attribution (ops.telemetry.PhaseStats + the ops.dispatch ledger), the
mapping service's epoch phase split, the exposition surfaces
(dump_pipeline_profile, prometheus phase/util/compile families, the
MMgrReport v4 profile carriage and the insights `profile` commands),
the profile_report renderer, and the tracing monotonic-clock fix."""

from __future__ import annotations

import json
import threading
import time
import unittest.mock as mock

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine

K1, M1, B1 = 4, 2, 64


def _jit_add():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x + 1
    return lambda b: f(jnp.asarray(b))


def _drive(engine, *, key=("ec_encode", 8), reqs=8, writers=2,
           stripes=8):
    """A short concurrent burst so the engine actually coalesces
    while busy (idle-flush would make every batch single-request)."""
    fn = _jit_add()
    op = np.ones((stripes, 8), dtype=np.uint8)
    start = threading.Barrier(writers + 1)
    errs: list = []

    def actor():
        start.wait()
        try:
            for _ in range(reqs):
                engine.submit(key, fn, op).result(timeout=60)
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=actor, daemon=True)
               for _ in range(writers)]
    for t in threads:
        t.start()
    start.wait()
    for t in threads:
        t.join()
    assert not errs, errs
    assert engine.flush(timeout=10)


# -- the ledger itself --------------------------------------------------------

def test_phase_sum_reconstructs_end_to_end_latency():
    """The acceptance pin: on a busy engine every flushed batch's
    named phases sum to (>= 95% of) its submit->delivery wall-clock —
    the ledger is contiguous by construction, so the sum matches to
    float noise, not just the 95% floor."""
    stats = telemetry.DispatchStats()
    eng = DeviceDispatchEngine(name="prof-e2e", stats=stats)
    try:
        _drive(eng, reqs=10, writers=3)
    finally:
        eng.stop()
    recent = stats.phases.dump()["recent"]
    assert len(recent) >= 3, recent
    for rec in recent:
        total = sum(rec["phases"].values())
        assert total >= 0.95 * rec["e2e_s"], rec
        assert total <= rec["e2e_s"] * 1.01 + 1e-6, rec
        assert set(rec["phases"]) == set(telemetry.PHASES)
    # the burst coalesced at least once (busy-engine precondition)
    assert any(r["requests"] > 1 for r in recent), recent


def test_compile_cost_separate_from_steady_state():
    """First-call batches (jit trace+compile) land in the compile
    ledger; the steady-state launch/compute histograms only sample
    post-compile batches."""
    stats = telemetry.DispatchStats()
    eng = DeviceDispatchEngine(name="prof-compile", stats=stats)
    import jax

    @jax.jit
    def f(x):
        return x + 1
    import jax.numpy as jnp
    op = np.ones((8, 8), dtype=np.uint8)
    try:
        for _ in range(4):   # serial: every flush is one request,
            eng.submit(("k", 8), lambda b: f(jnp.asarray(b)),
                       op).result(timeout=60)   # same bucket each time
    finally:
        eng.stop()
    d = stats.phases.dump()
    assert d["compile"]["k"]["events"] == 1, d["compile"]
    assert d["compile"]["k"]["seconds"] > 0.0
    # 4 batches total, 1 compiled: launch/compute sampled 3 times,
    # the always-steady phases 4 times
    fam = d["phases"]["k"]
    assert fam["launch"]["count"] == 3, fam["launch"]
    assert fam["compute"]["count"] == 3
    assert fam["queue_wait"]["count"] == 4
    recs = d["recent"]
    assert [r["compiled"] for r in recs] == [True, False, False, False]


def test_phase_stats_unit_busy_imbalance_and_ring():
    """Direct PhaseStats math: busy-seconds integral scales with
    devices, shard imbalance is the padded-lane share, the ring is
    bounded, and clear() re-arms first-call detection."""
    ps = telemetry.PhaseStats("unit")
    phases = {ph: 0.0 for ph in telemetry.PHASES}
    phases["compute"] = 0.5
    ps.record_batch("ec_encode", phases=phases, e2e_s=0.5, requests=3,
                    stripes=5, bucket=8, devices=4, misses=0)
    d = ps.dump()
    assert d["busy_seconds"] == pytest.approx(2.0)   # 0.5 s x 4 dev
    assert d["devices_seen"] == 4
    assert d["last_shard_imbalance"] == pytest.approx(1 - 5 / 8)
    assert d["shard_imbalance"]["count"] == 1
    assert 0.0 <= ps.utilization() <= 1.0
    # misses=0 says "probed, no retrace": no compile charged
    assert d["compile"] == {}
    # misses=None falls back to first-(family,bucket,devices) detection
    ps.record_batch("crush_rule", phases=phases, e2e_s=0.5, requests=1,
                    stripes=8, bucket=8, devices=1, misses=None)
    assert ps.dump()["compile"]["crush_rule"]["events"] == 1
    ps.record_batch("crush_rule", phases=phases, e2e_s=0.5, requests=1,
                    stripes=8, bucket=8, devices=1, misses=None)
    assert ps.dump()["compile"]["crush_rule"]["events"] == 1  # seen
    ps.clear()
    assert ps.dump()["recent"] == []
    ps.record_batch("crush_rule", phases=phases, e2e_s=0.5, requests=1,
                    stripes=8, bucket=8, devices=1, misses=None)
    assert ps.dump()["compile"]["crush_rule"]["events"] == 1  # re-armed


def test_profile_ring_knob_is_a_config_option():
    from ceph_tpu.common.context import CephTpuContext

    st = telemetry.dispatch_stats()
    try:
        ctx = CephTpuContext("client.profring")
        ctx.conf.set("kernel_profile_ring", "4")
        assert st.phases.records.maxlen == 4
        phases = {ph: 0.0 for ph in telemetry.PHASES}
        for i in range(9):
            st.phases.record_batch("k", phases=phases, e2e_s=0.0,
                                   requests=1, stripes=1, bucket=1,
                                   devices=1, misses=0)
        assert len(st.phases.dump()["recent"]) == 4
    finally:
        telemetry.set_profile_ring(telemetry.PROFILE_RING_DEFAULT)
        telemetry.reset()


# -- mapping epoch phase split ------------------------------------------------

def _small_map(epoch=2, pools=2, pg_num=32):
    from ceph_tpu.crush import build_two_level_map
    from ceph_tpu.osd import OSDMap, PGPool

    crush, _root, rule = build_two_level_map(4, 2)
    m = OSDMap(crush=crush, epoch=epoch)
    m.set_max_osd(8)
    for o in range(8):
        m.mark_up(o)
    for p in range(1, pools + 1):
        m.pools[p] = PGPool(pool_id=p, size=3, crush_rule=rule,
                            pg_num=pg_num)
    return m


def test_mapping_service_phase_split_live():
    """A live service's computed epochs split into device vs delta vs
    host-tail phases, readable from dump_mapping_stats — and the PR 10
    fused ladder COLLAPSES the host tail: the default (fused) service
    records zero host-tail seconds while an unfused twin of the same
    churn still pays it."""
    from ceph_tpu.osd import SharedPGMappingService

    def churn(svc, m):
        svc.update_to(m)
        for i in range(3):
            new = m.copy()
            new.epoch = m.epoch + 1
            new.osd_weight[i % 8] = 0x8000 if i % 2 == 0 else 0x10000
            upd = svc.update_to(new)
            assert not upd.full
            m = new

    telemetry.reset()
    churn(SharedPGMappingService(), _small_map())
    d = telemetry.mapping_dump()
    ph = d["phase_seconds"]
    assert set(ph) == {"device", "delta", "host_tail"}
    assert ph["device"]["count"] == 4          # first map + 3 epochs
    assert ph["device"]["sum"] > 0.0
    # the 3 churn epochs diffed fused outputs on device: the candidate
    # pass still costs delta time, the host tail contributes NOTHING
    assert ph["delta"]["sum"] > 0.0
    assert ph["host_tail"]["sum"] == 0.0
    assert d["host_tail_share"] == 0.0
    assert d["fused_epochs"] == 4
    assert d["unfused_epochs"] == 0
    summ = telemetry.mapping_stats().phase_summary()
    assert summ["epochs"] == 4
    assert summ["fused_epochs"] == 4
    assert sum(summ["share"].values()) == pytest.approx(1.0, abs=0.01)
    # the unfused twin (knob off) pays the per-candidate host tail
    telemetry.reset()
    churn(SharedPGMappingService(fused=False), _small_map())
    d = telemetry.mapping_dump()
    assert d["phase_seconds"]["host_tail"]["sum"] > 0.0
    assert d["host_tail_share"] > 0.0
    assert d["fused_epochs"] == 0
    assert d["unfused_epochs"] == 4
    telemetry.reset()


# -- admin socket -------------------------------------------------------------

def test_dump_pipeline_profile_admin_roundtrip():
    """The admin command serves the full profile — and, in this 8-dev
    test env, the context engine's mesh fan-out shows up in the
    utilization story."""
    from ceph_tpu.common.context import CephTpuContext

    telemetry.reset()
    ctx = CephTpuContext("prof-admin")
    eng = ctx.dispatch_engine()
    try:
        _drive(eng, reqs=4, writers=2)
        out = ctx.admin.execute("dump_pipeline_profile")
        assert set(out) == {"encode", "decode", "mapping"}
        enc = out["encode"]
        assert enc["recent"], enc
        fam = enc["phases"]["ec_encode"]
        assert set(telemetry.PHASES) >= set(fam)
        assert enc["busy_seconds"] > 0.0
        import jax
        if len(jax.devices()) > 1:
            assert enc["devices_seen"] > 1
            assert enc["shard_imbalance"]["count"] >= 1
        # payload is JSON-serializable end to end (the socket wire)
        json.dumps(out)
        # mapping split rides along
        assert set(out["mapping"]["seconds"]) == {"device", "delta",
                                                  "host_tail"}
    finally:
        eng.stop()
        telemetry.reset()


# -- prometheus families ------------------------------------------------------

def test_prometheus_phase_util_compile_families():
    from test_kernel_telemetry import _scrape, parse_exposition

    telemetry.reset()
    stats = telemetry.dispatch_stats()
    eng = DeviceDispatchEngine(name="prof-prom", stats=stats)
    try:
        _drive(eng, reqs=4, writers=2)
    finally:
        eng.stop()
    telemetry.mapping_stats().record_phases(
        device_s=0.01, delta_s=0.002, host_tail_s=0.001)
    fams = parse_exposition(_scrape())
    telemetry.reset()
    for want, typ in (
            ("ceph_kernel_phase_seconds", "histogram"),
            ("ceph_kernel_compile_seconds_total", "counter"),
            ("ceph_kernel_compile_events_total", "counter"),
            ("ceph_kernel_util_busy_seconds_total", "counter"),
            ("ceph_kernel_util_utilization", "gauge"),
            ("ceph_kernel_util_devices", "gauge"),
            ("ceph_kernel_util_shard_imbalance", "histogram"),
            ("ceph_kernel_mapping_phase_seconds", "histogram")):
        assert want in fams, (want, sorted(fams))
        assert fams[want]["type"] == typ, (want, fams[want]["type"])
    phase_labels = {(s[1].get("engine"), s[1].get("kernel"),
                     s[1].get("phase"))
                    for s in fams["ceph_kernel_phase_seconds"]["samples"]}
    assert ("encode", "ec_encode", "queue_wait") in phase_labels
    mapping_phases = {s[1].get("phase") for s in
                      fams["ceph_kernel_mapping_phase_seconds"]["samples"]}
    assert mapping_phases == {"device", "delta", "host_tail"}
    # utilization gauge is a sane fraction for both engines
    for _n, lab, v in fams["ceph_kernel_util_utilization"]["samples"]:
        assert lab["engine"] in ("encode", "decode")
        assert 0.0 <= v <= 1.0


# -- insights: cluster-wide merge ---------------------------------------------

def _digest(qw, comp, osd_busy, events=1):
    return {
        "encode": {"kernels": {"ec_encode": {
            "seconds": {"queue_wait": qw, "compute": comp},
            "share": {}, "batches": 5}},
            "compile": {"ec_encode": {"seconds": 0.25,
                                      "events": events}},
            "busy_seconds": osd_busy, "utilization": 0.5,
            "devices_seen": 8, "last_shard_imbalance": 0.1},
        "decode": {"kernels": {}, "compile": {}, "busy_seconds": 0.0,
                   "utilization": 0.0, "devices_seen": 1,
                   "last_shard_imbalance": 0.0},
        "mapping": {"seconds": {"device": 0.2, "delta": 0.05,
                                "host_tail": 0.01},
                    "share": {}, "epochs": 3},
    }


class _FeedMgr:
    def __init__(self, feed):
        self._feed = feed

    def get(self, name):
        assert name == "insights_feed"
        return self._feed


def test_insights_profile_merges_two_daemons_unit():
    """The merge math, pinned: seconds SUM across daemons, shares
    recomputed over merged totals, compile/mapping ledgers add up,
    and `profile top` ranks the cluster-wide stall first."""
    from ceph_tpu.mgr.modules.insights import Module

    feed = {0: {"profile": _digest(1.0, 3.0, 10.0), "slow_traces": [],
                "slow_ops": [], "stamp": 1.0},
            1: {"profile": _digest(2.0, 6.0, 20.0, events=2),
                "slow_traces": [], "slow_ops": [], "stamp": 1.0}}
    mod = Module(_FeedMgr(feed))
    merged = mod.profile_phases()
    row = merged["engines"]["encode"]["ec_encode"]
    assert row["seconds"]["queue_wait"] == pytest.approx(3.0)
    assert row["seconds"]["compute"] == pytest.approx(9.0)
    assert row["share"]["compute"] == pytest.approx(0.75)
    assert row["reported_by"] == [0, 1]
    assert row["batches"] == 10
    comp = merged["compile"]["encode"]["ec_encode"]
    assert comp == {"seconds": pytest.approx(0.5), "events": 3,
                    "reported_by": [0, 1]}
    assert merged["mapping"]["seconds"]["device"] == pytest.approx(0.4)
    assert merged["mapping"]["epochs"] == 6
    assert set(merged["utilization"]["encode"]) == {"osd.0", "osd.1"}
    top = mod.profile_top(3)
    assert top[0]["kernel"] == "ec_encode"
    assert top[0]["phase"] == "compute"
    assert top[0]["seconds"] == pytest.approx(9.0)
    # compile ranks as its own phase row
    assert any(r["phase"] == "compile" for r in mod.profile_top(20))
    # command tier round-trips JSON
    out, rc = mod.handle_command({"prefix": "profile top", "limit": 2})
    assert rc == 0
    assert len(json.loads(out)["stalls"]) == 2
    out, rc = mod.handle_command({"prefix": "profile phases"})
    assert rc == 0
    assert "engines" in json.loads(out)


def test_insights_profile_dedups_shared_registry_digests():
    """In-process daemons all ship the SAME process-global digest —
    the merge must count it once (every reporter listed), not inflate
    cluster totals by the daemon count."""
    from ceph_tpu.mgr.modules.insights import Module

    same = _digest(1.0, 3.0, 10.0)
    feed = {0: {"profile": same, "stamp": 1.0},
            1: {"profile": json.loads(json.dumps(same)), "stamp": 2.0},
            2: {"profile": _digest(5.0, 0.5, 1.0), "stamp": 3.0}}
    merged = Module(_FeedMgr(feed)).profile_phases()
    row = merged["engines"]["encode"]["ec_encode"]
    # osd 0+1 share one registry (identical digest): one contribution
    assert row["seconds"]["queue_wait"] == pytest.approx(1.0 + 5.0)
    assert row["seconds"]["compute"] == pytest.approx(3.0 + 0.5)
    assert sorted(row["reported_by"]) == [0, 1, 2]
    assert merged["mapping"]["epochs"] == 6     # 3 + 3, not 9
    assert set(merged["utilization"]["encode"]) == {"osd.0", "osd.1",
                                                    "osd.2"}


def test_insights_profile_top_e2e_two_daemons():
    """e2e: two OSDs ship pipeline-profile digests in MMgrReport v4
    and the mgr's `profile top` serves the cluster-wide merge."""
    from ceph_tpu.tools.vstart import MiniCluster

    telemetry.reset()
    c = MiniCluster(n_osds=2, ms_type="loopback").start()
    try:
        c.run_mgr()
        for oid in list(c.osds):       # osds re-report to the mgr
            c.kill_osd(oid)
            c.run_osd(oid)
        c.wait_for_osd_count(2)
        # engine traffic lands in the process-global profiler every
        # daemon's report reads (the in-process MiniCluster shares it)
        eng = DeviceDispatchEngine(name="prof-e2e-feed",
                                   stats=telemetry.dispatch_stats())
        try:
            _drive(eng, reqs=3, writers=2)
        finally:
            eng.stop()
        deadline = time.time() + 30
        mgr = c.mgr
        while time.time() < deadline:
            feed = mgr.insights_feed()
            ready = [o for o, e in feed.items()
                     if (e.get("profile") or {}).get(
                         "encode", {}).get("kernels")]
            if len(ready) >= 2:
                break
            time.sleep(0.2)
        assert len(ready) >= 2, feed.keys()
        out, rc = mgr._handle_command({"prefix": "profile top"})
        assert rc == 0, out
        stalls = json.loads(out)["stalls"]
        assert stalls, out
        enc = [r for r in stalls if r["kernel"] == "ec_encode"]
        assert enc, stalls
        # the merge really folded BOTH daemons' feeds
        assert sorted(enc[0]["reported_by"]) == sorted(ready)[:2] \
            or len(enc[0]["reported_by"]) >= 2
        out, rc = mgr._handle_command({"prefix": "profile phases"})
        assert rc == 0, out
        merged = json.loads(out)
        assert "ec_encode" in merged["engines"]["encode"]
    finally:
        c.stop()
        telemetry.reset()


# -- tracing: async batches re-join traces with phase spans --------------------

def test_async_dispatch_span_carries_phase_spans():
    """tracing show on an async submit explains its latency: the
    request span carries the batch's phases as child spans with their
    real intervals (every phase that had one: without a mesh nothing
    is placed), each with the CPU time of the engine thread that ran
    it, and the operand/result bytes as attributes."""
    tracing.reset()
    stats = telemetry.DispatchStats()
    eng = DeviceDispatchEngine(name="prof-span", stats=stats)
    import jax

    @jax.jit
    def f(x):
        return x + 1
    import jax.numpy as jnp
    try:
        with tracing.trace_ctx(name="traced ec write",
                               daemon="client") as tid:
            eng.submit(("ec_encode", 8),
                       lambda b: f(jnp.asarray(b)),
                       np.ones((8, 8), np.uint8)).result(timeout=60)
        eng.flush(timeout=10)
    finally:
        eng.stop()
    rows = tracing.dump(tid)
    dev = [r for r in rows if r.get("kind") == "span"
           and r["event"].startswith("device ")]
    assert dev, rows
    span_id = dev[0]["span_id"]
    kids = [r for r in rows
            if r.get("kind") == "span" and r["parent_span_id"] == span_id]
    for name in ("queue_wait", "build", "launch", "compute",
                 "materialize", "deliver"):
        (phase,) = [r for r in kids if r["event"] == f"engine {name}"]
        assert phase["dur"] is not None and phase["dur"] > 0
        assert dev[0]["start_ns"] <= phase["start_ns"] \
            <= phase["end_ns"] <= dev[0]["end_ns"]
        assert ("cpu_ns" in phase) == (name != "queue_wait")
    assert len(kids) == 6
    assert dev[0]["attrs"]["h2d_bytes"] == 64
    assert dev[0]["attrs"]["d2h_bytes"] == 64
    # the phases account for the request: queue wait through deliver
    assert sum(r["dur"] for r in kids) == pytest.approx(dev[0]["dur"],
                                                        abs=2e-4)
    # the ledger the benchmark reads keeps its histograms
    (rec,) = stats.phases.dump()["recent"]
    assert rec["kernel"] == "ec_encode" and set(rec["phases"]) \
        == {r["event"].split()[1] for r in kids} | {"place"}
    tracing.reset()


# -- tracing: monotonic duration math -----------------------------------------

def test_wall_clock_step_cannot_skew_durations():
    """An NTP step (wall clock jumping backwards mid-span) must not
    produce negative durations or mis-rank tail sampling: a span's
    start and end are perf_counter_ns readings, wall time is read once
    per trace and is display-only."""
    tracing.reset()
    tracing.set_slow_threshold(0.0)
    base = time.time()
    try:
        with mock.patch("time.time", lambda: base):
            with tracing.trace_ctx(name="ntp victim",
                                   daemon="t") as tid:
                sp = tracing.begin_span("inner", "t")
                time.sleep(0.02)
                # the step: wall clock falls an hour mid-span
                with mock.patch("time.time", lambda: base - 3600.0):
                    tracing.finish_span(sp)
        assert sp.duration is not None and sp.duration >= 0.02, \
            sp.duration
        # the completed trace promoted with a sane (>= 0) duration
        snap = [s for s in tracing.slow_traces()
                if s["trace_id"] == tid]
        assert snap and snap[0]["duration"] >= 0.0, snap
        # the dumped row's dur is the monotonic one, and its display
        # time hangs off the trace's one wall reading: the step never
        # reaches it
        row = [r for r in tracing.dump(tid)
               if r.get("span_id") == sp.span_id
               and r.get("kind") == "span"][0]
        assert row["dur"] >= 0.02
        assert base <= row["t"] < base + 1.0
    finally:
        tracing.reset()


def test_message_hop_span_ends_at_the_receiver():
    """stamp() opens the message's hop span; the sender closes it when
    the bytes are written, a receiver in the same process closes it
    again at dispatch — and only ever later."""
    from ceph_tpu.messages import MOSDOp
    tracing.reset()
    msg = MOSDOp(client_id=7, tid=1, oid="hop")
    with tracing.trace_ctx(name="root", daemon="t") as tid:
        tracing.stamp(msg, "client.7")
        hop = tracing.find_span(tid, msg.parent_span_id)
        assert hop.name == "msg MOSDOp" and hop.end is None
        time.sleep(0.005)
        tracing.sent(msg)
        wrote = hop.end
        assert hop.duration >= 0.005
        time.sleep(0.005)
        tracing.received(tid, msg.parent_span_id)
        assert hop.end > wrote and hop.duration >= 0.01
        tracing.sent(msg)                   # a late writer mark
        assert hop.duration >= 0.01
        # a resend keeps the ids and opens no second hop
        tracing.stamp(msg, "client.7")
    assert [r["event"] for r in tracing.dump(tid)
            if r["kind"] == "span"] == ["root", "msg MOSDOp"]
    tracing.reset()


# -- the report renderer ------------------------------------------------------

def test_profile_report_renders_all_input_shapes():
    from ceph_tpu.tools.profile_report import normalize, render

    telemetry.reset()
    stats = telemetry.dispatch_stats()
    eng = DeviceDispatchEngine(name="prof-render", stats=stats)
    try:
        _drive(eng, reqs=3, writers=2)
    finally:
        eng.stop()
    telemetry.mapping_stats().record_phases(
        device_s=0.01, delta_s=0.002, host_tail_s=0.001)
    dump = telemetry.pipeline_profile_dump()
    digest = telemetry.pipeline_profile_digest()
    telemetry.reset()
    for doc in (dump, digest, {"profile": digest, "metric": "x"}):
        n = normalize(doc)
        assert "ec_encode" in n["engines"]["encode"], doc.keys()
        text = render(doc)
        assert "ec_encode" in text
        assert "queue_wait" in text
        assert "compile ledger" in text
        assert "mapping epochs" in text
    # the insights merged shape renders too
    from ceph_tpu.mgr.modules.insights import Module
    mod = Module(_FeedMgr({0: {"profile": digest, "stamp": 1.0}}))
    text = render(mod.profile_phases())
    assert "ec_encode" in text
