"""The off-CPU and hop-section arithmetic of
perfbench/harness/offcpu_readers.py on hand-made span rows, and a
toy-size traced run of each EC cell that reports all seven metrics."""

import json
import types

import pytest

from perfbench.harness import manifest
from perfbench.harness import offcpu_readers as oc
from test_perfbench_degraded import READ, WRITE, run_within_limit
from test_perfbench_run import (LAT, compile_cache,  # noqa: F401
                                on_cpu, run)

MS = 1_000_000
T1, T2 = 101, 202
ECB, ENGINE, STORE, MSGR = ("PG / EC backend", "dispatch engine",
                            "objectstore", "messenger")
NEW = ("ecb.op_offcpu_ms.t1", "engine.op_offcpu_ms.t1",
       "store.op_offcpu_ms.t1", "trace.op_offcpu_share.t1",
       "msgr.hop_send_ms.t1", "msgr.hop_reader_ms.t1",
       "msgr.hop_dispatch_ms.t1")


def span(sid, parent, name, a, b, layer="", cpu=None, thread=None, **attrs):
    row = {"kind": "span", "span_id": sid, "parent_span_id": parent,
           "event": name, "start_ns": int(a * MS), "end_ns": int(b * MS),
           "layer": layer}
    if cpu is not None:
        row.update(cpu_ns=int(cpu * MS), thread=thread)
    if attrs:
        row["attrs"] = attrs
    return row


def hop(sid, parent, name, a, b, sent, framed, first=None, dequeued=None):
    us = {"sent_us": int(sent * 1000), "road": "inline"}
    if framed is not None:
        us.update(first_byte_us=int((first or 0) * 1000),
                  framed_us=int(framed * 1000),
                  dequeued_us=int((dequeued or framed) * 1000))
    return span(sid, parent, name, a, b, MSGR, **us)


def write_rows(t0=0.0):
    """An op of 40 ms.  The primary's continuation (thread T1, 30 ms, 9
    of them on the CPU) holds a lock wait of 2 ms, a commit of 10 ms
    that runs a digest request on its own thread, and a fan-out of
    8 ms that ran for 1; the slowest sub-write runs on T2."""
    def s(sid, parent, name, a, b, *rest, **kw):
        return span(sid, parent, name, t0 + a, t0 + b, *rest, **kw)

    return [
        s(1, 0, "osd_op x", 0, 40),
        hop(2, 1, "msg MOSDOp", t0 + 0, t0 + 4, sent=1, framed=3, first=0.5,
            dequeued=3.5),
        s(3, 2, "ec continuation", 4, 34, ECB, 9, T1),
        s(4, 3, "ec daemon lock wait", 4, 6, ECB, 0.1, T1, wait=True),
        s(5, 3, "bluestore commit", 6, 16, STORE, 3, T1),
        s(6, 5, "bluestore fsync", 6, 9, STORE, 0.2, T1, wait=True),
        # the request crosses no thread here, but its span is the
        # engine's own and carries no CPU time: its phases do
        s(7, 5, "device bluestore_data", 10, 15, ENGINE),
        s(8, 7, "engine launch", 10, 12, ENGINE, 0.5, T1),
        s(9, 7, "engine compute", 12, 14, ENGINE, 0.0, T1,
          device_wait=True),
        s(10, 7, "engine materialize", 14, 15, ENGINE, 1.5, T1),
        s(11, 3, "ec fan-out", 16, 24, ECB, 1, T1),
        hop(12, 11, "msg MOSDECSubOpWrite", t0 + 17, t0 + 26, sent=6,
            framed=5, first=1, dequeued=8),
        # another thread's span under the fan-out: not the fan-out's
        s(13, 12, "ec sub-write", 26, 33, ECB, 6.5, T2),
        # nothing stamped this hop (a receiver in another process)
        hop(14, 13, "msg MOSDOpReply", t0 + 33, t0 + 38, sent=1,
            framed=None),
        s(15, 14, "client complete", 38, 40, "client", 2.5, T2),
    ]


def ms(d):
    return {k: v / MS for k, v in d.items()}


def test_own_time_is_the_spans_less_what_nests_in_it_on_its_thread():
    rows = write_rows()
    by_id = {r["span_id"]: r for r in rows}
    children = {}
    for r in rows:
        children.setdefault(r["parent_span_id"], []).append(r)
    # the continuation: 30 ms less lock wait 2, commit 10, fan-out 8 =
    # 10 of its own, CPU 9 - 0.1 - 3 - 1 = 4.9
    assert oc.own_offcpu_ns(by_id[3], children) / MS == pytest.approx(5.1)
    # the commit: 10 ms less fsync 3 and the request's three phases
    # (found through the `device` span) 5 = 2 of its own; CPU 3 - 0.2 -
    # 0.5 - 0 - 1.5 = 0.8
    assert sorted(r["span_id"] for r in oc._nested(by_id[5], children)) \
        == [6, 8, 9, 10]
    assert oc.own_offcpu_ns(by_id[5], children) / MS == pytest.approx(1.2)
    # the fan-out: the sub-write under it ran on another thread
    assert oc._nested(by_id[11], children) == []
    assert oc.own_offcpu_ns(by_id[11], children) / MS == pytest.approx(7.0)
    # a coarse clock that ticked inside a short span reads more CPU
    # than interval: negative, so that the errors cancel in the sums
    assert oc.own_offcpu_ns(by_id[10], children) / MS == -0.5
    assert oc.own_offcpu_ns(by_id[15], children) / MS == -0.5


def test_offcpu_by_layer_leaves_waits_out():
    got = ms(oc.offcpu_by_layer(write_rows()))
    # backend: continuation 5.1 + fan-out 7.0 + sub-write 0.5; the lock
    # wait's 1.9 is a wait by design
    assert got[ECB] == pytest.approx(12.6)
    # store: the commit's own 1.2; fsync's 2.8 is a wait by design
    assert got[STORE] == pytest.approx(1.2)
    # engine: launch 1.5, materialize -0.5; compute is the device's
    assert got[ENGINE] == pytest.approx(1.0)
    assert got["client"] == -0.5 and got["root"] == 40
    assert MSGR not in got      # a hop has no thread to ask


def test_rows_without_cpu_time_give_nothing():
    rows = write_rows()
    for r in rows:
        r.pop("cpu_ns", None)
        r.pop("thread", None)
    assert oc.offcpu_by_layer(rows) is None
    for r in rows:
        (r.get("attrs") or {}).pop("framed_us", None)
    assert oc.hops_of_path(rows) is None
    assert oc.hops_of_path(write_rows()) is not None


def test_the_three_sections_add_up_to_the_hop():
    rows = write_rows()
    by_id = {r["span_id"]: r for r in rows}
    # sent at 1, whole at 3, dispatched at 4
    assert [x / MS for x in oc.hop_sections(by_id[2])] == [1, 2, 1]
    # the reader had the frame whole (5) before its sender's thread
    # noted the write (6): the send section ends there, none is
    # counted twice
    assert [x / MS for x in oc.hop_sections(by_id[12])] == [5, 0, 4]
    assert oc.hop_sections(by_id[14]) is None
    got = ms(oc.hops_of_path(rows))
    assert got == {"send": 6, "reader": 2, "dispatch": 5, "hops": 13}
    assert got["send"] + got["reader"] + got["dispatch"] == (4 - 0) + (26 - 17)


def test_readers_take_the_mean_over_the_slices_clocked_roots(monkeypatch):
    traces = [write_rows(t0) for t0 in (100.0, 200.0, 300.0, 340.0)]
    # a clock that steps by 10 ms: in the second op it ticked inside the
    # fan-out, in the third never; the three average to what it was
    cpu = {1: 11, 2: 0}
    for i, ms_cpu in cpu.items():
        fan = next(r for r in traces[i] if r["event"] == "ec fan-out")
        cont = next(r for r in traces[i]
                    if r["event"] == "ec continuation")
        cont["cpu_ns"] += (ms_cpu - 1) * MS
        fan["cpu_ns"] = ms_cpu * MS
    # an op that the program did not clock: hops stamped, no CPU time
    for row in traces[3]:
        row.pop("cpu_ns", None)
        row.pop("thread", None)
    # an op that began before the slice, an epoch: not read
    traces += [write_rows(50.0),
               [span(1, 0, "update_to", 150, 160, "", 1, T1)]]
    monkeypatch.setattr(oc, "completed_traces", lambda: traces)
    r = types.SimpleNamespace(slice_t=(0.09, 0.39))
    by_root = [p[ECB] / MS for p in oc.offcpu_of(r).offcpu]
    assert by_root == pytest.approx([12.6, 2.6, 13.6])
    assert oc.ecb_offcpu_ms(r) == pytest.approx(9.6)
    assert oc.store_offcpu_ms(r) == pytest.approx(1.2)
    assert oc.engine_offcpu_ms(r) == pytest.approx(1.0)
    assert oc.offcpu_share(r) == pytest.approx(100 * (9.6 + 1.2 + 1.0 - 0.5)
                                               / 40)
    assert (oc.hop_send_ms(r), oc.hop_reader_ms(r), oc.hop_dispatch_ms(r)) \
        == (6, 2, 5)
    assert len(oc.offcpu_of(r).offcpu) == 3 and len(oc.offcpu_of(r).hops) == 4
    # a slice so short that no clocked op lies in it whole: the clocked
    # op that reaches into it stands in (its hops need no stand-in)
    short = types.SimpleNamespace(slice_t=(0.335, 0.385))
    assert [p["root"] / MS for p in oc.offcpu_of(short).offcpu] == [40]
    assert oc.ecb_offcpu_ms(short) == pytest.approx(13.6)
    assert len(oc.offcpu_of(short).hops) == 1
    # a layer whose clock ticked more than its spans are long reads 0
    for p in oc.offcpu_of(r).offcpu:
        p[STORE] = -3 * MS
    assert oc.store_offcpu_ms(r) == 0.0
    # no slice, no table, or a program whose rows lack the fields
    assert oc.ecb_offcpu_ms(types.SimpleNamespace(slice_t=None)) is None
    monkeypatch.setattr(oc, "completed_traces", lambda: None)
    assert oc.hop_send_ms(types.SimpleNamespace(slice_t=(0.09, 0.35))) is None
    bare = write_rows(100.0)
    for row in bare:
        row.pop("cpu_ns", None)
        (row.get("attrs") or {}).pop("framed_us", None)
    monkeypatch.setattr(oc, "completed_traces", lambda: [bare])
    r = types.SimpleNamespace(slice_t=(0.09, 0.35))
    assert [read(r) for read in (oc.ecb_offcpu_ms, oc.offcpu_share,
                                 oc.hop_reader_ms)] == [None] * 3


def test_hop_sections_are_means_over_the_middle_half_by_hop_length(
        monkeypatch):
    """Medians of parts do not add up to the median of the whole; the
    mean over the roots between the quartiles of hop length does, and
    an op that stalled does not move it."""
    traces = []
    for i, stretch in enumerate((0, 1, 2, 3, 4, 5, 6, 500)):
        rows = write_rows(1000.0 * i)
        first = next(r for r in rows if r["event"] == "msg MOSDOp")
        # the reader thread got to the frame `stretch` ms later: the
        # hop and all behind it move out by as much
        first["attrs"]["framed_us"] += stretch * 1000
        for r in rows:
            if r is not first and r["span_id"] != 1:
                r["start_ns"] += stretch * MS
            if r["end_ns"] is not None:
                r["end_ns"] += stretch * MS
        traces.append(rows)
    monkeypatch.setattr(oc, "completed_traces", lambda: traces)
    r = types.SimpleNamespace(slice_t=(0.0, 10.0))
    got = oc.offcpu_of(r)
    assert len(got.hops) == 8
    # stretches 2, 3, 4, 5 are the middle half
    assert [p["hops"] / MS for p in got.middle_half()] \
        == [15, 16, 17, 18]
    parts = (oc.hop_send_ms(r), oc.hop_reader_ms(r), oc.hop_dispatch_ms(r))
    assert parts == (6, 2 + 3.5, 5)
    assert sum(parts) == pytest.approx(16.5)


def test_the_manifest_lists_the_seven_on_the_three_ec_cells():
    m = manifest.load_manifest()
    entries = {e["name"]: e for e in m["per_layer"]}
    assert [e["name"] for e in m["per_layer"][-7:]] == list(NEW)
    layers = {e["layer"] for e in m["per_layer"][:-7]}
    for name in NEW:
        e = entries[name]
        assert (e["source"], e["better"], e["moves"]) \
            == ("program_span", "lower", "op_lat_p50_ms")
        assert sorted(e["workloads"]) == sorted([LAT, WRITE, READ])
        assert e["layer"] in layers
        assert callable(manifest.load_reader(name))


def test_the_older_entries_are_as_they_were_before_the_seven():
    """What test_perfbench_reshape.py's `test_the_cells_traffic_and_metrics`
    held until entries were appended behind PR 37's three: they lie just
    before the seven, on the reshape cell alone, and no older list
    changed; the seven are on no map cell."""
    m = manifest.load_manifest()
    reshape, bulk = "crush10k.reshape_1m", "crush10k.weight_churn_1m"
    three = ("mapping.crush_tables_ms.epoch",
             "mapping.crush_table_upload_mb.epoch",
             "mapping.crush_programs_built.epoch")
    assert [e["name"] for e in m["per_layer"][-10:-7]] == list(three)
    for e in m["per_layer"][-10:-7]:
        assert e["workloads"] == [reshape] and e["better"] == "lower"
    layer = manifest.metrics_for(m, reshape, "per_layer")
    assert {e["moves"] for e in layer} == {"epoch_apply_p50_ms"}
    assert {e["name"] for e in layer} == {
        e["name"] for e in manifest.metrics_for(m, bulk, "per_layer")
    } | set(three)
    for e in m["end_to_end"] + m["per_layer"][:-10]:
        if bulk in e.get("workloads", []):
            assert e["workloads"][-2:] == [bulk, reshape]
        else:
            assert reshape not in e.get("workloads", [])
    assert len(m["per_layer"]) == 43 and len(m["workloads"]) == 7


@pytest.mark.parametrize("cell", [LAT, WRITE, READ])
def test_a_traced_run_of_each_ec_cell_reports_all_seven(on_cpu, capsys,
                                                        cell):
    result = (run(cell, trace=True) if cell == LAT
              else run_within_limit(cell, trace=True))
    assert result["correct"] is True
    metrics = result["metrics"]
    assert set(NEW) <= set(result["_wanted"])
    assert set(NEW) <= set(metrics), sorted(set(NEW) - set(metrics))
    for name in NEW:
        assert metrics[name]["value"] >= 0.0
        assert metrics[name]["unit"] == ("%" if "share" in name else "ms")
    assert metrics["trace.op_offcpu_share.t1"]["value"] <= 100.0
    # a hop of the path is read by some thread: the sections are there
    assert metrics["msgr.hop_dispatch_ms.t1"]["value"] > 0.0
    note = next(json.loads(ln.split(" ", 1)[1])
                for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("offcpu_readers "))
    # every root's hops are stamped, one root in four reads the CPU clock
    assert 0 < note["roots_with_cpu"] <= note["roots_with_stamped_hops"]
    hops = note["hop_ms_middle_half"]
    assert hops["send"] + hops["reader"] + hops["dispatch"] \
        == pytest.approx(hops["hops"])
    assert sum(metrics[f"msgr.hop_{s}_ms.t1"]["value"]
               for s in ("send", "reader", "dispatch")) \
        == pytest.approx(hops["hops"])
    # the layers' metrics are parts of what the share sums
    assert sum(note["offcpu_ms_mean"].values()) <= note["root_ms_mean"]
