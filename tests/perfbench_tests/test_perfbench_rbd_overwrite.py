"""`rbdec84.randwrite_4k_t1` on the CPU, on the program as it is: the
cell's entries, its readers on a fixture, the cell at toy size, and the
three faults its `correct` has to fail.

Cut in size and not in the code: k = 8, m = 4, 4 KiB stripe units and
4 KiB writes stay; the image is 8 objects of 256 KiB (8 stripes each),
so that random overwrites meet stripes written before within a short
window.  The platform and the peaks are injected as in
test_perfbench_run.py; a time read here is a count of work, never a
speed.
"""

import copy
import io
import json
import threading
import time
from types import SimpleNamespace

import pytest

from perfbench import faults_rbd
from perfbench.harness import cell as cell_mod
from perfbench.harness import manifest
from perfbench.harness.window import Ack
from perfbench.reference import rbd_plain, rs_plain
from perfbench.systems import rbd_ec_overwrite
from test_perfbench_run import compile_cache, on_cpu  # noqa: F401

CELL = "rbdec84.randwrite_4k_t1"
NEW = ("ecb.rmw_gather_ms.t1", "ecb.rmw_read_kb_per_write.t1",
       "store.blocks_per_overwrite.t1")
CHECKS = {"image_blocks_differ", "parity_shards_differ",
          "stored_block_csums_differ", "overwrites_not_rmw",
          "host_stood_in_for_device", "failed_ops"}
LIMIT_S = 300.0
M = manifest.load_manifest()


def cut() -> manifest.Cell:
    c = copy.deepcopy(manifest.load_cell(M, CELL))
    dep = c.config["deployment"]
    assert (dep["k"], dep["m"], dep["stripe_unit"]) == (8, 4, 4096)
    dep.update(image_order=18, image_stripe_unit=1 << 18,
               image_size=8 << 18)
    c.traffic.update(prefill_depth=4, precondition_acks=8,
                     verify_untouched_stripes=8, trace_offset_s=0.1,
                     trace_seconds=1.0)
    return c


def run_cut(trace: bool = False, seed: int = 2**31 + 41,
            seconds: float = 2.0) -> dict:
    out, err, done = io.StringIO(), io.StringIO(), {}
    wanted = manifest.metrics_for(
        M, CELL, "per_layer" if trace else "end_to_end")

    def body():
        try:
            done["rc"] = cell_mod.run_loaded(
                cut(), wanted, seed, seconds, trace, time.perf_counter(),
                out=out, err=err)
        except BaseException as e:      # re-raised on the test's thread
            done["error"] = e

    t = threading.Thread(target=body, name="cut-rbd-run", daemon=True)
    t.start()
    t.join(LIMIT_S)
    assert not t.is_alive(), f"the run did not end in {LIMIT_S} s"
    if "error" in done:
        raise done["error"]
    assert done["rc"] == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    result["_err"] = err.getvalue()
    return result


# -- the manifest's new entries ---------------------------------------------------

def test_the_entries_hold_to_the_manifests_contract():
    conf_entry = next(c for c in M["configs"]
                      if c["name"] == "rbd-ec84-overwrite")
    w = next(w for w in M["workloads"] if w["name"] == CELL)
    assert set(conf_entry) == {"name", "source", "file", "reduced", "why"}
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert len(conf_entry["source"]) <= 200
    cell = manifest.load_cell(M, CELL)
    conf = cell.config
    assert conf["system"] == "rbd_ec_overwrite" and conf["guarantees"]
    assert set(conf["reduced"]) == set(conf_entry["reduced"]) == {
        "osd_hosts", "pg_num", "image_size"}
    assert set(conf_entry["reduced"]) <= set(conf["deployment"])
    assert cell.traffic["kind"] == rbd_ec_overwrite.TRAFFIC_KIND
    # the pool is ec84-radosbench's, key for key
    base = manifest.load_cell(M, "ec84.write_4k_t1").config["deployment"]
    assert {k: conf["deployment"][k] for k in base} == base
    assert conf["deployment"]["allow_ec_overwrites"] is True
    e2e = {m["name"] for m in manifest.metrics_for(M, CELL, "end_to_end")}
    assert e2e == {"op_lat_p50_ms", "setup_s"}
    layer = manifest.metrics_for(M, CELL, "per_layer")
    assert {m["moves"] for m in layer} == {"op_lat_p50_ms"}
    names = {m["name"] for m in layer}
    assert set(NEW) <= names
    four_k = {m["name"] for m in manifest.metrics_for(
        M, "ec84.write_4k_t1", "per_layer")}
    assert names - set(NEW) == {n for n in four_k
                                if "offcpu" not in n and "hop_" not in n}


def test_the_new_metrics_are_the_last_three_and_list_only_the_cell():
    assert [m["name"] for m in M["per_layer"][-3:]] == list(NEW)
    for m in M["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert callable(manifest.load_reader(m["name"]))


def test_the_older_entries_are_where_they_were_before_these():
    """What the five cases that PR 41's entries outgrew held of the
    older ones (tests/conftest.py `_OUTGROWN`): PR 39's seven lie just
    before the three, on the three EC cells alone; PR 37's three before
    them, on the reshape cell; the reshape configuration and cell last
    but one, one four-chip cell of eight; and of the older lists only
    `op_lat_p50_ms` and the thirteen of the 4 KiB cell gained the cell,
    at their ends."""
    seven = ("ecb.op_offcpu_ms.t1", "engine.op_offcpu_ms.t1",
             "store.op_offcpu_ms.t1", "trace.op_offcpu_share.t1",
             "msgr.hop_send_ms.t1", "msgr.hop_reader_ms.t1",
             "msgr.hop_dispatch_ms.t1")
    ec = ["ec84.write_4k_t1", "ec84.write_4m_t1", "ec84deg.seq_read_4m_t1"]
    assert [e["name"] for e in M["per_layer"][-10:-3]] == list(seven)
    for e in M["per_layer"][-10:-3]:
        assert sorted(e["workloads"]) == sorted(ec)
    assert [e["name"] for e in M["per_layer"][-13:-10]] == [
        "mapping.crush_tables_ms.epoch",
        "mapping.crush_table_upload_mb.epoch",
        "mapping.crush_programs_built.epoch"]
    assert [c["name"] for c in M["configs"][-2:]] == [
        "crush10k-reshape-1m", "rbd-ec84-overwrite"]
    assert [w["name"] for w in M["workloads"][-2:]] == [
        "crush10k.reshape_1m", CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"]) == 1
    assert len(M["workloads"]) == 8 and len(M["per_layer"]) == 46
    joined = [e["name"] for e in M["end_to_end"] + M["per_layer"][:-3]
              if CELL in e.get("workloads", [])]
    assert len(joined) == 14
    for e in M["end_to_end"] + M["per_layer"][:-3]:
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL
            assert "ec84.write_4k_t1" in e["workloads"]


# -- the readers on a fixture -------------------------------------------------------

def _reading(before: dict, after: dict, acks=(), slice_t=None):
    log = SimpleNamespace(acks=list(acks), t_open=0.0, seconds=10.0)
    r = cell_mod.Reading(
        cell=None, device={}, peaks={}, seconds=10.0, setup_s=1.0, log=log,
        before=before, after=after, compiles_in_window=0,
        memory_peak_bytes=0, slice_t=slice_t)
    return r


def test_the_counter_readers_read_a_fixture_and_nothing_on_a_parent():
    acks = [Ack(i, i * 0.1, i * 0.1 + 0.02, True) for i in range(50)]
    before = {"osd.ec_rmw_writes": 10, "osd.ec_rmw_read_bytes": 0,
              "store.write_run_blocks": 100}
    after = {"osd.ec_rmw_writes": 60,
             "osd.ec_rmw_read_bytes": 50 * 8 * 4096,
             "store.write_run_blocks": 100 + 50 * 12}
    r = _reading(before, after, acks)
    assert manifest.load_reader(NEW[1])(r) == 32.0
    assert manifest.load_reader(NEW[2])(r) == 12.0
    parent = _reading({}, {}, acks)
    assert manifest.load_reader(NEW[1])(parent) is None
    assert manifest.load_reader(NEW[2])(parent) is None


def test_the_span_reader_reads_a_fixture(monkeypatch):
    from perfbench.harness import span_readers
    ns = 1_000_000

    def trace(t0, gather):
        rows = [{"kind": "span", "span_id": 1, "parent_span_id": 0,
                 "event": "osd_op rbd_data.x", "start_ns": t0,
                 "end_ns": t0 + 20 * ns, "layer": "client"},
                {"kind": "span", "span_id": 2, "parent_span_id": 1,
                 "event": "ec prepare", "start_ns": t0 + ns,
                 "end_ns": t0 + 2 * ns, "layer": "PG / EC backend"}]
        if gather:
            rows.append({"kind": "span", "span_id": 3, "parent_span_id": 2,
                         "event": "ec rmw gather", "start_ns": t0 + ns,
                         "end_ns": t0 + (1 + gather) * ns,
                         "layer": "PG / EC backend"})
        return rows
    traces = [trace(100 * ns, 3), trace(200 * ns, 5), trace(300 * ns, 4)]
    acks = [Ack(i, (t / 1e9) - 1e-6, (t + 19 * ns) / 1e9, True)
            for i, t in enumerate((100 * ns, 200 * ns, 300 * ns))]
    read = manifest.load_reader(NEW[0])
    monkeypatch.setattr(span_readers, "completed_traces", lambda: traces)
    assert read(_reading({}, {}, acks, slice_t=(0.0, 1.0))) == 4.0
    # a program without the span gives nothing
    monkeypatch.setattr(span_readers, "completed_traces",
                        lambda: [trace(100 * ns, 0)])
    assert read(_reading({}, {}, acks[:1], slice_t=(0.0, 1.0))) is None


def test_the_plain_image_is_its_writes_and_rs_plains_stripes():
    img = rbd_plain.PlainImage(bytes(range(256)) * 1024, 64 * 1024)
    img.write(70000, b"x" * 10)
    assert img.read(69998, 14) == bytes(
        [70000 - 2 & 255, 70000 - 1 & 255]) + b"x" * 10 + bytes(
        [70010 & 255, 70011 & 255])
    assert img.stripe_of(70000, 8 * 4096) == (1, 0)
    shards = img.stripe_shards(1, 0, 8, 4, 4096)
    assert shards == rs_plain.shards_of(img.read(65536, 32768), 8, 4, 4096)
    with pytest.raises(ValueError):
        img.write(len(img.data) - 1, b"ab")


# -- the cell at toy size ------------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_cut_cell_is_correct(on_cpu, trace):
    result = run_cut(trace)
    assert result["correct"], result["compared"]
    assert set(result["compared"]) == CHECKS
    assert result["failed"] == 0
    if trace:
        # a write in flight at an edge of the window counts its gather
        # on one side and its commit on the other: a toy window of a
        # few dozen writes reads a few percent off 32 and 12
        got = result["metrics"]
        assert got["ecb.rmw_read_kb_per_write.t1"]["value"] == \
            pytest.approx(32.0, rel=0.1)
        assert got["store.blocks_per_overwrite.t1"]["value"] == \
            pytest.approx(12.0, rel=0.1)
        assert got["ecb.rmw_gather_ms.t1"]["value"] > 0
    else:
        assert set(result["metrics"]) == {"op_lat_p50_ms", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("lost_write", "image_blocks_differ"),
    ("altered_parity", "parity_shards_differ"),
    ("stale_stripe", "image_blocks_differ")])
def test_each_fault_trips_its_number(on_cpu, fault, number):
    with faults_rbd.plant(fault):
        result = run_cut(seconds=3.0)
    assert not result["correct"]
    assert result["compared"][number]["value"] > 0, result["compared"]


def test_the_cell_refuses_a_program_without_the_path_at_once(monkeypatch):
    from ceph_tpu import rbd
    monkeypatch.delattr(rbd.Image, "aio_write")
    with pytest.raises(SystemExit, match="allow_ec_overwrites"):
        rbd_ec_overwrite._check_program()
