"""`crush10k-reshape-1m` and its cell `crush10k.reshape_1m`: the new
entries against the manifest's contract, the cell at toy size on the CPU
(platform injected as in test_perfbench_run.py; `FUSED_DIFF_HOST_MAX`
set to 0 as in test_perfbench_bulk.py, so that a toy table takes the
device diff), the faults that each new check has to fail on, the refusal
of a program without the counter, and the three new readers on made-up
readings.
"""

import copy
import io
import json
import time

import numpy as np
import pytest

from perfbench import faults
from perfbench.harness import cell as cell_mod
from perfbench.harness import manifest, span_readers
from perfbench.systems import osdmap_churn, osdmap_reshape
from test_perfbench_run import (assert_result_line,  # noqa: F401
                                compile_cache, on_cpu)

CELL, CONFIG = "crush10k.reshape_1m", "crush10k-reshape-1m"
BULK = "crush10k.weight_churn_1m"
M = manifest.load_manifest()
NEW_METRICS = ("mapping.crush_tables_ms.epoch",
               "mapping.crush_table_upload_mb.epoch",
               "mapping.crush_programs_built.epoch")
CHECKS = {"rows_differ_from_reference", "changed_set_differs_from_reference",
          "held_pgs_not_answered", "first_table_rows_differ",
          "host_epoch_changed_set_differs", "host_stood_in_for_device",
          "epochs_compared_short", "device_diffs_short",
          "crush_programs_built_after_first_map", "failed_ops"}


# -- the manifest's new entries ------------------------------------------------

def test_the_configuration_is_the_1m_ones_with_a_spare_host_and_nothing_cut():
    new = manifest.load_cell(M, CELL)
    old = manifest.load_cell(M, BULK)
    assert new.chips == 1 and new.config["system"] == "osdmap_reshape"
    assert new.config["deployment"] == dict(old.config["deployment"],
                                            spare_host_osds=40)
    assert new.config["guarantees"][:2] == old.config["guarantees"]
    assert len(new.config["guarantees"]) == 3
    assert "programs the first map built" in new.config["guarantees"][2]
    assert new.config["programs"] == old.config["programs"]
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and new.config["reduced"] == {}
    assert entry["file"] == "perfbench/configs/crush10k-reshape-1m.json"
    assert all(1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
               for k in ("source", "why"))
    assert entry["source"] != next(
        c for c in M["configs"] if c["name"] == old.config_name)["source"]
    assert {"weights", "map_seed", "one_pool", "ids_reused",
            "spare_host_weights", "host_epochs_check", "system"} <= set(
        new.config["assumed"])
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] == [
        CELL]
    assert M["configs"][-1] is entry and M["workloads"][-1]["name"] == CELL
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four == 1 and len(M["workloads"]) == 7


def test_the_cells_traffic_and_metrics():
    new = manifest.load_cell(M, CELL)
    assert new.traffic == {
        "kind": "epoch_groups", "group": list(osdmap_reshape.KINDS),
        "warm_groups": 2, "verify_group_stride": 3, "verify_min_epochs": 8,
        "verify_quiet_pgs": 16, "verify_initial_pgs": 128,
        "verify_changed_sample": 2048, "trace_offset_s": 2.0,
        "trace_seconds": 6.0}
    assert new.traffic["kind"] == osdmap_reshape.TRAFFIC_KIND
    w = M["workloads"][-1]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(w["why"]) <= 200 and w["traffic"] == "reshape_1m"
    e2e = {m["name"] for m in manifest.metrics_for(M, CELL, "end_to_end")}
    assert e2e == {"epoch_apply_p50_ms", "setup_s"}
    layer = manifest.metrics_for(M, CELL, "per_layer")
    assert {m["moves"] for m in layer} == {"epoch_apply_p50_ms"}
    # everything the 1 Mi cell reports, and the three of the tables
    assert {m["name"] for m in layer} == {m["name"] for m in
        manifest.metrics_for(M, BULK, "per_layer")} | set(NEW_METRICS)
    assert [m["name"] for m in M["per_layer"][-3:]] == list(NEW_METRICS)
    for m in M["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["better"] == "lower"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(manifest.load_reader(m["name"]))
    by_name = {m["name"]: m for m in M["per_layer"]}
    assert by_name[NEW_METRICS[0]]["source"] == "program_span"
    assert by_name[NEW_METRICS[0]]["layer"] == by_name[
        "mapping.fused_epoch_share"]["layer"]
    assert by_name[NEW_METRICS[2]]["layer"] == by_name[
        "setup.compiles_in_window.epoch"]["layer"]
    # the older lists gained the cell at their end and nothing else
    for m in M["end_to_end"] + M["per_layer"][:-3]:
        if BULK in m.get("workloads", []):
            assert m["workloads"][-2:] == [BULK, CELL]
        else:
            assert CELL not in m.get("workloads", [])


# -- the readers ----------------------------------------------------------------

def reading(before=None, after=None, slice_t=None):
    return cell_mod.Reading(
        cell=manifest.load_cell(M, CELL), device={}, peaks={},
        seconds=100.0, setup_s=1.0, log=osdmap_churn.EpochLog(),
        before=before or {}, after=after or {}, compiles_in_window=0,
        memory_peak_bytes=0, trace=None, slice_t=slice_t)


def test_table_upload_and_programs_built_readers():
    upload = manifest.load_reader(NEW_METRICS[1])
    built = manifest.load_reader(NEW_METRICS[2])
    keys = ("mapping.crush_table_upload_bytes", "mapping.epoch_updates",
            "mapping.crush_program_builds")
    r = reading(dict(zip(keys, (2_400_000, 9, 2))),
                dict(zip(keys, (7_200_000, 13, 2))))
    assert upload(r) == pytest.approx(1.2) and built(r) == 0.0
    r = reading(dict(zip(keys, (0, 9, 2))), dict(zip(keys, (0, 9, 5))))
    assert upload(r) is None and built(r) == 3.0
    # a program that does not count them: silent
    old = reading({keys[1]: 9}, {keys[1]: 13})
    assert upload(old) is None and built(old) is None


def span(event, a, b, sid, parent=0):
    return {"kind": "span", "event": event, "span_id": sid,
            "parent_span_id": parent, "start_ns": a, "end_ns": b}


def test_crush_tables_reader(monkeypatch):
    read = manifest.load_reader(NEW_METRICS[0])
    ms = 1_000_000

    def epoch(t0, tables, upload, sid):
        rows = [span("update_to", t0, t0 + 900 * ms, sid),
                span("mapping crush", t0 + ms, t0 + 800 * ms, sid + 1, sid)]
        if tables:
            rows += [span("mapping crush tables", t0 + 2 * ms,
                          t0 + (2 + tables) * ms, sid + 2, sid + 1),
                     span("mapping crush tables upload", t0 + 50 * ms,
                          t0 + (50 + upload) * ms, sid + 3, sid + 1)]
        return rows

    s = 1_000_000_000
    traces = [epoch(10 * s, 30, 2, 10), epoch(11 * s, 40, 4, 20),
              epoch(12 * s, 50, 6, 30),
              epoch(15 * s + 500 * ms, 99, 99, 40),     # across the end
              [span("osd_op x", 10 * s, 11 * s, 50)]]
    monkeypatch.setattr(span_readers, "completed_traces", lambda: traces)
    assert read(reading(slice_t=(10.0, 16.0))) == pytest.approx(44.0)
    assert read(reading()) is None                      # no slice
    # epochs that left the CRUSH map alone count as 0 ...
    traces[0] = epoch(10 * s, 0, 0, 10)
    assert read(reading(slice_t=(10.0, 16.0))) == pytest.approx(44.0)
    traces[1] = epoch(11 * s, 0, 0, 20)
    assert read(reading(slice_t=(10.0, 16.0))) == 0.0
    # ... and a program with no such span anywhere, or no table of
    # traces at all, gives nothing
    traces[2] = epoch(12 * s, 0, 0, 30)
    assert read(reading(slice_t=(10.0, 16.0))) is None
    monkeypatch.setattr(span_readers, "completed_traces", lambda: None)
    assert read(reading(slice_t=(10.0, 16.0))) is None


# -- the cell, at toy size ---------------------------------------------------------

def toy(hosts: int = 6) -> manifest.Cell:
    """6 hosts of 4 and a spare of 4: 7 of 8 lanes, inside the class
    (the XLA path pads to 8; the deployment's 251 of 256)."""
    c = copy.deepcopy(manifest.load_cell(M, CELL))
    c.config["deployment"].update(hosts=hosts, osds_per_host=4, pg_num=2048,
                                  kernel_mesh_devices=1, spare_host_osds=4)
    c.traffic.update(verify_group_stride=1, verify_min_epochs=4,
                     verify_initial_pgs=16, warm_groups=1,
                     verify_changed_sample=64,
                     trace_offset_s=0.1, trace_seconds=1.5)
    return c


def run(trace: bool = False, seed: int = 2**31 + 37, cell=None) -> dict:
    out, err = io.StringIO(), io.StringIO()
    wanted = manifest.metrics_for(
        M, CELL, "per_layer" if trace else "end_to_end")
    assert cell_mod.run_loaded(cell or toy(), wanted, seed, 2.0, trace,
                               time.perf_counter(), out=out, err=err) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    result["_err"] = err.getvalue()
    result["_wanted"] = [m["name"] for m in wanted]
    return result


@pytest.fixture
def device_diff(monkeypatch):
    from ceph_tpu.osd.mapping import SharedPGMappingService
    monkeypatch.setattr(SharedPGMappingService, "FUSED_DIFF_HOST_MAX", 0)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_at_toy_size_is_correct(on_cpu, device_diff, trace):
    result = run(trace)
    assert_result_line(result, trace)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 8 and result["attempted"] % 4 == 0
    assert set(result["compared"]) == CHECKS
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values()), result["compared"]
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == set(result["_wanted"])
        return
    assert metrics["setup.compiles_in_window.epoch"]["value"] == 0.0
    assert metrics["mapping.crush_programs_built.epoch"]["value"] == 0.0
    assert metrics["mapping.fused_epoch_share"]["value"] == 100.0
    assert metrics["mapping.device_diff_share.epoch"]["value"] == 100.0
    # every epoch is new CRUSH content: one table build and one upload
    # of the XLA path's tables, 8 root lanes and 8 x 8 leaf lanes
    root, leaf = 8 * (4 + 8 + 20 + 4), 64 * (4 + 8 + 20 + 4)
    assert metrics["mapping.crush_table_upload_mb.epoch"]["value"] == (
        pytest.approx((root + leaf) / 1e6))
    silent_here = {m["name"] for m in M["per_layer"]
                   if m["source"] == "device_trace" or m["layer"] == "device"
                   or (m["source"] == "program_span"
                       and '"roots_in_slice": 0' in result["_err"])}
    assert set(result["_wanted"]) - set(metrics) <= silent_here
    if '"roots_in_slice": 0' not in result["_err"]:
        assert metrics["mapping.crush_tables_ms.epoch"]["value"] > 0.0


def test_a_program_rebuilt_after_the_first_map_reads_not_correct(
        on_cpu, device_diff):
    """8 hosts fill the toy class: the spare host is a 9th, the tables
    grow to 16 lanes and the program is built anew — the answers are
    right all the same, and the cell may not pass for that."""
    result = run(cell=toy(hosts=8))
    assert result["correct"] is False
    c = result["compared"]["crush_programs_built_after_first_map"]
    assert c["value"] >= 1 > c["limit"]
    others = {k: v["value"] for k, v in result["compared"].items()
              if k != "crush_programs_built_after_first_map"}
    assert all(v == 0 for v in others.values()), others
    assert "compared crush_programs_built_after_first_map:" in result["_err"]


@pytest.mark.parametrize("fault,failing", [
    ("altered_answer", "rows_differ_from_reference"),
    ("half_delta", "host_epoch_changed_set_differs"),
    ("half_delta", "changed_set_differs_from_reference"),
    ("hidden_rows", "held_pgs_not_answered"),
    ("stale_state", "changed_set_differs_from_reference")])
def test_a_planted_fault_reads_not_correct(on_cpu, device_diff, fault,
                                           failing):
    with faults.plant(fault):
        result = run()
    assert result["correct"] is False
    c = result["compared"][failing]
    assert c["value"] > c["limit"], result["compared"]
    assert f"compared {failing}:" in result["_err"]
    assert "NOT CORRECT" in result["_err"]
    assert result["compared"]["crush_programs_built_after_first_map"][
        "value"] == 0


def test_a_changed_row_of_the_grown_map_reads_not_correct(
        on_cpu, device_diff, monkeypatch):
    """One wrong OSD in the rows the program answers while the spare
    host is in the map, reported as changed like the rest: only the
    rows' comparison sees it."""
    from ceph_tpu.osd.mapping import SharedPGMappingService
    real = SharedPGMappingService.lookup
    spare = set(range(24, 28))

    def lookup(self, osdmap, pool_id, pgid):
        up, up_primary, acting, acting_primary = real(
            self, osdmap, pool_id, pgid)
        if spare & set(up):
            up = [o if o not in spare else o ^ 1 for o in up]
        return up, up_primary, acting, acting_primary

    monkeypatch.setattr(SharedPGMappingService, "lookup", lookup)
    result = run()
    assert result["correct"] is False
    assert result["compared"]["rows_differ_from_reference"]["value"] > 0
    assert result["compared"]["first_table_rows_differ"]["value"] == 0


def test_a_program_without_the_counter_is_refused_before_any_map(
        on_cpu, monkeypatch):
    """The parent's program: the run ends at once, with a plain
    message, and builds nothing."""
    from ceph_tpu.ops import telemetry
    real = telemetry.mapping_summary

    def older():
        return {k: v for k, v in real().items()
                if not k.startswith("crush_")}

    monkeypatch.setattr(telemetry, "mapping_summary", older)
    monkeypatch.setattr(
        osdmap_churn.System, "_build_maps",
        lambda self: pytest.fail("a map was built"))
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="crush_program_builds"):
        cell_mod.run_loaded(toy(), [], 1, 1.0, False, time.perf_counter(),
                            out=io.StringIO(), err=io.StringIO())
    assert time.perf_counter() - t0 < 30.0


def test_the_references_states_of_a_group():
    """The four states the check holds a group to, from plain lists:
    the spare host in the root with the sum of its weights, the halved
    weight followed by its host's, the first map again at the end."""
    system = osdmap_reshape.System(toy(), 5)
    system._build_maps()
    system.spare_host = -8
    weights = np.arange(0x8000, 0x8004)
    system.groups[0] = (9, weights)
    s0, s1, s2, s3 = system._states(0)
    base = system.plain
    assert [int(h) for h in s0.root.items] == [int(h)
                                               for h in base.root.items]
    assert [int(h) for h in s1.root.items] == [
        int(h) for h in base.root.items] + [-8]
    assert int(s1.root.weights[-1]) == int(weights.sum())
    assert list(s1.hosts[-8].items) == [24, 25, 26, 27]
    assert s1.reweight[24:] == [0x10000] * 4 and s1.up[24:] == [True] * 4
    assert s0.reweight[24:] == [0] * 4 and s3.up[24:] == [False] * 4
    host = system._host_of(9)
    at = list(base.hosts[host].items).index(9)
    for s, halved, grown in ((s0, 0, 0), (s1, 0, 1), (s2, 1, 1), (s3, 1, 0)):
        want = int(base.hosts[host].weights[at]) >> halved
        assert int(s.hosts[host].weights[at]) == want
        assert (-8 in s.hosts) == bool(grown)
        i = [int(h) for h in s.root.items].index(host)
        assert int(s.root.weights[i]) == int(s.hosts[host].weights.sum())
    assert int(base.hosts[host].weights[at]) == int(
        s0.hosts[host].weights[at])      # the deployment's own is untouched
