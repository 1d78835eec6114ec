"""`crush10k-osdmap-1m` and its cell `crush10k.weight_churn_1m`: the bulk
plain reference against the per-PG one, the cell at toy size on the CPU
(platform injected as in test_perfbench_run.py; the service's
`FUSED_DIFF_HOST_MAX` is set to 0 here so that a toy table takes the
device diff, as the 1 Mi-PG table does by its size), the faults that
`correct` has to fail, the new entries against the manifest's contract,
and the three new readers on made-up readings.
"""

import copy
import io
import json
import time

import numpy as np
import pytest

from perfbench import faults
from perfbench.harness import cell as cell_mod
from perfbench.harness import manifest, work_mapping
from perfbench.harness.trace import TraceSummary
from perfbench.reference import crush_plain, crush_plain_bulk
from perfbench.systems import osdmap_churn, osdmap_churn_bulk
from test_perfbench_run import (assert_result_line,  # noqa: F401
                                compile_cache, on_cpu)

CELL, CONFIG = "crush10k.weight_churn_1m", "crush10k-osdmap-1m"
SMALL = "crush10k.weight_churn"
M = manifest.load_manifest()
NEW_METRICS = ("delta_diff_roofline.epoch", "mapping.delta_upload_mb.epoch",
               "mapping.device_diff_share.epoch")
CHECKS = {"rows_differ_from_reference", "changed_set_differs_from_reference",
          "held_pgs_not_answered", "first_table_rows_differ",
          "host_stood_in_for_device", "epochs_compared_short",
          "device_diffs_short", "failed_ops"}
V5E = {"bf16_flop_s": 197e12, "int8_op_s": 393e12, "hbm_bytes_s": 819e9,
       "hbm_bytes": 16e9}


# -- the bulk reference -------------------------------------------------------

def plain_map(hosts: int, per_host: int, pg_num: int):
    """The deployment's map as osdmap_churn draws it: the system (for its
    `_state`) and the map as plain lists."""
    cell = copy.deepcopy(manifest.load_cell(M, CELL))
    cell.config["deployment"].update(hosts=hosts, osds_per_host=per_host,
                                     pg_num=pg_num)
    system = osdmap_churn.System(cell, 1)
    system._build_maps()
    return system, system.plain


@pytest.fixture(scope="module")
def small_map():
    return plain_map(8, 4, 512)


@pytest.mark.parametrize("step", [0, 1, 2, 3],
                         ids=["base", "out", "reweight", "down"])
def test_bulk_reference_equals_the_per_pg_one_on_a_whole_small_map(
        small_map, step):
    system, _plain = small_map
    for osd in (5, 17):
        m = system._state(osd, step)
        want = crush_plain.up_table(m)
        got = crush_plain_bulk.up_table(m)
        assert got.dtype == want.dtype == np.int32
        np.testing.assert_array_equal(got, want)
        if step == 3:
            assert (want == -1).any() and not (want == osd).any()


def test_bulk_reference_equals_the_per_pg_one_on_the_10k_osd_map():
    """4,096 seeded PGs of the 1 Mi-PG pool on 250 hosts x 40."""
    dep = manifest.load_cell(M, CELL).config["deployment"]
    assert (dep["hosts"], dep["osds_per_host"], dep["pg_num"]) == (
        250, 40, 1 << 20)
    _system, m = plain_map(250, 40, 1 << 20)
    pgs = np.random.default_rng(35).choice(1 << 20, 4096, replace=False)
    got = crush_plain_bulk.up_rows(m, pgs)
    assert got.shape == (4096, 3)
    for row, pg in zip(got, pgs):
        up, _primary = crush_plain.up_of(m, int(pg))
        assert [int(o) for o in row if o >= 0] == up, pg
    # the map's rejected leaves make the retries real
    assert sum(w < 0x10000 for w in m.reweight) > 1000


def test_bulk_reference_spreads_a_pools_ranges_over_processes(
        small_map, monkeypatch):
    _system, m = small_map
    monkeypatch.setattr(crush_plain_bulk, "RANGE", 200)
    np.testing.assert_array_equal(crush_plain_bulk.up_table(m, workers=2),
                                  crush_plain.up_table(m))


def test_bulk_hashes_are_the_per_pg_references():
    rng = np.random.default_rng(2)
    a, b, c = (rng.integers(0, 1 << 32, 1000, dtype=np.uint32)
               for _ in range(3))
    np.testing.assert_array_equal(crush_plain_bulk.hash32_2(a, b),
                                  crush_plain.hash32_2(a, b))
    np.testing.assert_array_equal(crush_plain_bulk.hash32_3(a, b, c),
                                  crush_plain.hash32_3(a, b, c))
    m = crush_plain.PlainMap(None, {}, [], [], 7, 1000, 3)
    assert [int(p) for p in crush_plain_bulk.pps_of(m, np.arange(1000))] == [
        crush_plain.pps_of(m, pg) for pg in range(1000)]


# -- the manifest's new entries ------------------------------------------------

def test_the_configuration_is_the_small_ones_at_the_sources_own_pg_num():
    big = manifest.load_cell(M, CELL)
    small = manifest.load_cell(M, SMALL)
    assert big.chips == 1 and big.config["system"] == "osdmap_churn_bulk"
    assert big.config["deployment"] == dict(small.config["deployment"],
                                            pg_num=1048576)
    assert big.config["guarantees"] == small.config["guarantees"]
    assert big.config["programs"]["crush"] == small.config["programs"][
        "crush"]
    assert big.config["programs"]["delta_diff"] == ["jit_mapping_delta_diff"]
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and big.config["reduced"] == {}
    assert entry["file"] == "perfbench/configs/crush10k-osdmap-1m.json"
    assert all(1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
               for k in ("source", "why"))
    assert {"weights", "map_seed", "one_pool", "system"} <= set(
        big.config["assumed"])
    assert sum(c["name"] == CONFIG for c in M["configs"]) == 1
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] == [
        CELL]
    assert len(M["workloads"]) == 6 and sum(
        w["chips"] == 4 for w in M["workloads"]) == 1


def test_the_cells_traffic_and_metrics():
    big = manifest.load_cell(M, CELL)
    small = manifest.load_cell(M, SMALL)
    assert big.traffic == dict(small.traffic, verify_group_stride=4,
                               trace_seconds=6.0)
    assert big.traffic["kind"] == osdmap_churn_bulk.TRAFFIC_KIND
    w = next(w for w in M["workloads"] if w["name"] == CELL)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in manifest.metrics_for(M, CELL, "end_to_end")}
    assert e2e == {"epoch_apply_p50_ms", "setup_s"}
    layer = manifest.metrics_for(M, CELL, "per_layer")
    assert {m["moves"] for m in layer} == {"epoch_apply_p50_ms"}
    names = {m["name"] for m in layer}
    # everything the 65,536-PG cell reports, and the three of the diff
    assert names == {m["name"] for m in manifest.metrics_for(
        M, SMALL, "per_layer")} | set(NEW_METRICS)
    for m in M["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert callable(manifest.load_reader(m["name"]))
    by_name = {m["name"]: m for m in M["per_layer"]}
    assert by_name["delta_diff_roofline.epoch"]["unit"] == "%"
    assert by_name["delta_diff_roofline.epoch"]["source"] == "device_trace"
    assert by_name["delta_diff_roofline.epoch"]["layer"] == "kernels"
    assert by_name["mapping.delta_upload_mb.epoch"]["layer"] == by_name[
        "mapping.fused_epoch_share"]["layer"]


# -- the readers ----------------------------------------------------------------

def reading(before=None, after=None, epochs=(), trace=None, slice_t=None):
    log = osdmap_churn.EpochLog(
        [osdmap_churn.Epoch(i, 0, "out", 1, a, b, 0)
         for i, (a, b) in enumerate(epochs)], t_open=0.0, seconds=100.0)
    return cell_mod.Reading(
        cell=manifest.load_cell(M, CELL), device={}, peaks=V5E,
        seconds=100.0, setup_s=1.0, log=log, before=before or {},
        after=after or {}, compiles_in_window=0, memory_peak_bytes=0,
        trace=trace, slice_t=slice_t)


def summary(program_s: dict) -> TraceSummary:
    return TraceSummary(window_s=6.0, busy_s=5.0, busy_by_device={0: 5.0},
                        program_s=program_s,
                        program_calls=dict.fromkeys(program_s, 1),
                        device_ops=[], idle_gaps=[])


def test_table_diff_work_reads_both_tables_and_writes_a_byte_a_row():
    assert work_mapping.table_diff_work(1 << 20, 32) == {
        "ops": 0, "bytes": 2 * 32 * (1 << 20) + (1 << 20)}
    assert work_mapping.table_diff_work(0, 32) == {"ops": 0, "bytes": 0}


def test_delta_diff_roofline_reader():
    read = manifest.load_reader("delta_diff_roofline.epoch")
    # three epochs whole in the slice [10, 16], one across its end
    epochs = [(10.1, 11.0), (11.0, 12.9), (13.0, 15.9), (15.9, 16.4)]
    least = 3 * (2 * 32 + 1) * (1 << 20) / 819e9
    r = reading(epochs=epochs, slice_t=(10.0, 16.0), trace=summary(
        {"jit_mapping_delta_diff": 4 * least, "jit__unknown": 3.0}))
    assert read(r) == pytest.approx(25.0)
    # no such program in the trace (the parent's), no trace, no slice,
    # a configuration that names no diff program: silent
    assert read(reading(epochs=epochs, slice_t=(10.0, 16.0),
                        trace=summary({"jit__unknown": 3.0}))) is None
    assert read(reading(epochs=epochs)) is None
    assert read(reading(epochs=epochs, trace=summary({}))) is None
    del r.cell.config["programs"]["delta_diff"]
    assert read(r) is None


def test_delta_upload_reader():
    read = manifest.load_reader("mapping.delta_upload_mb.epoch")
    keys = ("mapping.delta_upload_bytes", "mapping.epoch_updates")
    r = reading(dict(zip(keys, (80_000_000, 9))),
                dict(zip(keys, (400_000_000, 13))))
    assert read(r) == pytest.approx(80.0)
    assert read(reading({keys[1]: 9}, {keys[1]: 13})) is None  # no counter
    assert read(reading(dict(zip(keys, (0, 9))),
                        dict(zip(keys, (0, 9))))) is None


def test_device_diff_share_reader():
    read = manifest.load_reader("mapping.device_diff_share.epoch")
    keys = ("mapping.delta_device_diffs", "mapping.delta_host_diffs")
    assert read(reading(dict(zip(keys, (8, 3))),
                        dict(zip(keys, (48, 3))))) == 100.0
    assert read(reading(dict(zip(keys, (0, 0))),
                        dict(zip(keys, (30, 10))))) == 75.0
    assert read(reading(dict(zip(keys, (5, 5))),
                        dict(zip(keys, (5, 5))))) is None
    assert read(reading({}, {})) is None                # no such counter


# -- the cell, at toy size ---------------------------------------------------------

def toy() -> manifest.Cell:
    c = copy.deepcopy(manifest.load_cell(M, CELL))
    c.config["deployment"].update(hosts=8, osds_per_host=4, pg_num=2048,
                                  kernel_mesh_devices=1)
    c.traffic.update(verify_group_stride=1, verify_min_epochs=4,
                     verify_initial_pgs=16, warm_groups=1,
                     trace_offset_s=0.1, trace_seconds=1.5)
    return c


def run(trace: bool = False, seed: int = 2**31 + 35) -> dict:
    out, err = io.StringIO(), io.StringIO()
    wanted = manifest.metrics_for(
        M, CELL, "per_layer" if trace else "end_to_end")
    assert cell_mod.run_loaded(toy(), wanted, seed, 2.0, trace,
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
    # the table came from the bulk reference and was kept
    kept = list((on_cpu / "kept").glob("up_table_bulk_*.npy"))
    assert len(kept) == 1 and np.load(kept[0]).shape == (2048, 3)
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == set(result["_wanted"])
        return
    assert metrics["setup.compiles_in_window.epoch"]["value"] == 0.0
    assert metrics["mapping.fused_epoch_share"]["value"] == 100.0
    assert metrics["mapping.device_diff_share.epoch"]["value"] == 100.0
    # two tables of 2,048 rows of 2 * 3 + 4 words an epoch
    assert metrics["mapping.delta_upload_mb.epoch"]["value"] == (
        pytest.approx(2 * 2048 * 40 / 1e6))
    # nothing ran on a TPU here: the device's readers are silent, and
    # on a loaded host the slice may hold no whole epoch for the spans'
    silent_here = {m["name"] for m in M["per_layer"]
                   if m["source"] == "device_trace" or m["layer"] == "device"
                   or (m["source"] == "program_span"
                       and '"roots_in_slice": 0' in result["_err"])}
    assert set(result["_wanted"]) - set(metrics) <= silent_here
    assert "delta_diff_roofline.epoch" not in metrics


def test_a_delta_the_host_computed_reads_not_correct(on_cpu):
    """A toy table is under FUSED_DIFF_HOST_MAX: every answer is right,
    and the cell may not pass for that."""
    result = run()
    assert result["correct"] is False
    c = result["compared"]["device_diffs_short"]
    assert c["value"] == result["attempted"] > c["limit"]
    assert "compared device_diffs_short:" in result["_err"]
    others = {k: v["value"] for k, v in result["compared"].items()
              if k != "device_diffs_short"}
    assert all(v == 0 for v in others.values()), others


@pytest.mark.parametrize("fault,failing", [
    ("altered_answer", "rows_differ_from_reference"),
    ("half_delta", "changed_set_differs_from_reference"),
    ("hidden_rows", "held_pgs_not_answered"),
    ("stale_state", "changed_set_differs_from_reference")])
def test_a_planted_fault_reads_not_correct(on_cpu, device_diff, fault,
                                           failing):
    assert fault in faults.FAULTS["osdmap_churn"]
    with faults.plant(fault):
        result = run()
    assert result["correct"] is False
    c = result["compared"][failing]
    assert c["value"] > c["limit"], result["compared"]
    assert f"compared {failing}:" in result["_err"]
    assert "NOT CORRECT" in result["_err"]
    assert result["compared"]["device_diffs_short"]["value"] == 0


def test_a_program_without_the_counters_is_refused_before_any_map(
        on_cpu, monkeypatch):
    """The parent's program: the run ends at once, with a plain
    message, and builds nothing."""
    from ceph_tpu.ops import telemetry
    real = telemetry.mapping_summary

    def older():
        return {k: v for k, v in real().items()
                if not k.startswith("delta_")}

    monkeypatch.setattr(telemetry, "mapping_summary", older)
    monkeypatch.setattr(
        osdmap_churn.System, "_build_maps",
        lambda self: pytest.fail("a map was built"))
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="delta_device_diffs"):
        cell_mod.run_loaded(toy(), [], 1, 1.0, False, time.perf_counter(),
                            out=io.StringIO(), err=io.StringIO())
    assert time.perf_counter() - t0 < 30.0
