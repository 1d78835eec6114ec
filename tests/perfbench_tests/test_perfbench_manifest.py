"""BENCHMARK.json against the benchmark's contract, and the files it
names: every configuration, traffic mix and metric is a file of its own
that the harness finds by name."""

import json
import os
import re

import pytest

from perfbench.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

#: BENCHMARK.json alone is what the driver reads ...
LIVE = manifest.load_manifest()
#: ... and with the parked cells' entries beside it, what it will be
#: again: both are held to the contract
M = manifest.with_parked(LIVE, "ec84.write_4m_t16")
METRICS = M["end_to_end"] + M["per_layer"]


def test_parked_entries_are_in_neither_or_both():
    """A parked cell is out of BENCHMARK.json whole: the cell, every
    metric that lists only parked cells, and its configuration where no
    other cell uses it."""
    live_cells = {w["name"] for w in LIVE["workloads"]}
    assert live_cells and len(M["workloads"]) > len(LIVE["workloads"])
    assert {w["config"] for w in LIVE["workloads"]} == {
        c["name"] for c in LIVE["configs"]}
    for m in LIVE["end_to_end"] + LIVE["per_layer"]:
        assert set(m.get("workloads", live_cells)) <= live_cells
    for w in LIVE["workloads"]:
        e2e = manifest.metrics_for(LIVE, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert manifest.metrics_for(LIVE, w["name"], "per_layer")


def test_a_metric_without_cells_of_its_own_follows_what_it_moves():
    """The contract's rule for a per-layer entry with no `workloads`
    key, which a later PR may add without touching the harness: it is
    reported in every cell that reports the metric it moves."""
    extra = {"name": "x.any", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "client",
             "moves": "epoch_apply_p50_ms"}
    m2 = dict(M, per_layer=M["per_layer"] + [extra])
    for w in M["workloads"]:
        names = {m["name"] for m in manifest.metrics_for(
            m2, w["name"], "per_layer")}
        assert ("x.any" in names) == w["name"].startswith("crush10k.")


def test_top_level_keys_and_sizes():
    assert set(LIVE) == set(M)
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["paths"]) <= 16 and all(PATH.match(p)
                                              for p in M["paths"])
    assert M["command"][-1].startswith(tuple(M["paths"]))
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert all(NAME.match(k) for k in entry["reduced"])
    assert len(entry["reduced"]) <= 16
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    assert entry["file"].startswith(tuple(p + "/" for p in M["paths"]))
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        conf = json.load(f)
    # the file states the system, the guarantees, and each cut by name
    assert conf["system"] in ("ec_pool", "osdmap_churn")
    assert conf["guarantees"]
    assert set(conf["reduced"]) == set(entry["reduced"])
    assert set(entry["reduced"]) <= set(conf["deployment"])
    assert any(w["config"] == entry["name"] for w in M["workloads"])


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_traffic_file(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = manifest.load_cell(M, w["name"])
    assert cell.traffic["kind"] in ("closed_loop_write", "epoch_groups")
    e2e = manifest.metrics_for(M, w["name"], "end_to_end")
    layer = manifest.metrics_for(M, w["name"], "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    # every per-layer metric of the cell moves a metric the cell reports
    assert {m["moves"] for m in layer} <= {m["name"] for m in e2e}


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader_file(m):
    e2e = m in M["end_to_end"]
    must = ({"name", "unit", "better", "bound", "source"} if e2e else
            {"name", "unit", "better", "source", "layer", "moves"})
    assert must <= set(m) <= must | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {x["name"] for x in M["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    cells = {w["name"] for w in M["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    assert callable(manifest.load_reader(m["name"]))


def test_names_are_unique():
    for names in ([m["name"] for m in METRICS],
                  [w["name"] for w in M["workloads"]],
                  [c["name"] for c in M["configs"]],
                  [(w["config"], w["traffic"]) for w in M["workloads"]],
                  [c["file"] for c in M["configs"]]):
        assert len(names) == len(set(names))


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        manifest.load_cell(M, "no.such.cell")
