"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name in the manifest:

  configuration  the manifest's ``file`` for it (perfbench/configs/)
  traffic mix    perfbench/traffic/<traffic>.json
  metric         perfbench/metrics/<name>.py, with ``read(reading)``

so a later change adds a cell or a metric by adding files and entries.
Entries of cells that were taken out of the manifest wait in
perfbench/parked/<cell>.json, in the manifest's own layout.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def with_parked(manifest: dict, name: str) -> dict:
    """`manifest` with the entries of perfbench/parked/<name>.json added:
    cells that were taken out of BENCHMARK.json and are kept ready
    (perfbench/control.py and the tests run them from there)."""
    with open(os.path.join(BENCH_DIR, "parked", name + ".json")) as f:
        parked = json.load(f)
    return {key: value + parked.get(key, []) if isinstance(value, list)
            and key in ("configs", "workloads", "end_to_end", "per_layer")
            else value for key, value in manifest.items()}


def load_cell(manifest: dict, workload: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r} in "
                         f"BENCHMARK.json (has: {', '.join(cells)})")
    w = cells[workload]
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, traffic)


def metrics_for(manifest: dict, workload: str, section: str) -> list[dict]:
    """The metrics of `section` (``end_to_end`` or ``per_layer``) that
    this cell reports.  One without a ``workloads`` key is every cell's
    that reports the end-to-end metric it moves (an end-to-end metric
    without the key is every cell's)."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if workload in m.get("workloads", [workload])}
    if section == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e_here]
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)]


def load_reader(name: str):
    """``read`` of perfbench/metrics/<name>.py."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_system(kind: str):
    """The module that stands up and drives one kind of deployment:
    perfbench/systems/<kind>.py."""
    return importlib.import_module(f"perfbench.systems.{kind}")
