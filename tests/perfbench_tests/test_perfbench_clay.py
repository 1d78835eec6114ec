"""`clay84deg.seq_read_4m_t1` on the CPU, on the program as it is: the
configuration `clay84-degraded` (a Clay k=8 m=4 d=11 pool, ec84-degraded
in all but the plugin), its plain reference, its three metrics, and the
manifest's older entries where the cases that these entries outgrew
held them (tests/conftest.py `_OUTGROWN`).

Cut in size and not in the code: k, m, d, the 64 sub-chunks and the
4 KiB stripe unit are the configuration's; an object is eight stripes
and twelve are preloaded.  The platform and the peaks are injected as
in test_perfbench_run.py; a time read here is a count of work, never a
speed.
"""

import copy
import io
import itertools
import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench.harness import cell as cell_mod
from perfbench.harness import manifest, work, work_clay
from perfbench.reference import clay_plain
from perfbench.systems import clay_pool_degraded
from test_perfbench_run import (assert_result_line,  # noqa: F401
                                compile_cache, on_cpu)

CELL, DEG = "clay84deg.seq_read_4m_t1", "ec84deg.seq_read_4m_t1"
RBD = "rbdec84.randwrite_4k_t1"
NEW = ("clay_decode_roofline.t1", "ecb.rebuild_path_ms.t1",
       "ecb.decode_subchunks_per_read.t1")
LIMIT_S = 300.0
M = manifest.load_manifest()
CHECKS = {"degraded_reads_not_exact", "reads_rebuilt_short",
          "pgs_not_degraded", "parity_shards_differ",
          "stored_block_csums_differ", "plain_rebuild_differs",
          "host_stood_in_for_device", "failed_ops"}


def cut() -> manifest.Cell:
    c = copy.deepcopy(manifest.load_cell(M, CELL))
    dep = c.config["deployment"]
    assert (dep["k"], dep["m"], dep["d"], dep["sub_chunks"],
            dep["stripe_unit"]) == (8, 4, 11, 64, 4096)
    c.traffic.update(object_size=8 * 8 * 4096, verify_objects=0,
                     preload_objects=12, preload_depth=4,
                     precondition_acks=12, trace_offset_s=0.1,
                     trace_seconds=0.5, trace_max_seconds=0.5)
    return c


def run_within_limit(trace: bool = False, seed: int = 2**31 + 43) -> dict:
    out, err, done = io.StringIO(), io.StringIO(), {}
    wanted = manifest.metrics_for(
        M, CELL, "per_layer" if trace else "end_to_end")

    def body():
        try:
            done["rc"] = cell_mod.run_loaded(
                cut(), wanted, seed, 1.0, trace, time.perf_counter(),
                out=out, err=err)
        except BaseException as e:      # re-raised on the test's thread
            done["error"] = e

    t = threading.Thread(target=body, name="cut-clay-run", daemon=True)
    t.start()
    t.join(LIMIT_S)
    assert not t.is_alive(), f"the run did not end in {LIMIT_S} s"
    if "error" in done:
        raise done["error"]
    assert done["rc"] == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    result["_err"] = err.getvalue()
    result["_wanted"] = [m["name"] for m in wanted]
    return result


def _fails_on(result: dict, name: str) -> None:
    assert result["correct"] is False
    c = result["compared"][name]
    assert c["value"] > c["limit"], result["compared"]
    assert "NOT CORRECT" in result["_err"]


# -- the manifest's entries ---------------------------------------------------------

def test_the_configuration_is_ec84_degradeds_pool_with_the_clay_plugin():
    clay = manifest.load_cell(M, CELL)
    deg = manifest.load_cell(M, DEG)
    assert clay.chips == deg.chips == 1
    assert clay.traffic == deg.traffic and clay.traffic_name == \
        deg.traffic_name == "seq_read_4m_t1"
    assert clay.config["system"] == "clay_pool_degraded"
    assert clay.config["deployment"] == dict(
        deg.config["deployment"], plugin="clay", d=11, sub_chunks=64)
    assert clay.config["guarantees"] == deg.config["guarantees"]
    assert clay.config["reduced"] == deg.config["reduced"]
    assert {"scalar_mds", "coupling", "chunk_size"} <= set(
        clay.config["assumed"])
    assert clay.config["programs"] == {"digest": ["jit_digest"],
                                       "decode": ["jit_clay_decode"],
                                       "encode": ["jit_clay_encode"]}


@pytest.mark.parametrize("what", ["configuration", "cell"])
def test_the_new_entries_hold_the_manifests_contract(what):
    """What test_perfbench_manifest.py holds every configuration and
    cell to, with its closed lists of systems and traffic kinds grown by
    the ones this configuration and its cell use."""
    entry = next(c for c in M["configs"] if c["name"] == "clay84-degraded")
    w = next(w for w in M["workloads"] if w["name"] == CELL)
    if what == "configuration":
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert all(1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
                   for k in ("source", "why"))
        assert entry["file"] == "perfbench/configs/clay84-degraded.json"
        conf = manifest.load_cell(M, CELL).config
        assert conf["system"] in ("ec_pool", "osdmap_churn",
                                  "ec_pool_degraded", "clay_pool_degraded")
        assert set(conf["reduced"]) == set(entry["reduced"]) <= set(
            conf["deployment"])
        return
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == 1 and 1 <= len(w["why"]) <= 200
    cell = manifest.load_cell(M, CELL)
    assert cell.traffic["kind"] == clay_pool_degraded.TRAFFIC_KIND
    e2e = manifest.metrics_for(M, CELL, "end_to_end")
    layer = manifest.metrics_for(M, CELL, "per_layer")
    assert {m["name"] for m in e2e} == {"op_lat_p50_ms", "setup_s"}
    assert {m["moves"] for m in layer} == {"op_lat_p50_ms"}


def test_the_cell_reports_the_read_cells_metrics_but_rs_decodes():
    names = {m["name"] for m in manifest.metrics_for(M, CELL, "per_layer")}
    deg = {m["name"] for m in manifest.metrics_for(M, DEG, "per_layer")}
    assert names == (deg - {"gf_decode_roofline.t1"}) | set(NEW)


def test_the_new_metrics_are_the_last_three_and_list_only_the_cell():
    assert [m["name"] for m in M["per_layer"][-3:]] == list(NEW)
    for m, layer in zip(M["per_layer"][-3:],
                        ("kernels", "PG / EC backend", "PG / EC backend")):
        assert m["workloads"] == [CELL] and m["layer"] == layer
        assert m["moves"] == "op_lat_p50_ms"
        assert callable(manifest.load_reader(m["name"]))
    assert M["per_layer"][-3]["unit"] == "%"


def test_the_older_entries_are_where_they_were_before_these():
    """What the four cases that these entries outgrew held of the older
    ones: the rbd cell's three metrics just before the three new ones,
    on the rbd cell alone; the rbd configuration and cell last but one; one
    four-chip cell of nine; and of the older lists only those that the
    degraded read is on gained the cell, at their ends, where the rbd
    cell was last before."""
    assert [m["name"] for m in M["per_layer"][-6:-3]] == [
        "ecb.rmw_gather_ms.t1", "ecb.rmw_read_kb_per_write.t1",
        "store.blocks_per_overwrite.t1"]
    for m in M["per_layer"][-6:-3]:
        assert m["workloads"] == [RBD]
    assert [c["name"] for c in M["configs"][-2:]] == [
        "rbd-ec84-overwrite", "clay84-degraded"]
    assert [w["name"] for w in M["workloads"][-2:]] == [RBD, CELL]
    assert sum(w["chips"] == 4 for w in M["workloads"]) == 1
    assert len(M["workloads"]) == 9 and len(M["per_layer"]) == 49
    for e in M["end_to_end"] + M["per_layer"][:-3]:
        cells = e.get("workloads", [])
        assert (CELL in cells) == (DEG in cells
                                   and e["name"] != "gf_decode_roofline.t1")
        if CELL in cells:
            assert cells[-1] == CELL
            if RBD in cells:
                assert cells[-2] == RBD


# -- the plain reference and the work count ------------------------------------------

ERASED = [e for n in (1, 2, 4) for e in itertools.combinations(range(12), n)
          if n == 1 or e in ((2, 5), (8, 9), (0, 11), (4, 5), (0, 1, 2, 3),
                             (8, 9, 10, 11), (2, 5, 10, 11))]


@pytest.fixture(scope="module")
def seeded_object():
    payload = np.random.default_rng((43, 0xC1)).bytes(2 * 8 * 4096 + 1234)
    return payload, clay_plain.shards_of(payload, 8, 4, 4096)


@pytest.mark.parametrize("erased", ERASED,
                         ids=lambda e: "-".join(map(str, e)))
def test_plain_reference_rebuilds_from_what_is_left(erased, seeded_object):
    payload, shards = seeded_object
    have = {s: b for s, b in enumerate(shards) if s not in erased}
    assert clay_plain.object_of(have, 8, 4, 4096, len(payload)) == payload


def test_plain_reference_is_the_programs_code(seeded_object):
    """The program's host layered code and its device route give the
    plain reference's parity (k = 8, m = 4, alpha = 64)."""
    from ceph_tpu.ec import registry_instance
    payload, shards = seeded_object
    stripes = len(shards[0]) // 4096
    data = np.stack([np.frombuffer(shards[i], np.uint8).reshape(
        stripes, 4096) for i in range(8)], axis=1)
    for runtime in ("cpu", "tpu"):
        codec = registry_instance().factory(
            "clay", {"k": "8", "m": "4", "runtime": runtime})
        parity = np.asarray(codec.encode_chunks(data))
        assert [parity[:, j].tobytes() for j in range(4)] == shards[8:]


def test_plain_reference_refuses_fewer_than_k(seeded_object):
    payload, shards = seeded_object
    with pytest.raises(ValueError):
        clay_plain.object_of(dict(list(enumerate(shards))[:7]), 8, 4, 4096,
                             len(payload))
    have = {s: b for s, b in enumerate(shards) if s not in (2, 5)}
    have[9] = bytes([have[9][0] ^ 1]) + have[9][1:]
    assert clay_plain.object_of(have, 8, 4, 4096, len(payload)) != payload


def test_clay_decode_work_is_the_hand_count():
    # 128 stripes, two chunks rebuilt: bytes (8 + 2) x 4096 a stripe;
    # ops: 2 x 8 x 4 products-and-sums a byte column, plus 6 per pair
    # transform on three quarters of the 10 chunks' bytes
    w = work_clay.clay_decode_work(128, 8, 4, 2, 4096, 64)
    assert w["bytes"] == 128 * 10 * 4096
    assert w["ops"] == 128 * 4096 * (64 + 6 * 10 * 3 / 4)
    least, bound = work.least_seconds(
        w, {"int8_op_s": 393e12, "hbm_bytes_s": 819e9})
    assert bound == "bytes"


# -- the readers on a canned reading ---------------------------------------------------

def _reading(before: dict, after: dict, acks=(), slice_t=None, trace=None):
    log = SimpleNamespace(acks=list(acks), t_open=0.0, seconds=10.0)
    cell = manifest.load_cell(M, CELL)
    return cell_mod.Reading(
        cell=cell, device={}, peaks={"int8_op_s": 393e12,
                                     "hbm_bytes_s": 819e9},
        seconds=10.0, setup_s=1.0, log=log, before=before, after=after,
        compiles_in_window=0, memory_peak_bytes=0, trace=trace,
        slice_t=slice_t)


def _acks(n: int):
    return [SimpleNamespace(ok=True, t_submit=1.0 + i, t_ack=1.05 + i)
            for i in range(n)]


def test_the_subchunks_reader_on_a_canned_reading():
    read = manifest.load_reader("ecb.decode_subchunks_per_read.t1")
    before = {"osd.ec_decode_subchunks": 100}
    after = {"osd.ec_decode_subchunks": 100 + 4 * 128 * 64 * 2}
    assert read(_reading(before, after, _acks(4))) == 128 * 64 * 2
    # a program without the counter gives nothing
    assert read(_reading({}, {}, _acks(4))) is None


def test_the_roofline_reader_on_a_canned_trace():
    read = manifest.load_reader("clay_decode_roofline.t1")
    trace = SimpleNamespace(
        calls_of=lambda *p: 3 if p == ("jit_clay_decode",) else 0,
        seconds_of=lambda *p: 3 * 0.5e-3 if p == ("jit_clay_decode",)
        else 0.0)
    before = {"osd.ec_decode_submits": 0, "decode.clay_batches": 0,
              "osd.ec_decode_targets": 0}
    after = {"osd.ec_decode_submits": 10, "decode.clay_batches": 10,
             "osd.ec_decode_targets": 20}
    got = read(_reading(before, after, trace=trace))
    want = work.roofline_share(
        work_clay.clay_decode_work(3 * 128, 8, 4, 2.0, 4096, 64),
        {"int8_op_s": 393e12, "hbm_bytes_s": 819e9}, 3 * 0.5e-3)
    assert got == pytest.approx(want) and 0 < got < 100
    assert read(_reading({}, {}, trace=trace)) is None
    assert read(_reading(before, after, trace=None)) is None


def test_the_rebuild_path_reader_on_canned_spans(monkeypatch):
    from perfbench.harness import span_readers as sr
    read = manifest.load_reader("ecb.rebuild_path_ms.t1")

    def trace(t0: int, rebuild_ms):
        rows = [{"kind": "span", "span_id": 1, "parent_span_id": 0,
                 "event": "osd_op obj", "start_ns": t0,
                 "end_ns": t0 + 50_000_000}]
        if rebuild_ms is not None:
            rows += [{"kind": "span", "span_id": 2, "parent_span_id": 1,
                      "event": "ec decode submit", "start_ns": t0 + 10**7,
                      "end_ns": t0 + 10**7 + 10**5},
                     {"kind": "span", "span_id": 3, "parent_span_id": 1,
                      "event": "ec decode continuation",
                      "start_ns": t0 + 10**7 + 10**6,
                      "end_ns": t0 + 10**7 + int(rebuild_ms * 1e6)}]
        return rows

    traces = [trace(2 * 10**9, 3.0), trace(3 * 10**9, 5.0),
              trace(4 * 10**9, None), trace(9 * 10**9, 1.0)]
    monkeypatch.setattr(sr, "completed_traces", lambda: traces)
    # the fourth lies outside the slice, the third rebuilt nothing
    assert read(_reading({}, {}, slice_t=(1.5, 5.0))) == pytest.approx(4.0)
    monkeypatch.setattr(sr, "completed_traces", lambda: None)
    assert read(_reading({}, {}, slice_t=(1.5, 5.0))) is None


# -- the cell, cut ------------------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_at_toy_size_is_correct(on_cpu, trace):
    result = run_within_limit(trace)
    assert_result_line(result, trace)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == CHECKS
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values()), result["compared"]
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == set(result["_wanted"])
        return
    # nothing ran on a TPU here: the device's readers are silent
    of_the_device = {m["name"] for m in M["per_layer"]
                     if m["source"] == "device_trace"
                     or m["layer"] == "device"}
    assert set(result["_wanted"]) - set(metrics) <= of_the_device
    assert "clay_decode_roofline.t1" not in metrics
    per_read = metrics["ecb.decode_subchunks_per_read.t1"]["value"]
    # 8 stripes x 64 sub-chunks x at most two data chunks a read
    assert 0 < per_read <= 8 * 64 * 2
    assert 0 < metrics["ecb.decode_submits_per_read.t1"]["value"] <= 1
    assert metrics["ecb.rebuild_path_ms.t1"]["value"] > 0
    assert '"clay_batches"' in result["_err"]


def test_an_altered_parity_sub_chunk_fails(on_cpu, monkeypatch):
    """One byte of one parity sub-chunk that the encode engine hands
    back is changed, in every fifth object written."""
    from ceph_tpu.osd.daemon import OSDDaemon
    real, count = OSDDaemon._ec_shard_columns, [0]

    def altered(si, stripes, parity, n):
        count[0] += 1
        if count[0] % 5 == 0:
            parity = np.array(parity, copy=True)
            parity[0, 1, 64 * 3] ^= 1          # sub-chunk 3 of chunk 9
        return real(si, stripes, parity, n)

    monkeypatch.setattr(OSDDaemon, "_ec_shard_columns",
                        staticmethod(altered))
    result = run_within_limit()
    _fails_on(result, "parity_shards_differ")


def test_an_altered_rebuilt_sub_chunk_reads_not_exact(on_cpu, monkeypatch):
    from ceph_tpu.osd.daemon import OSDDaemon
    real, count = OSDDaemon._do_ec_decode_done, [0]

    class Altered:
        def __init__(self, fut):
            self._fut = fut

        def exception(self):
            return self._fut.exception()

        def result(self, timeout=None):
            rec = np.array(self._fut.result(timeout=timeout), copy=True)
            rec[0, 0, 64 * 5 + 7] ^= 1          # sub-chunk 5, byte 7
            return rec

    def altered(self, reqid, state, si, stripes, targets, size, fut):
        count[0] += 1
        if count[0] % 3 == 0:
            fut = Altered(fut)
        return real(self, reqid, state, si, stripes, targets, size, fut)

    monkeypatch.setattr(OSDDaemon, "_do_ec_decode_done", altered)
    result = run_within_limit()
    _fails_on(result, "degraded_reads_not_exact")
    others = {k: v["value"] for k, v in result["compared"].items()
              if k not in ("degraded_reads_not_exact", "failed_ops")}
    assert all(v == 0 for v in others.values()), others


def test_a_bypassed_decode_submit_reads_rebuilt_short(on_cpu, monkeypatch):
    """The OSDs rebuild on the op's thread and not through the decode
    engine, as the parent did for Clay: every read is exact, and the
    cell may not pass for that."""
    from ceph_tpu.osd.daemon import OSDDaemon
    monkeypatch.setattr(OSDDaemon, "_ec_submit_decode",
                        lambda self, reqid, state: False)
    result = run_within_limit()
    _fails_on(result, "reads_rebuilt_short")
    others = {k: v["value"] for k, v in result["compared"].items()
              if k != "reads_rebuilt_short"}
    assert all(v == 0 for v in others.values()), others


def test_a_program_without_stripe_info_is_refused(monkeypatch):
    """The parent's Clay pool lays objects out whole: set-up refuses it
    before anything is stood up."""
    from ceph_tpu.ec.clay import ErasureCodeClay
    monkeypatch.setattr(ErasureCodeClay, "supports_rmw_striping", False)
    system = clay_pool_degraded.System(cut(), 1)
    with pytest.raises(SystemExit) as err:
        system.setup()
    assert "stripe info" in str(err.value)
    assert system.cluster is None
