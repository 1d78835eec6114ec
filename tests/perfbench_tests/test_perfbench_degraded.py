"""`ec84deg.seq_read_4m_t1` and `ec84.write_4m_t1` on the CPU, on the
program as it is.

Cut in size and not in k: `test_perfbench_run.py toy()` cuts the EC
cells to k = 2, where every reed_sol_van construction gives the same
rows.  Here k is the configurations' 8 and an object is eight whole
stripes: parity meets every column of the matrix, and a shard's eight
blocks are the least that BlueStore verifies by the digest batch on a
read (`bluestore_batched_read_min`) — under it a shard read meets no
device call, as a 4 MiB object's never does.  The read cell keeps m = 4
and its two OSDs down (a rebuild of two shards needs both); the write
cell is cut to m = 2 on 11 OSDs as PR 30's was (with as many OSDs as
shards CRUSH leaves a hole in some PG's up set, and a write there is
acknowledged a shard short).  The platform and the peaks are injected
as in test_perfbench_run.py; a time read here is a count of work, never
a speed.
"""

import copy
import glob
import io
import itertools
import json
import os
import threading
import time

import numpy as np
import pytest

from perfbench import faults
from perfbench.harness import cell as cell_mod
from perfbench.harness import manifest
from perfbench.reference import rs_plain, rs_plain_decode
from perfbench.systems import ec_pool_degraded
from test_perfbench_run import (assert_result_line,  # noqa: F401
                                compile_cache, on_cpu)

READ, WRITE = "ec84deg.seq_read_4m_t1", "ec84.write_4m_t1"
#: seconds one run of a cut cell may take before the test gives it up
LIMIT_S = 300.0
M = manifest.load_manifest()
READ_CHECKS = {"degraded_reads_not_exact", "reads_rebuilt_short",
               "pgs_not_degraded", "parity_shards_differ",
               "stored_block_csums_differ", "plain_rebuild_differs",
               "host_stood_in_for_device", "failed_ops"}
WRITE_CHECKS = {"acked_objects_not_read_back", "parity_shards_differ",
                "stored_block_csums_differ", "host_stood_in_for_device",
                "failed_ops"}


def cut(cell: str) -> manifest.Cell:
    c = copy.deepcopy(manifest.load_cell(M, cell))
    dep = c.config["deployment"]
    assert (dep["k"], dep["m"], dep["stripe_unit"]) == (8, 4, 4096)
    assert c.traffic["depth"] == 1
    c.traffic.update(object_size=8 * 8 * 4096, verify_objects=0,
                     trace_offset_s=0.1, trace_seconds=0.5,
                     trace_max_seconds=0.5)
    if cell == READ:
        c.traffic.update(preload_objects=12, preload_depth=4,
                         precondition_acks=12)
    else:
        dep.update(m=2, osds=11)
        c.traffic.update(precondition_acks=2)
    return c


def run_within_limit(cell: str, trace: bool = False,
                     seed: int = 2**31 + 31) -> dict:
    """One 1 s window of the cut cell; fails, and does not wait, if the
    run is still going after LIMIT_S."""
    out, err, done = io.StringIO(), io.StringIO(), {}
    wanted = manifest.metrics_for(
        M, cell, "per_layer" if trace else "end_to_end")

    def body():
        try:
            done["rc"] = cell_mod.run_loaded(
                cut(cell), wanted, seed, 1.0, trace, time.perf_counter(),
                out=out, err=err)
        except BaseException as e:      # re-raised on the test's thread
            done["error"] = e

    t = threading.Thread(target=body, name="cut-cell-run", daemon=True)
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


# -- the manifest's new entries ---------------------------------------------------

def test_the_write_cell_is_the_4k_cells_twin_but_for_the_object_size():
    big = manifest.load_cell(M, WRITE)
    small = manifest.load_cell(M, "ec84.write_4k_t1")
    assert big.config == small.config and big.chips == small.chips == 1
    assert (big.traffic["kind"], big.traffic["depth"]) == (
        small.traffic["kind"], small.traffic["depth"])
    assert big.traffic["object_size"] == 4 * 1024 * 1024
    assert {m["name"] for m in manifest.metrics_for(M, WRITE, "end_to_end")
            } == {"op_lat_p50_ms", "setup_s"}
    layer = {m["name"] for m in manifest.metrics_for(M, WRITE, "per_layer")}
    assert layer >= {m["name"] for m in manifest.metrics_for(
        M, "ec84.write_4k_t1", "per_layer")}
    assert layer >= {"gf_encode_roofline.t1", "digest_roofline.t1",
                     "kernels.digest_device_share.t1",
                     "store.blocks_per_csum_batch.t1"}


def test_the_degraded_configuration_is_the_write_cells_pool_with_two_down():
    """`ec84-degraded` is `ec84-radosbench`'s deployment with two OSDs
    down and in; its entry and its cell hold the contract that
    test_perfbench_manifest.py holds the older entries to (that file's
    lists of systems and traffic kinds are closed and older than this
    configuration)."""
    deg = manifest.load_cell(M, READ)
    base = manifest.load_cell(M, WRITE)
    assert deg.chips == 1 and deg.config["system"] == "ec_pool_degraded"
    extra = {"down_osds": 2, "down_out": False}
    assert deg.config["deployment"] == dict(base.config["deployment"],
                                            **extra)
    assert deg.config["guarantees"] and deg.config["source"]
    entry = next(c for c in M["configs"] if c["name"] == "ec84-degraded")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(deg.config["reduced"]) == set(entry["reduced"]) == {
        "osd_hosts", "pg_num"} <= set(deg.config["deployment"])
    assert all(1 <= len(entry[key]) <= 200 for key in ("source", "why"))
    assert deg.traffic["kind"] == ec_pool_degraded.TRAFFIC_KIND
    assert (deg.traffic["depth"], deg.traffic["object_size"],
            deg.traffic["preload_objects"], deg.traffic["down_osds"],
            deg.traffic["precondition_acks"]) == (1, 4194304, 64, 2, 64)
    e2e = {m["name"] for m in manifest.metrics_for(M, READ, "end_to_end")}
    assert e2e == {"op_lat_p50_ms", "setup_s"}
    layer = manifest.metrics_for(M, READ, "per_layer")
    assert {m["moves"] for m in layer} <= e2e
    names = {m["name"] for m in layer}
    assert names >= {"gf_decode_roofline.t1", "kernels.digest_device_share.t1",
                     "ecb.decode_submits_per_read.t1",
                     "store.blocks_per_verify_batch.t1",
                     "trace.op_named_share.t1", "device.idle_share.lat"}
    # what only a write has to read is not the read cell's
    assert not names & {"gf_encode_roofline.t1", "digest_roofline.t1",
                        "store.blocks_per_csum_batch.t1"}


def _large_object_traffic():
    """Every traffic file whose objects are 1 MiB or more."""
    folder = os.path.join(manifest.BENCH_DIR, "traffic")
    for path in sorted(glob.glob(os.path.join(folder, "*.json"))):
        with open(path) as f:
            traffic = json.load(f)
        if int(traffic.get("object_size", 0)) >= 1024 * 1024:
            yield os.path.basename(path), traffic


@pytest.mark.parametrize("name,traffic", list(_large_object_traffic()),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_a_large_objects_traced_slice_is_short(name, traffic):
    """The profiler takes about two seconds to stop for every digest
    call in the slice (a 1,024-step scan), and `SliceTracer.finish`
    waits 200 s after the window for it: a 4 s slice of a 4 MiB cell
    held ~130 calls and lost PR 30 its run.  At most 2 s where objects
    are 1 MiB or more."""
    assert 0 < float(traffic["trace_max_seconds"]) <= 2.0, name
    assert float(traffic["trace_seconds"]) <= float(
        traffic["trace_max_seconds"])


# -- the plain decoder --------------------------------------------------------------

K, MM, SU = 8, 4, 4096
ERASED = [e for n in (1, 2, 4)
          for e in itertools.combinations(range(K + MM), n)
          if n < 4 or e in ((0, 1, 2, 3), (0, 5, 8, 11), (4, 5, 6, 7))]


@pytest.fixture(scope="module")
def seeded_object():
    payload = np.random.default_rng((31, 0x84)).bytes(2 * K * SU + 1234)
    return payload, rs_plain.shards_of(payload, K, MM, SU)


@pytest.mark.parametrize("erased", ERASED,
                         ids=lambda e: "-".join(map(str, e)))
def test_plain_decoder_rebuilds_the_object_from_what_is_left(
        erased, seeded_object):
    """Every single and every pair of erasures of 8 + 4 (and a few of
    four): the object comes back from the shards left."""
    payload, shards = seeded_object
    have = {s: b for s, b in enumerate(shards) if s not in erased}
    assert rs_plain_decode.object_of(have, K, MM, SU,
                                     len(payload)) == payload
    data = rs_plain_decode.data_shards_of(have, K, MM)
    assert data == shards[:K]


def test_plain_decoder_refuses_fewer_than_k_shards(seeded_object):
    _payload, shards = seeded_object
    have = dict(list(enumerate(shards))[:K - 1])
    with pytest.raises(ValueError):
        rs_plain_decode.data_shards_of(have, K, MM)
    with pytest.raises(ValueError):
        rs_plain_decode.invert([[1, 1], [1, 1]])


def test_plain_decoder_sees_an_altered_shard(seeded_object):
    payload, shards = seeded_object
    have = {s: b for s, b in enumerate(shards) if s not in (2, 6)}
    have[9] = bytes([have[9][0] ^ 1]) + have[9][1:]
    assert rs_plain_decode.object_of(have, K, MM, SU,
                                     len(payload)) != payload


# -- the cells, cut ------------------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", [READ, WRITE])
def test_correct_at_the_profiles_k_on_the_program_as_it_is(on_cpu, cell,
                                                           trace):
    result = run_within_limit(cell, trace)
    assert_result_line(result, trace)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == (READ_CHECKS if cell == READ
                                       else WRITE_CHECKS)
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values()), result["compared"]
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == set(result["_wanted"])
        return
    assert metrics["setup.compiles_in_window.lat"]["value"] == 0.0
    assert metrics["trace.op_named_share.t1"]["value"] >= 80.0
    # nothing ran on a TPU here: trace readers are silent, never 0
    assert not {"gf_encode_roofline.t1", "gf_decode_roofline.t1",
                "digest_roofline.t1",
                "kernels.digest_device_share.t1"} & set(metrics)
    # the driver refuses a traced line that lacks a metric listing the
    # cell: off the chip only the device's own readers may be silent
    of_the_device = {m["name"] for m in M["per_layer"]
                     if m["source"] == "device_trace"
                     or m["layer"] == "device"}
    assert set(result["_wanted"]) - set(metrics) <= of_the_device
    if cell == READ:
        assert result["attempted"] > 12 + 12 + 2
        # a shard's eight blocks, verified by one digest call a shard read
        assert metrics["store.blocks_per_verify_batch.t1"]["value"] == 8.0
        assert 0.0 < metrics["ecb.decode_submits_per_read.t1"][
            "value"] <= 1.0
        # a read commits nothing: the reader has nothing to read, so
        # the cell is not on that metric's list
        assert "store.fsync_share.t1" not in result["_wanted"]
        assert '"objects_that_lost_data"' in result["_err"]
    else:
        assert result["attempted"] > 4
        assert metrics["store.blocks_per_csum_batch.t1"]["value"] == 8.0


def _fails_on(result: dict, name: str) -> None:
    assert result["correct"] is False
    c = result["compared"][name]
    assert c["value"] > c["limit"], result["compared"]
    assert f"compared {name}:" in result["_err"]
    assert "NOT CORRECT" in result["_err"]


def test_a_changed_byte_of_a_rebuilt_shard_reads_not_exact(on_cpu,
                                                            monkeypatch):
    """One byte of what the decode engine hands back is changed, in
    every third rebuild: those reads are not the acknowledged bytes."""
    from ceph_tpu.osd.daemon import OSDDaemon
    real, count = OSDDaemon._do_ec_decode_done, [0]

    class Altered:
        def __init__(self, fut):
            self._fut = fut

        def exception(self):
            return self._fut.exception()

        def result(self, timeout=None):
            rec = np.array(self._fut.result(timeout=timeout), copy=True)
            rec[0, 0, 0] ^= 1
            return rec

    def altered(self, reqid, state, si, stripes, targets, size, fut):
        count[0] += 1
        if count[0] % 3 == 0:
            fut = Altered(fut)
        return real(self, reqid, state, si, stripes, targets, size, fut)

    monkeypatch.setattr(OSDDaemon, "_do_ec_decode_done", altered)
    result = run_within_limit(READ)
    _fails_on(result, "degraded_reads_not_exact")
    # a read that is not exact is a failed operation, and nothing else
    assert result["compared"]["failed_ops"]["value"] == result[
        "compared"]["degraded_reads_not_exact"]["value"] == result["failed"]
    others = {k: v["value"] for k, v in result["compared"].items()
              if k not in ("degraded_reads_not_exact", "failed_ops")}
    assert all(v == 0 for v in others.values()), others


def test_a_bypassed_decode_submit_reads_rebuilt_short(on_cpu, monkeypatch):
    """The OSDs rebuild on the host and not through the decode engine
    (`osd_ec_decode_async` off): every read is exact, and the cell may
    not pass for that."""
    from ceph_tpu.osd.daemon import OSDDaemon
    monkeypatch.setattr(OSDDaemon, "_ec_submit_decode",
                        lambda self, reqid, state: False)
    result = run_within_limit(READ)
    _fails_on(result, "reads_rebuilt_short")
    others = {k: v["value"] for k, v in result["compared"].items()
              if k != "reads_rebuilt_short"}
    assert all(v == 0 for v in others.values()), others


def test_a_down_osd_brought_back_before_the_window_is_seen(on_cpu,
                                                            monkeypatch):
    real = ec_pool_degraded.System.take_down

    def take_down_and_bring_one_back(self):
        real(self)
        self.cluster.run_osd(self.down[0])
        self.cluster.wait_for_osd_count(self.n_osds - self.n_down + 1,
                                        timeout=60.0)
        epoch = self.cluster.mon.osdmap.epoch
        self.cluster.wait_for_epoch(epoch, timeout=60.0)
        self.io.client.wait_for_epoch(epoch)

    monkeypatch.setattr(ec_pool_degraded.System, "take_down",
                        take_down_and_bring_one_back)
    result = run_within_limit(READ)
    _fails_on(result, "pgs_not_degraded")
    assert result["compared"]["degraded_reads_not_exact"]["value"] == 0


def test_altered_parity_fails_both_cells(on_cpu):
    """The stored parity is looked at in the read cell too: a pool whose
    parity is not the profile's rebuilds bytes no other Ceph would."""
    with faults.plant("altered_parity"):
        for cell in (READ, WRITE):
            result = run_within_limit(cell)
            _fails_on(result, "parity_shards_differ")
