"""CPU rehearsal of whole benchmark runs at toy size, and the faults
that `correct` has to fail.

The benchmark demands a TPU and has no option that says otherwise; the
rehearsal injects the platform and a peaks entry from here
(``perfbench.harness.device``).  Everything else — the systems, the
closed loop, the counters, the plain references, the result line — is
the code the chip runs.  A time or rate read here is a count of work,
never a speed.
"""

import copy
import glob
import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import pytest

from perfbench import faults
from perfbench.harness import cell as cell_mod
from perfbench.harness import closed_loop, device, manifest
from perfbench.systems import osdmap_churn

ROOT = manifest.ROOT
#: the cells of BENCHMARK.json and, from perfbench/parked/, the 4 MiB
#: write cell, which waits for the program's reed_sol_van to be jerasure's
M = manifest.with_parked(manifest.load_manifest(), "ec84.write_4m_t16")
MB, LAT = "ec84.write_4m_t16", "ec84.write_4k_t1"
E1, E4 = "crush10k.weight_churn", "crush10k.weight_churn_x4"


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """The runs of this module share compiled programs, as the runs of
    one checkout do on the chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    old_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()
    if old_env is None:
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old_env


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"source": "tests", "cpu": {
        "bf16_flop_s": 1e12, "int8_op_s": 1e12, "hbm_bytes_s": 1e11,
        "hbm_bytes": 1e9}}))
    monkeypatch.setattr(device, "PLATFORM", "cpu")
    monkeypatch.setattr(device, "_PEAKS_FILE", str(peaks))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(osdmap_churn, "CACHE_DIR", str(tmp_path / "kept"))
    from ceph_tpu.ops import telemetry
    telemetry.reset()       # the fault counters are process-wide sinks
    return tmp_path


def toy(workload: str) -> manifest.Cell:
    c = copy.deepcopy(manifest.load_cell(M, workload))
    if c.config["system"] == "ec_pool":
        c.config["deployment"].update(k=2, m=2, osds=4)
        c.traffic.update(
            object_size=min(c.traffic["object_size"], 65536),
            depth=min(c.traffic["depth"], 4), precondition_acks=4,
            verify_objects=0)        # every acknowledged object
    else:
        c.config["deployment"].update(
            hosts=8, osds_per_host=4, pg_num=2048,
            kernel_mesh_devices=c.chips)
        c.traffic.update(verify_group_stride=1, verify_min_epochs=4,
                         verify_initial_pgs=16, warm_groups=1)
    c.traffic.update(trace_offset_s=0.1, trace_seconds=0.5)
    return c


def run(workload: str, trace: bool = False, seconds: float = 1.0,
        seed: int = 2**31 + 11) -> dict:
    out, err = io.StringIO(), io.StringIO()
    wanted = manifest.metrics_for(
        M, workload, "per_layer" if trace else "end_to_end")
    rc = cell_mod.run_loaded(toy(workload), wanted, seed, seconds, trace,
                             time.perf_counter(), out=out, err=err)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    result["_err"] = err.getvalue()
    result["_wanted"] = [m["name"] for m in wanted]
    return result


def assert_result_line(result: dict, trace: bool) -> None:
    keys = [k for k in result if not k.startswith("_")]
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "compared"
    assert ("breakdown" in keys) == trace
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    for name, m in result["metrics"].items():
        assert name in result["_wanted"]
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    # every number compared is printed beside its limit, last on stderr
    tail = result["_err"].strip().splitlines()[-len(result["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail)
    assert set(result["compared"]) == {
        ln.split()[1].rstrip(":") for ln in tail}


def test_exits_nonzero_without_a_chip():
    """As the driver runs it in a sandbox with no accelerator."""
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", E1, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs 1 tpu device" in r.stderr
    assert "correct" not in r.stdout


def test_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in M["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable] + M["command"][1:] + [
            "--workload", E1, "--seed", "1", "--seconds", "1",
            "--trace", "0"],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "correct" not in r.stdout


@pytest.fixture
def mended():
    """The EC cells on a program whose reed_sol_van is jerasure's.  The
    program's own coding matrix is not (PERF.md, Open questions): only
    its first column, of ones, is, so the 4 KiB cell, whose objects fill
    one data chunk, is right either way and the 4 MiB cell is parked."""
    with faults.mended():
        yield


@pytest.mark.parametrize("workload,trace", [(MB, False), (MB, True),
                                            (LAT, False)])
def test_write_cells_at_toy_size(on_cpu, mended, workload, trace):
    result = run(workload, trace)
    assert_result_line(result, trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    assert set(result["compared"]) == {
        "acked_objects_not_read_back", "parity_shards_differ",
        "stored_block_csums_differ", "host_stood_in_for_device",
        "failed_ops"}
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values())
    if not trace:
        assert set(result["metrics"]) == set(result["_wanted"])
    else:
        # nothing ran on a TPU here: trace readers are silent, never 0
        assert "gf_encode_roofline" not in result["metrics"]
        assert result["metrics"]["setup.compiles_in_window.mb"][
            "value"] == 0.0
        assert result["metrics"]["engine.coalesce_factor"]["value"] >= 1.0
    assert not glob.glob(str(on_cpu / "perfbench-*"))   # stores removed


@pytest.mark.parametrize("workload", [LAT, MB])
def test_write_cells_on_the_program_as_it_is(on_cpu, workload):
    """Whatever the program's coding matrix is, the parity check is the
    only number it moves: every object reads back and every stored
    checksum is its block's crc32.  The 4 KiB cell is correct as the
    program is.  (In the 4 MiB cell every parity shard differs from
    jerasure's today; the assertion holds on a mended program too.)"""
    result = run(workload)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    parity = compared.pop("parity_shards_differ")
    assert all(v == 0 for v in compared.values()), compared
    assert result["correct"] is (parity == 0)
    if workload == LAT:
        assert result["correct"] is True


@pytest.mark.parametrize("workload,trace", [(E1, False), (E4, True)])
def test_epoch_cells_at_toy_size(on_cpu, workload, trace):
    result = run(workload, trace, seconds=2.0)
    assert_result_line(result, trace)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 8 and result["attempted"] % 4 == 0
    if trace:
        assert result["metrics"]["mapping.fused_epoch_share"][
            "value"] == 100.0
        assert "mesh_devices_short" in result["compared"]


@pytest.mark.parametrize("workload,fault,failing", [
    (MB, "altered_write", "acked_objects_not_read_back"),
    (LAT, "lost_write", "acked_objects_not_read_back"),
    (MB, "altered_parity", "parity_shards_differ"),
    (LAT, "altered_parity", "parity_shards_differ"),
    (MB, "altered_csum", "stored_block_csums_differ"),
    (E1, "stale_state", "changed_set_differs_from_reference"),
    (E1, "altered_answer", "rows_differ_from_reference"),
    (E1, "half_delta", "changed_set_differs_from_reference"),
    (E1, "hidden_rows", "held_pgs_not_answered"),
])
def test_a_planted_fault_reads_not_correct(on_cpu, mended, workload, fault,
                                           failing):
    system = manifest.load_cell(M, workload).config["system"]
    assert fault in faults.FAULTS[system]
    with faults.plant(fault):
        result = run(workload, seconds=2.0)
    assert result["correct"] is False
    c = result["compared"][failing]
    assert c["value"] > c["limit"], result["compared"]
    assert f"compared {failing}:" in result["_err"]
    assert "NOT CORRECT" in result["_err"]


def test_store_is_removed_when_the_window_fails(on_cpu, monkeypatch):
    def broken(*_a, **_kw):
        raise RuntimeError("window broke")
    monkeypatch.setattr(closed_loop, "run", broken)
    with pytest.raises(RuntimeError, match="window broke"):
        run(LAT)
    assert not glob.glob(str(on_cpu / "perfbench-*"))


@pytest.mark.parametrize("opens_after,least,most", [(0.0, 0.2, 1.5),
                                                     (0.6, 0.6, 2.2),
                                                     (9.0, 3.0, 6.0)])
def test_traced_slice_goes_on_until_its_gate_opens(tmp_path, opens_after,
                                                   least, most):
    """A slice lasts `seconds`, then until the gate says it holds what
    its readers need, and never past `max_seconds`."""
    def make_gate():
        t0 = time.perf_counter()        # the slice opens here
        return lambda: time.perf_counter() - t0 >= opens_after

    # the limits leave room for a loaded host: each case still ends well
    # apart from the next one's least
    tracer = cell_mod.SliceTracer(str(tmp_path), 0.0, 0.2, max_seconds=3.0,
                                  make_gate=make_gate)
    tracer.start()
    tracer.join(60.0)
    tracer.finish()
    t_a, t_b = tracer.t
    assert least <= t_b - t_a <= most
