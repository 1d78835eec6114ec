"""CPU rehearsal of chip_smoke.py: the phases at toy size.

The script itself demands a TPU and has no option that says otherwise;
the rehearsal injects the platform from here (``chip_smoke.PLATFORM``) and
stubs the two checks that only a chip can meet (the Pallas branches of
the ``default_backend() == "tpu"`` gates).  Everything else — the cluster,
the bencher, the oracles, the zero-fallback counters, the last line — is
the code the chip runs.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from ceph_tpu.common import failpoint
from ceph_tpu.ops import telemetry

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))

TOY = chip_smoke.Sizes(
    n_osds=4, k=2, m=2, obj_size=64 << 10, depth=4, n_objects=6,
    degraded_min=1, hosts=8, per_host=4, n_pgs=2048, oracle_sample=64,
    epoch_sample=16, mesh_stripes=64)


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    for name in ("prove_kernels", "prove_mesh_kernels"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda facts: {"skipped": "cpu rehearsal"})
    # keep the rehearsal's compiles out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # the counters the smoke demands to be zero are process-wide sinks,
    # and a chip run starts with a fresh process
    telemetry.reset()


def _lines(capsys) -> list[str]:
    return capsys.readouterr().out.strip().splitlines()


def _phases(lines: list[str]) -> dict[str, dict]:
    """{"served_ec": {...}, ...} from the `phase <name> <json>` lines,
    in the order they were printed."""
    return {name: json.loads(facts) for _tag, name, facts in (
        ln.split(" ", 2) for ln in lines if ln.startswith("phase "))}


def _assert_last_line(line: str, count: int | None = None) -> None:
    last = json.loads(line)
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert isinstance(last["device"]["kind"], str)
    if count is not None:
        assert last["device"]["count"] == count


def test_exits_nonzero_without_a_chip():
    """As the driver runs it in a sandbox with no accelerator."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs 1 tpu device" in r.stderr
    assert "phase" not in r.stdout and '"ok"' not in r.stdout


def test_phases_at_toy_size(on_cpu, capsys):
    assert chip_smoke.main([], sizes=TOY) == 0
    lines = _lines(capsys)
    _assert_last_line(lines[-1])
    facts = _phases(lines)
    assert list(facts) == ["served_ec", "placement", "map_epochs"]
    ec = facts["served_ec"]
    assert ec["write"]["total_writes_or_reads"] == TOY.n_objects
    assert ec["read"]["errors"] == ec["degraded_read"]["errors"] == 0
    assert ec["degraded_read"]["decode_submits"] >= 1
    assert ec["parity"] == {"objects": TOY.n_objects, "shards_differ": 0,
                            "shards_compared": TOY.n_objects * TOY.m}
    assert facts["placement"]["xla_lanes_equal"] == TOY.n_pgs
    assert facts["map_epochs"]["mapping"]["fused_epochs"] == 4
    proof = json.loads(next(ln for ln in lines
                            if ln.startswith("proof ")).split(" ", 1)[1])
    assert not any(proof["counters"].values())


def test_forced_fallback_exits_nonzero(on_cpu, capsys):
    """One injected device fault on the encode channel is absorbed by
    the engine's ladder — every client op still succeeds — and that is
    exactly what the smoke must not let pass for the chip."""
    failpoint.set("dispatch.launch:ec_encode", "oneshot")
    try:
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="host stood in for the device"):
            chip_smoke.main([], sizes=TOY)
    finally:
        failpoint.clear("dispatch.launch:ec_encode")
    out = capsys.readouterr().out
    assert "phase served_ec" in out and '"ok"' not in out


def test_wrong_parity_exits_nonzero(on_cpu, capsys):
    """Every object reads back, degraded too, from parity that is not
    the profile's: the proof may not pass on that."""
    from perfbench import faults
    with faults.plant("altered_parity"):
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="stored parity shards"):
            chip_smoke.main([], sizes=TOY)
    out = capsys.readouterr().out
    assert "phase served_ec" not in out and '"ok"' not in out


def test_mesh_mode_runs_only_the_mesh_phase(on_cpu, capsys):
    import jax
    n = len(jax.devices())
    assert n >= 4, "tests/conftest.py provides 8 virtual CPU devices"
    assert chip_smoke.main(["--chips", "4"], sizes=TOY) == 0
    lines = _lines(capsys)
    _assert_last_line(lines[-1], count=n)
    facts = _phases(lines)
    assert list(facts) == ["mesh"]
    mesh = facts["mesh"]
    assert mesh["encode"]["placed_devices"] == n
    assert mesh["encode"]["result_devices"] == n
    assert mesh["dispatch"]["sharded_flushes"] >= 2


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_is_placed_from_outside(monkeypatch, env_dir):
    import jax
    from ceph_tpu.common.compile_cache import place_compile_cache
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_include_full_tracebacks_in_locations)
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert place_compile_cache() == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                ROOT, ".jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert place_compile_cache() == env_dir
            assert jax.config.jax_compilation_cache_dir == before[0]
        # without this a Pallas program's cache key depends on who called
        assert not jax.config.jax_include_full_tracebacks_in_locations
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          before[1])
