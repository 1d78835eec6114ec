"""Fault-injected device runtime (common/failpoint.py + the dispatch
engine's supervised recovery).

The load-bearing claims, each pinned here:

  * failpoint framework — named points with always/prob/oneshot/nth
    modes, channel qualifiers, deterministic under seed(), driven by
    the ``kernel_failpoints`` option and the ``failpoint set/clear/ls``
    admin commands;
  * retry ladder — a transient device fault is retried with bounded
    exponential backoff and heals invisibly (bit-exact result,
    counters tell the story); permanent errors fan immediately;
  * circuit breaker — consecutive device failures open a per-channel
    breaker, batches route through the BIT-EXACT host oracle
    (ec_encode_ref / host pattern decode / scalar CRUSH / numpy
    ladder), a background probe re-closes it when the device heals,
    and traffic returns to the device path;
  * thread supervision — a dead dispatch/completion run-loop is
    revived and re-fans its in-flight batches; past the restart budget
    the engine WEDGES LOUDLY: every waiter gets EngineWedgedError and
    flush() raises instead of silently timing out (the PR 11 satellite
    regression);
  * degraded-mode visibility — fault counters, the
    ceph_kernel_fallback_* / ceph_kernel_breaker_* prometheus
    families, the MMgrReport v4 faults tail, and the mgr's
    KERNEL_DEGRADED health warning;
  * client resend hardening — map-change resends of the same op back
    off exponentially with jitter (first resend immediate), surfaced
    in the client perf dump.

Geometry reuses test_dispatch's K1/M1 (k=4, m=2) so the process-global
jit cache is shared rather than grown.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import failpoint
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine, EngineWedgedError


@pytest.fixture(autouse=True)
def _clean_failpoints():
    """Failpoints are process-global: never leak armed points into (or
    out of) a test."""
    failpoint.clear()
    yield
    failpoint.clear()


def _engine(**kw):
    eng = DeviceDispatchEngine(stats=telemetry.DispatchStats(), **kw)
    eng.fault_backoff_ms = 1.0
    eng.fault_backoff_max_ms = 5.0
    eng.probe_interval = 0.05
    return eng


def _dbl(batch):
    return np.asarray(batch) * 2


def _wait_breaker(eng, channel, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.breaker_states().get(channel) == state:
            return True
        time.sleep(0.02)
    return False


# -- framework ----------------------------------------------------------------

class TestFailpointFramework:
    def test_modes(self):
        failpoint.seed(1234)
        failpoint.set("site.a", "always")
        with pytest.raises(failpoint.InjectedDeviceFault):
            failpoint.hit("site.a")
        failpoint.set("site.a", "oneshot")
        with pytest.raises(failpoint.InjectedDeviceFault):
            failpoint.hit("site.a")
        failpoint.hit("site.a")          # disarmed itself
        failpoint.set("site.b", "nth:3")
        failpoint.hit("site.b")
        failpoint.hit("site.b")
        with pytest.raises(failpoint.InjectedDeviceFault):
            failpoint.hit("site.b")
        failpoint.hit("site.b")          # fired once, gone
        failpoint.set("site.c", "prob:1.0")
        with pytest.raises(failpoint.InjectedDeviceFault):
            failpoint.hit("site.c")
        failpoint.set("site.c", "prob:0.0")
        for _ in range(50):
            failpoint.hit("site.c")

    def test_channel_qualifier_and_ls(self):
        failpoint.set("dispatch.launch:ec_encode", "always")
        failpoint.hit("dispatch.launch", tag="ec_decode")   # other lane
        with pytest.raises(failpoint.InjectedDeviceFault):
            failpoint.hit("dispatch.launch", tag="ec_encode")
        rows = failpoint.ls()
        assert rows["dispatch.launch:ec_encode"]["fires"] == 1
        assert rows["dispatch.launch:ec_encode"]["mode"] == "always"
        failpoint.clear("dispatch.launch:ec_encode")
        assert failpoint.ls() == {}

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError):
            failpoint.set("x", "sometimes")
        with pytest.raises(ValueError):
            failpoint.set("x", "prob:1.5")
        with pytest.raises(ValueError):
            failpoint.set("x", "nth:0")
        with pytest.raises(ValueError):
            failpoint.configure("just-a-name")
        assert failpoint.ls() == {}      # nothing half-applied

    def test_config_option_drives_registry(self):
        from ceph_tpu.common.config import Config
        conf = Config()
        failpoint.configure_from_conf(conf)
        conf.set("kernel_failpoints",
                 "dispatch.launch:ec_encode=prob:0.5;"
                 "dispatch.device_put=oneshot")
        rows = failpoint.ls()
        assert rows["dispatch.launch:ec_encode"]["mode"] == "prob:0.5"
        assert rows["dispatch.device_put"]["mode"] == "oneshot"
        conf.set("kernel_failpoints", "")
        assert failpoint.ls() == {}

    def test_context_construction_keeps_programmatic_points(self):
        """The registry is process-global but contexts come and go: a
        daemon revived mid-storm applies its default-EMPTY
        kernel_failpoints spec, and that must not disarm points the
        chaos mode (or an admin) armed via set() — only replace the
        points the option itself owns."""
        from ceph_tpu.common.context import CephTpuContext
        failpoint.set("dispatch.launch:ec_encode", "prob:0.25")
        ctx = CephTpuContext("fp-survive-test")   # applies empty spec
        assert "dispatch.launch:ec_encode" in failpoint.ls()
        # the option still owns (and replaces) its own points...
        ctx.conf.set("kernel_failpoints", "dispatch.device_put=always")
        ctx.conf.set("kernel_failpoints", "")
        rows = failpoint.ls()
        assert "dispatch.device_put" not in rows
        # ...while the storm's point rides through untouched
        assert "dispatch.launch:ec_encode" in rows
        # set()/clear() take ownership back from the option
        ctx.conf.set("kernel_failpoints", "site.conf=always")
        failpoint.set("site.conf", "oneshot")
        ctx.conf.set("kernel_failpoints", "")
        assert failpoint.ls()["site.conf"]["mode"] == "oneshot"

    def test_admin_commands(self):
        from ceph_tpu.common.context import CephTpuContext
        ctx = CephTpuContext("fp-admin-test")
        assert ctx.admin.execute("failpoint set", name="site.x",
                                 mode="always") == "ok"
        assert "site.x" in ctx.admin.execute("failpoint ls")
        assert ctx.admin.execute("failpoint clear",
                                 name="site.x") == "ok"
        assert ctx.admin.execute("failpoint ls") == {}
        dump = ctx.admin.execute("dump_fault_stats")
        assert set(dump) == {"encode", "decode"}
        assert "breaker_states" in dump["encode"]

    def test_configure_ownership_is_per_context(self):
        """Contexts COEXIST in one process: a second context applying
        its (default-empty or own) kernel_failpoints spec must replace
        only the points ITS option armed — never another context's."""
        from ceph_tpu.common.context import CephTpuContext
        a = CephTpuContext("fp-owner-a")
        a.conf.set("kernel_failpoints", "dispatch.launch=prob:0.2")
        # constructing B applies ITS default-empty spec: A's survives
        b = CephTpuContext("fp-owner-b")
        assert "dispatch.launch" in failpoint.ls()
        b.conf.set("kernel_failpoints", "site.b=always")
        b.conf.set("kernel_failpoints", "")
        rows = failpoint.ls()
        assert "site.b" not in rows          # B replaced its own...
        assert "dispatch.launch" in rows     # ...and left A's alone
        a.conf.set("kernel_failpoints", "")
        assert "dispatch.launch" not in failpoint.ls()

    def test_thread_death_points_inject_base_exception(self):
        failpoint.set("dispatch.complete_thread_death", "oneshot")
        with pytest.raises(failpoint.InjectedThreadDeath):
            failpoint.hit("dispatch.complete_thread_death")
        # and except Exception cannot absorb it
        assert not isinstance(failpoint.InjectedThreadDeath("x"),
                              Exception)


# -- engine recovery (pure numpy fns — no jit cost) ---------------------------

class TestEngineRecovery:
    def test_transient_fault_retried_bit_exact(self):
        eng = _engine()
        try:
            failpoint.set("dispatch.launch:chan", "oneshot")
            data = np.arange(12, dtype=np.int64).reshape(6, 2)
            got = eng.submit(("k",), _dbl, data, label="chan",
                             fallback=_dbl).result(10)
            assert (got == data * 2).all()
            d = eng.stats.fault_dump()
            assert d["retries"] == 1 and d["retry_successes"] == 1
            assert d["fallback_batches"] == 0
            assert d["breaker_states"] == {}
        finally:
            eng.stop()

    def test_permanent_error_fans_immediately(self):
        eng = _engine()
        try:
            def bad(batch):
                raise ValueError("shape nonsense")
            f = eng.submit(("k",), bad, np.ones((2, 2)), label="chan",
                           fallback=_dbl)
            with pytest.raises(ValueError):
                f.result(10)
            assert eng.stats.fault_dump()["retries"] == 0
        finally:
            eng.stop()

    def test_persistent_fault_serves_fallback_then_probe_recloses(self):
        eng = _engine()
        eng.breaker_threshold = 2
        try:
            failpoint.set("dispatch.launch:chan", "always")
            for i in range(5):
                got = eng.submit(("k",), _dbl,
                                 np.full((3, 2), i, dtype=np.int64),
                                 label="chan", fallback=_dbl).result(10)
                assert (got == i * 2).all()   # bit-exact degradation
            d = eng.stats.fault_dump()
            assert d["breaker_opens"] == 1, d
            assert d["fallback_batches"] >= 2, d
            assert eng.breaker_states()["chan"] == \
                telemetry.BREAKER_OPEN
            # faults clear -> the background probe re-closes and the
            # device path resumes
            failpoint.clear()
            assert _wait_breaker(eng, "chan", telemetry.BREAKER_CLOSED)
            d = eng.stats.fault_dump()
            assert d["breaker_closes"] == 1 and d["probe_successes"] >= 1
            before = eng.stats.fault_dump()["fallback_batches"]
            got = eng.submit(("k",), _dbl,
                             np.full((2, 2), 9, dtype=np.int64),
                             label="chan", fallback=_dbl).result(10)
            assert (got == 18).all()
            assert eng.stats.fault_dump()["fallback_batches"] == before
        finally:
            eng.stop()

    def test_probe_failure_keeps_breaker_open(self):
        eng = _engine()
        eng.breaker_threshold = 1
        eng.fault_max_retries = 0
        try:
            failpoint.set("dispatch.launch:chan", "always")
            eng.submit(("k",), _dbl, np.ones((2, 2), dtype=np.int64),
                       label="chan", fallback=_dbl).result(10)
            assert _wait_breaker(eng, "chan", telemetry.BREAKER_OPEN)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if eng.stats.fault_dump()["probe_failures"] >= 2:
                    break
                time.sleep(0.02)
            d = eng.stats.fault_dump()
            assert d["probe_failures"] >= 2, d
            assert d["breaker_closes"] == 0, d
            assert eng.breaker_states()["chan"] in (
                telemetry.BREAKER_OPEN, telemetry.BREAKER_HALF_OPEN)
        finally:
            failpoint.clear()
            eng.stop()

    def test_no_fallback_error_fans_after_retries(self):
        eng = _engine()
        try:
            failpoint.set("dispatch.launch:chan", "always")
            f = eng.submit(("k",), _dbl, np.ones((2, 2)), label="chan")
            with pytest.raises(failpoint.InjectedDeviceFault):
                f.result(10)
            d = eng.stats.fault_dump()
            assert d["retries"] == eng.fault_max_retries
        finally:
            failpoint.clear()
            eng.stop()

    def test_breaker_channels_are_independent(self):
        eng = _engine()
        eng.breaker_threshold = 1
        eng.fault_max_retries = 0
        try:
            failpoint.set("dispatch.launch:sick", "always")
            eng.submit(("a",), _dbl, np.ones((2, 2), dtype=np.int64),
                       label="sick", fallback=_dbl).result(10)
            assert _wait_breaker(eng, "sick", telemetry.BREAKER_OPEN)
            got = eng.submit(("b",), _dbl,
                             np.full((2, 2), 4, dtype=np.int64),
                             label="healthy", fallback=_dbl).result(10)
            assert (got == 8).all()
            states = eng.breaker_states()
            assert states.get("healthy", telemetry.BREAKER_CLOSED) \
                == telemetry.BREAKER_CLOSED
            assert eng.stats.fault_dump()["breaker_opens"] == 1
        finally:
            failpoint.clear()
            eng.stop()

    def test_thread_death_supervision_refans_in_flight(self):
        """A dying completion run-loop is revived and the queued work
        is re-fanned — waiters never notice beyond latency."""
        eng = _engine()
        try:
            # prime threads so the failpoint hits a RUNNING loop
            eng.submit(("k",), _dbl, np.ones((2, 2), dtype=np.int64),
                       label="chan").result(10)
            failpoint.set("dispatch.complete_thread_death", "oneshot")
            futs = [eng.submit(("k",), _dbl,
                               np.full((2, 2), i, dtype=np.int64),
                               label="chan") for i in range(4)]
            for i, f in enumerate(futs):
                assert (f.result(10) == i * 2).all()
            d = eng.stats.fault_dump()
            assert d["thread_deaths"] >= 1 and d["thread_restarts"] >= 1
            assert eng.flush(10)
        finally:
            eng.stop()

    def test_dispatch_thread_death_also_supervised(self):
        eng = _engine()
        try:
            failpoint.set("dispatch.dispatch_thread_death", "oneshot")
            got = eng.submit(("k",), _dbl,
                             np.full((3, 2), 5, dtype=np.int64),
                             label="chan").result(10)
            assert (got == 10).all()
            assert eng.stats.fault_dump()["thread_restarts"] >= 1
        finally:
            eng.stop()

    def test_restart_budget_decays_after_healthy_window(self):
        """The budget bounds death STORMS, not isolated recovered
        deaths over an engine's lifetime: a run-loop healthy past
        thread_restart_window since its last death earns the budget
        back, so deaths spread out never wedge."""
        eng = _engine()
        eng.thread_restarts = 1
        eng.thread_restart_window = 0.05
        try:
            for i in range(3):     # 3 isolated deaths > budget of 1
                failpoint.set("dispatch.complete_thread_death",
                              "oneshot")
                got = eng.submit(("k",), _dbl,
                                 np.full((2, 2), i + 1, dtype=np.int64),
                                 label="chan").result(10)
                assert (got == 2 * (i + 1)).all()
                # wait out the injected death AND the healthy window
                deadline = time.monotonic() + 5
                while (failpoint.ls() and time.monotonic() < deadline):
                    time.sleep(0.01)
                time.sleep(0.1)
            assert eng.stats.fault_dump()["thread_deaths"] >= 3
            assert not eng._wedged
            assert eng.flush(10.0)
        finally:
            failpoint.clear()
            eng.stop()

    def test_wedge_is_loud_not_silent(self):
        """PR 11 satellite regression: restart budget exhausted ->
        every waiter gets EngineWedgedError, flush() RAISES instead of
        silently timing out, stop() reports failure, and new submits
        run inline rather than hanging."""
        eng = _engine()
        eng.thread_restarts = 0
        try:
            failpoint.set("dispatch.complete_thread_death", "always")
            f = eng.submit(("k",), _dbl, np.ones((2, 2)), label="chan")
            with pytest.raises(EngineWedgedError):
                f.result(10)
            failpoint.clear()
            with pytest.raises(EngineWedgedError):
                eng.flush(2.0)
            assert eng.stats.fault_dump()["thread_deaths"] >= 1
            # new submits are served inline — never dropped, never hung
            got = eng.submit(("k",), _dbl,
                             np.full((2, 2), 7, dtype=np.int64),
                             label="chan").result(5)
            assert (got == 14).all()
            assert eng.stop() is False    # wedged engines report it
        finally:
            failpoint.clear()
            eng.stop()

    def test_fallback_preserves_per_key_order(self):
        """Breaker-open fallback batches still deliver per-key in
        submission order (the OSD's log/commit ordering contract)."""
        eng = _engine()
        eng.breaker_threshold = 1
        eng.fault_max_retries = 0
        try:
            failpoint.set("dispatch.launch:chan", "always")
            eng.submit(("k",), _dbl, np.ones((2, 2), dtype=np.int64),
                       label="chan", fallback=_dbl).result(10)
            assert _wait_breaker(eng, "chan", telemetry.BREAKER_OPEN)
            order: list[int] = []
            lock = threading.Lock()
            futs = []
            for i in range(16):
                fut = eng.submit(("k",), _dbl,
                                 np.full((2, 2), i, dtype=np.int64),
                                 label="chan", fallback=_dbl)
                fut.add_done_callback(
                    lambda f, i=i: (lock.acquire(timeout=5),
                                    order.append(i), lock.release()))
                futs.append(fut)
            for f in futs:
                f.result(10)
            eng.flush(10)
            deadline = time.monotonic() + 5
            while len(order) < 16 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert order == list(range(16))
        finally:
            failpoint.clear()
            eng.stop()

    def test_device_put_boundary_fires_on_unmeshed_engines(self):
        """The h2d boundary failpoint must be reachable on
        single-device (unmeshed) engines too: DeviceChaos arms
        dispatch.device_put, and chaos coverage must not silently
        shrink to meshed hosts."""
        eng = _engine()
        try:
            failpoint.set("dispatch.device_put:chan", "oneshot")
            got = eng.submit(("k",), _dbl,
                             np.full((2, 2), 3, dtype=np.int64),
                             label="chan", fallback=_dbl).result(10)
            assert (got == 6).all()
            assert failpoint.ls() == {}      # the oneshot was consumed
            assert eng.stats.fault_dump()["retries"] >= 1
        finally:
            eng.stop()

    def test_fallback_batches_keep_phase_ledger_clean(self):
        """Breaker-routed batches time the HOST oracle under the
        launch anchor — recording them would let an outage dominate
        the steady device phase histograms with host-path runtimes
        (the same rule the recovery ladder already applies)."""
        eng = _engine()
        eng.breaker_threshold = 1
        eng.fault_max_retries = 0
        try:
            failpoint.set("dispatch.launch:chan", "always")
            eng.submit(("k",), _dbl, np.ones((2, 2), dtype=np.int64),
                       label="chan", fallback=_dbl).result(10)
            assert _wait_breaker(eng, "chan", telemetry.BREAKER_OPEN)
            before = eng.stats.phases.dump(False)["phases"]
            for i in range(3):
                eng.submit(("k",), _dbl,
                           np.full((2, 2), i, dtype=np.int64),
                           label="chan", fallback=_dbl).result(10)
            after = eng.stats.phases.dump(True)
            assert after["phases"] == before
            assert after["recent"] == []
        finally:
            failpoint.clear()
            eng.stop()

    def test_future_delivery_is_first_wins(self):
        """_deliver must be idempotent: _wedge racing the live
        completion thread (or a revived loop re-fanning its batch)
        must never overwrite a delivered result with a contradictory
        outcome — an acked op's value flipping to an error after its
        callbacks already fired, or the reverse."""
        from ceph_tpu.ops.dispatch import DispatchFuture
        f = DispatchFuture()
        seen = []
        f.add_done_callback(lambda fut: seen.append(fut.exception()))
        f._deliver(5, None)
        f._deliver(None, RuntimeError("late wedge"))
        assert f.result(1) == 5 and f.exception(1) is None
        assert seen == [None]          # callbacks fired exactly once
        # and the reverse ordering: a delivered error stays an error
        g = DispatchFuture()
        g._deliver(None, RuntimeError("real failure"))
        g._deliver(7, None)
        with pytest.raises(RuntimeError):
            g.result(1)

    def test_base_exception_continuation_cannot_strand_batch(self):
        """A done-callback raising past Exception (SystemExit-class)
        escapes _deliver's Exception-only shield AFTER the batch was
        popped from _inflight — it must not kill the completion loop
        mid-fan-out, or the batch's remaining futures would hang
        forever with no thread death able to re-fan them."""
        def slow_dbl(batch):
            time.sleep(0.05)
            return np.asarray(batch) * 2
        eng = _engine(max_delay_us=200000)
        try:
            # occupy the pipeline so the next submits coalesce into
            # ONE batch (idle engines flush each submit alone)
            warm = eng.submit(("warm",), slow_dbl,
                              np.ones((2, 2), dtype=np.int64),
                              label="chan")
            futs = [eng.submit(("k",), _dbl,
                               np.full((2, 2), i, dtype=np.int64),
                               label="chan") for i in range(4)]
            futs[0].add_done_callback(
                lambda f: (_ for _ in ()).throw(SystemExit("boom")))
            warm.result(10)
            for i, f in enumerate(futs):
                assert (f.result(10) == i * 2).all()
            assert eng.stats.fault_dump()["thread_deaths"] == 0
            # the loop is alive and serving
            got = eng.submit(("k2",), _dbl,
                             np.full((2, 2), 9, dtype=np.int64),
                             label="chan").result(10)
            assert (got == 18).all()
            assert eng.flush(10)
        finally:
            eng.stop()

    def test_pre_assembly_failure_cannot_leak_or_strand(self):
        """A failure BEFORE batch assembly (mesh lookup, bucketing,
        breaker routing) must fan to the batch's futures like any
        build error — not escape _dispatch_batch with _building
        incremented and the reqs already partitioned out of _pending,
        which would strand the waiters and make flush() time out
        silently forever."""
        eng = _engine()
        try:
            calls = {"n": 0}

            def broken_mesh_lookup():
                calls["n"] += 1
                if calls["n"] == 1:       # only the dispatch-path call
                    raise MemoryError("mesh lookup under pressure")
                return None
            eng._mesh_placement = broken_mesh_lookup
            # MemoryError is transient: the completion-thread retry
            # ladder rebuilds from reqs (no placement) and succeeds
            got = eng.submit(("k",), _dbl,
                             np.full((3, 2), 4, dtype=np.int64),
                             label="chan", fallback=_dbl).result(10)
            assert (got == 8).all()
            d = eng.stats.fault_dump()
            assert d["retries"] >= 1 and d["retry_successes"] >= 1
            assert eng.flush(10)          # nothing leaked in _building
            assert eng._building == 0
        finally:
            eng.stop()


# -- per-channel fallback bit-exactness (the chaos-gate oracle compare) -------

class TestChannelBitExactness:
    def _open_breaker(self, eng, channel):
        eng.breaker_threshold = 1
        eng.fault_max_retries = 0
        failpoint.set(f"dispatch.launch:{channel}", "always")

    def test_encode_channel_fallback_matches_device(self):
        from ceph_tpu.ec import registry_instance
        codec = registry_instance().factory(
            "jerasure", {"technique": "reed_sol_van", "k": "4",
                         "m": "2", "runtime": "tpu"})
        rng = np.random.default_rng(11)
        data = rng.integers(0, 256, (7, 4, 512), dtype=np.uint8)
        eng = _engine()
        try:
            device = np.asarray(
                codec.submit_chunks(eng, data).result(120))
            self._open_breaker(eng, "ec_encode")
            # trip the breaker, then compare the oracle-served result
            codec.submit_chunks(eng, data).result(120)
            assert _wait_breaker(eng, "ec_encode",
                                 telemetry.BREAKER_OPEN)
            degraded = np.asarray(
                codec.submit_chunks(eng, data).result(120))
            assert (degraded == device).all()
            assert eng.stats.fault_dump()["fallback_batches"] >= 1
        finally:
            failpoint.clear()
            eng.stop()

    def test_decode_channel_fallback_matches_device(self):
        from ceph_tpu.ec import registry_instance
        codec = registry_instance().factory(
            "jerasure", {"technique": "reed_sol_van", "k": "4",
                         "m": "2", "runtime": "tpu"})
        rng = np.random.default_rng(13)
        stripes = rng.integers(0, 256, (6, 4, 512), dtype=np.uint8)
        chosen, targets = (0, 2, 4, 5), (1, 3)   # mixed-pattern decode
        chosen2, targets2 = (1, 2, 3, 4), (0,)
        eng = _engine()
        try:
            dev1 = np.asarray(codec.submit_decode_chunks(
                eng, chosen, stripes, targets).result(120))
            dev2 = np.asarray(codec.submit_decode_chunks(
                eng, chosen2, stripes, targets2).result(120))
            self._open_breaker(eng, "ec_decode")
            codec.submit_decode_chunks(
                eng, chosen, stripes, targets).result(120)
            assert _wait_breaker(eng, "ec_decode",
                                 telemetry.BREAKER_OPEN)
            deg1 = np.asarray(codec.submit_decode_chunks(
                eng, chosen, stripes, targets).result(120))
            deg2 = np.asarray(codec.submit_decode_chunks(
                eng, chosen2, stripes, targets2).result(120))
            assert (deg1 == dev1).all() and (deg2 == dev2).all()
        finally:
            failpoint.clear()
            eng.stop()

    def test_crush_channel_fallback_matches_device(self):
        from ceph_tpu.ops.dispatch import submit_flat_firstn
        rng = np.random.default_rng(17)
        n_osds = 24
        ids = np.arange(n_osds, dtype=np.int32)
        weights = np.full(n_osds, 0x10000, dtype=np.int64)
        reweight = np.full(n_osds, 0x10000, dtype=np.int64)
        reweight[5] = 0
        xs = rng.integers(0, 2**32, 64, dtype=np.uint32)
        eng = _engine()
        try:
            device = np.asarray(submit_flat_firstn(
                eng, xs, ids, weights, reweight,
                numrep=3).result(300))
            self._open_breaker(eng, "crush_firstn")
            submit_flat_firstn(eng, xs, ids, weights, reweight,
                               numrep=3).result(300)
            assert _wait_breaker(eng, "crush_firstn",
                                 telemetry.BREAKER_OPEN, timeout=30)
            degraded = np.asarray(submit_flat_firstn(
                eng, xs, ids, weights, reweight,
                numrep=3).result(300))
            assert (degraded == device).all()
        finally:
            failpoint.clear()
            eng.stop()

    def test_ladder_channel_fallback_matches_device(self):
        from ceph_tpu.ops import placement_kernel as pk
        from ceph_tpu.ops.dispatch import submit_finish_ladder
        rng = np.random.default_rng(19)
        n, w, pairs, m_osd = 48, 4, 2, 10
        raw = rng.integers(0, m_osd, (n, w)).astype(np.int32)
        raw[rng.random((n, w)) < 0.1] = pk.NONE
        operands = pk.LadderOperands(
            raw=raw,
            pps=rng.integers(0, 2**32, n, dtype=np.uint32),
            raw_len=np.full(n, w, dtype=np.int32),
            up_rows=rng.integers(0, m_osd, (n, w)).astype(np.int32),
            up_len=rng.integers(0, w + 1, n).astype(np.int32),
            items=rng.integers(-1, m_osd,
                               (n, pairs, 2)).astype(np.int32),
            temp_rows=rng.integers(-1, m_osd, (n, w)).astype(np.int32),
            temp_len=(rng.integers(0, w + 1, n)
                      * (rng.random(n) < 0.3)).astype(np.int32),
            ptemp=np.where(rng.random(n) < 0.2,
                           rng.integers(0, m_osd, n),
                           -1).astype(np.int32),
            state=rng.integers(0, 4, m_osd).astype(np.int32),
            weight=(rng.integers(0, 2, m_osd)
                    * 0x10000).astype(np.int64),
            affinity=np.where(rng.random(m_osd) < 0.5, 0x10000,
                              rng.integers(0, 0x10000,
                                           m_osd)).astype(np.int32),
            max_osd=m_osd, erasure=False, width=w)
        eng = _engine()
        try:
            device = np.asarray(
                submit_finish_ladder(eng, operands).result(300))
            self._open_breaker(eng, "pg_finish")
            submit_finish_ladder(eng, operands).result(300)
            assert _wait_breaker(eng, "pg_finish",
                                 telemetry.BREAKER_OPEN, timeout=30)
            degraded = np.asarray(
                submit_finish_ladder(eng, operands).result(300))
            assert (degraded == device).all()
            # and the standalone oracle agrees (ladder_ref twin)
            ref = pk.ladder_ref(operands.raw, *operands.aux(),
                                *operands.osd_operands(), erasure=False)
            assert (ref == device).all()
        finally:
            failpoint.clear()
            eng.stop()


# -- client resend backoff ----------------------------------------------------

class TestClientResendBackoff:
    def _client(self):
        from ceph_tpu.client.rados import RadosClient
        return RadosClient("client-backoff-test", ms_type="loopback")

    def test_first_resend_immediate_then_backoff(self):
        from types import SimpleNamespace
        c = self._client()
        try:
            c.ctx.conf.set("client_resend_backoff_ms", 30.0)
            sent: list[float] = []
            c._send_op = lambda w: sent.append(time.monotonic())
            from ceph_tpu.client.rados import _Waiter
            w = _Waiter(SimpleNamespace(tid=1, qos_tenant=""), 0, True)
            c._waiters[1] = w
            t0 = time.monotonic()
            c._resend_op(w)                      # 1st: immediate
            assert len(sent) == 1 and sent[0] - t0 < 0.02
            c._resend_op(w)                      # 2nd: deferred
            assert len(sent) == 1
            deadline = time.monotonic() + 5
            while len(sent) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(sent) == 2
            assert sent[1] - t0 >= 0.014         # >= base/2 (jitter floor)
            pd = c.ctx.perf.dump()
            obj = pd[f"objecter.{c.client_id}"]
            assert obj["op_resends"] == 2
            assert obj["op_resend_backoffs"] == 1
        finally:
            c.shutdown()

    def test_backoff_grows_and_caps(self):
        from types import SimpleNamespace
        c = self._client()
        try:
            c.ctx.conf.set("client_resend_backoff_ms", 10.0)
            c.ctx.conf.set("client_resend_backoff_max_ms", 25.0)
            c._send_op = lambda w: None
            from ceph_tpu.client.rados import _Waiter
            w = _Waiter(SimpleNamespace(tid=2, qos_tenant=""), 0, True)
            w.resends = 9                        # deep retry history
            c._waiters[2] = w
            t0 = time.monotonic()
            c._resend_op(w)
            with c._lock:
                (due, _w2), = c._resend_q
            # capped: jittered delay in [cap/2, cap]
            assert 0.010 <= due - t0 <= 0.027
        finally:
            c.shutdown()

    def test_completed_ops_drop_from_resend_queue(self):
        from types import SimpleNamespace
        c = self._client()
        try:
            c.ctx.conf.set("client_resend_backoff_ms", 20.0)
            sent = []
            c._send_op = lambda w: sent.append(w)
            from ceph_tpu.client.rados import _Waiter
            w = _Waiter(SimpleNamespace(tid=3, qos_tenant=""), 0, True)
            w.resends = 1
            c._waiters[3] = w
            c._resend_op(w)
            del c._waiters[3]                    # reply landed
            time.sleep(0.1)
            assert sent == []                    # never resent
        finally:
            c.shutdown()

    def test_epoch_storm_coalesces_deferred_resends(self):
        """A map storm while a resend is already deferred must NOT
        queue duplicate rows: the queued row targets from the newest
        map when it fires, so N epochs -> at most one queued send (and
        op_resends counts sends scheduled, not epochs observed)."""
        from types import SimpleNamespace
        c = self._client()
        try:
            c.ctx.conf.set("client_resend_backoff_ms", 30.0)
            sent = []
            c._send_op = lambda w: sent.append(time.monotonic())
            from ceph_tpu.client.rados import _Waiter
            w = _Waiter(SimpleNamespace(tid=1, qos_tenant=""), 0, True)
            c._waiters[1] = w
            c._resend_op(w)                      # 1st: immediate
            for _ in range(5):                   # epoch storm
                c._resend_op(w)
            with c._lock:
                assert len(c._resend_q) == 1     # coalesced, not 6 rows
            deadline = time.monotonic() + 5
            while len(sent) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            time.sleep(0.08)                     # no trailing duplicates
            assert len(sent) == 2
            obj = c.ctx.perf.dump()[f"objecter.{c.client_id}"]
            assert obj["op_resends"] == 2
            # drained: the next epoch defers a fresh (deduped) row
            c._resend_op(w)
            with c._lock:
                assert len(c._resend_q) == 1
        finally:
            c.shutdown()

    def test_resend_error_does_not_strand_queue(self):
        """A resend raising past OSError/TimeoutError (e.g. the op's
        pool deleted under it, making target calc raise) must not
        unwind the ONE shared timer thread mid-fan — the remaining
        ready waiters must still be sent."""
        from types import SimpleNamespace
        c = self._client()
        try:
            c.ctx.conf.set("client_resend_backoff_ms", 10.0)
            sent: list[int] = []

            def send(w):
                if w.msg.tid == 1:
                    raise KeyError("pool gone")
                sent.append(w.msg.tid)
            c._send_op = send
            from ceph_tpu.client.rados import _Waiter
            for tid in (1, 2):
                w = _Waiter(SimpleNamespace(tid=tid, qos_tenant=""),
                            0, True)
                w.resends = 1            # next resend defers
                c._waiters[tid] = w
                c._resend_op(w)
            deadline = time.monotonic() + 5
            while not sent and time.monotonic() < deadline:
                time.sleep(0.005)
            assert sent == [2]
        finally:
            c.shutdown()


# -- visibility ---------------------------------------------------------------

class TestVisibility:
    def test_mgr_report_carries_faults_tail(self):
        from ceph_tpu.mgr.daemon import MMgrReport
        from ceph_tpu.msg.encoding import Decoder, Encoder
        faults = {"encode": {"breaker_states": {"ec_encode": 1},
                             "fallback_batches": 3}}
        msg = MMgrReport(osd_id=4, faults=faults)
        enc = Encoder()
        msg.encode_payload(enc)
        out = MMgrReport.__new__(MMgrReport)
        out.decode_payload(Decoder(enc.tobytes()), MMgrReport.HEAD_VERSION)
        assert out.faults == faults

    def test_mgr_health_kernel_degraded(self):
        import time as _time
        from ceph_tpu.mgr.daemon import MgrDaemon, MMgrReport
        mgr = MgrDaemon("mgr-health-test", ms_type="loopback")
        degraded = MMgrReport(osd_id=1, faults={
            "encode": {"breaker_states": {"ec_encode": 1}},
            "decode": {"breaker_states": {}}})
        with mgr._lock:
            mgr.reports[1] = (_time.time(), degraded)
        h = mgr.health()
        checks = {c["check"]: c for c in h["checks"]}
        assert "KERNEL_DEGRADED" in checks, h
        assert checks["KERNEL_DEGRADED"]["severity"] == "warn"
        assert checks["KERNEL_DEGRADED"]["daemons"] == {
            "1": ["encode/ec_encode"]}
        assert h["status"] == "HEALTH_WARN"
        # breaker re-closes -> the warning clears
        healed = MMgrReport(osd_id=1, faults={
            "encode": {"breaker_states": {"ec_encode": 0}}})
        with mgr._lock:
            mgr.reports[1] = (_time.time(), healed)
        h = mgr.health()
        assert all(c["check"] != "KERNEL_DEGRADED"
                   for c in h["checks"]), h
        # a daemon that died mid-outage (stale report, never pruned)
        # must read as STALE, not pin KERNEL_DEGRADED forever
        with mgr._lock:
            mgr.reports[1] = (_time.time() - 3600.0, degraded)
        h = mgr.health()
        checks = {c["check"] for c in h["checks"]}
        assert "KERNEL_DEGRADED" not in checks, h
        assert "MGR_STALE_REPORTS" in checks, h

    def test_prometheus_fault_families(self):
        from test_kernel_telemetry import _scrape, parse_exposition
        stats = telemetry.dispatch_stats()
        stats.record_retry(True)
        stats.record_fallback(64)
        stats.record_breaker("ec_encode", telemetry.BREAKER_OPEN)
        stats.record_probe(False)
        try:
            fams = parse_exposition(_scrape())
            assert fams["ceph_kernel_fallback_batches_total"][
                "type"] == "counter"
            assert fams["ceph_kernel_fallback_stripes_total"][
                "type"] == "counter"
            assert fams["ceph_kernel_breaker_state"]["type"] == "gauge"
            assert fams["ceph_kernel_breaker_transitions_total"][
                "type"] == "counter"
            probes = fams["ceph_kernel_fallback_probes_total"]
            assert {s[1].get("outcome") for s in probes["samples"]} \
                == {"success", "failure"}
            state = [s for s in fams["ceph_kernel_breaker_state"]
                     ["samples"]
                     if s[1] == {"engine": "encode",
                                 "channel": "ec_encode"}]
            assert state and state[0][2] == 1.0
            batches = [s for s in fams[
                "ceph_kernel_fallback_batches_total"]["samples"]
                if s[1] == {"engine": "encode"}]
            assert batches[0][2] >= 1.0
            # both engines emit the families, decode included
            assert any(s[1].get("engine") == "decode" for s in fams[
                "ceph_kernel_fallback_batches_total"]["samples"])
        finally:
            stats.clear()

    def test_fault_digest_shape(self):
        d = telemetry.fault_digest()
        assert set(d) == {"encode", "decode"}
        for eng in d.values():
            assert {"retries", "fallback_batches", "breaker_opens",
                    "breaker_closes", "probe_successes",
                    "thread_deaths",
                    "breaker_states"} <= set(eng)

    def test_prometheus_daemon_breaker_family(self):
        """The mgr exports each daemon's shipped breaker map as
        ceph_kernel_daemon_breaker_state{ceph_daemon,engine,channel}:
        the process-local sink family cannot attribute degradation
        across daemons — this one names the right daemon."""
        import sys
        sys.path.insert(0, "tests")
        from test_kernel_telemetry import parse_exposition
        from ceph_tpu.mgr.modules.prometheus import Module

        class _Mgr:
            class _Map:
                max_osd = 1
                epoch = 1
                osd_weight = [0x10000]

                def is_up(self, o):
                    return True

                def exists(self, o):
                    return True

            osdmap = _Map()

            def get(self, name):
                return {
                    "health": {"status": "HEALTH_OK"},
                    "pg_summary": {},
                    "df": {"total_objects": 0, "total_bytes_used": 0},
                    "counters": {},
                    "perf_reports": {},
                    "qos_feed": {},
                    "faults_feed": {
                        3: {"encode": {"breaker_states":
                                       {"ec_encode": 1}},
                            "decode": {"breaker_states": {}}},
                        5: {"encode": {"breaker_states":
                                       {"ec_encode": 0}}}},
                }[name]

            def get_store(self, key, default=None):
                return default

        mod = Module.__new__(Module)
        mod.mgr = _Mgr()
        fams = parse_exposition(mod.scrape_text())
        fam = fams["ceph_kernel_daemon_breaker_state"]
        assert fam["type"] == "gauge"
        states = {(s[1]["ceph_daemon"], s[1]["engine"],
                   s[1]["channel"]): s[2] for s in fam["samples"]}
        # per-daemon attribution: osd.3 open, osd.5 closed — no
        # last-writer-wins masking across daemons
        assert states[("osd.3", "encode", "ec_encode")] == 1.0
        assert states[("osd.5", "encode", "ec_encode")] == 0.0

    def test_ctx_fault_digest_reads_own_engine_breakers(self):
        """The shipped MMgrReport faults tail attributes degradation
        to ONE daemon, but the process-global sink's breaker_states is
        last-writer-wins across every in-process daemon: a context's
        digest must read breaker ground truth from its OWN engines —
        and a daemon that never built an engine must not inherit
        another daemon's open breaker."""
        from ceph_tpu.common.context import CephTpuContext
        sink = telemetry.dispatch_stats()
        sink.record_breaker("ec_encode", telemetry.BREAKER_OPEN)
        try:
            ctx = CephTpuContext("fault-digest-test")
            # the raw telemetry digest sees the (polluted) global sink
            assert telemetry.fault_digest()["encode"][
                "breaker_states"] == {"ec_encode": 1}
            # no engine built: no breakers, nothing inherited
            d = ctx.fault_digest()
            assert d["encode"]["breaker_states"] == {}
            assert d["decode"]["breaker_states"] == {}
            # engine built but healthy: still its own (empty) map
            ctx.dispatch_engine()
            assert ctx.fault_digest()["encode"]["breaker_states"] == {}
            # counters still flow from the shared sink
            assert ctx.fault_digest()["encode"]["breaker_opens"] >= 1
            # the admin payload rides the same per-context digest
            assert ctx.admin.execute("dump_fault_stats")["encode"][
                "breaker_states"] == {}
            ctx.dispatch_engine().stop()
        finally:
            sink.clear()


# -- device-chaos thrasher (the PR 11 chaos gate, tier-1) ---------------------

def test_device_chaos_storm(tmp_path):
    """Failpoints fire at >=10%% on the encode/decode/ladder channels
    (plus hard outages, boundary faults and run-loop kills) while the
    thrasher kills OSDs under the mixed workload: ZERO acked-object
    corruption, and after the faults clear every breaker re-closes
    (reconvergence to the device path).  Deterministic seed, ~30s —
    fault injection runs on every PR."""
    from ceph_tpu.tools.thrasher import run_soak
    res = run_soak(duration=11.0, seed=5, n_osds=5,
                   base_path=str(tmp_path), device_chaos=True)
    assert res["corruptions"] == [], res
    assert res["lost_rep"] == [], res
    assert res["lost_ec"] == [], res
    assert res["chaos_actions"] > 0, res
    assert res["rep_ops"] + res["ec_ops"] > 5, res
    assert res["breakers_reconverged"] is True, res["fault_digest"]
    digest = res["fault_digest"]
    # the storm actually bit: the engines saw faults and recovered
    touched = sum(d.get("retries", 0) + d.get("fallback_batches", 0)
                  for d in digest.values())
    assert touched > 0, digest
    # every breaker ended CLOSED
    for d in digest.values():
        assert all(st == telemetry.BREAKER_CLOSED
                   for st in d.get("breaker_states", {}).values()), \
            digest
