"""CephTpuContext — the per-process service locator (CephContext analog,
src/common/ceph_context.h).

Owns the config, the perf-counter collection, the admin socket, and the log
levels; daemons and libraries receive one context and hang their services off
it, exactly as every reference component takes a CephContext*.
"""

from __future__ import annotations

from .admin_socket import AdminSocket
from .config import Config
from .perf_counters import PerfCountersCollection


class CephTpuContext:
    def __init__(self, name: str = "client", admin_path: str | None = None,
                 *, process_index: int | None = None,
                 n_processes: int | None = None,
                 coordinator: str | None = None):
        """``process_index``/``n_processes``/``coordinator`` opt this
        context into the multi-controller deployment mode (SURVEY §5's
        two-plane design): jax.distributed initializes against the
        coordinator, the kernel mesh spans every process's devices
        (engines place their own flushes over the process-local
        submesh — the ICI domain), and ``messenger_stack_for`` routes
        control-plane traffic ici intra-process / tcp across."""
        self.name = name
        self.process_index = 0 if process_index is None else int(process_index)
        self.n_processes = 1 if not n_processes else int(n_processes)
        if self.n_processes > 1:
            from ceph_tpu.parallel.dcn import init_distributed
            init_distributed(coordinator, self.n_processes,
                             self.process_index)
        self.conf = Config()
        self.perf = PerfCountersCollection()
        self.admin = AdminSocket(admin_path)
        self.admin.register_command(
            "perf dump", lambda **kw: self.perf.dump(),
            "dump perf counters")
        self.admin.register_command(
            "config show", lambda **kw: self.conf.show(),
            "show effective config")
        self.admin.register_command(
            "config diff", lambda **kw: self.conf.diff(),
            "show non-default config")
        self.admin.register_command(
            "config set",
            lambda name, value, **kw: (self.conf.set(name, value), "ok")[1],
            "set a runtime option")
        self.admin.register_command(
            "config get",
            lambda name, **kw: {name: self.conf.get(name)},
            "get one option")
        from ceph_tpu.common import tracing
        tracing.configure_from_conf(self.conf)
        trace_dump = (lambda trace_id=None, **kw: tracing.dump(
            int(trace_id) if trace_id else None))
        # one command, one help string, one reference-style alias: both
        # spellings serve the span-structured rows (span_id /
        # parent_span_id / dur / attrs per row)
        self.admin.register_command(
            "dump_tracing", trace_dump,
            "span-structured cross-daemon trace timelines "
            "[trace_id]: time-ordered rows with span_id, "
            "parent_span_id, duration and attributes; cpu_ns / thread "
            "(the CPU time and ident of the one thread that opened and "
            "closed the span; in one trace of four, absent on "
            "cross-thread spans; 0 or a multiple of the step where the "
            "host's thread CPU clock is coarse), attribute "
            "wait on a wait by design, and on a message hop sent_us / "
            "first_byte_us / framed_us / dequeued_us (us from its "
            "start: sender's last byte written; receiver's first byte "
            "seen, frame whole, taken off the dispatch queue)",
            aliases=("dump_traces",))
        self.admin.register_command(
            "dump_slow_traces", lambda **kw: tracing.slow_traces(),
            "completed traces retained by tail sampling (root span "
            "over tracing_slow_threshold)")
        from ceph_tpu.ops import telemetry
        telemetry.configure_from_conf(self.conf)
        # fault injection + degraded-mode visibility: the failpoint
        # registry is process-global (like the telemetry registry);
        # this context's config option and admin commands drive it
        from ceph_tpu.common import failpoint
        failpoint.configure_from_conf(self.conf)
        failpoint.register_admin(self.admin)
        self.admin.register_command(
            "dump_fault_stats", lambda **kw: self.fault_digest(),
            "device-runtime fault/degradation counters per dispatch "
            "engine: retries, host-oracle fallback batches/stripes, "
            "circuit-breaker opens/closes and per-channel states, "
            "background-probe outcomes, thread deaths/restarts")
        self.admin.register_command(
            "dump_kernel_stats", lambda **kw: telemetry.dump(),
            "device-kernel telemetry: latency/batch histograms, "
            "byte counters, jit retrace counts")
        #: lazily-built cross-op coalescing engine (ops.dispatch); one
        #: per context, like every other service hung off it.  The
        #: build is locked: two racing first callers splitting across
        #: two engines would break per-key submission-order delivery
        from ceph_tpu.common import lockdep
        self._dispatch = None
        self._decode_dispatch = None
        self._mapping_service = None
        self._kernel_mesh = None        # (knob_value, mesh-or-None)
        self._dispatch_lock = lockdep.make_lock(
            "CephTpuContext::dispatch_build")
        # knob flip rebuilds the mesh and swaps it into LIVE engines
        # (takes effect from their next flush)
        self.conf.add_observer(
            "kernel_mesh_devices", lambda _n, _v: self._remesh())
        self.admin.register_command(
            "dump_dispatch_stats",
            lambda **kw: {"encode": telemetry.dispatch_dump(),
                          "decode": telemetry.decode_dispatch_dump()},
            "dispatch-engine telemetry (encode + decode engines): "
            "coalesce factor, queue delay/depth, flush reasons, "
            "in-flight batches, mesh fan-out (devices per flush, "
            "sharded-flush count, mesh shape); decode adds "
            "erasure-pattern heterogeneity per call and "
            "pattern-table size")
        self.admin.register_command(
            "dump_mapping_stats",
            lambda **kw: telemetry.mapping_dump(),
            "shared PG-mapping-service telemetry: epoch-update "
            "latency, pools recomputed vs reused, changed-PG counts, "
            "epoch-skips, cache lookups vs scalar fallbacks, and the "
            "per-epoch device/delta/host-tail phase split (`device` is "
            "the wall time of OSDMapMapping.update(), host and device "
            "both; the update_to span tree splits it: dump_tracing)")
        self.admin.register_command(
            "dump_pipeline_profile",
            lambda **kw: telemetry.pipeline_profile_dump(),
            "per-batch pipeline phase attribution for both dispatch "
            "engines: queue-wait/build/place/launch/compute/"
            "materialize/deliver histograms per kernel family, the "
            "compile ledger (first-call jit cost, separate from "
            "steady-state compute), device busy-seconds/utilization/"
            "shard-imbalance, a ring of recent per-batch records, and "
            "the mapping service's epoch phase split")

    def fault_digest(self) -> dict:
        """telemetry.fault_digest() with THIS context's engines'
        per-channel breaker maps overlaid.  The counter sinks are
        process-global (every in-process daemon shares them, which is
        what a per-process exporter wants), but ``breaker_states`` is
        keyed by channel only — daemon B re-closing a breaker there is
        last-writer-wins over daemon A's still-open one.  The shipped
        MMgrReport ``faults`` tail and the admin payload attribute
        degradation to ONE daemon, so they must read breaker ground
        truth from that daemon's own engines; a context that never
        built an engine has no breakers (and must not inherit another
        daemon's)."""
        from ceph_tpu.ops import telemetry
        digest = telemetry.fault_digest()
        with self._dispatch_lock:
            engines = {"encode": self._dispatch,
                       "decode": self._decode_dispatch}
        for key, eng in engines.items():
            digest[key]["breaker_states"] = (
                eng.breaker_states() if eng is not None else {})
        return digest

    def kernel_mesh(self):
        """The ("dp", "ec") device mesh this context's dispatch engines
        shard over, or None (knob ``kernel_mesh_devices`` = 1, a
        single-device backend, or jax unavailable).  Built lazily on
        first engine construction — a context that never touches a
        kernel never imports jax.  In the multi-controller deployment
        mode this is the GLOBAL mesh spanning every process; engines
        place their own flushes over its process-local submesh."""
        knob = int(self.conf.get("kernel_mesh_devices"))
        with self._dispatch_lock:
            cached = self._kernel_mesh
            if cached is not None and cached[0] == knob:
                return cached[1]
            mesh = None
            if knob != 1:
                try:
                    import jax
                    n = len(jax.devices())
                    if knob > 1:
                        n = min(knob, n)
                    if n > 1:
                        from ceph_tpu.parallel.mesh import make_mesh
                        # pure dp by default: the engine coalesce axis
                        # is stripes/PGs; an ec axis only pays when the
                        # codec's k+m divides it (factor_devices)
                        mesh = make_mesh(n)
                except Exception as e:
                    # loud, like the engine's placement failure path:
                    # an operator who asked for N devices must not
                    # silently run single-device with no diagnostic
                    from ceph_tpu.common.logging import dout
                    dout("context", 0, "%s: kernel mesh unavailable, "
                         "engines run single-device: %r", self.name, e)
                    mesh = None
            self._kernel_mesh = (knob, mesh)
            return mesh

    def _remesh(self) -> None:
        """kernel_mesh_devices observer: rebuild and swap into live
        engines (their next flush re-places; see engine.set_mesh)."""
        with self._dispatch_lock:
            self._kernel_mesh = None
            mesh = self.kernel_mesh()
            for eng in (self._dispatch, self._decode_dispatch):
                if eng is not None:
                    eng.set_mesh(mesh)

    def messenger_stack_for(self, peer_process: int) -> str:
        """Control-plane routing for the multi-controller deployment:
        device-buffer ici inside the process, tcp async across (the
        SURVEY §5 two-plane rule, parallel.dcn.pick_stack)."""
        from ceph_tpu.parallel.dcn import pick_stack
        return pick_stack(peer_process, self.process_index)

    def _build_engine(self, name: str, stats=None):
        """One coalescing engine wired to the shared knobs (both the
        encode and decode engines hot-reload through the same config
        observers)."""
        from ceph_tpu.ops.dispatch import DeviceDispatchEngine
        eng = DeviceDispatchEngine(
            max_stripes=int(self.conf.get(
                "kernel_coalesce_max_stripes")),
            max_delay_us=float(self.conf.get(
                "kernel_coalesce_max_delay_us")),
            max_in_flight=int(self.conf.get(
                "kernel_dispatch_depth")),
            name=name, stats=stats, mesh=self.kernel_mesh())
        self.conf.add_observer(
            "kernel_coalesce_max_stripes",
            lambda _n, v: setattr(eng, "max_stripes", int(v)))
        self.conf.add_observer(
            "kernel_coalesce_max_delay_us",
            lambda _n, v: setattr(eng, "max_delay_us", float(v)))
        # fault-domain knobs (retry ladder, breaker, supervision):
        # same construction-read + hot-reload-observer pattern
        for opt, attr, cast in (
                ("kernel_fault_max_retries", "fault_max_retries", int),
                ("kernel_fault_backoff_ms", "fault_backoff_ms", float),
                ("kernel_fault_backoff_max_ms",
                 "fault_backoff_max_ms", float),
                ("kernel_fault_breaker_threshold",
                 "breaker_threshold", int),
                ("kernel_fault_probe_interval", "probe_interval",
                 float),
                ("kernel_fault_thread_restarts", "thread_restarts",
                 int)):
            setattr(eng, attr, cast(self.conf.get(opt)))
            self.conf.add_observer(
                opt, lambda _n, v, a=attr, c=cast:
                setattr(eng, a, c(v)))
        return eng

    def dispatch_engine(self):
        """The context's device dispatch engine (built on first use so
        contexts that never touch a kernel spawn no threads).  The
        coalescing knobs hot-reload through config observers."""
        if self._dispatch is None:
            with self._dispatch_lock:
                if self._dispatch is not None:
                    return self._dispatch
                self._dispatch = self._build_engine(
                    f"{self.name}-dispatch")
        return self._dispatch

    def decode_dispatch_engine(self):
        """The decode-side twin: EC decodes (degraded reads, recovery
        pulls, rmw gathers) coalesce here, separately double-buffered
        from the write path so a recovery storm cannot queue behind —
        or starve — client encodes.  Feeds the decode stats sink
        (telemetry.decode_dispatch_stats / ceph_kernel_decode_*)."""
        if self._decode_dispatch is None:
            with self._dispatch_lock:
                if self._decode_dispatch is not None:
                    return self._decode_dispatch
                from ceph_tpu.ops import telemetry
                self._decode_dispatch = self._build_engine(
                    f"{self.name}-decode",
                    stats=telemetry.decode_dispatch_stats())
        return self._decode_dispatch

    def mapping_service(self):
        """The context's shared epoch-keyed PG mapping cache
        (osd.mapping.SharedPGMappingService) — one per context like
        the dispatch engines; N daemons hanging off one context
        advancing the same epoch share a single table build, and its
        per-pool remaps ride this context's dispatch engine."""
        if self._mapping_service is None:
            with self._dispatch_lock:
                if self._mapping_service is not None:
                    return self._mapping_service
                from ceph_tpu.osd.mapping import SharedPGMappingService
                self._mapping_service = SharedPGMappingService(self)
        return self._mapping_service


_default: CephTpuContext | None = None


def default_context() -> CephTpuContext:
    """Process-wide fallback context (g_ceph_context analog)."""
    global _default
    if _default is None:
        _default = CephTpuContext()
    return _default
