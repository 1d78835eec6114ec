"""Typed configuration registry with observers.

The reference keeps one declarative option table (src/common/options.cc, 7510
lines of Option{name, type, level, default, description, flags}) consumed by
md_config_t (common/config.h:152-223) with observer-based hot reload
(common/config_obs.h).  Sources are layered: compiled defaults < config file <
mon config-db < env < CLI < runtime `config set`.  This module mirrors that:
a declarative OPTIONS table, a Config object with layered sources, and
observers notified on runtime changes.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass, field

OPT_INT = "int"
OPT_STR = "str"
OPT_BOOL = "bool"
OPT_FLOAT = "float"

LEVEL_BASIC = "basic"
LEVEL_ADVANCED = "advanced"
LEVEL_DEV = "dev"

_CASTS = {
    OPT_INT: int,
    OPT_FLOAT: float,
    OPT_STR: str,
    OPT_BOOL: lambda v: (v if isinstance(v, bool)
                         else str(v).lower() in ("true", "1", "yes", "on")),
}


@dataclass(frozen=True)
class Option:
    name: str
    type: str
    default: object
    description: str = ""
    level: str = LEVEL_ADVANCED
    runtime: bool = True      # changeable without restart (flag RUNTIME)
    see_also: tuple = ()

    def cast(self, value):
        try:
            return _CASTS[self.type](value)
        except (TypeError, ValueError):
            raise ValueError(
                f"option {self.name}: {value!r} is not a valid {self.type}")


#: The central option table (options.cc analog).  Components register theirs
#: at import via register_options().
OPTIONS: dict[str, Option] = {}


def register_options(opts: list[Option]) -> None:
    for o in opts:
        if o.name in OPTIONS and OPTIONS[o.name] != o:
            raise ValueError(f"conflicting re-registration of {o.name}")
        OPTIONS[o.name] = o


register_options([
    Option("erasure_code_plugins", OPT_STR, "jerasure isa",
           "plugins preloaded at init (options.cc:2197 analog)"),
    Option("erasure_code_runtime", OPT_STR, "tpu",
           "default EC execution runtime: tpu | cpu"),
    Option("osdmap_mapping_min_pgs", OPT_INT, 1024,
           "pools with fewer PGs than this rebuild their cached raw "
           "tables with the scalar rule engine instead of a device "
           "call (per-call dispatch + jit-compile overhead dominates "
           "tiny pools); the epoch cache, incremental invalidation "
           "and delta detection are identical either way"),
    Option("osd_pool_default_size", OPT_INT, 3, "replicas per object"),
    Option("mds_dentry_lease_ttl", OPT_FLOAT, 10.0,
           "seconds a client may trust a leased dentry+attrs without "
           "re-asking the MDS (client dcache, MClientLease analog)"),
    Option("osd_pool_default_min_size", OPT_INT, 2,
           "min replicas to serve IO"),
    Option("osd_pool_default_pg_num", OPT_INT, 32, "pgs per new pool"),
    Option("osd_heartbeat_interval", OPT_FLOAT, 1.0,
           "seconds between peer pings (osd_heartbeat_interval analog)"),
    Option("osd_heartbeat_grace", OPT_FLOAT, 6.0,
           "seconds without ping before reporting failure"),
    Option("mon_osd_min_down_reporters", OPT_INT, 2,
           "distinct reporters before the mon marks an osd down"),
    Option("mon_osd_adjust_heartbeat_grace", OPT_INT, 1,
           "scale the mark-down grace by the target's laggy history "
           "(OSDMonitor.cc:2548-2572 analog)"),
    Option("mon_osd_laggy_halflife", OPT_FLOAT, 3600.0,
           "seconds for laggy history to decay by half"),
    Option("mon_osd_laggy_weight", OPT_FLOAT, 0.3,
           "weight of the newest laggy interval in the decaying average"),
    Option("mon_osd_laggy_max_interval", OPT_FLOAT, 300.0,
           "cap on a single recorded laggy interval (seconds)"),
    Option("osd_op_complaint_time", OPT_FLOAT, 30.0,
           "age after which an in-flight op is a slow request"),
    Option("osd_map_renew_interval", OPT_FLOAT, 2.0,
           "seconds between mon map-subscription renewals"),
    Option("osd_op_queue", OPT_STR, "mclock",
           "op scheduler: mclock (sharded QoS queue) | direct"),
    Option("osd_op_num_shards", OPT_INT, 2,
           "op queue shards (ops shard by pgid; per-PG order kept)"),
    Option("osd_mclock_per_client", OPT_INT, 1,
           "tag client ops per client id (dmclock client-class QoS) "
           "instead of one aggregate client class"),
    Option("osd_mclock_client_reservation", OPT_FLOAT, 0.0,
           "per-client guaranteed ops/s (dmclock reservation; 0 = none)"),
    Option("osd_mclock_client_weight", OPT_FLOAT, 100.0,
           "per-client share of excess capacity (dmclock weight)"),
    Option("osd_mclock_client_limit", OPT_FLOAT, 0.0,
           "per-client ops/s cap (dmclock limit; 0 = unlimited)"),
    Option("osd_op_queue_max_client_backlog", OPT_INT, 512,
           "client ops queued per shard before dispatch backpressure "
           "blocks the intake (peer/recovery classes are never gated)"),
    Option("osd_qos_tenant_lanes", OPT_BOOL, True,
           "schedule client ops by the MOSDOp's authenticated tenant "
           "tag (client.<tenant> dmclock lanes with per-tenant "
           "profiles from the OSDMap qos_db); off = per-client-id "
           "lanes only, tenant tags ignored"),
    Option("osd_qos_idle_client_timeout", OPT_FLOAT, 60.0,
           "seconds a dynamic per-client/per-tenant dmclock lane may "
           "sit idle (empty queue, no enqueues) before the scheduler "
           "evicts its state — bounds the lane table under millions "
           "of one-shot clients; served/wait totals fold into the "
           "dump_qos_stats evicted rollup"),
    Option("osd_max_backfills", OPT_INT, 1,
           "PGs an osd recovers concurrently (reservation slots)"),
    Option("osd_recovery_max_active", OPT_INT, 3,
           "in-flight object pulls per recovering PG"),
    Option("osd_client_message_size_cap", OPT_INT, 256 << 20,
           "bytes of op payloads queued in the sharded op queue before "
           "dispatch threads block (front-door backpressure)"),
    Option("tracing_sample_rate", OPT_FLOAT, 0.0,
           "head-sampling probability for client ops (0 = trace only "
           "explicitly opened traces; 1 = trace everything)"),
    Option("tracing_slow_threshold", OPT_FLOAT, 0.5,
           "root-span seconds at/above which a completed trace is "
           "promoted into the slow-trace ring (tail retention) instead "
           "of aging out with the rest"),
    Option("tracing_slow_ring", OPT_INT, 64,
           "completed slow traces retained per process"),
    Option("kernel_coalesce_max_stripes", OPT_INT, 2048,
           "stripes per coalesced device call: the dispatch engine "
           "stacks concurrent EC/CRUSH requests on the batch axis and "
           "flushes when the batch reaches this many rows"),
    Option("kernel_coalesce_max_delay_us", OPT_FLOAT, 250.0,
           "microseconds a queued kernel request may wait for "
           "coalescing company while the pipeline is busy; an idle "
           "engine always flushes immediately, so single-op latency "
           "never pays this"),
    Option("kernel_dispatch_depth", OPT_INT, 2,
           "device calls in flight per dispatch engine (2 = double "
           "buffering: h2d of batch N+1 overlaps compute of batch N)"),
    Option("kernel_mesh_devices", OPT_INT, 0,
           "devices the dispatch engines shard each coalesced batch "
           "over (the stripe/PG axis splits across a dp x ec device "
           "mesh): 0 = all local devices, 1 = single-device (exact "
           "pre-mesh engine behavior), N = the first N devices; "
           "ignored when the backend exposes one device"),
    Option("kernel_failpoints", OPT_STR, "",
           "armed device-runtime failpoints (common/failpoint.py): "
           "'name=mode[;name=mode...]' where name is a boundary site "
           "optionally channel-qualified (dispatch.launch:ec_encode) "
           "and mode is always|prob:P|oneshot|nth:K|off; empty "
           "disarms everything; the failpoint set/clear/ls admin "
           "commands drive the same registry"),
    Option("kernel_fault_max_retries", OPT_INT, 2,
           "device re-attempts per coalesced batch after a transient "
           "device failure before the batch fails over to the host "
           "oracle (or fans its error); each retry waits an "
           "exponentially growing jittered backoff"),
    Option("kernel_fault_backoff_ms", OPT_FLOAT, 5.0,
           "base retry backoff in milliseconds: attempt i waits "
           "base * 2^i scaled by uniform jitter in [0.5, 1.0)"),
    Option("kernel_fault_backoff_max_ms", OPT_FLOAT, 200.0,
           "cap on a single retry backoff wait"),
    Option("kernel_fault_breaker_threshold", OPT_INT, 3,
           "consecutive device-path batch failures (retries "
           "exhausted) on one kernel channel before its circuit "
           "breaker opens and batches route through the bit-exact "
           "host oracle while a background probe retries the device"),
    Option("kernel_fault_probe_interval", OPT_FLOAT, 0.5,
           "seconds between background device-path probes while a "
           "channel breaker is open; a successful probe closes the "
           "breaker and traffic returns to the device"),
    Option("kernel_fault_thread_restarts", OPT_INT, 4,
           "times a dead dispatch/completion thread is restarted "
           "per engine (in-flight batches re-fan to the replacement); "
           "past the budget the engine is wedged: every waiter gets "
           "a loud EngineWedgedError and flush() raises"),
    Option("osd_scrub_chunk_timeout", OPT_FLOAT, 15.0,
           "seconds a scrubbing primary waits for replica scrub maps "
           "per gather round; peers the osdmap marks down are "
           "recorded as missing immediately instead of waited out"),
    Option("osd_scrub_retry_backoff_ms", OPT_FLOAT, 150.0,
           "backoff before the single MOSDScrub re-request to a "
           "replica that never answered the first gather round; a "
           "peer still silent after the retry lands in the report's "
           "missing_peers and the PG is never reported clean"),
    Option("osd_scrub_verify_repairs", OPT_BOOL, True,
           "re-fetch each repaired copy's digest (a follow-up scrub "
           "of just the repaired oids) before counting it repaired; "
           "repairs that never verify surface as repair_unverified"),
    Option("osd_scrub_verify_timeout", OPT_FLOAT, 6.0,
           "seconds to keep re-checking a pending repair (pushes and "
           "recovery pulls apply asynchronously) before reporting it "
           "repair_unverified"),
    Option("osd_scrub_background_weight", OPT_FLOAT, 1.0,
           "dmclock weight of the background_best_effort class scrub "
           "ops schedule in: background integrity shares only excess "
           "capacity, so a full-cluster deep scrub cannot starve "
           "tenant reservations"),
    Option("osd_scrub_background_limit", OPT_FLOAT, 0.0,
           "ops/s cap on the background_best_effort class (0 = "
           "unlimited — weight-arbitrated only)"),
    Option("osd_scrub_cost", OPT_INT, 4,
           "dmclock cost units one scrub map-build CHUNK charges (the "
           "delta its background tag advances by): a chunk's bulk "
           "read + digest batch is still a few small-op service "
           "times, and without cost scaling the per-op scheduler "
           "would hand the background class cost-times its weight's "
           "worth of worker-seconds"),
    Option("osd_scrub_chunk_objects", OPT_INT, 16,
           "store objects per scrub map-build chunk (chunky scrub): "
           "each background lane op reads+digests at most this many "
           "objects, so scrub's non-preemptive service quantum stays "
           "small-op sized and a tenant op never waits out a "
           "whole-PG map build"),
    Option("osd_scrub_sleep", OPT_FLOAT, 0.004,
           "seconds between scrub map-build chunks (the reference's "
           "osd_scrub_sleep, implemented as a delayed requeue so "
           "neither a shard worker nor an engine thread parks): "
           "paces the storm's python-side work so continuous deep "
           "scrub rides the excess instead of contending for the "
           "serving threads; 0 = no pacing"),
    Option("osd_scrub_auto_interval", OPT_FLOAT, 0.0,
           "seconds between automatic full deep-scrub sweeps "
           "(scrub_all_pgs) this osd starts for the PGs it leads; "
           "0 disables the continuous driver (manual/admin scrubs "
           "only)"),
    Option("client_resend_backoff_ms", OPT_FLOAT, 25.0,
           "base backoff in milliseconds before an Objecter resend "
           "of an already-resent in-flight op (map-change/stale-epoch "
           "retargeting): resend i of one op waits ~base * 2^(i-1) "
           "with uniform jitter; the FIRST resend is immediate, so a "
           "single map change never delays an op"),
    Option("client_resend_backoff_max_ms", OPT_FLOAT, 2000.0,
           "cap on a single client resend backoff wait"),
    Option("kernel_profile_ring", OPT_INT, 256,
           "recent per-batch pipeline-profile records retained per "
           "dispatch engine (the dump_pipeline_profile ring); "
           "aggregated phase histograms are unbounded-time regardless"),
    Option("kernel_fence_for_timing", OPT_BOOL, False,
           "fence (block_until_ready) each instrumented device kernel "
           "call so telemetry latency samples are real device time; "
           "serializes the dispatch pipeline, so keep off on hot paths"),
    Option("kernel_tenant_ledger_enabled", OPT_BOOL, True,
           "apportion each coalesced batch's device busy integral "
           "(compute x devices) to its requests' cost_tags by stripe "
           "share and accumulate the per-tenant x engine x channel "
           "device-time ledger (dump_tenant_usage / the MMgrReport "
           "tenant_usage tail / ceph_tenant_* prometheus families); "
           "measurement-only — scheduling never reads it"),
    Option("kernel_tenant_ledger_max_tenants", OPT_INT, 1024,
           "distinct tenants the device-time ledger tracks before new "
           "tenants fold into the _overflow bucket (a tenant-name "
           "flood cannot grow the table without bound; overflow work "
           "stays counted, so conservation holds)"),
    Option("mgr_slo_fast_window_s", OPT_FLOAT, 300.0,
           "fast burn-rate window of the mgr slo module: QOS_SLO_BURN "
           "fires only while the fast AND slow windows both burn at "
           ">= 1.0, and clears once the fast window recovers"),
    Option("mgr_slo_slow_window_s", OPT_FLOAT, 3600.0,
           "slow burn-rate window of the mgr slo module (the "
           "sustained-violation proof; see mgr_slo_fast_window_s)"),
    Option("mgr_slo_max_samples", OPT_INT, 2048,
           "rolling counter samples the mgr slo module retains for "
           "windowed burn evaluation (also time-bounded by the slow "
           "window)"),
    Option("bluestore_batched_csum_min", OPT_INT, 4,
           "minimum pending blocks before a commit's checksum batch "
           "rides the device; smaller batches take the scalar path "
           "(a one-block digest is cheaper on the host)"),
    Option("bluestore_data_timeout", OPT_FLOAT, 30.0,
           "seconds a bluestore commit or batched read waits on its "
           "bluestore_data digest future before falling back to "
           "scalar crc32 (generous: the engine's own retry/breaker "
           "ladder resolves failures far sooner)"),
    Option("bluestore_batched_read_min", OPT_INT, 8,
           "minimum checksummed blocks a read must cover before its "
           "verification batches to the device"),
    Option("bluestore_compression_mode", OPT_STR, "none",
           "default objectstore block compression mode when a pool "
           "sets none: none | aggressive | force (per-pool "
           "compression_mode overrides; passive is not carried — "
           "client hints do not exist in this stack)"),
    Option("bluestore_compression_algorithm", OPT_STR, "tpu_bitplane",
           "default compressor plugin for block compression "
           "(compressor registry name: tpu_bitplane | zlib | lzma)"),
    Option("bluestore_compression_required_ratio", OPT_FLOAT, 0.875,
           "a compressed block is kept only if stored_size <= "
           "block_size * ratio; otherwise it is stored raw "
           "(compress_rejected)"),
    Option("bluestore_compression_verify", OPT_BOOL, True,
           "round-trip every compressed block (decompress and "
           "compare byte-identical) before committing it; a "
           "mismatch stores the block raw and counts "
           "compress_roundtrip_failures"),
    Option("log_level", OPT_INT, 1, "default subsystem log level"),
    Option("ms_type", OPT_STR, "async",
           "messenger implementation: async | loopback"),
    Option("objectstore", OPT_STR, "memstore",
           "object store backend: memstore | filestore | bluestore"),
])


class Config:
    """Layered config with observers (md_config_t analog)."""

    #: source precedence, low to high (config.h "sources" semantics)
    SOURCES = ("default", "file", "mon", "env", "cli", "runtime")

    def __init__(self, options: dict[str, Option] | None = None):
        self._options = options if options is not None else OPTIONS
        # analysis: allow[bare-lock] -- config underpins lockdep's own enable gate (g_lockdep reads conf) -- bare avoids a bootstrap cycle; leaf around layer dicts
        self._lock = threading.RLock()
        self._values: dict[str, dict[str, object]] = {}  # name -> src -> val
        self._observers: dict[str, list] = {}            # name -> callbacks

    def get(self, name: str):
        with self._lock:
            opt = self._lookup(name)
            layers = self._values.get(name, {})
            for src in reversed(self.SOURCES):
                if src in layers:
                    return layers[src]
            return opt.default

    def set(self, name: str, value, source: str = "runtime") -> None:
        if source not in self.SOURCES:
            raise ValueError(f"unknown config source {source!r}")
        with self._lock:
            opt = self._lookup(name)
            if source == "runtime" and not opt.runtime:
                raise ValueError(
                    f"option {name} cannot change at runtime (STARTUP flag)")
            old = self.get(name)
            self._values.setdefault(name, {})[source] = opt.cast(value)
            new = self.get(name)
            observers = list(self._observers.get(name, []))
        if new != old:
            for cb in observers:
                cb(name, new)

    def rm(self, name: str, source: str) -> None:
        """Retract a layer's value (the mon config-db analog of
        `ceph config rm`); observers fire if the effective value moves."""
        with self._lock:
            self._lookup(name)
            old = self.get(name)
            layers = self._values.get(name, {})
            layers.pop(source, None)
            new = self.get(name)
            observers = list(self._observers.get(name, []))
        if new != old:
            for cb in observers:
                cb(name, new)

    def load_file(self, path: str) -> None:
        """JSON config file (the ceph.conf layer)."""
        with open(path) as f:
            for k, v in json.load(f).items():
                self.set(k, v, source="file")

    def add_observer(self, name: str, callback) -> None:
        """callback(name, new_value) on effective-value change
        (config_obs.h analog)."""
        with self._lock:
            self._lookup(name)
            self._observers.setdefault(name, []).append(callback)

    def show(self) -> dict:
        """Effective config (admin `config show`)."""
        with self._lock:
            return {name: self.get(name) for name in sorted(self._options)}

    def diff(self) -> dict:
        """Only values differing from defaults (admin `config diff`)."""
        with self._lock:
            return {name: self.get(name) for name in sorted(self._values)
                    if self.get(name) != self._options[name].default}

    def _lookup(self, name: str) -> Option:
        if name not in self._options:
            raise KeyError(f"unknown config option {name!r}")
        return self._options[name]
