"""Kernel telemetry at the JAX offload boundary.

The repo's two batchable numeric kernels — GF(2^8) EC encode/decode
(ops.gf_kernel) and CRUSH straw2 mapping (crush.mapper_jax) — are the
dominant data path, yet the device boundary itself was uninstrumented.
This module is the process-global registry those call sites feed:

  * per-kernel wall-time histograms.  By default the sample is the
    UNFENCED dispatch time (the async runtime acks before execution
    completes); with ``fence_for_timing`` on, each instrumented call
    blocks until the result is ready so the sample is real device
    residency.  The knob is a config option (``kernel_fence_for_timing``)
    because fencing serializes the pipeline — the hot path runs unfenced;
  * batch-size/occupancy histograms (how full each device call is — the
    whole thesis is batching, so occupancy IS the efficiency metric);
  * host->device / device->host byte counters (input operand bytes and
    result bytes crossing the boundary per call);
  * jit compile-cache hit/miss counters.  A miss is a retrace+compile —
    the silent throughput killer when shapes churn.  Counted from the
    jitted entry point's own compile cache (``_cache_size`` delta) when
    available, else from a seen-signature set the call site provides.

Everything here is stdlib-only: importing this module never pulls in
the kernel modules or pallas (the mgr's prometheus scraper and every
CephTpuContext import it; ceph_tpu.ops resolves its kernel exports
lazily for the same reason), and the instrumented call sites pass
callables for anything device-flavored.

Calls made UNDER an outer jit trace (any user jit composing our
kernels) return tracers: those are
counted as ``traced`` executions but produce no latency/byte samples —
a tracer has no wall time and fencing it would throw.

Surfaces: ``dump()`` (admin socket ``dump_kernel_stats``) and the mgr
prometheus module (histogram families per kernel).
"""

from __future__ import annotations

import time
from collections import deque

from ceph_tpu.common import lockdep, tracing

#: latency bucket upper bounds, seconds (log-spaced: 10 us .. 1 s)
LATENCY_BOUNDS = (
    1e-5, 2e-5, 5e-5, 1e-4, 2e-4, 5e-4,
    1e-3, 2e-3, 5e-3, 1e-2, 2e-2, 5e-2, 0.1, 0.25, 0.5, 1.0)

#: batch-occupancy bucket upper bounds (stripes or lanes per call)
BATCH_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                2048, 4096, 8192, 16384, 32768, 65536)

#: coalesce-factor / queue-depth bucket upper bounds (requests per
#: device call; the whole point of the dispatch engine is pushing the
#: mass of this histogram above 1)
COALESCE_BOUNDS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 128)

#: fraction bucket upper bounds (shard imbalance, padded-lane share)
FRACTION_BOUNDS = (0.01, 0.02, 0.05, 0.1, 0.15, 0.25, 0.4, 0.6,
                   0.8, 1.0)

#: the dispatch pipeline's phases, in TIMELINE order.  The ledger is
#: continuous — each phase starts exactly where the previous ended —
#: so the per-batch phase sum reconstructs the batch's submit→delivery
#: wall-clock (the "where did the time go" invariant the profiler
#: tests pin):
#:
#:   queue_wait   oldest submit → dispatch thread starts the batch
#:   build        pad/concat of the coalesced host batch (+ aux)
#:   place        device_put / h2d placement (mesh sharding included)
#:   launch       the fn() call — async dispatch ack; a first-call
#:                batch's jit trace+compile lands here (attributed to
#:                the compile ledger, not steady-state)
#:   compute      launch ack → result ready (device execution; also
#:                absorbs completion-thread pickup wait, which overlaps
#:                execution under double buffering)
#:   materialize  d2h materialization (np.asarray of the ready result)
#:   deliver      per-request slicing + future/continuation fan-out
PHASES = ("queue_wait", "build", "place", "launch", "compute",
          "materialize", "deliver")

#: default bound on retained per-batch profile records per engine
#: (the ``kernel_profile_ring`` option rebinds it at runtime)
PROFILE_RING_DEFAULT = 256
_profile_ring = PROFILE_RING_DEFAULT


class Histogram:
    """Cumulative-bucket histogram with a running sum (the Prometheus
    histogram data model: ``le`` buckets + ``_sum`` + ``_count``)."""

    __slots__ = ("bounds", "buckets", "sum")

    def __init__(self, bounds):
        self.bounds = tuple(bounds)
        self.buckets = [0] * (len(self.bounds) + 1)   # last = +Inf
        self.sum = 0.0

    def add(self, value: float) -> None:
        i = 0
        for b in self.bounds:
            if value <= b:
                break
            i += 1
        self.buckets[i] += 1
        self.sum += value

    @property
    def count(self) -> int:
        return sum(self.buckets)

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (upper bound of the bucket holding it);
        0.0 with no samples."""
        total = self.count
        if not total:
            return 0.0
        rank = q * total
        acc = 0
        for i, n in enumerate(self.buckets):
            acc += n
            if acc >= rank:
                return (self.bounds[i] if i < len(self.bounds)
                        else self.bounds[-1])
        return self.bounds[-1]

    def dump(self) -> dict:
        return {"bounds": list(self.bounds),
                "buckets": list(self.buckets),
                "sum": self.sum, "count": self.count}


class KernelStats:
    """Counters for one named kernel (e.g. "ec_encode", "crush_map")."""

    __slots__ = ("name", "calls", "traced", "jit_misses", "jit_hits",
                 "bytes_in", "bytes_out", "latency", "batch",
                 "_signatures", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0          # completed device calls (concrete result)
        self.traced = 0         # executions under an outer jit trace
        self.jit_misses = 0     # compile-cache misses (retrace+compile)
        self.jit_hits = 0       # calls served by a cached executable
        self.bytes_in = 0       # host->device operand bytes
        self.bytes_out = 0      # device->host result bytes
        self.latency = Histogram(LATENCY_BOUNDS)
        self.batch = Histogram(BATCH_BOUNDS)
        self._signatures: set = set()
        self._lock = lockdep.make_lock(f"KernelStats::lock({name})")

    def record(self, seconds: float, *, batch: int = 0, bytes_in: int = 0,
               bytes_out: int = 0, misses: int = 0) -> None:
        with self._lock:
            self.calls += 1
            self.latency.add(seconds)
            if batch:
                self.batch.add(batch)
            self.bytes_in += bytes_in
            self.bytes_out += bytes_out
            if misses > 0:
                self.jit_misses += misses
            else:
                self.jit_hits += 1

    def note_signature(self, sig) -> bool:
        """Fallback miss detector when the jit cache is not
        introspectable: True (miss) the first time a shape signature is
        seen."""
        with self._lock:
            if sig in self._signatures:
                return False
            self._signatures.add(sig)
            return True

    def dump(self) -> dict:
        with self._lock:
            return {
                "calls": self.calls,
                "traced": self.traced,
                "jit_misses": self.jit_misses,
                "jit_hits": self.jit_hits,
                "bytes_in": self.bytes_in,
                "bytes_out": self.bytes_out,
                "latency_seconds": self.latency.dump(),
                "batch_size": self.batch.dump(),
            }


class PhaseStats:
    """Per-batch pipeline phase attribution for one dispatch engine.

    Three ledgers, one question — where does a flushed batch's
    submit→delivery wall-clock go:

    * **phase histograms**, per kernel family (the request label:
      ec_encode, ec_decode, crush_rule, ...) × phase (PHASES above).
      Steady-state only — a first-call batch's launch+compute carry
      jit trace/compile cost and would poison the compute story, so
      they are diverted to
    * the **compile ledger**: total seconds and event count per
      family, attributed on the FIRST flush of each (family, bucket,
      mesh) combination (or whenever the submitter's jit-cache probe
      reports a miss — the ground truth when available);
    * **device utilization**: busy-seconds integral (compute seconds ×
      devices the flush landed on), a utilization gauge over the
      window since construction/clear, and the shard-imbalance story
      for mesh engines (padded-lane share of each sharded flush — rows
      are contiguous, so padding concentrates in the tail shards).

    A bounded ring of recent per-batch profile records rides along so
    ``dump_pipeline_profile`` can show the last N batches verbatim,
    not just aggregates.
    """

    __slots__ = ("_lock", "phase", "compile_seconds", "compile_events",
                 "caller_batches",
                 "_compiled_keys", "busy_seconds", "devices_seen",
                 "shard_imbalance", "last_shard_imbalance", "records",
                 "_anchor")

    def __init__(self, name: str = "phase"):
        self._lock = lockdep.make_lock(f"PhaseStats::lock({name})")
        #: (family, phase) -> Histogram of seconds (steady-state)
        self.phase: dict[tuple, Histogram] = {}
        self.compile_seconds: dict[str, float] = {}
        self.compile_events: dict[str, int] = {}
        #: family -> batches its submitter's own thread ran
        #: (dispatch.submit_waiting), of the family's batches
        self.caller_batches: dict[str, int] = {}
        #: (family, bucket, devices) combos already charged a compile
        self._compiled_keys: set = set()
        self.busy_seconds = 0.0     # sum of compute_s * devices
        self.devices_seen = 1       # widest flush fan-out observed
        self.shard_imbalance = Histogram(FRACTION_BOUNDS)
        self.last_shard_imbalance = 0.0
        self.records: deque = deque(maxlen=_profile_ring)
        self._anchor = time.monotonic()   # utilization window start

    def clear(self) -> None:
        with self._lock:
            self.phase = {}
            self.compile_seconds = {}
            self.compile_events = {}
            self.caller_batches = {}
            self._compiled_keys = set()
            self.busy_seconds = 0.0
            self.devices_seen = 1
            self.shard_imbalance = Histogram(FRACTION_BOUNDS)
            self.last_shard_imbalance = 0.0
            self.records = deque(maxlen=_profile_ring)
            self._anchor = time.monotonic()

    def _resize_ring(self, n: int) -> None:
        with self._lock:
            self.records = deque(self.records, maxlen=n)

    def record_batch(self, family: str, *, phases: dict, e2e_s: float,
                     requests: int, stripes: int, bucket: int,
                     devices: int, misses=None,
                     on_caller: bool = False) -> None:
        """One flushed batch's full ledger.  ``phases`` maps PHASES
        names to seconds (missing = 0); ``misses`` is the submitter's
        jit-cache delta when probed (None = not probed — first-call
        detection falls back to the (family, bucket, devices) set);
        ``on_caller``: the submitter's thread ran the batch."""
        d = max(1, int(devices))
        with self._lock:
            if on_caller:
                self.caller_batches[family] = \
                    self.caller_batches.get(family, 0) + 1
            key = (family, int(bucket), d)
            first = key not in self._compiled_keys
            if first:
                self._compiled_keys.add(key)
            compiled = (misses > 0) if misses is not None else first
            if compiled:
                self.compile_seconds[family] = (
                    self.compile_seconds.get(family, 0.0)
                    + phases.get("launch", 0.0)
                    + phases.get("compute", 0.0))
                self.compile_events[family] = \
                    self.compile_events.get(family, 0) + 1
            for ph in PHASES:
                if compiled and ph in ("launch", "compute"):
                    continue      # charged to the compile ledger above
                h = self.phase.get((family, ph))
                if h is None:
                    h = self.phase[(family, ph)] = \
                        Histogram(LATENCY_BOUNDS)
                h.add(phases.get(ph, 0.0))
            self.busy_seconds += phases.get("compute", 0.0) * d
            if d > self.devices_seen:
                self.devices_seen = d
            if d > 1 and bucket:
                imb = max(0.0, 1.0 - stripes / bucket)
                self.shard_imbalance.add(imb)
                self.last_shard_imbalance = imb
            self.records.append({
                "t": time.time(), "kernel": family,
                "requests": int(requests), "stripes": int(stripes),
                "bucket": int(bucket), "devices": d,
                "compiled": bool(compiled), "e2e_s": float(e2e_s),
                "caller_thread": bool(on_caller),
                "phases": {ph: float(phases.get(ph, 0.0))
                           for ph in PHASES}})

    def utilization(self) -> float:
        """Device-busy fraction of the window since construction /
        clear: busy-seconds integral over wall × widest fan-out.  An
        always-on approximation (compile time counts as busy), not a
        per-flush exactness claim."""
        with self._lock:
            wall = time.monotonic() - self._anchor
            if wall <= 0.0:
                return 0.0
            return min(1.0, self.busy_seconds
                       / (wall * max(1, self.devices_seen)))

    def dump(self, include_recent: bool = True) -> dict:
        """``include_recent=False`` skips copying the per-batch record
        ring — the prometheus scrape only reads the aggregates, and
        copying 256 dicts under the stats lock per poll is pure
        waste there."""
        util = self.utilization()
        with self._lock:
            fams: dict = {}
            for (family, ph), h in self.phase.items():
                fams.setdefault(family, {})[ph] = h.dump()
            return {
                "phases": fams,
                "compile": {f: {"seconds": self.compile_seconds[f],
                                "events": self.compile_events.get(f, 0)}
                            for f in self.compile_seconds},
                "caller_batches": dict(self.caller_batches),
                "busy_seconds": self.busy_seconds,
                "utilization": round(util, 4),
                "devices_seen": self.devices_seen,
                "shard_imbalance": self.shard_imbalance.dump(),
                "last_shard_imbalance": self.last_shard_imbalance,
                "window_seconds": round(
                    time.monotonic() - self._anchor, 3),
                "recent": ([dict(r) for r in self.records]
                           if include_recent else []),
            }

    def summary(self) -> dict:
        """Compact digest (MMgrReport carriage, perfbench): per
        kernel family the phase totals and shares, plus the compile
        ledger and the utilization gauges.  Ring omitted — digests
        travel the wire every tick."""
        util = self.utilization()
        with self._lock:
            fams: dict = {}
            for (family, ph), h in self.phase.items():
                fams.setdefault(family, {})[ph] = h.sum
            out_f: dict = {}
            for family, per in fams.items():
                total = sum(per.values())
                out_f[family] = {
                    "seconds": {ph: round(s, 6)
                                for ph, s in per.items()},
                    "share": {ph: (round(s / total, 4) if total else 0.0)
                              for ph, s in per.items()},
                    "batches": max((self.phase[(family, ph)].count
                                    for ph in PHASES
                                    if (family, ph) in self.phase),
                                   default=0),
                    "caller_batches": self.caller_batches.get(family, 0),
                }
            return {
                "kernels": out_f,
                "compile": {f: {"seconds": round(
                                    self.compile_seconds[f], 6),
                                "events": self.compile_events.get(f, 0)}
                            for f in self.compile_seconds},
                "busy_seconds": round(self.busy_seconds, 6),
                "utilization": round(util, 4),
                "devices_seen": self.devices_seen,
                "last_shard_imbalance": round(
                    self.last_shard_imbalance, 4),
            }


#: circuit-breaker states (ceph_kernel_breaker_state gauge values):
#: closed = device path live, open = routing through the host oracle,
#: half-open = a background probe is deciding
BREAKER_CLOSED = 0
BREAKER_OPEN = 1
BREAKER_HALF_OPEN = 2


class DispatchStats:
    """Counters for the cross-op coalescing engine (ops.dispatch).

    The engine's efficiency story is four numbers: how many requests
    share each device call (coalesce factor), how long they queue for
    the privilege (queue delay), how deep the backlog runs (queue
    depth), and how many calls are outstanding (in-flight).  Flush
    reasons tell WHY each batch closed — "idle" flushes are the no-wait
    single-op path, "full"/"timeout" flushes are coalescing at work.

    Mesh-sharded engines (ops.dispatch with a device mesh) add the
    fan-out story: how many devices each flush actually landed on
    (devices_used — mass above 1 is the multi-chip path at work), how
    many stripes each device's shard carried (shard_stripes — the
    per-chip occupancy after the batch splits), how many flushes went
    out sharded at all, and the engine's mesh shape gauges.
    """

    __slots__ = ("_lock", "submits", "stripes_in", "batches",
                 "caller_batches",
                 "stripes_out", "padded_stripes", "completed",
                 "coalesce", "queue_delay", "queue_depth",
                 "flush_reasons", "in_flight", "max_in_flight_seen",
                 "sharded_flushes", "devices_used", "shard_stripes",
                 "mesh_devices", "mesh_dp", "mesh_ec", "phases",
                 "retries", "retry_successes", "fallback_batches",
                 "fallback_stripes", "breaker_opens", "breaker_closes",
                 "probe_successes", "probe_failures", "thread_deaths",
                 "thread_restarts", "breaker_states")

    def __init__(self):
        self._lock = lockdep.make_lock("DispatchStats::lock")
        #: per-batch pipeline phase attribution (its own lock: the
        #: completion thread records a full profile per flush while
        #: submitters hammer record_submit)
        self.phases = PhaseStats(type(self).__name__)
        self.submits = 0          # requests submitted
        self.stripes_in = 0       # stripes submitted
        self.batches = 0          # device calls dispatched
        self.caller_batches = 0   # ... of them on the submitter's thread
        self.stripes_out = 0      # stripes dispatched (pre-padding)
        self.padded_stripes = 0   # zero rows added by shape bucketing
        self.completed = 0        # requests delivered
        self.coalesce = Histogram(COALESCE_BOUNDS)   # requests/batch
        self.queue_delay = Histogram(LATENCY_BOUNDS)  # submit->dispatch s
        self.queue_depth = Histogram(COALESCE_BOUNDS)  # pending at flush
        self.flush_reasons = {"idle": 0, "full": 0, "timeout": 0,
                              "stop": 0}
        self.in_flight = 0        # gauge: batches outstanding on device
        self.max_in_flight_seen = 0
        self.sharded_flushes = 0  # flushes placed across > 1 device
        self.devices_used = Histogram(COALESCE_BOUNDS)  # devices/flush
        self.shard_stripes = Histogram(BATCH_BOUNDS)  # stripes/device
        self.mesh_devices = 0     # gauge: devices in the engine's mesh
        self.mesh_dp = 0          # gauge: mesh dp axis
        self.mesh_ec = 0          # gauge: mesh ec axis
        # -- fault-domain counters (ops.dispatch supervised recovery) --
        self.retries = 0          # device re-attempts after a failure
        self.retry_successes = 0  # re-attempts that healed the batch
        self.fallback_batches = 0  # batches served by the host oracle
        self.fallback_stripes = 0  # stripes those batches carried
        self.breaker_opens = 0    # channel breakers opened
        self.breaker_closes = 0   # channel breakers re-closed
        self.probe_successes = 0  # background probes that healed
        self.probe_failures = 0   # background probes that failed
        self.thread_deaths = 0    # engine run-loop deaths observed
        self.thread_restarts = 0  # run-loops revived by supervision
        #: channel -> BREAKER_* (most recent transition per channel
        #: across every engine feeding this sink)
        self.breaker_states: dict[str, int] = {}

    def clear(self) -> None:
        """Reset IN PLACE: live engines hold a reference to this object
        (captured at construction), so reset must not swap it out."""
        with self._lock:
            self.submits = self.stripes_in = 0
            self.batches = self.stripes_out = self.padded_stripes = 0
            self.caller_batches = 0
            self.completed = 0
            self.coalesce = Histogram(COALESCE_BOUNDS)
            self.queue_delay = Histogram(LATENCY_BOUNDS)
            self.queue_depth = Histogram(COALESCE_BOUNDS)
            self.flush_reasons = {"idle": 0, "full": 0, "timeout": 0,
                                  "stop": 0}
            self.in_flight = 0
            self.max_in_flight_seen = 0
            self.sharded_flushes = 0
            self.devices_used = Histogram(COALESCE_BOUNDS)
            self.shard_stripes = Histogram(BATCH_BOUNDS)
            self.mesh_devices = self.mesh_dp = self.mesh_ec = 0
            self.retries = self.retry_successes = 0
            self.fallback_batches = self.fallback_stripes = 0
            self.breaker_opens = self.breaker_closes = 0
            self.probe_successes = self.probe_failures = 0
            self.thread_deaths = self.thread_restarts = 0
            self.breaker_states = {}
        self.phases.clear()

    def record_submit(self, stripes: int) -> None:
        with self._lock:
            self.submits += 1
            self.stripes_in += stripes

    def record_batch(self, *, requests: int, stripes: int, padded: int,
                     reason: str, delays, depth: int,
                     devices: int = 1, shard_stripes: int = 0,
                     on_caller: bool = False) -> None:
        """``on_caller``: a waiting submitter's own thread ran the
        batch (dispatch.submit_waiting), no engine thread did."""
        with self._lock:
            self.batches += 1
            if on_caller:
                self.caller_batches += 1
            self.stripes_out += stripes
            self.padded_stripes += padded
            self.coalesce.add(requests)
            self.queue_depth.add(depth)
            for d in delays:
                self.queue_delay.add(d)
            self.flush_reasons[reason] = \
                self.flush_reasons.get(reason, 0) + 1
            self.devices_used.add(devices)
            if devices > 1:
                self.sharded_flushes += 1
                if shard_stripes:
                    self.shard_stripes.add(shard_stripes)

    def set_mesh_shape(self, dp: int, ec: int) -> None:
        """Record the engine's mesh shape (1x1 = single device)."""
        with self._lock:
            self.mesh_dp = int(dp)
            self.mesh_ec = int(ec)
            self.mesh_devices = int(dp) * int(ec)

    def record_retry(self, success: bool) -> None:
        """One device re-attempt of a failed batch finished."""
        with self._lock:
            self.retries += 1
            if success:
                self.retry_successes += 1

    def record_fallback(self, stripes: int) -> None:
        """One batch was served by the bit-exact host oracle."""
        with self._lock:
            self.fallback_batches += 1
            self.fallback_stripes += stripes

    def record_breaker(self, channel: str, state: int) -> None:
        """A channel breaker transitioned (BREAKER_* constants)."""
        with self._lock:
            prev = self.breaker_states.get(channel, BREAKER_CLOSED)
            self.breaker_states[channel] = state
            # opens = CLOSED -> OPEN only (a failed probe's HALF_OPEN
            # -> OPEN is the SAME outage, not a new one); closes =
            # any re-entry into CLOSED
            if state == BREAKER_OPEN and prev == BREAKER_CLOSED:
                self.breaker_opens += 1
            elif state == BREAKER_CLOSED and prev != BREAKER_CLOSED:
                self.breaker_closes += 1

    def record_probe(self, success: bool) -> None:
        with self._lock:
            if success:
                self.probe_successes += 1
            else:
                self.probe_failures += 1

    def record_thread_death(self, restarted: bool) -> None:
        with self._lock:
            self.thread_deaths += 1
            if restarted:
                self.thread_restarts += 1

    def degraded_channels(self) -> list[str]:
        """Channels currently off the device path (breaker not
        closed) — the mgr health feed."""
        with self._lock:
            return sorted(c for c, s in self.breaker_states.items()
                          if s != BREAKER_CLOSED)

    def _fault_dict(self) -> dict:
        """Under self._lock: the ONE fault-counter shape every surface
        (admin dump, MMgrReport digest, prometheus) serializes — a key
        added here reaches them all in lockstep."""
        return {
            "retries": self.retries,
            "retry_successes": self.retry_successes,
            "fallback_batches": self.fallback_batches,
            "fallback_stripes": self.fallback_stripes,
            "breaker_opens": self.breaker_opens,
            "breaker_closes": self.breaker_closes,
            "probe_successes": self.probe_successes,
            "probe_failures": self.probe_failures,
            "thread_deaths": self.thread_deaths,
            "thread_restarts": self.thread_restarts,
            "breaker_states": dict(self.breaker_states),
        }

    def fault_dump(self) -> dict:
        with self._lock:
            return self._fault_dict()

    def record_complete(self, requests: int) -> None:
        with self._lock:
            self.completed += requests

    def set_in_flight(self, n: int) -> None:
        with self._lock:
            self.in_flight = n
            if n > self.max_in_flight_seen:
                self.max_in_flight_seen = n

    def dump(self) -> dict:
        with self._lock:
            return {
                "submits": self.submits,
                "stripes_in": self.stripes_in,
                "batches": self.batches,
                "caller_batches": self.caller_batches,
                "stripes_out": self.stripes_out,
                "padded_stripes": self.padded_stripes,
                "completed": self.completed,
                "coalesce": self.coalesce.dump(),
                "queue_delay_seconds": self.queue_delay.dump(),
                "queue_depth": self.queue_depth.dump(),
                "flush_reasons": dict(self.flush_reasons),
                "in_flight": self.in_flight,
                "max_in_flight_seen": self.max_in_flight_seen,
                "sharded_flushes": self.sharded_flushes,
                "devices_used": self.devices_used.dump(),
                "shard_stripes": self.shard_stripes.dump(),
                "mesh_devices": self.mesh_devices,
                "mesh_dp": self.mesh_dp,
                "mesh_ec": self.mesh_ec,
            } | {"faults": self._fault_dict()}

    def summary(self) -> dict:
        """chip_smoke.py's digest: amortization in three numbers."""
        with self._lock:
            batches = self.batches
            dev_n = self.devices_used.count
            return {
                "submits": self.submits,
                "device_calls": batches,
                "mean_coalesce": (round(self.coalesce.sum / batches, 2)
                                  if batches else 0.0),
                "p99_queue_delay_ms": round(
                    self.queue_delay.quantile(0.99) * 1e3, 3),
                "calls_per_1k_ops": (round(1000.0 * batches
                                           / self.submits, 1)
                                     if self.submits else 0.0),
                "padded_frac": (round(self.padded_stripes
                                      / (self.stripes_out
                                         + self.padded_stripes), 3)
                                if self.stripes_out else 0.0),
                "flush_reasons": dict(self.flush_reasons),
                "mesh_devices": self.mesh_devices,
                "sharded_flushes": self.sharded_flushes,
                "mean_devices": (round(self.devices_used.sum / dev_n, 2)
                                 if dev_n else 0.0),
            }


class DecodeDispatchStats(DispatchStats):
    """Decode-side twin of DispatchStats (the heterogeneous-matrix
    batched GF decode engine).

    Decodes differ from encodes in ONE dimension the base counters
    cannot see: the recovery matrix varies per erasure pattern, and the
    whole point of the heterogeneous kernel is that requests with
    DIFFERENT patterns still share a device call (pattern index carried
    per stripe, matrices gathered from a stacked table on-device).  So
    this adds the heterogeneity story: how many distinct erasure
    patterns each coalesced call carried, and how large the registered
    pattern table has grown (the matrix-table axis of the jit-cache
    bound).
    """

    __slots__ = ("patterns", "pattern_table_size")

    def __init__(self):
        super().__init__()
        self.patterns = Histogram(COALESCE_BOUNDS)  # distinct patterns/call
        self.pattern_table_size = 0   # gauge: registered recovery patterns

    def clear(self) -> None:
        super().clear()
        with self._lock:
            self.patterns = Histogram(COALESCE_BOUNDS)
            self.pattern_table_size = 0

    def record_patterns(self, distinct: int, table_size: int) -> None:
        """One batched decode ran with ``distinct`` erasure patterns
        against a table of ``table_size`` registered patterns."""
        with self._lock:
            self.patterns.add(distinct)
            if table_size > self.pattern_table_size:
                self.pattern_table_size = table_size

    def dump(self) -> dict:
        d = super().dump()
        with self._lock:
            d["patterns"] = self.patterns.dump()
            d["pattern_table_size"] = self.pattern_table_size
        return d

    def summary(self) -> dict:
        s = super().summary()
        with self._lock:
            n = self.patterns.count
            s["mean_patterns"] = (round(self.patterns.sum / n, 2)
                                  if n else 0.0)
            s["pattern_table_size"] = self.pattern_table_size
        return s


class MappingStats:
    """Counters for the shared PG mapping service (osd.mapping).

    The service's efficiency story: how often an epoch actually
    recomputes (vs reusing cached pool tables), how many PGs each
    epoch really changed (the O(changed) scan bound), how many queued
    epochs were skipped outright (burst coalescing), and how often a
    read had to fall back to the scalar oracle (epoch/object mismatch
    — the correctness escape hatch, not an error).

    The PHASE split answers ROADMAP item 2's standing question — is
    the epoch cost device or host: each computed epoch divides into
    ``device`` (the WALL time of ``OSDMapMapping.update()``: pool
    remaps through the mapper/dispatch engine, pps seeding, operand
    build and the fused ladder — host work and device work both, the
    name notwithstanding; the ``update_to`` span tree of
    common/tracing splits it by stage and says which of it waited on
    the device), ``delta`` (changed-PG candidate extraction: the
    on-device raw-table diff plus state/affinity/override membership),
    and ``host_tail`` (the per-candidate pipeline tail — upmap/
    affinity/temp filtering through ``_finish_from`` — that still
    finishes host-side).

    The FUSED counters track PR 10's device-resident pipeline tail:
    ``fused_epochs``/``unfused_epochs`` count computed epochs that
    published complete packed (up, acting) tables vs those serving the
    host tail, ``fused_lookups`` counts reads answered by a packed-row
    slice (a subset of ``lookups``), and the ``host_tail_share`` gauge
    is the host-tail phase's share of the total epoch cost — the
    number ``profile phases`` watches collapse on a fused cluster.

    The DELTA counters say who compared an epoch's tables with the
    previous epoch's: ``delta_device_diffs`` counts table diffs the
    device served (``mapping_delta_diff``), ``delta_upload_bytes`` the
    bytes of both tables uploaded for them, and ``delta_host_diffs``
    the diffs the host computed (tables at or under
    ``FUSED_DIFF_HOST_MAX``, a changed layout, or no device).

    The CRUSH-TABLE counters say what a changed CRUSH map cost:
    ``crush_table_builds`` counts host builds of a map's bucket tables
    (``crush.fastpath.build_tables``: new crush content),
    ``crush_table_upload_bytes`` the bytes of them put on devices, and
    ``crush_program_builds`` the CRUSH and ladder programs traced anew
    (each a compile or a cache load; the first of the process
    included) — which stands still while edits stay inside a shape
    class.  Two gauges say how the newest Pallas chooseleaf tables lay
    a host out in the leaf kernel (``crush.fastpath.tables_of``):
    ``crush_leaf_columns_per_slab``, the r-columns one 128-lane slab
    carries, and ``crush_leaf_lane_fill``, the widest host's items over
    the lanes its column draws.
    """

    __slots__ = ("_lock", "epoch_updates", "epoch_skips",
                 "pools_recomputed", "pools_reused", "full_rescans",
                 "lookups", "lookup_fallbacks", "update_latency",
                 "changed_pgs", "cached_pgs", "cached_pools",
                 "phase_device", "phase_delta", "phase_host_tail",
                 "fused_epochs", "unfused_epochs", "fused_lookups",
                 "delta_device_diffs", "delta_host_diffs",
                 "delta_upload_bytes", "crush_table_builds",
                 "crush_table_upload_bytes", "crush_program_builds",
                 "crush_leaf_columns_per_slab", "crush_leaf_lane_fill")

    def __init__(self):
        self._lock = lockdep.make_lock("MappingStats::lock")
        self.epoch_updates = 0     # epochs actually computed
        self.epoch_skips = 0       # queued epochs never computed
        self.pools_recomputed = 0  # pool tables rebuilt on device
        self.pools_reused = 0      # pool tables carried over unchanged
        self.full_rescans = 0      # deltas unavailable -> full consumer scan
        self.lookups = 0           # reads served from the cache
        self.lookup_fallbacks = 0  # reads that fell back to the oracle
        self.update_latency = Histogram(LATENCY_BOUNDS)  # per-epoch s
        self.changed_pgs = Histogram(BATCH_BOUNDS)       # delta size/epoch
        self.cached_pgs = 0        # gauge: PGs resident in raw tables
        self.cached_pools = 0      # gauge: pools resident
        # per-epoch phase attribution (see class docstring)
        self.phase_device = Histogram(LATENCY_BOUNDS)
        self.phase_delta = Histogram(LATENCY_BOUNDS)
        self.phase_host_tail = Histogram(LATENCY_BOUNDS)
        # fused-vs-fallback epoch/read accounting (see class docstring)
        self.fused_epochs = 0
        self.unfused_epochs = 0
        self.fused_lookups = 0
        # who diffed the epochs' tables (see class docstring)
        self.delta_device_diffs = 0
        self.delta_host_diffs = 0
        self.delta_upload_bytes = 0
        # what changed CRUSH maps cost (see class docstring)
        self.crush_table_builds = 0
        self.crush_table_upload_bytes = 0
        self.crush_program_builds = 0
        self.crush_leaf_columns_per_slab = 0     # gauges (see above)
        self.crush_leaf_lane_fill = 0.0

    def clear(self) -> None:
        with self._lock:
            self.epoch_updates = self.epoch_skips = 0
            self.pools_recomputed = self.pools_reused = 0
            self.full_rescans = 0
            self.lookups = self.lookup_fallbacks = 0
            self.update_latency = Histogram(LATENCY_BOUNDS)
            self.changed_pgs = Histogram(BATCH_BOUNDS)
            self.cached_pgs = 0
            self.cached_pools = 0
            self.phase_device = Histogram(LATENCY_BOUNDS)
            self.phase_delta = Histogram(LATENCY_BOUNDS)
            self.phase_host_tail = Histogram(LATENCY_BOUNDS)
            self.fused_epochs = self.unfused_epochs = 0
            self.fused_lookups = 0
            self.delta_device_diffs = self.delta_host_diffs = 0
            self.delta_upload_bytes = 0
            self.crush_table_builds = 0
            self.crush_table_upload_bytes = 0
            self.crush_program_builds = 0
            self.crush_leaf_columns_per_slab = 0
            self.crush_leaf_lane_fill = 0.0

    def record_crush_table_build(self) -> None:
        """One host build of a crush map's bucket tables."""
        with self._lock:
            self.crush_table_builds += 1

    def record_crush_table_upload(self, nbytes: int) -> None:
        """Bucket tables put on a device (or replicated over a mesh)."""
        with self._lock:
            self.crush_table_upload_bytes += int(nbytes)

    def record_leaf_layout(self, columns_per_slab: int,
                           lane_fill: float) -> None:
        """The leaf kernel's layout of the newest Pallas chooseleaf
        tables (gauges)."""
        with self._lock:
            self.crush_leaf_columns_per_slab = int(columns_per_slab)
            self.crush_leaf_lane_fill = float(lane_fill)

    def record_program_build(self) -> None:
        """A CRUSH or ladder program traced anew (called from inside
        the trace)."""
        with self._lock:
            self.crush_program_builds += 1

    def record_delta_diff(self, device: bool,
                          upload_bytes: int = 0) -> None:
        """One table diff of an epoch's delta: served by the device
        (with the bytes uploaded for it) or computed on the host."""
        with self._lock:
            if device:
                self.delta_device_diffs += 1
                self.delta_upload_bytes += int(upload_bytes)
            else:
                self.delta_host_diffs += 1

    def record_phases(self, *, device_s: float, delta_s: float,
                      host_tail_s: float) -> None:
        """One computed epoch's phase split (seconds per phase)."""
        with self._lock:
            self.phase_device.add(device_s)
            self.phase_delta.add(delta_s)
            self.phase_host_tail.add(host_tail_s)

    def record_update(self, *, seconds: float, recomputed: int,
                      reused: int, changed: int, cached_pgs: int,
                      cached_pools: int) -> None:
        with self._lock:
            self.epoch_updates += 1
            self.pools_recomputed += recomputed
            self.pools_reused += reused
            self.update_latency.add(seconds)
            self.changed_pgs.add(changed)
            self.cached_pgs = cached_pgs
            self.cached_pools = cached_pools

    def record_skip(self, n: int = 1) -> None:
        with self._lock:
            self.epoch_skips += n

    def record_full_rescan(self) -> None:
        with self._lock:
            self.full_rescans += 1

    def record_lookup(self, hit: bool, fused: bool = False) -> None:
        with self._lock:
            if hit:
                self.lookups += 1
                if fused:
                    self.fused_lookups += 1
            else:
                self.lookup_fallbacks += 1

    def record_fused_epoch(self, fused: bool) -> None:
        """One computed epoch's tail mode: complete packed fused
        tables vs the host-tail fallback."""
        with self._lock:
            if fused:
                self.fused_epochs += 1
            else:
                self.unfused_epochs += 1

    def _host_tail_share(self) -> float:
        """Called under the lock: host-tail share of the total epoch
        phase cost (the collapse gauge)."""
        total = (self.phase_device.sum + self.phase_delta.sum
                 + self.phase_host_tail.sum)
        return (self.phase_host_tail.sum / total) if total else 0.0

    def dump(self) -> dict:
        with self._lock:
            return {
                "epoch_updates": self.epoch_updates,
                "epoch_skips": self.epoch_skips,
                "pools_recomputed": self.pools_recomputed,
                "pools_reused": self.pools_reused,
                "full_rescans": self.full_rescans,
                "lookups": self.lookups,
                "lookup_fallbacks": self.lookup_fallbacks,
                "update_latency_seconds": self.update_latency.dump(),
                "changed_pgs": self.changed_pgs.dump(),
                "cached_pgs": self.cached_pgs,
                "cached_pools": self.cached_pools,
                "fused_epochs": self.fused_epochs,
                "unfused_epochs": self.unfused_epochs,
                "fused_lookups": self.fused_lookups,
                "delta_device_diffs": self.delta_device_diffs,
                "delta_host_diffs": self.delta_host_diffs,
                "delta_upload_bytes": self.delta_upload_bytes,
                "crush_table_builds": self.crush_table_builds,
                "crush_table_upload_bytes": self.crush_table_upload_bytes,
                "crush_program_builds": self.crush_program_builds,
                "crush_leaf_columns_per_slab":
                    self.crush_leaf_columns_per_slab,
                "crush_leaf_lane_fill": round(self.crush_leaf_lane_fill, 6),
                "host_tail_share": round(self._host_tail_share(), 6),
                "phase_seconds": {
                    "device": self.phase_device.dump(),
                    "delta": self.phase_delta.dump(),
                    "host_tail": self.phase_host_tail.dump(),
                },
            }

    def phase_summary(self) -> dict:
        """Per-phase totals + shares across computed epochs (the
        MMgrReport digest / `profile phases` mapping row)."""
        with self._lock:
            sums = {"device": self.phase_device.sum,
                    "delta": self.phase_delta.sum,
                    "host_tail": self.phase_host_tail.sum}
            epochs = self.phase_device.count
            fused, unfused = self.fused_epochs, self.unfused_epochs
        total = sum(sums.values())
        return {"seconds": {k: round(v, 6) for k, v in sums.items()},
                "share": {k: (round(v / total, 4) if total else 0.0)
                          for k, v in sums.items()},
                "epochs": epochs,
                "fused_epochs": fused,
                "unfused_epochs": unfused}

    def summary(self) -> dict:
        """The digest chip_smoke.py and perfbench read:
        incrementality in a few numbers."""
        with self._lock:
            n = self.update_latency.count
            return {
                "epoch_updates": self.epoch_updates,
                "epoch_skips": self.epoch_skips,
                "pools_recomputed": self.pools_recomputed,
                "pools_reused": self.pools_reused,
                "mean_update_ms": (round(self.update_latency.sum / n
                                         * 1e3, 3) if n else 0.0),
                "mean_changed_pgs": (round(self.changed_pgs.sum
                                           / self.changed_pgs.count, 1)
                                     if self.changed_pgs.count else 0.0),
                "lookups": self.lookups,
                "lookup_fallbacks": self.lookup_fallbacks,
                "fused_epochs": self.fused_epochs,
                "unfused_epochs": self.unfused_epochs,
                "fused_lookups": self.fused_lookups,
                "delta_device_diffs": self.delta_device_diffs,
                "delta_host_diffs": self.delta_host_diffs,
                "delta_upload_bytes": self.delta_upload_bytes,
                "crush_table_builds": self.crush_table_builds,
                "crush_table_upload_bytes": self.crush_table_upload_bytes,
                "crush_program_builds": self.crush_program_builds,
                "crush_leaf_columns_per_slab":
                    self.crush_leaf_columns_per_slab,
                "crush_leaf_lane_fill": round(self.crush_leaf_lane_fill, 6),
                "host_tail_share": round(self._host_tail_share(), 6),
            }


class ScrubStats:
    """Background-integrity counters (deep scrub + verified repair).

    Process-global like the dispatch sinks: every OSD in the process
    folds its scrub accounting in (the per-daemon copies feed
    ``dump_scrub_stats`` and the ``ceph_scrub_*`` prometheus families
    through the MMgrReport tail), so this sink is the cluster-wide
    roll-up the thrasher's scrub-storm gate polls —
    "every injected corruption detected and repaired" is a claim
    about the whole MiniCluster, not one daemon."""

    #: the counter vocabulary (unknown keys are still accepted — the
    #: sink must never make a daemon's accounting throw)
    FIELDS = ("sweeps", "pgs_scrubbed", "objects_scrubbed",
              "digest_batches", "digest_objects", "scalar_fallbacks",
              "inconsistent", "repaired", "repair_unverified",
              "missing_peer_scrubs", "missing_peer_retries")

    def __init__(self):
        self._lock = lockdep.make_lock("ScrubStats::lock")
        self._counts: dict[str, int] = {f: 0 for f in self.FIELDS}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def clear(self) -> None:
        with self._lock:
            self._counts = {f: 0 for f in self.FIELDS}

    def dump(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def summary(self) -> dict:
        """The thrasher's digest: the integrity story in a few
        numbers — how much was checked, how it was digested (batched
        vs scalar), and whether every found inconsistency ended in a
        VERIFIED repair."""
        with self._lock:
            c = dict(self._counts)
        batched = c.get("digest_objects", 0)
        scalar_batches = c.get("scalar_fallbacks", 0)
        return {
            "objects_scrubbed": c.get("objects_scrubbed", 0),
            "pgs_scrubbed": c.get("pgs_scrubbed", 0),
            "digest_batches": c.get("digest_batches", 0),
            "batched_digest_objects": batched,
            "scalar_fallback_batches": scalar_batches,
            "inconsistent": c.get("inconsistent", 0),
            "repaired": c.get("repaired", 0),
            "repair_unverified": c.get("repair_unverified", 0),
            "missing_peer_scrubs": c.get("missing_peer_scrubs", 0),
        }


class BlueStoreStats:
    """Device-resident objectstore counters (the ``bluestore_data``
    channel's write/read offload plus block compression and the KV
    journal's truncation ledger).

    Process-global like the other sinks: every BlueStoreLite in the
    process folds its accounting in; ``bluestore_dump`` and the
    ``ceph_bluestore_*`` prometheus families read it, and
    chip_smoke.py and perfbench poll ``summary()``."""

    FIELDS = ("csum_batches", "csum_blocks", "csum_scalar_blocks",
              "csum_fallbacks", "read_verify_batches",
              "read_verify_blocks", "write_runs", "write_run_blocks",
              "read_runs", "read_run_blocks", "compress_blocks",
              "compress_rejected", "compress_roundtrip_failures",
              "decompress_errors", "csum_errors",
              "kv_journal_truncated", "kv_journal_lost_bytes")

    def __init__(self):
        self._lock = lockdep.make_lock("BlueStoreStats::lock")
        self._counts: dict[str, int] = {f: 0 for f in self.FIELDS}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def clear(self) -> None:
        with self._lock:
            self._counts = {f: 0 for f in self.FIELDS}

    def dump(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def summary(self) -> dict:
        """chip_smoke.py / perfbench digest: how the store's checksum
        work was computed (batched device calls vs scalar), what compression
        did, and whether anything went wrong."""
        with self._lock:
            c = dict(self._counts)
        return {
            "csum_batches": c.get("csum_batches", 0),
            "batched_csum_blocks": c.get("csum_blocks", 0),
            "scalar_csum_blocks": c.get("csum_scalar_blocks", 0),
            "csum_fallbacks": c.get("csum_fallbacks", 0),
            "read_verify_batches": c.get("read_verify_batches", 0),
            "read_verify_blocks": c.get("read_verify_blocks", 0),
            # block-file I/O by extent run: blocks / runs is the
            # blocks a positioned read or write moved
            "write_runs": c.get("write_runs", 0),
            "write_run_blocks": c.get("write_run_blocks", 0),
            "read_runs": c.get("read_runs", 0),
            "read_run_blocks": c.get("read_run_blocks", 0),
            "compress_blocks": c.get("compress_blocks", 0),
            "compress_rejected": c.get("compress_rejected", 0),
            "csum_errors": c.get("csum_errors", 0),
            "kv_journal_truncated": c.get("kv_journal_truncated", 0),
        }


#: ledger bucket for work submitted WITHOUT a cost tag.  Untagged
#: device time is attributed here — visibly — never dropped: the
#: conservation property (sum over tenants == engine busy-seconds)
#: holds only because every batch lands somewhere.
UNTAGGED_TENANT = "_untagged"

#: ledger bucket absorbing tenants beyond the table bound
#: (kernel_tenant_ledger_max_tenants): overflow stays counted, so
#: conservation survives a tenant-name flood; only per-name
#: attribution degrades.
OVERFLOW_TENANT = "_overflow"

#: default bound on distinct tenants the ledger tracks
TENANT_LEDGER_MAX_DEFAULT = 1024


class TenantDeviceStats:
    """Tenant-attributed device-time ledger (per-tenant × engine ×
    channel).

    The dispatch engines apportion each completed batch's busy
    integral (``compute_s × devices``, the same product PhaseStats
    accumulates into ``busy_seconds``) to the batch's requests by
    stripe share and record it here under the request's ``cost_tag``
    (tenant + dmClock class).  Rows carry device-seconds, batch/request
    /stripe counts and a queue-wait histogram (submit → dispatch, the
    same window PhaseStats calls queue_wait); ``dump`` adds
    share-of-device gauges.

    Feeds ``dump_tenant_usage`` (admin socket), the MMgrReport
    ``tenant_usage`` tail (→ mgr tenant_feed → the slo module and the
    ``ceph_tenant_device_seconds_total`` prometheus family), and
    ``tools/profile_report.py``'s per-tenant table.

    Attribution is measurement-only: nothing here feeds back into
    batch admission (that is ROADMAP item 1's unified runtime).
    """

    def __init__(self):
        self._lock = lockdep.make_lock("TenantDeviceStats::lock")
        #: (tenant, engine, channel) -> row dict
        self._rows: dict[tuple, dict] = {}
        self._tenants: set = set()
        self.enabled = True
        self.max_tenants = TENANT_LEDGER_MAX_DEFAULT

    def _key_tenant(self, tenant) -> str:
        t = str(tenant) if tenant else UNTAGGED_TENANT
        if t in self._tenants:
            return t
        if len(self._tenants) >= self.max_tenants and t not in (
                UNTAGGED_TENANT, OVERFLOW_TENANT):
            return OVERFLOW_TENANT
        self._tenants.add(t)
        return t

    def record_batch(self, tenant, qos_class, *, engine: str,
                     channel: str, device_seconds: float,
                     requests: int, stripes: int,
                     queue_waits=()) -> None:
        """Account one tenant's share of one completed device batch."""
        if not self.enabled:
            return
        with self._lock:
            t = self._key_tenant(tenant)
            row = self._rows.get((t, engine, channel))
            if row is None:
                row = self._rows[(t, engine, channel)] = {
                    "qos_class": str(qos_class or ""),
                    "device_seconds": 0.0, "batches": 0,
                    "requests": 0, "stripes": 0,
                    "queue_wait": Histogram(LATENCY_BOUNDS)}
            row["device_seconds"] += float(device_seconds)
            row["batches"] += 1
            row["requests"] += int(requests)
            row["stripes"] += int(stripes)
            for w in queue_waits:
                row["queue_wait"].add(max(0.0, float(w)))

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._tenants.clear()

    def total_device_seconds(self) -> float:
        with self._lock:
            return sum(r["device_seconds"] for r in self._rows.values())

    def dump(self) -> dict:
        """Full ledger (the ``dump_tenant_usage`` admin payload):
        tenant -> engine -> channel rows with queue-wait histograms,
        plus per-tenant share-of-device gauges."""
        with self._lock:
            rows = {k: dict(r) for k, r in self._rows.items()}
        total = sum(r["device_seconds"] for r in rows.values())
        tenants: dict = {}
        for (t, eng, ch), r in sorted(rows.items()):
            trec = tenants.setdefault(
                t, {"device_seconds": 0.0, "share": 0.0, "engines": {}})
            trec["device_seconds"] += r["device_seconds"]
            trec["engines"].setdefault(eng, {})[ch] = {
                "qos_class": r["qos_class"],
                "device_seconds": r["device_seconds"],
                "batches": r["batches"], "requests": r["requests"],
                "stripes": r["stripes"],
                "queue_wait": r["queue_wait"].dump()}
        for trec in tenants.values():
            trec["share"] = (trec["device_seconds"] / total
                             if total else 0.0)
        return {"tenants": tenants, "total_device_seconds": total}

    def digest(self) -> dict:
        """Compact ledger (no histogram buckets) — the MMgrReport
        ``tenant_usage`` tail."""
        with self._lock:
            rows = {k: dict(r) for k, r in self._rows.items()}
        total = sum(r["device_seconds"] for r in rows.values())
        tenants: dict = {}
        for (t, eng, ch), r in sorted(rows.items()):
            trec = tenants.setdefault(
                t, {"device_seconds": 0.0, "share": 0.0, "engines": {}})
            trec["device_seconds"] += r["device_seconds"]
            trec["engines"].setdefault(eng, {})[ch] = {
                "qos_class": r["qos_class"],
                "device_seconds": round(r["device_seconds"], 9),
                "batches": r["batches"], "requests": r["requests"],
                "stripes": r["stripes"],
                "wait_p99_s": round(r["queue_wait"].quantile(0.99), 6),
                "wait_sum_s": round(r["queue_wait"].sum, 9),
                "wait_count": r["queue_wait"].count}
        for trec in tenants.values():
            trec["share"] = round(
                trec["device_seconds"] / total if total else 0.0, 6)
            trec["device_seconds"] = round(trec["device_seconds"], 9)
        return {"tenants": tenants,
                "total_device_seconds": round(total, 9)}


class KernelTelemetry:
    """The registry: one KernelStats per kernel name."""

    def __init__(self):
        self._lock = lockdep.make_lock("KernelTelemetry::lock")
        self._kernels: dict[str, KernelStats] = {}
        self.dispatch = DispatchStats()
        self.decode_dispatch = DecodeDispatchStats()
        self.mapping = MappingStats()
        self.scrub = ScrubStats()
        self.bluestore = BlueStoreStats()
        self.tenant = TenantDeviceStats()
        #: block_until_ready before closing each latency sample
        self.fence_for_timing = False
        #: master switch; off-path cost when False is one attribute read
        self.enabled = True

    def kernel(self, name: str) -> KernelStats:
        ks = self._kernels.get(name)
        if ks is None:
            with self._lock:
                ks = self._kernels.setdefault(name, KernelStats(name))
        return ks

    def dump(self) -> dict:
        with self._lock:
            kernels = list(self._kernels.values())
        return {ks.name: ks.dump() for ks in kernels}

    def reset(self) -> None:
        """Drop all samples (test isolation).  Signature sets go
        too, but jit caches live in jax — miss counting stays a delta
        against the real cache, so reset never fabricates misses."""
        with self._lock:
            self._kernels.clear()
        self.dispatch.clear()
        self.decode_dispatch.clear()
        self.mapping.clear()
        self.scrub.clear()
        self.bluestore.clear()
        self.tenant.clear()


_REG = KernelTelemetry()


def registry() -> KernelTelemetry:
    return _REG


def dump() -> dict:
    return _REG.dump()


def reset() -> None:
    _REG.reset()


def dispatch_stats() -> DispatchStats:
    """The process-global coalescing-engine counters.  Engines created
    without an explicit stats sink feed this (the MiniCluster's
    daemons share it exactly like the kernel registry); dump_dispatch
    and the mgr's ceph_kernel_coalesce_* families read it."""
    return _REG.dispatch


def dispatch_dump() -> dict:
    return _REG.dispatch.dump()


def dispatch_summary() -> dict:
    return _REG.dispatch.summary()


def decode_dispatch_stats() -> DecodeDispatchStats:
    """The decode-side coalescing counters (heterogeneous-matrix
    batched GF decode): engines built by ``ctx.decode_dispatch_engine``
    feed this, the codec's batched decode fn records the per-call
    pattern heterogeneity into it, and the mgr's
    ``ceph_kernel_decode_coalesce_*`` families read it."""
    return _REG.decode_dispatch


def decode_dispatch_dump() -> dict:
    return _REG.decode_dispatch.dump()


def scrub_stats() -> ScrubStats:
    """The process-global background-integrity counters: every OSD's
    scrub path feeds this alongside its own per-daemon accounting;
    the thrasher's scrub-storm gate reads the cluster-wide roll-up
    here."""
    return _REG.scrub


def scrub_dump() -> dict:
    return _REG.scrub.dump()


def scrub_summary() -> dict:
    return _REG.scrub.summary()


def bluestore_stats() -> BlueStoreStats:
    """The process-global device-resident-objectstore counters: every
    BlueStoreLite's write/read/compression paths feed this;
    ``dump_bluestore_stats``, the ``ceph_bluestore_*`` prometheus
    families, chip_smoke.py and perfbench read it."""
    return _REG.bluestore


def bluestore_dump() -> dict:
    return _REG.bluestore.dump()


def bluestore_summary() -> dict:
    return _REG.bluestore.summary()


def tenant_stats() -> TenantDeviceStats:
    """The process-global tenant-attributed device-time ledger: both
    dispatch engines apportion completed batches here by cost tag;
    ``dump_tenant_usage``, the MMgrReport ``tenant_usage`` tail and
    the ``ceph_tenant_device_seconds_total`` families read it."""
    return _REG.tenant


def tenant_dump() -> dict:
    return _REG.tenant.dump()


def tenant_usage_digest() -> dict:
    """Compact per-tenant ledger digest — the MMgrReport carriage."""
    return _REG.tenant.digest()


def mapping_stats() -> MappingStats:
    """The process-global shared-mapping-service counters: every
    SharedPGMappingService (one per context) feeds this, the
    ``dump_mapping_stats`` admin command and the mgr's
    ``ceph_kernel_mapping_*`` families read it."""
    return _REG.mapping


def mapping_dump() -> dict:
    return _REG.mapping.dump()


def mapping_summary() -> dict:
    return _REG.mapping.summary()


def pipeline_profile_dump(include_recent: bool = True) -> dict:
    """The full per-engine pipeline phase profile — the
    ``dump_pipeline_profile`` admin-socket payload: phase histograms
    per kernel family, the compile ledger, utilization gauges, and the
    bounded ring of recent per-batch records, for both dispatch
    engines, plus the mapping service's epoch phase split.
    ``include_recent=False`` drops the ring (aggregate-only readers:
    the prometheus scrape)."""
    return {"encode": _REG.dispatch.phases.dump(include_recent),
            "decode": _REG.decode_dispatch.phases.dump(include_recent),
            "mapping": _REG.mapping.phase_summary()}


def fault_digest() -> dict:
    """Per-engine fault/degradation digest — the MMgrReport v4
    ``faults`` tail (mgr health raises KERNEL_DEGRADED while any
    reported channel breaker is not closed), the ``dump_fault_stats``
    admin payload, and the thrasher chaos gate's reconvergence probe."""
    return {"encode": _REG.dispatch.fault_dump(),
            "decode": _REG.decode_dispatch.fault_dump()}


def pipeline_profile_digest() -> dict:
    """Compact phase-share digest (no histograms, no ring) — the
    MMgrReport v4 carriage; perfbench reads it too."""
    return {"encode": _REG.dispatch.phases.summary(),
            "decode": _REG.decode_dispatch.phases.summary(),
            "mapping": _REG.mapping.phase_summary()}


def set_profile_ring(n) -> None:
    """Rebind the per-engine recent-batch profile ring bound (the
    ``kernel_profile_ring`` option); existing records are kept up to
    the new bound, newest first."""
    global _profile_ring
    _profile_ring = max(1, int(n))
    _REG.dispatch.phases._resize_ring(_profile_ring)
    _REG.decode_dispatch.phases._resize_ring(_profile_ring)


def set_fence_for_timing(on: bool) -> None:
    _REG.fence_for_timing = bool(on)


def set_enabled(on: bool) -> None:
    _REG.enabled = bool(on)


def configure_from_conf(conf) -> None:
    """Bind the fence knob to a context's config (option
    ``kernel_fence_for_timing``), with hot reload via observer.

    The registry is process-global while configs are per-context
    (multi-daemon processes construct many): construction only turns
    fencing ON when this conf explicitly enables it — it never resets
    the global back to the default, or every later daemon/client
    construction would silently undo an operator's `config set` on
    another daemon.  Runtime changes propagate through the observer.
    """
    try:
        if conf.get("kernel_fence_for_timing"):
            set_fence_for_timing(True)
        conf.add_observer("kernel_fence_for_timing",
                          lambda _n, v: set_fence_for_timing(v))
    except KeyError:   # option table without the knob (stripped config)
        pass
    try:
        ring = int(conf.get("kernel_profile_ring"))
        if ring != PROFILE_RING_DEFAULT:
            set_profile_ring(ring)
        conf.add_observer("kernel_profile_ring",
                          lambda _n, v: set_profile_ring(v))
    except KeyError:
        pass
    # tenant-ledger knobs: same only-turn-away-from-default rule as the
    # fence — a later context's default construction must not undo an
    # operator's `config set` on another daemon in the same process
    try:
        if not bool(conf.get("kernel_tenant_ledger_enabled")):
            _REG.tenant.enabled = False
        conf.add_observer(
            "kernel_tenant_ledger_enabled",
            lambda _n, v: setattr(_REG.tenant, "enabled", bool(v)))
    except KeyError:
        pass
    try:
        cap = int(conf.get("kernel_tenant_ledger_max_tenants"))
        if cap != TENANT_LEDGER_MAX_DEFAULT:
            _REG.tenant.max_tenants = max(1, cap)
        conf.add_observer(
            "kernel_tenant_ledger_max_tenants",
            lambda _n, v: setattr(_REG.tenant, "max_tenants",
                                  max(1, int(v))))
    except KeyError:
        pass


def timed_kernel(name: str, fn, *, batch: int = 0, bytes_in: int = 0,
                 bytes_out: int = 0, cache_entries=None, signature=None):
    """Run ``fn()`` (one device call) under telemetry.

    cache_entries: zero-arg callable returning the current jit
    compile-cache entry count for the kernel's entry points; the delta
    across the call is the miss count.  signature: hashable shape key
    used as the fallback miss detector when cache_entries is None or
    fails.  Tracer results (outer jit trace in progress) are counted
    but not timed.
    """
    if not _REG.enabled:
        return fn()
    ks = _REG.kernel(name)
    # kernel span on the calling op's trace (common/tracing): a traced
    # slow write shows WHERE its device time went — the span is the
    # call's wall time, its attributes the operand and result bytes and
    # whether the call retraced.  Free when the thread is untraced.
    dev_span = tracing.begin_span(f"kernel {name}", "device") \
        if tracing.current() else None
    before = None
    if cache_entries is not None:
        try:
            before = cache_entries()
        except Exception:
            before = None
    t0 = time.perf_counter()
    try:
        out = fn()
    except BaseException:
        # the failing call is the one most worth seeing in the trace:
        # close the span instead of leaking it open (end=None)
        if dev_span is not None:
            tracing.set_attrs(dev_span, kernel=name, error=True)
            tracing.finish_span(dev_span)
        raise
    if _is_tracer(out):
        with ks._lock:
            ks.traced += 1
        if dev_span is not None:
            tracing.set_attrs(dev_span, kernel=name, traced=True)
            tracing.finish_span(dev_span)
        return out
    if _REG.fence_for_timing:
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:
            pass
    dt = time.perf_counter() - t0
    misses = 0
    if before is not None:
        try:
            misses = max(0, cache_entries() - before)
        except Exception:
            before = None
    if before is None and signature is not None:
        misses = 1 if ks.note_signature(signature) else 0
    ks.record(dt, batch=batch, bytes_in=bytes_in, bytes_out=bytes_out,
              misses=misses)
    if dev_span is not None:
        # fenced, the span is the host's wait for the device's result
        tracing.set_attrs(dev_span, kernel=name, batch=batch,
                          bytes_in=bytes_in, bytes_out=bytes_out,
                          retrace=misses > 0,
                          fenced=_REG.fence_for_timing,
                          device_wait=bool(_REG.fence_for_timing))
        tracing.finish_span(dev_span)
    return out


def _is_tracer(x) -> bool:
    # jax is only imported if the call site already produced a jax
    # value; a numpy/no-jax result short-circuits on the module check
    if type(x).__module__.split(".")[0] != "jax":
        return False
    import jax
    return isinstance(x, jax.core.Tracer)
