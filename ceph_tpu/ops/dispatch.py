"""Cross-op device-call coalescing: the async dispatch engine.

Every device call pays a fixed host-side cost (build, host-to-device
copy, launch, device-to-host copy) whatever its batch size, and an OSD
EC write that issues its own synchronous call pays it alone.  This
module amortizes it the way serving systems do (Clipper's adaptive
batching; "The Tail at Scale"'s keep-the-pipeline-full): concurrent
requests from DIFFERENT ops/PGs stack on the batch axis into ONE padded
device call.  The per-call cost on the attached chip: not measured
(root PERF.md).

Three mechanisms, one engine:

* **cross-op coalescing** — ``submit(key, fn, data)`` queues the
  request; the dispatch thread collects every queued request with the
  same ``key`` (same kernel + operand identity + trailing shape) into
  one call.  Flush policy: immediately while the engine is idle (a lone
  op never waits — single-op latency cannot regress), else accumulate
  until ``max_stripes`` or ``max_delay_us``, whichever first.  The
  batch is self-clocking: while batch N computes, batch N+1's requests
  pile up, exactly the adaptive-batching feedback loop.  The same
  rule one step further: a submitter that will block on its result at
  once (``submit_waiting``) runs a lone request on its OWN thread while
  the engine is idle — no hand-over to the two threads below and back
  — and queues like everyone else as soon as the engine is busy.

* **shape bucketing** — the coalesced batch rounds UP to a power-of-two
  stripe count with all-zero padding rows (bit-exact for every kernel
  here: zeros encode to zeros under a linear code, and padded CRUSH
  lanes are sliced off before delivery).  The jit compile cache is then
  bounded by the bucket table, not by the distribution of client write
  sizes.

* **async double-buffered submission** — the dispatch thread issues the
  device call (the runtime acks before execution: h2d of batch N+1
  overlaps compute of batch N) and a completion thread materializes
  results in FIFO order, resolving per-request futures/continuations.
  ``max_in_flight`` bounds outstanding device calls (2 = classic double
  buffering).

* **mesh-sharded fan-out** — an engine built with a device ``mesh``
  places every coalesced batch ACROSS the mesh before the kernel sees
  it: the shape bucket rounds up to a multiple of the mesh size (every
  shard non-empty, the jit cache still bounded by the bucket table —
  now keyed by (bucket, mesh) because committed input shardings are
  part of jax's compile-cache key), the batch is ``device_put`` with a
  ``NamedSharding`` splitting the stripe/PG axis over the ``("dp",
  "ec")`` axes, and aux side arrays shard in lockstep.  XLA partitions
  the jitted kernel (GSPMD), results stay device-resident and sharded
  until the completion thread materializes them.  One flush saturates
  every chip instead of one; bit-exactness is untouched because the
  kernels are elementwise/row-independent along the coalesce axis.  In
  a multi-controller deployment (jax.distributed) the engine's own
  flushes are process-local data, so placement uses the GLOBAL mesh's
  process-local submesh — each process's engine saturates its ICI
  domain while collective SPMD work spans the full mesh.

Delivery-order contract: completions for one ``key`` are delivered in
submission order, on a single completion thread.  The OSD leans on this
for per-object log/commit ordering (osd/daemon._ec_write_committed).

Everything here is numpy + threading; jax enters only through the
``fn`` callables the submitters pass — and, on mesh-sharded engines,
through the lazily-built ``_MeshPlacement`` scaffolding — so importing
this module never pulls in the kernel stack (same rule as
ops.telemetry).
"""

from __future__ import annotations

import random
import threading
import time
from collections import OrderedDict, deque

import numpy as np

from ceph_tpu.common import failpoint, lockdep, tracing
from ceph_tpu.ops import telemetry
from ceph_tpu.qos.dmclock import BACKGROUND_BEST_EFFORT


class EngineWedgedError(RuntimeError):
    """The engine's thread-restart budget is exhausted: every pending
    and in-flight waiter has been failed with this error, ``flush()``
    raises it, and new submits run inline (never silently dropped,
    never hung)."""


class DispatchFuture:
    """Completion handle for one submitted request.

    Callbacks added before completion run on the engine's completion
    thread, in batch order then submission order — the delivery-order
    contract continuations rely on.  Callbacks added after completion
    run inline on the caller.
    """

    __slots__ = ("_ev", "_value", "_exc", "_cbs", "_lock")

    def __init__(self):
        self._ev = threading.Event()
        self._value = None
        self._exc: BaseException | None = None
        self._cbs: list = []
        self._lock = lockdep.make_lock("DispatchFuture::lock")

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: float | None = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("dispatch result not ready")
        if self._exc is not None:
            raise self._exc
        return self._value

    def exception(self, timeout: float | None = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("dispatch result not ready")
        return self._exc

    def add_done_callback(self, cb) -> None:
        with self._lock:
            if not self._ev.is_set():
                self._cbs.append(cb)
                return
        cb(self)

    def _deliver(self, value, exc: BaseException | None) -> None:
        with self._lock:
            if self._ev.is_set():
                # first delivery wins: a revived run-loop re-fanning
                # its batch, or _wedge racing the live completion
                # thread, must never overwrite a delivered result
                # (an acked op's value flipping to EngineWedgedError
                # — or the reverse — after callbacks already fired)
                return
            self._value = value
            self._exc = exc
            self._ev.set()
            cbs, self._cbs = self._cbs, []
        for cb in cbs:
            try:
                cb(self)
            except Exception as e:
                from ceph_tpu.common.logging import dout
                dout("dispatch", 0, "dispatch continuation failed: %r", e)


class _Request:
    __slots__ = ("key", "fn", "data", "aux", "stripes", "future",
                 "t_submit", "label", "cache_entries", "trace",
                 "place", "fallback", "cost_tag")

    def __init__(self, key, fn, data, stripes, label=None,
                 cache_entries=None, aux=None, place=True,
                 fallback=None, cost_tag=None):
        self.place = place
        #: (tenant, dmclock class) for the device-time ledger; None
        #: lands in the visible _untagged bucket at completion
        self.cost_tag = cost_tag
        #: bit-exact host-path oracle for this request's kernel channel
        #: (ec_encode_ref / the host pattern decode / scalar CRUSH /
        #: the numpy ladder): the supervised-recovery ladder runs it
        #: when the device path stays broken past the retry budget, and
        #: an OPEN channel breaker routes batches straight to it.
        #: Requests sharing a key must agree on it (same submitter).
        self.fallback = fallback
        self.key = key
        self.fn = fn
        self.data = data
        self.aux = aux
        self.stripes = stripes
        self.future = DispatchFuture()
        self.t_submit = time.monotonic()
        self.label = label if label is not None else (
            key[0] if isinstance(key, tuple) and key
            and isinstance(key[0], str) else "dispatch")
        self.cache_entries = cache_entries
        # a traced submitter gets a per-request engine span with the
        # batch's phases as children, recorded at delivery
        # (_deliver_traced); timed_kernel's span runs on the engine
        # thread, outside every op's trace context
        tid = tracing.current()
        self.trace = (tid, tracing.current_span()) if tid else None


class _Batch:
    __slots__ = ("out", "reqs", "slices", "exc", "t_dispatch", "misses",
                 "profile", "via_fallback", "on_caller")

    def __init__(self, out, reqs, slices, exc=None, t_dispatch=0.0,
                 misses=None, profile=None, via_fallback=False,
                 on_caller=False):
        self.out = out
        self.reqs = reqs
        self.slices = slices
        self.exc = exc
        self.t_dispatch = t_dispatch
        self.misses = misses
        #: the dispatch thread already served this batch from the host
        #: oracle (open breaker): completion must not re-enter the
        #: device-retry ladder on its error
        self.via_fallback = via_fallback
        #: the submitter's own thread built this batch and completes it
        #: (submit_waiting): it holds its place in _inflight like any
        #: other, and the completion thread leaves it alone
        self.on_caller = on_caller
        #: dispatch-side half of the phase ledger (telemetry.PHASES):
        #: monotonic anchors + build/place/launch durations; the
        #: completion thread closes compute/materialize/deliver and
        #: records the batch profile.  None when the dispatch died
        #: before the ledger started.
        self.profile = profile


def bucket_stripes(n: int) -> int:
    """Power-of-two shape bucket for a batch of n rows (n >= 1)."""
    return 1 << max(0, (n - 1).bit_length())


def mesh_bucket_stripes(n: int, devices: int) -> int:
    """Shape bucket for a mesh of ``devices``: the power-of-two bucket
    rounded UP to a multiple of the mesh size, so the sharded leading
    axis divides evenly (jax rejects uneven NamedSharding placement)
    and every device's shard is non-empty.  For power-of-two meshes
    this is just max(bucket, devices); the bucket table stays bounded
    either way (it is a function of the pow-2 bucket)."""
    b = bucket_stripes(n)
    if devices > 1 and b % devices:
        b += devices - b % devices
    return max(b, devices)


def _mesh_shape(mesh) -> tuple[int, int]:
    """(dp, ec) gauge values for a mesh — the ONE place the
    missing-axis defaults live (a dp-only mesh is dp x 1, never
    dp x 0): (0, 0) means no mesh."""
    if mesh is None:
        return 0, 0
    shape = dict(mesh.shape)
    ec = int(shape.get("ec", 1))
    dp = int(shape.get("dp", max(1, int(mesh.size) // max(ec, 1))))
    return dp, ec


class _MeshPlacement:
    """Host-side placement scaffolding for a mesh-sharded engine.

    Built lazily on the first flush of an engine holding a mesh (so
    engines without one never import jax), it caches one
    ``NamedSharding`` per operand rank: the leading (stripe/PG) axis
    splits over every mesh axis, trailing axes replicate.  In a
    multi-controller deployment the engine's own flushes are
    process-local host data, so placement targets the GLOBAL mesh's
    process-local submesh (the process's ICI domain); single-process
    engines place over the full mesh.
    """

    __slots__ = ("mesh", "place_mesh", "devices", "_shardings")

    def __init__(self, mesh):
        import jax
        self.mesh = mesh
        self.place_mesh = (mesh.local_mesh if jax.process_count() > 1
                           else mesh)
        self.devices = int(self.place_mesh.size)
        self._shardings: dict = {}

    def sharding(self, ndim: int):
        s = self._shardings.get(ndim)
        if s is None:
            from jax.sharding import NamedSharding, PartitionSpec
            spec = PartitionSpec(tuple(self.place_mesh.axis_names),
                                 *([None] * (ndim - 1)))
            s = NamedSharding(self.place_mesh, spec)
            self._shardings[ndim] = s
        return s

    def put(self, arr):
        import jax
        return jax.device_put(arr, self.sharding(arr.ndim))


#: exception classes the retry ladder treats as PERMANENT (programming
#: errors — shape mismatches, bad operands): retrying cannot help and
#: the host oracle would fail identically, so they fan immediately
_PERMANENT_ERRORS = (ValueError, TypeError, KeyError, IndexError,
                     AttributeError)


class _Breaker:
    """Per-channel circuit breaker state (guarded by the engine cv).

    closed -> open after ``breaker_threshold`` consecutive device-path
    batch failures (each already past its retry budget); while open
    (or half-open, mid-probe) batches with a host fallback skip the
    device entirely; the background probe replays a retained one-stripe
    sample of the last failed batch and a success re-closes."""

    __slots__ = ("state", "consecutive", "probe")

    def __init__(self):
        self.state = telemetry.BREAKER_CLOSED
        self.consecutive = 0
        self.probe = None        # (fn, data_sample, aux_sample)


class DeviceDispatchEngine:
    """Per-CephContext coalescing dispatcher for batched device kernels.

    ``submit(key, fn, data)``: data is a numpy array whose LEADING axis
    is the coalesce axis (stripes for EC, x-lanes for CRUSH); fn maps a
    batched array of the same trailing shape to a device (or host)
    array with the matching leading axis.  All requests sharing ``key``
    must be mutually batchable (same fn semantics, same trailing
    shape); the key should therefore encode the operand identity and
    the trailing dimensions.
    """

    def __init__(self, *, max_stripes: int = 2048,
                 max_delay_us: float = 250.0, max_in_flight: int = 2,
                 name: str = "dispatch", stats=None, mesh=None):
        self.max_stripes = int(max_stripes)
        self.max_delay_us = float(max_delay_us)
        self.max_in_flight = max(1, int(max_in_flight))
        self.name = name
        self.stats = stats if stats is not None \
            else telemetry.dispatch_stats()
        #: ledger "engine" dimension: the stats sink decides (the two
        #: context engines are distinguished exactly this way), so
        #: per-test engines with private sinks still label sensibly
        self._ledger_engine = ("decode" if isinstance(
            self.stats, telemetry.DecodeDispatchStats) else "encode")
        #: jax.sharding.Mesh (or None): batches fan out across it —
        #: see the module docstring's mesh-sharded fan-out mechanism
        self._mesh = mesh
        self._placement: _MeshPlacement | None = None
        self._cv = lockdep.make_condition(
            f"DeviceDispatchEngine::cv({name})")
        self._pending: deque[_Request] = deque()
        #: per-key pending stripe totals, maintained incrementally so
        #: the flush-policy checks never rescan the queue
        self._key_totals: dict = {}
        self._inflight: deque[_Batch] = deque()
        self._building = 0          # batches being built/dispatched
        self._caller_runs = 0       # requests running on their caller
        self._caller_launching = False  # ... one of them not yet in flight
        self._stop = False
        #: role -> live thread ("submit" dispatches, "complete"
        #: materializes); supervised — see _thread_main
        self._threads: dict[str, threading.Thread] = {}
        # -- fault domain (retry / breaker / supervision knobs; the
        # context wires them to the kernel_fault_* options) ----------
        self.fault_max_retries = 2
        self.fault_backoff_ms = 5.0
        self.fault_backoff_max_ms = 200.0
        self.breaker_threshold = 3
        self.probe_interval = 0.5
        self.thread_restarts = 4
        #: a run-loop that stayed healthy this long since its last
        #: death earns its restart budget back (like the breaker's
        #: consecutive counter): the budget bounds death STORMS, not
        #: isolated recovered deaths spread over an engine's lifetime
        self.thread_restart_window = 300.0
        #: channel (kernel family label) -> _Breaker, under self._cv
        self._breakers: dict[str, _Breaker] = {}
        self._probe_thread: threading.Thread | None = None
        self._probe_wake = threading.Event()
        self._deaths: dict[str, int] = {}
        self._death_t: dict[str, float] = {}
        self._wedged = False
        self._wedge_exc: BaseException | None = None
        self._jitter = random.Random()

    # -- mesh -----------------------------------------------------------------

    def set_mesh(self, mesh) -> None:
        """Swap the engine's device mesh (knob hot-reload).  Takes
        effect from the next flush; in-flight batches keep the
        placement they were built with (their fns re-place operands to
        match whatever sharding the batch actually carries, so late
        completion stays correct)."""
        with self._cv:
            self._mesh = mesh
            self._placement = None
        try:
            self.stats.set_mesh_shape(*_mesh_shape(mesh))
        except Exception:
            pass

    def _mesh_placement(self) -> _MeshPlacement | None:
        """The live placement scaffolding, built lazily on first use.
        A build failure (single-device backend, jax unavailable)
        disables the mesh loudly ONCE instead of failing every flush.

        Lock-free fast paths for the two common cases — no mesh, and
        placement already built: submitters probe this per op
        (placement_mesh) and must not pay the engine condvar for it.
        The unlocked attribute reads race only with set_mesh, and
        benignly: a stale answer delays the new placement by at most
        one flush, and every fn re-places operands to match whatever
        sharding its batch actually carries."""
        mesh = self._mesh
        placement = self._placement
        if mesh is None:
            return None
        if placement is not None and placement.mesh is mesh:
            if self.stats.mesh_devices == 0:
                # a stats clear() (tests/bench isolation) zeroed the
                # shape gauges: republish so the mesh gauge cannot
                # read "no mesh" next to a growing sharded-flush count
                self._publish_mesh_shape(placement)
            return placement
        with self._cv:
            mesh = self._mesh
            placement = self._placement
        if mesh is None:
            return None
        if placement is not None and placement.mesh is mesh:
            return placement
        try:
            placement = _MeshPlacement(mesh)
            if placement.devices <= 1:
                placement = None
        except Exception as e:
            from ceph_tpu.common.logging import dout
            dout("dispatch", 0, "%s: mesh placement unavailable, "
                 "running single-device: %r", self.name, e)
            placement = None
        with self._cv:
            if self._mesh is mesh:
                self._placement = placement
                if placement is None:
                    self._mesh = None
        if placement is not None:
            self._publish_mesh_shape(placement)
        return placement

    def _publish_mesh_shape(self, placement: _MeshPlacement) -> None:
        try:
            self.stats.set_mesh_shape(*_mesh_shape(placement.mesh))
        except Exception:
            pass

    def placement_mesh(self):
        """The mesh this engine's batches are actually placed over (the
        process-local submesh under jax.distributed), or None.
        Submitters use it to pre-replicate operand tables so the jitted
        kernel sees mesh-consistent shardings."""
        p = self._mesh_placement()
        return p.place_mesh if p is not None else None

    # -- lifecycle ------------------------------------------------------------

    def _ensure_threads(self) -> None:
        if self._threads:
            return
        for role, tgt in (("submit", self._dispatch_loop),
                          ("complete", self._complete_loop)):
            t = threading.Thread(target=self._thread_main,
                                 args=(role, tgt), daemon=True,
                                 name=f"{self.name}-{role}")
            self._threads[role] = t
            t.start()

    def _thread_main(self, role: str, tgt) -> None:
        """Run-loop supervisor: a loop death (failpoint-injected
        InjectedThreadDeath, or any escaped BaseException) is counted
        and the loop RE-ENTERED on this thread up to ``thread_restarts``
        times — the queued requests and in-flight batches stay where
        they are, so the revived loop re-fans them instead of wedging
        every waiter.  Past the budget the engine wedges: every pending
        future is failed with a loud EngineWedgedError and flush()
        raises it."""
        while True:
            try:
                tgt()
                return                      # clean exit (stop)
            except BaseException as e:      # noqa: BLE001 — supervised
                from ceph_tpu.common.logging import dout
                with self._cv:
                    now = time.monotonic()
                    prev = self._death_t.get(role)
                    if (prev is not None and now - prev
                            > float(self.thread_restart_window)):
                        # healthy since the last death: budget earned
                        # back — only a death STORM may wedge
                        self._deaths[role] = 0
                    self._death_t[role] = now
                    self._deaths[role] = n = self._deaths.get(role, 0) + 1
                    revive = (not self._stop
                              and n <= self.thread_restarts)
                try:
                    self.stats.record_thread_death(restarted=revive)
                except Exception:
                    pass
                dout("dispatch", 0,
                     "%s: %s run-loop died (%d/%d): %r%s", self.name,
                     role, n, self.thread_restarts, e,
                     " — reviving" if revive else " — WEDGED")
                if revive:
                    continue
                self._wedge(role, e)
                return

    def _wedge(self, role: str, cause: BaseException) -> None:
        """Restart budget exhausted: fail every waiter loudly (a
        stranded future wedges OSD wpend gates and client ops behind a
        silent timeout — the exact failure mode this forbids)."""
        exc = EngineWedgedError(
            f"{self.name}: {role} thread died "
            f"{self._deaths.get(role, 0)} times "
            f"(thread_restarts={self.thread_restarts}); last: {cause!r}")
        with self._cv:
            self._wedged = True
            self._wedge_exc = exc
            victims = [r.future for r in self._pending]
            self._pending.clear()
            self._key_totals.clear()
            for b in self._inflight:
                victims.extend(r.future for r in b.reqs)
            self._inflight.clear()
            self._cv.notify_all()
        self._probe_wake.set()
        for fut in victims:
            if not fut.done():
                fut._deliver(None, exc)

    def stop(self) -> bool:
        """Drain queued work, then stop both threads.  Returns True
        when both exited; a thread surviving its join timeout (wedged
        device call) stays in _threads so a later stop() can re-join.
        On a WEDGED engine every outstanding future has already been
        failed with EngineWedgedError — stop() returns False so
        shutdown paths log it."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            # a request running on its caller's thread is in flight
            # like any other (and may be all there is: no thread to
            # join): wait it out, bounded like the joins below
            deadline = time.monotonic() + 5.0
            while self._caller_runs and time.monotonic() < deadline:
                self._cv.wait(0.05)
        self._probe_wake.set()
        for t in list(self._threads.values()):
            t.join(timeout=5.0)
        self._threads = {r: t for r, t in self._threads.items()
                         if t.is_alive()}
        pt = self._probe_thread
        if pt is not None:
            pt.join(timeout=2.0)
        return not self._threads and not self._wedged

    def flush(self, timeout: float = 10.0) -> bool:
        """Wait for the queues to drain (futures may still be resolving
        for the last popped batch — wait on them for hard ordering).
        Raises EngineWedgedError instead of silently timing out when
        the engine's thread-restart budget is exhausted — a wedged
        engine can never drain, and the waiters have already been
        failed with the same error."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while (self._pending or self._building or self._inflight):
                if self._wedged:
                    raise self._wedge_exc
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.05))
            if self._wedged:
                raise self._wedge_exc
        return True

    def building(self) -> bool:
        """True while the dispatch thread builds and launches a batch —
        where a request's first shape traces and compiles, for as long
        as that takes (a waiter that would give up asks this first)."""
        with self._cv:
            return self._building > 0

    def owns_current_thread(self) -> bool:
        """True when the caller IS one of this engine's own worker
        threads (dispatch/completion).  A submitter that would BLOCK on
        a future from such a thread must take a host path instead: the
        wait would starve the very thread that materializes batches and
        delivers results — a guaranteed self-deadlock.  BlueStore's
        batched-csum flush checks this before riding the engine (store
        commits run on engine completion threads via EC-write and
        recovery continuations)."""
        with self._cv:
            return threading.current_thread() in self._threads.values()

    # -- submit ---------------------------------------------------------------

    def submit(self, key, fn, data, *, label=None,
               cache_entries=None, aux=None,
               place: bool = True, fallback=None,
               cost_tag=None) -> DispatchFuture:
        """``aux``: optional tuple of per-stripe side arrays (each with
        the SAME leading axis as ``data``) that coalesce alongside it —
        concatenated per component, edge-padded (last row repeated) to
        the shape bucket, and passed to ``fn(batch, *aux_batches)``.  The batched GF
        decode rides this: the per-stripe erasure-pattern index travels
        as aux so requests with DIFFERENT recovery matrices still share
        one device call.  All requests under one key must agree on aux
        arity and trailing shapes (encode that in the key).

        ``place=False`` opts this request out of mesh-sharded placement
        (host-runtime fns — numpy/native codecs — would only gather the
        sharded batch straight back).  Requests sharing a key must
        agree on it (encode the runtime in the key, as the codecs do).

        ``fallback``: optional bit-exact host oracle
        ``fallback(batch, *aux) -> array`` for this kernel channel.
        With one, a batch whose device path fails past the bounded
        retry ladder is served by the oracle instead of fanning the
        error, and an open channel breaker routes batches straight to
        it while the background probe retries the device (see the
        module's failure-domain notes).

        ``cost_tag``: optional (tenant, dmclock_class) pair for the
        tenant-attributed device-time ledger.  Batches still coalesce
        ACROSS tenants exactly as before (the tag plays no part in
        batching); at completion the batch's busy integral
        (compute_s × devices) is apportioned to each request by stripe
        share and accounted under its tag in
        ``telemetry.TenantDeviceStats``.  Untagged requests land in
        the visible ``_untagged`` bucket — never dropped, so the
        ledger's tenant sum conserves the engine's busy-seconds."""
        return self._enqueue(self._make_request(
            key, fn, data, label=label, cache_entries=cache_entries,
            aux=aux, place=place, fallback=fallback, cost_tag=cost_tag))

    def submit_waiting(self, key, fn, data, **kw) -> DispatchFuture:
        """``submit`` for a caller that will block on the result at
        once (BlueStore's commit and wide read, under the store's
        lock).  The engine picks the road by what it observes, by the
        rule ``_dispatch_loop`` states — an idle engine flushes
        immediately, lone ops never wait:

        * nothing pending, building or in flight, the channel's
          breaker closed, the engine running, the caller not one of
          its threads: the CALLING thread does for a batch of one what
          the dispatch and completion threads would — ``_dispatch_batch``
          then ``_complete_batch``, so the same failpoint sites, stats,
          phase ledger (``queue_wait`` 0), tenant ledger, spans, retry
          ladder and breaker accounting — and the future comes back
          resolved.  Two thread hand-overs out and one back are not
          made.  While it runs the batch holds the launch and then
          a place in ``_inflight`` like any other: a concurrent
          ``submit`` queues, is launched after it and completes behind
          it (per-key order holds), ``flush()`` and ``stop()`` wait
          for it.  The wait for the device has no timeout here:
          a caller that must not hang on a wedged device call keeps to
          ``submit`` and ``result(timeout=)``.
        * otherwise — engine busy, breaker open or half-open, engine
          stopped or wedged, or an engine thread calling — the request
          queues exactly as ``submit`` would, and coalesces.

        Counted in ``stats.caller_batches`` (of ``batches``)."""
        req = self._make_request(key, fn, data, **kw)
        with self._cv:
            breaker = self._breakers.get(req.label)
            lone = not (self._stop or self._wedged or self._pending
                        or self._building or self._inflight
                        or (breaker is not None and breaker.state
                            != telemetry.BREAKER_CLOSED)
                        or threading.current_thread()
                        in self._threads.values())
            if lone:
                self._building += 1
                self._caller_runs += 1
                self._caller_launching = True
                self.stats.record_submit(req.stripes)
        if not lone:
            return self._enqueue(req)
        batch = None
        try:
            batch = self._dispatch_batch([req], req.stripes, "idle", 1,
                                         on_caller=True)
            if batch is not None:
                self._complete_batch(batch)
        finally:
            with self._cv:
                self._caller_runs -= 1
                self._caller_launching = False
                if batch is not None and batch in self._inflight:
                    # _complete_batch raised before its pop: an
                    # on_caller head nobody else completes would stall
                    # every batch behind it
                    self._inflight.remove(batch)
                    self._cv.notify_all()
            if not req.future.done():
                req.future._deliver(None, RuntimeError(
                    f"{self.name}: {req.label} request on its "
                    f"caller's thread ended without a result"))
        return req.future

    def _make_request(self, key, fn, data, *, label=None,
                      cache_entries=None, aux=None, place: bool = True,
                      fallback=None, cost_tag=None) -> _Request:
        # analysis: allow[blocking] -- caller-input normalization: submit() receives host arrays (numpy/bytes), not device values
        data = np.asarray(data)
        stripes = int(data.shape[0]) if data.ndim else 1
        if aux is not None:
            # analysis: allow[blocking] -- aux side arrays are host numpy by contract
            aux = tuple(np.asarray(a) for a in aux)
            for a in aux:
                if not a.ndim or a.shape[0] != stripes:
                    raise ValueError(
                        f"aux leading axis {a.shape} != stripes {stripes}")
        return _Request(key, fn, data, stripes, label=label,
                        cache_entries=cache_entries, aux=aux, place=place,
                        fallback=fallback, cost_tag=cost_tag)

    def _enqueue(self, req: _Request) -> DispatchFuture:
        fn, data, aux, fallback = req.fn, req.data, req.aux, req.fallback
        with self._cv:
            if not self._stop and not self._wedged:
                self._ensure_threads()
                self._pending.append(req)
                self._key_totals[req.key] = (
                    self._key_totals.get(req.key, 0) + req.stripes)
                self.stats.record_submit(req.stripes)
                self._cv.notify_all()
                return req.future
        # engine stopped: run inline so callers never hang.  First wait
        # out any still-draining queues — stop() lets the threads finish
        # every queued batch, and an inline run jumping that drain would
        # break the per-key submission-order contract the OSD's EC
        # log/commit ordering rides on.  Timed waits, not a bare wait:
        # the exiting threads' last notify may already have fired.
        # EXCEPTION: a continuation re-submitting from one of this
        # engine's OWN threads (an OSD completion callback re-entering
        # the engine mid-stop) must not wait on a drain only itself can
        # advance — that is a guaranteed self-deadlock wedging the
        # completion thread and stranding every outstanding future.
        # Running inline immediately forfeits ordering against the
        # still-queued work, which is strictly better than the wedge.
        # (A WEDGED engine takes the same inline path: its queues were
        # already failed and drained, so the wait below is a no-op and
        # new work is served host-side rather than dropped or hung.)
        me = threading.current_thread()
        with self._cv:
            if me not in self._threads.values():
                while self._pending or self._building or self._inflight:
                    self._cv.wait(0.05)
        # inline OUTSIDE the engine lock, so a device call here never
        # serializes concurrent submit()/flush()/stop() callers
        # (and future callbacks never fire under the lock)
        req.future._deliver(*self._run_inline(fn, data, aux, fallback))
        return req.future

    @staticmethod
    def _run_inline(fn, data, aux=None, fallback=None):
        try:
            out = fn(data) if aux is None else fn(data, *aux)
            # analysis: allow[blocking] -- stopped-engine inline fallback materializes deliberately (no pipeline left to stall)
            return np.asarray(out), None
        except BaseException as e:     # noqa: BLE001 — delivered to waiter
            if fallback is not None and not isinstance(
                    e, _PERMANENT_ERRORS):
                try:
                    out = (fallback(data) if aux is None
                           else fallback(data, *aux))
                    # analysis: allow[blocking] -- host-oracle result is already numpy
                    return np.asarray(out), None
                except BaseException as e2:  # noqa: BLE001 — to waiter
                    return None, e2
            return None, e

    # -- dispatch thread ------------------------------------------------------

    def _key_stripes(self, key) -> int:
        return self._key_totals.get(key, 0)

    def _dispatch_loop(self) -> None:
        while True:
            # thread-death injection site: OUTSIDE every handler, so
            # the raise reaches _thread_main's supervisor (the real
            # failure this models is a loop bug, not a batch error)
            failpoint.hit("dispatch.dispatch_thread_death")
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait()
                if not self._pending:
                    if self._stop:
                        self._cv.notify_all()
                        return
                    continue
                first = self._pending[0]
                deadline = first.t_submit + self.max_delay_us * 1e-6
                # accumulate while the pipeline is busy; an idle engine
                # flushes immediately (lone ops never wait).  A ripe
                # batch (full OR past deadline) still waits for a free
                # in-flight slot — max_in_flight is a hard bound on
                # outstanding device calls, not just a deadline gate
                while not self._stop:
                    now = time.monotonic()
                    in_use = len(self._inflight) + self._building
                    if in_use == 0:
                        break              # idle: flush immediately
                    if in_use < self.max_in_flight and (
                            self._key_stripes(first.key)
                            >= self.max_stripes
                            or now >= deadline):
                        break              # ripe + slot free
                    self._cv.wait(max(1e-4, min(deadline - now, 0.05))
                                  if now < deadline else 0.05)
                # one launch at a time: a request its caller's thread
                # is launching (submit_waiting) enters _inflight before
                # anything dispatched from here — the order of
                # admission, which the completion side delivers in
                while self._caller_launching:
                    self._cv.wait(0.05)
                # collect the batch in ONE pass, partitioning the
                # oldest request's key out of the deque: per-key FIFO
                # is preserved (once size-capped, no later same-key
                # request may jump into this batch), and nothing is
                # rescanned or removed one-by-one
                reqs: list[_Request] = []
                keep: deque[_Request] = deque()
                total = 0
                capped = False
                for r in self._pending:
                    if r.key != first.key or capped:
                        keep.append(r)
                    elif reqs and total + r.stripes > self.max_stripes:
                        capped = True
                        keep.append(r)
                    else:
                        reqs.append(r)
                        total += r.stripes
                self._pending = keep
                left = self._key_totals.get(first.key, 0) - total
                if left > 0:
                    self._key_totals[first.key] = left
                else:
                    self._key_totals.pop(first.key, None)
                if self._stop:
                    reason = "stop"
                elif capped or total >= self.max_stripes:
                    reason = "full"    # size-capped, incl. next-would-overflow
                elif not (self._inflight or self._building):
                    reason = "idle"
                else:
                    reason = "timeout"
                depth = len(self._pending) + len(reqs)
                self._building += 1
            self._dispatch_batch(reqs, total, reason, depth)

    def _dispatch_batch(self, reqs: list[_Request], total: int,
                        reason: str, depth: int,
                        on_caller: bool = False) -> _Batch | None:
        """Build the padded batch and issue the device call (runs
        OUTSIDE the engine lock: a first-shape call traces+compiles).
        Returns the batch it put in flight (None: the engine wedged
        meanwhile and the futures were failed); ``on_caller``: the
        submitter's thread runs this and will complete the batch
        itself (submit_waiting): its request waited in no queue, so
        its ledger starts at the submit."""
        now = reqs[0].t_submit if on_caller else time.monotonic()
        # a CPU-clocked trace's phase spans want each phase's CPU time,
        # a live profiler session each phase as an annotation: both are
        # read at the ledger's marks below (one check a batch otherwise)
        marks = tracing.phase_marks(
            r.trace[0] for r in reqs if r.trace is not None)
        marks.begin("engine build")
        # slices first (pure arithmetic, cannot fail): the completion
        # thread zips reqs against slices, so every request must have
        # one even when the batch build below dies
        slices, off = [], 0
        for r in reqs:
            slices.append((off, off + r.stripes))
            off += r.stripes
        exc = None
        out = None
        misses = None
        profile = None
        placement = None
        devices = 1
        bucket, pad = total, 0
        via_fallback = False
        batch = None
        channel = reqs[0].label
        try:
            # EVERYTHING fallible sits inside this try — mesh lookup,
            # bucketing, breaker routing, the profile dict, pad
            # allocation / concatenate (MemoryError under pressure,
            # shape mismatch), span bookkeeping, the device call itself
            # — and lands in exc to fan to the batch's futures.  An
            # exception escaping this frame would reach the supervisor
            # with _building already incremented and the reqs already
            # partitioned out of _pending: the revived loop could never
            # re-fan them, flush() would time out silently forever —
            # the exact silent-wedge failure mode this PR forbids.
            #
            # mesh-sharded engines round the bucket up to a multiple of
            # the mesh size (every shard non-empty, even NamedSharding
            # split); place=False requests keep the seed's pure pow-2
            # bucket, and 0-d submits (no batch axis to split — padding
            # would have to concatenate onto a scalar) always run
            # unplaced
            placement = (self._mesh_placement()
                         if reqs[0].place and reqs[0].data.ndim
                         else None)
            devices = placement.devices if placement is not None else 1
            bucket = (mesh_bucket_stripes(total, devices)
                      if devices > 1 else bucket_stripes(total))
            pad = bucket - total
            # an OPEN (or half-open) breaker routes the batch straight
            # to the host oracle — no device attempt, no retry ladder;
            # the background probe owns re-trying the device path
            via_fallback = (reqs[0].fallback is not None
                            and self._breaker_routed(channel))
            if via_fallback:
                placement = None
                devices = 1
                bucket = bucket_stripes(total)
                pad = bucket - total
            # phase ledger (telemetry.PHASES): contiguous monotonic
            # marks — queue_wait ended at `now`; build/place/launch
            # close below; the completion thread closes compute/
            # materialize/deliver so the phase sum reconstructs
            # submit→delivery wall-clock exactly
            profile = {"t_submit0": reqs[0].t_submit, "t0": now,
                       "build": 0.0, "place": 0.0, "launch": 0.0,
                       "t_launch_end": now, "bucket": bucket,
                       "devices": devices, "stripes": total,
                       "family": reqs[0].label, "marks": marks}
            batch_arr, aux_batch = self._assemble(reqs, pad)
            t_build_end = t_place_end = time.monotonic()
            profile["build"] = t_build_end - now
            # without a mesh nothing is placed: the phase has no length
            # (no span, no annotation) and the ledger goes on to launch
            marks.begin("engine place" if placement is not None
                        else "engine launch")
            if not via_fallback:
                # h2d boundary failpoint: fires for EVERY device-path
                # batch — on an unmeshed engine the transfer is
                # implicit in the kernel call, but the fault being
                # modeled (h2d failure) exists regardless, and chaos
                # coverage must not silently shrink to meshed hosts
                failpoint.hit("dispatch.device_put", tag=channel)
            if placement is not None:
                # device_put with the sharding on dispatch: the batch
                # (and its aux arrays, in lockstep) split their leading
                # axis across the mesh BEFORE the kernel fn runs, so
                # the jitted call compiles partitioned (GSPMD) and its
                # result stays sharded until the completion thread
                # materializes it.  A placement failure lands in exc
                # and fans to the batch's futures like any build error.
                batch_arr = placement.put(batch_arr)
                aux_batch = tuple(placement.put(a) for a in aux_batch)
                t_place_end = time.monotonic()
                profile["place"] = t_place_end - t_build_end
                marks.begin("engine launch")
            before = None
            if reqs[0].cache_entries is not None and not via_fallback:
                try:
                    before = reqs[0].cache_entries()
                except Exception:
                    before = None
            if via_fallback:
                # host oracle on the dispatch thread — exactly where a
                # cpu-runtime fn would run; the result is already host
                # numpy, so the completion thread's materialize is free
                out = reqs[0].fallback(batch_arr, *aux_batch)
            else:
                failpoint.hit("dispatch.launch", tag=channel)
                out = reqs[0].fn(batch_arr, *aux_batch)  # async dispatch
            profile["t_launch_end"] = time.monotonic()
            marks.end()
            # span bookkeeping + the cache probe sit between place and
            # launch: charge them to launch so the ledger stays gapless
            profile["launch"] = profile["t_launch_end"] - t_place_end
            if before is not None:
                try:
                    misses = max(0, reqs[0].cache_entries() - before)
                except Exception:
                    misses = None
        except BaseException as e:          # noqa: BLE001 — fan to futures
            exc = e
        finally:
            marks.end()
            try:
                self.stats.record_batch(
                    requests=len(reqs), stripes=total, padded=pad,
                    reason=reason, delays=[now - r.t_submit for r in reqs],
                    depth=depth, devices=devices,
                    shard_stripes=(bucket // devices if devices > 1
                                   else 0), on_caller=on_caller)
            except Exception:
                pass
            victims = None
            with self._cv:
                self._building -= 1
                if self._wedged:
                    # the completion side wedged while this batch was
                    # building: queueing it would strand its futures
                    # behind a thread that will never come back
                    victims = [r.future for r in reqs]
                else:
                    batch = _Batch(out, reqs, slices, exc,
                                   t_dispatch=time.monotonic(),
                                   misses=misses, profile=profile,
                                   via_fallback=via_fallback,
                                   on_caller=on_caller)
                    self._inflight.append(batch)
                self.stats.set_in_flight(len(self._inflight)
                                         + self._building)
                if on_caller:
                    self._caller_launching = False
                if not on_caller or self._pending:
                    # (the caller's thread goes on to complete its own
                    # batch: unless a request queued behind its launch,
                    # no thread has anything to do with it)
                    self._cv.notify_all()
            if victims is not None:
                for fut in victims:
                    if not fut.done():
                        fut._deliver(None, self._wedge_exc)
        return batch

    # -- completion thread ----------------------------------------------------

    def _complete_loop(self) -> None:
        while True:
            # thread-death injection site: outside every handler (see
            # _dispatch_loop) — the satellite regression this guards:
            # a dead completion thread used to wedge flush()/stop()
            # into silent timeouts with every waiter stranded
            failpoint.hit("dispatch.complete_thread_death")
            with self._cv:
                # the head of the FIFO is this thread's, unless its
                # submitter's own thread is completing it
                # (submit_waiting): then whatever was dispatched
                # behind it waits here for its pop
                while not self._inflight or self._inflight[0].on_caller:
                    if (not self._inflight and self._stop
                            and not self._pending and not self._building):
                        return
                    self._cv.wait(0.05 if self._stop or self._inflight
                                  else None)
                batch = self._inflight[0]
            self._complete_batch(batch)

    def _complete_batch(self, batch: _Batch) -> None:
        """Materialize one in-flight batch, recover it if its device
        path failed, pop it and fan its results out — on the
        completion thread, or for an ``on_caller`` batch on its
        submitter's."""
        channel = batch.reqs[0].label
        host, exc = None, batch.exc
        t_ready = t_mat = 0.0
        # this thread's half of the phases' CPU times and annotations
        # (see _dispatch_batch); `compute` is read from the pick-up
        marks = tracing.phase_marks(
            r.trace[0] for r in batch.reqs if r.trace is not None)
        if exc is None:
            try:
                marks.begin("engine compute")
                # split device compute from d2h: waiting out the
                # async execution first (free — the work is already
                # in flight) leaves np.asarray measuring only the
                # materialize copy.  compute is anchored at launch
                # end, so completion-thread pickup wait (which
                # overlaps execution under double buffering) is
                # attributed to compute, keeping the ledger gapless.
                if not batch.via_fallback:
                    failpoint.hit("dispatch.block_until_ready",
                                  tag=channel)
                wait = getattr(batch.out, "block_until_ready", None)
                if wait is not None:
                    try:
                        wait()
                    except Exception:
                        pass   # np.asarray below surfaces the error
                t_ready = time.monotonic()
                marks.begin("engine materialize")
                host = np.asarray(batch.out)   # d2h materialize
                t_mat = time.monotonic()
            except BaseException as e:         # noqa: BLE001
                exc = e
            finally:
                marks.end()
        # supervised recovery: a failed device-path batch walks the
        # bounded retry ladder, then the channel's host oracle; a
        # batch the dispatch thread already served via the oracle
        # never re-enters (its error is final)
        if batch.via_fallback:
            # same rule as the recovery ladder below: the "launch"
            # anchor timed the host oracle, not a device call —
            # recording it would let an outage dominate the steady
            # device phase histograms with host-path runtimes
            batch.profile = None
            if exc is None:
                total = batch.slices[-1][1] if batch.slices else 0
                self.stats.record_fallback(total)
        elif exc is not None:
            host, exc, how = self._recover_batch(batch, exc)
            if how is not None:
                batch.profile = None   # phase anchors now span the
                # recovery ladder: keep the steady-state ledger
                # clean rather than record a fabricated profile
                t_ready = t_mat = time.monotonic()
        else:
            self._record_device_ok(channel)
        with self._cv:
            if self._inflight and self._inflight[0] is batch:
                self._inflight.popleft()
            self.stats.set_in_flight(len(self._inflight)
                                     + self._building)
            if not batch.on_caller or self._pending or self._inflight:
                # (a lone request on its caller's thread leaves nobody
                # to wake: flush() and stop() poll)
                self._cv.notify_all()
        for req, (a, b) in zip(batch.reqs, batch.slices):
            try:
                value = None if exc is not None else host[a:b]
                if req.trace is not None:
                    self._deliver_traced(req, value, exc, batch,
                                         t_ready, t_mat, marks)
                else:
                    req.future._deliver(value, exc)
            except BaseException as e:  # noqa: BLE001 — see below
                # _deliver shields continuations with `except
                # Exception` only; one raising past that (SystemExit
                # in a done-callback) would escape here AFTER the
                # batch was popped — the supervisor would revive the
                # loop, but nothing could ever re-fan this batch, so
                # its remaining futures would hang forever.  The
                # future itself is already resolved (value set
                # before callbacks run): log loudly and keep fanning.
                from ceph_tpu.common.logging import dout
                dout("dispatch", 0,
                     "%s: continuation for %s raised past Exception"
                     " (swallowed to protect the batch fan-out): %r",
                     self.name, req.label, e)
        self.stats.record_complete(len(batch.reqs))
        if exc is None and batch.profile is not None:
            pr = batch.profile
            t_end = time.monotonic()
            try:
                self.stats.phases.record_batch(
                    pr["family"],
                    phases={"queue_wait": pr["t0"] - pr["t_submit0"],
                            "build": pr["build"],
                            "place": pr["place"],
                            "launch": pr["launch"],
                            "compute": t_ready - pr["t_launch_end"],
                            "materialize": t_mat - t_ready,
                            "deliver": t_end - t_mat},
                    e2e_s=t_end - pr["t_submit0"],
                    requests=len(batch.reqs),
                    stripes=pr["stripes"], bucket=pr["bucket"],
                    devices=pr["devices"], misses=batch.misses,
                    on_caller=batch.on_caller)
            except Exception:
                pass   # profiling must never wedge completions
            try:
                # tenant apportionment: the SAME busy integral the
                # phase ledger just accumulated (compute × devices),
                # split across the batch's requests by stripe share
                # — shares sum to 1 over the real stripes (padding
                # carries no tag and no share), so the per-tenant
                # ledger conserves busy_seconds exactly
                busy = (t_ready - pr["t_launch_end"]) * pr["devices"]
                total = max(1, pr["stripes"])
                groups: dict = {}
                for req in batch.reqs:
                    tag = req.cost_tag
                    if tag is None:
                        tenant, klass = None, ""
                    elif isinstance(tag, str):
                        tenant, klass = tag, ""
                    else:
                        tenant, klass = tag[0], tag[1]
                    g = groups.setdefault(
                        (tenant, klass, req.label), [0, 0, []])
                    g[0] += req.stripes
                    g[1] += 1
                    g[2].append(pr["t0"] - req.t_submit)
                ledger = telemetry.tenant_stats()
                for (tenant, klass, chan), (s, n, waits) \
                        in groups.items():
                    ledger.record_batch(
                        tenant, klass,
                        engine=self._ledger_engine, channel=chan,
                        device_seconds=busy * (s / total),
                        requests=n, stripes=s, queue_waits=waits)
            except Exception:
                pass   # the ledger must never wedge completions


    @staticmethod
    def _deliver_traced(req: _Request, value, exc, batch: _Batch,
                        t_ready: float, t_mat: float, marks) -> None:
        """Deliver a traced submitter's result under its spans: the
        request (submit -> delivered) with the batch's phases
        (telemetry.PHASES) as children over their real intervals, read
        off the same marks the phase ledger records — `time.monotonic()`
        readings of `_dispatch_batch` (submit, `t0`, build end, place
        end, launch end) and of `_complete_batch` (`t_ready`, `t_mat`),
        on Linux the clock of the spans' perf_counter_ns.  A phase
        without length (`place` without a mesh, `queue_wait` on the
        caller's own thread) gets no span.  In a CPU-clocked trace each
        phase that one thread ran carries that thread's CPU time over
        it, read at the same marks (`tracing.PhaseMarks`: the launching
        thread's in the batch's profile, the completing thread's in
        `marks`); `queue_wait` crosses threads and carries none.
        Continuations run under the `engine deliver` child, so whatever
        they fan out stays in the op's tree.  The batch is already
        popped from _inflight, so nothing here may raise past the
        delivery: tracing must never wedge completions."""
        tid, parent = req.trace
        rs = deliver = None
        try:
            attrs = {"kernel": req.label, "batch": len(batch.reqs),
                     "coalesced": len(batch.reqs) > 1,
                     "error": exc is not None,
                     "caller_thread": batch.on_caller,
                     "h2d_bytes": int(req.data.nbytes)}
            if value is not None:
                attrs["d2h_bytes"] = int(value.nbytes)
            if batch.misses is not None:
                attrs["retrace"] = batch.misses > 0
            rs = tracing.begin_span(
                f"device {req.label}", "device", trace_id=tid,
                parent_span_id=parent, attrs=attrs,
                start=int(req.t_submit * 1e9))
            pr = batch.profile
            if rs is not None and pr is not None and exc is None:
                t_build = pr["t0"] + pr["build"]
                bounds = (req.t_submit, pr["t0"], t_build,
                          t_build + pr["place"], pr["t_launch_end"],
                          t_ready, t_mat)
                ran = {name: (ns, m.thread) for m in (pr["marks"], marks)
                       for name, ns in m.cpu.items()
                       } if tracing.cpu_clocked(tid) else {}
                for phase, t_a, t_b in zip(tracing.ENGINE_PHASES, bounds,
                                           bounds[1:]):
                    if t_b <= t_a:
                        continue
                    name = f"engine {phase}"
                    cpu_ns, thread = ran.get(name, (None, None))
                    tracing.add_span(
                        name, "device", tid, rs.span_id,
                        int(t_a * 1e9), int(t_b * 1e9),
                        # the host's wait for the device's result
                        {"device_wait": True} if phase == "compute"
                        else None, cpu_ns=cpu_ns, thread=thread)
                deliver = tracing.begin_span(
                    "engine deliver", "device", trace_id=tid,
                    parent_span_id=rs.span_id, start=int(t_mat * 1e9))
        except Exception:
            pass
        under = deliver or rs
        try:
            with tracing.joined(tid, under.span_id if under is not None
                                else parent):
                req.future._deliver(value, exc)
        finally:
            marks.finish_span(deliver)
            tracing.finish_span(rs)

    # -- supervised recovery (retry ladder, breaker, probe) -------------------

    @staticmethod
    def _assemble(reqs: list[_Request], pad: int):
        """THE batch-assembly contract, shared by the dispatch path and
        the recovery ladder (a retried/fallback batch must present the
        exact layout the original device batch had, or the completion
        thread's slices lie).  Data pads with zero stripes; aux side
        arrays coalesce in lockstep with data — same concatenation
        order — but padding REPEATS the last row (edge padding) rather
        than writing zeros: aux rows are categorical (the decode's
        pattern index), and zero rows would invent category 0 in every
        padded batch — inflating the distinct-patterns telemetry and
        gathering a matrix no live stripe asked for.  Repeating a real
        row keeps the category set exact; the padded DATA rows are
        still all-zero, so whatever the repeated row selects computes
        zeros that are sliced off before delivery."""
        arrays = [r.data for r in reqs]
        if pad:
            arrays.append(np.zeros((pad,) + reqs[0].data.shape[1:],
                                   dtype=reqs[0].data.dtype))
        data = arrays[0] if len(arrays) == 1 \
            else np.concatenate(arrays, axis=0)
        aux = ()
        if reqs[0].aux is not None:
            for j in range(len(reqs[0].aux)):
                parts = [r.aux[j] for r in reqs]
                if pad:
                    parts.append(np.repeat(parts[-1][-1:], pad, axis=0))
                aux += (parts[0] if len(parts) == 1
                        else np.concatenate(parts, axis=0),)
        return data, aux

    @classmethod
    def _build_host_batch(cls, reqs: list[_Request]):
        """Rebuild the padded HOST batch for a retry/fallback run (the
        original batch may be a device-placed array whose backing
        devices are exactly what failed).  Pure pow-2 bucket, no
        placement — recovery runs single-device; every kernel here is
        bit-exact regardless of sharding."""
        total = sum(r.stripes for r in reqs)
        pad = (bucket_stripes(total) - total) if reqs[0].data.ndim else 0
        return cls._assemble(reqs, pad)

    def _recover_batch(self, batch: _Batch, exc: BaseException):
        """The failure ladder for one device-path batch: bounded
        retries with exponential backoff + jitter (transient errors
        only), then the channel's bit-exact host oracle, then fan the
        error.  Runs on the completion thread — holding the FIFO head
        during recovery is exactly the delivery-order contract.
        Returns (host_result, exc, how) with how in
        {"retry", "fallback", None}."""
        reqs = batch.reqs
        channel = reqs[0].label
        transient = not isinstance(exc, _PERMANENT_ERRORS)
        if transient and not self._breaker_routed(channel):
            for attempt in range(max(0, int(self.fault_max_retries))):
                delay = min(float(self.fault_backoff_max_ms),
                            float(self.fault_backoff_ms)
                            * (2 ** attempt)) / 1e3
                # jittered exponential backoff: decorrelates retry
                # storms across engines/channels (Tail at Scale rule)
                time.sleep(delay * (0.5 + 0.5 * self._jitter.random()))
                try:
                    data, aux = self._build_host_batch(reqs)
                    failpoint.hit("dispatch.launch", tag=channel)
                    out = reqs[0].fn(data, *aux)
                    failpoint.hit("dispatch.block_until_ready",
                                  tag=channel)
                    wait = getattr(out, "block_until_ready", None)
                    if wait is not None:
                        wait()
                    # analysis: allow[blocking] -- recovery materializes synchronously by design (the pipeline head is already stalled on this batch)
                    host = np.asarray(out)
                except BaseException as e:    # noqa: BLE001 — ladder
                    exc = e
                    self.stats.record_retry(False)
                    if isinstance(e, _PERMANENT_ERRORS):
                        break
                    continue
                self.stats.record_retry(True)
                self._record_device_ok(channel)
                return host, None, "retry"
        if transient:
            self._record_device_failure(channel, reqs)
        fb = reqs[0].fallback
        if fb is not None and transient:
            try:
                data, aux = self._build_host_batch(reqs)
                # analysis: allow[blocking] -- host-oracle result is already numpy
                host = np.asarray(fb(data, *aux))
            except BaseException as e:        # noqa: BLE001 — to waiters
                return None, e, None
            total = batch.slices[-1][1] if batch.slices else 0
            self.stats.record_fallback(total)
            return host, None, "fallback"
        return None, exc, None

    def _breaker_routed(self, channel: str) -> bool:
        """True while this channel's batches must take the host oracle
        (breaker open or mid-probe).  Lock-free empty-dict fast path:
        the common case is no breaker has ever tripped."""
        if not self._breakers:
            return False
        with self._cv:
            b = self._breakers.get(channel)
            return (b is not None
                    and b.state != telemetry.BREAKER_CLOSED)

    def _record_device_ok(self, channel: str) -> None:
        if not self._breakers:
            return
        with self._cv:
            b = self._breakers.get(channel)
            if b is None or (b.consecutive == 0
                             and b.state == telemetry.BREAKER_CLOSED):
                return
            b.consecutive = 0
            changed = b.state != telemetry.BREAKER_CLOSED
            b.state = telemetry.BREAKER_CLOSED
            b.probe = None
        if changed:
            self.stats.record_breaker(channel,
                                      telemetry.BREAKER_CLOSED)

    def _record_device_failure(self, channel: str,
                               reqs: list[_Request]) -> None:
        """One batch exhausted its device retries.  Past the threshold
        the channel breaker OPENS: a one-stripe sample of this batch is
        retained for the background probe, and every later batch with a
        fallback routes host-side until a probe heals the device."""
        opened = False
        with self._cv:
            b = self._breakers.get(channel)
            if b is None:
                b = self._breakers[channel] = _Breaker()
            b.consecutive += 1
            if (b.state == telemetry.BREAKER_CLOSED
                    and reqs[0].fallback is not None
                    and b.consecutive
                    >= max(1, int(self.breaker_threshold))):
                b.state = telemetry.BREAKER_OPEN
                r0 = reqs[0]
                sample = (r0.data[:1].copy() if r0.data.ndim
                          else r0.data.copy())
                auxs = (None if r0.aux is None
                        else tuple(a[:1].copy() for a in r0.aux))
                b.probe = (r0.fn, sample, auxs)
                opened = True
        if opened:
            self.stats.record_breaker(channel, telemetry.BREAKER_OPEN)
            self._ensure_probe_thread()

    def _ensure_probe_thread(self) -> None:
        with self._cv:
            if self._stop or self._wedged:
                return
            t = self._probe_thread
            if t is not None and t.is_alive():
                return
            self._probe_wake.clear()
            t = threading.Thread(target=self._probe_loop, daemon=True,
                                 name=f"{self.name}-probe")
            self._probe_thread = t
            t.start()

    def _probe_loop(self) -> None:
        """Background device-path probe: while any channel breaker is
        open, periodically replay its retained one-stripe sample
        through the device path; success re-closes the breaker and
        traffic returns to the device on the next flush.  Exits (and
        is respawned on the next open) once every breaker is closed."""
        while True:
            self._probe_wake.wait(max(0.05, float(self.probe_interval)))
            probes = []
            with self._cv:
                if self._stop or self._wedged:
                    self._probe_thread = None
                    return
                for ch, b in self._breakers.items():
                    if (b.state != telemetry.BREAKER_CLOSED
                            and b.probe is not None):
                        b.state = telemetry.BREAKER_HALF_OPEN
                        probes.append((ch, b, b.probe))
                if not probes:
                    self._probe_thread = None
                    return
            for ch, b, (fn, data, aux) in probes:
                self.stats.record_breaker(
                    ch, telemetry.BREAKER_HALF_OPEN)
                ok = False
                try:
                    failpoint.hit("dispatch.device_put", tag=ch)
                    failpoint.hit("dispatch.launch", tag=ch)
                    out = fn(data) if aux is None else fn(data, *aux)
                    failpoint.hit("dispatch.block_until_ready", tag=ch)
                    wait = getattr(out, "block_until_ready", None)
                    if wait is not None:
                        wait()
                    # analysis: allow[blocking] -- probe thread materializes its own one-stripe sample; nothing queues behind it
                    np.asarray(out)
                    ok = True
                except Exception:
                    ok = False
                self.stats.record_probe(ok)
                with self._cv:
                    if b.state == telemetry.BREAKER_HALF_OPEN:
                        if ok:
                            b.state = telemetry.BREAKER_CLOSED
                            b.consecutive = 0
                            b.probe = None
                        else:
                            b.state = telemetry.BREAKER_OPEN
                    state = b.state
                self.stats.record_breaker(ch, state)

    def breaker_states(self) -> dict[str, int]:
        """channel -> telemetry.BREAKER_* for this engine (tests and
        the thrasher's reconvergence gate)."""
        with self._cv:
            return {ch: b.state for ch, b in self._breakers.items()}


# ---------------------------------------------------------------------------
# CRUSH bulk-remap submit API (ops.crush_kernel's flat_firstn, coalesced)
# ---------------------------------------------------------------------------

#: mesh-replicated CRUSH operand tables, LRU-cached per (mesh, engine
#: key): the engine key already digests the operand content (bucket
#: ids/weights/reweight or mapper+rule+reweight), so repeated flushes
#: against the same map state reuse one broadcast instead of
#: re-uploading the tables per flush — the same residency rule
#: make_encoder and the decode pattern snapshot follow
_PLACED_OPS_CAP = 32
_placed_ops: OrderedDict = OrderedDict()
_placed_ops_lock = lockdep.make_lock("dispatch::placed_operands")


def _replicate_cached(mesh, cache_key, build):
    """build() -> operands device_put-replicated over ``mesh``, cached
    under (mesh, cache_key) — true LRU (move-to-end on hit, evict the
    single least-recent entry past the cap), the same OrderedDict
    idiom the codec recovery caches use; meshes are hashable.
    build() runs OUTSIDE the lock; a racing duplicate broadcast is
    idempotent."""
    k = (mesh, cache_key)
    with _placed_ops_lock:
        v = _placed_ops.get(k)
        if v is not None:
            _placed_ops.move_to_end(k)
            return v
    v = build()
    with _placed_ops_lock:
        _placed_ops[k] = v
        _placed_ops.move_to_end(k)
        while len(_placed_ops) > _PLACED_OPS_CAP:
            _placed_ops.popitem(last=False)
    return v


def submit_flat_firstn(engine: DeviceDispatchEngine, x, ids, weights,
                       reweight, *, numrep: int, tries: int = 51,
                       key=None, cost_tag=None) -> DispatchFuture:
    """Submit a bulk PG remap through the engine: concurrent remap
    requests against the SAME map state coalesce on the x axis into one
    device call (the ParallelPGMapper thread pool collapsed into one
    batched kernel invocation).  Padded lanes (x=0) compute garbage
    placements that are sliced off before delivery — bit-exactness of
    the delivered rows is untouched.

    ``key`` defaults to a digest of the bucket/reweight operands; pass
    an explicit (epoch, rule)-style key when the caller already knows
    the map identity to skip the hashing.
    """
    ids = np.asarray(ids, dtype=np.int32)
    weights = np.asarray(weights, dtype=np.int64)
    reweight = np.asarray(reweight, dtype=np.int64)
    if key is None:
        key = ("crush_firstn", numrep, tries,
               hash(ids.tobytes()), hash(weights.tobytes()),
               hash(reweight.tobytes()))

    def fn(xs, key=key):
        from ceph_tpu.ops.crush_kernel import flat_firstn
        i, w, rw = ids, weights, reweight
        mesh = getattr(getattr(xs, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            # host-side placement scaffolding, not traced compute: the
            # engine handed us a mesh-sharded batch, so replicate the
            # bucket/reweight operands over the same mesh — the jitted
            # kernel then compiles with consistent shardings (sharded
            # x, replicated tables) instead of erroring on mixed
            # committed device sets.  Cached per (mesh, key): the key
            # digests the operand content, so same-map flushes reuse
            # one broadcast.
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            i, w, rw = _replicate_cached(
                mesh, key,
                lambda: jax.device_put(
                    (i, w, rw), NamedSharding(mesh, PartitionSpec())))
        return flat_firstn(xs, i, w, rw, numrep=numrep, tries=tries)

    def host_oracle(xs, numrep=numrep, tries=tries):
        # bit-exact scalar CRUSH (crush.mapper_ref) — the breaker's
        # host-path degradation for this channel
        from ceph_tpu.crush.mapper_ref import flat_firstn_ref
        rows = flat_firstn_ref(np.asarray(xs), ids, weights, reweight,
                               numrep=numrep, tries=tries)
        return np.asarray(rows, dtype=np.int32)

    return engine.submit(key, fn, np.asarray(x, dtype=np.uint32),
                         label="crush_firstn", fallback=host_oracle,
                         cost_tag=cost_tag)


def submit_do_rule(engine: DeviceDispatchEngine, mapper, ruleno: int,
                   xs, result_max: int, reweight, *,
                   key=None, cost_tag=None) -> DispatchFuture:
    """Submit a general-rule bulk PG remap (BatchMapper.do_rule)
    through the engine.  Pool remaps for the SAME (map, rule, size,
    reweight) — e.g. several pools sharing one crush rule, or several
    OSD daemons in one context advancing the same epoch — coalesce on
    the x axis into ONE device call.  Padded lanes (x=0) compute
    garbage placements that are sliced off before delivery, exactly
    like submit_flat_firstn.

    ``mapper`` is a crush.mapper_jax.BatchMapper (or anything with its
    ``do_rule`` signature); ``key`` defaults to the mapper identity +
    rule + shape + a reweight digest, so callers holding one mapper
    per crush-map identity get cross-request coalescing for free.
    """
    reweight = np.asarray(reweight, dtype=np.int64)
    if key is None:
        key = ("crush_rule", id(mapper), ruleno, result_max,
               hash(reweight.tobytes()))

    def fn(batch, key=key):
        rw = reweight
        mesh = getattr(getattr(batch, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            # host-side placement scaffolding (see submit_flat_firstn):
            # replicate the reweight vector over the batch's mesh so
            # do_rule's jitted evaluator sees consistent shardings (the
            # mapper replicates its bucket tables over the same mesh,
            # fastpath.FastTables.on); cached per (mesh, key) — the key
            # digests mapper identity, rule and reweight content
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            rw = _replicate_cached(
                mesh, key,
                lambda: jax.device_put(
                    rw, NamedSharding(mesh, PartitionSpec())))
        return mapper.do_rule(ruleno, batch, result_max, rw)

    host_oracle = None
    cmap = getattr(mapper, "map", None)
    if cmap is not None:
        def host_oracle(batch, cmap=cmap):
            # scalar rule interpreter per lane, NONE-padded to the
            # batched mapper's row shape (dense prefix for firstn,
            # positional holes for indep — crush.mapper_jax contract)
            from ceph_tpu.crush.mapper_ref import crush_do_rule
            none = 0x7FFFFFFF
            rw = [int(v) for v in np.asarray(reweight)]
            out = np.full((np.asarray(batch).shape[0], result_max),
                          none, dtype=np.int32)
            for i, x in enumerate(np.asarray(batch)):
                row = crush_do_rule(cmap, ruleno, int(x), result_max,
                                    rw)
                if row:
                    out[i, :len(row)] = np.asarray(row,
                                                   dtype=np.int32)
            return out

    return engine.submit(key, fn, np.asarray(xs, dtype=np.uint32),
                         label="crush_rule", fallback=host_oracle,
                         cost_tag=cost_tag)


def submit_finish_ladder(engine: DeviceDispatchEngine, operands, *,
                         key=None, cost_tag=None) -> DispatchFuture:
    """Submit one pool's fused placement-pipeline tail (raw -> up ->
    acting; ops.placement_kernel) through the engine.  ``operands`` is
    a placement_kernel.LadderOperands: the raw table is the data
    channel, the per-PG override/pps tables ride aux in lockstep, and
    the per-OSD state/weight/affinity vectors (padded past max_osd)
    and the max_osd scalar are captured operands — mesh-replicated on
    sharded batches exactly like the CRUSH reweight vector.  Pools (and daemons) sharing one epoch's operand digest
    and table widths coalesce on the PG axis into ONE device call.

    ``key`` defaults to a digest of the captured vectors plus the
    static table shape; pass an explicit (epoch, widths)-style key when
    the caller already knows the map identity."""
    osd_ops = operands.osd_operands()
    if key is None:
        key = ("pg_finish", operands.erasure, operands.width,
               operands.items.shape[1], int(operands.max_osd),
               *(hash(v.tobytes()) for v in osd_ops[:3]))

    def fn(batch, *aux, key=key):
        from ceph_tpu.ops.placement_kernel import _ladder_jit
        ops_ = osd_ops
        mesh = getattr(getattr(batch, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            # host-side placement scaffolding (see submit_flat_firstn):
            # replicate the per-OSD operands over the batch's mesh so
            # the jitted ladder compiles with consistent shardings
            # (sharded PG tables, replicated osd vectors); cached per
            # (mesh, key) — the key digests their content
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            ops_ = _replicate_cached(
                mesh, key,
                lambda: jax.device_put(
                    osd_ops, NamedSharding(mesh, PartitionSpec())))
        return _ladder_jit(operands.erasure)(batch, *aux, *ops_)

    def host_oracle(batch, *aux, erasure=operands.erasure):
        # numpy twin of the fused ladder (placement_kernel.ladder_ref):
        # same packed-row output, bit for bit, no device involved
        from ceph_tpu.ops.placement_kernel import ladder_ref
        return ladder_ref(batch, *aux, *osd_ops, erasure=erasure)

    from ceph_tpu.ops.placement_kernel import ladder_cache_entries
    return engine.submit(key, fn, operands.raw, aux=operands.aux(),
                         label="pg_finish",
                         cache_entries=ladder_cache_entries,
                         fallback=host_oracle, cost_tag=cost_tag)


def submit_scrub_digest(engine: DeviceDispatchEngine, blobs,
                        key=None, cost_tag=None) -> DispatchFuture:
    """Submit a batch of byte blobs (object payloads / omap blobs) for
    integrity digesting through the engine — the FIFTH kernel channel
    (``scrub_digest``), with everything the other four have: a
    bit-exact host oracle (the literal ``shard_crc`` loop), the
    device-boundary failpoint sites (which fire by channel tag with no
    extra code here), the bounded retry ladder, and a per-channel
    circuit breaker.  Returns a DispatchFuture of (len(blobs), 2)
    uint32 — col 0 crc32 (== ``osd.ec_util.shard_crc``), col 1 the
    packed GF shard digest.

    Rows zero-pad to a shared pow-2 width (checksum_kernel.row_width)
    and the key is just that width, so concurrent scrubs of DIFFERENT
    PGs — or different daemons in one context — coalesce into one
    device call; the per-row unpad operands (the crc Z^-pad matrix
    columns and the GF alpha^-t lane multipliers) ride the aux channel
    in lockstep, which is what makes zero-padding bit-exact here
    despite crc32 not being linear in the padded row."""
    from ceph_tpu.ops import checksum_kernel as ck
    lengths = np.array([len(b) for b in blobs], dtype=np.int64)
    w = ck.row_width(int(lengths.max()) if len(blobs) else 0)
    data = np.zeros((len(blobs), w), dtype=np.uint8)
    for i, b in enumerate(blobs):
        if len(b):
            data[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
    mats, invp = ck.digest_operands(lengths, w)
    if key is None:
        key = ("scrub_digest", w)

    def fn(batch, lens, m, p):
        from ceph_tpu.ops.checksum_kernel import scrub_digest_batched
        return scrub_digest_batched(batch, m, p)

    def host_oracle(batch, lens, m, p):
        from ceph_tpu.ops.checksum_kernel import scrub_digest_ref
        return scrub_digest_ref(batch, lens)

    return engine.submit(key, fn, data, aux=(lengths, mats, invp),
                         label="scrub_digest",
                         cache_entries=ck.digest_jit_entries,
                         fallback=host_oracle,
                         cost_tag=cost_tag if cost_tag is not None
                         else (BACKGROUND_BEST_EFFORT,
                               BACKGROUND_BEST_EFFORT))


def _whole_block_batch(blobs, runs):
    """The (len(blobs), w) uint8 batch as a VIEW of the caller's own
    bytes, when ``runs`` are the buffers the blobs are consecutive
    whole slices of and every blob is one full row (w a digest width:
    no padding, so no unpad); else None.  One run is not copied at
    all; several are concatenated once."""
    from ceph_tpu.ops import checksum_kernel as ck
    n = len(blobs)
    if not runs or not n:
        return None
    w = len(blobs[0])
    if (w != ck.row_width(w) or len(blobs[-1]) != w
            or any(len(r) % w for r in runs)
            or sum(len(r) for r in runs) != n * w):
        return None
    parts = [np.frombuffer(r, dtype=np.uint8) for r in runs]
    flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return flat.reshape(n, w)


def submit_bluestore_data(engine: DeviceDispatchEngine, blobs,
                          key=None, cost_tag=None, *,
                          runs=None, wait: bool = False) -> DispatchFuture:
    """Submit a batch of STORED block payloads (raw padded blocks or
    compressed bodies — lengths vary, which is exactly what the unpad
    epilogue absorbs) for checksumming through the engine — the SIXTH
    kernel channel (``bluestore_data``), the objectstore's write/read
    hot path.  Same contract as ``submit_scrub_digest``: returns a
    DispatchFuture of (len(blobs), 2) uint32 with col 0 the crc32 of
    each blob (== the scalar ``zlib.crc32`` loop BlueStore ran per
    block in the seed), a bit-exact host oracle as the breaker
    fallback, the channel-tagged device-boundary failpoints
    (``dispatch.launch:bluestore_data``), the bounded retry ladder and
    a per-channel circuit breaker.

    ``runs``: the buffers the blobs were cut from, when the caller
    knows them — in order, ``b"".join(runs) == b"".join(blobs)``.
    BlueStore stages a write's body and reads a shard by extent run,
    so its blobs are consecutive 4,096-byte views of a few buffers
    (of one, for a fresh object): when every blob is one full row the
    batch is then a reshaped VIEW of those bytes, not a padded copy
    built blob by blob.  The view aliases the caller's buffers and
    the upload is asynchronous: nothing may change or free them
    before the result is delivered (both store call sites hold them
    and wait).  Blobs of unequal or short length (a compressed body,
    a last partial block), or no ``runs``, take the padded build.

    ``wait``: the caller blocks on ``.result()`` at once, as both
    store call sites do under the store's lock: the request goes
    through ``engine.submit_waiting``, which runs it on the calling
    thread while the engine is idle (a lone op: two thread hand-overs
    out and one back are not made) and queues it like ``submit``
    whenever the engine is busy, its breaker is not closed, or it is
    stopped — so at depth requests still queue and coalesce.

    Whole-row requests carry no per-row operands: the unpad operands
    of a shape whose every row is full depend on nothing but the
    shape, and stay on the device (``checksum_kernel.
    whole_row_operands``), so such a request uploads its data alone.
    With a mesh on the engine every request takes the padded build,
    whose rows and operands are placed in lockstep, as before.

    The key is the padded width and, for whole-row requests, their
    kind, so concurrent transaction batches — different stores,
    different daemons on one context — coalesce into one device call,
    like every other channel, each kind with its own.  The digest
    math IS the scrub kernel's (one checksum definition for
    store and scrub); only the channel label and telemetry family
    differ, so the store path's health is observable on its own."""
    from ceph_tpu.ops import checksum_kernel as ck
    submit_kw = dict(
        label="bluestore_data", cache_entries=ck.digest_jit_entries,
        cost_tag=cost_tag if cost_tag is not None
        else ("_bluestore", "client"))
    submit = engine.submit_waiting if wait else engine.submit
    data = (_whole_block_batch(blobs, runs)
            if engine.placement_mesh() is None else None)
    if data is not None:
        return submit(
            key if key is not None
            else ("bluestore_data", data.shape[1], "whole"),
            _digest_whole_rows, data, fallback=_whole_rows_oracle,
            **submit_kw)
    lengths = np.array([len(b) for b in blobs], dtype=np.int64)
    w = ck.row_width(int(lengths.max()) if len(blobs) else 0)
    data = np.zeros((len(blobs), w), dtype=np.uint8)
    for i, b in enumerate(blobs):
        if len(b):
            data[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
    mats, invp = ck.digest_operands(lengths, w)
    if key is None:
        key = ("bluestore_data", w)

    def fn(batch, lens, m, p):
        from ceph_tpu.ops.checksum_kernel import bluestore_digest_batched
        return bluestore_digest_batched(batch, m, p)

    def host_oracle(batch, lens, m, p):
        from ceph_tpu.ops.checksum_kernel import scrub_digest_ref
        return scrub_digest_ref(batch, lens)

    return submit(key, fn, data, aux=(lengths, mats, invp),
                  fallback=host_oracle, **submit_kw)


def _digest_whole_rows(batch):
    """A batch of whole rows (zero rows of padding among them: they
    digest as rows of zeros and are sliced off): the epilogue operands
    are resident on the device, only the batch is uploaded."""
    from ceph_tpu.ops.checksum_kernel import bluestore_digest_batched
    return bluestore_digest_batched(batch)


def _whole_rows_oracle(batch):
    from ceph_tpu.ops.checksum_kernel import scrub_digest_ref
    return scrub_digest_ref(batch, [batch.shape[1]] * batch.shape[0])
