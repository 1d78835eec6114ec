"""Cross-daemon distributed tracing with SPAN TREES, sampling, and
tail retention of slow traces (src/tracing/oprequest.tp +
src/common/zipkin_trace.h analogs, Dapper-style span model).

A trace is a tree of spans.  Each span has a span_id, a
parent_span_id, a begin and an end, a layer, and key/value attributes
(pool, pg, op size, kernel batch shape); point events (OpTracker
stages) attach to the span that was current when they fired.  The ids
ride the message frame (a flagged header extension carrying
``(trace_id, parent_span_id)``, see msg.message): the client's root
span parents its op's hop span, every receiver opens an
``rx <MsgType>`` dispatch span parented to the hop, and the whole
client → primary → shard → commit tree reconstructs from the rows.
``dump(trace_id)`` returns the flat time-ordered rows (the admin-socket
payload); ``span_tree(trace_id)`` nests them.

ONE CLOCK.  A span's ``start``/``end`` are ``time.perf_counter_ns()``:
on Linux the clock ``time.monotonic()`` reads too, so the dispatch
engine's phase marks, the mapping service's timers and a benchmark
harness's own stamps all lie on one timeline with the spans, and no
wall-clock step can skew a duration.  Wall-clock time is kept once per
trace (at its first row) and rows carry ``t`` = that anchor plus the
span's offset, for display and for correlating with logs.

RUNNING AND WAITING.  In a CPU-CLOCKED trace a span that one thread
opens and closes (the ``span(...)`` / ``root(...)`` context managers,
the dispatch engine's phases) also carries ``cpu_ns``, what
``time.thread_time_ns()`` of that thread advanced by over the span, and
``thread``, its ident: interval less CPU is the time the thread was off
the processor inside the span.  That clock is a system call which, on
the benchmark's host, holds the interpreter lock for 6 us (15 among
twelve busy threads; ``PERF.md``, PR 39) where ``perf_counter_ns`` takes
0.09, and steps by 10 ms there: so one root in ``CPU_CLOCKED_ONE_IN`` is
clocked (the first of a profiler session, then every fourth; one in
sixteen where only the sample rate arms them; a forced ``trace_ctx``
always), the choice rides the trace id's low bits so that every daemon
of the trace agrees without a field on the wire, and a reading at most
``_CPU_REUSE_NS`` old is carried forward by the time since (the thread
was running to get from there to here).  A site whose span is a wait by
design says so (attribute ``wait``; ``device_wait`` for a wait on a
device result), so that what is off the CPU in every other span is
waiting nobody named: the interpreter lock, an unnamed lock, a blocking
call.  A cross-thread span (an op's root, a message hop, a queue wait,
an engine request) has no thread to ask and carries neither.  A message
hop is split by its receiver instead: ``sent_us`` (the sender's last
byte written), ``first_byte_us`` / ``framed_us`` (the receiver's reader
thread saw the frame's first byte / had it whole) and ``dequeued_us``
(its dispatch thread took it off the queue), microseconds from the
hop's start.

TWO SINKS.  Every span goes to the in-memory table below.  A span
opened and closed on one thread (the ``span(...)`` context manager, a
phase of the dispatch engine between two of its marks) is also entered
as a ``jax.profiler.TraceAnnotation`` of the same name while a
profiler session is live, so that in any profile the program's spans
lie in the host plane of the same xplane as the device's operations,
on the profiler's clock.  Cross-thread spans (an op's root, a message
hop, a queue wait, an engine request and its ``queue_wait``) live in
the table only.

Sampling policy — head sampling plus tail retention:

  * ``tracing_sample_rate`` (config): probability that an UNTRACED
    root site (``RadosClient.aio_operate``, the mapping service's
    ``update_to``) opens a trace (``begin_root``).  Explicit
    ``trace_ctx`` calls are always traced (a forced trace).
  * a live profiler session (``jax.profiler.start_trace``) arms every
    root site: each op of the session is traced, and its traces are
    held until the next session starts (up to ``SESSION_CAP``; beyond
    it new roots go unsampled, so a session's first traces — the ones
    a reader clips to — are never the ones lost).
  * ``tracing_slow_threshold`` (config): a completed trace whose ROOT
    span ran at least this long is promoted into a bounded slow-trace
    ring (``tracing_slow_ring`` entries) instead of being evicted with
    the rest — the Dapper tail-based retention that keeps exactly the
    traces worth debugging.  Fast traces age out of the active table.

Propagation is THREAD-SCOPED: the dispatch loop installs the current
(trace_id, span_id) for the duration of handling a traced message, so
synchronous fan-out (the op pipeline) is covered; work handed to
queues, timers and engine threads re-enters with ``set_current`` /
``joined`` from the ids carried on the message or the request.

Cost: an untraced thread pays one thread-local read per span site; a
root site pays one ``armed()`` check (a module float compare plus
``TraceAnnotation.is_enabled()``, ~50 ns).  When on, a span is one
object, one counter increment and two GIL-atomic dict operations; the
registry lock is taken once per trace (creation, eviction), not per
span.
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time

from ceph_tpu.common import lockdep

_tls = threading.local()
# import-time module lock: named under CEPH_TPU_LOCKDEP=1 (the env
# gate is read before any module imports), plain otherwise.  Guards
# the trace TABLES (creation, eviction, promotion); rows of one trace
# are appended with GIL-atomic dict/list operations
_lock = lockdep.make_lock("tracing::registry")

now_ns = time.perf_counter_ns
#: the calling thread's CPU time: read beside ``now_ns`` by whatever
#: opens and closes a span on one thread
thread_cpu_ns = time.thread_time_ns
#: roots per CPU-clocked root under a profiler session (a power of two:
#: the choice is the trace id's low bits); how many times fewer where
#: only the sample rate arms the roots (a clocked 4 KiB write is a sixth
#: slower on the benchmark's host, and arming may cost its median a
#: tenth all told: ROADMAP S6); and how old a reading of the CPU clock
#: may be to be carried forward instead of read anew
CPU_CLOCKED_ONE_IN = 4
_SAMPLED_THIN = 4
_CPU_REUSE_NS = 20_000

#: active/recent traces kept for stitching (FIFO eviction; slow traces
#: survive in the dedicated ring below, a profiler session's are pinned)
_ACTIVE_CAP_DEFAULT = 512
_active_cap = _ACTIVE_CAP_DEFAULT
#: traces one profiler session may pin (then its new roots go unsampled)
SESSION_CAP = 4096
#: span+event rows per trace (runaway-fan-out guard)
MAX_ROWS_PER_TRACE = 4096

#: head-sampling probability for root sites (0 = only explicit traces)
_DEFAULT_SAMPLE_RATE = 0.0
_sample_rate = _DEFAULT_SAMPLE_RATE
#: root-span duration (seconds) at/above which a completed trace is
#: promoted into the slow ring
_DEFAULT_SLOW_THRESHOLD = 0.5
_slow_threshold = _DEFAULT_SLOW_THRESHOLD
_DEFAULT_SLOW_RING = 64
_slow_ring_size = _DEFAULT_SLOW_RING

#: trace_id -> _Trace (insertion-ordered for FIFO eviction)
_traces: "dict[int, _Trace]" = {}
#: trace_id -> completed slow-trace snapshot (tail retention)
_slow: "dict[int, dict]" = {}
#: a profiler session was live at the last root site, and how many
#: traces it has pinned
_session_live = False
_session_pinned = 0
#: sampled roots so far (of this session, under one): counted from the
#: first, every CPU_CLOCKED_ONE_IN-th (x _SAMPLED_THIN) is CPU-clocked
_root_counter = itertools.count()


# -- layers -------------------------------------------------------------------

#: The benchmark's layer names (BENCHMARK.json ``layer``), by span
#: name: readers sum a layer's spans and need no list of names.  A
#: name not listed here is looked up by its first word.
LAYER_CLIENT = "client"
LAYER_MSGR = "messenger"
LAYER_OPQ = "OSD op queue"
LAYER_ECB = "PG / EC backend"
LAYER_ENGINE = "dispatch engine"
LAYER_STORE = "objectstore"
LAYER_MAPPING = "mapping service"
LAYER_KERNELS = "kernels"

#: the dispatch engine's phases (telemetry.PHASES), as child spans of
#: an engine request
ENGINE_PHASES = ("queue_wait", "build", "place", "launch", "compute",
                 "materialize", "deliver")

LAYERS = {
    # client/rados.py
    "client submit": LAYER_CLIENT, "client complete": LAYER_CLIENT,
    # msg/: one hop span per message (send queue + encode + wire +
    # decode) and the receiver's dispatch span
    "msg": LAYER_MSGR, "rx": LAYER_MSGR,
    # osd/daemon.py (queue boundary) + osd/op_queue.py
    "opq wait": LAYER_OPQ,
    # osd/daemon.py, the primary and the shards
    "osd op": LAYER_ECB, "ec prepare": LAYER_ECB,
    "ec encode submit": LAYER_ECB, "ec continuation": LAYER_ECB,
    "ec daemon lock wait": LAYER_ECB, "ec local commit": LAYER_ECB,
    "ec fan-out": LAYER_ECB, "ec sub-write": LAYER_ECB,
    "ec sub-write ack": LAYER_ECB, "osd reply": LAYER_ECB,
    # the EC read path: the primary's gather (the wait for k shards),
    # a shard's side of it, the decode engine's submit and continuation
    "ec read prepare": LAYER_ECB, "ec read gather": LAYER_ECB,
    "ec sub-read": LAYER_ECB, "ec sub-read reply": LAYER_ECB,
    "ec decode submit": LAYER_ECB, "ec decode continuation": LAYER_ECB,
    "ec read finish": LAYER_ECB,
    # the rmw gather of an overwrite (the wait for k chunks of the
    # stripes it touches) on a pool with allow_ec_overwrites
    "ec rmw gather": LAYER_ECB,
    # ops/dispatch.py: the request and its phases; ops/telemetry.py
    "device": LAYER_ENGINE, "engine": LAYER_ENGINE,
    "kernel": LAYER_KERNELS,
    # objectstore/
    "bluestore commit": LAYER_STORE, "bluestore apply": LAYER_STORE,
    "bluestore csum settle": LAYER_STORE, "bluestore fsync": LAYER_STORE,
    "bluestore kv commit": LAYER_STORE,
    "bluestore on_commit": LAYER_STORE,
    "bluestore read": LAYER_STORE, "bluestore read blocks": LAYER_STORE,
    "bluestore csum verify": LAYER_STORE,
    "objectstore commit": LAYER_STORE,
    # osd/mapping.py
    "update_to": LAYER_MAPPING, "mapping": LAYER_MAPPING,
}


def layer_of(name: str) -> str:
    """The layer a span of this name belongs to ('' = none)."""
    layer = LAYERS.get(name)
    if layer is None:
        layer = LAYERS.get(name.partition(" ")[0], "")
    return layer


# -- the profiler sink ---------------------------------------------------------

def _no_session() -> bool:
    return False


_profiler_on = _no_session
_Annotation = None
try:
    from jax.profiler import TraceAnnotation as _Annotation
    _profiler_on = _Annotation.is_enabled
except Exception:        # no jax, or one without the TraceMe binding
    pass


def armed() -> bool:
    """Whether an untraced root site may open a trace: the sample rate
    says so, or a profiler session is live (then every root is
    sampled).  The one check an unarmed root site pays."""
    return _sample_rate > 0.0 or _profiler_on()


# -- spans ---------------------------------------------------------------------

class Span:
    """One node of a trace tree.  ``start``/``end`` are
    ``time.perf_counter_ns()`` readings (``end`` None while open);
    ``cpu_ns``/``thread`` are what the one thread that opened and
    closed it used of its CPU meanwhile and its ident (None on a
    cross-thread span)."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name",
                 "daemon", "start", "end", "attrs", "cpu_ns", "thread")

    def __init__(self, trace_id: int, span_id: int, parent_span_id: int,
                 name: str, daemon: str, start: int,
                 attrs: dict | None = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.name = name
        self.daemon = daemon
        self.start = start
        self.end: int | None = None
        self.attrs = attrs if attrs is not None else {}
        self.cpu_ns: int | None = None
        self.thread: int | None = None

    @property
    def duration(self) -> float | None:
        """Seconds on the monotonic clock (never negative)."""
        return None if self.end is None else (self.end - self.start) / 1e9


class _Trace:
    __slots__ = ("trace_id", "spans", "events", "root_span_id",
                 "wall0", "t0", "completed", "dropped_rows", "pinned")

    def __init__(self, trace_id: int):
        self.trace_id = trace_id
        #: span_id -> Span (insertion ordered)
        self.spans: "dict[int, Span]" = {}
        #: (span_id, daemon, event, t_ns) point events
        self.events: list[tuple[int, str, str, int]] = []
        self.root_span_id = 0
        #: the one wall-clock reading of the trace, with the monotonic
        #: reading it pairs: rows display ``wall0 + (t - t0)``
        self.wall0 = time.time()
        self.t0 = now_ns()
        self.completed = False
        self.dropped_rows = 0
        #: opened under a profiler session: held until the next one
        self.pinned = False

    def n_rows(self) -> int:
        return len(self.spans) + len(self.events)

    def wall(self, t_ns: int) -> float:
        return self.wall0 + (t_ns - self.t0) / 1e9

    def rows(self) -> list[dict]:
        out = []
        # list(...) of a dict view / a list is one GIL-atomic copy:
        # writers append without the registry lock
        for sp in list(self.spans.values()):
            r = {"trace_id": self.trace_id, "daemon": sp.daemon,
                 "event": sp.name, "t": self.wall(sp.start),
                 "kind": "span", "span_id": sp.span_id,
                 "parent_span_id": sp.parent_span_id,
                 "dur": sp.duration, "start_ns": sp.start,
                 "end_ns": sp.end, "layer": layer_of(sp.name)}
            if sp.cpu_ns is not None:
                r["cpu_ns"] = sp.cpu_ns
                r["thread"] = sp.thread
            if sp.attrs:
                r["attrs"] = dict(sp.attrs)
            out.append(r)
        out.extend({"trace_id": self.trace_id, "daemon": d, "event": e,
                    "t": self.wall(t), "kind": "event", "span_id": sid,
                    "start_ns": t}
                   for sid, d, e, t in list(self.events))
        out.sort(key=lambda r: r["start_ns"])
        return out


# -- ids and thread context ---------------------------------------------------

# A per-process random prefix and a counter: ids stay unique across the
# daemons of a cluster (31 random bits tell processes apart) without a
# syscall per span.  63 bits, never 0: they ride the frame as u64.
_ID_PREFIX = (int.from_bytes(os.urandom(4), "big") >> 1 or 1) << 32
_id_counter = itertools.count(1)


def new_span_id() -> int:
    """A span id — or a trace id: one sequence serves both."""
    return _ID_PREFIX | (next(_id_counter) & 0xFFFFFFFF) or 1


def cpu_clocked(trace_id: int) -> bool:
    """Whether the spans of this trace read their threads' CPU clock."""
    return not trace_id & (CPU_CLOCKED_ONE_IN - 1)


def _new_trace_id(clocked: bool) -> int:
    tid = new_span_id()
    while cpu_clocked(tid) != clocked and CPU_CLOCKED_ONE_IN > 1:
        tid = new_span_id()
    return tid


def _thread_cpu(now: int) -> int:
    """The calling thread's CPU clock at `now`, a ``perf_counter_ns``
    reading just taken on it."""
    last = getattr(_tls, "cpu", None)
    if last is not None and now - last[0] <= _CPU_REUSE_NS:
        return last[1] + now - last[0]
    cpu = thread_cpu_ns()
    _tls.cpu = (now, cpu)
    return cpu


def current() -> int:
    """The calling thread's current trace id (0 = untraced)."""
    return getattr(_tls, "ctx", (0, 0))[0]


def current_span() -> int:
    """The calling thread's current span id (0 = none)."""
    return getattr(_tls, "ctx", (0, 0))[1]


def set_current(trace_id, span_id: int = 0):
    """Install (trace_id, span_id) as the thread's current context;
    returns the previous context (restore it via set_current when
    done).  Accepts either two ints or the tuple a prior call
    returned."""
    if isinstance(trace_id, tuple):
        trace_id, span_id = trace_id
    prev = getattr(_tls, "ctx", (0, 0))
    _tls.ctx = (trace_id, span_id)
    return prev


class _Null:
    """The no-op context manager an untraced site gets (one shared
    object: no allocation on the untraced path)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class joined:
    """Run a block under (trace_id, span_id) — a thread picking up
    work that carries its ids on a message or a request."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, trace_id: int, span_id: int = 0):
        self._ctx = (trace_id, span_id)

    def __enter__(self):
        self._prev = set_current(self._ctx)

    def __exit__(self, *exc):
        set_current(self._prev)
        return False


# -- trace table internals ----------------------------------------------------

def _get_trace(tid: int) -> _Trace | None:
    tr = _traces.get(tid)
    if tr is not None:
        return tr
    with _lock:
        tr = _traces.get(tid)
        if tr is None:
            if tid in _slow:
                # the trace already completed, was promoted, and aged
                # out of the active table: a straggler row must not
                # resurrect an empty ghost that would shadow the
                # archived snapshot
                return None
            tr = _Trace(tid)
            _traces[tid] = tr
            while _n_unpinned_locked() > _active_cap:
                if not _evict_one_locked():
                    break
    return tr


def _n_unpinned_locked() -> int:
    return len(_traces) - _session_pinned


def _evict_one_locked() -> bool:
    """Drop one unpinned trace: COMPLETED (fast, un-promoted) traces go
    first — an in-flight trace may still turn out slow, and evicting it
    would defeat tail retention exactly when sampling load makes it
    matter.  Only when every retained trace is still open does the
    oldest open one go (the runaway bound must hold regardless)."""
    oldest = None
    for tid, tr in _traces.items():
        if tr.pinned:
            continue
        if tr.completed:
            del _traces[tid]
            return True
        if oldest is None:
            oldest = tid
    if oldest is None:
        return False
    del _traces[oldest]
    return True


def begin_span(name: str, daemon: str, trace_id: int | None = None,
               parent_span_id: int | None = None,
               attrs: dict | None = None,
               start: int | None = None) -> Span | None:
    """Open a span.  trace_id/parent default to the thread context;
    returns None when there is no trace to attach to.  ``start`` (a
    ``perf_counter_ns`` reading) backdates it.  Does NOT touch the
    thread context — callers that dispatch work under the span install
    it via set_current."""
    ctx = getattr(_tls, "ctx", (0, 0))
    tid = ctx[0] if trace_id is None else trace_id
    if not tid:
        return None
    if parent_span_id is None:
        parent_span_id = ctx[1] if ctx[0] == tid else 0
    tr = _get_trace(tid)
    if tr is None:
        return None
    if tr.n_rows() >= MAX_ROWS_PER_TRACE:
        tr.dropped_rows += 1
        return None
    sp = Span(tid, new_span_id(), parent_span_id, name, daemon,
              now_ns() if start is None else start, attrs)
    tr.spans[sp.span_id] = sp
    if not parent_span_id and not tr.root_span_id:
        tr.root_span_id = sp.span_id
    return sp


def finish_span(span: Span | None, end: int | None = None) -> None:
    """Close a span, now or at ``end`` (a ``perf_counter_ns`` reading).
    Closing a closed span again can only move its end later: a message
    hop is closed by its sender once the bytes are written and again,
    later, by a receiver in the same process."""
    if span is None:
        return
    t = now_ns() if end is None else end
    if span.end is None or t > span.end:
        span.end = max(t, span.start)


def add_span(name: str, daemon: str, trace_id: int, parent_span_id: int,
             start: int, end: int, attrs: dict | None = None,
             cpu_ns: int | None = None, thread: int | None = None
             ) -> Span | None:
    """Record a span whose interval is already known (an engine phase
    read off the batch's marks), with the CPU time of the one thread
    that ran it, where one did."""
    sp = begin_span(name, daemon, trace_id=trace_id,
                    parent_span_id=parent_span_id, attrs=attrs,
                    start=start)
    if sp is not None:
        sp.end = max(end, start)
        sp.cpu_ns, sp.thread = cpu_ns, thread
    return sp


def find_span(trace_id: int, span_id: int) -> Span | None:
    """The span of these ids, if this process holds it."""
    tr = _traces.get(trace_id)
    return tr.spans.get(span_id) if tr is not None else None


def set_attrs(span: Span | None, **attrs) -> None:
    if span is not None:
        span.attrs.update(attrs)


class _SpanCtx:
    """``with span(...)``: a child span of the thread's current span
    for the duration of the block, entered as a profiler annotation of
    the same name while a session is live.  In a CPU-clocked trace the
    thread's CPU clock is read at the span's two clock readings."""

    __slots__ = ("_name", "_daemon", "_attrs", "_span", "_prev", "_ann",
                 "_cpu0")

    def __init__(self, name: str, daemon: str, attrs: dict | None):
        self._name = name
        self._daemon = daemon
        self._attrs = attrs

    def _begin(self) -> Span | None:
        return begin_span(self._name, self._daemon, attrs=self._attrs)

    _finish = staticmethod(finish_span)

    def __enter__(self) -> Span | None:
        sp = self._span = self._begin()
        self._ann = None
        if sp is None:        # row-cap hit, or a root left unsampled
            return None
        self._cpu0 = (None if sp.trace_id & (CPU_CLOCKED_ONE_IN - 1)
                      else _thread_cpu(sp.start))     # cpu_clocked()
        self._prev = set_current(sp.trace_id, sp.span_id)
        if _profiler_on():
            self._ann = _Annotation(self._name)
            self._ann.__enter__()
        return sp

    def __exit__(self, *exc):
        sp = self._span
        if sp is not None:
            if self._ann is not None:
                self._ann.__exit__(*exc)
            set_current(self._prev)
            end = now_ns()
            if self._cpu0 is not None:
                sp.cpu_ns = max(0, _thread_cpu(end) - self._cpu0)
                sp.thread = threading.get_ident()
            self._finish(sp, end)
        return False


def span(name: str, daemon: str = "", **attrs):
    """Open a child span of the thread's current span for the duration
    of the block; a shared no-op (yields None) when the thread is
    untraced."""
    if not getattr(_tls, "ctx", (0, 0))[0]:
        return _NULL
    return _SpanCtx(name, daemon or "span", attrs or None)


# -- roots ---------------------------------------------------------------------

def _sampled() -> bool:
    """Whether this untraced root opens a trace; True with the second
    value says a profiler session pins it."""
    global _session_live, _session_pinned, _root_counter
    if _profiler_on():
        with _lock:
            if not _session_live:
                # a new session: the last one's traces have been read
                # (or never will be) and age out with the rest
                _session_live = True
                _session_pinned = 0
                _root_counter = itertools.count()
                for tr in _traces.values():
                    tr.pinned = False
                while _n_unpinned_locked() > _active_cap:
                    if not _evict_one_locked():
                        break
            return _session_pinned < SESSION_CAP
    _session_live = False
    return _sample_rate > 0.0 and random.random() < _sample_rate


def begin_root(name: str, daemon: str, attrs: dict | None = None
               ) -> Span | None:
    """Open a NEW trace's root span on an untraced thread, if the
    sampling policy says so (callers gate on ``armed()`` first: that
    is the whole cost of an unarmed site).  The root may be finished
    on another thread (``finish_root``); the thread context is the
    caller's to install (``joined``)."""
    global _session_pinned
    if not _sampled():
        return None
    every = CPU_CLOCKED_ONE_IN * (1 if _session_live else _SAMPLED_THIN)
    sp = begin_span(name, daemon, parent_span_id=0, attrs=attrs,
                    trace_id=_new_trace_id(next(_root_counter) % every == 0))
    if sp is not None and _session_live:
        with _lock:
            tr = _traces.get(sp.trace_id)
            if tr is not None and not tr.pinned:
                tr.pinned = True
                _session_pinned += 1
    return sp


def finish_root(root: Span | None, end: int | None = None) -> None:
    """Close a root and complete its trace (the tail-retention check
    against tracing_slow_threshold)."""
    if root is None:
        return
    finish_span(root, end)
    _maybe_complete(root.trace_id, root)


class _RootCtx(_SpanCtx):
    """``with root(...)`` on an untraced thread: a same-thread root,
    annotated like a span and completed on exit."""

    __slots__ = ()

    def _begin(self) -> Span | None:
        return begin_root(self._name, self._daemon, self._attrs)

    _finish = staticmethod(finish_root)


def root(name: str, daemon: str, **attrs):
    """A root site whose work runs on the calling thread: opens a new
    trace when armed and the thread is untraced, joins the caller's
    trace with a child span otherwise; a shared no-op when neither."""
    if getattr(_tls, "ctx", (0, 0))[0]:
        return _SpanCtx(name, daemon, attrs or None)
    return _RootCtx(name, daemon, attrs or None) if armed() else _NULL


# -- consecutive phases on one thread -------------------------------------------

class PhaseMarks:
    """Consecutive phases of one thread's work that no ``with`` block
    brackets (the dispatch engine's, between the marks of its phase
    ledger): ``begin(name)`` ends the phase before and starts the next,
    ``end()`` ends the last.  For a batch that holds a CPU-clocked
    trace each phase's share of the thread's CPU clock is kept in
    ``cpu`` by name, for the spans recorded afterwards (``add_span``);
    while a profiler session is live each phase is an annotation of its
    name."""

    __slots__ = ("cpu", "thread", "t_cpu", "_clocked", "_profiled",
                 "_name", "_ann")

    def __init__(self, clocked: bool, profiled: bool):
        #: phase name -> ns of this thread's CPU
        self.cpu: dict[str, int] = {}
        self.thread = threading.get_ident()
        #: the thread's CPU clock at the last mark
        self.t_cpu = 0
        self._clocked = clocked
        self._profiled = profiled
        self._name = None
        self._ann = None

    def begin(self, name: str | None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._clocked:
            t = _thread_cpu(now_ns())
            if self._name is not None:
                self.cpu[self._name] = max(0, t - self.t_cpu)
            self.t_cpu = t
        self._name = name
        if name is not None and self._profiled:
            self._ann = _Annotation(name)
            self._ann.__enter__()

    def end(self) -> None:
        if self._name is not None:
            self.begin(None)

    def finish_span(self, span: Span | None) -> None:
        """Close a span this thread opened at the last mark."""
        if (span is not None and self._clocked
                and cpu_clocked(span.trace_id)):
            end = now_ns()
            span.cpu_ns = max(0, _thread_cpu(end) - self.t_cpu)
            span.thread = self.thread
            finish_span(span, end)
        else:
            finish_span(span)


#: what an untraced batch outside any profiler session marks its
#: phases on: one shared object that reads and opens nothing
_NO_MARKS = PhaseMarks(False, False)


def phase_marks(trace_ids):
    """Marks for one thread's run of phases on behalf of the traces
    `trace_ids`: real ones where a CPU-clocked trace's spans will read
    them or a profiler session is live — the one check an untraced
    batch pays."""
    clocked = any(map(cpu_clocked, trace_ids))
    profiled = _profiler_on()
    return (PhaseMarks(clocked, profiled) if clocked or profiled
            else _NO_MARKS)


class trace_ctx:
    """Open (or join) a trace for the calling thread — a FORCED trace,
    whatever the sampling policy.  Opens a span; when that span is the
    trace's ROOT, exiting completes the trace (tail-retention check
    against tracing_slow_threshold).  Yields the trace id."""

    __slots__ = ("_tid", "_name", "_daemon", "_span", "_prev")

    def __init__(self, trace_id: int | None = None, name: str = "trace",
                 daemon: str = "client"):
        self._tid = trace_id
        self._name = name
        self._daemon = daemon

    def __enter__(self) -> int:
        tid = self._tid or _new_trace_id(True)
        self._tid = tid
        join = current() == tid
        sp = self._span = begin_span(
            self._name, self._daemon, trace_id=tid,
            parent_span_id=current_span() if join else 0)
        self._prev = set_current(tid, sp.span_id if sp else 0)
        return tid

    def __exit__(self, *exc):
        set_current(self._prev)
        sp = self._span
        if sp is not None:
            finish_span(sp)
            _maybe_complete(self._tid, sp)
        return False


def _maybe_complete(tid: int, root_span: Span) -> None:
    with _lock:
        tr = _traces.get(tid)
        if tr is None or tr.root_span_id != root_span.span_id:
            return
        tr.completed = True
        dur = root_span.duration or 0.0
        if dur < _slow_threshold:
            return
        _slow[tid] = {
            "trace_id": tid,
            "root": root_span.name,
            "daemon": root_span.daemon,
            "duration": round(dur, 6),
            "completed_at": tr.wall(root_span.end),
            "n_spans": len(tr.spans),
            "rows": tr.rows(),
        }
        while len(_slow) > _slow_ring_size:
            del _slow[next(iter(_slow))]


# -- event recording ----------------------------------------------------------

def record(daemon: str, event: str, trace_id: int | None = None,
           span_id: int | None = None) -> None:
    """Attach a point event to a trace (to the thread's current span
    when it belongs to the same trace)."""
    ctx = getattr(_tls, "ctx", (0, 0))
    tid = trace_id if trace_id is not None else ctx[0]
    if not tid:
        return
    if span_id is None:
        span_id = ctx[1] if ctx[0] == tid else 0
    tr = _get_trace(tid)
    if tr is None:
        return
    if tr.n_rows() >= MAX_ROWS_PER_TRACE:
        tr.dropped_rows += 1
        return
    # an event recorded off-thread (explicit trace_id) still belongs
    # in the tree: attach it to the trace root
    tr.events.append((span_id or tr.root_span_id, daemon, event,
                      now_ns()))


def stamp(msg, daemon: str) -> None:
    """Transport send hook: a message sent by a thread holding a trace
    inherits the ids (once), and the send opens the message's HOP span
    ``msg <MsgType>``, whose span_id rides the frame as the receiver's
    parent.  The hop stays open over the send queue, the encode, the
    wire and the decode: the sender closes it once the bytes are
    written (``sent``), and a receiver in the same process closes it
    again, later, as its dispatch begins (``received``).  Runs on the
    CALLER's thread — transports that encode later on an event loop
    still carry the ids because they live on the message."""
    if getattr(msg, "trace_id", 0):
        return
    tid = current()
    if not tid:
        return
    msg.trace_id = tid
    sp = begin_span(f"msg {type(msg).__name__}", daemon, trace_id=tid)
    if sp is not None:
        msg.parent_span_id = sp.span_id
        msg._hop_span = sp
    else:
        msg.parent_span_id = current_span()


def sent(msg) -> None:
    """Transport hook: the message's bytes are written (or handed to
    an in-process peer)."""
    sp = getattr(msg, "_hop_span", None)
    if sp is not None:
        t = now_ns()
        # where the hop's time went: up to here the send queue, the
        # encode and the write; from here the wire, the peer's reader
        # and its decode
        sp.attrs.setdefault("sent_us", (t - sp.start) // 1000)
        finish_span(sp, t)


def received(trace_id: int, hop_span_id: int, rx_stamps=None) -> None:
    """Receiver hook: dispatch of a traced message begins, so its hop
    — if this process holds it — ends here.  ``rx_stamps``: the
    ``perf_counter_ns`` readings the transport took on the frame's way
    in (first byte seen, frame whole, taken off the dispatch queue; a
    first byte of 0 was not seen armed), kept on the hop like
    ``sent_us``."""
    sp = find_span(trace_id, hop_span_id)
    if sp is not None and sp.name.startswith("msg "):
        if rx_stamps is not None:
            first, framed, dequeued = rx_stamps
            start, attrs = sp.start, sp.attrs
            if first:
                attrs["first_byte_us"] = max(0, first - start) // 1000
            attrs["framed_us"] = max(0, framed - start) // 1000
            attrs["dequeued_us"] = max(0, dequeued - start) // 1000
        finish_span(sp)


# -- query surface ------------------------------------------------------------

def dump(trace_id: int | None = None) -> list[dict]:
    """Stitched span-structured timeline(s), time-ordered — the
    admin-socket payload.  Every row carries span_id (and, for spans,
    parent_span_id/dur/start_ns/end_ns/layer/attrs).  Falls back to the
    slow ring for traces already evicted from the active table."""
    with _lock:
        if trace_id is None:
            out = []
            for tr in list(_traces.values()):
                out.extend(tr.rows())
            # slow-ring-only traces (already evicted from the active
            # table) stay visible in the unfiltered view too
            for tid, snap in _slow.items():
                if tid not in _traces:
                    out.extend(dict(r) for r in snap["rows"])
            out.sort(key=lambda r: r["start_ns"])
            return out
        tr = _traces.get(trace_id)
        if tr is not None:
            return tr.rows()
        snap = _slow.get(trace_id)
        return [dict(r) for r in snap["rows"]] if snap else []


def completed_traces() -> list[list[dict]]:
    """The rows of every completed trace still in the table, one list
    per trace, oldest first (what a reader of a profiler session's
    spans walks)."""
    with _lock:
        done = [tr for tr in _traces.values() if tr.completed]
    return [tr.rows() for tr in done]


def trace_ids() -> list[int]:
    with _lock:
        return sorted(set(_traces) | set(_slow))


def tree_from_rows(rows: list[dict]) -> list[dict]:
    """Nest span rows into trees: spans with their events and
    children.  Spans whose parent is unknown (0, or a span on a daemon
    whose rows were not shipped) surface as roots.  Shared by
    span_tree and the mgr insights module's cluster-wide merge."""
    nodes: dict[int, dict] = {}
    for r in rows:
        if r.get("kind") == "span":
            nodes[r["span_id"]] = {
                "span_id": r["span_id"],
                "parent_span_id": r.get("parent_span_id", 0),
                "name": r.get("event"), "daemon": r.get("daemon"),
                "start": r.get("t"), "dur": r.get("dur"),
                "layer": r.get("layer", ""),
                "attrs": r.get("attrs", {}),
                "events": [], "children": []}
            if "cpu_ns" in r:
                nodes[r["span_id"]].update(cpu_ns=r["cpu_ns"],
                                           thread=r["thread"])
    roots: list[dict] = []
    for r in rows:
        if r.get("kind") == "span":
            n = nodes[r["span_id"]]
            parent = nodes.get(n["parent_span_id"])
            (parent["children"] if parent else roots).append(n)
        else:
            holder = nodes.get(r.get("span_id", 0))
            if holder is not None:
                holder["events"].append(
                    {"daemon": r.get("daemon"), "event": r.get("event"),
                     "t": r.get("t")})
    return roots


def span_tree(trace_id: int) -> dict:
    """One trace's nested tree view."""
    rows = dump(trace_id)
    return {"trace_id": trace_id, "n_rows": len(rows),
            "spans": tree_from_rows(rows)}


# -- slow-trace ring (tail retention) -----------------------------------------

def slow_traces() -> list[dict]:
    """Completed traces whose root span crossed the slow threshold,
    oldest first (each entry: trace_id, root, daemon, duration,
    completed_at, n_spans, rows)."""
    with _lock:
        return [dict(s) for s in _slow.values()]


def slow_trace_digests(limit: int = 16,
                       max_rows: int = 128) -> list[dict]:
    """Compact newest-first digests for MMgrReport (rows capped)."""
    with _lock:
        snaps = list(_slow.values())[-limit:]
    out = []
    for s in reversed(snaps):
        d = {k: s[k] for k in ("trace_id", "root", "daemon", "duration",
                               "completed_at", "n_spans")}
        d["rows"] = [dict(r) for r in s["rows"][:max_rows]]
        out.append(d)
    return out


# -- policy knobs -------------------------------------------------------------

def set_sample_rate(rate) -> None:
    global _sample_rate
    _sample_rate = min(1.0, max(0.0, float(rate)))


def set_slow_threshold(seconds) -> None:
    global _slow_threshold
    _slow_threshold = max(0.0, float(seconds))


def set_slow_ring(size: int) -> None:
    global _slow_ring_size
    _slow_ring_size = max(1, int(size))
    with _lock:
        while len(_slow) > _slow_ring_size:
            del _slow[next(iter(_slow))]


def set_active_cap(size: int) -> None:
    """Bound on concurrently retained (non-slow, unpinned) traces; test
    surface."""
    global _active_cap
    _active_cap = max(1, int(size))
    with _lock:
        while _n_unpinned_locked() > _active_cap:
            if not _evict_one_locked():
                break


def configure_from_conf(conf) -> None:
    """Bind the sampling knobs to a context's config with hot reload.

    The trace tables are process-global while configs are per-context
    (multi-daemon processes construct many): construction only applies
    values that DIFFER from the defaults — it never resets a global
    back to its default, or every later daemon/client construction
    would silently undo an operator's `config set` on another daemon.
    Runtime changes propagate through the observers."""
    for name, setter, dflt in (
            ("tracing_sample_rate", set_sample_rate,
             _DEFAULT_SAMPLE_RATE),
            ("tracing_slow_threshold", set_slow_threshold,
             _DEFAULT_SLOW_THRESHOLD),
            ("tracing_slow_ring", set_slow_ring, _DEFAULT_SLOW_RING)):
        try:
            v = conf.get(name)
            if float(v) != dflt:
                setter(v)
            conf.add_observer(
                name, lambda _n, val, s=setter: s(val))
        except KeyError:   # option table without the knob
            pass


def reset() -> None:
    """Drop every trace and restore default policy (test isolation)."""
    global _sample_rate, _slow_threshold, _slow_ring_size, _active_cap
    global _session_live, _session_pinned, _root_counter
    with _lock:
        _traces.clear()
        _slow.clear()
        _session_live = False
        _session_pinned = 0
        _root_counter = itertools.count()
    _sample_rate = _DEFAULT_SAMPLE_RATE
    _slow_threshold = _DEFAULT_SLOW_THRESHOLD
    _slow_ring_size = _DEFAULT_SLOW_RING
    _active_cap = _ACTIVE_CAP_DEFAULT
