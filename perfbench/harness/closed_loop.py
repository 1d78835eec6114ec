"""The closed-loop client: `depth` operations in flight, the next one
submitted when one is acknowledged.

Copied in idea from ``ceph_tpu/tools/rados_bench.ObjBencher._drive`` and
corrected in two ways (PERF.md, verdict table): the client thread blocks
on a queue that acknowledgements are pushed to, stamped at the moment
they arrive, where the original polled every 0.5 ms; and the measured
window opens and closes while the pipeline is full, where the original
timed ramp, steady state and drain together.

One loop runs three phases without a gap between them:

  precondition  untimed, until `precondition_acks` ops were acknowledged
                and `depth` are in flight; part of set-up
  window        `seconds` long, submitting through its deadline, so that
                `depth` ops are still in flight when it closes
  drain         no new submissions; waits for what is in flight

What belongs to the window is decided afterwards from the stamps
(harness/window.py).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

from .window import Ack


class AckEvent(threading.Event):
    """A completion's event that also tells the loop, with the time.

    The program's client wakes a waiter by setting its
    ``threading.Event`` and has no completion callback; an event that
    reports its own ``set`` is the callback."""

    def __init__(self, sink: queue.SimpleQueue, index: int):
        super().__init__()
        self._sink = sink
        self._index = index
        self._told = False

    def set(self) -> None:
        t = time.perf_counter()
        super().set()
        if not self._told:
            self._told = True
            self._sink.put((self._index, t))


def watch(completion, sink: queue.SimpleQueue, index: int):
    """Make `completion` (an ``AioCompletion``) report to `sink`.
    Returns the event it had before, which the loop looks at once a
    second in case the reply raced the exchange."""
    waiter = completion._w
    ours = AckEvent(sink, index)
    theirs, waiter.event = waiter.event, ours
    if theirs.is_set():
        ours.set()
    return theirs


class LoopLog:
    """What one run of the loop saw."""

    def __init__(self):
        self.acks: list[Ack] = []
        self.t_open = 0.0
        self.seconds = 0.0
        self.submitted = 0
        self.lost: list[tuple[int, float]] = []   # (index, t_submit)

    @property
    def failed(self) -> int:
        return len(self.lost) + sum(1 for a in self.acks if not a.ok)

    def depth_at(self, t: float) -> int:
        """Ops submitted before `t` and not acknowledged by then."""
        return (sum(1 for a in self.acks if a.t_submit < t <= a.t_ack)
                + sum(1 for _i, t0 in self.lost if t0 < t))


def run(submit, *, depth: int, precondition_acks: int, seconds: float,
        on_open=None, on_close=None, op_timeout: float = 120.0,
        ok=lambda completion: completion.get_return_value() >= 0,
        span=None) -> LoopLog:
    """Drive ``submit(index) -> completion`` in a closed loop.

    ``on_open()`` runs on this thread just before the window's clock
    starts, with the pipeline full (it ends set-up: counters are read
    there); ``on_close()`` just after the deadline.  ``span(name)``
    gives a context manager that marks what this thread is doing, for
    the profiler."""
    span = span or no_span
    log = LoopLog()
    sink: queue.SimpleQueue = queue.SimpleQueue()
    flying: dict[int, tuple[float, object, object]] = {}
    acked = 0

    def fill() -> None:
        while len(flying) < depth:
            with span("generator"):
                index = log.submitted
                log.submitted += 1
                t0 = time.perf_counter()
                completion = submit(index)
                flying[index] = (t0, completion,
                                 watch(completion, sink, index))

    def take(index: int, t_ack: float) -> None:
        nonlocal acked
        t0, completion, _theirs = flying.pop(index)
        log.acks.append(Ack(index, t0, t_ack, bool(ok(completion))))
        acked += 1

    def wait(limit: float) -> bool:
        """Block for the next acknowledgement, at most `limit` seconds."""
        try:
            with span("client_op"):
                index, t_ack = sink.get(timeout=max(limit, 0.0))
        except queue.Empty:
            now = time.perf_counter()
            for index, (t0, completion, theirs) in list(flying.items()):
                if theirs.is_set() and not completion.is_complete():
                    completion._w.event.set()       # the reply raced us
                elif now - t0 > op_timeout:
                    completion._w.event._told = True    # not an ack
                    completion.cancel()
                    flying.pop(index)
                    log.lost.append((index, t0))
            return False
        take(index, t_ack)
        return True

    def reap() -> None:
        """Take what is already acknowledged, without blocking."""
        while True:
            try:
                take(*sink.get_nowait())
            except queue.Empty:
                return

    fill()
    # precondition
    while acked < precondition_acks:
        wait(1.0)
        fill()
    if on_open is not None:
        on_open()
    reap()
    fill()
    log.t_open = time.perf_counter()
    log.seconds = seconds
    t_close = log.t_open + seconds
    # window: keeps submitting until the deadline has passed
    while True:
        left = t_close - time.perf_counter()
        if left <= 0:
            break
        wait(min(left, 1.0))
        if time.perf_counter() < t_close:
            fill()
    if on_close is not None:
        on_close()
    # drain
    while flying:
        wait(1.0)
    return log


def run_all(submit, count: int, *, depth: int, op_timeout: float = 120.0,
            ok=lambda completion: completion.get_return_value() >= 0,
            collect=None) -> LoopLog:
    """Items 0..count-1 through ``submit`` with `depth` in flight, to
    the end and against no clock (the read-back of a check).
    ``collect(index, completion)`` is handed every good completion."""
    log = LoopLog()
    sink: queue.SimpleQueue = queue.SimpleQueue()
    flying: dict[int, tuple[float, object]] = {}
    while log.submitted < count or flying:
        while log.submitted < count and len(flying) < depth:
            index = log.submitted
            log.submitted += 1
            completion = submit(index)
            flying[index] = (time.perf_counter(), completion)
            watch(completion, sink, index)
        try:
            index, t_ack = sink.get(timeout=op_timeout)
        except queue.Empty:
            for index, (t0, completion) in flying.items():
                completion._w.event._told = True
                completion.cancel()
                log.lost.append((index, t0))
            break
        t0, completion = flying.pop(index)
        good = bool(ok(completion))
        log.acks.append(Ack(index, t0, t_ack, good))
        if good and collect is not None:
            collect(index, completion)
    return log


def no_span(_name: str):
    return contextlib.nullcontext()
