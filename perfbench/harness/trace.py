"""From a profiler trace (``.xplane.pb``) to device busy time, per
program device time, the device operations that took most time and the
idle gaps by what the host was doing.

The traced slice of a window is marked by the harness with a
``TraceAnnotation`` named ``WINDOW_SPAN``; everything is clipped to it.
Device planes are those named ``/device:TPU:<n>``.  On such a plane the
line ``XLA Modules`` holds one event per execution of a jitted program
(``jit_digest(123...)``) and the line ``XLA Ops`` one per operation in
it.  Busy time is the union of the operations' intervals; a program's
device time is the sum of its module events.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "perfbench_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Event:
    name: str
    start: float        # seconds on the trace's clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class DeviceLines:
    modules: list[Event] = field(default_factory=list)
    ops: list[Event] = field(default_factory=list)


@dataclass
class RawTrace:
    """What is read from the file, before any arithmetic."""
    devices: dict[int, DeviceLines] = field(default_factory=dict)
    host_spans: list[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, span_names) -> RawTrace:
    """Device modules and operations, and the host spans named in
    `span_names` (the harness's own annotations)."""
    from jax.profiler import ProfileData
    wanted = set(span_names) | {WINDOW_SPAN}
    raw = RawTrace()
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = raw.devices.setdefault(int(m.group(1)), DeviceLines())
            for line in plane.lines:
                if line.name == "XLA Modules":
                    into = dev.modules
                elif line.name == "XLA Ops":
                    into = dev.ops
                else:
                    continue
                for ev in line.events:
                    into.append(Event(ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        raw.host_spans.append(Event(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    return raw


# -- interval arithmetic ----------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of (start, end) pairs."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """What of [lo, hi] the disjoint, sorted `busy` does not cover."""
    out = []
    at = lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def program_of(name: str) -> str:
    """``jit_digest(1234567)`` -> ``jit_digest``."""
    return _MODULE_ID.sub("", name)


def op_of(name: str) -> str:
    """An operation's event is named by its whole HLO line,
    ``%fusion.4 = s32[4096,4]{0,1} fusion(...)``: keep ``fusion.4``."""
    return name.split(" = ", 1)[0].lstrip("%")


# -- the reduction ------------------------------------------------------------

@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the devices that ran
    busy_by_device: dict[int, float]
    program_s: dict[str, float]         # program -> device seconds, all devices
    program_calls: dict[str, int]
    device_ops: list[list]              # [[name, seconds], ...] top 10
    idle_gaps: list[list]               # [[span, seconds], ...] top 10

    def seconds_of(self, *prefixes: str) -> float:
        """Device seconds of the programs whose name starts with one of
        `prefixes`."""
        return sum(s for name, s in self.program_s.items()
                   if name.startswith(prefixes))

    def calls_of(self, *prefixes: str) -> int:
        return sum(n for name, n in self.program_calls.items()
                   if name.startswith(prefixes))


def summarize(raw: RawTrace, top: int = 10) -> TraceSummary:
    marks = [e for e in raw.host_spans if e.name == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0].start, marks[0].end
    else:                               # a trace not made by the harness
        every = [e for d in raw.devices.values() for e in d.ops + d.modules]
        if not every:
            raise ValueError("the trace holds no device event")
        lo, hi = min(e.start for e in every), max(e.end for e in every)
    busy_by_device: dict[int, float] = {}
    busy_cover: dict[int, list] = {}
    program_s: dict[str, float] = {}
    program_calls: dict[str, int] = {}
    op_s: dict[str, float] = {}
    for dev, lines in raw.devices.items():
        source = lines.ops or lines.modules
        cover = union(clip([(e.start, e.end) for e in source], lo, hi))
        busy_cover[dev] = cover
        busy_by_device[dev] = sum(b - a for a, b in cover)
        mods = sorted(lines.modules, key=lambda e: e.start)
        for e in mods:
            part = clip([(e.start, e.end)], lo, hi)
            if part:
                name = program_of(e.name)
                program_s[name] = program_s.get(name, 0.0) + (
                    part[0][1] - part[0][0])
                program_calls[name] = program_calls.get(name, 0) + 1
        for e in lines.ops:
            part = clip([(e.start, e.end)], lo, hi)
            if part:
                key = f"{_module_at(mods, e.start)}/{op_of(e.name)}"
                op_s[key] = op_s.get(key, 0.0) + part[0][1] - part[0][0]
    ran = [s for s in busy_by_device.values() if s > 0]
    busy_s = sum(ran) / len(ran) if ran else 0.0
    # idle gaps of the busiest device, by the harness span that was
    # open on the host at the gap's middle
    gap_s: dict[str, float] = {}
    if busy_by_device:
        busiest = max(busy_by_device, key=busy_by_device.get)
        spans = sorted((e for e in raw.host_spans if e.name != WINDOW_SPAN),
                       key=lambda e: e.start)
        for a, b in gaps(busy_cover[busiest], lo, hi):
            name = _span_at(spans, (a + b) / 2)
            gap_s[name] = gap_s.get(name, 0.0) + (b - a)

    def ranked(d: dict) -> list[list]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return TraceSummary(window_s=hi - lo, busy_s=busy_s,
                        busy_by_device=busy_by_device, program_s=program_s,
                        program_calls=program_calls,
                        device_ops=ranked(op_s), idle_gaps=ranked(gap_s))


def _covering(events: list[Event], t: float) -> Event | None:
    """The latest-started of `events` (sorted by start) that covers `t`;
    looks at the few that started last before `t`, which is enough for
    events of one thread."""
    lo, hi = 0, len(events)
    while lo < hi:
        mid = (lo + hi) // 2
        if events[mid].start <= t:
            lo = mid + 1
        else:
            hi = mid
    for e in reversed(events[max(0, lo - 8):lo]):
        if e.start <= t < e.end:
            return e
    return None


def _module_at(mods: list[Event], t: float) -> str:
    e = _covering(mods, t)
    return program_of(e.name) if e else "?"


def _span_at(spans: list[Event], t: float) -> str:
    e = _covering(spans, t)
    return e.name if e else "none"
