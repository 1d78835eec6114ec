"""Per-layer metrics that tell running from waiting on an op's critical
path, read from the same span trees as ``span_readers``' (the program's
table, ``ceph_tpu/common/tracing.py``).

A span that one thread opened and closed carries ``cpu_ns`` — what that
thread's CPU clock advanced by over the span — and ``thread``.  Its
*own* interval and CPU are the span's less those of the spans nested in
it on the same thread (its nearest descendants that carry ``cpu_ns``
with its ``thread``: a child, or the phases of an engine request that
the span's thread ran itself).  Own interval less own CPU is time the
thread spent off the processor in that span's own code.  A span marked
``wait`` or ``device_wait`` is a wait by design and is left out, so
what is summed is waiting nobody named: the interpreter lock, an
unnamed lock, a blocking call.  The program clocks one root in a few
(``tracing.cpu_clocked``); only those are read here.

The thread CPU clock may be coarse: on the benchmark's host it steps by
10 ms (and costs 6-15 us a call), so a span's ``cpu_ns`` there is 0 or
a multiple of 10 ms, right on average and wrong one by one.  Nothing is
clamped span by span, which would keep the errors of one sign only: own
times may be negative, a root's sum is what it is, and a metric is the
mean over the slice's clocked roots, floored at 0 at the end.

A message hop (``msg <Type>``) crosses threads and carries no CPU time;
its receiver stamps it instead: ``sent_us`` (the sender's last byte
written), ``framed_us`` (the receiver's reader thread had the frame
whole), microseconds from the hop's start.  The hop then has three
sections: *send* up to ``sent_us`` (send queue, framing, the writes),
*reader* from there to ``framed_us`` (kernel, the reader loop's wake,
its ``recv`` and copies), *dispatch* from there to the hop's end (the
dispatch queue, decode).  Where the reader had the frame whole before
the sender's thread got to note ``sent_us`` (it lost the interpreter
lock inside ``send()``) the send section ends at ``framed_us`` and the
reader section is empty, so the three always add up to the hop.

All over the ``osd_op`` roots that lie in the traced slice, on each
root's critical path (``span_readers.path_spans``).  An off-CPU metric
is the mean over the clocked ones.  A hop section is the mean over the
middle half of the roots by the summed length of their stamped hops
(between the quartiles; medians of parts do not add up to the median of
the whole, and the three sections are meant to be read as parts of one
hop time).  On a program whose rows lack the fields (the parent of the
PR that added them) every reader returns None.
"""

from __future__ import annotations

import json
import statistics
import sys
from functools import partial

from perfbench.harness.span_readers import (OP_ROOT, completed_traces,
                                            path_spans, root_of)

HOP = "msg "
SECTIONS = ("send", "reader", "dispatch")


def _is_wait(row: dict) -> bool:
    attrs = row.get("attrs") or {}
    return bool(attrs.get("wait") or attrs.get("device_wait"))


def _nested(row: dict, children: dict) -> list[dict]:
    """The spans nested in `row` on its thread: its nearest descendants
    that carry ``cpu_ns`` (looked for through spans that carry none, an
    engine request's ``device <kernel>``), those of its ``thread`` that
    lie inside its interval."""
    out, stack = [], list(children.get(row["span_id"], ()))
    while stack:
        c = stack.pop()
        if c.get("cpu_ns") is None:
            stack.extend(children.get(c["span_id"], ()))
        elif (c.get("thread") == row["thread"]
              and c.get("end_ns") is not None
              and row["start_ns"] <= c["start_ns"]
              and c["end_ns"] <= row["end_ns"]):
            out.append(c)
    return out


def own_offcpu_ns(row: dict, children: dict) -> int:
    """Own interval less own CPU of a span that carries ``cpu_ns``
    (negative where a coarse clock ticked inside a short span)."""
    interval = row["end_ns"] - row["start_ns"]
    cpu = row["cpu_ns"]
    for c in _nested(row, children):
        interval -= c["end_ns"] - c["start_ns"]
        cpu -= c["cpu_ns"]
    return interval - cpu


def offcpu_by_layer(rows: list[dict]) -> dict | None:
    """Nanoseconds off the CPU by layer over the trace's critical path,
    with ``root`` (the root's interval); None where no span of the
    trace carries ``cpu_ns``."""
    spans = [r for r in rows if r.get("kind") == "span"]
    if not any(r.get("cpu_ns") is not None for r in spans):
        return None
    path = path_spans(rows)
    if not path:
        return None
    children: dict[int, list[dict]] = {}
    for r in spans:
        children.setdefault(r.get("parent_span_id", 0), []).append(r)
    root = path[0][0]
    out = {"root": root["end_ns"] - root["start_ns"]}
    for r, _depth in path[1:]:
        if (r.get("cpu_ns") is None or r.get("end_ns") is None
                or _is_wait(r)):
            continue
        layer = r.get("layer") or "unnamed"
        out[layer] = out.get(layer, 0) + own_offcpu_ns(r, children)
    return out


def hop_sections(row: dict) -> tuple[int, int, int] | None:
    """(send, reader, dispatch) nanoseconds of a stamped hop span; None
    where the receiver left no stamp."""
    attrs = row.get("attrs") or {}
    if ("framed_us" not in attrs or "sent_us" not in attrs
            or row.get("end_ns") is None):
        return None
    length = row["end_ns"] - row["start_ns"]
    framed = min(attrs["framed_us"] * 1000, length)
    sent = min(attrs["sent_us"] * 1000, framed)
    return sent, framed - sent, length - framed


def hops_of_path(rows: list[dict]) -> dict | None:
    """Nanoseconds of the critical path's stamped hops by section, with
    ``hops`` (their summed length); None where the trace has no stamped
    hop at all."""
    if not any(r.get("kind") == "span" and r["event"].startswith(HOP)
               and "framed_us" in (r.get("attrs") or {}) for r in rows):
        return None
    out = dict.fromkeys(SECTIONS + ("hops",), 0)
    for r, _depth in path_spans(rows)[1:]:
        parts = hop_sections(r) if r["event"].startswith(HOP) else None
        if parts is not None:
            for key, ns in zip(SECTIONS, parts):
                out[key] += ns
            out["hops"] += sum(parts)
    return out


class SliceOffCpu:
    """The slice's ``osd_op`` roots, each split by `offcpu_by_layer` and
    `hops_of_path`."""

    def __init__(self, r):
        self.offcpu: list[dict] = []
        self.hops: list[dict] = []
        traces = completed_traces()
        if traces is None or r.slice_t is None:
            return
        lo, hi = (int(t * 1e9) for t in r.slice_t)
        # the roots that lie in the slice; where none of them is
        # clocked (a slice of a handful of ops: the session's first
        # root, which always is, began as the profiler started, a
        # moment before the slice's first stamp) those that reach into
        # it stand in
        inside, across = [], []
        for rows in traces:
            root = root_of(rows)
            if (root is None or not root["event"].startswith(OP_ROOT)
                    or root["end_ns"] < lo or root["start_ns"] > hi):
                continue
            parts = (offcpu_by_layer(rows), hops_of_path(rows))
            across.append(parts)
            if lo <= root["start_ns"] and root["end_ns"] <= hi:
                inside.append(parts)
        for i, into in enumerate((self.offcpu, self.hops)):
            into.extend(p[i] for p in inside if p[i] is not None)
            if not into:
                into.extend(p[i] for p in across if p[i] is not None)
        self.note()

    def offcpu_ms(self, layer: str):
        return (max(0.0, statistics.fmean(p.get(layer, 0)
                                          for p in self.offcpu)) / 1e6
                if self.offcpu else None)

    def offcpu_share(self):
        """All layers' off-CPU time over the roots' intervals: the
        ratio of the two means."""
        root = sum(p["root"] for p in self.offcpu)
        off = sum(v for p in self.offcpu for k, v in p.items()
                  if k != "root")
        return min(100.0, max(0.0, 100.0 * off / root)) if root else None

    def middle_half(self) -> list[dict]:
        """The roots between the quartiles of stamped-hop length (all
        of fewer than four)."""
        by_length = sorted(self.hops, key=lambda p: p["hops"])
        q = len(by_length) // 4
        return by_length[q:len(by_length) - q]

    def hop_ms(self, section: str):
        mid = self.middle_half()
        return (sum(p[section] for p in mid) / len(mid) / 1e6
                if mid else None)

    def note(self) -> None:
        """One line for the builder, on standard error: what was read,
        and that the hop's sections add up to the hops."""
        def mean(dicts, key):
            return sum(p.get(key, 0) for p in dicts) / len(dicts) / 1e6

        layers = sorted({k for p in self.offcpu for k in p} - {"root"})
        mid = self.middle_half()
        facts = {"roots_with_cpu": len(self.offcpu),
                 "roots_with_stamped_hops": len(self.hops),
                 "root_ms_mean": (mean(self.offcpu, "root")
                                  if self.offcpu else None),
                 "offcpu_ms_mean": {k: mean(self.offcpu, k)
                                    for k in layers},
                 "hop_ms_middle_half": {k: mean(mid, k)
                                        for k in SECTIONS + ("hops",)}
                 if mid else {},
                 "hops_ms_p50": (statistics.median(
                     p["hops"] for p in self.hops) / 1e6
                     if self.hops else None)}
        print("offcpu_readers", json.dumps(facts, sort_keys=True),
              file=sys.stderr, flush=True)


def offcpu_of(r) -> SliceOffCpu:
    """The run's `SliceOffCpu`, made once and kept on the reading."""
    got = getattr(r, "_slice_offcpu", None)
    if got is None:
        got = SliceOffCpu(r)
        r._slice_offcpu = got
    return got


# -- the readers -----------------------------------------------------------

def offcpu_ms(r, layer: str):
    return offcpu_of(r).offcpu_ms(layer)


def offcpu_share(r):
    return offcpu_of(r).offcpu_share()


def hop_ms(r, section: str):
    return offcpu_of(r).hop_ms(section)


ecb_offcpu_ms = partial(offcpu_ms, layer="PG / EC backend")
engine_offcpu_ms = partial(offcpu_ms, layer="dispatch engine")
store_offcpu_ms = partial(offcpu_ms, layer="objectstore")
hop_send_ms = partial(hop_ms, section="send")
hop_reader_ms = partial(hop_ms, section="reader")
hop_dispatch_ms = partial(hop_ms, section="dispatch")
