"""Median over the slice's reads that rebuilt of the time from the start
of their `ec decode submit` to the end of their `ec decode continuation`:
the submission, the decode engine's request (`device <kernel>`, queue
wait to delivery), the hand-over to an op-queue worker and the overlay
of the rebuilt rows.  A read at depth 1 waits for all of it, so it lies
on the op's critical path.  Roots without a submission (no data chunk
lost) are left out; a program without the spans gives nothing."""
from perfbench.harness import span_readers as sr

SUBMIT = "ec decode submit"
CONTINUATION = "ec decode continuation"


def read(r):
    traces = sr.completed_traces()
    if traces is None or r.slice_t is None:
        return None
    lo, hi = (int(t * 1e9) for t in r.slice_t)
    ms = []
    for rows in traces:
        root = sr.root_of(rows)
        if (root is None or not root["event"].startswith(sr.OP_ROOT)
                or not lo <= root["start_ns"] <= root["end_ns"] <= hi):
            continue
        starts = [s["start_ns"] for s in rows if s.get("event") == SUBMIT]
        ends = [s["end_ns"] for s in rows
                if s.get("event") == CONTINUATION
                and s.get("end_ns") is not None]
        if starts and ends:
            ms.append((max(ends) - min(starts)) / 1e6)
    return sr._median(ms) if ms else None
