"""Milliseconds of an epoch spent on a changed CRUSH map's bucket
tables: the median, over the `update_to` roots of the traced slice, of
the time in their `mapping crush tables` (the host's build) and
`mapping crush tables upload` spans.  Nothing on a program that has no
such spans, or keeps no table of traces."""

import statistics

from perfbench.harness import span_readers

SPANS = ("mapping crush tables", "mapping crush tables upload")


def read(r):
    traces = span_readers.completed_traces()
    if traces is None or r.slice_t is None:
        return None
    lo, hi = (int(t * 1e9) for t in r.slice_t)
    per_root, seen = [], False
    for rows in traces:
        root = span_readers.root_of(rows)
        if (root is None
                or not root["event"].startswith(span_readers.EPOCH_ROOT)
                or not lo <= root["start_ns"] <= root["end_ns"] <= hi):
            continue
        spans = [row for row in rows if row.get("kind") == "span"
                 and row["event"] in SPANS and row.get("end_ns") is not None]
        seen = seen or bool(spans)
        per_root.append(sum(row["end_ns"] - row["start_ns"]
                            for row in spans) / 1e6)
    return statistics.median(per_root) if seen else None
