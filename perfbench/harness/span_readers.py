"""Per-layer metrics read from the program's own span trees
(``ceph_tpu/common/tracing.py``): where on the host an op's, or a map
epoch's, time went.

While the traced slice's profiler session is live the program traces
every client op (root ``osd_op <oid>``, opened in ``aio_operate``) and
every ``update_to`` (root ``update_to``), on ``time.perf_counter_ns`` —
the clock of the harness's log and of ``Reading.slice_t``.  The readers
here take the completed traces out of the program's table when a metric
is read, keep the roots that lie inside the slice, and split each
root's interval over the layers of its *critical path*.

The critical path of a trace is found from the root down: of a span's
children the one whose subtree ends last is on it (of a fan-out, the
slowest branch); before that child began, the child whose whole
subtree had ended by then and that began last (the stage before it);
and so on back to the span's own start.  A branch any part of which
was still running when the chosen one began did not gate it and is
left out — so the program parents an asynchronous piece of work (an
engine request, a message hop) under the span that waits for it.
Every instant of the root's interval then belongs to the deepest span
of the path that is open at it, and to ``unnamed`` where only the root
is.
Each span carries the benchmark's name of its layer (``layer`` on its
row); a span marked ``device_wait`` is a host-side wait for a device
result.

A program that has no such table (the parent of the PR that added
these readers) gives nothing to read: every reader returns None.
"""

from __future__ import annotations

import json
import statistics
import sys
from functools import partial

UNNAMED = "unnamed"
ROOT = "root"
KERNELS = "kernels"
OP_ROOT = "osd_op "
EPOCH_ROOT = "update_to"
COMMIT = "bluestore commit"
FSYNC = "bluestore fsync"


# -- the critical path ----------------------------------------------------------

def by_layer(row: dict) -> str:
    """An op's categories: the span's layer."""
    return row.get("layer") or UNNAMED


def by_layer_and_wait(row: dict) -> str:
    """An epoch's categories: ``kernels`` for a host-side wait on a
    device result, whichever layer's code waits; else the layer."""
    if (row.get("attrs") or {}).get("device_wait"):
        return KERNELS
    return row.get("layer") or UNNAMED


def root_of(rows: list[dict]) -> dict | None:
    """The trace's root span row (no parent among the rows' spans; the
    earliest if several), or None if it has not ended."""
    spans = [r for r in rows if r.get("kind") == "span"]
    ids = {r["span_id"] for r in spans}
    tops = [r for r in spans if r.get("parent_span_id", 0) not in ids]
    if not tops:
        return None
    top = min(tops, key=lambda r: r["start_ns"])
    return top if top.get("end_ns") is not None else None


def path_spans(rows: list[dict]) -> list[tuple[dict, int]]:
    """The spans of the trace's critical path, root first, each with
    its depth below the root."""
    root = root_of(rows)
    if root is None:
        return []
    spans = [r for r in rows if r.get("kind") == "span"]
    end = {r["span_id"]: (r["end_ns"] if r.get("end_ns") is not None
                          else r["start_ns"]) for r in spans}
    children: dict[int, list[dict]] = {}
    for r in spans:
        if r is not root:
            children.setdefault(r.get("parent_span_id", 0), []).append(r)

    subtree_end: dict[int, int] = {}

    def settle(r: dict) -> int:
        t = end[r["span_id"]]
        for c in children.get(r["span_id"], ()):
            t = max(t, settle(c))
        subtree_end[r["span_id"]] = t
        return t

    settle(root)
    path: list[tuple[dict, int]] = []

    def walk(r: dict, depth: int) -> None:
        path.append((r, depth))
        t = subtree_end[r["span_id"]]
        first = True
        left = list(children.get(r["span_id"], ()))
        while left:
            # the first choice ends the subtree (it is what the span
            # waited for); an earlier stage has to have ended, with
            # all it started, before the chosen one began
            fit = [c for c in left if c["start_ns"] < t
                   and (first or subtree_end[c["span_id"]] <= t)]
            if not fit:
                break
            pick = max(fit, key=lambda c: (subtree_end[c["span_id"]],
                                           c["start_ns"]))
            walk(pick, depth + 1)
            left.remove(pick)
            t = pick["start_ns"]
            first = False

    walk(root, 0)
    return path


def critical_path(rows: list[dict], category=by_layer,
                  clip: tuple[int, int] | None = None) -> dict | None:
    """Nanoseconds of the root's interval (clipped to `clip`) by
    category, with ``unnamed`` (no span of the path open but the root)
    and ``root`` (the whole interval).  None without a finished root."""
    path = path_spans(rows)
    if not path:
        return None
    root = path[0][0]
    lo, hi = root["start_ns"], root["end_ns"]
    if clip is not None:
        lo, hi = max(lo, clip[0]), min(hi, clip[1])
    out = {ROOT: max(0, hi - lo), UNNAMED: 0}
    if hi <= lo:
        return out
    named = []
    for r, depth in path[1:]:
        a = max(r["start_ns"], lo)
        b = min(r["end_ns"] if r.get("end_ns") is not None
                else r["start_ns"], hi)
        if b > a:
            named.append((a, b, depth, category(r)))
    cuts = sorted({lo, hi} | {t for a, b, _d, _c in named for t in (a, b)})
    for a, b in zip(cuts, cuts[1:]):
        open_here = [(d, c) for sa, sb, d, c in named if sa <= a and b <= sb]
        cat = max(open_here)[1] if open_here else UNNAMED
        out[cat] = out.get(cat, 0) + (b - a)
    return out


# -- the program's table ----------------------------------------------------------

def completed_traces() -> list[list[dict]] | None:
    """Rows of every completed trace the program still holds, or None
    where the program keeps no such table."""
    try:
        from ceph_tpu.common import tracing
        fetch = tracing.completed_traces
    except Exception:
        return None
    try:
        return fetch()
    except Exception:
        return None


def _ns(t: float) -> int:
    return int(t * 1e9)


def _median(values):
    return statistics.median(values) if values else None


class SliceSpans:
    """The slice's roots, each matched to the log's entry it served,
    with its critical path split by category."""

    def __init__(self, r):
        self.paths: list[dict] = []     # matched roots' splits, ns
        self.unmatched = 0              # log entries without a root
        self.kind = ""
        self.fsync_ns = self.commit_ns = 0
        traces = completed_traces()
        self.readable = traces is not None and r.slice_t is not None
        if not self.readable:
            return
        t_a, t_b = r.slice_t
        clip = (_ns(t_a), _ns(t_b))
        if hasattr(r.log, "acks"):
            self.kind = "op"
            want = [(_ns(a.t_submit), _ns(a.t_ack)) for a in r.log.acks
                    if a.ok and t_a <= a.t_submit and a.t_ack <= t_b]
            prefix, category = OP_ROOT, by_layer
        else:
            self.kind = "epoch"
            want = [(_ns(e.t_start), _ns(e.t_end)) for e in r.log.epochs
                    if t_a <= e.t_start and e.t_end <= t_b]
            prefix, category = EPOCH_ROOT, by_layer_and_wait
        roots = []
        for rows in traces:
            root = root_of(rows)
            if (root is not None and root["event"].startswith(prefix)
                    and clip[0] <= root["start_ns"]
                    and root["start_ns"] <= clip[1]):
                roots.append((root, rows))
        roots.sort(key=lambda x: x[0]["start_ns"])
        for lo, hi in sorted(want):
            # an op's root opens after the generator's submit stamp
            # and closes after its ack stamp; an epoch's root lies
            # inside the generator's two stamps
            if self.kind == "op":
                hit = next((i for i, (root, _rows) in enumerate(roots)
                            if lo <= root["start_ns"] <= hi
                            <= root["end_ns"]), None)
            else:
                hit = next((i for i, (root, _rows) in enumerate(roots)
                            if lo <= root["start_ns"]
                            and root["end_ns"] <= hi), None)
            if hit is None:
                self.unmatched += 1
                continue
            _root, rows = roots.pop(hit)
            self.paths.append(critical_path(rows, category, clip))
            for row in rows:
                if row.get("kind") != "span" or row.get("end_ns") is None:
                    continue
                dur = (min(row["end_ns"], clip[1])
                       - max(row["start_ns"], clip[0]))
                if dur <= 0:
                    continue
                if row["event"] == COMMIT:
                    self.commit_ns += dur
                elif row["event"] == FSYNC:
                    self.fsync_ns += dur
        self.note()

    def path_ms(self, category: str):
        """Median over the slice's traced roots of the critical-path
        time in `category`."""
        if not self.paths:
            return None
        return _median([p.get(category, 0) for p in self.paths]) / 1e6

    def named_share(self):
        """Median over the slice's log entries of the share of the
        root's interval that some span of the path names; an entry
        without a complete trace counts as 0."""
        if not self.readable or not (self.paths or self.unmatched):
            return None
        shares = [100.0 * (1.0 - p[UNNAMED] / p[ROOT]) if p[ROOT] else 0.0
                  for p in self.paths] + [0.0] * self.unmatched
        return _median(shares)

    def note(self) -> None:
        """One line for the builder, on standard error: what was
        matched, and that the parts add up."""
        cats = sorted({c for p in self.paths for c in p} - {ROOT})
        facts = {"kind": self.kind, "roots_in_slice": len(self.paths),
                 "log_entries_without_root": self.unmatched,
                 "root_ms_p50": self.path_ms(ROOT),
                 "by_category_ms_p50": {c: self.path_ms(c) for c in cats},
                 "by_category_ms_mean": {
                     c: sum(p.get(c, 0) for p in self.paths)
                     / len(self.paths) / 1e6 for c in cats}
                 if self.paths else {},
                 "root_ms_mean": (sum(p[ROOT] for p in self.paths)
                                  / len(self.paths) / 1e6
                                  if self.paths else None)}
        print("span_readers", json.dumps(facts, sort_keys=True),
              file=sys.stderr, flush=True)


def spans_of(r) -> SliceSpans:
    """The run's `SliceSpans`, made once and kept on the reading."""
    got = getattr(r, "_slice_spans", None)
    if got is None:
        got = SliceSpans(r)
        r._slice_spans = got
    return got


# -- the readers ------------------------------------------------------------------

def path_ms(r, category: str):
    return spans_of(r).path_ms(category)


def named_share(r):
    return spans_of(r).named_share()


def fsync_share(r):
    """Percent of the slice's `bluestore commit` span time that lies in
    its `bluestore fsync` spans (block file and KV log)."""
    s = spans_of(r)
    return 100.0 * s.fsync_ns / s.commit_ns if s.commit_ns else None


client_path_ms = partial(path_ms, category="client")
msgr_path_ms = partial(path_ms, category="messenger")
opq_path_ms = partial(path_ms, category="OSD op queue")
ecb_path_ms = partial(path_ms, category="PG / EC backend")
engine_path_ms = partial(path_ms, category="dispatch engine")
store_path_ms = partial(path_ms, category="objectstore")
mapping_path_ms = partial(path_ms, category="mapping service")
kernels_wait_ms = partial(path_ms, category=KERNELS)
