"""Median over the slice's acknowledged overwrites of the time their
critical path spends in `ec rmw gather`: from the first ranged sub-read
to the k-th chunk in, the wait an overwrite on a pool with
allow_ec_overwrites pays before it can re-encode its stripes.  A root
whose path holds no such span counts 0; a program without the span (or
without span tables) gives nothing."""
from perfbench.harness import span_readers as sr

GATHER = "ec rmw gather"


def read(r):
    traces = sr.completed_traces()
    if traces is None or r.slice_t is None:
        return None
    t_a, t_b = r.slice_t
    lo_s, hi_s = int(t_a * 1e9), int(t_b * 1e9)
    roots = sorted(((root, rows) for rows in traces
                    for root in [sr.root_of(rows)]
                    if root is not None
                    and root["event"].startswith(sr.OP_ROOT)
                    and lo_s <= root["start_ns"] <= hi_s),
                   key=lambda x: x[0]["start_ns"])
    ms, seen = [], False
    for a in r.log.acks:
        if not (a.ok and t_a <= a.t_submit and a.t_ack <= t_b):
            continue
        lo, hi = int(a.t_submit * 1e9), int(a.t_ack * 1e9)
        # as span_readers matches them: the root opened after the
        # submit that closed after the ack
        hit = next((i for i, (root, _rows) in enumerate(roots)
                    if lo <= root["start_ns"] <= hi <= root["end_ns"]),
                   None)
        if hit is None:
            continue
        _root, rows = roots.pop(hit)
        got = 0
        for span, _depth in sr.path_spans(rows):
            if span["event"] == GATHER and span.get("end_ns") is not None:
                seen = True
                got += (min(span["end_ns"], hi_s)
                        - max(span["start_ns"], lo_s))
        ms.append(max(0, got) / 1e6)
    return sr._median(ms) if seen else None
