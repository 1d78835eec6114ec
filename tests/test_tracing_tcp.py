"""Cross-daemon trace propagation over the REAL TCP messengers (not
loopback): a traced EC write must yield ONE stitched span tree whose
shard sub-spans parent (transitively) under the primary's dispatch
span, with device spans attached where the encode ran."""

from __future__ import annotations

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.tools.vstart import MiniCluster


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.reset()


def _ancestor_ids(spans: dict, row: dict) -> set:
    out = set()
    cur = row
    while cur["parent_span_id"] and cur["parent_span_id"] in spans:
        cur = spans[cur["parent_span_id"]]
        out.add(cur["span_id"])
    return out


def test_ec_write_stitches_one_span_tree_over_tcp():
    c = MiniCluster(n_osds=4, ms_type="async").start()
    try:
        c.wait_for_osd_count(4)
        client = c.client(timeout=20.0)
        pool = c.create_pool(client, pg_num=1, pool_type="erasure",
                             k=2, m=1)
        io = client.open_ioctx(pool)
        io.write_full("warm", b"w" * 4096)     # peering settled

        with tracing.trace_ctx(name="ec write", daemon="client") as tid:
            io.write_full("traced-tcp", b"T" * 8192)

        rows = tracing.dump(tid)
        assert rows, "no span rows recorded"
        spans = {r["span_id"]: r for r in rows if r["kind"] == "span"}

        # ONE tree: a single root (the client's trace_ctx span), and
        # every other span's parent resolves inside the trace
        roots = [r for r in spans.values() if not r["parent_span_id"]]
        assert len(roots) == 1 and roots[0]["event"] == "ec write", roots
        for r in spans.values():
            if r["parent_span_id"]:
                assert r["parent_span_id"] in spans, \
                    f"orphan span {r} — tree is torn"

        # the tree spans client + >= k+m osd daemons
        daemons = {r["daemon"] for r in spans.values()}
        assert any(d.startswith("client.") for d in daemons), daemons
        assert len({d for d in daemons if d.startswith("osd.")}) >= 3

        # the primary's rx dispatch span for the client op...
        rx_op = [r for r in spans.values()
                 if r["event"] == "rx MOSDOp"
                 and r["daemon"].startswith("osd.")]
        assert rx_op, "no primary dispatch span"
        prim_ids = {r["span_id"] for r in rx_op}

        # ...is an ancestor of every shard sub-op dispatch span
        shard_rx = [r for r in spans.values()
                    if r["event"] == "rx MOSDECSubOpWrite"]
        assert len(shard_rx) >= 2, spans
        for r in shard_rx:
            assert _ancestor_ids(spans, r) & prim_ids, \
                f"shard span {r} not under the primary's dispatch"

        # engine request span attached under the primary with the h2d
        # bytes and the retrace attribute, its compute phase a child
        dev = [r for r in spans.values()
               if r["event"] == "device ec_encode"]
        assert dev, "no device span on the traced write"
        assert _ancestor_ids(spans, dev[0]) & prim_ids
        assert "retrace" in dev[0]["attrs"]
        assert dev[0]["attrs"]["h2d_bytes"] > 0
        kids = [r["event"] for r in spans.values()
                if r["parent_span_id"] == dev[0]["span_id"]]
        assert "engine compute" in kids, kids

        # objectstore commit spans sit inside the tree too
        assert any(r["event"] == "objectstore commit"
                   for r in spans.values()), daemons

        # the client's rx of the reply closes the round trip after the
        # first osd rx of the op
        t_op = min(r["t"] for r in rows if r["event"] == "rx MOSDOp")
        t_reply = max(r["t"] for r in rows
                      if "rx MOSDOpReply" in r["event"])
        assert t_reply >= t_op

        # an untraced op afterwards records nothing into this trace
        io.write_full("untraced", b"u")
        assert len(tracing.dump(tid)) == len(rows)
    finally:
        c.stop()
