"""The program's span trees where the traffic is: ``aio_write_full`` on
an EC pool, ``aio_read`` of an object that lost a shard and one
``update_to`` epoch give one complete tree each, with a span of every
layer on it and a critical path that is named end to end; a live
profiler session arms the roots and receives the same-thread spans as
annotations; unarmed, nothing is recorded and no clock is read.  Spans
tell running from waiting: a same-thread span and an engine phase carry
their thread's CPU time, a hop its receiver's stamps."""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import tracing

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from perfbench.harness import span_readers  # noqa: E402

OP_LAYERS = {tracing.LAYER_CLIENT, tracing.LAYER_MSGR, tracing.LAYER_OPQ,
             tracing.LAYER_ECB, tracing.LAYER_ENGINE, tracing.LAYER_STORE}
#: the share of a root's interval that no span of the critical path
#: names, at toy size on a shared CPU: what is left are thread
#: hand-overs between one span's end and the next one's start
UNNAMED_MAX = 0.10


@pytest.fixture(autouse=True)
def _clean_tracing(monkeypatch):
    """Every trace CPU-clocked, where the program clocks one root in
    four or sixteen (`test_one_root_in_a_few_reads_the_cpu_clock` runs
    it as it is)."""
    monkeypatch.setattr(tracing, "CPU_CLOCKED_ONE_IN", 1)
    monkeypatch.setattr(tracing, "_SAMPLED_THIN", 1)
    tracing.reset()
    yield
    tracing.reset()


@pytest.fixture(scope="module")
def ec_cluster(tmp_path_factory):
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=6, store_type="bluestore", ms_type="async",
                    base_path=str(tmp_path_factory.mktemp("stores"))
                    ).start()
    try:
        c.wait_for_osd_count(6)
        client = c.client(timeout=30.0)
        pool = c.create_pool(client, pool_type="erasure", k=4, m=2,
                             pg_num=8)
        io = client.open_ioctx(pool)
        for i in range(8):      # connections up, programs compiled
            io.write_full(f"warm-{i}", b"w" * 4096)
        yield c, io
    finally:
        c.stop()


def _op_traces():
    return [rows for rows in tracing.completed_traces()
            if span_readers.root_of(rows)["event"].startswith("osd_op ")]


def _write(io, names, size=4096):
    done = [io.aio_write_full(n, os.urandom(size)) for n in names]
    for c in done:
        assert c.wait_for_complete(30.0) and c.get_return_value() == 0
    # the op is complete when its reply wakes the client; the primary's
    # thread that sent the reply closes its own spans a moment later
    time.sleep(0.2)


def _phases(req, spans):
    """The engine phases under request span `req`, held to the ledger's
    order: every phase that had a length, so no ``queue_wait`` on the
    caller's own thread and ``place`` only where a mesh placed the
    batch (the tests' engines have one: eight virtual devices)."""
    phases = [r for r in spans if r["parent_span_id"] == req["span_id"]]
    names = [r["event"] for r in phases]
    want = [f"engine {p}" for p in tracing.ENGINE_PHASES
            if not (p == "queue_wait" and req["attrs"]["caller_thread"])]
    assert [n for n in names if n != "engine place"] \
        == [n for n in want if n != "engine place"], names
    assert all(r["end_ns"] > r["start_ns"] for r in phases[:-1]), phases
    return phases


def _hops_are_stamped(rows) -> int:
    """Every hop of an in-process trace carries its receiver's stamps
    beside the sender's, in order inside the hop; returns how many."""
    hops = [r for r in rows if r["kind"] == "span"
            and r["event"].startswith("msg ")]
    for r in hops:
        a = r["attrs"]
        assert {"sent_us", "road", "first_byte_us", "framed_us",
                "dequeued_us"} <= set(a), (r["event"], a)
        assert 0 <= a["first_byte_us"] <= a["framed_us"] \
            <= a["dequeued_us"] <= (r["end_ns"] - r["start_ns"]) // 1000, \
            (r["event"], a, r["end_ns"] - r["start_ns"])
        assert "cpu_ns" not in r and "thread" not in r
    return len(hops)


def test_aio_write_gives_one_complete_tree_per_op(ec_cluster):
    _c, io = ec_cluster
    tracing.set_sample_rate(1.0)
    names = [f"traced-{i}" for i in range(6)]
    _write(io, names)
    tracing.set_sample_rate(0.0)
    traces = _op_traces()
    assert sorted(span_readers.root_of(t)["event"] for t in traces) \
        == sorted(f"osd_op {n}" for n in names)
    for rows in traces:
        spans = [r for r in rows if r["kind"] == "span"]
        ids = {r["span_id"] for r in spans}
        root = span_readers.root_of(rows)
        assert root["layer"] == "" and root["end_ns"] is not None
        # ONE tree: every other span's parent is a span of the trace
        assert all(r["parent_span_id"] in ids
                   for r in spans if r is not root)
        assert all(r["end_ns"] is not None for r in spans), \
            [r["event"] for r in spans if r["end_ns"] is None]
        names_here = {r["event"] for r in spans}
        # every boundary of the write path has its span
        for want in ("client submit", "client complete", "msg MOSDOp",
                     "rx MOSDOp", "opq wait", "osd op", "ec prepare",
                     "ec encode submit", "device ec_encode",
                     "ec continuation", "ec daemon lock wait",
                     "ec local commit", "ec fan-out",
                     "msg MOSDECSubOpWrite", "ec sub-write",
                     "ec sub-write ack", "osd reply", "msg MOSDOpReply",
                     "bluestore commit", "bluestore apply",
                     "bluestore csum settle", "bluestore fsync",
                     "bluestore kv commit"):
            assert want in names_here, (want, sorted(names_here))
        for phase in tracing.ENGINE_PHASES:
            assert f"engine {phase}" in names_here
        # op, five sub-writes and their acks, reply: each hop split by
        # the thread that read it
        assert _hops_are_stamped(rows) == 12
        # a span one thread opened and closed says what it ran of it;
        # roots, hops, queue waits and engine requests cross threads
        for r in spans:
            same_thread = not (r is root or r["event"].startswith(
                ("msg ", "opq ", "device ")) or r["event"]
                == "engine queue_wait")
            assert ("cpu_ns" in r) == ("thread" in r) == same_thread, r
            if same_thread:
                assert 0 <= r["cpu_ns"] and r["thread"]
        for name in ("ec daemon lock wait", "bluestore fsync"):
            assert all(r["attrs"].get("wait") is True for r in spans
                       if r["event"] == name)
        # five remote shards, each with its queue wait and its commit
        assert sum(r["event"] == "ec sub-write" for r in spans) == 5
        assert sum(r["event"] == "bluestore commit" for r in spans) == 6
        q = [r for r in spans if r["event"] == "opq wait"]
        assert len(q) == 6
        assert all({"klass", "phase"} <= set(r["attrs"]) for r in q)
        # what each commit moved to the block file, and in how many
        # runs: one block a shard here
        applies = [r["attrs"] for r in spans
                   if r["event"] == "bluestore apply"]
        assert len(applies) == 6
        assert all(a["blocks"] == a["runs"] == 1 for a in applies), applies
        # the critical path holds a span of every layer and is named
        path = span_readers.critical_path(rows)
        assert OP_LAYERS <= set(path), path
        assert path["unnamed"] <= UNNAMED_MAX * path["root"], path
        assert sum(v for k, v in path.items() if k != "root") \
            == path["root"]
        on_path = {r["event"] for r, _d in span_readers.path_spans(rows)}
        # of the fan-out only the slowest branch is on the path
        assert sum(r["event"] == "ec sub-write"
                   for r, _d in span_readers.path_spans(rows)) == 1
        assert {"client submit", "engine deliver", "ec continuation",
                "ec fan-out", "client complete"} <= on_path


def test_one_root_in_a_few_reads_the_cpu_clock(ec_cluster, monkeypatch):
    """The CPU clock is a system call: where the sample rate arms the
    roots the first and every sixteenth after it are clocked, under a
    profiler session every fourth, whole — every daemon reads the
    choice off the trace id — and the spans of the others carry no CPU
    time at all, their hops' stamps all the same."""
    _c, io = ec_cluster
    monkeypatch.undo()
    assert (tracing.CPU_CLOCKED_ONE_IN, tracing._SAMPLED_THIN) == (4, 4)
    reads = []
    real = tracing.thread_cpu_ns
    monkeypatch.setattr(tracing, "thread_cpu_ns",
                        lambda: reads.append(1) or real())

    def clocked_roots(n: int) -> list[int]:
        tracing.reset()
        tracing.set_sample_rate(1.0)
        _write(io, [f"clocked-{i}" for i in range(n)])  # roots in order
        tracing.set_sample_rate(0.0)
        traces = sorted(_op_traces(), key=lambda rows: span_readers.root_of(
            rows)["start_ns"])
        assert len(traces) == n
        out = []
        for i, rows in enumerate(traces):
            spans = [r for r in rows if r["kind"] == "span"]
            clocked = [r for r in spans if "cpu_ns" in r]
            assert bool(clocked) == tracing.cpu_clocked(rows[0]["trace_id"])
            if clocked:
                out.append(i)
                assert {r["event"] for r in spans} - {
                    r["event"] for r in clocked} == {
                        span_readers.root_of(rows)["event"], "msg MOSDOp",
                        "msg MOSDECSubOpWrite", "msg MOSDECSubOpWriteReply",
                        "msg MOSDOpReply", "opq wait", "device ec_encode",
                        "engine queue_wait"}
            assert _hops_are_stamped(rows) == 12
        # adjacent readings are shared: under two calls a clocked span
        n_clocked = sum("cpu_ns" in r for rows in traces for r in rows)
        assert 0 < len(reads) < 2 * n_clocked
        del reads[:]
        return out

    assert clocked_roots(18) == [0, 16]
    # as under a live session (no profile is taken here)
    monkeypatch.setattr(tracing, "_profiler_on", lambda: True)
    assert clocked_roots(9) == [0, 4, 8]


def test_engine_phases_are_child_spans_with_their_intervals(ec_cluster):
    _c, io = ec_cluster
    tracing.set_sample_rate(1.0)
    _write(io, ["phased"])
    tracing.set_sample_rate(0.0)
    (rows,) = _op_traces()
    spans = [r for r in rows if r["kind"] == "span"]
    req = next(r for r in spans if r["event"] == "device ec_encode")
    assert not req["attrs"]["caller_thread"]
    phases = _phases(req, spans)
    # gapless, in order, inside the request: a reader can lay them on
    # a timeline and subtract them from their parent
    assert phases[0]["start_ns"] == req["start_ns"]
    for a, b in zip(phases, phases[1:]):
        assert a["end_ns"] == b["start_ns"]
    assert phases[-1]["end_ns"] <= req["end_ns"]
    by_name = {r["event"][len("engine "):]: r for r in phases}
    assert by_name["compute"]["attrs"] == {"device_wait": True}
    # each phase carries the CPU time of the engine thread that ran it
    # (the dispatch thread's up to the launch, the completion thread's
    # after); the wait in the queue crosses threads and carries none
    assert "cpu_ns" not in by_name["queue_wait"]
    for r in phases[1:]:
        assert 0 <= r["cpu_ns"] <= r["end_ns"] - r["start_ns"] + 1_000_000, r
    launcher = {by_name[p]["thread"] for p in ("build", "launch")}
    completer = {by_name[p]["thread"]
                 for p in ("compute", "materialize", "deliver")}
    assert len(launcher) == len(completer) == 1 and launcher != completer
    assert "thread" not in req and "cpu_ns" not in req
    assert {"h2d_bytes", "d2h_bytes", "retrace", "batch"} \
        <= set(req["attrs"])
    # no duration is formatted into a name any more
    assert not [r["event"] for r in rows
                if r["event"].rstrip().endswith("ms")
                and r["kind"] == "event" and "kernel" not in r["event"]]


def test_unarmed_aio_writes_record_nothing(ec_cluster, monkeypatch):
    _c, io = ec_cluster
    made = []

    class Counting(tracing.Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "Span", Counting)
    assert not tracing.armed()
    _write(io, [f"quiet-{i}" for i in range(36)])
    assert tracing.trace_ids() == [] and made == []
    # armed, the same writes do allocate: the count above is no accident
    tracing.set_sample_rate(1.0)
    _write(io, ["loud"])
    assert made and len(tracing.trace_ids()) == 1


def test_unarmed_threads_read_no_clock(ec_cluster, degraded_cluster,
                                       monkeypatch):
    """Neither the threads' CPU clock nor the reader threads' stamps
    are read while nothing is armed: a site that did would take its
    messenger's loop or its op down with it."""
    _c, io = ec_cluster
    _dc, dio, blobs, _lost = degraded_cluster
    read = []

    def clock(which):
        def boom():
            read.append((which, threading.current_thread().name))
            raise AssertionError(f"{which} read on an unarmed path")
        return boom

    assert not tracing.armed()
    monkeypatch.setattr(tracing, "thread_cpu_ns", clock("thread_cpu_ns"))
    monkeypatch.setattr(tracing, "now_ns", clock("now_ns"))
    _write(io, [f"clockless-{i}" for i in range(12)])
    _write(io, ["clockless-wide"], size=4 * 8 * 4096)
    _read(dio, blobs)
    assert read == [] and tracing.trace_ids() == []


def test_same_thread_span_tells_running_from_waiting():
    with tracing.trace_ctx(name="t", daemon="test") as tid:
        with tracing.span("asleep", "test"):
            time.sleep(0.05)
        with tracing.span("spinning", "test", wait=True) as outer:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.05:
                pass
            # a span that another thread closes has no thread to ask
            crossing = tracing.begin_span("crossing", "test")
            closer = threading.Thread(target=tracing.finish_span,
                                      args=(crossing,))
            closer.start()
            closer.join(10.0)
            assert not closer.is_alive()
        tracing.add_span("phase", "test", tid, outer.span_id,
                         outer.start, outer.end, cpu_ns=7, thread=11)
    rows = {r["event"]: r for r in tracing.dump(tid)}
    asleep, spinning = rows["asleep"], rows["spinning"]
    assert asleep["end_ns"] - asleep["start_ns"] >= 50_000_000
    assert asleep["cpu_ns"] < 10_000_000
    # near its interval: all of it but what other threads of this
    # process took of a shared CPU meanwhile
    assert spinning["cpu_ns"] > 25_000_000
    assert spinning["cpu_ns"] <= spinning["end_ns"] - spinning["start_ns"]
    assert asleep["thread"] == spinning["thread"] == threading.get_ident()
    assert spinning["attrs"] == {"wait": True} and "attrs" not in asleep
    assert (rows["phase"]["cpu_ns"], rows["phase"]["thread"]) == (7, 11)
    for name in ("t", "crossing"):
        assert rows[name]["end_ns"] is not None
        assert "cpu_ns" not in rows[name] and "thread" not in rows[name]
    # a name no site opens has no layer
    assert tracing.layer_of("rep op") == ""


@pytest.fixture(scope="module")
def mapping_service():
    """A two-level map whose pool is large enough for device CRUSH and
    the fused ladder (osdmap_mapping_min_pgs), as perfbench stands it
    up, at toy size."""
    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.crush import build_two_level_map
    from ceph_tpu.osd import OSDMap, PGPool
    crush, _root, rid = build_two_level_map(8, 4)
    m = OSDMap(crush=crush, epoch=2)
    m.set_max_osd(32)
    for o in range(32):
        m.osd_state[o] = 3
        m.osd_weight[o] = 0x10000
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rid, pg_num=2048)
    ctx = CephTpuContext("tracing-paths-map")
    svc = ctx.mapping_service()
    assert svc.update_to(m).full
    state = {"map": m}

    def epoch(weight: int):
        new = state["map"].copy()
        new.epoch = state["map"].epoch + 1
        new.osd_weight[5] = weight
        upd = svc.update_to(new, from_epoch=state["map"].epoch)
        state["map"] = new
        return upd

    epoch(0)            # every program of an epoch compiled
    epoch(0x10000)
    try:
        yield svc, epoch
    finally:
        for eng in (ctx._dispatch, ctx._decode_dispatch):
            if eng is not None:
                eng.stop()


def test_update_to_gives_one_complete_tree_per_epoch(mapping_service):
    svc, epoch = mapping_service
    assert tracing.trace_ids() == []
    tracing.set_sample_rate(1.0)
    upd = epoch(0)
    tracing.set_sample_rate(0.0)
    assert not upd.full and upd.changed
    (rows,) = tracing.completed_traces()
    root = span_readers.root_of(rows)
    assert root["event"] == "update_to"
    assert root["attrs"]["epoch"] == svc.epoch
    spans = [r for r in rows if r["kind"] == "span"]
    names = [r["event"] for r in spans]
    for want in ("mapping cv wait", "mapping signatures", "mapping crush",
                 "mapping ladder", "mapping ladder operands",
                 "mapping ladder run", "mapping delta",
                 "mapping delta sort",
                 "mapping install", "mapping account",
                 "device crush_rule", "device pg_finish"):
        assert names.count(want) == 1, (want, names)
    # both engine round trips carry their phases, CPU time on each
    # that one thread ran; the epoch's own spans carry theirs too
    for phase in tracing.ENGINE_PHASES:
        if phase != "place":
            assert names.count(f"engine {phase}") == 2
    for r in spans:
        if r["event"].startswith("mapping ") or (
                r["event"].startswith("engine ")
                and r["event"] != "engine queue_wait"):
            assert r["cpu_ns"] >= 0 and r["thread"], r
    assert root["cpu_ns"] >= 0 and root["thread"] == threading.get_ident()
    for name in ("mapping cv wait", "mapping delta read-back"):
        assert all(r["attrs"].get("wait") is True for r in spans
                   if r["event"] == name)
    path = span_readers.critical_path(
        rows, span_readers.by_layer_and_wait)
    assert {tracing.LAYER_MAPPING, tracing.LAYER_ENGINE,
            span_readers.KERNELS} <= set(path), path
    assert path["unnamed"] <= UNNAMED_MAX * path["root"], path
    assert sum(v for k, v in path.items() if k != "root") == path["root"]
    # a traced caller's update_to joins its trace and opens no root
    tracing.reset()
    with tracing.trace_ctx(name="caller", daemon="t") as tid:
        epoch(0x10000)
    assert tracing.trace_ids() == [tid]
    joined = [r for r in tracing.dump(tid) if r["event"] == "update_to"]
    assert len(joined) == 1 and joined[0]["parent_span_id"]


def test_profiler_session_arms_roots_and_receives_annotations(
        ec_cluster, mapping_service, tmp_path):
    import jax
    from perfbench.harness import trace as trace_mod
    _c, io = ec_cluster
    _svc, epoch = mapping_service
    assert not tracing.armed()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.armed()
        _write(io, ["profiled-0", "profiled-1"], size=4 * 8 * 4096)
        epoch(0)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.armed()
    roots = sorted(span_readers.root_of(t)["event"]
                   for t in tracing.completed_traces())
    assert roots == ["osd_op profiled-0", "osd_op profiled-1",
                     "update_to"]
    # the session's traces are pinned past the active cap ...
    tracing.set_active_cap(1)
    assert len(tracing.completed_traces()) == 3
    # ... and the host plane of the xplane holds the same-thread spans
    host: dict[str, int] = {}
    threads: list[set] = []         # the annotations of each host thread
    path = trace_mod.find_xplane(str(tmp_path))
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                threads.append(set())
                for ev in line.events:
                    host[ev.name] = host.get(ev.name, 0) + 1
                    threads[-1].add(ev.name)
    for want, n in (("client submit", 2), ("client complete", 2),
                    ("ec continuation", 2), ("ec sub-write", 10),
                    ("bluestore commit", 12), ("bluestore fsync", 24),
                    ("update_to", 1), ("mapping crush", 1),
                    ("mapping delta sort", 1)):
        assert host.get(want) == n, (want, n, host.get(want))
    # the engine's phases lie there too, between the marks of its
    # ledger: on its dispatch thread up to the launch and on its
    # completion thread after (an encode a write, two requests the
    # epoch), or all on a caller's own (a replica shard's digest
    # request, under the commit that waits for it)
    phases = ("engine build", "engine launch", "engine compute",
              "engine materialize")
    assert all(host.get(p, 0) >= 2 + 2 + 1 for p in phases), host
    assert any({"engine build", "engine launch"} <= t
               and "engine compute" not in t for t in threads)
    assert any({"engine compute", "engine materialize"} <= t
               and "engine launch" not in t for t in threads)
    assert any({"bluestore csum settle", *phases} <= t for t in threads)
    assert not [n for n in host if n.startswith("engine ")
                and n not in (*phases, "engine place")]
    # cross-thread spans live in the table only
    assert not [n for n in host if n.startswith(("msg ", "opq ",
                                                 "osd_op", "device "))]
    # writes after the session are not traced
    _write(io, ["after"])
    assert len(tracing.trace_ids()) == 3


# -- the EC read path -----------------------------------------------------------

#: four whole 4+2 stripes of 16 KiB chunks: a shard is 16 blocks, over
#: `bluestore_batched_read_min`, so a shard read verifies by the digest
#: batch as a 4 MiB object's shards do
READ_SIZE = 4 * 4 * 16384
DOWN_OSD = 1


@pytest.fixture(scope="module")
def degraded_cluster(tmp_path_factory):
    """A 4+2 pool on six OSDs with eight objects written, then one OSD
    killed and marked down (and left in): every PG is a shard short.
    Its own cluster: the kill may not reach the other tests' pool."""
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=6, store_type="bluestore", ms_type="async",
                    base_path=str(tmp_path_factory.mktemp("degraded"))
                    ).start()
    try:
        c.wait_for_osd_count(6)
        client = c.client(timeout=30.0)
        pool = c.create_pool(client, pool_type="erasure", k=4, m=2,
                             pg_num=8, stripe_unit=16384)
        io = client.open_ioctx(pool)
        rng = np.random.default_rng(31)
        blobs = {f"deg-{i}": rng.bytes(READ_SIZE) for i in range(8)}
        for name, blob in blobs.items():
            io.write_full(name, blob)
        lost_data = {name for name in blobs
                     if any(f"{name}:{s}" in c.osds[DOWN_OSD].store
                            .list_objects(cid) for s in range(4)
                            for cid in c.osds[DOWN_OSD].store
                            .list_collections())}
        c.kill_osd(DOWN_OSD)
        rc, out = client.mon_command({"prefix": "osd down",
                                      "id": str(DOWN_OSD)})
        assert rc == 0, out
        c.wait_for_epoch(c.mon.osdmap.epoch)
        client.wait_for_epoch(c.mon.osdmap.epoch)
        for name, blob in blobs.items():    # patterns met, programs compiled
            assert io.read(name) == blob
        yield c, io, blobs, lost_data
    finally:
        c.stop()


def _read(io, blobs):
    done = {n: io.aio_read(n) for n in blobs}
    for n, c in done.items():
        assert c.wait_for_complete(30.0) and c.get_return_value() >= 0
        assert c.data == blobs[n]
    time.sleep(0.2)


def _counters(cluster) -> dict:
    return {key: sum(d.perf.value(key) for d in cluster.osds.values())
            for key in ("ec_decode_submits", "ec_degraded_reads",
                        "ec_decode_targets")}


def test_degraded_aio_read_gives_one_complete_tree_per_op(degraded_cluster):
    c, io, blobs, lost_data = degraded_cluster
    assert 0 < len(lost_data) < len(blobs)
    before = _counters(c)
    tracing.set_sample_rate(1.0)
    _read(io, blobs)
    tracing.set_sample_rate(0.0)
    # always-on counters: one submit and one rebuilt shard for every
    # object whose data shard was on the dead OSD
    after = _counters(c)
    assert {k: after[k] - before[k] for k in after} == {
        "ec_decode_submits": len(lost_data),
        "ec_degraded_reads": len(lost_data),
        "ec_decode_targets": len(lost_data)}
    traces = {span_readers.root_of(t)["event"][len("osd_op "):]: t
              for t in _op_traces()}
    assert sorted(traces) == sorted(blobs)
    for name, rows in traces.items():
        spans = [r for r in rows if r["kind"] == "span"]
        by_id = {r["span_id"]: r for r in spans}
        root = span_readers.root_of(rows)
        # ONE tree, every span closed
        assert all(r["parent_span_id"] in by_id
                   for r in spans if r is not root)
        assert all(r["end_ns"] is not None for r in spans), \
            [r["event"] for r in spans if r["end_ns"] is None]
        names = [r["event"] for r in spans]
        for want in ("client submit", "msg MOSDOp", "rx MOSDOp",
                     "opq wait", "osd op", "ec read prepare",
                     "ec read gather", "msg MOSDECSubOpRead",
                     "ec sub-read", "msg MOSDECSubOpReadReply",
                     "ec sub-read reply", "bluestore read",
                     "bluestore read blocks", "bluestore csum verify",
                     "device bluestore_data", "ec read finish",
                     "osd reply", "msg MOSDOpReply", "client complete"):
            assert want in names, (name, want, sorted(set(names)))
        assert names.count("ec read gather") == 1
        # op, three sub-reads and their replies, reply
        assert _hops_are_stamped(rows) == 8
        # k shards: the primary's own and k - 1 asked for over the wire
        assert names.count("bluestore read") == 4
        assert names.count("ec sub-read") == 3
        assert all(r["layer"] == tracing.LAYER_ECB for r in spans
                   if r["event"].startswith("ec "))
        assert all(r["layer"] == tracing.LAYER_STORE for r in spans
                   if r["event"].startswith("bluestore "))

        def parent(r):
            return by_id[r["parent_span_id"]]["event"]

        def ancestors(r):
            while r["parent_span_id"] in by_id:
                r = by_id[r["parent_span_id"]]
                yield r["event"]

        # asynchronous work hangs under the span that waits for it:
        # a sub-read under the gather, a digest batch under the verify
        gather = next(r for r in spans if r["event"] == "ec read gather")
        for r in spans:
            if r["event"] in ("msg MOSDECSubOpRead", "ec sub-read",
                              "ec sub-read reply", "bluestore read"):
                assert "ec read gather" in ancestors(r), r["event"]
            if r["event"] == "device bluestore_data":
                assert parent(r) == "bluestore csum verify"
            if r["event"] in ("bluestore read blocks",
                              "bluestore csum verify"):
                assert parent(r) == "bluestore read"
            if r["event"] == "bluestore read blocks":
                # a shard of 16 blocks, written whole into a fresh
                # object: one extent run, one read of the block file
                assert (r["attrs"]["blocks"], r["attrs"]["runs"]) \
                    == (READ_SIZE // 4 // 4096, 1), r["attrs"]
        assert gather["end_ns"] >= max(
            r["start_ns"] for r in spans
            if r["event"] == "ec sub-read reply")
        decoded = name in lost_data
        for want in ("ec decode submit", "device ec_decode",
                     "ec decode continuation"):
            assert (want in names) == decoded, (name, want)
        if decoded:
            req = next(r for r in spans if r["event"] == "device ec_decode")
            assert parent(req) == "ec decode submit"
            cont = next(r for r in spans
                        if r["event"] == "ec decode continuation")
            # handed from the engine's delivery to an op-queue worker
            assert parent(cont) == "opq wait"
            assert list(ancestors(cont))[1:3] == ["engine deliver",
                                                  "device ec_decode"]
            finish = next(r for r in spans
                          if r["event"] == "ec read finish")
            assert parent(finish) == "ec decode continuation"
        # the critical path is named end to end and holds every layer
        path = span_readers.critical_path(rows)
        assert OP_LAYERS <= set(path), path
        assert path["unnamed"] <= UNNAMED_MAX * path["root"], path
        assert sum(v for k, v in path.items() if k != "root") \
            == path["root"]
        on_path = [r["event"] for r, _d in span_readers.path_spans(rows)]
        # of the gather's sub-reads only the last to arrive is on it
        assert on_path.count("ec sub-read") <= 1
        assert {"client submit", "ec read gather", "ec read finish",
                "osd reply", "client complete"} <= set(on_path)
        if decoded:
            assert {"ec decode submit", "device ec_decode",
                    "engine deliver", "ec decode continuation"} \
                <= set(on_path)


def _digest_requests_keep_their_spans(rows, waiter: str) -> int:
    """Every ``device bluestore_data`` request of the trace hangs under
    the store span that waits for it and carries the engine's phases,
    gapless and in order, whichever thread ran it; one that its
    caller's own thread ran (``caller_thread``) queued for nothing and
    its phases carry that thread's CPU time.
    Returns how many ran on their caller's thread."""
    spans = [r for r in rows if r["kind"] == "span"]
    by_id = {r["span_id"]: r for r in spans}
    reqs = [r for r in spans if r["event"] == "device bluestore_data"]
    assert reqs
    for req in reqs:
        assert by_id[req["parent_span_id"]]["event"] == waiter
        assert req["layer"] == tracing.LAYER_ENGINE
        assert {"h2d_bytes", "d2h_bytes", "retrace", "batch",
                "caller_thread"} <= set(req["attrs"])
        phases = _phases(req, spans)
        assert phases[0]["start_ns"] == req["start_ns"]
        for a, b in zip(phases, phases[1:]):
            assert a["end_ns"] == b["start_ns"]
        assert phases[-1]["end_ns"] <= req["end_ns"]
        if req["attrs"]["caller_thread"]:
            # it waited in no queue, and every phase ran on the thread
            # of the store span that waits for it, CPU time and all
            assert phases[0]["event"] == "engine build"
            assert req["attrs"]["batch"] == 1
            waiter_row = by_id[req["parent_span_id"]]
            assert {r["thread"] for r in phases} == {waiter_row["thread"]}
            assert all(r["cpu_ns"] >= 0 for r in phases)
            assert sum(r["cpu_ns"] for r in phases) \
                <= waiter_row["cpu_ns"] + 1_000_000
        # the store's span waits for the request: it ends no earlier
        assert by_id[req["parent_span_id"]]["end_ns"] >= req["end_ns"]
    return sum(bool(r["attrs"]["caller_thread"]) for r in reqs)


def test_traced_commit_keeps_digest_spans_on_the_callers_thread(ec_cluster):
    """A write of eight blocks a shard: each replica shard's commit
    settles its checksums by one ``bluestore_data`` request, which at
    one op in flight its committing thread runs itself — under
    ``bluestore csum settle`` all the same, phases and all."""
    _c, io = ec_cluster
    _write(io, ["settle-warm"], size=4 * 8 * 4096)  # the shape compiled
    tracing.set_sample_rate(1.0)
    _write(io, ["settle"], size=4 * 8 * 4096)
    tracing.set_sample_rate(0.0)
    (rows,) = _op_traces()
    assert _digest_requests_keep_their_spans(
        rows, "bluestore csum settle") >= 1
    path = span_readers.critical_path(rows)
    assert path["unnamed"] <= UNNAMED_MAX * path["root"], path


def test_traced_wide_read_keeps_digest_spans_on_the_callers_thread(
        degraded_cluster):
    """A shard read of sixteen blocks verifies them by one
    ``bluestore_data`` request on the reading thread, under
    ``bluestore csum verify``."""
    _c, io, blobs, _lost = degraded_cluster
    tracing.set_sample_rate(1.0)
    _read(io, blobs)
    tracing.set_sample_rate(0.0)
    on_caller = 0
    for rows in _op_traces():
        on_caller += _digest_requests_keep_their_spans(
            rows, "bluestore csum verify")
    assert on_caller >= 1


def test_unarmed_degraded_reads_record_nothing(degraded_cluster):
    _c, io, blobs, _lost = degraded_cluster
    assert not tracing.armed()
    _read(io, blobs)
    assert tracing.trace_ids() == []
