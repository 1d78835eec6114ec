"""The paths the code picks between, reached the way the code reaches
them.

No option selects between an old and a new path any more: the scalar
scan, the host ``zlib.crc32`` loop, the per-block read verify, the
``shard_crc`` scrub loop and the synchronous EC encode / decode are
taken when the code OBSERVES a reason (an exception from the service, a
store with no context, a caller that is an engine thread, a batch under
a floor, a row over the kernel's width cap, a whole-object codec).  Each
case below produces one such reason and checks that the old path ran
and that its answer is bit-equal to the new path's.

The same holds for the one choice between two live paths: a digest
request whose caller waits (``submit_bluestore_data(..., wait=True)``
-> ``DeviceDispatchEngine.submit_waiting``) runs on the caller's own
thread while the engine is idle and queues like any ``submit``
otherwise.  The ``waiting_caller_*`` cases produce each observed
condition and check the road taken by the engine's counters."""

from __future__ import annotations

import json
import threading
import time
import zlib

import numpy as np
import pytest

from ceph_tpu.objectstore import Transaction
from ceph_tpu.objectstore.bluestore import BLOCK, BlueStoreLite
from ceph_tpu.common import failpoint
from ceph_tpu.ops import checksum_kernel as ck
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine, submit_bluestore_data
from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.mapping import SharedPGMappingService
from ceph_tpu.tools.vstart import MiniCluster

pytestmark = pytest.mark.filterwarnings("ignore")


# -- helpers ------------------------------------------------------------------

def _ctx(name: str, **conf):
    from ceph_tpu.common.context import CephTpuContext
    c = CephTpuContext(name)
    for k, v in conf.items():
        c.conf.set(k, str(v), source="cli")
    return c


def _stop_engines(ctx) -> None:
    for attr in ("_decode_dispatch", "_dispatch"):
        e = getattr(ctx, attr, None)
        if e is not None:
            e.stop()


def _store(tmp_path, ctx, name: str) -> BlueStoreLite:
    s = BlueStoreLite(str(tmp_path / name), ctx=ctx)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection("2.0"))
    return s


def _csums(store, oid: str) -> list:
    return json.loads(
        store._db.get("obj", f"2.0\x00{oid}").decode())["csum"]


def _write(store, oid: str, payload: bytes) -> None:
    store.apply_transaction(Transaction().write("2.0", oid, 0, payload))


def _payload(seed: int, n: int) -> bytes:
    return bytes(np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8))


def _on_engine_thread(eng, fn):
    """Run ``fn()`` in a continuation on ``eng``'s completion thread
    (where EC-write and recovery continuations commit to the store) and
    return its result."""
    for _ in range(20):
        box: dict = {}
        done = threading.Event()

        def cb(_fut, box=box, done=done):
            try:
                box["on_engine"] = eng.owns_current_thread()
                if box["on_engine"]:
                    box["out"] = fn()
            except BaseException as e:      # _deliver swallows: carry it
                box["exc"] = e
            finally:
                done.set()

        submit_bluestore_data(eng, [b"x" * 64]).add_done_callback(cb)
        assert done.wait(120)
        if "exc" in box:
            raise box["exc"]
        if box["on_engine"]:
            return box["out"]
    raise AssertionError("no continuation ran on the engine's thread")


# -- the cases ----------------------------------------------------------------

def _case_update_to_raises_full_scalar_scan(tmp_path, monkeypatch):
    """The mapping service's ``update_to`` raises inside ``_handle_map``:
    the OSD scans every PG with the scalar pipeline (``_scan_pgs(None)``),
    ``lookup()`` answers from the scalar oracle, and the pool serves."""
    from test_mapping_service import _count_scan_scalar_calls

    def broken(self, *a, **kw):
        raise RuntimeError("mapping service down")

    monkeypatch.setattr(SharedPGMappingService, "update_to", broken)
    c = MiniCluster(n_osds=2, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(2)
        client = c.client()
        calls = _count_scan_scalar_calls(monkeypatch)
        pool = c.create_pool(client, pg_num=16, size=2)
        io = client.open_ioctx(pool)
        io.write_full("obj", b"scalar")
        assert io.read("obj") == b"scalar"
        assert calls["scan"] >= 16, calls       # a full scalar scan
        # nothing was ever published: every answer came from the oracle
        for osd in c.osds.values():
            assert osd.ctx.mapping_service().epoch == -1
    finally:
        c.stop()


def _case_bluestore_without_context_scalar_crc(tmp_path, monkeypatch):
    """A store with no context has no engine: zlib.crc32 settles its
    checksums, the same values the batched store commits."""
    ctx = _ctx("path-sel-noctx", bluestore_batched_csum_min=1)
    payload = _payload(11, 6 * BLOCK + 123)
    batched = _store(tmp_path, ctx, "batched")
    bare = _store(tmp_path, None, "bare")
    try:
        assert bare._batch_engine() is None
        _write(batched, "o", payload)
        _write(bare, "o", payload)
        assert batched.perf.value("csum_batches") == 1
        assert bare.perf.value("csum_batches") == 0
        want = [zlib.crc32(payload[i:i + BLOCK].ljust(BLOCK, b"\0"))
                for i in range(0, len(payload), BLOCK)]
        assert _csums(bare, "o") == want
        assert _csums(batched, "o") == want
        assert bare.read("2.0", "o") == payload
    finally:
        batched.umount()
        bare.umount()
        _stop_engines(ctx)


def _case_bluestore_on_engine_thread_scalar_crc(tmp_path, monkeypatch):
    """A commit made from an engine's own completion thread must not
    wait on that engine: ``_batch_engine()`` is None there and
    zlib.crc32 settles the checksums, equal to the batched ones."""
    ctx = _ctx("path-sel-engthread", bluestore_batched_csum_min=1)
    payload = _payload(12, 5 * BLOCK)
    s = _store(tmp_path, ctx, "s")
    try:
        eng = ctx.decode_dispatch_engine()
        assert s._batch_engine() is eng         # an ordinary caller
        _write(s, "plain", payload)
        assert s.perf.value("csum_batches") == 1

        def commit():
            assert s._batch_engine() is None
            _write(s, "cont", payload)
            return s.perf.value("csum_batches")

        assert _on_engine_thread(eng, commit) == 1   # no second batch
        assert _csums(s, "cont") == _csums(s, "plain")
        assert None not in _csums(s, "cont")
        assert s.read("2.0", "cont") == payload
    finally:
        s.umount()
        _stop_engines(ctx)


def _case_read_under_floor_verifies_per_block(tmp_path, monkeypatch):
    """A read of fewer than ``bluestore_batched_read_min`` blocks is
    verified block by block on the host; a wider one rides the digest
    channel.  Both return the bytes, both catch a flipped byte."""
    ctx = _ctx("path-sel-readfloor", bluestore_batched_csum_min=1)
    floor = int(ctx.conf.get("bluestore_batched_read_min"))
    payload = _payload(13, (floor + 4) * BLOCK)
    s = _store(tmp_path, ctx, "s")
    try:
        _write(s, "r", payload)
        seen = []
        orig = s._batch_read_verify

        def spy(*a, **kw):
            out = orig(*a, **kw)
            seen.append(len(out))
            return out

        monkeypatch.setattr(s, "_batch_read_verify", spy)
        under = (floor - 1) * BLOCK
        assert s.read("2.0", "r", 0, under) == payload[:under]
        assert seen == [0]                      # per-block path
        assert s.read("2.0", "r") == payload
        assert seen == [0, floor + 4]           # one digest batch
        m = json.loads(s._db.get("obj", "2.0\x00r").decode())
        s._f.seek(m["extents"][1] * BLOCK + 99)
        s._f.write(b"\xff" if payload[BLOCK + 99] != 0xFF else b"\x00")
        s._f.flush()
        for length in (under, None):
            with pytest.raises(IOError, match="checksum mismatch"):
                s.read("2.0", "r", 0, length)
    finally:
        s.umount()
        _stop_engines(ctx)


def _case_scrub_row_over_max_width_scalar_loop(tmp_path, monkeypatch):
    """A scrub chunk holding a row wider than the digest kernel's cap
    takes the ``shard_crc`` loop; narrower rows ride the channel, and
    the two fill the same triples."""
    from ceph_tpu.osd.ec_util import shard_crc
    c = MiniCluster(n_osds=1, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(1)
        client = c.client(timeout=30.0)
        pool = c.create_pool(client, pg_num=1, size=1)
        io = client.open_ioctx(pool)
        wide = _payload(14, ck.MAX_WIDTH + 1)
        io.write_full("wide", wide)
        for i in range(3):
            io.write_full(f"narrow{i}", _payload(20 + i, 1000 + i))
        osd = next(iter(c.osds.values()))
        cid = f"{pool}.0"
        _out, rows, _vers = osd._scrub_read_rows(cid)
        blobs = [r[1] for r in rows] + [r[2] for r in rows]
        assert max(len(b) for b in blobs) > ck.MAX_WIDTH
        assert osd._scrub_digest_rows(blobs) is None
        narrow = [r for r in rows if r[0] != "wide"]
        assert len(narrow) == 3
        digs = osd._scrub_digest_rows(
            [r[1] for r in narrow] + [r[2] for r in narrow])
        assert digs is not None
        assert (OSDDaemon._scrub_fill({}, narrow, digs)
                == OSDDaemon._scrub_fill({}, narrow, None))
        smap, _ = osd._scrub_map(cid)
        assert smap["wide"][:2] == (len(wide), shard_crc(wide))
        assert osd.scrub_all_pgs()["clean"]
    finally:
        c.stop()


def _case_whole_object_codec_sync_encode_and_decode(tmp_path,
                                                    monkeypatch):
    """A Clay pool is laid out per stripe as Ceph lays it (alpha
    sub-chunks a stripe unit), so it takes the engines' path as a
    jerasure pool on the same OSDs does: the write through the encode
    engine, the degraded read submitted to the decode engine.  Either
    way the read returns the acknowledged bytes."""
    answers: dict[int, list] = {}
    orig = OSDDaemon._ec_submit_decode

    def spy(self, reqid, state):
        out = orig(self, reqid, state)
        answers.setdefault(state["pool"].pool_id, []).append(out)
        return out

    monkeypatch.setattr(OSDDaemon, "_ec_submit_decode", spy)
    c = MiniCluster(n_osds=6, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(6)
        client = c.client(timeout=30.0)

        def submits() -> int:
            return sum(o.perf.value("ec_dispatch_submits")
                       for o in c.osds.values())

        def lose_shard(pool: int, oid: str) -> None:
            for osd in c.osds.values():
                for cid in osd.store.list_collections():
                    if cid.startswith(f"{pool}.") and \
                            f"{oid}:0" in osd.store.list_objects(cid):
                        osd.store.apply_transaction(
                            Transaction().remove(cid, f"{oid}:0"))
                        return
            raise AssertionError("shard 0 not found")

        clay = c.create_pool(client, pg_num=1, pool_type="erasure",
                             plugin="clay", k=4, m=2)
        rs = c.create_pool(client, pg_num=1, pool_type="erasure",
                           k=2, m=1)
        payload = _payload(15, 20000)
        s0 = submits()
        io = client.open_ioctx(clay)
        io.write_full("obj", payload)
        assert submits() == s0 + 1              # through the engine
        lose_shard(clay, "obj")
        assert io.read("obj") == payload
        assert answers.get(clay) == [True]

        io2 = client.open_ioctx(rs)
        io2.write_full("obj", payload)
        assert submits() == s0 + 2              # through the engine
        lose_shard(rs, "obj")
        assert io2.read("obj") == payload
        assert answers.get(rs) == [True]
    finally:
        c.stop()


def _case_balancer_warm_fails_scalar_histogram(tmp_path, monkeypatch):
    """When warming the shared cache fails the balancer reads every PG
    from the scalar pipeline, and counts the same placements."""
    from ceph_tpu import balancer
    from test_mapping_service import _base_map
    m, _rule = _base_map()
    svc = SharedPGMappingService(backend="scalar")
    svc.warm(m)
    cached = balancer.pool_pg_histogram(m, 1, service=svc)

    def broken(self, osdmap):
        raise RuntimeError("no table build today")

    monkeypatch.setattr(SharedPGMappingService, "warm", broken)
    assert balancer._shared_service(m) is None
    assert balancer.pool_pg_histogram(m, 1) == cached


# -- a waiting caller: its own thread, or the queue ----------------------------

def _engine() -> DeviceDispatchEngine:
    eng = DeviceDispatchEngine(stats=telemetry.DispatchStats(),
                               name="path-sel")
    eng.fault_backoff_ms = 1.0
    eng.fault_backoff_max_ms = 5.0
    eng.probe_interval = 30.0       # no probe re-closes a breaker here
    return eng


def _blocks(seed: int, n: int):
    buf = _payload(seed, n * BLOCK)
    return buf, [buf[i * BLOCK:(i + 1) * BLOCK] for i in range(n)]


def _crcs(blobs) -> list:
    return [zlib.crc32(b) & 0xFFFFFFFF for b in blobs]


def _col0(fut) -> list:
    return [int(v) for v in np.asarray(fut.result(60))[:, 0]]


def _gated(gate: threading.Event, entered: threading.Event):
    def fn(batch):
        entered.set()
        assert gate.wait(60)
        return batch
    return fn


def _case_waiting_caller_idle_engine_runs_on_its_thread(tmp_path,
                                                        monkeypatch):
    """Idle engine, waiting caller: the caller's thread builds,
    launches and completes the batch — the new counter and ``batches``
    step together, the engine's threads are never started, the future
    comes back resolved and the phase ledger records no queue wait.
    A store's commit and wide read take that road by themselves."""
    eng = _engine()
    try:
        buf, blobs = _blocks(31, 8)
        fut = submit_bluestore_data(eng, blobs, runs=[buf], wait=True)
        assert fut.done()
        assert _col0(fut) == _crcs(blobs)
        d = eng.stats.dump()
        assert (d["submits"], d["batches"], d["caller_batches"],
                d["completed"]) == (1, 1, 1, 1)
        assert d["flush_reasons"]["idle"] == 1
        assert not eng._threads                 # no hand-over, ever
        rec = eng.stats.phases.dump()["recent"][-1]
        assert rec["caller_thread"] and rec["kernel"] == "bluestore_data"
        assert rec["phases"]["queue_wait"] < 1e-3
        assert eng.stats.phases.summary()["kernels"]["bluestore_data"][
            "caller_batches"] == 1
        # the plain entry still queues: engine threads, no new count
        assert _col0(submit_bluestore_data(eng, blobs)) == _crcs(blobs)
        d = eng.stats.dump()
        assert (d["batches"], d["caller_batches"]) == (2, 1)
        assert sorted(eng._threads) == ["complete", "submit"]
        assert eng.flush()
    finally:
        eng.stop()
    ctx = _ctx("path-sel-caller", bluestore_batched_csum_min=1)
    s = _store(tmp_path, ctx, "s")
    try:
        stats = ctx.decode_dispatch_engine().stats
        n0 = stats.dump()["caller_batches"]
        payload = _payload(32, 16 * BLOCK)
        _write(s, "o", payload)
        assert stats.dump()["caller_batches"] == n0 + 1     # the commit
        assert s.read("2.0", "o") == payload
        assert stats.dump()["caller_batches"] == n0 + 2     # the read
        assert _csums(s, "o") == _crcs(
            [payload[i:i + BLOCK] for i in range(0, len(payload), BLOCK)])
    finally:
        s.umount()
        _stop_engines(ctx)


def _case_waiting_caller_behind_a_request_in_flight_queues(tmp_path,
                                                           monkeypatch):
    """A request already in flight: the waiting caller's is queued and
    delivered after it; and the other way round — while a request runs
    on its caller's thread a plain ``submit`` queues and completes
    behind it."""
    eng = _engine()
    order = []
    try:
        data = np.arange(8, dtype=np.uint8).reshape(2, 4)
        gate, entered = threading.Event(), threading.Event()
        first = eng.submit(("k", 4), _gated(gate, entered), data)
        first.add_done_callback(lambda _f: order.append("first"))
        assert entered.wait(60)
        waiting = eng.submit_waiting(("k", 4), lambda b: b + 1, data)
        waiting.add_done_callback(lambda _f: order.append("waiting"))
        time.sleep(0.1)
        assert not first.done() and not waiting.done()
        assert eng.stats.dump()["caller_batches"] == 0
        gate.set()
        assert np.array_equal(waiting.result(60), data + 1)
        assert order == ["first", "waiting"]
        assert eng.flush()

        gate, entered = threading.Event(), threading.Event()
        box = {}

        def on_caller():
            box["fut"] = eng.submit_waiting(
                ("k", 4), _gated(gate, entered), data)
            order.append("caller")

        t = threading.Thread(target=on_caller)
        t.start()
        assert entered.wait(60)
        behind = eng.submit(("k", 4), lambda b: b + 2, data)
        behind.add_done_callback(lambda _f: order.append("behind"))
        time.sleep(0.3)         # past the flush deadline: dispatched
        assert not behind.done()
        gate.set()
        t.join(60)
        assert np.array_equal(behind.result(60), data + 2)
        assert np.array_equal(box["fut"].result(0), data)
        assert order[2:] == ["caller", "behind"]
        assert eng.stats.dump()["caller_batches"] == 1
    finally:
        gate.set()
        eng.stop()


def _case_waiting_caller_breaker_open_host_oracle_via_queue(tmp_path,
                                                            monkeypatch):
    """The channel's breaker is open: the request is queued and the
    dispatch thread serves it from the host oracle, as for any
    ``submit`` — no attempt at the device from the caller's thread."""
    eng = _engine()
    eng.breaker_threshold = 1
    try:
        buf, blobs = _blocks(33, 4)
        failpoint.set("dispatch.launch:bluestore_data", "always")
        opener = submit_bluestore_data(eng, blobs, runs=[buf], wait=True)
        assert _col0(opener) == _crcs(blobs)
        assert eng.breaker_states()["bluestore_data"] \
            == telemetry.BREAKER_OPEN
        d0 = eng.stats.dump()
        assert d0["caller_batches"] == 1
        fut = submit_bluestore_data(eng, blobs, runs=[buf], wait=True)
        assert _col0(fut) == _crcs(blobs)
        d = eng.stats.dump()
        assert d["caller_batches"] == 1         # queued
        assert d["batches"] == d0["batches"] + 1
        assert d["faults"]["fallback_batches"] \
            == d0["faults"]["fallback_batches"] + 1
        assert d["faults"]["retries"] == d0["faults"]["retries"]
        assert "submit" in eng._threads
    finally:
        failpoint.clear()
        eng.stop()


def _case_waiting_caller_on_engine_thread_always_queues(tmp_path,
                                                        monkeypatch):
    """A continuation on the engine's completion thread that asks for
    the waiting entry — the engine is idle at that moment, its batch
    just popped — is queued: an engine thread never runs a request
    itself."""
    eng = _engine()
    try:
        buf, blobs = _blocks(34, 4)

        def ask():
            fut = submit_bluestore_data(eng, blobs, runs=[buf], wait=True)
            return fut, fut.done(), eng.stats.dump()["caller_batches"]

        fut, done_at_once, counted = _on_engine_thread(eng, ask)
        assert not done_at_once and counted == 0
        assert _col0(fut) == _crcs(blobs)
        assert eng.stats.dump()["caller_batches"] == 0
    finally:
        eng.stop()


def _case_waiting_caller_launch_fault_retry_then_oracle(tmp_path,
                                                        monkeypatch):
    """``dispatch.launch:bluestore_data`` injected on the caller's
    thread: one fault is retried there and heals; a lasting one walks
    the ladder to the host oracle and opens the breaker at its
    threshold, with the counters of the queued path; a store above it
    commits exact checksums with no ``csum_fallbacks``."""
    eng = _engine()
    eng.breaker_threshold = 2
    try:
        buf, blobs = _blocks(35, 4)
        failpoint.set("dispatch.launch:bluestore_data", "nth:1")
        assert _col0(submit_bluestore_data(
            eng, blobs, runs=[buf], wait=True)) == _crcs(blobs)
        f = eng.stats.fault_dump()
        assert (f["retries"], f["retry_successes"],
                f["fallback_batches"]) == (1, 1, 0)
        failpoint.set("dispatch.launch:bluestore_data", "always")
        for n in (1, 2):
            assert _col0(submit_bluestore_data(
                eng, blobs, runs=[buf], wait=True)) == _crcs(blobs)
            f = eng.stats.fault_dump()
            assert f["fallback_batches"] == n
            assert f["breaker_opens"] == (n == 2)
        assert f["retries"] == 1 + 2 * eng.fault_max_retries
        d = eng.stats.dump()
        assert (d["batches"], d["caller_batches"]) == (3, 3)
        assert not {"submit", "complete"} & set(eng._threads)
    finally:
        failpoint.clear()
        eng.stop()
    ctx = _ctx("path-sel-caller-fault", bluestore_batched_csum_min=1)
    s = _store(tmp_path, ctx, "s")
    try:
        ctx.decode_dispatch_engine().fault_backoff_ms = 1.0
        fb0 = telemetry.bluestore_summary()["csum_fallbacks"]
        failpoint.set("dispatch.launch:bluestore_data", "always")
        payload = _payload(36, 6 * BLOCK)
        _write(s, "o", payload)
        failpoint.clear()
        assert _csums(s, "o") == _crcs(
            [payload[i:i + BLOCK] for i in range(0, len(payload), BLOCK)])
        assert telemetry.bluestore_summary()["csum_fallbacks"] == fb0
        assert s.perf.value("csum_batches") == 1
    finally:
        failpoint.clear()
        s.umount()
        _stop_engines(ctx)


def _case_waiting_caller_flush_and_stop_wait_for_its_request(tmp_path,
                                                             monkeypatch):
    """``flush()`` and ``stop()`` during a request that runs on its
    caller's thread return only after it, though the engine has no
    thread of its own to join."""
    eng = _engine()
    gate, entered = threading.Event(), threading.Event()
    data = np.zeros((2, 4), dtype=np.uint8)
    t_done = {}

    def on_caller():
        eng.submit_waiting(("k", 4), _gated(gate, entered), data)
        t_done["request"] = time.monotonic()

    def stopper():
        t_done["stop_ok"] = eng.stop()
        t_done["stop"] = time.monotonic()

    t = threading.Thread(target=on_caller)
    t.start()
    try:
        assert entered.wait(60)
        assert eng.building() or eng._inflight
        assert eng.flush(timeout=0.2) is False
        s = threading.Thread(target=stopper)
        s.start()
        time.sleep(0.3)
        assert s.is_alive() and "stop" not in t_done
        gate.set()
        t.join(60)
        s.join(60)
        assert t_done["stop_ok"] is True
        assert t_done["stop"] >= t_done["request"]
        assert eng.flush(timeout=1.0)
        assert eng.stats.dump()["caller_batches"] == 1
    finally:
        gate.set()
        eng.stop()


CASES = {
    "update_to_raises_full_scalar_scan":
        _case_update_to_raises_full_scalar_scan,
    "bluestore_without_context_scalar_crc":
        _case_bluestore_without_context_scalar_crc,
    "bluestore_on_engine_thread_scalar_crc":
        _case_bluestore_on_engine_thread_scalar_crc,
    "read_under_floor_verifies_per_block":
        _case_read_under_floor_verifies_per_block,
    "scrub_row_over_max_width_scalar_loop":
        _case_scrub_row_over_max_width_scalar_loop,
    "whole_object_codec_sync_encode_and_decode":
        _case_whole_object_codec_sync_encode_and_decode,
    "balancer_warm_fails_scalar_histogram":
        _case_balancer_warm_fails_scalar_histogram,
    "waiting_caller_idle_engine_runs_on_its_thread":
        _case_waiting_caller_idle_engine_runs_on_its_thread,
    "waiting_caller_behind_a_request_in_flight_queues":
        _case_waiting_caller_behind_a_request_in_flight_queues,
    "waiting_caller_breaker_open_host_oracle_via_queue":
        _case_waiting_caller_breaker_open_host_oracle_via_queue,
    "waiting_caller_on_engine_thread_always_queues":
        _case_waiting_caller_on_engine_thread_always_queues,
    "waiting_caller_launch_fault_retry_then_oracle":
        _case_waiting_caller_launch_fault_retry_then_oracle,
    "waiting_caller_flush_and_stop_wait_for_its_request":
        _case_waiting_caller_flush_and_stop_wait_for_its_request,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_old_path_taken_for_observed_reason(case, tmp_path, monkeypatch):
    CASES[case](tmp_path, monkeypatch)


def test_clay_pool_two_osds_down_reads_back_through_the_decode_engine():
    """A Clay 8+4 pool on twelve OSDs, two of them down: objects written
    through the encode engine read back bit-exact, the rebuilds through
    the decode engine, each counting stripes x 64 sub-chunks x chunks."""
    c = MiniCluster(n_osds=12, ms_type="loopback").start()
    try:
        c.wait_for_osd_count(12)
        client = c.client(timeout=60.0)
        pool = c.create_pool(client, pg_num=4, pool_type="erasure",
                             plugin="clay", k=8, m=4, d=11)
        io = client.open_ioctx(pool)
        rng = np.random.default_rng(12)
        objs = {f"c{i}": rng.bytes(2 * 8 * 4096 + 777 * (i + 1))
                for i in range(6)}
        for name, data in objs.items():
            io.write_full(name, data)
        for osd in (1, 6):
            c.kill_osd(osd)
            rc, out = client.mon_command({"prefix": "osd down",
                                          "id": str(osd)})
            assert rc == 0, out
        epoch = c.mon.osdmap.epoch
        c.wait_for_epoch(epoch, timeout=60.0)
        client.wait_for_epoch(epoch)
        daemons = [d for o, d in c.osds.items() if o not in (1, 6)]

        def total(key):
            return sum(d.perf.value(key) for d in daemons)

        s0, t0, u0 = (total("ec_decode_submits"),
                      total("ec_decode_targets"),
                      total("ec_decode_subchunks"))
        for name, data in objs.items():
            assert io.read(name) == data
        submits = total("ec_decode_submits") - s0
        assert submits > 0
        # each object is three stripes here
        assert total("ec_decode_subchunks") - u0 == 3 * 64 * (
            total("ec_decode_targets") - t0)
    finally:
        c.stop()
