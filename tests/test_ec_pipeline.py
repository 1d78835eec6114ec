"""EC per-object write pipelining (ExtentCache reduced,
src/osd/ExtentCache.h:1-491): overlapping writes to one EC object ride ONE
rmw gather — later writes overlay in arrival order onto the gather's
projected content instead of serializing whole-object — and the final
content matches the sequential overlay exactly."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.messages.osd_msgs import OP_WRITE, OP_WRITEFULL, OSDOpField
from ceph_tpu.tools.vstart import MiniCluster


@pytest.fixture
def cluster(tmp_path):
    # BlueStore OSDs: an erasure pool takes overwrites only there
    c = MiniCluster(n_osds=3, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path)).start()
    c.wait_for_osd_count(3)
    yield c
    c.stop()


def _counter(cluster, name: str) -> int:
    total = 0
    for osd in cluster.osds.values():
        total += osd.perf.dump().get(name, 0)
    return total


def test_overlapping_writes_one_gather(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1, ec_overwrites=True)
    io = client.open_ioctx(pool)
    base = bytes(16384)
    io.write_full("pipe", base)

    g0 = _counter(cluster, "ec_rmw_gather")
    expected = bytearray(base)
    comps = []
    # back-to-back burst: overlapping 1 KiB ranges, no waiting between
    # submissions — all are in flight together
    writes = [(i * 512, bytes([i + 1]) * 1024) for i in range(8)]
    for off, data in writes:
        expected[off:off + len(data)] = data
        comps.append(client.aio_operate(
            pool, "pipe", [OSDOpField(OP_WRITE, off, len(data), data)]))
    for c in comps:
        assert c.wait_for_complete(15), "pipelined write timed out"
        assert c.get_return_value() == 0
    assert io.read("pipe") == bytes(expected)

    gathers = _counter(cluster, "ec_rmw_gather") - g0
    pipelined = _counter(cluster, "ec_rmw_pipelined")
    # one gather serves the whole burst: strictly fewer gathers than
    # writes, and at least one write rode the pipeline
    assert gathers < len(writes), (gathers, pipelined)
    assert pipelined >= 1, (gathers, pipelined)


def test_pipelined_writefull_replaces_projected_base(cluster):
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1, ec_overwrites=True)
    io = client.open_ioctx(pool)
    io.write_full("wf", b"A" * 8192)

    # partial (starts a gather), then WRITEFULL and another partial queue
    # behind it: ordering must hold — final = overlay(writefull, partial2)
    c1 = client.aio_operate(pool, "wf", [OSDOpField(
        OP_WRITE, 100, 4, b"BBBB")])
    c2 = client.aio_operate(pool, "wf", [OSDOpField(
        OP_WRITEFULL, 0, 2000, b"C" * 2000)])
    c3 = client.aio_operate(pool, "wf", [OSDOpField(
        OP_WRITE, 1990, 20, b"D" * 20)])
    for c in (c1, c2, c3):
        assert c.wait_for_complete(15)
        assert c.get_return_value() == 0
    expected = bytearray(b"C" * 2000)
    expected[1990:2010] = b"D" * 20
    assert io.read("wf") == bytes(expected)


def test_interleaved_objects_do_not_cross_pipeline(cluster):
    # writes to different oids must not share a pipeline or corrupt each
    # other's projected bases
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=2, pool_type="erasure",
                               k=2, m=1, ec_overwrites=True)
    io = client.open_ioctx(pool)
    rng = np.random.default_rng(11)
    bases = {}
    for o in range(4):
        bases[o] = bytearray(rng.integers(
            0, 256, 8192, dtype=np.uint8).tobytes())
        io.write_full(f"multi-{o}", bytes(bases[o]))
    comps = []
    for i in range(6):
        for o in range(4):
            off = 777 * i + o * 13
            data = bytes([16 * o + i + 1]) * 600
            bases[o][off:off + len(data)] = data
            comps.append(client.aio_operate(
                pool, f"multi-{o}",
                [OSDOpField(OP_WRITE, off, len(data), data)]))
    for c in comps:
        assert c.wait_for_complete(20)
        assert c.get_return_value() == 0
    for o in range(4):
        assert io.read(f"multi-{o}") == bytes(bases[o]), f"multi-{o}"


def test_burst_survives_repeat(cluster):
    # repeated bursts keep chaining correctly (projected base refreshes
    # from committed state between bursts)
    client = cluster.client()
    pool = cluster.create_pool(client, pg_num=1, pool_type="erasure",
                               k=2, m=1, ec_overwrites=True)
    io = client.open_ioctx(pool)
    expected = bytearray(4096)
    io.write_full("rep", bytes(expected))
    for round_ in range(3):
        comps = []
        for i in range(4):
            off = (997 * (round_ + 1) * (i + 1)) % 3000
            data = bytes([round_ * 40 + i + 1]) * 512
            expected[off:off + len(data)] = data
            comps.append(client.aio_operate(
                pool, "rep", [OSDOpField(OP_WRITE, off, len(data), data)]))
        for c in comps:
            assert c.wait_for_complete(15)
            assert c.get_return_value() == 0
    assert io.read("rep") == bytes(expected)
