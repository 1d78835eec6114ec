"""Heterogeneous-matrix batched GF decode riding the dispatch engine.

The decode-side twin of test_dispatch.py.  Encode coalesces trivially
(one matrix for everyone); decode's recovery matrix differs per erasure
pattern, so the load-bearing claims here are pattern-shaped:

  * bit-exactness under MIXED patterns — N threads submitting decodes
    with different erasure patterns AND different stripe counts through
    one engine each get exactly what the numpy recovery_matrix oracle
    computes for their own pattern, however the engine stacked, padded,
    gathered, and sliced;
  * padded-bucket equality — stripe-axis zero padding, matrix-table
    pow-2 padding, and target-row padding (t < t_bucket) are all
    invisible in delivered bytes;
  * the jit compile cache is bounded by the PRODUCT of the two bucket
    tables (stripe axis x matrix-table axis), not by the number of
    distinct erasure patterns or request sizes (exact-count via the
    decode entry point's compile-cache delta);
  * mixed-pattern requests queued while the engine is busy share ONE
    device call (the claim the per-stripe pattern index exists for),
    and the decode stats record the heterogeneity.

Chunk widths here are unique to this suite: the jit cache is
process-global and the bounded-cache test counts entries.
"""

from __future__ import annotations

import threading

import numpy as np

from ceph_tpu.gf.matrix import recovery_matrix
from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import DeviceDispatchEngine, bucket_stripes
from ceph_tpu.ops.gf_kernel import (decode_bit_table, ec_decode_batched,
                                    ec_decode_ref, ec_encode_ref)

K1, M1, B1 = 4, 2, 352     # bit-exactness suites
K2, M2, B2 = 5, 3, 224     # bounded-cache suite


def _codec(k, m, runtime="tpu"):
    from ceph_tpu.ec import registry_instance
    return registry_instance().factory(
        "isa", {"technique": "cauchy", "k": str(k), "m": str(m),
                "runtime": runtime})


def _patterns(k, m, count):
    """Deterministic spread of erasure patterns: (chosen, targets)
    pairs with 1..m erased data chunks, parity filling in."""
    out = []
    n = k + m
    for i in range(count):
        n_erase = 1 + i % m
        erased = sorted({(i * 7 + j * 3) % k for j in range(n_erase)})
        chosen = [c for c in range(n) if c not in erased][:k]
        out.append((tuple(chosen), tuple(erased)))
    # dedup, keep order
    seen, uniq = set(), []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def _stripes(n, k, b, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, k, b), dtype=np.uint8)


# -- kernel level -------------------------------------------------------------

def test_decode_ref_matches_encode_ref_per_pattern():
    """The heterogeneous oracle degenerates to the plain one when every
    stripe shares a pattern."""
    codec = _codec(K1, M1)
    (chosen, targets) = _patterns(K1, M1, 3)[1]
    rmat = recovery_matrix(codec.generator, list(chosen), list(targets))
    data = _stripes(6, K1, B1, seed=1)
    pidx = np.zeros(6, np.int32)
    got = ec_decode_ref(rmat[None], pidx, data)
    assert (got == ec_encode_ref(rmat, data)).all()


def test_kernel_mixed_patterns_one_call_bit_exact():
    """ec_decode_batched with stripes spanning several patterns equals
    the per-stripe oracle — the batched gather+matmul is the tentpole."""
    codec = _codec(K1, M1)
    pats = _patterns(K1, M1, 4)
    t = max(len(tg) for _c, tg in pats)
    mats = []
    for chosen, targets in pats:
        r = recovery_matrix(codec.generator, list(chosen), list(targets))
        p = np.zeros((t, K1), np.uint8)
        p[:len(targets)] = r
        mats.append(p)
    tab = decode_bit_table(mats)
    rng = np.random.default_rng(2)
    data = _stripes(19, K1, B1, seed=2)
    pidx = rng.integers(0, len(pats), 19).astype(np.int32)
    got = np.asarray(ec_decode_batched(tab, pidx, data, k=K1, t=t))
    want = ec_decode_ref(np.stack(mats), pidx, data)
    assert (got == want).all()


# -- codec submit path: bit-exactness under threaded mixed patterns ----------

def test_threaded_mixed_pattern_decodes_bit_exact():
    """8 readers x 5 decodes each — random erasure pattern AND random
    stripe count per op, all through one engine: every delivered
    reconstruction equals the numpy recovery_matrix oracle for that
    reader's own pattern and data."""
    codec = _codec(K1, M1)
    pats = _patterns(K1, M1, 2 * M1)
    eng = DeviceDispatchEngine(max_delay_us=500.0,
                               stats=telemetry.DecodeDispatchStats())
    errors: list[str] = []

    def reader(rid):
        rng = np.random.default_rng(300 + rid)
        for i in range(5):
            chosen, targets = pats[int(rng.integers(0, len(pats)))]
            data = _stripes(int(rng.integers(1, 27)), K1, B1,
                            seed=rid * 100 + i)
            got = codec.submit_decode_chunks(
                eng, chosen, data, targets).result(timeout=120)
            rmat = recovery_matrix(codec.generator, list(chosen),
                                   list(targets))
            want = ec_encode_ref(rmat, data)
            if np.asarray(got).shape != want.shape:
                errors.append(f"reader {rid} op {i}: shape "
                              f"{np.asarray(got).shape} != {want.shape}")
            elif not (np.asarray(got) == want).all():
                errors.append(f"reader {rid} op {i}: mismatch "
                              f"(pattern {targets})")

    try:
        threads = [threading.Thread(target=reader, args=(r,))
                   for r in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
    finally:
        eng.stop()


def test_padded_bucket_decode_equals_unpadded():
    """Non-pow2 stripe counts, a non-pow2 pattern table, and t below
    the target bucket all pad with zeros on dispatch; delivered rows
    must equal the unpadded oracle."""
    codec = _codec(K1, M1)
    pats = _patterns(K1, M1, 3)       # 3 patterns -> table pads to 4
    stats = telemetry.DecodeDispatchStats()
    eng = DeviceDispatchEngine(stats=stats)
    try:
        for n, (chosen, targets) in zip((3, 5, 7, 11), pats + pats[:1]):
            data = _stripes(n, K1, B1, seed=n)
            got = codec.submit_decode_chunks(
                eng, chosen, data, targets).result(timeout=120)
            rmat = recovery_matrix(codec.generator, list(chosen),
                                   list(targets))
            want = ec_encode_ref(rmat, data)
            assert np.asarray(got).shape == (n, len(targets), B1)
            assert (np.asarray(got) == want).all()
        # 3->4, 5->8, 7->8, 11->16: stripe padding genuinely happened
        assert stats.padded_stripes == (1 + 3 + 1 + 5)
    finally:
        eng.stop()


# -- compile-cache bound: stripe buckets x table buckets ---------------------

def test_decode_jit_cache_bounded_by_bucket_tables():
    """30 randomized decodes over mixed sizes AND mixed patterns
    compile AT MOST one executable per (stripe bucket x table bucket)
    pair — the two-axis bound the pow-2 padding exists for.  Unbucketed,
    the same traffic would retrace per (size, pattern-count) pair."""
    from ceph_tpu.ops.gf_kernel import _decode_jit_entries
    codec = _codec(K2, M2)
    pats = _patterns(K2, M2, 2 * M2)
    eng = DeviceDispatchEngine(stats=telemetry.DecodeDispatchStats())
    rng = np.random.default_rng(5)
    sizes = [int(s) for s in rng.integers(1, 49, 30)]
    table_buckets = set()
    before = _decode_jit_entries()
    try:
        n_pat = 0
        for i, n in enumerate(sizes):
            # grow the pattern population as we go: the table crosses
            # pow-2 boundaries mid-sweep
            n_pat = min(n_pat + 1, len(pats))
            chosen, targets = pats[i % n_pat]
            out = codec.submit_decode_chunks(
                eng, chosen, _stripes(n, K2, B2, seed=i),
                targets).result(timeout=120)
            assert np.asarray(out).shape == (n, len(targets), B2)
            table_buckets.add(bucket_stripes(n_pat))
        grown = _decode_jit_entries() - before
        stripe_buckets = {bucket_stripes(n) for n in sizes}
        bound = len(stripe_buckets) * len(table_buckets)
        assert grown <= bound, \
            f"{grown} compiles for {len(stripe_buckets)} stripe x " \
            f"{len(table_buckets)} table buckets (bound {bound})"
    finally:
        eng.stop()


# -- mixed patterns share one device call ------------------------------------

def test_mixed_patterns_queued_while_busy_share_one_call():
    """Decodes with DIFFERENT erasure patterns queued behind a busy
    engine coalesce into ONE device call — the claim the per-stripe
    pattern index exists for — and the decode stats record the
    heterogeneity (patterns histogram mass above 1)."""
    codec = _codec(K1, M1)
    pats = _patterns(K1, M1, 4)
    stats = telemetry.DecodeDispatchStats()
    eng = DeviceDispatchEngine(max_delay_us=50_000.0, stats=stats)
    entered = threading.Event()
    release = threading.Event()

    def slow(a):
        entered.set()
        release.wait(5.0)
        return a

    try:
        blocker = eng.submit(("slow", 0), slow, np.zeros((1,), np.uint8))
        assert entered.wait(5.0)
        futs, wants = [], []
        for i, (chosen, targets) in enumerate(pats):
            data = _stripes(2 + i, K1, B1, seed=40 + i)
            futs.append(codec.submit_decode_chunks(
                eng, chosen, data, targets))
            rmat = recovery_matrix(codec.generator, list(chosen),
                                   list(targets))
            wants.append(ec_encode_ref(rmat, data))
        release.set()
        for f, want in zip(futs, wants):
            assert (np.asarray(f.result(timeout=120)) == want).all()
        blocker.result(timeout=10)
        assert stats.batches == 2, \
            "4 mixed-pattern decodes must share 1 device call"
        assert stats.coalesce.sum == 5          # 1 blocker + 4 decodes
        # heterogeneity lands in the ENGINE's own stats sink, and the
        # one coalesced call carried EXACTLY the 4 real patterns —
        # bucket padding (14 stripes -> 16) edge-repeats the last
        # pattern index instead of inventing pattern 0
        assert stats.patterns.count == 1
        assert stats.patterns.sum == len(pats)
        assert stats.pattern_table_size >= len(pats)
    finally:
        eng.stop()


def test_pattern_table_retires_at_cap(monkeypatch):
    """A cap-full pattern table retires wholesale into a fresh
    generation: the registry stays bounded on churning membership,
    in-flight indices stay valid (the fn captures its table object and
    the generation rides the engine key), and decodes spanning a
    retirement stay bit-exact."""
    from ceph_tpu.ec import base as ec_base
    monkeypatch.setattr(ec_base, "PATTERN_TABLE_CAP", 2)
    codec = _codec(K1, M1)
    pats = _patterns(K1, M1, 2 * M1)
    assert len(pats) > 2               # more patterns than the cap
    eng = DeviceDispatchEngine(stats=telemetry.DecodeDispatchStats())
    try:
        gens = set()
        for i, (chosen, targets) in enumerate(pats * 2):
            data = _stripes(3 + i % 4, K1, B1, seed=60 + i)
            got = codec.submit_decode_chunks(
                eng, chosen, data, targets).result(timeout=120)
            rmat = recovery_matrix(codec.generator, list(chosen),
                                   list(targets))
            assert (np.asarray(got) == ec_encode_ref(rmat, data)).all()
            tab = codec._pattern_tables[
                codec._target_bucket(len(targets))]
            assert len(tab["mats"]) <= 2
            gens.add(tab["gen"])
        assert len(gens) > 1, "cap never retired the table"
    finally:
        eng.stop()


# -- end-to-end: degraded read + recovery ride the decode engine -------------

def test_degraded_read_rides_decode_engine():
    """A cluster degraded read (shard object removed) reconstructs
    through submit_decode_chunks: returned bytes intact, the OSD
    ec_decode_submits counter moves, and the context decode engine's
    stats sink (the global DecodeDispatchStats) records the call."""
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=3, ms_type="loopback").start()
    try:
        client = c.client()
        pool = c.create_pool(client, pg_num=1, pool_type="erasure",
                             k=2, m=1)
        io = client.open_ioctx(pool)
        payload = b"decode engine payload " * 200
        io.write_full("victim", payload)
        sub0 = telemetry.decode_dispatch_stats().submits
        removed = 0
        for osd in c.osds.values():
            for cid in list(osd.store.list_collections()):
                if not cid.startswith(f"{pool}."):
                    continue
                for oid in list(osd.store.list_objects(cid)):
                    if oid == "victim:0" and removed == 0:
                        from ceph_tpu.objectstore import Transaction
                        osd.store.apply_transaction(
                            Transaction().remove(cid, oid))
                        removed = 1
        assert removed == 1
        assert io.read("victim") == payload
        assert telemetry.decode_dispatch_stats().submits > sub0, \
            "degraded read did not ride the decode engine"
        assert sum(o.perf.value("ec_decode_submits")
                   for o in c.osds.values()) > 0
    finally:
        c.stop()


def test_degraded_reads_after_osd_down_on_bluestore(tmp_path):
    """An OSD dies and the map marks it down; every object is then read
    back at depth.  Two things used to stall this: the gather asked the
    down OSD — still the shard's past-interval holder — for its chunk
    and waited forever; and the decode continuation, parked on the
    daemon lock on the engine's completion thread, kept the block-
    checksum digest of a shard read (made under that lock, through the
    same engine) from ever being delivered, until BlueStore's digest
    timeout fell back to host crc32."""
    from ceph_tpu.tools.rados_bench import ObjBencher
    from ceph_tpu.tools.vstart import MiniCluster
    c = MiniCluster(n_osds=4, ms_type="loopback", store_type="bluestore",
                    base_path=str(tmp_path)).start()
    try:
        client = c.client(timeout=30.0)
        pool = c.create_pool(client, pool_type="erasure", k=2, m=2,
                             epoch_timeout=60.0)
        # 16 blocks a shard: past bluestore_batched_read_min, so shard
        # reads verify through the engine
        size, n = 64 << 10, 8
        payload_of = lambda i: bytes([i + 1]) * size      # noqa: E731
        bench = ObjBencher(client.open_ioctx(pool), obj_size=size,
                           concurrent=4, op_timeout=20.0)
        assert bench.write_bench(60.0, payload_of, n)["errors"] == 0
        fallbacks = telemetry.bluestore_stats().dump()["csum_fallbacks"]
        c.kill_osd(2)
        rc, out = client.mon_command({"prefix": "osd down", "id": "2"})
        assert rc == 0, out
        client.wait_for_epoch(c.mon.osdmap.epoch)
        res = bench.seq_read_bench(60.0, n, payload_of, n)
        assert res["total_writes_or_reads"] == n and res["errors"] == 0
        assert res["latency_max_s"] < 15.0
        assert (telemetry.bluestore_stats().dump()["csum_fallbacks"]
                == fallbacks)
        assert sum(o.perf.value("ec_decode_submits")
                   for o in c.osds.values()) > 0
    finally:
        c.stop()
