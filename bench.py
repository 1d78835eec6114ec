"""Flagship benchmark: erasure encode + 2-erasure recovery throughput.

Mirrors the reference's `ceph_erasure_code_benchmark` workload (BASELINE.json
north-star config: k=8 m=4 cauchy, 4 KiB chunks) — the reference harness reports
elapsed seconds and KiB processed (src/test/erasure-code/
ceph_erasure_code_benchmark.cc:188,326); here the same quantity is reported as
MB/s directly, batched over many stripes per device call instead of one stripe
per call (the ECUtil stripe-loop batch point, src/osd/ECUtil.cc:136).

Timing: each measurement runs the kernel N times inside one jitted lax.scan
with a forced data dependency between iterations, fetches a scalar (which
cannot resolve until everything executed), and differences two iteration
counts to cancel dispatch/transfer overhead.  Host-clock readings vary from
run to run, so every rate reported is the MEDIAN of `reps` independent
chained-scan differences and the min..max band rides along in the JSON (keys
*_band) — a single lucky or unlucky run cannot move the headline.

The device sections measure the chip and refuse to run anywhere else: without
a TPU the program exits non-zero, and the JSON names the platform, device
kind and device count it ran on.  One process holds the chip; bench.py starts
no child process.

vs_baseline: ratio against the single-core C baseline compiled from
ceph_tpu/native/baseline.c — an ISA-L-class split-nibble SIMD GF(2^8) encode
and a scalar straw2 crush_do_rule, both bit-validated against the same oracles
the TPU kernels are (tests/test_native.py) — measured in the same run, on this
host, never carried across sessions.

CRUSH runs with non-uniform bucket weights, a skewed reweight vector, and out
OSDs — the retry-ladder-heavy case, not the easy uniform one.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Sections: the run is split into named sweeps selectable with
``--sections`` (comma list) so any ONE section completes well inside a
590 s harness timeout on slow hosts:

  ec              device EC encode/recover rates + C baseline + the
                  fenced kernel-telemetry digest
  crush           device bulk CRUSH placement rate + C baseline
  dispatch_sweep  encode-side cross-op coalescing concurrency sweep
  recovery_sweep  decode-side (heterogeneous-pattern) concurrency sweep
  map_churn       map-epoch consumption storm: scalar full-scan vs the
                  shared PG mapping service (epochs/s, per-epoch scan
                  time, changed-PG counts), bit-verified vs the oracle
  profile         pipeline-profile micro-section: a short concurrent
                  encode/decode burst + a few mapping epochs, emitting
                  the where-did-the-time-go digest (phase shares,
                  compile seconds, utilization) into the JSON
  objectstore     device-resident objectstore write path: on-disk
                  bluestore write/read MB/s scalar vs the
                  bluestore_data checksum channel, the isolated
                  csum-settle micro, and the tpu_bitplane compression
                  leg — bit-verified against the host oracles

Default (no flag) runs every section EXCEPT map_churn and profile —
byte-compatible with the historical flagship JSON; ``--sections all``
adds the opt-ins.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np


def chained_rates(step_fn, carry, n_lo: int = 8, n_hi: int = 48,
                  reps: int = 5, inner: int = 5) -> list[float]:
    """Per-step seconds samples, robust against host-side stalls.

    Host-clock noise is ADDITIVE-POSITIVE (scheduling stalls, transfer
    hiccups), so each sample differences the MIN over `inner` timed
    runs of each iteration count — min-filtering converges on the true
    time where a single-pair difference can be dominated by one stall
    (round 3's band spanned 6x; a stall pair can even produce a
    near-zero difference, i.e. an absurd rate).  lo/hi runs alternate
    so a stall burst hits both counts, not just one side, and the wide
    n_hi - n_lo spread divides whatever residue remains."""
    import jax

    @functools.partial(jax.jit, static_argnames="n")
    def loop(c, n):
        c, _ = jax.lax.scan(lambda c, _: (step_fn(c), ()), c, None, length=n)
        leaf = jax.tree_util.tree_leaves(c)[0]
        return leaf.ravel()[0]

    def timed(n):
        t0 = time.perf_counter()
        jax.device_get(loop(carry, n))
        return time.perf_counter() - t0

    jax.device_get(loop(carry, n_lo))  # compile
    jax.device_get(loop(carry, n_hi))
    for _ in range(2):                 # clock/thermal warm-up
        timed(n_hi)
    out = []
    for _ in range(reps):
        ts_lo, ts_hi = [], []
        for _ in range(inner):
            ts_lo.append(timed(n_lo))
            ts_hi.append(timed(n_hi))
        d = (min(ts_hi) - min(ts_lo)) / (n_hi - n_lo)
        # a non-positive difference is clock noise; fall back to the full
        # n_hi run amortized per step — that INCLUDES dispatch overhead, so
        # it can only understate the rate, never inflate the headline
        out.append(d if d > 2e-9 else min(ts_hi) / n_hi)
    return out


def median_band(samples: list[float]):
    """(median, lo, hi): the band is TRIMMED when there are >= 5
    samples (drop the single best and worst) — with heavy-tailed
    host noise, min/max report one outlier stall or one fluke near-zero
    difference, not the kernel.  The trim is symmetric, so it cannot
    bias the band in the flattering direction only."""
    s = sorted(samples)
    if len(s) >= 5:
        return s[len(s) // 2], s[1], s[-2]
    return s[len(s) // 2], s[0], s[-1]


def chained_seconds_per_step(step_fn, carry, n_lo: int = 8, n_hi: int = 48,
                             reps: int = 5) -> float:
    return median_band(chained_rates(step_fn, carry, n_lo, n_hi, reps))[0]


def _closed_loop_sweep(levels, total_ops: int, stats, make_submit,
                       name: str, op_bytes: int, actor_key: str,
                       snapshot=None, extra_row=None, mesh=None) -> dict:
    """Shared closed-loop concurrency harness for the dispatch sweeps
    (encode-side dispatch_sweep and decode-side recovery_sweep evolve
    in lockstep): per level, N barrier-started actors each keep ONE op
    in flight (submit, wait, repeat), and the row reports wall-clock
    MB/s, op-latency percentiles, and before/after differencing of the
    engine's scalar counters.  ``make_submit(engine)`` returns
    ``submit(actor_id, i) -> future``; ``snapshot(stats)``/
    ``extra_row(before, stats, calls, n_ops)`` add sweep-specific
    columns."""
    import threading

    from ceph_tpu.ops.dispatch import DeviceDispatchEngine

    out = {}
    for conc in levels:
        ops_per_actor = max(3, total_ops // conc)
        eng = DeviceDispatchEngine(name=f"{name}-c{conc}", stats=stats,
                                   mesh=mesh)
        submit = make_submit(eng)
        lats: list[float] = []
        lat_lock = threading.Lock()
        start = threading.Barrier(conc + 1)

        def actor(aid):
            start.wait()
            mine = []
            for i in range(ops_per_actor):
                t0 = time.perf_counter()
                submit(aid, i).result(timeout=120)
                mine.append(time.perf_counter() - t0)
            with lat_lock:
                lats.extend(mine)

        threads = [threading.Thread(target=actor, args=(a,),
                                    daemon=True)
                   for a in range(conc)]
        for t in threads:
            t.start()
        sub0, bat0 = stats.submits, stats.batches
        before = snapshot(stats) if snapshot is not None else None
        start.wait()           # release every actor at once
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        eng.stop()
        n_ops = conc * ops_per_actor
        calls = stats.batches - bat0
        row = {
            actor_key: conc,
            "ops": n_ops,
            "mbps": round(n_ops * op_bytes / wall / 1e6, 1),
            "p99_op_ms": round(
                float(np.percentile(lats, 99)) * 1e3, 3),
            "median_op_ms": round(
                float(np.percentile(lats, 50)) * 1e3, 3),
            "mean_coalesce": (round((stats.submits - sub0) / calls, 2)
                              if calls else 0.0),
            "device_calls_per_1k_ops": (round(1000.0 * calls / n_ops, 1)
                                        if n_ops else 0.0),
        }
        if extra_row is not None:
            row.update(extra_row(before, stats, calls, n_ops))
        out[str(conc)] = row
    return out


def dispatch_sweep(encode, k: int, chunk: int,
                   levels=(1, 4, 16, 64), op_stripes: int = 32,
                   total_ops: int = 96, coding=None) -> dict:
    """Offered-concurrency sweep through the cross-op coalescing
    engine (ops.dispatch): N closed-loop writers each submit one
    op-sized encode at a time and wait for its parity, exactly the OSD
    EC write path's submit-and-continue shape.  Reports end-to-end
    MB/s and p99 op latency per level plus the engine's own coalesce
    metrics — the amortization story is "MB/s climbs with writers
    while device calls per op falls".  All levels feed the global
    DispatchStats sink, so the process-wide `dispatch` digest in the
    JSON covers the whole sweep; per-level factors difference the
    scalar counters around each level.

    Mesh column: the per-level rows above run single-device engines
    (the ``kernel_mesh_devices=1`` number); with ``coding`` and a
    multi-device backend, ONE extra run at the top writer level uses a
    MESH-sharded engine (batch fans out across every local device) and
    lands in ``mesh_devices`` / ``encode_mbps_mesh`` /
    ``mesh_sharded_flushes``."""
    from ceph_tpu.ops import telemetry

    rng = np.random.default_rng(7)
    op = rng.integers(0, 256, (op_stripes, k, chunk), dtype=np.uint8)
    key = ("bench_ec", k, chunk)

    def make_submit(eng):
        return lambda _aid, _i: eng.submit(key, encode, op)

    out = _closed_loop_sweep(levels, total_ops,
                             telemetry.dispatch_stats(), make_submit,
                             "bench", op.nbytes, "writers")
    import jax
    n_dev = len(jax.devices())
    out["mesh_devices"] = n_dev
    if coding is not None and n_dev > 1:
        from ceph_tpu.ops.gf_kernel import make_encoder
        from ceph_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(n_dev)
        mesh_encode = make_encoder(coding, mesh=mesh)
        mesh_stats = telemetry.DispatchStats()   # private sink: the
        # global digest stays the single-device sweep's story
        conc = max(levels)
        row = _closed_loop_sweep(
            (conc,), total_ops, mesh_stats,
            lambda eng: (lambda _aid, _i: eng.submit(
                key, mesh_encode, op)),
            "bench-mesh", op.nbytes, "writers", mesh=mesh)[str(conc)]
        out["encode_mbps_mesh"] = row["mbps"]
        out["mesh_sharded_flushes"] = mesh_stats.sharded_flushes
        out["mesh_mean_devices"] = mesh_stats.summary()["mean_devices"]
    return out


def recovery_sweep(k: int, m: int, chunk: int, levels=(1, 4, 16),
                   op_stripes: int = 32, total_ops: int = 48) -> dict:
    """Degraded-read/recovery concurrency sweep through the DECODE
    dispatch engine: N closed-loop readers each submit one op-sized
    reconstruction at a time — every op missing 2 chunks, with the
    erasure PATTERN rotating per reader and per op — exactly the OSD
    degraded-read/recovery-pull shape.  The point over the encode-side
    dispatch_sweep: decodes with DIFFERENT recovery matrices still
    coalesce (heterogeneous-matrix batched kernel, pattern index per
    stripe), so MB/s climbs with readers while device calls per op and
    single-pattern batches both fall.  All levels feed the global
    DecodeDispatchStats sink; per-level factors difference the scalar
    counters around each level."""
    from ceph_tpu.ec import registry_instance
    from ceph_tpu.ops import telemetry

    codec = registry_instance().factory(
        "isa", {"technique": "cauchy", "k": str(k), "m": str(m)})
    # 2-erasure patterns over the data chunks (the recovery case that
    # exercises distinct matrices): rotate through a handful
    patterns = []
    for e0 in range(min(k, 4)):
        e1 = (e0 + 1 + e0 % 2) % k
        erased = tuple(sorted({e0, e1}))
        if len(erased) < 2:
            continue
        chosen = [c for c in range(k + m) if c not in erased][:k]
        patterns.append((tuple(chosen), erased))
    rng = np.random.default_rng(11)
    op = rng.integers(0, 256, (op_stripes, k, chunk), dtype=np.uint8)

    def make_submit(eng):
        def submit(rid, i):
            chosen, targets = patterns[(rid + i) % len(patterns)]
            return codec.submit_decode_chunks(eng, chosen, op, targets)
        return submit

    def snapshot(st):
        return (st.patterns.count, st.patterns.sum)

    def extra_row(before, st, _calls, _n_ops):
        pat_n = st.patterns.count - before[0]
        return {"erasures": 2,
                "mean_patterns_per_call": (
                    round((st.patterns.sum - before[1]) / pat_n, 2)
                    if pat_n else 0.0)}

    return _closed_loop_sweep(levels, total_ops,
                              telemetry.decode_dispatch_stats(),
                              make_submit, "bench-rec", op.nbytes,
                              "readers", snapshot=snapshot,
                              extra_row=extra_row)


def map_churn(pools: int = 6, pg_num: int = 1024, hosts: int = 16,
              per_host: int = 4, epochs: int = 10) -> dict:
    """Map-epoch consumption sweep: a reweight/mark-down/override storm
    over many pools, comparing the seed's scalar full scan (every PG
    through pg_to_up_acting_osds on every epoch) against the shared
    mapping service (incremental pool recompute + on-device diff +
    O(changed) reads).  Every epoch's shared-cache reads are verified
    bit-identical to the scalar oracle across ALL PGs — the timing rows
    only count the work each consumption strategy actually does.

    Fused column: the primary ``shared_epoch_s`` row now runs the
    FUSED device ladder (PR 10 — packed up/acting tables, fused-output
    epoch diff, row-slice reads); an extra replay with
    ``osdmap_mapping_fused`` off reports the PR 5 host-tail cost as
    ``shared_epoch_s_unfused`` and the ``fused_speedup`` ratio — the
    ISSUE 10 acceptance number.  The default scale moved 1536 -> 6144
    PGs with this PR (ROADMAP item 3 direction): at toy scale the
    per-candidate host tail was already cheap; the fused ladder's win
    is that epoch cost stays flat while changed-PG counts grow.

    Mesh column: a THIRD consumption strategy rides a context-backed
    service whose pool remaps submit through the (mesh-sharded when the
    backend is multi-device) dispatch engine and whose on-device epoch
    diff shards over the kernel mesh — ``shared_epoch_s_mesh`` /
    ``mesh_devices``; ``mesh_devices`` 1 means one device and the row
    measures the engine path alone.  The mesh pass runs as a SEPARATE
    replay over the same recorded epoch sequence, after the ``mapping``
    digest is captured, so both the plain ``shared_epoch_s`` row and
    the digest stay comparable with the historical JSON."""
    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.crush import build_two_level_map
    from ceph_tpu.osd import OSDMap, PGPool, SharedPGMappingService

    crush, _root, rule = build_two_level_map(hosts, per_host)
    n = hosts * per_host
    m = OSDMap(crush=crush, epoch=2)
    m.set_max_osd(n)
    for o in range(n):
        m.mark_up(o)
    for p in range(1, pools + 1):
        m.pools[p] = PGPool(pool_id=p, size=3, crush_rule=rule,
                            pg_num=pg_num)
    base = m
    svc = SharedPGMappingService()
    svc.update_to(m)    # epoch 0->2: full build (+ kernel compile)
    rng = np.random.default_rng(5)
    t_shared: list[float] = []
    t_scalar: list[float] = []
    changed_counts: list[int] = []
    verified = True
    epoch_log: list[tuple[int, object, dict]] = []  # (from, map, oracle)
    for i in range(epochs):
        new = m.copy()
        new.epoch = m.epoch + 1
        kind = i % 5
        osd = int(rng.integers(0, n))
        if kind == 0:      # reweight storm step (pools recompute)
            for o in rng.integers(0, n, 4):
                new.osd_weight[int(o)] = int(rng.choice(
                    (0x4000, 0x8000, 0xC000, 0x10000)))
        elif kind == 1:    # host failure: a whole failure domain goes
            host = int(rng.integers(0, hosts))   # down (state-only:
            for o in range(host * per_host,      # tables reuse, many
                           (host + 1) * per_host):   # PGs remap)
                new.osd_state[o] = new.osd_state[o] & ~2
        elif kind == 2:    # a host comes back
            host = int(rng.integers(0, hosts))
            for o in range(host * per_host, (host + 1) * per_host):
                new.osd_state[o] = new.osd_state[o] | 3
        elif kind == 3:    # pg_temp inject/clear burst (override-only)
            for _ in range(4):
                pgid = (1 + int(rng.integers(0, pools)),
                        int(rng.integers(0, pg_num)))
                if pgid in new.pg_temp:
                    del new.pg_temp[pgid]
                else:
                    new.pg_temp[pgid] = [osd, (osd + 1) % n]
        else:              # mark out / back in (weight edge)
            for o in rng.integers(0, n, 2):
                new.osd_weight[int(o)] = (
                    0x10000 if new.osd_weight[int(o)] == 0 else 0)
        # shared-cache consumption: epoch update + reading every
        # changed PG (what _scan_pgs does beyond its local PGs)
        t0 = time.perf_counter()
        upd = svc.update_to(new, from_epoch=m.epoch)
        reads = (upd.changed if not upd.full
                 else [(pid, pg) for pid, pool in new.pools.items()
                       for pg in range(pool.pg_num)])
        for pid, pg in reads:
            svc.lookup(new, pid, pg)
        t_shared.append(time.perf_counter() - t0)
        changed_counts.append(len(reads))
        # scalar baseline: the seed's full per-epoch scan
        t0 = time.perf_counter()
        oracle = {(pid, pg): new.pg_to_up_acting_osds(pid, pg)
                  for pid, pool in new.pools.items()
                  for pg in range(pool.pg_num)}
        t_scalar.append(time.perf_counter() - t0)
        # bit-identical acceptance gate, over EVERY pg
        for (pid, pg), want in oracle.items():
            if svc.lookup(new, pid, pg) != want:
                verified = False
        epoch_log.append((m.epoch, new, oracle))
        m = new
    from ceph_tpu.ops import telemetry
    # capture the digest BEFORE the mesh replay: it then describes
    # exactly the engine-less service's work, byte-comparable with
    # pre-mesh runs (the global mapping stats sink is shared)
    digest = telemetry.mapping_summary()
    # the bit-verify gate above reads EVERY pg per epoch through the
    # same global stats — those lookup counters describe the gate, not
    # the timed consumption loop, so report the timed reads instead
    digest.pop("lookups", None)
    digest.pop("lookup_fallbacks", None)
    digest["timed_reads"] = int(sum(changed_counts))
    # unfused replay of the SAME epoch sequence (the PR 5 host-tail
    # consumption path): same cache machinery, per-candidate
    # _finish_from delta + host-tail lookups — the A/B for the fused
    # ladder the primary rows above ran.  Timing only: the fused run
    # already bit-verified every epoch against the oracle.
    svc_uf = SharedPGMappingService(fused=False)
    svc_uf.update_to(base)
    t_unfused: list[float] = []
    for frm, new, _oracle in epoch_log:
        t0 = time.perf_counter()
        upd_u = svc_uf.update_to(new, from_epoch=frm)
        reads_u = (upd_u.changed if not upd_u.full
                   else [(pid, pg) for pid, pool in new.pools.items()
                         for pg in range(pool.pg_num)])
        for pid, pg in reads_u:
            svc_uf.lookup(new, pid, pg)
        t_unfused.append(time.perf_counter() - t0)
    # mesh/engine-backed replay of the SAME epoch sequence.  The
    # min-pgs floor would route this workload's pool sizes to the
    # scalar rebuild path (engine never touched — the column would
    # measure nothing); zero it so recomputed pools really submit
    # through the (mesh-sharded when multi-device) dispatch engine.
    mesh_ctx = CephTpuContext("bench-map-mesh")
    mesh_ctx.conf.set("osdmap_mapping_min_pgs", 0)
    svc_mesh = SharedPGMappingService(mesh_ctx)
    svc_mesh.update_to(base)
    t_mesh: list[float] = []
    for frm, new, oracle in epoch_log:
        t0 = time.perf_counter()
        upd_m = svc_mesh.update_to(new, from_epoch=frm)
        reads_m = (upd_m.changed if not upd_m.full
                   else [(pid, pg) for pid, pool in new.pools.items()
                         for pg in range(pool.pg_num)])
        for pid, pg in reads_m:
            svc_mesh.lookup(new, pid, pg)
        t_mesh.append(time.perf_counter() - t0)
        for (pid, pg), want in oracle.items():
            if svc_mesh.lookup(new, pid, pg) != want:
                verified = False
    # mesh_devices is EVIDENCE, not aspiration: read the placement the
    # replay's engine actually used (1 = the engine path ran without a
    # mesh — single-device backend or mesh build failure)
    mesh_devices = 1
    if mesh_ctx._dispatch is not None:
        pm = mesh_ctx._dispatch.placement_mesh()
        if pm is not None:
            mesh_devices = int(pm.size)
    # the mesh context's engines were lazily built for this section
    # only: drain and stop their threads instead of leaking them for
    # the rest of the bench process
    for eng in (mesh_ctx._dispatch, mesh_ctx._decode_dispatch):
        if eng is not None:
            eng.stop()
    med = (lambda xs: sorted(xs)[len(xs) // 2])
    sh, sc = med(t_shared), med(t_scalar)
    shm = med(t_mesh)
    shu = med(t_unfused)
    return {
        "pgs": pools * pg_num,
        "osds": n,
        "epochs": epochs,
        "scalar_epoch_s": round(sc, 4),
        "shared_epoch_s": round(sh, 4),
        "shared_epoch_s_unfused": round(shu, 4),
        "shared_epoch_s_mesh": round(shm, 4),
        "mesh_devices": mesh_devices,
        "speedup": round(sc / sh, 1) if sh > 0 else 0.0,
        "fused_speedup": round(shu / sh, 2) if sh > 0 else 0.0,
        "speedup_mesh": round(sc / shm, 1) if shm > 0 else 0.0,
        "scalar_epochs_per_s": round(1.0 / sc, 2) if sc > 0 else 0.0,
        "shared_epochs_per_s": round(1.0 / sh, 2) if sh > 0 else 0.0,
        "mean_changed_pgs": round(sum(changed_counts)
                                  / len(changed_counts), 1),
        "verified": verified,
        "mapping": digest,
    }


def profile_section(k: int = 8, m: int = 4, chunk: int = 1024,
                    writers: int = 4, ops_each: int = 10,
                    epochs: int = 4) -> dict:
    """Pipeline-profile micro-section: a short burst of concurrent
    encodes + heterogeneous decodes through context-backed dispatch
    engines and a few mapping epochs, then the profiler digest — the
    bench JSON gains the same where-did-the-time-go attribution
    (phase shares, compile seconds, utilization, mapping phase split)
    an operator reads from ``dump_pipeline_profile`` on a live
    daemon.  Deliberately tiny: it exists to capture phase SHARES per
    bench round, not to be a throughput sweep."""
    import threading

    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.crush import build_two_level_map
    from ceph_tpu.ec import registry_instance
    from ceph_tpu.ops import telemetry
    from ceph_tpu.osd import OSDMap, PGPool, SharedPGMappingService

    # the phase ledgers are process-global and earlier sections'
    # engines feed them: clear so the digest describes THIS section's
    # burst (shares, first-call compile events, utilization window),
    # not the whole run.  Runs last in main(), after every other
    # section's digest is already captured into the JSON.
    telemetry.dispatch_stats().phases.clear()
    telemetry.decode_dispatch_stats().phases.clear()
    telemetry.mapping_stats().clear()
    codec = registry_instance().factory(
        "isa", {"technique": "cauchy", "k": str(k), "m": str(m)})
    ctx = CephTpuContext("bench-profile")
    eng = ctx.dispatch_engine()
    deng = ctx.decode_dispatch_engine()
    rng = np.random.default_rng(13)
    op = rng.integers(0, 256, (32, k, chunk), dtype=np.uint8)
    patterns = []
    for e0 in range(min(k, 3)):
        erased = (e0, (e0 + 2) % k)
        erased = tuple(sorted(set(erased)))
        chosen = [c for c in range(k + m) if c not in erased][:k]
        patterns.append((tuple(chosen), erased))
    start = threading.Barrier(writers + 1)

    def actor(aid):
        start.wait()
        for i in range(ops_each):
            codec.submit_chunks(eng, op).result(timeout=120)
            if i % 2 == 0:
                chosen, targets = patterns[(aid + i) % len(patterns)]
                codec.submit_decode_chunks(
                    deng, chosen, op, targets).result(timeout=120)

    threads = [threading.Thread(target=actor, args=(a,), daemon=True)
               for a in range(writers)]
    for t in threads:
        t.start()
    start.wait()
    for t in threads:
        t.join()
    eng.flush()
    deng.flush()
    # a few mapping epochs so the digest's mapping phase split is live
    crush, _root, rule = build_two_level_map(4, 2)
    mp = OSDMap(crush=crush, epoch=2)
    mp.set_max_osd(8)
    for o in range(8):
        mp.mark_up(o)
    for p in (1, 2):
        mp.pools[p] = PGPool(pool_id=p, size=3, crush_rule=rule,
                             pg_num=64)
    svc = SharedPGMappingService()
    svc.update_to(mp)
    for i in range(epochs):
        new = mp.copy()
        new.epoch = mp.epoch + 1
        new.osd_weight[i % 8] = 0x8000 if i % 2 else 0x10000
        svc.update_to(new)
        mp = new
    for e in (eng, deng):
        e.stop()
    return telemetry.pipeline_profile_digest()


def placement_digest(crush_map, rid: int, bm, reweight: np.ndarray,
                     t_crush: float, n_pgs: int, numrep: int = 3,
                     sample: int = 2048) -> dict:
    """Fused-pipeline placement digest for the crush section: the full
    raw→up→acting ladder (ops.placement_kernel) over all ``n_pgs`` PGs
    of a 10k-OSD map in one device call — affinity skew, temps and
    upmap pairs injected so every ladder stage does real work — vs the
    per-PG host pipeline tail it replaces.  ``pipeline_mpps`` composes
    the measured raw rate (``t_crush`` per batch) with the ladder;
    a ``sample`` of rows is bit-verified against the host tail."""
    import jax.numpy as jnp

    from ceph_tpu.ops import placement_kernel as pk
    from ceph_tpu.osd import OSDMap, PGPool
    from ceph_tpu.osd.mapping import _finish_from, pps_batch

    n_osds = len(reweight)
    m = OSDMap(crush=crush_map, epoch=2)
    m.set_max_osd(n_osds)
    for o in range(n_osds):
        m.osd_state[o] = 3                      # exists | up
        m.osd_weight[o] = int(reweight[o])
    orng = np.random.default_rng(9)
    for o in orng.integers(0, n_osds, 500):     # 5%-ish affinity skew
        m.osd_primary_affinity[int(o)] = 0x8000
    pool = PGPool(pool_id=1, size=numrep, crush_rule=rid, pg_num=n_pgs)
    m.pools[1] = pool
    for pg in orng.integers(0, n_pgs, 512):
        m.pg_temp[(1, int(pg))] = [int(x) for x in
                                   orng.integers(0, n_osds, numrep)]
    for pg in orng.integers(0, n_pgs, 512):
        frm = int(orng.integers(0, n_osds))
        m.pg_upmap_items[(1, int(pg))] = [(frm, (frm + 7) % n_osds)]
    for pg in orng.integers(0, n_pgs, 256):
        m.primary_temp[(1, int(pg))] = int(orng.integers(0, n_osds))

    pgids = np.arange(n_pgs, dtype=np.uint32)
    pps = np.asarray(pps_batch(pool, pgids))
    raw = np.asarray(bm.do_rule(rid, jnp.asarray(pps), numrep,
                                jnp.asarray(reweight)), dtype=np.int32)
    width, pairs = pk.pool_widths(m)
    ops_ = pk.build_operands(m, 1, pool, raw, pps, width=width,
                             pairs=pairs)

    def make_step():
        from ceph_tpu.ops.placement_kernel import _ladder_jit
        fn = _ladder_jit(ops_.erasure)
        aux = tuple(jnp.asarray(a) for a in ops_.aux())
        vecs = (jnp.asarray(ops_.state), jnp.asarray(ops_.weight),
                jnp.asarray(ops_.affinity))

        def step(r):
            packed = fn(r, *aux, *vecs)
            return r.at[0, 0].set(packed[0, 0] ^ r[0, 0])
        return step

    # lean counts: the ladder is one fused call per step and the crush
    # section is already the longest on slow hosts
    t_ladder, _lo, _hi = median_band(chained_rates(
        make_step(), jnp.asarray(raw), n_lo=2, n_hi=12, reps=3,
        inner=3))

    # host-tail baseline on a sample (the per-PG _finish_from the
    # ladder replaces), and the bit-exactness gate on the same rows
    packed = pk.run_ladder(ops_)
    raw_tab, pps_tab = {1: raw}, {1: pps}
    idx = orng.integers(0, n_pgs, sample)
    t0 = time.perf_counter()
    wants = [_finish_from(m, pool, 1, int(pg), raw_tab, pps_tab)
             for pg in idx]
    t_tail = (time.perf_counter() - t0) / sample
    verified = all(
        pk.unpack_row(packed[int(pg)], width) == want
        for pg, want in zip(idx, wants))
    ladder_mpps = n_pgs / t_ladder / 1e6
    return {
        "pgs": n_pgs,
        "osds": n_osds,
        "ladder_mpps": round(ladder_mpps, 3),
        "pipeline_mpps": round(n_pgs / (t_crush + t_ladder) / 1e6, 3),
        "host_tail_mpps": round(1.0 / t_tail / 1e6, 4),
        "ladder_vs_host_tail": round(ladder_mpps * 1e6 * t_tail, 1),
        "verified": verified,
    }


SECTIONS = ("ec", "crush", "dispatch_sweep", "recovery_sweep",
            "map_churn", "profile", "qos", "scrub", "objectstore")
#: the historical flagship run (map_churn is opt-in: it is a
#: consumption-path sweep, not a device-kernel headline)
DEFAULT_SECTIONS = ("ec", "crush", "dispatch_sweep", "recovery_sweep")
#: sections that never reach a device kernel (a host-only queue model)
HOST_SECTIONS = frozenset({"qos"})


def _tenant_queue_rates(profiles, pump_threads, *, service_s,
                        warmup_s, measure_s, qos_on=True,
                        extra_pumps=()):
    """Shared closed-loop tenant-pump harness for the queue-level QoS
    sweeps (qos_section and scrub_section both drive it — ONE copy,
    so the 4-tenant scenario cannot drift between them).  Pumps run
    closed-loop against one ShardedOpQueue whose handler has a FIXED
    per-op service time (capacity = 1/service_s with one shard
    worker); ``extra_pumps`` adds (name, klass, threads) pump sets
    (the scrub storm) on top of the tenant lanes.  Returns
    (rates, wait_p99) keyed by pump name."""
    import threading as _th

    from ceph_tpu.osd.op_queue import ClassInfo, ShardedOpQueue

    lock = _th.Lock()
    names = list(pump_threads) + [n for n, _k, _t in extra_pumps]
    counts = {n: 0 for n in names}
    waits: dict = {n: [] for n in names}

    def handler(klass, item, served=None):
        time.sleep(service_s)
        name, sem = item
        with lock:
            counts[name] += 1
            if served is not None:
                waits[name].append(served[1])
        sem.release()

    wq = ShardedOpQueue(
        handler, n_shards=1, name="bench-tenants",
        client_template=ClassInfo(weight=100.0),
        client_profiles={f"client.{t}": p
                         for t, p in profiles.items()}
        if qos_on else None)
    stop = _th.Event()

    def pump(name, klass):
        sem = _th.Semaphore(0)
        while not stop.is_set():
            wq.enqueue(name, klass, (name, sem))
            sem.acquire()

    specs = [(t, f"client.{t}" if qos_on else "client", n)
             for t, n in pump_threads.items()]
    specs += list(extra_pumps)
    threads = [_th.Thread(target=pump, args=(n, k), daemon=True)
               for n, k, cnt in specs for _ in range(cnt)]
    for t in threads:
        t.start()
    time.sleep(warmup_s)
    with lock:
        base = dict(counts)
        for v in waits.values():
            v.clear()
    t0 = time.perf_counter()
    time.sleep(measure_s)
    with lock:
        snap = {n: counts[n] - base[n] for n in names}
        wsnap = {n: sorted(waits[n]) for n in names}
    elapsed = time.perf_counter() - t0
    stop.set()
    wq.shutdown()
    rates = {n: c / elapsed for n, c in snap.items()}
    p99 = {n: (w[int(0.99 * (len(w) - 1))] if w else 0.0)
           for n, w in wsnap.items()}
    return rates, p99


def _tenant_device_burst(tenants, ops_each: int = 3, k: int = 4,
                         m: int = 2, chunk: int = 512) -> dict:
    """Tiny tagged-submit burst through a context-backed dispatch
    engine: each tenant's encode batches carry a ``cost_tag``, plus
    one scrub-style batch riding as background_best_effort, so the
    qos section's JSON gains the same tenant device-time ledger
    digest the mgr ships in the MMgrReport tail."""
    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.ec import registry_instance
    from ceph_tpu.ops import telemetry
    from ceph_tpu.ops.dispatch import BACKGROUND_BEST_EFFORT

    # the ledger is process-global and earlier sections' engines feed
    # it untagged: clear so the digest attributes THIS burst
    telemetry.tenant_stats().clear()
    codec = registry_instance().factory(
        "isa", {"technique": "cauchy", "k": str(k), "m": str(m)})
    ctx = CephTpuContext("bench-qos-tenants")
    eng = ctx.dispatch_engine()
    rng = np.random.default_rng(7)
    op = rng.integers(0, 256, (8, k, chunk), dtype=np.uint8)
    futs = []
    for tenant in tenants:
        futs.extend(codec.submit_chunks(eng, op,
                                        cost_tag=(tenant, "client"))
                    for _ in range(ops_each))
    futs.append(codec.submit_chunks(
        eng, op,
        cost_tag=(BACKGROUND_BEST_EFFORT, BACKGROUND_BEST_EFFORT)))
    for f in futs:
        f.result(timeout=120)
    eng.flush()
    eng.stop()
    return telemetry.tenant_usage_digest()


def qos_section(measure_s: float = 2.5, warmup_s: float = 0.8,
                service_s: float = 0.002) -> dict:
    """Multi-tenant dmClock fairness sweep (--sections qos; validated
    standalone — the full bench exceeds the 590 s budget on this host).

    Four tenants drive one sharded op queue whose handler has a FIXED
    per-op service time (capacity = 1/service_s with one shard
    worker): a hog (weight 8) floods, gold holds a 100 ops/s
    reservation, silver (weight 2) shares the excess, bronze is capped
    at 50 ops/s.  The sweep runs twice — dmclock lanes with profiles
    vs one aggregate FIFO class (QoS off = the seed's arbitration) —
    and reports per-tenant throughput + queue-wait p99, the
    reservation attainment, the limit overshoot, and the hog:silver
    excess ratio vs the configured 4.0.  A tagged device burst then
    captures the tenant device-time ledger digest under
    ``tenant_usage`` (renderable by tools/profile_report.py)."""
    from ceph_tpu.osd.op_queue import ClassInfo

    profiles = {
        "hog": ClassInfo(weight=8.0),
        "gold": ClassInfo(reservation=100.0, weight=0.01),
        "silver": ClassInfo(weight=2.0),
        "bronze": ClassInfo(weight=8.0, limit=50.0),
    }
    pumps = {"hog": 8, "gold": 3, "silver": 4, "bronze": 4}

    def run(qos_on: bool) -> dict:
        rates, p99 = _tenant_queue_rates(
            profiles, pumps, service_s=service_s, warmup_s=warmup_s,
            measure_s=measure_s, qos_on=qos_on)
        return {"tenant_ops_s": {t: round(r, 1)
                                 for t, r in rates.items()},
                "tenant_wait_p99_s": {t: round(v, 4)
                                      for t, v in p99.items()},
                "_rates": rates}

    qos = run(qos_on=True)
    fifo = run(qos_on=False)
    r = qos.pop("_rates")
    rf = fifo.pop("_rates")
    hog_silver = r["hog"] / max(r["silver"], 1e-9)
    return {
        "capacity_ops_s": round(1.0 / service_s, 1),
        "profiles": {t: {"reservation": p.reservation,
                         "weight": p.weight, "limit": p.limit}
                     for t, p in profiles.items()},
        "qos": qos,
        "fifo": fifo,
        "reservation_attainment": round(r["gold"] / 100.0, 3),
        "reservation_attainment_fifo": round(rf["gold"] / 100.0, 3),
        "limit_overshoot": round(r["bronze"] / 50.0, 3),
        "excess_ratio_hog_silver": round(hog_silver, 2),
        "excess_ratio_configured": 4.0,
        "tenant_usage": _tenant_device_burst(list(profiles)),
    }


def scrub_section(n_objects: int = 384, obj_bytes: int = 8192,
                  measure_s: float = 2.0, warmup_s: float = 0.6,
                  service_s: float = 0.002) -> dict:
    """Background-integrity sweep (--sections scrub; validated
    standalone — the full bench exceeds the 590 s budget on this
    host).  Two sub-sweeps:

    (a) digest throughput: a PG-sized object population digested by
        the seed's scalar shard_crc loop vs the batched scrub_digest
        channel through a private dispatch engine (objects/s + MB/s,
        bit-verified against each other);

    (b) tenant reservation attainment with and without the background
        class: the qos_section's 4-tenant queue with a continuous
        scrub pump added — scrub ops riding background_best_effort vs
        jammed into the aggregate client class vs scrub off — so the
        number the fairness gate watches (gold's attainment under a
        scrub storm, relative to the scrub-off baseline) prices the
        QoS lane directly."""
    from ceph_tpu.ops.dispatch import (
        DeviceDispatchEngine, submit_scrub_digest)
    from ceph_tpu.ops.telemetry import DispatchStats
    from ceph_tpu.osd.ec_util import shard_crc
    from ceph_tpu.osd.op_queue import ClassInfo

    rng = np.random.default_rng(11)
    sizes = rng.integers(obj_bytes // 2, obj_bytes, n_objects)
    blobs = [rng.integers(0, 256, int(s), dtype=np.uint8).tobytes()
             for s in sizes]
    total_bytes = int(sizes.sum())

    # scalar: the seed's per-object host loop
    t_scalar = float("inf")
    scalar_crcs = None
    for _ in range(3):
        t0 = time.perf_counter()
        scalar_crcs = [shard_crc(b) for b in blobs]
        t_scalar = min(t_scalar, time.perf_counter() - t0)

    # batched: PG-sized groups through one private engine (the groups
    # coalesce on the shared width bucket, exactly like concurrent
    # PG scrubs in the OSD)
    group = 64
    eng = DeviceDispatchEngine(name="bench-scrub",
                               stats=DispatchStats())
    try:
        futs = [submit_scrub_digest(
            eng, blobs[i:i + group])
            for i in range(0, len(blobs), group)]
        for f in futs:
            f.result(timeout=120.0)       # jit warmup outside timing
        t_batched = float("inf")
        digs = None
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [submit_scrub_digest(eng, blobs[i:i + group])
                    for i in range(0, len(blobs), group)]
            digs = np.concatenate(
                [np.asarray(f.result(timeout=120.0)) for f in futs])
            t_batched = min(t_batched, time.perf_counter() - t0)
        verified = all(int(digs[i, 0]) == scalar_crcs[i]
                       for i in range(len(blobs)))
        eng_summary = eng.stats.summary()
    finally:
        eng.stop()

    digest = {
        "objects": n_objects,
        "mbytes": round(total_bytes / 1e6, 2),
        "scalar_objects_s": round(n_objects / t_scalar, 1),
        "scalar_mbps": round(total_bytes / t_scalar / 1e6, 1),
        "batched_objects_s": round(n_objects / t_batched, 1),
        "batched_mbps": round(total_bytes / t_batched / 1e6, 1),
        "batched_vs_scalar": round(t_scalar / t_batched, 2),
        "mean_coalesce": eng_summary["mean_coalesce"],
        "verified": verified,
    }

    # -- (b) reservation attainment with/without the background class
    profiles = {
        "hog": ClassInfo(weight=8.0),
        "gold": ClassInfo(reservation=100.0, weight=0.01),
        "silver": ClassInfo(weight=2.0),
        "bronze": ClassInfo(weight=8.0, limit=50.0),
    }
    pumps = {"hog": 8, "gold": 3, "silver": 4, "bronze": 4}

    def run(scrub_class: str | None) -> dict:
        extra = (() if scrub_class is None
                 else (("_scrub", scrub_class, 4),))
        rates, _p99 = _tenant_queue_rates(
            profiles, pumps, service_s=service_s, warmup_s=warmup_s,
            measure_s=measure_s, extra_pumps=extra)
        rates.setdefault("_scrub", 0.0)
        return rates

    off = run(None)
    bg = run("background_best_effort")
    fg = run("client")    # scrub jammed into the aggregate client lane
    fairness = {
        "capacity_ops_s": round(1.0 / service_s, 1),
        "gold_reservation": 100.0,
        "attainment_scrub_off": round(off["gold"] / 100.0, 3),
        "attainment_background": round(bg["gold"] / 100.0, 3),
        "attainment_client_class": round(fg["gold"] / 100.0, 3),
        "attainment_vs_off": round(
            bg["gold"] / max(off["gold"], 1e-9), 3),
        "scrub_ops_s_background": round(bg["_scrub"], 1),
        "scrub_ops_s_client_class": round(fg["_scrub"], 1),
    }
    return {"digest": digest, "fairness": fairness}


def objectstore_section(n_objects: int = 96,
                        obj_bytes: int = 65536) -> dict:
    """Device-resident objectstore write path (--sections
    objectstore; validated standalone).  Three sub-sweeps over a real
    on-disk BlueStoreLite:

    (a) write+read MB/s: the seed's scalar per-block ``zlib.crc32``
        store vs one whose commits settle checksums through the
        ``bluestore_data`` channel (batched reads verify through the
        same channel); every committed checksum in the batched store
        is re-verified against host zlib.crc32 of the stored bytes,
        and every read is byte-compared against the written payloads;

    (b) csum settle micro: the channel's digest call vs the host crc32
        loop over identical staged payloads — the isolated quantity
        the channel accelerates, free of fsync/KV noise;

    (c) compression-on head-to-head: the seed scalar path with the
        registry's host zlib plugin vs the device store with
        tpu_bitplane (plane extraction batched per commit), same
        6-bit payloads, ``compression_mode=force`` both sides —
        write+read MB/s, stored-byte ratios, round-trip and csum
        verification.  Read-side channel verification is priced by
        (a)/(b); here it is disabled so the leg isolates the
        compressor comparison."""
    import os as _os
    import shutil as _shutil
    import tempfile
    import zlib as _zlib

    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.objectstore.bluestore import (
        BLOCK, BlueStoreLite)
    from ceph_tpu.objectstore.transaction import Transaction

    rng = np.random.default_rng(23)
    # 6-bit payloads: two provably-zero bit planes, so the bitplane
    # leg clearly clears the required-ratio gate; the csum legs are
    # content-agnostic
    payloads = [rng.integers(0, 64, obj_bytes,
                             dtype=np.uint8).tobytes()
                for _ in range(n_objects)]
    total = n_objects * obj_bytes
    group = 8   # objects per transaction -> blocks per digest batch

    base = tempfile.mkdtemp(prefix="bench-objstore-")
    ctx = CephTpuContext("bench-objectstore")
    ctx.conf.set("bluestore_batched_csum_min", "1", source="cli")

    def mkstore(name, use_ctx):
        path = _os.path.join(base, name)
        s = BlueStoreLite(path, ctx=ctx if use_ctx else None)
        s.mkfs()
        s.mount()
        t = Transaction().create_collection("2.0")
        s.apply_transaction(t)
        return s

    def write_all(store):
        t0 = time.perf_counter()
        for i in range(0, n_objects, group):
            txn = Transaction()
            for j in range(i, min(i + group, n_objects)):
                txn.write("2.0", f"obj-{j}", 0, payloads[j])
            store.apply_transaction(txn)
        return time.perf_counter() - t0

    def read_all(store):
        best, got = float("inf"), None
        for _ in range(2):
            t0 = time.perf_counter()
            got = [store.read("2.0", f"obj-{j}")
                   for j in range(n_objects)]
            best = min(best, time.perf_counter() - t0)
        return best, got

    def verify_csums(store):
        """Every committed csum must equal host zlib.crc32 of the
        block's STORED bytes — the bit-exactness gate on the device
        digest."""
        for blob in store._db.get_range("obj").values():
            meta = json.loads(blob.decode())
            co = meta.get("comp") or []
            for bi, b in enumerate(meta["extents"]):
                if b < 0:
                    continue
                comp = co[bi] if bi < len(co) else None
                data = store._read_block(b)
                stored = data[:comp[1]] if comp else data
                if _zlib.crc32(stored) != meta["csum"][bi]:
                    return False
        return True

    out: dict = {}
    try:
        scalar = mkstore("scalar", use_ctx=False)
        batched = mkstore("batched", use_ctx=True)
        try:
            write_all(batched)        # jit warmup outside timing
            t_ws = min(write_all(scalar) for _ in range(2))
            t_wb = min(write_all(batched) for _ in range(2))
            t_rs, got_s = read_all(scalar)
            t_rb, got_b = read_all(batched)
            verified = (got_s == payloads and got_b == payloads
                        and verify_csums(batched))
            from ceph_tpu.ops import telemetry
            bstats = telemetry.bluestore_summary()
        finally:
            scalar.umount()
            batched.umount()

        # (b) the isolated csum-settle quantity: host crc loop vs one
        # channel digest over the same staged payloads
        blobs = [p[i:i + BLOCK]
                 for p in payloads[:16]
                 for i in range(0, obj_bytes, BLOCK)]
        t_host = float("inf")
        host_crcs = None
        for _ in range(3):
            t0 = time.perf_counter()
            host_crcs = [_zlib.crc32(b) for b in blobs]
            t_host = min(t_host, time.perf_counter() - t0)
        from ceph_tpu.ops.dispatch import (
            DeviceDispatchEngine, submit_bluestore_data)
        from ceph_tpu.ops.telemetry import DispatchStats
        eng = DeviceDispatchEngine(name="bench-objstore",
                                   stats=DispatchStats())
        try:
            submit_bluestore_data(eng, blobs).result(timeout=120.0)
            t_dev = float("inf")
            dig = None
            for _ in range(3):
                t0 = time.perf_counter()
                dig = np.asarray(submit_bluestore_data(
                    eng, blobs).result(timeout=120.0))
                t_dev = min(t_dev, time.perf_counter() - t0)
            micro_ok = all(int(dig[i, 0]) == host_crcs[i]
                           for i in range(len(blobs)))
        finally:
            eng.stop()

        # (c) compression-on head-to-head: seed scalar path + host
        # zlib vs the device store + tpu_bitplane, force mode both
        # sides.  Channel read-verify is priced by (a)/(b) — off here
        # so the leg isolates the compressor comparison.
        def stored_ratio(store):
            stored = logical = 0
            for blob in store._db.get_range("obj").values():
                meta = json.loads(blob.decode())
                for bi, b in enumerate(meta["extents"]):
                    if b < 0:
                        continue
                    ce = (meta.get("comp") or [None] * (bi + 1))[bi]
                    logical += BLOCK
                    stored += ce[1] if ce else BLOCK
            return stored / max(logical, 1)

        ctx.conf.set("bluestore_batched_read_verify", "false",
                     source="cli")
        comp_s = mkstore("comp-scalar", use_ctx=False)
        comp_b = mkstore("comp-batched", use_ctx=True)
        try:
            comp_s.set_pool_compression(2, "force", "zlib")
            comp_b.set_pool_compression(2, "force", "tpu_bitplane")
            write_all(comp_b)     # jit warmup outside timing
            t_cws = min(write_all(comp_s) for _ in range(2))
            t_cwb = min(write_all(comp_b) for _ in range(2))
            t_crs, got_cs = read_all(comp_s)
            t_crb, got_cb = read_all(comp_b)
            comp_ok = (got_cs == payloads and got_cb == payloads
                       and verify_csums(comp_s)
                       and verify_csums(comp_b))
            ratio_s = stored_ratio(comp_s)
            ratio_b = stored_ratio(comp_b)
        finally:
            comp_s.umount()
            comp_b.umount()

        out = {
            "objects": n_objects,
            "mbytes": round(total / 1e6, 2),
            "write_scalar_mbps": round(total / t_ws / 1e6, 1),
            "write_batched_mbps": round(total / t_wb / 1e6, 1),
            "write_batched_vs_scalar": round(t_ws / t_wb, 2),
            "read_scalar_mbps": round(total / t_rs / 1e6, 1),
            "read_batched_mbps": round(total / t_rb / 1e6, 1),
            "csum_settle_host_mbps": round(
                len(blobs) * BLOCK / t_host / 1e6, 1),
            "csum_settle_device_mbps": round(
                len(blobs) * BLOCK / t_dev / 1e6, 1),
            "csum_settle_batched_vs_scalar": round(t_host / t_dev, 2),
            "csum_batches": bstats.get("csum_batches", 0),
            "batched_csum_blocks": bstats.get("batched_csum_blocks", 0),
            "read_verify_batches": bstats.get("read_verify_batches", 0),
            "comp_write_scalar_zlib_mbps": round(
                total / t_cws / 1e6, 1),
            "comp_write_batched_bitplane_mbps": round(
                total / t_cwb / 1e6, 1),
            "comp_write_batched_vs_scalar": round(t_cws / t_cwb, 2),
            "comp_read_scalar_zlib_mbps": round(
                total / t_crs / 1e6, 1),
            "comp_read_batched_bitplane_mbps": round(
                total / t_crb / 1e6, 1),
            "comp_read_batched_vs_scalar": round(t_crs / t_crb, 2),
            "comp_stored_ratio_zlib": round(ratio_s, 3),
            "comp_stored_ratio_bitplane": round(ratio_b, 3),
            "compress_verified": comp_ok,
            "verified": verified and micro_ok,
        }
    finally:
        for eng_attr in ("_decode_dispatch", "_dispatch"):
            e = getattr(ctx, eng_attr, None)
            if e is not None:
                e.stop()
        _shutil.rmtree(base, ignore_errors=True)
    return out


def main(argv=None) -> None:
    import argparse

    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()

    import jax
    import jax.numpy as jnp

    ap = argparse.ArgumentParser(
        prog="bench",
        description="tpu-rados flagship benchmark; see module "
                    "docstring for the section list")
    ap.add_argument(
        "--sections", default=None, metavar="NAMES",
        help="comma list of sweeps to run (%s), or 'all'; default "
             "runs the flagship set (%s).  Any single section "
             "completes well inside a 590 s harness timeout."
             % (",".join(SECTIONS), ",".join(DEFAULT_SECTIONS)))
    args = ap.parse_args(argv)
    if args.sections is None:
        secs = set(DEFAULT_SECTIONS)
    elif args.sections.strip() == "all":
        secs = set(SECTIONS)
    else:
        secs = {s.strip() for s in args.sections.split(",") if s.strip()}
        unknown = secs - set(SECTIONS)
        if unknown:
            ap.error(f"unknown sections {sorted(unknown)}; "
                     f"choose from {SECTIONS}")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and secs - HOST_SECTIONS:
        raise SystemExit(
            f"bench.py: sections {sorted(secs - HOST_SECTIONS)} measure "
            f"the chip, and this process has {device}; a CPU-backend "
            "timing is not a speed")

    k, m = 8, 4
    chunk = 4096          # 4 KiB chunks — BASELINE.json config
    stripes = 2048        # 64 MiB of data per device call
    erasures = [1, k + 1]  # one data + one parity chunk lost
    data_bytes = stripes * k * chunk
    rng = np.random.default_rng(0)
    out: dict = {}

    encode = None
    if secs & {"ec", "dispatch_sweep"}:
        from ceph_tpu.gf.matrix import gen_cauchy1_matrix, recovery_matrix
        from ceph_tpu.ops.gf_kernel import make_encoder

        gen = gen_cauchy1_matrix(k, m)
        coding = gen[k:]
        chosen = [i for i in range(k + m) if i not in set(erasures)][:k]
        rmat = recovery_matrix(gen, chosen, erasures)
        encode = make_encoder(coding)
        recover = make_encoder(rmat)

    data = None
    if "ec" in secs:
        data = jnp.asarray(
            rng.integers(0, 256, (stripes, k, chunk), dtype=np.uint8))

        def enc_step(d):
            p = encode(d)
            return d.at[0, 0, 0].set(p[0, 0, 0] ^ jnp.uint8(1))

        t_enc, t_enc_min, t_enc_max = median_band(
            chained_rates(enc_step, data))
        enc_mbps = data_bytes / t_enc / 1e6

        surv = jnp.asarray(
            rng.integers(0, 256, (stripes, k, chunk), dtype=np.uint8))

        def dec_step(s):
            r = recover(s)
            return s.at[0, 0, 0].set(r[0, 0, 0] ^ jnp.uint8(1))

        t_dec, t_dec_min, t_dec_max = median_band(
            chained_rates(dec_step, surv))
        dec_mbps = data_bytes / t_dec / 1e6

        combined = 2 * data_bytes / (t_enc + t_dec) / 1e6

        # single-core C baseline (ceph_tpu/native): ISA-L-class SIMD
        # encode, same inputs, same math
        from ceph_tpu.native import ec_encode_native

        cpu_data = np.asarray(data)
        t_c = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ec_encode_native(coding, cpu_data)
            t_c = min(t_c, time.perf_counter() - t0)
        c_enc_mbps = data_bytes / t_c / 1e6
        t_c = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ec_encode_native(rmat, cpu_data)
            t_c = min(t_c, time.perf_counter() - t0)
        c_dec_mbps = data_bytes / t_c / 1e6
        c_combined = 2 / (1 / c_enc_mbps + 1 / c_dec_mbps)

        out.update({
            "metric": "ec encode+recover MB/s "
                      "(k=8,m=4,4KiB chunks, batch=2048)",
            "value": round(combined, 1),
            "unit": "MB/s",
            "vs_baseline": round(combined / c_combined, 2),
            "encode_mbps": round(enc_mbps, 1),
            "encode_mbps_band": [round(data_bytes / t_enc_max / 1e6, 1),
                                 round(data_bytes / t_enc_min / 1e6, 1)],
            "recover_mbps": round(dec_mbps, 1),
            "recover_mbps_band": [round(data_bytes / t_dec_max / 1e6, 1),
                                  round(data_bytes / t_dec_min / 1e6, 1)],
            "c_encode_mbps": round(c_enc_mbps, 1),
            "c_recover_mbps": round(c_dec_mbps, 1),
            "encode_vs_c": round(enc_mbps / c_enc_mbps, 2),
        })

    bm = None
    if "crush" in secs:
        # CRUSH bulk placement (BASELINE config #5 shape): 10k-OSD
        # two-level map (250 hosts x 40 osds), chooseleaf firstn 3, 64k
        # PGs per device call.  Non-uniform: skewed per-osd bucket
        # weights, 10% reweighted to 0.5, 2% out — the retry ladder
        # actually fires.
        from ceph_tpu.crush import build_skewed_two_level_map
        from ceph_tpu.crush.mapper_jax import BatchMapper

        crush_map, rid, reweight = build_skewed_two_level_map(250, 40)

        bm = BatchMapper(crush_map)
        n_pgs, numrep = 65536, 3
        rw = jnp.asarray(reweight)
        xs = jnp.asarray(rng.integers(0, 2**32, (n_pgs,),
                                      dtype=np.uint32))
        bm.do_rule(rid, xs, numrep, rw)  # compile

        def crush_step(x):
            p = bm.do_rule(rid, x, numrep, rw)
            return x ^ p[:, 0].astype(jnp.uint32)

        t_crush, t_crush_min, t_crush_max = median_band(
            chained_rates(crush_step, xs, n_lo=4, n_hi=24, reps=5,
                          inner=4))
        crush_mpps = n_pgs / t_crush / 1e6

        # single-core C baseline: scalar straw2 crush_do_rule
        from ceph_tpu.native import CrushBaseline

        cb = CrushBaseline(crush_map)
        c_xs = np.asarray(xs[:8192], dtype=np.uint32)
        cb.do_rule_batch(rid, c_xs[:256], numrep,
                         reweight.astype(np.uint32))
        t0 = time.perf_counter()
        cb.do_rule_batch(rid, c_xs, numrep, reweight.astype(np.uint32))
        c_crush_mpps = len(c_xs) / (time.perf_counter() - t0) / 1e6

        out.update({
            "crush_mpps": round(crush_mpps, 3),
            "crush_mpps_band": [round(n_pgs / t_crush_max / 1e6, 3),
                                round(n_pgs / t_crush_min / 1e6, 3)],
            "c_crush_mpps": round(c_crush_mpps, 3),
            "crush_vs_c": round(crush_mpps / c_crush_mpps, 2),
        })
        # fused raw→up→acting ladder over the same map: the
        # device-resident pipeline-tail story (ISSUE 10), bit-verified
        # against the host tail on a sample
        out["placement"] = placement_digest(
            crush_map, rid, bm, reweight, t_crush, n_pgs)

    from ceph_tpu.ops import telemetry
    if "ec" in secs and "crush" in secs:
        # kernel telemetry digest (retraces, p50/p99 latency,
        # occupancy): the timed loops above run inside jitted scans, so
        # close with a few FENCED standalone calls — real per-call
        # device residency samples — before summarizing.  A retrace
        # count above the handful of shapes this harness uses is the
        # regression tell.
        telemetry.set_fence_for_timing(True)
        for _ in range(3):
            encode(data)
            bm.do_rule(rid, xs, numrep, rw)
        telemetry.set_fence_for_timing(False)
        out["kernel_telemetry"] = telemetry.registry().summary()

    if "dispatch_sweep" in secs:
        # cross-op coalescing: offered-concurrency sweep through the
        # dispatch engine (1/4/16/64 in-flight writers, OSD-write-sized
        # ops).  The headline EC numbers above are device-resident;
        # this is the END-TO-END rate a concurrent client population
        # sees, and the coalesce factor is the amortization making up
        # the gap.
        sweep = dispatch_sweep(encode, k, chunk, coding=coding)
        out["dispatch"] = telemetry.dispatch_summary()   # key order as
        out["dispatch_sweep"] = sweep                    # historically

    if "recovery_sweep" in secs:
        # decode-side twin: degraded-read/recovery concurrency sweep
        # with 2 erasures per op and MIXED recovery patterns across
        # readers — the heterogeneous-matrix batched decode's
        # amortization story
        rec = recovery_sweep(k, m, chunk)
        out["decode_dispatch"] = telemetry.decode_dispatch_summary()
        out["recovery_sweep"] = rec

    if "map_churn" in secs:
        # map-epoch consumption: scalar full scan vs the shared PG
        # mapping service, bit-verified against the oracle
        out["map_churn"] = map_churn()

    if "profile" in secs:
        # pipeline phase attribution: where a coalesced batch's
        # submit->delivery wall-clock goes (phase shares, compile
        # seconds, utilization, mapping phase split) — the
        # dump_pipeline_profile story embedded per bench round.
        # Render with: python -m ceph_tpu.tools.profile_report
        out["profile"] = profile_section()

    if "qos" in secs:
        # multi-tenant dmclock fairness: per-tenant throughput/p99
        # with vs without QoS lanes, reservation attainment, limit
        # overshoot, and the excess-sharing ratio against the
        # configured weights
        out["qos"] = qos_section()

    if "scrub" in secs:
        # background integrity: scalar vs batched digest throughput
        # and tenant reservation attainment under a scrub storm with
        # vs without the background_best_effort class
        out["scrub"] = scrub_section()

    if "objectstore" in secs:
        # device-resident objectstore write path: on-disk bluestore
        # write/read MB/s scalar vs the bluestore_data channel, the
        # isolated csum-settle micro, and the bitplane compression
        # leg — all bit-verified against the host oracles
        out["objectstore"] = objectstore_section()

    if "metric" not in out:
        out = {"metric": "sections " + "+".join(sorted(secs)),
               **out}
    out["device"] = device
    print(json.dumps(out))


if __name__ == "__main__":
    main()
