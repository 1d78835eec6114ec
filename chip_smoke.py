#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-rados still starts on the chip.

Default (no arguments, one chip, one process): drives the system's main
path once through the entry points a user calls, at the flagship
deployment's widths, and checks every result by the repo's own means:

  served_ec   an in-process cluster (tools/vstart.MiniCluster: 12 OSDs on
              on-disk BlueStore), an RS k=8 m=4 pool with 4 KiB stripe
              units, driven from the client by tools/rados_bench.ObjBencher
              at `rados bench` defaults (4 MiB objects, 16 in flight):
              seeded objects written, every one read back and compared,
              the parity shards the OSDs stored of a seeded sample compared
              with jerasure's reed_sol_van as the plain reference builds it
              (perfbench/reference/rs_plain.py), then an OSD taken down and
              every object read back degraded.
  placement   65,536 PGs on the 10,000-OSD two-level map through
              BatchMapper.do_rule as tools/crush_test reaches it, compared
              lane for lane with the scalar oracle on a seeded sample and
              with the XLA fast path on all of them.
  map_epochs  a few map epochs (an OSD out, a reweight, an OSD down)
              through the shared PG mapping service over that map with one
              65,536-PG pool; changed-PG sets checked against the scalar
              pg_to_up_acting_osds.

Then it fails unless the device did the work: the Pallas branches were
the ones taken, and no engine retried, fell back to its host oracle or
opened a breaker.  `--chips 4` runs the mesh path and what it is compared
with instead, and no other phase.

The last line of standard output is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Without an accelerator the script exits non-zero before any phase and
prints no such line.  Times printed on earlier lines are notes for the
builder, not measurements.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np

#: what jax.devices()[0].platform must be; tests/test_chip_smoke.py
#: rehearses the phases on the CPU by patching this and the two
#: prove_*kernels() functions
PLATFORM = "tpu"

_CHECKOUT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    """The flagship deployment.  Widths (k, m, stripe unit, object size,
    map and pool size) are never cut; a run short of time may cut
    n_objects or the epoch list, and says so."""

    n_osds: int = 12
    k: int = 8
    m: int = 4
    obj_size: int = 4 << 20         # rados bench default
    depth: int = 16                 # rados bench default
    n_objects: int = 64             # 256 MiB of user bytes
    degraded_min: int = 8
    parity_sample: int = 8          # objects whose stored parity is compared
    hosts: int = 250
    per_host: int = 40
    n_pgs: int = 65536
    numrep: int = 3
    oracle_sample: int = 2048
    epoch_sample: int = 128
    mesh_stripes: int = 2048        # one full coalesced flush


class SmokeFailure(RuntimeError):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(tag: str, **facts) -> None:
    print(tag, json.dumps(facts, sort_keys=True, default=str), flush=True)


def stop_engines(*contexts) -> None:
    """Stop the dispatch engines of contexts this script built itself
    (daemons stop their own)."""
    for ctx in contexts:
        for eng in (ctx._dispatch, ctx._decode_dispatch):
            if eng is not None:
                eng.stop()


def device_or_exit(count: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != PLATFORM or info["count"] < count:
        sys.exit(f"chip_smoke: needs {count} {PLATFORM} device(s), "
                 f"JAX found {info}")
    return info


class CompileWatch:
    """What JAX compiled, and what it found in its persistent cache."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, name: str, **_kw) -> None:
        with self._lock:
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache_writes += 1

    def _secs(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def facts(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "compile_s": round(self.compile_s, 3),
                    "cache_hits": self.cache_hits,
                    "cache_writes": self.cache_writes}


# ---------------------------------------------------------------------------
# phase 1: the served EC path
# ---------------------------------------------------------------------------

def stored_parity(sz: Sizes, seed: int, daemons, name_of, payload_of,
                  stripe_unit: int) -> dict:
    """Of a seeded sample of the objects written, the parity shards the
    OSDs' stores hold (store object ``<name>:<shard>``) against the plain
    reference's.  A read-back passes on any MDS code that the program
    decodes as it encodes; only this says the code is the profile's."""
    from perfbench.reference import rs_plain
    rng = np.random.default_rng((seed, 0x9a71))
    sample = sorted(rng.choice(
        sz.n_objects, min(sz.parity_sample, sz.n_objects),
        replace=False).tolist())
    where: dict[str, list] = {}
    for d in daemons:
        for cid in d.store.list_collections():
            for soid in d.store.list_objects(cid):
                where.setdefault(soid, []).append((d.store, cid))
    differ = []
    for i in sample:
        want = rs_plain.shards_of(payload_of(i), sz.k, sz.m, stripe_unit)
        for s in range(sz.k, sz.k + sz.m):
            soid = f"{name_of(i)}:{s}"
            holders = where.get(soid, [])
            if not holders or any(store.read(cid, soid) != want[s]
                                  for store, cid in holders):
                differ.append(soid)
    require(not differ,
            f"{len(differ)} of {len(sample) * sz.m} stored parity shards "
            f"are missing or not reed_sol_van's: {differ[:4]}")
    return {"objects": len(sample), "shards_compared": len(sample) * sz.m,
            "shards_differ": 0}


def served_ec(sz: Sizes, seed: int, base_path: str) -> dict:
    from ceph_tpu.ops import telemetry
    from ceph_tpu.tools.rados_bench import ObjBencher
    from ceph_tpu.tools.vstart import MiniCluster

    def payload_of(i: int) -> bytes:
        return np.random.default_rng((seed, i)).bytes(sz.obj_size)

    facts: dict = {}
    cluster = MiniCluster(n_osds=sz.n_osds, store_type="bluestore",
                          base_path=base_path).start()
    daemons = list(cluster.osds.values())
    try:
        cluster.wait_for_osd_count(sz.n_osds, timeout=60.0)
        client = cluster.client(timeout=120.0)
        pool = cluster.create_pool(client, pool_type="erasure",
                                   plugin="jerasure", k=sz.k, m=sz.m,
                                   epoch_timeout=120.0)
        profile = cluster.mon.osdmap.pools[pool].ec_profile
        require(profile.get("runtime", "tpu") == "tpu"
                and "stripe_unit" not in profile,
                f"pool profile is not the default device one: {profile}")
        io_ = client.open_ioctx(pool)
        # compiles land inside the first ops; no op may time out on one
        bench = ObjBencher(io_, obj_size=sz.obj_size, concurrent=sz.depth,
                           run_name=f"smoke{seed}", op_timeout=300.0)

        def run(mode: str, fn, **kw) -> None:
            res = fn(3600.0, **kw, max_objects=sz.n_objects)
            facts[mode] = {key: res[key] for key in (
                "seconds", "total_writes_or_reads", "errors",
                "latency_max_s")}
            require(res["total_writes_or_reads"] == sz.n_objects
                    and res["errors"] == 0,
                    f"{mode}: {res['errors']} of "
                    f"{res['total_writes_or_reads']} ops failed or read "
                    f"back wrong bytes")

        run("write", bench.write_bench, payload_of=payload_of)
        run("read", bench.seq_read_bench, n_objects=sz.n_objects,
            payload_of=payload_of)
        pg_pool = cluster.mon.osdmap.pools[pool]
        stripe_unit = daemons[0]._ec_stripe_info(
            daemons[0]._codec(pg_pool), pg_pool).su
        facts["parity"] = stored_parity(sz, seed, daemons, bench._obj,
                                        payload_of, stripe_unit)

        # one OSD down: every PG spans all k+m OSDs, so each object
        # lost a shard, and those that lost a data shard are rebuilt by
        # the decode engine
        victim = sz.n_osds // 2

        def decodes() -> int:
            return sum(d.perf.value("ec_decode_submits")
                       for d in cluster.osds.values())

        cluster.kill_osd(victim)
        decodes0 = decodes()
        rc, out = client.mon_command({"prefix": "osd down",
                                      "id": str(victim)})
        require(rc == 0, f"osd down: {out}")
        client.wait_for_epoch(cluster.mon.osdmap.epoch)
        run("degraded_read", bench.seq_read_bench, n_objects=sz.n_objects,
            payload_of=payload_of)
        decoded = decodes() - decodes0
        facts["degraded_read"]["decode_submits"] = decoded
        require(decoded >= min(sz.degraded_min, sz.n_objects),
                f"only {decoded} reads went through the decode engine")
        facts["user_bytes"] = sz.n_objects * sz.obj_size
        facts["pool"] = {
            "k": sz.k, "m": sz.m, "pg_num": pg_pool.pg_num,
            "stripe_unit": stripe_unit,
            "osds": sz.n_osds, "store": "bluestore"}
    finally:
        cluster.stop()
    # breaker ground truth is per engine, on every OSD's own context
    facts["breakers_not_closed"] = sorted(
        f"{d.ctx.name}:{side}:{chan}"
        for d in daemons
        for side, per in d.ctx.fault_digest().items()
        for chan, state in per["breaker_states"].items()
        if state != telemetry.BREAKER_CLOSED)
    facts["bluestore"] = telemetry.bluestore_summary()
    return facts


# ---------------------------------------------------------------------------
# phase 2: bulk placement at deployment size
# ---------------------------------------------------------------------------

def placement(sz: Sizes, seed: int, crush_map, rid: int, reweight) -> dict:
    from ceph_tpu.crush import crush_do_rule, fastpath
    from ceph_tpu.crush.mapper_jax import BatchMapper
    from ceph_tpu.crush.types import CRUSH_ITEM_NONE
    from ceph_tpu.tools.crush_test import run_test

    # `crushtool --test --min-x 0 --max-x N-1 --num-rep 3`, the tool's
    # own entry: builds a BatchMapper and makes one do_rule call
    t0 = time.perf_counter()
    stats = run_test(crush_map, [rid], 0, sz.n_pgs - 1, sz.numrep,
                     backend="tpu", reweight=[int(w) for w in reweight],
                     out=io.StringIO())[rid]
    t_tool = time.perf_counter() - t0

    # the same call again for its rows: a fresh mapper builds and
    # uploads the map's tables and runs the program the tool's call
    # built for the shape class
    xs = np.arange(sz.n_pgs, dtype=np.uint32)
    bm = BatchMapper(crush_map)
    t0 = time.perf_counter()
    rows = np.asarray(bm.do_rule(rid, xs, sz.numrep, reweight))
    t_again = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows2 = np.asarray(bm.do_rule(rid, xs, sz.numrep, reweight))
    t_warm = time.perf_counter() - t0
    require(np.array_equal(rows, rows2), "placement is not deterministic")
    util = np.bincount(rows[rows != CRUSH_ITEM_NONE],
                       minlength=crush_map.max_devices)
    require(util.tolist() == stats["util"],
            "crush_test's utilization differs from the mapper's rows")

    # the XLA fast path (what every CPU test runs), all lanes
    bm_xla = BatchMapper(crush_map)
    bm_xla._fast_cache[rid] = fastpath.tables_of(
        fastpath.detect(crush_map, rid), pallas=False)
    rows_xla = np.asarray(bm_xla.do_rule(rid, xs, sz.numrep, reweight))
    bad = int((rows != rows_xla).any(axis=1).sum())
    require(bad == 0, f"{bad} of {sz.n_pgs} lanes differ from the XLA path")

    # the scalar oracle, a seeded sample
    w = [int(v) for v in reweight]
    sample = np.random.default_rng(seed).choice(
        sz.n_pgs, min(sz.oracle_sample, sz.n_pgs), replace=False)
    t0 = time.perf_counter()
    for i in sample:
        want = crush_do_rule(crush_map, rid, int(xs[i]), sz.numrep, w)
        got = [int(v) for v in rows[i] if v != CRUSH_ITEM_NONE]
        require(got == want, f"x={int(xs[i])}: device {got} != oracle {want}")
    return {"pgs": sz.n_pgs, "osds": crush_map.max_devices,
            "numrep": sz.numrep, "sizes": stats["sizes"],
            "xla_lanes_equal": sz.n_pgs, "oracle_lanes_equal": len(sample),
            "pallas": bm.fast_tables(rid).shape.pallas,
            "tool_call_s": round(t_tool, 3),
            "fresh_mapper_call_s": round(t_again, 3),
            "warm_call_s": round(t_warm, 3),
            "oracle_s": round(time.perf_counter() - t0, 3)}


# ---------------------------------------------------------------------------
# phase 3: map epochs through the PG mapping service
# ---------------------------------------------------------------------------

def map_epochs(sz: Sizes, seed: int, crush_map, rid: int, reweight,
               kinds=("out", "reweight", "down")) -> dict:
    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.ops import telemetry
    from ceph_tpu.osd import OSDMap, PGPool
    from ceph_tpu.osd.osdmap import OSD_UP

    n_osds = crush_map.max_devices
    m = OSDMap(crush=crush_map, epoch=2)
    m.set_max_osd(n_osds)
    for o in range(n_osds):
        m.osd_state[o] = 3                      # exists | up
        m.osd_weight[o] = int(reweight[o])
    m.pools[1] = PGPool(pool_id=1, size=sz.numrep, crush_rule=rid,
                        pg_num=sz.n_pgs)
    ctx = CephTpuContext("smoke-map")
    svc = ctx.mapping_service()
    rng = np.random.default_rng(seed)
    before = telemetry.mapping_summary()
    epochs = []
    try:
        t0 = time.perf_counter()
        require(svc.update_to(m).full, "first map was not a full build")
        first_s = time.perf_counter() - t0
        full_in = np.flatnonzero(np.asarray(reweight) == 0x10000)
        for kind in kinds:
            new = m.copy()
            new.epoch = m.epoch + 1
            osd = int(rng.choice(full_in))
            if kind == "out":
                new.osd_weight[osd] = 0
            elif kind == "reweight":
                new.osd_weight[osd] = 0x8000
            else:
                new.osd_state[osd] &= ~OSD_UP
            raw_old = svc._mapping.get_raw(1)
            t0 = time.perf_counter()
            upd = svc.update_to(new, from_epoch=m.epoch)
            dt = time.perf_counter() - t0
            require(not upd.full, f"epoch {new.epoch}: no delta served")
            changed = {pg for _pool, pg in upd.changed}
            if kind != "reweight":
                # the OSD left every set it was in
                held = set(np.flatnonzero(
                    (raw_old == osd).any(axis=1)).tolist())
                require(held and held <= changed,
                        f"{kind} osd.{osd}: PGs {sorted(held - changed)} "
                        "held it and are not in the delta")
            rest = np.setdiff1d(np.arange(sz.n_pgs), sorted(changed))
            quiet = rng.choice(rest, min(sz.epoch_sample, len(rest)),
                               replace=False)
            for pg in sorted(changed) + [int(p) for p in quiet]:
                want = new.pg_to_up_acting_osds(1, pg)
                require(svc.lookup(new, 1, pg) == want,
                        f"epoch {new.epoch} pg {pg}: cache != scalar")
                moved = m.pg_to_up_acting_osds(1, pg) != want
                require(moved == (pg in changed),
                        f"epoch {new.epoch} pg {pg}: in delta="
                        f"{pg in changed}, scalar says moved={moved}")
            epochs.append({"epoch": new.epoch, "kind": kind, "osd": osd,
                           "changed_pgs": len(changed),
                           "quiet_pgs_checked": len(quiet),
                           "update_s": round(dt, 3)})
            m = new
        mapper = svc._mapping._mapper[1]
    finally:
        stop_engines(ctx)
    after = telemetry.mapping_summary()
    delta = {key: after[key] - before[key] for key in (
        "epoch_updates", "fused_epochs", "unfused_epochs", "lookups",
        "fused_lookups", "lookup_fallbacks")}
    return {"pool_pgs": sz.n_pgs, "osds": n_osds, "epochs": epochs,
            "first_build_s": round(first_s, 3), "mapping": delta,
            "pallas": mapper.fast_tables(rid).shape.pallas}


# ---------------------------------------------------------------------------
# proof that the device did the work
# ---------------------------------------------------------------------------

def prove_kernels(facts: dict) -> dict:
    """The TPU branches of the platform gates were the ones taken."""
    from ceph_tpu.ops import checksum_kernel, gf_kernel
    proof = {
        "encode_pallas_programs": gf_kernel._encode_pallas._cache_size(),
        "decode_programs": gf_kernel._decode_jit_entries(),
        "digest_programs": checksum_kernel.digest_jit_entries(),
        "placement_pallas": facts["placement"]["pallas"],
        "map_epochs_pallas": facts["map_epochs"]["pallas"],
    }
    require(all(proof.values()), f"a device branch was not taken: {proof}")
    return proof


def fault_counters() -> dict:
    """What must stay zero on both engines' process-wide sinks."""
    from ceph_tpu.ops import telemetry
    digest = telemetry.fault_digest()
    counters = {
        f"{side}.{key}": digest[side][key]
        for side in ("encode", "decode")
        for key in ("retries", "fallback_batches", "breaker_opens",
                    "thread_deaths")}
    counters["breakers_not_closed"] = [
        f"{side}:{chan}" for side in ("encode", "decode")
        for chan, st in digest[side]["breaker_states"].items()
        if st != telemetry.BREAKER_CLOSED]
    return counters


def prove_no_fallback(facts: dict) -> dict:
    """No engine retried, fell back to its host oracle or opened a
    breaker, and the mapping service stayed on its fused path."""
    counters = fault_counters()
    if "served_ec" in facts:
        counters["breakers_not_closed"] += facts["served_ec"][
            "breakers_not_closed"]
        counters["csum_fallbacks"] = facts["served_ec"]["bluestore"][
            "csum_fallbacks"]
    if "map_epochs" in facts:
        mapping = facts["map_epochs"]["mapping"]
        counters["unfused_epochs"] = mapping["unfused_epochs"]
        counters["lookup_fallbacks"] = mapping["lookup_fallbacks"]
        require(mapping["fused_epochs"] > 0, "no fused epoch ran")
    bad = {key: v for key, v in counters.items() if v}
    require(not bad, f"the host stood in for the device: {bad}")
    return counters


# ---------------------------------------------------------------------------
# --chips 4: the mesh path and what it is compared with
# ---------------------------------------------------------------------------

def mesh_path(sz: Sizes, seed: int, crush_map, rid: int, reweight,
              n_devices: int) -> dict:
    """One coalesced encode flush and one bulk CRUSH batch through an
    engine that shards over every local device (the default,
    kernel_mesh_devices = 0), each compared bit for bit with the same
    call through a single-device engine."""
    from ceph_tpu.common.context import CephTpuContext
    from ceph_tpu.crush import mapper_jax
    from ceph_tpu.crush.mapper_jax import BatchMapper
    from ceph_tpu.ec import registry_instance
    from ceph_tpu.ops import gf_kernel, telemetry
    from ceph_tpu.ops.dispatch import submit_do_rule

    ctx_mesh = CephTpuContext("smoke-mesh")
    ctx_one = CephTpuContext("smoke-one")
    ctx_one.conf.set("kernel_mesh_devices", 1)
    facts: dict = {}
    try:
        mesh = ctx_mesh.kernel_mesh()
        require(mesh is not None and mesh.size == n_devices
                and len(set(mesh.devices.flat)) == n_devices,
                f"asked for a {n_devices}-device mesh, got {mesh}")
        require(ctx_one.kernel_mesh() is None, "single-device engine "
                "got a mesh")
        eng_mesh, eng_one = (ctx_mesh.dispatch_engine(),
                             ctx_one.dispatch_engine())
        rng = np.random.default_rng(seed)

        # -- EC encode flush ------------------------------------------------
        codec = registry_instance().factory(
            "jerasure", {"k": str(sz.k), "m": str(sz.m),
                         "technique": "reed_sol_van"})
        data = rng.integers(0, 256, (sz.mesh_stripes, sz.k, 4096),
                            dtype=np.uint8)
        xla0 = gf_kernel._encode_xla._cache_size()
        par_mesh = codec.submit_chunks(eng_mesh, data).result(timeout=600)
        par_one = codec.submit_chunks(eng_one, data).result(timeout=600)
        require(np.array_equal(par_mesh, par_one),
                "encode: mesh result differs from one device")
        some = rng.choice(sz.mesh_stripes, 64, replace=False)
        require(np.array_equal(
            par_mesh[some],
            gf_kernel.ec_encode_ref(codec.generator[sz.k:], data[some])),
            "encode: mesh result differs from the oracle")
        # the batch as the engine places it, and what the kernel returns
        placed = eng_mesh._mesh_placement().put(data)
        out = codec._encoder_for_mesh(eng_mesh.placement_mesh())(placed)
        facts["encode"] = {
            "stripes": sz.mesh_stripes,
            "placed_devices": len(placed.sharding.device_set),
            "result_devices": len(out.sharding.device_set),
            "shard_map_programs":
                gf_kernel._pallas_sharded_fn.cache_info().currsize,
            "xla_reroutes": gf_kernel._encode_xla._cache_size() - xla0}
        require(np.array_equal(np.asarray(out), par_mesh),
                "encode: placed call differs from the engine's")
        require(facts["encode"]["placed_devices"] == n_devices
                and facts["encode"]["result_devices"] == n_devices,
                f"encode batch did not span {n_devices} devices: "
                f"{facts['encode']}")

        # -- CRUSH batch ----------------------------------------------------
        xs = rng.integers(0, 2 ** 32, sz.n_pgs, dtype=np.uint32)
        bm = BatchMapper(crush_map)
        rows_mesh = submit_do_rule(eng_mesh, bm, rid, xs, sz.numrep,
                                   reweight).result(timeout=900)
        rows_one = submit_do_rule(eng_one, bm, rid, xs, sz.numrep,
                                  reweight).result(timeout=900)
        require(np.array_equal(rows_mesh, rows_one),
                "crush: mesh result differs from one device")
        facts["crush"] = {
            "pgs": sz.n_pgs,
            "pallas": bm.fast_tables(rid).shape.pallas,
            "shard_map_programs": sum(
                1 for key in mapper_jax._FAST_PROGRAMS
                if key[2] is not None),
            "lanes_equal": sz.n_pgs}
        facts["dispatch"] = {
            key: telemetry.dispatch_summary()[key]
            for key in ("device_calls", "sharded_flushes", "mesh_devices")}
        require(facts["dispatch"]["sharded_flushes"] >= 2,
                f"flushes were not sharded: {facts['dispatch']}")
    finally:
        stop_engines(ctx_mesh, ctx_one)
    return facts


def prove_mesh_kernels(facts: dict) -> dict:
    """The shard_map-Pallas route was taken, not the XLA re-route."""
    enc, crush = facts["mesh"]["encode"], facts["mesh"]["crush"]
    proof = {"encode_shard_map_programs": enc["shard_map_programs"],
             "encode_not_rerouted": enc["xla_reroutes"] == 0,
             "crush_pallas": crush["pallas"],
             "crush_shard_map_programs": crush["shard_map_programs"]}
    require(all(proof.values()), f"a device branch was not taken: {proof}")
    return proof


# ---------------------------------------------------------------------------

def main(argv=None, sizes: Sizes = Sizes()) -> int:
    ap = argparse.ArgumentParser(
        prog="chip_smoke", description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the mesh path and its one-device "
                         "comparison, no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds every payload, x and sample")
    args = ap.parse_args(argv)

    import ceph_tpu  # noqa: F401  (x64 on before any array exists)
    from ceph_tpu.common.compile_cache import place_compile_cache
    from ceph_tpu.crush import build_skewed_two_level_map
    cache_dir = place_compile_cache()
    device = device_or_exit(args.chips)
    watch = CompileWatch()
    say("start", device=device, compile_cache=cache_dir, seed=args.seed,
        sizes=sizes.__dict__)
    t_start = time.perf_counter()
    crush_map, rid, reweight = build_skewed_two_level_map(
        sizes.hosts, sizes.per_host)
    facts: dict = {}

    def phase(name: str, fn, *a) -> None:
        t0 = time.perf_counter()
        facts[name] = fn(sizes, args.seed, *a)
        say(f"phase {name}", wall_s=round(time.perf_counter() - t0, 3),
            **facts[name], jax=watch.facts())
        prove_no_fallback(facts)    # fail at the first phase that hid one

    if args.chips == 4:
        phase("mesh", mesh_path, crush_map, rid, reweight, device["count"])
        say("proof", kernels=prove_mesh_kernels(facts),
            counters=prove_no_fallback(facts))
    else:
        base = os.path.join(_CHECKOUT, ".chip_smoke_store")
        shutil.rmtree(base, ignore_errors=True)
        try:
            phase("served_ec", served_ec, base)
        finally:
            shutil.rmtree(base, ignore_errors=True)
        phase("placement", placement, crush_map, rid, reweight)
        phase("map_epochs", map_epochs, crush_map, rid, reweight)
        say("proof", kernels=prove_kernels(facts),
            counters=prove_no_fallback(facts))
    say("done", wall_s=round(time.perf_counter() - t_start, 3),
        jax=watch.facts())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
