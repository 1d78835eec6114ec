"""Bulk PG -> OSD mapping on device (OSDMapMapping / ParallelPGMapper analog)
and the shared, epoch-keyed PG mapping service.

The reference computes the full PG->OSD table with a thread pool over pgid
batches (src/osd/OSDMapMapping.h:17 ParallelPGMapper, used by the mgr balancer
and OSDMonitor).  Here the whole pool maps in one device call: the pps seeds
are a vectorized stable_mod + rjenkins hash, and placement is the batched rule
engine (ceph_tpu.crush.mapper_jax.BatchMapper).

Post-CRUSH overrides (upmap, primary affinity, temps) are sparse per-PG state
and apply host-side on the dense result — the same split the reference uses
(its mapping cache also stores raw CRUSH output and applies overrides on read).

Three layers:

* ``OSDMapMapping`` — the per-epoch table builder.  ``update()`` is now
  INCREMENTAL: each pool carries a signature (crush content, rule, size,
  pg_num/pgp_num, the reweights of the OSDs its rule can actually reach) and
  only pools whose signature moved recompute; untouched pools reuse their raw
  tables.  The BatchMapper of the current crush content is kept, so
  unchanged-crush epochs skip the table build; a changed crush map
  costs a host-side build of its bucket tables and their upload, and
  runs the programs the first map of its shape class built
  (crush.mapper_jax).  Remaps submit
  through the context's dispatch engine (ops.dispatch.submit_do_rule) when
  one is supplied: pools sharing a rule — and daemons sharing a context —
  coalesce into one device call, and the double-buffered pipeline overlaps
  pool N+1's h2d with pool N's compute.

* ``SharedPGMappingService`` — one instance per CephTpuContext
  (``ctx.mapping_service()``), the epoch-keyed cache every mapping consumer
  reads: OSD map consumption (daemon._scan_pgs), client op targeting
  (client.rados), the balancer, and the offline tools.  On a new epoch it
  updates the mapping, diffs old-vs-new raw tables ON DEVICE, and derives the
  exact changed-PG delta (candidates from the device diff + override/osd-state
  diffs, then filtered through the host-side pipeline tail) so map consumption
  is O(changed PGs + local PGs) instead of O(cluster PGs).  A burst of epochs
  coalesces: while one update runs, later maps queue and only the NEWEST is
  computed (epoch-skip).  Reads are epoch- and identity-checked — a reader
  holding a different map object or epoch falls back to the scalar oracle, so
  the scalar ``pg_to_up_acting_osds`` remains the source of truth.

Contract (same as the reference's mapping cache): maps are immutable once
published — advance by building a NEW OSDMap with a higher epoch (OSDMap.copy
+ mutate), never by mutating a map the service has already seen.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque

import numpy as np

from ceph_tpu.common import lockdep, tracing
from ceph_tpu.common.allocator import pin_malloc_thresholds
from ceph_tpu.crush.types import (CRUSH_ITEM_NONE, CrushMap,
                                  padded_osds)
from ceph_tpu.ops import telemetry

from .osdmap import MAX_AFFINITY, OSDMap, PGPool

__all__ = ["OSDMapMapping", "SharedPGMappingService", "MapUpdate",
           "pps_batch", "crush_signature", "rule_devices"]


def pps_batch(pool: PGPool, pgids: np.ndarray) -> np.ndarray:
    """Vectorized raw_pg_to_pps over pg ids (osd_types.cc:1505-1521)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.crush_kernel import hash32_2
    ps = np.asarray(pgids, dtype=np.uint32)
    bmask = pool.pgp_num_mask
    low = ps & bmask
    stable = np.where(low < pool.pgp_num, low, ps & (bmask >> 1))
    return np.asarray(hash32_2(jnp.asarray(stable),
                               jnp.uint32(pool.pool_id & 0xFFFFFFFF)))


def pps_batch_scalar(pool: PGPool, pgids: np.ndarray) -> np.ndarray:
    """Scalar-backend twin of pps_batch (no jax import)."""
    return np.asarray([pool.raw_pg_to_pps(int(pg)) for pg in pgids],
                      dtype=np.uint32)


def crush_signature(crush: CrushMap) -> int:
    """Content hash of everything placement reads from the crush map:
    bucket structure/weights, rules, tunables, choose_args.  O(map
    size) per epoch — noise next to one pool remap — and it is what
    lets unchanged-crush epochs reuse both the BatchMapper (the map's
    bucket tables) and every pool's raw table."""
    buckets = tuple(
        (b.id, b.type, b.alg, b.hash, tuple(b.items),
         tuple(b.item_weights), b.weight)
        for b in crush.buckets if b is not None)
    rules = tuple(
        (i, tuple((s.op, s.arg1, s.arg2) for s in r.steps))
        for i, r in enumerate(crush.rules) if r is not None)
    t = crush.tunables
    tun = (t.choose_local_tries, t.choose_local_fallback_tries,
           t.choose_total_tries, t.chooseleaf_descend_once,
           t.chooseleaf_vary_r, t.chooseleaf_stable, t.straw_calc_version)
    return hash((crush.max_devices, buckets, rules, tun,
                 repr(crush.choose_args)))


def rule_devices(crush: CrushMap, ruleno: int) -> tuple[int, ...]:
    """Devices reachable from a rule's take roots — the OSDs whose
    reweight can change this rule's raw output.  Sorted tuple."""
    rule = crush.rules[ruleno] if 0 <= ruleno < len(crush.rules) else None
    if rule is None:
        return ()
    from ceph_tpu.crush.types import RULE_TAKE
    devs: set[int] = set()
    stack = [s.arg1 for s in rule.steps if s.op == RULE_TAKE]
    seen: set[int] = set()
    while stack:
        item = stack.pop()
        if item >= 0:
            devs.add(item)
            continue
        if item in seen:
            continue
        seen.add(item)
        b = crush.bucket(item)
        if b is not None:
            stack.extend(b.items)
    return tuple(sorted(devs))


@functools.lru_cache(maxsize=None)
def _delta_diff_program():
    """The epoch diff as one jitted program with a name of its own, so
    that a device trace shows it as ``jit_mapping_delta_diff``.  Built
    on first use: the scalar backend never imports jax."""
    import jax
    import jax.numpy as jnp

    def mapping_delta_diff(old, new):
        return jnp.any(old != new, axis=1)

    return jax.jit(mapping_delta_diff)


def _changed_rows(old: np.ndarray, new: np.ndarray,
                  mesh=None) -> np.ndarray:
    """Row indices where the two (pg_num, size) raw tables differ.
    The elementwise compare + row reduce runs on device
    (``mapping_delta_diff``); only the boolean row mask comes back to
    host.  With a ``mesh`` (the context's kernel mesh) and a PG axis
    the mesh size divides — pg_num is a power of two in practice — both
    tables split their PG axis across the mesh, so the epoch diff fans
    out with the rest of the mapping pipeline instead of serializing on
    one chip.  Counted in MappingStats (delta_device_diffs and the
    bytes uploaded for them, or delta_host_diffs where no device
    answered)."""
    if old.shape != new.shape:
        return np.arange(new.shape[0])
    if new.size == 0:
        return np.zeros(0, dtype=np.int64)
    stats = telemetry.mapping_stats()
    try:
        import jax.numpy as jnp
        with tracing.span("mapping delta upload", daemon="mapping"):
            if (mesh is not None and getattr(mesh, "size", 1) > 1
                    and old.shape[0] % mesh.size == 0):
                # single sharded placement straight from host
                # (jnp.asarray first would pay an extra default-device
                # transfer)
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                spec = NamedSharding(
                    mesh, PartitionSpec(tuple(mesh.axis_names), None))
                o, n = (jax.device_put(old, spec),
                        jax.device_put(new, spec))
            else:
                o, n = jnp.asarray(old), jnp.asarray(new)
        with tracing.span("mapping delta diff", daemon="mapping"):
            dev_mask = _delta_diff_program()(o, n)  # async dispatch
        # the host's wait for the device's answer, and its copy back
        with tracing.span("mapping delta read-back", daemon="mapping",
                          device_wait=True, wait=True):
            mask = np.asarray(dev_mask)
        stats.record_delta_diff(device=True,
                                upload_bytes=old.nbytes + new.nbytes)
    except Exception:   # scalar backend / no device: host diff
        mask = (old != new).any(axis=1)
        stats.record_delta_diff(device=False)
    return np.flatnonzero(mask)


#: seconds an epoch waits for one of its engine requests before it
#: asks whether the engine is still launching (_engine_result)
ENGINE_WAIT_S = 120.0


def _engine_result(engine, fut) -> np.ndarray:
    """The engine's answer to one of an epoch's requests.  The engine's
    completion thread has already copied it to the host (its
    `materialize` phase), so np.asarray is free.  The wait gives up
    after ENGINE_WAIT_S unless the engine is then launching a batch: a
    request's first shape compiles inside the launch, and the programs
    of a 1 Mi-PG pool's first build compile for well over a minute —
    a cold first build must not read as a dead engine."""
    while True:
        try:
            return np.asarray(fut.result(timeout=ENGINE_WAIT_S))
        except TimeoutError:
            if not engine.building():
                raise


def pool_signatures(m: OSDMap, reach: dict | None = None
                    ) -> tuple[int, dict[int, tuple]]:
    """(crush_sig, {pool_id: signature}) — the per-pool placement
    signature covering everything the RAW table depends on: crush
    content, rule, size/pg_num/pgp_num/type, and the reweights of the
    rule's reachable OSDs.  Two maps with equal signatures produce
    bit-identical raw tables.  ``reach`` is an optional
    (crush_sig, rule) -> devices memo shared across calls."""
    csig = crush_signature(m.crush)
    if reach is None:
        reach = {}
    sigs: dict[int, tuple] = {}
    w = m.osd_weight
    for pool_id, pool in m.pools.items():
        if (pool.crush_rule < 0 or pool.crush_rule >= m.crush.max_rules
                or m.crush.rules[pool.crush_rule] is None):
            sigs[pool_id] = ("invalid", pool.pg_num)
            continue
        devs = reach.get((csig, pool.crush_rule))
        if devs is None:
            devs = rule_devices(m.crush, pool.crush_rule)
            reach[(csig, pool.crush_rule)] = devs
        wsig = hash(tuple(w[o] if 0 <= o < len(w) else 0 for o in devs))
        sigs[pool_id] = (csig, pool.crush_rule, pool.size, pool.pg_num,
                        pool.pgp_num, pool.type, wsig)
    return csig, sigs


def scalar_rows(crush: CrushMap, ruleno: int, xs, numrep: int,
                weights) -> np.ndarray:
    """(len(xs), numrep) raw table via the scalar rule engine,
    CRUSH_ITEM_NONE-padded — the pure-python twin of a batched
    do_rule call (small pools, scalar backend, offline tools)."""
    from ceph_tpu.crush.mapper_ref import crush_do_rule
    w = [int(x) for x in weights]
    out = np.full((len(xs), numrep), CRUSH_ITEM_NONE, dtype=np.int32)
    for i, x in enumerate(xs):
        row = crush_do_rule(crush, ruleno, int(x), numrep, w)
        out[i, :len(row)] = row[:numrep]
    return out


def _vec(lst: list, n: int, fill: int = 0) -> np.ndarray:
    out = np.full(n, fill, dtype=np.int64)
    out[:len(lst)] = lst[:n] if len(lst) > n else lst
    return out


def _pool_override_digests(m: OSDMap) -> dict[int, int]:
    """Per-pool content digest of the four override dicts — part of
    the fused-table signature, so override-only churn recomputes just
    the touched pool's ladder."""
    acc: dict[int, list] = {}
    for attr in ("pg_upmap", "pg_upmap_items", "pg_temp",
                 "primary_temp"):
        d = getattr(m, attr)
        for (pid, pg), v in d.items():
            if isinstance(v, list):
                v = tuple(tuple(e) if isinstance(e, (list, tuple))
                          else e for e in v)
            acc.setdefault(pid, []).append((attr, pg, v))
    return {pid: hash(tuple(sorted(entries)))
            for pid, entries in acc.items()}


def _tail_equal(a: OSDMap, b: OSDMap) -> bool:
    """True when two maps agree on every PIPELINE-TAIL input (state,
    weights, affinity, overrides) — the gate for serving one map's
    fused rows to another object of the same epoch.  The raw-table
    signature already matched; this covers what it deliberately does
    not."""
    return (a.max_osd == b.max_osd
            and a.osd_state == b.osd_state
            and a.osd_weight == b.osd_weight
            and a.osd_primary_affinity == b.osd_primary_affinity
            and a.pg_upmap == b.pg_upmap
            and a.pg_upmap_items == b.pg_upmap_items
            and a.pg_temp == b.pg_temp
            and a.primary_temp == b.primary_temp)


def _finish_from(m: OSDMap, pool: PGPool, pool_id: int, pg: int,
                 raw_tab: dict, pps_tab: dict
                 ) -> tuple[list[int], int, list[int], int]:
    """Pipeline tail (upmap -> up -> affinity -> temps) over a cached
    raw row — the scalar oracle the fused device ladder
    (ops.placement_kernel) is bit-exact against, and the fallback when
    fused tables are unavailable."""
    raw = [int(o) for o in raw_tab[pool_id][pg]]
    if not pool.is_erasure():
        raw = [o for o in raw if o != CRUSH_ITEM_NONE]
    pps_arr = pps_tab.get(pool_id)
    pps = int(pps_arr[pg]) if pps_arr is not None else None
    return m._finish_pg_mapping(pool, (pool_id, pg), raw, pps)


class _Tables:
    """One epoch's published tables: the map object they were built
    from (identity IS the primary cache key — see module contract),
    the raw placements, the pps seeds, the per-pool signatures, and —
    when the fused device ladder ran — the packed
    (up, up_primary, acting, acting_primary) tables plus their shared
    width and tail signatures.

    ``bound`` / ``rejected`` memoize OTHER map objects of the same
    epoch that have been content-checked against the signatures —
    N daemons on one context each decode their own copy of a published
    epoch, and equal signatures mean bit-identical raw tables, so
    copies bind once and read the shared tables from then on.
    ``tail_bound`` additionally memoizes copies whose PIPELINE-TAIL
    inputs matched too (the raw signature deliberately excludes
    state/affinity/overrides): only those may read the fused rows —
    everyone else gets the host tail against their OWN map."""

    __slots__ = ("osdmap", "raw", "pps", "sigs", "epoch", "bound",
                 "rejected", "fused", "fused_w", "tail_sigs",
                 "tail_bound")

    def __init__(self, osdmap, raw, pps, sigs, epoch, fused=None,
                 fused_w=None, tail_sigs=None):
        self.osdmap = osdmap
        self.raw = raw
        self.pps = pps
        self.sigs = sigs
        self.epoch = epoch
        self.fused = fused if fused is not None else {}
        self.fused_w = fused_w if fused_w is not None else {}
        self.tail_sigs = tail_sigs if tail_sigs is not None else {}
        # id -> weakref (OSDMap is an eq-dataclass, hence unhashable;
        # membership verifies the ref still IS the object, so a reused
        # id after GC can never alias)
        self.bound: dict[int, object] = {}
        self.rejected: dict[int, object] = {}
        self.tail_bound: dict[int, object] = {}

    @staticmethod
    def _has(memo: dict, osdmap) -> bool:
        r = memo.get(id(osdmap))
        return r is not None and r() is osdmap

    @staticmethod
    def _memo(memo: dict, osdmap) -> None:
        import weakref
        dead = [k for k, r in memo.items() if r() is None]
        for k in dead:
            del memo[k]
        memo[id(osdmap)] = weakref.ref(osdmap)


class _UpdateInfo:
    __slots__ = ("prev", "recomputed", "reused")

    def __init__(self, prev, recomputed, reused):
        self.prev = prev
        self.recomputed = recomputed
        self.reused = reused


class MapUpdate:
    """What a consumer gets back from update_to(): the epochs it
    covers and the exact changed-PG list — or full=True when the
    delta chain cannot serve the caller's from_epoch (first map, or a
    reader older than the retained delta log), meaning: rescan
    everything, but still read the mappings from the cache."""

    __slots__ = ("epoch_from", "epoch_to", "changed", "full")

    def __init__(self, epoch_from, epoch_to, changed, full):
        self.epoch_from = epoch_from
        self.epoch_to = epoch_to
        self.changed = changed
        self.full = full

    def __repr__(self):
        return (f"MapUpdate({self.epoch_from}->{self.epoch_to}, "
                f"{'full' if self.full else len(self.changed)})")


class OSDMapMapping:
    """Full-map PG->OSD cache, updated per epoch (OSDMapMapping.h:324-332).

    ``update()`` recomputes only pools whose placement inputs changed
    since the cached epoch; see the module docstring.  ``backend``:
    "tpu" uses the batched device mapper, "scalar" the pure-python
    oracle (slow, but it keeps the incremental reuse and exists for
    hosts without a device)."""

    def __init__(self, osdmap: OSDMap | None = None, *,
                 backend: str = "tpu", min_device_pgs: int = 0,
                 fused: bool = True):
        self.osdmap = osdmap
        #: pools below this pg_num rebuild with the scalar rule engine
        #: (device dispatch + compile overhead dominates tiny pools);
        #: the osdmap_mapping_min_pgs option
        self.min_device_pgs = min_device_pgs
        #: fuse the post-CRUSH pipeline tail on device: publish packed
        #: (up, acting, primaries) tables next to the raw ones.
        #: Ignored on the scalar backend.
        self.fused = fused
        #: (content signature, BatchMapper) of the crush map last
        #: mapped, kept across update() calls so unchanged-crush epochs
        #: skip the table build.  A mapper holds a map's content only
        #: (its bucket tables); the compiled programs are the
        #: process's, one per shape class (crush.mapper_jax)
        self._mapper: tuple[int, object] | None = None
        #: rule -> shape class of the tables last built for it (the
        #: `class_changed` attribute of the `mapping crush tables` span)
        self._fast_shapes: dict[int, object] = {}
        self._raw: dict[int, np.ndarray] = {}    # pool -> (pg_num, size) raw
        self._pps: dict[int, np.ndarray] = {}    # pool -> (pg_num,) pps seeds
        self._sigs: dict[int, tuple] = {}        # pool -> placement signature
        self._fused: dict[int, np.ndarray] = {}  # pool -> packed ladder rows
        self._fused_w: dict[int, int] = {}       # pool -> packed width
        self._tail_sigs: dict[int, tuple] = {}   # pool -> tail signature
        self._reach: dict[tuple, tuple] = {}     # (crush_sig, rule) -> devs
        self.epoch = -1
        self.backend = backend

    def mapper_for(self, crush: CrushMap, csig: int | None = None):
        """The BatchMapper of this crush content: the one kept if the
        content is unchanged, else a new one (which replaces it: one
        map's tables are held, not a history of them).  New content is
        no new program — see ``_crush_tables``.  Offline tools share
        the production mapper path here."""
        if csig is None:
            csig = crush_signature(crush)
        if self._mapper is None or self._mapper[0] != csig:
            from ceph_tpu.crush.mapper_jax import BatchMapper
            self._mapper = (csig, BatchMapper(crush))
        return self._mapper[1]

    def _crush_tables(self, bm, ruleno: int, engine) -> None:
        """New crush content on the fast path: build the map's bucket
        tables on the host and put them where the engine will place
        the batch, under spans of their own.  A no-op once the mapper
        has them — every epoch that leaves the crush map alone."""
        if bm.has_fast_tables(ruleno):
            return
        with tracing.span("mapping crush tables", daemon="mapping") as sp:
            ft = bm.fast_tables(ruleno)
            if ft is None:
                return
            tracing.set_attrs(
                sp, hosts=ft.shape.root_lanes,
                leaf_lanes=ft.shape.leaf_lanes,
                class_changed=self._fast_shapes.get(ruleno) != ft.shape)
            self._fast_shapes[ruleno] = ft.shape
        mesh = None
        if engine is not None:
            try:
                mesh = engine.placement_mesh()
            except Exception:
                mesh = None
            if mesh is not None and getattr(mesh, "size", 1) <= 1:
                mesh = None
        with tracing.span("mapping crush tables upload", daemon="mapping",
                          bytes=ft.nbytes):
            ft.on(mesh)

    def update(self, osdmap: OSDMap | None = None,
               engine=None) -> _UpdateInfo:
        """Advance the cache to ``osdmap`` (default: the constructor's
        map re-read — the seed-compatible full path).  Recomputes only
        signature-changed pools; with ``engine`` the per-pool remaps
        ride the dispatch engine (submit-all, then collect)."""
        m = osdmap if osdmap is not None else self.osdmap
        if m is None:
            raise ValueError("OSDMapMapping.update: no osdmap")
        # prev pairs the CURRENT tables with the map they were built
        # from; nothing on self is reassigned until the commit point
        # below, so a mid-update exception (device error, future
        # timeout) leaves the old state fully consistent and the next
        # successful update diffs against the right old map
        with tracing.span("mapping signatures", daemon="mapping"):
            prev = _Tables(self.osdmap if self.epoch >= 0 else None,
                           self._raw, self._pps, self._sigs, self.epoch,
                           fused=self._fused, fused_w=self._fused_w,
                           tail_sigs=self._tail_sigs)
            # drop reachability memos of dead crush content before reuse
            csig, sigs = pool_signatures(m, self._reach)
            self._reach = {k: v for k, v in self._reach.items()
                           if k[0] == csig}
        # pps seeds, weights and the per-pool CRUSH remaps
        with tracing.span("mapping crush", daemon="mapping"):
            # zero-padded to the OSD axis quantum: weight 0 is out,
            # is_out's verdict for an id past the vector already, and
            # a max_osd that grows inside the quantum keeps the shape
            weights = np.zeros(padded_osds(m.max_osd), dtype=np.int64)
            k = min(m.max_osd, len(m.osd_weight))
            weights[:k] = m.osd_weight[:k]
            raw: dict[int, np.ndarray] = {}
            pps_t: dict[int, np.ndarray] = {}
            recomputed: list[int] = []
            reused: list[int] = []
            futures: list[tuple[int, object]] = []
            bm = None
            for pool_id, pool in m.pools.items():
                sig = sigs[pool_id]
                invalid = sig[0] == "invalid"
                if prev.sigs.get(pool_id) == sig and pool_id in prev.raw:
                    raw[pool_id] = prev.raw[pool_id]
                    if pool_id in prev.pps:
                        pps_t[pool_id] = prev.pps[pool_id]
                    reused.append(pool_id)
                    continue
                recomputed.append(pool_id)
                if invalid:
                    # invalid rule -> empty raw, matching _pg_to_raw_osds's []
                    raw[pool_id] = np.zeros((pool.pg_num, 0), dtype=np.int32)
                    continue
                pgids = np.arange(pool.pg_num, dtype=np.uint32)
                # pps seeds depend ONLY on (pool_id, pg_num, pgp_num) —
                # reweight/crush churn recomputes the raw table but may
                # reuse the seeds (noticeable per epoch on slow hosts)
                old_pool = (prev.osdmap.pools.get(pool_id)
                            if prev.osdmap is not None else None)
                pps = (prev.pps.get(pool_id)
                       if (old_pool is not None
                           and old_pool.pg_num == pool.pg_num
                           and old_pool.pgp_num == pool.pgp_num)
                       else None)
                if (self.backend == "scalar"
                        or pool.pg_num < self.min_device_pgs):
                    if pps is None:
                        pps = pps_batch_scalar(pool, pgids)
                    pps_t[pool_id] = pps
                    raw[pool_id] = scalar_rows(m.crush, pool.crush_rule,
                                               pps, pool.size, weights)
                    continue
                if pps is None:
                    pps = pps_batch(pool, pgids)
                pps_t[pool_id] = pps
                if bm is None:
                    # mapper_for keeps the mapper (its tables) across
                    # epochs of unchanged crush content
                    bm = self.mapper_for(m.crush, csig)
                self._crush_tables(bm, pool.crush_rule, engine)
                if engine is not None:
                    from ceph_tpu.ops.dispatch import BACKGROUND_BEST_EFFORT
                    from ceph_tpu.ops.dispatch import submit_do_rule
                    # the engine request's span parents under
                    # `mapping crush`, the span that waits for it
                    # below
                    futures.append((pool_id, submit_do_rule(
                        engine, bm, pool.crush_rule, pps, pool.size,
                        weights,
                        cost_tag=("system", BACKGROUND_BEST_EFFORT))))
                else:
                    raw[pool_id] = np.asarray(bm.do_rule(
                        pool.crush_rule, pps, pool.size, weights))
            for pool_id, fut in futures:
                raw[pool_id] = _engine_result(engine, fut)
        fused: dict[int, np.ndarray] = {}
        fused_w: dict[int, int] = {}
        tail_sigs: dict[int, tuple] = {}
        if self.fused and self.backend != "scalar":
            try:
                with tracing.span("mapping ladder", daemon="mapping"):
                    self._build_fused(m, sigs, raw, pps_t, prev, engine,
                                      fused, fused_w, tail_sigs)
            except Exception as e:
                from ceph_tpu.common.logging import dout
                dout("mapping", 0, "fused placement ladder failed, "
                     "serving host pipeline tail: %r", e)
                fused, fused_w, tail_sigs = {}, {}, {}
        self.osdmap = m
        self._raw, self._pps, self._sigs = raw, pps_t, sigs
        self._fused, self._fused_w = fused, fused_w
        self._tail_sigs = tail_sigs
        self.epoch = m.epoch
        return _UpdateInfo(prev, recomputed, reused)

    def _build_fused(self, m: OSDMap, sigs: dict, raw: dict,
                     pps_t: dict, prev: _Tables, engine,
                     fused: dict, fused_w: dict,
                     tail_sigs: dict) -> None:
        """Run the device ladder for every pool whose TAIL signature
        moved (raw signature + osd state/weight/affinity digest +
        per-pool override digest); unchanged pools alias their packed
        tables forward.  With an ``engine`` the per-pool ladders
        submit through submit_finish_ladder (pools sharing the epoch
        digest and widths coalesce into one device call, mesh-sharded
        on the PG axis); without one, each pool runs a direct jitted
        call at its own pow-2 bucket (pool pg_nums are powers of two
        in practice, so the bucket set — and the jit cache — stays
        stable under whichever subset recomputes each epoch).

        Maps below ``min_device_pgs`` TOTAL PGs skip the fused build
        entirely (same policy as the raw-table rebuild: per-call
        dispatch + jit-compile overhead dominates toy maps, and the
        host tail is already cheap there); engine-less services and
        dedicated tests default the floor to 0."""
        if sum(int(p.pg_num) for p in m.pools.values()) \
                < self.min_device_pgs:
            return
        from ceph_tpu.ops import placement_kernel as pk
        with tracing.span("mapping ladder operands", daemon="mapping"):
            width, pairs = pk.pool_widths(m)
            vectors = m.dense_osd_vectors()
            state, weight, affinity = vectors
            epoch_digest = (hash(state.tobytes()), hash(weight.tobytes()),
                            hash(affinity.tobytes()), width, pairs)
            ov = _pool_override_digests(m)
            jobs: list[tuple[int, object]] = []
            for pool_id, pool in m.pools.items():
                if pool_id not in raw:
                    continue
                tsig = (sigs[pool_id], epoch_digest, ov.get(pool_id))
                tail_sigs[pool_id] = tsig
                if (prev.tail_sigs.get(pool_id) == tsig
                        and pool_id in prev.fused
                        and raw.get(pool_id) is prev.raw.get(pool_id)):
                    fused[pool_id] = prev.fused[pool_id]
                    fused_w[pool_id] = prev.fused_w[pool_id]
                    continue
                pps = pps_t.get(pool_id)
                if pps is None:
                    # invalid-rule pools skip the remap, but the ladder
                    # still needs the affinity seed (it is what
                    # _finish_pg_mapping would compute per read)
                    pgids = np.arange(pool.pg_num, dtype=np.uint32)
                    pps = pps_batch(pool, pgids)
                    pps_t[pool_id] = pps
                jobs.append((pool_id, pk.build_operands(
                    m, pool_id, pool, raw[pool_id], pps, width=width,
                    pairs=pairs, vectors=vectors)))
        if not jobs:
            return
        if engine is not None:
            from ceph_tpu.ops.dispatch import BACKGROUND_BEST_EFFORT
            from ceph_tpu.ops.dispatch import submit_finish_ladder
            # submit and wait under ONE span, so that the engine
            # requests parent under the span that waits for them
            with tracing.span("mapping ladder run", daemon="mapping"):
                futs = [(pid, submit_finish_ladder(
                    engine, op,
                    cost_tag=("system", BACKGROUND_BEST_EFFORT)))
                        for pid, op in jobs]
                for pid, fut in futs:
                    fused[pid] = _engine_result(engine, fut)
                    fused_w[pid] = width
        else:
            # per-pool direct calls, NOT a concatenated group: pool
            # pg_nums are powers of two in practice, so each pool hits
            # one stable jit bucket, while a concatenated batch of
            # whichever subset recomputed this epoch walks a different
            # pow2 bucket per churn kind and recompiles on toy hosts
            for pid, op in jobs:
                fused[pid] = pk.run_ladder(op)
                fused_w[pid] = width

    def fused_complete(self) -> bool:
        """True when every pool of the cached map has a packed fused
        table — the gate for device-diff deltas and the
        fused-vs-fallback epoch counters."""
        return (self.osdmap is not None
                and all(pid in self._fused for pid in self.osdmap.pools))

    def get_raw(self, pool_id: int) -> np.ndarray:
        """(pg_num, size) int32 raw CRUSH output, CRUSH_ITEM_NONE holes."""
        return self._raw[pool_id]

    def get(self, pool_id: int, pgid: int
            ) -> tuple[list[int], int, list[int], int]:
        """Full pipeline for one PG: a fused-table row read when the
        device ladder ran, the host tail over the cached raw placement
        otherwise."""
        f = self._fused.get(pool_id)
        if f is not None and 0 <= pgid < f.shape[0]:
            from ceph_tpu.ops import placement_kernel as pk
            return pk.unpack_row(f[pgid], self._fused_w[pool_id])
        return _finish_from(self.osdmap, self.osdmap.pools[pool_id],
                            pool_id, pgid, self._raw, self._pps)

    def pg_counts(self, pool_id: int) -> np.ndarray:
        """Per-OSD PG count histogram for a pool (balancer input)."""
        raw = self._raw[pool_id]
        valid = raw[(raw != CRUSH_ITEM_NONE) & (raw >= 0)]
        return np.bincount(valid, minlength=self.osdmap.max_osd)


class SharedPGMappingService:
    """The epoch-keyed shared mapping cache (one per CephTpuContext).

    See the module docstring for the design.  Thread contract: any
    number of concurrent update_to()/lookup() callers; one update
    computes at a time, later targets queue with only the newest kept
    (epoch-skip), waiters return as soon as the cache reaches their
    epoch."""

    #: delta-log entries retained (epoch transitions a lagging reader
    #: can still be served incrementally)
    DELTA_LOG = 64

    #: packed fused tables at/below this many elements diff with one
    #: vectorized numpy compare instead of a device call — per-call
    #: dispatch overhead dominates tiny tables, exactly the
    #: osdmap_mapping_min_pgs rationale (1M elements ~ a 100k-PG pool
    #: at width 3, where the device/mesh diff starts paying)
    FUSED_DIFF_HOST_MAX = 1 << 20

    def __init__(self, ctx=None, backend: str = "tpu",
                 fused: bool = True):
        self._cv = lockdep.make_condition("SharedPGMappingService::cv")
        self._ctx = ctx
        #: "scalar" for tests and engine-less tools
        self._backend = backend
        #: False keeps the per-PG host tail (the fused rows' reference
        #: in tests)
        self._fused = fused
        self._mapping: OSDMapMapping | None = None
        self._tables: dict[int, _Tables] = {}     # current + previous epoch
        self._deltas: deque = deque(maxlen=self.DELTA_LOG)
        self._pending: OSDMap | None = None
        self._updating = False
        #: the service's published epoch — MONOTONIC, unlike the inner
        #: mapping's (a warm() against an older map rebuilds tables
        #: without regressing this, so update_to waiters can rely on
        #: "epoch only moves forward")
        self._epoch = -1
        #: False after a warm() installed tables outside the online
        #: epoch sequence: the NEXT online update's delta would be
        #: computed against those tables, so it must not be logged
        self._chain_valid = True
        self.stats = telemetry.mapping_stats()
        # table-sized buffers from here on, every epoch: see
        # common/allocator.py
        pin_malloc_thresholds()

    # -- plumbing -------------------------------------------------------------

    @property
    def epoch(self) -> int:
        return self._epoch

    def _engine(self):
        if self._ctx is None or self._backend == "scalar":
            return None
        return self._ctx.dispatch_engine()

    def _mesh(self):
        """The mesh for the on-device epoch diff — EXACTLY the mesh
        the engine places this service's remap batches over (its
        process-local submesh under jax.distributed; the diff tables
        are process-local host data, so placing onto non-addressable
        devices would raise).  Delegates to the engine so the
        multi-controller placement rule lives in one place."""
        eng = self._engine()
        if eng is None:
            return None
        try:
            return eng.placement_mesh()
        except Exception:
            return None

    def _ensure_mapping(self) -> OSDMapMapping:
        if self._mapping is None:
            self._mapping = OSDMapMapping(backend=self._backend,
                                          fused=self._fused)
        if self._ctx is not None:
            try:
                self._mapping.min_device_pgs = int(
                    self._ctx.conf.get("osdmap_mapping_min_pgs"))
            except KeyError:
                pass
        return self._mapping

    # -- epoch advance --------------------------------------------------------

    def update_to(self, osdmap: OSDMap,
                  from_epoch: int | None = None) -> MapUpdate:
        """Bring the cache to (at least) osdmap's epoch and return the
        delta since ``from_epoch`` (default: the service's previous
        epoch).  Concurrent callers advancing the same epoch share one
        computation; a burst queues and only the newest target is
        computed.

        A trace root site (common/tracing): an untraced caller opens
        an ``update_to`` trace when tracing is armed, a traced one gets
        a child span; every stage below is a span of that tree, one
        per epoch (never per PG)."""
        with tracing.root("update_to", daemon="mapping",
                          epoch=osdmap.epoch):
            return self._update_to(osdmap, from_epoch)

    def _update_to(self, osdmap: OSDMap,
                   from_epoch: int | None) -> MapUpdate:
        with tracing.span("mapping cv wait", daemon="mapping",
                          wait=True), self._cv:
            if from_epoch is None:
                from_epoch = self.epoch
            target = osdmap.epoch
            if target > self.epoch:
                # queue with only the newest target kept; skipped
                # intermediates are counted ONCE, by the jump
                # arithmetic of whichever update actually runs
                if (self._pending is None
                        or target > self._pending.epoch):
                    self._pending = osdmap
            while True:
                if self.epoch >= target:
                    return self._delta_since(from_epoch, target)
                if self._updating:
                    self._cv.wait()
                    continue
                work = self._pending
                self._pending = None
                if work is None or work.epoch <= self.epoch:
                    # the queued target was consumed by an update that
                    # FAILED (or was superseded): re-queue our own map
                    # so this loop makes progress instead of spinning
                    if (self._pending is None
                            or target > self._pending.epoch):
                        self._pending = osdmap
                    continue
                self._updating = True
                chain_valid = self._chain_valid
                mapping = self._ensure_mapping()
                break
        t0 = time.perf_counter()
        delta_s = host_tail_s = 0.0
        try:
            info = mapping.update(work, engine=self._engine())
            device_s = time.perf_counter() - t0
            if chain_valid:
                with tracing.span("mapping delta", daemon="mapping"):
                    changed, full, delta_s, host_tail_s = \
                        self._compute_delta(info)
            else:
                # prev tables came from a warm() outside the online
                # sequence: a delta against them would be discarded
                # below anyway — skip the whole candidate pass
                changed, full = None, True
        except BaseException:
            with self._cv:
                self._updating = False
                self._cv.notify_all()
            raise
        dt = time.perf_counter() - t0
        cached_pgs = sum(int(r.shape[0]) for r in mapping._raw.values())
        with tracing.span("mapping install", daemon="mapping"), self._cv:
            prev = info.prev
            newt = _Tables(work, mapping._raw, mapping._pps,
                           mapping._sigs, work.epoch,
                           fused=mapping._fused,
                           fused_w=mapping._fused_w,
                           tail_sigs=mapping._tail_sigs)
            self._tables = ({prev.epoch: prev, work.epoch: newt}
                            if prev.epoch >= 0 else {work.epoch: newt})
            if full or not self._chain_valid:
                # chain break (first map, or the prev tables came from
                # a warm() outside the online sequence): a delta
                # against them must never be served to online readers
                self._deltas.clear()
            else:
                self._deltas.append((prev.epoch, work.epoch,
                                     tuple(changed)))
            self._chain_valid = True
            skipped = (work.epoch - prev.epoch - 1
                       if prev.epoch >= 0 else 0)
            self._epoch = max(self._epoch, work.epoch)
            self._updating = False
            self._cv.notify_all()
        with tracing.span("mapping account", daemon="mapping"):
            if skipped > 0:
                self.stats.record_skip(skipped)
            self.stats.record_update(
                seconds=dt, recomputed=len(info.recomputed),
                reused=len(info.reused),
                changed=(len(changed) if not full else cached_pgs),
                cached_pgs=cached_pgs, cached_pools=len(mapping._raw))
            self.stats.record_fused_epoch(mapping.fused_complete())
            # where did this epoch go: device remap vs candidate
            # extraction vs the host pipeline tail (ROADMAP item 2's
            # bottleneck question, readable via dump_mapping_stats)
            self.stats.record_phases(device_s=device_s, delta_s=delta_s,
                                     host_tail_s=host_tail_s)
            with self._cv:
                # work.epoch >= target and _epoch is monotonic, so the
                # cache is guaranteed at/past the caller's map now; the
                # delta is clamped to the CALLER's epoch, not the head
                return self._delta_since(from_epoch, target)

    def warm(self, osdmap: OSDMap) -> None:
        """Make the cache serve THIS map object — the offline-consumer
        entry (balancer, osdmaptool, what-if runs) whose maps sit at a
        fixed epoch, are rebuilt per run, or may not even belong to
        the online cluster.  A map already served (same object, or a
        content-equal copy of a cached epoch) binds for the cost of a
        signature hash; anything else rebuilds DETACHED from the
        online epoch sequence: tables install for reads, but the
        incremental delta chain is invalidated (never extended with a
        diff against offline tables), the published epoch never
        regresses, and the next online update serves one full rescan.
        On a context shared with online consumers a warm therefore
        costs them cache hits, never correctness — the deployed
        topology gives daemons their own contexts."""
        if self._tables_for(osdmap) is not None:
            with self._cv:
                self._epoch = max(self._epoch, osdmap.epoch)
            return
        with self._cv:
            while self._updating:
                self._cv.wait()
            self._updating = True
            mapping = self._ensure_mapping()
        t0 = time.perf_counter()
        try:
            info = mapping.update(osdmap, engine=self._engine())
        except BaseException:
            with self._cv:
                self._updating = False
                self._cv.notify_all()
            raise
        cached_pgs = sum(int(r.shape[0]) for r in mapping._raw.values())
        with self._cv:
            self._tables = {osdmap.epoch: _Tables(
                osdmap, mapping._raw, mapping._pps, mapping._sigs,
                osdmap.epoch, fused=mapping._fused,
                fused_w=mapping._fused_w,
                tail_sigs=mapping._tail_sigs)}
            self._deltas.clear()
            self._chain_valid = False
            self._epoch = max(self._epoch, osdmap.epoch)
            self._updating = False
            self._cv.notify_all()
        self.stats.record_update(
            seconds=time.perf_counter() - t0,
            recomputed=len(info.recomputed), reused=len(info.reused),
            changed=0, cached_pgs=cached_pgs,
            cached_pools=len(mapping._raw))
        self.stats.record_fused_epoch(mapping.fused_complete())

    def _delta_since(self, from_epoch: int,
                     to_epoch: int | None = None) -> MapUpdate:
        """Union of logged deltas covering EXACTLY (from_epoch,
        to_epoch] — clamped to the caller's own map epoch, never the
        (possibly newer) cache head: a PG that changed at the caller's
        epoch but reverted by the head would be invisible in the
        head-spanning union, yet the caller's map DOES see it.
        Called under the lock."""
        tgt = self.epoch if to_epoch is None else min(to_epoch,
                                                     self.epoch)
        if from_epoch >= tgt:
            return MapUpdate(from_epoch, tgt, (), False)
        changed: set = set()
        e = tgt
        for frm, to, delta in reversed(self._deltas):
            if to > e:
                if frm < e:
                    break    # tgt sits inside a skipped jump
                continue     # entry entirely newer than the caller
            if to != e:
                break
            changed.update(delta)
            e = frm
            if e <= from_epoch:
                break
        if e != from_epoch:
            # chain gap (first map, log overflow, a reader epoch inside
            # a skipped jump, or a warm() broke the chain): full
            # rescan, still served from cache where possible
            self.stats.record_full_rescan()
            return MapUpdate(from_epoch, tgt, None, True)
        return MapUpdate(from_epoch, tgt, sorted(changed), False)

    # -- delta derivation -----------------------------------------------------

    def _fused_delta(self, old: _Tables, mapping: OSDMapMapping):
        """Exact changed-PG set by diffing both epochs' PACKED fused
        tables on device: rows encode the full oracle tuple with
        deterministic padding, so row inequality IS tuple inequality —
        no candidate extraction, no per-candidate host tail.  Returns
        None when either epoch lacks complete fused coverage (the host
        candidate path below stays the exactness fallback)."""
        m_new = mapping.osdmap
        m_old = old.osdmap
        mesh = self._mesh()
        changed: list[tuple[int, int]] = []
        for pool_id, pool in m_new.pools.items():
            newp = mapping._fused.get(pool_id)
            if newp is None:
                return None
            old_pool = m_old.pools.get(pool_id)
            if old_pool is None:
                changed.extend((pool_id, pg)
                               for pg in range(pool.pg_num))
                continue
            oldp = old.fused.get(pool_id)
            if oldp is None:
                return None
            wn = mapping._fused_w[pool_id]
            wo = old.fused_w[pool_id]
            if wn == wo and oldp.shape == newp.shape:
                if oldp.size <= self.FUSED_DIFF_HOST_MAX:
                    # toy tables: one vectorized host compare beats a
                    # device round trip by ~30x on this class of host;
                    # production pool sizes take the device diff below
                    mask = np.flatnonzero((oldp != newp).any(axis=1))
                    changed.extend((pool_id, int(pg)) for pg in mask)
                    self.stats.record_delta_diff(device=False)
                    continue
                rows = _changed_rows(oldp, newp, mesh=mesh)
                with tracing.span("mapping delta list", daemon="mapping",
                                  rows=len(rows)):
                    changed.extend((pool_id, int(pg)) for pg in rows)
                continue
            # shared width or pg_num moved (override growth, pool
            # resize): normalize to a common layout and compare the
            # overlapping rows host-side — rare, and still exact
            from ceph_tpu.ops.placement_kernel import normalize_packed
            w = max(wo, wn)
            a = normalize_packed(oldp, wo, w)
            b = normalize_packed(newp, wn, w)
            k = min(a.shape[0], b.shape[0])
            if k:
                for pg in np.flatnonzero((a[:k] != b[:k]).any(axis=1)):
                    changed.append((pool_id, int(pg)))
                self.stats.record_delta_diff(device=False)
            changed.extend((pool_id, pg)
                           for pg in range(k, newp.shape[0]))
        with tracing.span("mapping delta sort", daemon="mapping"):
            return sorted(changed)

    def _compute_delta(self, info: _UpdateInfo):
        """Exact changed-PG set for one epoch transition.  With
        complete fused tables on both sides the delta is a pure
        device diff of the packed outputs (_fused_delta) and the host
        tail contributes NOTHING; otherwise candidates come
        from (a) the on-device raw-table diff of recomputed pools,
        (b) PGs whose raw rows reference OSDs with changed up/exists
        state or primary affinity, and (c) override-keyed PGs whose
        entries moved (or any override key when osd visibility/weights
        moved — upmap validity reads them); then each candidate's full
        (up, up_primary, acting, acting_primary) is compared old-vs-new
        through the cached tables.  O(changed + overrides) host work.

        Returns (changed, full, delta_s, host_tail_s): the epoch's
        phase split — candidate extraction (incl. the on-device raw
        diff) vs the per-candidate host pipeline tail."""
        t0 = time.perf_counter()
        old = info.prev
        mapping = self._mapping
        m_new = mapping.osdmap
        if old.osdmap is None or old.epoch < 0:
            return None, True, 0.0, 0.0
        fused = self._fused_delta(old, mapping)
        if fused is not None:
            return fused, False, time.perf_counter() - t0, 0.0
        m_old = old.osdmap
        no = max(m_old.max_osd, m_new.max_osd, 1)
        st = (_vec(m_old.osd_state, no) != _vec(m_new.osd_state, no))
        af = (_vec(m_old.osd_primary_affinity, no, MAX_AFFINITY)
              != _vec(m_new.osd_primary_affinity, no, MAX_AFFINITY))
        changed_osds = np.flatnonzero(st | af)
        weights_moved = bool((_vec(m_old.osd_weight, no)
                              != _vec(m_new.osd_weight, no)).any())
        cand: set[tuple[int, int]] = set()
        recomputed = set(info.recomputed)
        mesh = self._mesh()     # once per epoch, not per pool
        for pool_id, pool in m_new.pools.items():
            new_raw = mapping._raw.get(pool_id)
            if new_raw is None:
                continue
            old_pool = m_old.pools.get(pool_id)
            old_raw = old.raw.get(pool_id)
            if (old_pool is None or old_raw is None
                    or old_pool.pg_num != pool.pg_num
                    or old_pool.type != pool.type
                    or old_raw.shape != new_raw.shape):
                cand.update((pool_id, pg) for pg in range(pool.pg_num))
                continue
            if pool_id in recomputed:
                for pg in _changed_rows(old_raw, new_raw, mesh=mesh):
                    cand.add((pool_id, int(pg)))
                if old_pool.pgp_num != pool.pgp_num:
                    # pps is the affinity seed: it can move a primary
                    # even where the raw row happens to coincide
                    po = old.pps.get(pool_id)
                    pn = mapping._pps.get(pool_id)
                    if po is None or pn is None:
                        cand.update((pool_id, pg)
                                    for pg in range(pool.pg_num))
                    else:
                        for pg in np.flatnonzero(po != pn):
                            cand.add((pool_id, int(pg)))
            if changed_osds.size and new_raw.size:
                mask = np.isin(new_raw, changed_osds).any(axis=1)
                if old_raw is not new_raw:   # reused pools alias
                    mask |= np.isin(old_raw, changed_osds).any(axis=1)
                for pg in np.flatnonzero(mask):
                    cand.add((pool_id, int(pg)))
        ov_keys: set[tuple[int, int]] = set()
        for attr in ("pg_temp", "primary_temp", "pg_upmap",
                     "pg_upmap_items"):
            do = getattr(m_old, attr)
            dn = getattr(m_new, attr)
            for k in set(do) | set(dn):
                if do.get(k) != dn.get(k):
                    ov_keys.add(k)
            if changed_osds.size or weights_moved:
                ov_keys.update(do)
                ov_keys.update(dn)
        for pool_id, pg in ov_keys:
            pool = m_new.pools.get(pool_id)
            if pool is not None and 0 <= pg < pool.pg_num:
                cand.add((pool_id, pg))
        t_cand = time.perf_counter()
        changed = []
        for pool_id, pg in cand:
            pool_n = m_new.pools[pool_id]
            new_t = _finish_from(m_new, pool_n, pool_id, pg,
                                 mapping._raw, mapping._pps)
            pool_o = m_old.pools.get(pool_id)
            old_t = None
            if (pool_o is not None and pg < pool_o.pg_num
                    and pool_id in old.raw
                    and pg < old.raw[pool_id].shape[0]):
                old_t = _finish_from(m_old, pool_o, pool_id, pg,
                                     old.raw, old.pps)
            if new_t != old_t:
                changed.append((pool_id, pg))
        return (sorted(changed), False, t_cand - t0,
                time.perf_counter() - t_cand)

    # -- reads ----------------------------------------------------------------

    def _tables_for(self, osdmap: OSDMap) -> _Tables | None:
        with self._cv:
            t = self._tables.get(osdmap.epoch)
            if t is None:
                return None
            # identity first: the module contract is that maps are
            # immutable once published, so the object the tables were
            # built from IS the epoch's content
            if t.osdmap is osdmap or t._has(t.bound, osdmap):
                return t
            if t._has(t.rejected, osdmap):
                return None
        # a DIFFERENT object at the same epoch — usually another
        # daemon's decode of the same published map.  Equal placement
        # signatures mean bit-identical raw tables (the pipeline tail
        # always reads the CALLER's map), so content-check once and
        # bind; a mismatch (foreign cluster sharing a context) is
        # memoized too so every later read is a cheap oracle fallback
        try:
            _csig, sigs = pool_signatures(osdmap)
        except Exception:
            return None
        tail_ok = False
        with self._cv:
            t2 = self._tables.get(osdmap.epoch)
        if t2 is not None and sigs == t2.sigs and t2.fused:
            # the raw signature deliberately excludes tail inputs:
            # verify them once (outside the lock — pure content
            # compare) so this copy may read the FUSED rows too;
            # a tail-divergent copy still binds, but reads go through
            # the host tail against its own map
            try:
                tail_ok = _tail_equal(t2.osdmap, osdmap)
            except Exception:
                tail_ok = False
        with self._cv:
            t3 = self._tables.get(osdmap.epoch)
            if t3 is None:
                return None
            if sigs == t3.sigs:
                t3._memo(t3.bound, osdmap)
                # tail_ok was verified against t2's map: only valid if
                # the published tables were not swapped meanwhile (a
                # racing warm() replacing the epoch)
                if tail_ok and t3 is t2:
                    t3._memo(t3.tail_bound, osdmap)
                return t3
            t3._memo(t3.rejected, osdmap)
            return None

    def lookup(self, osdmap: OSDMap, pool_id: int, pgid: int
               ) -> tuple[list[int], int, list[int], int]:
        """pg_to_up_acting_osds served from the cache — a packed-row
        read when the fused ladder published this pool (and the caller
        holds the service's map object or a tail-verified copy), the
        host pipeline tail over the cached raw row otherwise;
        scalar-oracle fallback on any epoch/object/pool mismatch."""
        pool = osdmap.pools[pool_id]
        t = self._tables_for(osdmap)
        if t is not None:
            if t.fused and (t.osdmap is osdmap
                            or t._has(t.tail_bound, osdmap)):
                fr = t.fused.get(pool_id)
                if fr is not None and 0 <= pgid < fr.shape[0]:
                    self.stats.record_lookup(True, fused=True)
                    from ceph_tpu.ops.placement_kernel import unpack_row
                    return unpack_row(fr[pgid], t.fused_w[pool_id])
            row = t.raw.get(pool_id)
            if row is not None and 0 <= pgid < row.shape[0]:
                self.stats.record_lookup(True)
                return _finish_from(osdmap, pool, pool_id, pgid,
                                    t.raw, t.pps)
        self.stats.record_lookup(False)
        return osdmap.pg_to_up_acting_osds(pool_id, pgid)

    def raw_row(self, osdmap: OSDMap, pool_id: int,
                pg: int) -> list[int] | None:
        """Cached _pg_to_raw_osds row (balancer's what-if input), or
        None when the cache cannot serve this map/pool."""
        t = self._tables_for(osdmap)
        if t is None:
            return None
        r = t.raw.get(pool_id)
        if r is None or not (0 <= pg < r.shape[0]):
            return None
        row = [int(o) for o in r[pg]]
        if not osdmap.pools[pool_id].is_erasure():
            row = [o for o in row if o != CRUSH_ITEM_NONE]
        return row

    def what_if_up(self, osdmap: OSDMap, pool_id: int,
                   candidates: list[tuple[int, list]]
                   ) -> list[list[int]] | None:
        """Batched what-if scoring for the balancer: the ``up`` set
        each candidate ``(pg, upmap_items_pairs)`` would produce —
        raw row + pair rewrites + state filtering, NO full-upmap/temp
        overrides, exactly the host ``up_of`` the balancer used to run
        per candidate — evaluated for ALL candidates in one fused
        ladder call.  None when the cache cannot serve this map or the
        fused ladder is unavailable (caller falls back to the host
        pipeline)."""
        if not candidates:
            return []
        mapping = self._mapping
        if (mapping is None or not getattr(mapping, "fused", False)
                or mapping.backend == "scalar"):
            return None
        t = self._tables_for(osdmap)
        if t is None:
            return None
        raw = t.raw.get(pool_id)
        pps = t.pps.get(pool_id)
        pool = osdmap.pools.get(pool_id)
        if raw is None or pps is None or pool is None:
            return None
        pgs = [pg for pg, _prs in candidates]
        if any(not (0 <= pg < raw.shape[0]) for pg in pgs):
            return None
        from ceph_tpu.ops import placement_kernel as pk
        b = len(candidates)
        pairs = max(max((len(prs) for _pg, prs in candidates),
                        default=1), 1)
        width = max(int(pool.size), raw.shape[1], 1)
        state, weight, affinity = osdmap.dense_osd_vectors()
        idx = np.asarray(pgs, dtype=np.int64)
        items = np.full((b, pairs, 2), -1, dtype=np.int32)
        for i, (_pg, prs) in enumerate(candidates):
            for j, (frm, to) in enumerate(prs[:pairs]):
                items[i, j, 0] = frm
                items[i, j, 1] = to
        ops_ = pk.LadderOperands(
            raw=pk.pad_raw(raw[idx], width),
            pps=np.asarray(pps)[idx].astype(np.uint32),
            raw_len=np.full(b, raw.shape[1], dtype=np.int32),
            up_rows=np.full((b, width), CRUSH_ITEM_NONE,
                            dtype=np.int32),
            up_len=np.zeros(b, dtype=np.int32),
            items=items,
            temp_rows=np.full((b, width), -1, dtype=np.int32),
            temp_len=np.zeros(b, dtype=np.int32),
            ptemp=np.full(b, -1, dtype=np.int32),
            state=state, weight=weight, affinity=affinity,
            max_osd=osdmap.max_osd, erasure=pool.is_erasure(),
            width=width)
        try:
            engine = self._engine()
            if engine is not None:
                from ceph_tpu.ops.dispatch import (
                    BACKGROUND_BEST_EFFORT, submit_finish_ladder)
                packed = np.asarray(submit_finish_ladder(
                    engine, ops_,
                    cost_tag=("system", BACKGROUND_BEST_EFFORT),
                ).result(timeout=ENGINE_WAIT_S))
            else:
                packed = pk.run_ladder(ops_)
        except Exception:
            return None
        return [pk.unpack_row(packed[i], width)[0] for i in range(b)]

    def pg_counts(self, osdmap: OSDMap, pool_id: int) -> np.ndarray:
        """Per-OSD PG count histogram for a pool (osdmaptool input);
        requires the cache to be at this map (update_to it first)."""
        t = self._tables_for(osdmap)
        if t is None:
            raise KeyError(f"mapping cache not at epoch {osdmap.epoch}")
        raw = t.raw[pool_id]
        valid = raw[(raw != CRUSH_ITEM_NONE) & (raw >= 0)]
        return np.bincount(valid, minlength=osdmap.max_osd)

    def place(self, crush: CrushMap, ruleno: int, xs, numrep: int,
              reweight) -> np.ndarray:
        """Bulk rule evaluation for offline tools (psim/crushtool):
        the production path — cached mapper, dispatch-engine
        submission — without needing an OSDMap."""
        xs = np.asarray(xs, dtype=np.uint32)
        reweight = np.asarray(reweight, dtype=np.int64)
        mapping = self._ensure_mapping()
        if mapping.backend == "scalar":
            return scalar_rows(crush, ruleno, xs, numrep, reweight)
        bm = mapping.mapper_for(crush)
        engine = self._engine()
        if engine is not None:
            from ceph_tpu.ops.dispatch import (
                BACKGROUND_BEST_EFFORT, submit_do_rule)
            return np.asarray(submit_do_rule(
                engine, bm, ruleno, xs, numrep, reweight,
                cost_tag=("system", BACKGROUND_BEST_EFFORT),
            ).result(timeout=ENGINE_WAIT_S))
        return np.asarray(bm.do_rule(ruleno, xs, numrep, reweight))
