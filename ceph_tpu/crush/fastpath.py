"""Fused fast path for the canonical CRUSH rules on two-level maps.

The generic batched mapper (mapper_jax) re-draws the whole batch every retry
ladder iteration and pads every bucket row to the global max bucket size.  For
the rule shapes that carry ~all real placement traffic —

    take root
    chooseleaf firstn N type-t     (replicated pools; mapper.c:460-648)
    emit
and
    take root
    choose firstn N osd            (flat maps)
    emit

over a *uniform two-level* straw2 hierarchy (root -> type-t buckets ->
devices), a better device schedule exists because the retry ladder's r values
are shared across replicas: replica ``rep`` draws with r = rep + ftotal, so
the whole ladder for all reps only ever consumes root/leaf winners at
r in [0, numrep + max_ftotal).  The fast path therefore:

  1. precomputes straw2 winners for a block of r values — a fori_loop
     producing one r column per step (root (N, H) draw -> winner; that
     host's item/weight rows, padded only to the max *leaf* size, -> (N, S)
     leaf draw -> device + its is_out verdict);
  2. consumes them with numrep cheap masked while_loops whose bodies are
     (N,)-sized gathers and compares — no redraws, and reps 1..n-1 reuse the
     winners rep 0 already paid for;
  3. if any lane's ftotal walks past the precomputed block (rare: needs many
     consecutive collisions/rejections), a lax.cond re-runs the same
     computation with the full r range R = tries + numrep, which by
     construction cannot overflow — bit-exactness is unconditional, the big
     recompute just never happens on healthy maps.

(A weight-class decomposition — draws are monotone in the 16-bit hash, so
only the max-u item per distinct weight can win — was evaluated and rejected:
truncated-quotient ties between items are common at realistic bucket weights
(quotient spacing ~ crush_ln slope / w approaches 1 for host-sized w), so an
exactness fallback triggers on virtually every bulk call.  The argmax over
full per-item draws handles ties for free.)

No XLA gather and no row sort on a table of the batch's size: the
winners' is_out verdicts come from the devices' reweight words, fetched
by an exact one-hot product (crush_kernel.out_columns), and the NONE
holes leave the result rows by a fixed network of selects
(_compact_rows) — on a TPU XLA's gather costs 7-11 ns a cell, and the
two were a sixth of the program (PERF.md, PR 40).  What is left of
either kind is the two-stage schedule's own, on stage 2's lanes.

Bit-exactness: validated against the scalar oracle (crush.mapper_ref) in
tests/test_mapper_jax.py::test_fastpath_* across skewed weights, reweights,
out OSDs, uneven host sizes, and forced-fallback configurations.

What is compiled and what is data.  A program depends on a rule's
*shape class* (``FastShape``: kind, numrep, tries, vary_r and the padded
table shapes) and on nothing of a map's content: the bucket tables —
ids, weights, magic divisors, the leaf kernel's packed rows — are
operands (``build_tables``, held with their device copies by
``FastTables``).  A host added, a host removed, an item reweighted is a
host-side table build and an upload; a program is built only when an
edit crosses a padded class (tests/test_crush_reshape.py).

What is drawn once a map.  A straw2 winner is a function of x, r and
the bucket tables alone — is_out rejects after the descent and the
retry moves to r + 1 (mapper.c) — so the first attempt's winner planes
of a batch are the same in every epoch that leaves the CRUSH map and
the batch's seeds alone: reweights, OSDs out, down or back.  ``run``
returns them and takes them back (``columns``): one program holds both
branches, the drawing one and the one that reuses the operand, under a
``lax.cond``; ``FastTables`` keeps them for the map by the batch's
content (``Seeds``), and they die with the tables
(tests/test_crush_column_cache.py).
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops import telemetry
from ceph_tpu.ops.crush_kernel import (
    compact_planes, out_columns, reweight_words)
from ceph_tpu.ops.straw2_u32 import (
    _ln_f32_error_bound, magic_tables, straw2_choose_index_approx)

from .types import (
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_EMIT,
    RULE_SET_CHOOSE_TRIES,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_TAKE,
    CrushMap,
)

NONE = jnp.int32(CRUSH_ITEM_NONE)

#: extra r-values beyond numrep precomputed in the first block.  6 covers
#: every lane on healthy maps (ftotal beyond 6 needs seven consecutive
#: collision/reject draws); the overflow cond recomputes with the full
#: range when it ever does not, so this is a latency knob, not a
#: correctness one.
DEFAULT_BLOCK = 6


@dataclass
class FastRule:
    """Host-side description of a fast-path-eligible rule."""

    kind: str                 # "chooseleaf" | "choose_flat"
    numrep_arg: int           # step arg1 (0 -> result_max)
    tries: int                # choose_total_tries + 1 (or SET override)
    vary_r: int
    root_ids: np.ndarray      # (H,) root bucket items
    root_w: np.ndarray        # (H,) int64 16.16 weights
    leaf_ids: np.ndarray | None   # (H, S) device ids, row per root item
    leaf_w: np.ndarray | None     # (H, S) int64, 0-padded
    max_devices: int


def detect(m: CrushMap, ruleno: int) -> FastRule | None:
    """Return a FastRule if ``ruleno`` on map ``m`` fits the fused kernel."""
    t = m.tunables
    if (t.choose_local_tries or t.choose_local_fallback_tries
            or t.chooseleaf_stable != 1):
        return None
    rule = m.rules[ruleno]
    if rule is None:
        return None
    tries = t.choose_total_tries + 1
    core: list = []
    for step in rule.steps:
        if step.op == RULE_SET_CHOOSE_TRIES:
            if step.arg1 > 0:
                tries = step.arg1
        elif step.op == RULE_SET_CHOOSELEAF_TRIES:
            if step.arg1 > 0 and step.arg1 != 1:
                return None  # leaf retry loop not fused
        else:
            core.append(step)
    if len(core) != 3:
        return None
    take, choose, emit = core
    if take.op != RULE_TAKE or emit.op != RULE_EMIT:
        return None
    root = m.bucket(take.arg1)
    if root is None or root.alg != CRUSH_BUCKET_STRAW2 or root.size == 0:
        return None
    if root.size > 1024:
        return None  # (N, R, H) blocks would dwarf the iterative cost
    root_ids = np.asarray(root.items, dtype=np.int32)
    root_w = np.asarray(root.item_weights, dtype=np.int64)

    if choose.op == RULE_CHOOSE_FIRSTN and choose.arg2 == 0:
        # flat: every root item is a device
        if any(i < 0 or i >= m.max_devices for i in root.items):
            return None
        return FastRule(
            kind="choose_flat", numrep_arg=choose.arg1, tries=tries,
            vary_r=t.chooseleaf_vary_r, root_ids=root_ids, root_w=root_w,
            leaf_ids=None, leaf_w=None, max_devices=m.max_devices)

    if choose.op != RULE_CHOOSELEAF_FIRSTN:
        return None
    if not t.chooseleaf_descend_once:
        # without descend_once the leaf recursion retries inside the host
        # (recurse_tries = choose_tries, mapper.c:1041-1046); the fused
        # kernel only models the single-attempt (descend_once) semantics
        return None
    want_type = choose.arg2
    hosts = []
    for item in root.items:
        h = m.bucket(item)
        if (h is None or h.alg != CRUSH_BUCKET_STRAW2
                or h.type != want_type or h.size == 0):
            return None
        if any(i < 0 or i >= m.max_devices for i in h.items):
            return None
        hosts.append(h)
    s_max = max(h.size for h in hosts)
    leaf_ids = np.zeros((len(hosts), s_max), dtype=np.int32)
    leaf_w = np.zeros((len(hosts), s_max), dtype=np.int64)
    for row, h in enumerate(hosts):
        leaf_ids[row, :h.size] = h.items
        leaf_w[row, :h.size] = h.item_weights
    return FastRule(
        kind="chooseleaf", numrep_arg=choose.arg1, tries=tries,
        vary_r=t.chooseleaf_vary_r, root_ids=root_ids, root_w=root_w,
        leaf_ids=leaf_ids, leaf_w=leaf_w, max_devices=m.max_devices)


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

def _draw_argmax(x, ids, weights, r, magic, off):
    """Straw2 winner position for one r value across the batch.

    x (N,) uint32; ids (S,) shared or (N, S) per-lane rows; weights /
    magic / off broadcastable to ids; r scalar uint32.  Returns (N,)
    positions.  Runs the u32 magic-division kernel (ops.straw2_u32) —
    bit-exact against the s64 kernel by exhaustive validation — whose
    argmin takes the first minimum, exactly the strict-``>`` scan of
    bucket_straw2_choose (mapper.c:374-380): truncation ties resolve to
    the lowest index for free.
    """
    idb = ids[None, :] if ids.ndim == 1 else ids
    wb = jnp.broadcast_to(
        weights[None, :] if weights.ndim == 1 else weights, idb.shape)
    mb = jnp.broadcast_to(
        magic[None, :, :] if magic.ndim == 2 else magic, (*idb.shape, 5))
    ob = jnp.broadcast_to(
        off[None, :] if off.ndim == 1 else off, idb.shape)
    return straw2_choose_index_approx(x, idb, r, wb, mb, ob)


def _consume(host_win, leaf_win, leaf_bad, numrep, tries, R, n):
    """Walk the firstn ladder over precomputed winners.

    host_win (N, R) int32: first-level item chosen at r (host id, or the
    device itself for flat rules).  leaf_win (N, R) int32: device at r.
    leaf_bad (N, R) bool: device rejected (is_out).  Returns
    (out_host, out_leaf, overflow): (N, numrep) selections with NONE holes
    and a per-lane flag for ftotal walking past R.
    """
    out_h = jnp.full((n, numrep), NONE, dtype=jnp.int32)
    out_l = jnp.full((n, numrep), NONE, dtype=jnp.int32)
    overflow = jnp.zeros((n,), dtype=bool)

    for rep in range(numrep):
        def cond(s):
            return jnp.any(s[3])

        def body(s, rep=rep, out_h=out_h, out_l=out_l):
            sel_h, sel_l, ft, act, ovf = s
            r = rep + ft
            within = r < R
            ridx = jnp.minimum(r, R - 1)[:, None]
            hb = jnp.take_along_axis(host_win, ridx, 1)[:, 0]
            lf = jnp.take_along_axis(leaf_win, ridx, 1)[:, 0]
            bad_l = jnp.take_along_axis(leaf_bad, ridx, 1)[:, 0]
            coll_h = jnp.any(out_h == hb[:, None], axis=1)
            coll_l = jnp.any(out_l == lf[:, None], axis=1)
            bad = coll_h | coll_l | bad_l
            place = act & within & ~bad
            sel_h = jnp.where(place, hb, sel_h)
            sel_l = jnp.where(place, lf, sel_l)
            ft = jnp.where(act & within & bad, ft + 1, ft)
            ovf = ovf | (act & ~within)
            act = act & within & bad & (ft < tries)
            return sel_h, sel_l, ft, act, ovf

        sel0 = jnp.full((n,), NONE, dtype=jnp.int32)
        sel_h, sel_l, _, _, overflow = jax.lax.while_loop(
            cond, body,
            (sel0, sel0, jnp.zeros((n,), jnp.int32),
             jnp.ones((n,), bool), overflow))
        out_h = out_h.at[:, rep].set(sel_h)
        out_l = out_l.at[:, rep].set(sel_l)
    return out_h, out_l, overflow


def _compact_rows(cols, result_max: int):
    """(numrep, N) selections with NONE holes -> the (N, result_max)
    rows of do_rule: the holes move behind the placed cells by the
    select network (``compact_planes``: no row sort, no
    ``take_along_axis``, each a gather on the chip), on planes with the
    batch on the lane axis, then one transpose; NONE fills a result
    wider than numrep."""
    planes = [cols[j] for j in range(cols.shape[0])]
    (planes,), _count = compact_planes(
        [c != NONE for c in planes], (planes, NONE))
    planes = planes[:result_max]
    planes += [jnp.full_like(planes[0], NONE)] * (result_max - len(planes))
    return jnp.stack(planes, axis=1)


#: lanes the XLA path pads a bucket to (the Pallas kernels pad the
#: root to their 128-lane vreg, ``pallas_straw2._pad_lanes``, and a
#: host to the leaf kernel's group width, ``_leaf_lanes``): a shape
#: class holds every map whose root and widest host round up alike, so
#: a host or an OSD added inside the padding reuses the class's
#: compiled program
XLA_LANES = 8


def _on_tpu() -> bool:
    """Whether programs built now run on a TPU.  Honors
    jax.default_device(<tpu>) too: a multi-platform process (cpu
    default + tpu reachable) running under that context IS on the tpu
    even though default_backend() still says cpu."""
    dd = getattr(jax.config, "jax_default_device", None)
    if dd is not None:
        # jax.default_device accepts a Device OR a platform string
        return getattr(dd, "platform", str(dd)) == "tpu"
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class FastShape:
    """What a fast-path program is traced for, and nothing of a map's
    content: the rule's kind and retry constants and the padded table
    shapes.  Maps of one FastShape share one FastMapper and its
    compiled programs.

    On the Pallas route ``leaf_lanes`` is the leaf kernel's group
    width: the widest host's items rounded up to 32 or 64, where
    128 / width r-columns share a 128-lane slab, and past 64 items to
    a multiple of 128 (one column over one slab or more).  An edit
    that keeps the widest host inside its width serves on the class's
    programs; one that grows it past 32, 64, 128, 256 ... items
    crosses a class and compiles once.  The root pads to 128 lanes and
    crosses at 128, 256 ... hosts; the XLA route pads both to
    ``XLA_LANES``."""

    kind: str                 # "chooseleaf" | "choose_flat"
    numrep_arg: int
    tries: int
    vary_r: int
    root_lanes: int           # padded root items (= leaf-table rows)
    leaf_lanes: int           # a host's lanes (above); 0 for a flat rule
    #: the fused Pallas column kernels (2.5x the XLA path on a TPU);
    #: the CPU mesh tests keep the XLA path
    pallas: bool
    interpret: bool = False   # Pallas in interpret mode (CPU tests)


def shape_of(fr: FastRule, pallas: bool | None = None,
             interpret: bool = False) -> FastShape:
    """The shape class of a rule on this backend (``pallas`` None: the
    Pallas kernels on a TPU, the XLA path elsewhere)."""
    if pallas is None:
        pallas = _on_tpu()
    if pallas:
        from ceph_tpu.ops.pallas_straw2 import _leaf_lanes, _pad_lanes
        pad, pad_leaf = _pad_lanes, _leaf_lanes
    else:
        def pad(n):
            return -(-n // XLA_LANES) * XLA_LANES
        pad_leaf = pad
    leaf = 0 if fr.leaf_ids is None else pad_leaf(fr.leaf_ids.shape[1])
    return FastShape(fr.kind, fr.numrep_arg, fr.tries, fr.vary_r,
                     pad(len(fr.root_ids)), leaf, bool(pallas),
                     bool(interpret))


def build_tables(fr: FastRule, shape: FastShape) -> tuple:
    """One map's bucket tables as the class's program takes them: host
    arrays, zero-padded to the class (a padded item has weight 0 and
    never wins; a padded host row is never selected).  The XLA path's
    order is root ids, weights, magic limbs, limb offsets, then the
    same four a host row; the Pallas kernels' is
    ``pallas_straw2.pack_tables``."""
    S, L = shape.root_lanes, shape.leaf_lanes
    ids = np.zeros(S, dtype=np.int32)
    ids[:len(fr.root_ids)] = fr.root_ids
    w = np.zeros(S, dtype=np.int64)
    w[:len(fr.root_w)] = fr.root_w
    lids = lw = None
    if fr.leaf_ids is not None:
        H, S_l = fr.leaf_ids.shape
        lids = np.zeros((S, L), dtype=np.int32)
        lids[:H, :S_l] = fr.leaf_ids
        lw = np.zeros((S, L), dtype=np.int64)
        lw[:H, :S_l] = fr.leaf_w
    if shape.pallas:
        from ceph_tpu.ops.pallas_straw2 import pack_tables
        return pack_tables(ids, w, lids, lw)
    tables = [ids, w, *magic_tables(w)]
    if lids is not None:
        tables += [lids, lw, *magic_tables(lw)]
    return tuple(tables)


class FastTables:
    """One (map, rule)'s bucket tables: the host arrays of
    ``build_tables`` and their device copies, one per placement (the
    default device, or replicated over a mesh for mesh-sharded
    batches), and the winner planes batches drew on them (``columns``).
    The content of a map lives here and nowhere in a program."""

    def __init__(self, shape: FastShape, host: tuple):
        self.shape = shape
        self.host = host
        self.nbytes = sum(int(a.nbytes) for a in host)
        self._placed: dict = {}
        #: (placement, batch key) -> the winner planes a batch drew on
        #: these tables (``FastMapper.run``): they die with the tables
        self._columns: dict = {}
        self._used: set = set()

    def placed(self, mesh=None) -> bool:
        """Whether ``on(mesh)`` has uploaded already."""
        return mesh in self._placed

    def on(self, mesh=None) -> tuple:
        """The tables on the default device (``mesh`` None) or
        replicated over ``mesh``, uploaded on first use; the bytes are
        counted in MappingStats (``crush_table_upload_bytes``)."""
        dev = self._placed.get(mesh)
        if dev is None:
            if mesh is None:
                dev = tuple(jnp.asarray(a) for a in self.host)
                copies = 1
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                dev = jax.device_put(
                    self.host, NamedSharding(mesh, PartitionSpec()))
                copies = mesh.size
            self._placed[mesh] = dev
            telemetry.mapping_stats().record_crush_table_upload(
                self.nbytes * copies)
        return dev

    def columns(self, mesh, key):
        """The winner planes a batch of ``key`` (its result width and
        ``Seeds.key``) drew on these tables where ``on(mesh)`` places
        them, or None."""
        got = self._columns.get((mesh, key))
        if got is not None:
            self._used.add((mesh, key))
        return got

    def keep_columns(self, mesh, key, planes) -> None:
        self._columns[(mesh, key)] = planes
        self._used.add((mesh, key))

    def sweep_columns(self) -> None:
        """A new epoch's first batch: planes the previous epoch's
        batches did not use go (a pool resized or gone: 32 MB an entry
        at 1 Mi lanes)."""
        self._columns = {k: v for k, v in self._columns.items()
                         if k in self._used}
        self._used = set()


class Seeds:
    """One request's CRUSH inputs as the column cache knows them:
    ``of``, the seeds (kept while the caller reuses the same array,
    so the digest is made once), their ``digest`` — count and bytes as
    the engine places them, uint32 — and ``columns``, what the last
    batch that carried them found: "hit", "miss" or None (no cached
    program ran)."""

    __slots__ = ("of", "digest", "columns")

    def __init__(self, xs):
        self.of = xs
        xs = np.ascontiguousarray(xs, dtype=np.uint32)
        self.digest = (xs.shape[0], hashlib.blake2b(
            xs.tobytes(), digest_size=16).digest())
        self.columns = None

    @staticmethod
    def key(parts, pad: int) -> tuple:
        """A batch's key: its parts' digests in order and the zero
        lanes padded after them — never a pool id or an epoch."""
        return tuple(p.digest for p in parts), pad


def tables_of(fr: FastRule, pallas: bool | None = None,
              interpret: bool = False) -> FastTables:
    """A rule's tables, built for its shape class on this backend (or
    on the path named: the chip's cross-validation runs both)."""
    shape = shape_of(fr, pallas, interpret)
    if shape.pallas and shape.leaf_lanes:
        from ceph_tpu.ops.pallas_straw2 import _columns_per_slab
        telemetry.mapping_stats().record_leaf_layout(
            _columns_per_slab(shape.leaf_lanes),
            fr.leaf_ids.shape[1] / shape.leaf_lanes)
    return FastTables(shape, build_tables(fr, shape))


@functools.lru_cache(maxsize=None)
def mapper_for(shape: FastShape) -> "FastMapper":
    """The process's one FastMapper of a shape class."""
    return FastMapper(shape)


class FastMapper:
    """The fast path of one shape class (``FastShape``): every method
    takes the bucket tables (``build_tables``) as an argument, so a
    program traced from ``run`` serves any map of the class — a host
    added, an item reweighted — with new operands and no compile."""

    def __init__(self, shape: FastShape):
        self.shape = shape
        _ln_f32_error_bound()   # measure eagerly: must be concrete by
        self._pallas = None     # the time jit traces
        if shape.pallas:
            # Mesh-sharded batches reach these kernels through the
            # shard_map wrapper in mapper_jax._fast_program (a
            # pallas_call is an opaque custom call GSPMD cannot split,
            # so the batch splits BEFORE the kernel; run() itself is
            # row-independent along x by the oracle-equivalence
            # contract).  Construction failures must surface, not
            # silently degrade to the slower XLA path
            from ceph_tpu.ops.pallas_straw2 import PallasColumns
            self._pallas = PallasColumns(
                shape.root_lanes, shape.leaf_lanes, shape.vary_r,
                interpret=shape.interpret)

    def _winners(self, xs, tables, R: int):
        """The first-level and leaf winner planes (R, N) for r in
        [0, R): a fori_loop producing one r column per step (bounds the
        (N, H) ln-matmul intermediates to a single r; an unrolled R-wide
        block OOMs HBM at bulk batch sizes).  A flat rule's one plane
        serves as both."""
        shape = self.shape
        root_ids, root_w, root_magic, root_off = tables[:4]
        n = xs.shape[0]
        flat = shape.kind == "choose_flat"
        planes0 = tuple(jnp.full((R, n), NONE, dtype=jnp.int32)
                        for _ in range(1 if flat else 2))

        def body(i, planes):
            r = i.astype(jnp.uint32)
            pos = _draw_argmax(xs, root_ids, root_w, r,
                               root_magic, root_off)
            cols = (root_ids[pos],)                            # (N,)
            if not flat:
                leaf_ids, leaf_w, leaf_magic, leaf_off = tables[4:]
                # r_leaf = vary_r ? r >> (vary_r-1) : 0 (mapper.c:578)
                if shape.vary_r:
                    r_leaf = r >> jnp.uint32(shape.vary_r - 1)
                else:
                    r_leaf = jnp.uint32(0)
                ids = leaf_ids[pos]                            # (N, S)
                w = leaf_w[pos]                                # (N, S)
                lpos = _draw_argmax(xs, ids, w, r_leaf,
                                    leaf_magic[pos], leaf_off[pos])
                cols += (jnp.take_along_axis(ids, lpos[:, None], 1)[:, 0],)
            return tuple(jax.lax.dynamic_update_slice(p, c[None, :], (i, 0))
                         for p, c in zip(planes, cols))

        return jax.lax.fori_loop(0, R, body, planes0)

    def _columns(self, xs, tables, R: int):
        """The first-level and leaf winner planes (R, n_padded) for r in
        [0, R), in the Pallas kernels' native column layout (no
        transposes); a flat rule's one plane serves as both.

        Root columns go through the fused approx-filter kernel when the
        R columns' candidates fit one lane block; its certificate flag
        (any column with more than K items inside the measured f32
        error band) falls the whole batch back to the exact column
        kernel, so bit-exactness is unconditional."""
        pc = self._pallas
        from ceph_tpu.ops.pallas_straw2 import _KPACK
        if R * _KPACK <= 128 and 512 <= pc.S_root <= 1024:
            # the approx filter narrows each column from S items to K
            # candidates — a win only when S spans many slabs (big flat
            # buckets); at host-count-sized roots the packing machinery
            # costs more than the exact pipeline it saves (measured).
            # Upper bound: the extractor packs item positions into 10
            # bits (pallas_straw2._extract_candidates), so past 1024
            # items the certificate would fire on every batch and the
            # filter pass would be pure overhead
            pos, ids, ovf = pc.froot_columns(xs, tables, R)
            pos, ids = jax.lax.cond(
                jnp.any(ovf != 0),
                lambda _: pc.root_columns(xs, tables, R),
                lambda _: (pos, ids), None)
        else:
            pos, ids = pc.root_columns(xs, tables, R)
        if self.shape.kind == "choose_flat":
            return (ids,)
        return ids, pc.leaf_columns(xs, pos, tables, R)

    @staticmethod
    def _first_columns(columns, draw):
        """The first attempt's winner planes: ``draw()``, or — where
        ``columns`` is (have, planes) and ``have`` is set — the planes
        an earlier call drew from the same seeds on the same tables.
        A winner is a function of x, r and the bucket tables alone
        (mapper.c: is_out rejects after the descent, and the retry
        moves to r + 1), so the reweights never enter them.  Both
        branches are one program: a cached answer builds nothing."""
        if columns is None:
            return draw()
        have, planes = columns
        return jax.lax.cond(have, lambda: tuple(planes), draw)

    #: minimum batch for the two-stage schedule; below it one pass at R0
    #: is cheaper than the compaction plumbing
    TWO_STAGE_MIN = 32768
    #: stage-2 capacity: lanes whose ladder outran the stage-1 columns.
    #: At realistic reject/collision rates the expected count is a few
    #: hundred per 64Ki (p ~ fail^2 per lane); 4096 makes the capacity
    #: overflow a tail-of-tail event, and the guard recomputes the whole
    #: batch when it ever fires, so it costs latency, never correctness.
    STAGE2_CAP = 4096
    #: ... and never less than one lane in STAGE2_SHARE of the batch:
    #: the count grows with the batch, and a fixed capacity turns the
    #: guard into the rule (on the 10,000-OSD map 2.9% of lanes overflow
    #: stage 1: 1,900 of 64Ki, 31,000 of 1Mi).  At 64Ki lanes one in 16
    #: is the 4096 above.
    STAGE2_SHARE = 16

    def _run_pallas(self, xs, word, tables, result_max, numrep,
                    R, R0, Rf, columns):
        """Winner columns and the consume ladder both on-device in their
        native (R, N) layout — no transposes, no XLA while_loops.

        Bulk batches run a two-stage schedule (``R`` < ``R0``): stage 1
        computes only numrep+1 columns for every lane (covers lanes
        whose firstn ladder saw at most one failure in the last replica
        — ~99% at realistic maps), then gathers the overflowing lanes
        into one compact STAGE2_CAP batch that gets the full R0
        treatment.  The placement for a given x is identical either way
        — the ladder is deterministic in (x, columns) — so this is pure
        scheduling, the oracle-equivalence property is untouched.
        Returns the rows and the first attempt's planes."""
        from ceph_tpu.ops.pallas_straw2 import consume_columns
        shape = self.shape
        n = xs.shape[0]
        interp = self._pallas.interpret

        def attempt(xv, R, cols=None):
            m = xv.shape[0]
            if cols is None:
                cols = self._columns(xv, tables, R)
            # the winner columns come back padded to the kernel block
            # quantum
            n_pad = cols[0].shape[1]
            xs_pad = jnp.concatenate(
                [xv, jnp.zeros((n_pad - m,), dtype=xv.dtype)]) \
                if n_pad > m else xv
            # is_out runs OUTSIDE the kernels, in XLA over the (R, n_pad)
            # winner planes: the in-kernel variant hit a Mosaic
            # miscompile (hash32_2 fed from the winner gather/sum
            # pipeline went wrong for ~0.03% of lanes, compiled mode
            # only; caught by TPU-vs-XLA cross-validation in round 3).
            # It is not cheap by itself — a gather of the reweight
            # vector for the planes was 68 ms of a 567 ms call at 1 Mi
            # lanes (PERF.md, section 6) — so the devices' words are fetched
            # by a one-hot product (out_columns)
            lb = out_columns(word, cols[-1], xs_pad)
            oh, ol, ovf = consume_columns(
                cols[0], cols[-1], lb, numrep=numrep, tries=shape.tries,
                interpret=interp)
            return oh[:, :m], ol[:, :m], ovf[:m]

        def attempt_full(xv, R, cols=None):
            oh, ol, ovf = attempt(xv, R, cols)
            return jax.lax.cond(
                jnp.any(ovf != 0),
                lambda _: attempt(xv, Rf)[:2],
                lambda _: (oh, ol), None)

        first = self._first_columns(
            columns, lambda: self._columns(xs, tables, R))
        if R == R0:
            out_h, out_l = attempt_full(xs, R0, first)
        else:
            oh1, ol1, ovf1 = attempt(xs, R, first)
            cap = max(self.STAGE2_CAP, n // self.STAGE2_SHARE)
            need = ovf1 != 0
            # overflowing lanes first, stable, then fillers
            order = jnp.argsort(jnp.where(need, 0, 1), stable=True)
            idx_c = order[:cap]
            xs2 = xs[idx_c]

            def merged(_):
                oh2, ol2 = attempt_full(xs2, R0)
                sel = need[idx_c][None, :]
                oh = oh1.at[:, idx_c].set(
                    jnp.where(sel, oh2, oh1[:, idx_c]))
                ol = ol1.at[:, idx_c].set(
                    jnp.where(sel, ol2, ol1[:, idx_c]))
                return oh, ol

            out_h, out_l = jax.lax.cond(
                jnp.sum(need) > cap,
                lambda _: attempt_full(xs, R0),
                merged, None)
        return _compact_rows(
            out_l if shape.kind == "chooseleaf" else out_h,
            result_max), first

    def _run_xla(self, xs, word, tables, result_max, numrep, R0, Rf,
                 columns):
        """The XLA route: winners at R0, the ladder by ``_consume``, the
        full range Rf when a lane overflows.  Returns the rows and the
        first attempt's planes."""
        shape = self.shape
        n = xs.shape[0]

        def ladder(cols, R):
            # is_out a plane row at a time: the one-hot fetch of a whole
            # plane would hold rows x R x N
            lb = jax.lax.map(
                lambda row: out_columns(word, row[None, :], xs)[0],
                cols[-1])
            return _consume(cols[0].T, cols[-1].T, lb.T, numrep,
                            shape.tries, R, n)

        first = self._first_columns(
            columns, lambda: self._winners(xs, tables, R0))
        out_h, out_l, ovf = ladder(first, R0)

        def slow(_):
            oh, ol, _ = ladder(self._winners(xs, tables, Rf), Rf)
            return oh, ol

        out_h, out_l = jax.lax.cond(
            jnp.any(ovf), slow, lambda _: (out_h, out_l), None)
        return _compact_rows(
            (out_l if shape.kind == "chooseleaf" else out_h).T,
            result_max), first

    def _schedule(self, n: int, result_max: int, block: int):
        """(numrep, R, R0, Rf) for a batch of n lanes: the replicas a
        lane places (0: none), the r-columns of the first attempt, of
        the one-stage block and of the full range.  R < R0 is the
        two-stage schedule (``_run_pallas``)."""
        numrep = self.shape.numrep_arg
        if numrep <= 0:
            numrep += result_max
        numrep = max(numrep, 0)
        Rf = self.shape.tries + numrep
        R0 = min(numrep + block, Rf)
        two = (self._pallas is not None and n >= self.TWO_STAGE_MIN
               and numrep + 1 < R0)
        return numrep, (numrep + 1 if two else R0), R0, Rf

    def column_shape(self, n: int, result_max: int,
                     block: int = DEFAULT_BLOCK) -> tuple[int, int, int]:
        """(planes, R, lanes): the first attempt's winner planes that
        ``run`` returns for a batch of n lanes (a device's share on the
        mesh route) are ``planes`` int32 arrays of (R, lanes) — two for
        chooseleaf, one for a flat rule, none where the rule places
        nothing."""
        numrep, R, _R0, _Rf = self._schedule(n, result_max, block)
        if numrep == 0:
            return 0, 0, n
        lanes = n
        if self._pallas is not None:
            from ceph_tpu.ops.pallas_straw2 import padded_batch
            lanes = padded_batch(n)
        return (1 if self.shape.kind == "choose_flat" else 2), R, lanes

    def run(self, xs, reweight, tables, result_max: int,
            block: int = DEFAULT_BLOCK, columns=None):
        """Full do_rule: returns (N, result_max) NONE-compacted placements.
        ``tables`` are the map's (``build_tables``); ``reweight`` may be
        zero-padded past max_osd (weight 0 is out, which is is_out's
        verdict for an id past the vector already).

        With ``columns`` = (have, planes) — the program the column
        cache runs (crush.mapper_jax.BatchMapper.do_rule) — the first
        attempt's winner planes (``column_shape``) are the operand
        where ``have`` is set and are drawn where it is not, and the
        result is (rows, planes).  Everything after them runs either
        way: this call's is_out verdicts, the firstn ladder, stage 2
        and the full-range fallback, which draw their own columns."""
        if isinstance(xs, jax.core.Tracer):
            # a trace of this function is a program built: a compile
            # or a cache load, on whatever path asked for it
            telemetry.mapping_stats().record_program_build()
        n = xs.shape[0]
        numrep, R, R0, Rf = self._schedule(n, result_max, block)
        if numrep == 0:
            rows = jnp.full((n, result_max), NONE, dtype=jnp.int32)
            return rows if columns is None else (rows, columns[1])

        # all is_out asks of a device, built once a program from the
        # int64 operand and fetched without a gather (out_columns)
        word = reweight_words(reweight)

        if self._pallas is not None:
            rows, first = self._run_pallas(xs, word, tables, result_max,
                                           numrep, R, R0, Rf, columns)
        else:
            rows, first = self._run_xla(xs, word, tables, result_max,
                                        numrep, R0, Rf, columns)
        return rows if columns is None else (rows, first)
