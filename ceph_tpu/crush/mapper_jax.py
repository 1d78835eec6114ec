"""Batched CRUSH rule evaluation on device.

One call evaluates a rule for N inputs at once — the TPU-native replacement for
ParallelPGMapper's thread-pool fan-out (src/osd/OSDMapMapping.h:17) and the
CrushTester loop (src/crush/CrushTester.cc:472-560).  Bit-exactness contract:
for any straw2 map with modern tunables, results equal the scalar oracle
(ceph_tpu.crush.mapper_ref, itself written against src/crush/mapper.c) exactly.

Shape of the implementation:
  * the rule program (TAKE/CHOOSE*/EMIT/SET_*) is interpreted in Python — it is
    static per map epoch, exactly like the reference (mapper.c:900-1105);
  * each CHOOSE step runs the whole batch through masked lax.while_loop retry
    ladders: descent through the hierarchy, the firstn collision/reject ladder
    (mapper.c:460-648) with chooseleaf recursion (vary_r/stable semantics), and
    the breadth-first positionally-stable indep pass (mapper.c:655-843);
  * per-lane state is (current bucket, ftotal, active); every draw is a
    straw2 argmax over a gathered bucket row (ops.crush_kernel.straw2_draws).

Working-set values are per-lane (a lane's chosen hosts differ), so multi-step
rules like "take root / choose firstn 0 host / choose firstn 1 osd / emit"
gather per-lane start buckets at each step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops.crush_kernel import hash32_4, is_out

from .compile import CompiledCrushMap, compile_map
from .types import (
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_UNIFORM,
    CRUSH_ITEM_NONE,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSE_INDEP,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_EMIT,
    RULE_SET_CHOOSE_TRIES,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    RULE_SET_CHOOSE_LOCAL_TRIES,
    RULE_SET_CHOOSELEAF_STABLE,
    RULE_SET_CHOOSELEAF_VARY_R,
    RULE_TAKE,
    CrushMap,
    padded_osds,
)

NONE = jnp.int32(CRUSH_ITEM_NONE)


class _Arrays:
    """Device-resident compiled map."""

    def __init__(self, c: CompiledCrushMap):
        self.bucket_id = jnp.asarray(c.bucket_id)
        self.bucket_type = jnp.asarray(c.bucket_type)
        self.bucket_size = jnp.asarray(c.bucket_size)
        self.bucket_alg = jnp.asarray(c.bucket_alg)
        self.items = jnp.asarray(c.items)
        self.weights = jnp.asarray(c.weights)
        self.n_nodes = jnp.asarray(c.n_nodes)
        self.node_weights = jnp.asarray(c.node_weights)
        self.has_tree = c.has_tree
        self.has_uniform = c.has_uniform
        self.max_uniform_size = c.max_uniform_size
        self.n_buckets = c.n_buckets
        self.max_devices = c.max_devices


def _straw2_draws_per_row(x, items_row, r, w_row):
    """Like ops.crush_kernel.straw2_draws but ids/weights differ per lane."""
    from ceph_tpu.crush.types import S64_MIN
    from ceph_tpu.ops.crush_kernel import _LN_2_48, crush_ln, hash32_3
    u = hash32_3(x[:, None], items_row, r[:, None]) & jnp.uint32(0xFFFF)
    ln = crush_ln(u) - _LN_2_48
    w = w_row.astype(jnp.int64)
    draw = -((-ln) // jnp.maximum(w, 1))
    return jnp.where(w > 0, draw, jnp.int64(S64_MIN))


def _tree_winner(a: _Arrays, cur: jax.Array, x: jax.Array,
                 r: jax.Array) -> jax.Array:
    """Tree-bucket winner: weighted binary descent from the root node
    (num_nodes/2) to a leaf (odd node; leaf i at node 2i+1), semantics of
    mapper.c:195-222.  Lanes whose bucket is not a tree terminate at node 1
    immediately; the caller selects them out by alg."""
    is_tree = a.bucket_alg[cur] == jnp.int32(CRUSH_BUCKET_TREE)
    n0 = (a.n_nodes[cur] >> 1).astype(jnp.uint32)
    n0 = jnp.where(is_tree & (n0 > 0), n0, jnp.uint32(1))
    bid = a.bucket_id[cur].astype(jnp.uint32)

    def cond(n):
        return jnp.any((n & 1) == 0)

    def body(n):
        live = (n & 1) == 0
        rows = a.node_weights[cur]                 # (N, T)
        safe = jnp.minimum(n, jnp.uint32(rows.shape[1] - 1)).astype(jnp.int32)
        w = jnp.take_along_axis(rows, safe[:, None], axis=1)[:, 0]
        h = hash32_4(x, n, r, bid).astype(jnp.uint64)
        t = (h * w.astype(jnp.uint64)) >> jnp.uint64(32)
        half = (n & (~n + jnp.uint32(1))) >> 1     # 1 << (h-1)
        left = n - half
        lsafe = jnp.minimum(
            left, jnp.uint32(rows.shape[1] - 1)).astype(jnp.int32)
        lw = jnp.take_along_axis(rows, lsafe[:, None], axis=1)[:, 0]
        nxt = jnp.where(t < lw.astype(jnp.uint64), left, n + half)
        return jnp.where(live, nxt, n)

    n = jax.lax.while_loop(cond, body, n0)
    leaf = (n >> 1).astype(jnp.int32)
    leaf = jnp.minimum(leaf, jnp.int32(a.items.shape[1] - 1))
    return jnp.take_along_axis(a.items[cur], leaf[:, None], axis=1)[:, 0]


def _uniform_winner(a: _Arrays, cur: jax.Array, x: jax.Array,
                    r: jax.Array) -> jax.Array:
    """Uniform-bucket winner (bucket_perm_choose, mapper.c:73-138): the
    permutation is a pure function of (x, bucket id) — each lane
    recomputes the Fisher-Yates prefix up to pr = r % size instead of
    consulting the reference's sequential perm cache, which is what
    makes uniform batchable at all.  Lanes whose bucket is not uniform
    compute garbage the caller selects away by alg."""
    from ceph_tpu.ops.crush_kernel import hash32_3
    size = jnp.maximum(a.bucket_size[cur], 1)          # (N,)
    pr = (r.astype(jnp.uint32)
          % size.astype(jnp.uint32)).astype(jnp.int32)
    bid = a.bucket_id[cur].astype(jnp.uint32)
    # loop bound: the largest UNIFORM bucket, not the map-wide widest
    # bucket (a straw2 root with hundreds of hosts would otherwise
    # multiply this loop's masked work for nothing)
    s_max = min(a.items.shape[1], max(a.max_uniform_size, 1))
    n = cur.shape[0]
    cols = jnp.arange(s_max, dtype=jnp.int32)[None, :]  # (1, S)
    perm0 = jnp.broadcast_to(cols, (n, s_max)).astype(jnp.int32)

    def body(p, perm):
        p32 = jnp.int32(p)
        # swap only while building the prefix (p <= pr) and while a
        # swap can matter (p < size-1); i == 0 swaps in place (no-op)
        live = (p32 <= pr) & (p32 < size - 1)
        span = jnp.maximum(size - p32, 1).astype(jnp.uint32)
        i = (hash32_3(x, bid, jnp.uint32(p))
             % span).astype(jnp.int32)              # (N,)
        idx = p32 + i
        val_p = perm[:, p]
        val_i = jnp.take_along_axis(perm, idx[:, None], axis=1)[:, 0]
        at_p = cols == p32
        at_i = cols == idx[:, None]
        swapped = jnp.where(at_i, val_p[:, None], perm)
        swapped = jnp.where(at_p, val_i[:, None], swapped)
        return jnp.where(live[:, None], swapped, perm)

    perm = jax.lax.fori_loop(0, s_max, body, perm0)
    s = jnp.take_along_axis(perm, pr[:, None], axis=1)[:, 0]
    return jnp.take_along_axis(a.items[cur], s[:, None], axis=1)[:, 0]


def _winner(a: _Arrays, cur: jax.Array, x: jax.Array, r: jax.Array) -> jax.Array:
    """Winner of bucket index ``cur`` for each lane: straw2 argmax (first max
    wins, mapper.c:361-384; choose_args overrides are scalar-path only),
    tree descent for tree buckets, or the recomputed uniform permutation
    — when the map contains those algs at all."""
    items_row = a.items[cur]                      # (N, S)
    w_row = a.weights[cur]                        # (N, S) — padding weight 0
    d = _straw2_draws_per_row(x, items_row, r, w_row)
    pos = jnp.argmax(d, axis=-1)
    out = jnp.take_along_axis(items_row, pos[:, None], axis=1)[:, 0]
    if a.has_tree:
        tw = _tree_winner(a, cur, x, r)
        out = jnp.where(
            a.bucket_alg[cur] == jnp.int32(CRUSH_BUCKET_TREE), tw, out)
    if a.has_uniform:
        uw = _uniform_winner(a, cur, x, r)
        out = jnp.where(
            a.bucket_alg[cur] == jnp.int32(CRUSH_BUCKET_UNIFORM),
            uw, out)
    return out


def _widx(a: _Arrays, item: jax.Array) -> jax.Array:
    """Bucket index of a (negative) item, clipped for safe gathering."""
    return jnp.clip(-1 - item, 0, a.n_buckets - 1)


def _wtype(a: _Arrays, item: jax.Array) -> jax.Array:
    """Type of an item: devices are 0, buckets their bucket_type."""
    return jnp.where(item < 0, a.bucket_type[_widx(a, item)], 0)


def _descend(a: _Arrays, x, start, r, want_type, active,
             ftotal=None, numrep: int = 0):
    """One full descent: from per-lane ``start`` bucket, draw and follow
    sub-buckets until an item of ``want_type`` (or a terminal failure).

    With ftotal/numrep given (the INDEP path), ``r`` is the BASE
    (rep + parent_r) and the retry offset is recomputed PER BUCKET on
    the way down: uniform buckets whose size divides numrep use
    (numrep+1)*ftotal instead of numrep*ftotal (mapper.c:720-728's
    "be careful" — without it the same permutation slot repeats on
    every retry and the position wedges).

    Returns (item, fail_perm, fail_retry):
      item       winner of want_type where neither failure flag is set
      fail_perm  skip_rep conditions — out-of-range device, wrong-type device,
                 unresolvable bucket (mapper.c:540-556 / 744-760)
      fail_retry empty bucket on the path (reject; mapper.c:533-537)
    """
    def cond(s):
        return jnp.any(s[3])

    def body(s):
        item, perm, retry, live, cur, rlast = s
        empty = a.bucket_size[cur] == 0
        if ftotal is None:
            rr = r
        else:
            mult = jnp.int32(numrep)
            if a.has_uniform and numrep > 0:
                special = ((a.bucket_alg[cur]
                            == jnp.int32(CRUSH_BUCKET_UNIFORM))
                           & (a.bucket_size[cur] % numrep == 0))
                mult = jnp.where(special, mult + 1, mult)
            rr = r + mult * ftotal
        win = _winner(a, cur, x, rr)
        wt = _wtype(a, win)
        oob = (win >= 0) & (win >= a.max_devices)
        reached = ~empty & ~oob & (wt == want_type)
        is_sub = win < 0
        new_perm = live & ~empty & ~reached & (oob | ~is_sub)
        new_retry = live & empty
        descend = live & ~empty & ~reached & ~new_perm
        item = jnp.where(live & reached, win, item)
        perm = perm | new_perm
        retry = retry | new_retry
        # the r actually used at the level that produced the winner:
        # the indep chooseleaf recursion inherits it as parent_r
        rlast = jnp.where(live, jnp.broadcast_to(rr, rlast.shape),
                          rlast)
        cur = jnp.where(descend, _widx(a, win), cur)
        live = descend
        return item, perm, retry, live, cur, rlast

    item0 = jnp.full_like(start, CRUSH_ITEM_NONE)
    f = jnp.zeros_like(active)
    r0 = jnp.broadcast_to(jnp.asarray(r, jnp.int32),
                          start.shape).astype(jnp.int32)
    out = jax.lax.while_loop(
        cond, body, (item0, f, f, active, start, r0))
    return out[0], out[1], out[2], out[5]


def _leaf_firstn(a: _Arrays, x, host_item, sub_r, leaf_out, rep, tries,
                 reweight, active):
    """chooseleaf recursion (stable tunable): choose 1 device inside
    ``host_item`` with r = sub_r + ftotal, colliding against leaves of earlier
    reps (out2 scoping, mapper.c:580-596).  Returns (leaf, ok)."""
    start = _widx(a, host_item)

    def cond(s):
        return jnp.any(s[2])

    def body(s):
        leaf, ftotal, live = s
        r = sub_r + ftotal
        item, perm, retry, _rl = _descend(a, x, start, r, 0, live)
        got = live & ~perm & ~retry
        collide = jnp.zeros_like(live)
        if rep > 0:
            collide = jnp.any(leaf_out[:, :rep] == item[:, None], axis=1)
        rejected = is_out(reweight, item, x)
        bad = collide | rejected | ~got
        leaf = jnp.where(live & got & ~bad, item, leaf)
        placed = live & got & ~bad
        ftotal = jnp.where(live & ~placed, ftotal + 1, ftotal)
        live = live & ~placed & ~perm & (ftotal < tries)
        return leaf, ftotal, live

    leaf0 = jnp.full_like(host_item, CRUSH_ITEM_NONE)
    leaf, _, _ = jax.lax.while_loop(
        cond, body, (leaf0, jnp.zeros_like(host_item), active))
    return leaf, leaf != NONE


def _choose_firstn(a: _Arrays, x, start, numrep, want_type, tries,
                   recurse_tries, vary_r, recurse_to_leaf, reweight, active):
    """Batched crush_choose_firstn (mapper.c:460-648), modern tunables.

    Returns (out, leaf_out): (N, numrep) int32, CRUSH_ITEM_NONE holes where a
    rep was abandoned (the scalar result is the NONE-compacted row).
    """
    n = x.shape[0]
    out = jnp.full((n, numrep), NONE, dtype=jnp.int32)
    leaf_out = jnp.full((n, numrep), NONE, dtype=jnp.int32)

    for rep in range(numrep):
        def cond(s):
            return jnp.any(s[3])

        def body(s, rep=rep):
            sel, leaf_sel, ftotal, live = s
            r = rep + ftotal
            item, perm, retry, _rl = _descend(a, x, start, r, want_type,
                                              live)
            got = live & ~perm & ~retry
            collide = jnp.any(out == item[:, None], axis=1) if numrep > 1 \
                else jnp.zeros_like(live)
            reject = jnp.zeros_like(live)
            leaf = jnp.full_like(item, CRUSH_ITEM_NONE)
            if recurse_to_leaf:
                # sub_r = vary_r ? r >> (vary_r-1) : 0 (mapper.c:578)
                sub_r = (r >> (vary_r - 1)) if vary_r else jnp.zeros_like(r)
                leaf, leaf_ok = _leaf_firstn(
                    a, x, item, sub_r, leaf_out, rep, recurse_tries,
                    reweight, got & ~collide)
                reject = got & ~collide & ~leaf_ok
            if want_type == 0:
                reject = reject | (got & is_out(reweight, item, x))
            bad = collide | reject | retry | ~got
            placed = live & ~perm & ~bad
            sel = jnp.where(placed, item, sel)
            if recurse_to_leaf:
                leaf_sel = jnp.where(placed, leaf, leaf_sel)
            ftotal = jnp.where(live & ~perm & bad, ftotal + 1, ftotal)
            live = live & ~perm & bad & (ftotal < tries)
            return sel, leaf_sel, ftotal, live

        sel0 = jnp.full((n,), NONE, dtype=jnp.int32)
        sel, leaf_sel, _, _ = jax.lax.while_loop(
            cond, body,
            (sel0, sel0, jnp.zeros((n,), jnp.int32), active))
        out = out.at[:, rep].set(sel)
        leaf_out = leaf_out.at[:, rep].set(leaf_sel)
    return out, leaf_out


def _leaf_indep(a: _Arrays, x, host_item, rep: int, parent_r, numrep_mult,
                tries, reweight, active):
    """indep chooseleaf recursion: positionally stable single-device pick at
    position ``rep``: r = rep + parent_r + numrep*ftotal with the parent's
    numrep as multiplier (the oracle's recursion wiring, mapper.c:794-806).
    Terminal (oob/wrong-type) failures are permanent, like the C break that
    leaves CRUSH_ITEM_NONE."""
    start = _widx(a, host_item)

    def cond(s):
        return jnp.any(s[2])

    def body(s):
        leaf, ftotal, live = s
        item, perm, retry, _rl = _descend(a, x, start, rep + parent_r,
                                          0, live, ftotal=ftotal,
                                          numrep=numrep_mult)
        got = live & ~perm & ~retry
        rejected = is_out(reweight, item, x)
        placed = got & ~rejected
        leaf = jnp.where(placed, item, leaf)
        ftotal = ftotal + 1
        live = live & ~placed & ~perm & (ftotal < tries)
        return leaf, ftotal, live

    leaf0 = jnp.full_like(host_item, CRUSH_ITEM_NONE)
    leaf, _, _ = jax.lax.while_loop(
        cond, body, (leaf0, jnp.zeros_like(host_item), active))
    return leaf, leaf != NONE


def _choose_indep(a: _Arrays, x, start, left, numrep_mult, want_type, tries,
                  recurse_tries, recurse_to_leaf, reweight, active):
    """Batched crush_choose_indep (mapper.c:655-843): breadth-first over
    ``left`` positions, r = rep + numrep*ftotal with the *step's* numrep as
    multiplier even when left < numrep; failures leave CRUSH_ITEM_NONE."""
    n = x.shape[0]
    out = jnp.full((n, left), NONE, dtype=jnp.int32)
    leaf_out = jnp.full((n, left), NONE, dtype=jnp.int32)
    undef = jnp.broadcast_to(active[:, None], (n, left)) & True

    def cond(s):
        out, leaf_out, undef, ftotal = s
        return jnp.any(undef) & (ftotal < tries)

    def body(s):
        out, leaf_out, undef, ftotal = s
        for rep in range(left):
            live = undef[:, rep]
            base = jnp.full((n,), rep, jnp.int32)
            item, perm, retry, host_r = _descend(
                a, x, start, base, want_type, live,
                ftotal=ftotal, numrep=numrep_mult)
            got = live & ~perm & ~retry
            collide = jnp.any(out == item[:, None], axis=1)
            reject = jnp.zeros_like(live)
            leaf = jnp.full_like(item, CRUSH_ITEM_NONE)
            if recurse_to_leaf:
                leaf, leaf_ok = _leaf_indep(
                    a, x, item, rep, host_r, numrep_mult,
                    recurse_tries, reweight, got & ~collide)
                reject = got & ~collide & ~leaf_ok
            if want_type == 0:
                reject = reject | (got & is_out(reweight, item, x))
            placed = got & ~collide & ~reject
            out = out.at[:, rep].set(jnp.where(placed, item, out[:, rep]))
            if recurse_to_leaf:
                leaf_out = leaf_out.at[:, rep].set(
                    jnp.where(placed, leaf, leaf_out[:, rep]))
            # perm: terminal failure, position stays NONE (mapper.c:744-760)
            undef = undef.at[:, rep].set(live & ~placed & ~perm)
        return out, leaf_out, undef, ftotal + 1

    out, leaf_out, _, _ = jax.lax.while_loop(
        cond, body, (out, leaf_out, undef, jnp.int32(0)))
    return out, leaf_out


def _compact_rows(rows: jax.Array) -> jax.Array:
    """Stable-compact NONE holes to the end of each row (firstn semantics:
    the scalar result is the dense prefix).  jnp.argsort is stable."""
    order = jnp.argsort(rows == NONE, axis=1)
    return jnp.take_along_axis(rows, order, axis=1)


#: the process's fast-path programs, keyed by what their trace depends
#: on and nothing of a map's content: (fastpath.FastShape, result_max,
#: the batch's row sharding or None).  The bucket tables are operands
#: (fastpath.FastTables), so every map of a shape class — a host added,
#: an item reweighted — runs the program the first one built.
_FAST_PROGRAMS: dict = {}

#: zero winner planes, one per (program, batch shape): the operand of a
#: call that draws its own (``have`` false), put once and never read
_COLUMN_FILLERS: dict = {}


def _fast_program(shape, result_max: int, sh=None):
    """The jitted ``FastMapper.run`` of a shape class in its column-cache
    form — ``fn(xs, reweight, tables, (have, planes)) -> (rows,
    planes)``.  With a row sharding ``sh`` the outputs carry ``sh`` and
    its ``lane_sharding``, so planes fed back meet the entry that made
    them; on the Pallas route through the shard_map wrapper: the
    column kernels are opaque custom calls GSPMD cannot split, so each
    device runs the full fused ladder on its local rows
    (row-independent by the oracle-equivalence contract) with the
    reweight vector and the bucket tables replicated and the planes
    split on their lane axis like the rows."""
    key = (shape, result_max, sh)
    fn = _FAST_PROGRAMS.get(key)
    if fn is None:
        from . import fastpath
        from ceph_tpu.ops.gf_kernel import (
            build_sharded_rows_fn, lane_sharding)
        mapper = fastpath.mapper_for(shape)

        def run(xs, reweight, tables, columns):
            return mapper.run(xs, reweight, tables, result_max,
                              columns=columns)

        # through a partial the programs keep the names a device trace
        # shows (BENCHMARK.json's programs.crush): jit names a partial
        # _unknown, shard_map names it after the function
        run = functools.partial(run)
        if sh is None:
            fn = jax.jit(run)
        elif shape.pallas:
            # two replicated operands: reweight, and the tuple of
            # tables (one spec covers its leaves)
            fn = build_sharded_rows_fn(run, sh, n_replicated=2,
                                       lane_planes=True)
        else:
            fn = jax.jit(run, out_shardings=(sh, lane_sharding(sh)))
        _FAST_PROGRAMS[key] = fn
    return fn


def _column_filler(key, shape, sharding):
    """Zero planes of ``shape`` (fastpath.FastMapper.column_shape, the
    lanes of the whole batch) where ``sharding`` puts them, or on the
    default device; kept per ``key``."""
    k = (key, shape)
    got = _COLUMN_FILLERS.get(k)
    if got is None:
        count, R, lanes = shape
        zero = np.zeros((R, lanes), dtype=np.int32)
        got = tuple(jax.device_put(zero, sharding) for _ in range(count))
        _COLUMN_FILLERS[k] = got
    return got


class BatchMapper:
    """Batched crush_do_rule over one crush map.

    >>> bm = BatchMapper(crush_map)
    >>> out = bm.do_rule(ruleno, xs, result_max, reweight)   # (N, result_max)

    firstn rules return NONE-compacted rows (dense prefix, NONE tail); indep
    rules return positionally-stable rows with NONE holes — matching the
    scalar crush_do_rule's list semantics in both cases.

    What is per map here is content only.  A rule the fused fast path
    takes (crush.fastpath: two levels, firstn) holds the map's bucket
    tables (``fast_tables``) and runs the process-wide program of its
    shape class (``_fast_program``): building a BatchMapper for an
    edited map costs a host-side table build and an upload, no
    compile.  The generic interpreter (three levels, indep rules, tree
    and uniform buckets) still closes over the compiled map, so its
    programs are per content (``_jit_cache``).
    """

    def __init__(self, m: CrushMap, compiled: CompiledCrushMap | None = None):
        self.map = m
        # host work of a millisecond, and the batchability check
        # (ValueError for list / legacy straw buckets)
        self.compiled = compiled or compile_map(m)
        self._arrays: _Arrays | None = None
        self._jit_cache: dict = {}
        self._fast_cache: dict = {}

    @property
    def arrays(self) -> _Arrays:
        """The generic interpreter's device-resident compiled map,
        uploaded when a rule first needs it (the fast path never
        does)."""
        if self._arrays is None:
            self._arrays = _Arrays(self.compiled)
        return self._arrays

    def fast_tables(self, ruleno: int):
        """This map's bucket tables for the fused two-level kernel
        (fastpath.FastTables), built on first use; None if the rule
        does not fit it."""
        if ruleno not in self._fast_cache:
            from . import fastpath
            ft = None
            fr = fastpath.detect(self.map, ruleno)
            if fr is not None:
                ft = fastpath.tables_of(fr)
                from ceph_tpu.ops import telemetry
                telemetry.mapping_stats().record_crush_table_build()
            self._fast_cache[ruleno] = ft
        return self._fast_cache[ruleno]

    def has_fast_tables(self, ruleno: int) -> bool:
        """Whether ``fast_tables(ruleno)`` has been settled (built, or
        found not to fit): the mapping service spans the build."""
        return ruleno in self._fast_cache

    def _jit_entries(self) -> int:
        """Compile-cache entries across every jitted rule evaluator
        this mapper can call — the telemetry retrace counter
        differences this per call."""
        return (sum(f._cache_size() for f in self._jit_cache.values())
                + sum(f._cache_size()
                      for f in list(_FAST_PROGRAMS.values())))

    def do_rule(self, ruleno: int, xs, result_max: int, reweight,
                content=None) -> jax.Array:
        """``content``: the batch's parts (fastpath.Seeds) and the zero
        lanes padded after them, as the dispatch engine hands them over
        — a fast-path batch then serves its first attempt's straw2
        winners from the planes a batch of the same content drew on
        the same tables (``_fast_call``)."""
        xs = jnp.asarray(xs, dtype=jnp.uint32)
        if not isinstance(reweight, jax.Array):
            # a host vector is zero-padded to the OSD axis quantum
            # (weight 0 is out, is_out's verdict for an id past the
            # vector already): maps whose max_osd differs inside the
            # quantum then share a program
            rw = reweight = np.asarray(reweight, dtype=np.int64)
            if len(rw) != padded_osds(len(rw)):
                reweight = np.zeros(padded_osds(len(rw)), dtype=np.int64)
                reweight[:len(rw)] = rw
        reweight = jnp.asarray(reweight, dtype=jnp.int64)
        if (ruleno < 0 or ruleno >= self.map.max_rules
                or self.map.rules[ruleno] is None):
            # crush_do_rule returns empty for unknown rules (mapper.c:902-904)
            return jnp.full((xs.shape[0], result_max), NONE, dtype=jnp.int32)
        ft = self.fast_tables(ruleno)
        if ft is not None:
            fn, key = self._fast_call(ft, xs, reweight, result_max,
                                      content)
        else:
            key = (ruleno, result_max)
            if key not in self._jit_cache:
                self._jit_cache[key] = jax.jit(
                    functools.partial(self._run, ruleno, result_max))
            fn = functools.partial(self._jit_cache[key], xs, reweight)
        n = xs.shape[0]
        from ceph_tpu.ops import telemetry
        return telemetry.timed_kernel(
            "crush_map", fn,
            batch=n, bytes_in=n * 4 + reweight.shape[0] * 8,
            bytes_out=n * result_max * 4,
            cache_entries=self._jit_entries,
            signature=("crush", key, n))

    def _fast_call(self, ft, xs, reweight, result_max: int, content):
        """The fast path's call (a thunk for timed_kernel) and its
        program key.  A mesh-sharded batch (the engine's placement)
        takes the tables replicated over its mesh; only the Pallas
        kernels need the shard_map wrapper, GSPMD splits the XLA path.

        The column cache: with ``content`` the first attempt's winner
        planes are looked up on ``ft`` by the batch's key and
        placement.  Found (a hit), the program takes them as an operand
        and draws none for that attempt; not (a miss), it draws them
        and they are kept.  One program serves both, so which epochs
        hit builds nothing."""
        from . import fastpath
        from ceph_tpu.ops import telemetry
        from ceph_tpu.ops.gf_kernel import (
            _multi_device, _row_sharding, lane_sharding)
        mesh = (getattr(xs.sharding, "mesh", None)
                if _multi_device(xs) else None)
        sh = _row_sharding(xs) if mesh is not None else None
        key = (ft.shape, result_max, sh)
        prog = _fast_program(*key)
        tables = ft.on(mesh)
        cached = (content is not None
                  and not isinstance(xs, jax.core.Tracer))
        # the planes' count of columns follows the replicas placed
        ckey = (result_max, fastpath.Seeds.key(*content)) if cached \
            else None
        planes = ft.columns(mesh, ckey) if cached else None
        have = planes is not None
        if not have:
            n = xs.shape[0]
            # shard_map: the planes are each device's, side by side
            shards = (n // xs.sharding.shard_shape(xs.shape)[0]
                      if sh is not None and ft.shape.pallas else 1)
            count, R, lanes = fastpath.mapper_for(ft.shape).column_shape(
                n // shards, result_max)
            planes = _column_filler(
                key, (count, R, lanes * shards),
                None if sh is None else lane_sharding(sh))

        def call():
            out, cols = prog(xs, reweight, tables,
                             (np.bool_(have), planes))
            if cached:
                if not have:
                    ft.keep_columns(mesh, ckey, cols)
                telemetry.mapping_stats().record_crush_columns(have)
                for part in content[0]:
                    part.columns = "hit" if have else "miss"
            return out

        return call, key

    # -- the rule interpreter (mapper.c:900-1105) -----------------------------

    def _run(self, ruleno: int, result_max: int, xs, reweight):
        if isinstance(xs, jax.core.Tracer):
            # a trace of the interpreter is a program built
            from ceph_tpu.ops import telemetry
            telemetry.mapping_stats().record_program_build()
        a = self.arrays
        rule = self.map.rules[ruleno]
        n = xs.shape[0]
        t = self.map.tunables

        choose_tries = self.compiled.tunables_tries
        choose_leaf_tries = 0
        vary_r = t.chooseleaf_vary_r
        # working set: per-lane item ids, NONE-padded; starts empty
        w = jnp.full((n, result_max), NONE, dtype=jnp.int32)
        wsize = 0
        results = []

        for step in rule.steps:
            if step.op == RULE_TAKE:
                # validate like the reference (mapper.c:941-948): unknown
                # bucket / device -> the take is ignored
                ok = (0 <= step.arg1 < self.map.max_devices or
                      self.map.bucket(step.arg1) is not None)
                if ok:
                    w = w.at[:, 0].set(jnp.int32(step.arg1))
                    wsize = 1
            elif step.op == RULE_SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    choose_tries = step.arg1
            elif step.op == RULE_SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    choose_leaf_tries = step.arg1
            elif step.op in (RULE_SET_CHOOSE_LOCAL_TRIES,
                             RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if step.arg1 > 0:
                    raise ValueError(
                        "legacy local-retry tunables are scalar-only")
            elif step.op == RULE_SET_CHOOSELEAF_VARY_R:
                if step.arg1 >= 0:
                    vary_r = step.arg1
            elif step.op == RULE_SET_CHOOSELEAF_STABLE:
                if step.arg1 >= 0 and step.arg1 != 1:
                    raise ValueError("batched mapper requires stable=1")
            elif step.op in (RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN,
                             RULE_CHOOSE_INDEP, RULE_CHOOSELEAF_INDEP):
                if wsize == 0:
                    continue
                firstn = step.op in (RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN)
                leafy = step.op in (RULE_CHOOSELEAF_FIRSTN,
                                    RULE_CHOOSELEAF_INDEP)
                # numrep <= 0 means result_max + numrep (mapper.c:1009-1014)
                numrep = step.arg1
                if numrep <= 0:
                    numrep += result_max
                    if numrep <= 0:
                        continue
                if firstn:
                    recurse = (choose_leaf_tries or
                               (1 if t.chooseleaf_descend_once
                                else choose_tries))
                else:
                    recurse = choose_leaf_tries if choose_leaf_tries else 1
                outs = []
                for i in range(wsize):
                    src = w[:, i]
                    active = src != NONE
                    start = _widx(a, src)
                    # a TAKE of a device id (src >= 0) is degenerate; treat
                    # as inactive like the reference's type check would
                    active = active & (src < 0)
                    if firstn:
                        # all numrep reps are attempted (count limiting in the
                        # reference only caps kept successes — equivalent to
                        # post-compaction truncation)
                        o, leaf = _choose_firstn(
                            a, xs, start, numrep, step.arg2, choose_tries,
                            recurse, vary_r, leafy, reweight, active)
                    else:
                        o, leaf = _choose_indep(
                            a, xs, start, min(numrep, result_max), numrep,
                            step.arg2, choose_tries, recurse,
                            leafy, reweight, active)
                    outs.append(leaf if leafy else o)
                new_w = jnp.concatenate(outs, axis=1)[:, :result_max]
                if firstn:
                    new_w = _compact_rows(new_w)
                w = jnp.full((n, result_max), NONE, dtype=jnp.int32)
                w = w.at[:, :new_w.shape[1]].set(new_w)
                wsize = new_w.shape[1]
            elif step.op == RULE_EMIT:
                results.append(w[:, :wsize])
                w = jnp.full((n, result_max), NONE, dtype=jnp.int32)
                wsize = 0
        if not results:
            return jnp.full((n, result_max), NONE, dtype=jnp.int32)
        res = jnp.concatenate(results, axis=1)[:, :result_max]
        pad = result_max - res.shape[1]
        if pad > 0:
            res = jnp.concatenate(
                [res, jnp.full((n, pad), NONE, dtype=jnp.int32)], axis=1)
        return res
