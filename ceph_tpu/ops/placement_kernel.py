"""Fused device-resident placement pipeline tail (raw -> up -> acting).

The batched mapper (crush.mapper_jax) computes raw CRUSH placements for
a whole pool in one device call, but the seed finished every PG
host-side: ``OSDMap._finish_pg_mapping`` (upmap -> up/state filter ->
primary affinity -> pg_temp/primary_temp) ran per PG per epoch, and the
PR 8 phase profiler attributed the mapping service's epoch cost to
exactly that ``host_tail``.  This module fuses the whole tail into ONE
jitted ladder over the PG axis:

    raw table (N, W) + pps seeds + dense epoch operands
        -> (up, up_primary, acting, acting_primary) for ALL N PGs

Semantics are the scalar oracle's, bit for bit (OSDMap.cc:2228-2445
via osd.osdmap._finish_pg_mapping):

  * ``pg_upmap`` rows replace the raw row wholesale when every entry
    exists and is not out; otherwise ``pg_upmap_items`` pairs apply
    SEQUENTIALLY (each pair sees the previous pair's rewrite, first
    occurrence of ``frm`` rewritten, ``to`` must be absent/exists/in);
  * up filtering keeps positions with NONE holes for erasure pools and
    stable-compacts for replicated ones;
  * primary affinity replays the hash coin-flip ladder with the pps
    seed (first winning position; default-affinity osds always win);
  * pg_temp replaces acting when present and non-empty; primary_temp
    overrides acting_primary, else the first non-NOSD member — unless
    acting equals up, which inherits up_primary.

Dense operand layout (built by OSDMap.dense_osd_vectors /
dense_pool_overrides): every per-PG table is NONE/NOSD padded to a
shared width ``W`` and pairs to ``P``, so pools (and daemons) sharing
one epoch's operand digest coalesce into one device call through
``ops.dispatch.submit_finish_ladder``; the per-OSD state/weight/
affinity vectors are captured operands, mesh-replicated on sharded
batches exactly like the CRUSH reweight vector.

Inside the program the PG axis is the lane axis.  The (N, W) tables
are cut once into W *planes* of shape (N,) — the device keeps a table
of three columns with N minor already, so a plane is a dense vector
and nothing is lane-padded — and the whole ladder is elementwise on
planes: an ``any`` / ``all`` / first-true over a row is a fold,
unrolled over the W planes at trace time.  The reason is the chip's
gather: XLA's costs a v5e 7-11 ns a cell, and ``take_along_axis``
behind a row sort is one more — per-OSD vectors gathered for (N, W)
tables and rows sorted made 261 ms for a million PGs, every ms of it
gathers, where this form takes 3.5 (PERF.md, PR 38).

  * **No sort.**  The two stable compactions (NONE holes out of a
    replicated raw row; down OSDs out of ``up``) are a fixed network:
    cell j lands at ``pos_j`` = the number of kept cells before it, so
    ``out_k`` is the one cell with ``keep_j & (pos_j == k)`` — W(W+1)/2
    selects on dense vectors, the same formulation for W = 3 and 16
    (``crush_kernel.compact_planes``; the CRUSH fast path compacts its
    result rows with it too).
  * **One attribute word an OSD**, built in the program from the three
    vectors: bit 0 exists, bit 1 up, bit 2 in (``weight != 0``), bits
    3..19 the primary affinity (clipped to [0, 0x10001], which keeps
    both of its tests for any int32) — all the ladder ever asks of an
    OSD, in 20 bits.  The words of the three id tables (base row,
    ``pg_upmap`` row, each pair's ``to``) are fetched at once; ``up``
    and ``acting`` carry their cells' words through pair rewrite,
    wholesale replacement and compaction; a ``pg_temp`` row asks
    nothing of its members.  An id outside ``[0, max_osd)`` has word 0.
  * **The fetch is an exact one-hot product**, not a gather: the id
    splits into (table row, lane), the lane's one-hot meets the
    (M / 128, 128) table on the MXU (f32, ``Precision.HIGHEST``: a
    word is under 2^24), a select over the rows keeps the id's own.
    XLA fuses one-hot, product and select into one pass; nothing of
    size N x 128 is ever stored (``crush_kernel.fetch_words``, which
    the CRUSH fast path's ``is_out`` calls too).
  * 32 bits everywhere: only the M-entry ``weight`` operand is int64,
    until it is folded into the word.

Every step is row-independent along the PG axis, so a mesh-sharded
engine (GSPMD over N) splits the planes across devices as it split
the rows, with bit-identical results and no collective (the
crush_kernel mesh contract).

Output packing: one (N, 2*W + 4) int32 array per call —
``[up (W) | acting (W) | up_len | up_primary | acting_len |
acting_primary]`` — rows unpack to the oracle tuple with
``unpack_row``; padded cells are a deterministic NOSD fill, so two
packed rows are equal IFF their oracle tuples are, which is what lets
the mapping service diff whole epochs on device.
"""

from __future__ import annotations

import functools

import numpy as np

from ceph_tpu.crush.types import CRUSH_ITEM_NONE

NONE = CRUSH_ITEM_NONE          # 0x7FFFFFFF — raw-table hole
NOSD = -1                       # CEPH_NOSD — up/acting hole
_MAX_AFFINITY = 0x10000
_OSD_EXISTS = 1
_OSD_UP = 2
# an OSD's attribute word, as the jitted ladder packs it: the two state
# bits where they are, bit 2 in (weight != 0), the affinity above
_W_IN = 4
_AFF_SHIFT = 3


# ---------------------------------------------------------------------------
# the jitted ladder
# ---------------------------------------------------------------------------

def _ladder_impl(raw, pps, raw_len, up_rows, up_len, items, temp_rows,
                 temp_len, ptemp, state, weight, affinity, max_osd, *,
                 erasure: bool):
    """See the module docstring.  All tables int32 except pps (uint32)
    and weight (int64); shapes: raw/up_rows/temp_rows (N, W), items
    (N, P, 2), the rest (N,) or (M,); max_osd an int32 scalar — the
    bound of every ``0 <= o < max_osd`` check, while M (the vectors'
    padded length, osdmap.padded_osds) only bounds the gather."""
    import jax.numpy as jnp

    from ceph_tpu.ops import telemetry
    from ceph_tpu.ops.crush_kernel import (
        compact_planes as compact, fetch_words, first_of, hash32_2)

    # only ever called under jit: a trace of it is a program built
    telemetry.mapping_stats().record_program_build()
    i32 = jnp.int32
    w = raw.shape[1]
    p_pairs = items.shape[1]

    def planes(table):
        """(N, K) -> K planes of shape (N,)."""
        t = table.T
        return [t[j] for j in range(t.shape[0])]

    def any_of(masks):
        return functools.reduce(jnp.logical_or, masks)

    def all_of(masks):
        return functools.reduce(jnp.logical_and, masks)

    # -- one attribute word an OSD: all the ladder ever asks of one.
    # The affinity is clipped to [0, MAX + 1], which keeps both of its
    # tests (!= MAX, hash16 < aff) for every int32 it could hold.
    word = ((state & (_OSD_EXISTS | _OSD_UP))
            | jnp.where(weight != 0, _W_IN, 0)
            | (jnp.clip(affinity, 0, _MAX_AFFINITY + 1) << _AFF_SHIFT)
            ).astype(i32)

    def words_of(ids):
        """The words of a list of id planes, fetched at once (the
        one-hot product of the module docstring, ``fetch_words``); 0
        (not existing, down, out) for an id outside [0, max_osd)."""
        o = jnp.stack(ids)                                  # (K, N)
        got = jnp.where((o >= 0) & (o < max_osd), fetch_words(word, o), 0)
        return [got[j] for j in range(len(ids))]

    def has(words, bits):
        return (words & bits) == bits

    # -- base row: the raw list _finish_from hands to _apply_upmap
    # (replicated compacts NONE holes first; erasure keeps positions)
    base = planes(raw)
    base_len = raw_len
    if not erasure:
        (base,), base_len = compact([c != NONE for c in base],
                                    (base, NONE))
    ups = planes(up_rows)
    pairs = planes(items.reshape(items.shape[0], 2 * p_pairs))
    tos = pairs[1::2]
    got = words_of(base + ups + tos)
    base_w, ups_w, tos_w = got[:w], got[w:2 * w], got[2 * w:]

    # -- pg_upmap_items: sequential pair rewrites (each pair sees the
    # previous pair's result — a static unroll over the pair axis).
    # Padded pairs are (-1, -1): -1 never appears in a raw row (cells
    # are osd ids or NONE), so pads can never match, while a genuine
    # NONE `frm` matches erasure holes exactly like list.index does.
    # Both scans mask to the ACTIVE row length: the scalar list simply
    # has no cells past it, and an unmasked NONE `frm` would match a
    # NONE pad cell on a hole-free row — writing `to` into the pad and
    # making a later pair's `to not in raw` check wrongly fail.
    row, row_w = list(base), list(base_w)
    active = [j < base_len for j in range(w)]
    for p in range(p_pairs):
        frm, to, to_w = pairs[2 * p], tos[p], tos_w[p]
        match = [active[j] & (row[j] == frm) for j in range(w)]
        to_in = any_of([active[j] & (row[j] == to) for j in range(w)])
        cond = ~to_in & has(to_w, _OSD_EXISTS | _W_IN)
        for j in range(w):          # the first occurrence of `frm`
            hit = cond & match[j]
            row[j] = jnp.where(hit, to, row[j])
            row_w[j] = jnp.where(hit, to_w, row_w[j])
            cond = cond & ~match[j]

    # -- pg_upmap: wholesale replacement when present and every entry
    # exists and is in (OSDMap._apply_upmap's validity gate); an
    # invalid or absent entry falls through to the items result
    allok = all_of([(j >= up_len) | has(ups_w[j], _OSD_EXISTS | _W_IN)
                    for j in range(w)]) & (up_len > 0)
    row = [jnp.where(allok, ups[j], row[j]) for j in range(w)]
    row_w = [jnp.where(allok, ups_w[j], row_w[j]) for j in range(w)]
    row_len = jnp.where(allok, up_len, base_len)

    # -- raw -> up: drop nonexistent/down osds (NONE-positional for
    # erasure, stable compaction for replicated; OSDMap.cc:2275-2297).
    # A NONE cell is outside [0, max_osd): its word is 0.
    valid = [(j < row_len) & has(row_w[j], _OSD_EXISTS | _OSD_UP)
             for j in range(w)]
    if erasure:
        up = [jnp.where(valid[j], row[j], NOSD) for j in range(w)]
        up_w, up_len_o = row_w, row_len
    else:
        (up, up_w), up_len_o = compact(valid, (row, NOSD), (row_w, 0))
    up_real = [c != NOSD for c in up]
    up_primary = first_of(up_real, up, NOSD)

    # -- primary affinity (OSDMap.cc _apply_primary_affinity): skip
    # entirely when every member has default affinity; otherwise the
    # first member winning its coin flip (default always wins) takes
    # primary, falling back to the positional primary.  A real member
    # of `up` exists, so it carries its own word.
    aff = [c >> _AFF_SHIFT for c in up_w]
    default_all = ~any_of([up_real[j] & (aff[j] != _MAX_AFFINITY)
                           for j in range(w)])
    # (the hash runs once, on the planes stacked: W times fewer ops
    # to compile for the same work)
    flip = (hash32_2(pps[None, :], jnp.stack(up).astype(jnp.uint32))
            >> jnp.uint32(16)).astype(i32)
    win = [up_real[j] & ((aff[j] == _MAX_AFFINITY) | (flip[j] < aff[j]))
           for j in range(w)]
    prim = jnp.where(default_all, up_primary,
                     first_of(win, up, up_primary))

    # -- temps (OSDMap.cc:2417-2445): pg_temp replaces acting when
    # present and non-empty; primary_temp overrides, else the first
    # non-NOSD member — with acting == up inheriting up_primary.  None
    # of it asks anything of an OSD: a pg_temp row needs no words.
    tset = temp_len > 0
    temps = planes(temp_rows)
    acting = [jnp.where(tset, temps[j], up[j]) for j in range(w)]
    act_len = jnp.where(tset, temp_len, up_len_o)
    act_first = first_of([c != NOSD for c in acting], acting, NOSD)
    same = (act_len == up_len_o) & all_of(
        [acting[j] == up[j] for j in range(w)])
    ap = jnp.where(ptemp != NOSD, ptemp,
                   jnp.where(same, prim, act_first))

    return jnp.stack(up + acting + [up_len_o, prim, act_len, ap],
                     axis=1).astype(i32)


@functools.lru_cache(maxsize=2)
def _ladder_jit(erasure: bool):
    import jax
    return jax.jit(functools.partial(_ladder_impl, erasure=erasure))


# ---------------------------------------------------------------------------
# numpy host oracle (the engine's pg_finish fallback channel)
# ---------------------------------------------------------------------------

_CRUSH_HASH_SEED = 1315423911    # crush/hash.c crush_hash_seed


def _mix_np(a, b, c):
    a = a - b - c; a = a ^ (c >> np.uint32(13))
    b = b - c - a; b = b ^ (a << np.uint32(8))
    c = c - a - b; c = c ^ (b >> np.uint32(13))
    a = a - b - c; a = a ^ (c >> np.uint32(12))
    b = b - c - a; b = b ^ (a << np.uint32(16))
    c = c - a - b; c = c ^ (b >> np.uint32(5))
    a = a - b - c; a = a ^ (c >> np.uint32(3))
    b = b - c - a; b = b ^ (a << np.uint32(10))
    c = c - a - b; c = c ^ (b >> np.uint32(15))
    return a, b, c


def _hash32_2_np(a, b):
    """crush_hash32_2 elementwise on numpy uint32 — the affinity
    coin-flip hash, host-side (no jax import on this path: the device
    runtime being broken is exactly when this runs)."""
    a = np.asarray(a, dtype=np.uint32)
    b = np.asarray(b, dtype=np.uint32)
    a, b = np.broadcast_arrays(a, b)
    h = np.uint32(_CRUSH_HASH_SEED) ^ a ^ b
    x = np.full(h.shape, 231232, dtype=np.uint32)
    y = np.full(h.shape, 1232, dtype=np.uint32)
    a, b, h = _mix_np(a.copy(), b.copy(), h)
    x, a, h = _mix_np(x, a, h)
    b, y, h = _mix_np(b, y, h)
    return h


def ladder_ref(raw, pps, raw_len, up_rows, up_len, items, temp_rows,
               temp_len, ptemp, state, weight, affinity, max_osd, *,
               erasure: bool) -> np.ndarray:
    """Numpy twin of ``_ladder_impl`` — the bit-exact host oracle the
    dispatch engine degrades the ``pg_finish`` channel to when the
    device path is out (and the unit tests' ground truth for the
    fused ladder).  Operand-for-operand the same pipeline, on (N, W)
    rows with numpy's own gathers and sorts where the program folds
    over planes; see ``_ladder_impl`` for the semantics commentary."""
    raw = np.asarray(raw, dtype=np.int32)
    pps = np.asarray(pps, dtype=np.uint32)
    raw_len = np.asarray(raw_len, dtype=np.int32)
    up_rows = np.asarray(up_rows, dtype=np.int32)
    up_len = np.asarray(up_len, dtype=np.int32)
    items = np.asarray(items, dtype=np.int32)
    temp_rows = np.asarray(temp_rows, dtype=np.int32)
    temp_len = np.asarray(temp_len, dtype=np.int32)
    ptemp = np.asarray(ptemp, dtype=np.int32)
    state = np.asarray(state, dtype=np.int32)
    weight = np.asarray(weight)
    affinity = np.asarray(affinity, dtype=np.int32)

    n, w = raw.shape
    m_pad = state.shape[0]
    max_osd = int(max_osd)
    iota = np.arange(w, dtype=np.int32)[None, :]

    def in_range(o):
        return (o >= 0) & (o < max_osd)

    def gather(vec, o):
        return vec[np.clip(o, 0, m_pad - 1)]

    def exists(o):
        return in_range(o) & ((gather(state, o) & _OSD_EXISTS) != 0)

    def is_up(o):
        return in_range(o) & ((gather(state, o) & _OSD_UP) != 0)

    def not_out(o):
        return in_range(o) & (gather(weight, o) != 0)

    if erasure:
        base = raw
        base_len = raw_len
    else:
        keep0 = raw != NONE
        order0 = np.argsort(~keep0, axis=1, kind="stable")
        base = np.take_along_axis(raw, order0, axis=1)
        base_len = np.sum(keep0, axis=1).astype(np.int32)
        base = np.where(iota < base_len[:, None], base, NONE)

    wrow = base
    base_mask = iota < base_len[:, None]
    for p in range(items.shape[1]):
        frm = items[:, p, 0]
        to = items[:, p, 1]
        match = base_mask & (wrow == frm[:, None])
        has = np.any(match, axis=1)
        to_in = np.any(base_mask & (wrow == to[:, None]), axis=1)
        cond = has & ~to_in & exists(to) & not_out(to)
        first = np.argmax(match, axis=1).astype(np.int32)
        wrow = np.where(cond[:, None] & (iota == first[:, None]),
                        to[:, None], wrow)

    upmask = iota < up_len[:, None]
    ent_ok = ~upmask | (exists(up_rows) & not_out(up_rows))
    allok = np.all(ent_ok, axis=1) & (up_len > 0)
    row = np.where(allok[:, None], up_rows, wrow)
    row_len = np.where(allok, up_len, base_len)

    lenmask = iota < row_len[:, None]
    valid = lenmask & (row != NONE) & exists(row) & is_up(row)
    if erasure:
        up = np.where(lenmask, np.where(valid, row, NOSD), NOSD)
        up_len_o = row_len
    else:
        order = np.argsort(~valid, axis=1, kind="stable")
        up = np.take_along_axis(row, order, axis=1)
        up_len_o = np.sum(valid, axis=1).astype(np.int32)
        up = np.where(iota < up_len_o[:, None], up, NOSD)
    up_real = up != NOSD
    has_any = np.any(up_real, axis=1)
    firstj = np.argmax(up_real, axis=1)
    first_val = np.take_along_axis(up, firstj[:, None], axis=1)[:, 0]
    up_primary = np.where(has_any, first_val, NOSD)

    aff = np.where(in_range(up), gather(affinity, up),
                   _MAX_AFFINITY).astype(np.int32)
    non_default = up_real & (aff != _MAX_AFFINITY)
    default_all = ~np.any(non_default, axis=1)
    h = (_hash32_2_np(pps[:, None], up.astype(np.uint32))
         >> np.uint32(16)).astype(np.int32)
    win = up_real & ((aff == _MAX_AFFINITY) | (h < aff))
    has_win = np.any(win, axis=1)
    wj = np.argmax(win, axis=1)
    wval = np.take_along_axis(up, wj[:, None], axis=1)[:, 0]
    prim = np.where(default_all, up_primary,
                    np.where(has_win, wval, up_primary))

    tset = temp_len > 0
    acting = np.where(tset[:, None], temp_rows, up)
    act_len = np.where(tset, temp_len, up_len_o)
    act_real = acting != NOSD
    act_has = np.any(act_real, axis=1)
    aj = np.argmax(act_real, axis=1)
    act_first = np.where(
        act_has, np.take_along_axis(acting, aj[:, None], axis=1)[:, 0],
        NOSD)
    same = (act_len == up_len_o) & np.all(acting == up, axis=1)
    ap = np.where(ptemp != NOSD, ptemp,
                  np.where(same, prim, act_first))

    return np.concatenate(
        [up, acting, up_len_o[:, None], prim[:, None],
         act_len[:, None], ap[:, None]], axis=1).astype(np.int32)


def ladder_cache_entries() -> int:
    """Compile-cache entries across the fused-ladder entry points — the
    dispatch profiler's retrace/compile probe differences this.  The
    factory call is cached and only builds the jit wrapper, never
    traces."""
    return sum(_ladder_jit(flag)._cache_size() for flag in (False, True))


def run_ladder(operands: "LadderOperands") -> np.ndarray:
    """Direct (engine-less) fused-ladder evaluation: one jitted device
    call, result materialized to host.  The PG axis pads up to a
    power-of-two bucket (all-zero rows compute garbage that is sliced
    off — the dispatch engine's shape-bucketing rule) so the jit cache
    is bounded by the bucket table, not the pg_num population.  The
    dispatch-engine path is ops.dispatch.submit_finish_ladder."""
    n = operands.raw.shape[0]
    bucket = 1 << max(0, (n - 1).bit_length())
    pad = bucket - n

    def padded(arr):
        if not pad:
            return arr
        return np.concatenate(
            [arr, np.zeros((pad,) + arr.shape[1:], dtype=arr.dtype)])

    fn = _ladder_jit(operands.erasure)
    out = fn(padded(operands.raw), padded(operands.pps),
             padded(operands.raw_len), padded(operands.up_rows),
             padded(operands.up_len), padded(operands.items),
             padded(operands.temp_rows), padded(operands.temp_len),
             padded(operands.ptemp), *operands.osd_operands())
    # analysis: allow[blocking] -- engine-less entry point: callers want the host table
    return np.asarray(out)[:n]


# ---------------------------------------------------------------------------
# dense operand bundle
# ---------------------------------------------------------------------------

class LadderOperands:
    """One pool's (or one what-if batch's) dense ladder operands.

    ``raw``/``pps``/``raw_len`` and the override tables have the PG
    leading axis (they coalesce/shard through the engine's data+aux
    channels); ``state``/``weight``/``affinity`` are the per-OSD
    vectors shared by every pool of the epoch, padded past
    ``max_osd`` (OSDMap.dense_osd_vectors), and ``max_osd`` the bound
    of the ladder's range checks (captured operands, mesh-replicated
    by the submit helper)."""

    __slots__ = ("raw", "pps", "raw_len", "up_rows", "up_len", "items",
                 "temp_rows", "temp_len", "ptemp", "state", "weight",
                 "affinity", "max_osd", "erasure", "width")

    def __init__(self, *, raw, pps, raw_len, up_rows, up_len, items,
                 temp_rows, temp_len, ptemp, state, weight, affinity,
                 max_osd, erasure, width):
        self.raw = raw
        self.pps = pps
        self.raw_len = raw_len
        self.up_rows = up_rows
        self.up_len = up_len
        self.items = items
        self.temp_rows = temp_rows
        self.temp_len = temp_len
        self.ptemp = ptemp
        self.state = state
        self.weight = weight
        self.affinity = affinity
        self.max_osd = np.int32(max_osd)
        self.erasure = bool(erasure)
        self.width = int(width)

    def osd_operands(self) -> tuple:
        """The per-OSD operands, in the ladder's argument order."""
        return (self.state, self.weight, self.affinity, self.max_osd)

    def aux(self) -> tuple:
        """The per-PG side arrays in submit_finish_ladder's aux order."""
        return (self.pps, self.raw_len, self.up_rows, self.up_len,
                self.items, self.temp_rows, self.temp_len, self.ptemp)


def pad_raw(raw: np.ndarray, width: int) -> np.ndarray:
    """(N, w) raw table NONE-padded to the shared ladder width."""
    raw = np.asarray(raw, dtype=np.int32)
    n, w = raw.shape
    if w == width:
        return raw
    out = np.full((n, width), NONE, dtype=np.int32)
    out[:, :w] = raw
    return out


def build_operands(m, pool_id: int, pool, raw: np.ndarray,
                   pps: np.ndarray, *, width: int, pairs: int,
                   vectors=None) -> LadderOperands:
    """Dense ladder operands for one pool at one epoch.  ``width`` and
    ``pairs`` are the epoch-shared table widths (so pools coalesce);
    ``vectors`` memoizes m.dense_osd_vectors() across pools."""
    n = int(pool.pg_num)
    raw_np = np.asarray(raw, dtype=np.int32)
    raw_w = raw_np.shape[1] if raw_np.ndim == 2 else 0
    if vectors is None:
        vectors = m.dense_osd_vectors()
    state, weight, affinity = vectors
    up_rows, up_len, items, temp_rows, temp_len, ptemp = \
        m.dense_pool_overrides(pool_id, n, width, pairs)
    return LadderOperands(
        raw=pad_raw(raw_np.reshape(n, raw_w), width),
        pps=np.asarray(pps, dtype=np.uint32),
        raw_len=np.full(n, raw_w, dtype=np.int32),
        up_rows=up_rows, up_len=up_len, items=items,
        temp_rows=temp_rows, temp_len=temp_len, ptemp=ptemp,
        state=state, weight=weight, affinity=affinity,
        max_osd=m.max_osd, erasure=pool.is_erasure(), width=width)


def pool_widths(m, pools=None) -> tuple[int, int]:
    """(width, pairs) shared by every pool of an epoch: W covers the
    widest of pool size / pg_upmap row / pg_temp row, P the longest
    pg_upmap_items pair list — each rounded up (P to a power of two,
    W's excess over the max size to a power of two) so the jit/bucket
    key space stays bounded under override churn."""
    if pools is None:
        pools = m.pools
    w = max((int(p.size) for p in pools.values()), default=1)
    w_need = w
    for (pid, _pg), lst in m.pg_upmap.items():
        if pid in pools:
            w_need = max(w_need, len(lst))
    for (pid, _pg), lst in m.pg_temp.items():
        if pid in pools:
            w_need = max(w_need, len(lst))
    if w_need > w:
        extra = w_need - w
        w += 1 << (extra - 1).bit_length() if extra > 1 else 1
    p = 1
    for (pid, _pg), lst in m.pg_upmap_items.items():
        if pid in pools:
            p = max(p, len(lst))
    if p > 1:
        p = 1 << (p - 1).bit_length()
    return max(w, 1), p


def unpack_row(row, width: int) -> tuple[list[int], int, list[int], int]:
    """One packed ladder row -> the oracle's (up, up_primary, acting,
    acting_primary) tuple."""
    lst = row.tolist() if hasattr(row, "tolist") else list(row)
    w = width
    up_len = lst[2 * w]
    act_len = lst[2 * w + 2]
    return (lst[:up_len], lst[2 * w + 1],
            lst[w:w + act_len], lst[2 * w + 3])


def normalize_packed(packed: np.ndarray, width: int,
                     to_width: int) -> np.ndarray:
    """Re-pad a packed table to a wider layout (NOSD fill) so two
    epochs built at different shared widths compare row-for-row."""
    if width == to_width:
        return packed
    n = packed.shape[0]
    out = np.full((n, 2 * to_width + 4), NOSD, dtype=np.int32)
    out[:, :width] = packed[:, :width]
    out[:, to_width:to_width + width] = packed[:, width:2 * width]
    out[:, 2 * to_width:] = packed[:, 2 * width:]
    return out
