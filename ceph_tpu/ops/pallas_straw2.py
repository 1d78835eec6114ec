"""Fused Pallas TPU kernels for the CRUSH straw2 column draws.

The XLA path (ops.straw2_u32 driven by crush.fastpath) is bit-exact but
this backend leaves long u32 elementwise chains unfused: a single
(65536, 256) draw column costs ~25 ms against a ~0.5 ms roofline, with
hundreds of materialized (N, S) intermediates.  These kernels fuse one
whole column — rjenkins hash, crush_ln limb pipeline, magic division
and first-min winner select — into one VMEM-resident Pallas program per
(r, block) grid step:

  root kernel:  xs block -> winner position/id per r
  leaf kernel:  root winner position -> the winning host's device row
                (fetched with an exact f32 one-hot MXU dot — a vectorized
                row gather the VPU cannot do) -> device winner; a host
                takes the group width g of the widest host (32 or 64
                lanes), so one 128-lane slab draws 128 // g r-columns
                and a grid step is (block, slab)

The is_out verdicts of the winners are NOT the kernels': the fast path
computes them in XLA over the winner planes the kernels return
(crush_kernel.out_columns — the devices' reweight words fetched by a
one-hot product, since an XLA gather for the planes cost an eighth of
the CRUSH program, PERF.md PR 40), then consume_columns walks the firstn
ladder over winners and verdicts.

Bit-exactness contract: identical output to ops.straw2_u32 (itself
validated exhaustively against the s64 kernel and the scalar C-semantics
oracle).  tests/test_pallas_straw2.py compares both, exhaustively over
the 16-bit hash domain for the ln/divide pipeline and end-to-end on
random maps, in interpret mode on CPU and compiled on TPU.

Table lookups ride the MXU as exact one-hot matmuls (8-bit limbs in
bf16, one-hot 0/1 exact; f32 accumulator sums < 2^15).  The count-
leading-zeros of the ln normalization uses the f32 exponent field
(exact: inputs < 2^17 convert exactly).  All element math is u32/i32 —
no 64-bit emulation anywhere.
"""

from __future__ import annotations

import functools
import sys

# the unrolled R-column kernels build deep expression trees; default
# CPython recursion limits trip inside jax lowering
if sys.getrecursionlimit() < 20000:
    sys.setrecursionlimit(20000)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.ops.crush_kernel import (
    _ln_limb_operands_np, hash32_2, hash32_3)

_U32 = jnp.uint32
_I32 = jnp.int32

#: rows per grid step (TPU blocks need a 128-divisible last dim).  512
#: measures fastest on v5e for the bulk-mapping shapes: fewer grid steps
#: amortize the per-step block/table traffic, and the (512, 128) slab
#: temporaries still fit VMEM comfortably.
BLOCK = 512

#: batch rows per grid step for the candidate-filter kernels: their
#: working set (approx bands + keys + 9 gathered operand planes) tops
#: 16 MB VMEM at 512 rows
CAND_BLOCK = 128


def _bitlen_f32(v):
    """bit length of v (uint32, v < 2^17) via the f32 exponent field —
    Mosaic-safe replacement for lax.clz; exact because the convert is."""
    # Mosaic has no u32->f32 cast; go through i32 (values < 2^17, safe)
    f = (v | _U32(1)).astype(_I32).astype(jnp.float32)
    e = (jax.lax.bitcast_convert_type(f, _U32) >> 23) - _U32(127)
    return e + _U32(1)


def _row_lookup(idx, row):
    """Per-lane table lookup: idx (B, S) i32 with values < S; row (S,)
    shared table — or (B, S) per-row tables.  Lowers to Mosaic's
    tpu.dynamic_gather (take_along_axis on same-shaped 2-D operands) —
    a lane shuffle, with none of the one-hot matmul's VMEM or reshape
    trouble."""
    x = (jnp.broadcast_to(row[None, :], idx.shape) if row.ndim == 1
         else row)
    # raw lax.gather with i32 indices: jnp.take_along_axis promotes its
    # indices to i64 under x64, which Mosaic cannot lower.  These
    # dimension numbers are exactly the per-lane tpu.dynamic_gather
    # pattern Mosaic's gather rule recognizes.
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return jax.lax.gather(
        x, idx[..., None], dnums, slice_sizes=(1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _ln_p48_pl(u, rhlh_ref, ll_lo_ref, ll_hi_ref, rh128):
    """P = 2^48 - crush_ln(u) as (p_hi17, p_lo32) u32 — the Pallas twin
    of straw2_u32._crush_ln_p48.

    rhlh_ref (13, S): limb j's table for k in [0, 127]; rh128 is the
    k == 128 row as python constants (tables must fit the S-lane gather
    width, and the leaf kernel runs at S = 128).  ll_lo/ll_hi (6, S):
    the 256-entry LL table split at row 128 the same way.
    """
    x = u.astype(_U32) + _U32(1)
    low17 = x & _U32(0x1FFFF)
    bits = _U32(16) - _bitlen_f32(low17)
    needs_norm = (x & _U32(0x18000)) == 0
    xnorm = jnp.where(needs_norm, x << bits, x).astype(_I32)
    iexpon = jnp.where(needs_norm, _U32(15) - bits, _U32(15)).astype(_I32)
    idx1 = (xnorm.astype(_U32) >> 8) << 1
    k = ((idx1 - _U32(256)) >> 1).astype(_I32)
    k_cap = jnp.minimum(k, _I32(127))
    is128 = k == _I32(128)
    rhlh = [jnp.where(is128, _I32(rh128[j]),
                      _row_lookup(k_cap, rhlh_ref[j, :]))
            for j in range(13)]
    acc = jnp.zeros_like(xnorm)
    for j in range(7):
        acc = (acc >> 8) + xnorm * rhlh[j]
    idx2 = acc & _I32(0xFF)
    lo7 = idx2 & _I32(127)
    hi_half = idx2 >= _I32(128)
    ll = [jnp.where(hi_half, _row_lookup(lo7, ll_hi_ref[j, :]),
                    _row_lookup(lo7, ll_lo_ref[j, :]))
          for j in range(6)]
    bj = []
    carry = jnp.zeros_like(xnorm)
    for j in range(6):
        t = rhlh[7 + j] + ll[j] + carry
        bj.append(t & _I32(0xFF))
        carry = t >> 8
    bj.append(carry)
    v = [((bj[j] >> 4) | ((bj[j + 1] & _I32(0xF)) << 4)) for j in range(6)]
    v[5] = v[5] + ((iexpon & _I32(0xF)) << 4)
    ln_lo = (v[0] | (v[1] << 8) | (v[2] << 16)).astype(_U32) \
        | (v[3].astype(_U32) << 24)
    ln_hi = (v[4] | (v[5] << 8)).astype(_U32)
    is_zero = (ln_lo == 0) & (ln_hi == 0)
    p_lo = (~ln_lo) + _U32(1)
    carry_in = jnp.where(ln_lo == 0, _U32(1), _U32(0))
    p_hi = (((~ln_hi) & _U32(0xFFFF)) + carry_in) & _U32(0x1FFFF)
    p_lo = jnp.where(is_zero, _U32(0), p_lo)
    p_hi = jnp.where(is_zero, _U32(0x10000), p_hi)
    return p_hi, p_lo


def _magic_div_pl(p_hi, p_lo, magic, off):
    """floor(P/w): the shared magic-multiply (straw2_u32) with magic as
    a list of 5 (B, S) limb planes — one implementation for both the
    XLA path and these kernels (pure jnp, Mosaic-safe)."""
    from ceph_tpu.ops.straw2_u32 import magic_divide_planes
    return magic_divide_planes(p_hi, p_lo, magic, off)


def _umin(v, axis, keepdims):
    """u32 min via the order-preserving signed bias (Mosaic has no
    unsigned reductions)."""
    s = (v ^ _U32(0x80000000)).astype(_I32)
    m = jnp.min(s, axis=axis, keepdims=keepdims)
    return m.astype(_U32) ^ _U32(0x80000000)


def _ult(a, b):
    """unsigned < via the sign bias (Mosaic lacks unsigned compares)."""
    return ((a ^ _U32(0x80000000)).astype(_I32)
            < (b ^ _U32(0x80000000)).astype(_I32))


def _first_min(q_hi, q_lo, ids):
    """Lexicographic first minimum along axis 1: winner q pair, position,
    id, and the winner one-hot mask (for gathering sibling values)."""
    b, s = q_hi.shape
    min_hi = _umin(q_hi, 1, True)
    on_h = q_hi == min_hi
    lo_m = jnp.where(on_h, q_lo, _U32(0xFFFFFFFF))
    min_lo = _umin(lo_m, 1, True)
    on = on_h & (lo_m == min_lo)
    # "first index wins": the smallest position among the tied minima
    # (no cumsum in Mosaic — a masked min over iota does the same)
    iota = jax.lax.broadcasted_iota(_I32, (b, s), 1)
    pos_m = jnp.where(on, iota, _I32(2 ** 31 - 1))
    minpos = jnp.min(pos_m, axis=1, keepdims=True)
    first = on & (iota == minpos)
    pos = minpos[:, 0]
    # dtype pinned: with x64 enabled jnp.sum promotes i32 -> i64,
    # which Mosaic cannot lower
    wid = jnp.sum(jnp.where(first, ids, _I32(0)), axis=1, dtype=_I32)
    return min_hi[:, 0], min_lo[:, 0], pos, wid, first


def _draw_slab(x, ids, wz, magic_planes, off, tabs, r):
    """One 128-lane slab of a straw2 column: (B,) x, (B, 128) item
    operands -> winner (q_hi, q_lo, pos, wid, first).  Slabs are 128 wide
    because tpu.dynamic_gather shuffles within a single vreg."""
    rhlh_ref, ll_lo_ref, ll_hi_ref, rh128 = tabs
    u = hash32_3(x[:, None], ids, r) & _U32(0xFFFF)
    p_hi, p_lo = _ln_p48_pl(u, rhlh_ref, ll_lo_ref, ll_hi_ref, rh128)
    q_hi, q_lo = _magic_div_pl(p_hi, p_lo, magic_planes, off)
    bad = wz != 0
    q_hi = jnp.where(bad, _U32(0xFFFFFFFF), q_hi)
    q_lo = jnp.where(bad, _U32(0xFFFFFFFF), q_lo)
    return _first_min(q_hi, q_lo, ids)


def _merge_slabs(best, new):
    """Merge a later slab's winner into the running best: strictly
    smaller (q_hi, q_lo) wins — ties stay with the earlier slab, whose
    positions are lower (the first-index rule)."""
    if best is None:
        return new
    bqh, bql, bpos, bwid, brw = best
    nqh, nql, npos, nwid, nrw = new
    better = _ult(nqh, bqh) | ((nqh == bqh) & _ult(nql, bql))
    return (jnp.where(better, nqh, bqh), jnp.where(better, nql, bql),
            jnp.where(better, npos, bpos), jnp.where(better, nwid, bwid),
            jnp.where(better, nrw, brw))


def _column_over_slabs(x, S, tabs, r, slab_operands, rw_of_slab):
    """Full-bucket column: iterate 128-wide slabs, merge winners.
    slab_operands(slab) -> (ids, wz, magic[5], off) as (B, 128) values;
    rw_of_slab(slab, first) -> (B,) winner reweight (or zeros)."""
    best = None
    for slab in range(S // 128):
        ids, wz, magic, off = slab_operands(slab)
        qh, ql, pos, wid, first = _draw_slab(x, ids, wz, magic, off,
                                             tabs, r)
        rwv = rw_of_slab(slab, first)
        pos = pos + _I32(slab * 128)
        best = _merge_slabs(best, (qh, ql, pos, wid, rwv))
    return best


def _store_row(ref, r, value):
    """Write one (B,) row at dynamic sublane index r of an (R, B) ref."""
    ref[pl.dslice(r, 1), :] = value[None, :]


def _root_kernel(xs_ref, ids_ref, wz_ref, magic_ref, off_ref,
                 rhlh_ref, ll_lo_ref, ll_hi_ref,
                 pos_ref, id_ref, *, S, rh128):
    """Grid (n//B, R): one (block, r) column per step — r rides the grid
    so the kernel stays small enough for Mosaic to compile quickly.

    is_out verdicts are NOT computed here: they are elementwise in
    (winner, x) and run in XLA over the output columns
    (crush_kernel.out_columns: a one-hot product, not a gather — the
    gather was 68 ms a call at 1 Mi lanes).  Keeping them out of the
    kernel also dodged a real Mosaic miscompile: hash32_2 fed from the
    gather/sum winner pipeline produced wrong values for ~0.03% of
    lanes (see r03 notes in fastpath._winners_cols)."""
    r = pl.program_id(1)
    x = xs_ref[0, :]
    tabs = (rhlh_ref, ll_lo_ref, ll_hi_ref, rh128)

    def operands(slab):
        sl = slice(slab * 128, (slab + 1) * 128)
        return (ids_ref[0, sl][None, :], wz_ref[0, sl][None, :],
                [magic_ref[j, sl][None, :].astype(_U32) for j in range(5)],
                off_ref[0, sl][None, :])

    def rw_of(slab, first):
        return jnp.zeros((x.shape[0],), dtype=_I32)

    _qh, _ql, pos, wid, _rwv = _column_over_slabs(
        x, S, tabs, r.astype(_U32), operands, rw_of)
    _store_row(pos_ref, r, pos)
    _store_row(id_ref, r, wid)


#: fields of a host row in the leaf table, each ``L`` lanes wide:
#: item ids, zero-weight mask, limb offset, the five magic limbs
_LEAF_FIELDS = 8

#: bits of an item's position in a column in the leaf kernel's packed
#: draw key (the low bits of its second word)
_POS_BITS = 15


def _leaf_lanes(items: int) -> int:
    """The leaf kernel's group width for a widest host of ``items``: the
    power of two at or above it, at least 32, while 128 // it columns
    share a 128-lane slab; past 64 items whole slabs (``_pad_lanes``).
    At 32 a slab's four columns' one-hot products fit the scoped VMEM
    side by side; eight of 16 lanes would overrun it."""
    if items > 64:
        return _pad_lanes(items)
    return max(32, 1 << max(0, items - 1).bit_length())


def _columns_per_slab(leaf_lanes: int) -> int:
    """r-columns one 128-lane slab of the leaf kernel carries."""
    return 128 // leaf_lanes if 0 < leaf_lanes < 128 else 1


def _draw_key(q_hi, q_lo, bad, pos):
    """(q_hi, q_lo, position) as one lexicographic two-word key, each
    word sign-biased to i32 so that plain signed compares order it:
    q_hi < 2^17 (Q <= 2^48) and the position fits _POS_BITS; a zero
    weight draws above every real item, still ordered by position."""
    hi = jnp.where(bad, _U32(0xFFFFFFFF), (q_hi << 15) | (q_lo >> 17))
    lo = jnp.where(bad, _U32(0xFFFF8000), (q_lo & _U32(0x1FFFF)) << 15) \
        | pos.astype(_U32)
    bias = _U32(0x80000000)
    return (hi ^ bias).astype(_I32), (lo ^ bias).astype(_I32)


def _key_less(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al < bl))


def _segment_first_min(kh, kl, width):
    """Segmented minimum of the draw keys over aligned groups of
    ``width`` lanes, all groups at once: a roll-and-select ladder of
    log2(width) steps after which the first lane of each group holds its
    group's key (other lanes hold partial minima).  Ties cannot occur —
    the key carries the position — so the lowest position wins them."""
    d = 1
    while d < width:
        # lane l looks at lane l + d (jnp.roll semantics: shift 128 - d)
        ph = pltpu.roll(kh, _I32(128 - d), 1)
        pl_ = pltpu.roll(kl, _I32(128 - d), 1)
        take = _key_less(ph, pl_, kh, kl)
        kh = jnp.where(take, ph, kh)
        kl = jnp.where(take, pl_, kl)
        d *= 2
    return kh, kl


def _leaf_kernel(xs_ref, pos_ref, fields_ref,
                 rhlh_ref, ll_lo_ref, ll_hi_ref,
                 id_ref, *, H, L, R, vary_r, rh128):
    """Grid (n//B, ceil(R/G)): one 128-lane slab of G = 128 // L
    r-columns a step (G = 1 and L // 128 slabs a column for hosts past
    64 items).  Lane l of a slab is item l mod L of column
    r = G * step + l // L, drawn from that column's host."""
    G = _columns_per_slab(L)
    s = pl.program_id(1)
    x = xs_ref[0, :]
    B = x.shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (1, 128), 1)
    group = lane // _I32(L) if G > 1 else jnp.zeros_like(lane)
    if vary_r:
        r_leaf = ((s * G + group) >> (vary_r - 1)).astype(_U32)
    else:
        r_leaf = _U32(0)
    iota = jax.lax.broadcasted_iota(_I32, (1, H), 1)

    def fetch(k):
        """Column k's winning hosts' rows: an exact f32 one-hot row
        gather on the MXU of the fields [ids | wz | off | magic0..4]
        (each L wide).  HIGHEST precision: the default TPU matmul
        truncates f32 operands to bf16, mangling ids and 16-bit magic
        limbs."""
        pos = pos_ref[pl.dslice(s * G + k, 1), :][0, :]
        oh = jnp.where(pos[:, None] == iota, jnp.float32(1.0),
                       jnp.float32(0.0))
        return jnp.dot(oh, fields_ref[...],
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)

    def lay_out(k, rows_k, fields, slab=0):
        """Column k's fields onto its group's lanes [k L, (k + 1) L) of
        the (B, 128) field slabs.  f32 -> u32 is an unhandled Mosaic
        cast; go via i32 (values < 2^16, exact)."""
        out = []
        for f in range(_LEAF_FIELDS):
            col = f * L + slab * 128
            base = col - col % 128
            piece = rows_k[:, base:base + 128].astype(_I32)
            if G > 1:
                piece = pltpu.roll(
                    piece, (k * L + 128 - col % 128) & _I32(127), 1)
                piece = jnp.where(group == k, piece, fields[f])
            out.append(piece)
        return out

    def may_be_empty(k):
        """Group k of the last slab lies past R when R is not a
        multiple of G: it is neither fetched nor stored."""
        return R % G and k >= R % G

    if G == 1:
        rows = fetch(0)
    else:
        # the columns unrolled, so their lane rolls are static: in a
        # loop they are dynamic rotates, and the cells' leaf call took
        # 95.0 ms where this takes 61.9 on a v5e (PERF.md)
        fields = [jnp.zeros((B, 128), _I32)] * _LEAF_FIELDS
        for k in range(G):
            if may_be_empty(k):
                fields = jax.lax.cond(
                    s * G + k < R,
                    lambda fl, k=k: lay_out(k, fetch(k), fl),
                    lambda fl: list(fl), fields)
            else:
                fields = lay_out(k, fetch(k), fields)
    best = None
    for slab in range(max(1, L // 128)):
        if G == 1:
            fields = lay_out(0, rows, None, slab)
        ids, wz, off = fields[:3]
        magic = [m.astype(_U32) for m in fields[3:]]
        u = hash32_3(x[:, None], ids, r_leaf) & _U32(0xFFFF)
        p_hi, p_lo = _ln_p48_pl(u, rhlh_ref, ll_lo_ref, ll_hi_ref, rh128)
        q_hi, q_lo = _magic_div_pl(p_hi, p_lo, magic, off)
        kh, kl = _segment_first_min(
            *_draw_key(q_hi, q_lo, wz != 0, lane % _I32(min(L, 128))
                       + _I32(slab * 128)), min(L, 128))
        # the winner's lane in this slab, from the first lane of a group
        at = (lane + (kl & _I32((1 << _POS_BITS) - 1))
              - _I32(slab * 128)) & _I32(127)
        wid = _row_lookup(jnp.broadcast_to(at, (B, 128)), ids)
        if best is None:
            best = (kh, kl, wid)
        else:
            take = _key_less(kh, kl, best[0], best[1])
            best = tuple(jnp.where(take, a, b)
                         for a, b in zip((kh, kl, wid), best))
    # a group's answer sits on its first lane: lanes onto sublanes
    out = best[2].T                                     # (128, B)
    for k in range(G):
        r = s * G + k

        def store(k=k, r=r):
            id_ref[pl.dslice(r, 1), :] = out[k * L:k * L + 1, :]
        if may_be_empty(k):
            pl.when(r < R)(store)
        else:
            store()


# ---------------------------------------------------------------------------
# approx-filter + packed-candidate exact verify (the fast path's fast path)
# ---------------------------------------------------------------------------
#
# The exact column kernels above price every (x, item, r) triple at the
# full ~200-op u32 pipeline.  The same certified-filter idea as
# straw2_u32.straw2_choose_index_approx — a cheap f32 draw approximation
# with a *measured* error bound narrows each (x, r) column to K candidate
# items — but packed across r: all R columns' candidates (R*K <= ~40
# rows) run through ONE exact sublane-oriented slab instead of R full
# lane slabs.  Exactness is unconditional: any (x, r) with more than K
# items inside the error band raises a flag and the caller re-runs the
# exact column kernels (measured: does not fire at realistic weights).
#
# The ln error bound is measured against the integer crush_ln over the
# full 16-bit domain USING THIS BACKEND'S OWN f32 log2 lowering (Mosaic's
# approximation differs from XLA's), so the certificate holds for the
# exact code path that runs.

_K = 4


def _ln_f32_pl(u):
    xf = u.astype(_I32).astype(jnp.float32) + jnp.float32(1.0)
    return jnp.log2(xf) * jnp.float32(2.0 ** 44)


def _ln_bound_kernel(u_ref, out_ref):
    out_ref[...] = _ln_f32_pl(u_ref[...].astype(_U32))


def _ln_f32_table(u, interpret: bool = False):
    """f32_ln over a (128, 512) int32 block through the filter kernel's
    own Pallas lowering (one whole-array program)."""
    return pl.pallas_call(
        _ln_bound_kernel,
        out_shape=jax.ShapeDtypeStruct((128, 512), jnp.float32),
        interpret=interpret,
    )(u)


@functools.lru_cache(maxsize=None)
def _ln_f32_bound(interpret: bool) -> float:
    """max |f32_ln(u) - crush_ln(u)| over every 16-bit u, with the f32
    evaluated by the same Pallas lowering the filter kernel uses."""
    from ceph_tpu.ops.crush_kernel import crush_ln
    u = jnp.arange(65536, dtype=jnp.int32).reshape(128, 512)
    approx = _ln_f32_table(u, interpret)
    exact = crush_ln(u.ravel().astype(jnp.uint32)).astype(jnp.float32)
    return float(jnp.max(jnp.abs(approx.ravel() - exact)))


def _approx_column(x, r, slab_ops, n_slabs, D):
    """One cheap f32 column: per-slab (q_lo, q_hi) bands.  slab_ops(s) ->
    (ids, wf, wz) with wf (B, 128) f32 weights, wz bool."""
    bands = []
    for s in range(n_slabs):
        ids, wf, wz = slab_ops(s)
        u = hash32_3(x[:, None], ids, r) & _U32(0xFFFF)
        q = (jnp.float32(2.0 ** 48) - _ln_f32_pl(u)) / wf
        # margin: measured ln bound + f32 representation of P (<= 2^25)
        # + f32 division/weight-rounding relative error + floor-tie
        # quantization
        m = ((jnp.float32(D) + jnp.float32(2 ** 25)) / wf
             + q * jnp.float32(2.0 ** -20) + jnp.float32(4.0))
        big = jnp.float32(3.0e38)
        q = jnp.where(wz, big, q)
        m = jnp.where(wz, jnp.float32(0.0), m)
        bands.append((q - m, q + m))
    return bands


def _sortable_f32(v):
    """Monotone u32 key for f32 (standard float-sort transform)."""
    bits = jax.lax.bitcast_convert_type(v, _U32)
    neg = (bits >> 31) == _U32(1)
    return jnp.where(neg, ~bits, bits | _U32(0x80000000))


def _extract_candidates(bands, K):
    """K candidate positions per row + the exactness certificate.

    Selection: K rounds of a packed-key argmin (the key truncates the
    f32 lower-bound's low 10 bits and carries the global position, so
    one unsigned min per round yields value AND position).  The
    certificate does not trust the selection order: after K rounds it
    checks directly that every lane inside the error band of the
    minimum upper bound was chosen — any miss raises the flag and the
    caller re-runs the exact kernels.  Returns ([(B,) pos] * K, flag).
    """
    n_slabs = len(bands)
    min_hi = None
    for _lo, hi in bands:
        h = jnp.min(hi, axis=1, keepdims=True)
        min_hi = h if min_hi is None else jnp.minimum(min_hi, h)
    los = [lo for lo, _ in bands]
    orig_in_band = [lo <= min_hi for lo in los]
    keys = []
    for s, lo in enumerate(los):
        b, width = lo.shape
        gpos = (jax.lax.broadcasted_iota(_I32, (b, width), 1)
                + _I32(s * 128)).astype(_U32)
        keys.append((_sortable_f32(lo) & _U32(0xFFFFFC00)) | gpos)
    chosen = [jnp.zeros_like(k, dtype=jnp.bool_) for k in keys]
    big_key = _U32(0xFFFFFFFF)
    positions = []
    for _k in range(K):
        best = None
        for s in range(n_slabs):
            m = _umin(keys[s], 1, False)
            best = m if best is None else \
                jnp.where(_ult(m, best), m, best)
        pos = (best & _U32(0x3FF)).astype(_I32)          # (B,)
        positions.append(pos)
        for s in range(n_slabs):
            b, width = keys[s].shape
            gpos = (jax.lax.broadcasted_iota(_I32, (b, width), 1)
                    + _I32(s * 128))
            hit = gpos == pos[:, None]
            keys[s] = jnp.where(hit, big_key, keys[s])
            chosen[s] = chosen[s] | hit
    missed = None
    for s in range(n_slabs):
        v = jnp.max(jnp.where(orig_in_band[s] & ~chosen[s], _I32(1),
                              _I32(0)), axis=1)
        missed = v if missed is None else jnp.maximum(missed, v)
    return positions, missed


#: candidate field order shared by the phase-1 and phase-2 kernels
_FIELDS = ("pos", "ids", "wz", "off", "m0", "m1", "m2", "m3", "m4")

#: candidate rows per column in the packed lane layout: K real
#: candidates padded to the 8-lane segment quantum with dummies
_KPACK = 8


def _gather_packed(positions, row_of_slab, n_slabs):
    """Gather one operand at all K candidate positions with ONE
    dynamic_gather per slab: lane k of the result holds candidate k's
    value (lanes >= K are garbage, masked later)."""
    b = positions[0].shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (b, 128), 1)
    gpos = jnp.zeros((b, 128), dtype=_I32)
    for k, p in enumerate(positions):
        gpos = jnp.where(lane == _I32(k), p[:, None], gpos)
    out = None
    for s in range(n_slabs):
        local = jnp.clip(gpos - _I32(s * 128), _I32(0), _I32(127))
        g = _row_lookup(local, row_of_slab(s))
        in_slab = (gpos >= _I32(s * 128)) & (gpos < _I32((s + 1) * 128))
        out = g if out is None else jnp.where(in_slab, g, out)
    return out


def _shift_to_segment(packed, r):
    """Move lanes [0, KPACK) to lanes [r*KPACK, (r+1)*KPACK): a per-row
    gather with a shifted index (garbage outside the segment, masked by
    the caller's segment write)."""
    b = packed.shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (b, 128), 1)
    idx = jnp.clip(lane - (r * _I32(_KPACK))[None, None], _I32(0),
                   _I32(127))
    return _row_lookup(jnp.broadcast_to(idx, (b, 128)), packed)


def _verify_packed(x, pos_p, ids_p, wz_p, off_p, magic_p, tabs,
                   *, R, vary_r):
    """The exact pipeline over a lane-packed candidate block (lane
    r*KPACK+k = candidate k of column r), then per-r segment winners.
    Returns two per-r lists of (B,) vectors: (wpos, wid)."""
    B = x.shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (B, 128), 1)
    valid = lane < _I32(R * _KPACK)
    seg_r = lane // _I32(_KPACK)
    if vary_r is None:
        r_vec = jnp.where(valid, seg_r, _I32(0)).astype(_U32)
    elif vary_r:
        r_vec = jnp.where(valid, seg_r >> _I32(vary_r - 1),
                          _I32(0)).astype(_U32)
    else:
        r_vec = jnp.zeros((B, 128), dtype=_U32)
    u = hash32_3(x[:, None], ids_p, r_vec) & _U32(0xFFFF)
    p_hi, p_lo = _ln_p48_pl(u, *tabs[:3], tabs[3])
    q_hi, q_lo = _magic_div_pl(p_hi, p_lo, magic_p, off_p)
    bad = (wz_p != 0) | ~valid
    q_hi = jnp.where(bad, _U32(0xFFFFFFFF), q_hi)
    q_lo = jnp.where(bad, _U32(0xFFFFFFFF), q_lo)
    wposs, wids = [], []
    for r in range(R):
        m = (seg_r == _I32(r)) & valid
        qh = jnp.where(m, q_hi, _U32(0xFFFFFFFF))
        mh = _umin(qh, 1, True)
        on_h = m & (qh == mh)
        ql_m = jnp.where(on_h, q_lo, _U32(0xFFFFFFFF))
        ml = _umin(ql_m, 1, True)
        on = on_h & (ql_m == ml)
        # ties resolve to the smallest ORIGINAL item position
        pos_m = jnp.where(on, pos_p, _I32(2 ** 31 - 1))
        minpos = jnp.min(pos_m, axis=1, keepdims=True)
        first = on & (pos_p == minpos) & m
        wid = jnp.sum(jnp.where(first, ids_p, _I32(0)), axis=1,
                      dtype=_I32)
        wposs.append(minpos[:, 0])
        wids.append(wid)
    return wposs, wids


def _froot_kernel(xs_ref, ids_ref, wz_ref, wf_ref, magic_ref, off_ref,
                  rhlh_ref, ll_lo_ref, ll_hi_ref,
                  pos_ref, id_ref, ovf_ref,
                  *, S, R, rh128, D):
    """Fused single-phase root columns: approx-filter every r column,
    pack the K candidates of all R columns into one (B, 128) lane block
    IN VMEM, run the exact pipeline once, emit per-r winners.

    This replaces the two-phase root_columns_fast whose staged candidate
    fields round-tripped ~10 (n, 128) i32 arrays through HBM between two
    pallas_calls — the layout the AOT toolchain compiled pathologically.
    One kernel, no staged state, same certificate: any (x, r) column
    with more than K items inside the measured f32 error band raises the
    overflow flag and the caller re-runs the exact column kernels."""
    x = xs_ref[0, :]
    B = x.shape[0]
    n_slabs = S // 128
    lane = jax.lax.broadcasted_iota(_I32, (B, 128), 1)
    tabs = (rhlh_ref, ll_lo_ref, ll_hi_ref, rh128)

    def slab_ops(s):
        sl = slice(s * 128, (s + 1) * 128)
        return (ids_ref[0, sl][None, :],
                wf_ref[0, sl][None, :],
                wz_ref[0, sl][None, :] != 0)

    def row_of(name):
        def rows(s):
            sl = slice(s * 128, (s + 1) * 128)
            if name == "ids":
                return ids_ref[0, sl]
            if name == "wz":
                return wz_ref[0, sl]
            if name == "off":
                return off_ref[0, sl]
            j = int(name[1])
            return magic_ref[j, sl].astype(_I32)
        return rows

    packed = {name: jnp.full((B, 128), _I32(2 ** 31 - 1)) if name == "pos"
              else jnp.zeros((B, 128), dtype=_I32) for name in _FIELDS}
    missed_all = jnp.zeros((B,), dtype=_I32)
    for r in range(R):
        bands = _approx_column(x, _U32(r), slab_ops, n_slabs, D)
        positions, missed = _extract_candidates(bands, _K)
        missed_all = jnp.maximum(missed_all, missed)
        in_seg = (lane >= _I32(r * _KPACK)) & (lane < _I32((r + 1) * _KPACK))
        for name in _FIELDS:
            if name == "pos":
                pk = jnp.full((B, 128), _I32(2 ** 31 - 1))
                for k, p in enumerate(positions):
                    pk = jnp.where(lane == _I32(k), p[:, None], pk)
            else:
                pk = _gather_packed(positions, row_of(name), n_slabs)
                pk = jnp.where(
                    (lane >= _I32(len(positions))) & (lane < _I32(_KPACK)),
                    _I32(1) if name == "wz" else _I32(0), pk)
            shifted = _shift_to_segment(pk, _I32(r))
            packed[name] = jnp.where(in_seg, shifted, packed[name])
    magic_p = [packed[f"m{j}"].astype(_U32) for j in range(5)]
    wposs, wids = _verify_packed(
        x, packed["pos"], packed["ids"], packed["wz"], packed["off"],
        magic_p, tabs, R=R, vary_r=None)
    for r in range(R):
        _store_row(pos_ref, r, wposs[r])
        _store_row(id_ref, r, wids[r])
    _store_row(ovf_ref, 0, missed_all)


def _consume_kernel(hw_ref, lw_ref, lb_ref, outh_ref, outl_ref, ovf_ref,
                    *, R, numrep, tries):
    """The firstn ladder over precomputed winner columns, fully unrolled.

    crush_choose_firstn (mapper.c:460-648) resets ftotal per replica and
    draws with r = rep + ftotal; within one replica every attempt either
    places (done) or fails (ftotal + 1), so an active lane at unroll step
    i of replica rep has ftotal == i exactly — r = rep + i is a STATIC
    row index into the winner columns.  That turns the XLA while_loop
    ladder (46 ms at the 64Ki bulk shape — as expensive as the draws it
    consumes) into ~numrep*R unrolled vector ops with no dynamic gathers.

    Collision semantics: a candidate collides if its host or device id
    equals ANY already-placed slot (earlier replicas only — the current
    replica has not placed yet), matching _consume/mapper.c; NONE slots
    (exhausted replicas) never match a real id.  Lanes that walk past the
    last precomputed column while still active raise the overflow flag,
    upon which the caller re-runs with the full r range."""
    b = hw_ref.shape[1]
    none_v = jnp.full((b,), _I32(0x7FFFFFFF))  # CRUSH_ITEM_NONE
    sel_h = [none_v for _ in range(numrep)]
    sel_l = [none_v for _ in range(numrep)]
    ovf = jnp.zeros((b,), dtype=jnp.bool_)
    for rep in range(numrep):
        done = jnp.zeros((b,), dtype=jnp.bool_)
        steps = min(tries, R - rep)
        for i in range(steps):
            r = rep + i
            hb = hw_ref[r, :]
            lf = lw_ref[r, :]
            bad = lb_ref[r, :] != 0
            for j in range(numrep):
                bad = bad | (sel_h[j] == hb) | (sel_l[j] == lf)
            place = ~done & ~bad
            sel_h[rep] = jnp.where(place, hb, sel_h[rep])
            sel_l[rep] = jnp.where(place, lf, sel_l[rep])
            done = done | place
            if i + 1 >= tries:
                done = jnp.ones((b,), dtype=jnp.bool_)
        # active lanes that ran out of columns (ft < tries): overflow
        ovf = ovf | (~done if steps < tries else jnp.zeros((b,), jnp.bool_))
    for rep in range(numrep):
        _store_row(outh_ref, rep, sel_h[rep])
        _store_row(outl_ref, rep, sel_l[rep])
    _store_row(ovf_ref, 0, ovf.astype(jnp.int32))


def consume_columns(hw, lw, lb, *, numrep: int, tries: int,
                    interpret: bool = False):
    """(R, N) winner columns -> (out_h, out_l, ovf): (numrep, N) int32
    selections with NONE holes and an (N,) overflow flag."""
    R, n = hw.shape
    B = min(BLOCK, n)
    z = np.int32(0)
    col = lambda: pl.BlockSpec((R, B), lambda i: (z, i))
    outs = [jax.ShapeDtypeStruct((numrep, n), jnp.int32),
            jax.ShapeDtypeStruct((numrep, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32)]
    out_specs = [pl.BlockSpec((numrep, B), lambda i: (z, i)),
                 pl.BlockSpec((numrep, B), lambda i: (z, i)),
                 pl.BlockSpec((1, B), lambda i: (z, i))]
    oh, ol, ovf = pl.pallas_call(
        functools.partial(_consume_kernel, R=R, numrep=numrep, tries=tries),
        grid=(n // B,),
        out_shape=outs,
        in_specs=[col(), col(), col()],
        out_specs=out_specs,
        interpret=interpret,
    )(hw, lw, lb.astype(jnp.int32))
    return oh, ol, ovf[0]


def _pad_lanes(n: int) -> int:
    return max(128, -(-n // 128) * 128)


def _pad_block(xs, *more):
    """Pad 1-D xs (and the last axis of any extra arrays) to a multiple
    of the batch block; returns (xs, padded_n, B, *more).  Small batches
    use a lane-quantum block so tests and trickle calls don't pay the
    bulk block's padding."""
    n = xs.shape[0]
    B = min(BLOCK, _pad_lanes(n))
    pad = (-n) % B
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad,), dtype=xs.dtype)])
        more = tuple(
            jnp.concatenate(
                [a, jnp.zeros((*a.shape[:-1], pad), dtype=a.dtype)],
            axis=-1) for a in more)
    out = (xs, n + pad, B)
    return out + more if more else out


@functools.lru_cache(maxsize=None)
def _ln_tables_rows():
    """Gather-layout ln tables, one vreg (128 lanes) wide: rhlh rows
    (13, 128) for k in [0,127] + the k==128 row as python constants; the
    256-entry LL table split at row 128 into (6, 128) halves."""
    rhlh, ll = _ln_limb_operands_np()          # (129, 13), (256, 6) bytes
    rhlh = rhlh.astype(np.int32)
    ll = ll.astype(np.int32)
    rh_rows = np.ascontiguousarray(rhlh[:128].T)
    rh128 = tuple(int(v) for v in rhlh[128])
    ll_lo = np.ascontiguousarray(ll[:128].T)
    ll_hi = np.ascontiguousarray(ll[128:].T)
    return rh_rows, rh128, ll_lo, ll_hi


def pack_tables(ids, w, lids=None, lw=None) -> tuple:
    """One map's bucket tables in the column kernels' operand layout,
    as host arrays.  ``ids`` / ``w`` (S,) are the root's items and
    weights, ``lids`` / ``lw`` (S, L) a host's a row (None: a flat
    rule), all zero-padded to the shape class by the caller
    (fastpath.build_tables; a padded item has weight 0 and never
    wins):

        root ids (1, S) | root zero-weight mask (1, S) | root weights
        as f32 (1, S) | root magic limbs (5, S) | root limb offsets
        (1, S) [| leaf fields (S, 8 * L) f32, chooseleaf rules only]

    The order is the kernels' ``tables`` argument.  Pure host work
    (the magic divisors are the cost): a changed map of the same class
    is this plus an upload, no program."""
    from ceph_tpu.ops.straw2_u32 import magic_tables
    limbs, off = magic_tables(w)
    tables = [ids[None, :], (w <= 0).astype(np.int32)[None, :],
              np.maximum(w, 1).astype(np.float32)[None, :],
              np.ascontiguousarray(limbs.T),            # (5, S)
              off.astype(np.int32)[None, :]]
    if lids is not None:
        l_limbs, l_off = magic_tables(lw)
        # a host's fields, _LEAF_FIELDS blocks of L lanes, every value
        # exact in f32 (ids < 2^24, limbs < 2^16)
        tables.append(np.concatenate([
            lids.astype(np.float32),
            (lw <= 0).astype(np.float32),
            l_off.astype(np.float32),
        ] + [l_limbs[..., j].astype(np.float32) for j in range(5)],
            axis=1))                                   # (S, 8 * L)
    return tuple(tables)


class PallasColumns:
    """The winner-precompute kernels of one shape class on the TPU
    backend: ``root_lanes`` padded root items (the one-hot dot of the
    leaf kernel wants 128-multiples, so they are the leaf table's host
    rows too) and ``leaf_lanes`` the leaf kernel's group width
    (``_leaf_lanes`` of the widest host; 0: a flat rule).
    The bucket tables are an argument of every call (``pack_tables``),
    never state of this object: the programs built around these calls
    serve every map of the class.

    Produces (host_win_ids, host_pos, leaf_win) arrays shaped (R, N)
    for r in [0, R): drop-in data for fastpath._consume.
    """

    def __init__(self, root_lanes: int, leaf_lanes: int, vary_r: int,
                 interpret: bool = False):
        self.interpret = interpret
        self.S_root = self.H = root_lanes
        self.S_leaf = leaf_lanes
        self.columns_per_slab = _columns_per_slab(leaf_lanes)
        if leaf_lanes >= 1 << _POS_BITS:
            raise ValueError(f"{leaf_lanes} leaf lanes overflow the "
                             f"leaf kernel's {_POS_BITS}-bit positions")
        self.vary_r = vary_r
        rh, self.rh128, ll_lo, ll_hi = _ln_tables_rows()
        self.tabs = (jnp.asarray(rh), jnp.asarray(ll_lo),
                     jnp.asarray(ll_hi))

    @property
    def D(self) -> float:
        """Certified ln error bound for the approx filter — measured
        lazily (a kernel compile + launch) since the filter is opt-in;
        lru-cached per backend mode, and a python constant by the time
        jit traces the filter kernels (property access runs eagerly in
        the wrappers before pallas_call)."""
        return _ln_f32_bound(self.interpret)

    @staticmethod
    def _fullspec(shape):
        return pl.BlockSpec(shape,
                            lambda i, r: (jnp.int32(0), jnp.int32(0)),
                            memory_space=pltpu.VMEM)

    def root_columns(self, xs, tables, R: int):
        """xs (N,) uint32 -> (pos, ids) each (R, N) int32.  is_out
        verdicts are computed by the caller in XLA (out_columns).
        Batches that are not a BLOCK multiple are zero-padded here."""
        root_ids, root_wz, _wf, root_magic, root_off = tables[:5]
        S = self.S_root
        xs, n, B = _pad_block(xs)
        grid = (n // B, R)     # r innermost: output blocks revisited
        outs = [jax.ShapeDtypeStruct((R, n), jnp.int32) for _ in range(2)]
        out_specs = [pl.BlockSpec((R, B), lambda i, r: (jnp.int32(0), i))
                     for _ in range(2)]
        fs = self._fullspec
        rh, ll_lo, ll_hi = self.tabs
        pos, ids = pl.pallas_call(
            functools.partial(_root_kernel, S=S, rh128=self.rh128),
            grid=grid,
            out_shape=outs,
            in_specs=[pl.BlockSpec((1, B), lambda i, r: (jnp.int32(0), i)),
                      fs((1, S)), fs((1, S)), fs((5, S)), fs((1, S)),
                      fs(rh.shape), fs(ll_lo.shape), fs(ll_hi.shape)],
            out_specs=out_specs,
            interpret=self.interpret,
        )(xs[None, :], root_ids, root_wz, root_magic, root_off,
          rh, ll_lo, ll_hi)
        return pos, ids

    def froot_columns(self, xs, tables, R: int):
        """Fused single-phase filtered root columns: (pos, ids, ovf) —
        one pallas_call, candidates packed in VMEM, is_out left to the
        caller.  Requires R * _KPACK <= 128."""
        if R * _KPACK > 128:
            raise ValueError(f"froot_columns: R={R} exceeds the lane pack")
        root_ids, root_wz, root_wf, root_magic, root_off = tables[:5]
        S = self.S_root
        D = self.D   # concrete before tracing
        xs, n, B = _pad_block(xs)
        Bc = 128   # 256 tops the 16M scoped-vmem limit (measured 16.22M)
        z = np.int32(0)
        fs1 = lambda shape: pl.BlockSpec(
            shape, lambda i: tuple(z for _ in shape),
            memory_space=pltpu.VMEM)
        rh, ll_lo, ll_hi = self.tabs
        outs = [jax.ShapeDtypeStruct((R, n), jnp.int32) for _ in range(2)]
        outs.append(jax.ShapeDtypeStruct((1, n), jnp.int32))
        out_specs = [pl.BlockSpec((R, Bc), lambda i: (z, i))
                     for _ in range(2)]
        out_specs.append(pl.BlockSpec((1, Bc), lambda i: (z, i)))
        pos, ids, ovf = pl.pallas_call(
            functools.partial(_froot_kernel, S=S, R=R,
                              rh128=self.rh128, D=D),
            grid=(n // Bc,),
            out_shape=outs,
            in_specs=[pl.BlockSpec((1, Bc), lambda i: (z, i)),
                      fs1((1, S)), fs1((1, S)), fs1((1, S)), fs1((5, S)),
                      fs1((1, S)),
                      fs1(rh.shape), fs1(ll_lo.shape), fs1(ll_hi.shape)],
            out_specs=out_specs,
            interpret=self.interpret,
        )(xs[None, :], root_ids, root_wz, root_wf, root_magic, root_off,
          rh, ll_lo, ll_hi)
        return pos, ids, ovf[0]

    def leaf_columns(self, xs, root_pos, tables, R: int):
        """root winner positions -> leaf_id (R, N): ``columns_per_slab``
        r-columns a grid step.  is_out verdicts are computed by the
        caller in XLA (out_columns)."""
        leaf_fields = tables[5]
        L, G = self.S_leaf, self.columns_per_slab
        # root_pos comes back padded from root_columns; re-pad from the
        # caller's batch width so both land on the same quantum
        root_pos = root_pos[:, :xs.shape[0]]
        xs, n, B, root_pos = _pad_block(xs, root_pos)
        grid = (n // B, -(-R // G))
        outs = [jax.ShapeDtypeStruct((R, n), jnp.int32)]
        out_specs = [pl.BlockSpec((R, B), lambda i, s: (jnp.int32(0), i))]
        fs = self._fullspec
        rh, ll_lo, ll_hi = self.tabs
        (lid,) = pl.pallas_call(
            functools.partial(_leaf_kernel, H=self.H, L=L, R=R,
                              vary_r=self.vary_r,
                              rh128=self.rh128),
            grid=grid,
            out_shape=outs,
            in_specs=[pl.BlockSpec((1, B), lambda i, s: (jnp.int32(0), i)),
                      pl.BlockSpec((R, B), lambda i, s: (jnp.int32(0), i)),
                      fs((self.H, _LEAF_FIELDS * L)),
                      fs(rh.shape), fs(ll_lo.shape), fs(ll_hi.shape)],
            out_specs=out_specs,
            interpret=self.interpret,
        )(xs[None, :], root_pos, leaf_fields,
          rh, ll_lo, ll_hi)
        return lid
