"""Batched CRUSH placement kernels (JAX).

The reference evaluates placement one x at a time (``crush_do_rule``,
src/crush/mapper.c:900) and parallelises bulk remaps with a thread pool
(``ParallelPGMapper``, src/osd/OSDMapMapping.h:17).  Here the same math is one
device call batched over x: every PG's straw2 draws for a bucket are a (N, size)
tensor, the winner an argmax, and the firstn collision/retry ladder a masked
``lax.while_loop`` — no data-dependent Python control flow, static shapes, so XLA
tiles the whole remap onto the VPU.

Bit-exactness contract: every function here matches the scalar oracle in
ceph_tpu.crush.mapper_ref (itself written against src/crush/mapper.c semantics)
exactly, including the 16.16 fixed-point straw2 draw (``crush_ln`` fixed-point
tables, u64 wrap-around product, truncating s64 division) and the first-max-wins
tie-break of ``bucket_straw2_choose`` (mapper.c:361-384).

int64 is required (jax_enable_x64 is switched on in ceph_tpu.__init__): straw2
draws are s64 and the ln tables are 48-bit fixed point.

Mesh contract: every kernel here is elementwise along the x (batch) axis —
each lane's draws, retry ladder and reject tests read only that lane plus the
replicated map operands — so a mesh-sharded dispatch engine may split x over
any device mesh with bit-identical results (GSPMD partitions the jitted call;
``jnp.any`` in the while_loop conds becomes the only cross-shard collective).
Callers placing x with a committed sharding must hand the operand tables in
uncommitted (numpy/jnp.asarray) or replicated over the SAME mesh — the submit
helpers in ops.dispatch do the latter when they see a sharded batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.crush.hashfn import CRUSH_HASH_SEED
from ceph_tpu.crush.ln_table import lh_table, ll_table, rh_table
from ceph_tpu.crush.types import S64_MIN

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# rjenkins1 hash family (crush/hash.c semantics, elementwise on uint32 arrays)
# ---------------------------------------------------------------------------

def _mix(a, b, c):
    a = a - b - c; a = a ^ (c >> 13)
    b = b - c - a; b = b ^ (a << 8)
    c = c - a - b; c = c ^ (b >> 13)
    a = a - b - c; a = a ^ (c >> 12)
    b = b - c - a; b = b ^ (a << 16)
    c = c - a - b; c = c ^ (b >> 5)
    a = a - b - c; a = a ^ (c >> 3)
    b = b - c - a; b = b ^ (a << 10)
    c = c - a - b; c = c ^ (b >> 15)
    return a, b, c


def _const(shape_like, v):
    return jnp.full(jnp.shape(shape_like), v, dtype=_U32)


def hash32_2(a, b):
    """crush_hash32_2 (hash.c:38-50), elementwise over broadcast uint32 arrays."""
    a = jnp.asarray(a).astype(_U32)
    b = jnp.asarray(b).astype(_U32)
    a, b = jnp.broadcast_arrays(a, b)
    h = jnp.uint32(CRUSH_HASH_SEED) ^ a ^ b
    x = _const(h, 231232)
    y = _const(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    """crush_hash32_3 (hash.c:52-66), elementwise over broadcast uint32 arrays."""
    a = jnp.asarray(a).astype(_U32)
    b = jnp.asarray(b).astype(_U32)
    c = jnp.asarray(c).astype(_U32)
    a, b, c = jnp.broadcast_arrays(a, b, c)
    h = jnp.uint32(CRUSH_HASH_SEED) ^ a ^ b ^ c
    x = _const(h, 231232)
    y = _const(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def hash32_4(a, b, c, d):
    """crush_hash32_4 (hash.c:68-84), elementwise over broadcast uint32
    arrays — the draw hash of tree and list buckets."""
    a = jnp.asarray(a).astype(_U32)
    b = jnp.asarray(b).astype(_U32)
    c = jnp.asarray(c).astype(_U32)
    d = jnp.asarray(d).astype(_U32)
    a, b, c, d = jnp.broadcast_arrays(a, b, c, d)
    h = jnp.uint32(CRUSH_HASH_SEED) ^ a ^ b ^ c ^ d
    x = _const(h, 231232)
    y = _const(h, 1232)
    a, b, h = _mix(a, b, h)
    c, d, h = _mix(c, d, h)
    a, x, h = _mix(a, x, h)
    y, b, h = _mix(y, b, h)
    c, x, h = _mix(c, x, h)
    y, d, h = _mix(y, d, h)
    return h


# ---------------------------------------------------------------------------
# crush_ln — 2^44*log2(x+1) in 48-bit fixed point (mapper.c:248-290)
# ---------------------------------------------------------------------------
#
# The table lookups are one-hot matmuls over 8-bit limbs, not gathers: TPU
# dynamic gathers from small int64 tables run ~0.06 Gops/s while an (N,129)
# bf16 one-hot matmul is exact (one-hot 0/1 and limbs < 2^8 are exact bf16;
# the f32 accumulator sums < 2^15) and far faster (measured on v5e).

@functools.lru_cache(maxsize=None)
def _ln_limb_operands_np():
    """Host-side limb tables; kept numpy so no device value is cached across
    jit traces (a cached tracer-context array leaks into later traces).

    Limbs are 8-bit so the matmul runs in bf16 (values < 256 and one-hot 0/1
    are exact in bf16; sums of <=129 such products stay < 2^15, exact in the
    f32 accumulator) — ~4x the f32 MXU rate for ~2x the MACs.  rh needs 7
    limbs (RH[0] = 2^48 exactly, a 49-bit value); lh/ll fit 6.  Layout:
    rh limbs 0..6, lh limbs 7..12; ll limbs 0..5."""
    rhlh = np.concatenate([
        np.stack([(rh_table() >> (8 * i)) & 0xFF for i in range(7)], -1),
        np.stack([(lh_table() >> (8 * i)) & 0xFF for i in range(6)], -1),
    ], axis=1).astype(np.float32)
    ll = np.stack([(ll_table() >> (8 * i)) & 0xFF
                   for i in range(6)], -1).astype(np.float32)
    return rhlh, ll


def _ln_limb_operands():
    rhlh, ll = _ln_limb_operands_np()
    return (jnp.asarray(rhlh, dtype=jnp.bfloat16),
            jnp.asarray(ll, dtype=jnp.bfloat16))


def _onehot_rows(idx, n_rows, table):
    """Exact limb lookup: (N,) int32 -> (N, limbs) f32 via the MXU."""
    oh = (idx[..., None] == jnp.arange(n_rows, dtype=jnp.int32)).astype(
        jnp.bfloat16)
    flat = oh.reshape(-1, n_rows)
    out = jax.lax.dot_general(
        flat, table, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    return out.reshape(*idx.shape, table.shape[1])


def _limbs_to_i64(v, lo, hi):
    r = v[..., lo].astype(jnp.int64)
    for i in range(lo + 1, hi):
        r = r + (v[..., i].astype(jnp.int64) << (8 * (i - lo)))
    return r


def crush_ln(xin):
    """Elementwise crush_ln over uint32 input arrays; returns int64."""
    x = (jnp.asarray(xin).astype(_U32) + jnp.uint32(1))
    low17 = x & jnp.uint32(0x1FFFF)
    # bits to normalize the mantissa into [0x8000, 0x18000); the C code computes
    # this with a shift loop (mapper.c:263-268), here via count-leading-zeros
    bitlen = jnp.uint32(32) - jax.lax.clz(low17 | jnp.uint32(1))
    bits = jnp.uint32(16) - bitlen
    needs_norm = (x & jnp.uint32(0x18000)) == 0
    xnorm = jnp.where(needs_norm, x << bits, x)
    iexpon = jnp.where(needs_norm, jnp.uint32(15) - bits, jnp.uint32(15))
    idx1 = (xnorm >> 8) << 1
    k = ((idx1 - jnp.uint32(256)) >> 1).astype(jnp.int32)
    rhlh_tab, ll_tab = _ln_limb_operands()
    rhlh = _onehot_rows(k, 129, rhlh_tab)
    rh = _limbs_to_i64(rhlh, 0, 7)
    lh = _limbs_to_i64(rhlh, 7, 13)
    # u64 wrap-around product; only bits [48..56) survive
    xl64 = (xnorm.astype(jnp.uint64) * rh.astype(jnp.uint64)) >> jnp.uint64(48)
    idx2 = (xl64 & jnp.uint64(0xFF)).astype(jnp.int32)
    ll = _limbs_to_i64(_onehot_rows(idx2, 256, ll_tab), 0, 6)
    return (iexpon.astype(jnp.int64) << 44) + ((lh + ll) >> 4)


_LN_2_48 = np.int64(1) << 48


def straw2_draws(x, ids, r, weights):
    """Per-item straw2 draws (mapper.c:334-359  generate_exponential_distribution).

    x : (...,) uint32 input values      ids : (S,) item ids
    r : (...,) replica numbers          weights : (S,) 16.16 fixed-point, >= 0
    returns (..., S) int64 draws; weight==0 items get S64_MIN.
    """
    x = jnp.asarray(x)
    r = jnp.asarray(r)
    ids = jnp.asarray(ids)
    w = jnp.asarray(weights).astype(jnp.int64)
    u = hash32_3(x[..., None], ids, r[..., None]) & jnp.uint32(0xFFFF)
    ln = crush_ln(u) - _LN_2_48
    # div64_s64 truncates toward zero; ln <= 0 and w > 0, so trunc == -((-ln)//w)
    draw = -((-ln) // jnp.maximum(w, 1))
    return jnp.where(w > 0, draw, jnp.int64(S64_MIN))


def straw2_choose_index(x, ids, r, weights):
    """Winning *position* in the bucket for each (x, r) — first max wins, matching
    the strict `>` comparison in bucket_straw2_choose (mapper.c:374-380)."""
    return jnp.argmax(straw2_draws(x, ids, r, weights), axis=-1)


# ---------------------------------------------------------------------------
# is_out — probabilistic rejection by the reweight vector (mapper.c:424-438)
# ---------------------------------------------------------------------------

def verdict(w, item, x):
    """is_out's arithmetic on the devices' reweights ``w``, however they
    were fetched: in at 0x10000 or more, out at 0, else in when the
    16-bit hash of (x, item) is under ``w``."""
    keep_full = w >= 0x10000
    zero = w == 0
    h = hash32_2(x, item.astype(jnp.uint32)) & jnp.uint32(0xFFFF)
    keep_prob = h.astype(w.dtype) < w
    return ~(keep_full | (~zero & keep_prob))


def is_out(reweight, item, x):
    """reweight: (D,) 16.16 per-device; item: (...,) device ids; x: (...,) inputs.
    Ids beyond the reweight vector are out, like the weight_max guard in
    mapper.c:424-427 (jax gathers clamp, so the bound is checked explicitly).
    The fetch is an XLA gather: right for the (N,) items of the generic
    interpreter's loops and of ``flat_firstn``; the fused fast path
    fetches ``reweight_words`` by ``fetch_words`` instead."""
    reweight = jnp.asarray(reweight)
    n = reweight.shape[0]
    oob = (item < 0) | (item >= n)
    w = reweight[jnp.clip(item, 0, n - 1)].astype(jnp.int64)
    return oob | verdict(w, item, x)


def reweight_words(reweight):
    """All ``verdict`` asks of a device, in an int32 word: its reweight
    clipped to [0, 0x10000] (17 bits) keeps the three tests for any
    int64 it could hold."""
    return jnp.clip(jnp.asarray(reweight), 0, 0x10000).astype(jnp.int32)


# a word table is (M / 128, 128): an id is a row and a lane of it
_LANE_BITS = 7
_LANES = 1 << _LANE_BITS


def fetch_words(word, ids):
    """``word[clip(ids, 0, M - 1)]`` without a gather, which costs a
    v5e 7-11 ns a cell (PERF.md, PR 38).  ``word`` (M,) int32 of values
    under 2^24, ``ids`` int32 planes with the batch on the last axis.
    The id splits into (table row, lane); the lane's one-hot meets the
    (M / 128, 128) table on the MXU, which gives every table row's
    candidate, and a select over the rows keeps the id's own.  Exact:
    f32 holds a word, and HIGHEST keeps the f32 operand whole on the
    MXU.  XLA fuses one-hot, product and select into one pass; nothing
    of size rows x ids is stored.  The caller zeroes the words of ids
    outside its bounds."""
    m_pad = word.shape[0]
    at = jnp.clip(ids, 0, m_pad - 1)
    n_rows = -(-m_pad // _LANES)
    table = jnp.pad(word, (0, n_rows * _LANES - m_pad)).astype(
        jnp.float32).reshape(n_rows, _LANES)
    over = (slice(None),) + (None,) * ids.ndim
    lane = jnp.arange(_LANES, dtype=jnp.int32)[over]
    onehot = ((at & (_LANES - 1))[None] == lane).astype(jnp.float32)
    rows = jax.lax.dot_general(                         # (rows, *ids.shape)
        table, onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    row = jnp.arange(n_rows, dtype=jnp.int32)[over]
    return jnp.sum(jnp.where((at >> _LANE_BITS)[None] == row, rows, 0.0),
                   axis=0).astype(jnp.int32)


def out_columns(word, item, x):
    """``is_out`` for id planes ``item`` (R, N) against ``x`` (N,), from
    the devices' ``reweight_words``: the fast path's fetch.  An id
    outside the vector (a NONE hole, a padded lane) has word 0: out."""
    got = fetch_words(word, item)
    got = jnp.where((item >= 0) & (item < word.shape[0]), got, 0)
    return verdict(got, item, x[None, :])


# ---------------------------------------------------------------------------
# folds over planes (the batch on the lane axis): what the fast path and the
# placement ladder use where rows would want a sort
# ---------------------------------------------------------------------------

def first_of(masks, cells, default):
    """The cell of the first plane whose mask is set."""
    out = default
    for mask, cell in zip(reversed(masks), reversed(cells)):
        out = jnp.where(mask, cell, out)
    return out


def compact_planes(keep, *carried):
    """Stable compaction of W planes without a sort: the kept cells
    move to the front in order.  Cell j lands at k = the number of kept
    cells before it, so out_k is the one cell with keep_j & (pos_j == k)
    (j >= k) — a fixed network of W (W + 1) / 2 selects on dense
    vectors.  ``keep`` is W masks; ``carried`` is (planes, fill) pairs
    moved alike; returns their compacted planes and the count of kept
    cells."""
    w = len(keep)
    pos, count = [], jnp.zeros(keep[0].shape, dtype=jnp.int32)
    for j in range(w):
        pos.append(count)
        count = count + keep[j].astype(jnp.int32)
    lands = [[keep[j] & (pos[j] == k) for j in range(k, w)]
             for k in range(w)]
    outs = [[first_of(lands[k], cells[k:], fill) for k in range(w)]
            for cells, fill in carried]
    return outs, count


# ---------------------------------------------------------------------------
# flat firstn select: one straw2 bucket, n distinct replicas, retry ladder
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("numrep", "tries"))
def flat_firstn(x, ids, weights, reweight, *, numrep: int, tries: int = 51):
    """Batched CHOOSE_FIRSTN of ``numrep`` distinct devices from one straw2 bucket.

    Semantics match crush_choose_firstn (mapper.c:460-648) specialised to a flat
    map (single straw2 root of devices, modern tunables: choose_local_tries=0,
    choose_local_fallback_tries=0): for replica ``rep`` the draw uses
    r = rep + ftotal where ftotal counts this replica's collision/reject retries,
    and a replica is abandoned after ``tries`` failures (tries =
    choose_total_tries + 1 = 51 by default, mapper.c:906).

    x        : (N,) uint32 batch of inputs (pps values)
    ids      : (S,) device ids in the bucket
    weights  : (S,) 16.16 straw2 weights
    reweight : (D,) 16.16 per-device reweight vector (is_out test)
    returns  : (N, numrep) int32 device ids, CRUSH_ITEM_NONE (0x7fffffff) on failure
    """
    x = jnp.asarray(x).astype(_U32)
    ids = jnp.asarray(ids).astype(jnp.int32)
    n = x.shape[0]
    none = jnp.int32(0x7FFFFFFF)
    out = jnp.full((n, numrep), none, dtype=jnp.int32)

    def place_rep(rep, out):
        def cond(state):
            _, _, active = state
            return jnp.any(active)

        def body(state):
            sel, ftotal, active = state
            r = jnp.full((n,), rep, dtype=_U32) + ftotal.astype(_U32)
            pos = straw2_choose_index(x, ids, r, weights)
            item = ids[pos]
            collide = jnp.any(out == item[:, None], axis=1)
            rejected = is_out(reweight, item, x)
            bad = collide | rejected
            sel = jnp.where(active & ~bad, item, sel)
            ftotal = jnp.where(active & bad, ftotal + 1, ftotal)
            active = active & bad & (ftotal < tries)
            return sel, ftotal, active

        sel = jnp.full((n,), none, dtype=jnp.int32)
        ftotal = jnp.zeros((n,), dtype=jnp.int32)
        active = jnp.ones((n,), dtype=bool)
        sel, _, _ = jax.lax.while_loop(cond, body, (sel, ftotal, active))
        return out.at[:, rep].set(sel)

    for rep in range(numrep):
        out = place_rep(rep, out)
    return out
