"""Batched GF(2^8) erasure-code kernels.

The reference's hot loop is ``ec_encode_data(blocksize, k, m, tbls, data, coding)``
(ISA-L, called from src/erasure-code/isa/ErasureCodeIsa.cc:118-130) — a GF(2^8)
matrix-vector product applied independently to every byte column of a stripe, which the
OSD invokes per 4-64 KiB stripe in a loop (src/osd/ECUtil.cc:120-159).  Here that whole
loop is one batched device call.

TPU-first design (not a translation).  GF(2^8) multiplication by a constant is linear
over GF(2) in the bits of the input, so the coding matrix becomes a 0/1 matrix W of
shape (k*8, m*8) (ceph_tpu.gf.tables.bit_matrix) and encoding is

    parity_bits = bits(data) @ W   (mod 2)

an integer matrix multiply on the MXU whose ``& 1`` epilogue is the XOR reduction.
Two executors share that formulation:

* **Fused Pallas kernel** (TPU): per grid step, a block of stripes is loaded to VMEM,
  bit-expanded on sublanes, lane-split into G=4 groups stacked on the contraction
  axis, and multiplied against a block-diagonal W (G*k*8, G*m*8) int8 operand.  The
  block-diagonal packing is the core trick: a plain (k*8, m*8) matmul uses m*8 = 32 of
  the MXU's 128 output lanes (1/8 utilization — the measured ceiling of the previous
  nibble one-hot kernel); four independent lane-groups sharing one matmul fill all 128.
  Expansion, matmul and bit-pack all stay VMEM-resident — no HBM intermediates.
  Kernel time and roofline share on today's code: not measured (root PERF.md).

* **XLA path** (any backend; also the CPU-mesh test fallback): the same bits @ W
  product tiled with lax.map so the 8x bit expansion stays in VMEM-scale working sets.

Decode is the same kernel with a host-side inverted sub-matrix (tiny, k x k), exactly
mirroring the reference's decode structure (ErasureCodeIsa.cc:150-310).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.gf.tables import bit_matrix, mul_table
from ceph_tpu.ops import telemetry


# ---------------------------------------------------------------------------
# numpy oracle — ground truth for bit-exactness tests and the CPU plugin
# ---------------------------------------------------------------------------

def ec_encode_ref(coeff: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Reference GF(2^8) encode on host.

    coeff : (m, k) uint8 coding matrix
    data  : (..., k, B) uint8 data chunks
    returns (..., m, B) uint8 parity chunks
    """
    # analysis: allow[blocking] -- host oracle: inputs are host numpy by contract (fallback/verification path)
    coeff = np.asarray(coeff, dtype=np.uint8)
    # analysis: allow[blocking] -- host oracle: inputs are host numpy by contract (fallback/verification path)
    data = np.asarray(data, dtype=np.uint8)
    mt = mul_table()
    # prods[..., i, j, b] = coeff[i, j] * data[..., j, b]
    prods = mt[coeff[..., :, :, None], data[..., None, :, :]]
    return np.bitwise_xor.reduce(prods, axis=-2)


def ec_decode_ref(tables: np.ndarray, pidx: np.ndarray,
                  data: np.ndarray) -> np.ndarray:
    """Reference heterogeneous-matrix decode on host.

    tables : (P, t, k) uint8 stacked recovery matrices
    pidx   : (S,) integer pattern index per stripe
    data   : (S, k, B) uint8 surviving chunks
    returns (S, t, B) uint8 — stripe i rebuilt with tables[pidx[i]]
    """
    tables = np.asarray(tables, dtype=np.uint8)
    data = np.asarray(data, dtype=np.uint8)
    mats = tables[np.asarray(pidx)]            # (S, t, k)
    mt = mul_table()
    prods = mt[mats[:, :, :, None], data[:, None, :, :]]  # (S, t, k, B)
    return np.bitwise_xor.reduce(prods, axis=2)


# ---------------------------------------------------------------------------
# shared table prep
# ---------------------------------------------------------------------------

_BITW = np.arange(8, dtype=np.int32)

#: lane groups sharing one block-diagonal matmul in the Pallas kernel (fills
#: the 128 MXU output lanes at m*8 = 32 outputs per group)
_G = 4

#: stripes per Pallas grid step (amortizes per-step pipeline overhead).
#: Chosen by a sweep on a retired set-up (sb in {8, 16, 32}, g in
#: {2, 4, 8, 16}); not re-measured on today's code
_SB = 16

#: byte-rows per XLA-path tile.  The bit expansion is k*8 int8 per source
#: byte; tiling keeps it in VMEM-scale working sets while the batch streams
#: (an untiled call materializes the expansion in HBM and halves throughput).
_TILE_ROWS = 1 << 17


def _blockdiag(wb: np.ndarray, g: int) -> np.ndarray:
    """Block-diagonal stack of g copies of the (k*8, m*8) bit matrix."""
    r, c = wb.shape
    out = np.zeros((g * r, g * c), dtype=np.int8)
    for i in range(g):
        out[i * r:(i + 1) * r, i * c:(i + 1) * c] = wb
    return out


# ---------------------------------------------------------------------------
# XLA executor (any backend)
# ---------------------------------------------------------------------------

def _xla_tile(w_bits: jax.Array, x: jax.Array, k: int, m: int,
              dot_dtype) -> jax.Array:
    """x: (T, k) uint8 byte rows -> (T, m) uint8 parity bytes."""
    t = x.shape[0]
    bits = ((x[:, :, None].astype(jnp.int32) >> _BITW) & 1)
    bits = bits.reshape(t, k * 8).astype(dot_dtype)
    acc = jax.lax.dot_general(
        bits, w_bits.astype(dot_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32 if dot_dtype == jnp.bfloat16 else jnp.int32,
    )
    pb = acc.astype(jnp.int32) & 1  # (T, m*8)
    return jnp.sum(pb.reshape(t, m, 8) << _BITW, axis=-1,
                   dtype=jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("k", "m", "dot_dtype"))
def _encode_xla(w_bits: jax.Array, data: jax.Array, *, k: int, m: int,
                dot_dtype=jnp.int8) -> jax.Array:
    """data: (S, k, B) uint8 -> parity (S, m, B) uint8 via tiled bits @ W."""
    s, _, b = data.shape
    x = jnp.transpose(data, (0, 2, 1)).reshape(s * b, k)  # (SB, k)
    rows = s * b
    if rows <= _TILE_ROWS:
        packed = _xla_tile(w_bits, x, k, m, dot_dtype)
    else:
        pad = (-rows) % _TILE_ROWS
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, k), dtype=x.dtype)])
        tiles = x.reshape(-1, _TILE_ROWS, k)
        packed = jax.lax.map(
            lambda xt: _xla_tile(w_bits, xt, k, m, dot_dtype), tiles
        ).reshape(-1, m)[:rows]
    return jnp.transpose(packed.reshape(s, b, m), (0, 2, 1)).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# heterogeneous-matrix batched decode (XLA, any backend)
# ---------------------------------------------------------------------------
#
# Encode coalesces trivially: every stripe multiplies the SAME coding
# matrix, so concurrent ops stack on the batch axis of one matmul.
# Decode could not — the recovery matrix depends on WHICH chunks
# survived, so each erasure pattern used to be its own device call
# (and its own jit entry).  Here the per-pattern bit matrices live
# stacked in one (P, k*8, t*8) table operand; each stripe carries a
# pattern index, the matrix is gathered on-device, and the product is
# one batched dot_general over all stripes of all patterns: the MXU
# sees a single (S, B, k8) x (S, k8, t8) batched matmul regardless of
# how many distinct erasure patterns the batch mixes.  The jit cache
# is bounded by buckets on BOTH data axes: the dispatch engine pow-2
# buckets the stripe axis, the codec pow-2 pads the table axis, and t
# is padded to a per-codec constant (zero matrix rows decode to zero
# rows, sliced off by the submitter).

#: stripes per decode tile: bounds the (ts, B, k*8) bit-expansion and
#: the gathered (ts, k*8, t*8) matrix stack to VMEM-scale working sets
#: while the batch streams through lax.map
_DEC_TILE_S = 256


def _decode_tile(w_tab: jax.Array, pidx: jax.Array, x: jax.Array,
                 k: int, t: int, dot_dtype) -> jax.Array:
    """x: (TS, k, B) uint8, pidx: (TS,) int32 -> (TS, t, B) uint8."""
    ts, _, b = x.shape
    bits = ((x[:, :, :, None].astype(jnp.int32) >> _BITW) & 1)  # (TS,k,B,8)
    bits = jnp.transpose(bits, (0, 2, 1, 3)).reshape(ts, b, k * 8)
    w = w_tab[pidx].astype(dot_dtype)                  # (TS, k8, t8) gather
    acc = jax.lax.dot_general(
        bits.astype(dot_dtype), w,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32 if dot_dtype == jnp.bfloat16
        else jnp.int32,
    )
    pb = acc.astype(jnp.int32) & 1                     # (TS, B, t*8)
    out = jnp.sum(pb.reshape(ts, b, t, 8) << _BITW, axis=-1,
                  dtype=jnp.int32)
    return jnp.transpose(out, (0, 2, 1)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("k", "t", "dot_dtype"))
def _decode_xla(w_tab: jax.Array, pidx: jax.Array, data: jax.Array, *,
                k: int, t: int, dot_dtype=jnp.int8) -> jax.Array:
    """data: (S, k, B) uint8 + per-stripe pattern index -> (S, t, B)."""
    s = data.shape[0]
    if s <= _DEC_TILE_S:
        return _decode_tile(w_tab, pidx, data, k, t, dot_dtype)
    pad = (-s) % _DEC_TILE_S
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((pad,) + data.shape[1:], dtype=data.dtype)])
        pidx = jnp.concatenate(
            [pidx, jnp.zeros((pad,), dtype=pidx.dtype)])
    tiles = (data.reshape(-1, _DEC_TILE_S, *data.shape[1:]),
             pidx.reshape(-1, _DEC_TILE_S))
    out = jax.lax.map(
        lambda xp: _decode_tile(w_tab, xp[1], xp[0], k, t, dot_dtype),
        tiles)
    return out.reshape(-1, t, out.shape[-1])[:s]


def _decode_jit_entries() -> int:
    """Compile-cache entry count for the batched decode entry point
    (kept separate from _jit_entries so encode-side retrace accounting
    is untouched)."""
    return _decode_xla._cache_size()


def ec_decode_batched(tables_bits: np.ndarray, pidx, data, *,
                      k: int, t: int, dot_dtype=jnp.int8) -> jax.Array:
    """Heterogeneous-matrix batched decode: one device call for stripes
    spanning MIXED erasure patterns.

    tables_bits : (P, k*8, t*8) int8 — stacked bit matrices
                  (decode_bit_table), P power-of-two padded by the
                  caller so the jit cache stays bounded by the table
                  bucket, not the pattern population
    pidx        : (S,) int — pattern index per stripe
    data        : (S, k, B) uint8 surviving chunks
    returns (S, t, B) uint8 (padded target rows are zeros).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    pidx = jnp.asarray(pidx, dtype=jnp.int32)
    tables_bits = jnp.asarray(tables_bits, dtype=jnp.int8)
    s, _, b = data.shape
    return telemetry.timed_kernel(
        "ec_decode",
        lambda: _decode_xla(tables_bits, pidx, data, k=k, t=t,
                            dot_dtype=dot_dtype),
        # the table operand is device-resident across calls (the codec
        # caches its device_put per snapshot), so only the per-call
        # operands count as h2d traffic
        batch=s, bytes_in=s * k * b + pidx.nbytes,
        bytes_out=s * t * b,
        cache_entries=_decode_jit_entries,
        signature=("ec_decode", k, t, s, b, tables_bits.shape[0],
                   str(dot_dtype)))


def decode_bit_table(mats) -> np.ndarray:
    """Stack per-pattern recovery matrices into the kernel's table
    operand: [(t, k) uint8, ...] -> (len(mats), k*8, t*8) int8."""
    return np.stack([bit_matrix(np.asarray(m, dtype=np.uint8))
                     for m in mats])


# ---------------------------------------------------------------------------
# fused Pallas executor (TPU)
# ---------------------------------------------------------------------------

def _expand_bits(d: jax.Array, k: int) -> jax.Array:
    """(k, B) uint8 -> (k*8, B) int8 bit planes: row j*8+t = bit t of chunk j."""
    d32 = d.astype(jnp.int32)
    rep = jnp.repeat(d32, 8, axis=0)
    shifts = jnp.tile(jnp.arange(8, dtype=jnp.int32), k)[:, None]
    return ((rep >> shifts) & 1).astype(jnp.int8)


def _pallas_kernel(d_ref, w_ref, out_ref, *, k, m, g, bc, sb):
    """One grid step: (sb, k, bc) uint8 -> (sb, m, bc) uint8 parity."""
    bg = bc // g
    outs = []
    for s in range(sb):
        bits = _expand_bits(d_ref[s], k)                     # (k8, bc) int8
        bits4 = jnp.concatenate(
            [bits[:, i * bg:(i + 1) * bg] for i in range(g)], axis=0)
        acc = jax.lax.dot_general(
            w_ref[...].T, bits4, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)                # (g*m8, bg)
        pb = (acc.astype(jnp.int32) & 1).reshape(g, m, 8, bg)
        bw = jnp.arange(8, dtype=jnp.int32)[None, None, :, None]
        packed = jnp.sum(pb << bw, axis=2, dtype=jnp.int32)  # (g, m, bg)
        outs.append(jnp.concatenate([packed[i] for i in range(g)], axis=1))
    out_ref[...] = jnp.stack(outs).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("k", "m", "bc", "interpret"))
def _encode_pallas(w_blk: jax.Array, data: jax.Array, *, k: int, m: int,
                   bc: int, interpret: bool = False) -> jax.Array:
    """data: (S, k, B) uint8 with S % _SB == 0 and B % bc == 0."""
    s, _, b = data.shape
    z = np.int32(0)  # concrete + 32-bit: neither a captured tracer under an
    return pl.pallas_call(  # outer jit nor an i64 index under x64
        functools.partial(_pallas_kernel, k=k, m=m, g=_G, bc=bc, sb=_SB),
        grid=(s // _SB, b // bc),
        in_specs=[
            pl.BlockSpec((_SB, k, bc), lambda i, j: (i, z, j)),
            pl.BlockSpec(w_blk.shape, lambda i, j: (z, z),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_SB, m, bc), lambda i, j: (i, z, j)),
        out_shape=jax.ShapeDtypeStruct((s, m, b), jnp.uint8),
        interpret=interpret,
    )(data, w_blk)


def _pick_bc(b: int) -> int | None:
    """Lane-block width for the Pallas kernel: a divisor of B that is a
    multiple of _G * 128 (each lane group needs >= one full vreg) and small
    enough that per-stripe VMEM temporaries stay modest."""
    for c in (4096, 2048, 1024, 512):
        if b % c == 0:
            return c
    return None


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _jit_entries() -> int:
    """Compile-cache entry count across the jitted entry points — the
    telemetry retrace counter differences this around each call."""
    return _encode_xla._cache_size() + _encode_pallas._cache_size()


def _multi_device(x) -> bool:
    """True when x is committed/sharded across more than one device
    (a mesh-sharded engine batch).  numpy inputs have no sharding;
    tracers (outer-jit composition) conservatively count as single."""
    try:
        return len(x.sharding.device_set) > 1
    except Exception:
        return False


def _row_sharding(x):
    """x's NamedSharding when it splits ONLY the leading (stripe)
    axis — the dispatch engine's placement contract — else None."""
    try:
        sh = x.sharding
        spec = sh.spec
    except Exception:
        return None
    if getattr(sh, "mesh", None) is None or len(spec) == 0:
        return None
    if spec[0] is None or any(s is not None for s in spec[1:]):
        return None
    return sh


def build_sharded_rows_fn(fn, sh, n_replicated: int = 0):
    """jit(shard_map(fn)) over a committed row sharding ``sh`` — the
    ONE construction site for the wrappers that let an opaque
    ``pallas_call`` (a custom call GSPMD cannot split) ride a
    mesh-sharded engine batch: the batch splits BEFORE the kernel, one
    program per device, output re-assembled under the same sharding.
    ``fn(data_shard, *replicated)`` must be row-independent along the
    leading axis (every kernel in this repo's dispatch channels is —
    the crush_kernel mesh contract); the ``n_replicated`` trailing
    operands broadcast whole to every shard.  Callers cache the
    returned callable per (sharding, static-args) — a fresh wrapper
    per flush would re-trace on the hot dispatch path."""
    from jax.sharding import PartitionSpec

    rep_specs = tuple(PartitionSpec() for _ in range(n_replicated))
    # check_vma=False: pallas_call has no shard_map replication rule
    # (jax raises NotImplementedError otherwise); replication here is
    # by construction — every replicated operand is broadcast whole
    return jax.jit(jax.shard_map(
        fn, mesh=sh.mesh, in_specs=(sh.spec,) + rep_specs,
        out_specs=sh.spec, check_vma=False))


def shard_map_rows(fn, data, *replicated):
    """One-shot convenience over build_sharded_rows_fn: run
    ``fn(data_shard, *replicated)`` over ``data``'s committed row
    sharding.  Uncached — use build_sharded_rows_fn (and cache the
    result) on hot paths."""
    return build_sharded_rows_fn(
        fn, data.sharding, len(replicated))(data, *replicated)


def _pallas_rows(w_blk, data, *, k, m, bc):
    """The fused Pallas encode over one (local) row block, padding the
    stripe axis to the grid quantum."""
    s = data.shape[0]
    pad = (-s) % _SB
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((pad, k, data.shape[2]), dtype=data.dtype)])
    out = _encode_pallas(w_blk, data, k=k, m=m, bc=bc)
    return out[:s] if pad else out


def _pallas_rows_shard(d, w, *, k, m, bc):
    """_pallas_rows with shard_map's (data, replicated...) arg order."""
    return _pallas_rows(w, d, k=k, m=m, bc=bc)


@functools.lru_cache(maxsize=32)
def _pallas_sharded_fn(sh, k: int, m: int, bc: int):
    """Cached sharded Pallas encode per (sharding, k, m, bc) —
    NamedShardings are hashable, so the cache key is exact."""
    return build_sharded_rows_fn(
        functools.partial(_pallas_rows_shard, k=k, m=m, bc=bc), sh,
        n_replicated=1)


def _encode_dispatch_impl(w_bits, w_blk, data, *, k, m, dot_dtype):
    s, _, b = data.shape
    bc = _pick_bc(b)
    # batches below one grid step would pad up to _SB-1 all-zero
    # stripes through the Pallas path; the XLA path wastes nothing
    if (w_blk is not None and bc is not None and s >= _SB
            and jax.default_backend() == "tpu"):
        if not _multi_device(data):
            return _pallas_rows(w_blk, data, k=k, m=m, bc=bc)
        # mesh-sharded batch: pallas_call is an opaque custom call
        # GSPMD cannot split, so wrap it in shard_map — the stripe
        # axis splits BEFORE the kernel and each device runs its own
        # fused program (PR 7's XLA-only routing guard, lifted).
        # Tables committed to a different mesh than the batch (knob
        # hot-reload race) fall back to the XLA path, which jit
        # re-places freely.
        sh = _row_sharding(data)
        blk_mesh = getattr(getattr(w_blk, "sharding", None), "mesh",
                           None)
        if (sh is not None
                and s // len(data.sharding.device_set) >= _SB
                and (blk_mesh is None or blk_mesh == sh.mesh)):
            return _pallas_sharded_fn(sh, k, m, bc)(data, w_blk)
    return _encode_xla(w_bits, data, k=k, m=m, dot_dtype=dot_dtype)


def _encode_dispatch(w_bits, w_blk, data, *, k, m, dot_dtype):
    s, _, b = data.shape
    return telemetry.timed_kernel(
        "ec_encode",
        lambda: _encode_dispatch_impl(w_bits, w_blk, data,
                                      k=k, m=m, dot_dtype=dot_dtype),
        batch=s, bytes_in=s * k * b, bytes_out=s * m * b,
        cache_entries=_jit_entries,
        signature=("ec", k, m, s, b, str(dot_dtype)))


def ec_encode_jax(coeff: np.ndarray, data, dot_dtype=jnp.int8) -> jax.Array:
    """One-shot encode (builds the bit tables each call; use make_encoder for reuse)."""
    coeff = np.asarray(coeff, dtype=np.uint8)
    m, k = coeff.shape
    wb = bit_matrix(coeff)
    w_bits = jnp.asarray(wb)
    data = jnp.asarray(data, dtype=jnp.uint8)
    squeeze = data.ndim == 2
    if squeeze:
        data = data[None]
    # only pay the block-diagonal build + upload when the Pallas path can run
    w_blk = (jnp.asarray(_blockdiag(wb, _G))
             if jax.default_backend() == "tpu" and _pick_bc(data.shape[2])
             else None)
    out = _encode_dispatch(w_bits, w_blk, data, k=k, m=m, dot_dtype=dot_dtype)
    return out[0] if squeeze else out


def make_encoder(coeff: np.ndarray, dot_dtype=jnp.int8, mesh=None):
    """Return a jitted encode(data (S,k,B) uint8) -> (S,m,B) with tables resident.

    ``mesh``: optional jax.sharding.Mesh — the bit tables are placed
    REPLICATED over it, so encode() accepts batches a mesh-sharded
    dispatch engine split across those devices without re-broadcasting
    the tables on every flush (and without tripping jax's mixed
    committed-device check)."""
    coeff = np.asarray(coeff, dtype=np.uint8)
    m, k = coeff.shape
    wb = bit_matrix(coeff)
    wb_host = jnp.asarray(wb)               # uncommitted: follows any batch
    blk_host = (jnp.asarray(_blockdiag(wb, _G))
                if jax.default_backend() == "tpu" else None)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        w_bits = jax.device_put(wb_host, rep)
        w_blk = (jax.device_put(blk_host, rep) if blk_host is not None
                 else None)
    else:
        w_bits = jax.device_put(wb_host)
        w_blk = (jax.device_put(blk_host) if blk_host is not None
                 else None)

    def encode(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        wb_use, blk_use = w_bits, w_blk
        # VALUE equality, not identity: a knob hot-reload rebuilds an
        # EQUAL Mesh object (jax Mesh __eq__ is value-based, same
        # devices/layout), and tables committed to the equal mesh are
        # fully compatible — an identity check would silently take the
        # re-broadcast fallback on every flush forever after a rebuild
        if mesh is not None and getattr(
                getattr(data, "sharding", None), "mesh", None) != mesh:
            # the batch arrived committed to a DIFFERENT mesh (knob
            # hot-reload between submit and flush) or unplaced (engine
            # stopped, inline run): mesh-committed tables would trip
            # jax's mixed-committed-devices check, so fall back to the
            # uncommitted copies — jit re-places them to match the
            # batch, trading one broadcast for correctness
            wb_use, blk_use = wb_host, blk_host
        return _encode_dispatch(wb_use, blk_use, data,
                                k=k, m=m, dot_dtype=dot_dtype)

    return encode
