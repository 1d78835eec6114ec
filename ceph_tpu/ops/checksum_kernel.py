"""Batched object-integrity digests — the deep-scrub checksum kernel.

Deep scrub is a checksum workload: every object's payload and omap
blob hashes into the (size, data_crc, omap_crc) scrub-map triple
(`osd/ec_util.shard_crc`, the reference's chunky-scrub digests in
src/osd/PGBackend::be_deep_scrub).  The seed computed those digests
one object at a time on the host; this module turns a whole PG's
digests into ONE batched device call riding the dispatch engine,
exactly the treatment PRs 3-11 gave encode/decode/CRUSH/placement.

Variable object sizes are the obstacle: a CRC over row[:L] with L
varying per row defeats naive batching (per-byte masking serializes
the hot loop on selects).  Two linearity facts remove the lengths from
the device kernel entirely:

* **crc32 zero-padding is invertible.**  The crc register update for a
  ZERO byte is a GF(2)-linear map Z of the 32 register bits (the table
  lookup of a linear function of the register is linear).  So the
  register over row[:L] relates to the register over the zero-padded
  fixed width W by r_true = Z^-(W-L) r_padded: the kernel digests the
  padded batch at one fixed width — every row identical shape, no
  per-byte selects — and a per-row 32x32 GF(2) matrix-vector epilogue
  (matrices gathered from an aux operand the submitter builds from the
  lengths) strips the padding's effect exactly.

* **GF(2^8) Horner trailing zeros are a multiplier.**  The GF shard
  digest is a 4-lane Horner evaluation d = alpha*d ^ byte over the
  byte stream (lane l takes bytes l, l+4, ...); t trailing zero steps
  multiply the lane state by alpha^t, undone by a gathered alpha^-t.

* **Both digests are GF(2)-linear in the row's bits.**  One crc byte
  step from register c on byte x is Z(c) ^ T0[x], and T0 is linear in
  the bits of x; so the register after W bytes is Z^W(init) xor, over
  the set bits (p, b) of the row, Z^(W-1-p)(T0[1 << b]).  A GF(2^8)
  multiplication by a constant is an 8x8 bit matrix, so lane p % 4 of
  the Horner digest is the xor of alpha^((W-1-p) // 4) * (1 << b) over
  the same bits.  A padded batch (S, W) is therefore ONE product
  bits (S, 8W) @ M (8W, 64) reduced mod 2 — 32 crc columns, 4 x 8 GF
  columns — an integer matmul on the MXU whose ``& 1`` is the xor,
  exactly the formulation ``ops/gf_kernel`` gives the encode.  Rows
  wider than SEG_WIDTH are cut into segments that share one segment
  matrix, and a second, small product folds the segments' partial
  registers (Z^(C*i) on the crc, alpha^(C/4*i) on the lanes).  No
  step of the program depends on the one before it: nothing in it is
  sequential in the row's bytes.

Both digests share that one product, so a PG's whole object
population digests in a single kernel launch.  The host oracle
(`scrub_digest_ref`) is the literal per-row `shard_crc` loop — the
seed's path, and the bit-exactness ground truth the property tests
pin; it doubles as the channel's breaker fallback.

Like every kernel module, jax only enters through the jitted entry
point — the oracle and the operand builders are numpy/zlib only, so
the OSD's scalar fallback path never imports the device stack.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

from ceph_tpu.ops import telemetry

#: crc32 (zlib/ISO-HDLC) reflected polynomial; the repo's shard_crc is
#: zlib.crc32 — the Castagnoli polynomial of the reference's hardware
#: crc32c is an implementation detail of the integrity attr (see
#: osd/ec_util.py), the detection semantics are identical
_CRC_POLY = 0xEDB88320
_CRC_INIT = 0xFFFFFFFF

#: GF(2^8) Horner evaluation point for the shard digest (alpha = x)
_GF_ALPHA = 2

#: minimum padded row width (pow2, multiple of the 4 GF lanes)
MIN_WIDTH = 8

#: rows wider than this take the scalar host path: a row is padded to
#: a pow-2 width and expanded to a byte per bit on the device, so one
#: multi-MB object would cost 8x its padded size in device memory and
#: one more compiled width, where the host's crc32 runs at GB/s
MAX_WIDTH = 1 << 18

#: widest row ONE bit-matrix product digests (its (8 * 4096, 64) int8
#: matrix is 2 MiB); wider rows are cut into segments of this width
#: and a second product folds their W / SEG_WIDTH <= 64 partial registers
SEG_WIDTH = 1 << 12


# ---------------------------------------------------------------------------
# host oracle — ground truth for bit-exactness tests and the breaker fallback
# ---------------------------------------------------------------------------

def gf_digest_ref(row: np.ndarray) -> int:
    """4-lane GF(2^8) Horner digest of one row, packed little-endian:
    lane l evaluates bytes row[l::4] at alpha (the literal per-byte
    loop — the definition the batched kernel must reproduce)."""
    alpha = _gf_alpha_list()
    packed = 0
    for lane in range(4):
        d = 0
        for b in row[lane::4].tolist():
            d = alpha[d] ^ b
        packed |= d << (8 * lane)
    return packed


def scrub_digest_ref(batch, lengths, *_aux) -> np.ndarray:
    """Bit-exact host oracle: per row i, col 0 is ``shard_crc`` of
    row[:L_i] (the seed's scalar scrub loop, literally) and col 1 the
    packed GF Horner digest.  Extra aux operands (the device path's
    unpad matrices) are accepted and ignored so the engine's fallback
    ladder can call this with the full aux tuple."""
    # analysis: allow[blocking] -- host oracle: inputs are host numpy by contract (fallback/verification path)
    batch = np.asarray(batch, dtype=np.uint8)
    lengths = np.asarray(lengths, dtype=np.int64)
    out = np.zeros((batch.shape[0], 2), dtype=np.uint32)
    for i in range(batch.shape[0]):
        row = batch[i, : int(lengths[i])]
        out[i, 0] = zlib.crc32(row.tobytes()) & 0xFFFFFFFF
        out[i, 1] = gf_digest_ref(row)
    return out


# ---------------------------------------------------------------------------
# table prep (host, cached)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _crc_table() -> np.ndarray:
    """(256,) uint32: the classic byte-at-a-time table T0."""
    t0 = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (_CRC_POLY if c & 1 else 0)
        t0[i] = c
    return t0


def _apply_cols(cols: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """GF(2) matrix (32 uint32 columns) applied to uint32 value(s):
    out = XOR of columns selected by the set bits of each value."""
    vals = np.asarray(vals, dtype=np.uint32)
    out = np.zeros_like(vals)
    for j in range(32):
        bit = (vals >> np.uint32(j)) & np.uint32(1)
        out ^= cols[j] * bit
    return out


@functools.lru_cache(maxsize=1)
def _zero_cols() -> np.ndarray:
    """Columns of Z, the crc-register update for one ZERO byte:
    Z(c) = (c >> 8) ^ T0[c & 0xFF] — linear because T0 is the crc map
    of the byte, itself linear over GF(2)."""
    t0 = _crc_table()
    cols = np.zeros(32, dtype=np.uint32)
    for j in range(32):
        c = np.uint32(1 << j)
        cols[j] = (c >> np.uint32(8)) ^ t0[int(c) & 0xFF]
    return cols


@functools.lru_cache(maxsize=1)
def _zero_inv_cols() -> np.ndarray:
    """Z^-1 columns via GF(2) Gaussian elimination (Z is invertible:
    the crc register after a zero byte determines the register
    before)."""
    n = 32
    cols = _zero_cols()
    m = np.zeros((n, 2 * n), dtype=np.uint8)
    for j in range(n):
        for i in range(n):
            m[i, j] = (int(cols[j]) >> i) & 1
        m[j, n + j] = 1
    for col in range(n):
        piv = next(r for r in range(col, n) if m[r, col])
        if piv != col:
            m[[col, piv]] = m[[piv, col]]
        for r in range(n):
            if r != col and m[r, col]:
                m[r] ^= m[col]
    inv = np.zeros(n, dtype=np.uint32)
    for j in range(n):
        v = 0
        for i in range(n):
            if m[i, n + j]:
                v |= 1 << i
        inv[j] = v
    return inv


def _identity_cols() -> np.ndarray:
    return np.uint32(1) << np.arange(32, dtype=np.uint32)


@functools.lru_cache(maxsize=4096)
def _zero_pow_cols(k: int) -> np.ndarray:
    """Columns of Z^k for any integer k (square-and-multiply over the
    composition _apply_cols): k > 0 passes a crc register over k zero
    bytes, k < 0 strips -k trailing zero bytes from it."""
    if k == 0:
        return _identity_cols()
    step = 1 if k > 0 else -1
    half = _zero_pow_cols(step * (abs(k) // 2))
    sq = _apply_cols(half, half)
    if abs(k) % 2:
        return _apply_cols(_zero_cols() if k > 0 else _zero_inv_cols(), sq)
    return sq


#: widest padded width whose full Z^-k table is precomputed (one
#: compose per entry: ~0.1 ms each, so ~0.4 s once per process at the
#: cap); wider batches build only the DISTINCT pad counts they need
#: via square-and-multiply (_zero_pow_cols, O(log k) composes, memoized)
#: — an O(width) build at MAX_WIDTH would stall the submitting thread
#: for tens of seconds
_TABLE_WIDTH_MAX = 4096


@functools.lru_cache(maxsize=16)
def _unpad_table(width: int) -> np.ndarray:
    """(width + 1, 32) uint32: Z^-k columns for every pad count a
    batch of this width can need — built once per width (iterating
    Z^-1 composition), so the per-call operand build is one numpy
    gather instead of a per-row python loop (the scrub hot path runs
    hundreds of chunks a second; per-row python there is measurable
    GIL theft from the serving threads)."""
    out = np.zeros((width + 1, 32), dtype=np.uint32)
    out[0] = _identity_cols()
    zinv = _zero_inv_cols()
    for k in range(1, width + 1):
        out[k] = _apply_cols(zinv, out[k - 1])
    return out


@functools.lru_cache(maxsize=1)
def _gf_alpha_row() -> np.ndarray:
    from ceph_tpu.gf.tables import mul_table
    return np.ascontiguousarray(mul_table()[_GF_ALPHA])


@functools.lru_cache(maxsize=1)
def _gf_alpha_list() -> list:
    """alpha * d for d in 0..255 as python ints (the oracle's inner
    loop indexes it once per byte)."""
    return _gf_alpha_row().tolist()


@functools.lru_cache(maxsize=1)
def _gf_alpha_inv() -> int:
    row = _gf_alpha_row()
    return int(np.nonzero(row == 1)[0][0])


@functools.lru_cache(maxsize=32)
def _gf_inv_pows(n: int) -> np.ndarray:
    """(n + 1,) uint8: alpha^-t for t in 0..n (undoes t trailing zero
    Horner steps on one lane)."""
    from ceph_tpu.gf.tables import mul_table
    mt = mul_table()
    inv = _gf_alpha_inv()
    out = np.zeros(n + 1, dtype=np.uint8)
    out[0] = 1
    for t in range(1, n + 1):
        out[t] = mt[int(out[t - 1]), inv]
    return out


# ---------------------------------------------------------------------------
# the digests as one GF(2) matrix (host-built, device-resident)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _gf_alpha_pows() -> np.ndarray:
    """(255,) uint8: alpha^e for e mod 255 (a nonzero element's 255th
    power is 1, so the exponent of any Horner weight reduces mod 255)."""
    row = _gf_alpha_row()
    out = np.ones(255, dtype=np.uint8)
    for e in range(1, 255):
        out[e] = row[out[e - 1]]
    return out


def _bits(vals: np.ndarray, n: int) -> np.ndarray:
    """(...,) unsigned -> (..., n) int8: the low n bits, LSB first."""
    shifts = np.arange(n, dtype=vals.dtype)
    return ((vals[..., None] >> shifts) & 1).astype(np.int8)


def _gf_const_bits(consts: np.ndarray) -> np.ndarray:
    """(n,) GF(2^8) constants -> (n, 8, 8) 0/1: [i, s, r] is bit r of
    consts[i] * (1 << s) — gf.tables.bit_matrix of a one-row coding
    matrix, one 8x8 block a constant."""
    from ceph_tpu.gf.tables import bit_matrix
    return bit_matrix(consts[None, :]).reshape(len(consts), 8, 8)


def _segment_matrix(c: int) -> np.ndarray:
    """(8c, 64) int8, 0/1: what bit b of byte p of a c-byte segment
    adds to the digests when the register starts from ZERO.  Row
    b * c + p (bit-plane major: the kernel lays the eight planes of a
    row side by side, no interleave); columns 0..31 are the crc
    register's bits Z^(c-1-p)(T0[1 << b]), columns 32 + 8l .. 39 + 8l
    lane l's byte — alpha^((c-1-p) // 4) * (1 << b) on lane p % 4 and
    zero on the other three."""
    if c < 4 or c & (c - 1):
        raise ValueError(f"segment width {c} is not a power of two >= 4")
    # crc[d, b] = Z^d(T0[1 << b]) by doubling: the upper half of each
    # level is Z^n of the lower one
    crc = _crc_table()[1 << np.arange(8)][None, :]
    n = 1
    while n < c:
        crc = np.concatenate([crc, _apply_cols(_zero_pow_cols(n), crc)])
        n *= 2
    out = np.zeros((8, c, 64), dtype=np.int8)
    out[:, :, :32] = _bits(crc[::-1], 32).transpose(1, 0, 2)
    dist = c - 1 - np.arange(c)
    gf = _gf_const_bits(_gf_alpha_pows()[(dist // 4) % 255])   # (c, 8, 8)
    for lane in range(4):
        out[:, lane::4, 32 + 8 * lane:40 + 8 * lane] = (
            gf[lane::4].transpose(1, 0, 2))
    return out.reshape(8 * c, 64)


def _fold_matrix(nseg: int, c: int) -> np.ndarray:
    """(64 * nseg, 64) int8, 0/1: folds the partial digests of a row's
    nseg segments of c bytes (each from a zero register) into the
    row's.  Segment i is followed by nseg-1-i more, so its 64 rows are
    Z^(c * (nseg-1-i)) on the crc bits and alpha^(c/4 * (nseg-1-i)) on
    each lane's eight."""
    out = np.zeros((nseg, 64, 64), dtype=np.int8)
    after = nseg - 1 - np.arange(nseg)
    gf = _gf_const_bits(_gf_alpha_pows()[(c // 4 * after) % 255])
    for lane in range(4):
        out[:, 32 + 8 * lane:40 + 8 * lane,
            32 + 8 * lane:40 + 8 * lane] = gf
    step, cols = _zero_pow_cols(c), _identity_cols()
    for i in range(nseg - 1, -1, -1):
        out[i, :32, :32] = _bits(cols, 32)
        cols = _apply_cols(step, cols)
    return out.reshape(64 * nseg, 64)


@functools.lru_cache(maxsize=16)
def _segment_operand(c: int):
    import jax.numpy as jnp
    return jnp.asarray(_segment_matrix(c))


@functools.lru_cache(maxsize=8)
def _fold_operand(nseg: int):
    import jax.numpy as jnp
    return jnp.asarray(_fold_matrix(nseg, SEG_WIDTH))


def linear_operands(width: int) -> tuple:
    """The constant operands of a digest at this padded width, built
    once per process and width and kept on the device (uncommitted:
    they follow any batch): the segment matrix, and for rows wider
    than SEG_WIDTH the fold matrix.  They are operands, not literals
    of the program — a 2 MiB constant in the HLO would be hashed,
    stored and loaded with every executable of the compile cache."""
    c = min(width, SEG_WIDTH)
    if width == c:
        return (_segment_operand(c),)
    return (_segment_operand(c), _fold_operand(width // c))


def _padded_init_bits(width: int) -> np.ndarray:
    """(32,) 0/1: the crc register after ``width`` zero bytes from
    _CRC_INIT — the constant term Z^W(init) of the affine map."""
    return _bits(np.uint32(zlib.crc32(bytes(width)) ^ _CRC_INIT), 32)


def digest_operands(lengths, width: int):
    """The per-row epilogue operands for a padded batch of ``width``:
    (mats (S, 32) uint32 — Z^-(W-L) columns per row; invp (S, 4)
    uint8 — alpha^-t per GF lane).  Submitters build these host-side
    from the lengths; they ride the engine's aux channel in lockstep
    with the data rows."""
    lengths = np.asarray(lengths, dtype=np.int64)
    pads = width - lengths
    if width <= _TABLE_WIDTH_MAX:
        mats = _unpad_table(width)[pads]
    else:
        # wide rows: only the distinct pad counts this batch needs,
        # each O(log k) via the memoized square-and-multiply
        lut = {int(k): _zero_pow_cols(-int(k)) for k in np.unique(pads)}
        mats = np.stack([lut[int(k)] for k in pads])
    steps = width // 4
    pows = _gf_inv_pows(steps)
    lanes = np.arange(4, dtype=np.int64)[None, :]
    # lane l holds ceil((L - l) / 4) real bytes; the rest of its
    # width/4 Horner steps consumed padding zeros
    n_real = np.clip(-(-(lengths[:, None] - lanes) // 4), 0, steps)
    invp = pows[(steps - n_real).astype(np.int64)]
    return mats, invp.astype(np.uint8)


@functools.lru_cache(maxsize=32)
def whole_row_operands(rows: int, width: int) -> tuple:
    """``digest_operands`` of a batch whose every row is ``width`` real
    bytes — nothing to strip: identity columns, lane multipliers of
    one — as device arrays, built once per process and shape and kept
    beside ``linear_operands(width)``: operands of the same jitted
    entry at the same shapes and dtypes, not literals of a program.
    A request of whole rows then uploads its data and nothing else."""
    import jax.numpy as jnp
    mats, invp = digest_operands(np.full(rows, width, dtype=np.int64),
                                 width)
    return (jnp.asarray(mats.astype(np.uint32)),
            jnp.asarray(invp.astype(np.uint8)))


def row_width(max_len: int) -> int:
    """Shared pow-2 padded width for a digest batch (>= MIN_WIDTH so
    the 4 GF lanes always divide it): concurrent scrubs bucket
    their rows to the same widths, so different PGs coalesce."""
    if max_len <= MIN_WIDTH:
        return MIN_WIDTH
    return 1 << (int(max_len) - 1).bit_length()


# ---------------------------------------------------------------------------
# the jitted kernel
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _jit_digest():
    """Build (and cache) the jitted fixed-width digest entry point.
    jax imports live inside so the oracle path never pulls it in."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.gf.tables import mul_table

    mt_host = mul_table()

    def product_mod2(bits, matrix):
        # 0/1 int8 operands, exact int32 accumulator (any K is safe),
        # ``& 1`` is the xor — gf_kernel._xla_tile's product
        acc = jax.lax.dot_general(
            bits.astype(jnp.int8), matrix,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc & 1

    @functools.partial(jax.jit, static_argnames=("w",))
    def digest(data, mats, invp, lin, *, w):
        mt = jnp.asarray(mt_host)
        s = data.shape[0]
        u32 = jnp.uint32
        c = lin[0].shape[0] // 8
        # the row's bits, plane b of every segment side by side:
        # column b * c + p, the segment matrix's row order
        seg = data.reshape(s * (w // c), c)
        planes = jnp.concatenate(
            [(seg >> jnp.uint8(b)) & jnp.uint8(1) for b in range(8)],
            axis=1)
        bits = product_mod2(planes, lin[0])            # (S * W/C, 64)
        if w > c:
            bits = product_mod2(bits.reshape(s, 64 * (w // c)), lin[1])
        crc_bits = (bits[:, :32] ^ jnp.asarray(
            _padded_init_bits(w), dtype=jnp.int32)).astype(u32)
        # epilogue: strip the zero padding's effect — Z^-(W-L) per row
        # (gathered matrix columns), alpha^-t per GF lane
        true = jax.lax.reduce(mats * crc_bits, np.uint32(0),
                              jax.lax.bitwise_xor, (1,))
        crc_final = true ^ u32(_CRC_INIT)
        bitw = jnp.arange(8, dtype=jnp.int32)
        g = jnp.sum(bits[:, 32:].reshape(s, 4, 8) << bitw, axis=-1,
                    dtype=jnp.int32)
        lanes = mt[g, invp.astype(jnp.int32)].astype(u32)
        gf = (lanes[:, 0] | (lanes[:, 1] << u32(8))
              | (lanes[:, 2] << u32(16)) | (lanes[:, 3] << u32(24)))
        return jnp.stack([crc_final, gf], axis=1)

    return digest


def digest_jit_entries() -> int:
    """Compile-cache entry count for the digest entry point (the
    telemetry retrace counter differences this around each call)."""
    try:
        return _jit_digest()._cache_size()
    except Exception:
        return 0


def _digest_batched(kname: str, data, mats=None, invp=None):
    """``mats`` / ``invp`` None: every row is whole, and the epilogue
    operands are the resident ``whole_row_operands`` of the shape."""
    import jax.numpy as jnp
    data = jnp.asarray(np.asarray(data, dtype=np.uint8))
    s, w = (int(n) for n in data.shape)
    bytes_in = s * w
    if mats is None:
        mats, invp = whole_row_operands(s, w)
    else:
        mats = jnp.asarray(np.asarray(mats, dtype=np.uint32))
        invp = jnp.asarray(np.asarray(invp, dtype=np.uint8))
        bytes_in += mats.nbytes + invp.nbytes
    # misses by signature: the engine's own probe of
    # digest_jit_entries() brackets this same call
    return telemetry.timed_kernel(
        kname,
        lambda: _jit_digest()(data, mats, invp, linear_operands(w), w=w),
        batch=s, bytes_in=bytes_in, bytes_out=s * 8,
        signature=(kname, s, w))


def scrub_digest_batched(data, mats, invp):
    """One batched device digest call: data (S, W) uint8 zero-padded
    rows, mats/invp from ``digest_operands``.  Returns (S, 2) uint32 —
    col 0 crc32 (== shard_crc of the unpadded row), col 1 the packed
    GF Horner digest — bit-exact vs ``scrub_digest_ref``."""
    return _digest_batched("scrub_digest", data, mats, invp)


def bluestore_digest_batched(data, mats=None, invp=None):
    """The objectstore flavor of the batched digest: identical math
    through the SAME jitted entry point (equal-width store and scrub
    batches share one compiled executable — one checksum definition for
    both), but accounted under its own telemetry family so the
    ``ceph_kernel_bluestore_data_*`` histograms track the write/read
    hot path separately from background scrub.  Without ``mats`` /
    ``invp`` every row is ``data.shape[1]`` real bytes (BlueStore's
    whole blocks) and only ``data`` is uploaded."""
    return _digest_batched("bluestore_data", data, mats, invp)
