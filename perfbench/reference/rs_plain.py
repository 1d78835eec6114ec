"""Plain reference for the erasure-coded cells: the shards that jerasure
`reed_sol_van` (w = 8) makes of an object, and the block checksums a
BlueStore OSD keeps of each.

Written from the published construction (jerasure's reed_sol.c:
reed_sol_extended_vandermonde_matrix and
reed_sol_big_vandermonde_distribution_matrix; galois.c for GF(2^8) over
x^8 + x^4 + x^3 + x^2 + 1) and ECUtil's stripe layout.  It imports
nothing of the program and takes no table the program has made: the
field's tables and the coding matrix are computed here.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

_POLY = 0x11D


@functools.lru_cache(maxsize=None)
def _tables() -> tuple[np.ndarray, np.ndarray]:
    """(exp, log) of GF(2^8) on the generator 2."""
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:] = exp[:255]
    return exp, log


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _tables()
    return int(exp[log[a] + log[b]])


def gf_inv(a: int) -> int:
    exp, log = _tables()
    return int(exp[255 - log[a]])


@functools.lru_cache(maxsize=None)
def _mul_table() -> np.ndarray:
    """(256, 256) uint8: the product of every pair of bytes."""
    exp, log = _tables()
    t = exp[log[:, None] + log[None, :]].astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    return t


def _extended_vandermonde(rows: int, cols: int) -> list[list[int]]:
    """Row 0 is e_0, the last row e_(cols-1), row i between them the
    powers of i."""
    v = [[0] * cols for _ in range(rows)]
    v[0][0] = 1
    v[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        x = 1
        for j in range(cols):
            v[i][j] = x
            x = gf_mul(x, i)
    return v


@functools.lru_cache(maxsize=None)
def coding_matrix(k: int, m: int) -> np.ndarray:
    """The m coding rows of reed_sol_vandermonde_coding_matrix(k, m, 8):
    the extended Vandermonde matrix brought by column operations to an
    identity on its first k rows, the first coding row then scaled to
    ones by columns and the first column of the others to one by rows."""
    rows, cols = k + m, k
    d = _extended_vandermonde(rows, cols)
    for i in range(1, cols):
        j = next(j for j in range(i, rows) if d[j][i] != 0)
        if j != i:
            d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            inv = gf_inv(d[i][i])
            for r in range(rows):
                d[r][i] = gf_mul(inv, d[r][i])
        for j in range(cols):
            f = d[i][j]
            if j != i and f != 0:
                for r in range(rows):
                    d[r][j] ^= gf_mul(f, d[r][i])
    for j in range(cols):
        f = d[cols][j]
        if f != 1:
            inv = gf_inv(f)
            for r in range(cols, rows):
                d[r][j] = gf_mul(inv, d[r][j])
    for r in range(cols + 1, rows):
        f = d[r][0]
        if f != 1:
            inv = gf_inv(f)
            d[r] = [gf_mul(inv, x) for x in d[r]]
    return np.array(d[cols:], dtype=np.uint8)


def shards_of(payload: bytes, k: int, m: int, stripe_unit: int) -> list[bytes]:
    """The k + m shards of one object written whole: the object, padded
    with zeros to whole stripes of k * stripe_unit bytes, is cut into
    stripes; shard s holds chunk s of every stripe, in order; parity
    chunk j of a stripe is the coding row j applied to its k data
    chunks, byte by byte."""
    width = k * stripe_unit
    stripes = max(1, -(-len(payload) // width))
    padded = np.zeros(stripes * width, dtype=np.uint8)
    padded[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = padded.reshape(stripes, k, stripe_unit).transpose(1, 0, 2)
    data = data.reshape(k, stripes * stripe_unit)
    mul, rows = _mul_table(), coding_matrix(k, m)
    parity = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for j in range(m):
        for i in range(k):
            parity[j] ^= mul[rows[j, i]][data[i]]
    return ([data[i].tobytes() for i in range(k)]
            + [parity[j].tobytes() for j in range(m)])


def block_csums(shard: bytes, block: int) -> list[int]:
    """crc32 (zlib's) of each `block` bytes of a shard as a BlueStore
    OSD stores it, the last block padded with zeros."""
    out = []
    for off in range(0, len(shard), block):
        b = shard[off:off + block]
        out.append(zlib.crc32(b + bytes(block - len(b))))
    return out
