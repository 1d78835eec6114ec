"""Plain reference for the Clay cells: the shards that a Clay code
(k, m, d = k + m - 1) makes of an object, stripe by stripe, and the
object rebuilt from any k of them.

Written from the published construction (M. Vajha et al., "Clay Codes:
Moulding MDS Codes to Yield an MSR Code", FAST'18, sections 3-4) and
ECUtil's stripe layout.  It imports nothing of the program and takes no
table the program has made: the field's tables come from rs_plain.py.

The construction.  n = k + m nodes lie on a q x t grid, q = d - k + 1 = m
and t = n / q; node i is (x, y) = (i mod q, i div q).  A chunk is
alpha = q^t sub-chunks, sub-chunk z a vector (z_0, ..., z_{t-1}) of base-q
digits, z_0 the most significant in the chunk's order.  Every plane z
holds an uncoupled codeword U(., z) of an [n, k] MDS code; the stored
(coupled) sub-chunks C pair node (x, y) at plane z with node (z_y, y) at
plane z with z_y replaced by x, where the two differ:

    C(x, y, z)     = U(x, y, z)     + g U(z_y, y, z')
    C(z_y, y, z')  = g U(x, y, z)   + U(z_y, y, z')        g = 2

and C = U where x = z_y.  Decoding walks the planes by intersection
score, the number of grid rows whose erased node sits at the plane's
digit: every surviving node's U on a plane follows from its own C and
its partner's (C of a surviving partner, or U of an erased partner on a
plane of lower score); the plane's MDS code then gives the erased nodes'
U, and the pairs their C.  Encoding is decoding with the m parities
erased.

Where this departs from the published description, or from Ceph's
ErasureCodeClay:
  * the plane code is the Cauchy matrix with rows inv(i xor j), i >= k
    (systematic), where Ceph's default scalar_mds is jerasure
    reed_sol_van;
  * the coupling is the paper's pairwise transform with g = 2, where
    Ceph builds it as a 2 x 2 pairwise-transform (PFT) sub-code;
  * d = k + m - 1 only, and k + m a multiple of m (no shortened nodes);
  * the object is cut into stripes of k stripe units (ECUtil), each
    stripe coded on its own: a stripe's chunk is the stripe unit, alpha
    sub-chunks of stripe_unit / alpha bytes side by side.
The stripes of an object are a batch axis here: every loop below runs
over planes and nodes, and each stripe's bytes meet only its own.
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

from . import rs_plain, rs_plain_decode

G = 2


def geometry(k: int, m: int) -> tuple[int, int, int]:
    """(q, t, alpha)."""
    n = k + m
    if n % m:
        raise ValueError(f"k + m = {n} is not a multiple of m = {m}")
    q, t = m, n // m
    return q, t, q ** t


@functools.lru_cache(maxsize=None)
def generator(k: int, m: int) -> tuple[tuple[int, ...], ...]:
    """The (k + m) x k generator of the plane code: identity rows, then
    the Cauchy rows inv(i xor j)."""
    rows = [tuple(int(i == j) for j in range(k)) for i in range(k)]
    rows += [tuple(rs_plain.gf_inv(i ^ j) for j in range(k))
             for i in range(k, k + m)]
    return tuple(rows)


def _scale(c: int, v: np.ndarray) -> np.ndarray:
    return rs_plain._mul_table()[c][v]


def _planes(q: int, t: int) -> list[tuple[int, ...]]:
    return list(itertools.product(range(q), repeat=t))


def decode_stripes(have: dict[int, np.ndarray], k: int, m: int
                   ) -> dict[int, np.ndarray]:
    """``have``: node -> (S, stripe_unit) chunks of S stripes, k of the
    n nodes or more.  Returns every node's chunks."""
    q, t, alpha = geometry(k, m)
    n = k + m
    avail = sorted(have)[:k]
    if len(avail) < k:
        raise ValueError(f"{len(avail)} chunks in hand, {k} needed")
    erased = [i for i in range(n) if i not in avail]
    first = next(iter(have.values()))
    s, su = first.shape
    sb = su // alpha
    planes = _planes(q, t)
    index = {z: i for i, z in enumerate(planes)}

    def sub(chunk, z):
        return chunk[:, index[z] * sb:(index[z] + 1) * sb]

    C = {(i, z): sub(have[i], z) for i in avail for z in planes}
    U: dict = {}
    gen = generator(k, m)
    inverse = rs_plain_decode.invert([list(gen[i]) for i in avail])
    # rows that give an erased node's U from the k nodes in hand
    rebuild = {e: [_dot_row(gen[e], inverse, j, k) for j in range(k)]
               for e in erased}
    det_inv = rs_plain.gf_inv(1 ^ rs_plain.gf_mul(G, G))

    def score(z):
        return sum((z[y] + q * y) in erased for y in range(t))

    for z in sorted(planes, key=score):
        for i in avail:
            x, y = i % q, i // q
            if z[y] == x:
                U[(i, z)] = C[(i, z)]
                continue
            partner = z[y] + q * y
            zp = z[:y] + (x,) + z[y + 1:]
            if partner in erased:
                U[(i, z)] = C[(i, z)] ^ _scale(G, U[(partner, zp)])
            else:
                # [C1, C2] = [[1, g], [g, 1]] [U1, U2]: invert the 2 x 2
                U[(i, z)] = _scale(det_inv, C[(i, z)]
                                   ^ _scale(G, C[(partner, zp)]))
        for e in erased:
            acc = np.zeros((s, sb), dtype=np.uint8)
            for j, i in enumerate(avail):
                if rebuild[e][j]:
                    acc ^= _scale(rebuild[e][j], U[(i, z)])
            U[(e, z)] = acc
    out = {i: np.asarray(have[i]) for i in avail}
    for e in erased:
        parts = []
        for z in planes:
            x, y = e % q, e // q
            if z[y] == x:
                parts.append(U[(e, z)])
            else:
                partner = z[y] + q * y
                zp = z[:y] + (x,) + z[y + 1:]
                parts.append(U[(e, z)] ^ _scale(G, U[(partner, zp)]))
        out[e] = np.concatenate(parts, axis=1)
    return out


def _dot_row(row, inverse, j: int, k: int) -> int:
    """Entry j of ``row`` times ``inverse`` over GF(2^8)."""
    acc = 0
    for c in range(k):
        if row[c] and inverse[c][j]:
            acc ^= rs_plain.gf_mul(row[c], inverse[c][j])
    return acc


def _stripes_of(payload: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """(k, S, stripe_unit): chunk s of every stripe, the object padded
    with zeros to whole stripes."""
    width = k * stripe_unit
    stripes = max(1, -(-len(payload) // width))
    padded = np.zeros(stripes * width, dtype=np.uint8)
    padded[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    return padded.reshape(stripes, k, stripe_unit).transpose(1, 0, 2)


def shards_of(payload: bytes, k: int, m: int,
              stripe_unit: int) -> list[bytes]:
    """The k + m shards of one object written whole: shard s holds chunk
    s of every stripe, in order; the parity chunks of a stripe are the
    Clay encode of its k data chunks."""
    data = _stripes_of(payload, k, stripe_unit)
    every = decode_stripes({i: data[i] for i in range(k)}, k, m)
    return [np.ascontiguousarray(every[i]).tobytes() for i in range(k + m)]


def object_of(have: dict[int, bytes], k: int, m: int, stripe_unit: int,
              size: int) -> bytes:
    """The object of ``size`` bytes whose shards ``have`` holds k or more
    of (``shards_of``'s layout undone)."""
    chunks = {i: np.frombuffer(b, dtype=np.uint8).reshape(-1, stripe_unit)
              for i, b in have.items()}
    every = decode_stripes(chunks, k, m)
    cols = np.stack([every[i] for i in range(k)], axis=1)  # (S, k, su)
    return cols.reshape(-1).tobytes()[:size]
