"""Plain reference for "any k shards suffice": an object rebuilt from k
of the k + m shards that jerasure `reed_sol_van` (w = 8) made of it.

Gaussian elimination over GF(2^8) on the rows of the generator — the
identity over `rs_plain.coding_matrix` — that belong to the shards in
hand; the inverse applied to those shards gives the k data shards, and
the stripe layout undone gives the object.  Like rs_plain.py it imports
nothing of the program and takes no table the program has made.
"""

from __future__ import annotations

import numpy as np

from . import rs_plain


def generator_rows(k: int, m: int, shards) -> list[list[int]]:
    """Row s of the (k + m) x k generator for each s in `shards`: e_s for
    a data shard, coding row s - k for a parity shard."""
    coding = rs_plain.coding_matrix(k, m)
    return [[int(j == s) for j in range(k)] if s < k
            else [int(x) for x in coding[s - k]] for s in shards]


def invert(rows: list[list[int]]) -> list[list[int]]:
    """The inverse of a square matrix over GF(2^8), by Gauss-Jordan
    elimination with row swaps.  Raises ValueError on a singular one
    (no k rows of an MDS code's generator are)."""
    n = len(rows)
    a = [list(r) + [int(i == j) for j in range(n)]
         for i, r in enumerate(rows)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col]), None)
        if pivot is None:
            raise ValueError("the chosen rows are singular")
        a[col], a[pivot] = a[pivot], a[col]
        inv = rs_plain.gf_inv(a[col][col])
        a[col] = [rs_plain.gf_mul(inv, x) for x in a[col]]
        for r in range(n):
            f = a[r][col]
            if r != col and f:
                a[r] = [x ^ rs_plain.gf_mul(f, y)
                        for x, y in zip(a[r], a[col])]
    return [row[n:] for row in a]


def data_shards_of(have: dict[int, bytes], k: int, m: int) -> list[bytes]:
    """The k data shards, from any k (the k lowest-numbered of those
    given) of the k + m shards.  Every shard has the same length."""
    chosen = sorted(have)[:k]
    if len(chosen) < k:
        raise ValueError(f"{len(chosen)} shards in hand, {k} needed")
    inverse = invert(generator_rows(k, m, chosen))
    got = [np.frombuffer(have[s], dtype=np.uint8) for s in chosen]
    mul = rs_plain._mul_table()
    out = []
    for d in range(k):
        if d in have:               # in hand: nothing to rebuild
            out.append(bytes(have[d]))
            continue
        acc = np.zeros(len(got[0]), dtype=np.uint8)
        for j, shard in enumerate(got):
            if inverse[d][j]:
                acc ^= mul[inverse[d][j]][shard]
        out.append(acc.tobytes())
    return out


def object_of(have: dict[int, bytes], k: int, m: int, stripe_unit: int,
              size: int) -> bytes:
    """The object of `size` bytes whose shards `have` holds k or more
    of: `rs_plain.shards_of`'s layout undone (shard s is chunk s of every
    stripe, in order; the last stripe was padded with zeros)."""
    data = data_shards_of(have, k, m)
    stripes = len(data[0]) // stripe_unit
    cols = np.stack([np.frombuffer(d, dtype=np.uint8)
                     .reshape(stripes, stripe_unit) for d in data], axis=1)
    return cols.reshape(-1).tobytes()[:size]
