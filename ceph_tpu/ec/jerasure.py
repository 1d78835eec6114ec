"""jerasure-family techniques.

The reference wraps the jerasure library (src/erasure-code/jerasure/; the SIMD
kernels live in empty submodules, so the math here is reimplemented from the
published constructions — Plank's jerasure 2.0 — not translated code).  Each
technique is a generator-matrix recipe; encode/decode lower to the shared
batched MXU kernel via the ErasureCode base.

Techniques (ErasureCodeJerasure.h:82-253):
  reed_sol_van    extended-Vandermonde distribution matrix (always MDS)
  reed_sol_r6_op  RAID-6: P = sum d_j, Q = sum 2^j d_j (m forced to 2)
  cauchy_orig     a[i][j] = 1/(i xor (m+j))
  cauchy_good     cauchy_orig normalized to minimize bitmatrix ones
                  (jerasure improve_coding_matrix semantics)

The bitmatrix schedule techniques (liberation, blaum_roth, liber8tion) are
registered in ceph_tpu.ec.bitmatrix.
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.gf.tables import gf_inv, gf_mul, gf_pow, mul_table

from .base import ErasureCode
from .registry import register


# ---------------------------------------------------------------------------
# matrix constructions
# ---------------------------------------------------------------------------

def extended_vandermonde_matrix(rows: int, cols: int) -> np.ndarray:
    """Extended Vandermonde: row 0 = e_0, row i = [1, i, i^2, ...],
    last row = e_{cols-1}.  Always MDS for rows <= 257 over GF(2^8)."""
    if rows > 257:
        raise ValueError(f"rows={rows} exceeds GF(2^8) extended-Vandermonde bound")
    vdm = np.zeros((rows, cols), dtype=np.uint8)
    vdm[0, 0] = 1
    for i in range(1, rows - 1):
        p = 1
        for j in range(cols):
            vdm[i, j] = p
            p = gf_mul(p, i)
    vdm[rows - 1, cols - 1] = 1
    return vdm


def big_vandermonde_distribution_matrix(rows: int, cols: int) -> np.ndarray:
    """jerasure's reed_sol_big_vandermonde_distribution_matrix, step for
    step: (1) column operations make the top cols x cols block of the
    extended Vandermonde the identity; (2) each column is scaled so that row
    `cols`, the first coding row, is all ones; (3) each later coding row is
    scaled so that its first element is one.  reed_sol.c swaps rows at a
    zero pivot; none occurs, since the leading minors are Vandermonde on the
    distinct points 0..i (`gf_inv` would raise on one)."""
    mul = mul_table()
    vdm = extended_vandermonde_matrix(rows, cols)
    for i in range(1, cols):
        if vdm[i, i] != 1:
            vdm[:, i] = mul[gf_inv(int(vdm[i, i]))][vdm[:, i]]
        for j in range(cols):
            f = int(vdm[i, j])
            if j != i and f:
                vdm[:, j] ^= mul[f][vdm[:, i]]
    for j in range(cols):
        if vdm[cols, j] != 1:
            vdm[cols:, j] = mul[gf_inv(int(vdm[cols, j]))][vdm[cols:, j]]
    for i in range(cols + 1, rows):
        if vdm[i, 0] != 1:
            vdm[i] = mul[gf_inv(int(vdm[i, 0]))][vdm[i]]
    return vdm


def reed_sol_r6_matrix(k: int) -> np.ndarray:
    """RAID-6 generator: parity row of ones, Q row of 2^j (jerasure
    reed_sol_r6_coding_matrix semantics)."""
    gen = np.zeros((k + 2, k), dtype=np.uint8)
    gen[:k, :k] = np.eye(k, dtype=np.uint8)
    gen[k, :] = 1
    for j in range(k):
        gen[k + 1, j] = gf_pow(2, j)
    return gen


def cauchy_original_matrix(k: int, m: int) -> np.ndarray:
    """jerasure cauchy_original_coding_matrix: a[i][j] = 1/(i xor (m+j))."""
    if k + m > 256:
        raise ValueError(f"k+m={k + m} exceeds GF(2^8) field size")
    gen = np.zeros((k + m, k), dtype=np.uint8)
    gen[:k, :k] = np.eye(k, dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            gen[k + i, j] = gf_inv(i ^ (m + j))
    return gen


def _bitmatrix_ones(e: int) -> int:
    """Ones in the 8x8 GF(2) bitmatrix of multiply-by-e: the XOR cost the
    improvement heuristic minimizes (jerasure cauchy.c)."""
    return sum(bin(gf_mul(e, 1 << b)).count("1") for b in range(8))


def cauchy_good_matrix(k: int, m: int) -> np.ndarray:
    """cauchy_orig normalized (jerasure improve_coding_matrix semantics):
    scale columns so coding row 0 is all ones, then scale each later row by
    the row element whose division minimizes total bitmatrix ones."""
    gen = cauchy_original_matrix(k, m)
    coding = gen[k:]
    for j in range(k):
        e = int(coding[0, j])
        if e != 1:
            einv = gf_inv(e)
            for i in range(m):
                coding[i, j] = gf_mul(int(coding[i, j]), einv)
    for i in range(1, m):
        row = [int(v) for v in coding[i]]
        best_row, best_cost = row, sum(_bitmatrix_ones(v) for v in row)
        for div in row:
            if div in (0, 1):
                continue
            dinv = gf_inv(div)
            cand = [gf_mul(v, dinv) for v in row]
            cost = sum(_bitmatrix_ones(v) for v in cand)
            if cost < best_cost:
                best_row, best_cost = cand, cost
        coding[i] = best_row
    return gen


# ---------------------------------------------------------------------------
# plugin classes
# ---------------------------------------------------------------------------

class ErasureCodeJerasure(ErasureCode):
    """Base for jerasure techniques; dispatches on profile technique=
    (ErasureCodeJerasure.cc factory behaviour).  Defaults k=7 m=3 w=8."""

    TECHNIQUE = ""

    def parse(self, profile):
        super().parse(profile)
        self.technique = profile.get("technique", self.TECHNIQUE)
        w = self.to_int("w", profile, 8)
        if w != 8:
            raise ValueError(
                f"w={w}: only w=8 is supported (GF(2^8) device kernels); the "
                f"reference default is also 8")
        self.w = w


class ReedSolomonVandermonde(ErasureCodeJerasure):
    TECHNIQUE = "reed_sol_van"

    def _build_generator(self):
        return big_vandermonde_distribution_matrix(self.k + self.m, self.k)


class ReedSolomonR6(ErasureCodeJerasure):
    TECHNIQUE = "reed_sol_r6_op"

    def parse(self, profile):
        super().parse(profile)
        self.m = 2  # RAID-6: m is forced to 2 (ErasureCodeJerasure.h:112)

    def _build_generator(self):
        return reed_sol_r6_matrix(self.k)


class CauchyOrig(ErasureCodeJerasure):
    TECHNIQUE = "cauchy_orig"

    def _build_generator(self):
        return cauchy_original_matrix(self.k, self.m)


class CauchyGood(ErasureCodeJerasure):
    TECHNIQUE = "cauchy_good"

    def _build_generator(self):
        return cauchy_good_matrix(self.k, self.m)


_TECHNIQUES = {
    "reed_sol_van": ReedSolomonVandermonde,
    "reed_sol_r6_op": ReedSolomonR6,
    "cauchy_orig": CauchyOrig,
    "cauchy_good": CauchyGood,
}


def _factory(profile):
    technique = profile.get("technique", "reed_sol_van")
    try:
        from . import bitmatrix
        cls = {**_TECHNIQUES, **bitmatrix.TECHNIQUES}[technique]
    except KeyError:
        raise ValueError(
            f"jerasure technique {technique!r} unknown; known: "
            f"{sorted(_TECHNIQUES)} + bitmatrix techniques")
    return cls()


register("jerasure", _factory)
