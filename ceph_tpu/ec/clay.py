"""CLAY — coupled-layer MSR regenerating code
(src/erasure-code/clay/ErasureCodeClay.cc analog; the reason the plugin
interface carries sub-chunks, ErasureCodeInterface.h:259).

Construction (Clay codes, FAST'18): n = k + m nodes on a q x t grid
(q = m, t = n/q), each chunk split into alpha = q^t sub-chunks indexed
by z in Z_q^t.  A virtual UNCOUPLED code U is MDS per z-plane (an [n,k]
RS codeword across the nodes); the physical chunks C couple sub-chunk
PAIRS across planes with an invertible 2x2 GF(2^8) transform:

    pair of (x, y, z) with x != z_y  is  (z_y, y, z(y->x))
    C1 = U1 + g*U2        C2 = g*U1 + U2        (g = 2; 1+g^2 != 0)
    x == z_y: C = U (fixed points)

Encode treats the m parities as erasures and runs the generic decoder.
Decode walks planes by INTERSECTION SCORE s(z) = |{y : (z_y, y) is
erased}|: in score order, every surviving node's U is computable (its
partner is either surviving, or an erased node in a lower-score plane
already recovered), the plane's RS codeword is then decoded for the
erased nodes, and finally erased C values come back through the pair
transform.

Single-node repair is the headline: only the q^(t-1) planes S =
{z : z_{y0} = x0} are read from each of the d = n-1 helpers — alpha/q
sub-chunks instead of whole chunks, the MSR repair-bandwidth optimum.
On each S-plane the y != y0 rows uncouple internally (their partners
stay inside S), the y0 row's q unknowns fall to the plane's m = q RS
parity equations, and the pair algebra then yields the failed node's
off-S sub-chunks from helper row y0's coupled values.  All transforms
are elementwise table lookups over the sub-chunk byte axis — batched,
vectorized compute, no per-byte loops.

Layout and devices.  A pool lays an object out as Ceph does
(ECUtil::encode): stripes of k stripe units, each stripe coded on its
own, its chunk alpha contiguous sub-chunks of su / alpha bytes — one
stripe is what encode() of its k * su bytes gives.  encode_chunks /
decode_chunks take (S, k, su) and submit_chunks / submit_decode_chunks
are their dispatch-engine forms; on the device a pattern's whole layered
decode is one dense bit matrix (``ops/clay_kernel.py``), built from the
host layered code below, which stays the oracle (``runtime`` cpu) and
the engines' host stand-in.  The single-node repair stays on the host.
"""

from __future__ import annotations

import numpy as np

from ceph_tpu.gf.matrix import gen_cauchy1_matrix
from ceph_tpu.gf.tables import gf_inv, gf_mul, mul_table

from .base import ErasureCode
from .interface import ErasureCodeProfile
from .registry import register

GAMMA = 2


def _mul(coef: int, arr: np.ndarray) -> np.ndarray:
    """scalar * vector over GF(2^8), one table-row gather."""
    return mul_table()[coef][arr]


class ErasureCodeClay(ErasureCode):
    #: Ceph's layout: the object is cut into stripes of k stripe units
    #: (ECUtil), and each stripe's chunk is alpha contiguous sub-chunks of
    #: su / alpha bytes, so one stripe is what encode() of its k * su
    #: bytes gives.  The stripe unit is a multiple of alpha
    #: (get_alignment() // k).
    supports_rmw_striping = True

    _PROFILE_KEYS = ErasureCode._PROFILE_KEYS + ("d",)

    def __init__(self):
        super().__init__()
        self.q = 0
        self.t = 0
        self.d = 0

    def _default_k(self) -> int:
        return 4

    def _default_m(self) -> int:
        return 2

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        n = self.k + self.m
        if n % self.m != 0:
            raise ValueError(
                f"clay requires m | (k+m); got k={self.k} m={self.m} "
                f"(the reference shortens instead; not implemented)")
        # q = d - k + 1 is m only at Ceph's default d
        self.d = self.to_int("d", profile, n - 1)
        if self.d != n - 1:
            raise ValueError(f"clay d={self.d}: only d = k + m - 1 = "
                             f"{n - 1} is implemented")
        self.q = self.m
        self.t = n // self.q

    def _build_generator(self) -> np.ndarray:
        return gen_cauchy1_matrix(self.k, self.m)

    # -- geometry -------------------------------------------------------------

    def get_sub_chunk_count(self) -> int:
        return self.q ** self.t

    def get_alignment(self) -> int:
        return self.k * self.get_sub_chunk_count()

    def node_xy(self, i: int) -> tuple[int, int]:
        return i % self.q, i // self.q

    def node_id(self, x: int, y: int) -> int:
        return y * self.q + x

    def _planes(self):
        """All z vectors (alpha of them), as tuples."""
        import itertools
        return list(itertools.product(range(self.q), repeat=self.t))

    @staticmethod
    def _zset(z: tuple, y: int, x: int) -> tuple:
        return z[:y] + (x,) + z[y + 1:]

    # -- pair transforms (vectorized over the sub-chunk byte axis).
    # the forward coupling C1 = U1 ^ g*U2 lives inline in _decode_planes
    # and repair; only the inverse needs a helper.

    @staticmethod
    def _uncouple(c1, c2):
        inv = gf_inv(1 ^ gf_mul(GAMMA, GAMMA))
        u1 = _mul(inv, c1 ^ _mul(GAMMA, c2))
        u2 = _mul(inv, _mul(GAMMA, c1) ^ c2)
        return u1, u2

    # -- the generic layered decoder ------------------------------------------

    def _decode_planes(self, C: dict, erased: list[int],
                       host: bool = False):
        """C: {(node, z): uint8 array} for all surviving nodes and all
        planes.  Returns (U, C) completed for every node and plane
        (ErasureCodeClay recover: intersection-score order); ``host``
        keeps the plane code on the numpy oracle."""
        n = self.k + self.m
        planes = self._planes()
        er = set(erased)
        surv = [i for i in range(n) if i not in er]
        if len(surv) < self.k:
            raise IOError(f"clay cannot decode {sorted(er)}")
        U: dict = {}

        def score(z):
            return sum(1 for y in range(self.t)
                       if self.node_id(z[y], y) in er)

        for z in sorted(planes, key=score):
            # uncouple every surviving node on this plane
            for i in surv:
                x, y = self.node_xy(i)
                if z[y] == x:
                    U[(i, z)] = C[(i, z)]
                    continue
                partner = self.node_id(z[y], y)
                zp = self._zset(z, y, x)
                if partner in er:
                    # partner plane has lower score: its U is recovered
                    U[(i, z)] = C[(i, z)] ^ _mul(GAMMA, U[(partner, zp)])
                else:
                    u1, _u2 = self._uncouple(C[(i, z)], C[(partner, zp)])
                    U[(i, z)] = u1
            # plane RS decode for the erased nodes
            chosen = surv[:self.k]
            arr = np.stack([U[(i, z)] for i in chosen])
            rmat = self._recovery(tuple(chosen), tuple(sorted(er)))
            rebuilt = self._apply(rmat, arr, host)
            for idx, i in enumerate(sorted(er)):
                U[(i, z)] = rebuilt[idx]
        # couple the erased nodes' C back from U
        for z in planes:
            for i in sorted(er):
                x, y = self.node_xy(i)
                if z[y] == x:
                    C[(i, z)] = U[(i, z)]
                else:
                    partner = self.node_id(z[y], y)
                    zp = self._zset(z, y, x)
                    C[(i, z)] = U[(i, z)] ^ _mul(GAMMA, U[(partner, zp)])
        return U, C

    def _apply(self, mat: np.ndarray, arr: np.ndarray,
               host: bool = False) -> np.ndarray:
        """(r, c) GF matrix times (c, B) rows, on the selected runtime
        (``host``: the numpy oracle whatever the runtime)."""
        if host or self.runtime == "cpu":
            from ceph_tpu.ops.gf_kernel import ec_encode_ref
            return ec_encode_ref(mat, arr[None])[0]
        from ceph_tpu.ops.gf_kernel import ec_encode_jax
        return np.asarray(ec_encode_jax(mat, arr[None]))[0]

    # -- chunk <-> sub-chunk plumbing -----------------------------------------

    def _split(self, chunk: np.ndarray) -> dict:
        alpha = self.get_sub_chunk_count()
        sub = len(chunk) // alpha
        planes = self._planes()
        return {z: chunk[i * sub:(i + 1) * sub]
                for i, z in enumerate(planes)}

    def _join(self, per_plane: dict) -> bytes:
        return b"".join(per_plane[z].tobytes() for z in self._planes())

    # -- striped encode / decode: (S, k, su) chunks of S stripes --------------

    #: a pattern's dense bit matrix is (k * alpha * 8, t * alpha * 8):
    #: 4 MiB at k = 8, alpha = 64 and two chunks wanted; a table
    #: generation holds at most this many before it retires
    pattern_table_cap = 64

    def _erased_of(self, chosen) -> tuple:
        n = self.k + self.m
        return tuple(j for j in range(n) if j not in set(chosen))

    def _host_layered(self, chosen, chunks) -> np.ndarray:
        """The layered decode on the host, every stripe at once (one
        erasure pattern): (S, k, su) chunks of the nodes ``chosen`` ->
        (S, m, su) chunks of the others, ascending.  A sub-chunk of the
        decoder is the S stripes' sub-chunk z side by side."""
        data = np.asarray(chunks, dtype=np.uint8)
        s, _k, su = data.shape
        alpha = self.get_sub_chunk_count()
        sb = su // alpha
        by_plane = data.reshape(s, self.k, alpha, sb)
        planes = self._planes()
        C = {(node, z): np.ascontiguousarray(by_plane[:, c, i]).reshape(-1)
             for c, node in enumerate(chosen)
             for i, z in enumerate(planes)}
        erased = self._erased_of(chosen)
        _U, C = self._decode_planes(C, list(erased), host=True)
        out = np.empty((s, len(erased), alpha, sb), dtype=np.uint8)
        for e, node in enumerate(erased):
            for i, z in enumerate(planes):
                out[:, e, i] = C[(node, z)].reshape(s, sb)
        return out.reshape(s, len(erased), su)

    def _host_decode(self, chosen, targets, chunks) -> np.ndarray:
        rows = [self._erased_of(chosen).index(t) for t in targets]
        return self._host_layered(tuple(chosen), chunks)[:, rows]

    def _dense_bits(self, chosen, targets) -> np.ndarray:
        """The layered decode of one pattern as one GF(2^8) matrix over a
        stripe's sub-chunk byte columns, bit-expanded: (k * alpha * 8,
        len(targets) * alpha * 8) int8 (clay_kernel's operand).  Column
        c * alpha + z of the matrix is what a lone 1 in byte column b of
        chunk c's sub-chunk z rebuilds in byte column b of the targets'
        sub-chunks: the host layered code run on those unit columns."""
        from ceph_tpu.gf.tables import bit_matrix
        k, alpha = self.k, self.get_sub_chunk_count()
        cols = k * alpha
        unit = np.zeros((1, k, alpha, cols), dtype=np.uint8)
        unit[0].reshape(cols, cols)[np.arange(cols), np.arange(cols)] = 1
        out = self._host_decode(chosen, targets,
                                unit.reshape(1, k, alpha * cols))
        return bit_matrix(out.reshape(len(targets) * alpha, cols)).astype(
            np.int8)

    def _dense(self, chosen: tuple, targets: tuple):
        """(pattern index, table, device bit matrix) of a pattern, the
        matrix built on first sight (outside the codec lock; a racing
        duplicate is idempotent) and kept with the table generation."""
        idx, _tb, tab = self._register_pattern(chosen, targets)
        with self._decode_lock:
            w = tab.setdefault("dense", {}).get(idx)
        if w is None:
            import jax
            w = jax.device_put(self._dense_bits(chosen, targets))
            with self._decode_lock:
                w = tab["dense"].setdefault(idx, w)
        return idx, tab, w

    def encode_chunks(self, data_chunks):
        """(S, k, su) data chunks -> (S, m, su) parity chunks, each
        stripe encoded on its own (ECUtil::encode)."""
        data = np.asarray(data_chunks, dtype=np.uint8)
        if self.runtime != "tpu":
            return self._host_layered(tuple(range(self.k)), data)
        if self._encoder is None:
            import jax
            self._encoder = jax.device_put(self._dense_bits(
                tuple(range(self.k)), tuple(range(self.k, self.k + self.m))))
        from ceph_tpu.ops import clay_kernel
        return clay_kernel.run("encode", [(self._encoder, None)], data,
                               alpha=self.get_sub_chunk_count())

    def decode_chunks(self, chosen, chunks, targets):
        """(S, k, su) chunks of the nodes ``chosen`` -> (S, len(targets),
        su)."""
        chosen, targets = tuple(chosen), tuple(targets)
        if self.runtime != "tpu":
            return self._host_decode(chosen, targets, chunks)
        from ceph_tpu.ops import clay_kernel
        _idx, _tab, w = self._dense(chosen, targets)
        return clay_kernel.run(
            "decode", [(w, None)], np.asarray(chunks, dtype=np.uint8),
            alpha=self.get_sub_chunk_count())

    def submit_chunks(self, engine, data_chunks, cost_tag=None):
        """The encode through a dispatch engine: concurrent submits of
        one chunk width coalesce on the stripe axis (zeros encode to
        zeros), the host layered code standing in when the device path
        stays broken.  Batches stay whole on one device (no mesh
        placement)."""
        data = np.asarray(data_chunks, dtype=np.uint8)
        key = ("ec_encode_clay", id(self), self.k, self.m, data.shape[-1],
               self.runtime)
        from ceph_tpu.ops import clay_kernel
        return engine.submit(
            key, self.encode_chunks, data, label="ec_encode_clay",
            cache_entries=(clay_kernel.jit_entries
                           if self.runtime == "tpu" else None),
            place=False,
            fallback=lambda batch: self._host_layered(
                tuple(range(self.k)), np.asarray(batch)),
            cost_tag=cost_tag)

    def _host_patterns(self, tab: dict, host_pidx, data) -> np.ndarray:
        """A coalesced batch on the host, pattern group by group."""
        data = np.asarray(data, dtype=np.uint8)
        with self._decode_lock:
            pats = {i: key for key, i in tab["ids"].items()}
        out = None
        for p in np.unique(host_pidx):
            rows = np.nonzero(host_pidx == p)[0]
            got = self._host_decode(*pats[int(p)], data[rows])
            if out is None:
                out = np.zeros((data.shape[0],) + got.shape[1:], np.uint8)
            out[rows] = got
        return out

    def submit_decode_chunks(self, engine, chosen, chunks, targets,
                             cost_tag=None):
        """The decode through the decode engine: a stripe carries its
        erasure pattern's index, so reads with different patterns (and
        as many chunks wanted) share one engine batch, and the batch is
        one program call a pattern group (``clay_kernel.run``).  The
        future gives (S, len(targets), su)."""
        data = np.asarray(chunks, dtype=np.uint8)
        chosen, targets = tuple(chosen), tuple(targets)
        device = self.runtime == "tpu"
        if device:
            idx, tab, _w = self._dense(chosen, targets)
        else:
            idx, _tb, tab = self._register_pattern(chosen, targets)
        pidx = np.full(data.shape[0], idx, dtype=np.int32)
        key = ("ec_decode_clay", id(self), self.k, len(targets),
               data.shape[-1], self.runtime, tab["gen"])
        from ceph_tpu.ops import clay_kernel, telemetry
        stats = engine.stats if isinstance(
            engine.stats, telemetry.DecodeDispatchStats) \
            else telemetry.decode_dispatch_stats()
        alpha = self.get_sub_chunk_count()

        def fn(batch, batch_pidx):
            # analysis: allow[blocking] -- the pattern indices are a small host array (place=False)
            host_pidx = np.asarray(batch_pidx)
            uniq = np.unique(host_pidx)
            stats.record_patterns(int(uniq.size), len(tab["mats"]))
            if not device:
                return self._host_patterns(tab, host_pidx, batch)
            with self._decode_lock:
                dense = dict(tab["dense"])
            if uniq.size == 1:
                groups = [(dense[int(uniq[0])], None)]
            else:
                groups = [(dense[int(p)], np.nonzero(host_pidx == p)[0])
                          for p in uniq]
            return clay_kernel.run("decode", groups, batch, alpha=alpha)

        return engine.submit(
            key, fn, data, aux=(pidx,), label="ec_decode_clay",
            cache_entries=clay_kernel.jit_entries if device else None,
            place=False,
            fallback=lambda batch, batch_pidx: self._host_patterns(
                tab, np.asarray(batch_pidx), batch),
            cost_tag=cost_tag)

    # -- repair-bandwidth-optimal single-node repair --------------------------

    def repair_subchunks(self, lost: int) -> list[int]:
        """Sub-chunk indices each helper must send to repair `lost` —
        the q^(t-1) planes with z_{y0} = x0 (minimum_to_decode's
        sub-chunk range payload, ErasureCodeInterface.h:297-300)."""
        x0, y0 = self.node_xy(lost)
        return [i for i, z in enumerate(self._planes()) if z[y0] == x0]

    def repair(self, lost: int, helper_subchunks: dict) -> bytes:
        """Rebuild node `lost` from alpha/q sub-chunks per helper.

        helper_subchunks: {node: {z_tuple: uint8 array}} covering
        exactly the S-planes from every surviving node.
        """
        n = self.k + self.m
        x0, y0 = self.node_xy(lost)
        planes = self._planes()
        S = [z for z in planes if z[y0] == x0]
        surv = [i for i in range(n) if i != lost]
        U: dict = {}
        # 1. on each S-plane, uncouple the y != y0 rows (partners stay
        # inside S) and RS-solve the y0 row (q unknowns, m = q checks)
        for z in S:
            known: dict[int, np.ndarray] = {}
            for i in surv:
                x, y = self.node_xy(i)
                if y == y0:
                    continue
                if z[y] == x:
                    known[i] = helper_subchunks[i][z]
                else:
                    partner = self.node_id(z[y], y)
                    zp = self._zset(z, y, x)
                    u1, _ = self._uncouple(helper_subchunks[i][z],
                                           helper_subchunks[partner][zp])
                    known[i] = u1
            chosen = sorted(known)[:self.k]
            targets = [self.node_id(x, y0) for x in range(self.q)]
            rmat = self._recovery(tuple(chosen), tuple(targets))
            rebuilt = self._apply(rmat, np.stack([known[i]
                                                  for i in chosen]))
            for idx, i in enumerate(targets):
                U[(i, z)] = rebuilt[idx]
        # 2. the failed node's S sub-chunks are fixed points: C = U
        out_planes: dict = {z: U[(lost, z)] for z in S}
        # 3. off-S sub-chunks via the pair algebra through row y0:
        #    for zt in S and x != x0:  z = zt(y0->x)  pairs (lost, z)
        #    with helper (x, y0, zt):
        #      C_helper = g*U(lost, z) + U(helper, zt)
        ginv = gf_inv(GAMMA)
        for zt in S:
            for x in range(self.q):
                if x == x0:
                    continue
                helper = self.node_id(x, y0)
                z = self._zset(zt, y0, x)
                u_lost_z = _mul(ginv, helper_subchunks[helper][zt]
                                ^ U[(helper, zt)])
                out_planes[z] = u_lost_z ^ _mul(GAMMA, U[(helper, zt)])
        return self._join(out_planes)


register("clay", lambda profile: ErasureCodeClay())
