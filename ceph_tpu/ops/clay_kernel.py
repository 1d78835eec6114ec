"""Clay (coupled-layer MSR) encode and decode on the device.

A Clay chunk of ``su`` bytes is alpha sub-chunks of ``su / alpha`` bytes
(``ec/clay.py``).  Whatever the layers do, a rebuild is linear over
GF(2^8) in the k chunks read, and byte column b of every sub-chunk meets
only byte column b of the others: for one erasure pattern (the nodes
read, the nodes wanted) the whole layered decode of a stripe is one
(t * alpha, k * alpha) GF(2^8) matrix applied to each of the su / alpha
byte columns.  The codec builds that matrix once a pattern, from its
host layered code, as a (k * alpha * 8, t * alpha * 8) bit matrix
(``gf.tables.bit_matrix``); here it is one int8 product on the MXU a
pattern group — ``bits(columns) @ W`` with the ``& 1`` epilogue as the
XOR reduction, as ``gf_kernel`` does for Reed-Solomon — and an encode is
the same product with the parities as the wanted nodes.

The dense product was measured against a layered program (t + 1 rounds
of uncoupling, plane code and recoupling over all planes, four bytes a
word on the vector unit) on a v5e: 0.55 against 0.71 ms for one 4 MiB
rebuild of k = 8, m = 4 (alpha 64), 2.23 against 4.76 ms for four
(PERF.md, findings on the Clay configuration).  A stripe never gathers
a matrix: a batch is split by pattern on the host, and each group is
one call of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops import telemetry
from ceph_tpu.ops.dispatch import bucket_stripes

_BITW = np.arange(8, dtype=np.int32)

#: stripes per program tile: the (S * su / alpha, k * alpha * 8) bit
#: expansion of a large group streams through lax.map in pieces
_TILE_S = 256


def _tile(w_bits, data, alpha: int):
    """data (S, k, su) uint8 -> (S, t, su) uint8 through the
    (k * alpha * 8, t * alpha * 8) bit matrix."""
    s, k, su = data.shape
    sb = su // alpha
    t = w_bits.shape[1] // (alpha * 8)
    # one row per (stripe, byte column): the k chunks' alpha sub-chunks
    x = jnp.transpose(data.reshape(s, k, alpha, sb), (0, 3, 1, 2))
    x = x.reshape(s * sb, k * alpha)
    bits = ((x[:, :, None].astype(jnp.int32) >> _BITW) & 1).astype(
        jnp.int8).reshape(s * sb, k * alpha * 8)
    acc = jax.lax.dot_general(bits, w_bits, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    pb = (acc & 1).reshape(s * sb, t * alpha, 8)
    out = jnp.sum(pb << _BITW, axis=-1, dtype=jnp.int32).astype(jnp.uint8)
    return jnp.transpose(out.reshape(s, sb, t, alpha),
                         (0, 2, 3, 1)).reshape(s, t, su)


def _apply(w_bits, data, alpha: int):
    s = data.shape[0]
    if s <= _TILE_S:
        return _tile(w_bits, data, alpha)
    pad = (-s) % _TILE_S
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((pad,) + data.shape[1:], data.dtype)])
    out = jax.lax.map(lambda d: _tile(w_bits, d, alpha),
                      data.reshape(-1, _TILE_S, *data.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:s]


@functools.partial(jax.jit, static_argnames=("alpha",))
def clay_decode(w_bits, data, *, alpha: int):
    """The decode program (``jit_clay_decode`` in a device trace)."""
    return _apply(w_bits, data, alpha)


@functools.partial(jax.jit, static_argnames=("alpha",))
def clay_encode(w_bits, data, *, alpha: int):
    """The encode program (``jit_clay_encode``): the parities' matrix."""
    return _apply(w_bits, data, alpha)


def jit_entries() -> int:
    """Compile-cache entries of both programs (retrace accounting)."""
    return clay_decode._cache_size() + clay_encode._cache_size()


def run(program: str, groups, data, *, alpha: int):
    """One timed batch (telemetry kernels ``ec_decode_clay`` /
    ``ec_encode_clay``): ``groups`` = [(w_bits, rows), ...], the stripes
    ``rows`` of the (S, k, su) batch ``data`` taking the pattern whose
    bit matrix is ``w_bits`` (``rows`` None: every stripe).  Each group
    is one call of the program on its stripes, padded with zero stripes
    to a power of two (zeros rebuild to zeros).  Returns (S, t, su)."""
    fn = clay_decode if program == "decode" else clay_encode
    s, k, su = data.shape
    t = groups[0][0].shape[1] // (alpha * 8)

    def call():
        if len(groups) == 1 and groups[0][1] is None:
            return fn(groups[0][0], jnp.asarray(data, jnp.uint8),
                      alpha=alpha)
        # analysis: allow[blocking] -- a batch is host numpy by the submit contract (the engine does not place Clay batches)
        host = np.asarray(data, dtype=np.uint8)
        outs, order = [], []
        for w_bits, rows in groups:
            sel = host[rows]
            n = bucket_stripes(len(rows))
            if n > len(rows):
                sel = np.concatenate(
                    [sel, np.zeros((n - len(rows), k, su), np.uint8)])
            outs.append(fn(w_bits, jnp.asarray(sel), alpha=alpha)
                        [:len(rows)])
            order.append(np.asarray(rows))
        back = np.argsort(np.concatenate(order))
        return jnp.take(jnp.concatenate(outs), jnp.asarray(back), axis=0)

    return telemetry.timed_kernel(
        f"ec_{program}_clay", call, batch=s,
        bytes_in=s * k * su, bytes_out=s * t * su,
        cache_entries=jit_entries,
        signature=(f"ec_{program}_clay", k, t, s, su, len(groups)))
