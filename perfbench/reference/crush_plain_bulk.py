"""The plain placement reference of crush_plain.py over many PGs at
once: the same map (`PlainMap`), the same rule (`take root; chooseleaf
firstn 0 type host; emit`), the same "jewel" tunables, written a second
time with a block of PGs in a numpy vector and a bucket's items along
the other axis.  crush_plain.py stays the reference of a single PG's
answer; this one exists for the table of a whole pool of 1,048,576 PGs,
which one PG at a time takes a quarter of an hour to make.

It imports nothing of the program.  From crush_plain it takes the data
types and `crush_ln`, the logarithm's defining arithmetic; the hash is
written here again, on uint32 throughout (crush_plain's goes through
int64 for every argument, which is most of its time on a block).

A block of PGs goes through the rule in lockstep per replica: every lane
draws its host and its leaf for r = rep + ftotal, the lanes that met a
collision or a rejected leaf go round again with ftotal one higher, and
a lane gives its replica up after TOTAL_TRIES, as `crush_plain.do_rule`.
Blocks are independent, so a whole pool's PG ranges are spread over
worker processes (`up_table`); the workers import numpy and this module
and nothing else.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

from perfbench.reference.crush_plain import (NONE, TOTAL_TRIES, PlainMap,
                                             crush_ln)

#: PGs that go through the rule together: 4,096 x 250 uint32 is 4 MB a
#: temporary, which stays in a core's cache
BLOCK = 4096
#: PGs a worker process is handed at a time
RANGE = 65536
_SEED = np.uint32(1315423911)
_X, _Y = np.uint32(231232), np.uint32(1232)
_S64_MIN = np.int64(-(1 << 63))


# -- rjenkins1 (hash.c) on uint32 ---------------------------------------------

def _mix(a, b, c):
    """One mix of hash.c on uint32 arrays (a constant may come as a
    scalar).  Each name's first statement makes a new array, so the
    in-place steps after it never write into an argument."""
    a = a - b; a -= c; a ^= c >> 13
    b = b - c; b -= a; b ^= a << 8
    c = c - a; c -= b; c ^= b >> 13
    a -= b; a -= c; a ^= c >> 12
    b -= c; b -= a; b ^= a << 16
    c -= a; c -= b; c ^= b >> 5
    a -= b; a -= c; a ^= c >> 3
    b -= c; b -= a; b ^= a << 10
    c -= a; c -= b; c ^= b >> 15
    return a, b, c


def hash32_2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """crush_hash32_2 of uint32 arrays (broadcast against each other)."""
    a, b = np.broadcast_arrays(a, b)
    h = _SEED ^ a ^ b
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(_X, a, h)
    b, y, h = _mix(b, _Y, h)
    return h


def hash32_3(a: np.ndarray, b: np.ndarray, c: np.ndarray) -> np.ndarray:
    a, b, c = np.broadcast_arrays(a, b, c)
    h = _SEED ^ a ^ b ^ c
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, _X, h)
    y, a, h = _mix(_Y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


@functools.lru_cache(maxsize=None)
def _neg_ln() -> np.ndarray:
    """2^48 - crush_ln(u) for every 16-bit u (never negative), int64."""
    return np.array([(1 << 48) - crush_ln(u) for u in range(1 << 16)],
                    dtype=np.int64)


# -- the map, as arrays -------------------------------------------------------

class _Arrays:
    """A PlainMap's buckets as dense arrays: hosts in the root's order,
    every host's items padded to the widest with weight 0 (an item of
    weight 0 never wins a draw)."""

    def __init__(self, m: PlainMap):
        self.root_items = np.asarray(m.root.items).astype(np.uint32)
        self.root_w = np.asarray(m.root.weights, dtype=np.int64)
        hosts = [m.hosts[int(h)] for h in m.root.items]
        width = max(len(h.items) for h in hosts)
        self.leaf_items = np.zeros((len(hosts), width), dtype=np.int64)
        self.leaf_w = np.zeros((len(hosts), width), dtype=np.int64)
        for i, h in enumerate(hosts):
            self.leaf_items[i, :len(h.items)] = h.items
            self.leaf_w[i, :len(h.items)] = h.weights
        self.leaf_items_u32 = self.leaf_items.astype(np.uint32)
        self.reweight = np.asarray(m.reweight, dtype=np.int64)
        self.up = np.asarray(m.up, dtype=bool)


def _straw2(x, items_u32, weights, r: int) -> np.ndarray:
    """The index, along the last axis, of the item with the largest
    ln(u)/weight draw, the first of equals (mapper.c:361-384).  The
    draw is -(2^48 - ln) // w, truncated toward zero as C's div64_s64:
    the numerator is never negative and w is positive."""
    u = hash32_3(x[:, None], items_u32, np.uint32(r)) & np.uint32(0xFFFF)
    neg = _neg_ln()[u]
    positive = weights > 0
    draw = -(neg // np.where(positive, weights, 1))
    draw = np.where(positive, draw, _S64_MIN)
    return np.argmax(draw, axis=-1)


def _is_out(a: _Arrays, osd: np.ndarray, x: np.ndarray) -> np.ndarray:
    """mapper.c:424-438, for OSD ids inside the map."""
    w = a.reweight[osd]
    draw = (hash32_2(x, osd.astype(np.uint32)) & np.uint32(0xFFFF)
            ).astype(np.int64)
    return (w == 0) | ((w < 0x10000) & (draw >= w))


def _do_rule(a: _Arrays, x: np.ndarray, size: int) -> np.ndarray:
    """`crush_plain.do_rule` for the inputs `x` (uint32): (len(x), size)
    int64, a row's leaves packed to the left and NONE after them."""
    n = len(x)
    hosts_out = np.full((n, size), -1, dtype=np.int64)  # index in root
    leaves = np.full((n, size), NONE, dtype=np.int64)
    count = np.zeros(n, dtype=np.int64)
    for rep in range(size):
        lanes = np.arange(n)
        for ftotal in range(TOTAL_TRIES):
            if not lanes.size:
                break
            xs, r = x[lanes], rep + ftotal
            host = _straw2(xs, a.root_items[None, :], a.root_w[None, :], r)
            # vary_r 1, descend_once, stable: the leaf's r is the
            # parent's, one try
            pick = _straw2(xs, a.leaf_items_u32[host], a.leaf_w[host], r)
            osd = a.leaf_items[host, pick]
            bad = ((hosts_out[lanes] == host[:, None]).any(axis=1)
                   | (leaves[lanes] == osd[:, None]).any(axis=1)
                   | _is_out(a, osd, xs))
            good = lanes[~bad]
            hosts_out[good, count[good]] = host[~bad]
            leaves[good, count[good]] = osd[~bad]
            count[good] += 1
            lanes = lanes[bad]
    return leaves


# -- the pool -----------------------------------------------------------------

def pps_of(m: PlainMap, pgs: np.ndarray) -> np.ndarray:
    """pg_pool_t::raw_pg_to_pps with HASHPSPOOL (osd_types.cc)."""
    mask = (1 << (m.pg_num - 1).bit_length()) - 1
    pgs = np.asarray(pgs, dtype=np.int64)
    low = pgs & mask
    stable = np.where(low < m.pg_num, low, pgs & (mask >> 1))
    return hash32_2(stable.astype(np.uint32), np.uint32(m.pool_id))


def up_rows(m: PlainMap, pgs) -> np.ndarray:
    """`crush_plain.up_of(m, pg)[0]` for every pg of `pgs`:
    (len(pgs), size) int32, short rows filled with -1."""
    a = _Arrays(m)
    pgs = np.asarray(pgs, dtype=np.int64)
    out = np.empty((len(pgs), m.size), dtype=np.int32)
    with np.errstate(over="ignore"):
        for lo in range(0, len(pgs), BLOCK):
            raw = _do_rule(a, pps_of(m, pgs[lo:lo + BLOCK]), m.size)
            keep = raw != NONE
            keep &= a.up[np.where(keep, raw, 0)]
            # the OSDs that are up, in order, packed to the left
            order = np.argsort(~keep, axis=1, kind="stable")
            out[lo:lo + BLOCK] = np.where(
                np.take_along_axis(keep, order, axis=1),
                np.take_along_axis(raw, order, axis=1), -1)
    return out


def _range_rows(job) -> np.ndarray:
    m, lo, hi = job
    return up_rows(m, np.arange(lo, hi))


def up_table(m: PlainMap, workers: int | None = None) -> np.ndarray:
    """`up` of every PG of the pool, as `crush_plain.up_table` gives
    it.  A pool larger than one RANGE is spread over worker processes
    (at most eight, and no more than the host has cores); they are
    started anew (`spawn`), so they inherit nothing of a parent that
    holds a chip."""
    jobs = [(m, lo, min(lo + RANGE, m.pg_num))
            for lo in range(0, m.pg_num, RANGE)]
    if workers is None:
        workers = min(8, os.cpu_count() or 1, len(jobs))
    if workers <= 1:
        parts = [_range_rows(j) for j in jobs]
    else:
        with ProcessPoolExecutor(workers,
                                 mp_context=get_context("spawn")) as pool:
            parts = list(pool.map(_range_rows, jobs))
    return np.concatenate(parts)
