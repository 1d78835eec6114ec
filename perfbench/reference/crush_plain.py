"""Plain reference for the placement cells: where the PGs of one
replicated pool live on a two-level CRUSH map (root -> hosts -> OSDs,
straw2 buckets, one `chooseleaf firstn 0 type host` rule, the "jewel"
tunables), and what `up` is once down OSDs are taken out.

Written from the published algorithm (src/crush/mapper.c, hash.c,
crush_ln_table.h and OSDMap.cc of the reference implementation) for
this one shape of map.  It imports nothing of the program and takes no
table the program has made: the map is given as plain lists, the log
tables are computed here from their defining arithmetic.  One PG at a
time, the items of a bucket in a numpy vector.

Tunables fixed: choose_local_tries 0, choose_local_fallback_tries 0,
choose_total_tries 50, chooseleaf_descend_once 1, chooseleaf_vary_r 1,
chooseleaf_stable 1.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from decimal import Decimal, localcontext

import numpy as np

NONE = 0x7FFFFFFF
TOTAL_TRIES = 51            # choose_total_tries + 1
_SEED = np.uint32(1315423911)
_S64_MIN = -(1 << 63)


# -- rjenkins1 (hash.c) ------------------------------------------------------

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


def _u32(x):
    return np.asarray(x, dtype=np.int64).astype(np.uint32)


def hash32_2(a, b):
    """crush_hash32_2 on uint32 scalars or vectors (wrap-around)."""
    with np.errstate(over="ignore"):
        a, b = _u32(a), _u32(b)
        h = _SEED ^ a ^ b
        x, y = np.uint32(231232), np.uint32(1232)
        a, b, h = _mix(a, b, h)
        x, a, h = _mix(x, a, h)
        b, y, h = _mix(b, y, h)
        return h


def hash32_3(a, b, c):
    with np.errstate(over="ignore"):
        a, b, c = _u32(a), _u32(b), _u32(c)
        h = _SEED ^ a ^ b ^ c
        x, y = np.uint32(231232), np.uint32(1232)
        a, b, h = _mix(a, b, h)
        c, x, h = _mix(c, x, h)
        y, a, h = _mix(y, a, h)
        b, x, h = _mix(b, x, h)
        y, c, h = _mix(y, c, h)
        return h


# -- crush_ln (mapper.c:248-290) and its tables ------------------------------

_LL_EXCESS = 0x147700000
_LL_EXACT = frozenset([0, 1, 203, 216, 222, 233, 237, 238, 239, 243, 244,
                       245, 246, 248, 249, 250, 251, 252, 253, 254, 255])
_LL_STRAY = {
    56: 0xA2B07F3458, 127: 0x16DF6CA19BD, 134: 0x182B07F3458,
    181: 0x209C06E6212, 184: 0x212B07F3458, 188: 0x21D6A73A78F,
    193: 0x22C23679B4E, 198: 0x23A2C3B0EA4, 199: 0x23D13EE805B,
    200: 0x24035E9221F, 207: 0x25492644D65, 210: 0x25D13EE805B,
    212: 0x26296453882, 225: 0x287BDBF5255, 227: 0x28D13EE805B,
    228: 0x29035E9221F, 229: 0x29296453882, 231: 0x29902A37AAB,
    235: 0x2A4C7605D61, 236: 0x2A7BDBF5255, 240: 0x2B296453882,
    241: 0x2B5D022D80F, 247: 0x2C61A5E8F4C,
}


def _log2_scaled(num: int, den: int) -> int:
    with localcontext() as ctx:
        ctx.prec = 60
        val = (Decimal(num) / Decimal(den)).ln() / Decimal(2).ln()
        return int((val * (1 << 48)).to_integral_value(
            rounding="ROUND_FLOOR"))


@functools.lru_cache(maxsize=None)
def ln_tables() -> tuple[list[int], list[int], list[int]]:
    """RH[k] = ceil(2^48 / (1 + k/128)), LH[k] = floor(2^48 log2(1 +
    k/128)), LL[k] = floor(2^48 log2(1 + k/2^15)) — with the deviations
    of the tables as every deployed Ceph ships them (crush_ln_table.h):
    LH[128], a constant excess on most of LL, and 23 stray LL values."""
    rh = [-((-(1 << 48) * 128) // (128 + k)) for k in range(129)]
    lh = [_log2_scaled(128 + k, 128) for k in range(129)]
    lh[128] = 0xFFFF00000000
    ll = []
    for k in range(256):
        if k in _LL_STRAY:
            ll.append(_LL_STRAY[k])
        else:
            base = _log2_scaled((1 << 15) + k, 1 << 15)
            ll.append(base if k in _LL_EXACT else base + _LL_EXCESS)
    return rh, lh, ll


def crush_ln(xin: int) -> int:
    rh, lh, ll = ln_tables()
    x = (xin + 1) & 0xFFFFFFFF
    iexpon = 15
    if not (x & 0x18000):
        bits = 16 - (x & 0x1FFFF).bit_length()
        x = (x << bits) & 0xFFFFFFFF
        iexpon = 15 - bits
    k = (((x >> 8) << 1) - 256) >> 1
    xl64 = ((x * rh[k]) & ((1 << 64) - 1)) >> 48
    return (iexpon << 44) + ((lh[k] + ll[xl64 & 0xFF]) >> 4)


@functools.lru_cache(maxsize=None)
def _ln_minus_2_48() -> np.ndarray:
    """crush_ln(u) - 2^48 for every 16-bit u, as int64."""
    return np.array([crush_ln(u) - (1 << 48) for u in range(1 << 16)],
                    dtype=np.int64)


# -- the map -----------------------------------------------------------------

@dataclass
class Bucket:
    id: int
    items: np.ndarray       # int64 ids (hosts: negative; OSDs: >= 0)
    weights: np.ndarray     # int64, 16.16 fixed point


@dataclass
class PlainMap:
    """A two-level map and the state of its OSDs, as plain data."""
    root: Bucket
    hosts: dict[int, Bucket]        # host id -> its bucket
    reweight: list[int]             # per OSD, 16.16; 0 = out
    up: list[bool]                  # per OSD
    pool_id: int
    pg_num: int
    size: int

    @property
    def max_devices(self) -> int:
        return len(self.reweight)


def straw2_choose(bucket: Bucket, x: int, r: int) -> int:
    """mapper.c:361-384: the item with the largest ln(u)/weight draw,
    the first of equals; truncating division as C's div64_s64."""
    u = hash32_3(x, bucket.items, r).astype(np.int64) & 0xFFFF
    ln = _ln_minus_2_48()[u]
    w = bucket.weights
    safe = np.where(w > 0, w, 1)
    draw = -((-ln) // safe)         # ln <= 0 and w > 0: toward zero
    draw = np.where(w > 0, draw, _S64_MIN)
    return int(bucket.items[int(np.argmax(draw))])


def is_out(m: PlainMap, osd: int, x: int) -> bool:
    """mapper.c:424-438."""
    if osd >= len(m.reweight):
        return True
    w = m.reweight[osd]
    if w >= 0x10000:
        return False
    if w == 0:
        return True
    return not (int(hash32_2(x, osd)) & 0xFFFF) < w


def _leaf_firstn(m: PlainMap, host: Bucket, x: int, parent_r: int,
                 chosen: list[int]) -> int | None:
    """The recursive call of chooseleaf (mapper.c:560-580) under
    descend_once and stable: numrep 1, one try, rep 0, the collision
    scope is the leaves already taken at earlier positions."""
    r = parent_r                    # rep 0 + parent_r + ftotal 0
    osd = straw2_choose(host, x, r)
    if osd in chosen or is_out(m, osd, x):
        return None
    return osd


def do_rule(m: PlainMap, x: int) -> list[int]:
    """`take root; chooseleaf firstn 0 type host; emit` for input x
    (mapper.c:460-648 for this rule): per replica descend from the root,
    retry the whole descent with r = rep + ftotal on a collision or a
    rejected leaf, give the replica up after TOTAL_TRIES."""
    hosts_out: list[int] = []
    leaves: list[int] = []
    for rep in range(m.size):
        ftotal = 0
        while True:
            r = rep + ftotal
            host_id = straw2_choose(m.root, x, r)
            leaf = None
            if host_id not in hosts_out:
                # vary_r 1: the leaf's r is the parent's r
                leaf = _leaf_firstn(m, m.hosts[host_id], x, r, leaves)
            if leaf is not None:
                hosts_out.append(host_id)
                leaves.append(leaf)
                break
            ftotal += 1
            if ftotal >= TOTAL_TRIES:
                break
    return leaves


def stable_mod(x: int, b: int, bmask: int) -> int:
    return x & bmask if (x & bmask) < b else x & (bmask >> 1)


def pps_of(m: PlainMap, pg: int) -> int:
    """pg_pool_t::raw_pg_to_pps with HASHPSPOOL (osd_types.cc)."""
    mask = (1 << (m.pg_num - 1).bit_length()) - 1
    return int(hash32_2(stable_mod(pg, m.pg_num, mask), m.pool_id))


def raw_of(m: PlainMap, pg: int) -> list[int]:
    return do_rule(m, pps_of(m, pg))


def up_of(m: PlainMap, pg: int) -> tuple[list[int], int]:
    """(up, up_primary) of a replicated pool with no upmap, no primary
    affinity and no temp mapping: the raw OSDs that are up, in order;
    the first is primary (OSDMap.cc:2275-2297); acting equals up."""
    up = [o for o in raw_of(m, pg) if o != NONE and m.up[o]]
    return up, (up[0] if up else -1)


def up_table(m: PlainMap) -> np.ndarray:
    """`up` of every PG of the pool, (pg_num, size) int32, short rows
    filled with -1.  One PG at a time, as `up_of`: a minute for 65,536
    PGs on 10,000 OSDs, so a caller keeps it."""
    table = np.full((m.pg_num, m.size), -1, dtype=np.int32)
    for pg in range(m.pg_num):
        up, _primary = up_of(m, pg)
        table[pg, :len(up)] = up
    return table
