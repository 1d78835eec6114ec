"""Pallas straw2 kernels vs the XLA u32 kernel (itself exhaustively
validated against the s64 kernel and the scalar C-semantics oracle).

Runs in interpret mode on the CPU mesh — the TPU compile path is
exercised by the benchmark and by the fastpath bit-exactness tests when
a TPU backend is present (fastpath auto-selects PallasColumns there).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from ceph_tpu.crush import build_two_level_map
from ceph_tpu.crush.builder import add_simple_rule, make_bucket
from ceph_tpu.crush.fastpath import FastMapper, detect, tables_of
from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2, CrushMap
from ceph_tpu.ops.crush_kernel import is_out
from ceph_tpu.ops.straw2_u32 import (
    magic_tables, straw2_choose_index_u32, straw2_qvals)


@pytest.fixture(scope="module")
def skewed_map():
    # 200 hosts -> two 128-lane root slabs; 6 osds/host -> padded leaf
    crush_map, _root, rid = build_two_level_map(200, 6)
    wrng = np.random.default_rng(42)
    for b in crush_map.buckets:
        if b is not None and b.type == 1:
            b.item_weights = [int(w) for w in
                              wrng.integers(0x8000, 0x20000, b.size)]
            b.weight = sum(b.item_weights)
    root = crush_map.bucket(-1)
    root.item_weights = [crush_map.bucket(h).weight for h in root.items]
    root.weight = sum(root.item_weights)
    return crush_map, rid


def columns_of(fr, interpret):
    """The column kernels of the rule's shape class, and its tables."""
    ft = tables_of(fr, pallas=True, interpret=interpret)
    return FastMapper(ft.shape)._pallas, ft.on()


def host_map(sizes, seed, *, vary_r=1, big=False):
    """A two-level straw2 map of hosts of the given sizes (unequal
    sizes share a class), weights skewed from ``seed``; the first and
    last OSD of every third host weigh 0 (a zero-weight item at group
    position 0, and at width - 1 where a host fills its group).  ``big``
    weights (2^40 16.16 units) make floor(P / w) span a few hundred
    values, so equal draws — ties — are common."""
    rng = np.random.default_rng(seed)
    m = CrushMap()
    m.max_devices = int(sum(sizes))
    order, first = [], 0
    for h, size in enumerate(sizes):
        w = rng.integers(0x8000, 0x20000, size)
        if big:
            w = w.astype(np.int64) << 24
        if h % 3 == 0:
            w[0] = w[-1] = 0
        hid = -(h + 2)
        m.add_bucket(make_bucket(hid, CRUSH_BUCKET_STRAW2, 1,
                                 list(range(first, first + size)),
                                 [int(v) for v in w]))
        order.append(hid)
        first += size
    m.add_bucket(make_bucket(-1, CRUSH_BUCKET_STRAW2, 2, order,
                             [m.bucket(h).weight for h in order]))
    m.tunables.chooseleaf_vary_r = vary_r
    return m, add_simple_rule(m, -1, 1, "firstn")


def _sizes(widest, hosts, seed):
    """``hosts`` host sizes of one class: the first is the widest, the
    others drawn from (widest / 2, widest]."""
    rng = np.random.default_rng(seed)
    return [widest] + [int(v) for v in rng.integers(
        max(1, widest // 2), widest + 1, hosts - 1)]


#: (widest host, leaf lanes, r-columns a slab, R, vary_r, big weights)
LEAF_CASES = [
    (40, 64, 2, 4, 1, False),     # the cells' hosts: two columns a slab
    (40, 64, 2, 9, 0, False),     # stage 2's R, an odd R: an empty group
    (16, 32, 4, 10, 1, False),    # a narrow host takes 32; 10: two empty
    (30, 32, 4, 5, 0, False),     # four a slab; 5 leaves three empty
    (64, 64, 2, 4, 1, False),     # a host filling its group: g - 1 real
    (65, 128, 1, 4, 1, False),    # past 64: one column a slab
    (100, 128, 1, 3, 0, False),
    (130, 256, 1, 4, 1, False),   # two slabs a column, merged
    (40, 64, 2, 5, 1, True),      # ties on equal draws
]


def _check_columns(fr, pc, tables, N, R, seed):
    """Root and leaf columns of the kernels, lane for lane, against
    the u32 kernel (and the leaf's is_out verdicts); returns the root
    winners of r = 0."""
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.integers(0, 2 ** 32, (N,), dtype=np.uint32))
    reweight = np.full(fr.max_devices, 0x10000, dtype=np.int64)
    reweight[3] = 0           # an out osd
    reweight[7] = 0x8000      # a half-reweighted osd
    rw = jnp.asarray(reweight)

    pos, ids = pc.root_columns(xs, tables, R)
    lid = pc.leaf_columns(xs, pos, tables, R)
    assert lid.shape[0] == R
    lbad = np.asarray(is_out(rw, lid, jnp.asarray(
        np.pad(np.asarray(xs), (0, lid.shape[1] - N)))[None, :])
    ).astype(np.int32)

    Sr = len(fr.root_ids)
    rm, ro = magic_tables(fr.root_w)
    lm, lo = magic_tables(fr.leaf_w)
    for r in range(R):
        ref = np.asarray(straw2_choose_index_u32(
            xs, jnp.asarray(fr.root_ids)[None, :], jnp.uint32(r),
            jnp.asarray(fr.root_w)[None, :],
            jnp.asarray(np.broadcast_to(rm[None], (N, Sr, 5)).copy()),
            jnp.asarray(np.broadcast_to(ro[None], (N, Sr)).copy())))
        assert (ref == np.asarray(pos[r])[:N]).all(), f"root col r={r}"
        assert (np.asarray(ids[r])[:N]
                == np.asarray(fr.root_ids)[ref]).all()

        posr = np.asarray(pos[r])[:N]
        lids = fr.leaf_ids[posr]
        lws = fr.leaf_w[posr]
        r_leaf = (r >> (fr.vary_r - 1)) if fr.vary_r else 0
        ref_l = np.asarray(straw2_choose_index_u32(
            xs, jnp.asarray(lids), jnp.uint32(r_leaf), jnp.asarray(lws),
            jnp.asarray(lm[posr]), jnp.asarray(lo[posr])))
        ref_id = lids[np.arange(N), ref_l]
        assert (ref_id == np.asarray(lid[r])[:N]).all(), f"leaf col r={r}"
        ref_bad = np.asarray(
            is_out(rw, jnp.asarray(ref_id), xs)).astype(np.int32)
        assert (ref_bad == np.asarray(lbad[r])[:N]).all(), \
            f"leaf bad r={r}"
    return np.asarray(pos[0])[:N]


@pytest.mark.parametrize(
    "case", [None] + LEAF_CASES,
    ids=["skewed-200x6-R5"] + [
        f"w{c[0]}-g{c[1]}-G{c[2]}-R{c[3]}-vr{c[4]}"
        + ("-ties" if c[5] else "") for c in LEAF_CASES])
def test_pallas_columns_match_u32_kernel(skewed_map, case):
    """The leaf kernel packs 128 // g r-columns into one 128-lane slab
    (g: the widest host's group width); its (R, N) output equals the u32
    kernel lane for lane whatever the packing, R and vary_r."""
    if case is None:
        crush_map, rid = skewed_map
        N, R = 256, 5
    else:
        widest, lanes, per_slab, R, vary_r, big = case
        crush_map, rid = host_map(_sizes(widest, 20, widest + R),
                                  widest, vary_r=vary_r, big=big)
        N = 256
    fr = detect(crush_map, rid)
    assert fr is not None
    pc, tables = columns_of(fr, True)
    if case is not None:
        assert fr.vary_r == vary_r
        assert (pc.S_leaf, pc.columns_per_slab) == (lanes, per_slab)
    pos = _check_columns(fr, pc, tables, N, R, 0)
    if case is not None and big:
        # the ties were there to be broken: lanes on which two items of
        # the host drew the winning value
        lm, lo = magic_tables(fr.leaf_w)
        xs = jnp.asarray(np.random.default_rng(0).integers(
            0, 2 ** 32, (N,), dtype=np.uint32))
        q_hi, q_lo = straw2_qvals(
            xs, jnp.asarray(fr.leaf_ids[pos]), jnp.uint32(0),
            jnp.asarray(fr.leaf_w[pos]), jnp.asarray(lm[pos]),
            jnp.asarray(lo[pos]))
        q = (np.asarray(q_hi).astype(np.uint64) << np.uint64(32)) \
            | np.asarray(q_lo).astype(np.uint64)
        tied = (q == q.min(axis=1, keepdims=True)).sum(axis=1) > 1
        assert tied.sum() >= 8, tied.sum()


def test_pack_tables_leaf_block_is_eight_fields_of_the_group_width():
    """The leaf table is the host's eight fields, each g lanes wide —
    ids, zero-weight mask, limb offset, five magic limbs — and no block
    of raw weights."""
    crush_map, rid = host_map(_sizes(40, 20, 1), 1)
    fr = detect(crush_map, rid)
    ft = tables_of(fr, pallas=True, interpret=True)
    S, L = ft.shape.root_lanes, ft.shape.leaf_lanes
    assert (S, L) == (128, 64)
    assert len(ft.host) == 6
    leaf = ft.host[5]
    assert leaf.shape == (S, 8 * L) and leaf.dtype == np.float32
    H, W = fr.leaf_ids.shape
    lw = np.zeros((S, L), dtype=np.int64)
    lw[:H, :W] = fr.leaf_w
    limbs, off = magic_tables(lw)
    blocks = [leaf[:, f * L:(f + 1) * L] for f in range(8)]
    np.testing.assert_array_equal(blocks[0][:H, :W], fr.leaf_ids)
    np.testing.assert_array_equal(blocks[1], (lw <= 0))
    np.testing.assert_array_equal(blocks[2], off)
    for j in range(5):
        np.testing.assert_array_equal(blocks[3 + j], limbs[..., j])
    assert not any(np.array_equal(b, lw.astype(np.float32))
                   for b in blocks)


def test_pallas_flat_rule(skewed_map):
    from ceph_tpu.crush import build_flat_map
    crush_map, _root, rid = build_flat_map(300)
    fr = detect(crush_map, rid)
    assert fr is not None and fr.kind == "choose_flat"
    pc, tables = columns_of(fr, True)
    N, R = 128, 3
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.integers(0, 2 ** 32, (N,), dtype=np.uint32))
    reweight = np.full(300, 0x10000, dtype=np.int64)
    reweight[5] = 0
    rw = jnp.asarray(reweight)
    pos, ids = pc.root_columns(xs, tables, R)
    bad = np.asarray(is_out(rw, ids, jnp.asarray(
        np.pad(np.asarray(xs), (0, ids.shape[1] - N)))[None, :])
    ).astype(np.int32)
    Sr = len(fr.root_ids)
    rm, ro = magic_tables(fr.root_w)
    for r in range(R):
        ref = np.asarray(straw2_choose_index_u32(
            xs, jnp.asarray(fr.root_ids)[None, :], jnp.uint32(r),
            jnp.asarray(fr.root_w)[None, :],
            jnp.asarray(np.broadcast_to(rm[None], (N, Sr, 5)).copy()),
            jnp.asarray(np.broadcast_to(ro[None], (N, Sr)).copy())))
        assert (ref == np.asarray(pos[r])).all()
        ref_id = np.asarray(fr.root_ids)[ref]
        ref_bad = np.asarray(
            is_out(rw, jnp.asarray(ref_id), xs)).astype(np.int32)
        assert (ref_bad == np.asarray(bad[r])).all()


def test_consume_columns_matches_xla_ladder(skewed_map):
    """The unrolled Pallas firstn ladder == fastpath._consume on random
    winner columns, including collision, reject, tries-exhaustion and
    overflow lanes."""
    from ceph_tpu.crush.fastpath import _consume
    from ceph_tpu.ops.pallas_straw2 import consume_columns

    rng = np.random.default_rng(3)
    n, R, numrep = 256, 7, 3
    for tries, seed in ((51, 0), (2, 1), (5, 2)):
        r2 = np.random.default_rng(seed)
        # few distinct ids -> plenty of collisions; bad ~ 1/4 of draws
        hw = r2.integers(-6, -1, (R, n)).astype(np.int32)
        lw = r2.integers(0, 8, (R, n)).astype(np.int32)
        lb = (r2.random((R, n)) < 0.25)
        oh, ol, ovf = consume_columns(
            jnp.asarray(hw), jnp.asarray(lw), jnp.asarray(lb),
            numrep=numrep, tries=tries, interpret=True)
        ref_h, ref_l, ref_ovf = _consume(
            jnp.asarray(hw.T), jnp.asarray(lw.T), jnp.asarray(lb.T),
            numrep, tries, R, n)
        np.testing.assert_array_equal(np.asarray(oh).T, np.asarray(ref_h))
        np.testing.assert_array_equal(np.asarray(ol).T, np.asarray(ref_l))
        np.testing.assert_array_equal(np.asarray(ovf) != 0,
                                      np.asarray(ref_ovf))


def test_froot_columns_match_exact(skewed_map):
    """Fused single-phase filter kernel == exact root columns, with the
    certificate clean on realistic weights."""
    crush_map, rid = skewed_map
    fr = detect(crush_map, rid)
    pc, tables = columns_of(fr, True)
    N, R = 256, 5
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.integers(0, 2 ** 32, (N,), dtype=np.uint32))
    reweight = np.full(1200, 0x10000, dtype=np.int64)
    reweight[3] = 0
    reweight[7] = 0x8000
    rw = jnp.asarray(reweight)

    pos, ids = pc.root_columns(xs, tables, R)
    fpos, fids, ovf = pc.froot_columns(xs, tables, R)
    assert int(np.asarray(ovf).max()) == 0, "certificate fired on clean map"
    np.testing.assert_array_equal(np.asarray(fpos), np.asarray(pos))
    np.testing.assert_array_equal(np.asarray(fids), np.asarray(ids))
