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
from ceph_tpu.crush.fastpath import FastMapper, detect, tables_of
from ceph_tpu.ops.crush_kernel import is_out
from ceph_tpu.ops.straw2_u32 import magic_tables, straw2_choose_index_u32


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


def test_pallas_columns_match_u32_kernel(skewed_map):
    crush_map, rid = skewed_map
    fr = detect(crush_map, rid)
    assert fr is not None
    pc, tables = columns_of(fr, True)
    N, R = 256, 5
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.integers(0, 2 ** 32, (N,), dtype=np.uint32))
    reweight = np.full(1200, 0x10000, dtype=np.int64)
    reweight[3] = 0           # an out osd
    reweight[7] = 0x8000      # a half-reweighted osd
    rw = jnp.asarray(reweight)

    pos, ids = pc.root_columns(xs, tables, R)
    lid = pc.leaf_columns(xs, pos, tables, R)
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
        assert (ref == np.asarray(pos[r])).all(), f"root col r={r}"
        assert (np.asarray(ids[r])
                == np.asarray(fr.root_ids)[ref]).all()

        posr = np.asarray(pos[r])
        lids = fr.leaf_ids[posr]
        lws = fr.leaf_w[posr]
        r_leaf = (r >> (fr.vary_r - 1)) if fr.vary_r else 0
        ref_l = np.asarray(straw2_choose_index_u32(
            xs, jnp.asarray(lids), jnp.uint32(r_leaf), jnp.asarray(lws),
            jnp.asarray(lm[posr]), jnp.asarray(lo[posr])))
        ref_id = lids[np.arange(N), ref_l]
        assert (ref_id == np.asarray(lid[r])).all(), f"leaf col r={r}"
        ref_bad = np.asarray(
            is_out(rw, jnp.asarray(ref_id), xs)).astype(np.int32)
        assert (ref_bad == np.asarray(lbad[r])).all(), f"leaf bad r={r}"


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
