"""Cross-validation of the native (C) single-core baseline kernels against
the in-repo oracles: GF(2^8) encode vs the numpy reference, and the scalar C
crush_do_rule vs crush.mapper_ref across map shapes, weights, and rule modes.

These guarantee the ``runtime=native`` reference codec and BASELINE.md's C
yardsticks compute the same math the TPU kernels do."""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.crush import build_flat_map, build_two_level_map
from ceph_tpu.crush.builder import add_simple_rule
from ceph_tpu.crush.mapper_ref import crush_do_rule
from ceph_tpu.native import CrushBaseline, ec_encode_native
from ceph_tpu.ops.gf_kernel import ec_encode_ref


# -- GF encode ---------------------------------------------------------------

@pytest.mark.parametrize("k,m,chunk", [(2, 1, 64), (4, 2, 4096),
                                       (8, 4, 4096), (10, 4, 1000),
                                       (8, 3, 33)])
def test_ec_encode_c_matches_numpy_oracle(k, m, chunk):
    rng = np.random.default_rng(k * 100 + m)
    matrix = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (7, k, chunk), dtype=np.uint8)
    got = ec_encode_native(matrix, data)
    want = ec_encode_ref(matrix, data)
    assert (got == want).all()


def test_ec_encode_c_special_coefficients():
    # identity / zero coefficients exercise the c==0 / c==1 table rows
    matrix = np.array([[0, 1, 2, 255], [1, 0, 128, 3]], dtype=np.uint8)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (3, 4, 256), dtype=np.uint8)
    assert (ec_encode_native(matrix, data) == ec_encode_ref(matrix, data)).all()


# -- CRUSH -------------------------------------------------------------------

def _compare(m, rid, xs, numrep, weights):
    cb = CrushBaseline(m)
    try:
        for x in xs:
            want = crush_do_rule(m, rid, x, numrep, weights)
            got = cb.do_rule(rid, x, numrep, weights)
            assert got == want, (x, got, want)
    finally:
        cb.close()


def test_crush_c_flat_firstn_uniform():
    m, _root, rid = build_flat_map(32)
    weights = [0x10000] * 32
    _compare(m, rid, range(512), 3, weights)


def test_crush_c_flat_indep():
    m, _root, _rid = build_flat_map(24)
    weights = [0x10000] * 24
    _compare(m, 1, range(512), 6, weights)


def test_crush_c_two_level_chooseleaf():
    m, _root, rid = build_two_level_map(8, 4)
    weights = [0x10000] * 32
    _compare(m, rid, range(512), 3, weights)


def test_crush_c_nonuniform_weights_and_reweight():
    rng = np.random.default_rng(7)
    m, _root, rid = build_two_level_map(6, 5)
    # skew the straw2 item weights inside each host bucket
    for b in m.buckets:
        if b is not None and b.type == 1:
            b.item_weights = [int(w) for w in
                              rng.integers(0x4000, 0x30000, b.size)]
    weights = [int(w) for w in rng.integers(0, 0x10001, 30)]  # reweights
    weights[3] = 0  # one fully out
    _compare(m, rid, range(256), 3, weights)


def test_crush_c_indep_two_level():
    m, _root, _rid = build_two_level_map(8, 4)
    rid = add_simple_rule(m, -1, 1, "indep")
    weights = [0x10000] * 32
    _compare(m, rid, range(256), 4, weights)


def test_crush_c_batch_matches_scalar():
    m, _root, rid = build_two_level_map(10, 4)
    weights = np.full(40, 0x10000, dtype=np.uint32)
    xs = np.arange(200, dtype=np.uint32)
    cb = CrushBaseline(m)
    try:
        batch = cb.do_rule_batch(rid, xs, 3, weights)
        for i, x in enumerate(xs):
            want = crush_do_rule(m, rid, int(x), 3, list(weights))
            got = [int(v) for v in batch[i] if v != 0x7FFFFFFF]
            assert got == want
    finally:
        cb.close()


def test_crush_c_tree_buckets():
    # tree host buckets under a straw2 root, and a pure tree root: the C
    # descent (bucket_tree_choose) must match the oracle's mapper.c:195-222
    from ceph_tpu.crush.types import CRUSH_BUCKET_TREE
    m, _root, rid = build_two_level_map(8, 4, host_alg=CRUSH_BUCKET_TREE)
    _compare(m, rid, range(256), 3, [0x10000] * 32)

    rng = np.random.default_rng(3)
    weights = [int(w) for w in rng.integers(0x4000, 0x30000, 19)]
    from ceph_tpu.crush import build_flat_map as _bfm
    m2, _root2, rid2 = _bfm(19, weights=weights, alg=CRUSH_BUCKET_TREE)
    rw = [int(w) for w in rng.integers(0, 0x10001, 19)]
    _compare(m2, rid2, range(256), 3, rw)


def test_crush_c_result_max_guard_raises():
    # result_max beyond the fixed 64-slot working set must be a loud error,
    # never a silent empty result
    m, _root, rid = build_flat_map(8)
    cb = CrushBaseline(m)
    try:
        with pytest.raises(ValueError):
            cb.do_rule(rid, 1, 65, [0x10000] * 8)
        with pytest.raises(ValueError):
            cb.do_rule_batch(rid, np.arange(4, dtype=np.uint32), 65,
                             np.full(8, 0x10000, dtype=np.uint32))
    finally:
        cb.close()
