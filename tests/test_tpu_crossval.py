"""Compiled-TPU vs XLA cross-validation of the CRUSH fast path.

Interpret-mode tests cannot catch Mosaic *compiled-path* divergence: in
round 3 the in-kernel is_out (hash32_2 fed from the winner gather/sum
pipeline) miscompiled for ~0.03% of lanes on TPU while interpret mode was
bit-exact.  This suite re-runs the full bulk placement on the real device
against the XLA fast path (itself oracle-validated in test_mapper_jax).

A chip belongs to one process, so this file is run alone and in one
process on a chip host (tests/conftest.py forces cpu otherwise, and the
module then skips):

    CEPH_TPU_TEST_PLATFORM=cpu,tpu python -m pytest \
        tests/test_tpu_crossval.py -p no:xdist
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ceph_tpu.crush import build_flat_map, build_skewed_two_level_map
from ceph_tpu.crush.fastpath import detect, mapper_for, tables_of


@pytest.fixture(scope="module")
def tpu():
    try:
        return jax.devices("tpu")[0]
    except RuntimeError as e:
        pytest.skip(f"no TPU backend reachable on this host: {e}")


@pytest.fixture(autouse=True)
def _on_tpu(tpu):
    """Every computation in this module runs on the real chip even
    though the suite's default backend is the virtual CPU mesh."""
    with jax.default_device(tpu):
        yield


def test_two_stage_pallas_matches_xla_bulk():
    crush_map, rid, reweight = build_skewed_two_level_map(250, 40)
    fr = detect(crush_map, rid)
    rw = jnp.asarray(reweight)
    xs = jnp.asarray(np.random.default_rng(0).integers(
        0, 2 ** 32, (65536,), dtype=np.uint32))
    res_pl, res_xla = _both_paths(fr, xs, rw)
    np.testing.assert_array_equal(res_pl, res_xla)


def _both_paths(fr, xs, rw):
    """The rule's placements by the Pallas kernels (what a TPU selects)
    and by the XLA path, each on its own tables."""
    ft = tables_of(fr)
    assert ft.shape.pallas
    ft_xla = tables_of(fr, pallas=False)
    return (np.asarray(mapper_for(ft.shape).run(xs, rw, ft.on(), 3)),
            np.asarray(mapper_for(ft_xla.shape).run(xs, rw, ft_xla.on(), 3)))


# 300 items take the exact column kernels; 600 (512..1024 items) take the
# approx-filter root kernel, whose error bound is measured on the chip
@pytest.mark.parametrize("n_osds", [300, 600])
def test_flat_rule_pallas_matches_xla(n_osds):
    fmap, _r, frid = build_flat_map(n_osds)
    fr = detect(fmap, frid)
    rw = jnp.asarray(np.where(np.arange(n_osds) % 37 == 0, 0x8000,
                              0x10000).astype(np.int64))
    xs = jnp.asarray(np.random.default_rng(1).integers(
        0, 2 ** 32, (8192,), dtype=np.uint32))
    res_pl, res_xla = _both_paths(fr, xs, rw)
    np.testing.assert_array_equal(res_pl, res_xla)
