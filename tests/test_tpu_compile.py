"""The smoke path's kernels, compiled for a described v5e chip.

No TPU is attached where the tests run, but the TPU compiler is
installed: it compiles for a chip that is described and not attached,
and refuses what the chip's compiler would refuse (a VMEM limit, an
unaligned slice, a custom call it cannot partition).  Interpret-mode
tests cannot see any of that.  One case per kernel on ``chip_smoke.py``'s
path, at the smoke's shapes; nothing runs, so these say nothing about
results or times.

This is the only file that describes a topology, and it does so inside
a module-scoped fixture: only the xdist worker that is handed this file
loads the TPU library.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding)

from ceph_tpu.crush import build_flat_map, build_skewed_two_level_map
from ceph_tpu.crush.fastpath import (FastMapper, build_tables, detect,
                                     shape_of)
from ceph_tpu.ops import pallas_straw2 as ps


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.experimental import mesh_utils
    return Mesh(mesh_utils.create_device_mesh((4, 1), devices=topo.devices),
                axis_names=("dp", "ec"))


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# -- GF(2^8) encode / decode --------------------------------------------------

@pytest.mark.parametrize("s,b,bc", [(2048, 4096, 4096), (128, 65536, 4096)])
def test_encode_pallas(one_chip, s, b, bc):
    from ceph_tpu.ops.gf_kernel import _G, _encode_pallas, _pick_bc
    k, m = 8, 4
    assert _pick_bc(b) == bc
    text = _encode_pallas.lower(
        _spec(one_chip, (_G * k * 8, _G * m * 8), jnp.int8),
        _spec(one_chip, (s, k, b), jnp.uint8),
        k=k, m=m, bc=bc).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s", [128, 2048])
def test_decode_pattern_table(one_chip, s):
    """The heterogeneous-pattern decode a degraded read rides: per-stripe
    gather from the stacked (P, k*8, t*8) table."""
    from ceph_tpu.ops.gf_kernel import _decode_xla
    k, t = 8, 4
    _decode_xla.lower(
        _spec(one_chip, (2, k * 8, t * 8), jnp.int8),
        _spec(one_chip, (s,), jnp.int32),
        _spec(one_chip, (s, k, 4096), jnp.uint8),
        k=k, t=t).compile()


@pytest.mark.parametrize("program,s,t", [
    ("decode", 128, 2), ("decode", 1024, 1), ("encode", 128, 4)])
def test_clay_dense(one_chip, program, s, t):
    """Clay k=8 m=4 d=11 (alpha 64) at a 4 KiB stripe unit: one 4 MiB
    object's 128 stripes rebuilt, a batch past one program tile, and
    the encode — one (k * 64 * 8, t * 64 * 8) bit matrix each."""
    from ceph_tpu.ops import clay_kernel
    getattr(clay_kernel, f"clay_{program}").lower(
        _spec(one_chip, (8 * 64 * 8, t * 64 * 8), jnp.int8),
        _spec(one_chip, (s, 8, 4096), jnp.uint8), alpha=64).compile()


def test_encode_shard_map_four_chips(mesh4):
    """The engine's mesh route: one fused Pallas program per device
    under shard_map, batch split on the stripe axis."""
    from ceph_tpu.ops.gf_kernel import _G, _pallas_sharded_fn
    k, m, s, b = 8, 4, 2048, 4096
    rows = NamedSharding(mesh4, PartitionSpec(("dp", "ec"), None, None))
    rep = NamedSharding(mesh4, PartitionSpec())
    compiled = _pallas_sharded_fn(rows, k, m, b).lower(
        _spec(rows, (s, k, b), jnp.uint8),
        _spec(rep, (_G * k * 8, _G * m * 8), jnp.int8)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (out_sh,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out_sh.device_set) == 4


# -- CRUSH column kernels -----------------------------------------------------

N_PGS = 65536


@pytest.fixture(scope="module")
def columns():
    """PallasColumns over the 250-host x 40-OSD deployment map."""
    crush_map, rid, _rw = build_skewed_two_level_map(250, 40)
    return _columns_of(detect(crush_map, rid))


def _columns_of(fr):
    """The column kernels of the rule's shape class on a TPU, and its
    tables (host arrays: only their shapes are compiled against)."""
    shape = shape_of(fr, pallas=True)
    return FastMapper(shape)._pallas, build_tables(fr, shape)


#: the two-stage schedule's shapes at numrep 3: every lane gets
#: R = numrep + 1 columns, then FastMapper.STAGE2_CAP overflowing lanes
#: get R = numrep + DEFAULT_BLOCK
STAGES = [(N_PGS, 4), (4096, 9)]
#: ... and of a pool of 1,048,576 PGs in one batch (the cell
#: crush10k.weight_churn_1m): stage 2 holds one lane in
#: FastMapper.STAGE2_SHARE of the batch
STAGES_1M = [(1 << 20, 4), ((1 << 20) // 16, 9)]


@pytest.mark.parametrize("n,R", STAGES + STAGES_1M)
def test_root_columns(one_chip, columns, n, R):
    pc, tables = columns
    text = _compile(lambda xs, *t: pc.root_columns(xs, t, R),
                    _spec(one_chip, (n,), jnp.uint32),
                    *(_spec(one_chip, t.shape, t.dtype) for t in tables))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,R", STAGES + STAGES_1M)
def test_leaf_columns(one_chip, columns, n, R):
    pc, tables = columns
    text = _compile(lambda xs, pos, *t: pc.leaf_columns(xs, pos, t, R),
                    _spec(one_chip, (n,), jnp.uint32),
                    _spec(one_chip, (R, n), jnp.int32),
                    *(_spec(one_chip, t.shape, t.dtype) for t in tables))
    assert "tpu_custom_call" in text


def test_leaf_columns_under_the_four_chip_shard_map(mesh4, columns):
    """crush10k.weight_churn_x4's route: the single-stage R = 9 leaf
    call on each chip's 16,384 lanes, two columns a slab, the tables
    replicated; Mosaic refuses a kernel over the scoped VMEM limit."""
    pc, tables = columns
    assert (pc.S_leaf, pc.columns_per_slab) == (64, 2)
    R, n = 9, 4 * 16384
    rows = NamedSharding(mesh4, PartitionSpec(("dp", "ec")))
    cols = NamedSharding(mesh4, PartitionSpec(None, ("dp", "ec")))
    rep = NamedSharding(mesh4, PartitionSpec())
    fn = jax.jit(jax.shard_map(
        lambda xs, pos, *t: pc.leaf_columns(xs, pos, t, R), mesh=mesh4,
        in_specs=(rows.spec, cols.spec) + (rep.spec,) * len(tables),
        out_specs=cols.spec, check_vma=False))
    compiled = fn.lower(_spec(rows, (n,), jnp.uint32),
                        _spec(cols, (R, n), jnp.int32),
                        *(_spec(rep, t.shape, t.dtype)
                          for t in tables)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (out_sh,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out_sh.device_set) == 4


@pytest.mark.parametrize("widest,lanes", [(16, 32), (30, 32), (100, 128),
                                          (130, 256)])
def test_leaf_columns_of_each_group_width(one_chip, widest, lanes):
    """Four r-columns a slab (a host of 16 takes the 32-lane floor, so
    four columns' one-hot products stay inside the scoped VMEM), one,
    and one column over two slabs: each layout compiles at stage 2's
    shape of the 1 Mi cell."""
    crush_map, rid, _rw = build_skewed_two_level_map(250, widest)
    pc, tables = _columns_of(detect(crush_map, rid))
    assert pc.S_leaf == lanes
    R, n = 9, (1 << 20) // 16
    text = _compile(lambda xs, pos, *t: pc.leaf_columns(xs, pos, t, R),
                    _spec(one_chip, (n,), jnp.uint32),
                    _spec(one_chip, (R, n), jnp.int32),
                    *(_spec(one_chip, t.shape, t.dtype) for t in tables))
    assert "tpu_custom_call" in text


# R = tries + numrep is the cannot-overflow recompute
@pytest.mark.parametrize("n,R", STAGES + [(N_PGS, 54)] + STAGES_1M)
def test_consume_columns(one_chip, n, R):
    col = _spec(one_chip, (R, n), jnp.int32)
    text = _compile(
        functools.partial(ps.consume_columns, numrep=3, tries=51),
        col, col, _spec(one_chip, (R, n), jnp.bool_))
    assert "tpu_custom_call" in text


# what FastMapper._winners_cols runs in XLA between the Pallas calls:
# stage 1's and stage 2's planes of the 1 Mi cell, a chip's share of four
@pytest.mark.parametrize("planes,n", [(4, 1 << 20), (9, (1 << 20) // 16),
                                      (9, N_PGS // 4)])
def test_is_out_of_the_winner_planes(one_chip, planes, n):
    """The reweight words of the winner planes come by a one-hot
    product the compiler fuses with its one-hot and its select: no
    gather (7-11 ns a cell on the chip), and nothing of table rows x
    planes x lanes is stored."""
    from ceph_tpu.crush.types import padded_osds
    from ceph_tpu.ops.crush_kernel import out_columns, reweight_words
    compiled = jax.jit(
        lambda rw, ids, xs: out_columns(reweight_words(rw), ids, xs)).lower(
        _spec(one_chip, (padded_osds(10000),), jnp.int64),
        _spec(one_chip, (planes, n), jnp.int32),
        _spec(one_chip, (n,), jnp.uint32)).compile()
    text = compiled.as_text()
    assert "convolution" in text
    assert "gather" not in text and " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 * planes * n


def test_compact_rows_of_1m_lanes(one_chip):
    """NONE holes leave the (numrep, N) selections by a select network
    and one transpose: no row sort, no take_along_axis."""
    from ceph_tpu.crush.fastpath import _compact_rows
    text = _compile(functools.partial(_compact_rows, result_max=3),
                    _spec(one_chip, (3, 1 << 20), jnp.int32))
    assert "gather" not in text and " sort(" not in text


def test_ln_f32_table(one_chip):
    """The eager program _ln_f32_bound measures the filter's error
    bound with."""
    text = _compile(ps._ln_f32_table,
                    _spec(one_chip, (128, 512), jnp.int32))
    assert "tpu_custom_call" in text


def test_froot_columns(one_chip, monkeypatch):
    """The approx-filter root kernel runs on flat buckets of 512-1024
    items (not the smoke's map); its error bound is a measurement on the
    chip, so the test supplies a value of the measured order."""
    fmap, _root, frid = build_flat_map(600)
    pc, tables = _columns_of(detect(fmap, frid))
    assert 512 <= pc.S_root <= 1024
    monkeypatch.setattr(ps, "_ln_f32_bound", lambda interpret: 2.0 ** 22)
    text = _compile(lambda xs, *t: pc.froot_columns(xs, t, 4),
                    _spec(one_chip, (8192,), jnp.uint32),
                    *(_spec(one_chip, t.shape, t.dtype) for t in tables))
    assert "tpu_custom_call" in text


# -- checksum, compression, placement ladder ----------------------------------

@pytest.mark.parametrize("s,w", [(128, 4096), (16, 65536), (4, 262144)])
def test_block_digest(one_chip, s, w):
    """One shard write's BlueStore blocks, 128 x 4 KiB (also the scrub
    digest; the two channels share the program), and deep scrub's wide
    rows, which go through the segment product and the fold.  The
    compiled program is a matrix product and holds no loop."""
    from ceph_tpu.ops import checksum_kernel as ck
    lin = tuple(_spec(one_chip, m.shape, m.dtype)
                for m in ck.linear_operands(w))
    text = ck._jit_digest().lower(
        _spec(one_chip, (s, w), jnp.uint8),
        _spec(one_chip, (s, 32), jnp.uint32),
        _spec(one_chip, (s, 4), jnp.uint8), lin, w=w).compile().as_text()
    assert "convolution(" in text or " dot(" in text
    assert "while(" not in text


def test_bitplane_transpose(one_chip):
    from ceph_tpu.ops.compression_kernel import _jit_planes
    _jit_planes().lower(_spec(one_chip, (128, 4096), jnp.uint8)).compile()


@pytest.mark.parametrize("erasure,n_pgs", [(False, 1024), (True, 1024),
                                            (False, 1 << 20)])
def test_placement_ladder(one_chip, erasure, n_pgs):
    from ceph_tpu.crush.types import padded_osds
    from ceph_tpu.ops.placement_kernel import _ladder_jit
    w, pairs, n_osds = (12 if erasure else 3), 1, padded_osds(10000)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    _ladder_jit(erasure).lower(
        i32((n_pgs, w)),                                   # raw
        _spec(one_chip, (n_pgs,), jnp.uint32),             # pps
        i32((n_pgs,)), i32((n_pgs, w)), i32((n_pgs,)),     # raw_len, upmap
        i32((n_pgs, pairs, 2)),                            # upmap items
        i32((n_pgs, w)), i32((n_pgs,)), i32((n_pgs,)),     # temps
        i32((n_osds,)),                                    # state
        _spec(one_chip, (n_osds,), jnp.int64),             # weight
        i32((n_osds,)), i32(())).compile()       # affinity, max_osd


def test_mapping_delta_diff_of_two_1m_row_tables(one_chip):
    """The epoch diff of two packed tables of a 1,048,576-PG pool of
    size 3 (2 * 3 + 4 words a row), under its own name."""
    from ceph_tpu.osd.mapping import _delta_diff_program
    table = _spec(one_chip, (1 << 20, 10), jnp.int32)
    compiled = _delta_diff_program().lower(table, table).compile()
    assert "mapping_delta_diff" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 1 << 20          # a byte a row
