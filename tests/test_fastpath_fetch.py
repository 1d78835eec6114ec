"""The fast path's two gather-free pieces against what they replaced:
an OSD's reweight word fetched by a one-hot product (`out_columns`)
against `is_out`'s gather, and the select-network compaction
(`_compact_rows`) against `argsort` + `take_along_axis`."""

import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.crush.fastpath import _compact_rows
from ceph_tpu.crush.types import CRUSH_ITEM_NONE as NONE
from ceph_tpu.ops.crush_kernel import is_out, out_columns, reweight_words

WEIGHTS = [0, 1, 0xFFFF, 0x10000, 0x10001, 2 ** 31, 2 ** 32 - 1]
LENGTHS = [1, 127, 128, 129, 1024, 10240]


def _ids(length, rng, n=384, planes=4):
    """(planes, n) ids: the edges of the vector and of a table row in
    every plane, the rest drawn over and a little beyond the vector."""
    edges = [-1, 0, 127, 128, length - 1, length, NONE]
    ids = rng.integers(-4, length + 4, (planes, n)).astype(np.int32)
    ids[:, :len(edges)] = edges
    ids[rng.integers(0, planes, 16), rng.integers(0, n, 16)] = NONE
    return ids


@pytest.mark.parametrize("weight", WEIGHTS)
@pytest.mark.parametrize("length", LENGTHS)
def test_fetched_verdict_equals_is_out(length, weight):
    """Every edge id meets `weight` (its neighbours every other weight)
    on random x: the one-hot fetch answers as the gather does."""
    rng = np.random.default_rng((length, weight))
    reweight = rng.choice(WEIGHTS, length).astype(np.int64)
    reweight[[i for i in (0, 127, 128, length - 1) if i < length]] = weight
    ids = _ids(length, rng)
    x = rng.integers(0, 2 ** 32, ids.shape[1], dtype=np.uint32)
    want = np.asarray(is_out(reweight, jnp.asarray(ids), x[None, :]))
    got = np.asarray(out_columns(
        reweight_words(reweight), jnp.asarray(ids), jnp.asarray(x)))
    assert got.dtype == np.bool_ and got.shape == ids.shape
    np.testing.assert_array_equal(got, want)
    outside = (ids < 0) | (ids >= length)
    assert outside.any() and got[outside].all()


@pytest.mark.parametrize("weight", [0x4000, 0xC000])
def test_fetched_verdict_flips_the_same_coins(weight):
    """A partial weight keeps about weight / 0x10000 of the draws, the
    same ones as `is_out`."""
    rng = np.random.default_rng(weight)
    reweight = np.full(10240, weight, dtype=np.int64)
    ids = rng.integers(0, 10240, (3, 4096)).astype(np.int32)
    x = rng.integers(0, 2 ** 32, 4096, dtype=np.uint32)
    got = np.asarray(out_columns(
        reweight_words(reweight), jnp.asarray(ids), jnp.asarray(x)))
    np.testing.assert_array_equal(
        got, np.asarray(is_out(reweight, jnp.asarray(ids), x[None, :])))
    assert abs((~got).mean() - weight / 0x10000) < 0.03


def _compacted_by_sort(rows, result_max):
    """What `_compact_rows` was: a stable row sort and a gather, then
    NONE to the result's width."""
    order = np.argsort(rows == NONE, axis=1, kind="stable")
    out = np.take_along_axis(rows, order, axis=1)
    pad = np.full((len(rows), max(0, result_max - rows.shape[1])), NONE,
                  dtype=np.int32)
    return np.concatenate([out, pad], axis=1)[:, :result_max]


@pytest.mark.parametrize("width,result_max", [
    (w, r) for w in (1, 2, 3, 4) for r in sorted({w, w + 1, w + 3, w - 1})
    if r > 0])
def test_select_network_compacts_as_the_sort_did(width, result_max):
    """Every hole pattern of a row of `width`, in a result as wide,
    wider (NONE-filled) and narrower (cut after compaction)."""
    holes = np.array(list(itertools.product([False, True], repeat=width)))
    rows = np.where(holes, NONE, 100 + np.arange(width)).astype(np.int32)
    rows = np.concatenate([rows, rows[::-1]])
    got = np.asarray(_compact_rows(jnp.asarray(rows.T), result_max))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _compacted_by_sort(rows, result_max))


def _lowered(fn, *shapes):
    """The lowered text and the names of its operations."""
    text = jax.jit(fn).lower(*shapes).as_text()
    ops = re.findall(r"=\s*\"?(?:stablehlo|mhlo|chlo)\.(\w+)", text)
    assert ops
    return text, ops


def test_the_lowered_text_would_show_a_gather_and_a_sort():
    """The two checks below can fail: the pieces replaced lower to a
    gather and to a sort."""
    _text, ops = _lowered(
        is_out, jax.ShapeDtypeStruct((10240,), np.int64),
        jax.ShapeDtypeStruct((4, 1024), np.int32),
        jax.ShapeDtypeStruct((1, 1024), np.uint32))
    assert "gather" in ops
    _text, ops = _lowered(lambda rows: jnp.take_along_axis(
        rows, jnp.argsort(rows == NONE, axis=1), axis=1),
        jax.ShapeDtypeStruct((1024, 3), np.int32))
    assert "sort" in ops and "gather" in ops


@pytest.mark.parametrize("planes,n,osds", [
    (4, 1 << 20, 10240), (9, 1 << 16, 10240), (9, 1 << 14, 10240),
    (4, 1024, 12)])
def test_lowered_fetch_has_no_gather_and_no_sort(planes, n, osds):
    """The fast path's `is_out` as lowered for the cells' shapes (stage
    1 and stage 2 at 1 Mi lanes, a chip's share of four) and for a
    twelve-OSD map: a product, no gather, no sort, nothing 64 bits wide
    on the batch axis."""
    text, ops = _lowered(
        lambda rw, ids, x: out_columns(reweight_words(rw), ids, x),
        jax.ShapeDtypeStruct((osds,), np.int64),
        jax.ShapeDtypeStruct((planes, n), np.int32),
        jax.ShapeDtypeStruct((n,), np.uint32))
    assert "dot_general" in ops
    assert not [op for op in ops if "gather" in op or "sort" in op]
    wide = [t for t in re.findall(r"tensor<([0-9x]*)x[su]?i64>", text)
            if str(n) in t.split("x")]
    assert not wide, wide[:3]


@pytest.mark.parametrize("numrep,result_max,n", [
    (3, 3, 1 << 20), (3, 5, 1 << 16), (4, 2, 1024), (1, 1, 128)])
def test_lowered_compaction_has_no_gather_and_no_sort(numrep, result_max, n):
    _text, ops = _lowered(
        functools.partial(_compact_rows, result_max=result_max),
        jax.ShapeDtypeStruct((numrep, n), np.int32))
    assert not [op for op in ops if "gather" in op or "sort" in op]
    assert "select" in ops
