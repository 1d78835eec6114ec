"""The block digest as a GF(2) bit-matrix product (ops/checksum_kernel):
the device program against the literal per-row oracle over widths,
lengths and batches; the host-built segment and fold matrices against
a literal per-byte loop; and the shape of the lowered program — a
matrix product, no loop over the row's bytes."""

from __future__ import annotations

import zlib

import numpy as np
import pytest

from ceph_tpu.ops import checksum_kernel as ck

WIDTHS = [8, 64, 4096, 8192, 65536, 262144]
LENGTHS = ["0", "1", "3", "w-1", "w", "ragged"]


def _lengths(kind: str, w: int, s: int, rng) -> np.ndarray:
    if kind == "ragged":
        return rng.integers(0, w + 1, s)
    fixed = {"0": 0, "1": 1, "3": 3, "w-1": w - 1, "w": w}[kind]
    return np.full(s, fixed, dtype=np.int64)


def _padded_batch(lengths, w: int, rng) -> np.ndarray:
    data = np.zeros((len(lengths), w), dtype=np.uint8)
    for i, n in enumerate(lengths):
        data[i, :n] = rng.integers(0, 256, int(n), dtype=np.uint8)
    return data


def _digest(data, lengths, fn=ck.scrub_digest_batched) -> np.ndarray:
    mats, invp = ck.digest_operands(lengths, data.shape[1])
    return np.asarray(fn(data, mats, invp))


# -- the device program against the oracle ------------------------------------

@pytest.mark.parametrize("s", [1, 128])
@pytest.mark.parametrize("kind", LENGTHS)
@pytest.mark.parametrize("w", WIDTHS)
def test_digest_equals_oracle(w, kind, s):
    """Both columns, every row, exactly: crc32 of row[:L] and the
    packed 4-lane GF Horner digest, from one product (w <= SEG_WIDTH)
    or from segments and a fold (wider)."""
    rng = np.random.default_rng([w, LENGTHS.index(kind), s])
    lengths = _lengths(kind, w, s, rng)
    data = _padded_batch(lengths, w, rng)
    got = _digest(data, lengths)
    assert got.dtype == np.uint32 and got.shape == (s, 2)
    np.testing.assert_array_equal(got, ck.scrub_digest_ref(data, lengths))


@pytest.mark.parametrize("w", [2 * ck.SEG_WIDTH << i for i in range(6)])
def test_each_fold_level(w):
    """W / SEG_WIDTH = 2, 4, ... 64 segments: a byte in every segment
    (and nothing else) must reach the digest through its own block of
    the fold matrix; then a seeded ragged batch."""
    nseg = w // ck.SEG_WIDTH
    rng = np.random.default_rng(w)
    data = np.zeros((nseg + 2, w), dtype=np.uint8)
    for i in range(nseg):
        data[i, i * ck.SEG_WIDTH + int(rng.integers(ck.SEG_WIDTH))] = (
            rng.integers(1, 256))
    lengths = np.full(nseg + 2, w, dtype=np.int64)
    lengths[nseg:] = rng.integers(w // 2, w, 2)
    data[nseg:] = _padded_batch(lengths[nseg:], w, rng)
    np.testing.assert_array_equal(
        _digest(data, lengths), ck.scrub_digest_ref(data, lengths))


def test_both_channels_share_the_program():
    rng = np.random.default_rng(3)
    lengths = rng.integers(0, 4097, 128)
    data = _padded_batch(lengths, 4096, rng)
    a = _digest(data, lengths)
    before = ck.digest_jit_entries()
    b = _digest(data, lengths, ck.bluestore_digest_batched)
    assert ck.digest_jit_entries() == before
    np.testing.assert_array_equal(a, b)
    assert all(int(b[i, 0]) == zlib.crc32(data[i, :lengths[i]].tobytes())
               for i in range(128))


# -- the host-built matrices against a literal per-byte loop ------------------

def _byte_loop(row) -> tuple[int, int]:
    """The crc register after the row's bytes from a ZERO register (no
    final xor) and the packed GF digest: one table step a byte."""
    t0 = ck._crc_table()
    c = 0
    for x in row.tolist():
        c = (c >> 8) ^ int(t0[(c ^ x) & 0xFF])
    return c, ck.gf_digest_ref(row)


def _packed(bits64) -> tuple[int, int]:
    vals = [int(b) << (i % 32) for i, b in enumerate(bits64)]
    return sum(vals[:32]), sum(vals[32:])


@pytest.mark.parametrize("bit", range(8))
def test_segment_matrix_rows_are_the_byte_loop(bit):
    c = 16
    m = ck._segment_matrix(c)
    assert m.shape == (8 * c, 64) and m.dtype == np.int8
    assert set(np.unique(m)) <= {0, 1}
    for p in range(c):
        row = np.zeros(c, dtype=np.uint8)
        row[p] = 1 << bit
        assert _packed(m[bit * c + p]) == _byte_loop(row), (p, bit)


def test_segment_matrix_is_linear_over_a_random_row():
    c = 16
    rng = np.random.default_rng(16)
    row = rng.integers(0, 256, c, dtype=np.uint8)
    planes = np.concatenate([(row >> b) & 1 for b in range(8)])
    assert _packed((planes @ ck._segment_matrix(c)) & 1) == _byte_loop(row)


@pytest.mark.parametrize("nseg", [2, 3, 8, 64])
def test_fold_matrix_joins_segments(nseg):
    c = 16
    rng = np.random.default_rng(nseg)
    row = rng.integers(0, 256, nseg * c, dtype=np.uint8)
    seg = row.reshape(nseg, c)
    planes = np.concatenate([(seg >> b) & 1 for b in range(8)], axis=1)
    partial = (planes.astype(np.int64) @ ck._segment_matrix(c)) & 1
    folded = (partial.reshape(-1) @ ck._fold_matrix(nseg, c)) & 1
    assert _packed(folded) == _byte_loop(row)


def test_padded_init_is_the_register_over_zero_bytes():
    for w in (8, 4096, 65536):
        reg = 0xFFFFFFFF
        t0 = ck._crc_table()
        for _ in range(min(w, 4096)):
            reg = (reg >> 8) ^ int(t0[reg & 0xFF])
        if w > 4096:
            reg = int(ck._apply_cols(ck._zero_pow_cols(w - 4096),
                                     np.uint32(reg)))
        assert _packed(list(ck._padded_init_bits(w)) + [0] * 32)[0] == reg


def test_constant_operands_are_built_once():
    for w in (64, 4096, 65536):
        assert all(a is b for a, b in zip(ck.linear_operands(w),
                                          ck.linear_operands(w)))
    # every wide width shares the one segment matrix
    assert ck.linear_operands(8192)[0] is ck.linear_operands(262144)[0]
    assert ck.linear_operands(4096)[0] is ck.linear_operands(8192)[0]
    assert [m.shape for m in ck.linear_operands(262144)] == [
        (8 * ck.SEG_WIDTH, 64), (64 * 64, 64)]


# -- the shape of the program --------------------------------------------------

@pytest.mark.parametrize("s,w", [(128, 4096), (4, 262144)])
def test_lowered_program_is_a_product_and_holds_no_loop(s, w):
    """A later change cannot bring a serial loop over the row back
    unseen, nor close the matrices into the program as literals (the
    GF multiplication table, 64 KiB, is the largest that may stay)."""
    import jax
    import jax.numpy as jnp
    lin = ck.linear_operands(w)
    text = ck._jit_digest().lower(
        jax.ShapeDtypeStruct((s, w), jnp.uint8),
        jax.ShapeDtypeStruct((s, 32), jnp.uint32),
        jax.ShapeDtypeStruct((s, 4), jnp.uint8), lin, w=w).as_text()
    assert "dot_general" in text
    assert "while" not in text
    assert text.count("dot_general") == len(lin)
    assert len(text) < 400_000, len(text)
