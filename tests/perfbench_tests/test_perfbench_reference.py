"""The benchmark's plain references against the repo's own oracles.

perfbench/reference/crush_plain.py imports nothing of the program; here
it is held against ceph_tpu's scalar CRUSH and OSDMap pipeline, which it
was written beside, on maps of the benchmark's shape."""

import numpy as np
import pytest

from ceph_tpu.crush import build_two_level_map, crush_do_rule
from ceph_tpu.crush.hashfn import crush_hash32_2, crush_hash32_3
from ceph_tpu.crush.mapper_ref import crush_ln
from ceph_tpu.osd import OSDMap, PGPool
from perfbench.reference import crush_plain, payloads


def twin_maps(hosts, per_host, seed, pg_num=256):
    rng = np.random.default_rng(seed)
    n = hosts * per_host
    item_w = rng.integers(0x8000, 0x20000, (hosts, per_host))
    reweight = np.full(n, 0x10000, dtype=np.int64)
    idx = rng.permutation(n)
    reweight[idx[:n // 10]] = 0x8000
    reweight[idx[n // 10:n // 10 + max(1, n // 50)]] = 0
    crush, _root, rid = build_two_level_map(hosts, per_host)
    root = crush.bucket(-1)
    plain_hosts = {}
    for h, hid in enumerate(root.items):
        b = crush.bucket(hid)
        b.item_weights = [int(w) for w in item_w[h]]
        b.weight = sum(b.item_weights)
        plain_hosts[int(hid)] = crush_plain.Bucket(
            int(hid), np.array(b.items, dtype=np.int64),
            item_w[h].astype(np.int64))
    root.item_weights = [crush.bucket(h).weight for h in root.items]
    root.weight = sum(root.item_weights)
    plain = crush_plain.PlainMap(
        crush_plain.Bucket(-1, np.array(root.items, dtype=np.int64),
                           item_w.sum(axis=1).astype(np.int64)),
        plain_hosts, [int(w) for w in reweight], [True] * n, 1, pg_num, 3)
    m = OSDMap(crush=crush, epoch=2)
    m.set_max_osd(n)
    for o in range(n):
        m.osd_state[o] = 3
        m.osd_weight[o] = int(reweight[o])
    m.pools[1] = PGPool(pool_id=1, size=3, crush_rule=rid, pg_num=pg_num)
    return crush, rid, m, plain


def test_hashes_and_ln_match_on_vectors_and_scalars():
    rng = np.random.default_rng(0)
    a, b, c = (rng.integers(0, 2**32, 64) for _ in range(3))
    ids = rng.integers(-300, 10000, 64)
    assert crush_plain.hash32_2(a, b).tolist() == [
        crush_hash32_2(int(x), int(y)) for x, y in zip(a, b)]
    assert crush_plain.hash32_3(int(a[0]), ids, 5).tolist() == [
        crush_hash32_3(int(a[0]), int(i), 5) for i in ids]
    for u in list(range(0, 0x10000, 37)) + [0, 1, 0xFFFF]:
        assert crush_plain.crush_ln(u) == crush_ln(u)


@pytest.mark.parametrize("hosts,per_host,seed,n_x",
                         [(8, 4, 1, 400), (250, 40, 2, 40)])
def test_do_rule_matches_the_scalar_oracle(hosts, per_host, seed, n_x):
    crush, rid, m, plain = twin_maps(hosts, per_host, seed)
    xs = np.random.default_rng(seed).integers(0, 2**32, n_x)
    w = [int(v) for v in m.osd_weight]
    for x in xs:
        assert crush_plain.do_rule(plain, int(x)) == crush_do_rule(
            crush, rid, int(x), 3, w)


def test_up_sets_match_pg_to_up_acting_with_out_reweighted_and_down():
    _crush, _rid, m, plain = twin_maps(8, 4, 3)
    osd = int(np.flatnonzero(np.array(plain.reweight) == 0x10000)[0])
    m.osd_weight[osd] = 0x8000
    m.osd_state[(osd + 1) % 32] &= ~2           # down
    plain.reweight[osd] = 0x8000
    plain.up[(osd + 1) % 32] = False
    for pg in range(256):
        up, primary, acting, acting_primary = m.pg_to_up_acting_osds(1, pg)
        assert crush_plain.up_of(plain, pg) == (up, primary)
        assert (acting, acting_primary) == (up, primary)


def test_payloads_depend_on_seed_and_index_only():
    a = payloads.payload(2**31 + 5, 3, 4096)
    assert a == payloads.payload(2**31 + 5, 3, 4096) and len(a) == 4096
    assert a != payloads.payload(2**31 + 5, 4, 4096)
    assert a != payloads.payload(2**31 + 6, 3, 4096)
    assert payloads.object_name(7, 3) != payloads.object_name(7, 4)


# -- reed_sol_van (perfbench/reference/rs_plain.py) ---------------------------

from perfbench.reference import rs_plain      # noqa: E402

#: the coding rows that the jerasure manual prints for `reed_sol_01 7 7 8`
MANUAL_7_7 = [
    [1, 1, 1, 1, 1, 1, 1],
    [1, 199, 210, 240, 105, 121, 248],
    [1, 70, 91, 245, 56, 142, 167],
    [1, 170, 114, 42, 87, 78, 231],
    [1, 38, 236, 53, 233, 175, 65],
    [1, 64, 174, 232, 52, 237, 39],
    [1, 187, 104, 210, 211, 105, 186]]


def test_coding_matrix_is_the_one_the_jerasure_manual_prints():
    assert rs_plain.coding_matrix(7, 7).tolist() == MANUAL_7_7


@pytest.mark.parametrize("k,m", [(8, 4), (2, 2), (4, 2), (7, 3)])
def test_coding_matrix_first_row_and_column_are_ones_and_it_is_mds(k, m):
    c = rs_plain.coding_matrix(k, m).astype(int)
    assert (c[0] == 1).all() and (c[:, 0] == 1).all()
    # every square submatrix of an MDS code's coding rows is invertible;
    # here all of orders 1 and 2
    assert (c != 0).all()
    for r0 in range(m):
        for r1 in range(r0 + 1, m):
            for c0 in range(k):
                for c1 in range(c0 + 1, k):
                    det = (rs_plain.gf_mul(c[r0, c0], c[r1, c1])
                           ^ rs_plain.gf_mul(c[r0, c1], c[r1, c0]))
                    assert det != 0


def test_field_arithmetic_by_hand():
    assert rs_plain.gf_mul(2, 128) == 0x1D          # x * x^7 = x^8
    assert rs_plain.gf_mul(3, 7) == 9               # (x+1)(x^2+x+1)
    for a in (1, 2, 53, 255):
        assert rs_plain.gf_mul(a, rs_plain.gf_inv(a)) == 1


def test_shards_layout_parity_and_block_checksums():
    k, m, su = 3, 2, 8
    payload = bytes(range(1, 41))       # 40 bytes: 2 stripes of 24, padded
    shards = rs_plain.shards_of(payload, k, m, su)
    assert [len(s) for s in shards] == [16] * 5
    padded = payload + bytes(8)
    # shard s holds chunk s of stripe 0, then chunk s of stripe 1
    for s in range(k):
        assert shards[s] == (padded[s * su:(s + 1) * su]
                             + padded[24 + s * su:24 + (s + 1) * su])
    assert shards[k] == bytes(a ^ b ^ c for a, b, c in zip(*shards[:k]))
    rows = rs_plain.coding_matrix(k, m)
    want = bytes(
        rs_plain.gf_mul(int(rows[1, 0]), a)
        ^ rs_plain.gf_mul(int(rows[1, 1]), b)
        ^ rs_plain.gf_mul(int(rows[1, 2]), c) for a, b, c in zip(*shards[:k]))
    assert shards[k + 1] == want
    import zlib
    assert rs_plain.block_csums(b"abc" * 4, 8) == [
        zlib.crc32(b"abcabcab"), zlib.crc32(b"cabc" + bytes(4))]


def test_one_chunk_objects_meet_only_the_column_of_ones():
    """Why the 4 KiB cell is correct whatever the rest of the matrix is:
    with one data chunk filled, every parity chunk is that chunk."""
    payload = payloads.payload(7, 0, 4096)
    shards = rs_plain.shards_of(payload, 8, 4, 4096)
    assert shards[0] == payload and shards[1] == bytes(4096)
    assert all(s == payload for s in shards[8:])


def test_the_program_agrees_where_the_first_coding_row_is_ones_by_itself():
    """k = 7, jerasure's default and its manual's example: the points
    0..7 are closed under XOR, so the systematic Vandermonde matrix has
    a first coding row of ones before jerasure scales its columns to
    make it so, and the program, which leaves that step out, agrees."""
    from ceph_tpu.ec.jerasure import big_vandermonde_distribution_matrix
    assert np.asarray(big_vandermonde_distribution_matrix(14, 7))[
        7:].tolist() == MANUAL_7_7


@pytest.mark.xfail(strict=False, reason="the program's reed_sol_van "
                   "leaves out jerasure's column scaling: at k = 8 its "
                   "first parity is not the XOR of the data (PERF.md, "
                   "Open questions)")
def test_the_programs_reed_sol_van_matrix_is_jerasures_at_k8():
    from ceph_tpu.ec.jerasure import big_vandermonde_distribution_matrix
    got = np.asarray(big_vandermonde_distribution_matrix(12, 8))[8:]
    assert (got[0] == 1).all()
    assert got.tolist() == rs_plain.coding_matrix(8, 4).tolist()


def test_the_programs_matrix_shares_the_column_of_ones():
    from ceph_tpu.ec.jerasure import big_vandermonde_distribution_matrix
    assert (np.asarray(big_vandermonde_distribution_matrix(12, 8))[
        8:, 0] == 1).all()
