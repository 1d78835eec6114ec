"""Batched hierarchical mapper vs the scalar oracle — bit-exactness across
topologies, rule shapes, weights, reweights, and exhaustion corners."""

import numpy as np
import pytest

from ceph_tpu.crush import build_flat_map, build_two_level_map, crush_do_rule
from ceph_tpu.crush.builder import add_simple_rule, make_bucket
from ceph_tpu.crush.mapper_jax import BatchMapper
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    CrushMap,
    Rule,
    RuleStep,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_EMIT,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_TAKE,
    Tunables,
)

rng = np.random.default_rng(1234)


def assert_matches(m, rid, result_max, reweight, n=150):
    bm = BatchMapper(m)
    xs = rng.integers(0, 2**32, n, dtype=np.uint32)
    got = np.asarray(bm.do_rule(rid, xs, result_max,
                                np.asarray(reweight, dtype=np.int64)))
    for i, x in enumerate(xs):
        want = crush_do_rule(m, rid, int(x), result_max, list(reweight))
        mine = [int(v) for v in got[i]]
        # oracle firstn rows are dense; indep rows are positional — compare
        # against the dense compaction first, positional prefix second
        compact = [v for v in mine if v != CRUSH_ITEM_NONE]
        assert want in (compact, mine[:len(want)]), \
            f"x={x}: want={want} got={mine}"


def test_flat_firstn_and_indep():
    m, _root, rid = build_flat_map(20)
    assert_matches(m, rid, 3, [0x10000] * 20)
    assert_matches(m, 1, 6, [0x10000] * 20)


def test_two_level_chooseleaf_firstn():
    m, _root, rid = build_two_level_map(8, 4)
    assert_matches(m, rid, 3, [0x10000] * 32)


def test_two_level_chooseleaf_indep_with_tries():
    m, _root, _ = build_two_level_map(6, 3)
    rid = m.add_rule(Rule(ruleset=9, type=3, min_size=1, max_size=20, steps=[
        RuleStep(RULE_SET_CHOOSELEAF_TRIES, 5, 0),
        RuleStep(RULE_TAKE, -1, 0),
        RuleStep(RULE_CHOOSELEAF_INDEP, 0, 1),
        RuleStep(RULE_EMIT, 0, 0)]))
    assert_matches(m, rid, 5, [0x10000] * 18)


def test_multistep_choose_then_choose():
    m, _root, _ = build_two_level_map(8, 4)
    rid = m.add_rule(Rule(ruleset=8, type=1, min_size=1, max_size=10, steps=[
        RuleStep(RULE_TAKE, -1, 0),
        RuleStep(RULE_CHOOSE_FIRSTN, 3, 1),
        RuleStep(RULE_CHOOSE_FIRSTN, 1, 0),
        RuleStep(RULE_EMIT, 0, 0)]))
    assert_matches(m, rid, 3, [0x10000] * 32)


def test_weighted_hosts_with_reweight_outs():
    m = CrushMap()
    m.max_devices = 24
    hosts = []
    for h in range(6):
        osds = list(range(h * 4, h * 4 + 4))
        wts = [int(w) for w in rng.integers(0x8000, 0x30000, 4)]
        hid = -(h + 2)
        m.add_bucket(make_bucket(hid, CRUSH_BUCKET_STRAW2, 1, osds, wts))
        hosts.append(hid)
    m.add_bucket(make_bucket(-1, CRUSH_BUCKET_STRAW2, 2, hosts,
                             [m.bucket(h).weight for h in hosts]))
    rid = add_simple_rule(m, -1, 1, "firstn")
    rw = [0x10000] * 24
    rw[5] = 0
    rw[11] = 0x4000
    rw[17] = 0
    assert_matches(m, rid, 3, rw)


def test_exhaustion_returns_short_or_none():
    m, _root, rid = build_two_level_map(3, 2)
    assert_matches(m, rid, 6, [0x10000] * 6, n=80)


def test_negative_numrep_means_result_max_minus():
    # "choose firstn -1 type 0" places result_max-1 items (mapper.c:1009-1014)
    m, _root, _ = build_flat_map(12)
    rid = m.add_rule(Rule(ruleset=5, type=1, min_size=1, max_size=10, steps=[
        RuleStep(RULE_TAKE, -1, 0),
        RuleStep(RULE_CHOOSE_FIRSTN, -1, 0),
        RuleStep(RULE_EMIT, 0, 0)]))
    assert_matches(m, rid, 3, [0x10000] * 12, n=60)


def test_fastpath_detected_for_canonical_rules():
    from ceph_tpu.crush import fastpath
    m, _root, rid = build_two_level_map(8, 4)
    fr = fastpath.detect(m, rid)
    assert fr is not None and fr.kind == "chooseleaf"
    mf, _root2, ridf = build_flat_map(16)
    fr2 = fastpath.detect(mf, ridf)
    assert fr2 is not None and fr2.kind == "choose_flat"
    # indep rule on the flat map is not fast-pathed
    assert fastpath.detect(mf, 1) is None


def test_fastpath_overflow_falls_back_exactly():
    """Tiny block forces the lax.cond full-range recompute; results must
    still match the oracle bit for bit (heavy rejection: most OSDs out)."""
    import functools
    import jax
    from ceph_tpu.crush import fastpath
    m, _root, rid = build_two_level_map(4, 3)
    rw = [0] * 12
    rw[1] = 0x10000
    rw[7] = 0x6000
    rw[10] = 0x2000  # nearly everything out -> long retry ladders
    fr = fastpath.detect(m, rid)
    assert fr is not None
    shape = fastpath.shape_of(fr)
    fm = fastpath.FastMapper(shape)
    xs = rng.integers(0, 2**32, 100, dtype=np.uint32)
    got = np.asarray(jax.jit(functools.partial(fm.run, result_max=3, block=1))(
        xs, np.asarray(rw, dtype=np.int64),
        fastpath.build_tables(fr, shape)))
    for i, x in enumerate(xs):
        want = crush_do_rule(m, rid, int(x), 3, rw)
        compact = [int(v) for v in got[i] if v != CRUSH_ITEM_NONE]
        assert compact == want, f"x={x}: want={want} got={compact}"


def test_fastpath_vary_r_zero():
    m, _root, rid = build_two_level_map(5, 4)
    m.tunables.chooseleaf_vary_r = 0
    assert_matches(m, rid, 3, [0x10000] * 20, n=100)


def test_fastpath_uneven_host_sizes():
    m = CrushMap()
    m.max_devices = 16
    sizes = [1, 3, 5, 7]
    hosts, base = [], 0
    for h, sz in enumerate(sizes):
        osds = list(range(base, base + sz))
        base += sz
        hid = -(h + 2)
        m.add_bucket(make_bucket(hid, CRUSH_BUCKET_STRAW2, 1, osds,
                                 [0x10000 + 0x1000 * i for i in range(sz)]))
        hosts.append(hid)
    m.add_bucket(make_bucket(-1, CRUSH_BUCKET_STRAW2, 2, hosts,
                             [m.bucket(h).weight for h in hosts]))
    rid = add_simple_rule(m, -1, 1, "firstn")
    rw = [0x10000] * 16
    rw[0] = 0x8000
    assert_matches(m, rid, 3, rw, n=120)


def test_invalid_ruleno_returns_empty():
    m, _root, _rid = build_flat_map(8)
    bm = BatchMapper(m)
    out = np.asarray(bm.do_rule(99, np.arange(16, dtype=np.uint32), 3,
                                np.full(8, 0x10000, dtype=np.int64)))
    assert (out == CRUSH_ITEM_NONE).all()
    # matching the scalar oracle's empty result
    assert crush_do_rule(m, 99, 1, 3, [0x10000] * 8) == []


def test_non_straw2_map_rejected():
    m, _root, _rid = build_flat_map(8, alg=CRUSH_BUCKET_STRAW)
    with pytest.raises(ValueError, match="straw2"):
        BatchMapper(m)


def test_legacy_tunables_rejected():
    m, _root, _rid = build_flat_map(8)
    m.tunables = Tunables.legacy()
    with pytest.raises(ValueError, match="modern tunables"):
        BatchMapper(m)


def test_two_stage_pallas_schedule_interpret():
    """The two-stage _run_pallas schedule (R1 probe, argsort compaction,
    scatter-merge, cap overflow guard) vs the XLA ladder, in interpret
    mode — the TPU-only glue otherwise never runs in CI."""
    import jax.numpy as jnp

    from ceph_tpu.crush.fastpath import FastMapper, detect, tables_of

    crush_map, _root, rid = build_two_level_map(20, 4)
    # small tries -> small Rf fallback range: interpret-mode tracing of
    # the full-range cond branch is minutes-slow at the default 51
    crush_map.tunables.choose_total_tries = 7
    wrng = np.random.default_rng(11)
    for b in crush_map.buckets:
        if b is not None and b.type == 1:
            b.item_weights = [int(w) for w in
                              wrng.integers(0x8000, 0x20000, b.size)]
            b.weight = sum(b.item_weights)
    root = crush_map.bucket(-1)
    root.item_weights = [crush_map.bucket(h).weight for h in root.items]
    root.weight = sum(root.item_weights)
    fr = detect(crush_map, rid)
    n_osds = 80
    reweight = np.full(n_osds, 0x10000, dtype=np.int64)
    reweight[::7] = 0x4000   # heavy rejection -> stage-2 lanes exist
    reweight[::13] = 0
    rw = jnp.asarray(reweight)
    xs = jnp.asarray(np.random.default_rng(2).integers(
        0, 2 ** 32, (1024,), dtype=np.uint32))

    ft = tables_of(fr, pallas=True, interpret=True)
    tables = ft.on()
    fm = FastMapper(ft.shape)
    fm.TWO_STAGE_MIN = 512     # force the two-stage path at test size
    fm.STAGE2_CAP = 512
    res_two = np.asarray(fm.run(xs, rw, tables, 3))

    ft_xla = tables_of(fr, pallas=False)
    res_xla = np.asarray(FastMapper(ft_xla.shape).run(
        xs, rw, ft_xla.on(), 3))
    np.testing.assert_array_equal(res_two, res_xla)

    # cap overflow guard: capacity 8 certainly overflows -> whole-batch
    # recompute path, still exact
    fm.STAGE2_CAP, fm.STAGE2_SHARE = 8, 1 << 20
    res_cap = np.asarray(fm.run(xs, rw, tables, 3))
    np.testing.assert_array_equal(res_cap, res_xla)

    # the capacity grows with the batch: one lane in four of 1,024
    # holds what 8 could not, through the merge and not the guard
    fm.STAGE2_SHARE = 4
    res_share = np.asarray(fm.run(xs, rw, tables, 3))
    np.testing.assert_array_equal(res_share, res_xla)


@pytest.mark.parametrize("kind,n,numrep,result_max", [
    ("chooseleaf", 32768, 0, 3),     # the two-stage schedule as it ships
    ("chooseleaf", 1000, 0, 3),      # one stage, a batch the block pads
    ("choose_flat", 32768, 0, 3),
    ("choose_flat", 1000, 2, 4),     # a result wider than numrep
])
def test_pallas_route_equals_the_scalar_oracle(kind, n, numrep, result_max):
    """`FastMapper.run` on the Pallas route (interpret mode), with
    nothing patched — at 32,768 lanes the two-stage schedule, below it
    one stage — held to `mapper_ref` itself on sampled lanes, on a map
    with OSDs out, at partial and at full reweight."""
    import jax.numpy as jnp

    from ceph_tpu.crush.fastpath import FastMapper, detect, tables_of

    if kind == "chooseleaf":
        crush_map, _root, rid = build_two_level_map(20, 4)
        n_osds = 80
    else:
        crush_map, _root, _rid = build_flat_map(24)
        n_osds = 24
        rid = crush_map.add_rule(Rule(
            ruleset=7, type=1, min_size=1, max_size=10, steps=[
                RuleStep(RULE_TAKE, -1, 0),
                RuleStep(RULE_CHOOSE_FIRSTN, numrep, 0),
                RuleStep(RULE_EMIT, 0, 0)]))
    # few tries, so a short full range: interpret mode takes minutes
    # over the 54 columns of the default 51
    crush_map.tunables.choose_total_tries = 7
    reweight = np.full(n_osds, 0x10000, dtype=np.int64)
    reweight[::7] = 0x4000
    reweight[::13] = 0
    local = np.random.default_rng((n, numrep))
    xs = local.integers(0, 2 ** 32, n, dtype=np.uint32)
    ft = tables_of(detect(crush_map, rid), pallas=True, interpret=True)
    assert ft.shape.kind == kind
    fm = FastMapper(ft.shape)
    assert (n >= fm.TWO_STAGE_MIN) == (n == 32768)
    got = np.asarray(fm.run(jnp.asarray(xs), jnp.asarray(reweight),
                            ft.on(), result_max))
    assert got.shape == (n, result_max)
    short = 0
    for i in local.choice(n, 400, replace=False):
        want = crush_do_rule(crush_map, rid, int(xs[i]), result_max,
                             list(reweight))
        short += len(want) < result_max
        assert [int(v) for v in got[i]] == \
            want + [CRUSH_ITEM_NONE] * (result_max - len(want)), int(xs[i])
    if numrep:
        assert short == 400     # every row NONE-filled past numrep


# -- tree buckets (batched descent vs the scalar oracle) ---------------------

def test_tree_hosts_chooseleaf_firstn():
    from ceph_tpu.crush.types import CRUSH_BUCKET_TREE
    m, _root, rid = build_two_level_map(8, 4, host_alg=CRUSH_BUCKET_TREE)
    assert_matches(m, rid, 3, [0x10000] * 32)


def test_tree_root_flat_firstn_and_indep():
    from ceph_tpu.crush.types import CRUSH_BUCKET_TREE
    m, _root, rid = build_flat_map(17, alg=CRUSH_BUCKET_TREE)
    assert_matches(m, rid, 3, [0x10000] * 17)
    assert_matches(m, 1, 5, [0x10000] * 17)


def test_tree_nonuniform_weights_and_reweight():
    from ceph_tpu.crush.types import CRUSH_BUCKET_TREE
    wrng = np.random.default_rng(42)
    weights = [int(w) for w in wrng.integers(0x4000, 0x30000, 21)]
    m, _root, rid = build_flat_map(21, weights=weights,
                                   alg=CRUSH_BUCKET_TREE)
    reweight = [int(w) for w in wrng.integers(0, 0x10001, 21)]
    reweight[2] = 0
    assert_matches(m, rid, 4, reweight)


def test_mixed_straw2_root_tree_hosts():
    # straw2 root over tree host buckets: both algs inside one descent
    from ceph_tpu.crush.types import CRUSH_BUCKET_TREE
    m, _root, rid = build_two_level_map(
        6, 5, host_alg=CRUSH_BUCKET_TREE, root_alg=CRUSH_BUCKET_STRAW2)
    assert_matches(m, rid, 3, [0x10000] * 30)
