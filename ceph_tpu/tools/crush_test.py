"""crushtool --test analog (src/tools/crushtool.cc -> CrushTester::test,
src/crush/CrushTester.cc:472-560) with the per-x loop replaced by one batched
device call.

Usage:
    python -m ceph_tpu.tools.crush_test --num-rep 3 --min-x 0 --max-x 1023 \
        [--rule N] [--show-utilization] [--show-statistics] [--show-mappings] \
        [--osds N | --hosts H --per-host P] [--backend tpu|scalar]

Output matches the reference's shape: per-rule "rule N (name) num_rep R
result size == S:\tX/Y" lines, optional per-device utilization, and the
choose-tries-style batch statistics.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from ceph_tpu.crush import build_flat_map, build_two_level_map, crush_do_rule
from ceph_tpu.crush.types import (
    CRUSH_ITEM_NONE, RULE_CHOOSE_FIRSTN, RULE_EMIT, RULE_TAKE)


def _flat_firstn_operands(m, rid: int):
    """(ids, item_weights) when rule ``rid`` is the shape
    ``ops.crush_kernel.flat_firstn`` computes — ``take <straw2 root of
    devices> / choose firstn 0 osd / emit`` with stock tunables — else
    None and the caller uses the generic rule engine."""
    from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2
    rule = m.rules[rid] if 0 <= rid < m.max_rules else None
    if rule is None or len(rule.steps) != 3:
        return None
    take, choose, emit = rule.steps
    if (take.op != RULE_TAKE or choose.op != RULE_CHOOSE_FIRSTN
            or choose.arg1 != 0 or choose.arg2 != 0
            or emit.op != RULE_EMIT):
        return None
    root = m.bucket(take.arg1)
    if (root is None or root.alg != CRUSH_BUCKET_STRAW2
            or any(i < 0 for i in root.items)
            or m.tunables != type(m.tunables)()
            or m.choose_args or m.class_bucket):
        return None
    return (np.asarray(root.items, dtype=np.int32),
            np.asarray(root.item_weights, dtype=np.int64))


def _dispatch_flat_firstn(flat, xs, num_rep: int, weight) -> list[list[int]]:
    """Bulk remap through the device dispatch engine: the x range rides
    ``submit_flat_firstn`` in engine-sized chunks, so chunk N+1's h2d
    overlaps chunk N's compute and concurrent callers against the same
    map coalesce into shared device calls (docs/PERF.md)."""
    from ceph_tpu.common.context import default_context
    from ceph_tpu.ops.dispatch import submit_flat_firstn
    ids, weights = flat
    reweight = np.asarray(weight, dtype=np.int64)
    eng = default_context().dispatch_engine()
    futs = [submit_flat_firstn(eng, xs[i:i + eng.max_stripes], ids,
                               weights, reweight, numrep=num_rep)
            for i in range(0, len(xs), eng.max_stripes)]
    out = np.concatenate([np.asarray(f.result()) for f in futs], axis=0)
    return [[int(v) for v in row if v != CRUSH_ITEM_NONE] for row in out]


def run_test(m, rules, min_x: int, max_x: int, num_rep: int,
             backend: str = "tpu", reweight=None,
             show_utilization: bool = False, show_mappings: bool = False,
             out=sys.stdout) -> dict:
    n = m.max_devices
    weight = reweight if reweight is not None else [0x10000] * n
    xs = np.arange(min_x, max_x + 1, dtype=np.uint32)
    stats = {}
    for rid in rules:
        t0 = time.perf_counter()
        if backend == "tpu":
            flat = _flat_firstn_operands(m, rid)
            if flat is not None:
                rows = _dispatch_flat_firstn(flat, xs, num_rep, weight)
            else:
                from ceph_tpu.crush.mapper_jax import BatchMapper
                bm = BatchMapper(m)
                res = np.asarray(bm.do_rule(
                    rid, xs, num_rep, np.asarray(weight, dtype=np.int64)))
                rows = [[int(v) for v in row if v != CRUSH_ITEM_NONE]
                        for row in res]
        else:
            rows = [crush_do_rule(m, rid, int(x), num_rep, list(weight))
                    for x in xs]
        dt = time.perf_counter() - t0
        sizes = {}
        util = np.zeros(n, dtype=np.int64)
        for row in rows:
            sizes[len(row)] = sizes.get(len(row), 0) + 1
            for o in row:
                util[o] += 1
        for size, count in sorted(sizes.items()):
            print(f"rule {rid} num_rep {num_rep} result size == {size}:\t"
                  f"{count}/{len(xs)}", file=out)
        if show_mappings:
            for x, row in zip(xs, rows):
                print(f"CRUSH rule {rid} x {x} {row}", file=out)
        if show_utilization:
            expected = util.sum() / max((util > 0).sum(), 1)
            for o in range(n):
                if util[o] or weight[o]:
                    print(f"  device {o}:\t\tstored : {util[o]}\t "
                          f"expected : {expected:.2f}", file=out)
        stats[rid] = {"sizes": sizes, "util": util.tolist(),
                      "elapsed_s": dt, "mappings_per_s": len(xs) / dt}
    return stats


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="crush_test")
    p.add_argument("--num-rep", type=int, default=3)
    p.add_argument("--min-x", type=int, default=0)
    p.add_argument("--max-x", type=int, default=1023)
    p.add_argument("--rule", type=int, default=None)
    p.add_argument("--osds", type=int, default=None,
                   help="flat map with N osds")
    p.add_argument("--hosts", type=int, default=16)
    p.add_argument("--per-host", type=int, default=4)
    p.add_argument("--backend", choices=["tpu", "scalar"], default="tpu")
    p.add_argument("--show-utilization", action="store_true")
    p.add_argument("--show-statistics", action="store_true")
    p.add_argument("--show-mappings", action="store_true")
    args = p.parse_args(argv)

    if args.osds is not None:
        m, _root, rule = build_flat_map(args.osds)
    else:
        m, _root, rule = build_two_level_map(args.hosts, args.per_host)
    rules = [args.rule] if args.rule is not None else [rule]
    stats = run_test(m, rules, args.min_x, args.max_x, args.num_rep,
                     backend=args.backend,
                     show_utilization=args.show_utilization,
                     show_mappings=args.show_mappings)
    if args.show_statistics:
        for rid, s in stats.items():
            print(f"rule {rid}: {s['mappings_per_s']:.0f} mappings/s "
                  f"({s['elapsed_s']*1e3:.1f} ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
