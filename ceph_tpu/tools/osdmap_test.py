"""osdmaptool --test-map-pgs analog (src/tools/osdmaptool.cc:32-42,184-196):
map every PG of every pool through the full placement pipeline and print the
distribution summary (avg/min/max PGs per OSD, mapping rate).

Runs through the context's shared PG mapping service — the same
epoch-keyed cache, incremental invalidation and dispatch-engine path
the OSDs/client/balancer use — so the tool exercises (and measures)
the production mapping path, not a private mapper."""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from ceph_tpu.common.context import default_context
from ceph_tpu.crush import build_two_level_map
from ceph_tpu.osd import OSDMap, PGPool


def test_map_pgs(m: OSDMap, out=sys.stdout, dump: bool = False) -> dict:
    t0 = time.perf_counter()
    svc = default_context().mapping_service()
    svc.warm(m)
    total = np.zeros(max(m.max_osd, 1), dtype=np.int64)
    n_pgs = 0
    for pool_id, pool in m.pools.items():
        counts = svc.pg_counts(m, pool_id)
        total[:len(counts)] += counts
        n_pgs += pool.pg_num
        if dump:
            for pg in range(pool.pg_num):
                up, upp, acting, actp = svc.lookup(m, pool_id, pg)
                print(f"{pool_id}.{pg}\t{up}\t{upp}", file=out)
    dt = time.perf_counter() - t0
    in_osds = total[total > 0]
    result = {
        "pg_total": n_pgs,
        "osd_count": int((total > 0).sum()),
        "avg": float(in_osds.mean()) if in_osds.size else 0.0,
        "min": int(in_osds.min()) if in_osds.size else 0,
        "max": int(in_osds.max()) if in_osds.size else 0,
        "elapsed_s": dt,
        "pgs_per_s": n_pgs / dt if dt else 0.0,
    }
    print(f"pool pg_num sum {n_pgs}", file=out)
    print(f"size distribution: avg {result['avg']:.2f} "
          f"min {result['min']} max {result['max']} "
          f"over {result['osd_count']} osds "
          f"({result['pgs_per_s']:.0f} pg mappings/s)", file=out)
    return result


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="osdmap_test")
    p.add_argument("--hosts", type=int, default=32)
    p.add_argument("--per-host", type=int, default=4)
    p.add_argument("--pg-num", type=int, default=4096)
    p.add_argument("--size", type=int, default=3)
    p.add_argument("--test-map-pgs", action="store_true", default=True)
    p.add_argument("--test-map-pgs-dump", action="store_true")
    args = p.parse_args(argv)

    crush, _root, rule = build_two_level_map(args.hosts, args.per_host)
    m = OSDMap(crush=crush)
    n = args.hosts * args.per_host
    m.set_max_osd(n)
    for o in range(n):
        m.mark_up(o)
    m.pools[1] = PGPool(pool_id=1, size=args.size, crush_rule=rule,
                        pg_num=args.pg_num)
    test_map_pgs(m, dump=args.test_map_pgs_dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
