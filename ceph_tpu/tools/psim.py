"""psim analog (src/crush/psim.cc): toy placement simulator — build a
synthetic hierarchy, place N objects, report the per-device utilization
spread.  Quick sanity of CRUSH balance without a cluster.

Usage: python -m ceph_tpu.tools.psim [--hosts H] [--per-host D]
          [--objects N] [--numrep R]
"""

from __future__ import annotations

import argparse
import json


def simulate(hosts: int = 16, per_host: int = 4, objects: int = 4096,
             numrep: int = 3) -> dict:
    import numpy as np

    from ceph_tpu.common.context import default_context
    from ceph_tpu.crush import build_two_level_map

    crush_map, _root, rid = build_two_level_map(hosts, per_host)
    n_dev = hosts * per_host
    reweight = np.full(n_dev, 0x10000, dtype=np.int64)
    rng = np.random.default_rng(0)
    xs = rng.integers(0, 2 ** 32, (objects,), dtype=np.uint32)
    # the production bulk-placement path: the shared mapping service's
    # cached mapper + dispatch-engine submission, not a private mapper
    svc = default_context().mapping_service()
    out = np.asarray(svc.place(crush_map, rid, xs, numrep, reweight))
    counts = np.zeros(n_dev, dtype=np.int64)
    for col in range(out.shape[1]):
        valid = out[:, col] >= 0
        np.add.at(counts, out[valid, col], 1)
    expected = objects * numrep / n_dev
    return {
        "devices": n_dev, "objects": objects, "numrep": numrep,
        "placements": int(counts.sum()),
        "expected_per_device": round(expected, 1),
        "min": int(counts.min()), "max": int(counts.max()),
        "stddev_pct": round(float(counts.std() / expected * 100), 2),
    }


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    ap = argparse.ArgumentParser(prog="psim")
    ap.add_argument("--hosts", type=int, default=16)
    ap.add_argument("--per-host", type=int, default=4)
    ap.add_argument("--objects", type=int, default=4096)
    ap.add_argument("--numrep", type=int, default=3)
    a = ap.parse_args(argv)
    print(json.dumps(simulate(a.hosts, a.per_host, a.objects, a.numrep)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
