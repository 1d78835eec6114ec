"""ObjectStore micro-benchmark — the fio ObjectStore engine analog
(src/test/fio/fio_ceph_objectstore.cc): drive a store backend directly
(no cluster) with write/read workloads and report IOPS + MB/s.

Usage: python -m ceph_tpu.tools.objectstore_bench --type bluestore \
          --path DIR [--objects N] [--size BYTES] [--threads T]
"""

from __future__ import annotations

import argparse
import json
import threading
import time

from ceph_tpu.objectstore import Transaction, create_objectstore


def run(store, n_objects: int, obj_size: int, n_threads: int) -> dict:
    cid = "bench.0"
    if cid not in store.list_collections():
        store.apply_transaction(Transaction().create_collection(cid))
    payload = (b"\xa5" * obj_size)
    results = {}

    def phase(name, fn, bytes_per_op=None):
        per_op = obj_size if bytes_per_op is None else bytes_per_op
        errs = [0] * n_threads

        def worker(t):
            for i in range(t, n_objects, n_threads):
                try:
                    fn(i)
                except Exception:
                    errs[t] += 1

        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        results[name] = {
            "seconds": round(dt, 3),
            "iops": round(n_objects / dt, 1),
            "mb_s": round(n_objects * per_op / dt / 1e6, 2),
            "errors": sum(errs),
        }

    phase("write", lambda i: store.apply_transaction(
        Transaction().write(cid, f"o{i}", 0, payload)))
    phase("read", lambda i: store.read(cid, f"o{i}"))
    phase("overwrite", lambda i: store.apply_transaction(
        Transaction().write(cid, f"o{i}", obj_size // 2,
                            payload[:obj_size // 2])),
          bytes_per_op=obj_size // 2)
    # small sub-block overwrites: the deferred-write (WAL) fast path on
    # bluestore — a 512 B patch inside an existing block
    phase("small_overwrite", lambda i: store.apply_transaction(
        Transaction().write(cid, f"o{i}", 1024, payload[:512])),
          bytes_per_op=512)
    phase("delete", lambda i: store.apply_transaction(
        Transaction().remove(cid, f"o{i}")))
    results["config"] = {"objects": n_objects, "size": obj_size,
                         "threads": n_threads}
    return results


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    ap = argparse.ArgumentParser(prog="objectstore-bench")
    ap.add_argument("--type", default="bluestore",
                    choices=["memstore", "filestore", "bluestore"])
    ap.add_argument("--path", required=True)
    ap.add_argument("--objects", type=int, default=1024)
    ap.add_argument("--size", type=int, default=65536)
    ap.add_argument("--threads", type=int, default=4)
    args = ap.parse_args(argv)
    store = create_objectstore(args.type, args.path)
    store.mkfs_if_needed()
    store.mount()
    try:
        print(json.dumps(run(store, args.objects, args.size,
                             args.threads)))
        return 0
    finally:
        store.umount()


if __name__ == "__main__":
    raise SystemExit(main())
