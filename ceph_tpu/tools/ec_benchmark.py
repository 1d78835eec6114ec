"""ceph_erasure_code_benchmark analog
(src/test/erasure-code/ceph_erasure_code_benchmark.cc).

Same flags, same output contract — one line per run:

    <elapsed seconds>\t<total KiB processed>

Usage mirrors the reference (:40-65 usage text):
    python -m ceph_tpu.tools.ec_benchmark --plugin jerasure \
        --parameter k=4 --parameter m=2 --parameter technique=reed_sol_van \
        --size 1048576 --iterations 100 --workload encode
    ... --workload decode --erasures 2 [--erasures-generation exhaustive]

Additions over the reference: --batch (stripes per device call — the ECUtil
batch point) and --runtime tpu|cpu.
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time

import numpy as np

from ceph_tpu.ec import registry_instance


def bench_encode(codec, object_size: int, iterations: int,
                 batch: int) -> tuple[float, int]:
    k = codec.get_data_chunk_count()
    chunk = codec.get_chunk_size(object_size)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
    # warm (compile) then measure
    codec.encode_chunks(data)
    total_kib = 0
    t0 = time.perf_counter()
    done = 0
    while done < iterations:
        n = min(batch, iterations - done)
        out = codec.encode_chunks(data[:n])
        done += n
        total_kib += n * object_size // 1024
    np.asarray(out)  # materialize
    return time.perf_counter() - t0, total_kib


def bench_decode(codec, object_size: int, iterations: int, batch: int,
                 erasures: int, exhaustive: bool) -> tuple[float, int]:
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    chunk = codec.get_chunk_size(object_size)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8)
    parity = np.asarray(codec.encode_chunks(data))
    full = np.concatenate([data, parity], axis=1)
    if exhaustive:
        patterns = list(itertools.combinations(range(n), erasures))
    else:
        patterns = [tuple(sorted(rng.choice(n, erasures, replace=False)))]
    total_kib = 0
    t0 = time.perf_counter()
    done = 0
    while done < iterations:
        lost = patterns[done % len(patterns)]
        chosen = [i for i in range(n) if i not in lost][:k]
        m = min(batch, iterations - done)
        out = codec.decode_chunks(chosen, full[:m, chosen], list(lost))
        done += m
        total_kib += m * object_size // 1024
    np.asarray(out)
    return time.perf_counter() - t0, total_kib


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    p = argparse.ArgumentParser(prog="ec_benchmark")
    p.add_argument("--plugin", "-p", default="jerasure")
    p.add_argument("--parameter", "-P", action="append", default=[],
                   help="profile key=value (k=, m=, technique=, ...)")
    p.add_argument("--size", "-S", type=int, default=1024 * 1024,
                   help="object size in bytes")
    p.add_argument("--iterations", "-i", type=int, default=100)
    p.add_argument("--workload", "-w", choices=["encode", "decode"],
                   default="encode")
    p.add_argument("--erasures", "-e", type=int, default=1)
    p.add_argument("--erasures-generation", "-E",
                   choices=["random", "exhaustive"], default="random")
    p.add_argument("--batch", type=int, default=64,
                   help="stripes per device call")
    p.add_argument("--runtime", choices=["tpu", "cpu"], default="tpu")
    p.add_argument("--verbose", "-v", action="store_true")
    args = p.parse_args(argv)

    profile = {"runtime": args.runtime}
    for kv in args.parameter:
        key, _, val = kv.partition("=")
        profile[key] = val
    codec = registry_instance().factory(args.plugin, profile)

    if args.workload == "encode":
        elapsed, kib = bench_encode(codec, args.size, args.iterations,
                                    args.batch)
    else:
        elapsed, kib = bench_decode(
            codec, args.size, args.iterations, args.batch, args.erasures,
            args.erasures_generation == "exhaustive")
    # the reference's output contract (:188, :326)
    print(f"{elapsed:.6f}\t{kib}")
    if args.verbose:
        print(f"# {kib / 1024 / max(elapsed, 1e-9):.1f} MB/s "
              f"{args.plugin} {profile}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
