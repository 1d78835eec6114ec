"""Erasure-code non-regression corpus tool
(src/test/erasure-code/ceph_erasure_code_non_regression.cc:113,304-328
analog).

--create writes, for every plugin x technique x (k, m) configuration, the
chunks produced from a fixed PRNG payload into an .npz archive (an
archive whose chunks are already the encoder's is left as it is, so a
regeneration touches exactly the files whose bytes changed);
--check re-encodes and byte-compares.  The committed corpus
(tests/golden/ec_corpus/) pins every kernel's output bytes forever: any
change to the GF math, the generator constructions, shec windows, lrc
layering, or clay coupling fails CI.

    python -m ceph_tpu.tools.ec_non_regression --create
    python -m ceph_tpu.tools.ec_non_regression --check
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tests", "golden", "ec_corpus")

PAYLOAD_LEN = 2111    # deliberately unaligned: pins padding semantics too
SEED = 20260730

LRC_LAYERS = json.dumps([
    ["cDDD____", {"plugin": "jerasure", "technique": "reed_sol_van"}],
    ["____cDDD", {"plugin": "jerasure", "technique": "reed_sol_van"}],
])

#: (name, plugin, profile)
CONFIGS = [
    ("jerasure_rsvan_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "reed_sol_van"}),
    ("jerasure_rsvan_k7m3", "jerasure",
     {"k": "7", "m": "3", "technique": "reed_sol_van"}),
    ("jerasure_rsvan_k8m3", "jerasure",
     {"k": "8", "m": "3", "technique": "reed_sol_van"}),
    ("jerasure_rsvan_k8m4", "jerasure",
     {"k": "8", "m": "4", "technique": "reed_sol_van"}),
    ("jerasure_rsr6_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "reed_sol_r6_op"}),
    ("jerasure_cauchy_good_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "cauchy_good"}),
    ("jerasure_cauchy_orig_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "cauchy_orig"}),
    ("jerasure_liberation_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "liberation"}),
    ("jerasure_blaum_roth_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "blaum_roth"}),
    ("jerasure_liber8tion_k4m2", "jerasure",
     {"k": "4", "m": "2", "technique": "liber8tion"}),
    ("isa_cauchy_k8m4", "isa",
     {"k": "8", "m": "4", "technique": "cauchy"}),
    ("isa_vand_k4m2", "isa",
     {"k": "4", "m": "2", "technique": "reed_sol_van"}),
    ("shec_k4m3c2", "shec", {"k": "4", "m": "3", "c": "2"}),
    ("lrc_2x3", "lrc", {"mapping": "_DDD_DDD", "layers": LRC_LAYERS}),
    ("clay_k4m2", "clay", {"k": "4", "m": "2"}),
    ("clay_k2m2", "clay", {"k": "2", "m": "2"}),
    ("clay_k8m4", "clay", {"k": "8", "m": "4"}),
]


def _payload() -> bytes:
    rng = np.random.default_rng(SEED)
    return rng.integers(0, 256, PAYLOAD_LEN, dtype=np.uint8).tobytes()


def _encode_all(plugin: str, profile: dict) -> dict[int, bytes]:
    from ceph_tpu.ec import registry_instance
    prof = dict(profile)
    prof.setdefault("runtime", "cpu")   # the oracle path pins the bytes;
    # kernel-vs-oracle equality is covered by the unit tests
    codec = registry_instance().factory(plugin, prof)
    n = codec.get_chunk_count()
    return codec.encode(set(range(n)), _payload())


def mismatches(directory: str, name: str, plugin: str,
               profile: dict) -> list[str]:
    """What of one configuration's archive is not what the encoder makes
    now: "MISSING corpus <name>" or one "MISMATCH <name> chunk <i>" per
    chunk."""
    path = os.path.join(directory, f"{name}.npz")
    if not os.path.exists(path):
        return [f"MISSING corpus {name}"]
    stored = np.load(path)
    return [f"MISMATCH {name} chunk {i}"
            for i, blob in _encode_all(plugin, profile).items()
            if f"chunk_{i}" not in stored
            or blob != stored[f"chunk_{i}"].tobytes()]


def create(directory: str) -> int:
    os.makedirs(directory, exist_ok=True)
    for name, plugin, profile in CONFIGS:
        if not mismatches(directory, name, plugin, profile):
            print(f"kept {name}: bit-identical")
            continue
        enc = _encode_all(plugin, profile)
        arrays = {f"chunk_{i}": np.frombuffer(v, dtype=np.uint8)
                  for i, v in enc.items()}
        np.savez_compressed(os.path.join(directory, f"{name}.npz"),
                            **arrays)
        print(f"created {name}: {len(enc)} chunks")
    return 0


def check(directory: str) -> int:
    failures = [line for config in CONFIGS
                for line in mismatches(directory, *config)]
    for line in failures:
        print(line)
    if not failures:
        print(f"all {len(CONFIGS)} corpus configs bit-identical")
    return 1 if failures else 0


def main(argv=None) -> int:
    from ceph_tpu.common.compile_cache import place_compile_cache
    place_compile_cache()
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--create", action="store_true")
    g.add_argument("--check", action="store_true")
    ap.add_argument("--directory", default=DEFAULT_DIR)
    args = ap.parse_args(argv)
    return create(args.directory) if args.create else check(args.directory)


if __name__ == "__main__":
    sys.exit(main())
