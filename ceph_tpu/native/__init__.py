"""Native (C) single-core baseline kernels, compiled on first use.

These are the CPU references behind the EC profile key ``runtime=native``
and the yardsticks of BASELINE.md's rows: an ISA-L-class split-nibble
GF(2^8) encode and a scalar straw2 ``crush_do_rule`` (semantics of src/crush/mapper.c:900, ported
from the in-repo oracle ``crush.mapper_ref`` and cross-validated in
tests/test_native.py).

The shared library builds with the system C compiler at first call and is
cached next to the source, keyed by the source, the compiler flags and the
host CPU's feature flags (it is built ``-march=native``); no pip/cmake
involved.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "baseline.c")
# analysis: allow[bare-lock] -- import-time ctypes loader guard; leaf
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None


class NativeUnavailable(RuntimeError):
    pass


_CFLAGS = ("-O3", "-march=native", "-funroll-loops", "-shared", "-fPIC")


def _cpu_features() -> bytes:
    """What ``-march=native`` resolves to on this host: the CPU's model
    and feature flags.  Part of the library's name, so a library built
    on one machine is never loaded on another that copied the tree."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            lines = f.read().splitlines()
    except OSError:
        import platform
        return platform.processor().encode()
    keep = (b"model name", b"flags", b"Features")
    return b"\n".join(sorted({ln for ln in lines if ln.startswith(keep)}))


def _build() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CFLAGS).encode())
    h.update(_cpu_features())
    out = os.path.join(_DIR, f"_baseline_{h.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    for cc in ("cc", "gcc", "clang"):
        try:
            subprocess.run(
                [cc, *_CFLAGS, "-o", out + ".tmp", _SRC],
                check=True, capture_output=True, timeout=120)
            os.replace(out + ".tmp", out)
            return out
        except (OSError, subprocess.SubprocessError):
            continue
    raise NativeUnavailable("no working C compiler found")


def lib() -> ctypes.CDLL:
    global _LIB
    with _LOCK:
        if _LIB is None:
            so = _build()
            L = ctypes.CDLL(so)
            L.ec_encode_c.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_long, ctypes.c_long]
            L.ec_encode_c.restype = None
            L.crush_init.argtypes = [ctypes.POINTER(ctypes.c_int64)]
            L.crush_init.restype = ctypes.c_void_p
            L.crush_free.argtypes = [ctypes.c_void_p]
            L.crush_free.restype = None
            L.crush_do_rule_c.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint32), ctypes.c_int]
            L.crush_do_rule_c.restype = ctypes.c_int
            L.crush_batch_c.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint32), ctypes.c_long, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
                ctypes.POINTER(ctypes.c_int32)]
            L.crush_batch_c.restype = ctypes.c_int
            _LIB = L
        return _LIB


def ec_encode_native(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Single-core C encode.  matrix (m, k) uint8; data (stripes, k, chunk)
    uint8 C-contiguous.  Returns parity (stripes, m, chunk)."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = matrix.shape
    stripes, k2, chunk = data.shape
    assert k2 == k
    parity = np.empty((stripes, m, chunk), dtype=np.uint8)
    lib().ec_encode_c(
        matrix.ctypes.data_as(ctypes.c_char_p), k, m,
        data.ctypes.data_as(ctypes.c_char_p),
        parity.ctypes.data_as(ctypes.c_char_p), stripes, chunk)
    return parity


_TUNABLE_FIELDS = (
    "choose_local_tries", "choose_local_fallback_tries", "choose_total_tries",
    "chooseleaf_descend_once", "chooseleaf_vary_r", "chooseleaf_stable",
    "straw_calc_version")

CRUSH_ITEM_NONE = 0x7FFFFFFF


def _map_blob(crush_map) -> np.ndarray:
    """Serialize a crush.types.CrushMap into the int64 blob crush_init eats."""
    from ceph_tpu.crush.ln_table import lh_table, ll_table, rh_table
    from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW, CRUSH_BUCKET_TREE

    words: list[int] = [0xCB02, crush_map.max_devices,
                       crush_map.max_buckets, crush_map.max_rules]
    words += [getattr(crush_map.tunables, f) for f in _TUNABLE_FIELDS]
    for b in crush_map.buckets:
        if b is None:
            words.append(0)
            continue
        words += [1, b.id, b.type, b.alg, b.size]
        words += list(b.items)
        if b.alg == CRUSH_BUCKET_STRAW:
            words += list(b.straws)  # straw draws use straws, not weights
        else:
            words += list(b.item_weights) if b.item_weights \
                else [b.item_weight] * b.size
        if b.alg == CRUSH_BUCKET_TREE:
            words += [len(b.node_weights)]
            words += list(b.node_weights)
    for r in crush_map.rules:
        if r is None:
            words.append(0)
            continue
        words += [1, len(r.steps)]
        for s in r.steps:
            words += [s.op, s.arg1, s.arg2]
    words += [int(v) for v in rh_table()]
    words += [int(v) for v in lh_table()]
    words += [int(v) for v in ll_table()]
    return np.asarray(
        [w - (1 << 64) if w >= (1 << 63) else w for w in words],
        dtype=np.int64)


class CrushBaseline:
    """Scalar C crush_do_rule over a frozen CrushMap (one core, one x at a
    time) — the single-core number the batched TPU engine must beat."""

    def __init__(self, crush_map):
        self._blob = _map_blob(crush_map)
        self._h = lib().crush_init(
            self._blob.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if not self._h:
            raise NativeUnavailable("crush_init failed")
        self.result_max_limit = 64

    def close(self) -> None:
        if getattr(self, "_h", None):
            lib().crush_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def do_rule(self, ruleno: int, x: int, result_max: int,
                weights: list[int] | np.ndarray) -> list[int]:
        w = np.ascontiguousarray(weights, dtype=np.uint32)
        out = np.full(result_max, CRUSH_ITEM_NONE, dtype=np.int32)
        n = lib().crush_do_rule_c(
            self._h, ruleno, x & 0xFFFFFFFF,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), result_max,
            w.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(w))
        if n < 0:
            raise ValueError(
                f"result_max {result_max} exceeds the C baseline's "
                f"working-set capacity ({self.result_max_limit})")
        return [int(v) for v in out[:n]]

    def do_rule_batch(self, ruleno: int, xs: np.ndarray, result_max: int,
                      weights: np.ndarray) -> np.ndarray:
        """(nx, result_max) int32, NONE-padded — the bulk-remap workload."""
        xs = np.ascontiguousarray(xs, dtype=np.uint32)
        w = np.ascontiguousarray(weights, dtype=np.uint32)
        out = np.empty((len(xs), result_max), dtype=np.int32)
        rc = lib().crush_batch_c(
            self._h, ruleno,
            xs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(xs),
            result_max,
            w.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(w),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if rc < 0:
            raise ValueError(
                f"result_max {result_max} exceeds the C baseline's "
                f"working-set capacity ({self.result_max_limit})")
        return out
