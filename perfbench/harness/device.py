"""The device a run is on: found or refused, and its published peaks."""

from __future__ import annotations

import json
import os
import sys
import threading

#: what ``jax.devices()[0].platform`` has to be.  The tests under
#: tests/perfbench_tests rehearse the rest of a run on the CPU by
#: replacing this; the benchmark itself has no option that does.
PLATFORM = "tpu"

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def load_peaks() -> dict:
    with open(_PEAKS_FILE) as f:
        return json.load(f)


def peaks_of(kind: str, table: dict | None = None) -> dict:
    """Peaks of one chip of `kind`.  A kind that is not in the table is
    an error, never a default."""
    table = load_peaks() if table is None else table
    if kind not in table or kind == "source":
        raise KeyError(f"device kind {kind!r} is not in {_PEAKS_FILE}")
    return table[kind]


def device_or_exit(chips: int) -> dict:
    """The contract's `device` facts, or exit non-zero with no result
    line: no accelerator, fewer chips than the cell asks for, or a chip
    whose peaks are not known."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != PLATFORM or info["count"] < chips:
        sys.exit(f"perfbench: the cell needs {chips} {PLATFORM} device(s), "
                 f"JAX found {info}")
    try:
        peaks_of(info["kind"])
    except KeyError as e:
        sys.exit(f"perfbench: {e.args[0]}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Counts what JAX compiles (backend compiles, not cache loads)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def _secs(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def facts(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "compile_s": round(self.compile_s, 3),
                    "cache_hits": self.cache_hits}
