"""Arithmetic that several metric files share.  Each takes the run's
``Reading`` (harness/cell.py) and returns a number, or nothing where
there is nothing to read."""

from __future__ import annotations

from . import work
from .window import in_window, latencies_ms, quantile


def compiles_in_window(r):
    """Programs JAX compiled, or loaded from its cache, while the
    window was open.  Has to read 0."""
    return float(r.compiles_in_window)


def idle_share(r):
    """Percent of the traced slice in which no operation ran on the
    busiest device."""
    if r.trace is None or not r.trace.busy_by_device:
        return None
    busiest = max(r.trace.busy_by_device.values())
    return 100.0 * (1.0 - busiest / r.trace.window_s)


def hbm_peak_gb(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None


def window_ops(r) -> int:
    """Client ops acknowledged inside the window."""
    return len(in_window(r.log.acks, r.log.t_open, r.log.seconds))


def op_latency_ms(r, q: float):
    lat = latencies_ms(r.log.acks, r.log.t_open, r.log.seconds)
    return quantile(lat, q) if lat else None


def epoch_apply_ms(r, q: float):
    ms = [(e.t_end - e.t_start) * 1e3 for e in r.log.in_window()]
    return quantile(ms, q) if ms else None


def noncompute_share(r):
    """Percent of the dispatch engines' batch time (both engines, every
    phase from queue wait to delivery) that is not the `compute` phase —
    host time around the device call, by the program's own ledger."""
    total = compute = 0.0
    for key in r.after:
        if key.startswith("phase."):
            d = r.delta(key)
            total += d
            if key.endswith(".compute"):
                compute += d
    return 100.0 * (total - compute) / total if total > 0 else None


def crush_roofline(r):
    """CRUSH and placement-ladder device time of the traced slice
    against the least time the chip's peaks allow for the straw2 draws
    of the epochs applied in it."""
    if r.trace is None or r.slice_t is None:
        return None
    dep = r.cell.config["deployment"]
    t_a, t_b = r.slice_t
    epochs = sum(1 for e in r.log.epochs
                 if t_a <= e.t_start and e.t_end <= t_b)
    seconds = r.trace.seconds_of(*r.cell.config["programs"]["crush"])
    w = work.crush_work(
        pgs=epochs * int(dep["pg_num"]), numrep=int(dep["size"]),
        bucket_sizes=(int(dep["hosts"]), int(dep["osds_per_host"])))
    return work.roofline_share(w, r.peaks, seconds)
