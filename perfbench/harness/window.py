"""Window accounting: what a measured window counts, as pure functions
of a completion log.

A completion is ``(index, t_submit, t_ack, ok)`` on one monotonic clock.
The window is ``[t_open, t_open + seconds)``.  An op belongs to it when
its *acknowledgement* lies inside; when it was submitted does not
matter, so a closed loop that is full at both edges neither gains the
ops of its ramp nor pays for its drain: both lie outside.
"""

from __future__ import annotations

from typing import NamedTuple


class Ack(NamedTuple):
    index: int
    t_submit: float
    t_ack: float
    ok: bool


def quantile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no samples")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(acks, t_open: float, seconds: float) -> list[Ack]:
    t_close = t_open + seconds
    return [a for a in acks if t_open <= a.t_ack < t_close]


def rate_mb_s(acks, t_open: float, seconds: float, obj_size: int) -> float:
    """User megabytes (10^6 bytes) acknowledged inside the window per
    second of window: all of the window's work over all of its time."""
    done = sum(1 for a in in_window(acks, t_open, seconds) if a.ok)
    return done * obj_size / seconds / 1e6


def latencies_ms(acks, t_open: float, seconds: float) -> list[float]:
    """Submit-to-ack latency of every op acknowledged in the window."""
    return [(a.t_ack - a.t_submit) * 1e3
            for a in in_window(acks, t_open, seconds) if a.ok]
