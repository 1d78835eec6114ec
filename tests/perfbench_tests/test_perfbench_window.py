"""Window accounting of the benchmark (perfbench/harness/window.py and
closed_loop.py) on synthetic completion logs: what lies outside the
window is not counted, ramp and drain are excluded, a stall inside the
window lowers the rate."""

import threading
import time

import pytest

from perfbench.harness import closed_loop
from perfbench.harness.window import (Ack, in_window, latencies_ms,
                                      quantile, rate_mb_s)

MB = 1_000_000


def steady_log(depth=4, service=1.0, t_first=0.0, n_rounds=30):
    """`depth` ops in flight, each taking `service` seconds: acks come
    in rounds of `depth`, one round per `service` seconds."""
    acks, index = [], 0
    for rnd in range(n_rounds):
        for _ in range(depth):
            t0 = t_first + rnd * service
            acks.append(Ack(index, t0, t0 + service, True))
            index += 1
    return acks


def test_acks_outside_the_window_are_not_counted():
    acks = steady_log()                     # acks at t = 1, 2, ..., 30
    got = in_window(acks, t_open=9.5, seconds=10.0)   # acks at 10..19
    assert len(got) == 10 * 4
    assert min(a.t_ack for a in got) == 10.0
    assert max(a.t_ack for a in got) == 19.0
    # the window is half-open: an ack at t_close belongs to the next
    assert len(in_window(acks, 10.0, 10.0)) == 10 * 4
    assert rate_mb_s(acks, 9.5, 10.0, MB) == pytest.approx(4.0)


def test_ramp_and_drain_are_excluded():
    """The same steady pipeline, measured over its whole life as
    ObjBencher._drive does and over a window inside it."""
    acks = steady_log(depth=4, service=1.0, n_rounds=20)
    # ramp: nothing is acknowledged in the first second; drain: the
    # last round is waited for after submissions stop.  Whole life:
    whole = rate_mb_s(acks, 0.0, 20.0 + 1e-9, MB)
    inside = rate_mb_s(acks, 5.5, 10.0, MB)
    assert inside == pytest.approx(4.0)     # depth / service
    # a slow last op in the drain moves the whole-life rate only
    slow = acks[:-1] + [acks[-1]._replace(t_ack=30.0)]
    assert rate_mb_s(slow, 5.5, 10.0, MB) == inside
    assert rate_mb_s(slow, 0.0, 30.0 + 1e-9, MB) < 0.7 * whole


def test_a_stall_inside_the_window_lowers_the_rate():
    acks = steady_log(depth=4, service=1.0, n_rounds=10)
    # the pipeline stops for 5 s after the round acknowledged at t=10,
    # then goes on as before
    later = [a._replace(index=a.index + 40, t_submit=a.t_submit + 15.0,
                        t_ack=a.t_ack + 15.0) for a in acks]
    stalled = acks + later                  # acks at 1..10 and 16..25
    assert rate_mb_s(stalled, 5.5, 10.0, MB) == pytest.approx(2.0)
    assert rate_mb_s(stalled, 15.5, 10.0, MB) == pytest.approx(4.0)
    # the ops that waited out the stall show it in their latency
    assert max(latencies_ms(stalled, 5.5, 10.0)) == pytest.approx(1000.0)


def test_failed_ops_carry_no_bytes_and_no_latency():
    acks = steady_log(n_rounds=10)
    acks[20] = acks[20]._replace(ok=False)
    assert len(latencies_ms(acks, 0.0, 11.0)) == 39
    assert rate_mb_s(acks, 0.0, 10.0 + 1e-9, MB) == pytest.approx(3.9)


@pytest.mark.parametrize("q,want", [(0.0, 1.0), (0.5, 2.5), (1.0, 4.0),
                                    (0.95, 3.85)])
def test_quantile_interpolates_between_ranks(q, want):
    assert quantile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


class FakeCompletion:
    """Completes `service` seconds after it is made, the way the
    program's AioCompletion does: by setting its waiter's event."""

    class _Waiter:
        def __init__(self):
            self.event = threading.Event()

    def __init__(self, service: float, rv: int = 0):
        self._w = self._Waiter()
        self._rv = rv
        threading.Timer(service, lambda: self._w.event.set()).start()

    def is_complete(self):
        return self._w.event.is_set()

    def get_return_value(self):
        return self._rv

    def cancel(self):
        self._w.event.set()


def test_closed_loop_window_is_full_at_both_edges():
    depth, service, seconds = 4, 0.1, 1.0
    opened = []
    log = closed_loop.run(
        lambda i: FakeCompletion(service), depth=depth,
        precondition_acks=2 * depth, seconds=seconds,
        on_open=lambda: opened.append(time.perf_counter()))
    assert opened and opened[0] <= log.t_open
    t_close = log.t_open + seconds
    assert log.depth_at(log.t_open) == depth
    # an acknowledgement that a loaded host hands to the loop only after
    # the deadline leaves its slot open at the close: one at the most
    assert depth - 1 <= log.depth_at(t_close) <= depth
    # preconditioning is before the window, the drain after it
    before = [a for a in log.acks if a.t_ack < log.t_open]
    after = [a for a in log.acks if a.t_ack >= t_close]
    assert len(before) >= 2 * depth
    assert len(after) == log.depth_at(t_close)
    # every submitted op was acknowledged, none lost
    assert log.submitted == len(log.acks) and not log.lost
    # the rate is the steady state's: depth / service, within timer slack
    got = rate_mb_s(log.acks, log.t_open, seconds, MB)
    assert 0.4 * depth / service < got <= depth / service + 1e-6


def test_closed_loop_counts_an_error_return_as_failed():
    log = closed_loop.run(
        lambda i: FakeCompletion(0.01, rv=-5 if i == 3 else 0), depth=2,
        precondition_acks=2, seconds=0.2)
    assert log.failed == 1
    assert [a.index for a in log.acks if not a.ok] == [3]


def test_run_all_reads_every_item_once():
    seen = {}
    log = closed_loop.run_all(
        lambda j: FakeCompletion(0.005), 11, depth=4,
        collect=lambda j, c: seen.setdefault(j, c))
    assert sorted(seen) == list(range(11)) and log.submitted == 11
    assert log.failed == 0
