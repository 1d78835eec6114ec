"""The benchmark's trace reduction (perfbench/harness/trace.py): on
synthetic events with hand-computed answers, and on a small trace
recorded on a TPU v5e (data/small_v5e.xplane.pb)."""

import os

import pytest

from perfbench.harness import trace
from perfbench.harness.trace import DeviceLines, Event, RawTrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_and_gaps():
    cover = trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (6, 7)])
    assert cover == [(0, 2), (3, 4), (6, 7)]
    assert trace.clip(cover, 1, 6.5) == [(1, 2), (3, 4), (6, 6.5)]
    assert trace.gaps(cover, 0, 8) == [(2, 3), (4, 6), (7, 8)]
    assert trace.gaps([], 0, 1) == [(0, 1)]
    assert trace.program_of("jit_digest(17823)") == "jit_digest"


def synthetic() -> RawTrace:
    """Window 10..20.  Device 0: two digest programs and one encode;
    device 1: one crush program.  Host spans of one client thread."""
    dev0 = DeviceLines(
        modules=[Event("jit_digest(1)", 9.0, 11.0),     # half outside
                 Event("jit__encode_pallas(2)", 12.0, 12.5),
                 Event("jit_digest(1)", 14.0, 16.0)],
        ops=[Event("while.2", 9.0, 11.0),
             Event("fusion.1", 9.5, 10.5),              # nested in while.2
             Event("tpu_custom_call.1", 12.0, 12.5),
             Event("while.2", 14.0, 15.0),
             Event("fusion.1", 15.5, 16.0)])
    dev1 = DeviceLines(modules=[Event("jit_crush(3)", 10.0, 11.0)],
                       ops=[Event("fusion.9", 10.0, 11.0)])
    spans = [Event(trace.WINDOW_SPAN, 10.0, 20.0),
             Event("client_op", 10.0, 13.0), Event("generator", 13.0, 14.0),
             Event("client_op", 14.0, 25.0)]
    return RawTrace(devices={0: dev0, 1: dev1}, host_spans=spans)


def test_summary_of_synthetic_trace():
    s = trace.summarize(synthetic())
    assert s.window_s == pytest.approx(10.0)
    # device 0: [10,11] + [12,12.5] + [14,15] + [15.5,16] = 3.0
    assert s.busy_by_device == {0: pytest.approx(3.0), 1: pytest.approx(1.0)}
    assert s.busy_s == pytest.approx(2.0)               # mean over devices
    assert s.seconds_of("jit_digest") == pytest.approx(1.0 + 2.0)
    assert s.calls_of("jit_digest") == 2
    assert s.seconds_of("jit__encode", "jit_crush") == pytest.approx(1.5)
    ops = dict(map(tuple, s.device_ops))
    assert ops["jit_digest/while.2"] == pytest.approx(2.0)
    assert ops["jit__encode_pallas/tpu_custom_call.1"] == pytest.approx(0.5)
    # gaps of the busiest device (0): 11-12 and 12.5-14 and 15-15.5 and
    # 16-20, named by the span open at their middle
    gaps = dict(map(tuple, s.idle_gaps))
    assert gaps["client_op"] == pytest.approx(1.0 + 0.5 + 4.0)
    assert gaps["generator"] == pytest.approx(1.5)
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10


def test_a_trace_without_the_harness_mark_spans_its_own_events():
    raw = synthetic()
    raw.host_spans = []
    s = trace.summarize(raw)
    assert s.window_s == pytest.approx(16.0 - 9.0)
    # device 0 is busy 9-11, 12-12.5, 14-15, 15.5-16 of 9..16
    assert s.idle_gaps == [["none", pytest.approx(7.0 - 4.0)]]
    with pytest.raises(ValueError):
        trace.summarize(RawTrace())


def test_recorded_v5e_trace():
    """Recorded on the chip (PR 27): a jitted sum over 2^20 int32s run
    five times inside the harness's window mark, 50 ms apart.  The
    device's clock runs about a millisecond ahead of the host's in this
    trace, so the first call lies just before the mark and is clipped."""
    path = os.path.join(DATA, "small_v5e.xplane.pb")
    raw = trace.read_xplane(path, ("epoch_apply",))
    assert list(raw.devices) == [0]
    s = trace.summarize(raw)
    assert 0 < s.busy_s < s.window_s
    assert len(raw.devices[0].modules) == 5
    assert s.calls_of("jit_small_sum") == 4
    assert 0 < s.seconds_of("jit_small_sum") <= s.busy_s * 1.001
    assert s.device_ops and s.device_ops[0][0].startswith("jit_small_sum/")
    gaps = dict(map(tuple, s.idle_gaps))
    assert gaps["epoch_apply"] > 0.1        # the pauses, by their span
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
