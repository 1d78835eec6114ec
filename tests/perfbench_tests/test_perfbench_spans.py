"""The critical-path arithmetic of perfbench/harness/span_readers.py on
hand-made span rows, and its readers on a hand-made log."""

import types

import pytest

from perfbench.harness import span_readers as sr
from perfbench.harness.window import Ack

MS = 1_000_000


def span(sid, parent, name, a, b, layer="", **attrs):
    row = {"kind": "span", "span_id": sid, "parent_span_id": parent,
           "event": name, "start_ns": a * MS,
           "end_ns": None if b is None else b * MS, "layer": layer}
    if attrs:
        row["attrs"] = attrs
    return row


def op_rows(t0=0, slow=3, name="osd_op x"):
    """An op of 20 ms: submit, a hop, the primary's work, a fan-out of
    three sub-writes whose branch `slow` answers last, the reply."""
    rows = [span(1, 0, name, t0, t0 + 20),
            span(2, 1, "client submit", t0, t0 + 1, "client"),
            span(3, 2, "msg MOSDOp", t0 + 1, t0 + 2, "messenger"),
            span(4, 3, "ec prepare", t0 + 2, t0 + 4, "PG / EC backend"),
            span(5, 4, "ec fan-out", t0 + 4, t0 + 5, "PG / EC backend")]
    ends = {1: 9, 2: 12, 3: 10}
    ends[slow] = 17
    for i in (1, 2, 3):
        hop = 10 * i
        a = t0 + 4 + 0.25 * i
        rows += [span(hop, 5, "msg MOSDECSubOpWrite", a, a + 1, "messenger"),
                 span(hop + 1, hop, "ec sub-write", a + 1, t0 + ends[i],
                      "PG / EC backend"),
                 span(hop + 2, hop + 1, "bluestore commit", a + 2,
                      t0 + ends[i] - 1, "objectstore")]
    last = 10 * slow + 1
    rows += [span(90, last, "msg MOSDOpReply", t0 + 17, t0 + 18,
                  "messenger"),
             span(91, 90, "client complete", t0 + 19, t0 + 20, "client")]
    return rows


def ms(path):
    return {k: v / MS for k, v in path.items()}


@pytest.mark.parametrize("slow", [1, 2, 3])
def test_slowest_branch_of_a_fan_out_wins(slow):
    rows = op_rows(slow=slow)
    on_path = [r["span_id"] for r, _d in sr.path_spans(rows)]
    hop = 10 * slow
    assert {hop, hop + 1, hop + 2} <= set(on_path)
    others = {10 * i + j for i in (1, 2, 3) if i != slow for j in (0, 1, 2)}
    assert not others & set(on_path)
    path = ms(sr.critical_path(rows))
    a = 4 + 0.25 * slow
    assert path["objectstore"] == pytest.approx(17 - 1 - (a + 2))
    # the branches that were still running when the slowest began do
    # not count: the fan-out span owns the time before it
    assert path["PG / EC backend"] == pytest.approx(
        2 + (a - 4) + 1 + 1)    # prepare, fan-out's own, sub-write x2
    assert path["messenger"] == pytest.approx(1 + 1 + 1)
    assert sum(v for k, v in path.items() if k != "root") \
        == pytest.approx(path["root"]) == pytest.approx(20)


def test_gap_between_spans_of_the_path_is_unnamed():
    path = ms(sr.critical_path(op_rows()))
    # reply received at 18, completion wake begins at 19
    assert path["unnamed"] == pytest.approx(1.0)
    assert path["client"] == pytest.approx(2.0)
    share = 100 * (1 - path["unnamed"] / path["root"])
    assert share == pytest.approx(95.0)


def test_stages_on_one_thread_are_all_on_the_path():
    """An epoch: stages one after the other under the root, an engine
    request under the stage that waits for it, a device wait."""
    rows = [span(1, 0, "update_to", 0, 10),
            span(2, 1, "mapping signatures", 0, 1, "mapping service"),
            span(3, 1, "mapping crush", 1, 6.5, "mapping service"),
            span(4, 3, "device crush_rule", 1.5, 6, "dispatch engine"),
            span(5, 4, "engine queue_wait", 1.5, 2.5, "dispatch engine"),
            span(6, 4, "engine compute", 2.5, 5.5, "dispatch engine",
                 device_wait=True),
            span(7, 4, "engine deliver", 5.5, 6, "dispatch engine"),
            span(9, 1, "mapping install", 7, 10, "mapping service")]
    assert [r["span_id"] for r, _d in sr.path_spans(rows)] \
        == [1, 9, 3, 4, 7, 6, 5, 2]
    path = ms(sr.critical_path(rows, sr.by_layer_and_wait))
    assert path == {"root": 10, "unnamed": 0.5, "kernels": 3.0,
                    "dispatch engine": 1.5, "mapping service": 5.0}
    # by layer alone the wait is the engine's
    assert ms(sr.critical_path(rows))["dispatch engine"] == 4.5
    # a stage whose own span has ended but whose work goes on beside
    # the next stage did not gate it: neither it nor its work counts
    rows.append(span(10, 1, "mapping side", 6.5, 6.8, "mapping service"))
    rows.append(span(11, 10, "device side", 6.6, 9, "dispatch engine"))
    assert ms(sr.critical_path(rows, sr.by_layer_and_wait)) == path


def test_clipping_to_the_slice():
    rows = op_rows()
    whole = sr.critical_path(rows)
    part = ms(sr.critical_path(rows, clip=(1 * MS, 3 * MS)))
    assert part == {"root": 2.0, "unnamed": 0.0, "messenger": 1.0,
                    "PG / EC backend": 1.0}
    assert ms(sr.critical_path(rows, clip=(30 * MS, 40 * MS))) \
        == {"root": 0.0, "unnamed": 0.0}
    assert sr.critical_path(rows, clip=(-5 * MS, 50 * MS)) == whole
    # a root that has not ended gives no path
    assert sr.critical_path([span(1, 0, "osd_op y", 0, None)]) is None


def reading(acks, slice_t):
    log = types.SimpleNamespace(acks=acks)
    return types.SimpleNamespace(log=log, slice_t=slice_t)


def test_readers_on_a_slice(monkeypatch, capsys):
    """Three ops acknowledged inside the slice, one of them untraced;
    one before it and one across its edge do not count."""
    traces = [op_rows(t0=t) for t in (100, 130, 190, 210)]
    # commits and fsyncs of the sub-writes, for store.fsync_share
    for rows in traces:
        t0 = rows[0]["start_ns"] / MS
        rows.append(span(200, 32, "bluestore fsync", t0 + 8, t0 + 10,
                         "objectstore"))
    monkeypatch.setattr(sr, "completed_traces", lambda: traces)
    s = 1e-3
    acks = [Ack(0, 99.9 * s, 120 * s, True),      # before the slice
            Ack(1, 129.9 * s, 150 * s, True),
            Ack(2, 160 * s, 180 * s, True),       # no trace: counts 0
            Ack(3, 189.9 * s, 209.95 * s, True),
            Ack(4, 209.97 * s, 230 * s, True)]    # acked after it
    r = reading(acks, (125 * s, 215 * s))
    assert sr.store_path_ms(r) == pytest.approx(17 - 1 - 6.75)
    assert sr.client_path_ms(r) == pytest.approx(2.0)
    assert sr.named_share(r) == pytest.approx(95.0)
    spans = sr.spans_of(r)
    assert len(spans.paths) == 2 and spans.unmatched == 1
    # every commit of the slice counts, on the critical path or off it:
    # 2 ms of fsync in the three branches' 1.75 + 4.5 + 9.25 ms
    assert sr.fsync_share(r) == pytest.approx(100 * 2 / 15.5)
    assert "span_readers" in capsys.readouterr().err
    # two of three untraced: the median op is unnamed
    monkeypatch.setattr(sr, "completed_traces", lambda: traces[1:2])
    assert sr.named_share(reading(acks, (125 * s, 215 * s))) == 0.0
    # no table in the program (the parent commit): nothing, no error
    monkeypatch.setattr(sr, "completed_traces", lambda: None)
    quiet = reading(acks, (125 * s, 215 * s))
    assert sr.named_share(quiet) is None
    assert sr.engine_path_ms(quiet) is None
    assert sr.fsync_share(quiet) is None
    # an untraced run has no slice
    monkeypatch.setattr(sr, "completed_traces", lambda: traces)
    assert sr.msgr_path_ms(reading(acks, None)) is None


def test_epoch_readers_on_a_slice(monkeypatch):
    def epoch_rows(t0):
        return [span(1, 0, "update_to", t0, t0 + 10),
                span(2, 1, "mapping crush", t0, t0 + 6,
                     "mapping service"),
                span(3, 2, "device crush_rule", t0 + 1, t0 + 5,
                     "dispatch engine"),
                span(4, 3, "engine compute", t0 + 2, t0 + 5,
                     "dispatch engine", device_wait=True),
                span(5, 1, "mapping delta read-back", t0 + 6, t0 + 8,
                     "mapping service", device_wait=True)]
    monkeypatch.setattr(sr, "completed_traces",
                        lambda: [epoch_rows(t) for t in (50, 70, 90)])
    s = 1e-3
    log = types.SimpleNamespace(epochs=[
        types.SimpleNamespace(t_start=t * s, t_end=(t + 10.5) * s)
        for t in (49.9, 69.9, 89.9)])
    r = types.SimpleNamespace(log=log, slice_t=(60 * s, 200 * s))
    assert sr.kernels_wait_ms(r) == pytest.approx(5.0)
    assert sr.engine_path_ms(r) == pytest.approx(1.0)
    assert sr.mapping_path_ms(r) == pytest.approx(2.0)
    assert sr.named_share(r) == pytest.approx(80.0)
    assert len(sr.spans_of(r).paths) == 2
