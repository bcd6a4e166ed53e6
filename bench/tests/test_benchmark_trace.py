"""The trace reduction, on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``, made by ``data/record_tiny_trace.py``) and on
hand-made operations."""
from __future__ import annotations

import os

import pytest

from bench import trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return trace.load(TINY)


def test_window_is_the_window_span(tiny):
    windows = [s for s in tiny.spans if s.name == "window"]
    assert len(windows) == 1
    assert tiny.window == (windows[0].start, windows[0].end)
    assert 0.03 < tiny.window_s < 0.1  # three rounds with a 10 ms sleep


def test_busy_time_is_the_union_of_device_ops(tiny):
    assert tiny.devices == ["/device:TPU:0"]
    busy = tiny.busy_s()
    assert 0 < busy < tiny.window_s
    # every op inside the window, summed without overlap, bounds busy time
    lo, hi = tiny.window
    total = sum(min(o.end, hi) - max(o.start, lo)
                for o in tiny.ops if o.end > lo and o.start < hi) * 1e-9
    assert busy <= total + 1e-12


def test_ops_are_named_by_instruction(tiny):
    names = {o.name for o in tiny.ops}
    assert "sort.6" in names
    assert all(not n.startswith("%") and " " not in n for n in names)


def test_sort_time_by_pattern(tiny):
    sort_s = tiny.op_s(r"^sort")
    assert 0 < sort_s <= tiny.busy_s()
    assert tiny.op_s(r"^no-such-op") == 0.0


def test_top_ops_and_idle_gaps(tiny):
    top = tiny.top_ops(3)
    assert len(top) == 3 and top[0][0].startswith("%sort.6")
    assert top[0][1] >= top[1][1] >= top[2][1]
    gaps = tiny.idle_by_span()
    assert {name for name, _ in gaps} <= {"dispatch", "fetch", "other"}
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(tiny.window_s - tiny.busy_s(), rel=1e-6)


def _op(start, end, name="fusion.1", device="/device:TPU:0"):
    return trace.Op(device, name, float(start), float(end), name)


def test_parents_are_marked_and_left_out_of_top_ops():
    ops = trace._mark_parents([
        _op(0, 100, "while.1"), _op(10, 40, "sort.2"), _op(50, 90, "fusion.3"),
        _op(120, 130, "fusion.4"),
    ])
    parents = {o.name for o in ops if o.parent}
    assert parents == {"while.1"}
    t = trace.Trace(ops, [trace.Span("window", 0.0, 200.0)], (0.0, 200.0))
    assert [name for name, _ in t.top_ops()] == ["fusion.3", "sort.2", "fusion.4"]
    # busy is the union: the while holds the others
    assert t.busy_s() == pytest.approx(110e-9)


def test_idle_gaps_go_to_the_span_that_covers_them():
    ops = [_op(0, 10), _op(50, 60), _op(90, 100)]
    spans = [trace.Span("window", 0.0, 100.0), trace.Span("feed", 10.0, 50.0),
             trace.Span("dispatch", 5.0, 95.0)]
    t = trace.Trace(trace._mark_parents(ops), spans, (0.0, 100.0))
    got = dict(t.idle_by_span())
    # 10..50 lies in both spans: the shorter, inner feed span takes it
    assert got == {"feed": pytest.approx(40e-9), "dispatch": pytest.approx(30e-9)}


def test_shares_average_over_devices():
    ops = [_op(0, 100, device="/device:TPU:0"), _op(0, 50, device="/device:TPU:1"),
           _op(0, 20, "all-to-all.1", device="/device:TPU:1")]
    t = trace.Trace(trace._mark_parents(ops), [], (0.0, 100.0))
    assert t.busy_s() == pytest.approx(75e-9)
    assert t.op_s(r"^all-to-all") == pytest.approx(10e-9)
