"""The program's ``blaze.`` spans read back from a trace, the idle gaps put
down to them, and the two readers of the program's span counters."""
from __future__ import annotations

import importlib
import os

import pytest

from bench import program_spans, run, trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
MAIN, PREFETCH, OTHER = ("/host:CPU", 0), ("/host:CPU", 1), ("/host:CPU", 2)


def _op(start, end, device="/device:TPU:0"):
    return trace.Op(device, "fusion.1", float(start), float(end), "fusion.1")


def _span(name, start, end, line=MAIN):
    return program_spans.LineSpan(name, float(start), float(end), line)


def _trace(ops, bench, program, window=(0.0, 100.0)):
    spans = [trace.Span(s.name, s.start, s.end) for s in bench]
    t = trace.Trace(trace._mark_parents(ops), spans, window)
    return program_spans.ProgramTrace(t, bench, program)


def test_a_gap_in_a_sync_is_named_dispatch_sync():
    # device idle 10..50 and 60..90; the host syncs through the first
    pt = _trace([_op(0, 10), _op(50, 60), _op(90, 100)],
                [_span("window", 0, 100), _span("dispatch", 5, 95)],
                [_span("sync", 8, 52), _span("dispatch", 55, 88)])
    got = dict(pt.idle_by_span())
    assert got == {"dispatch/sync": pytest.approx(40e-9),
                   "dispatch/dispatch": pytest.approx(30e-9)}


def test_a_wait_for_the_next_block_is_named_dispatch_feed_wait():
    # the block is produced on the prefetch thread while the main thread
    # waits: the produce span is on another line and is left out
    pt = _trace([_op(0, 10), _op(60, 100)],
                [_span("window", 0, 100), _span("dispatch", 0, 100)],
                [_span("feed.wait", 10, 58),
                 _span("feed.produce", 5, 60, PREFETCH)])
    assert dict(pt.idle_by_span()) == {"dispatch/feed.wait": pytest.approx(50e-9)}


def test_a_gap_no_program_span_covers_keeps_its_bench_name():
    pt = _trace([_op(0, 10), _op(50, 100)],
                [_span("window", 0, 100), _span("fetch", 10, 50)],
                [_span("sync", 60, 70)])
    assert dict(pt.idle_by_span()) == {"fetch": pytest.approx(40e-9)}
    # and no bench span at all: "other", as in trace.py
    bare = _trace([_op(0, 10)], [_span("window", 0, 100)],
                  [_span("sync", 10, 100)])
    assert dict(bare.idle_by_span()) == {"other": pytest.approx(90e-9)}


def test_program_spans_of_another_thread_are_ignored():
    pt = _trace([_op(0, 10), _op(50, 100)],
                [_span("window", 0, 100), _span("dispatch", 0, 100)],
                [_span("feed.produce", 10, 50, PREFETCH),
                 _span("sync", 10, 50, OTHER)])
    assert dict(pt.idle_by_span()) == {"dispatch": pytest.approx(40e-9)}


def test_ties_go_to_the_inner_program_span():
    # without prefetch the block is produced inside the wait
    pt = _trace([_op(0, 10), _op(50, 100)],
                [_span("window", 0, 100), _span("dispatch", 0, 100)],
                [_span("feed.wait", 5, 55), _span("feed.produce", 8, 52)])
    assert dict(pt.idle_by_span()) == {"dispatch/feed.produce": pytest.approx(40e-9)}


def test_idle_seconds_sum_to_the_window_less_busy():
    pt = _trace([_op(0, 10), _op(30, 40), _op(70, 80)],
                [_span("window", 0, 100), _span("dispatch", 0, 60),
                 _span("fetch", 60, 100)],
                [_span("sync", 10, 30), _span("dispatch", 40, 50)])
    gaps = pt.idle_by_span()
    assert sum(s for _, s in gaps) == pytest.approx(
        pt.trace.window_s - pt.trace.busy_s())
    names = {n.split("/")[0] for n, _ in gaps}
    assert names == {"dispatch", "fetch"}


def test_a_trace_without_program_spans_keeps_trace_py_names():
    pt = program_spans.load(TINY)
    assert pt.program == []
    assert pt.idle_by_span() == trace.load(TINY).idle_by_span()


def test_span_totals_count_spans_that_start_in_the_window():
    pt = _trace([], [_span("window", 10, 100)],
                [_span("dispatch", 5, 20), _span("dispatch", 20, 30),
                 _span("dispatch", 40, 60), _span("sync", 90, 120)],
                window=(10.0, 100.0))
    assert pt.span_totals() == {"dispatch": [2, pytest.approx(30e-9)],
                                "sync": [1, pytest.approx(10e-9)]}


def test_a_cpu_profile_holds_the_program_spans_on_their_threads(tmp_path):
    """A real ``jax.profiler`` trace of ``run_stream`` on the CPU, read
    back: the program's spans, the feed's produce on its own thread."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BlazeSession

    sess = BlazeSession()
    cv = sess.chunked(np.arange(256, dtype=np.float32), block_rows=64)

    def step(ctx, s):
        part = ctx.map_reduce(
            cv, lambda i, x, emit: emit(i % 7, x), "sum",
            jnp.zeros((7,), jnp.float32),
        )
        return {"acc": s["acc"] + part}

    prog = sess.program(step)
    state = {"acc": jnp.zeros((7,), jnp.float32)}
    jax.profiler.start_trace(str(tmp_path))
    try:
        with run.span("window"), run.span("dispatch"):
            sess.run_stream(prog, state, max_epochs=2, cond=lambda s: False)
            sess.host_value(state["acc"])
    finally:
        jax.profiler.stop_trace()
    pt = program_spans.load(str(tmp_path))
    totals = pt.span_totals()
    assert totals["compile"][0] == 1
    assert totals["dispatch"][0] == 7  # 2 epochs of 4 blocks, less the compile
    assert totals["feed.produce"][0] == 8
    assert totals["feed.wait"][0] == 10
    assert totals["sync"][0] == 3  # a cond per epoch and the host_value
    lines = {name: {s.line for s in pt.program if s.name == name}
             for name in totals}
    (main,) = lines["feed.wait"]
    assert lines["compile"] == lines["dispatch"] == lines["sync"] == {main}
    assert main not in lines["feed.produce"]
    (bench_dispatch,) = [s for s in pt.bench if s.name == "dispatch"]
    assert bench_dispatch.line == main


def _reading(counters, window_s=2.0):
    cell = run.load_cell("wordcount-text-32k.stream")
    return run.Reading(cell, 1, 3, 12, 0, window_s, counters, {}, None)


def _read(metric, reading):
    return importlib.import_module(f"bench.metrics.{metric}").read(reading)


def test_dispatch_host_us_is_dispatch_seconds_per_dispatch():
    r = _reading({"dispatch_s": 0.003, "dispatches": 12})
    assert _read("dispatch_host_us", r) == pytest.approx(250.0)
    # a program without the counter, or no dispatch in the window: nothing
    assert _read("dispatch_host_us", _reading({"dispatches": 12})) is None
    assert _read("dispatch_host_us",
                 _reading({"dispatch_s": 0.0, "dispatches": 0})) is None


def test_feed_wait_pct_is_the_wait_over_the_window():
    r = _reading({"feed_wait_s": 0.01}, window_s=2.0)
    assert _read("feed_wait_pct", r) == pytest.approx(0.5)
    assert _read("feed_wait_pct", _reading({"host_syncs": 3})) is None
