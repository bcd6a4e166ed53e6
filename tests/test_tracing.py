"""The program's host spans (``repro.core.tracing``) and the two counters they
feed: every span opens and closes once per event, under ``blaze.``, and
``SessionStats.dispatch_s`` / ``feed_wait_s`` grow only where their path
runs."""
import threading
import time
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BlazeSession, faults, tracing
from repro.core.session import SessionStats


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each open
    and close with its thread, and the metadata."""

    def __init__(self):
        self.events = []
        self.meta = []
        rec = self

        class Annotation:
            def __init__(self, name, **meta):
                self.name = name
                rec.meta.append((name, dict(meta)))

            def set_metadata(self, **meta):
                rec.meta.append((self.name, dict(meta)))

            def __enter__(self):
                rec.events.append(("open", self.name,
                                   threading.current_thread().name))

            def __exit__(self, *exc):
                rec.events.append(("close", self.name,
                                   threading.current_thread().name))

        self.Annotation = Annotation

    def opened(self):
        return Counter(n for kind, n, _ in self.events if kind == "open")

    def closed(self):
        return Counter(n for kind, n, _ in self.events if kind == "close")

    def threads(self, name):
        return {t for kind, n, t in self.events if kind == "open" and n == name}


@pytest.fixture
def rec(monkeypatch):
    r = _Recorder()
    monkeypatch.setattr(tracing, "TraceAnnotation", r.Annotation)
    return r


def _sq_mapper(i, x, emit):
    emit(i % 7, x * x)


def _loop_program(sess):
    x = sess.distribute(np.arange(64, dtype=np.float32))

    def step(ctx, s):
        part = ctx.map_reduce(x, _sq_mapper, "sum", jnp.zeros((7,), jnp.float32))
        return {"acc": s["acc"] + part.astype(s["acc"].dtype)}

    return sess.program(step), {"acc": jnp.zeros((7,), jnp.float32)}


def _stream_program(sess, n_blocks=4):
    cv = sess.chunked(np.arange(64 * n_blocks, dtype=np.float32), block_rows=64)

    def step(ctx, s):
        part = ctx.map_reduce(cv, _sq_mapper, "sum", jnp.zeros((7,), jnp.float32))
        return {"acc": s["acc"] + part}

    return sess.program(step), {"acc": jnp.zeros((7,), jnp.float32)}


# -- the helper ---------------------------------------------------------------


def test_span_is_named_blaze_and_carries_metadata(rec):
    with tracing.span("compile", plan_hash="abc") as sp:
        sp.set_metadata(n=1)
    assert rec.events == [("open", "blaze.compile", "MainThread"),
                          ("close", "blaze.compile", "MainThread")]
    assert rec.meta == [("blaze.compile", {"plan_hash": "abc"}),
                        ("blaze.compile", {"n": 1})]


def test_span_adds_its_duration_to_the_counter():
    stats = SessionStats()
    with tracing.span("dispatch", stats, "dispatch_s"):
        time.sleep(0.01)
    assert 0.01 <= stats.dispatch_s < 1.0
    before = stats.dispatch_s
    with tracing.span("sync"):  # no counter named: none moves
        time.sleep(0.001)
    assert stats.dispatch_s == before and stats.feed_wait_s == 0.0


def test_span_closes_and_counts_when_its_body_raises(rec):
    stats = SessionStats()
    with pytest.raises(RuntimeError):
        with tracing.span("feed.wait", stats, "feed_wait_s"):
            raise RuntimeError("boom")
    assert rec.opened() == rec.closed() == Counter({"blaze.feed.wait": 1})
    assert stats.feed_wait_s > 0.0


# -- once per event -------------------------------------------------------------


def test_run_loop_spans_once_per_event(rec):
    sess = BlazeSession()
    prog, state = _loop_program(sess)
    _, info = sess.run_loop(prog, state, cond=lambda s: False, max_iters=6,
                            unroll=2)
    assert info.dispatches == 3 and info.host_syncs == 3
    want = Counter({"blaze.compile": 1, "blaze.dispatch": 2, "blaze.sync": 3})
    assert rec.opened() == rec.closed() == want


@pytest.mark.parametrize("prefetch", [True, False])
def test_run_stream_spans_once_per_event(rec, prefetch):
    sess = BlazeSession()
    prog, state = _stream_program(sess, n_blocks=4)
    _, info = sess.run_stream(prog, state, cond=lambda s: False,
                              prefetch=prefetch)
    assert info.dispatches == 4
    want = Counter({
        "blaze.compile": 1, "blaze.dispatch": 3,
        "blaze.feed.produce": 4,
        "blaze.feed.wait": 5,  # one per block and the one that finds the end
        "blaze.sync": 1,
    })
    assert rec.opened() == rec.closed() == want
    produced_on = rec.threads("blaze.feed.produce")
    assert produced_on == ({"blaze-prefetch"} if prefetch else {"MainThread"})
    assert rec.threads("blaze.feed.wait") == {"MainThread"}


def test_host_value_is_one_sync_span(rec):
    sess = BlazeSession()
    got = sess.host_value(jnp.arange(3))
    np.testing.assert_array_equal(got, [0, 1, 2])
    assert rec.opened() == rec.closed() == Counter({"blaze.sync": 1})
    assert sess.stats.host_syncs == 1


def test_a_first_build_is_one_compile_span_with_its_plan_hash(rec):
    sess = BlazeSession()
    prog, state = _loop_program(sess)
    for _ in range(3):
        state = prog(state, 1)
    assert rec.opened() == Counter({"blaze.compile": 1, "blaze.dispatch": 2})
    assert ("blaze.compile", {"plan_hash": prog.plan_hash}) in rec.meta
    # a new state signature is a new executable: it compiles again
    prog({"acc": jnp.zeros((7,), jnp.int32)}, 1)
    assert rec.opened()["blaze.compile"] == 2


def test_a_program_built_before_its_first_call_compiles_at_that_call(rec):
    sess = BlazeSession()
    prog, state = _loop_program(sess)
    prog.build(state)
    assert rec.opened() == Counter()
    prog(state, 1)
    prog(state, 1)
    assert rec.opened() == Counter({"blaze.compile": 1, "blaze.dispatch": 1})


def test_a_forced_retry_is_one_retry_span(rec):
    sess = BlazeSession()
    prog, state = _loop_program(sess)
    prog(state, 1)  # compiled outside the fault window
    with faults.inject("dispatch", every=1, times=1):
        sess.run_loop(prog, state, max_iters=1)
    assert sess.stats.retries == 1
    got = rec.opened()
    assert got["blaze.retry"] == 1
    assert got["blaze.dispatch"] == 2  # the faulted attempt and its retry
    assert rec.opened() == rec.closed()


def test_a_per_op_dispatch_is_a_dispatch_span_per_attempt(rec):
    sess = BlazeSession()
    x = sess.distribute(np.arange(64, dtype=np.float32))
    zeros = jnp.zeros((7,), jnp.float32)
    sess.map_reduce(x, _sq_mapper, "sum", zeros)
    with faults.inject("dispatch", every=1, times=1):
        sess.map_reduce(x, _sq_mapper, "sum", zeros)
    got = rec.opened()
    assert got == Counter({"blaze.dispatch": 3, "blaze.retry": 1})
    assert rec.opened() == rec.closed()


# -- the counters -----------------------------------------------------------------


def test_dispatch_s_grows_and_feed_wait_stays_zero_under_run_loop():
    sess = BlazeSession()
    prog, state = _loop_program(sess)
    sess.run_loop(prog, state, max_iters=1)  # compiles: no dispatch seconds
    assert sess.stats.dispatch_s == 0.0 and sess.stats.dispatches == 1
    sess.run_loop(prog, state, max_iters=4)
    assert sess.stats.dispatch_s > 0.0
    assert sess.stats.feed_wait_s == 0.0


@pytest.mark.parametrize("prefetch", [True, False])
def test_feed_wait_s_grows_under_run_stream(prefetch):
    sess = BlazeSession()
    prog, state = _stream_program(sess)
    sess.run_stream(prog, state, prefetch=prefetch)
    assert sess.stats.feed_wait_s > 0.0
    assert sess.stats.dispatch_s > 0.0


def test_cache_info_reports_both_counters():
    sess = BlazeSession()
    info = sess.cache_info()
    assert info["dispatch_s"] == 0.0 and info["feed_wait_s"] == 0.0
    prog, state = _stream_program(sess)
    sess.run_stream(prog, state, max_epochs=2)
    info = sess.cache_info()
    assert info["dispatch_s"] == sess.stats.dispatch_s > 0.0
    assert info["feed_wait_s"] == sess.stats.feed_wait_s > 0.0
