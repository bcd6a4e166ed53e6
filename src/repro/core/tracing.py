"""Host spans of the program, on the profiler's clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` named
``blaze.<name>``.  With no profiler session running it costs about a
microsecond; while one runs it lands on the host plane of the same trace as
the device's operations, on the line of the thread that opened it.  Given
``stats`` and ``field``, the span's ``time.perf_counter`` duration is also
added to that float counter of ``stats`` (a ``SessionStats``), so a counter
and its span come from one enter and one exit and cannot disagree.

The program's spans (main thread unless noted):

* ``blaze.dispatch`` — ``Program.__call__`` until the executable call
  returns (the host's enqueue), and each per-op dispatch attempt;
  adds to ``SessionStats.dispatch_s``;
* ``blaze.compile`` — the first call of a newly built program executable,
  discovery, trace, lowering and compile included, in place of
  ``blaze.dispatch``; metadata ``plan_hash``;
* ``blaze.sync`` — ``BlazeSession.host_value``, and ``cond(state)`` in
  ``run_loop`` and ``run_stream``;
* ``blaze.feed.wait`` — ``run_stream`` waiting for its next block; adds to
  ``SessionStats.feed_wait_s``;
* ``blaze.feed.produce`` — one block read, decompressed and put on the
  device (on the ``blaze-prefetch`` thread, or inside ``feed.wait``
  without prefetch);
* ``blaze.retry`` — the backoff sleep before a dispatch is re-attempted.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "blaze."


class span:
    """``with span("sync"): ...`` — a ``blaze.``-named host span; with
    ``stats`` and ``field``, its duration in seconds is added to
    ``stats.<field>``.  ``meta`` is the span's trace metadata."""

    __slots__ = ("_ann", "_stats", "_field", "_t0")

    def __init__(self, name: str, stats=None, field: str | None = None, **meta):
        self._ann = TraceAnnotation(PREFIX + name, **meta)
        self._stats = stats
        self._field = field

    def set_metadata(self, **meta) -> None:
        """Metadata known only once the span is open (a plan's hash)."""
        self._ann.set_metadata(**meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._stats is not None:
            elapsed = time.perf_counter() - self._t0
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + elapsed)
        return self._ann.__exit__(*exc)
