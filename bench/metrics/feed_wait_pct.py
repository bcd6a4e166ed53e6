"""Share of the window the program spent waiting for its next streamed
block: the seconds of ``run_stream``'s ``blaze.feed.wait`` spans over the
window (``SessionStats.feed_wait_s``) ÷ the window (layer: feed).  Reads
nothing where the counters lack it."""
from __future__ import annotations


def read(r):
    if "feed_wait_s" not in r.counters or not r.window_s:
        return None
    return 100.0 * r.counters["feed_wait_s"] / r.window_s
