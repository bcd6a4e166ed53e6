#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, and the idle gaps put
down to them.

``bench/trace.py`` reads the device operations and the benchmark's
``bench.`` spans.  This module reads the same ``.xplane.pb`` for the
program's ``blaze.`` spans as well (``repro.core.tracing``), each with its
line: the host thread that opened it.  Each idle gap of the window goes to
the benchmark span that covers most of it, by the rule of
``trace.Trace.idle_by_span``; then, of the program spans on that benchmark
span's line, the one that covers most of the gap (ties go to the shorter,
inner one) names it ``<bench span>/<program span>``, as ``dispatch/sync``
or ``dispatch/feed.wait``.  A gap that no program span on that line covers
keeps the benchmark span's name.

On a trace kept by ``bench/run.py --trace 1 --keep-trace DIR``::

    python3 bench/program_spans.py DIR

prints one JSON object: the window, the busy time, the idle gaps so named,
and for each program span its count and seconds inside the window.  Like
``bench/trace.py``, it imports nothing of the system under test.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "blaze."


@dataclasses.dataclass(frozen=True)
class LineSpan:
    """A host span, its prefix (``bench.`` or ``blaze.``) stripped, and its
    line: the trace's host plane and the index of the thread's line."""

    name: str
    start: float
    end: float
    line: tuple


def _widest(g0: float, g1: float, spans) -> LineSpan | None:
    """The span that covers most of the gap ``[g0, g1)``; ties go to the
    shorter (inner) span, and ``None`` where none covers any of it."""
    best, cover, best_len = None, 0.0, float("inf")
    for sp in spans:
        c = trace._overlap(g0, g1, sp.start, sp.end)
        if c > cover or (c == cover and c > 0 and sp.end - sp.start < best_len):
            best, cover, best_len = sp, c, sp.end - sp.start
    return best


@dataclasses.dataclass
class ProgramTrace:
    """A reduced trace (device ops, ``bench.`` spans, the window) with the
    benchmark's and the program's host spans on their lines (ns)."""

    trace: trace.Trace
    bench: list[LineSpan]
    program: list[LineSpan]

    def idle_by_span(self, n: int = 10) -> list[list]:
        """Idle device time in the window by ``<bench span>/<program
        span>`` (the bench span's name alone where no program span on its
        line covers the gap; ``other`` where no bench span does), averaged
        over the devices: ``[[name, seconds], ...]``, largest first."""
        t = self.trace
        devs = t.devices
        lo, hi = t.window
        bench = [s for s in self.bench if s.name != "window"]
        tot: dict[str, float] = {}
        for d in devs:
            edges = [lo]
            for s, e in t._busy(d):
                edges += [s, e]
            edges.append(hi)
            for g0, g1 in zip(edges[::2], edges[1::2]):
                if g1 <= g0:
                    continue
                outer = _widest(g0, g1, bench)
                name = "other" if outer is None else outer.name
                if outer is not None:
                    inner = _widest(g0, g1, [p for p in self.program
                                             if p.line == outer.line])
                    if inner is not None:
                        name = f"{name}/{inner.name}"
                tot[name] = tot.get(name, 0.0) + (g1 - g0)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / max(1, len(devs))] for k, v in ranked]

    def span_totals(self) -> dict[str, list]:
        """For each program span name, ``[count, seconds]`` of its spans
        that start inside the window, over every line; seconds end at the
        window's end."""
        lo, hi = self.trace.window
        out: dict[str, list] = {}
        for sp in self.program:
            if lo <= sp.start < hi:
                row = out.setdefault(sp.name, [0, 0.0])
                row[0] += 1
                row[1] += (min(sp.end, hi) - sp.start) * 1e-9
        return out


def reduce_profile(profile) -> ProgramTrace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`ProgramTrace`."""
    bench: list[LineSpan] = []
    program: list[LineSpan] = []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                for prefix, into in ((trace.SPAN_PREFIX, bench),
                                     (PROGRAM_PREFIX, program)):
                    if ev.name.startswith(prefix):
                        into.append(LineSpan(
                            ev.name[len(prefix):], ev.start_ns,
                            ev.start_ns + ev.duration_ns, (plane.name, i),
                        ))
    return ProgramTrace(trace.reduce_profile(profile), bench, program)


def load(path: str) -> ProgramTrace:
    """Read one ``.xplane.pb`` file, or the newest under a directory that
    ``jax.profiler.start_trace`` wrote."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(found, key=os.path.getmtime)
    return reduce_profile(ProfileData.from_file(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a trace directory or one .xplane.pb")
    pt = load(ap.parse_args(argv).path)
    print(json.dumps({
        "window_s": pt.trace.window_s,
        "busy_s": pt.trace.busy_s(),
        "idle_gaps": pt.idle_by_span(20),
        "spans": pt.span_totals(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
