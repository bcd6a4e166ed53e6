"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The trace holds one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line lists every operation the device ran, with its start and duration in
nanoseconds, and host planes whose lines hold the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names that start with ``bench.``).  Both are
on one clock.  From them:

* busy time: the union of a device's operation intervals inside the window;
* the window: the ``bench.window`` span the runner puts around its timed
  loop (the whole trace where it is missing);
* operation time by name pattern, per device;
* idle gaps: the stretches of the window in which a device ran nothing,
  each put down to the benchmark span that covers most of it.

Only ``jax.profiler.ProfileData`` is used, so the reduction reads any trace
without importing the system under test.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OP_LABEL = 120  # characters of an operation's HLO line in the breakdown


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: its device, its instruction name (``sort.6``
    of the event ``%sort.6 = (f32[...]) sort(...)``), its interval (ns),
    the event's whole HLO line, and whether it holds other operations (a
    ``while`` holds its body's)."""

    device: str
    name: str
    start: float
    end: float
    text: str
    parent: bool = False


@dataclasses.dataclass(frozen=True)
class Span:
    """One host span of the benchmark, ``bench.`` stripped from its name."""

    name: str
    start: float
    end: float


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclasses.dataclass
class Trace:
    """A reduced trace: device ops, benchmark spans and the window (ns)."""

    ops: list[Op]
    spans: list[Span]
    window: tuple[float, float]

    @property
    def devices(self) -> list[str]:
        return sorted({o.device for o in self.ops})

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, device: str) -> list[tuple[float, float]]:
        return _clip(
            _union([(o.start, o.end) for o in self.ops if o.device == device]),
            *self.window,
        )

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(
            sum(e - s for s, e in self._busy(d)) for d in devs
        ) * 1e-9 / len(devs)

    def op_s(self, pattern: str) -> float:
        """Seconds of the operations whose name matches ``pattern`` (a
        regular expression searched case-insensitively), inside the window,
        averaged over the devices."""
        rx = re.compile(pattern, re.IGNORECASE)
        devs = self.devices
        if not devs:
            return 0.0
        per_dev = {d: [] for d in devs}
        for o in self.ops:
            if rx.search(o.name):
                per_dev[o.device].append((o.start, o.end))
        return sum(
            sum(e - s for s, e in _clip(_union(iv), *self.window))
            for iv in per_dev.values()
        ) * 1e-9 / len(devs)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations (by the start of their HLO line) with the
        most device time in the window, as ``[op, seconds]`` averaged over
        the devices; an operation that holds others is left out."""
        devs = self.devices
        tot: dict[str, float] = {}
        lo, hi = self.window
        for o in self.ops:
            d = _overlap(o.start, o.end, lo, hi)
            if d > 0 and not o.parent:
                key = o.text[:OP_LABEL]
                tot[key] = tot.get(key, 0.0) + d
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / max(1, len(devs))] for k, v in ranked]

    def idle_by_span(self, n: int = 10) -> list[list]:
        """Idle device time in the window, summed by the benchmark span that
        covers most of each gap (``other`` where none does), averaged over
        the devices: ``[[span, seconds], ...]``, largest first."""
        devs = self.devices
        tot: dict[str, float] = {}
        lo, hi = self.window
        spans = [s for s in self.spans if s.name != "window"]
        for d in devs:
            edges = [lo]
            for s, e in self._busy(d):
                edges += [s, e]
            edges.append(hi)
            for g0, g1 in zip(edges[::2], edges[1::2]):
                if g1 <= g0:
                    continue
                best, cover, best_len = "other", 0.0, float("inf")
                for sp in spans:
                    c = _overlap(g0, g1, sp.start, sp.end)
                    # ties go to the shorter (inner) span
                    if c > cover or (c == cover and c > 0 and
                                     sp.end - sp.start < best_len):
                        best, cover = sp.name, c
                        best_len = sp.end - sp.start
                tot[best] = tot.get(best, 0.0) + (g1 - g0)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / max(1, len(devs))] for k, v in ranked]


def _op_name(event_name: str) -> str:
    """``sort.6`` from ``%sort.6 = (...) sort(...)``; a plain name as is."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _mark_parents(ops: list[Op]) -> list[Op]:
    """Mark, per device, each operation whose interval holds another's."""
    out = []
    for dev in sorted({o.device for o in ops}):
        mine = sorted((o for o in ops if o.device == dev),
                      key=lambda o: (o.start, -o.end))
        parent = [False] * len(mine)
        stack: list[int] = []
        for i, o in enumerate(mine):
            while stack and mine[stack[-1]].end <= o.start:
                stack.pop()
            if stack and o.end <= mine[stack[-1]].end:
                parent[stack[-1]] = True
            stack.append(i)
        out += [dataclasses.replace(o, parent=p) for o, p in zip(mine, parent)]
    return out


def reduce_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    ops: list[Op] = []
    spans: list[Span] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(
                        plane.name, _op_name(ev.name), ev.start_ns,
                        ev.start_ns + ev.duration_ns, ev.name,
                    ))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(
                        ev.name[len(SPAN_PREFIX):], ev.start_ns,
                        ev.start_ns + ev.duration_ns,
                    ))
    windows = [s for s in spans if s.name == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if windows:
        w = max(windows, key=lambda s: s.end - s.start)
        window = (w.start, w.end)
    elif ops:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    else:
        window = (0.0, 0.0)
    return Trace(_mark_parents(ops), spans, window)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file, or the newest under a directory that
    ``jax.profiler.start_trace`` wrote."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(
            os.path.join(path, "**", "*.xplane.pb"), recursive=True
        )
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(found, key=os.path.getmtime)
    return reduce_profile(ProfileData.from_file(path))
