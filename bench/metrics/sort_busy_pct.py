"""Share of the device's busy time spent in sort operations, in percent
(layer: local combine, the eager hash path's sort-based combine)."""
from __future__ import annotations

PATTERN = r"^sort"


def read(r):
    t = r.trace
    if t is None:
        return None
    busy = t.busy_s()
    if busy <= 0:
        return None
    return 100.0 * t.op_s(PATTERN) / busy
