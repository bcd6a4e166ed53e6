"""Share of the device's busy time spent in collectives (all-to-all,
all-reduce, all-gather, collective-permute, reduce-scatter), in percent,
averaged over the chips (layer: collectives)."""
from __future__ import annotations

PATTERN = r"^(all-to-all|all-reduce|all-gather|collective-permute|reduce-scatter)"


def read(r):
    t = r.trace
    if t is None or len(t.devices) < 2:
        return None
    busy = t.busy_s()
    if busy <= 0:
        return None
    return 100.0 * t.op_s(PATTERN) / busy
