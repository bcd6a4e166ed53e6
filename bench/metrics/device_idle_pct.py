"""Share of the traced window in which the device ran no operation, in
percent, averaged over the chips of the cell (layer: device)."""
from __future__ import annotations


def read(r):
    t = r.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
