"""Share of the HBM roofline one step of the whole pass reaches, in percent
(layer: map and combine).

The least time of a step is the bytes the step must read and write once,
from the configuration's shapes (``step_bytes`` of the cell's job kind),
split over the chips and divided by one chip's peak HBM bandwidth.  The
measured time of a step is the window's host time over its steps.
"""
from __future__ import annotations


def read(r):
    step_bytes = getattr(r.cell.job, "step_bytes", None)
    bandwidth = r.peaks.get("hbm_bytes_per_s")
    if step_bytes is None or not bandwidth or not r.steps or r.window_s <= 0:
        return None
    least_s = step_bytes(r.cell.config) / r.chips / bandwidth
    return 100.0 * least_s / (r.window_s / r.steps)
