"""Share of the HBM roofline the segment-reduce kernel reaches, in percent
(layer: kernels, ``kernels/segment_reduce.py``).

Bytes: one pass of the combine reads every pair once and writes the table
(``segment_kernel_bytes`` of the cell's job kind, from the configuration's
shapes), times the passes the window ran, split over the chips.  Time: the
device time of the kernel's operations in the trace.  The kernel moves far
more bytes than it computes operations, so the bandwidth bounds it.
"""
from __future__ import annotations

# The kernel's operations: on a v5e the custom call takes the name of the
# jitted ``segment_reduce`` that launches it (``%segment_reduce.7 = ...
# custom-call(...)``).
PATTERN = r"^segment_reduce"


def read(r):
    job = r.cell.job
    kernel_bytes = getattr(job, "segment_kernel_bytes", None)
    bandwidth = r.peaks.get("hbm_bytes_per_s")
    if r.trace is None or kernel_bytes is None or not bandwidth:
        return None
    seconds = r.trace.op_s(PATTERN)
    if seconds <= 0:
        return None
    passes = r.jobs * job.passes_per_job(r.cell.config)
    least_s = kernel_bytes(r.cell.config) * passes / r.chips / bandwidth
    return 100.0 * least_s / seconds
