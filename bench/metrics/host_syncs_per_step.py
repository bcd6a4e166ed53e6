"""Blocking host syncs the session counted over the window, per step
(layer: session and program, ``SessionStats.host_syncs``)."""
from __future__ import annotations


def read(r):
    if not r.steps:
        return None
    return r.counters["host_syncs"] / r.steps
