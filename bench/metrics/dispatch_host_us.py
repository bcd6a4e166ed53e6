"""Host time of one dispatch, in microseconds: the seconds of the program's
``blaze.dispatch`` spans over the window (``SessionStats.dispatch_s``) per
dispatch (``SessionStats.dispatches``) (layer: session and program).  Reads
nothing where the counters lack them."""
from __future__ import annotations


def read(r):
    c = r.counters
    if "dispatch_s" not in c or not c.get("dispatches"):
        return None
    return 1e6 * c["dispatch_s"] / c["dispatches"]
