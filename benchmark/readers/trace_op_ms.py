"""Device milliseconds a request of the operations whose name matches a
pattern (the collectives of the cross-chip merge, say), averaged over
the device planes.

The requests are those sent and answered inside the device's traced
window, and an operation counts where it started while one of them was
in flight, as ``trace_roofline.py`` selects both. ``params``:
``op_pattern``, a regular expression searched in the operation's name
(the HLO text the trace gives it). None without a traced device, or
where no such request or no matching operation is found."""

import bisect
import re

from harness import trace
from readers import searches


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["devices"] or t.get("clock_offset_ns") is None:
        return None

    def clients(ns):  # a time of the trace on the clients' clock
        return (ns - t["clock_offset_ns"]) / 1e9

    lo, hi = (clients(ns) for ns in t["device_window_ns"])
    inside = [r for r in searches(ctx) if lo <= r["sent"] and r["done"] <= hi]
    flight = trace.union([(r["sent"], r["done"]) for r in inside])
    starts = [f[0] for f in flight]
    rx = re.compile(params["op_pattern"])
    op_ns = 0
    for events in t["busy_events"].values():
        for name, start, dur in events:
            i = bisect.bisect_right(starts, clients(start)) - 1
            if i >= 0 and clients(start) <= flight[i][1] and rx.search(name):
                op_ns += dur
    if not op_ns or not inside:
        return None
    return op_ns / len(t["busy_events"]) / len(inside) / 1e6
