"""A kernel's share of its roofline: the least time the chip could take
for the work of the requests whose kernel launches lie inside the
device's traced window, over the device time of the kernel's events
that those requests launched.

Both sides are of the same requests. The device's window (first start
to last end of the device's operations) is carried onto the clients'
clock by the tracer's marks (``harness/trace.py``); a request counts
where it was sent and answered inside it, so every launch it made is in
the trace; a kernel event counts where it started while one of those
requests was in flight, so the launches of a request that the window
cut are left out with its work. How long the profiler takes to start
and stop moves neither side. The work is the cell's reference's
(``work(ref)``, ``harness/work.py``); the kernel is found by the name
pattern in the metric's file."""

import bisect
import re

from harness import trace, work
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
    kernel_ns = 0
    for events in t["busy_events"].values():
        for name, start, dur in events:
            i = bisect.bisect_right(starts, clients(start)) - 1
            if i >= 0 and clients(start) <= flight[i][1] and rx.search(name):
                kernel_ns += dur
    if not kernel_ns:
        return None
    least = sum(work.least_seconds(
        ctx["reference"].work(ctx["refs"][tuple(r["id"])]),
        ctx["peaks"], ctx["chips"]) for r in inside)
    return 100.0 * least * t["devices"] / (kernel_ns / 1e9)
