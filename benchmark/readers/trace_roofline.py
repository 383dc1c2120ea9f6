"""A kernel's share of its roofline: the least time the chip could take
for the work of the queries completed inside the traced interval, over
the device time of the kernel's events there. The work is counted from
the benchmark's own corpus (``harness/work.py``); the kernel is found by
the name pattern in the metric's file."""

from harness import trace, work
from readers import searches


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["devices"]:
        return None
    kernel_s = trace.matching_seconds(t["op_seconds"], params["op_pattern"])
    if not kernel_s:
        return None
    lo, hi = ctx["trace_interval"]
    n_bytes = 0
    for r in searches(ctx):
        if lo <= r["sent"] and r["done"] <= hi:
            ref = ctx["refs"][tuple(r["id"])]
            n_bytes += work.match_postings_bytes(
                ctx["view"]["text_fields"][ref["field"]], ref["terms"])
    if not n_bytes:
        return None
    return 100.0 * work.least_seconds(
        n_bytes, ctx["peaks"], ctx["chips"]) / kernel_s
