"""How unevenly a launch's work falls on the chips: the busiest device
plane's busy time over the mean plane's, from the traced interval's
``busy_events`` (``trace.reduce``: each device plane's operations, the
union of their intervals being its busy time).

One mesh program runs on every chip at once and ends with its slowest,
so a chip that holds no segment (headroom only) idles while the others
score: 1.0 where every chip does the same work, 2.0 where half of them
do all of it. That idle is spent INSIDE the program's first collective,
which on a chip with nothing to score starts at once and ends when the
slowest chip joins it: counted as busy, it makes every chip look alike.
``params``: ``exclude_pattern``, a regular expression searched in an
operation's name; operations it matches (the collectives) count as no
work. A chip the trace shows no operation for counts at zero busy time,
out of the cell's ``chips``. None without a traced device."""

import re

from harness import trace


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["devices"]:
        return None
    skip = params.get("exclude_pattern")
    skip = re.compile(skip) if skip else None
    busy = [sum(hi - lo for lo, hi in trace.union(
        [(e[1], e[1] + e[2]) for e in events
         if not (skip and skip.search(e[0]))]))
        for events in t["busy_events"].values()]
    planes = max(len(busy), ctx.get("chips") or 1)
    mean = sum(busy) / planes
    return max(busy) / mean if mean > 0 else None
