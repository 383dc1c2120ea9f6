"""From a ``jax.profiler`` trace to busy and idle time, time per device
operation and the longest idle gaps.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain lists
(the only part that needs JAX); ``reduce`` is arithmetic on those lists
and is checked against a small recorded trace kept beside this file
(``recorded_trace.json``, ``tests/test_trace.py``).

The profiler's clock starts with its session; the clients' records are
on ``time.monotonic()``. The tracer (``run_cell.Tracer``) leaves marks in
the trace, annotations named ``MARK`` whose ``time.monotonic_ns()`` it
noted as it entered them; ``clock_offset_ns`` reads them back, and with
it a reader can say which requests' kernel launches lie inside the
device's traced window (``readers/trace_roofline.py``).
"""

from __future__ import annotations

import glob
import json
import os
import re

MARK = "bench:clock_mark"
RULES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "trace_rules.json")


def rules() -> dict:
    with open(RULES, encoding="utf-8") as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> list:
    """[{"plane", "line", "events": [[name, start_ns, duration_ns]]}]"""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                out.append({"plane": plane.name, "line": line.name,
                            "events": events})
    return out


def inventory(lines: list) -> list:
    """[plane, line, events, summed seconds]: what a trace holds, for
    the look by hand that comes before any rule."""
    return [[l["plane"], l["line"], len(l["events"]),
             sum(e[2] for e in l["events"]) / 1e9] for l in lines]


def union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def reduce(lines: list, rule: dict, top: int = 10) -> dict:
    """Busy seconds (union of the device-op intervals, averaged over the
    device planes), the traced window, seconds per operation name, and
    the longest gaps between operations on the first device with the
    host event that overlapped each most. ``busy_events`` keeps each
    device plane's operations as they were read and ``device_window_ns``
    their first start and last end, for a reader that attributes them to
    requests."""
    device = re.compile(rule["device_plane"])
    busy_line = [re.compile(p) for p in rule["busy_lines"]]
    host = re.compile(rule["host_plane"])
    per_plane = {}
    for l in lines:
        if device.search(l["plane"]) and any(
                p.search(l["line"]) for p in busy_line):
            per_plane.setdefault(l["plane"], []).extend(l["events"])
    every = [e for l in lines for e in l["events"]]
    if not every:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "op_seconds": {},
                "busy_events": {}, "device_window_ns": None}
    start = min(e[1] for e in every)
    end = max(e[1] + e[2] for e in every)
    busy, op_seconds = [], {}
    for events in per_plane.values():
        merged = union([(e[1], e[1] + e[2]) for e in events])
        busy.append(sum(hi - lo for lo, hi in merged) / 1e9)
        for name, _, dur in events:
            op_seconds[name] = op_seconds.get(name, 0.0) + dur / 1e9
    n_dev = len(per_plane)
    op_seconds = {k: v / n_dev for k, v in op_seconds.items()}
    gaps = []
    if per_plane:
        first = per_plane[sorted(per_plane)[0]]
        merged = union([(e[1], e[1] + e[2]) for e in first])
        edges = [start] + [x for iv in merged for x in iv] + [end]
        holes = sorted(((edges[i + 1] - edges[i], edges[i])
                        for i in range(0, len(edges), 2)), reverse=True)
        host_events = [e for l in lines if host.search(l["plane"])
                       for e in l["events"]]
        for length, lo in holes[:top]:
            if length <= 0:
                continue
            best, best_overlap = "no_host_event", 0
            for name, s, d in host_events:
                overlap = min(s + d, lo + length) - max(s, lo)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            gaps.append([best, length / 1e9])
    ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
    ops = [(short_name(k), v) for k, v in ops]
    return {"busy_s": sum(busy) / n_dev if n_dev else 0.0,
            "window_s": (end - start) / 1e9, "devices": n_dev,
            "device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps,
            "op_seconds": op_seconds,
            "start_ns": start, "end_ns": end,
            "busy_events": per_plane,
            "device_window_ns": [
                min(e[1] for ev in per_plane.values() for e in ev),
                max(e[1] + e[2] for ev in per_plane.values() for e in ev)]
            if per_plane else None}


def clock_offset_ns(lines: list, rule: dict, marks: list):
    """(profiler's clock less ``time.monotonic_ns()``, how far the marks
    disagree about it), from the tracer's marks in the order it left
    them; (None, None) where the trace holds no mark for each."""
    host = re.compile(rule["host_plane"])
    found = sorted(e[1] for l in lines if host.search(l["plane"])
                   for e in l["events"] if e[0] == MARK)
    if not marks or len(found) != len(marks):
        return None, None
    offsets = [at - mono for at, mono in zip(found, marks)]
    return offsets[0], max(offsets) - min(offsets)


def short_name(op: str, limit: int = 120) -> str:
    """An HLO instruction's text cut to what names it."""
    return op if len(op) <= limit else op[:limit] + "..."
