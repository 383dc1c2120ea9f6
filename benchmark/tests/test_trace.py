"""The reduction from a trace to busy, idle and per-operation time: on
hand-made intervals whose answer is known, and on the recording of a
real trace of this system on a v5e kept beside the reduction; the marks
that carry the device's window onto the clients' clock, and the roofline
reader that counts the work and the kernel time of the same requests."""

import json
import os
import re

import pytest

from harness import trace
from readers import trace_roofline

RULE = trace.rules()
HERE = os.path.dirname(os.path.abspath(trace.__file__))


def _lines():
    ms = 1_000_000
    return [
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": [
            ["fusion.1", 0 * ms, 2 * ms], ["kernel.7", 1 * ms, 3 * ms],
            ["fusion.1", 10 * ms, 1 * ms]]},
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            ["jit_program", 0, 11 * ms]]},  # not a busy line
        {"plane": "/host:CPU", "line": "python", "events": [
            ["wait_for_request", 4 * ms, 5 * ms], ["dispatch", 9 * ms, ms],
            ["end", 19 * ms, ms]]}]


def test_busy_is_the_union_and_gaps_are_named_by_the_host():
    out = trace.reduce(_lines(), RULE)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.005)  # [0,4) and [10,11)
    assert out["window_s"] == pytest.approx(0.020)
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 0.003, "kernel.7": 0.003})
    assert out["idle_gaps"][0] == ["end", pytest.approx(0.009)]
    assert out["idle_gaps"][1] == ["wait_for_request", pytest.approx(0.006)]


def test_no_device_plane_is_no_busy_time():
    host_only = [l for l in _lines() if l["plane"] == "/host:CPU"]
    out = trace.reduce(host_only, RULE)
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_recorded_trace_of_a_v5e():
    with open(os.path.join(HERE, "recorded_trace.json"),
              encoding="utf-8") as f:
        recorded = json.load(f)
    out = trace.reduce(recorded["lines"], RULE)
    want = recorded["expected"]
    assert out["devices"] == want["devices"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    for metric_file in want["kernel_patterns"]:
        held = json.load(open(os.path.join(
            os.path.dirname(HERE), "layer_metrics", metric_file)))
        rx = re.compile(held["params"]["op_pattern"])
        assert sum(v for k, v in out["op_seconds"].items()
                   if rx.search(k)) > 0


# ----------------------------------------------------------------------
# The clients' clock, and the roofline of the device window's requests
# ----------------------------------------------------------------------

MS = 1_000_000
OFFSET = 5 * MS - 7_000_000_000_000  # profiler's clock less monotonic_ns


def test_clock_offset_is_read_from_the_marks():
    lines = _lines() + [{"plane": "/host:CPU", "line": "tracer", "events": [
        [trace.MARK, 2 * MS, MS], [trace.MARK, 18 * MS, MS]]}]
    marks = [2 * MS - OFFSET, 18 * MS + 300 - OFFSET]
    assert trace.clock_offset_ns(lines, RULE, marks) == (OFFSET, 300)
    assert trace.clock_offset_ns(_lines(), RULE, marks) == (None, None)
    assert trace.clock_offset_ns(lines, RULE, marks[:1]) == (None, None)


class _Work:
    """A reference whose requests need a known number of bytes."""

    def work(self, ref):
        return {"bytes": ref["bytes"], "flops": 0}


def _roofline_ctx(extra_records=()):
    """Five serial requests of 10 ms, one every 12 ms; the device traced
    from the middle of the second to the middle of the fifth; a kernel
    of 1 ms, 3 ms into each request."""
    def at(ms):  # a time of the trace, on the clients' clock, in seconds
        return (ms * MS - OFFSET) / 1e9

    records = [{"kind": "search", "status": 200, "id": ["q", i],
                "sent": at(12 * i), "done": at(12 * i + 10)}
               for i in range(5)] + list(extra_records)
    kernels = [["%kernel.1 = f32[8]", (12 * i + 3) * MS, MS]
               for i in range(1, 5)]  # the first request: before the trace
    other = [["%sort.1 = f32[8]", (12 * i + 5) * MS, 2 * MS]
             for i in range(1, 5)]
    lines = [{"plane": "/device:TPU:0", "line": "XLA Ops",
              "events": [["%fusion = f32[8]", 14 * MS, MS]] + kernels
              + other[:3] + [["%fusion = f32[8]", 52 * MS, MS]]}]
    reduced = trace.reduce(lines, RULE)
    reduced["clock_offset_ns"] = OFFSET
    refs = {("q", i): {"bytes": 1000 * (i + 1)} for i in range(5)}
    refs[("late", 0)] = {"bytes": 10**9}
    return {"records": records, "refs": refs, "trace": reduced,
            "reference": _Work(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 1e9}}


def test_roofline_is_of_the_requests_inside_the_devices_window():
    ctx = _roofline_ctx()
    # the window is [14, 53) ms: requests 2 and 3 lie inside (24-34,
    # 36-46); 1 was sent before it and 4 answered after it, and their
    # kernels (at 15 and 51 ms) are left out with their work
    assert ctx["trace"]["device_window_ns"] == [14 * MS, 53 * MS]
    got = trace_roofline.read(ctx, {"op_pattern": "^%kernel"})
    least = (3000 + 4000) / 1e9
    assert got == pytest.approx(100.0 * least / 0.002)


def test_roofline_does_not_move_with_the_hosts_interval():
    """Requests answered while the profiler starts and stops (the old
    reader's 7 s around the device's 3 s) change nothing."""
    base = trace_roofline.read(_roofline_ctx(), {"op_pattern": "^%kernel"})
    late = [{"kind": "search", "status": 200, "id": ["late", 0],
             "sent": 100.0 + 7000, "done": 100.01 + 7000}]
    assert trace_roofline.read(_roofline_ctx(late),
                               {"op_pattern": "^%kernel"}) == base


def test_roofline_says_nothing_without_marks_kernel_or_device():
    ctx = _roofline_ctx()
    assert trace_roofline.read(ctx, {"op_pattern": "^%absent"}) is None
    ctx["trace"]["clock_offset_ns"] = None
    assert trace_roofline.read(ctx, {"op_pattern": "^%kernel"}) is None
    assert trace_roofline.read(dict(ctx, trace=None),
                               {"op_pattern": "^%kernel"}) is None
