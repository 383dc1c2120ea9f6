"""The reduction from a trace to busy, idle and per-operation time: on
hand-made intervals whose answer is known, and on the recording of a
real trace of this system on a v5e kept beside the reduction."""

import json
import os

import pytest

from harness import trace

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


def test_pattern_that_matches_nothing_says_nothing():
    ops = trace.reduce(_lines(), RULE)["op_seconds"]
    assert trace.matching_seconds(ops, "^kernel") == pytest.approx(0.003)
    assert trace.matching_seconds(ops, "no_such_kernel") is None


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
        assert trace.matching_seconds(
            out["op_seconds"], held["params"]["op_pattern"]) > 0
