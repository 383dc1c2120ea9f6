"""The benchmark's ``readers/trace_plane_imbalance.py`` and
``readers/trace_op_ms.py`` on hand-made ``ctx``s whose answers are
known: how unevenly the chips of a four-chip cell are busy, and the
device time a request of the merge's collectives. Loaded by file path,
as ``test_launch_split_reader.py`` loads its reader: nothing under
``benchmark/`` knows of this file."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH, ROOT]  # the readers' ``harness`` and ``readers``


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "readers", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


imbalance = _load("trace_plane_imbalance")
op_ms = _load("trace_op_ms")

OFFSET = 7_000_000_000  # the trace's clock less time.monotonic_ns()
MS = 1_000_000


def _params(metric):
    """The ``params`` the cell's metric file gives its reader."""
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json"),
              encoding="utf-8") as f:
        return json.load(f)["params"]


COLLECTIVES = _params("mesh.collective_ms.mesh4")
IMBALANCE = _params("device.plane_imbalance.mesh4")


def _ctx(planes, chips=4, records=(), window=(0, 100)):
    """``planes``: {plane: [[name, start_ms, duration_ms]]}, on the
    trace's clock less its offset."""
    events = {p: [[n, int(s * MS) + OFFSET, int(d * MS)] for n, s, d in evs]
              for p, evs in planes.items()}
    return {"chips": chips, "records": list(records), "trace": {
        "devices": len(events), "clock_offset_ns": OFFSET,
        "busy_events": events,
        "device_window_ns": [int(window[0] * MS) + OFFSET,
                             int(window[1] * MS) + OFFSET]}}


def _search(sent_ms, done_ms):
    return {"kind": "search", "status": 200, "group": "g",
            "sent": sent_ms * MS / 1e9, "done": done_ms * MS / 1e9}


def _plane(kernel_ms, merge_ms=0.1, slowest_ms=None):
    """A launch at 10 ms: ``kernel_ms`` of passes, then the merge, which
    waits for the slowest chip's ``slowest_ms`` of passes."""
    slowest = kernel_ms if slowest_ms is None else slowest_ms
    return [["%score_tiles.1 = custom-call", 10.0, kernel_ms],
            ["%all-reduce.3 = all-reduce", 10.0 + kernel_ms,
             slowest - kernel_ms + merge_ms]]


class TestPlaneImbalance:
    def test_even_chips_read_one(self):
        ctx = _ctx({f"/device:TPU:{d}": _plane(0.7) for d in range(4)})
        assert imbalance.read(ctx, IMBALANCE) == pytest.approx(1.0)

    def test_two_chips_doing_all_the_passes(self):
        # the parent's layout: all 8 segments on chips 0 and 1; chips 2
        # and 3 wait inside the merge's all-reduce for them
        planes = {"/device:TPU:0": _plane(1.4), "/device:TPU:1": _plane(1.4),
                  "/device:TPU:2": _plane(0.0, slowest_ms=1.4),
                  "/device:TPU:3": _plane(0.0, slowest_ms=1.4)}
        busy = [1.4, 1.4, 0.0, 0.0]
        assert imbalance.read(_ctx(planes), IMBALANCE) == pytest.approx(
            1.4 / (sum(busy) / 4))

    def test_waiting_inside_a_collective_is_no_work(self):
        # counted as busy, the wait makes every chip look alike
        planes = {f"/device:TPU:{d}": _plane(1.4 if d < 2 else 0.0,
                                             slowest_ms=1.4)
                  for d in range(4)}
        assert imbalance.read(_ctx(planes), {}) == pytest.approx(1.0)
        assert imbalance.read(_ctx(planes), IMBALANCE) == pytest.approx(2.0)

    def test_a_chip_the_trace_shows_nothing_for_counts_at_zero(self):
        planes = {"/device:TPU:0": _plane(0.9), "/device:TPU:1": _plane(0.9)}
        assert imbalance.read(_ctx(planes, chips=4),
                              IMBALANCE) == pytest.approx(2.0)

    def test_overlapping_events_are_a_union(self):
        # a conditional's event encloses its kernel's
        plane = [["%conditional.1 = conditional", 10.0, 1.0],
                 ["%score_tiles.1 = custom-call", 10.2, 0.5]]
        ctx = _ctx({"/device:TPU:0": plane, "/device:TPU:1": plane},
                   chips=2)
        assert imbalance.read(ctx, IMBALANCE) == pytest.approx(1.0)

    def test_no_traced_device_reads_none(self):
        assert imbalance.read({"trace": None, "chips": 4}, IMBALANCE) is None
        assert imbalance.read(_ctx({}), IMBALANCE) is None
        only_merge = {"/device:TPU:0": [["%all-reduce.3 = all-reduce",
                                         10.0, 0.1]]}
        assert imbalance.read(_ctx(only_merge, chips=1), IMBALANCE) is None


class TestCollectiveMs:
    def test_device_ms_a_request_averaged_over_the_planes(self):
        planes = {f"/device:TPU:{d}": _plane(0.7, merge_ms=0.05 * (d + 1))
                  for d in range(4)}
        ctx = _ctx(planes, records=[_search(9.0, 12.0)])
        # 0.05 + 0.10 + 0.15 + 0.20 over four planes, one request
        assert op_ms.read(ctx, COLLECTIVES) == pytest.approx(0.125)

    def test_only_ops_of_requests_inside_the_window_count(self):
        planes = {"/device:TPU:0": _plane(0.7) + [
            ["%all-gather.2 = all-gather", 50.0, 0.3]]}
        # the second request's launch at 50 ms ends past the window
        ctx = _ctx(planes, chips=1, window=(0, 51),
                   records=[_search(9.0, 12.0), _search(49.0, 52.0)])
        assert op_ms.read(ctx, COLLECTIVES) == pytest.approx(0.1)

    def test_the_pattern_names_the_collectives_alone(self):
        planes = {"/device:TPU:0": [
            ["%all-reduce-start.4 = all-reduce-start", 10.0, 0.02],
            ["%all-reduce-done.4 = all-reduce-done", 10.1, 0.03],
            ["%fusion.all-reduce = fusion", 10.2, 5.0],
            ["%sort.1 = sort", 10.3, 0.5]]}
        ctx = _ctx(planes, chips=1, records=[_search(9.0, 12.0)])
        assert op_ms.read(ctx, COLLECTIVES) == pytest.approx(0.05)

    def test_nothing_matching_reads_none(self):
        ctx = _ctx({"/device:TPU:0": [["%sort.1 = sort", 10.0, 0.5]]},
                   chips=1, records=[_search(9.0, 12.0)])
        assert op_ms.read(ctx, COLLECTIVES) is None
        assert op_ms.read({"trace": None}, COLLECTIVES) is None
