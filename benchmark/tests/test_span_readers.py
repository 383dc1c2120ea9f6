"""The readers of the program's span tree (``_stats`` ``search.spans``):
arithmetic on a made-up window, the manifest's twelve entries, and the
contract between what the program writes and what the readers expect,
on a tiny node on the CPU (a check of counts: the values there are the
sandbox's times and no device metric)."""

import importlib
import json
import os

import pytest

from harness import manifest_check, server
from readers import client_side, span_mean

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_METRICS = [
    "frontdoor.http_request_ms.serial", "frontdoor.http_inbound_ms.serial",
    "frontdoor.http_outbound_ms.serial", "frontdoor.admit_ms.serial",
    "frontdoor.unattributed_ms.serial", "frontdoor.client_side_ms.serial",
    "mesh.lock_wait_ms.serial", "mesh.dispatch_ms.serial",
    "mesh.device_wait_ms.serial", "mesh.d2h_ms.serial",
    "frontdoor.route_ms.serial", "frontdoor.respond_ms.serial"]


def _span(count, sum_ns, self_ns):
    return {"count": count, "sum_ns": sum_ns, "self_ns": self_ns}


def _ctx():
    before = {"spans": {"http.request": _span(10, 50_000_000, 5_000_000),
                        "search.request": _span(10, 40_000_000, 2_000_000),
                        "kernel.dispatch": _span(10, 9_000_000, 9_000_000)}}
    after = {"spans": {"http.request": _span(14, 110_000_000, 7_000_000),
                       "search.request": _span(14, 92_000_000, 3_000_000),
                       "kernel.dispatch": _span(14, 13_000_000, 13_000_000),
                       "merge.d2h": _span(4, 2_000_000, 2_000_000)}}
    records = [{"kind": "search", "status": 200, "group": "g",
                "sent": 1.0 + i, "done": 1.0 + i + 0.0165}
               for i in range(4)]
    return {"stats_before": before, "stats_after": after,
            "records": records}


def test_span_mean_is_the_windows_delta_over_its_count():
    ctx = _ctx()
    assert span_mean.read(ctx, {"spans": ["http.request"],
                                "field": "sum_ns"}) == pytest.approx(15.0)
    assert span_mean.read(ctx, {"spans": ["kernel.dispatch"],
                                "field": "sum_ns"}) == pytest.approx(1.0)
    # a span the window saw first: nothing to subtract
    assert span_mean.read(ctx, {"spans": ["merge.d2h"],
                                "field": "sum_ns"}) == pytest.approx(0.5)
    # several spans: their fields summed, over the first one's count
    assert span_mean.read(
        ctx, {"spans": ["http.request", "search.request"],
              "field": "self_ns"}) == pytest.approx(0.75)


def test_span_mean_says_nothing_where_there_is_nothing_to_read():
    ctx = _ctx()
    params = {"spans": ["http.request"], "field": "sum_ns"}
    for side in ("stats_before", "stats_after"):
        lacking = dict(ctx, **{side: {"phases": {}}})  # the parent commit
        assert span_mean.read(lacking, params) is None
        assert client_side.read(lacking, {}) is None
    still = dict(ctx, stats_after=ctx["stats_before"])  # a zero count
    assert span_mean.read(still, params) is None
    assert span_mean.read(ctx, {"spans": ["kernel.lock_wait"],
                                "field": "sum_ns"}) is None


def test_client_side_is_the_round_trip_less_the_servers_span():
    ctx = _ctx()
    assert client_side.read(ctx, {}) == pytest.approx(16.5 - 15.0)
    assert client_side.read(dict(ctx, records=[]), {}) is None


def test_manifest_holds_the_span_metrics_and_is_sound():
    assert manifest_check.check(ROOT) == []
    manifest = manifest_check.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(SPAN_METRICS) <= set(by_name) and len(by_name) == 18
    assert "frontdoor.outside_phases_ms.serial" not in by_name  # retired
    for m in (by_name[name] for name in SPAN_METRICS):
        assert (m["unit"], m["better"], m["source"], m["moves"],
                m["workloads"]) == ("ms", "lower", "program_span",
                                    "search_p50_ms", ["msmarco-serial"])


def test_the_block_the_program_writes_is_the_block_the_readers_expect(
        tmp_path):
    served = server.Served(str(tmp_path / "data"))
    http, index, n = served.http, "spans", 6
    try:
        http.request("PUT", f"/{index}", {
            "settings": {"number_of_shards": 2, "refresh_interval": -1},
            "mappings": {"_doc": {"properties": {
                "text": {"type": "text"}}}}})
        lines = []
        for d in range(120):
            lines.append(json.dumps({"index": {"_id": str(d)}}))
            lines.append(json.dumps(
                {"text": f"w{d % 7} w{d % 11} w{d % 13}"}))
        http.request("POST", f"/{index}/_bulk", "\n".join(lines) + "\n")
        http.request("POST", f"/{index}/_forcemerge?max_num_segments=1")
        http.request("POST", f"/{index}/_refresh")
        body = {"query": {"match": {"text": "w1 w2"}}, "size": 10}
        assert http.request("POST", f"/{index}/_search",
                            body)["_plane"] == "mesh_pallas"
        before = served.search_stats(index)
        for _ in range(n):
            http.request("POST", f"/{index}/_search", body)
        after = served.search_stats(index)
    finally:
        served.close()
    ctx = {"stats_before": before, "stats_after": after,
           "records": [{"kind": "search", "status": 200, "group": "g",
                        "sent": 0.0, "done": 1.0}] * n}
    spans = {"http.request", "http.inbound", "http.outbound",
             "search.request", "search.admit", "kernel.lock_wait",
             "kernel.dispatch", "kernel.device_wait", "merge.d2h",
             "search.route", "search.respond"}
    for name in spans:  # each once a request, all of them drained
        assert (after["spans"][name]["count"]
                - before["spans"][name]["count"]) == n, name
    for name in SPAN_METRICS:
        held = manifest_check.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.json"))
        assert set(held["params"].get("spans", [])) <= spans
        reader = importlib.import_module(f"readers.{held['reader']}")
        value = reader.read(ctx, held["params"])
        assert value is not None and value >= 0.0, name
