"""``references/logs_columns.py`` and the cell it decides,
``http-logs-search-serial``: the reference in the program's place passes,
its control (timestamps and bounds in float32) does not, an altered answer
does not; and whole runs of the cell, less the look for a chip, against
the program on one and on four virtual devices."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import rehearsal

rehearsal.prepare()

import references  # noqa: E402
from harness import manifest_check, run_cell  # noqa: E402
from harness.comparison import Comparison  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = ["--workload", "http-logs-search-serial", "--seed", str(2**31 + 33),
        "--seconds", "3", "--trace", "0"]


@pytest.fixture(scope="module")
def logs():
    config = manifest_check.load_json(
        os.path.join(ROOT, "benchmark", "configs", "http-logs-search.json"))
    config["docs"] = 3000
    config["generator_params"]["append_pool_docs"] = 1000
    gen = importlib.import_module(f"generators.{config['generator']}")
    dataset = gen.Dataset(config, 2**31 + 12, 2)
    view = dataset.view(dataset.n_docs)
    requests = dataset.operations()["logs_search"]
    refs = {("logs_search", i): r["ref"] for i, r in enumerate(requests)}
    return config, dataset, view, requests, refs


def _answers(reference, refs, control=None):
    """Window records as the reference itself (or its control) answers."""
    records = []
    for rid, ref in refs.items():
        got = reference.answer(ref, control)
        records.append({
            "id": list(rid), "kind": "search", "status": 200,
            "total": got["total"], "ids": [str(i) for i in got["ids"]],
            "scores": got["scores"],
            "aggs": {name: [[key, c] for key, c in counts.items()]
                     for name, counts in got["aggs"].items()}})
    return records


def _compare(config, view, records, refs, control=None):
    cmp = Comparison(config["limits"])
    reference = references.build(config, view)
    run_cell.compare_searches(
        cmp, records, refs, reference,
        lambda ref: reference.work(ref)["bytes"], 1, 10_000, control=control)
    return cmp


def test_the_mix_is_the_one_the_configuration_states(logs):
    config, dataset, _view, requests, _refs = logs
    kinds = [r["ref"]["kind"] for r in requests]
    assert len(kinds) == 1000
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "hourly_agg": 200, "panel": 400, "range": 100, "200s-in-range": 100,
        "404s-in-range": 100, "desc_sort_timestamp": 50,
        "asc_sort_timestamp": 50}
    # Rally's own bodies, verbatim
    assert requests[0]["body"] == {"size": 0, "aggs": {"by_hour": {
        "date_histogram": {"field": "@timestamp", "interval": "hour"}}}}
    assert requests[-1]["body"] == {"query": {"match_all": {}},
                                    "sort": [{"@timestamp": "asc"}]}
    assert all("request_cache=false" in r["path"] for r in requests)
    # about half of the bounds lie on a document's own timestamp
    on_doc = set(dataset.timestamp[: dataset.n_docs].tolist())
    bounds = [b for r in requests if r["ref"]["range"]
              for b in r["ref"]["range"]]
    share = sum(b in on_doc for b in bounds) / len(bounds)
    assert len(bounds) == 1400 and 0.4 < share < 0.6
    # the corpus is http-logs', document for document
    plain = importlib.import_module("generators.http_logs").Dataset(
        config, 2**31 + 12, 2)
    assert dataset.source(17) == plain.source(17)
    assert np.array_equal(dataset.status, plain.status)


def test_the_reference_passes_and_the_float32_control_fails(logs):
    config, _dataset, view, _requests, refs = logs
    reference = references.build(config, view)
    assert reference.controls == ("float32_dates",)
    exact = _answers(reference, refs)
    cmp = _compare(config, view, exact, refs)
    assert cmp.correct() and cmp.compared == 1000, cmp.numbers()
    assert all(n["value"] == 0 for n in cmp.numbers().values())
    # the same answers with timestamps and bounds as float32 holds them
    low = _compare(config, view, _answers(reference, refs, "float32_dates"),
                   refs)
    assert not low.correct()
    worst = low.numbers()
    assert worst["total_abs_diff"]["value"] >= 1
    assert worst["bucket_abs_diff"]["value"] >= 1
    assert worst["bad_hits"]["value"] >= 1  # a sorted top-10 in another order
    # and the harness's --control puts them in the program's place itself
    assert not _compare(config, view, exact, refs,
                        control="float32_dates").correct()


@pytest.mark.parametrize("fault", [
    "a_bucket_altered", "a_hit_out_of_order", "a_total_altered",
    "a_hit_that_does_not_match", "a_score_altered", "a_hit_dropped",
    "a_bucket_left_out"])
def test_an_altered_answer_is_not_correct(logs, fault):
    config, _dataset, view, _requests, refs = logs
    reference = references.build(config, view)
    records = _answers(reference, refs)
    by_kind = {refs[tuple(r["id"])]["kind"]: r for r in reversed(records)}
    if fault == "a_bucket_altered":
        by_kind["hourly_agg"]["aggs"]["by_hour"][3][1] += 1
    elif fault == "a_bucket_left_out":
        by_kind["panel"]["aggs"]["status"].pop()
    elif fault == "a_hit_out_of_order":
        ids = by_kind["desc_sort_timestamp"]["ids"]
        ids[4], ids[5] = ids[5], ids[4]
    elif fault == "a_total_altered":
        by_kind["range"]["total"] -= 1
    elif fault == "a_hit_that_does_not_match":
        hit = by_kind["404s-in-range"]
        ref = refs[tuple(hit["id"])]
        hit["ids"][0] = str(int(np.flatnonzero(
            ~reference.matched(ref))[0]))
    elif fault == "a_score_altered":
        by_kind["200s-in-range"]["scores"][2] = 1.0  # two clauses score 2.0
    elif fault == "a_hit_dropped":
        by_kind["range"]["ids"].pop()
        by_kind["range"]["scores"].pop()
    assert not _compare(config, view, records, refs).correct()


def test_work_counts_the_columns_a_request_reads(logs):
    config, _dataset, view, requests, _refs = logs
    reference = references.build(config, view)
    n = 3000
    by_kind = {r["ref"]["kind"]: reference.work(r["ref"]) for r in requests}
    assert {k: w["bytes"] // n for k, w in by_kind.items()} == {
        "hourly_agg": 4, "panel": 16, "range": 8, "200s-in-range": 12,
        "404s-in-range": 12, "desc_sort_timestamp": 8,
        "asc_sort_timestamp": 8}
    assert {k: w["flops"] // n for k, w in by_kind.items()} == {
        "hourly_agg": 1, "panel": 3, "range": 1, "200s-in-range": 2,
        "404s-in-range": 2, "desc_sort_timestamp": 1,
        "asc_sort_timestamp": 1}
    peaks = manifest_check.load_json(
        os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    assert all(w["peak"] in peaks for w in by_kind.values())


# ----------------------------------------------------------------------
# Whole runs against the program (half a minute each)
# ----------------------------------------------------------------------


def _check(code, result, log, want):
    assert code == 0, log[-3000:]
    assert result["correct"] is want, json.dumps(result["compared"])
    assert result["attempted"] > 0 and result["failed"] == 0
    return result


def test_the_cell_against_the_program():
    result = _check(*rehearsal.run(CELL), want=True)
    assert all(n["value"] == 0 for n in result["compared"].values())
    assert set(result["metrics"]) == {"search_p50_ms", "search_p95_ms",
                                      "setup_s"}


def test_the_cell_under_its_control():
    _check(*rehearsal.run(CELL + ["--control", "float32_dates"]), want=False)


def test_the_cell_with_a_bucket_altered_and_a_hit_out_of_order(monkeypatch):
    from elasticsearch_tpu.rest.controller import RestController

    plain = RestController.dispatch

    def dispatch(self, method, path, query, body, **kw):
        status, payload = plain(self, method, path, query, body, **kw)
        if path.endswith("/_search") and isinstance(payload, dict):
            hits = payload.get("hits", {}).get("hits", [])
            if len(hits) > 3 and hits[0].get("sort"):
                hits[2], hits[3] = hits[3], hits[2]
            for agg in payload.get("aggregations", {}).values():
                if len(agg["buckets"]) > 1:
                    agg["buckets"][1]["doc_count"] += 1
        return status, payload

    monkeypatch.setattr(RestController, "dispatch", dispatch)
    result = _check(*rehearsal.run(CELL), want=False)
    assert result["compared"]["bad_hits"]["value"] >= 1
    assert result["compared"]["bucket_abs_diff"]["value"] == 1
    assert result["compared"]["total_abs_diff"]["value"] == 0


def test_the_cell_traced_on_four_virtual_devices():
    """A process of its own: the device count is fixed before JAX starts."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearsal.py")] + CELL[:-1]
        + ["1"], capture_output=True, text=True, timeout=600,
        env={**os.environ, "REHEARSE_DEVICES": "4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["device"]["count"] == 4
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert set(window["planes"]) == {"mesh"}
    assert window["compiles_in_window"] == 0
    assert "host.sort_ineligible" not in window["decisions"]
    # every metric that needs no device trace reads a number
    assert {"agg.fused_pct.logs", "ladder.mesh_pct.logs",
            "agg.aggregate_ms.logs", "mesh.staging_ms.logs",
            "plan.plan_build_ms.logs", "mesh.kernel_span_ms.logs",
            "mesh.merge_ms.logs"} <= set(result["metrics"])
    assert result["metrics"]["agg.fused_pct.logs"]["value"] == 100.0
    assert result["metrics"]["ladder.mesh_pct.logs"]["value"] == 100.0
