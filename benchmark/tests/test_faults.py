"""A whole run, less the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true for the same run unbroken. Slow for a unit test (each
run starts a node and a load generator): half a minute a case here."""

import json
import os

import pytest
import rehearsal

rehearsal.prepare()

from elasticsearch_tpu.rest.controller import RestController  # noqa: E402
from harness.corpus import shard_of_ids  # noqa: E402

SEARCH = ["--workload", "msmarco-serial", "--seed", str(2**31 + 5),
          "--seconds", "3", "--trace", "0"]
APPEND = ["--workload", "http-logs-append", "--seed", str(2**31 + 6),
          "--seconds", "4", "--trace", "0"]


def _break(monkeypatch, fault):
    plain = RestController.dispatch

    def dispatch(self, method, path, query, body, **kw):
        if fault == "half_of_a_bulk_left_out" and path.endswith("/_bulk"):
            lines = body.split(b"\n")
            keep = len(lines) // 4 * 2
            status, payload = plain(self, method, path, query,
                                    b"\n".join(lines[:keep]) + b"\n", **kw)
            payload["items"] = payload["items"] * 2  # acknowledged whole
            return status, payload
        status, payload = plain(self, method, path, query, body, **kw)
        if not path.endswith("/_search") or not isinstance(payload, dict):
            return status, payload
        hits = payload.get("hits", {}).get("hits", [])
        if fault == "a_score_altered" and len(hits) > 2:
            hits[2]["_score"] *= 1.002
        elif fault == "one_shards_hits_left_out":
            ids = [int(h["_id"]) for h in hits]
            mine = shard_of_ids(ids, 2) == 0
            payload["hits"]["hits"] = [h for h, m in zip(hits, mine) if m]
        elif fault == "a_total_altered" and "total" in payload.get("hits", {}):
            payload["hits"]["total"] += 1
        return status, payload

    monkeypatch.setattr(RestController, "dispatch", dispatch)


@pytest.fixture()
def unproved_cells(monkeypatch):
    """The manifest with the entries of ``unproved_cells.json`` added:
    the append cell is built and tested, and not in BENCHMARK.json."""
    from harness import manifest_check

    here = os.path.dirname(os.path.abspath(__file__))
    extra = manifest_check.load_json(
        os.path.join(here, "unproved_cells.json"))
    plain = manifest_check.load_json

    def load_json(path):
        held = plain(path)
        if os.path.basename(path) == "BENCHMARK.json":
            for section in ("configs", "workloads", "end_to_end",
                            "per_layer"):
                held[section] = held[section] + extra[section]
        return held

    monkeypatch.setattr(manifest_check, "load_json", load_json)


def _check(code, result, log, want):
    assert code == 0, log[-3000:]
    assert result["correct"] is want, json.dumps(result["compared"])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert result["attempted"] > 0 and "setup_s" in result["metrics"]


@pytest.mark.parametrize("fault", [None, "a_score_altered",
                                   "one_shards_hits_left_out"])
def test_search_cell(monkeypatch, fault):
    if fault:
        _break(monkeypatch, fault)
    _check(*rehearsal.run(SEARCH, docs=1500), want=fault is None)


@pytest.mark.parametrize("fault", [None, "half_of_a_bulk_left_out",
                                   "a_total_altered"])
def test_append_cell(monkeypatch, unproved_cells, fault):
    if fault:
        _break(monkeypatch, fault)
    _check(*rehearsal.run(APPEND, docs=1500), want=fault is None)


def test_no_chip_no_result(capfd):
    import run as bench_run

    assert bench_run.main(SEARCH) != 0
    out = capfd.readouterr()
    assert "no TPU" in out.err and out.out == ""
