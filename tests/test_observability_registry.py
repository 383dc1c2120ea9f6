"""Observability-registry lint: every counter/histogram key exported by
the ``_stats`` / ``_nodes/stats`` search sections must be documented in
docs/OBSERVABILITY.md.

Mirror of test_settings_registry.py: an undocumented stats key silently
ships an operator surface nobody can discover or rely on — this tier-1
lint walks the REAL response shapes and fails on drift, so new
telemetry must land in the doc first.
"""

import os

import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService

DOC_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "docs", "OBSERVABILITY.md")


def _doc_text():
    with open(DOC_PATH, encoding="utf-8") as f:
        return f.read()


def _walk_keys(obj, out, skip_subtrees=("groups", "tenants"),
               split_subtrees=("decisions",)):
    """Collect every dict key in the response, skipping log2 bucket
    labels (``le_*``), numeric keys (batch-size histogram buckets), and
    the user-named ``groups``/``tenants`` subtrees (tenant keys are
    client-chosen X-Opaque-Id values — docs/OVERLOAD.md); ``decisions``
    keys are ``<plane>.<reason>`` compounds — each part collects
    separately."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            ks = str(k)
            if ks.isdigit() or ks.startswith("le_"):
                continue
            if ks in split_subtrees:
                out.add(ks)
                for ck in v:
                    out.update(str(ck).split("."))
                continue
            out.add(ks)
            if ks in skip_subtrees:
                continue
            _walk_keys(v, out, skip_subtrees, split_subtrees)
    elif isinstance(obj, list):
        for v in obj:
            _walk_keys(v, out, skip_subtrees, split_subtrees)


@pytest.fixture(scope="module")
def exercised_index():
    idx = IndexService("obslint", Settings({
        "index.number_of_shards": 2,
        "index.refresh_interval": -1,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    for d in range(12):
        idx.index_doc(str(d), {"body": f"w{d % 3} common"})
    idx.refresh()
    # populate the phase histograms / decision counters with real
    # traffic (whatever plane serves on this backend)
    idx.search({"query": {"match": {"body": "common"}}, "size": 3})
    idx.search({"query": {"match": {"body": "w1"}}, "size": 3,
                "profile": True})
    yield idx
    idx.close()


class TestObservabilityRegistryLint:
    def test_index_search_stats_keys_documented(self, exercised_index):
        doc = _doc_text()
        keys: set = set()
        _walk_keys(exercised_index.search_stats(), keys)
        missing = sorted(k for k in keys if k not in doc)
        assert not missing, (
            f"_stats search keys absent from docs/OBSERVABILITY.md: "
            f"{missing} — document every exported counter/histogram "
            f"(phase taxonomy, plane names, and ladder-decision reasons "
            f"included) before shipping it")

    def test_node_stats_search_keys_documented(self, exercised_index):
        from elasticsearch_tpu.search.telemetry import merge_phase_stats

        doc = _doc_text()
        merged = merge_phase_stats([exercised_index.search_stats()])
        keys: set = set()
        _walk_keys(merged, keys)
        missing = sorted(k for k in keys if k not in doc)
        assert not missing, (
            f"_nodes/stats search keys absent from docs/OBSERVABILITY.md:"
            f" {missing}")

    def test_lint_actually_sees_known_keys(self, exercised_index):
        # the lint is only trustworthy if the walk reaches the real
        # structure: anchor on keys known to exist today
        keys: set = set()
        _walk_keys(exercised_index.search_stats(), keys)
        for known in ("phases", "histogram_us", "counters", "decisions",
                      "taxonomy", "queries_recorded", "planes", "batch",
                      "quarantine_events", "plane_failures_total",
                      "admission", "brownout_level"):
            assert known in keys, f"lint walk no longer reaches [{known}]"

    def test_admission_block_exported_and_documented(self, exercised_index):
        # ISSUE 12 (docs/OVERLOAD.md): the `search.admission` block —
        # queue gauges, admitted/rejected/expired counters, brownout
        # ladder state + per-step shed counts, Retry-After — exported in
        # _stats and merged into _nodes/stats, every key documented
        doc = _doc_text()
        adm = exercised_index.search_stats()["admission"]
        for key in ("queue_capacity", "queued", "in_flight",
                    "admitted_total", "rejected_total",
                    "expired_in_queue_total", "brownout_level",
                    "brownout", "brownout_transitions", "retry_after_s",
                    "drain_rate_qps", "tenants"):
            assert key in adm, adm.keys()
            assert key in doc, f"[{key}] undocumented"
        for step in ("forced_pruned_total", "shed_rescore_total",
                     "shed_features_total"):
            assert step in adm["brownout"], adm["brownout"]
            assert step in doc, f"[{step}] undocumented"
        # the exercised traffic was admitted and accounted
        assert adm["admitted_total"] >= 2
        assert "_anonymous" in adm["tenants"]
        # batch block: the adaptive-window gauge rides beside the
        # batch-size histogram
        batch = exercised_index.search_stats()["batch"]
        assert "batch_window_effective_ms" in batch
        assert "batch_window_effective_ms" in doc

    def test_fused_agg_counters_exported_and_documented(
            self, exercised_index):
        # ISSUE 13 (docs/AGGS.md): the fused-aggregation plane's
        # adoption counters — and the fallback-reason vocabulary — are
        # part of the documented operator surface
        doc = _doc_text()
        planes = exercised_index.search_stats()["planes"]
        for key in ("agg_fused_query_total", "agg_host_fallback_total",
                    "agg_host_fallback_by_reason",
                    "agg_bucket_dense_total", "agg_bucket_product_total"):
            assert key in planes, planes.keys()
            assert key in doc, f"[{key}] undocumented"
        # the `aggregate` phase joined the taxonomy ring
        phases = exercised_index.search_stats()["phases"]
        assert "aggregate" in phases["taxonomy"]
        assert "aggregate" in doc
        for reason in ("disabled", "unsupported_agg", "sub_aggs",
                       "multi_valued", "values_not_fusable",
                       "bucket_range", "unsupported_params",
                       "field_ineligible", "resolve_error"):
            assert reason in doc, f"fallback reason [{reason}] undocumented"

    def test_compile_block_exported_and_documented(self, exercised_index):
        # ISSUE 14 (docs/RESILIENCE.md "Rollout & drain"): the compile
        # plane's counters — persistent-cache hit/miss, warmed
        # programs, query-path first compiles, the stall histogram —
        # are part of the documented operator surface, as are the
        # admission drain keys
        doc = _doc_text()
        comp = exercised_index.search_stats()["compile"]
        for key in ("cache_enabled", "cache_path", "variants_recorded",
                    "compile_cache_hit_total", "compile_cache_miss_total",
                    "programs_warmed_total",
                    "query_path_first_compile_total",
                    "first_compile_stall_ms", "first_compile_events"):
            assert key in comp, comp.keys()
            assert key in doc, f"[{key}] undocumented"
        adm = exercised_index.search_stats()["admission"]
        for key in ("draining", "drain_rejected_total"):
            assert key in adm, adm.keys()
            assert key in doc, f"[{key}] undocumented"

    def test_lint_catches_undocumented_key(self):
        doc = _doc_text()
        keys: set = set()
        _walk_keys({"phases": {"totally_undocumented_key_xyz": 1}}, keys)
        assert "totally_undocumented_key_xyz" in keys
        assert "totally_undocumented_key_xyz" not in doc

    def test_device_memory_stats_keys_documented(self, exercised_index):
        # the search.memory block (ISSUE 9): ledger byte sums, staging/
        # eviction event rings, restage amplification — every exported
        # key (kind names included) must be in docs/OBSERVABILITY.md
        doc = _doc_text()
        mem = exercised_index.search_stats()["memory"]
        keys: set = set()
        _walk_keys(mem, keys)
        missing = sorted(k for k in keys if k not in doc)
        assert not missing, (
            f"search.memory keys absent from docs/OBSERVABILITY.md: "
            f"{missing}")
        from elasticsearch_tpu.common.memory import KINDS

        for kind in KINDS:
            assert kind in mem["staged_bytes"], mem["staged_bytes"]
            assert kind in doc, f"ledger kind [{kind}] undocumented"

    def test_integrity_stats_keys_documented(self, exercised_index):
        # ISSUE 16: the `search.integrity` block — detection counters
        # with the per-site split, marker lifecycle counters + event
        # ring, scrub counters — every exported key (site names
        # included) must be in docs/OBSERVABILITY.md
        doc = _doc_text()
        integ = exercised_index.search_stats()["integrity"]
        keys: set = set()
        _walk_keys(integ, keys)
        missing = sorted(k for k in keys if k not in doc)
        assert not missing, (
            f"search.integrity keys absent from docs/OBSERVABILITY.md: "
            f"{missing}")
        from elasticsearch_tpu.common.integrity import SITES

        for site in SITES:
            assert site in integ["corruption_detected_by_site"], integ
            assert site in doc, f"detection site [{site}] undocumented"
        # the marker-event vocabulary (action values + event fields) is
        # part of the documented operator surface
        for word in ("detected", "marked", "cleared", "drift",
                     "action", "marker", "reason", "timestamp_ms"):
            assert word in doc, f"event vocabulary [{word}] undocumented"

    def test_staging_fault_counters_documented_and_exported(
            self, exercised_index):
        # ISSUE 10: the classified staging-fault model must export its
        # counters (search.memory) and the plane-probe/reason split
        # (search.planes) — and every key must be documented
        doc = _doc_text()
        mem = exercised_index.search_stats()["memory"]
        for key in ("staging_retries_total",
                    "staging_faults_transient_total",
                    "staging_faults_deterministic_total",
                    "staging_fault_events"):
            assert key in mem, mem.keys()
            assert key in doc, f"[{key}] undocumented"
        planes = exercised_index.search_stats()["planes"]
        for key in ("plane_failures_by_reason", "plane_probes_total"):
            assert key in planes, planes.keys()
            assert key in doc, f"[{key}] undocumented"
        # the quarantine reasons + decision reason are part of the
        # documented vocabulary
        for reason in ("kernel_fault", "staging_fault"):
            assert reason in doc, f"reason [{reason}] undocumented"

    def test_node_breakers_and_transport_keys_documented(self):
        # _nodes/stats breakers (the accounting child mirrors the device
        # ledger) and the PR-2 transport resilience counters must stay
        # documented — OBSERVABILITY.md for the blocks, RESILIENCE.md
        # carries the transport row-level table
        from elasticsearch_tpu.common.breaker import breaker_service
        from elasticsearch_tpu.transport.local import (
            aggregate_transport_stats,
        )

        doc = _doc_text()
        keys: set = set()
        _walk_keys(breaker_service().stats(), keys)
        _walk_keys(aggregate_transport_stats(), keys)
        missing = sorted(k for k in keys if k not in doc)
        assert not missing, (
            f"_nodes/stats breakers/transport keys absent from "
            f"docs/OBSERVABILITY.md: {missing}")
