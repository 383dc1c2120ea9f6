"""Fused on-device aggregations (ISSUE 13, docs/AGGS.md).

Byte-parity contract: for every fused-eligible agg type, the mesh
program's in-launch reduction must return the EXACT response dict the
host oracle computes — same bucket keys/order/counts, same metric
floats — on every rung (serial mesh_pallas, batched members, with
deletes, multi-segment packed slots). Everything outside the engineered
envelope falls back STRUCTURALLY to the host reduce (counted per
reason) and the pruning x aggs mutual exclusion forces agg'd queries
onto the exhaustive path. Runs the kernel in interpret mode on the CPU
backend (tests/test_pallas_scoring idiom).
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.memory import memory_accountant
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.search import fused_aggs
from elasticsearch_tpu.search.fused_aggs import (
    DENSE_COUNT_MAX_BUCKETS,
    emit_agg_partials,
)
from elasticsearch_tpu.testing.disruption import (
    PlaneFailScheme,
    QueuePressureScheme,
    clear_search_disruptions,
)

MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "n": {"type": "integer"},
    "price": {"type": "double"},
    "ts": {"type": "date"},
    "tag": {"type": "keyword"},
    "tags": {"type": "keyword"},
}}

EPOCH = 1500000000000  # ~2017-07-14, epoch millis


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    yield
    clear_search_disruptions()


def _fill(idx, n_docs=90, refreshes=1, seed=0):
    rng = np.random.RandomState(seed)
    vocab = [f"t{i}" for i in range(12)]
    tags = ["red", "green", "blue", "teal"]
    per = n_docs // refreshes
    for batch in range(refreshes):
        for d in range(batch * per, (batch + 1) * per):
            toks = [vocab[rng.randint(len(vocab))]
                    for _ in range(rng.randint(3, 9))]
            idx.index_doc(str(d), {
                "body": " ".join(toks),
                "n": d % 17,
                "price": (d % 5) + 0.25,  # non-integer: sum falls back
                "ts": EPOCH + (d % 7) * 3600_000,
                "tag": tags[d % 4],
            })
        idx.refresh()
    return idx


def build_pair(prefix, n_shards=2, n_docs=90, refreshes=1, seed=0,
               mesh_extra=None):
    """(mesh index, host-only oracle index) over identical docs."""
    def mk(name, mesh):
        settings = {"index.number_of_shards": n_shards,
                    "index.refresh_interval": -1,
                    "index.search.mesh": mesh}
        settings.update(mesh_extra or {} if mesh else {})
        return _fill(IndexService(name, Settings(settings),
                                  mapping=MAPPING),
                     n_docs=n_docs, refreshes=refreshes, seed=seed)

    return mk(f"{prefix}-mesh", True), mk(f"{prefix}-host", False)


ALL_FUSED_AGGS = {
    "tags": {"terms": {"field": "tag"}},
    "top2": {"terms": {"field": "tag", "size": 2}},
    "bykey": {"terms": {"field": "tag", "order": {"_key": "asc"}}},
    "hist": {"histogram": {"field": "n", "interval": 5}},
    "hoff": {"histogram": {"field": "n", "interval": 4, "offset": 1}},
    "dh": {"date_histogram": {"field": "ts", "interval": "1h"}},
    "st": {"stats": {"field": "n"}},
    "mn": {"min": {"field": "n"}},
    "mx": {"max": {"field": "n"}},
    "sm": {"sum": {"field": "n"}},
    "av": {"avg": {"field": "n"}},
    "vc": {"value_count": {"field": "n"}},
    "dmn": {"min": {"field": "ts"}},  # epoch-ms ints: hi/lo split path
    "dsm": {"sum": {"field": "ts"}},  # bignum digit reconstruction
}


def assert_parity(got, want, score_tol=0.0):
    assert got["hits"]["total"] == want["hits"]["total"]
    assert ([h["_id"] for h in got["hits"]["hits"]]
            == [h["_id"] for h in want["hits"]["hits"]])
    for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
        if score_tol:
            assert abs(g["_score"] - w["_score"]) <= score_tol
        else:
            assert g["_score"] == w["_score"], (g, w)
    assert got.get("aggregations") == want.get("aggregations"), (
        got.get("aggregations"), want.get("aggregations"))


class TestFusedParity:
    def test_every_fused_type_byte_identical(self):
        mesh, host = build_pair("fap")
        try:
            body = {"query": {"match": {"body": "t0 t1"}}, "size": 5,
                    "aggs": dict(ALL_FUSED_AGGS)}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got["_plane"] == "mesh_pallas", got["_plane"]
            assert_parity(got, want)
            ms = mesh._mesh_search
            assert ms.agg_fused_query_total == 1
            assert ms.agg_host_fallback_total == 0, \
                ms.agg_host_fallback_by_reason
            # one count a bucket aggregation, all of them small
            assert (ms.agg_bucket_dense_total,
                    ms.agg_bucket_product_total) == (6, 0)
            # the doc_values ledger kind is populated by the staged
            # agg/sort columns and visible in _stats search.memory
            mem = mesh.search_stats()["memory"]
            assert mem["staged_bytes"]["doc_values"] > 0
        finally:
            mesh.close()
            host.close()
        # leak-free: close released every doc_values byte with the scope
        assert memory_accountant().stats("fap-mesh")[
            "staged_bytes_total"] == 0

    def test_multi_segment_packed_slots(self):
        # 5 shards x 2 refreshes = 10 segments > 8 devices: slot packing
        mesh, host = build_pair("fpk", n_shards=5, n_docs=100,
                                refreshes=2)
        try:
            n_pairs = sum(
                1 for sid in mesh.shards
                for seg in mesh.shards[sid].engine.searchable_segments()
                if seg.num_docs > 0)
            assert n_pairs > 8
            body = {"query": {"match": {"body": "t1 t2"}}, "size": 6,
                    "aggs": {"tags": {"terms": {"field": "tag"}},
                             "st": {"stats": {"field": "n"}},
                             "dh": {"date_histogram": {
                                 "field": "ts", "interval": "1h"}}}}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got["_plane"] == "mesh_pallas", got["_plane"]
            assert_parity(got, want)
        finally:
            mesh.close()
            host.close()

    def test_deletes_excluded_on_device(self):
        mesh, host = build_pair("fdel")
        try:
            for d in range(0, 90, 3):
                mesh.delete_doc(str(d))
                host.delete_doc(str(d))
            body = {"query": {"match": {"body": "t0 t1 t2"}}, "size": 5,
                    "aggs": {"tags": {"terms": {"field": "tag"}},
                             "sm": {"sum": {"field": "n"}},
                             "vc": {"value_count": {"field": "n"}}}}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got["_plane"] == "mesh_pallas", got["_plane"]
            assert_parity(got, want)
        finally:
            mesh.close()
            host.close()

    def test_sorted_query_stays_on_plane_with_fused_aggs(self):
        mesh, host = build_pair("fsrt")
        try:
            body = {"query": {"match": {"body": "t0 t1"}}, "size": 5,
                    "sort": [{"n": "desc"}],
                    "aggs": {"tags": {"terms": {"field": "tag"}}}}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got["_plane"] == "mesh_pallas", got["_plane"]
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            assert got["aggregations"] == want["aggregations"]
            assert mesh._mesh_search.agg_fused_query_total == 1
        finally:
            mesh.close()
            host.close()


class TestBatchedFusedAggs:
    def test_heterogeneous_members_one_launch_member_isolation(self):
        mesh, host = build_pair("fbat")
        try:
            burst = [
                {"query": {"match": {"body": "t0 t1"}}, "size": 5,
                 "aggs": {"tags": {"terms": {"field": "tag"}}}},
                {"query": {"match": {"body": "t2"}}, "size": 4,
                 "aggs": {"st": {"stats": {"field": "n"}},
                          "dh": {"date_histogram": {"field": "ts",
                                                    "interval": "1h"}}}},
                {"query": {"match": {"body": "t3 t4"}}, "size": 6},
                {"query": {"match": {"body": "t1 t5"}}, "size": 5,
                 "aggs": {"h": {"histogram": {"field": "n",
                                              "interval": 4}}}},
            ]
            out = mesh.search_batch([dict(b) for b in burst])
            ms = mesh._mesh_search
            assert ms.batched_launch_total == 1
            for b, got in zip(burst, out):
                assert isinstance(got, dict), got
                assert got["_plane"] == "mesh_pallas", got["_plane"]
                want = host.search(dict(b))
                # batched members share union tables: hits/aggs exact,
                # scores within the established q_batch tolerance
                assert_parity(got, want, score_tol=1e-5)
            assert ms.agg_fused_query_total == 3
        finally:
            mesh.close()
            host.close()

    def test_ineligible_agg_member_demotes_batch_not_peers(self):
        mesh, host = build_pair("fbad")
        try:
            burst = [
                {"query": {"match": {"body": "t0"}}, "size": 4,
                 "aggs": {"tags": {"terms": {"field": "tag"}}}},
                # sub-aggs: outside the fused envelope — the batch falls
                # to the host rung, every member still serves correctly
                {"query": {"match": {"body": "t1"}}, "size": 4,
                 "aggs": {"tags": {"terms": {"field": "tag"},
                                   "aggs": {"m": {"max": {
                                       "field": "n"}}}}}},
            ]
            out = mesh.search_batch([dict(b) for b in burst])
            for b, got in zip(burst, out):
                assert isinstance(got, dict), got
                want = host.search(dict(b))
                assert got["hits"]["total"] == want["hits"]["total"]
                assert got["aggregations"] == want["aggregations"]
            ms = mesh._mesh_search
            assert ms.agg_host_fallback_by_reason.get("sub_aggs", 0) >= 1
        finally:
            mesh.close()
            host.close()


class TestStructuralFallback:
    def test_fallback_reasons_counted_and_results_exact(self):
        mesh, host = build_pair("ffb")
        try:
            # multi-valued keyword: a doc with two tags
            for idx in (mesh, host):
                idx.index_doc("mv", {"body": "t0 t1", "n": 1,
                                     "price": 1.5, "ts": EPOCH,
                                     "tags": ["red", "blue"]})
                idx.refresh()
            cases = [
                # sub-aggs
                ({"tags": {"terms": {"field": "tag"},
                           "aggs": {"m": {"max": {"field": "n"}}}}},
                 "sub_aggs"),
                # multi-valued keyword column
                ({"mv": {"terms": {"field": "tags"}}}, "multi_valued"),
                # non-integer values for a sum
                ({"p": {"sum": {"field": "price"}}},
                 "values_not_fusable"),
                # calendar interval
                ({"cal": {"date_histogram": {"field": "ts",
                                             "interval": "month"}}},
                 "unsupported_params"),
                # cardinality: not a fused type
                ({"card": {"cardinality": {"field": "tag"}}},
                 "unsupported_agg"),
            ]
            for aggs, reason in cases:
                body = {"query": {"match": {"body": "t0 t1"}}, "size": 4,
                        "aggs": aggs}
                got = mesh.search(dict(body))
                want = host.search(dict(body))
                assert got["aggregations"] == want["aggregations"], aggs
                ms = mesh._mesh_search
                assert ms.agg_host_fallback_by_reason.get(reason), (
                    reason, ms.agg_host_fallback_by_reason)
            assert mesh._mesh_search.agg_fused_query_total == 0
        finally:
            mesh.close()
            host.close()

    def test_disabled_by_setting_falls_back_identically(self):
        mesh, host = build_pair(
            "foff", mesh_extra={"index.search.aggs.fused": "false"})
        try:
            body = {"query": {"match": {"body": "t0"}}, "size": 4,
                    "aggs": {"tags": {"terms": {"field": "tag"}}}}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got["aggregations"] == want["aggregations"]
            ms = mesh._mesh_search
            assert ms.agg_fused_query_total == 0
            assert ms.agg_host_fallback_by_reason.get("disabled", 0) >= 1
            # dynamic cluster override re-enables without a restart
            mesh.set_cluster_overrides(
                Settings({"search.aggs.fused": True}))
            got2 = mesh.search(dict(body, size=5))
            assert got2["aggregations"] == want["aggregations"]
            assert ms.agg_fused_query_total == 1
        finally:
            mesh.close()
            host.close()


class TestPruningExclusion:
    EXTRA = {"search.pallas.pruning.enabled": True,
             "search.pallas.pruning.probe_tiles": 2}

    def test_agg_queries_never_prune(self):
        mesh, host = build_pair("fpx", n_docs=600, mesh_extra=self.EXTRA)
        try:
            plain = mesh.search({"query": {"match": {"body": "t1"}},
                                 "size": 5})
            assert "_pruned" in plain, (
                "pruning sanity: the agg-less twin should serve pruned")
            body = {"query": {"match": {"body": "t1"}}, "size": 5,
                    "aggs": {"tags": {"terms": {"field": "tag"}},
                             "sm": {"sum": {"field": "n"}}}}
            got = mesh.search(dict(body))
            want = host.search(dict(body))
            # aggs force the exhaustive path: exact totals, no pruned
            # marker, buckets byte-identical (docs/PRUNING.md)
            assert "_pruned" not in got
            assert got["_plane"] == "mesh_pallas"
            assert_parity(got, want)
        finally:
            mesh.close()
            host.close()


class TestResilienceInteraction:
    def test_brownout_shed_aggs_contract_unchanged(self):
        mesh, _host = build_pair(
            "fbr", mesh_extra={"search.queue.size": 100})
        try:
            body = {"query": {"match": {"body": "t0"}}, "size": 4,
                    "aggs": {"tags": {"terms": {"field": "tag"}}}}
            qp = QueuePressureScheme(occupancy=90,
                                     indices=["fbr-mesh"]).install()
            try:
                mesh.admission.refresh_level()
                shed = mesh.search(dict(body))
            finally:
                qp.remove()
                mesh.admission.refresh_level()
            assert "aggs" in shed.get("_degraded", [])
            assert "aggregations" not in shed
            # no fused work happened for the shed aggs
            assert mesh._mesh_search.agg_fused_query_total == 0
            healed = mesh.search(dict(body))
            assert "_degraded" not in healed
            assert "aggregations" in healed
        finally:
            mesh.close()

    def test_fused_launch_fault_quarantines_once_host_serves(self):
        mesh, host = build_pair("fqf")
        try:
            body = {"query": {"match": {"body": "t0 t1"}}, "size": 5,
                    "aggs": {"tags": {"terms": {"field": "tag"}},
                             "st": {"stats": {"field": "n"}}}}
            scheme = PlaneFailScheme(planes=["mesh_pallas"]).install()
            try:
                got = mesh.search(dict(body))
            finally:
                scheme.remove()
            want = host.search(dict(body))
            assert got["_plane"] != "mesh_pallas"
            assert got["aggregations"] == want["aggregations"]
            ph = mesh._mesh_search.plane_health
            assert ph.failures_total["mesh_pallas"] == 1
            assert "mesh_pallas" in ph.quarantined()
        finally:
            mesh.close()
            host.close()


class TestLedgerLifecycle:
    def test_doc_values_leak_free_across_merge_and_evict(self):
        acct = memory_accountant()
        mesh, host = build_pair("flg", refreshes=2, n_docs=80)
        try:
            body = {"query": {"match": {"body": "t0"}}, "size": 4,
                    "aggs": {"tags": {"terms": {"field": "tag"}},
                             "sm": {"sum": {"field": "n"}}}}
            got = mesh.search(dict(body))
            assert got["_plane"] == "mesh_pallas"
            mem = acct.stats("flg-mesh")
            assert mem["staged_bytes"]["doc_values"] > 0
            assert any(e["kind"] == "doc_values"
                       for e in mem["staging_events"]), (
                "doc_values staging must emit lifecycle events")
            # force-merge retires the segment set: the executor (and its
            # doc_values columns) rebuild on the next query, leak-free
            mesh.force_merge()
            mesh.refresh()
            got2 = mesh.search(dict(body))
            want = host.search(dict(body))
            assert got2["aggregations"] == want["aggregations"]
            # eviction drops the staged columns; the next query restages
            # (force_evict is global-LRU, so assertions stay per-index —
            # other tests' cold scopes may evict too)
            freed = acct.force_evict(scopes=8)
            assert freed > 0
            got3 = mesh.search(dict(body))
            assert got3["aggregations"] == want["aggregations"]
        finally:
            mesh.close()
            host.close()
        for name in ("flg-mesh", "flg-host"):
            assert acct.staged_bytes(name) == 0, (
                f"doc_values ledger leaked for [{name}] across "
                f"merge/evict cycles")


# bucket counts on both sides of the limit between the two formulations
BUCKET_SIZES = [1, 5, 96, DENSE_COUNT_MAX_BUCKETS,
                DENSE_COUNT_MAX_BUCKETS + 1, 4096]
BUCKET_ND1 = 1537  # no multiple of a tile


def _bucket_case(case, nb, rng):
    codes = rng.randint(-1, nb, size=BUCKET_ND1).astype(np.int32)
    mask = rng.rand(BUCKET_ND1) < 0.5
    if case == "all_false":
        mask[:] = False
    elif case == "all_true":
        mask[:] = True
    elif case == "every_code_missing":
        codes[:] = -1
    elif case == "codes_only_at_last":
        codes[:] = nb - 1
    return codes, mask


class TestBucketCounts:
    @pytest.mark.parametrize("case", [
        "all_false", "all_true", "random", "every_code_missing",
        "codes_only_at_last"])
    @pytest.mark.parametrize("nb", BUCKET_SIZES)
    def test_bucket_partial_is_the_bincount_of_the_selected_codes(
            self, nb, case):
        import jax

        codes, mask = _bucket_case(case, nb, np.random.RandomState(nb))
        statics = (("bucket", "codes", nb),)
        (got,) = jax.jit(
            lambda c, m: emit_agg_partials(statics, {"codes": c}, m))(
                codes, mask)
        got = np.asarray(got)
        assert got.dtype == np.int32 and got.shape == (nb,)
        selected = codes[mask & (codes >= 0)]
        assert np.array_equal(got, np.bincount(selected, minlength=nb))

    @pytest.mark.parametrize("nb", BUCKET_SIZES)
    def test_dead_slot_stand_in_yields_the_identity(self, nb):
        # what ``_mesh_query_program.dead_slot`` asks: one document, an
        # all-false mask
        (got,) = emit_agg_partials(
            (("bucket", "codes", nb),),
            {"codes": np.zeros((1,), np.int32)}, np.zeros((1,), bool))
        got = np.asarray(got)
        assert got.dtype == np.int32 and got.shape == (nb,)
        assert not got.any()

    def test_counters_say_which_formulation_each_count_traced(
            self, monkeypatch):
        # (the limit is read as the program is traced and as the query
        # is counted: lowered, this small index has counts on both sides)
        monkeypatch.setattr(fused_aggs, "DENSE_COUNT_MAX_BUCKETS", 3)
        mesh, host = build_pair("fbc")
        try:
            body = {"query": {"match": {"body": "t0 t1"}}, "size": 3,
                    "aggs": {"tags": {"terms": {"field": "tag"}},  # 4
                             "h5": {"histogram": {"field": "n",
                                                  "interval": 5}},  # 4
                             "h8": {"histogram": {"field": "n",
                                                  "interval": 8}},  # 3
                             "sm": {"sum": {"field": "n"}}}}
            got = mesh.search(dict(body))
            assert got["_plane"] == "mesh_pallas", got["_plane"]
            assert_parity(got, host.search(dict(body)))
            planes = mesh.search_stats()["planes"]
            assert planes["agg_fused_query_total"] == 1
            assert planes["agg_bucket_dense_total"] == 1
            assert planes["agg_bucket_product_total"] == 2
        finally:
            mesh.close()
            host.close()
