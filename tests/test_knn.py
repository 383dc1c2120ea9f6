"""Dense-vector kNN retrieval plane + hybrid ranking (ISSUE 7).

Covers the vertical slice end to end:

- kernel: ``knn_score_tiles`` (the MXU matmul with fused per-tile
  top-k, q_batch dim, dot/cosine metrics) matches the exact f32 numpy
  oracle over the same bf16-rounded vectors;
- mapper/segment: dims validation (wrong-dims / non-numeric / oversized
  mapping reject with 400), bf16-grid storage, store + translog-only
  recovery round-trips, ``_source`` intact;
- search: knn query clause + top-level knn section, live-mask delete
  exclusion, hybrid RRF/convex fusion, host/mesh parity on the
  8-device CPU mesh, batched kNN bursts through search_batch,
  PlaneFailScheme quarantine-once, dynamic search.knn.* overrides;
- REST: track_total_hits-style total rendering (the PR-6 gte leftover).

Everything runs the kernels in interpret mode on the CPU backend — the
same semantics the compiled TPU path executes (test_pallas_scoring
idiom).
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import (
    IllegalArgumentException,
    MapperParsingException,
    QueryShardException,
)
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.ops import pallas_knn as pkn
from elasticsearch_tpu.testing.disruption import (
    PlaneFailScheme,
    clear_search_disruptions,
)

DIMS = 12

MAPPING = {
    "properties": {
        "emb": {"type": "dense_vector", "dims": DIMS,
                "similarity": "cosine"},
        "body": {"type": "text", "analyzer": "whitespace"},
        "n": {"type": "integer"},
    }
}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    yield
    clear_search_disruptions()


def build_index(n_shards=1, n_docs=60, seed=0, mapping=None,
                **extra_settings):
    idx = IndexService(
        f"knn-{n_shards}s-{seed}", Settings({
            "index.number_of_shards": n_shards,
            "index.refresh_interval": -1, **extra_settings}),
        mapping=mapping or MAPPING)
    rng = np.random.RandomState(seed)
    vecs = rng.randn(n_docs, DIMS).astype(np.float32)
    for d in range(n_docs):
        idx.index_doc(str(d), {"emb": vecs[d].tolist(),
                               "body": f"term{d % 7} term{d % 3}",
                               "n": d})
    idx.refresh()
    return idx, vecs


def oracle_ids(vecs, q, k, metric="cosine", live=None):
    vb = pkn.bf16_round(vecs)
    mask = np.ones(len(vb), bool) if live is None else live
    _s, idx = pkn.reference_knn_topk(vb, mask, q, k, metric)
    return [str(i) for i in idx]


# ----------------------------------------------------------------------
# Kernel
# ----------------------------------------------------------------------


class TestKnnKernel:
    @pytest.mark.parametrize("metric", pkn.METRICS)
    def test_kernel_matches_oracle(self, metric):
        import jax.numpy as jnp

        rng = np.random.RandomState(2)
        nd, d = 3000, 24
        vecs = pkn.bf16_round(rng.randn(nd, d))
        d_pad = pkn.pad_dims(d)
        geom = pkn.knn_geometry(4096, d_pad, 8)
        assert geom.n_tiles > 1  # exercise the grid + doc-base offsets
        emb = np.zeros((geom.nd_pad, d_pad), np.float32)
        emb[:nd, :d] = vecs
        mask = np.zeros((geom.nd_pad, 1), np.float32)
        mask[:nd] = 1.0
        mask[7] = 0.0  # a deleted doc must never surface
        scale = np.zeros((geom.nd_pad, 1), np.float32)
        scale[:nd] = (pkn.vector_scale_column(vecs, metric)[:nd]
                      if metric == "cosine" else 1.0)
        qs = rng.randn(3, d).astype(np.float32)
        qmat = np.stack([pkn.normalize_query(q, metric, d_pad)
                         for q in qs]
                        + [np.zeros(d_pad, np.float32)])  # q_pad row
        ts, td = pkn.knn_score_tiles(
            jnp.asarray(emb, jnp.bfloat16), jnp.asarray(scale),
            jnp.asarray(mask), jnp.asarray(qmat),
            sub=geom.tile_sub, k=10, q_batch=4, interpret=True)
        top_s, top_d = (np.asarray(o)
                        for o in pkn.merge_knn_topk(ts, td, 10))
        live = np.ones(nd, bool)
        live[7] = False
        for q in range(3):
            ref_s, ref_i = pkn.reference_knn_topk(vecs, live, qs[q], 10,
                                                  metric)
            assert top_d[q].tolist() == ref_i.tolist()
            # the kernel ranks by the similarity; the winners' _score is
            # hit_score of it
            np.testing.assert_allclose(pkn.hit_score(top_s[q], metric),
                                       ref_s, rtol=1e-6)
            assert 7 not in top_d[q]

    def test_tile_sub_shrinks_for_vmem(self):
        # high-dimensional fields shrink the tile so the f32 block fits
        assert pkn.knn_tile_sub(1 << 20, pkn.pad_dims(1024)) < \
            pkn.DEFAULT_KNN_SUB
        assert pkn.knn_tile_sub(1 << 20, pkn.pad_dims(128)) == \
            pkn.DEFAULT_KNN_SUB


# ----------------------------------------------------------------------
# Mapper validation + recovery
# ----------------------------------------------------------------------


class TestMapperValidation:
    def test_missing_dims_rejected(self):
        with pytest.raises(MapperParsingException):
            IndexService("bad-dims", Settings({
                "index.number_of_shards": 1}), mapping={
                "properties": {"v": {"type": "dense_vector"}}}).close()

    def test_dims_above_max_rejected(self):
        with pytest.raises(IllegalArgumentException):
            IndexService("big-dims", Settings({
                "index.number_of_shards": 1,
                "index.mapping.dense_vector.max_dims": 8}), mapping={
                "properties": {
                    "v": {"type": "dense_vector", "dims": 16}}}).close()

    def test_unknown_similarity_rejected(self):
        with pytest.raises(MapperParsingException):
            IndexService("bad-sim", Settings({
                "index.number_of_shards": 1}), mapping={
                "properties": {"v": {"type": "dense_vector", "dims": 4,
                                     "similarity": "l2"}}}).close()

    def test_wrong_dims_doc_rejected_400(self):
        idx, _ = build_index()
        with pytest.raises(MapperParsingException) as ei:
            idx.index_doc("bad", {"emb": [1.0, 2.0]})
        assert ei.value.status_code == 400
        idx.close()

    def test_non_numeric_vector_rejected_400(self):
        idx, _ = build_index()
        with pytest.raises(MapperParsingException) as ei:
            idx.index_doc("bad", {"emb": ["x"] * DIMS})
        assert ei.value.status_code == 400
        with pytest.raises(MapperParsingException):
            idx.index_doc("bad2", {"emb": "not-a-vector"})
        idx.close()

    def test_dense_vector_multi_field_rejected(self):
        with pytest.raises(MapperParsingException):
            IndexService("mf", Settings({
                "index.number_of_shards": 1}), mapping={
                "properties": {"t": {"type": "text", "fields": {
                    "v": {"type": "dense_vector", "dims": 4}}}}}).close()

    def test_knn_on_non_vector_field_400(self):
        idx, _ = build_index()
        with pytest.raises(QueryShardException):
            idx.search({"query": {"knn": {
                "field": "body", "query_vector": [0.0] * DIMS}}})
        with pytest.raises(IllegalArgumentException):
            idx.search({"query": {"knn": {
                "field": "emb", "query_vector": [0.0] * (DIMS + 1)}}})
        idx.close()


class TestRecovery:
    def test_translog_only_recovery_round_trip(self, tmp_data_dir):
        settings = Settings({"index.number_of_shards": 1,
                             "index.refresh_interval": -1})
        idx = IndexService("vrec", settings, mapping=MAPPING,
                           data_path=tmp_data_dir)
        rng = np.random.RandomState(4)
        vecs = rng.randn(8, DIMS).astype(np.float32)
        idx.index_doc("0", {"emb": vecs[0].tolist()})
        idx.flush()  # one committed segment
        for d in range(1, 8):
            idx.index_doc(str(d), {"emb": vecs[d].tolist()})
        idx.close()  # docs 1..7 exist ONLY in the translog

        idx2 = IndexService("vrec", settings, mapping=MAPPING,
                            data_path=tmp_data_dir)
        q = rng.randn(DIMS).astype(np.float32)
        r = idx2.search({"query": {"knn": {
            "field": "emb", "query_vector": q.tolist()}}, "size": 8})
        assert r["hits"]["total"] == 8
        assert [h["_id"] for h in r["hits"]["hits"]] == \
            oracle_ids(vecs, q, 8)
        # _source round-trips bit-exactly through the translog replay
        got = idx2.get_doc("5")
        assert got.found and np.allclose(got.source["emb"], vecs[5])
        idx2.close()

    def test_store_persists_bf16_grid(self, tmp_data_dir):
        settings = Settings({"index.number_of_shards": 1,
                             "index.refresh_interval": -1})
        idx = IndexService("vstore", settings, mapping=MAPPING,
                           data_path=tmp_data_dir)
        vec = (np.random.RandomState(5).randn(DIMS) * 3).tolist()
        idx.index_doc("a", {"emb": vec})
        idx.flush()
        idx.close()
        idx2 = IndexService("vstore", settings, mapping=MAPPING,
                            data_path=tmp_data_dir)
        seg = idx2.shards[0].engine.segments[0]
        col = seg.vector_columns["emb"]
        assert col.dims == DIMS and col.count == 1
        # persisted values sit exactly on the bf16 grid
        np.testing.assert_array_equal(col.vectors,
                                      pkn.bf16_round(col.vectors))
        idx2.close()


# ----------------------------------------------------------------------
# Search semantics (host path)
# ----------------------------------------------------------------------


class TestKnnSearch:
    def test_knn_clause_matches_oracle(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        r = idx.search({"query": {"knn": {
            "field": "emb", "query_vector": q.tolist(), "k": 5}},
            "size": 5})
        assert [h["_id"] for h in r["hits"]["hits"]] == \
            oracle_ids(vecs, q, 5)
        assert r["hits"]["total"] == 60  # live docs carrying the field
        idx.close()

    def test_top_level_knn_section(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        r = idx.search({"knn": {"field": "emb",
                                "query_vector": q.tolist(), "k": 4}})
        assert len(r["hits"]["hits"]) == 4
        assert [h["_id"] for h in r["hits"]["hits"]] == \
            oracle_ids(vecs, q, 4)
        idx.close()

    def test_deleted_docs_excluded_via_live_mask(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        top = oracle_ids(vecs, q, 3)
        idx.delete_doc(top[0])
        idx.refresh()
        r = idx.search({"query": {"knn": {
            "field": "emb", "query_vector": q.tolist()}}, "size": 5})
        ids = [h["_id"] for h in r["hits"]["hits"]]
        assert top[0] not in ids
        live = np.ones(len(vecs), bool)
        live[int(top[0])] = False
        assert ids == oracle_ids(vecs, q, 5, live=live)
        assert r["hits"]["total"] == 59
        idx.close()

    def test_knn_inside_bool_filter(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        r = idx.search({"query": {"bool": {
            "must": [{"knn": {"field": "emb",
                              "query_vector": q.tolist()}}],
            "filter": [{"range": {"n": {"lt": 10}}}]}}, "size": 5})
        ids = [h["_id"] for h in r["hits"]["hits"]]
        assert ids and all(int(i) < 10 for i in ids)
        live = np.zeros(len(vecs), bool)
        live[:10] = True
        assert ids == oracle_ids(vecs, q, 5, live=live)
        idx.close()

    def test_hybrid_rrf_and_convex(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        hb = {"query": {"match": {"body": "term1"}},
              "knn": {"field": "emb", "query_vector": q.tolist(), "k": 10},
              "rank": {"rrf": {"rank_constant": 60, "window_size": 20}},
              "size": 10}
        r = idx.search(dict(hb))
        assert r["_total_relation"] == "gte"
        assert r["_hybrid"]["fusion"] == "rrf"
        # oracle-side RRF over the two exact rankings
        lex = idx.search({"query": {"match": {"body": "term1"}},
                          "size": 20})
        knn_ids = oracle_ids(vecs, q, 20)
        scores = {}
        for rank, h in enumerate(lex["hits"]["hits"]):
            scores[h["_id"]] = scores.get(h["_id"], 0.0) \
                + 1.0 / (60 + rank + 1)
        for rank, did in enumerate(knn_ids):
            scores[did] = scores.get(did, 0.0) + 1.0 / (60 + rank + 1)
        want = [d for d, _ in sorted(scores.items(),
                                     key=lambda kv: (-kv[1], kv[0]))][:10]
        assert [h["_id"] for h in r["hits"]["hits"]] == want
        # convex fusion (no rank), exactly: the k winners join the
        # query's matches, a hit scores its BM25 score plus boost times
        # its kNN score, and hits.total counts the union
        rc = idx.search({"query": {"match": {"body": "term1"}},
                         "knn": {"field": "emb",
                                 "query_vector": q.tolist(), "k": 10},
                         "size": 5})
        assert rc["_hybrid"]["fusion"] == "convex"
        assert rc["_total_relation"] == "eq"
        every = idx.search({"query": {"match": {"body": "term1"}},
                            "size": len(vecs)})["hits"]
        fused = {h["_id"]: np.float32(h["_score"]) for h in every["hits"]}
        knn_s, knn_i = pkn.reference_knn_topk(
            pkn.bf16_round(vecs), np.ones(len(vecs), bool), q, 10)
        for doc, score in zip(knn_i, knn_s):
            fused[str(doc)] = fused.get(str(doc), np.float32(0)) + score
        want = sorted(fused.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
        assert rc["hits"]["total"] == len(fused) > every["total"]
        assert [h["_id"] for h in rc["hits"]["hits"]] == [d for d, _ in want]
        np.testing.assert_allclose([h["_score"] for h in rc["hits"]["hits"]],
                                   [s for _, s in want], rtol=1e-6)
        idx.close()

    def test_knn_filter_restricts_candidates(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        r = idx.search({"query": {"knn": {
            "field": "emb", "query_vector": q.tolist(),
            "filter": {"range": {"n": {"lt": 10}}}}}, "size": 5})
        ids = [h["_id"] for h in r["hits"]["hits"]]
        assert ids and all(int(i) < 10 for i in ids)
        live = np.zeros(len(vecs), bool)
        live[:10] = True
        assert ids == oracle_ids(vecs, q, 5, live=live)
        assert r["hits"]["total"] == 10
        # unknown knn parameters strict-parse to 400
        from elasticsearch_tpu.common.errors import ParsingException

        with pytest.raises(ParsingException):
            idx.search({"query": {"knn": {
                "field": "emb", "query_vector": q.tolist(),
                "filtr": {"match_all": {}}}}})
        idx.close()

    def test_rrf_rank_constant_validated(self):
        idx, _ = build_index()
        q = [0.0] * DIMS
        with pytest.raises(IllegalArgumentException):
            idx.search({"query": {"match_all": {}},
                        "knn": {"field": "emb", "query_vector": q},
                        "rank": {"rrf": {"rank_constant": 0}}})
        # misspelled rrf knobs must 400 (strict parse), and the
        # reference's rank_window_size name is accepted as an alias
        with pytest.raises(IllegalArgumentException):
            idx.search({"query": {"match_all": {}},
                        "knn": {"field": "emb", "query_vector": q},
                        "rank": {"rrf": {"rankconstant": 10}}})
        r = idx.search({"query": {"match_all": {}},
                        "knn": {"field": "emb", "query_vector": q},
                        "rank": {"rrf": {"rank_window_size": 15}},
                        "size": 5})
        assert len(r["hits"]["hits"]) == 5
        idx.close()

    def test_nan_query_vector_rejected_everywhere(self):
        idx, _ = build_index()
        bad = [float("nan")] + [0.0] * (DIMS - 1)
        with pytest.raises(IllegalArgumentException):
            idx.search({"query": {"knn": {"field": "emb",
                                          "query_vector": bad}}})
        # the mesh eligibility gate must not accept it either (the
        # serial path owns the 400, never a kernel OOB doc id)
        from elasticsearch_tpu.search.batching import knn_batch_spec

        body = {"knn": {"field": "emb", "query_vector": bad}}
        if idx._mesh_search is not None:
            assert idx._mesh_search.query_knn_batch(
                [body["knn"]], [10]) is None
        idx.close()

    def test_ineligible_knn_body_runs_solo_not_in_lexical_batch(self):
        from elasticsearch_tpu.search.batching import batchable_body

        # filtered / boosted / malformed knn bodies must NOT join a
        # micro-batch (they would demote every peer off the mesh rung)
        assert not batchable_body({"query": {"knn": {
            "field": "emb", "query_vector": [0.0] * DIMS,
            "filter": {"match_all": {}}}}})
        assert not batchable_body({"knn": {
            "field": "emb", "query_vector": [0.0] * DIMS, "boost": 2.0}})
        assert not batchable_body({"query": {"knn": {
            "field": "emb", "query_vector": [0.0] * DIMS,
            "filtr": {}}}})
        assert batchable_body({"knn": {
            "field": "emb", "query_vector": [0.0] * DIMS, "k": 5}})

    def test_convex_fusion_truncates_knn_side_to_k(self):
        idx, vecs = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        knn_ids = oracle_ids(vecs, q, 10)
        # k=2: only the 2 nearest neighbors may receive a vector score;
        # with a match_none lexical side the fused list IS those 2 docs
        r = idx.search({"query": {"match_none": {}},
                        "knn": {"field": "emb",
                                "query_vector": q.tolist(), "k": 2},
                        "size": 10})
        assert [h["_id"] for h in r["hits"]["hits"]] == knn_ids[:2]
        idx.close()

    def test_nested_include_in_parent_vector_searchable(self):
        idx = IndexService("nestv", Settings({
            "index.number_of_shards": 1,
            "index.refresh_interval": -1}), mapping={
            "properties": {"obj": {
                "type": "nested", "include_in_parent": True,
                "properties": {
                    "emb": {"type": "dense_vector", "dims": 4}}}}})
        idx.index_doc("a", {"obj": [{"emb": [1.0, 0.0, 0.0, 0.0]}]})
        idx.refresh()
        r = idx.search({"query": {"knn": {
            "field": "obj.emb", "query_vector": [1.0, 0.0, 0.0, 0.0]}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["a"]
        # two nested objects flattening the same vector path must 400
        with pytest.raises(MapperParsingException):
            idx.index_doc("b", {"obj": [{"emb": [1, 0, 0, 0]},
                                        {"emb": [0, 1, 0, 0]}]})
        idx.close()

    def test_hybrid_carries_lexical_aggregations_and_source_filtering(self):
        idx, _ = build_index()
        q = np.random.RandomState(9).randn(DIMS).astype(np.float32)
        r = idx.search({
            "query": {"match": {"body": "term1"}},
            "knn": {"field": "emb", "query_vector": q.tolist(), "k": 10},
            "aggs": {"byn": {"avg": {"field": "n"}}},
            "_source": False, "size": 5})
        assert "aggregations" in r and "byn" in r["aggregations"]
        # the knn side inherits _source: false — no fused hit leaks it
        assert all("_source" not in h for h in r["hits"]["hits"])
        # shard header stays internally consistent
        sh = r["_shards"]
        assert sh["successful"] + sh["failed"] == sh["total"]
        idx.close()

    def test_rank_without_knn_rejected(self):
        idx, _ = build_index()
        with pytest.raises(IllegalArgumentException):
            idx.search({"knn": {"field": "emb",
                                "query_vector": [0.0] * DIMS},
                        "rank": {"rrf": {}}})
        idx.close()


# ----------------------------------------------------------------------
# Mesh plane (8-device CPU mesh, interpret kernels)
# ----------------------------------------------------------------------


def build_pair(n_shards=3, n_docs=90, seed=1, **extra):
    mesh, vecs = build_index(n_shards=n_shards, n_docs=n_docs, seed=seed,
                             **extra)
    host, _ = build_index(n_shards=n_shards, n_docs=n_docs, seed=seed,
                          **{"index.search.mesh": False, **extra})
    return mesh, host, vecs


class TestKnnMeshPlane:
    def test_mesh_host_parity_byte_identical(self):
        mesh, host, vecs = build_pair()
        q = np.random.RandomState(3).randn(DIMS).astype(np.float32)
        body = {"query": {"knn": {"field": "emb",
                                  "query_vector": q.tolist(), "k": 6}},
                "size": 6}
        got = mesh.search(dict(body))
        want = host.search(dict(body))
        assert got["_plane"] == "mesh_pallas"
        assert want["_plane"] == "host"
        assert got["hits"]["total"] == want["hits"]["total"]
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
            assert g["_score"] == w["_score"]
        assert mesh._mesh_search.knn_query_total == 1
        mesh.close()
        host.close()

    def test_batched_knn_burst_one_launch(self):
        mesh, host, _ = build_pair()
        rng = np.random.RandomState(6)
        burst = [{"knn": {"field": "emb",
                          "query_vector": rng.randn(DIMS).tolist(),
                          "k": 5}, "size": 5} for _ in range(4)]
        # a top-level-knn member with NO size must default to k hits —
        # the same count the serial path returns (batching must never
        # change a member's observable result)
        burst.append({"knn": {"field": "emb",
                              "query_vector": rng.randn(DIMS).tolist(),
                              "k": 3}})
        out = mesh.search_batch([dict(b) for b in burst])
        assert mesh._mesh_search.batched_launch_total == 1
        assert mesh._mesh_search.knn_query_total == 5
        for b, got in zip(burst, out):
            assert isinstance(got, dict), got
            assert got["_plane"] == "mesh_pallas"
            want = host.search(dict(b))
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            assert got["hits"]["total"] == want["hits"]["total"]
        assert len(out[-1]["hits"]["hits"]) == 3
        mesh.close()
        host.close()

    def test_plane_fault_quarantines_once(self):
        mesh, host, _ = build_pair()
        q = np.random.RandomState(3).randn(DIMS).astype(np.float32)
        body = {"query": {"knn": {"field": "emb",
                                  "query_vector": q.tolist()}}, "size": 5}
        scheme = PlaneFailScheme(planes=["mesh_pallas"]).install()
        try:
            got = mesh.search(dict(body))
            assert got["_plane"] == "host"
            want = host.search(dict(body))
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            ph = mesh._mesh_search.plane_health
            assert ph.failures_total["mesh_pallas"] == 1
            assert "mesh_pallas" in ph.quarantined()
        finally:
            clear_search_disruptions()
        mesh.close()
        host.close()

    def test_knn_disabled_setting_falls_to_host(self):
        mesh, host, _ = build_pair(**{"search.knn.enabled": False})
        q = np.random.RandomState(3).randn(DIMS).astype(np.float32)
        body = {"query": {"knn": {"field": "emb",
                                  "query_vector": q.tolist()}}, "size": 5}
        got = mesh.search(dict(body))
        assert got["_plane"] == "host"
        want = host.search(dict(body))
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        mesh.close()
        host.close()

    def test_deletes_invalidate_mesh_staging(self):
        mesh, host, vecs = build_pair()
        q = np.random.RandomState(3).randn(DIMS).astype(np.float32)
        body = {"query": {"knn": {"field": "emb",
                                  "query_vector": q.tolist(), "k": 5}},
                "size": 5}
        first = mesh.search(dict(body))
        victim = first["hits"]["hits"][0]["_id"]
        for idx in (mesh, host):
            idx.delete_doc(victim)
            idx.refresh()
        got = mesh.search(dict(body))
        want = host.search(dict(body))
        assert got["_plane"] == "mesh_pallas"
        assert victim not in [h["_id"] for h in got["hits"]["hits"]]
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        assert got["hits"]["total"] == want["hits"]["total"]
        mesh.close()
        host.close()


# ----------------------------------------------------------------------
# REST total rendering (the PR-6 gte leftover)
# ----------------------------------------------------------------------


class TestTotalRendering:
    def test_track_total_hits_renders_object(self):
        from elasticsearch_tpu.rest.handlers import _render_total_hits

        resp = {"hits": {"total": 42, "hits": []}}
        _render_total_hits(resp, {"track_total_hits": True})
        assert resp["hits"]["total"] == {"value": 42, "relation": "eq"}

    def test_pruned_marker_renders_gte(self):
        from elasticsearch_tpu.rest.handlers import _render_total_hits

        resp = {"hits": {"total": 42, "hits": []},
                "_pruned": {"total_relation": "gte", "tiles_scored": 3}}
        _render_total_hits(resp, {})
        assert resp["hits"]["total"] == {"value": 42, "relation": "gte"}

    def test_hybrid_marker_renders_gte(self):
        from elasticsearch_tpu.rest.handlers import _render_total_hits

        resp = {"hits": {"total": 7, "hits": []},
                "_total_relation": "gte"}
        _render_total_hits(resp, {})
        assert resp["hits"]["total"] == {"value": 7, "relation": "gte"}

    def test_integer_threshold_form_opts_in(self):
        from elasticsearch_tpu.rest.handlers import _render_total_hits

        resp = {"hits": {"total": 42, "hits": []}}
        _render_total_hits(resp, {"track_total_hits": 10000})
        assert resp["hits"]["total"] == {"value": 42, "relation": "eq"}

    def test_default_stays_bare_int(self):
        from elasticsearch_tpu.rest.handlers import _render_total_hits

        resp = {"hits": {"total": 42, "hits": []}}
        _render_total_hits(resp, {})
        assert resp["hits"]["total"] == 42

    def test_rest_search_knn_end_to_end(self):
        from elasticsearch_tpu.client import Client
        from elasticsearch_tpu.node import Node

        node = Node(Settings({"cluster.name": "knn-rest"}))
        try:
            c = Client(node)
            status, _ = c.perform("PUT", "/vidx", body={
                "settings": {"index": {"number_of_shards": 1}},
                "mappings": {"_doc": {"properties": {
                    "emb": {"type": "dense_vector", "dims": 4}}}}})
            assert status == 200
            rng = np.random.RandomState(0)
            for d in range(6):
                status, _ = c.perform(
                    "PUT", f"/vidx/_doc/{d}",
                    body={"emb": rng.randn(4).tolist()})
                assert status in (200, 201)
            c.perform("POST", "/vidx/_refresh")
            status, r = c.perform("POST", "/vidx/_search", body={
                "knn": {"field": "emb",
                        "query_vector": rng.randn(4).tolist(), "k": 3}})
            assert status == 200, r
            assert len(r["hits"]["hits"]) == 3
            assert r["hits"]["total"] == 6  # bare int without opt-in
            status, r2 = c.perform(
                "POST", "/vidx/_search",
                params={"track_total_hits": "true"},
                body={"query": {"match_all": {}}})
            assert status == 200
            assert r2["hits"]["total"] == {"value": 6, "relation": "eq"}
        finally:
            node.close()
