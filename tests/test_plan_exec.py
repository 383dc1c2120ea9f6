"""Mesh plan executor: the production query DSL over an 8-device mesh.

VERDICT r1 item 2: the distributed program must be the ENGINE, not a demo
kernel — arbitrary query-DSL plans execute as one multi-device shard_map
program, with results identical to the single-node per-segment path merged
host-side (SearchPhaseController.java:408 semantics).
"""

import numpy as np
import pytest

import jax

from elasticsearch_tpu.analysis.analyzers import AnalysisRegistry
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.mapper.mapping import MapperService
from elasticsearch_tpu.parallel.mesh import shard_mesh
from elasticsearch_tpu.parallel.plan_exec import (
    MeshPlanExecutor,
    PlanStructureMismatch,
    _unpack_answer,
    stack_plans,
)
from elasticsearch_tpu.search import plan as P
from elasticsearch_tpu.search.query_dsl import ShardQueryContext, parse_query


MAPPING = {
    "properties": {
        "body": {"type": "text", "analyzer": "whitespace"},
        "n": {"type": "integer"},
        "tag": {"type": "keyword"},
        "price": {"type": "float"},
    }
}



@pytest.fixture(autouse=True)
def _scatter_plans(monkeypatch):
    """Most of this module pins the SCATTER mesh formulation so its
    parity tests stay deterministic and fast; TestMeshPallasPlane below
    overrides to "interpret" to exercise the tile kernel INSIDE the mesh
    program. (_pallas_mode reads ES_TPU_PALLAS at call time — import
    order is irrelevant.)"""
    monkeypatch.setenv("ES_TPU_PALLAS", "off")


def build_corpus(n_shards, docs_per_shard, seed=0):
    """Sharded corpus with text + numeric + keyword fields. Every query
    term below appears on every shard (dense vocab)."""
    rng = np.random.RandomState(seed)
    vocab = [f"w{i}" for i in range(12)]
    tags = ["red", "green", "blue", "black"]
    svc = MapperService(AnalysisRegistry(), MAPPING)
    segments, ctxs = [], []
    for s in range(n_shards):
        b = SegmentBuilder(f"shard{s}")
        for d in range(docs_per_shard):
            toks = [vocab[rng.randint(len(vocab))]
                    for _ in range(rng.randint(3, 15))]
            doc = {
                "body": " ".join(toks),
                "n": int(rng.randint(0, 50)),
                "tag": tags[rng.randint(len(tags))],
                "price": float(rng.rand() * 100),
            }
            b.add_document(svc.parse_document(f"{s}-{d}", doc), d)
        segments.append(b.seal())
        ctxs.append(ShardQueryContext(svc))
    return segments, ctxs


def host_reference(segments, ctxs, query_body, k):
    """Single-node path: per-segment P.execute + host top-k merge."""
    qb = parse_query(query_body)
    rows = []
    total = 0
    for sid, (seg, ctx) in enumerate(zip(segments, ctxs)):
        node = qb.to_plan(ctx, seg)
        scores_d, matched_d = P.execute(seg.device_arrays(), node)
        scores = np.asarray(scores_d)
        matched = np.asarray(matched_d)
        live1 = np.concatenate([seg.live, np.zeros(1, bool)])
        matched = matched & live1
        total += int(matched.sum())
        for doc in np.nonzero(matched)[0]:
            rows.append((float(scores[doc]), sid, int(doc)))
    rows.sort(key=lambda r: (-r[0], r[1], r[2]))
    return total, rows[:k]


def mesh_result(executor, segments, ctxs, query_body, k):
    qb = parse_query(query_body)
    plans = [qb.to_plan(ctx, seg) for seg, ctx in zip(segments, ctxs)]
    scores, shards, docs, total = _unpack_answer(
        np.asarray(executor.execute(plans, k)[0]))[:4]
    got = [(float(s), int(sh), int(d))
           for s, sh, d in zip(scores, shards, docs) if s > -np.inf]
    return int(total), got


QUERY_MATRIX = [
    {"term": {"body": "w3"}},
    {"match": {"body": "w1 w4 w7"}},
    {"match_all": {}},
    {"range": {"n": {"gte": 10, "lt": 35}}},
    {"terms": {"tag": ["red", "blue"]}},
    {"exists": {"field": "n"}},
    {"bool": {
        "must": [{"match": {"body": "w2 w5"}}],
        "filter": [{"range": {"n": {"gte": 5}}}],
        "must_not": [{"term": {"tag": "black"}}],
    }},
    {"bool": {
        "should": [{"term": {"body": "w0"}}, {"term": {"body": "w9"}},
                   {"term": {"tag": "green"}}],
        "minimum_should_match": 2,
    }},
    {"constant_score": {"filter": {"range": {"price": {"lte": 50.0}}},
                        "boost": 2.5}},
    {"dis_max": {"queries": [{"term": {"body": "w1"}},
                             {"term": {"body": "w2"}}],
                 "tie_breaker": 0.3}},
    {"match_phrase": {"body": "w1 w2"}},
    {"function_score": {"query": {"match": {"body": "w3 w6"}},
                        "field_value_factor": {"field": "price"},
                        "boost_mode": "multiply"}},
]


@pytest.fixture(scope="module")
def corpus8():
    return build_corpus(8, 60)


@pytest.fixture(scope="module")
def executor8(corpus8):
    segments, _ = corpus8
    return MeshPlanExecutor(segments, shard_mesh(8))


class TestMeshPlanParity:
    @pytest.mark.parametrize("query", QUERY_MATRIX,
                             ids=[list(q)[0] + str(i)
                                  for i, q in enumerate(QUERY_MATRIX)])
    def test_parity_with_host_path(self, corpus8, executor8, query):
        segments, ctxs = corpus8
        ref_total, ref_rows = host_reference(segments, ctxs, query, k=10)
        got_total, got_rows = mesh_result(executor8, segments, ctxs, query,
                                          k=10)
        assert got_total == ref_total
        # same scores in order; doc identity may permute within exact ties
        ref_scores = [r[0] for r in ref_rows]
        got_scores = [r[0] for r in got_rows]
        assert got_scores == pytest.approx(ref_scores, rel=1e-5)
        # same (shard, doc) set wherever scores are distinct
        assert {(s, d) for sc, s, d in got_rows if got_scores.count(sc) == 1} \
            == {(s, d) for sc, s, d in ref_rows if ref_scores.count(sc) == 1}

    def test_uneven_shard_sizes(self):
        segments, ctxs = build_corpus(3, 10, seed=5)
        big_seg, big_ctx = build_corpus(1, 400, seed=6)
        segments.append(big_seg[0])
        ctxs.append(big_ctx[0])
        ex = MeshPlanExecutor(segments, shard_mesh(8))
        q = {"bool": {"must": [{"match": {"body": "w1 w2"}}],
                      "filter": [{"range": {"n": {"gte": 1}}}]}}
        ref_total, ref_rows = host_reference(segments, ctxs, q, k=7)
        got_total, got_rows = mesh_result(ex, segments, ctxs, q, k=7)
        assert got_total == ref_total
        assert [r[0] for r in got_rows] == pytest.approx(
            [r[0] for r in ref_rows], rel=1e-5)

    def test_fewer_shards_than_devices(self):
        segments, ctxs = build_corpus(3, 30, seed=2)
        ex = MeshPlanExecutor(segments, shard_mesh(8))
        q = {"match": {"body": "w4"}}
        ref_total, ref_rows = host_reference(segments, ctxs, q, k=10)
        got_total, got_rows = mesh_result(ex, segments, ctxs, q, k=10)
        assert got_total == ref_total
        assert [r[0] for r in got_rows] == pytest.approx(
            [r[0] for r in ref_rows], rel=1e-5)

    def test_program_cached_across_same_shape_queries(self, corpus8,
                                                      executor8):
        from elasticsearch_tpu.parallel.plan_exec import _mesh_query_program

        segments, ctxs = corpus8
        mesh_result(executor8, segments, ctxs, {"term": {"body": "w5"}}, 10)
        info1 = _mesh_query_program.cache_info()
        mesh_result(executor8, segments, ctxs, {"term": {"body": "w6"}}, 10)
        info2 = _mesh_query_program.cache_info()
        assert info2.misses == info1.misses  # same structure -> cache hit

    def test_structure_mismatch_raises(self):
        segments, ctxs = build_corpus(2, 10, seed=3)
        qb = parse_query({"term": {"body": "w1"}})
        plans = [qb.to_plan(ctxs[0], segments[0]),
                 parse_query({"match_all": {}}).to_plan(ctxs[1], segments[1])]
        with pytest.raises(PlanStructureMismatch):
            stack_plans(plans, [s.nd_pad for s in segments], 1024, 8)


class TestIndexMeshAggsSort:
    """Index-level mesh path with aggregations and field sort: the mesh
    program computes matched/scores per device; aggregations reduce over
    those views with the host framework (full agg-type parity), and
    single-field f32-exact numeric sorts rank in-program (VERDICT r3
    item 4: UNSUPPORTED must shrink by aggs + sort)."""

    BODY = {
        "mappings": {"properties": {
            "body": {"type": "text", "analyzer": "whitespace"},
            "n": {"type": "integer"},
            "tag": {"type": "keyword"},
            "price": {"type": "float"},
        }}
    }

    def _mk(self, name, mesh):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService(name, Settings({
            "index.number_of_shards": 3,
            "index.search.mesh": mesh,
            # no background NRT refresh: a refresh sneaking between
            # index_doc calls under suite load seals extra segments,
            # pushing (shard, segment) pairs past the 8-device mesh and
            # flaking the mesh-served assertion
            "index.refresh_interval": -1,
        }), mapping=self.BODY["mappings"])
        rng = np.random.RandomState(11)
        vocab = [f"w{i}" for i in range(10)]
        tags = ["red", "green", "blue"]
        for d in range(60):
            doc = {
                "body": " ".join(vocab[rng.randint(len(vocab))]
                                 for _ in range(6)),
                "tag": tags[rng.randint(len(tags))],
                "price": d * 0.5,  # unique + f32-exact
            }
            if d % 7 != 0:  # leave some docs without n (missing policy)
                doc["n"] = int(rng.randint(0, 40))
            idx.index_doc(str(d), doc)
        idx.refresh()
        return idx

    @pytest.fixture()
    def pair(self):
        mesh_idx = self._mk("meshagg", True)
        host_idx = self._mk("hostagg", False)
        yield mesh_idx, host_idx
        mesh_idx.close()
        host_idx.close()

    def test_aggs_parity_and_mesh_used(self, pair):
        mesh_idx, host_idx = pair
        body = {
            "query": {"match": {"body": "w1 w4"}},
            "size": 5,
            "aggs": {
                "tags": {"terms": {"field": "tag"},
                         "aggs": {"avg_n": {"avg": {"field": "n"}}}},
                "card": {"cardinality": {"field": "tag"}},
                "price_stats": {"stats": {"field": "price"}},
            },
        }
        got = mesh_idx.search(dict(body))
        want = host_idx.search(dict(body))
        assert mesh_idx._mesh_search is not None
        assert mesh_idx._mesh_search.query_total >= 1
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["aggregations"] == want["aggregations"]
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])

    def test_sort_parity(self, pair):
        mesh_idx, host_idx = pair
        body = {
            "query": {"match_all": {}},
            "sort": [{"price": {"order": "desc"}}],
            "size": 8,
        }
        got = mesh_idx.search(dict(body))
        want = host_idx.search(dict(body))
        assert mesh_idx._mesh_search.query_total >= 1
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        assert ([h["sort"] for h in got["hits"]["hits"]]
                == [h["sort"] for h in want["hits"]["hits"]])
        assert got["hits"]["max_score"] is None

    def test_sort_missing_policy(self, pair):
        mesh_idx, host_idx = pair
        for missing in ("_last", "_first", 7):
            body = {
                "query": {"match_all": {}},
                "sort": [{"n": {"order": "asc", "missing": missing}}],
                "size": 60,
            }
            got = mesh_idx.search(dict(body))
            want = host_idx.search(dict(body))
            # ties on n are order-ambiguous between paths; compare the
            # sort-value sequence (the ranking contract), not doc ids
            assert ([h["sort"] for h in got["hits"]["hits"]]
                    == [h["sort"] for h in want["hits"]["hits"]]), missing

    def test_non_f32_exact_sort_ranks_by_ordinal_on_the_mesh(self, pair):
        mesh_idx, _ = pair
        # a fresh float column with non-f32-exact values via a new index
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService("meshinexact", Settings({
            "index.number_of_shards": 3,
            "index.search.mesh": True,
        }), mapping={"properties": {"t": {"type": "double"}}})
        for d in range(30):
            idx.index_doc(str(d), {"t": 1700000000000.0 + d})  # epoch ms
        idx.refresh()
        before = (idx._mesh_search.query_total
                  if idx._mesh_search is not None else 0)
        r = idx.search({"query": {"match_all": {}},
                        "sort": [{"t": "asc"}], "size": 5})
        # exact f64 ordering and the stored values, from the mesh program:
        # the key is each value's rank among the distinct values (f32
        # holds 1.7e12 to 131,072 and would tie all thirty)
        assert [h["sort"] for h in r["hits"]["hits"]] == [
            [1700000000000.0 + d] for d in range(5)]
        assert r["_plane"] == "mesh"
        assert idx._mesh_search.query_total == before + 1
        assert idx._mesh_search.sort_device_query_total == 1
        idx.close()


class TestMeshFeatureParity:
    """VERDICT r4 item 1: the mesh program must cover the collector-chain
    features (QueryPhase.java:179-268) — post_filter / min_score /
    terminate_after as mask stages, search_after as an oriented-key cut,
    rescore as an in-program window pass, slice as a deterministic doc
    partition, keyword sorts via global ordinals. Every test asserts
    mesh-vs-host parity AND that the mesh actually served the query."""

    BODY = TestIndexMeshAggsSort.BODY

    def _mk(self, name, mesh, n_docs=80, shards=3):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService(name, Settings({
            "index.number_of_shards": shards,
            "index.search.mesh": mesh,
            "index.refresh_interval": -1,  # see TestIndexMeshAggsSort._mk
        }), mapping=self.BODY["mappings"])
        rng = np.random.RandomState(23)
        vocab = [f"w{i}" for i in range(10)]
        tags = ["amber", "blue", "coral", "denim", "ecru"]
        for d in range(n_docs):
            doc = {
                "body": " ".join(vocab[rng.randint(len(vocab))]
                                 for _ in range(6)),
                "price": d * 0.25,  # unique + f32-exact
            }
            if d % 9 != 0:  # keyword-missing docs for sort fills
                doc["tag"] = tags[rng.randint(len(tags))]
            if d % 7 != 0:
                doc["n"] = int(rng.randint(0, 40))
            idx.index_doc(str(d), doc)
        idx.refresh()
        return idx

    @pytest.fixture()
    def pair(self):
        mesh_idx = self._mk("meshfeat", True)
        host_idx = self._mk("hostfeat", False)
        yield mesh_idx, host_idx
        mesh_idx.close()
        host_idx.close()

    def _both(self, pair, body, mesh_used=True):
        mesh_idx, host_idx = pair
        before = (mesh_idx._mesh_search.query_total
                  if mesh_idx._mesh_search is not None else 0)
        got = mesh_idx.search(dict(body))
        want = host_idx.search(dict(body))
        after = mesh_idx._mesh_search.query_total
        if mesh_used:
            assert after == before + 1, "mesh path did not serve the query"
        else:
            assert after == before, "mesh path unexpectedly served it"
        return got, want

    @staticmethod
    def _same_hits(got, want, check_scores=True):
        assert got["hits"]["total"] == want["hits"]["total"]
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        if check_scores:
            g = [h.get("_score") for h in got["hits"]["hits"]]
            w = [h.get("_score") for h in want["hits"]["hits"]]
            for a, b in zip(g, w):
                if a is None or b is None:
                    assert a == b
                else:
                    assert abs(a - b) < 1e-5, (g, w)

    def test_post_filter(self, pair):
        body = {
            "query": {"match": {"body": "w1 w4"}},
            "post_filter": {"term": {"tag": "blue"}},
            "size": 10,
            "aggs": {"tags": {"terms": {"field": "tag"}}},
        }
        got, want = self._both(pair, body)
        self._same_hits(got, want)
        # aggregations must see PRE-post_filter docs (the defining
        # property of post_filter)
        assert got["aggregations"] == want["aggregations"]
        assert len(got["aggregations"]["tags"]["buckets"]) > 1

    def test_min_score(self, pair):
        probe = pair[1].search({"query": {"match": {"body": "w1 w4"}},
                                "size": 1})
        cut = probe["hits"]["max_score"] * 0.6
        body = {
            "query": {"match": {"body": "w1 w4"}},
            "min_score": float(np.float32(cut)),
            "size": 10,
            "aggs": {"tags": {"terms": {"field": "tag"}}},
        }
        got, want = self._both(pair, body)
        self._same_hits(got, want)
        # min_score filters aggregations too (MinimumScoreCollector wraps
        # the whole chain)
        assert got["aggregations"] == want["aggregations"]

    def test_terminate_after(self, pair):
        body = {
            "query": {"match": {"body": "w2"}},
            "terminate_after": 3,
            "size": 5,
        }
        got, want = self._both(pair, body)
        # the cap is per shard (3 shards x 3): totals must agree
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["terminated_early"] is True
        assert want["terminated_early"] is True

    def test_search_after_numeric_sort(self, pair):
        base = {"query": {"match_all": {}},
                "sort": [{"price": {"order": "desc"}}], "size": 10}
        got1, want1 = self._both(pair, base)
        self._same_hits(got1, want1, check_scores=False)
        cursor = got1["hits"]["hits"][-1]["sort"]
        page2 = dict(base, search_after=cursor)
        got2, want2 = self._both(pair, page2)
        self._same_hits(got2, want2, check_scores=False)
        # pagination is gap-free and non-overlapping
        ids1 = {h["_id"] for h in got1["hits"]["hits"]}
        ids2 = {h["_id"] for h in got2["hits"]["hits"]}
        assert not ids1 & ids2
        # total is NOT affected by search_after (collector counts all)
        assert got2["hits"]["total"] == got1["hits"]["total"]

    def test_search_after_relevance(self, pair):
        base = {"query": {"match": {"body": "w3 w5"}}, "size": 5}
        got1, want1 = self._both(pair, base)
        cursor = [got1["hits"]["hits"][-1]["_score"]]
        page2 = dict(base, search_after=cursor)
        got2, want2 = self._both(pair, page2)
        self._same_hits(got2, want2)

    def test_keyword_sort_global_ordinals(self, pair):
        for order in ("asc", "desc"):
            body = {
                "query": {"match_all": {}},
                "sort": [{"tag": {"order": order}}],
                "size": 30,
            }
            got, want = self._both(pair, body)
            assert ([h["sort"] for h in got["hits"]["hits"]]
                    == [h["sort"] for h in want["hits"]["hits"]]), order
            # real terms surface as strings, missing docs as null
            vals = [h["sort"][0] for h in got["hits"]["hits"]]
            assert any(isinstance(v, str) for v in vals)

    def test_keyword_sort_search_after(self, pair):
        base = {"query": {"match_all": {}},
                "sort": [{"tag": {"order": "asc"}}], "size": 12}
        got1, want1 = self._both(pair, base)
        cursor = got1["hits"]["hits"][-1]["sort"]
        page2 = dict(base, search_after=cursor)
        got2, want2 = self._both(pair, page2)
        assert ([h["sort"] for h in got2["hits"]["hits"]]
                == [h["sort"] for h in want2["hits"]["hits"]])

    @pytest.mark.parametrize("mode", ["total", "multiply", "avg", "max",
                                      "min"])
    def test_rescore_modes(self, pair, mode):
        body = {
            "query": {"match": {"body": "w1"}},
            "rescore": {
                "window_size": 6,
                "query": {
                    "rescore_query": {"match": {"body": "w4"}},
                    "query_weight": 0.7,
                    "rescore_query_weight": 1.3,
                    "score_mode": mode,
                },
            },
            "size": 8,
        }
        got, want = self._both(pair, body)
        self._same_hits(got, want)

    def test_slice_partition(self, pair):
        mesh_idx, host_idx = pair
        all_ids = set()
        for i in range(3):
            body = {"query": {"match_all": {}},
                    "slice": {"id": i, "max": 3}, "size": 80}
            got, want = self._both(pair, body)
            self._same_hits(got, want, check_scores=False)
            ids = {h["_id"] for h in got["hits"]["hits"]}
            assert not ids & all_ids  # disjoint partitions
            all_ids |= ids
        assert len(all_ids) == 80  # exhaustive

    def test_suggest_and_highlight_on_mesh(self, pair):
        body = {
            "query": {"match": {"body": "w1"}},
            "size": 3,
            "highlight": {"fields": {"body": {}}},
            "suggest": {"s1": {"text": "w1", "term": {"field": "body"}}},
        }
        got, want = self._both(pair, body)
        self._same_hits(got, want)
        assert got.get("suggest") == want.get("suggest")
        assert ([h.get("highlight") for h in got["hits"]["hits"]]
                == [h.get("highlight") for h in want["hits"]["hits"]])

    def test_combined_feature_stack(self, pair):
        """Everything at once: the fused mask stages must compose."""
        body = {
            "query": {"match": {"body": "w1 w2 w3"}},
            "post_filter": {"range": {"n": {"gte": 5}}},
            "min_score": float(np.float32(0.05)),
            "size": 12,
            "aggs": {"tags": {"terms": {"field": "tag"}}},
        }
        got, want = self._both(pair, body)
        self._same_hits(got, want)
        assert got["aggregations"] == want["aggregations"]

    def test_collapse_still_falls_back(self, pair):
        body = {"query": {"match": {"body": "w1"}}, "size": 5,
                "collapse": {"field": "tag"}}
        got, want = self._both(pair, body, mesh_used=False)
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])

    def test_profile_is_plane_truthful(self, pair):
        """ISSUE 8: "profile": true no longer demotes to the host path —
        the mesh serves it (mesh_used asserted by _both) and the profile
        section reports the serving plane + its phase spans, with hits
        identical to the unprofiled run."""
        base = {"query": {"match": {"body": "w1"}}, "size": 5}
        plain, _ = self._both(pair, dict(base))
        got, want = self._both(pair, dict(base, profile=True))
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in plain["hits"]["hits"]])
        assert ([h["_score"] for h in got["hits"]["hits"]]
                == [h["_score"] for h in plain["hits"]["hits"]])
        prof = got["profile"]
        assert prof["plane"] == got["_plane"] != "host"
        assert {s["phase"] for s in prof["phases"]} >= {"kernel", "merge"}

    def test_rare_term_stays_on_mesh(self, pair):
        """A term present in only ONE shard's dictionary must not force
        the whole query off the mesh: absent shards plan an
        all-invalid-lane scorer with the same tree skeleton instead of
        MatchNone (PlanStructureMismatch -> silent host fallback)."""
        mesh_idx, host_idx = pair
        for idx in (mesh_idx, host_idx):
            idx.index_doc("rare", {"body": "zzz_unique_token"})
            idx.refresh()
        body = {"query": {"match": {"body": "zzz_unique_token"}}, "size": 5}
        got, want = self._both(pair, body)
        self._same_hits(got, want)
        assert got["hits"]["total"] == 1
        assert got["hits"]["hits"][0]["_id"] == "rare"

    def test_terminate_after_multi_segment_shards(self, pair):
        """terminate_after caps per SHARD; a mesh device holds one
        SEGMENT. With two segments per shard the per-device counts must
        be grouped by shard before capping, or mesh totals diverge from
        the host path (review finding, round 5)."""
        mesh_idx, host_idx = pair
        for idx in (mesh_idx, host_idx):  # second refresh -> 2nd segment
            for d in range(100, 130):
                idx.index_doc(str(d), {"body": "w2 w2 w2",
                                       "n": d, "price": d * 1.0})
            idx.refresh()
        body = {"query": {"match": {"body": "w2"}},
                "terminate_after": 4, "size": 5}
        got, want = self._both(pair, body)
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["terminated_early"] == want["terminated_early"] is True


class TestMeshPallasPlane:
    """The tentpole contract: the Pallas tile kernel IS the mesh
    program's scorer (one fast plane for distributed queries). Asserts
    mesh-vs-host parity for scores / top-k order / aggregations with the
    kernel serving (``_plane == "mesh_pallas"``, no silent fallback),
    including the PACKED case (segments > devices via slot unroll)."""

    MAPPING = {"properties": {
        "body": {"type": "text", "analyzer": "whitespace"},
        "tag": {"type": "keyword"},
        "n": {"type": "integer"},
        "price": {"type": "float"},
    }}

    @pytest.fixture(autouse=True)
    def _kernel_plans(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_PALLAS", "interpret")

    def _mk(self, name, mesh, shards=3, batches=((0, 60),)):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService(name, Settings({
            "index.number_of_shards": shards,
            "index.search.mesh": mesh,
            "index.refresh_interval": -1,
        }), mapping=self.MAPPING)
        rng = np.random.RandomState(17)
        vocab = [f"w{i}" for i in range(10)]
        tags = ["amber", "blue", "coral"]
        for lo, hi in batches:
            for d in range(lo, hi):
                doc = {"body": " ".join(vocab[rng.randint(len(vocab))]
                                        for _ in range(6)),
                       "tag": tags[d % 3], "price": d * 0.5}
                if d % 7 != 0:
                    doc["n"] = int(rng.randint(0, 40))
                idx.index_doc(str(d), doc)
            idx.refresh()  # each batch seals one segment per shard
        return idx

    @pytest.fixture()
    def pair(self):
        mesh_idx = self._mk("meshpal", True)
        host_idx = self._mk("hostpal", False)
        yield mesh_idx, host_idx
        mesh_idx.close()
        host_idx.close()

    @pytest.fixture()
    def packed_pair(self):
        # 5 shards x 2 sealed segments = 10 (shard, segment) pairs on the
        # 8-device mesh: the packed regime (slots_per_dev == 2)
        mesh_idx = self._mk("meshpalpk", True, shards=5,
                            batches=((0, 50), (100, 140)))
        host_idx = self._mk("hostpalpk", False, shards=5,
                            batches=((0, 50), (100, 140)))
        yield mesh_idx, host_idx
        mesh_idx.close()
        host_idx.close()

    @staticmethod
    def _check(mesh_idx, host_idx, body, plane="mesh_pallas"):
        before = (mesh_idx._mesh_search.pallas_query_total
                  if mesh_idx._mesh_search is not None else 0)
        got = mesh_idx.search(dict(body))
        want = host_idx.search(dict(body))
        assert got["_plane"] == plane, (got["_plane"], body)
        if plane == "mesh_pallas":
            assert (mesh_idx._mesh_search.pallas_query_total
                    == before + 1), "kernel plane did not serve the query"
        assert got["hits"]["total"] == want["hits"]["total"], body
        # same score sequence; doc identity may permute within EXACT
        # ties (same contract as TestMeshPlanParity)
        gs = [h.get("_score") for h in got["hits"]["hits"]]
        ws = [h.get("_score") for h in want["hits"]["hits"]]
        assert len(gs) == len(ws), body
        for a, b in zip(gs, ws):
            if a is None or b is None:
                assert a == b, body
            else:
                assert abs(a - b) < 1e-5, (body, gs, ws)
        gids = [h["_id"] for h in got["hits"]["hits"]]
        wids = [h["_id"] for h in want["hits"]["hits"]]
        assert ({i for i, s in zip(gids, gs) if gs.count(s) == 1}
                == {i for i, s in zip(wids, ws) if ws.count(s) == 1}), body
        if "aggs" in body:
            assert got["aggregations"] == want["aggregations"], body
        return got, want

    def test_match_parity_on_kernel_plane(self, pair):
        self._check(*pair, {"query": {"match": {"body": "w1 w4"}},
                            "size": 10})

    def test_bool_with_filter_and_aggs(self, pair):
        self._check(*pair, {
            "query": {"bool": {"must": [{"match": {"body": "w2 w5"}}],
                               "filter": [{"range": {"n": {"gte": 5}}}]}},
            "size": 10,
            "aggs": {"tags": {"terms": {"field": "tag"},
                              "aggs": {"avg_n": {"avg": {"field": "n"}}}},
                     "price_stats": {"stats": {"field": "price"}}},
        })

    def test_rare_term_stays_on_kernel_plane(self, pair):
        mesh_idx, host_idx = pair
        # present on exactly one shard's dictionary: absent shards keep
        # the kernel node with an empty lane set (same skeleton)
        for idx in pair:
            idx.index_doc("rare", {"body": "zzz_rare_token w1"})
            idx.refresh()
        got, _ = self._check(mesh_idx, host_idx,
                             {"query": {"match": {"body": "zzz_rare_token"}},
                              "size": 5})
        assert got["hits"]["total"] == 1
        assert got["hits"]["hits"][0]["_id"] == "rare"

    def test_min_should_match_counts(self, pair):
        self._check(*pair, {
            "query": {"bool": {
                "should": [{"term": {"body": "w0"}},
                           {"term": {"body": "w3"}},
                           {"term": {"body": "w9"}}],
                "minimum_should_match": 2}},
            "size": 10})

    def test_match_all_uses_scatter_mesh(self, pair):
        # no terms node -> nothing for the kernel to score; the query
        # still runs on the mesh (scatter formulation)
        self._check(*pair, {"query": {"match_all": {}},
                            "sort": [{"price": "desc"}], "size": 8},
                    plane="mesh")

    def test_packed_segments_exceed_devices(self, packed_pair):
        mesh_idx, host_idx = packed_pair
        got, _ = self._check(mesh_idx, host_idx,
                             {"query": {"match": {"body": "w1 w4"}},
                              "size": 10,
                              "aggs": {"tags": {"terms": {"field": "tag"}}}})
        ms = mesh_idx._mesh_search
        ex = ms._executor
        assert len(ms._pairs) > ex.n_dev, "corpus must exceed device count"
        assert ex.slots_per_dev >= 2
        assert ex.n_slots == ex.slots_per_dev * ex.n_dev

    def test_packed_post_filter_terminate_after(self, packed_pair):
        mesh_idx, host_idx = packed_pair
        self._check(mesh_idx, host_idx,
                    {"query": {"match": {"body": "w3"}},
                     "post_filter": {"term": {"tag": "blue"}}, "size": 10})
        # terminate_after caps per SHARD while slots are SEGMENTS
        body = {"query": {"match": {"body": "w1"}},
                "terminate_after": 3, "size": 5}
        got = mesh_idx.search(dict(body))
        want = host_idx.search(dict(body))
        assert got["_plane"] == "mesh_pallas"
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["terminated_early"] == want["terminated_early"]

    def test_plane_override_scatter(self):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService("meshpalovr", Settings({
            "index.number_of_shards": 3,
            "index.search.mesh": True,
            "index.search.mesh.plane": "scatter",
            "index.refresh_interval": -1,
        }), mapping=self.MAPPING)
        for d in range(30):
            idx.index_doc(str(d), {"body": f"w{d % 5} w1"})
        idx.refresh()
        r = idx.search({"query": {"match": {"body": "w1"}}, "size": 5})
        assert r["_plane"] == "mesh"  # override keeps the kernel out
        assert idx._mesh_search.pallas_query_total == 0
        idx.close()

    def test_packing_limit_falls_back_to_host(self):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService("meshpallim", Settings({
            "index.number_of_shards": 5,
            "index.search.mesh": True,
            "index.search.mesh.max_slots_per_device": 1,
            "index.refresh_interval": -1,
        }), mapping=self.MAPPING)
        for batch in range(2):
            for d in range(batch * 40, batch * 40 + 40):
                idx.index_doc(str(d), {"body": f"w{d % 5} w1"})
            idx.refresh()  # 10 segments > 8 devices * 1 slot
        r = idx.search({"query": {"match": {"body": "w1"}}, "size": 5})
        assert r["_plane"] == "host"
        idx.close()

    def test_fifth_segment_on_one_device_reports_slots_exceeded(self):
        # a default one-chip index: 1 device x 4 slots. Its fifth
        # segment leaves the fast plane, and _stats says why.
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService
        from elasticsearch_tpu.parallel.mesh import shard_mesh
        from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

        idx = IndexService("meshslots", Settings({
            "index.number_of_shards": 2,
            "index.search.mesh": True,
            "index.refresh_interval": -1,
        }), mapping=self.MAPPING)
        idx._mesh_search = IndexMeshSearch(idx, mesh=shard_mesh(1))
        for batch in range(3):
            for d in range(batch * 40, batch * 40 + 40):
                idx.index_doc(str(d), {"body": f"w{d % 5} w1"})
            idx.refresh()  # 2 shards x 3 segments > 1 device x 4 slots
        r = idx.search({"query": {"match": {"body": "w1"}}, "size": 5})
        assert r["_plane"] == "host"
        decisions = idx.search_stats()["phases"]["decisions"]
        assert decisions["host.slots_exceeded"] == 1, decisions
        assert "host.staging_unavailable" not in decisions
        idx.close()


class TestExecutionPlaneObservability:
    """VERDICT r4 weak 3: 'did we use the chip?' must be observable —
    plane markers on responses/profiles + counters in _stats."""

    def test_plane_markers_and_counters(self):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService

        idx = IndexService("obs", Settings({
            "index.number_of_shards": 3,
            "index.search.mesh": True,
        }), mapping={"properties": {"body": {"type": "text",
                                             "analyzer": "whitespace"}}})
        for d in range(30):
            idx.index_doc(str(d), {"body": f"w{d % 5} w1"})
        idx.refresh()
        # mesh-eligible query
        r1 = idx.search({"query": {"match": {"body": "w1"}}, "size": 5})
        assert r1["_plane"] == "mesh"
        # host-only query (collapse is mesh-UNSUPPORTED; profile no
        # longer demotes — ISSUE 8 plane-truthfulness); a profiled host
        # query still carries the per-segment tree
        r2 = idx._search_uncached(
            {"query": {"match": {"body": "w1"}}, "size": 5,
             "profile": True}, skip_mesh=True)
        assert r2["_plane"] == "host"
        shard_profile = r2["profile"]["shards"][0]
        assert shard_profile["plane"] == "host"
        assert shard_profile["searches"][0]["query"][0]["engine"] in (
            "pallas_tile_kernel", "xla_scatter")
        planes = idx.stats()["_all"]["total"]["search"]["planes"] \
            if "_all" in idx.stats() else \
            idx.stats()["total"]["search"]["planes"]
        assert planes["mesh_query_total"] >= 1
        assert planes["host_query_total"] >= 1
        assert (planes["pallas_segments_total"]
                + planes["scatter_segments_total"]) >= 1
        idx.close()
