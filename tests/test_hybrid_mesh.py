"""Hybrid search in one launch: a ``match`` query and a ``knn`` section
in one ``_search``, fused as Elasticsearch 8 fuses them without a
``rank`` (the ``k`` global nearest neighbours join the query's matches,
a hit scores its BM25 score plus ``knn.boost`` times its kNN score,
``hits.total`` counts the union), answered by ONE launch of the mesh
hybrid program and, where the mesh cannot serve, by the host rung with
the same answer.

The answers are held to the benchmark's own plain reference
(``benchmark/references/hybrid.py``: numpy BM25 with per-shard
statistics and float64 inner products over the bfloat16 vectors) at a
small size, on seeded data: a winner past the lexical top-10, a winner
that matches no term, boost 0 / 1 / 10, ``k`` above the matches,
paging, deleted documents, a slot with no live vector, the exact union
total; and one span tree and one launch a request.

Kernel paths run in interpret mode on the CPU backend.
"""

import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.comparison import Comparison  # noqa: E402
from harness.corpus import TextField, shard_of_ids, words  # noqa: E402
from references import bm25, hybrid, knn_exact  # noqa: E402

with open(os.path.join(BENCH, "configs", "msmarco-passage-hybrid.json"),
          encoding="utf-8") as _f:
    CONFIG = json.load(_f)

N, DIMS, VOCAB = 360, 16, 60
MAPPING = {"properties": {
    "text": {"type": "text"},
    "emb": {"type": "dense_vector", "dims": DIMS,
            "similarity": "max_inner_product"}}}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def _index(name, texts, vecs, mesh=True, skip=()):
    """A 2-shard index of the documents, one segment a shard; the
    documents in ``skip`` carry no vector."""
    idx = IndexService(name, Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        "index.search.mesh": mesh}), mapping=MAPPING)
    for i, text in enumerate(texts):
        doc = {"text": text}
        if i not in skip:
            doc["emb"] = vecs[i].tolist()
        idx.index_doc(str(i), doc)
    idx.refresh()
    return idx


class Corpus:
    """Seeded passages of ``t<rank>`` words (zipfian) and 16-wide vectors
    of whole millionths; queries of 1-3 terms, each with a vector, and
    two made to order: a winner past the lexical top-10, and a winner
    that matches no term."""

    def __init__(self, seed=4100000041):
        rng = np.random.RandomState(seed % 2**32)
        probs = 1.0 / np.arange(1, VOCAB + 1)
        probs /= probs.sum()
        lengths = rng.randint(4, 16, N)
        tokens = rng.choice(VOCAB, int(lengths.sum()), p=probs)
        self.field = TextField(tokens, lengths, VOCAB)
        self.texts = [words("t", self.field.doc_tokens(i)) for i in range(N)]
        self.vecs = np.round(rng.standard_normal((N, DIMS)) / 4,
                             6).astype(np.float32)
        self.shard = shard_of_ids(np.arange(N), 2)
        self.terms = [sorted(rng.choice(np.arange(3, 30), rng.randint(1, 4),
                                        replace=False).tolist())
                      for _ in range(8)]
        qvecs = list(np.round(rng.standard_normal((8, DIMS)) / 4, 6))
        text = bm25.Bm25(self.field, self.shard, 2)
        # a document the term matches, ranked 20th or later by BM25
        scores, matched = text.match([4])
        ranked = np.argsort(-np.where(matched, scores, -np.inf),
                            kind="stable")
        self.past = int(ranked[25])
        self.terms.append([4])
        qvecs.append(self.vecs[self.past] * 4)
        # a document it does not match
        self.unmatched = int(np.flatnonzero(~matched)[3])
        self.terms.append([4])
        qvecs.append(self.vecs[self.unmatched] * 4)
        self.queries = np.round(np.asarray(qvecs), 6).astype(np.float32)
        self.view = {"text_fields": {"text": self.field}, "columns": {},
                     "shard": self.shard, "vectors": self.vecs,
                     "queries": self.queries}
        self.reference = hybrid.Reference(self.view, CONFIG)

    def body(self, n, k=10, boost=10.0, size=10, **extra):
        return {"query": {"match": {"text": words("t", self.terms[n])}},
                "knn": {"field": "emb", "k": k, "num_candidates": 100,
                        "boost": boost,
                        "query_vector": self.queries[n].tolist()},
                "size": size, "_source": False, **extra}

    def ref(self, n, k=10, boost=10.0, size=10):
        return {"kind": "hybrid", "field": "text", "terms": self.terms[n],
                "n": n, "k": k, "boost": boost, "size": size}

    def expected(self, n, k=10, boost=10.0):
        """(fused float32 scores, union) of request ``n``."""
        scores, matched = self.reference._lexical(self.ref(n))
        winners = self.reference._winner_sets(self.ref(n, k=k))
        assert len(winners) == 1  # no tie at the k-th neighbour here
        return self.reference._fuse(scores, matched, winners[0], boost)


@pytest.fixture(scope="module")
def corpus():
    return Corpus()


@pytest.fixture(scope="module")
def served(corpus):
    mesh = _index("hybrid-mesh", corpus.texts, corpus.vecs)
    host = _index("hybrid-host", corpus.texts, corpus.vecs, mesh=False)
    yield mesh, host
    mesh.close()
    host.close()


def _answer(resp):
    hits = resp["hits"]["hits"]
    return {"total": resp["hits"]["total"],
            "ids": [int(h["_id"]) for h in hits],
            "scores": [h["_score"] for h in hits]}


def _compare(corpus, resp, n, **kw):
    cmp = Comparison(CONFIG["limits"])
    corpus.reference.compare(cmp, f"q{n}", _answer(resp), corpus.ref(n, **kw))
    return cmp


@pytest.mark.parametrize("boost", [0.0, 1.0, 10.0])
def test_the_one_launch_program_matches_the_plain_reference(
        corpus, served, boost):
    mesh, _ = served
    for n in range(len(corpus.terms)):
        resp = mesh.search(corpus.body(n, boost=boost))
        assert resp["_plane"] == "mesh_pallas"
        assert resp["_hybrid"] == {"lexical_plane": "mesh_pallas",
                                   "knn_plane": "mesh_pallas",
                                   "fusion": "convex"}
        assert resp["_total_relation"] == "eq"
        cmp = _compare(corpus, resp, n, boost=boost)
        assert cmp.correct(), (n, cmp.numbers())
        # hits.total is the union's exact count
        fused, union = corpus.expected(n, boost=boost)
        assert resp["hits"]["total"] == int(union.sum())


def test_a_winner_past_the_lexical_window_keeps_its_text_score(
        corpus, served):
    """The parent's fault: a kNN winner that matches the query but ranks
    past the lexical top-10 scored 0 for its BM25 part."""
    mesh, _ = served
    n = len(corpus.terms) - 2
    scores, matched = corpus.reference._lexical(corpus.ref(n))
    assert matched[corpus.past] and scores[corpus.past] > 0
    assert (np.where(matched, scores, -np.inf) > scores[corpus.past]).sum() \
        >= 20
    resp = mesh.search(corpus.body(n))
    got = {int(h["_id"]): h["_score"] for h in resp["hits"]["hits"]}
    fused, _ = corpus.expected(n)
    assert got[corpus.past] == pytest.approx(float(fused[corpus.past]),
                                             rel=1e-6)
    sim = float(knn_exact.bf16(corpus.vecs[corpus.past]).astype(np.float64)
                @ corpus.queries[n])
    knn_part = 10.0 * float(knn_exact.score_of(np.asarray([sim]))[0])
    assert got[corpus.past] == pytest.approx(
        float(scores[corpus.past]) + knn_part, rel=1e-5)
    assert _compare(corpus, resp, n).correct()
    # ... which the parent's fusion (a lexical window of 10) gets wrong
    low = Comparison(CONFIG["limits"])
    corpus.reference.compare(low, "window", None, corpus.ref(n),
                             control="lexical_window")
    assert not low.correct()


def test_a_winner_that_matches_no_term_scores_its_knn_part_alone(
        corpus, served):
    mesh, host = served
    n = len(corpus.terms) - 1
    _, matched = corpus.reference._lexical(corpus.ref(n))
    assert not matched[corpus.unmatched]
    for idx in served:
        resp = idx.search(corpus.body(n))
        got = {int(h["_id"]): h["_score"] for h in resp["hits"]["hits"]}
        fused, union = corpus.expected(n)
        assert got[corpus.unmatched] == pytest.approx(
            float(fused[corpus.unmatched]), rel=1e-6)
        assert resp["hits"]["total"] == int(union.sum()) > int(matched.sum())
        assert _compare(corpus, resp, n).correct()


def test_k_above_the_number_of_matches(corpus, served):
    """A rare term: fewer matches than ``k``; every member of the union
    comes back, and the total counts it."""
    mesh, host = served
    n = 0
    rare = {"query": {"match": {"text": "t55"}}}
    _, matched = bm25.Bm25(corpus.field, corpus.shard, 2).match([55])
    assert 0 < matched.sum() < 30
    terms, corpus.terms[n] = corpus.terms[n], [55]
    try:
        for idx in served:
            resp = idx.search(dict(corpus.body(n, k=30, size=60), **rare))
            cmp = _compare(corpus, resp, n, k=30, size=60)
            assert cmp.correct(), cmp.numbers()
            _, union = corpus.expected(n, k=30)
            assert resp["hits"]["total"] == len(resp["hits"]["hits"]) \
                == int(union.sum())
    finally:
        corpus.terms[n] = terms


def test_paging_is_a_window_of_the_fused_ranking(corpus, served):
    mesh, _ = served
    full = mesh.search(corpus.body(3, size=25))
    for from_, size in ((0, 5), (5, 5), (10, 5), (12, 10)):
        page = mesh.search(dict(corpus.body(3, size=size), **{"from": from_}))
        assert page["_plane"] == "mesh_pallas"
        assert [h["_id"] for h in page["hits"]["hits"]] == [
            h["_id"] for h in full["hits"]["hits"]][from_: from_ + size]
        assert page["hits"]["total"] == full["hits"]["total"]


def _same_answer(a, b):
    """The same total and scores rank by rank; the same documents with
    the same scores where the page holds the whole union (ties among
    BM25 scores of this small vocabulary are common, and each rung
    breaks them its own way)."""
    assert a["hits"]["total"] == b["hits"]["total"]
    ra = [(h["_id"], h["_score"]) for h in a["hits"]["hits"]]
    rb = [(h["_id"], h["_score"]) for h in b["hits"]["hits"]]
    np.testing.assert_allclose([s for _, s in ra], [s for _, s in rb],
                               rtol=1e-6)
    if len(ra) == a["hits"]["total"]:
        assert sorted(ra) == sorted(rb)


@pytest.mark.parametrize("boost", [0.0, 1.0, 10.0])
def test_the_host_rung_answers_as_the_mesh_program(corpus, served, boost):
    mesh, host = served
    for n in range(len(corpus.terms)):
        for size in (10, N):
            m = mesh.search(corpus.body(n, boost=boost, size=size))
            h = host.search(corpus.body(n, boost=boost, size=size))
            assert (m["_plane"], h["_plane"]) == ("mesh_pallas", "host")
            assert h["_total_relation"] == "eq"
            _same_answer(m, h)


def test_deleted_documents_leave_both_sides(corpus):
    mesh = _index("hybrid-del-mesh", corpus.texts, corpus.vecs)
    host = _index("hybrid-del-host", corpus.texts, corpus.vecs, mesh=False)
    try:
        n = 2
        before = mesh.search(corpus.body(n))
        gone = [h["_id"] for h in before["hits"]["hits"][:3]]
        gone.append(str(corpus.past))
        for idx in (mesh, host):
            for doc_id in gone:
                idx.delete_doc(doc_id)
            idx.refresh()
        for q in (n, len(corpus.terms) - 2):
            m = mesh.search(corpus.body(q, size=N))
            h = host.search(corpus.body(q, size=N))
            assert m["_plane"] == "mesh_pallas"
            assert not set(gone) & {x["_id"] for x in m["hits"]["hits"]}
            _same_answer(m, h)
        assert mesh.search(corpus.body(n))["hits"]["total"] \
            < before["hits"]["total"]
    finally:
        mesh.close()
        host.close()


def test_a_slot_with_no_live_vector(corpus):
    """Shard 1's documents carry no vector: its slot's kNN pass is
    skipped, its lexical pass is not."""
    skip = set(np.flatnonzero(corpus.shard == 1).tolist())
    mesh = _index("hybrid-novec-mesh", corpus.texts, corpus.vecs, skip=skip)
    host = _index("hybrid-novec-host", corpus.texts, corpus.vecs, mesh=False,
                  skip=skip)
    try:
        for n in range(4):
            m = mesh.search(corpus.body(n, size=N))
            h = host.search(corpus.body(n, size=N))
            assert m["_plane"] == "mesh_pallas"
            _same_answer(m, h)
            ids = {int(x["_id"]) for x in m["hits"]["hits"]}
            assert ids & skip  # lexical hits of the vectorless shard
    finally:
        mesh.close()
        host.close()


def _planes(idx):
    return idx.search_stats()["planes"]


def test_one_span_tree_and_one_launch_a_request(corpus, served):
    mesh, host = served
    counters = ("hybrid_query_total", "hybrid_fused_total",
                "hybrid_host_fallback_total", "hybrid_launches_total")
    before = {idx.name: _planes(idx) for idx in served}
    spans0 = mesh.telemetry.spans_dict()
    resp = mesh.search(corpus.body(1, profile=True))
    spans1 = mesh.telemetry.spans_dict()
    after = _planes(mesh)
    assert [after[c] - before[mesh.name][c] for c in counters] == [1, 1, 0, 1]
    prof = resp["profile"]
    tree = prof["spans"]
    assert [s["name"] for s in tree if s["parent"] is None] == [
        "search.request"]
    names = [s["name"] for s in tree]
    for name in ("parse_rewrite", "plan_build", "staging", "kernel",
                 "kernel.lock_wait", "kernel.dispatch", "kernel.device_wait",
                 "merge", "fetch"):
        assert name in names, name
    assert names.count("kernel") == 1
    assert len(prof["annotations"]["launches"]) == 1
    assert {p["phase"] for p in prof["phases"]} >= {
        "parse_rewrite", "plan_build", "staging", "kernel", "merge", "fetch"}
    # the tree drained into _stats search.spans: one request, one kernel
    for name in ("search.request", "kernel", "fetch"):
        assert spans1[name]["count"] - spans0.get(name, {}).get(
            "count", 0) == 1, name
    # the host rung: one fallback, its fusion a span of the request
    resp = host.search(corpus.body(1, profile=True))
    after = _planes(host)
    assert [after[c] - before[host.name][c] for c in counters] == [1, 0, 1, 0]
    assert "hybrid.fuse" in [s["name"] for s in resp["profile"]["spans"]]


def test_the_launch_counter_counts_the_launches_made(corpus, monkeypatch):
    """``hybrid_launches_total`` counts the launches a request made, not
    the requests answered: where the kernel rung's copy fails, the
    scatter rung launches again and answers, and the counter reads two
    for the one request."""
    import jax

    from elasticsearch_tpu.parallel import plan_exec

    class FailFirstCopy:
        def __init__(self):
            self.gets = 0

        def __getattr__(self, name):
            return getattr(jax, name)

        def device_get(self, x):
            self.gets += 1
            if self.gets == 1:
                raise RuntimeError("injected: the copy to the host failed")
            return jax.device_get(x)

    mesh = _index("hybrid-relaunch", corpus.texts, corpus.vecs)
    host = _index("hybrid-relaunch-host", corpus.texts, corpus.vecs,
                  mesh=False)
    counters = ("hybrid_query_total", "hybrid_fused_total",
                "hybrid_host_fallback_total", "hybrid_launches_total")
    try:
        assert mesh.search(corpus.body(1))["_plane"] == "mesh_pallas"
        before = _planes(mesh)
        waits = FailFirstCopy()
        monkeypatch.setattr(plan_exec, "jax", waits)
        got = mesh.search(corpus.body(1, profile=True))
        after = _planes(mesh)
        assert waits.gets == 2
        assert got["_plane"] == "mesh"
        assert [after[c] - before[c] for c in counters] == [1, 1, 0, 2]
        assert [s["name"] for s in got["profile"]["spans"]].count(
            "kernel") == 2
        _same_answer(got, host.search(corpus.body(1)))
    finally:
        mesh.close()
        host.close()
