"""Exact vector search at a deployed width (ISSUE 35): 768-wide vectors
through REST, on the normal path (``PUT`` index, ``_bulk``, ``_refresh``,
``_flush``, ``POST _search`` with a top-level ``knn`` section), against
the benchmark's own plain reference (``benchmark/references/
knn_exact.py``: float64 inner products over vectors it rounded to
bfloat16 itself).

- ``max_inner_product`` with winners on both sides of zero, ``cosine``
  and ``dot_product`` as they were: ids rank by rank, scores within the
  configuration's limit, ``hits.total``;
- a vector sent over ``_bulk`` reads back equal from ``_source`` and
  equal in the staged array, before and after ``_flush`` and reopen;
- the flat kNN program with two dead slots of four answers as the one
  with none, and ``knn_slots_scanned_total`` counts 2 a query;
- the packed answer holds what the four arrays held;
- the kNN request's span tree has the lexical request's shape.

Kernel paths run in interpret mode on the CPU backend.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.ops import pallas_knn as pkn
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.parallel.mesh import shard_mesh
from elasticsearch_tpu.search.telemetry import SearchTelemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.comparison import Comparison  # noqa: E402
from harness.corpus import shard_of_ids  # noqa: E402
from references import knn_exact  # noqa: E402

DIMS = 768
N_DOCS = 2000
with open(os.path.join(BENCH, "configs", "cohere-768-knn.json"),
          encoding="utf-8") as _f:
    CONFIG = json.load(_f)


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def _vectors(seed, n=N_DOCS):
    """(documents [n, 768], queries [6, 768]) as the float32 of six
    decimals, so that their text round-trips. The first query has five
    documents on its side and every other against it: a top-10 with
    winners on both sides of zero; the second has all but those five on
    its side."""
    rng = np.random.RandomState(seed)
    docs = rng.standard_normal((n, DIMS)) / np.sqrt(DIMS)
    docs *= rng.lognormal(0.0, 0.1, (n, 1))
    queries = rng.standard_normal((6, DIMS)) / np.sqrt(DIMS)
    q0 = queries[0] / np.linalg.norm(queries[0])
    docs -= 0.5 * q0  # everyone against the first two queries ...
    docs[rng.choice(n, 5, replace=False)] += q0  # ... but five, for one
    queries[1] = -0.7 * q0 + queries[1] * 0.1
    docs[:, :] = np.round(docs, 6)
    return (docs.astype(np.float32),
            np.round(queries, 6).astype(np.float32))


class _Served:
    """A node behind its HTTP front door holding one 2-shard index of
    768-wide vectors, loaded as ``benchmark/harness/server.py`` loads a
    base: ``PUT``, ``_bulk``, ``_refresh``, ``_flush``."""

    def __init__(self, similarity, docs, data_path=None, load=True):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.http_server import HttpServer

        self.node = Node(data_path=data_path)
        self.server = HttpServer(self.node, port=0)
        self.server.start()
        if not load:
            return
        self.call("PUT", "/vec", {
            "settings": {"index": {"number_of_shards": 2,
                                   "number_of_replicas": 0,
                                   "refresh_interval": "-1"}},
            "mappings": {"_doc": {"properties": {"emb": {
                "type": "dense_vector", "dims": DIMS,
                "similarity": similarity,
                "index_options": {"type": "flat"}}}}}})
        lines = []
        for i, vec in enumerate(docs):
            lines.append('{"index":{"_type":"_doc","_id":"%d"}}' % i)
            lines.append(json.dumps({"emb": [float(v) for v in vec]}))
        resp = self.call("POST", "/vec/_bulk", "\n".join(lines) + "\n",
                         ctype="application/x-ndjson")
        assert resp["errors"] is False
        self.call("POST", "/vec/_refresh")
        self.call("POST", "/vec/_flush")

    def call(self, method, path, body=None, ctype="application/json"):
        data = None
        if body is not None:
            data = (body if isinstance(body, str)
                    else json.dumps(body)).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.server.port}{path}", data=data,
            method=method, headers={"Content-Type": ctype})
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())

    def knn(self, query, k=10, **extra):
        resp = self.call("POST", "/vec/_search", {
            "knn": {"field": "emb",
                    "query_vector": [float(v) for v in query], "k": k,
                    "num_candidates": 100},
            "size": k, "_source": False, **extra})
        assert resp["_plane"] == "mesh_pallas", resp["_plane"]
        return resp

    def staged(self):
        """The field's staged bf16 rows of the live slots, by (shard,
        document), as float32."""
        ex = self.node.indices["vec"]._mesh_search._executor
        entry = ex._knn["emb"]
        rows = np.asarray(entry["emb"].astype(jnp.float32)).reshape(
            ex.n_slots, entry["nd_pad"], -1)
        return {(sid, seg.doc_ids[d]): rows[slot, d]
                for slot, (sid, seg) in enumerate(ex.pairs)
                for d in range(seg.num_docs)}

    def planes(self):
        return self.call("GET", "/vec/_stats")["indices"]["vec"][
            "total"]["search"]["planes"]

    def close(self):
        self.server.stop()
        self.node.close()


def _answer(resp):
    hits = resp["hits"]["hits"]
    return {"total": resp["hits"]["total"],
            "ids": [int(h["_id"]) for h in hits],
            "scores": [h["_score"] for h in hits]}


# ----------------------------------------------------------------------
# Against the benchmark's reference
# ----------------------------------------------------------------------


def test_max_inner_product_against_the_plain_reference():
    docs, queries = _vectors(35)
    served = _Served("max_inner_product", docs)
    try:
        reference = knn_exact.Reference(
            {"vectors": docs, "queries": queries,
             "shard": shard_of_ids(np.arange(N_DOCS), 2)}, CONFIG)
        top_ids, top_sims = reference.top()
        cmp = Comparison(CONFIG["limits"])
        for n, query in enumerate(queries):
            got = _answer(served.knn(query))
            reference.compare(cmp, f"q{n}", got,
                              {"kind": "knn", "n": n, "size": 10})
            assert got["ids"] == top_ids[n][:10].tolist(), n
            assert got["total"] == N_DOCS
        assert cmp.correct(), cmp.numbers()
        assert cmp.compared == len(queries)
        # winners on both sides of zero, scored by both branches
        sims = top_sims[0][:10]
        assert (sims > 0).sum() == 5 and (sims < 0).sum() == 5
        assert (top_sims[1][:10] > 0).all()
        scores = _answer(served.knn(queries[0]))["scores"]
        assert all(s > 1 for s in scores[:5])
        assert all(0 < s < 1 for s in scores[5:])
        # the controls are told from the program by the same limits
        for control in knn_exact.Reference.controls:
            low = Comparison(CONFIG["limits"])
            for n in range(len(queries)):
                reference.compare(low, f"q{n}", None,
                                  {"kind": "knn", "n": n, "size": 10},
                                  control=control)
            assert not low.correct(), control
    finally:
        served.close()


@pytest.mark.parametrize("decimals", [6, 4])
def test_the_generators_vectors_are_the_float32_of_its_text(decimals):
    """The reference and the program start from the same numbers: what
    a JSON parser and float32 make of a bulk line, or of a request's
    body, is the view's row, bit for bit; blocks join seamlessly."""
    from generators import cohere_vector

    config = dict(CONFIG, docs=700, generator_params=dict(
        CONFIG["generator_params"], block_docs=256, queries=5,
        decimals=decimals))
    data = cohere_vector.Dataset(config, 3500000077, 2)
    view = data.view(700)
    assert view["vectors"].shape == (700, DIMS)
    assert view["vectors"].dtype == np.float32
    lines = data.bulk_body(250, 520).split("\n")  # across two blocks
    assert len(lines) == 2 * 270 + 1 and lines[-1] == ""
    for j in (0, 5, 6, 261, 269):
        assert json.loads(lines[2 * j]) == {
            "index": {"_type": "_doc", "_id": str(250 + j)}}
        row = json.loads(lines[2 * j + 1])["emb"]
        np.testing.assert_array_equal(np.asarray(row, np.float32),
                                      view["vectors"][250 + j])
        assert all(len(repr(abs(v)).replace(".", "").strip("0")) <= 7
                   for v in row)  # at most 7 significant digits
    requests = data.operations()["knn_top10"]
    assert len(requests) == 5
    for n, req in enumerate(requests):
        body = json.loads(json.dumps(req["body"]))
        assert body["knn"]["k"] == 10 and body["_source"] is False
        np.testing.assert_array_equal(
            np.asarray(body["knn"]["query_vector"], np.float32),
            view["queries"][n])
        assert req["ref"] == {"kind": "knn", "n": n, "size": 10}
    again = cohere_vector.Dataset(config, 3500000077, 2).view(700)
    np.testing.assert_array_equal(again["vectors"], view["vectors"])
    other = cohere_vector.Dataset(config, 3500000078, 2).view(700)
    assert not np.array_equal(other["vectors"], view["vectors"])
    # (the lengths are the structure's, the same for every seed)
    np.testing.assert_allclose(np.linalg.norm(other["vectors"], axis=1),
                               np.linalg.norm(view["vectors"], axis=1),
                               rtol=1e-3)


@pytest.mark.parametrize("similarity", ["cosine", "dot_product"])
def test_cosine_and_dot_product_answer_as_before(similarity):
    """(1 + sim) / 2 over the bf16 vectors, as the module's own oracle
    has scored them since the field type came."""
    docs, queries = _vectors(36, n=600)
    served = _Served(similarity, docs)
    try:
        mirror = pkn.bf16_round(docs)
        for query in queries[2:5]:
            got = _answer(served.knn(query))
            want_s, want_i = pkn.reference_knn_topk(
                mirror, np.ones(len(docs), bool), query, 10, similarity)
            assert got["ids"] == want_i.tolist()
            np.testing.assert_allclose(got["scores"], want_s, rtol=2e-6)
            assert got["total"] == len(docs)
    finally:
        served.close()


@pytest.mark.parametrize("options, refused", [
    ({"type": "flat"}, False), ({"type": "hnsw"}, True),
    ({"type": "int8_hnsw"}, True), ({"type": "int8_flat"}, True),
    ("flat", True)])
def test_index_options_flat_alone_is_accepted(options, refused):
    from elasticsearch_tpu.common.errors import MapperParsingException

    def build():
        IndexService("opts", Settings({"index.number_of_shards": 1}),
                     mapping={"properties": {"emb": {
                         "type": "dense_vector", "dims": 4,
                         "similarity": "max_inner_product",
                         "index_options": options}}}).close()

    if refused:
        with pytest.raises(MapperParsingException, match="scans every"):
            build()
    else:
        build()


@pytest.mark.parametrize("vector, fault", [
    ([0.5] * 3, "does not match the mapping"),
    ([0.5, True, 0.5, 0.5], "non-numeric element"),
    ([0.5, "0.5", 0.5, 0.5], "non-numeric element"),
    ([0.5, None, 0.5, 0.5], "non-numeric element"),
    ([0.5, float("nan"), 0.5, 0.5], "non-finite"),
    ([0.5, 1e39, 0.5, 0.5], "non-finite"),
    ("0.5 0.5 0.5 0.5", "expected an array"),
])
def test_a_vector_parsed_as_one_row_keeps_the_faults(vector, fault):
    from elasticsearch_tpu.common.errors import MapperParsingException
    from elasticsearch_tpu.mapper.field_types import DenseVectorFieldType

    ft = DenseVectorFieldType("emb", {"dims": 4})
    with pytest.raises(MapperParsingException, match=fault):
        ft.parse_vector(vector)
    row = ft.parse_vector([1, 0.25, -3, 1e-3])
    assert row.dtype == np.float32 and row.shape == (4,)
    assert row.tolist() == [1.0, 0.25, -3.0, np.float32(1e-3)]


# ----------------------------------------------------------------------
# From the socket to the device, before and after flush and reopen
# ----------------------------------------------------------------------


def test_a_vector_reads_back_equal_from_source_and_from_the_device(
        tmp_path):
    docs, queries = _vectors(37, n=300)
    shard = shard_of_ids(np.arange(len(docs)), 2)
    served = _Served("max_inner_product", docs, data_path=str(tmp_path))
    try:
        before = served.knn(queries[3])
        for state in ("loaded", "reopened"):
            got = served.call("GET", "/vec/_doc/17")["_source"]["emb"]
            assert got == [float(v) for v in docs[17]]  # as sent
            staged = served.staged()
            assert len(staged) == len(docs)
            for i in (0, 17, 150, len(docs) - 1):
                np.testing.assert_array_equal(
                    staged[(int(shard[i]), str(i))][:DIMS],
                    knn_exact.bf16(docs[i]))
            if state == "loaded":
                served.close()
                served = _Served(None, None, data_path=str(tmp_path),
                                 load=False)
                again = served.knn(queries[3])
                assert again["hits"] == before["hits"]
    finally:
        served.close()


# ----------------------------------------------------------------------
# The flat program: dead slots, the packed answer
# ----------------------------------------------------------------------


def _index(name, docs, **settings):
    idx = IndexService(name, Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        "index.search.mesh": True,
        "index.staging.compact.threshold": 0.0, **settings}),
        mapping={"properties": {"emb": {
            "type": "dense_vector", "dims": DIMS,
            "similarity": "max_inner_product"}}})
    idx._mesh_search = plan_exec.IndexMeshSearch(idx, mesh=shard_mesh(1))
    for i, vec in enumerate(docs):
        idx.index_doc(str(i), {"emb": vec.tolist()})
    idx.refresh()
    return idx


def _knn_body(query, k=10):
    return {"knn": {"field": "emb", "query_vector": query.tolist(),
                    "k": k}, "size": k, "_source": False}


def test_two_dead_slots_of_four_change_no_answer_and_are_not_scanned():
    docs, queries = _vectors(38, n=400)
    headroom = _index("knn-headroom", docs)
    full = _index("knn-full", docs,
                  **{"index.staging.delta.enabled": False})
    try:
        for query in queries:
            a, b = (idx.search(_knn_body(query))
                    for idx in (headroom, full))
            assert a["_plane"] == b["_plane"] == "mesh_pallas"
            assert _answer(a) == _answer(b)
        for idx, slots in ((headroom, 4), (full, 2)):
            ex = idx._mesh_search._executor
            assert (len(ex.segments), ex.n_slots) == (2, slots)
            assert ex._knn["emb"]["emb"].shape == (
                slots * ex._knn["emb"]["nd_pad"], DIMS)  # flat
            mesh = idx._mesh_search
            assert mesh.knn_query_total == len(queries)
            # 2 a query whether 2 or 0 slots are free
            assert mesh.knn_slots_scanned_total == 2 * len(queries)
            assert mesh.embedding_bytes_streamed_total == (
                2 * len(queries) * ex._knn["emb"]["nd_pad"] * DIMS * 2)
        planes = headroom.stats()["total"]["search"]["planes"]
        assert planes["knn_slots_scanned_total"] == 2 * len(queries)
        assert planes["knn_query_total"] == len(queries)
        assert planes["embedding_bytes_streamed_total"] > 0
        # a shard whose documents are all deleted is one more dead slot
        ex = headroom._mesh_search._executor
        for sid, seg in ex.pairs[:1]:
            for doc_id in list(seg.doc_ids):
                headroom.delete_doc(doc_id)
        headroom.refresh()
        scanned = headroom._mesh_search.knn_slots_scanned_total
        resp = headroom.search(_knn_body(queries[4]))
        assert resp["_plane"] == "mesh_pallas"
        assert resp["hits"]["total"] == len(ex.pairs[1][1].doc_ids)
        assert headroom._mesh_search.knn_slots_scanned_total == scanned + 1
    finally:
        headroom.close()
        full.close()


def test_the_packed_answer_holds_what_the_four_arrays_held():
    """The program's one ``int32[q, 2 + 5k]`` against the kernel and the
    merge called by hand, slot by slot, on the same staged arrays."""
    docs, queries = _vectors(39, n=400)
    idx = _index("knn-packed", docs)
    try:
        idx.search(_knn_body(queries[0]))  # stages the field
        ex = idx._mesh_search._executor
        entry = ex.ensure_knn("emb", DIMS, "max_inner_product")
        nd, spd, k = entry["nd_pad"], ex.slots_per_dev, 16
        sub = pkn.knn_geometry(nd, DIMS).tile_sub
        qmat = np.stack([pkn.normalize_query(q, "max_inner_product", DIMS)
                         for q in queries[:2]])
        program = plan_exec._mesh_knn_program(
            ex.mesh, spd, 2, k, sub, DIMS, nd, "max_inner_product", True)
        packed = np.asarray(program(entry["emb"], entry["scale"],
                                    entry["mask"], jnp.asarray(qmat)))
        assert packed.shape == (2, 2 + 5 * k) and packed.dtype == np.int32
        pools = []
        for slot in range(spd):
            rows = slice(slot * nd, (slot + 1) * nd)
            ts, td = pkn.knn_score_tiles(
                entry["emb"][rows], entry["scale"][rows],
                entry["mask"][rows], jnp.asarray(qmat), sub=sub, k=k,
                q_batch=2, interpret=True)
            s, d = (np.asarray(o) for o in pkn.merge_knn_topk(ts, td, k))
            pools.append((s, d, np.full(s.shape, slot)))
        total = int(np.asarray(entry["mask"]).sum())
        for q in range(2):
            sims = np.concatenate([p[0][q] for p in pools])
            order = np.argsort(-sims, kind="stable")[:k]
            keys, slots, found, got_total, scores, _ = \
                plan_exec._unpack_answer(packed[q])
            np.testing.assert_array_equal(keys, sims[order])
            np.testing.assert_array_equal(
                slots, np.concatenate([p[2][q] for p in pools])[order])
            np.testing.assert_array_equal(
                found, np.concatenate([p[1][q] for p in pools])[order])
            np.testing.assert_array_equal(
                scores, pkn.hit_score(sims[order], "max_inner_product"))
            assert got_total == total == len(docs)
    finally:
        idx.close()


# ----------------------------------------------------------------------
# The request's span tree
# ----------------------------------------------------------------------

# span -> parent for a kNN `_search` over HTTP: the lexical request's
# tree (tests/test_observability.py SPAN_PARENTS) less the two phases a
# kNN request has no use for (it parses no query and builds no plan),
# and the phase in which the answer of a batch of one is handed to its
# one member (serial and batched kNN share the response assembly)
KNN_SPAN_PARENTS = {
    "http.request": None,
    "http.inbound": "http.request",
    "search.request": "http.request",
    "search.admit": "search.request",
    "search.route": "search.request",
    "staging": "search.request",
    "kernel": "search.request",
    "kernel.lock_wait": "kernel",
    "kernel.dispatch": "kernel",
    "kernel.device_wait": "kernel",
    "merge": "search.request",
    "merge.d2h": "merge",
    "merge.assemble": "merge",
    "batch_demux": "search.request",
    "fetch": "search.request",
    "search.respond": "search.request",
    "http.outbound": "http.request",
}


def test_the_knn_requests_span_tree_has_the_lexical_shape(monkeypatch):
    from test_observability import SPAN_PARENTS

    assert KNN_SPAN_PARENTS == dict(
        {name: parent for name, parent in SPAN_PARENTS.items()
         if name not in ("parse_rewrite", "plan_build")},
        batch_demux="search.request")
    drained = []
    keep = SearchTelemetry.record_spans

    def record_spans(tel, tracer):
        drained.append(tracer)
        keep(tel, tracer)

    monkeypatch.setattr(SearchTelemetry, "record_spans", record_spans)
    docs, queries = _vectors(40, n=300)
    served = _Served("max_inner_product", docs)

    def tree(query):
        n = len(drained)
        served.knn(query)
        deadline = time.monotonic() + 5.0
        while len(drained) == n and time.monotonic() < deadline:
            time.sleep(0.001)
        rows = {i: (name, parent) for name, _s, _e, parent, _self, _a, i
                in drained[-1].closed_spans()}
        assert drained[-1].spans_dropped == 0
        return {name: (rows[parent][0] if parent >= 0 else None)
                for name, parent in rows.values()}

    try:
        # the request that stages the field's embeddings says so
        assert tree(queries[0]) == dict(
            KNN_SPAN_PARENTS, **{"staging.knn_embeddings": "staging"})
        assert tree(queries[1]) == KNN_SPAN_PARENTS
        spans = served.call("GET", "/vec/_stats")["indices"]["vec"][
            "total"]["search"]["spans"]
        assert spans["staging.knn_embeddings"]["count"] == 1
        assert spans["kernel.device_wait"]["count"] == 2
        assert spans["merge.d2h"]["count"] == 2
        counters = served.call("GET", "/vec/_stats")["indices"]["vec"][
            "total"]["search"]["phases"]["counters"]
        assert counters["d2h_arrays_total"] == 2  # ONE fetch a query
    finally:
        served.close()


# ----------------------------------------------------------------------
# The translog does not parse 8 KB vectors back to count or trim them
# ----------------------------------------------------------------------


def test_an_appended_generation_is_counted_and_trimmed_unparsed(
        tmp_path, monkeypatch):
    """What this process appended it knows (ops, seqno range, bytes and
    CRC32): stats and trimming read the file's bytes back, not its JSON,
    as long as they are the bytes appended; a file that changed is the
    line-by-line reader's again (tests/test_crash_recovery.py holds
    what it reports)."""
    from elasticsearch_tpu.index.translog import Translog, TranslogOp

    tl = Translog(str(tmp_path / "t"), durability="async")
    vec = np.round(np.random.RandomState(3).randn(DIMS), 6).tolist()
    for seqno in range(40):
        tl.add(TranslogOp(TranslogOp.INDEX, seqno, str(seqno),
                          {"emb": vec}))
    parsed = []
    read_gen = Translog._read_gen

    def counting(self, gen, tolerate_tail=False):
        parsed.append(gen)
        return read_gen(self, gen, tolerate_tail)

    monkeypatch.setattr(Translog, "_read_gen", counting)
    assert tl.stats()["operations"] == 40
    assert tl.stats()["uncommitted_operations"] == 40
    tl.mark_committed(39)
    tl.roll_generation()
    assert tl.stats()["uncommitted_operations"] == 0
    tl.mark_committed(39)  # trims generation 1
    assert not os.path.exists(tl._gen_path(1))
    assert tl.stats()["operations"] == 0
    assert parsed == []
    # the commit point cuts through a generation: it is read
    for seqno in range(40, 44):
        tl.add(TranslogOp(TranslogOp.INDEX, seqno, str(seqno),
                          {"emb": vec}))
    tl.committed_seqno = 41
    assert tl.stats()["uncommitted_operations"] == 2
    assert parsed == [2]
    # a file that is not what was appended is read, and found wanting
    tl.roll_generation()
    with open(tl._gen_path(2), "a", encoding="utf-8") as f:
        f.write("{torn\n")
    del parsed[:]
    tl.mark_committed(42)  # (43 is still to be committed: retained)
    assert parsed == [2] and tl.corrupt_generations == {2}
    assert os.path.exists(tl._gen_path(2))
    # what was replayed and reopened is read as it always was
    tl.close()
    again = Translog(str(tmp_path / "t"), durability="async")
    known = again._appended.get(again.generation)
    assert known is None or known.ops == 0
    again.close()
