"""Where a generation's segments sit on the mesh (docs/MESH.md,
"Placement").

A shard's segments live on one device, ``shard id mod n_dev``, slot
``device * spd + rank`` there, and every device keeps headroom of its
own: 8 one-segment shards on 4 chips stage 2 live slots on each, where
filling slots in list order put all 8 on devices 0 and 1. Runs on 4 of
the conftest's 8 virtual CPU devices, the tile kernel in interpret mode;
answers are held to a plain numpy BM25 with per-shard statistics and to
the host plane.
"""

import math
from collections import Counter

import numpy as np
import pytest

from elasticsearch_tpu.parallel.mesh import shard_mesh
from elasticsearch_tpu.parallel.plan_exec import place_slots

MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "tag": {"type": "keyword"},
    "n": {"type": "integer"},
}}
K1, B = 1.2, 0.75
REL = 5e-4  # the score limit of the benchmark's msmarco cells


def _docs(seed, lo, hi):
    """Seeded documents ``lo``..``hi``: zipf-ish words, varied lengths."""
    rng = np.random.RandomState(seed + lo)
    vocab = [f"w{i}" for i in range(40)]
    p = 1.0 / np.arange(1, len(vocab) + 1)
    p /= p.sum()
    out = {}
    for d in range(lo, hi):
        words = rng.choice(vocab, size=rng.randint(3, 16), p=p)
        out[str(d)] = {"body": " ".join(words),
                       "tag": ["amber", "blue", "coral", "dune"][d % 4],
                       "n": int(rng.randint(0, 500))}
    return out


def _index(name, n_dev, shards=8, mesh=True, seed=39, n_docs=480,
           **extra):
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

    idx = IndexService(name, Settings({
        "index.number_of_shards": shards,
        "index.search.mesh": mesh,
        "index.refresh_interval": -1, **extra,
    }), mapping=MAPPING)
    if mesh:
        idx._mesh_search = IndexMeshSearch(idx, mesh=shard_mesh(n_dev))
    docs = _docs(seed, 0, n_docs)
    for doc_id, src in docs.items():
        idx.index_doc(doc_id, src)
    idx.refresh()  # one segment a shard
    return idx, docs


class NumpyBm25:
    """BM25 (Lucene's idf, k1 1.2, b 0.75) of ``body`` with each shard's
    own statistics, over the documents each shard holds: those of its
    segment, as the engine scores (one segment a shard until a refresh
    adds one)."""

    def __init__(self, idx, docs):
        self.docs = docs
        self.shard_of, self.group_of = {}, {}
        for sid, shard in idx.shards.items():
            for seg in shard.engine.searchable_segments():
                for d in range(seg.num_docs):
                    if seg.live[d]:
                        self.shard_of[seg.doc_ids[d]] = sid
                        self.group_of[seg.doc_ids[d]] = (sid, seg.name)
        self.tokens = {i: src["body"].split() for i, src in docs.items()
                       if i in self.shard_of}
        self.count, self.sum_len = Counter(), Counter()
        for i, toks in self.tokens.items():
            self.count[self.group_of[i]] += 1
            self.sum_len[self.group_of[i]] += len(toks)

    def scores(self, terms):
        """{doc id: score} of an OR of ``terms``, the matched docs."""
        out = {}
        for t in terms:
            df = Counter(self.group_of[i] for i, toks in self.tokens.items()
                         if t in toks)
            for i, toks in self.tokens.items():
                tf = toks.count(t)
                if not tf:
                    continue
                s = self.group_of[i]
                n, d = self.count[s], df[s]
                idf = math.log(1.0 + (n - d + 0.5) / (d + 0.5))
                avgdl = self.sum_len[s] / n
                norm = K1 * (1.0 - B + B * len(toks) / avgdl)
                out[i] = out.get(i, 0.0) + idf * tf * (K1 + 1.0) / (tf + norm)
        return out


def _same_as_numpy(resp, want, size):
    """hits.total exact; every hit's score its document's, each rank's
    score the reference's at that rank (ids free only among ties)."""
    assert resp["hits"]["total"] == len(want)
    hits = resp["hits"]["hits"]
    ranked = sorted(want.values(), reverse=True)[:size]
    assert len(hits) == len(ranked)
    for h, r in zip(hits, ranked):
        assert abs(h["_score"] - want[h["_id"]]) <= REL * want[h["_id"]]
        assert abs(h["_score"] - r) <= REL * r
    cut = ranked[-1] if ranked else None
    # every reference doc scoring clearly above the cut is returned
    for i, s in want.items():
        if cut is not None and s > cut * (1 + 2 * REL):
            assert i in {h["_id"] for h in hits}, i


def _same(got, want):
    """Mesh against the host plane: the same hits, scores, aggs."""
    assert got["hits"]["total"] == want["hits"]["total"]
    assert ([h["_id"] for h in got["hits"]["hits"]]
            == [h["_id"] for h in want["hits"]["hits"]])
    for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
        if w["_score"] is None:  # a field sort scores nothing
            assert g["_score"] is None
        else:
            assert abs(g["_score"] - w["_score"]) <= 1e-5 * max(
                1.0, abs(w["_score"])), (g, w)
        assert g.get("sort") == w.get("sort")
    assert got.get("aggregations") == want.get("aggregations")
    assert got.get("terminated_early") == want.get("terminated_early")


QUERIES = [["w3"], ["w1", "w7"], ["w0", "w12", "w25"], ["w5", "w9", "w30",
                                                          "w2"]]


@pytest.fixture(autouse=True)
def _kernel_plans(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


@pytest.fixture(scope="module")
def four():
    """8 one-segment shards on 4 devices, with the host plane's twin."""
    import os

    saved = os.environ.get("ES_TPU_PALLAS")
    os.environ["ES_TPU_PALLAS"] = "interpret"
    mesh_idx, docs = _index("place4", 4)
    host_idx, _ = _index("place4h", 4, mesh=False)
    mesh_idx.search({"query": {"match": {"body": "w1"}}, "size": 1})
    yield mesh_idx, host_idx, docs
    mesh_idx.close()
    host_idx.close()
    if saved is None:
        os.environ.pop("ES_TPU_PALLAS", None)
    else:
        os.environ["ES_TPU_PALLAS"] = saved


class TestPlacement:
    def test_eight_shards_stage_two_live_slots_on_every_device(self, four):
        mesh_idx, _, _ = four
        ex = mesh_idx._mesh_search._executor
        assert (ex.n_dev, ex.slots_per_dev, ex.n_slots) == (4, 4, 16)
        assert ex.live_slots_per_device() == [2, 2, 2, 2]
        assert ex.live_devices == 4
        for slot, (sid, _seg) in zip(ex.slots, ex.pairs):
            assert slot // ex.slots_per_dev == sid % 4
            assert ex.pair_of_slot[slot][0] == sid
        # the staged live masks say the same: a live row in slots 0, 1
        # of each device, headroom dead
        live1 = np.asarray(ex._seg_staged["live1"]).any(axis=1)
        assert live1.reshape(4, 4).tolist() == [[True, True, False,
                                                 False]] * 4
        planes = mesh_idx.search_stats()["planes"]
        assert planes["mesh_live_slots_per_device"] == [2, 2, 2, 2]

    def test_a_shards_segments_follow_it_to_its_device(self):
        slots, spd = place_slots([0, 0, 1, 2, 3, 3], 4, 3)
        assert spd == 3
        assert [s // spd for s in slots] == [0, 0, 1, 2, 3, 3]
        assert slots == [0, 1, 3, 6, 9, 10]
        # past the balanced count a segment goes where the fewest are
        slots, spd = place_slots([0] * 10 + [1] * 10, 8)
        loads = Counter(s // spd for s in slots)
        assert spd == 3 and max(loads.values()) == 3
        assert len(set(slots)) == 20

    @pytest.mark.parametrize("terms", QUERIES)
    def test_answers_are_numpy_bm25_with_per_shard_statistics(
            self, four, terms):
        mesh_idx, host_idx, docs = four
        body = {"query": {"match": {"body": " ".join(terms)}}, "size": 10}
        got = mesh_idx.search(dict(body))
        assert got["_plane"] == "mesh_pallas"
        _same_as_numpy(got, NumpyBm25(mesh_idx, docs).scores(terms), 10)
        _same(got, host_idx.search(dict(body)))

    def test_terminate_after_caps_each_shard(self, four):
        mesh_idx, host_idx, docs = four
        body = {"query": {"match": {"body": "w0 w1"}}, "size": 5,
                "terminate_after": 3}
        got = mesh_idx.search(dict(body))
        assert got["_plane"] in ("mesh", "mesh_pallas")
        ref = NumpyBm25(mesh_idx, docs)
        per_shard = Counter(ref.shard_of[i] for i in ref.scores(["w0", "w1"]))
        assert got["hits"]["total"] == sum(min(c, 3)
                                           for c in per_shard.values())
        assert got["terminated_early"] is True
        _same(got, host_idx.search(dict(body)))

    def test_a_terms_aggregation_counts_every_shard(self, four):
        mesh_idx, host_idx, docs = four
        body = {"query": {"match": {"body": "w2 w4"}}, "size": 3,
                "aggs": {"tags": {"terms": {"field": "tag"}},
                         "st": {"stats": {"field": "n"}}}}
        got = mesh_idx.search(dict(body))
        assert got["_plane"] in ("mesh", "mesh_pallas")
        matched = NumpyBm25(mesh_idx, docs).scores(["w2", "w4"])
        want = Counter(docs[i]["tag"] for i in matched)
        assert {b["key"]: b["doc_count"] for b in
                got["aggregations"]["tags"]["buckets"]} == dict(want)
        assert got["aggregations"]["st"]["sum"] == sum(
            docs[i]["n"] for i in matched)
        _same(got, host_idx.search(dict(body)))

    def test_a_field_sort_ranks_across_every_device(self, four):
        mesh_idx, host_idx, docs = four
        body = {"query": {"match": {"body": "w6 w8"}}, "size": 10,
                "sort": [{"n": "asc"}]}
        got = mesh_idx.search(dict(body))
        assert got["_plane"] in ("mesh", "mesh_pallas")
        matched = NumpyBm25(mesh_idx, docs).scores(["w6", "w8"])
        assert [h["sort"][0] for h in got["hits"]["hits"]] == sorted(
            docs[i]["n"] for i in matched)[:10]
        _same(got, host_idx.search(dict(body)))

    def test_batched_program_equals_serial(self, four):
        mesh_idx, _, _ = four
        bodies = [{"query": {"match": {"body": " ".join(t)}}, "size": 10}
                  for t in QUERIES]
        out = mesh_idx.search_batch([dict(b) for b in bodies])
        for body, got in zip(bodies, out):
            assert got["_plane"] == "mesh_pallas", got
            want = mesh_idx.search(dict(body))
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            assert got["hits"]["total"] == want["hits"]["total"]

    def test_every_device_live_reads_one(self, four):
        mesh_idx, _, _ = four
        before = mesh_idx.search_stats()["planes"]
        for terms in QUERIES:
            mesh_idx.search({"query": {"match": {"body": " ".join(terms)}},
                             "size": 10})
        after = mesh_idx.search_stats()["planes"]
        devices = after["mesh_devices_total"] - before["mesh_devices_total"]
        live = (after["mesh_devices_live_total"]
                - before["mesh_devices_live_total"])
        assert devices == 4 * len(QUERIES)
        assert live / devices == 1.0


def test_a_refresh_appends_onto_each_shards_own_device():
    # (no background compaction: the delta generation itself is read)
    idx, docs = _index("place4d", 4, n_docs=240,
                       **{"index.staging.compact.threshold": 0.0})
    try:
        assert idx.search({"query": {"match": {"body": "w1"}},
                           "size": 3})["_plane"] == "mesh_pallas"
        ms = idx._mesh_search
        old = ms._executor
        # few enough new words that no new segment outgrows the slots
        fresh = _docs(41, 240, 246)
        for doc_id, src in fresh.items():
            idx.index_doc(doc_id, src)
        idx.refresh()
        docs.update(fresh)
        got = idx.search({"query": {"match": {"body": "w1 w3"}},
                          "size": 10})
        assert got["_plane"] == "mesh_pallas"
        assert ms.delta_restage_total == 1  # appended, not rebuilt
        new = ms._executor
        assert new.n_slots == old.n_slots
        n_old = len(old.segments)
        assert len(new.segments) > n_old
        assert new.slots[:n_old] == old.slots
        for slot, (sid, _seg) in zip(new.slots[n_old:], new.pairs[n_old:]):
            assert slot // new.slots_per_dev == sid % 4
        _same_as_numpy(got, NumpyBm25(idx, docs).scores(["w1", "w3"]), 10)
    finally:
        idx.close()


def test_one_device_places_as_before():
    """On one chip the placement is the identity: pair i in slot i, the
    slot tables byte for byte what filling slots in order stages."""
    from elasticsearch_tpu.ops import pallas_scoring as psc
    from elasticsearch_tpu.parallel.distributed import stack_shard_arrays

    assert place_slots([0, 0, 1, 1, 5], 1, 8) == ([0, 1, 2, 3, 4], 8)
    idx, _ = _index("place1", 1, shards=2, n_docs=200)
    try:
        more = _docs(43, 200, 400)
        for doc_id, src in more.items():
            idx.index_doc(doc_id, src)
        idx.refresh()  # a second segment a shard
        assert idx.search({"query": {"match": {"body": "w1"}},
                           "size": 3})["_plane"] == "mesh_pallas"
        ex = idx._mesh_search._executor
        assert ex.slots == list(range(len(ex.segments)))
        assert [sid for sid, _ in ex.pairs] == [0, 0, 1, 1]
        want = stack_shard_arrays(ex.segments, ex.n_slots)
        for name in ("block_docs", "block_tfs", "norms", "live1"):
            got = np.asarray(ex._seg_staged[name])
            assert got.tobytes() == want[name].tobytes(), name
        key = "k_packed" if ex.postings_codec == "packed" else "k_docs"
        table = np.asarray(ex._seg_staged[key])
        rows = table.shape[0] // ex.n_slots
        for i, seg in enumerate(ex.segments):
            f = seg._block_frac()
            if key == "k_docs":
                blocks = psc.pad_segment_blocks(seg.block_docs, f,
                                                seg.nd_pad)[0]
            else:
                blocks = psc.pack_segment_blocks(seg.block_docs, f,
                                                 seg.nd_pad)
            assert (table[i * rows: i * rows + blocks.shape[0]].tobytes()
                    == blocks.tobytes())
    finally:
        idx.close()
