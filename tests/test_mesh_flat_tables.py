"""The mesh programs on ONE device that holds every slot (ISSUE 26).

The benchmark's cell is one chip with four slots, so each kernel table
is one flat 2-D array and ``score_tiles`` reads a slot at its row offset
(``row_base``). The 8-device mesh of the other mesh tests mostly leaves
one slot a device, where the offset is 0. Runs the kernel in interpret
mode on the CPU backend.
"""

import numpy as np
import pytest

from elasticsearch_tpu.parallel.mesh import shard_mesh

MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "tag": {"type": "keyword"},
    "n": {"type": "integer"},
}}


class TestOneChipFlatTables:
    """The benchmark cell's layout: ONE device holding every slot, so
    the kernel tables are one flat array and each slot is read at its
    row offset (ISSUE 26). 2 shards x 2 segments fill the chip's four
    slots; the four mesh programs and the host rung must agree on it,
    raw and packed."""

    BODIES = [
        {"query": {"match": {"body": "w1 w4"}}, "size": 10},
        {"query": {"match": {"body": "w2"}}, "size": 6},
        {"query": {"match": {"body": "w0 w3 w7"}}, "size": 8},
    ]

    @pytest.fixture(autouse=True)
    def _kernel_plans(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_PALLAS", "interpret")

    @staticmethod
    def _mk(name, mesh, codec, n_docs=240, **extra):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.index_service import IndexService
        from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

        idx = IndexService(name, Settings({
            "index.number_of_shards": 2,
            "index.search.mesh": mesh,
            "index.refresh_interval": -1,
            "index.search.pallas.postings_codec": codec, **extra,
        }), mapping=MAPPING)
        if mesh:
            idx._mesh_search = IndexMeshSearch(idx, mesh=shard_mesh(1))
        rng = np.random.RandomState(26)
        vocab = [f"w{i}" for i in range(10)]
        for lo, hi in ((0, n_docs // 2), (n_docs // 2, n_docs)):
            for d in range(lo, hi):
                idx.index_doc(str(d), {
                    "body": " ".join(vocab[rng.randint(len(vocab))]
                                     for _ in range(rng.randint(3, 9))),
                    "tag": ["amber", "blue", "coral"][d % 3],
                    "n": int(rng.randint(0, 40))})
            idx.refresh()  # a segment per shard and refresh
        return idx

    @pytest.fixture(params=["raw", "packed"])
    def pair(self, request):
        mesh_idx = self._mk(f"flat-{request.param}", True, request.param)
        host_idx = self._mk(f"flath-{request.param}", False,
                            request.param)
        yield mesh_idx, host_idx
        mesh_idx.close()
        host_idx.close()

    @staticmethod
    def _same(got, want, scores_exact=True):
        assert got["hits"]["total"] == want["hits"]["total"]
        assert ([h["_id"] for h in got["hits"]["hits"]]
                == [h["_id"] for h in want["hits"]["hits"]])
        for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
            if scores_exact:
                assert g["_score"] == w["_score"], (g, w)
            else:  # a batch scores with the members' union tables
                assert abs(g["_score"] - w["_score"]) < 1e-5, (g, w)
        assert got.get("aggregations") == want.get("aggregations")

    def test_serial_program_fills_the_chips_slots_and_equals_host(
            self, pair):
        mesh_idx, host_idx = pair
        for body in self.BODIES:
            got = mesh_idx.search(dict(body))
            assert got["_plane"] == "mesh_pallas"
            self._same(got, host_idx.search(dict(body)))
        ex = mesh_idx._mesh_search._executor
        assert (ex.n_dev, ex.slots_per_dev, len(ex.segments)) == (1, 4, 4)
        key = "k_packed" if ex.postings_codec == "packed" else "k_docs"
        assert ex._seg_staged[key].ndim == 2  # the slots, row after row

    def test_batched_kernel_program_equals_serial(self, pair):
        mesh_idx, _ = pair
        out = mesh_idx.search_batch([dict(b) for b in self.BODIES])
        assert mesh_idx._mesh_search.batched_launch_total == 1
        for body, got in zip(self.BODIES, out):
            assert got["_plane"] == "mesh_pallas", got
            self._same(got, mesh_idx.search(dict(body)),
                       scores_exact=False)

    def test_batched_dense_agg_program_equals_serial(self, pair):
        mesh_idx, host_idx = pair
        aggs = {"tags": {"terms": {"field": "tag"}},
                "st": {"stats": {"field": "n"}}}
        burst = [dict(b, aggs=aggs) for b in self.BODIES[:2]] \
            + [dict(self.BODIES[2])]
        out = mesh_idx.search_batch([dict(b) for b in burst])
        ms = mesh_idx._mesh_search
        assert ms.batched_launch_total == 1
        assert ms.agg_fused_query_total == 2
        for body, got in zip(burst, out):
            assert got["_plane"] == "mesh_pallas", got
            self._same(got, mesh_idx.search(dict(body)),
                       scores_exact=False)
            self._same(got, host_idx.search(dict(body)),
                       scores_exact=False)

    @pytest.mark.parametrize("codec", ["raw", "packed"])
    def test_batched_pruned_program_finds_the_same_hits(self, codec):
        prune = {"search.pallas.pruning.enabled": True,
                 "search.pallas.pruning.probe_tiles": 2}
        plain = self._mk(f"flatpl-{codec}", True, codec, n_docs=1200)
        pruned = self._mk(f"flatpr-{codec}", True, codec, n_docs=1200,
                          **prune)
        try:
            for body in self.BODIES:
                got = pruned.search(dict(body))
                want = plain.search(dict(body))
                assert got["_plane"] == "mesh_pallas"
                assert "_pruned" in got, "the pruned program did not run"
                assert ([h["_id"] for h in got["hits"]["hits"]]
                        == [h["_id"] for h in want["hits"]["hits"]])
                for g, w in zip(got["hits"]["hits"],
                                want["hits"]["hits"]):
                    assert abs(g["_score"] - w["_score"]) < 1e-5
                assert got["hits"]["total"] <= want["hits"]["total"]
            assert pruned._mesh_search._executor.slots_per_dev == 4
        finally:
            plain.close()
            pruned.close()
