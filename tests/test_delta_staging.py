"""Delta device staging (ISSUE 20, docs/MESH.md "Slot allocator").

The mesh plane keeps its collective geometry across refreshes: an
appended segment stages ONLY its own tables into a free slot
(lifecycle reason ``delta_append`` — restage_amplification ~1 for a
pure append), a delete updates ONLY the affected slot's live-mask
column in place (reason ``tombstone``), and a background pass compacts
sparse slots into a fresh generation (reason ``compaction``) off the
query path. The parity contract is absolute: a delta-staged index must
return byte-identical hits (ids + scores), fused aggs, and kNN results
to a freshly full-restaged oracle on every rung, and the ledger must
return to baseline exactly across append → tombstone → compact — a
mid-delta staging fault restores the exact pre-attempt ledger.
Runs the kernel in interpret mode on the CPU backend.
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.memory import memory_accountant
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.testing.disruption import (
    StagingFailScheme,
    clear_search_disruptions,
)

MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "n": {"type": "integer"},
    "tag": {"type": "keyword"},
}}

DIMS = 8

KNN_MAPPING = {"properties": {
    "emb": {"type": "dense_vector", "dims": DIMS,
            "similarity": "cosine"},
    "body": {"type": "text", "analyzer": "whitespace"},
}}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    yield
    clear_search_disruptions()


def _doc(d):
    return {"body": f"w{d % 5} common", "n": d % 17,
            "tag": ["red", "green", "blue"][d % 3]}


def build_index(name, mesh=True, delta=True, compact=0.0, shards=3,
                mapping=None, **extra):
    """compact=0 disables background compaction so the staging tests
    observe the delta generations themselves, not the compactor
    rewriting them from under the assertions."""
    settings = {"index.number_of_shards": shards,
                "index.refresh_interval": -1,
                "index.search.mesh": mesh,
                "index.staging.delta.enabled": delta,
                "index.staging.compact.threshold": compact}
    if mesh:
        # one CPU device: raise the packing bound so multi-refresh
        # sequences keep fitting (a real mesh spreads over n_dev)
        settings.setdefault("index.search.mesh.max_slots_per_device", 16)
    settings.update(extra)
    return IndexService(name, Settings(settings),
                        mapping=mapping or MAPPING)


def _fill(idx, lo, hi):
    for d in range(lo, hi):
        idx.index_doc(str(d), _doc(d))
    idx.refresh()


def assert_parity(got, want):
    assert got["hits"]["total"] == want["hits"]["total"]
    assert ([h["_id"] for h in got["hits"]["hits"]]
            == [h["_id"] for h in want["hits"]["hits"]])
    for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
        assert g["_score"] == w["_score"], (g, w)  # byte-identical
    assert got.get("aggregations") == want.get("aggregations"), (
        got.get("aggregations"), want.get("aggregations"))


class TestDeltaAppend:
    def test_pure_append_keeps_generation_and_amp_1(self):
        idx = build_index("da-amp")
        try:
            _fill(idx, 0, 48)
            assert idx.search({"query": {"match": {"body": "common"}},
                               "size": 5})["_plane"] == "mesh_pallas"
            ms = idx._mesh_search
            acc = memory_accountant()
            st0 = acc.stats("da-amp")
            scope0 = ms._executor.scope
            free0 = ms._executor.free_slots()
            assert free0 >= idx.num_shards  # headroom for one refresh

            _fill(idx, 48, 64)
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            assert r["_plane"] == "mesh_pallas"
            assert r["hits"]["total"] == 64
            # served by a delta append, not a rebuild: the successor
            # generation carries the old arrays (fresh scope, but the
            # delta counter — not a full-restage reason — moved)
            assert ms.delta_restage_total == 1
            assert ms._executor.scope != scope0
            assert ms._executor.free_slots() == free0 - idx.num_shards
            st1 = acc.stats("da-amp")
            d_rest = (st1["restaged_bytes_total"]
                      - st0["restaged_bytes_total"])
            d_log = (st1["bytes_logically_changed_total"]
                     - st0["bytes_logically_changed_total"])
            # the headline number this PR exists for: a pure-append
            # refresh restages only the appended segments' bytes
            assert d_log > 0
            assert d_rest / d_log <= 1.5, (d_rest, d_log)
            reasons = {e["reason"] for e in st1["staging_events"]
                       if e not in st0["staging_events"]}
            assert "delta_append" in reasons
        finally:
            idx.close()

    @pytest.mark.parametrize("codec", ["raw", "packed"])
    def test_appended_segment_lands_in_its_slots_rows_of_the_flat_table(
            self, codec):
        """The kernel tables are ONE 2-D array, slots one after the
        other (ISSUE 26): a delta append writes the new slots' row
        range and nothing else, and the new segment is found."""
        from elasticsearch_tpu.ops import pallas_scoring as psc

        idx = build_index(
            f"da-flat-{codec}", shards=2,
            **{"index.search.pallas.postings_codec": codec})
        key = "k_packed" if codec == "packed" else "k_docs"
        try:
            _fill(idx, 0, 40)
            assert idx.search({"query": {"match": {"body": "common"}},
                               "size": 5})["_plane"] == "mesh_pallas"
            ms = idx._mesh_search
            old = ms._executor
            before = np.asarray(old._seg_staged[key])
            rows = before.shape[0] // old.n_slots
            assert before.ndim == 2 and before.shape[1] == psc.LANE
            assert rows % psc.CB_MAX == 0 and rows * old.n_slots \
                == before.shape[0]
            # few enough new words that the new segments have no more
            # blocks than the staged ones (else the refresh rebuilds)
            for d in range(40, 44):
                idx.index_doc(str(d), {"body": f"fresh{d} common",
                                       "n": d, "tag": "red"})
            idx.refresh()
            r = idx.search({"query": {"match": {"body": "fresh42"}},
                            "size": 5})
            assert r["_plane"] == "mesh_pallas"
            assert [h["_id"] for h in r["hits"]["hits"]] == ["42"]
            assert ms.delta_restage_total == 1
            new = ms._executor
            after = np.asarray(new._seg_staged[key])
            assert after.shape == before.shape  # no geometry rebuild
            first_new = len(old.segments)
            assert len(new.segments) > first_new
            # every old slot's rows, and the still-free slots', are as
            # they were; each new slot's rows are its segment's table
            keep = np.ones(after.shape[0], bool)
            keep[first_new * rows: len(new.segments) * rows] = False
            np.testing.assert_array_equal(after[keep], before[keep])
            for slot in range(first_new, len(new.segments)):
                seg = new.segments[slot]
                f = seg._block_frac()
                if codec == "packed":
                    want = psc.pack_segment_blocks(seg.block_docs, f,
                                                   seg.nd_pad)
                else:
                    want = psc.pad_segment_blocks(seg.block_docs, f,
                                                  seg.nd_pad)[0]
                got = after[slot * rows: (slot + 1) * rows]
                np.testing.assert_array_equal(got[: want.shape[0]], want)
                if codec == "raw":
                    np.testing.assert_array_equal(
                        np.asarray(new._seg_staged["k_frac"])[
                            slot * rows: slot * rows + f.shape[0]], f)
        finally:
            idx.close()

    def test_segment_with_more_blocks_than_a_slots_rows_takes_the_rebuild(
            self):
        """A segment whose postings do not fit a slot's row range of
        the staged table cannot delta-append: the refresh rebuilds the
        generation at the larger geometry and serves it."""
        from elasticsearch_tpu.ops import pallas_scoring as psc

        idx = build_index("da-toolong", shards=2)
        try:
            _fill(idx, 0, 24)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            old = ms._executor
            rows = old._seg_staged["k_docs"].shape[0] // old.n_slots
            assert old.free_slots() >= 1  # slots are not what runs out
            # every distinct word takes a block of its own
            n_words = rows  # more blocks than a slot's rows hold
            for d in range(24, 24 + n_words // 4):
                idx.index_doc(str(d), {"body": " ".join(
                    f"u{d}x{j}" for j in range(8)) + " common",
                    "n": d, "tag": "red"})
            idx.refresh()
            r = idx.search({"query": {"match": {"body": "u30x3"}},
                            "size": 5})
            assert r["_plane"] == "mesh_pallas"
            assert [h["_id"] for h in r["hits"]["hits"]] == ["30"]
            assert ms.delta_restage_total == 0
            new = ms._executor
            assert new.scope != old.scope
            new_rows = new._seg_staged["k_docs"].shape[0] // new.n_slots
            assert new_rows > rows and new_rows % psc.CB_MAX == 0
            assert idx.search({"query": {"match": {"body": "common"}},
                               "size": 1})["hits"]["total"] \
                == 24 + n_words // 4
        finally:
            idx.close()

    def test_append_slots_exhausted_falls_back_to_rebuild(self):
        # packing allows 2 slots total: the second refresh cannot fit a
        # delta append — the classifier must fall back to the full
        # rebuild (and the index keeps serving correctly)
        idx = build_index("da-fallback", shards=1,
                          **{"index.search.mesh.max_slots_per_device": 2})
        try:
            _fill(idx, 0, 24)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            _fill(idx, 24, 36)
            _fill(idx, 36, 48)  # 3 segments > 2 slots
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            assert r["hits"]["total"] == 48
            assert ms.delta_restage_total <= 1  # the 3rd seg rebuilt
        finally:
            idx.close()

    def test_delta_disabled_setting_forces_rebuild(self):
        idx = build_index("da-off", delta=False)
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            scope0 = ms._executor.scope if ms._executor else None
            _fill(idx, 48, 64)
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            assert r["hits"]["total"] == 64
            assert ms.delta_restage_total == 0
            assert ms._executor.scope != scope0  # full new generation
        finally:
            idx.close()


class TestTombstone:
    def test_delete_updates_only_live_mask_in_place(self):
        idx = build_index("ts-mask")
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            scope0 = ms._executor.scope
            acc = memory_accountant()
            n_before = len(acc.stats("ts-mask")["staging_events"])

            idx.delete_doc("7")
            idx.refresh()
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 48})
            assert r["hits"]["total"] == 47
            assert "7" not in [h["_id"] for h in r["hits"]["hits"]]
            # in place: SAME generation, only mask bytes restaged
            assert ms._executor.scope == scope0
            assert ms.tombstone_update_total == 1
            new_events = acc.stats("ts-mask")["staging_events"][n_before:]
            mesh_events = [e for e in new_events
                           if e["reason"] == "tombstone"]
            assert mesh_events, new_events
            assert all(e["kind"] in ("live_mask", "mesh_slot_tables")
                       for e in mesh_events), mesh_events
        finally:
            idx.close()

    def test_tombstone_density_visible_in_slot_stats(self):
        idx = build_index("ts-density", shards=2)
        try:
            _fill(idx, 0, 20)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            for d in range(5):
                idx.delete_doc(str(d))
            idx.refresh()
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            stats = ms.staging_slot_stats()
            assert stats["free_slots"] >= 1
            assert stats["free_slots_per_device"] >= 1
            # 5 of 20 docs tombstoned, visible per slot
            assert sum(s["docs"] - s["live"]
                       for s in stats["slots"]) == 5
            assert any(s["tombstone_density"] > 0
                       for s in stats["slots"]), stats
        finally:
            idx.close()


class TestDeltaVsFullParity:
    def _run_interleaved(self, idx):
        """Interleaved index/delete/refresh/search sequence, identical
        on every index it is applied to (the searches between steps
        keep a generation staged so the delta index actually exercises
        append + tombstone paths rather than one cold staging)."""
        probe = {"query": {"match": {"body": "common"}}, "size": 3}
        _fill(idx, 0, 48)
        idx.search(dict(probe))
        for d in (3, 17, 30):
            idx.delete_doc(str(d))
        idx.refresh()
        idx.search(dict(probe))
        _fill(idx, 48, 60)
        idx.search(dict(probe))
        for d in (48, 5):
            idx.delete_doc(str(d))
        idx.refresh()
        idx.search(dict(probe))
        _fill(idx, 60, 72)

    def test_hits_scores_and_aggs_byte_identical_every_rung(self):
        delta = build_index("par-delta")
        full = build_index("par-full", delta=False)
        host = build_index("par-host", mesh=False)
        try:
            for idx in (delta, full, host):
                self._run_interleaved(idx)
            bodies = [
                {"query": {"match": {"body": "common"}}, "size": 30},
                {"query": {"match": {"body": "w1 w2"}}, "size": 20,
                 "aggs": {"tags": {"terms": {"field": "tag"}},
                          "hist": {"histogram": {"field": "n",
                                                 "interval": 5}},
                          "st": {"stats": {"field": "n"}}}},
            ]
            for body in bodies:
                got = delta.search(dict(body))
                oracle = full.search(dict(body))
                want_host = host.search(dict(body))
                assert got["_plane"] == "mesh_pallas", got["_plane"]
                # delta index actually served deltas, oracle rebuilt
                assert_parity(got, oracle)
                assert_parity(got, want_host)
            assert delta._mesh_search.delta_restage_total >= 1
            assert delta._mesh_search.tombstone_update_total >= 1
            assert full._mesh_search.delta_restage_total == 0
        finally:
            delta.close()
            full.close()
            host.close()

    def test_knn_byte_identical_after_append_and_delete(self):
        rng = np.random.RandomState(7)
        vecs = rng.randn(72, DIMS).astype(np.float32)

        def fill(idx, lo, hi):
            for d in range(lo, hi):
                idx.index_doc(str(d), {"emb": vecs[d].tolist(),
                                       "body": f"t{d % 3}"})
            idx.refresh()

        delta = build_index("knnpar-delta", mapping=KNN_MAPPING)
        full = build_index("knnpar-full", delta=False,
                           mapping=KNN_MAPPING)
        try:
            body = {"knn": {"field": "emb",
                            "query_vector": vecs[0].tolist(), "k": 10,
                            "num_candidates": 50}, "size": 10}
            for idx in (delta, full):
                fill(idx, 0, 48)
                idx.search(dict(body))  # stage the kNN plane
                fill(idx, 48, 64)
                idx.delete_doc("9")
                idx.refresh()
                fill(idx, 64, 72)
            got = delta.search(dict(body))
            want = full.search(dict(body))
            assert got["hits"]["total"] == want["hits"]["total"]
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            for g, w in zip(got["hits"]["hits"], want["hits"]["hits"]):
                assert g["_score"] == w["_score"], (g, w)
            assert "9" not in [h["_id"] for h in got["hits"]["hits"]]
        finally:
            delta.close()
            full.close()


class TestCompaction:
    def test_compact_merges_sparse_slots_and_releases_old_generation(self):
        # threshold 0 suppresses the post-delta auto-trigger so the
        # pass runs exactly once, here, deterministically
        idx = build_index("cp-run", compact=0.0)
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            scope0 = ms._executor.scope
            # delete enough to cross the density threshold
            for d in range(0, 12):
                idx.delete_doc(str(d))
            idx.refresh()
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            # any shard with ≥1 tombstone is "dense" at this threshold,
            # so the pass expunges every delete (hash routing spreads
            # the 12 deletes unevenly across the 3 shards)
            idx.set_cluster_overrides(Settings(
                {"index.staging.compact.threshold": 0.01}))
            out = idx.compact_now()
            assert out["ran"] is True, out
            assert out["merged_shards"], out  # deletes expunged
            assert out["restaged"] is True
            assert ms.compaction_runs_total == 1
            assert ms._executor.scope != scope0  # fresh generation
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 48})
            assert r["hits"]["total"] == 36
            stats = ms.staging_slot_stats()
            assert all(s["tombstone_density"] == 0.0
                       for s in stats["slots"]), stats
        finally:
            idx.close()

    def test_compaction_single_flight_and_drain_abort(self):
        idx = build_index("cp-drain", compact=0.2)
        try:
            _fill(idx, 0, 24)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            idx.admission.begin_drain()
            out = idx.compact_now()
            assert out == {"ran": False, "reason": "draining"}
            assert idx.maybe_compact_async() is False  # drain wins
            # single-flight: a held lock means "already running"
            with idx._compact_lock:
                assert idx.compact_now() == {
                    "ran": False, "reason": "already_running"}
        finally:
            idx.close()

    def test_compact_noop_below_threshold(self):
        idx = build_index("cp-noop", compact=0.9)
        try:
            _fill(idx, 0, 24)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            assert idx.maybe_compact_async() is False
        finally:
            idx.close()


class TestLedgerExactness:
    def test_leak_free_across_append_tombstone_compact_cycle(self):
        acc = memory_accountant()
        base = acc.stats()["staged_bytes_total"]
        idx = build_index("lg-cycle", compact=0.2)
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            _fill(idx, 48, 60)  # delta append
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            for d in range(20):
                idx.delete_doc(str(d))  # tombstone, then compaction
            idx.refresh()
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            idx.compact_now()
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            assert acc.stats("lg-cycle")["staged_bytes_total"] > 0
        finally:
            idx.close()
        # every generation the cycle created was released: the node
        # ledger is byte-exactly back at its pre-index baseline
        assert acc.stats()["staged_bytes_total"] == base
        assert acc.stats("lg-cycle")["staged_bytes_total"] == 0

    def test_mid_delta_fault_restores_exact_pre_attempt_ledger(self):
        acc = memory_accountant()
        idx = build_index("lg-fault")
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search
            scope0 = ms._executor.scope

            def mesh_rows():
                # the mesh generations' ledger rows only: the host rung
                # legitimately stages per-segment tables while the mesh
                # staging is benched — those are NOT attempt residue
                return sorted(
                    (r["segment"], r["kind"], r["bytes"], r["tables"])
                    for r in acc.table()
                    if r["index"] == "lg-fault"
                    and r["segment"].startswith("mesh#"))

            snapshot = mesh_rows()
            # deterministic fault at the delta-append staging boundary:
            # the attempt must register NOTHING (register-then-commit)
            StagingFailScheme(kinds=["mesh_slot_tables"],
                              transient=False, times=1,
                              indices=["lg-fault"]).install()
            _fill(idx, 48, 60)
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            # served from the host rung (staging benched), still correct
            assert r["hits"]["total"] == 60
            assert r["_plane"] != "mesh_pallas"
            assert mesh_rows() == snapshot
            # the OLD generation survived the failed attempt untouched
            assert ms._executor is not None
            assert ms._executor.scope == scope0
        finally:
            idx.close()

    def test_mid_tombstone_fault_restores_exact_pre_attempt_ledger(self):
        acc = memory_accountant()
        idx = build_index("lg-tfault")
        try:
            _fill(idx, 0, 48)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            ms = idx._mesh_search

            def mesh_rows():
                return sorted(
                    (r["segment"], r["kind"], r["bytes"], r["tables"])
                    for r in acc.table()
                    if r["index"] == "lg-tfault"
                    and r["segment"].startswith("mesh#"))

            snapshot = mesh_rows()
            StagingFailScheme(kinds=["live_mask"],
                              transient=False, times=1,
                              indices=["lg-tfault"]).install()
            idx.delete_doc("3")
            idx.refresh()
            r = idx.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            assert r["hits"]["total"] == 47  # host rung serves truth
            assert mesh_rows() == snapshot
            assert ms.tombstone_update_total == 0
        finally:
            idx.close()


class TestSettingsPlumbing:
    def test_counters_exported_in_search_stats(self):
        idx = build_index("st-exp")
        try:
            _fill(idx, 0, 24)
            idx.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            planes = idx.search_stats()["planes"]
            for key in ("delta_restage_total", "tombstone_update_total",
                        "compaction_runs_total"):
                assert key in planes, planes.keys()
        finally:
            idx.close()

    def test_cluster_override_and_create_seeding(self):
        from elasticsearch_tpu.node import Node

        node = Node(Settings.EMPTY)
        try:
            node.create_index("ovr-a", {"settings": {
                "index": {"number_of_shards": 1}}})
            svc_a = node.indices["ovr-a"]

            def delta_enabled(svc):
                return svc.live.get_bool("index.staging.delta.enabled",
                                         True)

            assert delta_enabled(svc_a) is True
            node.put_cluster_settings({"persistent": {
                "index.staging.delta.enabled": False,
                "index.staging.compact.threshold": 0.5}})
            assert delta_enabled(svc_a) is False
            assert svc_a._compact_threshold() == 0.5
            # an index created AFTER the commit honors the live value
            node.create_index("ovr-b", {"settings": {
                "index": {"number_of_shards": 1}}})
            svc_b = node.indices["ovr-b"]
            assert delta_enabled(svc_b) is False
            assert svc_b._compact_threshold() == 0.5
            # clearing hands control back to each index's own setting
            node.put_cluster_settings({"persistent": {
                "index.staging.delta.enabled": None,
                "index.staging.compact.threshold": None}})
            assert delta_enabled(svc_a) is True
            assert svc_a._compact_threshold() == 0.25  # default
        finally:
            node.close()

    def test_cat_staging_shows_slot_columns(self):
        from elasticsearch_tpu.client import Client
        from elasticsearch_tpu.node import Node

        node = Node(Settings.EMPTY)
        client = Client(node)
        try:
            node.create_index("cat-d", {"settings": {"index": {
                "number_of_shards": 2, "refresh_interval": -1,
                "search": {"mesh": True}}},
                "mappings": MAPPING})
            svc = node.indices["cat-d"]
            for d in range(24):
                svc.index_doc(str(d), _doc(d))
            svc.refresh()
            svc.search({"query": {"match": {"body": "common"}},
                        "size": 5})
            status, out = client.perform("GET", "/_cat/staging",
                                         params={"v": "true"})
            assert status == 200
            header = out.splitlines()[0]
            assert "free_slots_per_dev" in header
            assert "tombstone_density" in header
            ms = svc._mesh_search
            if ms is not None and ms._executor is not None:
                assert "/slot0" in out  # per-slot summary rows
        finally:
            node.close()
