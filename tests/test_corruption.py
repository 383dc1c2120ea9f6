"""Data-integrity matrix (ISSUE 16): corruption-marker lifecycle,
at-rest detection for every corruption kind, device-drift detection for
every staged table kind, the PR-4 partial contract on a quarantined
query path, the scrub-interval knob (dynamic + cluster override), the
snapshot digest satellites, the operator surfaces (_cat/shards,
allocation explain, _stats), and the cluster heal outcomes — corrupt
replica, corrupt primary, last copy retained RED."""

import os
import time

import numpy as np
import pytest

from elasticsearch_tpu.client import Client
from elasticsearch_tpu.cluster.multinode import ClusterClient, ClusterNode
from elasticsearch_tpu.cluster.state import ShardRoutingState
from elasticsearch_tpu.common.errors import SearchPhaseExecutionException
from elasticsearch_tpu.common.integrity import integrity_service
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.index.store import (
    MARKER_PREFIX,
    CorruptIndexException,
    Store,
)
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.testing.disruption import StoreCorruptionScheme
from elasticsearch_tpu.transport.local import TransportHub

MAPPING = {"properties": {"body": {"type": "text"},
                          "n": {"type": "integer"}}}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def mk_service(tmp_path, name="cx", shards=1, docs=20):
    svc = IndexService(
        name,
        Settings({"index.number_of_shards": shards,
                  "index.search.mesh": False}),
        mapping=MAPPING, data_path=str(tmp_path / name))
    for i in range(docs):
        svc.index_doc(str(i), {"body": f"alpha common doc{i}", "n": i})
    svc.refresh()
    svc.flush()
    return svc


def _wait(predicate, attempts=200, delay=0.05):
    for _ in range(attempts):
        if predicate():
            return True
        time.sleep(delay)
    return predicate()


# ---------------------------------------------------------------------------
# Marker lifecycle (Store.markStoreCorrupted parity)
# ---------------------------------------------------------------------------


class TestMarkerLifecycle:
    def test_written_once_first_cause_wins(self, tmp_path):
        store = Store(str(tmp_path / "s"))
        first = store.mark_corrupted("cause A", site="load")
        second = store.mark_corrupted("cause B", site="query")
        assert second["marker"] == first["marker"]
        markers = store.corruption_markers()
        assert len(markers) == 1
        assert markers[0]["reason"] == "cause A"
        assert markers[0]["site"] == "load"
        assert markers[0]["marker"].startswith(MARKER_PREFIX)

    def test_marker_blocks_load_and_read(self, tmp_path):
        svc = mk_service(tmp_path, "mb", docs=8)
        try:
            store = svc.shards[0].engine.store
            seg_names = (store.read_commit() or {}).get("segments", [])
            assert seg_names, "flush must have committed a segment"
            store.mark_corrupted("bit rot", site="scrub")
            with pytest.raises(CorruptIndexException):
                store.load_segments()
            with pytest.raises(CorruptIndexException):
                store.read_segment(seg_names[0])
        finally:
            svc.close()

    def test_torn_marker_still_counts(self, tmp_path):
        store = Store(str(tmp_path / "torn"))
        torn = os.path.join(store.directory, MARKER_PREFIX + "torn.json")
        with open(torn, "w", encoding="utf-8") as f:
            f.write('{"reason": "trunc')  # unparseable: still a marker
        assert store.is_corrupted()
        markers = store.corruption_markers()
        assert markers[0]["marker"] == MARKER_PREFIX + "torn.json"
        with pytest.raises(CorruptIndexException):
            store._check_not_corrupted()

    def test_clear_reopens_the_store(self, tmp_path):
        svc = mk_service(tmp_path, "cl", docs=8)
        try:
            store = svc.shards[0].engine.store
            store.mark_corrupted("transient", site="load")
            assert store.is_corrupted()
            assert store.clear_corruption_markers() == 1
            assert not store.is_corrupted()
            assert store.load_segments()  # legal again after clear
        finally:
            svc.close()

    def test_marker_survives_later_commits(self, tmp_path):
        """Commit GC only prunes segment DIRECTORIES — the marker file
        sitting next to them must survive every later commit cycle."""
        svc = mk_service(tmp_path, "gc", docs=8)
        try:
            store = svc.shards[0].engine.store
            marker = store.mark_corrupted("at-rest rot", site="scrub")
            for i in range(8, 16):
                svc.index_doc(str(i), {"body": f"beta {i}", "n": i})
            svc.refresh()
            svc.flush()
            markers = store.corruption_markers()
            assert [m["marker"] for m in markers] == [marker["marker"]]
        finally:
            svc.close()

    def test_unquarantine_is_the_only_exit(self, tmp_path):
        svc = mk_service(tmp_path, "uq", docs=8)
        try:
            before = integrity_service().stats()
            svc._quarantine_shard(0, CorruptIndexException("injected"),
                                  site="query")
            shard = svc.shards[0]
            assert shard.store_corrupted
            assert shard.engine.store.is_corrupted()
            svc.unquarantine_shard(0)
            assert not shard.store_corrupted
            assert not shard.engine.store.is_corrupted()
            after = integrity_service().stats()
            assert after["markers_written_total"] \
                == before["markers_written_total"] + 1
            assert after["markers_cleared_total"] \
                == before["markers_cleared_total"] + 1
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# Background scrubber: at-rest detection, one kind at a time
# ---------------------------------------------------------------------------


class TestScrubAtRest:
    @pytest.mark.parametrize(
        "kind", ["bitflip", "truncate", "torn_checksums",
                 "missing_checksums"])
    def test_each_kind_detected_and_quarantined(self, tmp_path, kind):
        svc = mk_service(tmp_path, f"ar_{kind}"[:14], shards=2, docs=24)
        try:
            store = svc.shards[0].engine.store
            assert (store.read_commit() or {}).get("segments")
            StoreCorruptionScheme(kind, seed=11).corrupt_store(store)
            before = integrity_service().stats()
            rep = svc.scrub_now()
            assert rep["checksum_failures"] >= 1
            assert svc.shards[0].store_corrupted
            assert store.is_corrupted()
            after = integrity_service().stats()
            assert (after["corruption_detected_by_site"].get("scrub", 0)
                    - before["corruption_detected_by_site"]
                    .get("scrub", 0)) >= 1
            assert after["markers_written_total"] \
                > before["markers_written_total"]
            # a quarantined copy pins no HBM (PR-9 ledger exactness)
            assert all(not getattr(s, "_device", None)
                       for s in svc.shards[0].engine.segments)
            # the next pass skips the quarantined copy: heal, don't
            # re-verify — detection is counted exactly once
            rep2 = svc.scrub_now()
            assert rep2["checksum_failures"] == 0
            final = integrity_service().stats()
            assert final["corruption_detected_total"] \
                == after["corruption_detected_total"]
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# Background scrubber: device drift, one staged table kind at a time
# ---------------------------------------------------------------------------


class TestScrubDeviceDrift:
    @pytest.mark.parametrize("key", ["block_docs", "block_tfs", "norms"])
    def test_each_staged_table_kind(self, tmp_path, key):
        import jax.numpy as jnp

        svc = mk_service(tmp_path, f"dr_{key[:7]}", docs=16)
        try:
            probe = {"query": {"match": {"body": "alpha"}}}
            want = svc._search_uncached(dict(probe), skip_mesh=True)
            want_hits = [(h["_id"], h["_score"])
                         for h in want["hits"]["hits"]]
            assert want_hits
            seg = next((s for sh in svc.shards.values()
                        for s in sh.engine.segments
                        if getattr(s, "_device", None)), None)
            assert seg is not None, "host path did not stage tables"
            drifted = np.asarray(seg._device[key]).copy()
            drifted.flat[0] += 1
            seg._device[key] = jnp.asarray(drifted)
            before = integrity_service().stats()
            rep = svc.scrub_now()
            assert rep["drift"] >= 1
            after = integrity_service().stats()
            assert after["scrub_drift_total"] \
                - before["scrub_drift_total"] >= 1
            assert after["scrub_runs_total"] > before["scrub_runs_total"]
            assert after["scrub_bytes_verified_total"] \
                > before["scrub_bytes_verified_total"]
            # drift is a staging fault, not store corruption: no marker,
            # no detected-total bump, the copy keeps serving
            assert after["corruption_detected_total"] \
                == before["corruption_detected_total"]
            assert not svc.shards[0].store_corrupted
            assert not svc.shards[0].engine.store.is_corrupted()
            # the staging was invalidated + the restage is classified
            assert seg.stage_reason_initial == "scrub"
            assert not seg._device
            got = svc._search_uncached(dict(probe), skip_mesh=True)
            got_hits = [(h["_id"], h["_score"])
                        for h in got["hits"]["hits"]]
            assert got_hits == want_hits  # host truth re-adopted
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# Query path: the PR-4 partial contract under quarantine
# ---------------------------------------------------------------------------


def _always_corrupt(*a, **k):
    raise CorruptIndexException("injected: torn posting block")


class TestQueryPartialContract:
    def test_corrupt_shard_becomes_failures_entry(self, tmp_path):
        svc = mk_service(tmp_path, "qp", shards=2, docs=24)
        try:
            svc.shards[0].searcher.query = _always_corrupt
            before = integrity_service().stats()
            r = svc.search({"query": {"match": {"body": "alpha"}}})
            assert r["_shards"]["failed"] >= 1
            assert r["_shards"]["successful"] >= 1
            assert r["hits"]["hits"]  # the healthy shard still answers
            reasons = str(r["_shards"]["failures"]).lower()
            assert "corrupt" in reasons
            # first detection quarantined the copy: marker, site=query
            assert svc.shards[0].store_corrupted
            assert svc.shards[0].engine.store.is_corrupted()
            after = integrity_service().stats()
            assert after["corruption_detected_total"] \
                == before["corruption_detected_total"] + 1
            assert (after["corruption_detected_by_site"].get("query", 0)
                    - before["corruption_detected_by_site"]
                    .get("query", 0)) == 1
            # repeated searches fail fast on the flag: still partial,
            # never recounted, never a re-read of the marked bytes
            r2 = svc.search({"query": {"match": {"body": "alpha"}}})
            assert r2["_shards"]["failed"] >= 1
            final = integrity_service().stats()
            assert final["corruption_detected_total"] \
                == after["corruption_detected_total"]
        finally:
            svc.close()

    def test_all_copies_failed_is_search_phase_exception(self, tmp_path):
        svc = mk_service(tmp_path, "qp1", shards=1, docs=8)
        try:
            svc.shards[0].searcher.query = _always_corrupt
            with pytest.raises(SearchPhaseExecutionException):
                svc.search({"query": {"match": {"body": "alpha"}}})
        finally:
            svc.close()

    def test_allow_partial_false_raises(self, tmp_path):
        svc = mk_service(tmp_path, "qp2", shards=2, docs=24)
        try:
            svc.shards[0].searcher.query = _always_corrupt
            with pytest.raises(SearchPhaseExecutionException):
                svc.search({"query": {"match": {"body": "alpha"}},
                            "allow_partial_search_results": False})
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# index.scrub.interval: off by default, dynamic, cluster override wins
# ---------------------------------------------------------------------------


class TestScrubIntervalKnob:
    def test_dynamic_update_and_cluster_override(self):
        node = Node(Settings.EMPTY)
        try:
            node.create_index("si", {"settings": {"number_of_shards": 1},
                                     "mappings": MAPPING})
            svc = node.indices["si"]
            assert svc._scrub_effective_interval() is None  # off
            node.update_index_settings(
                "si", {"index.scrub.interval": "30s"})
            assert svc._scrub_effective_interval() == 30.0
            # an explicit cluster value overrides the index setting
            node.put_cluster_settings(
                {"persistent": {"index.scrub.interval": "5s"}})
            assert svc._scrub_effective_interval() == 5.0
            # clearing hands control back to the index setting
            node.put_cluster_settings(
                {"persistent": {"index.scrub.interval": None}})
            assert svc._scrub_effective_interval() == 30.0
        finally:
            node.close()


# ---------------------------------------------------------------------------
# Snapshot satellites: digests on create, _status + restore verification
# ---------------------------------------------------------------------------


def _corrupt_snapshot_blob(repo, snapshot, index):
    """Flip one bit in the first digest-covered blob of one index."""
    m = repo.read_manifest(snapshot)
    sid, sinfo = next(iter(m["indices"][index]["shards"].items()))
    rel = next(iter(sinfo["digests"]))
    full = os.path.join(repo.snapshot_path(snapshot),
                        "indices", index, str(sid), rel)
    with open(full, "r+b") as f:
        data = bytearray(f.read())
        data[0] ^= 0x01
        f.seek(0)
        f.write(data)


class TestSnapshotIntegrity:
    @pytest.fixture()
    def node(self, tmp_path):
        n = Node(Settings.EMPTY)
        for name in ("snap_a", "snap_b"):
            n.create_index(name, {"settings": {"number_of_shards": 1},
                                  "mappings": MAPPING})
            for i in range(8):
                n.index_doc(name, str(i), {"body": f"alpha {i}", "n": i})
            n.indices[name].refresh()
        n.snapshots.put_repository(
            "ri", {"type": "fs",
                   "settings": {"location": str(tmp_path / "repo")}})
        yield n
        n.close()

    def test_create_records_digests_status_verifies(self, node):
        node.snapshots.create_snapshot("ri", "s1")
        m = node.snapshots._repo("ri").read_manifest("s1")
        digests = m["indices"]["snap_a"]["shards"]["0"]["digests"]
        assert digests and all(len(d) == 64 for d in digests.values())
        st = node.snapshots.snapshot_status("ri", "s1")["snapshots"][0]
        ver = st["indices"]["snap_a"]["0"]["verification"]
        assert ver["verified"]
        assert ver["files_verified"] == ver["files_total"] > 0

    def test_status_flags_corrupt_blob(self, node):
        node.snapshots.create_snapshot("ri", "s2")
        _corrupt_snapshot_blob(node.snapshots._repo("ri"), "s2", "snap_a")
        st = node.snapshots.snapshot_status("ri", "s2")["snapshots"][0]
        ver = st["indices"]["snap_a"]["0"]["verification"]
        assert not ver["verified"]
        assert ver["files_verified"] < ver["files_total"]

    def test_restore_fails_only_the_corrupt_index(self, node):
        node.snapshots.create_snapshot("ri", "s3")
        _corrupt_snapshot_blob(node.snapshots._repo("ri"), "s3", "snap_a")
        node.delete_index("snap_a")
        node.delete_index("snap_b")
        before = integrity_service().stats()
        r = node.snapshots.restore_snapshot("ri", "s3")
        snap = r["snapshot"]
        assert snap["indices"] == ["snap_b"]
        assert snap["shards"]["failed"] == 1
        fail = snap["failures"][0]
        assert fail["index"] == "snap_a"
        assert fail["type"] == "corrupted_snapshot_exception"
        # the corrupt index was never half-created; the healthy one is up
        assert "snap_a" not in node.indices
        assert "snap_b" in node.indices
        assert node.indices["snap_b"].search(
            {"query": {"match_all": {}}})["hits"]["total"] == 8
        after = integrity_service().stats()
        assert (after["corruption_detected_by_site"].get("restore", 0)
                - before["corruption_detected_by_site"]
                .get("restore", 0)) >= 1

    def test_verify_repository_rest(self, node):
        client = Client(node)
        status, out = client.perform("POST", "/_snapshot/ri/_verify")
        assert status == 200
        assert out["nodes"]


# ---------------------------------------------------------------------------
# Operator surfaces: _cat/shards, allocation explain, _stats integrity
# ---------------------------------------------------------------------------


class TestOperatorSurfaces:
    @pytest.fixture()
    def noderef(self):
        n = Node(Settings.EMPTY)
        n.create_index("rx", {"settings": {"number_of_shards": 2},
                              "mappings": MAPPING})
        for i in range(10):
            n.index_doc("rx", str(i), {"body": f"alpha {i}", "n": i})
        n.indices["rx"].refresh()
        n.indices["rx"].flush()
        yield n
        n.close()

    def test_cat_shards_integrity_column(self, noderef):
        client = Client(noderef)
        status, text = client.perform("GET", "/_cat/shards")
        assert status == 200
        assert MARKER_PREFIX not in text  # healthy: "-" in the column
        noderef.indices["rx"].shards[0].engine.store.mark_corrupted(
            "bit rot", site="scrub")
        status, text = client.perform("GET", "/_cat/shards")
        assert MARKER_PREFIX in text

    def test_allocation_explain_surfaces_markers(self, noderef):
        client = Client(noderef)
        status, out = client.perform("GET", "/_cluster/allocation/explain")
        assert out["can_allocate"] == "yes"
        noderef.indices["rx"].shards[1].engine.store.mark_corrupted(
            "torn checksums", site="load")
        status, out = client.perform("GET", "/_cluster/allocation/explain")
        assert out["can_allocate"] == "no"
        copies = out["corrupted_copies"]
        assert copies[0]["index"] == "rx"
        assert copies[0]["shard"] == 1
        assert copies[0]["site"] == "load"
        assert copies[0]["marker"].startswith(MARKER_PREFIX)

    def test_stats_integrity_block(self, noderef):
        block = noderef.indices["rx"].search_stats()["integrity"]
        for key in ("corruption_detected_total",
                    "corruption_detected_by_site", "scrub_runs_total",
                    "scrub_bytes_verified_total", "scrub_drift_total",
                    "markers_written_total", "markers_cleared_total",
                    "marker_events", "events_dropped"):
            assert key in block


# ---------------------------------------------------------------------------
# Cluster heal outcomes: corrupt replica / corrupt primary / last copy
# ---------------------------------------------------------------------------


class TestClusterHealOutcomes:
    def _cluster(self, tmp_path, n=2):
        hub = TransportHub()
        nodes = [ClusterNode(f"cn-{i}", hub,
                             data_path=str(tmp_path / f"cn{i}"))
                 for i in range(n)]
        nodes[0].bootstrap_cluster()
        for nd in nodes[1:]:
            nd.join("cn-0")
        return hub, nodes

    @staticmethod
    def _seed(client, index, docs=10):
        for i in range(docs):
            client.index(index, str(i), {"body": f"alpha {i}", "n": i})
        client.refresh(index)

    @staticmethod
    def _started(master, index, want):
        copies = master.routing.get(index, {}).get(0, [])
        return (len(copies) == want
                and all(c.state == ShardRoutingState.STARTED
                        for c in copies))

    @staticmethod
    def _node_of(nodes, node_id):
        return next(n for n in nodes if n.node_id == node_id)

    def _healed(self, master, nodes, index):
        copies = master.routing.get(index, {}).get(0, [])
        if len(copies) != 2 or any(
                c.state != ShardRoutingState.STARTED for c in copies):
            return False
        for copy in copies:
            shard = self._node_of(nodes, copy.node_id).shards.get(
                (index, 0))
            if shard is None or getattr(shard, "store_corrupted", False) \
                    or shard.engine.store.is_corrupted():
                return False
        return True

    def test_corrupt_replica_re_recovers_from_primary(self, tmp_path):
        hub, nodes = self._cluster(tmp_path)
        try:
            master = nodes[0]
            master.create_index("hr", {"index": {
                "number_of_shards": 1, "number_of_replicas": 1}})
            client = ClusterClient(nodes[0])
            self._seed(client, "hr")
            assert _wait(lambda: self._started(master, "hr", 2))
            replica = next(c for c in master.routing["hr"][0]
                           if not c.primary)
            rnode = self._node_of(nodes, replica.node_id)
            shard = rnode.shards[("hr", 0)]
            shard.searcher.query = _always_corrupt
            before = integrity_service().stats()
            with pytest.raises(CorruptIndexException):
                rnode._on_query({"index": "hr", "shard": 0,
                                 "body": {"query": {"match_all": {}}},
                                 "k": 10}, "test")
            after = integrity_service().stats()
            assert (after["corruption_detected_by_site"].get("query", 0)
                    - before["corruption_detected_by_site"]
                    .get("query", 0)) >= 1
            assert after["markers_written_total"] \
                > before["markers_written_total"]
            # the master removes the corrupt copy; a fresh replica
            # re-recovers from the primary and clears the marker
            assert _wait(lambda: self._healed(master, nodes, "hr"))
            final = integrity_service().stats()
            assert final["markers_cleared_total"] \
                > before["markers_cleared_total"]
            r = client.search("hr", {"query": {"match_all": {}},
                                     "size": 20})
            assert r["_shards"]["failed"] == 0
            assert r["hits"]["total"] == 10
        finally:
            for nd in nodes:
                nd.close()

    def test_corrupt_primary_fails_over_then_rebuilds(self, tmp_path):
        hub, nodes = self._cluster(tmp_path)
        try:
            master = nodes[0]
            master.create_index("hp", {"index": {
                "number_of_shards": 1, "number_of_replicas": 1}})
            client = ClusterClient(nodes[0])
            self._seed(client, "hp")
            assert _wait(lambda: self._started(master, "hp", 2))
            old_primary = next(c for c in master.routing["hp"][0]
                               if c.primary)
            pnode = self._node_of(nodes, old_primary.node_id)
            pnode.shards[("hp", 0)].searcher.query = _always_corrupt
            with pytest.raises(CorruptIndexException):
                pnode._on_query({"index": "hp", "shard": 0,
                                 "body": {"query": {"match_all": {}}},
                                 "k": 10}, "test")

            def failed_over():
                if not self._healed(master, nodes, "hp"):
                    return False
                newp = next(c for c in master.routing["hp"][0]
                            if c.primary)
                return newp.node_id != old_primary.node_id

            assert _wait(failed_over)
            r = client.search("hp", {"query": {"match_all": {}},
                                     "size": 20})
            assert r["_shards"]["failed"] == 0
            assert r["hits"]["total"] == 10
        finally:
            for nd in nodes:
                nd.close()

    def test_last_copy_retained_red_never_resurrected(self, tmp_path):
        hub, nodes = self._cluster(tmp_path)
        try:
            master = nodes[0]
            master.create_index("lc", {"index": {
                "number_of_shards": 1, "number_of_replicas": 0}})
            client = ClusterClient(nodes[0])
            self._seed(client, "lc", docs=6)
            assert _wait(lambda: self._started(master, "lc", 1))
            copy = master.routing["lc"][0][0]
            pnode = self._node_of(nodes, copy.node_id)
            shard = pnode.shards[("lc", 0)]
            shard.searcher.query = _always_corrupt
            # degraded 200 (PR-4 contract), never a raw 500
            r = client.search("lc", {"query": {"match_all": {}}})
            assert r["_shards"]["failed"] == 1
            assert r["hits"]["hits"] == []
            # the last copy is retained quarantined: RED, still routed
            # to its node, never replaced by a fresh empty primary
            assert _wait(lambda: ("lc", 0) in master.corrupt_retained)
            assert shard.engine.store.is_corrupted()
            time.sleep(0.3)  # give reroute passes a chance to misbehave
            copies = master.routing["lc"][0]
            assert len(copies) == 1
            assert copies[0].node_id == pnode.node_id
            # repeat: still a loud partial failure, no silent resurrect
            r2 = client.search("lc", {"query": {"match_all": {}}})
            assert r2["_shards"]["failed"] == 1
            assert r2["hits"]["hits"] == []
        finally:
            for nd in nodes:
                nd.close()
