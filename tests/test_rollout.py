"""Zero-downtime rollout (ISSUE 14): graceful drain, synced-flush
warm restart, graceful cluster leave, persistent compile cache + AOT
variant warming, and the rolling-restart soak.

Contracts under test (docs/RESILIENCE.md "Rollout & drain"):
- a draining index stops admitting with a clean 503 + Retry-After and
  sheds its queue with exact counters (no silent drops); in-flight
  searches finish; undrain resumes service;
- Node.close() shuts admission down FIRST, drains in-flight searches,
  then flushes with a synced-flush marker and closes indices — queued
  work is never stranded;
- warm restart over a persistent data path is ops-free (zero translog
  ops replayed) and byte-identical;
- ClusterNode.close() announces a graceful leave (replicas promote on
  the leave publish, not the FD timeout) and deregisters from
  transport BEFORE closing shards;
- the variant registry + warming replay eliminate query-path first
  compiles after a warmed restart (compile_cache counters prove it).
"""

import os
import threading
import time

import pytest

from elasticsearch_tpu.common.errors import NodeDrainingException
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.testing.chaos import RollingRestartSoak


def _mk_index(name="drainidx", **settings):
    base = {"index.number_of_shards": 2, "index.refresh_interval": -1}
    base.update(settings)
    return IndexService(name, Settings(base), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})


class TestAdmissionDrain:
    def test_drain_rejects_new_and_sheds_queued_with_exact_counters(self):
        svc = _mk_index("drain1", **{
            "search.admission.max_concurrent": 1,
            "search.queue.size": 8})
        adm = svc.admission
        try:
            for d in range(6):
                svc.index_doc(str(d), {"body": f"w{d % 2} common"})
            svc.refresh()
            # occupy the single slot so followers queue
            hold = adm.acquire(tenant="holder")
            results = []

            def queued():
                try:
                    token = adm.acquire(tenant="queued")
                    adm.release(token)
                    results.append("admitted")
                except NodeDrainingException as e:
                    results.append(("draining", e.retry_after_s))
                except Exception as e:  # noqa: BLE001
                    results.append(type(e).__name__)

            t = threading.Thread(target=queued)
            t.start()
            for _ in range(200):
                if adm._queued_total:
                    break
                time.sleep(0.005)
            assert adm._queued_total == 1
            base = adm.stats_dict()
            shed = adm.begin_drain()
            t.join(5)
            # the queued entry was shed with the clean 503 + Retry-After
            assert shed == 1
            assert results and results[0][0] == "draining"
            assert results[0][1] > 0
            # new arrivals get the same contract (from a fresh thread —
            # the holder's own thread would take the nested-query bypass)
            late: list = []

            def late_arrival():
                try:
                    adm.acquire(tenant="late")
                    late.append("admitted")
                except NodeDrainingException:
                    late.append("draining")

            t2 = threading.Thread(target=late_arrival)
            t2.start()
            t2.join(5)
            assert late == ["draining"]
            stats = adm.stats_dict()
            assert stats["draining"] is True
            assert stats["drain_rejected_total"] == 2
            # the exact partition admitted+rejected+expired survives
            assert (stats["rejected_total"] - base["rejected_total"]) == 2
            # the in-flight holder finishes and the drain completes
            assert adm.await_drained(0.05) is False  # holder still in
            adm.release(hold)
            assert adm.await_drained(5) is True
            # undrain resumes service
            adm.end_drain()
            token = adm.acquire(tenant="resumed")
            adm.release(token)
            assert adm.stats_dict()["draining"] is False
        finally:
            svc.close()

    def test_draining_search_returns_503_with_retry_after(self):
        svc = _mk_index("drain2")
        try:
            for d in range(4):
                svc.index_doc(str(d), {"body": "w0 common"})
            svc.refresh()
            svc.admission.begin_drain()
            with pytest.raises(NodeDrainingException) as ei:
                svc.search({"query": {"match": {"body": "common"}}})
            assert ei.value.status_code == 503
            assert ei.value.retry_after_s > 0
            svc.admission.end_drain()
            r = svc.search({"query": {"match": {"body": "common"}}})
            assert r["hits"]["total"] == 4
        finally:
            svc.close()

    def test_drain_rejects_even_with_admission_disabled(self):
        # the kill switch (search.admission.enabled=false) must not
        # void the drain contract: new arrivals still get the clean 503
        svc = _mk_index("drain4", **{"search.admission.enabled": False})
        try:
            for d in range(4):
                svc.index_doc(str(d), {"body": "w0 common"})
            svc.refresh()
            r = svc.search({"query": {"match": {"body": "common"}}})
            assert r["hits"]["total"] == 4  # admitted via the bypass
            svc.admission.begin_drain()
            with pytest.raises(NodeDrainingException):
                svc.search({"query": {"match": {"body": "common"}}})
        finally:
            svc.close()

    def test_index_created_while_node_drains_joins_the_drain(self):
        from elasticsearch_tpu.node import Node

        node = Node(Settings.EMPTY)
        try:
            node.create_index("pre", {"settings": {
                "number_of_shards": 1, "index.refresh_interval": -1}})
            node.drain()
            # a straggling write auto-creates an index mid-drain: it
            # must refuse searches like every other index on the node
            node.index_doc("straggler", "1", {"f": 1})
            assert node.indices["straggler"].admission.draining
            with pytest.raises(NodeDrainingException):
                node.search("straggler", {"query": {"match_all": {}}})
            node.undrain()
        finally:
            node.close()

    def test_nested_queries_of_admitted_search_survive_drain(self):
        # an in-flight search's nested re-entry (collapse expansion,
        # hybrid sides) must not be rejected by a drain that began
        # after the outer query was admitted
        svc = _mk_index("drain3")
        try:
            for d in range(4):
                svc.index_doc(str(d), {"body": "w0 common"})
            svc.refresh()
            adm = svc.admission
            outer = adm.acquire(tenant="outer")
            adm.begin_drain()
            # the nested-guard contextvar is set by the outer token:
            # a nested acquire must return the noop token, not raise
            nested = adm.acquire(tenant="outer")
            assert nested.noop
            adm.release(nested)
            adm.release(outer)
            assert adm.await_drained(5) is True
        finally:
            svc.close()


class TestNodeDrainAndWarmRestart:
    def test_node_close_does_not_strand_inflight_search(self, tmp_path):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.testing import disruption as dis

        node = Node(Settings.EMPTY, data_path=str(tmp_path / "n1"))
        node.create_index("inflight", {"settings": {
            "index.number_of_shards": 2, "index.refresh_interval": -1}})
        for d in range(6):
            node.index_doc("inflight", str(d), {"body": "w0 common"})
        node.indices["inflight"].refresh()
        scheme = dis.SearchDelayScheme(0.05, indices=["inflight"]).install()
        out = {}

        def slow_search():
            try:
                out["resp"] = node.search(
                    "inflight", {"query": {"match": {"body": "common"}}})
            except Exception as e:  # noqa: BLE001
                out["error"] = e

        t = threading.Thread(target=slow_search)
        try:
            t.start()
            time.sleep(0.02)  # the search is admitted and executing
            node.close()  # drains first: the search must COMPLETE
            t.join(10)
            assert "error" not in out, out.get("error")
            assert out["resp"]["hits"]["total"] == 6
        finally:
            scheme.remove()

    def test_drained_restart_is_ops_free_and_byte_identical(self, tmp_path):
        from elasticsearch_tpu.cluster.multinode import (
            clear_recovery_progress,
            recovery_progress_rows,
        )
        from elasticsearch_tpu.node import Node

        clear_recovery_progress()
        path = str(tmp_path / "warm")
        node = Node(Settings.EMPTY, data_path=path)
        node.create_index("warmidx", {"settings": {
            "index.number_of_shards": 2, "index.refresh_interval": -1}})
        for d in range(10):
            node.index_doc("warmidx", str(d), {"body": f"w{d % 3} common"})
        node.indices["warmidx"].refresh()
        probe = {"query": {"match": {"body": "common"}}, "size": 10}
        want = [(h["_id"], h["_score"])
                for h in node.search("warmidx", dict(probe))["hits"]["hits"]]
        report = node.drain()
        assert report["drained"] is True
        # every shard carries the synced-flush marker + empty translog
        for shard in node.indices["warmidx"].shards.values():
            assert shard.engine.last_sync_id is not None
            assert shard.engine.translog.stats()[
                "uncommitted_operations"] == 0
        node.close()

        node2 = Node(Settings.EMPTY, data_path=path)
        try:
            rows = [r for r in recovery_progress_rows()
                    if r["index"] == "warmidx" and r["type"] == "store"]
            assert len(rows) == 2, rows
            assert all(r["ops_recovered"] == 0 for r in rows), rows
            got = [(h["_id"], h["_score"]) for h in
                   node2.search("warmidx", dict(probe))["hits"]["hits"]]
            assert got == want
            for shard in node2.indices["warmidx"].shards.values():
                assert shard.engine.last_sync_id is not None
        finally:
            node2.close()
            clear_recovery_progress()

    def test_undrain_via_rest_surface(self):
        from elasticsearch_tpu.client import Client
        from elasticsearch_tpu.node import Node

        node = Node(Settings.EMPTY)
        try:
            node.create_index("restdrain", {"settings": {
                "index.number_of_shards": 1,
                "index.refresh_interval": -1}})
            client = Client(node)
            status, body = client.perform("POST", "/_nodes/_local/_drain")
            assert status == 200 and body["draining"] is True
            assert node.indices["restdrain"].admission.draining
            status, body = client.perform("DELETE",
                                          "/_nodes/_local/_drain")
            assert status == 200 and body["draining"] is False
            assert not node.indices["restdrain"].admission.draining
        finally:
            node.close()


class TestGracefulLeave:
    def _cluster(self, names=("ga", "gb", "gc")):
        from elasticsearch_tpu.cluster.multinode import ClusterNode
        from elasticsearch_tpu.transport.local import TransportHub

        hub = TransportHub()
        nodes = {n: ClusterNode(n, hub) for n in names}
        nodes[names[0]].bootstrap_cluster()
        for n in names[1:]:
            nodes[n].join(names[0])
        return hub, nodes

    def test_follower_leave_removes_it_without_fd(self):
        hub, nodes = self._cluster()
        nodes["ga"].create_index("gidx", {
            "index": {"number_of_shards": 2, "number_of_replicas": 1}})
        nodes["gc"].close(graceful=True)
        # no fault-detection tick ran: the leave announcement alone
        # removed the node and rerouted its shards
        assert "gc" not in nodes["ga"].known_nodes
        for copies in nodes["ga"].routing["gidx"].values():
            assert all(c.node_id != "gc" for c in copies)
            assert any(c.primary for c in copies)
        nodes["ga"].close(graceful=False)
        nodes["gb"].close(graceful=False)

    def test_master_abdicates_and_replicas_promote_on_leave(self):
        from elasticsearch_tpu.cluster.state import ShardRoutingState

        hub, nodes = self._cluster()
        nodes["ga"].create_index("gidx2", {
            "index": {"number_of_shards": 2, "number_of_replicas": 1}})
        for _ in range(40):
            nodes["ga"].reroute()
            copies = [c for cs in nodes["ga"].routing["gidx2"].values()
                      for c in cs]
            if copies and all(c.state == ShardRoutingState.STARTED
                              for c in copies):
                break
            time.sleep(0.05)
        old_terms = dict(nodes["gb"].primary_terms)
        had_primary = {sid for sid, cs in nodes["ga"].routing["gidx2"]
                       .items() if any(c.primary and c.node_id == "ga"
                                       for c in cs)}
        nodes["ga"].close(graceful=True)
        # lowest-id surviving eligible node took over WITHOUT an epoch
        # of fault-detection silence
        assert nodes["gb"].is_master
        assert "ga" not in nodes["gb"].known_nodes
        for sid, copies in nodes["gb"].routing["gidx2"].items():
            primary = [c for c in copies if c.primary]
            assert primary and primary[0].node_id != "ga"
            if sid in had_primary:
                # the promotion bumped the fencing term
                assert nodes["gb"].primary_terms[("gidx2", sid)] \
                    > old_terms.get(("gidx2", sid), 1)
        nodes["gb"].close(graceful=False)
        nodes["gc"].close(graceful=False)

    def test_close_deregisters_transport_before_shard_close(self):
        from elasticsearch_tpu.common.errors import (
            NodeNotConnectedException,
        )
        from elasticsearch_tpu.cluster.multinode import ACTION_GET

        hub, nodes = self._cluster(names=("ha", "hb"))
        nodes["ha"].create_index("hidx", {
            "index": {"number_of_shards": 1, "number_of_replicas": 0}})
        nodes["hb"].close(graceful=True)
        # a routed request to the closed node fails FAST at the hub —
        # it can never reach a half-closed shard
        with pytest.raises(NodeNotConnectedException):
            nodes["ha"].transport.send_request(
                "hb", ACTION_GET, {"index": "hidx", "shard": 0,
                                   "id": "x"})
        nodes["ha"].close(graceful=False)


class TestCompileCachePlane:
    def test_variant_registry_round_trip(self, tmp_path):
        from elasticsearch_tpu.common import compile_cache as cc

        path = str(tmp_path / "variants.json")
        reg = cc.VariantRegistry(path)
        assert not reg.program_known("serial:abc")
        reg.record_program("serial:abc")
        reg.record_warm("idx", "k1", {"kind": "search",
                                      "bodies": [{"size": 1}]})
        # a fresh load (the next process) sees both — and the program
        # key now counts as preexisting (the cache-hit baseline)
        reg2 = cc.VariantRegistry(path)
        assert reg2.program_known("serial:abc")
        assert reg2.warm_entries("idx") == [
            {"kind": "search", "bodies": [{"size": 1}]}]
        reg2.forget_index("idx")
        assert cc.VariantRegistry(path).warm_entries("idx") == []

    @pytest.mark.parametrize("placed", [False, True])
    def test_cache_directory_is_placed_from_outside(self, tmp_path,
                                                    monkeypatch, placed):
        """Where JAX_COMPILATION_CACHE_DIR is set the setting yields to
        it and no directory is set in code; unset, the setting places
        the cache, and a compile lands in the chosen directory only."""
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.common import compile_cache as cc

        outside, setting = tmp_path / "outside", tmp_path / "setting"
        set_dirs = []
        update = jax.config.update

        def spy(name, value):
            if name == "jax_compilation_cache_dir":
                set_dirs.append(value)
            update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        if placed:
            monkeypatch.setenv(cc.CACHE_DIR_ENV, str(outside))
            # what JAX does with the variable when it is imported
            update("jax_compilation_cache_dir", str(outside))
        else:
            monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        try:
            assert cc.configure_compile_cache(str(setting))
            want = outside if placed else setting
            assert cc.compile_cache_path() == str(want)
            jax.jit(lambda x: x * 3 + len(str(tmp_path)))(
                jnp.arange(7)).block_until_ready()
            assert os.listdir(want)
            assert not (setting if placed else outside).exists()
            # None turns the setting's cache off, never the outside one
            assert cc.configure_compile_cache(None) is placed
            assert set_dirs == ([] if placed else [str(setting), None])
        finally:
            monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
            cc.configure_compile_cache(None)
            update("jax_compilation_cache_dir", None)

    def test_instrument_program_counts_first_call_once(self):
        from elasticsearch_tpu.common import compile_cache as cc

        calls = []
        fn = cc.instrument_program(lambda x: calls.append(x) or x,
                                   "serial", "serial:testkey1")
        before = cc.compile_stats().stats()
        assert fn(1) == 1 and fn(2) == 2
        after = cc.compile_stats().stats()
        first = (after["compile_cache_hit_total"]
                 + after["compile_cache_miss_total"]
                 - before["compile_cache_hit_total"]
                 - before["compile_cache_miss_total"])
        assert first == 1
        assert "serial:testkey1" in cc.variant_registry().programs

    def test_warming_context_classifies_first_call(self):
        from elasticsearch_tpu.common import compile_cache as cc

        before = cc.compile_stats().stats()
        fn = cc.instrument_program(lambda: None, "serial",
                                   "serial:testkey2")
        with cc.warming():
            fn()
        after = cc.compile_stats().stats()
        assert (after["programs_warmed_total"]
                - before["programs_warmed_total"]) == 1
        assert (after["query_path_first_compile_total"]
                == before["query_path_first_compile_total"])

    def test_compile_block_exported_in_stats(self):
        svc = _mk_index("compstats")
        try:
            for d in range(6):
                svc.index_doc(str(d), {"body": "w0 common"})
            svc.refresh()
            svc.search({"query": {"match": {"body": "common"}}})
            block = svc.search_stats()["compile"]
            for key in ("cache_enabled", "variants_recorded",
                        "compile_cache_hit_total",
                        "compile_cache_miss_total",
                        "programs_warmed_total",
                        "query_path_first_compile_total",
                        "first_compile_stall_ms",
                        "first_compile_events"):
                assert key in block, block.keys()
        finally:
            svc.close()

    def test_mesh_query_records_warmable_variant(self):
        from elasticsearch_tpu.common import compile_cache as cc

        cc.set_variant_registry(cc.VariantRegistry(None))
        svc = _mk_index("varrec", **{"index.search.mesh.plane": "pallas"})
        try:
            for d in range(8):
                svc.index_doc(str(d), {"body": f"w{d % 2} common"})
            svc.refresh()
            r = svc.search({"query": {"match": {"body": "common"}},
                            "size": 5})
            if r["_plane"] in ("mesh_pallas", "mesh"):
                entries = cc.variant_registry().warm_entries("varrec")
                assert entries, "mesh-served query recorded no variant"
                assert entries[0]["kind"] == "search"
                # warming replays it without growing the lattice
                n_before = len(cc.variant_registry().warm_entries("varrec"))
                assert svc.warm_compile_variants() >= 1
                assert len(cc.variant_registry()
                           .warm_entries("varrec")) == n_before
        finally:
            svc.close()
            cc.set_variant_registry(cc.VariantRegistry(None))


class TestRollingRestartSoak:
    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_PALLAS", "interpret")

    def test_smoke(self, tmp_path):
        soak = RollingRestartSoak(str(tmp_path / "soak"), seed=11,
                                  nodes=3, shards=2, seed_docs=16,
                                  docs_per_roll=4, searches_per_roll=4,
                                  drain_searches=3, index="rollsmoke")
        report = soak.run()
        assert report["drain"]["drain"]["drained"] is True
        assert report["drain"]["ops_replayed"] == 0
        assert report["drain"]["restart_hits_identical"] is True
        assert report["cluster"]["acked"] >= 16 + 3 * 4
        assert report["cluster"]["hits_identical"] is True
        assert len(report["cluster"]["rolls"]) == 3
        comp = report["compile"]
        assert comp["query_path_first_compiles"] == 0
        assert comp["programs_warmed"] >= 1
        assert comp["hits_identical"] and comp["ledger_restored"]


@pytest.mark.slow
class TestRollingRestartSoakFull:
    def test_full(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
        soak = RollingRestartSoak(str(tmp_path / "soakfull"), seed=23,
                                  nodes=3, shards=3, seed_docs=60,
                                  docs_per_roll=20, searches_per_roll=12,
                                  drain_searches=6, index="rollfull")
        report = soak.run()
        assert report["cluster"]["hits_identical"] is True
        assert report["compile"]["query_path_first_compiles"] == 0
