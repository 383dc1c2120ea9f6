"""Phase-attributed query tracing (ISSUE 8, docs/OBSERVABILITY.md).

Covers the four contracts:
- plane-truthful profile: a "profile": true query is served by the same
  rung as its unprofiled twin (mesh_pallas / batched / pruned included)
  with byte-identical hits, and reports that plane's phase spans +
  annotations;
- stats-counter correctness under concurrency: a burst of mixed
  batched/serial/knn traffic leaves every counter summing consistently
  (no double counts, no lost increments);
- tracer overhead guard: span count capped, per-phase accumulation
  bounded by the taxonomy, the hot path fast, and the
  search.telemetry.enabled kill switch honored (registered + dynamic);
- MicroBatcher window-wait/batch-shape annotations.

Kernel paths run in interpret mode on the CPU backend (the
tests/test_pallas_scoring idiom).
"""

import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.search.batching import MicroBatcher
from elasticsearch_tpu.search.telemetry import (
    NULL_TRACER,
    PHASES,
    QueryTracer,
    SearchTelemetry,
    merge_phase_stats,
)
from elasticsearch_tpu.testing.disruption import clear_search_disruptions

MAPPING = {
    "properties": {
        "body": {"type": "text", "analyzer": "whitespace"},
        "n": {"type": "integer"},
        "emb": {"type": "dense_vector", "dims": 8,
                "similarity": "cosine"},
    }
}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    yield
    clear_search_disruptions()


def build_index(name="obs", n_shards=2, n_docs=80, seed=0,
                **extra_settings):
    idx = IndexService(name, Settings({
        "index.number_of_shards": n_shards,
        "index.refresh_interval": -1, **extra_settings}), mapping=MAPPING)
    rng = np.random.RandomState(seed)
    vocab = [f"t{i}" for i in range(12)]
    for d in range(n_docs):
        toks = [vocab[rng.randint(len(vocab))]
                for _ in range(rng.randint(3, 9))]
        idx.index_doc(str(d), {"body": " ".join(toks), "n": d,
                               "emb": rng.randn(8).tolist()})
    idx.refresh()
    return idx


def ids(r):
    return [h["_id"] for h in r["hits"]["hits"]]


def scores(r):
    return [h["_score"] for h in r["hits"]["hits"]]


class TestPlaneTruthfulProfile:
    def test_mesh_pallas_profile_reports_plane_and_phases(self):
        idx = build_index("obsprof")
        try:
            body = {"query": {"match": {"body": "t0 t1"}}, "size": 5}
            plain = idx.search(dict(body))
            assert plain["_plane"] == "mesh_pallas", plain["_plane"]
            prof = idx.search(dict(body, profile=True))
            # profile never demotes the plane, hits byte-identical
            assert prof["_plane"] == "mesh_pallas", prof["_plane"]
            assert ids(prof) == ids(plain)
            assert scores(prof) == scores(plain)
            p = prof["profile"]
            assert p["plane"] == "mesh_pallas"
            names = {s["phase"] for s in p["phases"]}
            assert {"staging", "kernel", "merge"} <= names, names
            assert all(s["time_in_nanos"] >= 0 for s in p["phases"])
            # mesh-served: one compiled program, no per-segment trees
            assert p["shards"] == []
        finally:
            idx.close()

    def test_pruned_profile_reports_tile_economy(self):
        idx = build_index("obspruned", n_docs=600, **{
            "index.search.pallas.postings_codec": "packed",
            "search.pallas.pruning.enabled": True,
            "search.pallas.pruning.probe_tiles": 2,
        })
        try:
            body = {"query": {"match": {"body": "t0 t3 t7"}}, "size": 5}
            plain = idx.search(dict(body))
            assert plain["_plane"] == "mesh_pallas"
            assert "_pruned" in plain
            prof = idx.search(dict(body, profile=True))
            assert prof["_plane"] == "mesh_pallas"
            assert "_pruned" in prof
            assert ids(prof) == ids(plain)
            assert scores(prof) == scores(plain)
            ann = prof["profile"]["annotations"]
            assert ann["tiles_scored"] > 0
            assert ann["tiles_pruned"] > 0
            assert ann["postings_bytes_skipped"] > 0
            assert ann["postings_bytes_streamed"] > 0
            counters = idx.search_stats()["phases"]["counters"]
            assert counters["postings_bytes_skipped_total"] > 0
        finally:
            idx.close()

    def test_batched_member_profile_reports_batch_shape(self):
        idx = build_index("obsbatch")
        try:
            burst = [dict({"query": {"match": {"body": f"t{i}"}},
                           "size": 4}, profile=True) for i in range(3)]
            out = idx.search_batch([dict(b) for b in burst])
            for j, got in enumerate(out):
                assert isinstance(got, dict), got
                assert got["_plane"] == "mesh_pallas", got["_plane"]
                ann = got["profile"]["annotations"]
                assert ann["batch_size"] == 3
                assert ann["batch_member_index"] == j
                assert got["profile"]["phases"]
                solo = idx.search({"query": {"match": {"body": f"t{j}"}},
                                   "size": 4})
                assert ids(got) == ids(solo), j
        finally:
            idx.close()

    def test_host_profile_keeps_segment_tree_plus_phases(self):
        idx = build_index("obshost", n_shards=1)
        try:
            r = idx.search({"query": {"match": {"body": "t1"}},
                            "size": 5, "profile": True})
            assert r["_plane"] == "host"
            p = r["profile"]
            assert p["plane"] == "host"
            assert p["shards"], "host profile lost the per-segment tree"
            assert {s["phase"] for s in p["phases"]} >= {"kernel",
                                                         "merge"}
        finally:
            idx.close()

    def test_opaque_id_joins_task_slowlog_and_profile(self, caplog):
        import logging

        from elasticsearch_tpu.search.telemetry import set_opaque_id

        idx = build_index("obsoid", n_shards=1, **{
            "index.search.slowlog.threshold.query.warn": "0s"})
        try:
            set_opaque_id("client-7")
            with caplog.at_level(
                    logging.WARNING,
                    logger="elasticsearch_tpu.index.search.slowlog"):
                r = idx.search({"query": {"match": {"body": "t1"}},
                                "size": 3, "profile": True})
            assert r["profile"]["annotations"]["opaque_id"] == "client-7"
            lines = [rec.getMessage() for rec in caplog.records
                     if rec.name.endswith("search.slowlog")]
            assert lines and "id[client-7]" in lines[0], lines
            assert "plane[host]" in lines[0]
            assert "phases[" in lines[0]
        finally:
            set_opaque_id(None)
            idx.close()

    def test_batch_member_slowlog_keeps_own_opaque_id(self, caplog):
        """Kill switch OFF: every member's tracer is NULL_TRACER, so the
        slowlog falls back to the contextvar — which must be the
        MEMBER's id while its result is built on the leader's thread,
        never the leader's own client id."""
        import logging

        from elasticsearch_tpu.search.telemetry import set_opaque_id

        idx = build_index("obsoidbatch", **{
            "search.telemetry.enabled": False,
            "index.search.slowlog.threshold.query.warn": "0s"})
        try:
            set_opaque_id("leader-client")
            bodies = [{"query": {"match": {"body": f"t{i}"}}, "size": 3}
                      for i in range(3)]
            with caplog.at_level(
                    logging.WARNING,
                    logger="elasticsearch_tpu.index.search.slowlog"):
                out = idx.search_batch(
                    bodies, oids=[f"client-{i}" for i in range(3)])
            assert all(isinstance(r, dict) for r in out)
            lines = [rec.getMessage() for rec in caplog.records
                     if rec.name.endswith("search.slowlog")]
            assert len(lines) == 3, lines
            for i in range(3):
                assert any(f"id[client-{i}]" in ln for ln in lines), (
                    i, lines)
            assert not any("id[leader-client]" in ln for ln in lines)
            # the leader's own request context is restored afterwards
            from elasticsearch_tpu.search.telemetry import get_opaque_id
            assert get_opaque_id() == "leader-client"
        finally:
            set_opaque_id(None)
            idx.close()


class TestCountersUnderConcurrency:
    def test_mixed_burst_counts_consistently(self):
        idx = build_index("obsconc", n_docs=100, **{
            "search.batch.max_queries": 4})
        try:
            # prewarm every program shape serially so the concurrent
            # phase measures counting, not compilation
            idx.search({"query": {"match": {"body": "t0"}}, "size": 3})
            idx.search_batch([
                {"query": {"match": {"body": "t1"}}, "size": 3},
                {"query": {"match": {"body": "t2"}}, "size": 3}])
            qv = [0.1] * 8
            idx.search({"knn": {"field": "emb", "query_vector": qv,
                                "k": 3}})

            lex = [{"query": {"match": {"body": f"t{i % 6}"}}, "size": 3}
                   for i in range(8)]
            knn = [{"knn": {"field": "emb", "query_vector": qv, "k": 3}}
                   for _ in range(4)]
            serial = [{"query": {"match": {"body": f"t{i}"}}, "size": 3,
                       "sort": [{"n": "desc"}]} for i in range(2)]
            bodies = lex + knn + serial
            base_recorded = idx.telemetry.queries_recorded
            mesh = idx._mesh_search
            base_mesh = mesh.query_total
            base_knn = mesh.knn_query_total
            base_host = idx._host_query_total

            errors = []

            def worker(b):
                try:
                    r = idx.search(dict(b))
                    assert isinstance(r, dict)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(b,))
                       for b in bodies]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert not errors, errors

            # every request recorded exactly once in the telemetry
            assert (idx.telemetry.queries_recorded - base_recorded
                    == len(bodies))
            # every request served by exactly one plane: mesh-served +
            # host-served partition the burst
            mesh_served = mesh.query_total - base_mesh
            host_served = idx._host_query_total - base_host
            assert mesh_served + host_served == len(bodies), (
                mesh_served, host_served)
            # every kNN request reached the MXU rung exactly once
            assert mesh.knn_query_total - base_knn == len(knn)
            # batch accounting stays internally consistent: the batched
            # totals equal the histogram's weighted sum
            bstats = idx.batch_stats.as_dict()
            hist_sum = sum(int(size) * count for size, count
                           in bstats["batch_size_histogram"].items())
            assert bstats["batched_query_total"] == hist_sum
            # per-shard attribution: each shard saw every query once
            for sid, shard in idx.shards.items():
                assert shard.searcher.query_total >= len(bodies), sid
        finally:
            idx.close()


class TestTracerOverheadGuard:
    def test_span_list_capped_and_accumulators_bounded(self):
        tr = QueryTracer()
        for i in range(10_000):
            t0 = tr.start("kernel")
            tr.stop("kernel", t0)
        # span list capped; accumulators bounded by the taxonomy and
        # still exact past the cap (a dropped span's token is its start)
        assert len(tr.closed_spans()) == QueryTracer.MAX_SPANS
        assert len(tr._spans) == QueryTracer.MAX_SPANS
        assert tr.spans_dropped == 10_000 - QueryTracer.MAX_SPANS
        spans = tr.spans()
        assert len(spans) == 1  # one accumulator per phase, not 10k
        assert spans[0]["count"] == 10_000
        assert spans[0]["time_in_nanos"] >= sum(
            end - start for _, start, end, *_ in tr.closed_spans())
        assert set(tr._acc) <= set(PHASES)
        assert tr.annotations()["spans_dropped"] == tr.spans_dropped

    def test_hot_loop_is_cheap(self):
        # generous bound: 20k start/stop pairs (a 5000-segment scan's
        # worth of spans) must stay far from per-query latency budgets.
        # This guards against accidental allocation/IO creeping into
        # the hot path, not against scheduler noise.
        tr = QueryTracer()
        t0 = time.perf_counter()
        for _ in range(20_000):
            t = tr.start("kernel")
            tr.stop("kernel", t)
        took = time.perf_counter() - t0
        assert took < 1.0, f"tracer hot path took {took:.3f}s for 20k spans"

    def test_null_tracer_is_inert(self):
        t0 = NULL_TRACER.start("kernel")
        NULL_TRACER.stop("kernel", t0)
        NULL_TRACER.annotate("x", 1)
        assert NULL_TRACER.spans() == []
        assert NULL_TRACER.annotations() == {}
        tel = SearchTelemetry()
        tel.record_query("host", NULL_TRACER)
        assert tel.queries_recorded == 0

    def test_kill_switch_registered_and_honored(self):
        from elasticsearch_tpu.common.settings import cluster_settings

        reg = cluster_settings()._settings
        assert "search.telemetry.enabled" in reg
        assert reg["search.telemetry.enabled"].dynamic
        idx = build_index("obskill", n_shards=1, **{
            "search.telemetry.enabled": False})
        try:
            assert idx._tracer() is NULL_TRACER
            r = idx.search({"query": {"match": {"body": "t1"}},
                            "size": 3})
            assert isinstance(r, dict)
            phases = idx.search_stats()["phases"]
            assert phases["queries_recorded"] == 0
            assert phases["histogram_us"] == {}
            # the dynamic override wins over the creation-time setting
            idx.set_cluster_overrides(
                Settings({"search.telemetry.enabled": True}))
            idx.search({"query": {"match": {"body": "t1"}}, "size": 3})
            assert idx.search_stats()["phases"]["queries_recorded"] == 1
        finally:
            idx.close()


class TestBatchWindowAnnotations:
    def test_microbatcher_annotate_hook(self):
        mb = MicroBatcher(window_s=0.05, max_queries=4)
        seen = {}
        mb.annotate = (lambda item, wait_s, size, idx:
                       seen.setdefault(item, (wait_s, size, idx)))
        start = threading.Barrier(3)
        results = {}

        def slow_single(x):
            time.sleep(0.15)
            return ("single", x)

        def worker(i):
            start.wait()
            results[i] = mb.run(
                "k", i, single_fn=slow_single,
                batch_fn=lambda items: [("batch", x) for x in items])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        # one went direct (never annotated); the group members carry
        # wait + shape
        assert seen, "annotate hook never fired"
        for item, (wait_s, size, idx) in seen.items():
            assert wait_s >= 0.0
            assert size == len(seen)
            assert 0 <= idx < size

    def test_window_wait_lands_in_profile_annotations(self):
        idx = build_index("obswait", n_docs=60)
        try:
            # prewarm compile so the timed window isn't compilation
            idx.search_batch([
                {"query": {"match": {"body": "t1"}}, "size": 3},
                {"query": {"match": {"body": "t2"}}, "size": 3}])
            start = threading.Barrier(3)
            results = {}

            def worker(i):
                start.wait()
                results[i] = idx.search(dict(
                    {"query": {"match": {"body": f"t{i}"}}, "size": 3},
                    profile=True))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            waits = [r["profile"]["annotations"].get(
                "batch_window_wait_ms") for r in results.values()
                if isinstance(r, dict)]
            # at least the grouped members carry the window wait
            assert any(w is not None and w >= 0.0 for w in waits), waits
        finally:
            idx.close()


class TestQuarantineEvents:
    def test_fault_records_timestamped_event(self):
        from elasticsearch_tpu.testing.disruption import PlaneFailScheme

        idx = build_index("obsquar")
        try:
            body = {"query": {"match": {"body": "t1"}}, "size": 3}
            assert idx.search(dict(body))["_plane"] == "mesh_pallas"
            before_ms = int(time.time() * 1000)
            scheme = PlaneFailScheme(planes=["mesh_pallas"],
                                     indices=["obsquar"]).install()
            try:
                r = idx.search(dict(body))
                assert r["_plane"] != "mesh_pallas"
            finally:
                clear_search_disruptions()
            planes = idx.search_stats()["planes"]
            events = planes["quarantine_events"]
            assert events, "no quarantine event recorded"
            ev = events[-1]
            assert ev["plane"] == "mesh_pallas"
            assert ev["timestamp_ms"] >= before_ms
            assert ev["cooldown_s"] > 0
            # ladder decisions recorded the fault and the fallback
            decisions = idx.search_stats()["phases"]["decisions"]
            assert decisions.get("mesh_pallas.fault", 0) >= 1
        finally:
            idx.close()


class TestNodeStatsMerge:
    def test_merge_phase_stats_sums_histograms(self):
        a = {"query_total": 2,
             "phases": {"taxonomy": list(PHASES), "queries_recorded": 2,
                        "histogram_us": {"host": {"kernel": {"le_8": 2}}},
                        "counters": {"x_total": 1}, "decisions": {}}}
        b = {"query_total": 3,
             "phases": {"taxonomy": list(PHASES), "queries_recorded": 3,
                        "histogram_us": {"host": {"kernel": {"le_8": 1,
                                                             "le_16": 4}}},
                        "counters": {"x_total": 2}, "decisions": {}}}
        m = merge_phase_stats([a, b])
        assert m["query_total"] == 5
        assert m["phases"]["queries_recorded"] == 5
        assert m["phases"]["histogram_us"]["host"]["kernel"] == {
            "le_8": 3, "le_16": 4}
        assert m["phases"]["counters"]["x_total"] == 3
        assert m["phases"]["taxonomy"] == list(PHASES)


# ----------------------------------------------------------------------
# The request's span tree (ISSUE 25): one tree from the HTTP socket to
# the last byte, `_stats` search.spans, profile.spans, the profiler's
# trace
# ----------------------------------------------------------------------

# span -> parent, for a `_search` over HTTP served by mesh_pallas
# (docs/OBSERVABILITY.md "The request's span tree")
SPAN_PARENTS = {
    "http.request": None,
    "http.inbound": "http.request",
    "search.request": "http.request",
    "search.admit": "search.request",
    "search.route": "search.request",
    "parse_rewrite": "search.request",
    "plan_build": "search.request",
    "staging": "search.request",
    "kernel": "search.request",
    "kernel.lock_wait": "kernel",
    "kernel.dispatch": "kernel",
    "kernel.device_wait": "kernel",
    "merge": "search.request",
    "merge.d2h": "merge",
    "merge.assemble": "merge",
    "fetch": "search.request",
    "search.respond": "search.request",
    "http.outbound": "http.request",
}
PARENT_SPANS = {"http.request", "search.request", "kernel", "merge"}


class _Http:
    """A node behind its HTTP front door, two shards of one segment
    each (the recipe that reaches mesh_pallas), and every finished
    request's tracer as the front door drained it."""

    def __init__(self, monkeypatch, **index_settings):
        import json
        import urllib.request

        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.http_server import HttpServer

        self._json, self._url = json, urllib.request
        self.drained = []
        keep = SearchTelemetry.record_spans

        def record_spans(tel, tracer):
            self.drained.append(tracer)
            keep(tel, tracer)

        monkeypatch.setattr(SearchTelemetry, "record_spans", record_spans)
        self.node = Node()
        self.server = HttpServer(self.node, port=0)
        self.server.start()
        self.call("PUT", "/spans", {
            "settings": {"number_of_shards": 2, "refresh_interval": -1,
                         **index_settings},
            "mappings": {"_doc": {"properties": {
                "body": {"type": "text", "analyzer": "whitespace"}}}}})
        rng = np.random.RandomState(3)
        lines = []
        for d in range(80):
            toks = [f"t{rng.randint(12)}" for _ in range(rng.randint(3, 9))]
            lines.append(json.dumps({"index": {"_id": str(d)}}))
            lines.append(json.dumps({"body": " ".join(toks)}))
        self.call("POST", "/spans/_bulk", "\n".join(lines) + "\n",
                  ctype="application/x-ndjson")
        self.call("POST", "/spans/_forcemerge?max_num_segments=1")
        self.call("POST", "/spans/_refresh")

    def call(self, method, path, body=None, ctype="application/json"):
        data = None
        if body is not None:
            data = (body if isinstance(body, str)
                    else self._json.dumps(body)).encode()
        req = self._url.Request(
            f"http://127.0.0.1:{self.server.port}{path}", data=data,
            method=method, headers={"Content-Type": ctype})
        with self._url.urlopen(req) as resp:
            return self._json.loads(resp.read())

    def search(self, **extra):
        body = {"query": {"match": {"body": "t0 t1"}}, "size": 5, **extra}
        n = len(self.drained)
        resp = self.call("POST", "/spans/_search", body)
        assert resp["_plane"] == "mesh_pallas", resp["_plane"]
        # the tree drains after the last byte: the client may be here
        # before the front door's thread is
        deadline = time.monotonic() + 5.0
        while len(self.drained) == n and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(self.drained) == n + 1
        return resp, self.drained[-1]

    def spans_stats(self):
        return self.call("GET", "/spans/_stats")[
            "indices"]["spans"]["total"]["search"]["spans"]

    def close(self):
        self.server.stop()
        self.node.close()


@pytest.fixture()
def http(monkeypatch):
    served = _Http(monkeypatch)
    served.search()  # the first call compiles
    yield served
    served.close()


def _rows(tracer):
    """{index: (name, start, end, parent, self_ns)} of a drained tracer."""
    return {i: (name, start, end, parent, self_ns)
            for name, start, end, parent, self_ns, _attrs, i
            in tracer.closed_spans()}


class TestRequestSpanTree:
    def test_names_and_parents_of_a_mesh_search(self, http):
        _, tracer = http.search()
        rows = _rows(tracer)
        got = {name: (rows[parent][0] if parent >= 0 else None)
               for name, _s, _e, parent, _self in rows.values()}
        assert got == SPAN_PARENTS
        assert tracer.spans_dropped == 0
        assert tracer.request_id > 0

    def test_children_inside_parents_and_leaves_disjoint(self, http):
        _, tracer = http.search()
        rows = _rows(tracer)
        for name, start, end, parent, _self in rows.values():
            assert end >= start, name
            if parent >= 0:
                _p, p_start, p_end, _pp, _ps = rows[parent]
                assert p_start <= start and end <= p_end, (name, _p)
        # a serial request does one thing at a time: its leaves tile
        # it without overlap, on whichever thread each ran
        leaves = sorted((start, end, name) for name, start, end, _p, _s
                        in rows.values() if name not in PARENT_SPANS)
        for (_s0, e0, n0), (s1, _e1, n1) in zip(leaves, leaves[1:]):
            assert e0 <= s1, (n0, n1)

    def test_self_ns_is_duration_less_children(self, http):
        _, tracer = http.search()
        rows = _rows(tracer)
        for i, (name, start, end, _parent, self_ns) in rows.items():
            children = sum(e - s for _n, s, e, p, _x in rows.values()
                           if p == i)
            assert self_ns == end - start - children, name
            if name not in PARENT_SPANS:
                assert self_ns == end - start, name

    def test_stats_deltas_equal_the_requests_own_spans(self, http):
        before = http.spans_stats()
        _, tracer = http.search()
        after = http.spans_stats()
        own = {}
        for name, start, end, _parent, self_ns in _rows(tracer).values():
            st = own.setdefault(name, {"count": 0, "sum_ns": 0,
                                       "self_ns": 0})
            st["count"] += 1
            st["sum_ns"] += end - start
            st["self_ns"] += self_ns
        delta = {name: {k: v - before.get(name, {}).get(k, 0)
                        for k, v in st.items()}
                 for name, st in after.items()}
        assert {n: st for n, st in delta.items() if st["count"]} == own
        assert set(own) == set(SPAN_PARENTS)

    def test_profile_phases_unchanged_by_the_spans(self, http):
        resp, tracer = http.search(profile=True)
        prof = resp["profile"]
        names = [p["phase"] for p in prof["phases"]]
        assert set(names) <= set(PHASES)
        assert names == [p for p in PHASES if p in names]
        by_name = {}
        for name, start, end, _p, _s in _rows(tracer).values():
            by_name[name] = by_name.get(name, 0) + end - start
        for p in prof["phases"]:
            # a phase's accumulator IS its spans: to the nanosecond
            assert p["time_in_nanos"] == by_name[p["phase"]], p
        assert (sum(p["time_in_nanos"] for p in prof["phases"])
                <= by_name["search.request"])
        assert set(tracer._acc) <= set(PHASES)

    def test_profile_spans_is_the_tree_so_far(self, http):
        resp, tracer = http.search(profile=True)
        prof = resp["profile"]
        assert prof["request_id"] == tracer.request_id
        spans = {s["id"]: s for s in prof["spans"]}
        names = {s["name"] for s in spans.values()}
        # everything but what had not ended or begun when the response
        # was built
        assert names == set(SPAN_PARENTS) - {"search.respond",
                                             "http.outbound"}
        assert {s["name"] for s in spans.values() if s.get("open")} == {
            "http.request", "search.request"}
        for s in spans.values():
            assert set(s) >= {"id", "parent", "name",
                              "start_offset_nanos", "time_in_nanos"}
            want = SPAN_PARENTS[s["name"]]
            assert (spans[s["parent"]]["name"] if s["parent"] is not None
                    else None) == want, s
            assert s["start_offset_nanos"] >= 0
        root = next(s for s in spans.values() if s["parent"] is None)
        assert root["name"] == "http.request"
        assert root["start_offset_nanos"] == 0

    def test_first_call_is_marked_on_the_dispatch_span(self, monkeypatch):
        from elasticsearch_tpu.parallel.plan_exec import (
            clear_compiled_programs,
        )

        clear_compiled_programs()  # whatever earlier tests compiled
        served = _Http(monkeypatch)
        try:
            resp, _ = served.search(profile=True)  # compiles
            first = [s for s in resp["profile"]["spans"]
                     if s["name"] == "kernel.dispatch"]
            assert [s.get("first_call") for s in first] == [True]
            resp, _ = served.search(profile=True)
            again = [s for s in resp["profile"]["spans"]
                     if s["name"] == "kernel.dispatch"]
            assert [s.get("first_call") for s in again] == [None]
        finally:
            served.close()

    def test_a_request_that_is_no_search_leaves_spans_untouched(self, http):
        before = http.spans_stats()
        n = len(http.drained)
        http.call("GET", "/spans/_doc/1")
        http.call("POST", "/spans/_bulk",
                  '{"index": {"_id": "x1"}}\n{"body": "t0"}\n',
                  ctype="application/x-ndjson")
        http.call("GET", "/_cat/indices?format=json")
        assert http.spans_stats() == before
        assert len(http.drained) == n

    def test_kill_switch_records_no_spans(self, monkeypatch):
        served = _Http(monkeypatch)
        try:
            served.call("PUT", "/_cluster/settings", {
                "transient": {"search.telemetry.enabled": False}})
            served.drained.clear()
            before = served.spans_stats()
            resp = served.call("POST", "/spans/_search", {
                "query": {"match": {"body": "t0 t1"}}, "profile": True})
            assert resp["_plane"] == "mesh_pallas"
            assert resp["profile"]["phases"] == []
            assert resp["profile"]["spans"] == []
            assert served.spans_stats() == before
            assert served.drained == []
        finally:
            served.close()

    def test_direct_caller_owns_and_drains_its_tracer(self):
        idx = build_index("obsdirect")
        try:
            idx.search({"query": {"match": {"body": "t0 t1"}}, "size": 3})
            spans = idx.search_stats()["spans"]
            # no front door: the tree starts at search.request
            assert "http.request" not in spans
            assert spans["search.request"]["count"] == 1
            assert spans["kernel.device_wait"]["count"] == 1
            assert (spans["search.request"]["self_ns"]
                    <= spans["search.request"]["sum_ns"])
        finally:
            idx.close()

    def test_slowlog_names_the_leaves(self, caplog):
        import logging

        idx = build_index("obsleaf", **{
            "index.search.slowlog.threshold.query.warn": "0ms"})
        try:
            with caplog.at_level(
                    logging.INFO,
                    logger="elasticsearch_tpu.index.search.slowlog"):
                r = idx.search({"query": {"match": {"body": "t0 t1"}}})
            assert r["_plane"] == "mesh_pallas"
            line = next(rec.getMessage() for rec in caplog.records
                        if "plane[mesh_pallas]" in rec.getMessage())
            phases = line.split("phases[")[1].split("]")[0]
            names = [p.split(":")[0] for p in phases.split(", ")]
            assert names and not set(names) & PARENT_SPANS, line
        finally:
            idx.close()

    def test_batched_launch_spans_fold_into_each_member(self):
        idx = build_index("obsfold")
        try:
            bodies = [{"query": {"match": {"body": f"t{i} t{i + 1}"}},
                       "size": 3} for i in range(3)]
            tracers = [QueryTracer() for _ in bodies]
            parents = [t.start_parent("search.request") for t in tracers]
            out = idx.search_batch(bodies, tracers=tracers)
            for t, tok in zip(tracers, parents):
                t.stop("search.request", tok)
            assert all(r["_plane"] == "mesh_pallas" for r in out)
            for t in tracers:
                rows = _rows(t)
                names = [r[0] for r in rows.values()]
                for name in ("kernel", "kernel.lock_wait",
                             "kernel.dispatch", "kernel.device_wait",
                             "merge.d2h"):
                    assert names.count(name) == 1, (name, names)
                kernel = next(i for i, r in rows.items()
                              if r[0] == "kernel")
                assert rows[kernel][3] == 0  # under search.request
                assert {r[0] for r in rows.values() if r[3] == kernel} == {
                    "kernel.lock_wait", "kernel.dispatch",
                    "kernel.device_wait"}
                # the launch's one fetch, once, beside its kernel
                assert [r[3] for r in rows.values()
                        if r[0] == "merge.d2h"] == [0]
                assert t.annotations()["batch_size"] == 3
        finally:
            idx.close()

    def test_window_wait_becomes_a_span_and_stays_an_annotation(self):
        tr = QueryTracer()
        tok = tr.start_parent("search.request")
        time.sleep(0.002)
        IndexService._annotate_batch_member(({}, None, tr, None),
                                            10.0, 2, 1)
        tr.stop("search.request", tok)
        rows = _rows(tr)
        wait = next(r for r in rows.values()
                    if r[0] == "batch.window_wait")
        # a wait that began before its parent is cut to the parent
        assert wait[1] == rows[0][1] and wait[2] <= rows[0][2]
        assert tr.annotations()["batch_window_wait_ms"] == 10000.0

    def test_an_abandoned_parent_ends_with_the_span_above_it(self):
        tr = QueryTracer()
        root = tr.start_parent("search.request")
        tr.start_parent("kernel")    # an exception skips its stop
        tr.start("kernel.dispatch")  # and this leaf's
        tr.stop("search.request", root)
        rows = _rows(tr)
        assert [r[0] for r in rows.values()] == ["search.request",
                                                 "kernel"]
        assert rows[1][2] == rows[0][2]
        after = tr.start("fetch")
        tr.stop("fetch", after)
        assert _rows(tr)[after][3] == -1  # nothing left open

    def test_a_fill_leaf_lasts_until_the_next_span_or_its_parents_end(self):
        tr = QueryTracer()
        root = tr.start_parent("search.request")
        tr.fill("search.route")
        leaf = tr.start("parse_rewrite")
        tr.stop("parse_rewrite", leaf)
        tr.fill("search.respond")
        tr.stop("search.request", root)
        rows = _rows(tr)
        assert [r[0] for r in rows.values()] == [
            "search.request", "search.route", "parse_rewrite",
            "search.respond"]
        assert rows[1][2] == rows[2][1]      # ends where the next begins
        assert rows[3][2] == rows[0][2]      # ends with its parent
        assert {r[3] for i, r in rows.items() if i} == {0}
        assert tr._filler == -1

    def test_merge_phase_stats_sums_spans(self):
        a = {"spans": {"kernel": {"count": 2, "sum_ns": 10, "self_ns": 4}}}
        b = {"spans": {"kernel": {"count": 1, "sum_ns": 5, "self_ns": 5},
                       "fetch": {"count": 1, "sum_ns": 7, "self_ns": 7}}}
        assert merge_phase_stats([a, b])["spans"] == {
            "kernel": {"count": 3, "sum_ns": 15, "self_ns": 9},
            "fetch": {"count": 1, "sum_ns": 7, "self_ns": 7}}


class TestProfilerAnnotations:
    def test_leaves_and_only_leaves_enter_the_profilers_trace(
            self, http, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            _, tracer = http.search()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[-1]
        events = []  # (thread line, name, start, end, request)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("es:"):
                        events.append((line.name, e.name[3:], e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats).get("request")))
        mine = [e for e in events if e[4] == tracer.request_id]
        assert {e[1] for e in mine} == set(SPAN_PARENTS) - PARENT_SPANS
        by_line = {}
        for line, _name, start, end, _req in mine:
            by_line.setdefault(line, []).append((start, end))
        for spans in by_line.values():
            spans.sort()
            for (_s0, e0), (s1, _e1) in zip(spans, spans[1:]):
                assert e0 <= s1
        # off again: a leaf pays one flag check and enters nothing
        _, tracer = http.search()
        assert tracer._ann is None

    def test_telemetry_imports_without_jax(self):
        import subprocess
        import sys

        code = ("import sys; import elasticsearch_tpu.search.telemetry as t;"
                "tr = t.QueryTracer(); tr.stop('kernel', tr.start('kernel'));"
                "assert 'jax' not in sys.modules")
        subprocess.run([sys.executable, "-c", code], check=True)

    def test_pallas_call_states_the_kernels_name(self):
        """The device trace's readers find the kernel by the name the
        pallas_call states (the TPU lowering of it is checked in
        tests/test_tpu_compile.py against the roofline metric's own
        pattern)."""
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import pallas_scoring as psc

        geom = psc.tile_geometry(1 << 12)
        blocks = (64 + psc.CB_MAX, psc.LANE)
        jaxpr = jax.make_jaxpr(
            lambda *a: psc.score_tiles(*a, t_pad=2, cb=2, sub=geom.tile_sub,
                                       k=4, interpret=True))(
            jnp.zeros(blocks, jnp.int32), jnp.zeros(blocks, jnp.float32),
            jnp.zeros((geom.n_tiles * psc.LANE, geom.tile_sub),
                      jnp.float32),
            jnp.zeros((geom.n_tiles, 2), jnp.int32),
            jnp.zeros((geom.n_tiles, 2), jnp.int32),
            jnp.zeros((1, 2), jnp.float32))
        assert "score_tiles" in str(jaxpr)
