"""Shard request cache tests (IndicesRequestCache.java:64 analog):
size==0 agg/count responses cached against the shards' visibility epoch,
invalidated by any visible write, with stats in _stats."""

import numpy as np

from elasticsearch_tpu.node import Node


def make_node():
    node = Node()
    node.create_index("logs", {
        "settings": {"number_of_shards": 1},
        "mappings": {"_doc": {"properties": {
            "host": {"type": "keyword"},
            "msg": {"type": "text"},
        }}}})
    for i in range(40):
        node.index_doc("logs", str(i), {
            "host": f"web-{i % 4}", "msg": f"event {i}"},
            refresh=(i == 39))
    return node


AGG_BODY = {
    "query": {"match": {"msg": "event"}},
    "size": 0,
    "aggs": {"hosts": {"terms": {"field": "host"}}},
}


def cache_stats(node):
    return node.indices["logs"].request_cache.stats()


class TestRequestCache:
    def test_repeat_agg_request_hits(self):
        node = make_node()
        r1 = node.search("logs", dict(AGG_BODY))
        s = cache_stats(node)
        assert s["miss_count"] == 1 and s["hit_count"] == 0
        r2 = node.search("logs", dict(AGG_BODY))
        s = cache_stats(node)
        assert s["hit_count"] == 1
        assert r2["hits"]["total"] == r1["hits"]["total"] == 40
        assert r2["aggregations"] == r1["aggregations"]
        assert s["entries"] == 1 and s["memory_size_in_bytes"] > 0

    def test_write_invalidates_before_refresh(self):
        node = make_node()
        node.search("logs", dict(AGG_BODY))
        # update an existing doc: per NRT semantics NOTHING changes for
        # search until refresh (the old copy's delete is buffered, the
        # new copy sits in the indexing buffer) — the cached entry stays
        # valid and the refresh flips visibility + epoch together
        node.index_doc("logs", "7", {"host": "web-9", "msg": "changed"})
        r = node.search("logs", dict(AGG_BODY))
        assert r["hits"]["total"] == 40  # unchanged reader, cache valid
        node.indices["logs"].refresh()
        r = node.search("logs", dict(AGG_BODY))
        # old copy out; the replacement doc no longer matches the query
        assert r["hits"]["total"] == 39

    def test_delete_invalidates(self):
        node = make_node()
        node.search("logs", dict(AGG_BODY))
        node.delete_doc("logs", "3", refresh=True)
        r = node.search("logs", dict(AGG_BODY))
        assert r["hits"]["total"] == 39
        assert cache_stats(node)["hit_count"] == 0

    def test_refresh_with_new_docs_invalidates(self):
        node = make_node()
        node.search("logs", dict(AGG_BODY))
        node.index_doc("logs", "new", {"host": "web-0", "msg": "event new"},
                       refresh=True)
        r = node.search("logs", dict(AGG_BODY))
        assert r["hits"]["total"] == 41
        assert cache_stats(node)["hit_count"] == 0

    def test_empty_refresh_keeps_cache_valid(self):
        node = make_node()
        node.search("logs", dict(AGG_BODY))
        node.indices["logs"].refresh()  # nothing new: same reader identity
        node.search("logs", dict(AGG_BODY))
        assert cache_stats(node)["hit_count"] == 1

    def test_hit_requests_never_cached(self):
        node = make_node()
        body = {"query": {"match": {"msg": "event"}}, "size": 5}
        node.search("logs", body)
        node.search("logs", body)
        s = cache_stats(node)
        assert s["hit_count"] == 0 and s["miss_count"] == 0

    def test_profile_not_cached(self):
        node = make_node()
        body = dict(AGG_BODY)
        body["profile"] = True
        node.search("logs", body)
        node.search("logs", body)
        assert cache_stats(node)["hit_count"] == 0

    def test_cache_disabled_by_setting(self):
        node = Node()
        node.create_index("quiet", {
            "settings": {"index": {"requests": {"cache": {"enable": False}}}},
            "mappings": {"_doc": {"properties": {
                "msg": {"type": "text"}}}}})
        node.index_doc("quiet", "1", {"msg": "hello"}, refresh=True)
        body = {"query": {"match_all": {}}, "size": 0}
        node.search("quiet", body)
        node.search("quiet", body)
        s = node.indices["quiet"].request_cache.stats()
        assert s["miss_count"] == 0 and s["hit_count"] == 0

    def test_stats_exposed_in_index_stats(self):
        node = make_node()
        node.search("logs", dict(AGG_BODY))
        node.search("logs", dict(AGG_BODY))
        st = node.indices["logs"].stats()
        rc = st["total"]["request_cache"]
        assert rc["hit_count"] == 1 and rc["miss_count"] == 1

    def test_lru_eviction_by_bytes(self):
        from elasticsearch_tpu.index.request_cache import RequestCache

        cache = RequestCache(max_bytes=3000)
        for i in range(50):
            cache.put(f"k{i}", {"payload": "x" * 100, "i": i})
        s = cache.stats()
        assert s["evictions"] > 0
        assert s["memory_size_in_bytes"] <= 3000
        # most recent entries survive
        assert cache.get("k49") is not None


class TestRequestCacheUrlParameter:
    """``?request_cache=false`` (RestSearchAction) keeps one request out
    of the cache: Rally's http_logs runs ``hourly_agg`` that way."""

    def test_false_opts_a_request_out_and_true_leaves_it_cached(self):
        from elasticsearch_tpu.client import Client

        node = make_node()
        client = Client(node)
        for _ in range(2):
            status, r = client.perform(
                "POST", "/logs/_search", {"request_cache": "false"},
                body=AGG_BODY)
            assert status == 200 and r["hits"]["total"] == 40
            assert len(r["aggregations"]["hosts"]["buckets"]) == 4
        assert cache_stats(node)["miss_count"] == 0
        assert cache_stats(node)["entries"] == 0
        for _ in range(2):
            status, r = client.perform(
                "POST", "/logs/_search", {"request_cache": "true"},
                body=AGG_BODY)
            assert status == 200
        s = cache_stats(node)
        assert (s["miss_count"], s["hit_count"], s["entries"]) == (1, 1, 1)
        # the parameter is no part of the search: the same entry serves
        # a request without it
        node.search("logs", dict(AGG_BODY))
        assert cache_stats(node)["hit_count"] == 2
