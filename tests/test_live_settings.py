"""A dynamic search setting reaches its reader one way (ISSUE 31).

``LayeredSettings`` answers from the first source that has the key;
``IndexService.live`` layers the cluster settings' explicit values over
the index's own ``Settings``. Held here, for every key that used to
travel as a hand-copied attribute: an explicit cluster value wins,
clearing it hands control back to the index's own value, and an index
created after the PUT reads what its older peer reads. And the kernel's
postings codec is the index's own business: two nodes in one process
each stage theirs, and an index reopened from disk stages what the node
that reopens it says. A cluster value that cannot be parsed is refused
before it is committed: the readers parse on every request.
"""

import pytest

from elasticsearch_tpu.common.errors import IllegalArgumentException
from elasticsearch_tpu.common.settings import LayeredSettings, Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch


def test_layered_settings_first_source_with_the_key_answers():
    layers = [Settings({"a": "1", "t": "5s"}),
              Settings({"a": "2", "b": "true", "n": "1kb"})]
    view = LayeredSettings(lambda: layers[0], lambda: layers[1])
    assert view.get_int("a") == 1 and view.get_str("a") == "1"
    assert view.get_bool("b") is True
    assert view.get_time("t") == 5.0 and view.get_bytes("n") == 1024
    assert view.get_str("missing", "dflt") == "dflt"
    assert view.get_float("missing") is None
    # the layers are replaced, never mutated: a view handed out once
    # follows them
    layers[0] = Settings.EMPTY
    assert view.get_int("a") == 2 and view.get_time("t", 9.0) == 9.0


_mesh = IndexMeshSearch  # cheap to build: it stages nothing until asked


# key: (the index's own value, the cluster's value, the reader of the
# effective value, what it reads for each of the two)
KEYS = {
    "search.pallas.pruning.enabled": (
        True, False, lambda svc: _mesh(svc)._pruning_config()[0],
        True, False),
    "search.pallas.pruning.probe_tiles": (
        4, 16, lambda svc: _mesh(svc)._pruning_config()[1], 4, 16),
    "search.knn.enabled": (
        False, True, lambda svc: _mesh(svc)._knn_config()[0], False, True),
    "search.knn.tile_sub": (
        32, 16, lambda svc: _mesh(svc)._knn_config()[1], 32, 16),
    "search.aggs.fused": (
        False, True, lambda svc: _mesh(svc)._fused_aggs_enabled(),
        False, True),
    "search.telemetry.enabled": (
        False, True, lambda svc: svc._telemetry_enabled(), False, True),
    "index.scrub.interval": (
        "30s", "5s", lambda svc: svc._scrub_effective_interval(),
        30.0, 5.0),
    "index.staging.delta.enabled": (
        False, True, lambda svc: _mesh(svc)._delta_enabled(), False, True),
    "index.staging.compact.threshold": (
        0.75, 0.5, lambda svc: svc._compact_threshold(), 0.75, 0.5),
}


@pytest.mark.parametrize("key", sorted(KEYS))
def test_explicit_cluster_value_wins_clears_and_reaches_a_late_index(key):
    own, cluster, read, reads_own, reads_cluster = KEYS[key]
    # a node-scope key is the index's own through the node file, which
    # create_index seeds into it; an index-scope key through the body
    index_key = key.startswith("index.")
    node = Node(Settings.EMPTY if index_key else Settings({key: own}))
    body = {"settings": {"number_of_shards": 1,
                         **({key: own} if index_key else {})}}
    try:
        node.create_index("before", body)
        before = node.indices["before"]
        assert read(before) == reads_own
        node.put_cluster_settings({"transient": {key: cluster}})
        assert read(before) == reads_cluster
        node.create_index("after", body)
        assert read(node.indices["after"]) == reads_cluster
        node.put_cluster_settings({"transient": {key: None}})
        assert read(before) == reads_own
    finally:
        node.close()


@pytest.mark.parametrize("key,bad", [
    ("search.knn.enabled", "maybe"),       # node scope
    ("index.scrub.interval", "x"),         # index scope
])
def test_malformed_cluster_value_is_refused_before_the_commit(key, bad):
    # the readers parse on every request, so a value that cannot be
    # parsed must never reach the committed state
    node = Node(Settings.EMPTY)
    try:
        node.create_index("idx", {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        node.indices["idx"].index_doc("1", {"body": "alpha"})
        node.indices["idx"].refresh()
        committed = node._committed_cluster_settings().as_dict()
        with pytest.raises(IllegalArgumentException):
            node.put_cluster_settings({"transient": {key: bad}})
        assert node._committed_cluster_settings().as_dict() == committed
        assert node.indices["idx"].cluster_explicit.get(key) is None
        hits = node.search("idx", {"query": {"match": {"body": "alpha"}}})
        assert hits["hits"]["total"] == 1
        assert node.indices["idx"]._scrub_effective_interval() is None
        # a clearing PUT carries None, which no parser sees
        node.put_cluster_settings({"transient": {key: None}})
    finally:
        node.close()


def _staged_codec(svc):
    for d in range(8):
        svc.index_doc(f"{svc.uuid}-{d}", {"body": f"alpha beta t{d}"})
    svc.refresh()
    seg = svc.shards[0].engine.searchable_segments()[-1]
    seg.device_arrays()
    return seg.kernel_codec


@pytest.mark.parametrize("created_under,reopened_under", [
    ({"search.pallas.postings_codec": "raw"},
     {"search.pallas.postings_codec": "packed"}),
    ({"search.pallas.postings_codec": "packed"},
     {"search.pallas.postings_codec": "raw"}),
    ({"search.pallas.postings_codec": "packed"}, {}),
])
def test_recovered_index_follows_the_reopening_nodes_codec(
        monkeypatch, tmp_path, created_under, reopened_under):
    # the key is static and node-scope: an index whose own key is
    # "default" stages what THIS node's file says, not what its Settings
    # were seeded with under the file of the node that created it
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    body = {"settings": {"number_of_shards": 1, "refresh_interval": -1},
            "mappings": {"properties": {"body": {"type": "text"}}}}
    node = Node(Settings(created_under), data_path=str(tmp_path))
    try:
        node.create_index("follows", body)
        node.create_index("own", {**body, "settings": {
            **body["settings"],
            "index.search.pallas.postings_codec": "packed"}})
        assert _staged_codec(node.indices["follows"]) == \
            created_under["search.pallas.postings_codec"]
    finally:
        node.close()
    node = Node(Settings(reopened_under), data_path=str(tmp_path))
    try:
        want = reopened_under.get("search.pallas.postings_codec", "raw")
        assert node.indices["follows"].postings_codec_pref == want
        assert _staged_codec(node.indices["follows"]) == want
        assert node.indices["own"].postings_codec_pref == "packed"
    finally:
        node.close()


def test_two_nodes_in_one_process_each_stage_their_own_codec(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    nodes = {codec: Node(Settings({"search.pallas.postings_codec": codec}))
             for codec in ("packed", "raw")}
    try:
        for codec, node in nodes.items():
            node.create_index("codec", {
                "settings": {"number_of_shards": 1, "refresh_interval": -1},
                "mappings": {"properties": {"body": {"type": "text"}}}})
        for codec, node in nodes.items():
            svc = node.indices["codec"]
            for d in range(8):
                svc.index_doc(str(d), {"body": f"alpha beta t{d}"})
            svc.refresh()
            (seg,) = svc.shards[0].engine.searchable_segments()
            seg.device_arrays()
            assert seg.kernel_codec == codec
    finally:
        for node in nodes.values():
            node.close()
