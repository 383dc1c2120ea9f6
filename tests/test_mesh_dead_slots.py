"""A slot that holds no live document costs a query nothing (ISSUE 32).

The serial mesh program guards each slot's pass (tile kernel, masks,
top-k) with a ``lax.cond`` on ``any(live1)`` of the slot: delta-staging
headroom, and a segment whose documents are all deleted, are branched
around. Held here, on one CPU device like the benchmark's one chip
(2 shards, so 4 slots with headroom and 2 without):

- the same documents staged with 0 and with 2 free slots answer alike,
  bit for bit, and like the host plane;
- the pass RUNS only for slots with a live document (a host callback
  planted in the pass counts its runs);
- a delta append into a free slot is served by the program that was
  compiled before it;
- ``mesh_slots_occupied_total`` over ``mesh_slots_total`` is the share
  of its slots a query pays for.

Kernel paths run in interpret mode on the CPU backend.
"""

import numpy as np
import pytest

import jax

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.parallel.mesh import shard_mesh
from test_mesh_packed_answer import MAPPING, MATCH, SERIAL_BODIES

BODIES = dict(SERIAL_BODIES, **{
    # min/max partials have identities that are not zero
    "fused_min_max": {"query": MATCH, "size": 3, "aggs": {
        "lo": {"min": {"field": "n"}}, "hi": {"max": {"field": "n"}},
        "all": {"stats": {"field": "n"}}}},
    "fused_terms": {"query": MATCH, "size": 3, "aggs": {
        "tags": {"terms": {"field": "tag"}}}},
    "views_stats": {"query": MATCH, "size": 3, "aggs": {
        "tags": {"terms": {"field": "tag"}, "aggs": {
            "n": {"stats": {"field": "n"}}}}}},
    "post_filter": {"query": MATCH, "size": 5,
                    "post_filter": {"term": {"tag": "red"}}},
    "search_after": {"query": MATCH, "size": 5,
                     "sort": [{"n": {"order": "desc"}}],
                     "search_after": [9]},
    # fewer matches than k: the skipped slots' -inf lanes never surface
    "fewer_than_k": {"query": {"match": {"body": "rare"}}, "size": 10},
    "no_match": {"query": {"match": {"body": "absent"}}, "size": 10},
})
HOST_REDUCED = {"views", "views_stats"}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def _doc(rng, d):
    toks = [f"t{rng.randint(10)}" for _ in range(rng.randint(3, 9))]
    if d in (7, 8, 31):
        toks.append("rare")
    return {"body": " ".join(toks), "n": int(d % 17),
            "tag": ["red", "green", "blue"][d % 3]}


def _build(name, devices=1, mesh=True, **settings):
    idx = IndexService(name, Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        "index.search.mesh": mesh,
        "index.staging.compact.threshold": 0.0, **settings}),
        mapping=MAPPING)
    if mesh:
        idx._mesh_search = plan_exec.IndexMeshSearch(
            idx, mesh=shard_mesh(devices))
    rng = np.random.RandomState(11)
    for d in range(60):
        idx.index_doc(str(d), _doc(rng, d))
    idx.refresh()
    return idx


def _slots(idx):
    ex = idx._mesh_search._executor
    return len(ex.segments), ex.n_slots


def _counters(idx):
    c = idx.stats()["total"]["search"]["phases"]["counters"]
    return (c.get("mesh_slots_occupied_total", 0),
            c.get("mesh_slots_total", 0))


def _answer(resp):
    return (resp["hits"]["total"],
            [(h["_id"], h["_score"], h.get("sort"))
             for h in resp["hits"]["hits"]],
            resp.get("terminated_early"), resp.get("aggregations"))


@pytest.fixture(scope="module")
def indices():
    """The same 60 documents four times: 4 slots of which 2 are free
    (the cell's layout), 2 slots and none free, 4 devices of which 3
    hold no segment, and the host plane."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_PALLAS", "interpret")
        built = {
            "headroom": _build("deadslots-headroom"),
            "full": _build("deadslots-full",
                           **{"index.staging.delta.enabled": False}),
            "four_devices": _build("deadslots-four", devices=4),
            "host": _build("deadslots-host", mesh=False),
        }
        yield built
        for idx in built.values():
            idx.close()


@pytest.mark.parametrize("case", sorted(BODIES))
def test_free_slots_change_no_answer(indices, case):
    body = BODIES[case]
    fused_off = Settings({"search.aggs.fused": False})
    answers = {}
    for name, idx in indices.items():
        if case in HOST_REDUCED:  # the host reduces the program's views
            idx.set_cluster_overrides(fused_off)
        try:
            resp = idx.search(body)
        finally:
            idx.set_cluster_overrides(Settings.EMPTY)
        want = "host" if name == "host" else ("mesh", "mesh_pallas")
        assert resp["_plane"] in want, (name, resp["_plane"])
        answers[name] = _answer(resp)
    assert _slots(indices["headroom"]) == (2, 4)
    assert _slots(indices["full"]) == (2, 2)
    assert _slots(indices["four_devices"]) == (2, 8)
    assert (answers["headroom"] == answers["full"]
            == answers["four_devices"])
    if case == "rescore":
        # (the host combines the two scores in float64, the program in
        # float32: one unit in the last place, with or without this PR)
        total, hits, *rest = answers["host"]
        got = answers["headroom"]
        assert (total, [h[0] for h in hits], rest) == (
            got[0], [h[0] for h in got[1]], list(got[2:]))
        assert [h[1] for h in got[1]] == pytest.approx(
            [h[1] for h in hits], rel=1e-6)
    else:
        assert answers["headroom"] == answers["host"]
    if case == "fewer_than_k":
        assert answers["host"][0] == len(answers["host"][1]) == 3
    if case == "no_match":
        assert answers["host"][:2] == (0, [])


def test_counters_read_the_share_of_slots_a_query_pays_for(indices):
    for name, per_query in (("headroom", (2, 4)), ("full", (2, 2)),
                            ("four_devices", (2, 8))):
        idx = indices[name]
        before = _counters(idx)
        for _ in range(3):
            idx.search(BODIES["plain_top10"])
        after = _counters(idx)
        assert (after[0] - before[0], after[1] - before[1]) == (
            3 * per_query[0], 3 * per_query[1]), name
    assert _counters(indices["host"]) == (0, 0)  # no mesh launch


class _Passes:
    """Counts the per-slot passes that RUN on the device: a host
    callback planted where ``per_slot`` builds its emit context, so
    it sits inside the guarded branch of every program traced while
    this is installed."""

    def __init__(self, monkeypatch):
        self.runs = 0
        real = plan_exec.EmitCtx
        me = self

        def counting_ctx(*args, **kwargs):
            jax.debug.callback(me._ran)
            return real(*args, **kwargs)

        monkeypatch.setattr(plan_exec, "EmitCtx", counting_ctx)

    def _ran(self):
        self.runs += 1

    def of(self, search):
        jax.effects_barrier()
        before = self.runs
        resp = search()
        jax.effects_barrier()
        return resp, self.runs - before


def _recorded_programs(monkeypatch):
    """Every program ``_mesh_query_program`` hands out, in order."""
    build = plan_exec._mesh_query_program
    handed = []

    def recording(*args, **kwargs):
        handed.append(build(*args, **kwargs))
        return handed[-1]

    recording.cache_info = build.cache_info
    monkeypatch.setattr(plan_exec, "_mesh_query_program", recording)
    return handed


# (a size no other test of this file asks for: the programs traced here
# carry the counting callback, and must be traced here)
COUNTED = {"query": MATCH, "size": 60}


def test_pass_runs_only_for_slots_with_a_live_document(monkeypatch):
    passes = _Passes(monkeypatch)
    handed = _recorded_programs(monkeypatch)
    idx = _build("deadslots-append")
    host = _build("deadslots-append-host", mesh=False)
    try:
        resp, ran = passes.of(lambda: idx.search(COUNTED))
        assert resp["_plane"] == "mesh_pallas"
        assert _slots(idx) == (2, 4) and ran == 2  # not 4
        assert _answer(resp) == _answer(host.search(COUNTED))
        assert _counters(idx) == (2, 4)
        compiled = plan_exec._mesh_query_program.cache_info().misses

        # -- a refresh delta-appends one segment into a free slot
        doc = {"body": "t0 t1 t2", "n": 3, "tag": "red"}
        for i in (idx, host):
            i.index_doc("appended", doc)
            i.refresh()
        resp, ran = passes.of(lambda: idx.search(COUNTED))
        assert idx._mesh_search.delta_restage_total == 1
        assert _slots(idx) == (3, 4) and ran == 3
        assert "appended" in [h["_id"] for h in resp["hits"]["hits"]]
        assert _answer(resp) == _answer(host.search(COUNTED))
        assert _counters(idx) == (2 + 3, 4 + 4)
        # ... served by the program compiled before it
        assert plan_exec._mesh_query_program.cache_info().misses \
            == compiled
        assert handed[-1] is handed[0]
        assert handed[0].__wrapped__._cache_size() == 1
        assert not handed[0].first_call_pending()

        # -- every document of one segment deleted while a query holds
        # its generation: the slot stays staged, its mask all false (a
        # tombstone), and is skipped
        ex = idx._mesh_search._executor
        monkeypatch.setattr(idx._mesh_search, "_ensure_staged",
                            lambda: True)
        _sid, seg = ex.pairs[0]
        for doc_id in list(seg.doc_ids):
            for i in (idx, host):
                i.delete_doc(doc_id)
        for i in (idx, host):
            i.refresh()
        assert ex.apply_tombstones([0]) > 0
        assert not np.asarray(ex._seg_staged["live1"])[0].any()
        resp, ran = passes.of(lambda: idx.search(COUNTED))
        assert resp["_plane"] == "mesh_pallas"
        assert _slots(idx) == (3, 4) and ran == 2
        assert _answer(resp) == _answer(host.search(COUNTED))
        assert 0 < resp["hits"]["total"] < 55
        assert handed[-1] is handed[0]
        assert handed[0].__wrapped__._cache_size() == 1
    finally:
        idx.close()
        host.close()


def test_every_slot_is_scored_where_none_is_free(monkeypatch):
    """Bypassed: an index staged without headroom pays for all of its
    slots, and the counters say so (1.0)."""
    passes = _Passes(monkeypatch)
    idx = _build("deadslots-nofree",
                 **{"index.staging.delta.enabled": False})
    try:
        resp, ran = passes.of(lambda: idx.search(COUNTED))
        assert resp["_plane"] == "mesh_pallas"
        assert _slots(idx) == (2, 2) and ran == 2
        occupied, total = _counters(idx)
        assert occupied / total == 1.0
    finally:
        idx.close()


@pytest.mark.parametrize("statics", [
    (("bucket", "codes", 5),),
    (("metric", "f", True, True), ("empty",), ("bucket", "codes", 3)),
    (("metric", "f", False, True),),
])
def test_dead_slot_partials_are_the_all_false_masks(statics):
    """``dead_slot`` asks ``emit_agg_partials`` for the identities on a
    one-document stand-in: what the full-size slot gives for an
    all-false mask, bit for bit (min/max keep +-inf)."""
    import jax.numpy as jnp

    from elasticsearch_tpu.search.fused_aggs import (
        N_DIGITS,
        emit_agg_partials,
    )

    rng = np.random.RandomState(3)

    def seg(nd):
        return {"codes": jnp.asarray(rng.randint(-1, 3, nd), jnp.int32),
                "f.ex": jnp.asarray(rng.rand(nd) < 0.8),
                "f.mm": jnp.asarray(rng.randn(nd, 2), jnp.float32),
                "f.dig": jnp.asarray(rng.randint(0, 99, (nd, N_DIGITS)),
                                     jnp.int32)}

    full = emit_agg_partials(statics, seg(37), jnp.zeros((37,), bool))
    one = emit_agg_partials(
        statics, {k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                  for k, v in seg(37).items()}, jnp.zeros((1,), bool))
    assert len(full) == len(one) > 0
    for a, b in zip(full, one):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
