"""A query's answer reaches the host in one transfer (ISSUE 30).

The serial mesh program packs its merged answer (top-k keys, slots,
docs, scores, raw sort values and the total) into ONE ``int32`` array,
``_fetch`` makes one copy of it, and the host unpacks it into views.
Held here:

- pack and unpack round-trip every bit (``-inf`` keys of unfilled
  ranks, negative zero, totals no float32 or int32 holds);
- the serial path copies exactly one array a query, whatever the
  request asks for beside its hits (``d2h_arrays_total`` over
  ``merge.d2h``'s count is 1.0), and ``merge.d2h`` stays one span
  under ``merge``;
- ``_fetch`` adapts to what it is handed: one array, or several whose
  copies are all asked for before the first is waited for.

Kernel paths run in interpret mode on the CPU backend.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.parallel.plan_exec import (
    _fetch,
    _pack_answer,
    _unpack_answer,
)
from elasticsearch_tpu.search.telemetry import (
    NULL_TRACER,
    QueryTracer,
    SearchTelemetry,
)

MAPPING = {
    "properties": {
        "body": {"type": "text", "analyzer": "whitespace"},
        "n": {"type": "integer"},
        "tag": {"type": "keyword"},
    }
}


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def _bits(a):
    return np.asarray(a).view(np.int32).tolist()


# -- pack / unpack ------------------------------------------------------

@pytest.mark.parametrize("total", [0, (1 << 24) + 1, (1 << 31) + 5])
@pytest.mark.parametrize("k", [1, 10, 37])
def test_pack_unpack_round_trips_every_bit(k, total):
    rng = np.random.RandomState(k)
    keys = -np.sort(-rng.randn(k).astype(np.float32) * 1e3)
    keys[k // 2:] = -np.inf          # unfilled ranks
    scores = rng.rand(k).astype(np.float32)
    scores[-1] = np.float32(1e-42)   # a denormal
    scores[0] = -0.0                 # a sign bit and nothing else
    raws = -rng.rand(k).astype(np.float32) * 3.0e38  # negative sort keys
    raws[0] = np.float32(-3.0e38)    # the missing-fill sentinel
    slots = rng.randint(0, 32, k).astype(np.int32)
    docs = rng.randint(0, 1 << 30, k).astype(np.int32)
    packed = np.asarray(jax.jit(_pack_answer)(
        keys, slots, docs, jnp.int64(total), scores, raws))
    assert packed.dtype == np.int32 and packed.shape == (2 + 5 * k,)
    got = _unpack_answer(packed)
    for have, want in zip(got, (keys, slots, docs, None, scores, raws)):
        if want is None:
            continue
        assert have.dtype == want.dtype and have.shape == (k,)
        assert _bits(have) == _bits(want)
        assert np.shares_memory(have, packed)  # a view, not a copy
    assert got[3].dtype == np.int64 and int(got[3]) == total
    assert np.signbit(got[4][0]) and got[4][0] == 0.0
    assert (got[0][k // 2:] == -np.inf).all()


@pytest.mark.parametrize("bad", ["keys", "total", "docs"])
def test_pack_refuses_a_row_it_would_have_to_convert(bad):
    rows = dict(keys=np.zeros(3, np.float32), slots=np.zeros(3, np.int32),
                docs=np.zeros(3, np.int32), total=jnp.int64(1),
                scores=np.zeros(3, np.float32),
                raws=np.zeros(3, np.float32))
    rows[bad] = {"keys": np.zeros(3, np.float64),
                 "total": jnp.float32(1.0),
                 "docs": np.zeros(3, np.int64)}[bad]
    with pytest.raises(TypeError, match="bit for bit"):
        jax.jit(_pack_answer)(**rows)


# -- _fetch ---------------------------------------------------------------

def test_fetch_adapts_to_one_array_or_several():
    tel = SearchTelemetry()
    one = _fetch(NULL_TRACER, jnp.arange(7, dtype=jnp.int32), tel)
    assert isinstance(one, np.ndarray) and one.tolist() == list(range(7))
    assert tel.counters["d2h_arrays_total"] == 1
    several = _fetch(NULL_TRACER, (jnp.zeros(3), jnp.ones((2, 2)),
                                   jnp.int32(9)), tel)
    assert [type(a) for a in several[:2]] == [np.ndarray, np.ndarray]
    assert int(several[2]) == 9 and several[1].shape == (2, 2)
    assert tel.counters["d2h_arrays_total"] == 4
    tracer = QueryTracer()
    _fetch(tracer, jnp.zeros(2), None)  # no telemetry: only the span
    assert [r[0] for r in tracer.closed_spans()] == ["merge.d2h"]


# -- the serial path, end to end -----------------------------------------

def _build(name, **settings):
    idx = IndexService(name, Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        **settings}), mapping=MAPPING)
    rng = np.random.RandomState(11)
    tags = ["red", "green", "blue"]
    for d in range(60):
        toks = [f"t{rng.randint(10)}" for _ in range(rng.randint(3, 9))]
        idx.index_doc(str(d), {"body": " ".join(toks), "n": int(d % 17),
                               "tag": tags[d % 3]})
    idx.refresh()
    return idx


@pytest.fixture(scope="module")
def indices():
    """The same 60 documents twice: served by the mesh, and pinned to
    the host plane."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_PALLAS", "interpret")
        mesh = _build("packed")
        host = _build("packedhost", **{"index.search.mesh": False})
        yield mesh, host
        mesh.close()
        host.close()


@pytest.fixture()
def index(indices):
    return indices[0]


@pytest.fixture()
def host_reduced_aggs(indices):
    """The mesh index with search.aggs.fused explicitly off at the
    cluster level, for the length of one test."""
    indices[0].set_cluster_overrides(Settings({"search.aggs.fused": False}))
    yield
    indices[0].set_cluster_overrides(Settings.EMPTY)


def _drained(monkeypatch):
    """Every tracer the index's telemetry drains, as it drains it."""
    drained = []
    keep = SearchTelemetry.record_spans

    def record_spans(tel, tracer):
        drained.append(tracer)
        keep(tel, tracer)

    monkeypatch.setattr(SearchTelemetry, "record_spans", record_spans)
    return drained


def _d2h(idx):
    return (idx.telemetry.counters.get("d2h_arrays_total", 0),
            idx.telemetry.spans_dict().get("merge.d2h", {}).get("count", 0))


MATCH = {"match": {"body": "t0 t1 t2"}}
SERIAL_BODIES = {
    "plain_top10": {"query": MATCH, "size": 10},
    "k_of_1": {"query": MATCH, "size": 1},
    "k_beyond_a_segment": {"query": {"match_all": {}}, "size": 50},
    "terminate_after": {"query": MATCH, "size": 5, "terminate_after": 3},
    "views": {"query": MATCH, "size": 5, "aggs": {
        "tags": {"terms": {"field": "tag"}}}},
    "fused_aggs": {"query": MATCH, "size": 5, "aggs": {
        "mean": {"avg": {"field": "n"}}}},
    "field_sort": {"query": MATCH, "size": 5,
                   "sort": [{"n": {"order": "asc"}}]},
    "min_score": {"query": MATCH, "size": 5, "min_score": 0.5},
    "rescore": {"query": MATCH, "size": 5, "rescore": {
        "window_size": 4, "query": {"rescore_query": {
            "match": {"body": "t3"}}}}},
}


@pytest.mark.parametrize("case", sorted(SERIAL_BODIES))
def test_serial_path_copies_one_array_a_query(index, monkeypatch, case,
                                              request):
    body = SERIAL_BODIES[case]
    if case == "views":  # the host reduces over the program's views
        request.getfixturevalue("host_reduced_aggs")
    drained = _drained(monkeypatch)
    index.search(body)  # a first call compiles
    arrays0, spans0 = _d2h(index)
    del drained[:]
    resp = index.search(body)
    arrays1, spans1 = _d2h(index)
    assert resp["_plane"] in ("mesh", "mesh_pallas"), resp["_plane"]
    assert spans1 - spans0 == 1
    assert (arrays1 - arrays0) / (spans1 - spans0) == 1.0  # was 6.0
    # ``merge.d2h`` is exactly one span, under ``merge``
    (tracer,) = drained
    rows = {i: (name, parent) for name, _s, _e, parent, _self, _a, i
            in tracer.closed_spans()}
    d2h = [parent for name, parent in rows.values() if name == "merge.d2h"]
    assert len(d2h) == 1 and rows[d2h[0]][0] == "merge"
    assert [n for n, _p in rows.values()].count("merge") == 1


@pytest.mark.parametrize("case", sorted(SERIAL_BODIES))
def test_packed_answer_is_the_host_planes_answer(indices, request, case):
    body = SERIAL_BODIES[case]
    mesh_idx, host_idx = indices
    if case == "views":
        request.getfixturevalue("host_reduced_aggs")
    mesh, host = mesh_idx.search(body), host_idx.search(body)
    assert mesh["_plane"] in ("mesh", "mesh_pallas"), mesh["_plane"]
    assert host["_plane"] == "host"
    assert mesh["hits"]["total"] == host["hits"]["total"]

    def hits(resp):
        return [(h["_id"], h["_score"], h.get("sort"))
                for h in resp["hits"]["hits"]]

    assert hits(mesh) == hits(host)
    assert mesh.get("terminated_early") == host.get("terminated_early")
    assert mesh.get("aggregations") == host.get("aggregations")


def test_batched_launch_counts_its_arrays_once(index):
    """The programs that are not packed keep their outputs: one launch
    of the batched kernel program copies its four arrays, asked for
    together, and counts them once whatever the number of members."""
    bodies = [{"query": {"match": {"body": f"t{i} t{i + 1}"}},
               "size": 3} for i in range(3)]
    index.search_batch(bodies)  # a first call compiles
    before = index.telemetry.counters.get("d2h_arrays_total", 0)
    out = index.search_batch(bodies)
    assert all(r["_plane"] == "mesh_pallas" for r in out)
    assert index.telemetry.counters["d2h_arrays_total"] - before == 4
