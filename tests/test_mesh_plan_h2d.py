"""A request's plan arrays ride the launch, as ONE array (ISSUE 36).

``MeshPlanExecutor.execute`` stacks a request's plan arrays on the host,
packs them (and the traced scalars, a copy a slot) into one
``int32[n_slots, W]`` by their bits and hands that to the jitted mesh
program: ``shard_map``'s in_specs fix where it lands, on one device and
on four, and the jitted call places it. Held here:

- the pack round-trips every bit of every dtype a plan array takes
  (``int32``, ``float32`` with NaN, infinities, a denormal and negative
  zero, ``int64`` with the sign bit and ``SORTABLE_MISSING``, ``bool``),
  and an array whose dtype cannot be put back bit for bit on a TPU (a
  ``float64``) stays an argument of its own;
- a WARM request makes no ``jax.device_put`` anywhere in ``plan_exec``
  (the module's ``jax`` is wrapped, every call counted), for every
  shape of serial request, on one device and on four;
- the launch carries the staged columns, the pack and nothing else;
  ``h2d_arrays_total`` counts it once a launch (1.0 over ``kernel``'s
  count; it was four sharded puts for a ``match`` on the kernel plane);
- host arrays retrace nothing: another request of the same shape runs
  the executable the first compiled;
- every answer (hits, scores bit for bit, sort values, totals,
  buckets) is the host plane's.

Kernel paths run in interpret mode on the CPU backend.
"""

import jax
import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.parallel.mesh import shard_mesh
from elasticsearch_tpu.search.plan import SORTABLE_MISSING

MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "@timestamp": {"type": "date"},
    "status": {"type": "integer"},
    "n": {"type": "integer"},
    "tag": {"type": "keyword"},
}}
T0 = 897436800000  # 1998-06-10T00:00:00Z, epoch millis: no float32 holds it
HOUR = 3_600_000
N_DOCS = 72
STATUS = (200, 304, 404)


def _build(name, n_dev):
    """Two shards; ``n_dev`` devices under the mesh, or the host plane."""
    idx = IndexService(name, Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        "index.search.mesh": n_dev is not None}), mapping=MAPPING)
    if n_dev is not None:
        idx._mesh_search = plan_exec.IndexMeshSearch(
            idx, mesh=shard_mesh(n_dev))
    rng = np.random.RandomState(36)
    for d in range(N_DOCS):
        toks = [f"t{rng.randint(10)}" for _ in range(rng.randint(3, 9))]
        idx.index_doc(str(d), {
            "body": " ".join(toks), "@timestamp": T0 + d * (HOUR // 3),
            "status": STATUS[d % 3], "n": int(d % 17),
            "tag": ("red", "green", "blue")[d % 3]})
    idx.refresh()
    return idx


@pytest.fixture(scope="module")
def indices():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_PALLAS", "interpret")
        built = {n: _build(f"h2d-{n}", n) for n in (1, 4, None)}
        yield built
        for idx in built.values():
            idx.close()


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


MATCH = {"match": {"body": "t0 t1 t2"}}
IN_RANGE = {"range": {"@timestamp": {"gte": T0 + 5 * HOUR,
                                     "lt": T0 + 20 * HOUR}}}
# name -> (body, the same shape with other values, plane)
CASES = {
    "match": ({"query": MATCH, "size": 10},
              {"query": {"match": {"body": "t3 t4 t5"}}, "size": 10},
              "mesh_pallas"),
    "range_status_histogram": (
        {"size": 5, "query": {"bool": {"must": [
            IN_RANGE, {"match": {"status": 200}}]}},
         "aggs": {"by_hour": {"date_histogram": {
             "field": "@timestamp", "interval": "hour"}}}},
        {"size": 5, "query": {"bool": {"must": [
            {"range": {"@timestamp": {"gte": T0 + 2 * HOUR,
                                      "lt": T0 + 9 * HOUR}}},
            {"match": {"status": 404}}]}},
         "aggs": {"by_hour": {"date_histogram": {
             "field": "@timestamp", "interval": "hour"}}}},
        "mesh"),
    "sort_search_after": (
        {"query": MATCH, "size": 5, "sort": [{"n": {"order": "asc"}}],
         "search_after": [4]},
        {"query": MATCH, "size": 5, "sort": [{"n": {"order": "asc"}}],
         "search_after": [9]},
        "mesh_pallas"),
    "min_score": ({"query": MATCH, "size": 5, "min_score": 0.5},
                  {"query": MATCH, "size": 5, "min_score": 2.25},
                  "mesh_pallas"),
    "post_filter": (
        {"query": MATCH, "size": 5, "post_filter": {"term": {"tag": "red"}}},
        {"query": MATCH, "size": 5, "post_filter": {"term": {"tag": "blue"}}},
        "mesh_pallas"),
    "rescore": (
        {"query": MATCH, "size": 5, "rescore": {
            "window_size": 4, "query": {
                "rescore_query": {"match": {"body": "t3"}},
                "query_weight": 0.7, "rescore_query_weight": 1.2}}},
        {"query": MATCH, "size": 5, "rescore": {
            "window_size": 4, "query": {
                "rescore_query": {"match": {"body": "t6"}},
                "query_weight": 0.5, "rescore_query_weight": 2.0}}},
        "mesh_pallas"),
}
# the traced scalars each case hands the launch
SCALARS = {"sort_search_after": {"search_after"}, "min_score": {"min_score"},
           "rescore": {"query_weight", "rescore_query_weight"}}


class _CountingJax:
    """``plan_exec``'s ``jax``, every ``device_put`` recorded."""

    def __init__(self):
        self.puts = []

    def __getattr__(self, name):
        return getattr(jax, name)

    def device_put(self, x, *args, **kwargs):
        self.puts.append(getattr(x, "shape", None))
        return jax.device_put(x, *args, **kwargs)


def _readings(idx):
    c = idx.telemetry.counters
    return (c.get("h2d_arrays_total", 0),
            idx.telemetry.spans_dict().get("staging", {}).get("count", 0))


def _answer(resp):
    return ([(h["_id"], np.float32(h["_score"] or 0).view(np.int32).item(),
              h.get("sort")) for h in resp["hits"]["hits"]],
            resp["hits"]["total"], resp.get("aggregations"))


def _words(a):
    """int32 words a slot's row of ``a`` takes in the pack."""
    return -(-a[0].size * a.dtype.itemsize // 4)


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.view(np.uint8).tolist()


N_SLOTS = 4
ROUND_TRIP = {
    "int32": np.array([[0, -1, 2**31 - 1]] * N_SLOTS, np.int32)
    * np.arange(1, N_SLOTS + 1, dtype=np.int32)[:, None],
    "float32": np.array([[[np.nan, -0.0], [np.inf, 1e-42]],
                         [[-np.inf, 3.0e38], [0.0, -1.5]]] * 2, np.float32),
    "float32_scalar_a_slot": np.full(N_SLOTS, 0.7, np.float32),
    "int64": np.array([[-2**63, SORTABLE_MISSING],
                       [897436800000, -1],
                       [2**32, 2**31],
                       [-(2**32) - 1, 0]], np.int64),
    "int64_one_a_slot": np.array([1, -1, 2**40, -2**40], np.int64),
    "bool": np.array([[True, False, True]] * N_SLOTS, bool),
    "bool_doc_mask": np.random.RandomState(3).rand(N_SLOTS, 2, 1025) < 0.5,
    "empty": np.zeros((N_SLOTS, 0), np.float32),
}
STAY_OUT = {
    "float64": np.array([[1e300, -0.0]] * N_SLOTS, np.float64),
    "uint8": np.arange(N_SLOTS * 3, dtype=np.uint8).reshape(N_SLOTS, 3),
    "int16": np.ones((N_SLOTS, 2), np.int16),
}


@pytest.mark.parametrize("name", sorted(ROUND_TRIP))
def test_pack_round_trips_every_bit(name):
    want = ROUND_TRIP[name]
    # beside two neighbours, so that its offset is not 0
    arrays = [ROUND_TRIP["int64"], want, ROUND_TRIP["bool"]]
    packed, loose, layout = plan_exec._pack_plan_arrays(arrays, N_SLOTS)
    assert packed.dtype == np.int32 and packed.shape[0] == N_SLOTS
    assert loose == [] and layout[1] == (want.shape[1:], want.dtype.name)
    assert packed.shape == (N_SLOTS, sum(_words(a) for a in arrays))
    got = jax.jit(lambda p: plan_exec._unpack_plan_arrays(layout, p, []))(
        packed)
    for have, orig in zip(got, arrays):
        have = np.asarray(have)
        assert have.dtype == orig.dtype and have.shape == orig.shape
        assert _bits(have) == _bits(orig)


@pytest.mark.parametrize("name", sorted(STAY_OUT))
def test_an_array_that_cannot_go_in_by_its_bits_stays_an_argument(name):
    odd = STAY_OUT[name]
    arrays = [ROUND_TRIP["float32"], odd, ROUND_TRIP["int64"]]
    packed, loose, layout = plan_exec._pack_plan_arrays(arrays, N_SLOTS)
    assert layout[1] is None and len(loose) == 1 and loose[0] is odd
    assert packed.shape == (N_SLOTS, 4 + 2 * 2)
    got = jax.jit(lambda p, rest: plan_exec._unpack_plan_arrays(
        layout, p, rest))(packed, loose)
    for have, orig in zip(got, arrays):
        have = np.asarray(have)
        assert have.dtype == orig.dtype and _bits(have) == _bits(orig)


def test_nothing_to_carry_is_an_empty_pack():
    packed, loose, layout = plan_exec._pack_plan_arrays([], N_SLOTS)
    assert packed.shape == (N_SLOTS, 0) and loose == [] and layout == ()
    assert plan_exec._unpack_plan_arrays(layout, packed, []) == []


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_warm_request_puts_nothing_and_the_launch_carries_its_arrays(
        indices, monkeypatch, case, n_dev):
    body, other, plane = CASES[case]
    idx, host = indices[n_dev], indices[None]
    assert idx._mesh_search._mesh_or_default().devices.size == n_dev
    idx.search(dict(body))  # a first call stages columns and compiles

    launches = []
    launch = plan_exec._launch_locked

    def recording(tracer, run, *args, **kwargs):
        launches.append((run, args))
        return launch(tracer, run, *args, **kwargs)

    carried = []
    pack = plan_exec._pack_plan_arrays

    def recording_pack(arrays, n_slots):
        carried.append(list(arrays))
        return pack(arrays, n_slots)

    counting = _CountingJax()
    monkeypatch.setattr(plan_exec, "_pack_plan_arrays", recording_pack)
    monkeypatch.setattr(plan_exec, "_launch_locked", recording)
    monkeypatch.setattr(plan_exec, "jax", counting)
    h2d0, spans0 = _readings(idx)
    got = idx.search(dict(body))
    h2d1, spans1 = _readings(idx)
    assert got["_plane"] == plane
    assert counting.puts == []  # was one a plan array

    ((run, (_seg, packed, loose)),) = launches
    assert type(packed) is np.ndarray and packed.dtype == np.int32
    assert packed.shape[0] == idx._mesh_search._executor.n_slots
    assert packed.shape[1] > 0 and loose == []
    # what went in: the plan arrays with the dtypes they were built with
    # (a date's bounds cross as the int64 they are), then a traced
    # scalar a slot for each the request names
    arrays = carried[0]
    n_scalars = len(SCALARS.get(case, ()))
    assert len(arrays) > n_scalars
    assert all(a.shape == (packed.shape[0],) and a.dtype == np.float32
               for a in arrays[len(arrays) - n_scalars:])
    assert packed.shape[1] == sum(_words(a) for a in arrays)
    if case == "range_status_histogram":
        assert np.dtype(np.int64) in {a.dtype for a in arrays}
    if case == "match":
        # row_lo, row_hi, kweights, min_match (PallasScoreTermsNode)
        assert len(arrays) == 4
    # (a sort or an aggregation is resolved under a ``staging`` span of
    # its own, ahead of ``execute``'s: a dict lookup on a warm request)
    assert spans1 - spans0 == 1 + ("sort" in body or "aggs" in body)
    assert h2d1 - h2d0 == 1  # were 4 sharded puts for the ``match``

    # host arrays retrace nothing: other values, the same executable
    idx.search(dict(other))
    assert counting.puts == []
    assert launches[1][0] is run
    assert run.__wrapped__._cache_size() == 1

    for request in (body, other):
        mesh_resp, want = idx.search(dict(request)), host.search(dict(request))
        assert mesh_resp["_plane"] == plane and want["_plane"] == "host"
        assert _answer(mesh_resp) == _answer(want)
        assert mesh_resp["hits"]["total"] > 0


def test_counters_reach_stats_beside_the_d2h_count(indices):
    idx = indices[1]
    idx.search(dict(CASES["match"][0]))
    counters = idx.search_stats()["phases"]["counters"]
    assert "explicit_puts_total" not in counters
    assert counters["h2d_arrays_total"] >= 1
    assert counters["d2h_arrays_total"] >= 1
    assert counters["mesh_slots_occupied_total"] > 0


def test_execute_counts_nothing_without_a_telemetry(indices):
    """A bare executor (no index, no telemetry) launches all the same."""
    ms = indices[1]._mesh_search
    ex = ms._executor
    from elasticsearch_tpu.search.query_dsl import (
        ShardQueryContext,
        parse_query,
    )

    qb = parse_query({"match_all": {}})
    plans = []
    for sid, seg in ex.pairs:
        shard = indices[1].shards[sid]
        ctx = ShardQueryContext(shard.mapper_service, engine=shard.engine)
        ctx.for_mesh = True
        plans.append(qb.to_plan(ctx, seg))
    before = dict(indices[1].telemetry.counters)
    outs = ex.execute(plans, 3)
    total = plan_exec._unpack_answer(np.asarray(outs[0]))[3]
    assert int(total) == N_DOCS
    assert dict(indices[1].telemetry.counters) == before
