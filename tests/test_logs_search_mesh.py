"""Log search and dashboards on the mesh program (ISSUE 33): the search
operations of Rally's http_logs that score no text (``range``,
``200s-in-range``, ``hourly_agg``, the two timestamp sorts) and the panel
a dashboard puts under its time picker, answered on the device exactly:

- ``date_histogram`` with a named unit of fixed length in UTC and numeric
  ``terms`` take the fused route, bucket for bucket as the host reduce;
- a sort on a column float32 cannot hold ranks by the position of each
  value among the distinct values (epoch milliseconds 1 ms apart);
- a numeric filter compares a column staged once, as int64 in the order
  of the float64 it holds, against bounds that travel alone.

Small, seeded, CPU; the host plane (``index.search.mesh: false``) is the
oracle throughout.
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.search import plan as P
from elasticsearch_tpu.search.aggregations import (
    _calendar_bucket_keys,
    _date_interval_ms,
)

MAPPING = {"properties": {
    "@timestamp": {"type": "date"},
    "status": {"type": "integer"},
    "size": {"type": "integer"},
    "ratio": {"type": "double"},
    "codes": {"type": "integer"},
}}
T0 = 897436800000  # 1998-06-10T00:00:00Z, epoch millis
STEP = 3456
N_DOCS = 240
STATUS = (200, 304, 404, 206, 500)


def _doc(i, rng):
    doc = {"@timestamp": T0 + i * STEP * 400,  # ~4 days in all
           "status": int(rng.choice(STATUS, p=(.6, .2, .1, .05, .05))),
           "size": i,
           "codes": [i % 3, 7]}  # multi-valued
    if i % 5:
        doc["ratio"] = float(i) / 8.0 - 3.0
    return doc


def build(name, mesh=True, docs=None, n_shards=2):
    idx = IndexService(name, Settings({
        "index.number_of_shards": n_shards, "index.refresh_interval": -1,
        "index.search.mesh": mesh}), mapping=MAPPING)
    rng = np.random.RandomState(33)
    for i, doc in enumerate(docs if docs is not None else
                            [_doc(i, rng) for i in range(N_DOCS)]):
        idx.index_doc(str(i), doc)
    idx.refresh()
    return idx


@pytest.fixture(scope="module")
def pair():
    mesh, host = build("logs33-mesh"), build("logs33-host", mesh=False)
    yield mesh, host
    mesh.close()
    host.close()


def planes(idx):
    return idx.search_stats()["planes"]


def decisions(idx):
    return idx.search_stats()["phases"]["decisions"]


def spans(idx):
    return idx.search_stats()["spans"]


LO, HI = T0 + 30 * STEP * 400, T0 + 200 * STEP * 400  # both on a document
IN_RANGE = {"range": {"@timestamp": {"gte": LO, "lt": HI}}}
HOURS = {"date_histogram": {"field": "@timestamp", "interval": "hour"}}
SIX = {
    "hourly_agg": {"size": 0, "aggs": {"by_hour": HOURS}},
    "panel": {"size": 0, "query": IN_RANGE, "aggs": {
        "by_hour": HOURS, "status": {"terms": {"field": "status"}}}},
    "range": {"query": IN_RANGE},
    "200s-in-range": {"query": {"bool": {"must": [
        IN_RANGE, {"match": {"status": 200}}]}}},
    "desc_sort_timestamp": {"query": {"match_all": {}},
                            "sort": [{"@timestamp": "desc"}]},
    "asc_sort_timestamp": {"query": {"match_all": {}},
                           "sort": [{"@timestamp": "asc"}]},
}


def same_answer(got, want, hits="ids"):
    assert got["hits"]["total"] == want["hits"]["total"]
    key = ((lambda h: (h["_id"], h.get("sort"))) if hits == "ids"
           else (lambda h: h.get("sort")))
    assert ([key(h) for h in got["hits"]["hits"]]
            == [key(h) for h in want["hits"]["hits"]])
    assert got.get("aggregations") == want.get("aggregations")


# ----------------------------------------------------------------------
# The six operations stay on the mesh program
# ----------------------------------------------------------------------


def test_the_six_operations_are_answered_by_the_mesh_program(pair):
    mesh, host = pair
    before = planes(mesh)
    for name, body in SIX.items():
        got, want = mesh.search(dict(body)), host.search(dict(body))
        assert got["_plane"] == "mesh", name
        assert want["_plane"] == "host", name
        same_answer(got, want)
        if "sort" not in body and body.get("size") != 0:
            # every match ties at the constant score: any ten will do,
            # and both planes take the first ten in document order
            clauses = 2.0 if "bool" in body["query"] else 1.0
            assert {h["_score"] for h in got["hits"]["hits"]} == {clauses}
    after = planes(mesh)
    assert after["agg_host_fallback_total"] == 0
    assert after["agg_fused_query_total"] - before["agg_fused_query_total"] == 2
    assert (after["sort_device_query_total"]
            - before["sort_device_query_total"]) == 2
    assert "host.sort_ineligible" not in decisions(mesh)
    assert after["mesh_query_total"] - before["mesh_query_total"] == 6


def test_what_a_request_needs_is_staged_once(pair):
    """A warm request stages nothing: the spans that wrap a staging are
    opened by the request that stages, and by no other."""
    mesh, _host = pair
    for body in SIX.values():
        mesh.search(dict(body))
    seen = spans(mesh)
    # hour codes, status ordinals (the two filter columns are staged
    # where the plan is built, inside ``plan_build``); two orders
    assert seen["staging.doc_values"]["count"] == 2
    assert seen["staging.sort_column"]["count"] == 2
    for body in SIX.values():
        mesh.search(dict(body))
    again = spans(mesh)
    assert again["staging.doc_values"] == seen["staging.doc_values"]
    assert again["staging.sort_column"] == seen["staging.sort_column"]
    # the reduce's two leaves tile their parent: once an aggregating request
    n = again["aggregate"]["count"]
    assert again["aggregate.fetch"]["count"] == n
    assert again["aggregate.finalize"]["count"] == n
    assert (again["aggregate.fetch"]["sum_ns"]
            + again["aggregate.finalize"]["sum_ns"]
            <= again["aggregate"]["sum_ns"])


def test_a_column_staged_later_compiles_no_program_again(monkeypatch):
    """The program takes the columns it reads, not all that are staged:
    another request's sort column leaves its executable alone."""
    build_program = plan_exec._mesh_query_program
    handed = []

    def recording(*args, **kwargs):
        handed.append(build_program(*args, **kwargs))
        return handed[-1]

    monkeypatch.setattr(plan_exec, "_mesh_query_program", recording)
    idx = build("logs33-retrace")
    try:
        body = dict(SIX["range"], size=7)  # a program of this test's own
        idx.search(dict(body))
        first = handed[0]
        assert first.__wrapped__._cache_size() == 1
        idx.search(dict(SIX["desc_sort_timestamp"]))  # stages msort.*
        idx.search(dict(SIX["panel"]))                # stages maggs.*
        idx.search(dict(body))
        assert handed[-1] is first
        assert first.__wrapped__._cache_size() == 1
    finally:
        idx.close()


# ----------------------------------------------------------------------
# date_histogram: named units of one length in UTC
# ----------------------------------------------------------------------

LEAP_AND_NEGATIVE = np.array(
    [0, -1, 1, -86_400_000, 86_399_999, -3_600_001,
     951_782_399_999, 951_782_400_000,    # 2000-02-29T00:00 and 1 ms before
     951_868_799_999, 951_868_800_000,    # 2000-03-01T00:00
     1_078_012_800_000, 1_078_099_199_999,  # 2004-02-29
     -2_208_988_800_000, -2_208_988_800_001,  # 1900-01-01 (no leap day)
     T0, T0 + 3_599_999, T0 + 3_600_000], np.int64)


@pytest.mark.parametrize("unit", ["second", "minute", "hour", "day"])
def test_a_named_fixed_unit_cuts_where_the_calendar_does(unit):
    rng = np.random.RandomState(7)
    millis = np.concatenate([
        LEAP_AND_NEGATIVE, rng.randint(-4 * 10**12, 4 * 10**12, 5000)])
    ms = _date_interval_ms(unit)
    assert ms == _date_interval_ms({"second": "1s", "minute": "1m",
                                    "hour": "1h", "day": "1d"}[unit])
    fixed = (np.floor(millis.astype(np.float64) / ms) * ms).astype(np.int64)
    assert np.array_equal(fixed, _calendar_bucket_keys(millis, unit))


@pytest.mark.parametrize("unit", ["week", "month", "quarter", "year"])
def test_calendar_units_have_no_fixed_length(unit):
    assert _date_interval_ms(unit) is None


@pytest.mark.parametrize("body", [
    {"interval": "hour"}, {"interval": "day"}, {"interval": "1h"},
    {"fixed_interval": "day"}, {"interval": "hour", "time_zone": "UTC"},
    {"interval": "hour", "offset": 600_000},
    {"interval": "day", "offset": -7_200_000},
    {"interval": "1h", "offset": 1},
    {"interval": "hour", "min_doc_count": 1},
], ids=lambda b: "-".join(f"{k}={v}" for k, v in b.items()))
def test_fused_date_histogram_equals_the_host_reduce(pair, body):
    mesh, host = pair
    request = {"size": 0, "query": IN_RANGE, "aggs": {"h": {
        "date_histogram": {"field": "@timestamp", **body}}}}
    before = planes(mesh)
    got, want = mesh.search(dict(request)), host.search(dict(request))
    same_answer(got, want)
    buckets = got["aggregations"]["h"]["buckets"]
    assert sum(b["doc_count"] for b in buckets) == got["hits"]["total"] == 170
    after = planes(mesh)
    assert after["agg_fused_query_total"] == before["agg_fused_query_total"] + 1
    assert after["agg_host_fallback_total"] == before["agg_host_fallback_total"]
    if "offset" not in body:
        ms = _date_interval_ms(body.get("interval") or body["fixed_interval"])
        assert all(b["key"] % ms == 0 for b in buckets)


@pytest.mark.parametrize("body", [
    {"interval": "week"}, {"interval": "month"}, {"interval": "quarter"},
    {"interval": "year"}, {"interval": "hour", "time_zone": "+01:00"},
    {"interval": "hour", "time_zone": "Europe/Paris"},
    {"interval": "hour", "offset": "+1h"},
], ids=lambda b: "-".join(f"{k}={v}" for k, v in b.items()))
def test_what_is_not_a_fixed_length_in_utc_stays_on_the_host(body):
    idx, host = build("logs33-cal"), build("logs33-cal-host", mesh=False)
    try:
        request = {"size": 0, "aggs": {"h": {
            "date_histogram": {"field": "@timestamp", **body}}}}
        if isinstance(body.get("offset"), str):
            # the host reduce owns the error of an offset it cannot read
            with pytest.raises(Exception):
                host.search(dict(request))
        else:
            same_answer(idx.search(dict(request)), host.search(dict(request)))
        if not isinstance(body.get("offset"), str):
            assert planes(idx)["agg_host_fallback_by_reason"] == {
                "unsupported_params": 1}
            assert planes(idx)["agg_fused_query_total"] == 0
    finally:
        idx.close()
        host.close()


# ----------------------------------------------------------------------
# terms on a numeric column
# ----------------------------------------------------------------------


@pytest.mark.parametrize("body", [
    {"field": "status"},
    {"field": "status", "size": 2},
    {"field": "status", "size": 3, "order": {"_key": "desc"}},
    {"field": "status", "order": {"_count": "asc"}},
    {"field": "size", "size": 5},            # 240 distinct values, all ties
    {"field": "ratio", "size": 4},           # non-integer keys, some missing
    {"field": "@timestamp", "size": 3},      # a date column's numbers
], ids=lambda b: "-".join(f"{k}={v}" for k, v in b.items()))
def test_fused_numeric_terms_equal_the_host_reduce(pair, body):
    mesh, host = pair
    request = {"size": 0, "query": IN_RANGE,
               "aggs": {"t": {"terms": body}, "by_hour": HOURS}}
    before = planes(mesh)
    got, want = mesh.search(dict(request)), host.search(dict(request))
    same_answer(got, want)
    keys = [b["key"] for b in got["aggregations"]["t"]["buckets"]]
    assert keys and all(isinstance(k, (int, float)) for k in keys)
    if body["field"] == "status":
        assert all(isinstance(k, int) for k in keys)
    after = planes(mesh)
    assert after["agg_fused_query_total"] == before["agg_fused_query_total"] + 1
    assert after["agg_host_fallback_total"] == before["agg_host_fallback_total"]


def test_numeric_terms_outside_the_envelope_stay_on_the_host(monkeypatch):
    from elasticsearch_tpu.search import fused_aggs

    idx, host = build("logs33-terms"), build("logs33-terms-h", mesh=False)
    try:
        multi = {"size": 0, "aggs": {"t": {"terms": {"field": "codes"}}}}
        same_answer(idx.search(dict(multi)), host.search(dict(multi)))
        monkeypatch.setattr(fused_aggs, "MAX_TERMS_ORDS", 100)
        wide = {"size": 0, "aggs": {"t": {"terms": {"field": "size"}}}}
        same_answer(idx.search(dict(wide)), host.search(dict(wide)))
        assert planes(idx)["agg_host_fallback_by_reason"] == {
            "multi_valued": 1, "bucket_range": 1}
    finally:
        idx.close()
        host.close()


# ----------------------------------------------------------------------
# A sort on values float32 cannot hold
# ----------------------------------------------------------------------

BIG = float(1 << 40)  # float32 resolves 65,536 here


def _sort_docs():
    rng = np.random.RandomState(5)
    docs = []
    for i in range(120):
        doc = {"size": i}
        if i % 7:
            # 1 ms apart, with ties (every value twice) across the shards
            doc["@timestamp"] = int(BIG) + int(rng.randint(0, 40))
        docs.append(doc)
    return docs


@pytest.fixture(scope="module")
def sort_pair():
    docs = _sort_docs()
    mesh = build("logs33-sort", docs=docs)
    host = build("logs33-sort-host", mesh=False, docs=docs)
    yield mesh, host
    mesh.close()
    host.close()


@pytest.mark.parametrize("missing", [None, "_last", "_first"])
@pytest.mark.parametrize("order", ["asc", "desc"])
def test_timestamp_sort_equals_the_host_path(sort_pair, order, missing):
    mesh, host = sort_pair
    spec = {"order": order}
    if missing:
        spec["missing"] = missing
    body = {"query": {"match_all": {}}, "size": 120,
            "sort": [{"@timestamp": spec}]}
    got, want = mesh.search(dict(body)), host.search(dict(body))
    assert got["_plane"] == "mesh" and want["_plane"] == "host"
    # values, missing documents' nulls and ties by (shard, document): all
    same_answer(got, want)
    values = [h["sort"][0] for h in got["hits"]["hits"]]
    real = [v for v in values if v is not None]
    assert len(real) == 102 and len(set(real)) > 30
    assert all(isinstance(v, float) and v >= BIG for v in real)
    assert real == sorted(real, reverse=order == "desc")
    nulls_first = missing == "_first"
    assert (values[0] is None) == nulls_first
    assert (values[-1] is None) == (not nulls_first)


@pytest.mark.parametrize("order", ["asc", "desc"])
def test_search_after_pages_like_the_host_path(sort_pair, order):
    mesh, host = sort_pair
    body = {"query": {"match_all": {}}, "size": 9,
            "sort": [{"@timestamp": order}]}
    first = mesh.search(dict(body))
    cursors = [first["hits"]["hits"][-1]["sort"],  # a value that is there
               [BIG + 17.5],                       # between two values
               [BIG - 5.0], [BIG + 1e6],           # below and above all
               [None]]                             # a missing document's
    for cursor in cursors:
        page = dict(body, search_after=cursor)
        got, want = mesh.search(dict(page)), host.search(dict(page))
        assert got["_plane"] == "mesh", cursor
        # (a page of missing documents alone is all ties: the host path
        # cuts each shard's candidates before it merges them, and which
        # nine of them it keeps is its own affair)
        after_all = cursor == [BIG + 1e6 if order == "asc" else BIG - 5.0]
        same_answer(got, want, hits="values" if after_all else "ids")
    assert "host.sort_ineligible" not in decisions(mesh)
    assert "host.feature_ineligible" not in decisions(mesh)


def test_a_custom_missing_value_among_ordinals_stays_on_the_host(sort_pair):
    mesh, host = sort_pair
    body = {"query": {"match_all": {}}, "size": 20, "sort": [
        {"@timestamp": {"order": "asc", "missing": 1024}}]}
    before = decisions(mesh).get("host.sort_ineligible", 0)
    got, want = mesh.search(dict(body)), host.search(dict(body))
    assert got["_plane"] == "host"
    same_answer(got, want)
    assert decisions(mesh)["host.sort_ineligible"] == before + 1


def test_a_column_float32_holds_is_its_own_key(sort_pair):
    mesh, host = sort_pair
    body = {"query": {"match_all": {}}, "size": 15,
            "sort": [{"size": "desc"}]}
    got = mesh.search(dict(body))
    same_answer(got, host.search(dict(body)))
    ex = mesh._mesh_search._executor
    assert ex.sort_meta["msort.size.desc._last"] == {"vocab": None}
    assert isinstance(
        ex.sort_meta["msort.@timestamp.asc._last"]["vocab"], np.ndarray)


# ----------------------------------------------------------------------
# Numeric filters on a column staged once
# ----------------------------------------------------------------------


def test_sortable_int64_keeps_the_order_of_float64():
    values = np.array([-np.inf, -1e300, -2.5, -1.0, -5e-324, -0.0, 0.0,
                       5e-324, 1.0, np.nextafter(1.0, 2.0), float(T0),
                       np.nextafter(float(T0), np.inf), 2.0**53,
                       1e300, np.inf])
    keys = P.sortable_int64(values)
    assert keys.dtype == np.int64
    assert keys[5] == keys[6]  # -0.0 and 0.0 compare equal, as floats do
    rest = np.delete(keys, 5)
    assert np.all(np.diff(rest) > 0)
    assert P.SORTABLE_MISSING > keys[-1]
    assert P.sortable_int64(np.nan) < P.SORTABLE_MISSING


@pytest.mark.parametrize("bounds, want", [
    ({"gte": LO, "lt": HI}, 170),      # Rally's form; both on a document
    ({"gt": LO, "lte": HI}, 170),
    ({"gt": LO, "lt": HI}, 169),
    ({"gte": LO, "lte": HI}, 171),
    ({"gte": LO + 1}, 209), ({"lt": LO}, 30), ({"lte": LO - 1}, 30),
    ({"gte": T0 - 10**12, "lt": T0 + 10**12}, 240),
    ({"gt": T0 + 10**12}, 0),
], ids=str)
def test_a_bound_on_a_documents_own_value_cuts_exactly(pair, bounds, want):
    mesh, host = pair
    body = {"query": {"range": {"@timestamp": bounds}}, "size": 3}
    got = mesh.search(dict(body))
    assert got["_plane"] == "mesh"
    assert got["hits"]["total"] == want
    same_answer(got, host.search(dict(body)))


@pytest.mark.parametrize("query", [
    {"term": {"status": 404}},
    {"terms": {"status": [200, 500, 999]}},
    {"match": {"status": "206"}},
    {"range": {"ratio": {"gte": -0.5, "lt": 11.125}}},   # some missing
    {"term": {"ratio": 0.0}},
    {"bool": {"filter": [{"range": {"size": {"gte": 10, "lte": 99}}}],
              "must_not": [{"term": {"status": 200}}]}},
], ids=lambda q: next(iter(q)) + ":" + next(iter(next(iter(q.values())))))
def test_staged_numeric_filters_equal_the_host_plane(pair, query):
    mesh, host = pair
    body = {"query": query, "size": 240}
    got, want = mesh.search(dict(body)), host.search(dict(body))
    assert got["_plane"] == "mesh"
    assert got["hits"]["total"] == want["hits"]["total"] > 0
    assert (sorted(h["_id"] for h in got["hits"]["hits"])
            == sorted(h["_id"] for h in want["hits"]["hits"]))
    assert ({h["_score"] for h in got["hits"]["hits"]}
            == {h["_score"] for h in want["hits"]["hits"]})


def test_a_multi_valued_column_keeps_the_node_that_carries_it(pair):
    mesh, host = pair
    body = {"query": {"range": {"codes": {"gte": 2, "lte": 6}}}, "size": 240}
    got, want = mesh.search(dict(body)), host.search(dict(body))
    assert got["_plane"] == "mesh"
    assert got["hits"]["total"] == want["hits"]["total"] == 80
    ex = mesh._mesh_search._executor
    assert "mnum.codes" not in ex._seg_staged
    assert ex._agg_field_checks["codes"]["single"] is False
    staged = ex._seg_staged["mnum.@timestamp"]
    assert staged.dtype == np.int64 and staged.shape == (ex.n_slots, ex.nd1)


def test_a_filter_aggregation_is_planned_for_the_host_reduce(pair):
    """Outside the fused set: the host reduce plans the ``filter`` body
    over each segment's own arrays, where no staged column is."""
    mesh, host = pair
    body = {"size": 0, "query": IN_RANGE, "aggs": {"big": {
        "filter": {"range": {"size": {"gte": 100}}},
        "aggs": {"codes": {"terms": {"field": "status"}}}}}}
    before = planes(mesh)["agg_host_fallback_by_reason"].get(
        "unsupported_agg", 0)
    got = mesh.search(dict(body))
    assert got["_plane"] == "mesh"
    same_answer(got, host.search(dict(body)))
    assert got["aggregations"]["big"]["doc_count"] == 100
    assert planes(mesh)["agg_host_fallback_by_reason"][
        "unsupported_agg"] == before + 1
