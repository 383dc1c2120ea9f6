"""A query's answer reaches the host in one transfer, inside the
launch's one wait.

The serial mesh program packs its merged answer (top-k keys, slots,
docs, scores, raw sort values and the total) into ONE ``int32`` array;
``_launch_locked`` copies it, with whatever else the request reads of
the program's outputs, in the one ``jax.device_get`` that also waits
for the program, and the host unpacks it into views. Held here:

- pack and unpack round-trip every bit (``-inf`` keys of unfilled
  ranks, negative zero, totals no float32 or int32 holds);
- a launch makes one ``jax.device_get`` and no ``block_until_ready``,
  of one array or several, all or those the caller names; it counts
  ``d2h_arrays_total`` and ``fetched_launches_total`` once a launch
  (over ``kernel``'s count), and opens no ``merge.d2h`` span;
- the serial path copies exactly the outputs a request reads: the
  packed answer always, the per-slot counts only under
  ``terminate_after``, the views or fused partials of its aggregations
  in the same call;
- a copy that fails is a plane fault, and the next rung serves.

Kernel paths run in interpret mode on the CPU backend.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.parallel.plan_exec import (
    _launch_locked,
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


# -- _launch_locked: one wait a launch --------------------------------------

class _Waits:
    """``plan_exec``'s ``jax``, every ``device_get`` (what it was handed)
    and every ``block_until_ready`` recorded."""

    def __init__(self, fail=0):
        self.gets = []
        self.blocks = 0
        self.fail = fail  # the first ``fail`` copies raise

    def __getattr__(self, name):
        return getattr(jax, name)

    def device_get(self, x):
        self.gets.append(x)
        if len(self.gets) <= self.fail:
            raise RuntimeError("injected: the copy to the host failed")
        return jax.device_get(x)

    def block_until_ready(self, x):
        self.blocks += 1
        return jax.block_until_ready(x)


LAUNCHES = {
    # name: (program, reads, what comes back)
    "one_array": (lambda x: x * 2, None, [[0, 2, 4, 6, 8, 10, 12]]),
    "several_all": (lambda x: (x[:3] * 0, jnp.ones((2, 2)), x[:1].sum()),
                    None, [[0, 0, 0], [[1.0, 1.0], [1.0, 1.0]], 0]),
    "several_some": (lambda x: (x[:3], x * 0, x[-1] + 3), (0, 2),
                     [[0, 1, 2], None, 9]),
}


@pytest.mark.parametrize("case", sorted(LAUNCHES))
def test_a_launch_waits_once_for_what_its_caller_reads(monkeypatch, case):
    program, reads, want = LAUNCHES[case]
    run = jax.jit(program)
    x = jnp.arange(7, dtype=jnp.int32)
    run(x)  # a first call compiles
    waits = _Waits()
    monkeypatch.setattr(plan_exec, "jax", waits)
    tel, tracer = SearchTelemetry(), QueryTracer()
    for _ in range(2):
        got = _launch_locked(tracer, run, x, reads=reads, telemetry=tel)
    assert len(waits.gets) == 2 and waits.blocks == 0
    outs = [got] if case == "one_array" else list(got)
    assert [None if o is None else np.asarray(o).tolist()
            for o in outs] == want
    assert all(o is None or isinstance(o, (np.ndarray, np.generic))
               for o in outs)
    # an output the caller does not read is never handed to the copy
    handed = waits.gets[-1]
    if reads is not None:
        assert [i for i, o in enumerate(handed) if o is not None] == \
            list(reads)
    tel.record_spans(tracer)
    kernels = tel.spans_dict()["kernel"]["count"]
    copied = sum(o is not None for o in outs)
    assert kernels == 2
    assert tel.counters["d2h_arrays_total"] / kernels == copied
    assert tel.counters["fetched_launches_total"] == kernels
    assert {r[0] for r in tracer.closed_spans()} == {
        "kernel", "kernel.lock_wait", "kernel.dispatch",
        "kernel.device_wait"}
    # no telemetry: the spans alone
    _launch_locked(NULL_TRACER, run, x, reads=reads)
    assert tel.counters["fetched_launches_total"] == kernels


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
    c = idx.telemetry.counters
    return (c.get("d2h_arrays_total", 0), c.get("fetched_launches_total", 0),
            idx.telemetry.spans_dict().get("kernel", {}).get("count", 0))


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
    arrays0, fetched0, kernels0 = _d2h(index)
    del drained[:]
    waits = _Waits()
    monkeypatch.setattr(plan_exec, "jax", waits)
    resp = index.search(body)
    arrays1, fetched1, kernels1 = _d2h(index)
    assert resp["_plane"] in ("mesh", "mesh_pallas"), resp["_plane"]
    assert kernels1 - kernels0 == 1
    assert fetched1 - fetched0 == kernels1 - kernels0
    # ONE copy a launch and no other wait: the packed answer (one array
    # where there were six), the counts only under ``terminate_after``,
    # and whatever the aggregations reduce, in the same call
    assert waits.blocks == 0
    (handed,) = waits.gets
    read = [i for i, o in enumerate(handed) if o is not None]
    n_aggs = {"views": 2, "fused_aggs": len(handed) - 2}.get(case, 0)
    assert len(handed) == 2 + n_aggs
    assert read == [0, *([1] if case == "terminate_after" else []),
                    *range(2, 2 + n_aggs)]
    if case == "fused_aggs":
        assert n_aggs >= 1
    assert (arrays1 - arrays0) / (kernels1 - kernels0) == len(read)
    # no span of its own: ``merge`` holds the assembly alone
    (tracer,) = drained
    rows = {i: (name, parent) for name, _s, _e, parent, _self, _a, i
            in tracer.closed_spans()}
    names = [n for n, _p in rows.values()]
    assert "merge.d2h" not in names and "aggregate.fetch" not in names
    assert names.count("merge") == 1
    assert {rows[p][0] for n, p in rows.values()
            if n == "merge.assemble"} == {"merge"}


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


def test_a_failed_copy_is_a_plane_fault_and_the_next_rung_serves(
        monkeypatch):
    """The copy is part of the launch: where it raises, the plane that
    launched is benched as for any fault of its program, and the request
    is answered by the next rung."""
    idx = _build("packedfault")
    host = _build("packedfaulthost", **{"index.search.mesh": False})
    try:
        body = SERIAL_BODIES["plain_top10"]
        assert idx.search(body)["_plane"] == "mesh_pallas"
        waits = _Waits(fail=1)
        monkeypatch.setattr(plan_exec, "jax", waits)
        got = idx.search(body)
        assert len(waits.gets) == 2  # the kernel rung's, then the mesh's
        assert got["_plane"] == "mesh"
        decisions = idx.search_stats()["phases"]["decisions"]
        assert decisions.get("mesh_pallas.fault") == 1
        want = host.search(body)
        assert got["hits"]["total"] == want["hits"]["total"]
        # (the scatter rung sums a document's terms in another order
        # than the host plane: its scores may differ in the last bit)
        assert [h["_id"] for h in got["hits"]["hits"]] == \
            [h["_id"] for h in want["hits"]["hits"]]
        assert [h["_score"] for h in got["hits"]["hits"]] == pytest.approx(
            [h["_score"] for h in want["hits"]["hits"]], rel=1e-6)
    finally:
        idx.close()
        host.close()
