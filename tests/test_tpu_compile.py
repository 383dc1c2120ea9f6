"""Compile the served path's kernels for a described (not attached) v5e.

Interpret mode cannot see what the TPU compiler refuses: VMEM budgets,
tiling alignment, mosaic legalization. These cases lower and compile the
kernels at the 1M-doc geometry the chip serves, against a ``v5e:2x2``
topology description, so a refusal costs a test failure here instead of
a chip call. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker imports
every test file).
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from elasticsearch_tpu.ops import pallas_aggs as pag
from elasticsearch_tpu.ops import pallas_knn as pkn
from elasticsearch_tpu.ops import pallas_scoring as psc
from elasticsearch_tpu.search.fused_aggs import DENSE_COUNT_MAX_BUCKETS

ND_PAD = 1 << 20
# 1M docs x ~80 tokens / 128 postings per block, rounded up
N_BLOCKS = 1 << 19


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises when it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """Shape of an array that lives on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


SCORE_TILES_CASES = {
    "raw_q1": dict(),
    "raw_q8": dict(q_batch=8),
    "packed_q1": dict(codec="packed"),
    "dense_counts": dict(dense=True, with_counts=True),
    "raw_tps2": dict(tiles_per_step=2),
    "tile_ids": dict(n_sel=16),
}


@pytest.mark.parametrize("case", sorted(SCORE_TILES_CASES))
def test_score_tiles_compiles_at_1m_docs(sds, case):
    kw = dict(SCORE_TILES_CASES[case])
    n_sel = kw.pop("n_sel", None)
    geom = psc.tile_geometry(ND_PAD)
    t_pad, cb = 4, psc.CB_MAX // 2  # 3-term match; widest DMA window
    q_batch = kw.get("q_batch", 1)
    n_rows = n_sel if n_sel is not None else geom.n_tiles
    blocks = (N_BLOCKS + psc.CB_MAX, psc.LANE)
    docs = sds(blocks, jnp.int32)
    frac = None if kw.get("codec") == "packed" else sds(blocks, jnp.float32)
    args = [docs, frac,
            sds((geom.n_tiles * psc.LANE, geom.tile_sub), jnp.float32),
            sds((n_rows, t_pad), jnp.int32),
            sds((n_rows, t_pad), jnp.int32),
            sds((q_batch, t_pad), jnp.float32)]
    if n_sel is not None:
        kw["tile_ids"] = sds((n_sel,), jnp.int32)
    text = psc.score_tiles.lower(
        *args, t_pad=t_pad, cb=cb, sub=geom.tile_sub, k=10,
        **kw).compile().as_text()
    assert "tpu_custom_call" in text


def test_score_tiles_custom_call_keeps_the_name_the_roofline_reads(sds):
    """``bm25_roofline.serial`` finds the kernel in a device trace by
    the pattern its metric file holds. The name is the one
    ``pl.pallas_call(..., name="score_tiles")`` states: a rename must
    fail here, not empty the metric on the chip."""
    import json
    import re

    held = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "layer_metrics", "bm25_roofline.serial.json")))
    pattern = re.compile(held["params"]["op_pattern"])
    geom = psc.tile_geometry(1 << 17)
    blocks = ((1 << 16) + psc.CB_MAX, psc.LANE)
    text = psc.score_tiles.lower(
        sds(blocks, jnp.int32), sds(blocks, jnp.float32),
        sds((geom.n_tiles * psc.LANE, geom.tile_sub), jnp.float32),
        sds((geom.n_tiles, 4), jnp.int32),
        sds((geom.n_tiles, 4), jnp.int32), sds((1, 4), jnp.float32),
        t_pad=4, cb=psc.CB_MAX // 2, sub=geom.tile_sub,
        k=10).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert calls and all(pattern.search(c) for c in calls), calls


# msmarco-serial's staged kernel tables on its one chip: 4 slots (2 live,
# 2 headroom) of 80,922 rows, each padded to a CB_MAX multiple
CELL_SLOTS = 4
CELL_ROWS = 80922
CELL_ROWS_PAD = -(-CELL_ROWS // psc.CB_MAX) * psc.CB_MAX


def _table_sized_ops(text):
    """Instructions of a compiled module that produce or copy an array
    with the cell's table row count (one slot's or the whole table's),
    other than the kernel's custom call, bitcasts and what hands a
    buffer's address on (a conditional takes its branch's operands as a
    tuple): each is a pass over 41 MB or more that a query pays before
    it scores anything."""
    import re

    rows = {CELL_ROWS, CELL_ROWS_PAD, CELL_SLOTS * CELL_ROWS_PAD}
    sized = re.compile(
        r"[\[,](%s)[,\]]" % "|".join(str(n) for n in sorted(rows)))
    instruction = re.compile(r"(ROOT )?%[\w.-]+ = ")
    found = []
    for line in text.splitlines():
        line = line.strip()
        if not instruction.match(line) or not sized.search(line):
            continue  # module header, a computation's signature, small
        if (" parameter(" in line or " bitcast(" in line
                or " tuple(" in line or " get-tuple-element(" in line
                or ("custom-call(" in line and "tpu_custom_call" in line)):
            continue
        found.append(line[:200])
    return found


def _four_slot_scores(row_base_of):
    """``score_tiles`` called once per slot, as the mesh programs'
    ``per_device`` unrolls it; ``row_base_of`` None slices slot ``i``
    out of a stacked table, the shape staged before ISSUE 26."""
    geom = psc.tile_geometry(1 << 16)
    kw = dict(t_pad=8, cb=16, sub=geom.tile_sub, dense=True)

    def run(kd, kf, lt, rl, rh, w):
        outs = []
        for i in range(CELL_SLOTS):
            if row_base_of is None:
                outs.append(psc.score_tiles(
                    kd[i], kf[i], lt[i], rl[i], rh[i], w[i], **kw)[0])
            else:
                outs.append(psc.score_tiles(
                    kd, kf, lt[i], rl[i], rh[i], w[i],
                    row_base=row_base_of(i), **kw)[0])
        return outs

    small = [((CELL_SLOTS, geom.n_tiles * psc.LANE, geom.tile_sub),
              jnp.float32),
             ((CELL_SLOTS, geom.n_tiles, 8), jnp.int32),
             ((CELL_SLOTS, geom.n_tiles, 8), jnp.int32),
             ((CELL_SLOTS, 1, 8), jnp.float32)]
    return jax.jit(run), small


def test_four_slots_read_a_flat_table_in_place(sds):
    """The kernel reads each slot's rows of the flat table in place
    (``row_base`` moves its row tables, which its index maps read): the
    compiled program holds the four custom calls and no pass over the
    table."""
    run, small = _four_slot_scores(lambda i: i * CELL_ROWS_PAD)
    flat = (CELL_SLOTS * CELL_ROWS_PAD, psc.LANE)
    text = run.lower(sds(flat, jnp.int32), sds(flat, jnp.float32),
                     *(sds(*a) for a in small)).compile().as_text()
    assert text.count("tpu_custom_call") >= CELL_SLOTS
    assert _table_sized_ops(text) == []


def test_four_slots_sliced_from_a_stacked_table_are_copied(sds):
    """The twin: the table stacked [slots, rows, 128] and sliced per
    slot, as it was staged before. The TPU interleaves the slots row by
    row, so every slice is a strided gather and a re-tiling; this is
    the fault the case above must be able to see."""
    run, small = _four_slot_scores(None)
    stacked = (CELL_SLOTS, CELL_ROWS, psc.LANE)
    text = run.lower(sds(stacked, jnp.int32), sds(stacked, jnp.float32),
                     *(sds(*a) for a in small)).compile().as_text()
    found = _table_sized_ops(text)
    assert any(" copy(" in line or " slice(" in line for line in found), \
        found


@pytest.mark.parametrize("q_batch", [1, 8])
@pytest.mark.parametrize("dims", [128, 768, 1536])
def test_knn_score_tiles_compiles_at_1m_docs(sds, dims, q_batch):
    d_pad = pkn.pad_dims(dims)
    geom = pkn.knn_geometry(ND_PAD, d_pad)  # the production geometry
    text = pkn.knn_score_tiles.lower(
        sds((ND_PAD, d_pad), jnp.bfloat16),
        sds((ND_PAD, 1), jnp.float32),
        sds((ND_PAD, 1), jnp.float32),
        sds((q_batch, d_pad), jnp.float32),
        sub=geom.tile_sub, k=16, q_batch=q_batch).compile().as_text()
    assert "tpu_custom_call" in text


def test_segment_aggregate_compiles_at_1m_docs(sds):
    text = pag.segment_aggregate.lower(
        sds((ND_PAD,), jnp.int32), sds((ND_PAD,), jnp.float32),
        sds((ND_PAD,), jnp.float32),
        n_ords=2000, with_sum=True).compile().as_text()
    assert "tpu_custom_call" in text


def _conditionals(text):
    """How many conditionals a compiled module holds."""
    return sum(" conditional(" in line for line in text.splitlines())


def _branch_bodies(text):
    """The text of a compiled module's conditional branches (the
    computations named in ``branch_computations``, or the true and the
    false computation of a predicated one)."""
    import re

    names = set(re.findall(r"(?:true|false)_computation=(%[\w.-]+)", text))
    for group in re.findall(r"branch_computations=\{([^}]*)\}", text):
        names.update(name.strip() for name in group.split(","))
    bodies, keep = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            keep = line.split(" ", 1)[0] in names
        if keep:
            bodies.append(line)
    return "\n".join(bodies)


def _for_tpu(plan):
    """Copy of a template plan with its kernel nodes out of interpret
    mode: what the same query traces to on a TPU backend."""
    import copy

    from elasticsearch_tpu.search.plan import PlanNode

    plan = copy.copy(plan)
    for name, val in vars(plan).items():
        if name == "interpret":
            setattr(plan, name, False)
        elif isinstance(val, PlanNode):
            setattr(plan, name, _for_tpu(val))
        elif isinstance(val, list) and val and all(
                isinstance(v, PlanNode) for v in val):
            setattr(plan, name, [_for_tpu(v) for v in val])
    return plan


def _recording_programs(monkeypatch):
    """(the real ``_mesh_query_program``, what its last launch saw: the
    template holder, the builder's arguments, the launch's arrays)."""
    from elasticsearch_tpu.parallel import plan_exec

    build_program = plan_exec._mesh_query_program
    seen = {}

    def recording(mesh, holder, *args, **kwargs):
        program = build_program(mesh, holder, *args, **kwargs)

        def call(*arrays):
            seen.update(holder=holder, args=args, kwargs=kwargs,
                        arrays=arrays)
            return program(*arrays)

        return call

    monkeypatch.setattr(plan_exec, "_mesh_query_program", recording)
    return build_program, seen


def _recorded_serial_launch(monkeypatch, n_shards, n_devices, n_docs):
    """A scratch harness: a small index is searched on virtual CPU
    devices in interpret mode and the call into ``_mesh_query_program``
    is recorded. Returns (the real program builder, what was seen: the
    template holder, the builder's arguments, the launch's arrays)."""
    import numpy as np

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.parallel import plan_exec
    from elasticsearch_tpu.parallel.mesh import shard_mesh

    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    build_program, seen = _recording_programs(monkeypatch)
    idx = IndexService("tpucompile", Settings({
        "index.number_of_shards": n_shards,
        "index.search.mesh": True,
        "index.refresh_interval": -1,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    idx._mesh_search = plan_exec.IndexMeshSearch(
        idx, mesh=shard_mesh(n_devices))
    rng = np.random.RandomState(5)
    try:
        for d in range(n_docs):  # ~750 docs a shard: 8-sublane tiles
            idx.index_doc(str(d), {"body": " ".join(
                f"w{t}" for t in rng.zipf(1.3, 12) % 200)})
        idx.refresh()
        resp = idx.search({"query": {"match": {"body": "w1 w2 w3"}},
                           "size": 10})
    finally:
        idx.close()
    assert resp["_plane"] == "mesh_pallas"
    return build_program, seen


def _compiled_for_tpu(build_program, seen, devices, seg_shapes=None):
    """The recorded serial program built again on a mesh of described
    TPU devices and compiled from the recorded shapes (``seg_shapes``
    replaces those of the staged arrays it names). The shapes are the
    small index's: this finds what real chips refuse, not what 1M
    documents need."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from elasticsearch_tpu.parallel import plan_exec

    holder = seen["holder"]
    tpu_mesh = Mesh(np.asarray(devices), ("shards",))
    program = build_program(
        tpu_mesh,
        plan_exec._TemplateHolder(_for_tpu(holder.plan),
                                  holder._key + f"|tpu{len(devices)}"),
        *seen["args"], **seen["kwargs"])
    sharded = NamedSharding(tpu_mesh, PS("shards"))
    replicated = NamedSharding(tpu_mesh, PS())

    def shapes(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=sharding), tree)

    seg, *per_slot, scalars = seen["arrays"]
    seg = shapes(seg, sharded)
    for name, shape in (seg_shapes or {}).items():
        seg[name] = jax.ShapeDtypeStruct(shape, seg[name].dtype,
                                         sharding=sharded)
    return program.__wrapped__.lower(
        seg, *(shapes(t, sharded) for t in per_slot),
        shapes(scalars, replicated)).compile()


def test_serial_mesh_program_compiles_for_four_chips(topo, monkeypatch):
    """The serial mesh program (shard_map + tile kernel + ICI merge) on a
    Mesh of the four described devices."""
    build_program, seen = _recorded_serial_launch(
        monkeypatch, n_shards=8, n_devices=4, n_docs=6000)
    text = _compiled_for_tpu(build_program, seen, topo.devices).as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    # every device guards each of its slots, and the merge's collectives
    # stay outside the branches (a device that skips must still meet them)
    assert _conditionals(text) == seen["kwargs"]["spd"]
    assert "all-gather" not in _branch_bodies(text)


def test_serial_mesh_program_reads_the_cells_tables_in_place(
        topo, monkeypatch):
    """``msmarco-serial``'s program: 2 shards on one chip, so 4 slots
    (one of headroom a shard), with the kernel tables at the cell's
    size. Every query launches it, so a pass over a slot's table (41 MB)
    in it is paid by every query: there is none, and one launch of the
    kernel per slot, each behind the conditional that skips a slot with
    no live document (ISSUE 32): a conditional that copied its operands
    would copy the tables for every slot of every query."""
    build_program, seen = _recorded_serial_launch(
        monkeypatch, n_shards=2, n_devices=1, n_docs=1500)
    assert seen["kwargs"]["spd"] == CELL_SLOTS
    flat = (CELL_SLOTS * CELL_ROWS_PAD, psc.LANE)
    text = _compiled_for_tpu(build_program, seen, topo.devices[:1],
                             {"k_docs": flat, "k_frac": flat}).as_text()
    assert text.count("tpu_custom_call") >= CELL_SLOTS
    assert _table_sized_ops(text) == []
    assert _conditionals(text) == CELL_SLOTS
    # the kernel and the sort are inside the branches, nowhere else
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == CELL_SLOTS
    assert all(line in _branch_bodies(text) for line in calls)


@pytest.mark.parametrize("chips, n_shards, n_docs",
                         [(1, 2, 1500), (4, 8, 6000)])
def test_serial_mesh_program_has_one_replicated_merge_output(
        topo, monkeypatch, chips, n_shards, n_docs):
    """A query's merged answer leaves the device as ONE array: the
    program's first output is the packed ``int32[2 + 5k]`` (the total's
    two words, five rows of ``k``), on every chip alike; what follows
    keeps a row per slot (the counts; views and fused partials where a
    request asks for them) and is fetched only by the requests that
    need it."""
    import numpy as np

    from elasticsearch_tpu.parallel import plan_exec

    build_program, seen = _recorded_serial_launch(
        monkeypatch, n_shards=n_shards, n_devices=chips, n_docs=n_docs)
    compiled = _compiled_for_tpu(build_program, seen, topo.devices[:chips])
    k = seen["args"][0]
    n_slots = chips * seen["kwargs"]["spd"]
    packed, counts = compiled.out_info
    assert (packed.shape, packed.dtype) == ((2 + 5 * k,), np.int32)
    assert (counts.shape, counts.dtype) == ((n_slots,), np.int64)
    packed_sharding, counts_sharding = compiled.output_shardings
    assert packed_sharding.is_fully_replicated
    assert chips == 1 or not counts_sharding.is_fully_replicated
    # the host's unpack reads that very layout
    rows = plan_exec._unpack_answer(np.zeros(packed.shape, packed.dtype))
    assert [r.shape for r in rows] == [(k,), (k,), (k,), (), (k,), (k,)]


# ----------------------------------------------------------------------
# The log-search cell's programs (ISSUE 33): no text scored, the scatter
# rung; range filters on a staged int64 column, fused histogram and
# terms counts, rank-keyed sorts
# ----------------------------------------------------------------------

# (the cell pads to 65,537 documents a slot; the described chip's
# compiler takes 30 s a program at that size, most of it the top-k's
# sort, and 3 s at this one: what it refuses does not depend on it)
LOGS_ND1 = (1 << 12) + 1
LOGS_T0 = 897436800000
def test_a_launchs_packed_plan_arrays_unpack_on_the_chip(sds):
    """``_unpack_plan_arrays`` (ISSUE 36) for the described chip, every
    dtype the pack takes, at the cell's four slots: an int64 from two
    words on the emulated 64-bit path, a document-sized bool mask from
    four to a word. (The two serial cells' own programs, which unpack
    int32, float32 and int64, compile below.)"""
    import numpy as np

    from elasticsearch_tpu.parallel import plan_exec

    arrays = [np.zeros((CELL_SLOTS, 2), np.int64),
              np.zeros((CELL_SLOTS, 65537), bool),
              np.zeros((CELL_SLOTS, 1, 16), np.float32),
              np.zeros((CELL_SLOTS, 1, 16), np.int32),
              np.zeros((CELL_SLOTS,), np.float32)]
    packed, loose, layout = plan_exec._pack_plan_arrays(arrays, CELL_SLOTS)
    assert loose == []

    def run(packed, column):
        bounds, mask, weights, rows, scalar = plan_exec._unpack_plan_arrays(
            layout, packed, [])
        hit = (column >= bounds[:, :1]) & (column < bounds[:, 1:]) & mask
        return jnp.sum(hit, axis=1), weights * scalar[0] + rows

    text = jax.jit(run).lower(
        sds(packed.shape, jnp.int32),
        sds((CELL_SLOTS, 65537), jnp.int64)).compile().as_text()
    assert "f64" not in text


LOGS_DOCS = 300


def _logs_ts(d):
    """Document ``d``'s timestamp: the documents span as many hours as
    the dense count takes buckets, so the panel's histogram is the
    largest that formulation serves."""
    return LOGS_T0 + d * (DENSE_COUNT_MAX_BUCKETS - 1) * 3_600_000 // (
        LOGS_DOCS - 1)


LOGS_RANGE = {"range": {"@timestamp": {
    "gte": _logs_ts(20), "lt": _logs_ts(200)}}}
LOGS_HOURS = {"date_histogram": {"field": "@timestamp", "interval": "hour"}}
LOGS_REQUESTS = {  # (hourly_agg and range are parts of these)
    "panel": {"size": 0, "query": LOGS_RANGE, "aggs": {
        "by_hour": LOGS_HOURS, "status": {"terms": {"field": "status"}}}},
    "200s-in-range": {"query": {"bool": {"must": [
        LOGS_RANGE, {"match": {"status": 200}}]}}},
    "desc_sort_timestamp": {"query": {"match_all": {}},
                            "sort": [{"@timestamp": "desc"}]},
}


@pytest.mark.parametrize("name", sorted(LOGS_REQUESTS))
def test_log_search_programs_compile_for_one_chip(topo, monkeypatch, name):
    """The request shapes of ``http-logs-search-serial`` for one
    described chip, four slots. No float64 reaches the
    device: a TPU emulates it in fewer bits than it has (an exclusive
    bound on a document's own timestamp let the document in on the chip,
    PERF.md 6, PR 33), so the filters compare int64 in the order of the
    float64 values and the sorts rank by ordinal."""
    import numpy as np

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.parallel import plan_exec
    from elasticsearch_tpu.parallel.mesh import shard_mesh

    build_program, seen = _recording_programs(monkeypatch)
    idx = IndexService(f"tpulogs-{name}", Settings({
        "index.number_of_shards": 2, "index.search.mesh": True,
        "index.refresh_interval": -1,
    }), mapping={"properties": {"@timestamp": {"type": "date"},
                                "status": {"type": "integer"}}})
    idx._mesh_search = plan_exec.IndexMeshSearch(idx, mesh=shard_mesh(1))
    try:
        for d in range(LOGS_DOCS):
            idx.index_doc(str(d), {"@timestamp": _logs_ts(d),
                                   "status": (200, 304, 404)[d % 3]})
        idx.refresh()
        resp = idx.search(dict(LOGS_REQUESTS[name]))
    finally:
        idx.close()
    assert resp["_plane"] == "mesh"
    seg = seen["arrays"][0]
    small = seg["live1"].shape[1]
    at_size = {n: tuple(LOGS_ND1 if d == small else d for d in a.shape)
               for n, a in seg.items() if small in a.shape}
    assert "live1" in at_size and at_size["live1"] == (4, LOGS_ND1)
    # the request's own columns are in the argument, and no other's
    on_demand = {n for n in seg if n.startswith(plan_exec._ON_DEMAND)}
    assert on_demand == {
        "panel": {"maggs.hist.@timestamp.date_histogram.3600000.0.0.0",
                  "maggs.nord.status", "mnum.@timestamp"},
        "200s-in-range": {"mnum.@timestamp", "mnum.status"},
        "desc_sort_timestamp": {"msort.@timestamp.desc._last",
                                "msort.@timestamp.desc._last.raw"},
    }[name]
    assert all(seg[n].dtype == np.int64 for n in on_demand
               if n.startswith("mnum."))
    compiled = _compiled_for_tpu(build_program, seen, topo.devices[:1],
                                 at_size)
    text = compiled.as_text()
    assert "f64[" not in text
    assert "tpu_custom_call" not in text  # no kernel: the scatter rung
    assert _conditionals(text) == 4
    if name == "panel":
        # the counts compare and sum: no scatter-add, and nothing of
        # buckets x documents is written to memory (the program's
        # temporaries, all of them, are smaller than ONE such array)
        hist = "maggs.hist.@timestamp.date_histogram.3600000.0.0.0"
        assert seen["kwargs"]["agg_static"] == (
            ("bucket", hist, DENSE_COUNT_MAX_BUCKETS),
            ("bucket", "maggs.nord.status", 3))
        assert " scatter(" not in text
        assert (compiled.memory_analysis().temp_size_in_bytes
                < DENSE_COUNT_MAX_BUCKETS * LOGS_ND1 * 4)


# cohere-768-knn-serial's staged embeddings on its one chip: 4 slots (2
# live, 2 of headroom) of 131,072 rows (75,000 documents a shard), 768
# wide, read by q_batch 1, k 16
KNN_SLOT_ROWS = 1 << 17
KNN_D_PAD = 768


@pytest.mark.parametrize("chips, spd", [(1, 4), (4, 2)])
def test_knn_mesh_program_reads_the_flat_embeddings_in_place(
        topo, chips, spd):
    """The flat kNN program at the cell's size, for one described chip
    and for ``v5e:2x2``: one launch of the kernel a slot, each behind the
    conditional that skips a slot with no live vector, no pass over a
    slot's embeddings (200 MB) or the whole table outside the kernel,
    the merge's collectives outside the branches, and ONE output (the
    packed answers)."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from elasticsearch_tpu.parallel import plan_exec

    mesh = Mesh(np.asarray(topo.devices[:chips]), ("shards",))
    sub = pkn.knn_tile_sub(KNN_SLOT_ROWS, KNN_D_PAD)
    assert sub == 16  # 2,048 documents a tile at 768 wide
    k = 16
    program = plan_exec._mesh_knn_program(
        mesh, spd, 1, k, sub, KNN_D_PAD, KNN_SLOT_ROWS,
        "max_inner_product", False)
    sharded = NamedSharding(mesh, PS("shards"))
    rows = chips * spd * KNN_SLOT_ROWS

    def on(shape, dtype, sharding=sharded):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = program.__wrapped__.lower(
        on((rows, KNN_D_PAD), jnp.bfloat16), on((rows, 1), jnp.float32),
        on((rows, 1), jnp.float32),
        on((1, KNN_D_PAD), jnp.float32,
           NamedSharding(mesh, PS()))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == spd
    assert all("knn_tiles" in line for line in calls)
    assert _conditionals(text) == spd
    assert all(line in _branch_bodies(text) for line in calls)
    if chips > 1:
        assert "all-gather" in text
        assert "all-gather" not in _branch_bodies(text)
    wide = re.compile(r"bf16\[\d+,%d\]" % KNN_D_PAD)
    passes = [line.strip()[:160] for line in text.splitlines()
              if wide.search(line) and re.match(r"\s*(ROOT )?%[\w.-]+ = ",
                                                line)
              and not any(op in line for op in (
                  " parameter(", " bitcast(", " tuple(",
                  " get-tuple-element(", "tpu_custom_call",
                  " conditional("))]
    assert passes == []
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (1, 2 + 5 * k) and out.dtype == jnp.int32


# msmarco-hybrid-serial's staged embeddings on its one chip: 4 slots (2
# live, 2 of headroom) of 32,768 rows (25,000 documents a shard), 768 wide
HYBRID_SLOT_ROWS = 1 << 15


def test_hybrid_mesh_program_compiles_for_one_chip(topo, monkeypatch):
    """The hybrid program (``_mesh_query_program`` with ``knn``) as a
    small 2-shard index launches it, rebuilt for one described chip with
    the cell's flat embedding table: one kNN kernel and one tile kernel a
    slot, each behind a conditional that skips a slot with no live
    document or vector, no pass over a slot's embeddings outside the
    kernel, and ONE packed answer first among the outputs."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.parallel import plan_exec
    from elasticsearch_tpu.parallel.mesh import shard_mesh

    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    build_program, seen = _recording_programs(monkeypatch)
    idx = IndexService("tpucompile-hybrid", Settings({
        "index.number_of_shards": 2, "index.refresh_interval": -1,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"},
        "emb": {"type": "dense_vector", "dims": KNN_D_PAD,
                "similarity": "max_inner_product"}}})
    idx._mesh_search = plan_exec.IndexMeshSearch(idx, mesh=shard_mesh(1))
    rng = np.random.RandomState(6)
    vecs = np.round(rng.standard_normal((600, KNN_D_PAD)) / 28, 6)
    try:
        for d in range(600):
            idx.index_doc(str(d), {"body": " ".join(
                f"w{t}" for t in rng.zipf(1.3, 12) % 200),
                "emb": vecs[d].tolist()})
        idx.refresh()
        resp = idx.search({"query": {"match": {"body": "w1 w2 w3"}},
                           "knn": {"field": "emb", "k": 10, "boost": 10,
                                   "query_vector": vecs[7].tolist()},
                           "size": 10})
    finally:
        idx.close()
    assert resp["_plane"] == "mesh_pallas"
    kk, k_knn, _sub, d_pad, _nd, metric, _interp = seen["kwargs"]["knn"]
    spd = seen["kwargs"]["spd"]
    assert (spd, d_pad) == (CELL_SLOTS, KNN_D_PAD)
    sub = pkn.knn_tile_sub(HYBRID_SLOT_ROWS, KNN_D_PAD)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("shards",))
    holder = seen["holder"]
    program = build_program(
        mesh, plan_exec._TemplateHolder(_for_tpu(holder.plan),
                                        holder._key + "|tpu1"),
        *seen["args"], **dict(seen["kwargs"], knn=(
            kk, k_knn, sub, KNN_D_PAD, HYBRID_SLOT_ROWS, metric, False)))
    sharded = NamedSharding(mesh, PS("shards"))

    def on(a, shape=None):
        return jax.ShapeDtypeStruct(np.shape(a) if shape is None else shape,
                                    a.dtype, sharding=sharded)

    seg, packed, loose, emb, scale, mask = seen["arrays"]
    rows = spd * HYBRID_SLOT_ROWS
    compiled = program.__wrapped__.lower(
        jax.tree_util.tree_map(on, seg), on(packed),
        jax.tree_util.tree_map(on, loose), on(emb, (rows, KNN_D_PAD)),
        on(scale, (rows, 1)), on(mask, (rows, 1))).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert sum("knn_tiles" in line for line in calls) == spd
    assert len(calls) == 2 * spd
    assert _conditionals(text) == 2 * spd
    assert all(line in _branch_bodies(text) for line in calls)
    wide = re.compile(r"bf16\[\d+,%d\]" % KNN_D_PAD)
    assert [line.strip()[:160] for line in text.splitlines()
            if wide.search(line)
            and re.match(r"\s*(ROOT )?%[\w.-]+ = ", line)
            and not any(op in line for op in (
                " parameter(", " bitcast(", " tuple(",
                " get-tuple-element(", "tpu_custom_call",
                " conditional("))] == []
    k = seen["args"][0]
    packed_out = jax.tree_util.tree_leaves(compiled.out_info)[0]
    assert (packed_out.shape, packed_out.dtype) == ((2 + 5 * k,), jnp.int32)
