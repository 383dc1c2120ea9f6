"""A scored term is planned from its lane's metadata.

``term_blocks_arrays`` resolves each term to one lane (its posting-block
run, its weight, its similarity's constants) and nothing per block; the
scatter node's per-block gather arrays are expanded from the lanes by
``scatter_block_arrays`` only where a ``ScoreTermsNode`` is built, and
``build_tile_tables`` plans every live lane of a kernel node in one
vectorized pass. Held here against the per-block and per-lane loops they
replaced, kept below as oracles:

- the tile tables equal the per-lane loop's, array for array and dtype
  for dtype, over random lane sets (empty runs, zero weights, a given
  ``t_pad``, a given ``cb``), on several geometries and a ladder rung
  below the staged one; each ``ValueError`` still raises, with the same
  lane named;
- ``build_tile_tables_sets`` plans every (shard, segment) pair of a
  kernel node group in one pass over one flat array of block bounds:
  each pair's tables equal the per-pair loop's for 1, 2, 3 and 8 pairs
  (a pair with no live lane among them), and where a dense term forces
  one or two rungs down, the rung, ``cb`` and each node's ``sub`` and
  ``live_key`` that ``harmonize_kernel_nodes`` sets are the per-pair
  ladder's; an unsorted run, or blocks no rung fits, is a
  ``PlanStructureMismatch`` as before; ``_stats`` counts one pass a
  kernel node group and the rungs descended;
- the scatter node's nine arrays and ``kinds`` equal the per-block
  loop's bit for bit: BM25 at default and other ``k1``/``b``, a
  similarity that reads ``ttf``, several kinds in one node, a term one
  shard's segment lacks, and the padded length the unsatisfiable
  ``min_match`` pins;
- a ``match`` served by the kernel plane (interpret mode) expands no
  block (``plan_blocks_expanded_total`` stays 0 in ``_stats``), counts
  its lanes, and answers as the host plane does; the scatter rung counts
  the rows it expands.
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.ops import pallas_scoring as psc
from elasticsearch_tpu.ops.pallas_scoring import (
    CB_MAX,
    LANE,
    QueryLane,
    block_min_max,
    build_tile_tables,
    build_tile_tables_batched,
    next_pow2,
    tile_geometry,
)
from elasticsearch_tpu.ops.scoring import B, K1
from elasticsearch_tpu.parallel import plan_exec
from elasticsearch_tpu.parallel.mesh import shard_mesh
from elasticsearch_tpu.search import plan as P
from elasticsearch_tpu.search import query_dsl
from elasticsearch_tpu.search.query_dsl import (
    ShardQueryContext,
    scatter_block_arrays,
    score_terms_node,
    term_blocks_arrays,
)

# ---------------------------------------------------------------------------
# Oracles: the per-lane table loop and the per-block expansion as they were
# ---------------------------------------------------------------------------


def oracle_tile_tables(lanes, bmin, bmax, geom, t_pad=None, cb=None):
    w = geom.tile_w
    n_tiles = geom.n_tiles
    t_pad = t_pad or next_pow2(max(len(lanes), 1))
    row_lo = np.zeros((n_tiles, t_pad), dtype=np.int32)
    row_hi = np.zeros((n_tiles, t_pad), dtype=np.int32)
    weights = np.zeros((1, t_pad), dtype=np.float32)
    tile_lo = np.arange(n_tiles, dtype=np.int64) * w
    need = 1
    for j, lane in enumerate(lanes):
        s, c = lane.block_start, lane.block_count
        if c <= 0 or lane.weight == 0.0:
            continue
        tb_min = bmin[s: s + c]
        tb_max = bmax[s: s + c]
        if c > 1 and (np.any(np.diff(tb_min) < 0)
                      or np.any(np.diff(tb_max) < 0)):
            raise ValueError(f"lane {j}: per-block doc ranges not sorted")
        first = np.searchsorted(tb_max, tile_lo, side="left")
        end = np.searchsorted(tb_min, tile_lo + w, side="left")
        end = np.maximum(end, first)
        row_lo[:, j] = s + first
        row_hi[:, j] = s + end
        weights[0, j] = lane.weight
        need = max(need, int((end - first).max()))
    cb_req = next_pow2(max(need, 8))
    if cb_req > CB_MAX // 2:
        raise ValueError("per-tile covering window exceeds the kernel bound")
    if cb is not None:
        if cb < cb_req:
            raise ValueError(f"cb={cb} too small, need {cb_req}")
        if cb > CB_MAX // 2 or cb & (cb - 1):
            raise ValueError(f"cb={cb} must be a power of two")
        cb_req = cb
    return row_lo, row_hi, weights, cb_req


def _pad_pow2(lst, pad_value, dtype):
    n = 8
    while n < len(lst):
        n *= 2
    return np.asarray(list(lst) + [pad_value] * (n - len(lst)), dtype=dtype)


def oracle_block_arrays(segment, weighted_terms, ctx=None):
    blocks, weights, rows, avgdls = [], [], [], []
    p1s, p2s, p3s, kind_ids = [], [], [], []
    kinds = []
    for field, token, boost in weighted_terms:
        tid = segment.term_id(field, token)
        if tid < 0:
            continue
        st = segment.field_stats.get(field, {})
        row = segment.field_norm_idx.get(field, 0)
        avgdl = segment.field_avgdl(field)
        sim = ((ctx.similarity(field) if ctx is not None else None)
               or query_dsl._DEFAULT_BM25)
        kind, w, p1, p2, p3 = sim.lane_params({
            "df": int(segment.term_doc_freq[tid]),
            "ttf": segment.term_ttf(tid) if sim.needs_ttf else 0,
            "doc_count": st.get("doc_count", 0),
            "sum_ttf": st.get("sum_ttf", 0),
            "avgdl": avgdl,
            "boost": boost,
        })
        if kind not in kinds:
            kinds.append(kind)
        kid = kinds.index(kind)
        start = int(segment.term_block_start[tid])
        for bi in range(start, start + int(segment.term_block_count[tid])):
            blocks.append(bi)
            weights.append(w)
            rows.append(row)
            avgdls.append(avgdl)
            p1s.append(p1)
            p2s.append(p2)
            p3s.append(p3)
            kind_ids.append(kid)
    return {
        "q_blocks": _pad_pow2(blocks, 0, np.int32),
        "q_weights": _pad_pow2(weights, 0.0, np.float32),
        "q_norm_rows": _pad_pow2(rows, 0, np.int32),
        "q_avgdl": _pad_pow2(avgdls, 1.0, np.float32),
        "q_valid": _pad_pow2([True] * len(blocks), False, bool),
        "q_p1": _pad_pow2(p1s, 1.0, np.float32),
        "q_p2": _pad_pow2(p2s, 1.0, np.float32),
        "q_p3": _pad_pow2(p3s, 0.0, np.float32),
        "q_kinds": _pad_pow2(kind_ids, 0, np.int32),
        "kinds": tuple(kinds) if kinds else ("bm25",),
    }


BLOCK_ARRAYS = ("q_blocks", "q_weights", "q_norm_rows", "q_avgdl", "q_valid",
                "q_p1", "q_p2", "q_p3", "q_kinds")


def assert_bits_equal(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def assert_tables_equal(got, want):
    for g, w in zip(got[:3], want[:3]):
        assert_bits_equal(g, w)
    assert got[3] == want[3]


# ---------------------------------------------------------------------------
# build_tile_tables: one pass over every lane
# ---------------------------------------------------------------------------


def block_runs(rng, nd, n_terms, max_df):
    """Block-packed postings as SegmentBuilder.seal() lays them out: the
    per-block [min, max] doc of each term's run, and its (start, count)."""
    nd_pad = next_pow2(nd)
    mins, maxs, runs = [], [], []
    for i in range(n_terms):
        # every fourth term is dense: its run spans many blocks
        df = nd if i % 4 == 0 else rng.randint(1, max_df)
        docs = np.sort(rng.choice(nd, size=min(df, nd), replace=False))
        nb = -(-len(docs) // LANE)
        runs.append((len(mins), nb))
        for b in range(nb):
            chunk = docs[b * LANE:(b + 1) * LANE]
            mins.append(int(chunk[0]))
            maxs.append(int(chunk[-1]))
    return (np.asarray(mins, np.int64), np.asarray(maxs, np.int64), runs,
            nd_pad)


def random_lanes(rng, runs, n_lanes):
    lanes = []
    for _ in range(n_lanes):
        s, c = runs[rng.randint(len(runs))]
        roll = rng.randint(6)
        if roll == 0:
            c = 0  # an empty run: skipped
        weight = 0.0 if roll == 1 else float(rng.uniform(0.1, 4.0))
        lanes.append(QueryLane(s, c, weight))
    return lanes


def both(fn_args, **kw):
    """(vectorized, oracle) outcomes: the tables, or the ValueError."""
    out = []
    for fn in (build_tile_tables, oracle_tile_tables):
        try:
            out.append(fn(*fn_args, **kw))
        except ValueError as e:
            out.append(e)
    return out


class TestTileTablesOnePass:
    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("tile_sub", [128, 64, 32, 8, 1])
    def test_random_lane_sets_match_the_per_lane_loop(self, seed, tile_sub):
        rng = np.random.RandomState(seed)
        bmin, bmax, runs, nd_pad = block_runs(rng, 6000, 24, 700)
        geom = tile_geometry(nd_pad, tile_sub)
        raised = 0
        for n_lanes in (1, 3, 7, 12):
            lanes = random_lanes(rng, runs, n_lanes)
            t_pad = None if n_lanes % 2 else next_pow2(n_lanes) * 2
            got, want = both((lanes, bmin, bmax, geom), t_pad=t_pad)
            if isinstance(want, ValueError):
                raised += 1
                assert isinstance(got, ValueError)
                continue
            assert_tables_equal(got, want)
            # a given cb at or above the need is the one returned
            cb = max(want[3], 16)
            if cb <= CB_MAX // 2:
                got, want = both((lanes, bmin, bmax, geom), t_pad=t_pad,
                                 cb=cb)
                assert_tables_equal(got, want)
        assert raised < 4 or tile_sub == 128

    @pytest.mark.parametrize("seed", range(3))
    def test_one_ladder_rung_below_the_staged_geometry(self, seed):
        """Where the staged tile overflows the window, the next rung down
        plans the same tables as the per-lane loop."""
        rng = np.random.RandomState(100 + seed)
        bmin, bmax, runs, nd_pad = block_runs(rng, 1 << 16, 8, 3000)
        lanes = [QueryLane(s, c, 1.0 + i) for i, (s, c) in enumerate(runs)]
        staged = tile_geometry(nd_pad, 128)
        got, want = both((lanes, bmin, bmax, staged))
        assert isinstance(got, ValueError) and isinstance(want, ValueError)
        rung = tile_geometry(nd_pad, 64)
        got, want = both((lanes, bmin, bmax, rung))
        assert not isinstance(want, ValueError)
        assert_tables_equal(got, want)

    def test_all_lanes_dead(self):
        bmin, bmax, runs, nd_pad = block_runs(np.random.RandomState(9),
                                              2000, 4, 300)
        lanes = [QueryLane(runs[0][0], 0, 1.0), QueryLane(*runs[1], 0.0)]
        geom = tile_geometry(nd_pad, 4)
        got, want = both((lanes, bmin, bmax, geom), t_pad=4)
        assert_tables_equal(got, want)
        assert not got[2].any() and got[3] == 8

    def test_batched_tables_are_the_union_planned_once(self):
        rng = np.random.RandomState(11)
        bmin, bmax, runs, nd_pad = block_runs(rng, 5000, 16, 400)
        geom = tile_geometry(nd_pad, 16)
        sets = [random_lanes(rng, runs, n) for n in (2, 5, 3)]
        union, weights = psc.union_query_lanes(sets)
        t_pad = weights.shape[1] * 2
        rl, rh, w, cb = build_tile_tables_batched(sets, bmin, bmax, geom,
                                                  t_pad=t_pad)
        want = oracle_tile_tables(union, bmin, bmax, geom, t_pad=t_pad)
        assert_tables_equal((rl, rh, want[2], cb), want)
        assert w.shape == (3, t_pad)


# ---------------------------------------------------------------------------
# build_tile_tables_sets: every (shard, segment) pair's tables in one pass
# ---------------------------------------------------------------------------


def oracle_harmonize(lane_sets, bounds, geom):
    """The per-pair ladder harmonize_kernel_nodes ran before one pass
    served every pair: each rung rebuilt every pair's tables. Returns
    (tables a pair, shared cb, geometry), or raises
    PlanStructureMismatch where no rung serves."""
    t_pad = max(next_pow2(max(len(lanes), 1)) for lanes in lane_sets)
    sub = geom.tile_sub
    while True:
        g = geom if sub == geom.tile_sub else tile_geometry(geom.nd_pad, sub)
        try:
            tables = [oracle_tile_tables(lanes, bmin, bmax, g, t_pad=t_pad)
                      for lanes, (bmin, bmax) in zip(lane_sets, bounds)]
            break
        except ValueError:
            if sub <= 32 or g.tile_sub < sub:
                raise plan_exec.PlanStructureMismatch("no shared geometry")
            sub //= 2
    return tables, max(t[3] for t in tables), g


def flat(bounds):
    """One array of every pair's block bounds, and each pair's first row
    in it: what the kernel session keeps as ``bounds``/``bound_base``."""
    bases = np.cumsum([0] + [len(b) for b, _ in bounds[:-1]])
    return (np.concatenate([b for b, _ in bounds]),
            np.concatenate([b for _, b in bounds]), [int(b) for b in bases])


def dense_run(first_doc, n_docs):
    """Block bounds of a term in every document of [first_doc, first_doc
    + n_docs), 128 postings a block."""
    docs = np.arange(first_doc, first_doc + n_docs)
    nb = -(-n_docs // LANE)
    return (docs[::LANE].astype(np.int64),
            docs[np.minimum(np.arange(1, nb + 1) * LANE, n_docs) - 1]
            .astype(np.int64))


class _Staged:
    """What harmonize_kernel_nodes reads of a staged executor: the kernel
    session's geometry and flat bounds, and the ladder's live masks."""

    harmonize = plan_exec.MeshPlanExecutor.harmonize_kernel_nodes

    def __init__(self, geom, bmin, bmax):
        self._kernel = {"geom": geom, "bounds": (bmin, bmax)}

    def ensure_kernel_live(self, sub):
        return f"k_live_t_{sub}"


def deferred(lane_sets, bases):
    return [P.PallasScoreTermsNode.mesh_deferred(lanes, base, 1,
                                                 interpret=True)
            for lanes, base in zip(lane_sets, bases)]


class TestTileTablesAcrossPairs:
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("n_pairs", [1, 2, 3, 8])
    def test_random_pairs_match_the_per_pair_loop(self, seed, n_pairs):
        rng = np.random.RandomState(200 + seed)
        bounds, sets = [], []
        for i in range(n_pairs):
            bmin, bmax, runs, nd_pad = block_runs(rng, 3000 + 500 * i, 12,
                                                  400)
            bounds.append((bmin, bmax))
            lanes = random_lanes(rng, runs, rng.randint(1, 9))
            if i == 1:  # a pair with no live lane: empty runs, weight 0
                lanes = [QueryLane(s, 0, 1.0) if k % 2 else
                         QueryLane(s, c, 0.0)
                         for k, (s, c, _) in enumerate(lanes)]
            sets.append(lanes)
        bmin, bmax, bases = flat(bounds)
        geom = tile_geometry(8192, 4)
        t_pad = max(next_pow2(max(len(lanes), 1)) for lanes in sets)
        for given in (None, 2 * t_pad):
            want = [oracle_tile_tables(lanes, b0, b1, geom,
                                       t_pad=given or t_pad)
                    for lanes, (b0, b1) in zip(sets, bounds)]
            rl, rh, w, cb, g = psc.build_tile_tables_sets(
                sets, bmin, bmax, geom, t_pad=given, bases=bases)
            assert g == geom and cb == max(t[3] for t in want)
            assert rl.shape == (n_pairs, geom.n_tiles, given or t_pad)
            for i, t in enumerate(want):
                assert_tables_equal((rl[i], rh[i], w[i], t[3]), t)
        # a given cb at or above every pair's need is the one returned
        rl, rh, w, cb, _ = psc.build_tile_tables_sets(
            sets, bmin, bmax, geom, cb=32, bases=bases)
        assert cb == 32
        for i, (lanes, (b0, b1)) in enumerate(zip(sets, bounds)):
            assert_tables_equal((rl[i], rh[i], w[i], cb), oracle_tile_tables(
                lanes, b0, b1, geom, t_pad=t_pad, cb=32))
        if n_pairs > 1:
            assert not w[1].any() and not rl[1].any() and not rh[1].any()

    @pytest.mark.parametrize("n_pairs", [1, 2, 3, 8])
    @pytest.mark.parametrize("first_doc,rungs", [(0, 0), (0, 1), (1, 2)])
    def test_dense_term_takes_the_per_pair_ladder_rung(self, n_pairs,
                                                       first_doc, rungs):
        """A dense term on ONE pair shrinks every pair's tile: 16,384
        aligned documents fit at half the staged tile, one document later
        they need a quarter. The pass chooses the rung, the shared cb, the
        tables and each node's sub and live_key as the per-pair ladder."""
        rng = np.random.RandomState(300 + n_pairs)
        bounds, sets = [], []
        for i in range(n_pairs):
            bmin, bmax, runs, _ = block_runs(rng, 5000, 10, 600)
            lanes = random_lanes(rng, runs, 4)
            if i == n_pairs - 1 and rungs:
                dmin, dmax = dense_run(first_doc, 1 << 14)
                lanes.append(QueryLane(len(bmin), len(dmin), 1.25))
                bmin = np.concatenate([bmin, dmin])
                bmax = np.concatenate([bmax, dmax])
            bounds.append((bmin, bmax))
            sets.append(lanes)
        staged = tile_geometry(1 << 15, 128)
        want, want_cb, want_g = oracle_harmonize(sets, bounds, staged)
        assert want_g.tile_sub == 128 >> rungs
        bmin, bmax, bases = flat(bounds)
        rl, rh, w, cb, g = psc.build_tile_tables_sets(
            sets, bmin, bmax, staged, t_pad=want[0][0].shape[1],
            bases=bases, ladder=True)
        assert g == want_g and cb == want_cb
        nodes = deferred(sets, bases)
        assert _Staged(staged, bmin, bmax).harmonize(nodes) == (1, rungs)
        live_key = "k_live_t" if not rungs else f"k_live_t_{128 >> rungs}"
        for i, (node, t) in enumerate(zip(nodes, want)):
            assert_tables_equal((rl[i], rh[i], w[i], cb), t[:3] + (cb,))
            assert_tables_equal((node.row_lo, node.row_hi, node.kweights,
                                 node.cb), t[:3] + (want_cb,))
            assert (node.sub, node.live_key) == (want_g.tile_sub, live_key)
            assert node.key() == nodes[0].key()
            # a node holds views of the group's arrays, not copies
            assert node.row_lo.base is not None

    def test_without_the_ladder_the_staged_tile_raises(self):
        dmin, dmax = dense_run(0, 1 << 14)
        with pytest.raises(ValueError, match="exceeds the kernel bound"):
            psc.build_tile_tables_sets(
                [[QueryLane(0, len(dmin), 1.0)]], dmin, dmax,
                tile_geometry(1 << 15, 128))

    def test_no_rung_fits_overlapping_blocks(self):
        """Blocks that each span the whole doc space are sorted but fit no
        rung: the ladder walks to its floor and the group falls back."""
        n = 100
        bmin = np.zeros(n, np.int64)
        bmax = np.full(n, (1 << 15) - 1, np.int64)
        sets = [[QueryLane(0, n, 1.0)], [QueryLane(0, 2, 1.0)]]
        bounds = [(bmin, bmax), (bmin[:2], bmax[:2])]
        staged = tile_geometry(1 << 15, 128)
        with pytest.raises(plan_exec.PlanStructureMismatch):
            oracle_harmonize(sets, bounds, staged)
        fb0, fb1, bases = flat(bounds)
        with pytest.raises(ValueError, match="exceeds the kernel bound"):
            psc.build_tile_tables_sets(sets, fb0, fb1, staged, bases=bases,
                                       ladder=True)
        with pytest.raises(plan_exec.PlanStructureMismatch):
            _Staged(staged, fb0, fb1).harmonize(deferred(sets, bases))

    @pytest.mark.parametrize("which", ["bmin", "bmax"])
    def test_unsorted_run_is_a_plan_structure_mismatch(self, which):
        rng = np.random.RandomState(17)
        bounds, sets = [], []
        for i in range(3):
            bmin, bmax, runs, _ = block_runs(rng, 4000, 8, 500)
            bounds.append((bmin.copy(), bmax.copy()))
            sets.append([QueryLane(*runs[1], 1.0), QueryLane(*runs[4], 2.0)])
        s, c = sets[2][1][:2]  # a dense run of the third pair
        arr = bounds[2][0 if which == "bmin" else 1]
        arr[s + 1], arr[s + 2] = arr[s + 2], arr[s + 1]
        staged = tile_geometry(4096, 4)
        with pytest.raises(ValueError, match="lane 1: per-block doc"):
            oracle_tile_tables(sets[2], *bounds[2], staged)
        with pytest.raises(plan_exec.PlanStructureMismatch):
            oracle_harmonize(sets, bounds, staged)
        bmin, bmax, bases = flat(bounds)
        with pytest.raises(ValueError, match="set 2, lane 1: per-block doc "
                                             "ranges not sorted"):
            psc.build_tile_tables_sets(sets, bmin, bmax, staged, bases=bases,
                                       ladder=True)
        with pytest.raises(plan_exec.PlanStructureMismatch):
            _Staged(staged, bmin, bmax).harmonize(deferred(sets, bases))


class TestTileTableErrors:
    def setup_method(self):
        self.bmin, self.bmax, self.runs, self.nd_pad = block_runs(
            np.random.RandomState(3), 4000, 8, 500)
        self.geom = tile_geometry(self.nd_pad, 4)

    @pytest.mark.parametrize("which", ["bmin", "bmax"])
    def test_unsorted_run_names_the_same_lane(self, which):
        bmin, bmax = self.bmin.copy(), self.bmax.copy()
        s, c = self.runs[4]  # a dense run: many blocks
        assert c > 2
        arr = bmin if which == "bmin" else bmax
        arr[s + 1], arr[s + 2] = arr[s + 2], arr[s + 1]
        lanes = [QueryLane(*self.runs[1], 1.0), QueryLane(*self.runs[2], 1.0),
                 QueryLane(s, c, 2.0)]
        for fn in (build_tile_tables, oracle_tile_tables):
            with pytest.raises(ValueError, match="lane 2: per-block doc "
                                                 "ranges not sorted"):
                fn(lanes, bmin, bmax, self.geom)

    def test_empty_mid_run_block_is_unsorted(self):
        """An all-padding block inside a run (bmax -1) breaks sortedness."""
        nd = 2048
        docs = np.arange(nd).reshape(-1, LANE)
        tfs = np.ones(docs.shape, np.float32)
        tfs[3] = 0.0
        bmin, bmax = block_min_max(docs, tfs, nd)
        with pytest.raises(ValueError, match="lane 0"):
            build_tile_tables([QueryLane(0, docs.shape[0], 1.0)], bmin, bmax,
                              tile_geometry(nd, 4))

    def test_window_over_the_kernel_bound(self):
        nd = 1 << 16
        docs = np.arange(nd).reshape(-1, LANE)
        bmin, bmax = block_min_max(docs, np.ones(docs.shape, np.float32), nd)
        lanes = [QueryLane(0, docs.shape[0], 1.5)]
        with pytest.raises(ValueError, match="exceeds the kernel bound"):
            build_tile_tables(lanes, bmin, bmax, tile_geometry(nd, 128))
        rl, rh, w, cb = build_tile_tables(lanes, bmin, bmax,
                                          tile_geometry(nd, 32))
        assert cb <= CB_MAX // 2

    @pytest.mark.parametrize("cb,match", [
        (8, None), (4, "too small"), (24, "power of two"),
        (CB_MAX, "power of two")])
    def test_cb_checks(self, cb, match):
        lanes = [QueryLane(*self.runs[1], 1.0)]
        need = oracle_tile_tables(lanes, self.bmin, self.bmax, self.geom)[3]
        assert need == 8
        if match is None:
            assert build_tile_tables(lanes, self.bmin, self.bmax, self.geom,
                                     cb=cb)[3] == cb
            return
        with pytest.raises(ValueError, match=match):
            build_tile_tables(lanes, self.bmin, self.bmax, self.geom, cb=cb)


# ---------------------------------------------------------------------------
# The scatter node's arrays, expanded from the lanes
# ---------------------------------------------------------------------------

SIM_SETTINGS = {
    "index.similarity.tuned.type": "BM25",
    "index.similarity.tuned.k1": 1.7,
    "index.similarity.tuned.b": 0.4,
    "index.similarity.dfr.type": "DFR",
    "index.similarity.dfr.basic_model": "if",
    "index.similarity.dfr.after_effect": "b",
    "index.similarity.dfr.normalization": "h1",
}
MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "tuned": {"type": "text", "analyzer": "whitespace",
              "similarity": "tuned"},
    "dfr": {"type": "text", "analyzer": "whitespace", "similarity": "dfr"},
}}
N_DOCS = 720


def _doc(d, rng):
    words = ["common"] + [f"w{rng.randint(12)}" for _ in range(4)]
    if d % 2:
        words.append("odd")  # in half the documents: several blocks
    if d == 7:
        words.append("rare")  # in one document: one shard lacks it
    text = " ".join(words)
    return {"body": text, "tuned": text, "dfr": text}


def _build(name, mesh):
    idx = IndexService(name, Settings(dict(SIM_SETTINGS, **{
        "index.number_of_shards": 2, "index.refresh_interval": -1,
        "index.search.mesh": mesh})), mapping=MAPPING)
    if mesh:
        idx._mesh_search = plan_exec.IndexMeshSearch(idx, mesh=shard_mesh(1))
    rng = np.random.RandomState(38)
    for d in range(N_DOCS):
        idx.index_doc(str(d), _doc(d, rng))
    idx.refresh()
    return idx


@pytest.fixture(scope="module")
def indices():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_PALLAS", "interpret")
        built = {"mesh": _build("lanes-mesh", True),
                 "host": _build("lanes-host", False)}
        yield built
        for idx in built.values():
            idx.close()


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")


def _segments(idx):
    out = []
    for sid, shard in sorted(idx.shards.items()):
        ctx = ShardQueryContext(shard.mapper_service, engine=shard.engine)
        for seg in shard.engine.searchable_segments():
            out.append((ctx, seg))
    return out


TERM_SETS = {
    "bm25_default": [("body", "common", 1.0), ("body", "odd", 2.0),
                     ("body", "w3", 1.0)],
    "bm25_tuned": [("tuned", "common", 1.0), ("tuned", "odd", 0.5)],
    "ttf_similarity": [("dfr", "odd", 1.0), ("dfr", "w5", 1.0)],
    "mixed_kinds": [("dfr", "w1", 1.0), ("body", "odd", 1.0),
                    ("tuned", "w1", 3.0), ("dfr", "common", 1.0)],
    "missing_term": [("body", "rare", 1.0), ("body", "w2", 1.0)],
    "absent_everywhere": [("body", "nowhere", 1.0)],
}


class TestScatterArrays:
    @pytest.mark.parametrize("case", sorted(TERM_SETS))
    def test_block_arrays_match_the_per_block_loop(self, indices, case):
        terms = TERM_SETS[case]
        for ctx, seg in _segments(indices["host"]):
            got = scatter_block_arrays(term_blocks_arrays(seg, terms, ctx))
            want = oracle_block_arrays(seg, terms, ctx)
            for name in BLOCK_ARRAYS:
                assert_bits_equal(got[name], want[name])
            assert got["kinds"] == want["kinds"]

    def test_terms_resolve_to_lanes_not_blocks(self, indices):
        (ctx, seg), _ = _segments(indices["host"])
        arrs = term_blocks_arrays(seg, TERM_SETS["mixed_kinds"], ctx)
        assert set(arrs) == {"n_present", "lanes_meta", "lane_params"}
        assert arrs["n_present"] == len(arrs["lanes_meta"]) == 4
        eligible = [ok for _, _, _, ok in arrs["lanes_meta"]]
        assert eligible == [False, True, False, False]
        dense = arrs["lanes_meta"][3]
        assert dense[1] > 2  # "common": a run of several blocks

    def test_missing_term_lacks_its_lane_on_one_shard(self, indices):
        present = [term_blocks_arrays(seg, TERM_SETS["missing_term"],
                                      ctx)["n_present"]
                   for ctx, seg in _segments(indices["host"])]
        assert sorted(present) == [1, 2]

    @pytest.mark.parametrize("case", ["bm25_tuned", "missing_term"])
    def test_scatter_node_carries_the_loop_arrays(self, indices, case):
        terms = TERM_SETS[case]
        for ctx, seg in _segments(indices["host"]):
            ctx.for_mesh = True
            ctx.mesh_kernel = None
            before = ctx.blocks_expanded
            node = score_terms_node(seg, terms, 1, ctx=ctx)
            want = oracle_block_arrays(seg, terms, ctx)
            assert isinstance(node, P.ScoreTermsNode)
            for name in BLOCK_ARRAYS:
                assert_bits_equal(getattr(node, name), want[name])
            assert node.kinds == want["kinds"]
            assert (ctx.blocks_expanded - before
                    == int(want["q_valid"].sum()))

    def test_unsatisfiable_min_match_pins_past_the_padded_rows(self,
                                                               indices):
        terms = TERM_SETS["bm25_default"]
        for ctx, seg in _segments(indices["host"]):
            ctx.for_mesh = True
            ctx.mesh_kernel = None
            node = score_terms_node(seg, terms, 5, ctx=ctx)
            want = oracle_block_arrays(seg, terms, ctx)
            assert node.min_match == want["q_valid"].shape[0] + 1


# ---------------------------------------------------------------------------
# End to end: the kernel plane expands nothing
# ---------------------------------------------------------------------------


def _hits(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


class TestKernelPlaneExpandsNoBlock:
    def test_match_on_the_kernel_plane(self, indices):
        mesh, host = indices["mesh"], indices["host"]
        ms = mesh._mesh_search
        expanded, lanes = (ms.plan_blocks_expanded_total,
                           ms.kernel_lanes_planned_total)
        for text in ("common odd w3", "rare w2 w7", "w1 w4"):
            body = {"query": {"match": {"body": text}}, "size": 10}
            got, want = mesh.search(dict(body)), host.search(dict(body))
            assert got["_plane"] == "mesh_pallas"
            assert got["hits"]["total"] == want["hits"]["total"]
            ids = [i for i, _ in _hits(got)]
            assert ids == [i for i, _ in _hits(want)]
            np.testing.assert_allclose([s for _, s in _hits(got)],
                                       [s for _, s in _hits(want)],
                                       rtol=1e-5)
        assert ms.plan_blocks_expanded_total == expanded
        # 3 + 3 + 2 terms on two shards, "rare" on one of them
        assert ms.kernel_lanes_planned_total - lanes == 15
        planes = mesh.search_stats()["planes"]
        assert planes["plan_blocks_expanded_total"] == expanded
        assert planes["kernel_lanes_planned_total"] == (
            ms.kernel_lanes_planned_total)

    def test_scatter_rung_counts_the_rows_it_expands(self, indices):
        """A tuned-BM25 field cannot take the kernel: the scatter rung
        serves and counts the block rows of both shards' arrays."""
        mesh, host = indices["mesh"], indices["host"]
        ms = mesh._mesh_search
        before = ms.plan_blocks_expanded_total
        body = {"query": {"match": {"tuned": "common odd"}}, "size": 5}
        got, want = mesh.search(dict(body)), host.search(dict(body))
        assert got["_plane"] == "mesh"
        assert [i for i, _ in _hits(got)] == [i for i, _ in _hits(want)]
        rows = sum(
            int(oracle_block_arrays(seg, [("tuned", "common", 1.0),
                                          ("tuned", "odd", 1.0)],
                                    ctx)["q_valid"].sum())
            for ctx, seg in _segments(mesh))
        assert ms.plan_blocks_expanded_total - before == rows > 0

    @pytest.mark.parametrize("lanes,blocks", [
        ([], [0] * 8),
        ([(5, 3)], [5, 6, 7] + [0] * 5),
        ([(5, 3), (0, 0), (40, 7)], [5, 6, 7] + list(range(40, 47)) + [0] * 6),
    ])
    def test_expansion_of_hand_built_lanes(self, lanes, blocks):
        """Rows run over each lane's blocks in lane order; an empty run
        adds none; padding rows point at block 0 and never match."""
        arrs = {"lanes_meta": [(s, c, 1.5, True) for s, c in lanes],
                "lane_params": [("bm25", 0, 10.0, 1.5, K1, B, 0.0)
                                for _ in lanes],
                "n_present": len(lanes)}
        blk = scatter_block_arrays(arrs)
        n_real = sum(c for _, c in lanes)
        assert blk["q_blocks"].tolist() == blocks
        assert blk["q_valid"].tolist() == (
            [True] * n_real + [False] * (len(blocks) - n_real))
        assert blk["kinds"] == ("bm25",)


# ---------------------------------------------------------------------------
# The counters of the one pass
# ---------------------------------------------------------------------------

# two shards of ~9,000 documents: each pads to 16,384 (one staged tile of
# 128 sublanes), where "dense", in every document, needs ~71 blocks; at half
# the tile the first 8,192 documents take exactly 64: one rung down
DENSE_DOCS = 18_000


@pytest.fixture(scope="module")
def dense_index():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ES_TPU_PALLAS", "interpret")
        idx = IndexService("lanes-dense", Settings({
            "index.number_of_shards": 2, "index.refresh_interval": -1,
            "index.translog.durability": "async",
            "index.search.mesh": True}), mapping={"properties": {
                "body": {"type": "text", "analyzer": "whitespace"}}})
        idx._mesh_search = plan_exec.IndexMeshSearch(idx, mesh=shard_mesh(1))
        for d in range(DENSE_DOCS):
            idx.index_doc(str(d), {"body": "dense sparse" if d % 97 == 0
                                   else "dense"})
        idx.refresh()
        yield idx
        idx.close()


class TestTablePassCounters:
    @pytest.mark.parametrize("query,total,passes,rungs", [
        ({"match": {"body": "sparse"}}, -(-DENSE_DOCS // 97), 1, 0),
        ({"match": {"body": "dense"}}, DENSE_DOCS, 1, 1),
        ({"bool": {"should": [{"match": {"body": "sparse"}},
                              {"match": {"body": "dense"}}]}},
         DENSE_DOCS, 2, 1),
    ], ids=["sparse", "dense", "two_groups"])
    def test_one_pass_a_group_and_the_rungs_it_descended(
            self, dense_index, query, total, passes, rungs):
        ms = dense_index._mesh_search
        before = (ms.kernel_table_passes_total, ms.kernel_ladder_rungs_total)
        got = dense_index.search({"query": query, "size": 3})
        assert got["_plane"] == "mesh_pallas"
        assert got["hits"]["total"] == total
        assert (ms.kernel_table_passes_total - before[0],
                ms.kernel_ladder_rungs_total - before[1]) == (passes, rungs)
        planes = dense_index.search_stats()["planes"]
        assert planes["kernel_table_passes_total"] == (
            ms.kernel_table_passes_total)
        assert planes["kernel_ladder_rungs_total"] == (
            ms.kernel_ladder_rungs_total)

