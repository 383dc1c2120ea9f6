"""Tests for the Pallas tile-scoring kernel (ops/pallas_scoring.py).

Run on the CPU backend in interpreter mode (interpret=True): the kernel
semantics are identical to the compiled TPU path; mosaic-specific layout
constraints are held by tests/test_tpu_compile.py and, on hardware, by
chip_smoke.py and the benchmark.

Oracle: reference_scores — a host scatter-add over the same block-packed
postings, i.e. exactly what ops/scoring.score_term_blocks computes and
what Lucene's BulkScorer loop (search/query/QueryPhase.java:272) produces
for a weighted disjunction.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from elasticsearch_tpu.ops.pallas_scoring import (
    CB_MAX,
    LANE,
    QueryLane,
    block_min_max,
    build_live_t,
    build_tile_tables,
    compute_block_frac,
    dense_to_flat,
    merge_tile_topk,
    next_pow2,
    pad_segment_blocks,
    reference_scores,
    score_tiles,
    tile_geometry,
)


def assert_topk_valid(top_s, top_d, ref, k):
    """Tie-robust top-k check: returned scores must equal the reference's
    sorted top-k values, and every returned doc's own reference score must
    equal its returned score (so any tie-breaking choice is accepted)."""
    top_s = np.asarray(top_s)
    top_d = np.asarray(top_d)
    expect = np.sort(ref[ref > 0])[::-1][:k]
    got = top_s[top_s > -np.inf]
    np.testing.assert_allclose(got, expect, rtol=1e-5)
    for s, d in zip(top_s, top_d):
        if s > -np.inf:
            np.testing.assert_allclose(ref[d], s, rtol=1e-5)
    assert len(set(top_d[top_s > -np.inf].tolist())) == len(got)


def build_corpus(rng, nd, vocab, max_df=300):
    """Block-packed synthetic postings like SegmentBuilder.seal() emits."""
    nd_pad = next_pow2(nd)
    blocks_docs, blocks_tfs = [], []
    term_start, term_count = [], []
    for _ in range(vocab):
        df = rng.randint(1, max_df)
        docs = np.sort(rng.choice(nd, size=min(df, nd),
                                  replace=False)).astype(np.int32)
        tfs = rng.randint(1, 5, size=len(docs)).astype(np.float32)
        nb = -(-len(docs) // LANE)
        term_start.append(len(blocks_docs))
        term_count.append(nb)
        for i in range(nb):
            d = np.full(LANE, nd_pad, np.int32)
            f = np.zeros(LANE, np.float32)
            chunk = docs[i * LANE:(i + 1) * LANE]
            d[: len(chunk)] = chunk
            f[: len(chunk)] = tfs[i * LANE:(i + 1) * LANE]
            blocks_docs.append(d)
            blocks_tfs.append(f)
    return (np.stack(blocks_docs), np.stack(blocks_tfs),
            term_start, term_count, nd_pad)


def run_kernel(block_docs, frac, live, lanes, nd_pad, k=10, tile_sub=4,
               dense=False, with_counts=False):
    geom = tile_geometry(nd_pad, tile_sub=tile_sub)
    bmin, bmax = block_min_max(block_docs, frac, nd_pad)
    row_lo, row_hi, weights, cb = build_tile_tables(lanes, bmin, bmax, geom)
    dp, fp = pad_segment_blocks(block_docs, frac, nd_pad)
    live_t = build_live_t(live, geom)
    out = score_tiles(
        jnp.asarray(dp), jnp.asarray(fp), jnp.asarray(live_t),
        jnp.asarray(row_lo), jnp.asarray(row_hi), jnp.asarray(weights),
        t_pad=weights.shape[1], cb=cb, sub=geom.tile_sub, k=k,
        dense=dense, with_counts=with_counts, interpret=True)
    return out, geom


class TestTopkKernel:
    def test_matches_scatter_reference(self):
        rng = np.random.RandomState(1)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 3000, 80)
        doc_len = np.full(nd_pad + 1, 40.0, np.float32)
        frac = compute_block_frac(bd, bt, doc_len, avgdl=40.0)
        live = np.zeros(nd_pad, np.float32)
        live[:3000] = 1.0
        lanes = [QueryLane(ts_[3], tc[3], 1.4),
                 QueryLane(ts_[10], tc[10], 0.9),
                 QueryLane(ts_[55], tc[55], 2.0)]
        (tile_s, tile_d, tile_h), geom = run_kernel(
            bd, frac, live, lanes, nd_pad)
        top_s, top_d, hits = merge_tile_topk(tile_s, tile_d, tile_h, 10)
        ref = reference_scores(bd, frac, lanes, nd_pad)
        ref[live == 0] = 0.0
        assert int(hits) == int((ref > 0).sum())
        assert_topk_valid(top_s, top_d, ref, 10)

    def test_deleted_docs_excluded(self):
        rng = np.random.RandomState(2)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 1000, 20)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 10.0, np.float32),
                                  avgdl=10.0)
        live = np.zeros(nd_pad, np.float32)
        live[:1000] = 1.0
        dead = rng.choice(1000, 200, replace=False)
        live[dead] = 0.0
        lanes = [QueryLane(ts_[0], tc[0], 1.0)]
        (tile_s, tile_d, tile_h), _ = run_kernel(bd, frac, live, lanes, nd_pad)
        top_s, top_d, hits = merge_tile_topk(tile_s, tile_d, tile_h, 10)
        docs = np.asarray(top_d)
        assert not set(docs[np.asarray(top_s) > -np.inf].tolist()) & set(
            dead.tolist())
        ref = reference_scores(bd, frac, lanes, nd_pad)
        ref[live == 0] = 0.0
        assert int(hits) == int((ref > 0).sum())

    def test_fewer_matches_than_k(self):
        rng = np.random.RandomState(3)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 600, 10, max_df=5)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 10.0, np.float32),
                                  avgdl=10.0)
        live = np.zeros(nd_pad, np.float32)
        live[:600] = 1.0
        lanes = [QueryLane(ts_[2], tc[2], 1.0)]
        (tile_s, tile_d, tile_h), _ = run_kernel(bd, frac, live, lanes, nd_pad,
                                                 k=10)
        top_s, top_d, hits = merge_tile_topk(tile_s, tile_d, tile_h, 10)
        ref = reference_scores(bd, frac, lanes, nd_pad)
        n = int((ref > 0).sum())
        assert int(hits) == n < 10
        top_s = np.asarray(top_s)
        top_d = np.asarray(top_d)
        assert (top_d[top_s == -np.inf] == -1).all()
        assert (top_s > -np.inf).sum() == n

    def test_padded_lanes_ignored(self):
        """t_pad > len(lanes): zero-weight padding lanes contribute nothing."""
        rng = np.random.RandomState(4)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 1500, 30)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 20.0, np.float32),
                                  avgdl=20.0)
        live = np.zeros(nd_pad, np.float32)
        live[:1500] = 1.0
        lanes3 = [QueryLane(ts_[i], tc[i], 1.0) for i in (1, 5, 9)]
        geom = tile_geometry(nd_pad, tile_sub=4)
        bmin, bmax = block_min_max(bd, frac, nd_pad)
        row_lo, row_hi, weights, cb = build_tile_tables(
            lanes3, bmin, bmax, geom, t_pad=8)
        dp, fp = pad_segment_blocks(bd, frac, nd_pad)
        live_t = build_live_t(live, geom)
        tile_s, tile_d, tile_h = score_tiles(
            jnp.asarray(dp), jnp.asarray(fp), jnp.asarray(live_t),
            jnp.asarray(row_lo), jnp.asarray(row_hi), jnp.asarray(weights),
            t_pad=8, cb=cb, sub=geom.tile_sub, k=10, interpret=True)
        top_s, top_d, hits = merge_tile_topk(tile_s, tile_d, tile_h, 10)
        ref = reference_scores(bd, frac, lanes3, nd_pad)
        ref[live == 0] = 0.0
        assert_topk_valid(top_s, top_d, ref, 10)

    def test_single_tile_segment(self):
        """Segments smaller than one tile (n_tiles == 1) still work."""
        rng = np.random.RandomState(5)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 200, 8, max_df=60)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 15.0, np.float32),
                                  avgdl=15.0)
        live = np.zeros(nd_pad, np.float32)
        live[:200] = 1.0
        lanes = [QueryLane(ts_[0], tc[0], 1.0), QueryLane(ts_[4], tc[4], 3.0)]
        (tile_s, tile_d, tile_h), geom = run_kernel(bd, frac, live, lanes,
                                                    nd_pad, tile_sub=4)
        assert geom.n_tiles == 1
        top_s, top_d, hits = merge_tile_topk(tile_s, tile_d, tile_h, 10)
        ref = reference_scores(bd, frac, lanes, nd_pad)
        ref[live == 0] = 0.0
        assert_topk_valid(top_s, top_d, ref, 10)


class TestDenseKernel:
    def test_dense_scores_and_counts(self):
        rng = np.random.RandomState(6)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 2500, 40)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 30.0, np.float32),
                                  avgdl=30.0)
        live = np.zeros(nd_pad, np.float32)
        live[:2500] = 1.0
        lanes = [QueryLane(ts_[i], tc[i], w)
                 for i, w in [(0, 1.0), (7, 2.5), (13, 0.5)]]
        (dense, counts), geom = run_kernel(bd, frac, live, lanes, nd_pad,
                                           dense=True, with_counts=True)
        flat = np.asarray(dense_to_flat(dense, geom.tile_sub))
        cflat = np.asarray(dense_to_flat(counts, geom.tile_sub))
        ref = reference_scores(bd, frac, lanes, nd_pad)
        ref[live == 0] = 0.0
        np.testing.assert_allclose(flat, ref, rtol=1e-5)
        # counts: distinct matching lanes per doc
        cref = np.zeros(nd_pad, np.float32)
        for lane in lanes:
            rows = slice(lane.block_start, lane.block_start + lane.block_count)
            docs = bd[rows].ravel()
            f = frac[rows].ravel()
            sel = (f > 0) & (docs < nd_pad)
            np.add.at(cref, docs[sel], 1.0)
        cref[live == 0] = 0.0
        np.testing.assert_allclose(cflat, cref, rtol=1e-6)


class TestWindowAlignment:
    def test_misaligned_window_not_truncated(self):
        """Regression: a lane whose covering window starts at a block row
        with a high offset modulo CB (e.g. row 6 with cb=8) must still see
        all its blocks — the kernel fetches two aligned windows, so rows
        past the first aligned block are not dropped."""
        rng = np.random.RandomState(8)
        nd = 512
        nd_pad = next_pow2(nd)
        blocks_docs, blocks_tfs = [], []
        # 6 filler one-block terms so the dense term starts at row 6
        for i in range(6):
            d = np.full(LANE, nd_pad, np.int32)
            f = np.zeros(LANE, np.float32)
            d[0] = i
            f[0] = 1.0
            blocks_docs.append(d)
            blocks_tfs.append(f)
        # dense term: every doc -> 4 full blocks at rows [6, 10)
        docs = np.arange(nd, dtype=np.int32)
        for i in range(4):
            blocks_docs.append(docs[i * LANE:(i + 1) * LANE])
            blocks_tfs.append(np.ones(LANE, np.float32))
        bd = np.stack(blocks_docs)
        bt = np.stack(blocks_tfs)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 10.0, np.float32),
                                  avgdl=10.0)
        live = np.zeros(next_pow2(max(nd_pad, LANE)), np.float32)
        live[:nd] = 1.0
        lanes = [QueryLane(6, 4, 1.0)]
        (dense, ), geom = run_kernel(bd, frac, live, lanes, nd_pad,
                                     tile_sub=4, dense=True)
        flat = np.asarray(dense_to_flat(dense, geom.tile_sub))
        ref = reference_scores(bd, frac, lanes, geom.nd_pad)
        ref[live[: geom.nd_pad] == 0] = 0.0
        np.testing.assert_allclose(flat, ref, rtol=1e-5)
        assert (flat[:nd] > 0).all()  # every doc scored — nothing dropped


class TestHostGeometry:
    def test_tile_tables_cover_all_postings(self):
        """Every real posting must fall inside its tile's [row_lo, row_hi)
        window — the correctness contract of the searchsorted coverage."""
        rng = np.random.RandomState(7)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 4000, 50)
        geom = tile_geometry(nd_pad, tile_sub=4)
        w = geom.tile_w
        bmin, bmax = block_min_max(bd, bt, nd_pad)
        lanes = [QueryLane(ts_[i], tc[i], 1.0) for i in range(12)]
        row_lo, row_hi, weights, cb = build_tile_tables(lanes, bmin, bmax, geom)
        assert cb <= CB_MAX
        for j, lane in enumerate(lanes):
            for b in range(lane.block_start, lane.block_start + lane.block_count):
                docs = bd[b][bt[b] > 0]
                for t in np.unique(docs // w):
                    assert row_lo[t, j] <= b < row_hi[t, j], (
                        f"block {b} with docs in tile {t} not covered")

    def test_geometry_small_segments(self):
        assert tile_geometry(64).n_tiles == 1
        g = tile_geometry(1 << 20)
        assert g.n_tiles * g.tile_w == 1 << 20

    def test_dense_term_needs_smaller_tile(self):
        """A clustered dense term overflows the covering-window bound at
        big tiles; the planner's geometry ladder must find a tile_sub
        where it fits (sub=32 always does: need <= sub + 2), and the
        kernel at that geometry must still match the oracle."""
        nd = 1 << 16  # 64k docs so tile_sub=128 tiles exist
        nd_pad = nd
        # one term matching every doc: 512 maximally-dense blocks
        docs = np.arange(nd, dtype=np.int32).reshape(-1, LANE)
        tfs = np.ones_like(docs, np.float32)
        frac = compute_block_frac(docs, tfs, np.full(nd_pad + 1, 10.0,
                                                     np.float32), 10.0)
        bmin, bmax = block_min_max(docs, tfs, nd_pad)
        lanes = [QueryLane(0, docs.shape[0], 1.5)]
        with pytest.raises(ValueError):
            build_tile_tables(lanes, bmin, bmax,
                              tile_geometry(nd_pad, tile_sub=128))
        # the ladder's floor geometry fits and scores correctly
        geom = tile_geometry(nd_pad, tile_sub=32)
        row_lo, row_hi, weights, cb = build_tile_tables(
            lanes, bmin, bmax, geom)
        assert cb <= CB_MAX // 2
        dp, fp = pad_segment_blocks(docs, frac, nd_pad)
        live = np.ones(nd_pad, np.float32)
        out = score_tiles(
            jnp.asarray(dp), jnp.asarray(fp),
            jnp.asarray(build_live_t(live, geom)),
            jnp.asarray(row_lo), jnp.asarray(row_hi), jnp.asarray(weights),
            t_pad=weights.shape[1], cb=cb, sub=geom.tile_sub, k=10,
            interpret=True)
        top_s, top_d, hits = merge_tile_topk(*out, 10)
        ref = reference_scores(docs, frac, lanes, nd_pad)
        assert int(hits) == nd
        assert_topk_valid(top_s, top_d, ref, 10)


class TestRowBase:
    """``row_base`` reads one segment's rows out of a table that holds
    several (the mesh executor's flat staging): same bits as the
    segment's own table, whatever the codec, variant, batch or cb."""

    SLOTS = 2
    LADDER = (8, 16, 32, CB_MAX // 2)

    def _slots(self, codec, off_boundary=0):
        """Per slot its own padded table(s) and query tables; plus the
        flat table: the slots' tables, each padded to a CB_MAX multiple
        of rows (and ``off_boundary`` rows more), one after the other."""
        from elasticsearch_tpu.ops.pallas_scoring import pack_segment_blocks

        rng = np.random.RandomState(26)
        slots = []
        for _ in range(self.SLOTS):
            bd, bt, ts_, tc, nd_pad = build_corpus(rng, 2000, 40)
            frac = compute_block_frac(
                bd, bt, np.full(nd_pad + 1, 30.0, np.float32), avgdl=30.0)
            if codec == "packed":
                own = (pack_segment_blocks(bd, frac, nd_pad), None)
            else:
                own = pad_segment_blocks(bd, frac, nd_pad)
            live = (rng.rand(nd_pad) > 0.1).astype(np.float32)
            terms = rng.choice(40, size=3, replace=False)
            lanes = [QueryLane(ts_[t], tc[t], float(rng.rand() + 0.5))
                     for t in terms]
            slots.append(dict(own=own, bd=bd, frac=frac, live=live,
                              lanes=lanes, nd_pad=nd_pad))
        n_rows_pad = -(-max(s["own"][0].shape[0] for s in slots)
                       // CB_MAX) * CB_MAX + off_boundary
        nd_pad = slots[0]["nd_pad"]
        total = -(-self.SLOTS * n_rows_pad // CB_MAX) * CB_MAX
        flat_docs = np.full((total, LANE),
                            0 if codec == "packed" else nd_pad, np.int32)
        flat_frac = np.zeros((total, LANE), np.float32)
        for i, s in enumerate(slots):
            lo = i * n_rows_pad
            flat_docs[lo: lo + s["own"][0].shape[0]] = s["own"][0]
            if codec != "packed":
                flat_frac[lo: lo + s["own"][1].shape[0]] = s["own"][1]
        flat = (flat_docs, None if codec == "packed" else flat_frac)
        return slots, flat, n_rows_pad

    @pytest.mark.parametrize("q_batch", [1, 8])
    @pytest.mark.parametrize("variant", ["dense", "topk", "tile_ids"])
    @pytest.mark.parametrize("codec", ["raw", "packed"])
    def test_flat_table_scores_the_slots_own_bits(self, codec, variant,
                                                  q_batch):
        slots, flat, n_rows_pad = self._slots(codec)
        case = (["raw", "packed"].index(codec) * 6
                + ["dense", "topk", "tile_ids"].index(variant) * 2
                + [1, 8].index(q_batch))
        rng = np.random.RandomState(case)
        for i, s in enumerate(slots):
            if i == 0:
                continue  # row_base 0: the table's first rows, as ever
            cb = self.LADDER[case % len(self.LADDER)]
            geom = tile_geometry(s["nd_pad"], tile_sub=4)
            bmin, bmax = block_min_max(s["bd"], s["frac"], s["nd_pad"])
            row_lo, row_hi, weights, cb = build_tile_tables(
                s["lanes"], bmin, bmax, geom, cb=cb)
            if q_batch > 1:
                # one row per member; a zero is a lane dead for it
                weights = (weights * rng.rand(q_batch, weights.shape[1])
                           * (rng.rand(q_batch, weights.shape[1]) > 0.3)
                           ).astype(np.float32)
            kw = dict(t_pad=weights.shape[1], cb=cb, sub=geom.tile_sub,
                      k=10, q_batch=q_batch, codec=codec, interpret=True)
            if variant == "dense":
                kw.update(dense=True, with_counts=True)
            elif variant == "tile_ids":
                sel = np.asarray([2, 0, 3], np.int32)
                row_lo, row_hi = row_lo[sel], row_hi[sel]
                kw["tile_ids"] = jnp.asarray(sel)
            rest = (jnp.asarray(build_live_t(s["live"], geom)),
                    jnp.asarray(row_lo), jnp.asarray(row_hi),
                    jnp.asarray(weights))
            own = score_tiles(
                jnp.asarray(s["own"][0]),
                None if codec == "packed" else jnp.asarray(s["own"][1]),
                *rest, **kw)
            shared = score_tiles(
                jnp.asarray(flat[0]),
                None if codec == "packed" else jnp.asarray(flat[1]),
                *rest, row_base=i * n_rows_pad, **kw)
            assert len(own) == len(shared)
            for a, b in zip(own, shared):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert float(np.max(np.asarray(own[0]))) > 0.0  # scored

    def test_row_base_off_a_block_boundary_scores_the_same(self):
        """The row tables carry the offset, and the kernel's windows are
        the aligned blocks around wherever they point: a slot need not
        start on a block boundary (the executor's do, to keep a block
        one slot's)."""
        slots, flat, n_rows_pad = self._slots("raw", off_boundary=8)
        s = slots[1]
        geom = tile_geometry(s["nd_pad"], tile_sub=4)
        bmin, bmax = block_min_max(s["bd"], s["frac"], s["nd_pad"])
        row_lo, row_hi, weights, cb = build_tile_tables(
            s["lanes"], bmin, bmax, geom, cb=32)
        assert n_rows_pad % cb == 8
        kw = dict(t_pad=weights.shape[1], cb=cb, sub=geom.tile_sub,
                  dense=True, with_counts=True, interpret=True)
        rest = (jnp.asarray(build_live_t(s["live"], geom)),
                jnp.asarray(row_lo), jnp.asarray(row_hi),
                jnp.asarray(weights))
        own = score_tiles(jnp.asarray(s["own"][0]),
                          jnp.asarray(s["own"][1]), *rest, **kw)
        shared = score_tiles(jnp.asarray(flat[0]), jnp.asarray(flat[1]),
                             *rest, row_base=n_rows_pad, **kw)
        for a, b in zip(own, shared):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(np.max(np.asarray(own[0]))) > 0.0
