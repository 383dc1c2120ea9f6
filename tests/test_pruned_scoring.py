"""Bit-packed postings codec + block-max pruned scoring (ISSUE 6).

Three layers, all in interpret mode on the CPU backend (the
tests/test_pallas_scoring idiom — identical semantics to the compiled
TPU path):

- codec: pack/quantize round-trip invariants; ``score_tiles`` with
  codec="packed" matches the numpy oracle EXACTLY over the dequantized
  impact factors (the kernel's in-VMEM decode is deterministic f32),
  and within quantization tolerance of the raw oracle; match COUNTS are
  bit-exact (quantization preserves the frac > 0 posting-validity rule).
- pruning: the per-(tile, query) block-max bound dominates every in-tile
  doc score (property-tested over random corpora), so the pruned top-k
  equals the exhaustive top-k while skipping tiles; batched pruning
  isolates members (per-query thresholds over union lanes).
- service: the mesh_pallas pruned path matches the exhaustive path,
  exports the ``_pruned`` marker + ``_stats`` counters, falls back to
  exhaustive execution for aggs / minimum_should_match / sort requests,
  and a plane fault under pruning still quarantines exactly once.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.ops import pallas_scoring as psc
from elasticsearch_tpu.ops.pallas_scoring import (
    PACK_FRAC_MASK,
    PACK_FRAC_SCALE,
    QueryLane,
    block_frac_max,
    block_min_max,
    build_live_t,
    build_tile_tables,
    build_tile_tables_batched,
    compute_block_frac,
    dequantize_frac,
    merge_tile_topk,
    merge_tile_topk_batched,
    pack_segment_blocks,
    pad_segment_blocks,
    plan_pruned_tiles,
    quantize_frac,
    reference_scores,
    score_tiles,
    score_tiles_pruned,
    tile_geometry,
    tile_lane_ub,
)
from elasticsearch_tpu.testing.disruption import (
    PlaneFailScheme,
    clear_search_disruptions,
)

from test_pallas_scoring import assert_topk_valid, build_corpus


@pytest.fixture(autouse=True)
def _interpret_kernel(monkeypatch):
    monkeypatch.setenv("ES_TPU_PALLAS", "interpret")
    yield
    clear_search_disruptions()


def _staged(bd, frac, live, geom, nd_pad):
    dp, fp = pad_segment_blocks(bd, frac, nd_pad)
    pk = pack_segment_blocks(bd, frac, nd_pad)
    lt = build_live_t(live, geom)
    return (jnp.asarray(dp), jnp.asarray(fp), jnp.asarray(pk),
            jnp.asarray(lt))


class TestPackedCodec:
    def test_quantize_roundtrip_invariants(self):
        rng = np.random.RandomState(0)
        frac = np.where(rng.rand(64, 128) < 0.3, 0.0,
                        rng.rand(64, 128) * psc.PACK_MAX_FRAC * 0.999
                        ).astype(np.float32)
        q = quantize_frac(frac)
        # validity survives the round trip exactly: frac > 0 <-> q > 0
        np.testing.assert_array_equal(q > 0, frac > 0)
        assert q.max() <= PACK_FRAC_MASK
        dq = dequantize_frac(q)
        # lossiness bound: half a quantization step (real postings only;
        # sub-step fracs clamp UP to code 1 so they stay valid)
        real = frac > PACK_FRAC_SCALE
        assert np.abs(dq[real] - frac[real]).max() <= PACK_FRAC_SCALE

    def test_pack_rejects_oversized_doc_space(self):
        docs = np.zeros((1, 128), np.int32)
        frac = np.ones((1, 128), np.float32)
        with pytest.raises(ValueError):
            pack_segment_blocks(docs, frac, psc.PACKED_DOC_CAP * 2)

    def test_codec_resolution(self):
        assert psc.resolve_postings_codec("packed", 1 << 20) == "packed"
        # doc space beyond the packed word's doc bits demotes to raw
        assert psc.resolve_postings_codec("packed", 1 << 21) == "raw"
        assert psc.resolve_postings_codec("raw", 1 << 10) == "raw"
        # no preference, or one the kernel does not know, is raw
        assert psc.resolve_postings_codec(None, 1 << 10) == "raw"
        assert psc.resolve_postings_codec("default", 1 << 10) == "raw"
        assert psc.resolve_postings_codec("packed", 1 << 10) == "packed"

    @pytest.mark.parametrize("settings, want", [
        ({}, "raw"),
        ({"search.pallas.postings_codec": "packed"}, "packed"),
        ({"search.pallas.postings_codec": "packed",
          "index.search.pallas.postings_codec": "raw"}, "raw"),
        ({"search.pallas.postings_codec": "raw",
          "index.search.pallas.postings_codec": "packed"}, "packed"),
        ({"search.pallas.postings_codec": "packed",
          "index.search.pallas.postings_codec": "default"}, "packed"),
    ])
    def test_index_codec_preference(self, settings, want):
        """The index key unless "default", else the node key its
        Settings were seeded with, else raw."""
        idx = IndexService("codec-pref", Settings(
            {"index.number_of_shards": 1, **settings}))
        try:
            assert idx.postings_codec_pref == want
            assert all(s.engine.postings_codec == want
                       for s in idx.shards.values())
        finally:
            idx.close()

    def test_packed_kernel_parity(self):
        """Dense + top-k outputs over the packed corpus equal the oracle
        over DEQUANTIZED fracs exactly, and the raw oracle approximately
        (the documented quantization tolerance)."""
        rng = np.random.RandomState(1)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 3000, 60)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 40.0,
                                                  np.float32), avgdl=40.0)
        live = np.zeros(nd_pad, np.float32)
        live[:3000] = 1.0
        lanes = [QueryLane(ts_[3], tc[3], 1.4),
                 QueryLane(ts_[10], tc[10], 0.9),
                 QueryLane(ts_[55], tc[55], 2.0)]
        geom = tile_geometry(nd_pad, tile_sub=4)
        bmin, bmax = block_min_max(bd, bt, nd_pad)
        rl, rh, w, cb = build_tile_tables(lanes, bmin, bmax, geom)
        _dp, _fp, pk, lt = _staged(bd, frac, live, geom, nd_pad)
        kw = dict(t_pad=w.shape[1], cb=cb, sub=geom.tile_sub,
                  interpret=True, codec="packed")
        fq = dequantize_frac(quantize_frac(frac))
        ref = reference_scores(bd, fq, lanes, nd_pad)
        ref[live == 0] = 0.0
        # dense: exact vs the dequantized oracle
        od = score_tiles(pk, None, lt, jnp.asarray(rl), jnp.asarray(rh),
                         jnp.asarray(w), dense=True, **kw)
        flat = np.asarray(psc.dense_to_flat(od[0], geom.tile_sub))
        np.testing.assert_allclose(flat, ref, rtol=1e-5)
        # ...and within quantization tolerance of the RAW oracle
        ref_raw = reference_scores(bd, frac, lanes, nd_pad)
        ref_raw[live == 0] = 0.0
        mism = np.abs(flat - ref_raw)
        assert mism.max() <= 3 * len(lanes) * PACK_FRAC_SCALE
        # top-k: exact vs the dequantized oracle
        o = score_tiles(pk, None, lt, jnp.asarray(rl), jnp.asarray(rh),
                        jnp.asarray(w), k=10, **kw)
        top_s, top_d, hits = merge_tile_topk(*o, 10)
        assert int(hits) == int((ref > 0).sum())
        assert_topk_valid(top_s, top_d, ref, 10)

    def test_packed_counts_bit_exact(self):
        """minimum_should_match COUNTS are unaffected by quantization:
        frac > 0 round-trips exactly, so the matched-lane sets agree."""
        rng = np.random.RandomState(2)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 1500, 30)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 20.0,
                                                  np.float32), avgdl=20.0)
        live = np.zeros(nd_pad, np.float32)
        live[:1500] = 1.0
        lanes = [QueryLane(ts_[i], tc[i], 1.0) for i in (1, 5, 9)]
        geom = tile_geometry(nd_pad, tile_sub=4)
        bmin, bmax = block_min_max(bd, bt, nd_pad)
        rl, rh, w, cb = build_tile_tables(lanes, bmin, bmax, geom)
        dp, fp, pk, lt = _staged(bd, frac, live, geom, nd_pad)
        kw = dict(t_pad=w.shape[1], cb=cb, sub=geom.tile_sub,
                  dense=True, with_counts=True, interpret=True)
        raw = score_tiles(dp, fp, lt, jnp.asarray(rl), jnp.asarray(rh),
                          jnp.asarray(w), **kw)
        packed = score_tiles(pk, None, lt, jnp.asarray(rl),
                             jnp.asarray(rh), jnp.asarray(w),
                             codec="packed", **kw)
        np.testing.assert_array_equal(np.asarray(raw[1]),
                                      np.asarray(packed[1]))

    def test_tile_subset_rejects_dense(self):
        rng = np.random.RandomState(3)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 600, 10)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 10.0,
                                                  np.float32), avgdl=10.0)
        geom = tile_geometry(nd_pad, tile_sub=4)
        bmin, bmax = block_min_max(bd, bt, nd_pad)
        rl, rh, w, cb = build_tile_tables(
            [QueryLane(ts_[0], tc[0], 1.0)], bmin, bmax, geom)
        dp, fp, _pk, lt = _staged(
            bd, frac, np.ones(nd_pad, np.float32), geom, nd_pad)
        with pytest.raises(ValueError):
            score_tiles(dp, fp, lt, jnp.asarray(rl), jnp.asarray(rh),
                        jnp.asarray(w), t_pad=w.shape[1], cb=cb,
                        sub=geom.tile_sub, dense=True, interpret=True,
                        tile_ids=jnp.arange(rl.shape[0], dtype=jnp.int32))


class TestBlockMaxPruning:
    def test_bound_dominates_every_tile_score(self):
        """Property test: for random corpora and queries, the summed
        per-(tile, lane) bound dominates EVERY doc's true score within
        its tile — the invariant that makes pruning lossless."""
        for seed in range(4):
            rng = np.random.RandomState(100 + seed)
            bd, bt, ts_, tc, nd_pad = build_corpus(
                rng, rng.randint(800, 4000), 40)
            frac = compute_block_frac(
                bd, bt, np.full(nd_pad + 1, 25.0, np.float32), avgdl=25.0)
            geom = tile_geometry(nd_pad, tile_sub=4)
            bmin, bmax = block_min_max(bd, bt, nd_pad)
            picks = rng.choice(40, 3, replace=False)
            lanes = [QueryLane(ts_[i], tc[i], float(rng.rand() * 2 + 0.1))
                     for i in picks]
            rl, rh, w, cb = build_tile_tables(lanes, bmin, bmax, geom)
            ub = tile_lane_ub(rl, rh, block_frac_max(frac))
            bounds = (ub @ w.T)[:, 0]  # [n_tiles]
            ref = reference_scores(bd, frac, lanes, nd_pad)
            tile_w = geom.tile_w
            for t in range(geom.n_tiles):
                seg = ref[t * tile_w: (t + 1) * tile_w]
                assert seg.max() <= bounds[t] + 1e-4, (seed, t)

    def test_pruned_equals_exhaustive_topk(self):
        """score_tiles_pruned == exhaustive top-k over random corpora,
        and pruning actually fires on at least one of them."""
        any_pruned = False
        for seed in range(4):
            rng = np.random.RandomState(200 + seed)
            bd, bt, ts_, tc, nd_pad = build_corpus(rng, 3500, 60)
            frac = compute_block_frac(
                bd, bt, np.full(nd_pad + 1, 30.0, np.float32), avgdl=30.0)
            live = np.zeros(nd_pad, np.float32)
            live[:3500] = 1.0
            dead = rng.choice(3500, 300, replace=False)
            live[dead] = 0.0
            geom = tile_geometry(nd_pad, tile_sub=4)
            bmin, bmax = block_min_max(bd, bt, nd_pad)
            picks = rng.choice(60, 3, replace=False)
            lanes = [QueryLane(ts_[i], tc[i],
                               float(rng.rand() * 2 + 0.1))
                     for i in picks]
            rl, rh, w, cb = build_tile_tables(lanes, bmin, bmax, geom)
            dp, fp, _pk, lt = _staged(bd, frac, live, geom, nd_pad)
            plan = plan_pruned_tiles(rl, rh, w, block_frac_max(frac),
                                     probe_tiles=2)
            assert plan is not None
            top_s, top_d, hits, scored = score_tiles_pruned(
                dp, fp, lt,
                jnp.asarray(plan["rl_probe"]),
                jnp.asarray(plan["rh_probe"]),
                jnp.asarray(plan["tid_probe"]),
                jnp.asarray(plan["rl_rest"]),
                jnp.asarray(plan["rh_rest"]),
                jnp.asarray(plan["tid_rest"]),
                jnp.asarray(plan["bounds_rest"]), jnp.asarray(w),
                t_pad=w.shape[1], cb=cb, sub=geom.tile_sub, k=10,
                interpret=True)
            ref = reference_scores(bd, frac, lanes, nd_pad)
            ref[live == 0] = 0.0
            assert_topk_valid(np.asarray(top_s[0]), np.asarray(top_d[0]),
                              ref, 10)
            assert int(scored) <= geom.n_tiles
            # hits under pruning: a lower bound, never an overcount
            assert int(hits[0]) <= int((ref > 0).sum())
            if int(scored) < geom.n_tiles:
                any_pruned = True
                # a pruned run must still find the full top-k (checked
                # above) — this asserts the skipping actually happened
        assert any_pruned, "pruning never fired across seeds"

    def test_batched_pruning_member_isolation(self):
        """Per-query thresholds over union lanes: each member's pruned
        top-k equals ITS serial exhaustive top-k; padding members stay
        empty (they must never keep tiles alive or emit candidates)."""
        rng = np.random.RandomState(7)
        bd, bt, ts_, tc, nd_pad = build_corpus(rng, 4000, 60)
        frac = compute_block_frac(bd, bt, np.full(nd_pad + 1, 30.0,
                                                  np.float32), avgdl=30.0)
        live = np.zeros(nd_pad, np.float32)
        live[:4000] = 1.0
        geom = tile_geometry(nd_pad, tile_sub=4)
        bmin, bmax = block_min_max(bd, bt, nd_pad)
        lane_sets = [
            [QueryLane(ts_[1], tc[1], 1.2), QueryLane(ts_[7], tc[7], 0.6)],
            [QueryLane(ts_[7], tc[7], 2.0),
             QueryLane(ts_[20], tc[20], 1.0)],
            [QueryLane(ts_[33], tc[33], 0.8)],
        ]
        rl, rh, w, cb = build_tile_tables_batched(
            lane_sets, bmin, bmax, geom)
        q_pad = 4
        wp = np.zeros((q_pad, w.shape[1]), np.float32)
        wp[:3] = w
        pk = jnp.asarray(pack_segment_blocks(bd, frac, nd_pad))
        lt = jnp.asarray(build_live_t(live, geom))
        fq = dequantize_frac(quantize_frac(frac))
        plan = plan_pruned_tiles(rl, rh, wp, block_frac_max(fq),
                                 probe_tiles=2)
        top_s, top_d, hits, scored = score_tiles_pruned(
            pk, None, lt,
            jnp.asarray(plan["rl_probe"]), jnp.asarray(plan["rh_probe"]),
            jnp.asarray(plan["tid_probe"]),
            jnp.asarray(plan["rl_rest"]), jnp.asarray(plan["rh_rest"]),
            jnp.asarray(plan["tid_rest"]),
            jnp.asarray(plan["bounds_rest"]), jnp.asarray(wp),
            t_pad=wp.shape[1], cb=cb, sub=geom.tile_sub, k=10,
            q_batch=q_pad, q_real=3, codec="packed", interpret=True)
        for q, lanes in enumerate(lane_sets):
            ref = reference_scores(bd, fq, lanes, nd_pad)
            ref[live == 0] = 0.0
            assert_topk_valid(np.asarray(top_s[q]), np.asarray(top_d[q]),
                              ref, 10)
        assert (np.asarray(top_s[3]) == -np.inf).all()
        assert int(hits[3]) == 0


MAPPING = {"properties": {
    "body": {"type": "text", "analyzer": "whitespace"},
    "n": {"type": "integer"},
    "tag": {"type": "keyword"},
}}


def build_index(name, n_shards=2, n_docs=600, seed=0, **extra_settings):
    idx = IndexService(
        name, Settings({
            "index.number_of_shards": n_shards,
            "index.refresh_interval": -1, **extra_settings}),
        mapping=MAPPING)
    rng = np.random.RandomState(seed)
    vocab = [f"t{i}" for i in range(20)]
    tags = ["red", "green", "blue"]
    for d in range(n_docs):
        toks = [vocab[rng.randint(len(vocab))]
                for _ in range(rng.randint(3, 9))]
        idx.index_doc(str(d), {"body": " ".join(toks), "n": d,
                               "tag": tags[d % 3]})
    idx.refresh()
    return idx


PRUNE_SETTINGS = {
    "search.pallas.pruning.enabled": True,
    "search.pallas.pruning.probe_tiles": 2,
    "index.search.pallas.postings_codec": "packed",
}


class TestServicePruning:
    def test_mesh_pruned_parity_stats_and_marker(self):
        plain = build_index("prune-plain")
        pruned = build_index("prune-on", **PRUNE_SETTINGS)
        try:
            for q in [{"query": {"match": {"body": "t0 t3 t7"}},
                       "size": 10},
                      {"query": {"match": {"body": "t1"}}, "size": 5}]:
                want = plain.search(dict(q))
                got = pruned.search(dict(q))
                assert got["_plane"] == "mesh_pallas"
                assert "_pruned" in got, "pruned marker missing"
                w_hits = [h["_id"] for h in want["hits"]["hits"]]
                g_hits = [h["_id"] for h in got["hits"]["hits"]]
                assert w_hits == g_hits, q
                for gh, wh in zip(got["hits"]["hits"],
                                  want["hits"]["hits"]):
                    assert abs(gh["_score"] - wh["_score"]) < 2e-3
                # totals: a lower bound under pruning, never an overcount
                assert got["hits"]["total"] <= want["hits"]["total"]
            st = pruned.stats()["total"]["search"]["planes"]
            assert st["pruned_query_total"] >= 2
            assert st["tiles_scored_total"] > 0
            assert st["postings_codec"] == "packed"
            assert st["postings_bytes_staged"] > 0
            # packed staging is half the raw posting bytes
            st_plain = plain.stats()["total"]["search"]["planes"]
            assert st_plain["postings_codec"] == "raw"
            assert (st["postings_bytes_staged"]
                    < st_plain["postings_bytes_staged"])
        finally:
            plain.close()
            pruned.close()

    def test_pruning_actually_skips_tiles(self):
        """With a skewed posting distribution the bound order separates
        tiles and some are pruned (tiles_pruned_total > 0)."""
        idx = build_index("prune-skip", n_docs=700, seed=3,
                          **PRUNE_SETTINGS)
        try:
            for i in range(4):
                r = idx.search({"query": {"match": {"body": f"t{i} t19"}},
                                "size": 3})
                assert r["_plane"] == "mesh_pallas"
            st = idx.stats()["total"]["search"]["planes"]
            assert st["tiles_scored_total"] > 0
            # tiles_pruned may legitimately be zero on tiny corpora with
            # uniform bounds; assert the accounting adds up instead
            assert (st["tiles_scored_total"] + st["tiles_pruned_total"]
                    > 0)
        finally:
            idx.close()

    def test_exhaustive_fallback_triggers(self):
        """Requests needing every tile's dense output never take the
        pruned path: aggs, minimum_should_match (operator:and), sort —
        all still served correctly, with NO _pruned marker."""
        plain = build_index("fb-plain")
        pruned = build_index("fb-on", **PRUNE_SETTINGS)
        try:
            bodies = [
                {"query": {"match": {"body": "t0 t1"}}, "size": 5,
                 "aggs": {"tags": {"terms": {"field": "tag"}}}},
                {"query": {"match": {"body": {"query": "t0 t1",
                                              "operator": "and"}}},
                 "size": 5},
                {"query": {"match": {"body": "t2"}},
                 "sort": [{"n": {"order": "desc"}}], "size": 5},
            ]
            for q in bodies:
                want = plain.search(dict(q))
                got = pruned.search(dict(q))
                assert "_pruned" not in got, q
                assert got["hits"]["total"] == want["hits"]["total"], q
                assert ([h["_id"] for h in got["hits"]["hits"]]
                        == [h["_id"] for h in want["hits"]["hits"]]), q
                if "aggs" in q:
                    assert got["aggregations"] == want["aggregations"]
        finally:
            plain.close()
            pruned.close()

    def test_plane_fault_under_pruning_quarantines_once(self):
        idx = build_index("prune-fault", **PRUNE_SETTINGS)
        try:
            scheme = PlaneFailScheme(planes=["mesh_pallas"]).install()
            r = idx.search({"query": {"match": {"body": "t0 t1"}},
                            "size": 5})
            # served from a fallback rung, exactly one quarantine
            assert r["_plane"] != "mesh_pallas"
            assert r["hits"]["total"] > 0
            ph = idx._mesh_search.plane_health
            assert ph.failures_total["mesh_pallas"] == 1
            assert scheme.hits == 1
            assert "mesh_pallas" in ph.quarantined()
        finally:
            idx.close()

    def test_count_stays_exact_and_batch_stats_clean(self):
        """Review regressions: (a) _count / size:0 requests are
        exact-total consumers — they must never ride the pruned path
        (whose totals are gte lower bounds); (b) the Q==1 pruned fast
        path is not cross-query batching and must not inflate the
        batching-adoption counters."""
        plain = build_index("count-plain")
        pruned = build_index("count-on", **PRUNE_SETTINGS)
        try:
            q = {"query": {"match": {"body": "t0 t3"}}}
            want = plain.count(dict(q))
            got = pruned.count(dict(q))
            assert got["count"] == want["count"]
            r0 = pruned.search({"query": {"match": {"body": "t1"}},
                                "size": 0})
            assert "_pruned" not in r0
            assert r0["hits"]["total"] == plain.search(
                {"query": {"match": {"body": "t1"}},
                 "size": 0})["hits"]["total"]
            # a few pruned single queries: no batched-launch accounting
            for i in range(3):
                r = pruned.search({"query": {"match": {"body": f"t{i}"}},
                                   "size": 5})
                assert "_pruned" in r
            assert pruned._mesh_search.batched_launch_total == 0
            assert pruned._mesh_search.batched_query_total == 0
            assert pruned._mesh_search.pruned_query_total >= 3
        finally:
            plain.close()
            pruned.close()

    def test_deadline_honored_on_pruned_fast_path(self):
        """Review regression: the pruned single-query route must keep
        the PR-4 deadline contract — an expired deadline degrades to a
        partial timed_out response, never a full answer (and never a
        plane quarantine)."""
        from elasticsearch_tpu.search.cancellation import SearchDeadline

        idx = build_index("prune-deadline", **PRUNE_SETTINGS)
        try:
            # warm the pruned program so the expiry isn't racing compile
            warm = idx.search({"query": {"match": {"body": "t0"}},
                               "size": 5})
            assert "_pruned" in warm
            expired = SearchDeadline(1e-9)
            r = idx.search({"query": {"match": {"body": "t0"}},
                            "size": 5}, deadline=expired)
            assert r["timed_out"] is True
            assert idx._mesh_search.plane_health.failures_total[
                "mesh_pallas"] == 0
        finally:
            idx.close()

    def test_host_path_packed_codec_parity(self):
        """Single-shard (host plan path): the packed codec serves the
        same hits as raw within quantization tolerance — the codec
        threads the host rung, not just the mesh."""
        raw = build_index("codec-raw", n_shards=1, n_docs=300)
        packed = build_index("codec-packed", n_shards=1, n_docs=300, **{
            "search.pallas.postings_codec": "packed"})
        try:
            # staging happened under the seeded node default
            seg = next(iter(packed.shards.values())) \
                .engine.searchable_segments()[0]
            seg.device_arrays()
            assert seg.kernel_codec == "packed"
            assert seg.kernel_postings_bytes > 0
            q = {"query": {"match": {"body": "t0 t4 t9"}}, "size": 10}
            want = raw.search(dict(q))
            got = packed.search(dict(q))
            assert got["hits"]["total"] == want["hits"]["total"]
            assert ([h["_id"] for h in got["hits"]["hits"]]
                    == [h["_id"] for h in want["hits"]["hits"]])
            for gh, wh in zip(got["hits"]["hits"], want["hits"]["hits"]):
                assert abs(gh["_score"] - wh["_score"]) < 2e-3
        finally:
            raw.close()
            packed.close()
