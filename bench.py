"""Benchmark: BM25 match-query latency on the flagship TPU query path.

Mirrors the Rally `pmc` match-query config from BASELINE.md: a synthetic
academic-scale corpus (1M docs, zipfian vocabulary, ~80 terms/doc), a
multi-term BM25 disjunction with top-10 collection, p50 service time
(the marginal-batch method cannot observe per-query tails, so no p99 is
claimed; a second independent p50 estimate bounds dispersion).

The primary path is the Pallas tile-scoring kernel
(elasticsearch_tpu/ops/pallas_scoring.py): doc-tiled scatter-free scoring
with fused per-tile top-k. For comparison the bench also measures the
legacy XLA scatter-add program (the r03 path that was 4x slower than
numpy on the chip) and a vectorized numpy implementation of the same
exhaustive scoring on the host CPU (the stand-in for the reference's CPU
execution; BASELINE.json's 32-vCPU Rally baseline is not reachable in
this image). vs_baseline = numpy_p50 / kernel_p50.

Extra configs (BASELINE.md table): bool must/should/filter, terms +
cardinality aggregation over a keyword column, rescore over top-1000.

The parent process NEVER imports jax: a chip belongs to one process, and
the child that measures must own it. The parent runs that child under a
hard watchdog. No TPU, or a phase that failed, is a non-zero exit: no
number from a CPU run is ever printed under a device metric's name.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import numpy as np

N_DOCS = 1_000_000
AVG_DOC_LEN = 80
VOCAB = 50_000
BLOCK = 128
N_QUERY_TERMS = 3
K = 10
WARMUP = 5
ITERS = 50
# sustained pre-timing warm-up (~3.5s of device work): ramps the chip to
# steady state so the first timed section is not ~0.6ms/query high
WARM_QUERIES = int(os.environ.get("BENCH_WARM_QUERIES", "6000"))

TPU_ATTEMPT_TIMEOUT_S = int(os.environ.get("BENCH_TPU_TIMEOUT_S", "540"))


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def pctl(xs, p):
    return float(np.percentile(np.asarray(xs), p) * 1000)


def measure_marginal(fn, queries, b_small=10, b_big=60, reps=5):
    """Per-query device service time in seconds via marginal batch timing.

    Runs batches of b_small and b_big chained executions, each ending in one
    tiny D2H fetch (np.asarray of fn(...)[0]) that forces full completion,
    and returns (T_big - T_small) / (b_big - b_small). This cancels the
    fixed per-batch dispatch + sync overhead. Minimum over `reps`
    repetitions cuts scheduler noise."""
    def batch_time(b):
        best = None
        for r in range(reps):
            t0 = time.perf_counter()
            out = None
            for i in range(b):
                out = fn(queries[(r * b + i) % len(queries)])
            np.asarray(out[0])
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best
    t_small = batch_time(b_small)
    t_big = batch_time(b_big)
    return max((t_big - t_small) / (b_big - b_small), 1e-9)


# ----------------------------------------------------------------------
# Corpus
# ----------------------------------------------------------------------


def pack_postings(term_ids, docs, tfs, vocab, nd_pad):
    """Block-pack a (term, doc)-sorted flat posting list (vectorized —
    the same packing for the full corpus and for per-shard slices)."""
    term_start = np.searchsorted(term_ids, np.arange(vocab))
    term_end = np.searchsorted(term_ids, np.arange(vocab) + 1)
    term_df = (term_end - term_start).astype(np.int64)
    n_blocks_per_term = -(-term_df // BLOCK)
    total_blocks = max(int(n_blocks_per_term.sum()), 1)
    block_docs = np.full((total_blocks, BLOCK), nd_pad, dtype=np.int32)
    block_tfs = np.zeros((total_blocks, BLOCK), dtype=np.float32)
    term_block_start = np.concatenate(
        [[0], np.cumsum(n_blocks_per_term)[:-1]])
    within = np.arange(len(term_ids), dtype=np.int64) - term_start[term_ids]
    rows = term_block_start[term_ids] + within // BLOCK
    lanes = within % BLOCK
    block_docs[rows, lanes] = docs
    block_tfs[rows, lanes] = tfs.astype(np.float32)
    return (block_docs, block_tfs, term_block_start, n_blocks_per_term,
            term_df)


def build_synthetic_corpus(seed=7):
    """Directly build block-packed postings for a zipfian corpus (bypasses
    the host tokenizer — the bench targets the query path)."""
    rng = np.random.RandomState(seed)
    nd_pad = 1
    while nd_pad < N_DOCS:
        nd_pad *= 2
    doc_len = np.clip(
        rng.lognormal(np.log(AVG_DOC_LEN), 0.4, N_DOCS), 5, 500
    ).astype(np.int64)
    total_tokens = int(doc_len.sum())
    ranks = np.arange(1, VOCAB + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    tokens = rng.choice(VOCAB, total_tokens, p=probs).astype(np.int32)
    doc_of_token = np.repeat(np.arange(N_DOCS, dtype=np.int32), doc_len)
    keys = tokens.astype(np.int64) * N_DOCS + doc_of_token
    uniq, counts = np.unique(keys, return_counts=True)
    term_ids = (uniq // N_DOCS).astype(np.int32)
    docs = (uniq % N_DOCS).astype(np.int32)
    tfs = counts.astype(np.float32)
    (block_docs, block_tfs, term_block_start, n_blocks_per_term,
     term_df) = pack_postings(term_ids, docs, tfs, VOCAB, nd_pad)
    norms = np.ones((1, nd_pad + 1), dtype=np.float32)
    norms[0, :N_DOCS] = doc_len.astype(np.float32)
    live1 = np.zeros(nd_pad + 1, dtype=bool)
    live1[:N_DOCS] = True
    avgdl = float(doc_len.mean())
    # a zipfian keyword column for the agg config (e.g. journal name):
    # 2000 distinct values, one per doc
    kranks = np.arange(1, 2001)
    kprobs = (1.0 / kranks) / (1.0 / kranks).sum()
    keyword_ord = rng.choice(2000, N_DOCS, p=kprobs).astype(np.int32)
    keyword_pad = np.full(nd_pad, 2000, np.int32)  # sentinel ord for padding
    keyword_pad[:N_DOCS] = keyword_ord
    # a numeric column for rescore (e.g. recency score)
    numeric = np.zeros(nd_pad, np.float32)
    numeric[:N_DOCS] = rng.rand(N_DOCS).astype(np.float32) * 10.0
    return {
        "block_docs": block_docs,
        "block_tfs": block_tfs,
        "norms": norms,
        "live1": live1,
        "term_block_start": term_block_start,
        "n_blocks_per_term": n_blocks_per_term,
        "term_df": term_df,
        "avgdl": avgdl,
        "nd_pad": nd_pad,
        "keyword_ord": keyword_pad,
        "numeric": numeric,
        # flat (term, doc)-sorted postings + per-doc lengths: the mesh
        # config re-packs doc-range slices of these into per-shard blocks
        "flat": (term_ids, docs, tfs),
        "doc_len": doc_len,
    }


def idf(df):
    return math.log(1 + (N_DOCS - df + 0.5) / (df + 0.5))


# ----------------------------------------------------------------------
# Legacy scatter program + numpy baseline (same exhaustive algorithm)
# ----------------------------------------------------------------------


def make_query_legacy(corpus, terms, qb_pad):
    blocks, weights, avgdls = [], [], []
    for t in terms:
        w = idf(int(corpus["term_df"][t]))
        start = int(corpus["term_block_start"][t])
        for bi in range(start, start + int(corpus["n_blocks_per_term"][t])):
            blocks.append(bi)
            weights.append(w)
            avgdls.append(corpus["avgdl"])
    n = qb_pad
    assert len(blocks) <= n, f"query needs {len(blocks)} blocks > pad {n}"
    pad = n - len(blocks)
    return (
        np.asarray(blocks + [0] * pad, np.int32),
        np.asarray(weights + [0.0] * pad, np.float32),
        np.zeros(n, np.int32),
        np.asarray(avgdls + [1.0] * pad, np.float32),
        np.asarray([True] * len(blocks) + [False] * pad),
    )


def numpy_reference_query(corpus, q, k=K):
    """Host-CPU scoring of the same query (vectorized numpy baseline)."""
    from elasticsearch_tpu.ops.scoring import B, K1

    q_blocks, q_weights, _, q_avgdl, q_valid = q
    docs = corpus["block_docs"][q_blocks]
    tfs = corpus["block_tfs"][q_blocks]
    doc_len = corpus["norms"][0][docs]
    denom = tfs + K1 * (1 - B + B * doc_len / q_avgdl[:, None])
    matched = (tfs > 0) & q_valid[:, None]
    contrib = np.where(matched, q_weights[:, None] * tfs * (K1 + 1) / denom, 0.0)
    nd1 = corpus["norms"].shape[1]
    scores = np.zeros(nd1, np.float32)
    np.add.at(scores, docs.ravel(), contrib.ravel())
    masked = np.where((scores > 0) & corpus["live1"], scores, -np.inf)
    top_idx = np.argpartition(-masked, k)[:k]
    top_idx = top_idx[np.argsort(-masked[top_idx])]
    return masked[top_idx], top_idx


# ----------------------------------------------------------------------
# Child measurement
# ----------------------------------------------------------------------


def run_measurement() -> dict:
    t_init = time.perf_counter()
    import jax

    import jax.numpy as jnp
    from jax import lax

    devices = jax.devices()
    platform = devices[0].platform
    log(f"backend up: {platform} x{len(devices)} "
        f"in {time.perf_counter() - t_init:.1f}s")
    if platform != "tpu":
        raise RuntimeError(
            f"no TPU (jax.devices()[0].platform == {platform!r}): every "
            f"number this bench prints is a device metric")

    from elasticsearch_tpu.common import compile_cache as cc

    cc.configure_compile_cache(cc.checkout_cache_dir())

    from elasticsearch_tpu.ops.scoring import B, K1
    from elasticsearch_tpu.ops import pallas_scoring as psc

    t0 = time.perf_counter()
    corpus = build_synthetic_corpus()
    nd_pad = corpus["nd_pad"]
    log(f"corpus built in {time.perf_counter() - t0:.1f}s "
        f"({corpus['block_docs'].shape[0]} blocks)")

    # ---------------- kernel staging (shard-open analog) ----------------
    t0 = time.perf_counter()
    geom = psc.tile_geometry(nd_pad)
    frac = psc.compute_block_frac(
        corpus["block_docs"], corpus["block_tfs"], corpus["norms"][0],
        corpus["avgdl"])
    bmin, bmax = psc.block_min_max(
        corpus["block_docs"], corpus["block_tfs"], nd_pad)
    dp, fp = psc.pad_segment_blocks(corpus["block_docs"], frac, nd_pad)
    live_t = psc.build_live_t(
        corpus["live1"][:nd_pad].astype(np.float32), geom)
    dev = {
        "docs": jnp.asarray(dp),
        "frac": jnp.asarray(fp),
        "live_t": jnp.asarray(live_t),
        # legacy path arrays
        "block_docs": jnp.asarray(corpus["block_docs"]),
        "block_tfs": jnp.asarray(corpus["block_tfs"]),
        "norms": jnp.asarray(corpus["norms"]),
        "live1": jnp.asarray(corpus["live1"]),
        "keyword_ord": jnp.asarray(corpus["keyword_ord"]),
        "numeric": jnp.asarray(corpus["numeric"]),
    }
    for v in dev.values():
        v.block_until_ready()
    hbm_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in dev.values())
    stage_ms = (time.perf_counter() - t0) * 1000.0
    log(f"staged {hbm_bytes / 1e6:.0f} MB to device in "
        f"{stage_ms / 1000.0:.1f}s; geom={geom}")
    # bench stages the corpus directly (no Segment/IndexService in the
    # loop), so it registers with the device-memory accountant itself —
    # the report's staged_bytes_total / restage_amplification read the
    # same ledger production serves from (ISSUE 9, docs/OBSERVABILITY.md)
    from elasticsearch_tpu.common import memory as dm

    acct = dm.memory_accountant()
    _k = dict(reason="initial", duration_ms=stage_ms)
    acct.register("bench", "corpus", dm.KIND_POSTINGS_RAW, "k_postings",
                  int(dev["docs"].nbytes + dev["frac"].nbytes
                      + dev["block_docs"].nbytes
                      + dev["block_tfs"].nbytes), **_k)
    acct.register("bench", "corpus", dm.KIND_LIVE_MASK, "live",
                  int(dev["live_t"].nbytes + dev["live1"].nbytes), **_k)
    acct.register("bench", "corpus", dm.KIND_SCALE_NORM, "norms",
                  int(dev["norms"].nbytes), **_k)
    acct.register("bench", "corpus", dm.KIND_DOC_VALUES, "columns",
                  int(dev["keyword_ord"].nbytes + dev["numeric"].nbytes),
                  **_k)

    # ---------------- query mix ----------------
    rng = np.random.RandomState(3)
    term_sets = [list(rng.randint(50, 1000, N_QUERY_TERMS))
                 for _ in range(ITERS + WARMUP)]

    # legacy/numpy query pad: one shape bucket covering the whole run
    max_blocks = max(
        sum(int(corpus["n_blocks_per_term"][t]) for t in ts)
        for ts in term_sets)
    qb_pad = 1
    while qb_pad < max_blocks:
        qb_pad *= 2

    def kernel_query(terms, t_pad=4, cb=None):
        lanes = [psc.QueryLane(int(corpus["term_block_start"][t]),
                               int(corpus["n_blocks_per_term"][t]),
                               idf(int(corpus["term_df"][t])))
                 for t in terms]
        return psc.build_tile_tables(lanes, bmin, bmax, geom,
                                     t_pad=t_pad, cb=cb)

    kernel_metrics = None
    cb_run = None
    try:
        # uniform CB bucket across the whole run -> one compiled program;
        # the tile tables themselves do not depend on cb, so build once
        kqueries = [kernel_query(ts) for ts in term_sets]
        cb_run = max(kq[3] for kq in kqueries)
        staged_kq = [(jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))
                     for rl, rh, w, _ in kqueries]

        @jax.jit
        def _kernel_fused(docs, frac, live_t, rl, rh, w):
            # one program = one dispatch: the tile kernel + global merge
            # fuse under a single jit (two separate dispatches double the
            # per-call overhead and the marginal-timing jitter)
            ts_, td_, th_ = psc.score_tiles(
                docs, frac, live_t, rl, rh, w,
                t_pad=4, cb=cb_run, sub=geom.tile_sub, k=K)
            return psc.merge_tile_topk(ts_, td_, th_, K)

        def run_kernel(q):
            rl, rh, w = q
            return _kernel_fused(dev["docs"], dev["frac"], dev["live_t"],
                                 rl, rh, w)

        t0 = time.perf_counter()
        top_s, top_d, hits = run_kernel(staged_kq[0])
        top_s.block_until_ready()
        log(f"kernel first compile+run in {time.perf_counter() - t0:.1f}s "
            f"(cb={cb_run})")

        # Timing methodology: MARGINAL BATCH time. Run B and then N*B
        # chained executions, each batch ending in one tiny D2H that
        # forces full completion; the per-query device service time is
        # (T_big - T_small) / (extra queries), which cancels the fixed
        # dispatch+sync overhead exactly. measure_marginal() below also
        # repeats each batch and takes the minimum to cut scheduler noise.
        np.asarray(hits)  # first D2H before any timed section

        # sustained warm-up to steady-state clocks/pipeline: without it
        # the FIRST timed section reads ~0.6 ms/query high regardless of
        # what it contains (round 4 reported "merge_topk 0.829ms" in the
        # stage breakdown — that was exactly this artifact hitting the
        # fused program, which was measured before score-only; verified
        # by reordering the sections in experiments/merge_variants.py:
        # whichever variant is timed first is slow, and the same program
        # re-timed later runs at ~0.58 ms)
        t0 = time.perf_counter()
        wout = None
        for i in range(WARM_QUERIES):
            wout = run_kernel(staged_kq[i % len(staged_kq)])
        if wout is not None:  # BENCH_WARM_QUERIES=0 skips the warm-up
            np.asarray(wout[0])
        log(f"steady-state warmup: {WARM_QUERIES} queries in "
            f"{time.perf_counter() - t0:.1f}s")

        timed = staged_kq[WARMUP:]
        per_query = measure_marginal(run_kernel, timed)

        def run_score_only(q):
            rl, rh, w = q
            return psc.score_tiles(
                dev["docs"], dev["frac"], dev["live_t"], rl, rh, w,
                t_pad=4, cb=cb_run, sub=geom.tile_sub, k=K)

        score_only = measure_marginal(run_score_only, timed)

        # per-phase attribution (ISSUE 8, docs/OBSERVABILITY.md): the
        # host-side plan/table-build cost per query — the production
        # path rebuilds the tile tables per request, so the staging rung
        # of the phase taxonomy has a real per-query price even though
        # the corpus itself stays resident
        t0 = time.perf_counter()
        n_stage = 0
        for ts in term_sets[WARMUP:]:
            kernel_query(ts, cb=cb_run)
            n_stage += 1
        table_build_ms = ((time.perf_counter() - t0)
                          / max(n_stage, 1) * 1000)

        kernel_metrics = {
            "stage_table_build": table_build_ms,
            "p50": per_query * 1000,
            # marginal estimates carry no per-query tail — a "p99" from
            # this method would be an artifact (round-4 VERDICT). Report
            # a SECOND independent p50 estimate as a dispersion proxy,
            # under a name that says what it is.
            "p50_2": measure_marginal(run_kernel, timed) * 1000,
            "stage_score_p50": score_only * 1000,
            # gate fetch happens after all timed sections
            "gate": (top_s, top_d),
        }
    except Exception as e:
        # the primary path failed: no other program takes its place
        log(f"kernel path FAILED ({type(e).__name__}: {e})")
        raise

    # ---------------- extra configs (same marginal methodology) ----------
    def stamp_mem(*cfgs):
        """Stamp the device-memory ledger's view (ISSUE 9) onto each
        config dict AS IT COMPLETES: staged_bytes_total is the ledger's
        bench-index bytes at that point, restage_amplification the
        restaged/logically-changed ratio (non-null once the packed
        config re-stages the corpus)."""
        st = dm.memory_accountant().stats("bench")
        for cfg in cfgs:
            if isinstance(cfg, dict) and "error" not in cfg:
                cfg["staged_bytes_total"] = st["staged_bytes_total"]
                cfg["restage_amplification"] = st["restage_amplification"]

    extra_configs = None
    if kernel_metrics is not None:
        extra_configs = run_extra_configs(
            jax, jnp, lax, psc, corpus, dev, geom, bmin, bmax, cb_run, rng)
        stamp_mem(*extra_configs.values())
        # cross-query micro-batching sweep (ISSUE 5 acceptance config)
        try:
            extra_configs["batched_qps"] = run_batched_qps_config(
                jax, jnp, psc, corpus, dev, geom, frac, bmin, bmax)
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["batched_qps"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["batched_qps"])
        # the mesh-path config: distributed scoring on the tile kernel
        # (acceptance: within 2x of the single-chip pallas p50)
        try:
            extra_configs["mesh_pallas_packed"] = run_mesh_pallas_config(
                jax, jnp, lax, psc, corpus, term_sets)
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["mesh_pallas_packed"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["mesh_pallas_packed"])
        # ISSUE 6 acceptance configs: bit-packed postings codec and
        # block-max pruned scoring (each recall-gated vs the RAW oracle)
        try:
            packed_cfg, pruned_cfg = run_codec_pruning_configs(
                jax, jnp, psc, corpus, dev, geom, frac, bmin, bmax,
                cb_run, term_sets)
            extra_configs["packed_postings"] = packed_cfg
            extra_configs["pruned_scoring"] = pruned_cfg
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["packed_postings"] = {
                "error": f"{type(e).__name__}: {e}"}
            extra_configs["pruned_scoring"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["packed_postings"],
                  extra_configs["pruned_scoring"])
        # ISSUE 7 acceptance configs: dense-vector kNN on the MXU +
        # hybrid BM25 ∪ kNN ranking (recall-gated vs the numpy oracle)
        try:
            knn_cfg, hybrid_cfg = run_knn_configs(
                jax, jnp, psc, corpus, dev, geom, frac, bmin, bmax,
                term_sets)
            extra_configs["knn_top10"] = knn_cfg
            extra_configs["hybrid_rrf"] = hybrid_cfg
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["knn_top10"] = {
                "error": f"{type(e).__name__}: {e}"}
            extra_configs["hybrid_rrf"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["knn_top10"],
                  extra_configs["hybrid_rrf"])
        # ISSUE 10 acceptance config: serving capacity with the chaos
        # schemes running (BENCH_r10 — availability + qps under faults)
        try:
            extra_configs["fault_soak"] = run_fault_soak_config()
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["fault_soak"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["fault_soak"])
        # ISSUE 12 acceptance config: goodput/fairness at offered load
        # >> capacity with zipfian tenants (docs/OVERLOAD.md)
        try:
            extra_configs["overload_zipfian"] = \
                run_overload_zipfian_config()
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["overload_zipfian"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["overload_zipfian"])
        # ISSUE 14 acceptance config: cold-start stall elimination —
        # first-query latency cold vs compile-cache-warmed + drain p99
        # (docs/RESILIENCE.md "Rollout & drain")
        try:
            extra_configs["cold_start"] = run_cold_start_config()
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["cold_start"] = {
                "error": f"{type(e).__name__}: {e}"}
        stamp_mem(extra_configs["cold_start"])
        # ISSUE 20 acceptance config: ingest + search under sustained
        # delta device staging (docs/MESH.md "Slot allocator &
        # generations"). NO stamp_mem here: the config reports its own
        # windowed restage_amplification and the stamp would clobber it
        try:
            extra_configs["nrt_ingest"] = run_nrt_ingest_config()
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra_configs["nrt_ingest"] = {
                "error": f"{type(e).__name__}: {e}"}

    # ---------------- timings: legacy scatter path (r03) ----------------
    legacy_p50 = None
    try:
        n_legacy = (WARMUP + 10) if kernel_metrics else (WARMUP + ITERS // 2)

        @jax.jit
        def legacy_query(block_docs, block_tfs, norms, live1, q_blocks,
                         q_weights, q_norm_rows, q_avgdl, q_valid):
            docs = block_docs[q_blocks]
            tfs = block_tfs[q_blocks]
            nd1 = norms.shape[1]
            flat_idx = (q_norm_rows[:, None] * nd1 + docs).ravel()
            doc_len = norms.ravel()[flat_idx].reshape(docs.shape)
            denom = tfs + K1 * (1.0 - B + B * doc_len / q_avgdl[:, None])
            matched_blk = (tfs > 0.0) & q_valid[:, None]
            contrib = jnp.where(
                matched_blk, q_weights[:, None] * tfs * (K1 + 1.0) / denom,
                0.0)
            scores = jnp.zeros((nd1,), jnp.float32).at[docs].add(contrib)
            masked = jnp.where((scores > 0) & live1, scores, -jnp.inf)
            return lax.top_k(masked, K)

        lq = [tuple(jnp.asarray(x)
                    for x in make_query_legacy(corpus, ts, qb_pad))
              for ts in term_sets[:n_legacy]]

        def run_legacy(q):
            return legacy_query(dev["block_docs"], dev["block_tfs"],
                                dev["norms"], dev["live1"], *q)

        np.asarray(run_legacy(lq[0])[0])  # compile
        legacy_pq = measure_marginal(run_legacy, lq[WARMUP:] or lq)
        legacy_p50 = legacy_pq * 1000
    except Exception as e:  # noqa: BLE001
        log(f"legacy path failed: {e}")

    # ---------------- correctness gate ------------------------------------
    sync_overhead_ms = None
    if kernel_metrics is not None:
        try:
            top_s, top_d = kernel_metrics.pop("gate")
            q0 = make_query_legacy(corpus, term_sets[0], qb_pad)
            ref_s, ref_i = numpy_reference_query(corpus, q0)
            got_s = np.asarray(top_s)
            got_d = np.asarray(top_d)
            # tie-robust gate: sorted score values must match; the doc set
            # may legitimately differ on exact score ties. recall_at_10
            # reports the MEASURED intersection, not an assumption.
            np.testing.assert_allclose(got_s, ref_s, rtol=1e-3)
            recall = len(set(got_d.tolist()) & set(ref_i.tolist())) / K
            if recall < 1.0:
                kth = ref_s[-1]
                assert (got_s >= kth * (1 - 1e-3)).all(), \
                    "non-tie doc mismatch vs reference"
            kernel_metrics["recall"] = recall
            log(f"correctness gate passed (measured recall@10 = {recall})")

            # record the fixed per-sync cost: one execution + one tiny
            # D2H, minus the device time already measured marginally
            sync_lat = []
            for q in staged_kq[WARMUP: WARMUP + 3]:
                t0 = time.perf_counter()
                np.asarray(run_kernel(q)[0])
                sync_lat.append(time.perf_counter() - t0)
            sync_overhead_ms = max(
                pctl(sync_lat, 50) - kernel_metrics["p50"], 0.0)
        except Exception as e:
            # a failed gate fails the run: no other path's numbers
            # stand in for the kernel's
            log(f"kernel correctness gate FAILED ({type(e).__name__}: {e})")
            raise

    # ---------------- numpy baseline ----------------
    nq = [make_query_legacy(corpus, ts, qb_pad)
          for ts in term_sets[: WARMUP + 10]]
    cpu_lat = []
    for q in nq:
        t0 = time.perf_counter()
        numpy_reference_query(corpus, q)
        cpu_lat.append(time.perf_counter() - t0)
    cpu_p50 = pctl(cpu_lat[2:], 50)

    p50, p50_2 = kernel_metrics["p50"], kernel_metrics["p50_2"]
    path = "pallas_tile_kernel"
    # HBM traffic for one kernel query: two cb-aligned posting windows
    # (docs + frac) per lane per tile + the live mask + tiny outputs
    bytes_per_query = (
        geom.n_tiles * 4 * (2 * cb_run) * BLOCK * (4 + 4)
        + geom.n_tiles * geom.tile_w * 4
        + geom.n_tiles * (2 * K + 1) * 4
    )
    stage = {
        "score_tiles_kernel": round(kernel_metrics["stage_score_p50"], 3),
        "merge_topk": round(
            max(kernel_metrics["p50"]
                - kernel_metrics["stage_score_p50"], 0.0), 3),
    }
    # per-phase p50 attribution in the phase-taxonomy vocabulary
    # (docs/OBSERVABILITY.md): where one query's wall budget goes —
    # the item-1/item-5 tuning decisions (codec/pruning flips, ICI
    # serving loop) read this, not the raw stage numbers
    phase_attribution = {
        "plan_build": round(kernel_metrics["stage_table_build"], 3),
        "kernel": stage["score_tiles_kernel"],
        "merge": stage["merge_topk"],
    }
    recall = kernel_metrics["recall"]
    method = ("marginal batch timing: per-query device service time = "
              "(T[60 chained queries] - T[10]) / 50, each batch ending in "
              "one tiny D2H that forces completion; cancels the fixed "
              "per-batch dispatch + sync overhead")
    # ISSUE 6: the headline reports the best codec/pruning mode that
    # PASSED its recall gate (recall@10 == 1.0 vs the raw oracle) —
    # and says which mode produced it. Raw exhaustive remains the
    # floor: a failed gate or slower config can never claim it.
    headline_mode = {"config": "main", "postings_codec": "raw",
                     "pruning": False}
    if isinstance(extra_configs, dict):
        for cfg_name, mode in (
                ("packed_postings",
                 {"postings_codec": "packed", "pruning": False}),
                ("pruned_scoring",
                 {"postings_codec": "packed", "pruning": True})):
            cfg = extra_configs.get(cfg_name)
            if not isinstance(cfg, dict):
                continue
            cfg_p50 = cfg.get("p50_ms")
            if (cfg.get("recall_at_10") == 1.0
                    and isinstance(cfg_p50, (int, float))
                    and cfg_p50 < p50):
                p50 = cfg_p50
                p50_2 = cfg_p50 + cfg.get("p50_spread_ms", 0.0)
                headline_mode = dict(mode, config=cfg_name)
                bq = cfg.get("bytes_per_query_mb_pruned",
                             cfg.get("bytes_per_query_mb_packed"))
                if bq is not None:
                    bytes_per_query = bq * 1e6

    # ISSUE 13 acceptance config: fused on-device aggregations,
    # bucket-equality gated vs the numpy oracle (docs/AGGS.md)
    try:
        agg_cfg = run_agg_fused_config(
            jax, jnp, lax, psc, corpus, dev, geom, bmin, bmax, cb_run)
    except Exception as e:  # noqa: BLE001 — recorded, never fatal
        import traceback

        traceback.print_exc(file=sys.stderr)
        agg_cfg = {"error": f"{type(e).__name__}: {e}"}
    if not isinstance(extra_configs, dict):
        extra_configs = {}
    extra_configs["agg_fused"] = agg_cfg
    stamp_mem(agg_cfg)

    hbm_gbps = bytes_per_query / (p50 / 1000) / 1e9

    return {
        "metric": "bm25_match_top10_p50_latency_1M_docs",
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_p50 / p50, 2),
        "extra": {
            "backend": platform,
            "path": path,
            # which postings codec / pruning mode produced the headline
            # value (ISSUE 6): only recall-gated configs may claim it
            "headline_mode": headline_mode,
            # marginal batch timing cannot observe per-query tails; a
            # second independent estimate bounds run-to-run dispersion
            "p50_second_estimate_ms": round(p50_2, 3),
            "qps_per_chip": round(1000.0 / p50, 1),
            # cross-query micro-batching headline (q_batch=8 sweep point;
            # the full sweep is configs.batched_qps)
            "qps_per_chip_batched": (
                (extra_configs or {}).get("batched_qps", {})
                .get("q_batch_8", {}).get("qps_per_chip_batched")
                if isinstance(extra_configs, dict) else None),
            "bytes_per_query_mb_batched": (
                (extra_configs or {}).get("batched_qps", {})
                .get("q_batch_8", {}).get("bytes_per_query_mb_batched")
                if isinstance(extra_configs, dict) else None),
            # dense-vector plane headlines (ISSUE 7): exact kNN top-10
            # p50 on the MXU (recall-gated) and hybrid BM25 ∪ kNN RRF
            # throughput — None when the config errored or failed its
            # recall gate (configs.knn_top10 / configs.hybrid_rrf carry
            # the detail either way)
            "vector_top10_p50": (
                (extra_configs or {}).get("knn_top10", {}).get("p50_ms")
                if isinstance(extra_configs, dict)
                and (extra_configs.get("knn_top10", {})
                     .get("recall_at_10") == 1.0) else None),
            "hybrid_qps_per_chip": (
                (extra_configs or {}).get("hybrid_rrf", {})
                .get("qps_per_chip")
                if isinstance(extra_configs, dict)
                and (extra_configs.get("hybrid_rrf", {})
                     .get("fused_recall_at_10") == 1.0) else None),
            # device-plane chaos headline (ISSUE 10): serving capacity
            # with fault injection running — availability (zero-5xx as
            # a measured fraction) and qps/chip under the fault_soak
            # scheme mix (configs.fault_soak carries the detail)
            "availability_under_faults": (
                (extra_configs or {}).get("fault_soak", {})
                .get("availability_under_faults")
                if isinstance(extra_configs, dict) else None),
            "qps_under_faults_per_chip": (
                (extra_configs or {}).get("fault_soak", {})
                .get("qps_under_faults_per_chip")
                if isinstance(extra_configs, dict) else None),
            # NRT delta-staging headlines (ISSUE 20, docs/MESH.md "Slot
            # allocator & generations"): ingest + search throughput
            # under sustained incremental device staging, and the
            # append-window restage amplification (~1 = every refresh
            # rode the delta path; configs.nrt_ingest has the detail —
            # its restage_amplification is windowed over the append
            # legs, unlike the whole-run ratio below)
            "ingest_docs_per_s": (
                (extra_configs or {}).get("nrt_ingest", {})
                .get("ingest_docs_per_s")
                if isinstance(extra_configs, dict) else None),
            "search_p50_under_ingest_ms": (
                (extra_configs or {}).get("nrt_ingest", {})
                .get("search_p50_under_ingest_ms")
                if isinstance(extra_configs, dict) else None),
            "restage_amplification_nrt": (
                (extra_configs or {}).get("nrt_ingest", {})
                .get("restage_amplification")
                if isinstance(extra_configs, dict) else None),
            # overload-control headline (ISSUE 12, docs/OVERLOAD.md):
            # goodput, bounded admitted-p99, reject rate, and tenant
            # fairness at offered load >> capacity with zipfian tenants
            # (configs.overload_zipfian carries the detail)
            "goodput_qps_under_overload": (
                (extra_configs or {}).get("overload_zipfian", {})
                .get("goodput_qps_under_overload")
                if isinstance(extra_configs, dict) else None),
            "admitted_p99_ms": (
                (extra_configs or {}).get("overload_zipfian", {})
                .get("admitted_p99_ms")
                if isinstance(extra_configs, dict) else None),
            "reject_rate": (
                (extra_configs or {}).get("overload_zipfian", {})
                .get("reject_rate")
                if isinstance(extra_configs, dict) else None),
            "max_tenant_starvation_ratio": (
                (extra_configs or {}).get("overload_zipfian", {})
                .get("max_tenant_starvation_ratio")
                if isinstance(extra_configs, dict) else None),
            # fused on-device aggregations headline (ISSUE 13,
            # docs/AGGS.md): agg'd-query latency with the bucket
            # reductions fused into the scoring launch, what the host
            # round-trip used to cost on top, and the doc-value column
            # bytes per query (configs.agg_fused carries the detail +
            # the bucket-equality gate)
            "agg_p50_ms": agg_cfg.get("agg_p50_ms"),
            "agg_host_roundtrip_saved_ms": agg_cfg.get(
                "agg_host_roundtrip_saved_ms"),
            "bytes_per_query_mb_agg": agg_cfg.get(
                "bytes_per_query_mb_agg"),
            "cpu_numpy_p50_ms": round(cpu_p50, 3),
            "legacy_scatter_p50_ms": (round(legacy_p50, 3)
                                      if legacy_p50 else None),
            "sync_overhead_ms": (
                round(sync_overhead_ms, 3) if sync_overhead_ms is not None
                else None),
            "stage_breakdown_ms": stage,
            # where one query's p50 goes, in the phase-taxonomy
            # vocabulary of docs/OBSERVABILITY.md (staging vs kernel vs
            # merge) — the ROADMAP item-1/item-5 decisions read this
            "phase_attribution_p50_ms": phase_attribution,
            "n_docs": N_DOCS,
            "recall_at_10": recall,
            # device-memory ledger view (ISSUE 9): exact bytes the bench
            # corpus holds staged, and restaged/logically-changed — the
            # ROADMAP item-3 number (non-null once the packed config
            # re-staged the corpus in a second layout)
            "staged_bytes_total": (
                dm.memory_accountant().stats("bench")
                ["staged_bytes_total"]),
            "restage_amplification": (
                dm.memory_accountant().stats("bench")
                ["restage_amplification"]),
            "hbm_gb_per_s_estimate": round(hbm_gbps, 1),
            "bytes_per_query_mb": round(bytes_per_query / 1e6, 2),
            "corpus_hbm_mb": round(hbm_bytes / 1e6, 1),
            "tile_geometry": {"n_tiles": geom.n_tiles, "tile_w": geom.tile_w,
                              "cb": cb_run},
            "configs": extra_configs,
            "method": method,
        },
    }


def run_extra_configs(jax, jnp, lax, psc, corpus, dev, geom, bmin, bmax,
                      cb_run, rng):
    """The remaining BASELINE.md configs, each a small timed program.
    Failures are reported per-config, never fatal."""
    import numpy as np

    out = {}
    # Estimator note (BENCH_r05 rescore_top1000 diagnosis: p50 1.625 vs
    # second estimate 2.406 ms): between configs the device idles while
    # the host stages the next config's arrays, so clocks ramp down and
    # the next marginal estimate reads HIGH — the same artifact the main
    # path's 6000-query warm-up removes, re-entering here config by
    # config. Marginal-batch noise is one-sided (preemption, ramp-down
    # and sync jitter only ADD time; nothing executes faster than the
    # device), so the MINIMUM of several estimates after a short re-warm
    # is the trustworthy p50; the spread field bounds dispersion.
    out["estimator_note"] = (
        "p50_ms is the min of 3 marginal estimates after a 200-query "
        "re-warm (marginal noise is one-sided: idle clock ramp-down "
        "between configs inflates estimates, nothing deflates them); "
        "p50_spread_ms = max - min of the 3")

    def time_it(fn, warm=2):
        """fn() must return the (device-array, ...) outputs of one query.
        Marginal batch timing — see measure_marginal and estimator_note."""
        for _ in range(warm):
            fn()
        # short sustained re-warm to steady-state clocks: the host-side
        # staging between configs idles the device long enough for the
        # first estimate to read high otherwise
        o = None
        for _ in range(200):
            o = fn()
        np.asarray(o[0])
        ests = sorted(measure_marginal(lambda _q: fn(), [None])
                      for _ in range(3))
        return ests[0] * 1000, (ests[-1] - ests[0]) * 1000

    def lanes_for(terms):
        return [psc.QueryLane(int(corpus["term_block_start"][t]),
                              int(corpus["n_blocks_per_term"][t]),
                              idf(int(corpus["term_df"][t])))
                for t in terms]

    # ---- config 2: bool must + should + filter ----
    try:
        must_t = int(rng.randint(50, 200))
        should_ts = [int(x) for x in rng.randint(200, 2000, 2)]
        rl_m, rh_m, w_m, _ = psc.build_tile_tables(
            lanes_for([must_t]), bmin, bmax, geom, t_pad=4, cb=cb_run)
        rl_a, rh_a, w_a, _ = psc.build_tile_tables(
            lanes_for([must_t] + should_ts), bmin, bmax, geom, t_pad=4,
            cb=cb_run)
        args_m = (jnp.asarray(rl_m), jnp.asarray(rh_m), jnp.asarray(w_m))
        args_a = (jnp.asarray(rl_a), jnp.asarray(rh_a), jnp.asarray(w_a))
        lo, hi = 2.0, 8.0

        @jax.jit
        def bool_query(docs, frac, live_t, rlm, rhm, wm, rla, rha, wa,
                       numeric):
            # dense scores for all clauses; dense counts for the must lane
            all_s = psc.score_tiles(docs, frac, live_t, rla, rha, wa,
                                    t_pad=4, cb=cb_run, sub=geom.tile_sub,
                                    dense=True)[0]
            must_s, must_c = psc.score_tiles(
                docs, frac, live_t, rlm, rhm, wm, t_pad=4, cb=cb_run,
                sub=geom.tile_sub, dense=True, with_counts=True)
            scores = psc.dense_to_flat(all_s, geom.tile_sub)
            mustc = psc.dense_to_flat(must_c, geom.tile_sub)
            filt = (numeric >= lo) & (numeric <= hi)
            masked = jnp.where((mustc > 0) & filt, scores, -jnp.inf)
            # hierarchical top-k: per-row then global
            m2 = masked.reshape(1024, -1)
            s_r, i_r = lax.top_k(m2, K)
            flat_i = (jnp.arange(1024, dtype=jnp.int32)[:, None] * m2.shape[1] + i_r).reshape(-1)
            s_f, i_f = lax.top_k(s_r.reshape(-1), K)
            return s_f, flat_i[i_f], jnp.sum(masked > -jnp.inf)

        def run_bool():
            return bool_query(dev["docs"], dev["frac"], dev["live_t"],
                              *args_m, *args_a, dev["numeric"])
        p50b, spreadb = time_it(run_bool)
        out["bool_must_should_filter"] = {"p50_ms": round(p50b, 3),
                                          "p50_spread_ms": round(spreadb, 3)}
    except Exception as e:  # noqa: BLE001
        out["bool_must_should_filter"] = {"error": f"{type(e).__name__}: {e}"}

    # ---- config 3: terms + cardinality agg over keyword column ----
    try:
        terms = [int(x) for x in rng.randint(50, 500, 2)]
        rl, rh, w, _ = psc.build_tile_tables(
            lanes_for(terms), bmin, bmax, geom, t_pad=4, cb=cb_run)
        args = (jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))

        from elasticsearch_tpu.ops import pallas_aggs as pag

        @jax.jit
        def agg_query(docs, frac, live_t, rl, rh, w, kw):
            ds = psc.score_tiles(docs, frac, live_t, rl, rh, w,
                                 t_pad=4, cb=cb_run, sub=geom.tile_sub,
                                 dense=True)[0]
            scores = psc.dense_to_flat(ds, geom.tile_sub)
            contrib = jnp.where(scores > 0, jnp.float32(1.0),
                                jnp.float32(0.0))
            # terms agg: pallas segment-sum over keyword ordinals (the
            # scatter-free BucketsAggregator.collect analog)
            (counts,) = pag.segment_aggregate(kw, contrib, n_ords=2000)
            top_counts, top_ords = lax.top_k(counts, 10)
            # cardinality: count of distinct matched ordinals (exact here;
            # the engine's HLL++ kernel is ops/aggs.py)
            card = jnp.sum(counts > 0)
            return top_counts, top_ords, card

        def run_agg():
            return agg_query(dev["docs"], dev["frac"], dev["live_t"],
                             *args, dev["keyword_ord"])
        p50a, spreada = time_it(run_agg)
        out["terms_cardinality_agg"] = {"p50_ms": round(p50a, 3),
                                        "p50_spread_ms": round(spreada, 3)}
    except Exception as e:  # noqa: BLE001
        out["terms_cardinality_agg"] = {"error": f"{type(e).__name__}: {e}"}

    # ---- config 5: DMA double-buffering (tiles_per_step=2) ----
    try:
        terms = [int(x) for x in rng.randint(50, 1000, 3)]
        rl5, rh5, w5, _ = psc.build_tile_tables(
            lanes_for(terms), bmin, bmax, geom, t_pad=4, cb=cb_run)
        args5 = (jnp.asarray(rl5), jnp.asarray(rh5), jnp.asarray(w5))

        @jax.jit
        def tps2_query(docs, frac, live_t, rl, rh, w):
            ts_, td_, th_ = psc.score_tiles(
                docs, frac, live_t, rl, rh, w,
                t_pad=4, cb=cb_run, sub=geom.tile_sub, k=K,
                tiles_per_step=2)
            return psc.merge_tile_topk(ts_, td_, th_, K)

        def run_tps2():
            return tps2_query(dev["docs"], dev["frac"], dev["live_t"],
                              *args5)
        p50t, spreadt = time_it(run_tps2)
        out["pallas_tiles_per_step2"] = {
            "p50_ms": round(p50t, 3),
            "p50_spread_ms": round(spreadt, 3),
            "note": ("grid coarsened to 2 tiles/step: posting-window DMAs "
                     "for the second tile issue while the first computes, "
                     "halving the fixed per-step cost the kernel comment "
                     "names as dominant; compare against the main p50 to "
                     "decide the search.pallas.tiles_per_step default"),
        }
    except Exception as e:  # noqa: BLE001
        out["pallas_tiles_per_step2"] = {"error": f"{type(e).__name__}: {e}"}

    # ---- config 4: rescore over top-1000 ----
    try:
        terms = [int(x) for x in rng.randint(50, 1000, 3)]
        rl, rh, w, _ = psc.build_tile_tables(
            lanes_for(terms), bmin, bmax, geom, t_pad=4, cb=cb_run)
        args = (jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))

        @jax.jit
        def rescore_query(docs, frac, live_t, rl, rh, w, numeric):
            ds = psc.score_tiles(docs, frac, live_t, rl, rh, w,
                                 t_pad=4, cb=cb_run, sub=geom.tile_sub,
                                 dense=True)[0]
            scores = psc.dense_to_flat(ds, geom.tile_sub)
            masked = jnp.where(scores > 0, scores, -jnp.inf)
            # exact top-1000 window (a per-row hierarchical cut would clip
            # rows holding >4 of the true top-1000)
            s1k, window = lax.top_k(masked, 1000)
            # function_score rescore: query_weight*s + rescore_weight*fn
            fn = jnp.log1p(numeric[window])
            rescored = s1k * 1.0 + fn * 0.5
            return lax.top_k(rescored, K)

        def run_rescore():
            return rescore_query(dev["docs"], dev["frac"], dev["live_t"],
                                 *args, dev["numeric"])
        p50r, spreadr = time_it(run_rescore)
        out["rescore_top1000"] = {
            "p50_ms": round(p50r, 3),
            "p50_spread_ms": round(spreadr, 3),
            "note": ("r05 showed 1.625 vs 2.406 ms estimates here: the "
                     "second estimate ran after the device idled through "
                     "host-side staging (clock ramp-down); see "
                     "estimator_note — min-of-3 after re-warm is the "
                     "trustworthy figure"),
        }
    except Exception as e:  # noqa: BLE001
        out["rescore_top1000"] = {"error": f"{type(e).__name__}: {e}"}

    return out


def run_batched_qps_config(jax, jnp, psc, corpus, dev, geom, frac,
                           bmin, bmax):
    """Cross-query micro-batching sweep (ISSUE 5): q_batch in {1,4,8,16}
    on the 1M-doc corpus, one batched ``score_tiles`` launch per batch
    over UNION tables + the per-query fused top-k, every member
    recall-gated against the numpy oracle.

    Query mix: 3 terms per query drawn ZIPFIAN from a 1000-term hot
    query vocabulary — the production property the batching exploits
    (concurrent queries share hot terms, so the union lane count grows
    sublinearly in Q and the shared posting-window DMA amortizes). The
    estimator is the min-of-3 marginal method of estimator_note (the
    r05 rescore_top1000 one-sided-spread fix applies here too: these
    numbers gate an acceptance criterion and must not be
    ramp-down-noise-dominated)."""
    import numpy as np

    rng = np.random.RandomState(11)
    # zipf over a hot query vocabulary (rank 50..1049 of the corpus
    # zipf, i.e. realistic mid-frequency search terms)
    qvocab = np.arange(50, 1050)
    ranks = np.arange(1, len(qvocab) + 1, dtype=np.float64)
    probs = (1.0 / ranks) / (1.0 / ranks).sum()

    def draw_query():
        return list(np.unique(rng.choice(qvocab, 3, p=probs)))

    def lanes_for(terms):
        return [psc.QueryLane(int(corpus["term_block_start"][t]),
                              int(corpus["n_blocks_per_term"][t]),
                              idf(int(corpus["term_df"][t])))
                for t in terms]

    def time_min3(fn):
        """min-of-3 marginal estimate after a sustained re-warm (see
        estimator_note: marginal noise is one-sided)."""
        for _ in range(2):
            fn()
        o = None
        for _ in range(200):
            o = fn()
        np.asarray(o[0])
        ests = sorted(measure_marginal(lambda _q: fn(), [None])
                      for _ in range(3))
        return ests[0] * 1000, (ests[-1] - ests[0]) * 1000

    out = {"query_mix": ("3 zipfian terms per query from a 1000-term "
                         "hot vocabulary; batches drawn independently")}
    nd_pad = corpus["nd_pad"]
    base_qps = None
    for q_batch in (1, 4, 8, 16):
        n_batches = 8
        batches = [[draw_query() for _ in range(q_batch)]
                   for _ in range(n_batches)]
        staged, t_pad_run, cb_run = [], 8, 8
        tables = []
        for batch in batches:
            rl, rh, w, cbr = psc.build_tile_tables_batched(
                [lanes_for(ts) for ts in batch], bmin, bmax, geom)
            tables.append((rl, rh, w))
            t_pad_run = max(t_pad_run, rl.shape[1])
            cb_run = max(cb_run, cbr)
        # one shape bucket per q_batch: pad every batch's tables to the
        # run-wide (t_pad, cb) so the sweep compiles once per Q
        for rl, rh, w in tables:
            if rl.shape[1] < t_pad_run:
                pad = t_pad_run - rl.shape[1]
                rl = np.pad(rl, ((0, 0), (0, pad)))
                rh = np.pad(rh, ((0, 0), (0, pad)))
                w = np.pad(w, ((0, 0), (0, pad)))
            staged.append((jnp.asarray(rl), jnp.asarray(rh),
                           jnp.asarray(w)))

        @jax.jit
        def _batched_fused(docs, frac_d, live_t, rl, rh, w,
                           t_pad=t_pad_run, cb=cb_run, qb=q_batch):
            ts_, td_, th_ = psc.score_tiles(
                docs, frac_d, live_t, rl, rh, w,
                t_pad=t_pad, cb=cb, sub=geom.tile_sub, k=K, q_batch=qb)
            return psc.merge_tile_topk_batched(ts_, td_, th_, K)

        cycle = {"i": 0}

        def run_batch():
            q = staged[cycle["i"] % len(staged)]
            cycle["i"] += 1
            return _batched_fused(dev["docs"], dev["frac"], dev["live_t"],
                                  *q)

        # recall gate: EVERY member of the first batch vs the numpy
        # oracle (acceptance requires 1.0 across the batch)
        top_s, top_d, _hits = run_batch()
        top_s = np.asarray(top_s)
        top_d = np.asarray(top_d)
        recall_min = 1.0
        for q, terms in enumerate(batches[0]):
            ref = psc.reference_scores(
                corpus["block_docs"], frac, lanes_for(terms), nd_pad)
            ref = np.where(corpus["live1"][:nd_pad], ref[:nd_pad], 0.0)
            expect_i = np.argpartition(-ref, K)[:K]
            expect_i = expect_i[np.argsort(-ref[expect_i])]
            np.testing.assert_allclose(
                top_s[q], ref[expect_i], rtol=1e-3)
            recall = len(set(top_d[q].tolist())
                         & set(expect_i.tolist())) / K
            recall_min = min(recall_min, recall)
        cycle["i"] = 0
        p50_launch, spread = time_min3(run_batch)
        per_query = p50_launch / q_batch
        qps = q_batch * 1000.0 / p50_launch
        # HBM traffic per launch: the union posting windows (shared by
        # the whole batch) + live mask + per-query top-k outputs
        launch_bytes = (
            geom.n_tiles * t_pad_run * (2 * cb_run) * BLOCK * (4 + 4)
            + geom.n_tiles * geom.tile_w * 4
            + geom.n_tiles * q_batch * (2 * K + 1) * 4
        )
        entry = {
            "p50_ms_per_launch": round(p50_launch, 3),
            "p50_spread_ms": round(spread, 3),
            "p50_ms_per_query": round(per_query, 4),
            "qps_per_chip_batched": round(qps, 1),
            "union_t_pad": t_pad_run,
            "cb": cb_run,
            "bytes_per_query_mb_batched": round(
                launch_bytes / q_batch / 1e6, 2),
            "recall_at_10": recall_min,
        }
        if q_batch == 1:
            base_qps = qps
        elif base_qps:
            entry["qps_speedup_vs_q1"] = round(qps / base_qps, 2)
        out[f"q_batch_{q_batch}"] = entry
        log(f"batched_qps q={q_batch}: {p50_launch:.3f} ms/launch "
            f"({per_query:.3f} ms/query, {qps:.0f} qps, "
            f"t_pad={t_pad_run}, recall={recall_min})")
    return out


def run_knn_configs(jax, jnp, psc, corpus, dev, geom, frac, bmin, bmax,
                    term_sets):
    """ISSUE 7 acceptance configs — the dense-vector plane on the MXU:

    - ``knn_top10``: exhaustive exact kNN over a 1M x d=128 bf16
      embedding corpus (cosine), one ``knn_score_tiles`` MXU launch +
      fused per-tile top-10. Recall@10 gated against the exact f32
      numpy oracle over the same bf16-rounded vectors; min-of-3
      marginal estimator (r05 methodology). Headline:
      ``vector_top10_p50``.
    - ``hybrid_rrf``: BM25 top-10 (tile kernel) + kNN top-10 (MXU)
      fused by reciprocal-rank fusion — the latency is both device
      launches chained (marginal) plus the measured host fusion cost.
      Gated on the fused id list matching the oracle-side fusion.
      Headline: ``hybrid_qps_per_chip``.
    """
    import numpy as np

    import ml_dtypes

    from elasticsearch_tpu.ops import pallas_knn as pkn

    D = 128
    METRIC = "cosine"
    RRF_C = 60
    nd_pad = corpus["nd_pad"]
    rng = np.random.RandomState(23)

    t0 = time.perf_counter()
    # 1M x 128 embeddings, generated + bf16-rounded in chunks to bound
    # peak host memory (standard_normal materializes f64)
    vecs = np.empty((N_DOCS, D), np.float32)
    for lo in range(0, N_DOCS, 100_000):
        hi = min(lo + 100_000, N_DOCS)
        chunk = rng.standard_normal((hi - lo, D)).astype(np.float32)
        vecs[lo:hi] = chunk.astype(ml_dtypes.bfloat16).astype(np.float32)
    geom_k = pkn.knn_geometry(nd_pad, pkn.pad_dims(D))
    d_pad = pkn.pad_dims(D)
    emb_host = np.zeros((geom_k.nd_pad, d_pad), ml_dtypes.bfloat16)
    emb_host[:N_DOCS, :D] = vecs.astype(ml_dtypes.bfloat16)
    inv_norms = np.zeros(geom_k.nd_pad, np.float32)
    norms = np.sqrt(np.einsum("ij,ij->i", vecs, vecs))
    inv_norms[:N_DOCS] = np.where(norms > 0, 1.0 / norms, 0.0)
    scale_host = inv_norms.reshape(-1, 1)
    mask_host = np.zeros((geom_k.nd_pad, 1), np.float32)
    mask_host[:N_DOCS] = 1.0
    emb_d = jnp.asarray(emb_host)
    scale_d = jnp.asarray(scale_host)
    mask_d = jnp.asarray(mask_host)
    log(f"knn corpus staged in {time.perf_counter() - t0:.1f}s "
        f"({emb_host.nbytes / 1e6:.0f} MB bf16, tile_sub="
        f"{geom_k.tile_sub}, n_tiles={geom_k.n_tiles})")
    from elasticsearch_tpu.common import memory as dm

    acct = dm.memory_accountant()
    knn_ms = (time.perf_counter() - t0) * 1000.0
    acct.register("bench", "knn_corpus", dm.KIND_EMBEDDINGS, "emb",
                  int(emb_host.nbytes), duration_ms=knn_ms)
    acct.register("bench", "knn_corpus", dm.KIND_SCALE_NORM, "scale",
                  int(scale_host.nbytes), duration_ms=knn_ms)
    acct.register("bench", "knn_corpus", dm.KIND_LIVE_MASK, "mask",
                  int(mask_host.nbytes), duration_ms=knn_ms)

    # query mix: a random doc's embedding + gaussian noise — neighbors
    # exist (recall is meaningful) without being degenerate self-matches
    def draw_qvec():
        base = vecs[rng.randint(N_DOCS)]
        return (base + 0.25 * rng.standard_normal(D).astype(np.float32))

    n_queries = WARMUP + 24
    qvecs = [draw_qvec() for _ in range(n_queries)]
    staged_q = [jnp.asarray(pkn.normalize_query(q, METRIC, d_pad)
                            .reshape(1, d_pad)) for q in qvecs]

    @jax.jit
    def knn_query(qrow):
        ts, td = pkn.knn_score_tiles(
            emb_d, scale_d, mask_d, qrow,
            sub=geom_k.tile_sub, k=K, q_batch=1)
        return pkn.merge_knn_topk(ts, td, K)

    def oracle_knn(q):
        s = vecs @ pkn.normalize_query(q, METRIC, d_pad)[:D]
        s = s * inv_norms[:N_DOCS] * np.float32(0.5) + np.float32(0.5)
        idx = np.argpartition(-s, K)[:K]
        return idx[np.argsort(-s[idx], kind="stable")], s

    def time_min3(fn, arg_cycle):
        """min-of-3 marginal estimate after a sustained re-warm (the
        r05 estimator: marginal noise is one-sided)."""
        cycle = {"i": 0}

        def call(_q=None):
            a = arg_cycle[cycle["i"] % len(arg_cycle)]
            cycle["i"] += 1
            return fn(a)

        o = None
        for _ in range(200):
            o = call()
        np.asarray(o[0])
        ests = sorted(measure_marginal(call, [None]) for _ in range(3))
        return ests[0] * 1000, (ests[-1] - ests[0]) * 1000

    # ---- knn_top10 ----
    top_s, top_d = knn_query(staged_q[0])
    top_s, top_d = np.asarray(top_s)[0], np.asarray(top_d)[0]
    recall_min, err_max = 1.0, 0.0
    for i in range(8):
        got_s, got_d = (np.asarray(o) for o in knn_query(staged_q[i]))
        ref_i, ref_s = oracle_knn(qvecs[i])
        recall = len(set(got_d[0].tolist()) & set(ref_i.tolist())) / K
        recall_min = min(recall_min, recall)
        err_max = max(err_max, float(np.max(np.abs(
            np.sort(got_s[0]) - np.sort(ref_s[ref_i])))))
    p50k, spreadk = time_min3(knn_query, staged_q[WARMUP:])
    # HBM per query: the bf16 embedding stream + scale/mask columns +
    # tiny per-tile candidate outputs
    knn_bytes = (geom_k.nd_pad * d_pad * 2 + geom_k.nd_pad * 2 * 4
                 + geom_k.n_tiles * K * 2 * 4)
    knn_cfg = {
        "p50_ms": round(p50k, 3),
        "p50_spread_ms": round(spreadk, 3),
        "qps_per_chip": round(1000.0 / p50k, 1),
        "recall_at_10": recall_min,
        "max_abs_score_err": round(err_max, 8),
        "n_docs": N_DOCS,
        "dims": D,
        "metric": METRIC,
        "storage": "bf16",
        "tile_sub": geom_k.tile_sub,
        "bytes_per_query_mb": round(knn_bytes / 1e6, 2),
        "hbm_gb_per_s_estimate": round(
            knn_bytes / (p50k / 1000) / 1e9, 1),
        "note": ("exhaustive exact kNN on the MXU (no ANN graph): one "
                 "tiled [W, d] @ [d, Q] matmul per doc tile with fused "
                 "per-tile top-10; recall gated vs the exact f32 numpy "
                 "oracle over the same bf16-rounded vectors"),
    }
    log(f"knn_top10: {p50k:.3f} ms, recall={recall_min}")

    # ---- hybrid_rrf: BM25 launch + kNN launch + host RRF fusion ----
    qb_pad = 8
    t_pad_run = cb_run = None
    bm25_staged = []
    for ts_ in term_sets[:n_queries]:
        lanes = [psc.QueryLane(int(corpus["term_block_start"][t]),
                               int(corpus["n_blocks_per_term"][t]),
                               idf(int(corpus["term_df"][t])))
                 for t in ts_]
        rl, rh, w, cbr = psc.build_tile_tables(lanes, bmin, bmax, geom)
        t_pad_run = max(t_pad_run or 8, rl.shape[1])
        cb_run = max(cb_run or 8, cbr)
        bm25_staged.append((rl, rh, w))
    bm25_dev = []
    for rl, rh, w in bm25_staged:
        if rl.shape[1] < t_pad_run:
            pad = t_pad_run - rl.shape[1]
            rl = np.pad(rl, ((0, 0), (0, pad)))
            rh = np.pad(rh, ((0, 0), (0, pad)))
            w = np.pad(w, ((0, 0), (0, pad)))
        bm25_dev.append((jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w)))

    @jax.jit
    def hybrid_query(rl, rh, w, qrow):
        ts_, td_, th_ = psc.score_tiles(
            dev["docs"], dev["frac"], dev["live_t"], rl, rh, w,
            t_pad=t_pad_run, cb=cb_run, sub=geom.tile_sub, k=K)
        bs, bd, _ = psc.merge_tile_topk(ts_, td_, th_, K)
        kts, ktd = pkn.knn_score_tiles(
            emb_d, scale_d, mask_d, qrow,
            sub=geom_k.tile_sub, k=K, q_batch=1)
        ks_, kd_ = pkn.merge_knn_topk(kts, ktd, K)
        return bs, bd, ks_[0], kd_[0]

    def rrf_fuse(bm25_docs, knn_docs):
        scores = {}
        for r, d_ in enumerate(bm25_docs):
            if d_ >= 0:
                scores[int(d_)] = scores.get(int(d_), 0.0) \
                    + 1.0 / (RRF_C + r + 1)
        for r, d_ in enumerate(knn_docs):
            if d_ >= 0:
                scores[int(d_)] = scores.get(int(d_), 0.0) \
                    + 1.0 / (RRF_C + r + 1)
        return [d_ for d_, _s in sorted(scores.items(),
                                        key=lambda kv: (-kv[1], kv[0]))][:K]

    # gate: kernel-side fusion must equal oracle-side fusion
    hybrid_recall = 1.0
    for i in range(4):
        outs = hybrid_query(*bm25_dev[i], staged_q[i])
        _bs, bd, _ks, kd = (np.asarray(o) for o in outs)
        q0 = make_query_legacy(corpus, term_sets[i], qb_pad)
        _ref_s, ref_bm = numpy_reference_query(corpus, q0)
        ref_knn, _ = oracle_knn(qvecs[i])
        got = rrf_fuse(bd, kd)
        want = rrf_fuse(ref_bm, ref_knn)
        hybrid_recall = min(hybrid_recall,
                            len(set(got) & set(want)) / K)

    def hybrid_call(i):
        rl, rh, w = bm25_dev[i % len(bm25_dev)]
        return hybrid_query(rl, rh, w, staged_q[i % len(staged_q)])

    cyc = {"i": 0}

    def hybrid_fn(_arg):
        cyc["i"] += 1
        return hybrid_call(cyc["i"])

    p50h, spreadh = time_min3(hybrid_fn, [None])
    # host fusion cost (numpy over 2*K candidates) measured separately:
    # the marginal estimator must stay device-only (one D2H per batch)
    outs = [np.asarray(o) for o in hybrid_call(0)]
    t0 = time.perf_counter()
    for _ in range(200):
        rrf_fuse(outs[1], outs[3])
    fuse_ms = (time.perf_counter() - t0) / 200 * 1000
    p50_total = p50h + fuse_ms
    hybrid_cfg = {
        "p50_ms": round(p50_total, 3),
        "p50_spread_ms": round(spreadh, 3),
        "device_p50_ms": round(p50h, 3),
        "host_fusion_ms": round(fuse_ms, 4),
        "qps_per_chip": round(1000.0 / p50_total, 1),
        "fused_recall_at_10": hybrid_recall,
        "rank_constant": RRF_C,
        "window": K,
        "note": ("BM25 tile-kernel launch + kNN MXU launch chained on "
                 "device, RRF-fused host-side over 2*10 candidates; "
                 "gated on the fused id list matching oracle-side "
                 "fusion of the two exact reference rankings"),
    }
    log(f"hybrid_rrf: {p50_total:.3f} ms ({p50h:.3f} device + "
        f"{fuse_ms:.4f} fuse), fused_recall={hybrid_recall}")
    return knn_cfg, hybrid_cfg


def run_fault_soak_config():
    """ISSUE 10 config: serving capacity WITH chaos running.

    A packed multi-shard IndexService corpus answers a zipfian query
    stream twice — clean, then with the device fault-injection schemes
    active (transient staging faults absorbed by the bounded retry,
    kernel-launch faults driving quarantine + single-flight probes, an
    eviction storm forcing restages) — and reports:

    - ``availability_under_faults``: fraction of under-fault searches
      that returned a complete answer (no exception, no failed shards)
      — the zero-5xx invariant as a measured number;
    - ``qps_under_faults_per_chip`` vs the clean ``qps_per_chip``: what
      the retry/demotion/restage machinery costs in throughput;
    - ``ledger_leak_free`` / ``healed_plane``: after scheme removal +
      one healing query the per-kind device ledger returns exactly to
      its pre-fault snapshot and the fast plane serves again.
    """
    import numpy as np

    from elasticsearch_tpu.common.memory import memory_accountant
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.testing.disruption import (
        EvictionStormScheme,
        KernelLaunchFailScheme,
        SearchDelayScheme,
        StagingFailScheme,
        clear_search_disruptions,
    )

    N_DOCS_SOAK = 6000
    N_QUERIES = 120
    rng = np.random.RandomState(10)
    vocab = [f"w{i}" for i in range(24)]
    idx = IndexService("bench_fault_soak", Settings({
        "index.number_of_shards": 4,
        "index.search.mesh": True,
        "index.search.mesh.plane": "pallas",
        "index.search.plane_quarantine.cooldown": "100ms",
        "index.refresh_interval": -1,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    try:
        for d in range(N_DOCS_SOAK):
            toks = [vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                    for _ in range(3 + int(rng.randint(6)))]
            idx.index_doc(str(d), {"body": " ".join(toks)})
        idx.refresh()

        def q():
            terms = " ".join(
                vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                for _ in range(1 + int(rng.randint(2))))
            return {"query": {"match": {"body": terms}}, "size": 10}

        queries = [q() for _ in range(N_QUERIES)]
        # warm both rungs + compiles off the clock
        idx.search(dict(queries[0]))
        idx._search_uncached(dict(queries[0]), skip_mesh=True)
        t0 = time.perf_counter()
        for body in queries:
            idx.search(dict(body))
        clean_s = time.perf_counter() - t0
        plane_clean = idx.search(dict(queries[0]))["_plane"]
        idx._search_uncached(dict(queries[0]), skip_mesh=True)
        snap = memory_accountant().staged_bytes_by_kind(
            "bench_fault_soak")
        schemes = [
            StagingFailScheme(kinds=["postings"], transient=True,
                              times=6, indices=["bench_fault_soak"]),
            KernelLaunchFailScheme(rungs=("mesh_pallas", "batched"),
                                   times=3,
                                   indices=["bench_fault_soak"]),
            EvictionStormScheme(period=10,
                                indices=["bench_fault_soak"]),
            SearchDelayScheme(0.0005, indices=["bench_fault_soak"]),
        ]
        for s in schemes:
            s.install()
        ok = 0
        t0 = time.perf_counter()
        try:
            for body in queries:
                try:
                    r = idx.search(dict(body))
                    if not r["_shards"]["failed"]:
                        ok += 1
                except Exception:  # noqa: BLE001 — availability metric
                    pass
        finally:
            fault_s = time.perf_counter() - t0
            hits = {type(s).__name__: s.hits for s in schemes}
            for s in schemes:
                s.remove()
        time.sleep(0.15)  # quarantine cooldown
        healed = idx.search(dict(queries[0]))
        idx._search_uncached(dict(queries[0]), skip_mesh=True)
        after = memory_accountant().staged_bytes_by_kind(
            "bench_fault_soak")
        mem = memory_accountant().stats("bench_fault_soak")
        return {
            "availability_under_faults": round(ok / N_QUERIES, 4),
            "qps_under_faults_per_chip": round(N_QUERIES / fault_s, 1),
            "qps_per_chip": round(N_QUERIES / clean_s, 1),
            "qps_retention": round(clean_s / fault_s, 3),
            "plane_clean": plane_clean,
            "healed_plane": healed["_plane"],
            "ledger_leak_free": after == snap,
            "scheme_hits": hits,
            "staging_retries_total": mem["staging_retries_total"],
            "staging_faults_transient_total":
                mem["staging_faults_transient_total"],
            "staging_faults_deterministic_total":
                mem["staging_faults_deterministic_total"],
            "n_docs": N_DOCS_SOAK,
            "n_queries": N_QUERIES,
            "note": ("zipfian search stream over a packed 4-shard "
                     "corpus with device fault injection running "
                     "(transient staging faults, kernel-launch faults, "
                     "eviction storm, 0.5ms shard delay) — the "
                     "ROADMAP item-5 aggregate-QPS target's fault leg"),
        }
    finally:
        clear_search_disruptions()
        idx.close()


def run_nrt_ingest_config():
    """ISSUE 20 config: ingest + search under sustained delta staging
    (docs/MESH.md "Slot allocator & generations").

    A packed 3-shard mesh corpus takes a sustained interleaved
    ingest/refresh/search stream — every refresh window is a pure
    append, so the delta staging path carries each one as a
    copy-on-write successor generation; between passes a synchronous
    compaction pass re-densifies the generation (the background
    single-flight pass, run on the clock's edge for determinism) —
    then a delete+refresh leg exercises the tombstone path. Reports:

    - ``ingest_docs_per_s``: docs through index_doc+refresh per second
      of ingest time (search time excluded);
    - ``search_p50_under_ingest_ms``: p50 search latency measured
      INSIDE the ingest windows — min of 3 per-pass medians (the
      fault_soak min-of-3 estimator convention: marginal noise is
      one-sided);
    - ``restage_amplification``: restaged/logically-changed bytes over
      the append windows only (compaction restages excluded — reported
      separately) — the ISSUE 20 headline, ~1 when every window rode
      the delta path, ~n_slots when each refresh rebuilt the full
      generation.
    """
    import numpy as np

    from elasticsearch_tpu.common.memory import memory_accountant
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    NAME = "bench_nrt_ingest"
    N_BASE = 2400
    PASSES = 3               # min-of-3: one p50 estimate per pass
    DOCS_PER_WINDOW = 120    # one append window (refresh) per pass
    SEARCHES_PER_WINDOW = 12
    N_DELETES = 60
    rng = np.random.RandomState(20)
    vocab = [f"w{i}" for i in range(24)]
    idx = IndexService(NAME, Settings({
        "index.number_of_shards": 3,
        "index.search.mesh": True,
        "index.search.mesh.plane": "pallas",
        "index.search.mesh.max_slots_per_device": 16,
        "index.staging.delta.enabled": True,
        # deterministic windows: no background compaction mid-measure
        "index.staging.compact.threshold": 0.0,
        "index.refresh_interval": -1,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})

    def doc():
        toks = [vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                for _ in range(3 + int(rng.randint(6)))]
        return {"body": " ".join(toks)}

    def q():
        terms = " ".join(
            vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
            for _ in range(1 + int(rng.randint(2))))
        return {"query": {"match": {"body": terms}}, "size": 10}

    try:
        for d in range(N_BASE):
            idx.index_doc(str(d), doc())
        idx.refresh()
        # warm both rungs + compiles off the clock
        idx.search(q())
        idx._search_uncached(q(), skip_mesh=True)
        acc = memory_accountant()
        next_id = N_BASE
        ingest_s = 0.0
        restaged = logical = compaction_bytes = 0
        pass_p50s = []
        for p in range(PASSES):
            s0 = acc.stats(NAME)
            lat = []
            t0 = time.perf_counter()
            for _ in range(DOCS_PER_WINDOW):
                idx.index_doc(str(next_id), doc())
                next_id += 1
            idx.refresh()
            ingest_s += time.perf_counter() - t0
            for _ in range(SEARCHES_PER_WINDOW):
                body = q()
                t0 = time.perf_counter()
                idx.search(body)
                lat.append((time.perf_counter() - t0) * 1000)
            pass_p50s.append(float(np.percentile(lat, 50)))
            s1 = acc.stats(NAME)
            restaged += (s1["restaged_bytes_total"]
                         - s0["restaged_bytes_total"])
            logical += (s1["bytes_logically_changed_total"]
                        - s0["bytes_logically_changed_total"])
            # between passes: the compaction pass re-densifies the
            # generation (fresh slot headroom) so the NEXT window's
            # append fits the free slots — run synchronously here, off
            # the ingest clock and outside the amp snapshots, standing
            # in for the background single-flight thread
            if p < PASSES - 1:
                c0 = acc.stats(NAME)["restaged_bytes_total"]
                idx.compact_now()
                idx.search(q())  # restage on the spot, not next window
                compaction_bytes += (acc.stats(NAME)
                                     ["restaged_bytes_total"] - c0)
        amp = round(restaged / logical, 3) if logical else None
        # delete leg: tombstones restage only live-mask bytes
        for d in range(N_DELETES):
            idx.delete_doc(str(d * 7))
        idx.refresh()
        idx.search(q())
        planes = idx.search_stats()["planes"]
        n_appended = PASSES * DOCS_PER_WINDOW
        return {
            "ingest_docs_per_s": round(n_appended / ingest_s, 1),
            "search_p50_under_ingest_ms": round(min(pass_p50s), 3),
            "search_p50_spread_ms": round(
                max(pass_p50s) - min(pass_p50s), 3),
            "restage_amplification": amp,
            "restaged_bytes_append_windows": restaged,
            "logical_bytes_append_windows": logical,
            "compaction_restaged_bytes": compaction_bytes,
            "delta_restage_total": planes["delta_restage_total"],
            "tombstone_update_total": planes["tombstone_update_total"],
            "compaction_runs_total": planes["compaction_runs_total"],
            "n_docs_base": N_BASE,
            "n_docs_appended": n_appended,
            "n_deletes": N_DELETES,
            "note": ("interleaved ingest/refresh/search over a packed "
                     "3-shard mesh corpus — every refresh window is a "
                     "pure append carried by the delta staging path "
                     "(restage_amplification ~1 when no window fell "
                     "back to a full generation rebuild), a synchronous "
                     "compaction pass re-densifies between windows "
                     "(bytes reported separately), then a "
                     "delete+refresh leg drives the tombstone path; "
                     "p50 is the min of 3 per-pass medians per the "
                     "fault_soak estimator convention"),
        }
    finally:
        idx.close()


def run_cold_start_config():
    """ISSUE 14 config: what does a restart cost the first query, and
    what does the rollout plane save (docs/RESILIENCE.md "Rollout &
    drain")?

    Three headline numbers, all measured on this backend (a future TPU
    run quantifies the real 2–27 s stall elimination):

    - ``first_query_cold_ms``: restart with NO persistent cache and NO
      warming — compiled-program caches cleared, the first query pays
      trace + XLA compile on its own path;
    - ``first_query_warmed_ms``: restart WITH the persistent
      compilation cache + variant-registry warming — programs warm in
      the background off the clock, the first query pays only its
      serving latency (``query_path_first_compiles`` proves it paid no
      compile);
    - ``drain_p99_ms``: p99 time for a drain to quiesce the index
      under concurrent in-flight searches (begin_drain →
      await_drained over repeated cycles).
    """
    import shutil
    import tempfile
    import threading

    import numpy as np

    from elasticsearch_tpu.common import compile_cache as cc
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.parallel.plan_exec import (
        clear_compiled_programs,
    )
    from elasticsearch_tpu.testing.disruption import SearchDelayScheme

    root = tempfile.mkdtemp(prefix="estpu-coldstart-")
    N_DOCS = 4000
    rng = np.random.RandomState(14)
    vocab = [f"w{i}" for i in range(24)]
    settings = Settings({
        "index.number_of_shards": 4,
        "index.search.mesh": True,
        "index.search.mesh.plane": "pallas",
        "index.refresh_interval": -1,
    })
    mapping = {"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}}
    data_path = os.path.join(root, "index")

    def mk():
        return IndexService("bench_cold_start", settings,
                            mapping=mapping, data_path=data_path)

    probe = {"query": {"match": {"body": "w0 w1"}}, "size": 10}

    def timed_query(svc):
        t0 = time.perf_counter()
        svc.search(dict(probe))
        return (time.perf_counter() - t0) * 1000.0

    prev_registry = cc.variant_registry()
    prev_cache = cc.compile_cache_path()
    # a fixed subdirectory of the checkout's cache (the path is part of
    # the cache key), emptied here so that the cold leg is cold
    cache_dir = os.path.join(cc.checkout_cache_dir(), "cold_start")
    shutil.rmtree(cache_dir, ignore_errors=True)
    try:
        cc.configure_compile_cache(None)
        registry_path = os.path.join(root, "variants.json")
        cc.set_variant_registry(cc.VariantRegistry(registry_path))
        svc = mk()
        for d in range(N_DOCS):
            toks = [vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                    for _ in range(3 + int(rng.randint(5)))]
            svc.index_doc(str(d), {"body": " ".join(toks)})
        svc.refresh()
        svc.flush()

        # ---- cold restart: no cache, no warming ----
        clear_compiled_programs()
        first_query_cold_ms = timed_query(svc)

        # ---- populate the persistent cache (the "previous process") --
        cache_on = cc.configure_compile_cache(cache_dir)
        clear_compiled_programs()
        svc.search(dict(probe))  # compiles + serializes to disk
        svc.close()

        # ---- warmed restart: cache + registry + background warming --
        clear_compiled_programs()
        cc.set_variant_registry(cc.VariantRegistry(registry_path))
        svc = mk()
        t_warm0 = time.perf_counter()
        warmed = svc.warm_compile_variants()
        warm_ms = (time.perf_counter() - t_warm0) * 1000.0
        qp0 = cc.compile_stats().stats()[
            "query_path_first_compile_total"]
        first_query_warmed_ms = timed_query(svc)
        query_path_first_compiles = (
            cc.compile_stats().stats()["query_path_first_compile_total"]
            - qp0)

        # ---- drain p99 under concurrent in-flight searches ----
        adm = svc.admission
        delay = SearchDelayScheme(0.004,
                                  indices=["bench_cold_start"]).install()
        drain_ms = []
        try:
            for _ in range(20):
                stop = threading.Barrier(3)

                def inflight():
                    stop.wait(timeout=5)
                    try:
                        svc.search(dict(probe))
                    except Exception:  # noqa: BLE001 — drain may refuse
                        pass

                threads = [threading.Thread(target=inflight)
                           for _ in range(2)]
                for t in threads:
                    t.start()
                stop.wait(timeout=5)
                time.sleep(0.002)  # searches admitted + executing
                t0 = time.perf_counter()
                adm.begin_drain()
                drained = adm.await_drained(10.0)
                drain_ms.append(time.perf_counter() - t0)  # seconds;
                # pctl() scales to ms
                adm.end_drain()
                for t in threads:
                    t.join()
                if not drained:
                    break
        finally:
            delay.remove()
        svc.close()
        return {
            "n_docs": N_DOCS,
            "cache_enabled": bool(cache_on),
            "variants_recorded": len(cc.variant_registry().programs),
            "warm_specs_replayed": warmed,
            "warm_background_ms": round(warm_ms, 3),
            # headline keys (BENCH_rNN)
            "first_query_cold_ms": round(first_query_cold_ms, 3),
            "first_query_warmed_ms": round(first_query_warmed_ms, 3),
            "cold_start_stall_saved_ms": round(
                first_query_cold_ms - first_query_warmed_ms, 3),
            "query_path_first_compiles": query_path_first_compiles,
            "drain_p99_ms": round(pctl(drain_ms, 99), 3) if drain_ms
            else None,
            "drain_p50_ms": round(pctl(drain_ms, 50), 3) if drain_ms
            else None,
            "drain_cycles": len(drain_ms),
        }
    finally:
        cc.configure_compile_cache(prev_cache)
        cc.set_variant_registry(prev_registry)
        shutil.rmtree(root, ignore_errors=True)


def run_overload_zipfian_config():
    """ISSUE 12 config: goodput + fairness at offered load ≫ capacity.

    A packed multi-shard IndexService with a TIGHT admission shape
    (2 concurrency slots, queue 8 — docs/OVERLOAD.md) answers a burst
    from 16 client threads whose tenants are zipfian-assigned, so one
    hot tenant dominates the offered load. Reports:

    - ``saturated_capacity_qps``: completed/sec with exactly
      max_concurrent clients (no rejects) — best of 3 runs, the
      fault_soak min-of-3 estimator convention;
    - ``goodput_qps_under_overload``: admitted completions/sec while
      offered load exceeds capacity (``offered_capacity_ratio``);
      the acceptance bar is goodput within 10% of saturated capacity;
    - ``admitted_p99_ms``: p99 latency of ADMITTED queries under
      overload (bounded queueing — the queue depth caps the wait);
    - ``reject_rate``: rejected/offered — every one a clean 429 with
      Retry-After (``zero_5xx`` asserts nothing else escaped);
    - ``max_tenant_starvation_ratio``: max over active tenants of
      (demand-capped fair share) / (achieved admission share) — 1.0 is
      perfectly fair, and the no-starvation bar is <= 2 (every tenant
      gets at least half its fair share).
    """
    import threading

    import numpy as np

    from elasticsearch_tpu.common.errors import (
        EsRejectedExecutionException,
    )
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    N_DOCS_OV = 4000
    N_THREADS = 16
    N_PER_THREAD = 30
    N_TENANTS = 8
    rng = np.random.RandomState(12)
    vocab = [f"w{i}" for i in range(24)]
    idx = IndexService("bench_overload", Settings({
        "index.number_of_shards": 4,
        "index.search.mesh": True,
        "index.search.mesh.plane": "pallas",
        "index.refresh_interval": -1,
        "search.admission.max_concurrent": 2,
        "search.queue.size": 8,
        # brownout step 1 (forced pruning) is excluded from this
        # config's measurement: on the interpret/CPU smoke backend the
        # pruned kernel is SLOWER than exhaustive (inverting the trade
        # it exists for), which would corrupt the goodput number. The
        # hardware tuning pass (ROADMAP item 1) re-enables it by
        # dropping this threshold; steps 2-4 still measure.
        "search.admission.brownout.pruned_threshold": 10.0,
        # adaptive-window widening is capped at the base window here:
        # with max_concurrent=2 a wider collection window cannot form a
        # bigger batch (batch size <= in-flight), so widening would be
        # pure added latency in THIS shape; wide-slot hardware configs
        # measure the real trade (docs/OVERLOAD.md)
        "search.batch.max_window_ms": 0.2,
    }), mapping={"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    try:
        from elasticsearch_tpu.search.telemetry import set_opaque_id

        for d in range(N_DOCS_OV):
            toks = [vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                    for _ in range(3 + int(rng.randint(6)))]
            idx.index_doc(str(d), {"body": " ".join(toks)})
        idx.refresh()

        def q():
            terms = " ".join(
                vocab[min(int(rng.zipf(1.4)) - 1, len(vocab) - 1)]
                for _ in range(1 + int(rng.randint(2))))
            return {"query": {"match": {"body": terms}}, "size": 10}

        idx.search(dict(q()))  # warm compiles off the clock
        idx._search_uncached(dict(q()), skip_mesh=True)
        clean_queries = [q() for _ in range(40)]
        for body in clean_queries:
            idx.search(dict(body))  # warm every shape variant
        clean_lat = []  # seconds (pctl scales to ms)
        for body in clean_queries:
            t0 = time.perf_counter()
            idx.search(dict(body))
            clean_lat.append(time.perf_counter() - t0)

        # --- overload burst: zipfian tenants, offered >> capacity.
        # Clients honor Retry-After (capped for bench speed) and retry
        # a bounded number of times — a rejected closed-loop client
        # that never backs off would just exhaust its workload in the
        # first milliseconds of queue-full and read as "starved".
        tenant_of = [f"tenant{min(int(rng.zipf(1.3)) - 1, N_TENANTS - 1)}"
                     for _ in range(N_THREADS)]
        thread_queries = [[q() for _ in range(N_PER_THREAD)]
                          for _ in range(N_THREADS)]
        lock = threading.Lock()

        def client(tid, start, stats):
            tenant = tenant_of[tid]
            set_opaque_id(tenant)
            start.wait()
            counts, per_tenant, admitted_lat = stats
            for body in thread_queries[tid]:
                # clients honor Retry-After (capped for bench speed),
                # bounded retries: a rejected closed-loop client that
                # never backs off would exhaust its workload in the
                # first milliseconds of queue-full and read "starved"
                for _attempt in range(5):
                    if counts is not None:
                        with lock:
                            counts["offered"] += 1
                            t_bucket = per_tenant.setdefault(
                                tenant, {"offered": 0, "admitted": 0,
                                         "rejected": 0})
                            t_bucket["offered"] += 1
                    t0 = time.perf_counter()
                    try:
                        r = idx.search(dict(body))
                        lat = time.perf_counter() - t0  # seconds
                        if counts is not None:
                            with lock:
                                counts["admitted"] += 1
                                t_bucket["admitted"] += 1
                                admitted_lat.append(lat)
                                if r["_shards"]["failed"]:
                                    counts["errors"] += 1
                        break
                    except EsRejectedExecutionException as e:
                        if counts is not None:
                            with lock:
                                counts["rejected"] += 1
                                t_bucket["rejected"] += 1
                        time.sleep(min(getattr(e, "retry_after_s", 1.0),
                                       0.02))
                    except Exception:  # noqa: BLE001 — zero-5xx metric
                        if counts is not None:
                            with lock:
                                counts["errors"] += 1
                        break

        def run_burst(stats=(None, None, None)):
            start = threading.Barrier(N_THREADS + 1)
            threads = [threading.Thread(target=client,
                                        args=(t, start, stats))
                       for t in range(N_THREADS)]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        # unmeasured pre-burst: compiles every batched-launch variant
        # the measured mix will hit (first-compile stalls are a
        # COLD-START cost — 2-27s in this image, ROADMAP item 4's
        # compilation cache — not steady-state overload behavior)
        run_burst()
        # saturated capacity: the SAME client load with the queue bound
        # lifted (explicit override, then cleared) so nothing rejects —
        # isolates what overflow handling costs vs pure queueing under
        # identical thread pressure; best of 3 (min-of-3 convention)
        idx.admission.set_cluster_overrides(
            Settings({"search.queue.size": 1_000_000}))
        capacity = 0.0
        for _ in range(3):
            sat = ({"offered": 0, "admitted": 0, "rejected": 0,
                    "errors": 0}, {}, [])
            sat_wall = run_burst(sat)
            capacity = max(capacity, sat[0]["admitted"] / sat_wall)
        idx.admission.set_cluster_overrides(Settings({}))
        # measured overload burst against the tight queue
        counts = {"offered": 0, "admitted": 0, "rejected": 0,
                  "errors": 0}
        per_tenant = {}
        admitted_lat = []
        wall = run_burst((counts, per_tenant, admitted_lat))
        set_opaque_id(None)

        goodput = counts["admitted"] / wall
        # closed-loop clients: each thread always has one request
        # outstanding, so the offered CONCURRENCY (threads vs slots) is
        # the honest overload ratio — completed-rate ratios would be
        # throttled by admission itself
        offered_ratio = N_THREADS / 2.0
        # demand-capped fairness: a tenant that offered less than its
        # fair share cannot be "starved" below what it asked for
        active = [t for t, b in per_tenant.items() if b["offered"]]
        starvation = 1.0
        if counts["admitted"] and active:
            fair = 1.0 / len(active)
            for t in active:
                b = per_tenant[t]
                entitled = min(fair, b["offered"] / counts["offered"])
                share = b["admitted"] / counts["admitted"]
                ratio = (entitled / share) if share > 0 else 99.0
                starvation = max(starvation, ratio)
        adm = idx.admission.stats_dict()
        return {
            "saturated_capacity_qps": round(capacity, 1),
            "goodput_qps_under_overload": round(goodput, 1),
            "goodput_retention": round(goodput / capacity, 3),
            "offered_capacity_ratio": round(offered_ratio, 2),
            "admitted_p99_ms": round(pctl(admitted_lat, 99), 3),
            "admitted_p50_ms": round(pctl(admitted_lat, 50), 3),
            "clean_p99_ms": round(pctl(clean_lat, 99), 3),
            "reject_rate": round(counts["rejected"]
                                 / max(counts["offered"], 1), 4),
            "max_tenant_starvation_ratio": round(starvation, 3),
            "zero_5xx": counts["errors"] == 0,
            "offered": counts["offered"],
            "admitted": counts["admitted"],
            "rejected": counts["rejected"],
            "active_tenants": len(active),
            "retry_after_s": adm["retry_after_s"],
            "brownout": adm["brownout"],
            "n_docs": N_DOCS_OV,
            "note": ("16 zipfian-tenant client threads against a "
                     "2-slot/8-deep admission shape on a packed 4-shard "
                     "corpus — the ROADMAP item-5 overload invariant: "
                     "goodput near saturated capacity, bounded admitted "
                     "p99, no tenant below half its fair share, every "
                     "non-admitted query a clean 429 (docs/OVERLOAD.md)"),
        }
    finally:
        idx.close()


def run_codec_pruning_configs(jax, jnp, psc, corpus, dev, geom, frac,
                              bmin, bmax, cb_run, term_sets):
    """ISSUE 6 configs on the 1M corpus, same query mix as the headline:

    - ``packed_postings``: the bit-packed postings codec — one i32 word
      per posting, decoded in-kernel — exhaustive scoring. Halves the
      posting-window HBM bytes the kernel is bandwidth-bound on.
    - ``pruned_scoring``: block-max pruned top-k over the packed corpus
      (probe pass seeds the threshold, rest tiles skip when their summed
      block-max bound cannot beat it; the threshold never leaves the
      device — no per-query D2H sync).

    Both recall-gate EVERY measured aspect against the RAW numpy oracle
    (quantization is lossy by ~2.7e-4 absolute; the gate is what decides
    whether the codec/pruning mode may claim the headline)."""
    import numpy as np

    out_packed, out_pruned = {}, {}
    nd_pad = corpus["nd_pad"]
    n_gate = 8  # queries recall-gated per config

    def lanes_for(terms):
        return [psc.QueryLane(int(corpus["term_block_start"][t]),
                              int(corpus["n_blocks_per_term"][t]),
                              idf(int(corpus["term_df"][t])))
                for t in terms]

    def time_min3(fn):
        for _ in range(2):
            fn()
        o = None
        for _ in range(200):
            o = fn()
        np.asarray(o[0])
        ests = sorted(measure_marginal(lambda _q: fn(), [None])
                      for _ in range(3))
        return ests[0] * 1000, (ests[-1] - ests[0]) * 1000

    def recall_gate(top_s, top_d, terms):
        """Measured (recall@10, max score error) vs the RAW oracle.

        Never raises: the gate's job is to MEASURE — a failed gate
        demotes the config from headline contention (recall < 1.0),
        it must not crash the config into an error dict. The score
        tolerance carries an ABSOLUTE term: quantization error is
        absolute (~(k1+1)/2^13), so a relative-only check would flag
        legitimately low-scoring queries."""
        qb_pad = 1
        nb = sum(int(corpus["n_blocks_per_term"][t]) for t in terms)
        while qb_pad < nb:
            qb_pad *= 2
        ref_s, ref_i = numpy_reference_query(
            corpus, make_query_legacy(corpus, terms, qb_pad))
        got_s = np.asarray(top_s).reshape(-1)
        got_d = np.asarray(top_d).reshape(-1)
        err = float(np.abs(got_s - ref_s).max())
        tol = 2e-3 * float(np.abs(ref_s).max()) + 4 * psc.PACK_FRAC_SCALE
        recall = len(set(got_d.tolist()) & set(ref_i.tolist())) / K
        if err > tol:
            recall = min(recall, 0.0)  # scores off the rails: fail gate
        return recall, err

    # ---- staging: the packed corpus (one word per posting) ----
    t0 = time.perf_counter()
    pk = psc.pack_segment_blocks(corpus["block_docs"], frac, nd_pad)
    dev_pk = jnp.asarray(pk)
    dev_pk.block_until_ready()
    stage_s = time.perf_counter() - t0
    raw_bytes = int(dev["docs"].size * 4 + dev["frac"].size * 4)
    packed_bytes = int(pk.nbytes)
    log(f"packed staging: {packed_bytes / 1e6:.0f} MB (raw "
        f"{raw_bytes / 1e6:.0f} MB) in {stage_s:.1f}s")
    # the packed layout re-stages the SAME logical corpus — a
    # geometry_change restage in the device-memory ledger, so the
    # report's restage_amplification reflects a real restage cycle
    from elasticsearch_tpu.common import memory as dm

    dm.memory_accountant().register(
        "bench", "corpus", dm.KIND_POSTINGS_PACKED, "k_packed",
        packed_bytes, reason="geometry_change",
        duration_ms=stage_s * 1000.0)

    timed_terms = term_sets[WARMUP:]
    tables = []
    for ts in timed_terms:
        rl, rh, w, _ = psc.build_tile_tables(
            lanes_for(ts), bmin, bmax, geom, t_pad=4, cb=cb_run)
        tables.append((rl, rh, w))
    staged_kq = [(jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))
                 for rl, rh, w in tables]

    # ---- config: packed_postings (exhaustive, packed codec) ----
    try:
        @jax.jit
        def _packed_fused(pkc, live_t, rl, rh, w):
            ts_, td_, th_ = psc.score_tiles(
                pkc, None, live_t, rl, rh, w,
                t_pad=4, cb=cb_run, sub=geom.tile_sub, k=K,
                codec="packed")
            return psc.merge_tile_topk(ts_, td_, th_, K)

        cycle = {"i": 0}

        def run_packed():
            q = staged_kq[cycle["i"] % len(staged_kq)]
            cycle["i"] += 1
            return _packed_fused(dev_pk, dev["live_t"], *q)

        recall_min, err_max = 1.0, 0.0
        for i in range(n_gate):
            top_s, top_d, _h = _packed_fused(dev_pk, dev["live_t"],
                                             *staged_kq[i])
            recall, err = recall_gate(top_s, top_d, timed_terms[i])
            recall_min = min(recall_min, recall)
            err_max = max(err_max, err)
        cycle["i"] = 0
        p50p, spreadp = time_min3(run_packed)
        # posting windows stream as ONE word (4 B) instead of 8 B
        bytes_packed = (
            geom.n_tiles * 4 * (2 * cb_run) * BLOCK * 4
            + geom.n_tiles * geom.tile_w * 4
            + geom.n_tiles * (2 * K + 1) * 4)
        out_packed = {
            "p50_ms": round(p50p, 3),
            "p50_spread_ms": round(spreadp, 3),
            "recall_at_10": recall_min,
            "max_score_abs_err_vs_raw": round(err_max, 6),
            "bytes_per_query_mb_packed": round(bytes_packed / 1e6, 2),
            "postings_bytes_staged_mb": round(packed_bytes / 1e6, 1),
            "postings_bytes_staged_raw_mb": round(raw_bytes / 1e6, 1),
            "stage_seconds": round(stage_s, 2),
            "note": ("bit-packed postings decoded in-kernel: half the "
                     "posting-window HBM bytes and half the staged "
                     "posting bytes; recall measured vs the RAW oracle "
                     "(frac quantized to 12 bits over (0, k1+1))"),
        }
        log(f"packed_postings: {p50p:.3f} ms, recall={recall_min}")
    except Exception as e:  # noqa: BLE001
        import traceback

        traceback.print_exc(file=sys.stderr)
        out_packed = {"error": f"{type(e).__name__}: {e}"}

    # ---- config: pruned_scoring (block-max pruning over packed) ----
    try:
        probe = 8
        bfmax = psc.block_frac_max(
            psc.dequantize_frac(psc.quantize_frac(frac)))
        plans = []
        for (rl, rh, w) in tables:
            plan = psc.plan_pruned_tiles(rl, rh, w, bfmax,
                                         probe_tiles=probe)
            assert plan is not None, "corpus too small to prune"
            plans.append(plan)
        staged_pr = [
            tuple(jnp.asarray(x) for x in (
                p["rl_probe"], p["rh_probe"], p["tid_probe"],
                p["rl_rest"], p["rh_rest"], p["tid_rest"],
                p["bounds_rest"], t[2]))
            for p, t in zip(plans, tables)]

        def run_pruned_q(q):
            (rlp, rhp, tidp, rlr, rhr, tidr, br, w) = q
            return psc.score_tiles_pruned(
                dev_pk, None, dev["live_t"], rlp, rhp, tidp,
                rlr, rhr, tidr, br, w,
                t_pad=4, cb=cb_run, sub=geom.tile_sub, k=K,
                codec="packed")

        cycle = {"i": 0}

        def run_pruned():
            q = staged_pr[cycle["i"] % len(staged_pr)]
            cycle["i"] += 1
            return run_pruned_q(q)

        recall_min, err_max = 1.0, 0.0
        scored_total = 0
        tiles_total = 0
        for i in range(n_gate):
            top_s, top_d, _h, scored = run_pruned_q(staged_pr[i])
            recall, err = recall_gate(top_s, top_d, timed_terms[i])
            recall_min = min(recall_min, recall)
            err_max = max(err_max, err)
            scored_total += int(scored)
            tiles_total += geom.n_tiles
        pruned_fraction = 1.0 - scored_total / max(tiles_total, 1)
        cycle["i"] = 0
        p50r, spreadr = time_min3(run_pruned)
        scored_avg = scored_total / n_gate
        # only SCORED tiles stream their posting windows + live slabs
        bytes_pruned = scored_avg * (
            4 * (2 * cb_run) * BLOCK * 4 + geom.tile_w * 4) \
            + geom.n_tiles * (2 * K + 1) * 4 * 2
        out_pruned = {
            "p50_ms": round(p50r, 3),
            "p50_spread_ms": round(spreadr, 3),
            "recall_at_10": recall_min,
            "max_score_abs_err_vs_raw": round(err_max, 6),
            "probe_tiles": probe,
            "tiles_scored_avg": round(scored_avg, 1),
            "tiles_total": geom.n_tiles,
            "tiles_pruned_fraction": round(pruned_fraction, 3),
            "tiles_pruned_total": tiles_total - scored_total,
            "bytes_per_query_mb_pruned": round(bytes_pruned / 1e6, 2),
            "note": ("block-max pruned top-k over the packed corpus: "
                     "the probe pass scores the 8 highest-bound tiles, "
                     "the rest run only if their bound beats the "
                     "running k-th score (threshold computed on-device "
                     "— no per-query host sync); under pruning hit "
                     "totals are a lower bound (WAND semantics)"),
        }
        log(f"pruned_scoring: {p50r:.3f} ms, recall={recall_min}, "
            f"scored {scored_avg:.1f}/{geom.n_tiles} tiles")
    except Exception as e:  # noqa: BLE001
        import traceback

        traceback.print_exc(file=sys.stderr)
        out_pruned = {"error": f"{type(e).__name__}: {e}"}

    return out_packed, out_pruned


def run_mesh_pallas_config(jax, jnp, lax, psc, corpus, term_sets,
                           n_shards=4):
    """The packed mesh plane on this chip: the 1M corpus split into
    n_shards doc-range shards, every shard scored BY THE TILE KERNEL
    inside ONE shard_map program with all shards packed as slots on the
    single device, candidates merged in-program — the mesh data plane of
    parallel/plan_exec.py in bench form (same slot unroll, same per-slot
    kernel invocation, same all_gather+top_k merge). This is the path a
    multi-chip pod runs per device; acceptance: p50 within 2x of the
    single-chip pallas p50 with recall@10 = 1.0 (it replaces the 6.9 ms
    scatter formulation distributed queries were pinned to)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as PS

    term_ids, docs, tfs = corpus["flat"]
    doc_len = corpus["doc_len"]
    shard_size = N_DOCS // n_shards
    nd_pad_s = 1
    while nd_pad_s < shard_size:
        nd_pad_s *= 2
    geom = psc.tile_geometry(nd_pad_s)
    sub, n_tiles = geom.tile_sub, geom.n_tiles
    shards = []
    max_rows = 0
    t0 = time.perf_counter()
    for s in range(n_shards):
        lo = s * shard_size
        hi = (s + 1) * shard_size if s < n_shards - 1 else N_DOCS
        m = (docs >= lo) & (docs < hi)
        bd, bt, tbs, nbt, _df = pack_postings(
            term_ids[m], docs[m] - lo, tfs[m], VOCAB, nd_pad_s)
        norms_s = np.ones(nd_pad_s + 1, np.float32)
        norms_s[: hi - lo] = doc_len[lo:hi].astype(np.float32)
        # per-posting norm factors with the CORPUS avgdl: scores must
        # equal the single-index kernel's exactly for the recall gate
        frac = psc.compute_block_frac(bd, bt, norms_s, corpus["avgdl"])
        bmin, bmax = psc.block_min_max(bd, bt, nd_pad_s)
        dp, fp = psc.pad_segment_blocks(bd, frac, nd_pad_s)
        live = np.zeros(nd_pad_s, np.float32)
        live[: hi - lo] = 1.0
        shards.append({"dp": dp, "fp": fp, "tbs": tbs, "nbt": nbt,
                       "bmin": bmin, "bmax": bmax,
                       "live_t": psc.build_live_t(live, geom),
                       "live1": live.astype(bool), "lo": lo})
        max_rows = max(max_rows, dp.shape[0])
    k_docs = np.full((n_shards, max_rows, BLOCK), nd_pad_s, np.int32)
    k_frac = np.zeros((n_shards, max_rows, BLOCK), np.float32)
    for i, sh in enumerate(shards):
        k_docs[i, : sh["dp"].shape[0]] = sh["dp"]
        k_frac[i, : sh["fp"].shape[0]] = sh["fp"]
    live_t = np.stack([sh["live_t"] for sh in shards])
    live1 = np.stack([sh["live1"] for sh in shards])
    log(f"mesh config: {n_shards} shards packed "
        f"(nd_pad_s={nd_pad_s}, n_tiles={n_tiles}) built in "
        f"{time.perf_counter() - t0:.1f}s")

    def shard_tables(terms, cb=None):
        per = []
        need_cb = 8
        for sh in shards:
            lanes = [psc.QueryLane(int(sh["tbs"][t]), int(sh["nbt"][t]),
                                   idf(int(corpus["term_df"][t])))
                     for t in terms]
            rl, rh, w, cbr = psc.build_tile_tables(
                lanes, sh["bmin"], sh["bmax"], geom, t_pad=4, cb=cb)
            per.append((rl, rh, w))
            need_cb = max(need_cb, cbr)
        return (np.stack([p[0] for p in per]),
                np.stack([p[1] for p in per]),
                np.stack([p[2] for p in per]), need_cb)

    queries = [shard_tables(ts) for ts in term_sets]
    cb_run = max(q[3] for q in queries)
    staged_q = [(jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))
                for rl, rh, w, _ in queries]

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    spd = n_shards

    def per_device(kd, kf, lt, lv, rl, rh, w):
        cand_s, cand_d = [], []
        for i in range(spd):
            ds = psc.score_tiles(
                kd[i], kf[i], lt[i], rl[i], rh[i], w[i],
                t_pad=4, cb=cb_run, sub=sub, dense=True)[0]
            scores = psc.dense_to_flat(ds, sub)
            masked = jnp.where((scores > 0) & lv[i], scores, -jnp.inf)
            s_i, d_i = lax.top_k(masked, K)
            cand_s.append(s_i)
            cand_d.append(d_i + jnp.int32(i * shard_size))
        all_s = lax.all_gather(jnp.concatenate(cand_s), "shards").reshape(-1)
        all_d = lax.all_gather(jnp.concatenate(cand_d), "shards").reshape(-1)
        top_s, ti = lax.top_k(all_s, K)
        return top_s[None], all_d[ti][None]

    mapped = shard_map(per_device, mesh=mesh,
                       in_specs=(PS("shards"),) * 7,
                       out_specs=(PS("shards"),) * 2, check_vma=False)

    @jax.jit
    def run_prog(kd, kf, lt, lv, rl, rh, w):
        o = mapped(kd, kf, lt, lv, rl, rh, w)
        return o[0][0], o[1][0]

    sharding = jax.sharding.NamedSharding(mesh, PS("shards"))
    dev_kd = jax.device_put(k_docs, sharding)
    dev_kf = jax.device_put(k_frac, sharding)
    dev_lt = jax.device_put(live_t, sharding)
    dev_lv = jax.device_put(live1, sharding)
    for v in (dev_kd, dev_kf, dev_lt, dev_lv):
        v.block_until_ready()

    def run_mesh(q):
        return run_prog(dev_kd, dev_kf, dev_lt, dev_lv, *q)

    t0 = time.perf_counter()
    top_s, top_d = run_mesh(staged_q[0])
    np.asarray(top_s)
    log(f"mesh program first compile+run in {time.perf_counter() - t0:.1f}s "
        f"(cb={cb_run})")
    # re-warm + marginal timing (same estimator as the main path)
    wout = None
    for i in range(400):
        wout = run_mesh(staged_q[i % len(staged_q)])
    np.asarray(wout[0])
    timed = staged_q[WARMUP:]
    ests = sorted(measure_marginal(run_mesh, timed) for _ in range(3))
    # recall gate vs the full-corpus numpy oracle (shard-local doc ids
    # were offset back to global in-program)
    qb_pad = 1
    nb = sum(int(corpus["n_blocks_per_term"][t]) for t in term_sets[0])
    while qb_pad < nb:
        qb_pad *= 2
    ref_s, ref_i = numpy_reference_query(
        corpus, make_query_legacy(corpus, term_sets[0], qb_pad))
    got_s, got_d = (np.asarray(x) for x in run_mesh(staged_q[0]))
    np.testing.assert_allclose(got_s, ref_s, rtol=1e-3)
    recall = len(set(got_d.tolist()) & set(ref_i.tolist())) / K
    return {
        "p50_ms": round(ests[0] * 1000, 3),
        "p50_spread_ms": round((ests[-1] - ests[0]) * 1000, 3),
        "recall_at_10": recall,
        "n_shards": n_shards,
        "devices": 1,
        "slots_per_device": spd,
        "note": ("the mesh data plane scoring with the tile kernel: "
                 "n_shards segments packed as slots on this one chip, "
                 "scored per slot by score_tiles inside shard_map and "
                 "merged in-program — distributed queries no longer pay "
                 "the scatter formulation"),
    }


# ----------------------------------------------------------------------
# Parent process driver (never imports jax)
# ----------------------------------------------------------------------


def run_agg_fused_config(jax, jnp, lax, psc, corpus, dev, geom, bmin,
                         bmax, cb_run):
    """ISSUE 13 acceptance config (docs/AGGS.md): fused on-device
    aggregations — terms(10 buckets over the zipfian 2000-value keyword
    column) + date_histogram (hourly week rolled to 7 day buckets) over
    the 1M corpus, WITH fusion (bucket counts reduced in the SAME
    program/launch that scores, only tiny accumulators cross to the
    host) and WITHOUT (the old path: the dense score vector D2H's and
    the host re-reads the columns). Bucket-equality gated vs the numpy
    oracle. The scoring front end is the tile kernel; the agg
    formulation is precomputed int32 code columns + int32 scatter
    counts."""
    import numpy as np

    from elasticsearch_tpu.common import memory as dm

    nd_pad = corpus["nd_pad"]
    nd1 = nd_pad + 1
    live1 = corpus["live1"]
    # doc-value code columns, precomputed host-side with the oracle's
    # exact arithmetic (the production staging contract,
    # search/fused_aggs.py): ordinal codes for terms, day-bucket codes
    # for the date_histogram; -1 = no value / padding doc
    n_kw = 2000
    kw_codes = np.full(nd1, -1, np.int32)
    kw_raw = corpus["keyword_ord"]
    kw_codes[:nd_pad] = np.where(
        (kw_raw < n_kw) & live1[:nd_pad], kw_raw, -1)
    epoch = 1_500_000_000_000
    day_ms = 86_400_000.0
    ts = epoch + (np.arange(nd_pad, dtype=np.int64) % 168) * 3_600_000
    b = np.floor(ts / day_ms).astype(np.int64)
    b_min = int(b.min())
    n_dh = int(b.max()) - b_min + 1
    dh_codes = np.full(nd1, -1, np.int32)
    dh_codes[:nd_pad] = np.where(live1[:nd_pad],
                                 (b - b_min).astype(np.int32), -1)
    dev_kw = jnp.asarray(kw_codes)
    dev_dh = jnp.asarray(dh_codes)
    dv_bytes = int(dev_kw.nbytes + dev_dh.nbytes)
    acct = dm.memory_accountant()
    acct.register("bench", "corpus", dm.KIND_DOC_VALUES, "agg_codes",
                  dv_bytes, reason="initial")

    def bucket_counts(codes, mask, nb):
        sel = mask & (codes >= 0)
        safe = jnp.where(sel, codes, 0)
        return jnp.zeros((nb,), jnp.int32).at[safe].add(
            sel.astype(jnp.int32))

    rng = np.random.RandomState(23)
    terms = [int(x) for x in rng.randint(50, 500, 3)]
    lanes = [psc.QueryLane(int(corpus["term_block_start"][t]),
                           int(corpus["n_blocks_per_term"][t]),
                           idf(int(corpus["term_df"][t])))
             for t in terms]
    rl, rh, w, _cb = psc.build_tile_tables(lanes, bmin, bmax, geom,
                                           t_pad=4, cb=cb_run)
    args = (jnp.asarray(rl), jnp.asarray(rh), jnp.asarray(w))

    @jax.jit
    def _scores1(rl_, rh_, w_):
        ds = psc.score_tiles(dev["docs"], dev["frac"], dev["live_t"],
                             rl_, rh_, w_, t_pad=4, cb=cb_run,
                             sub=geom.tile_sub, dense=True)[0]
        s = psc.dense_to_flat(ds, geom.tile_sub)[:nd_pad]
        return jnp.concatenate([s, jnp.zeros(1, jnp.float32)])

    path = "pallas_tile_kernel"

    @jax.jit
    def fused(*a):
        # ONE program: score + rank + both bucket reductions on device;
        # only the top-k and the tiny count vectors cross to the host
        scores = _scores1(*a)
        mask = scores > 0.0
        top_s, top_d = lax.top_k(jnp.where(mask, scores, -jnp.inf), K)
        kw_counts = bucket_counts(dev_kw, mask, n_kw)
        top_kw_c, top_kw_o = lax.top_k(kw_counts, 10)
        dh_counts = bucket_counts(dev_dh, mask, n_dh)
        return top_s, top_d, top_kw_c, top_kw_o, dh_counts

    @jax.jit
    def score_only(*a):
        scores = _scores1(*a)
        top_s, top_d = lax.top_k(
            jnp.where(scores > 0.0, scores, -jnp.inf), K)
        return top_s, top_d, scores

    def host_roundtrip():
        # the pre-fusion path: rank on device, ship the DENSE score
        # vector to the host, re-read the columns there
        top_s, top_d, scores = score_only(*args)
        m = np.asarray(scores) > 0.0
        kw_counts = np.zeros(n_kw, np.int64)
        sel = m & (kw_codes >= 0)
        np.add.at(kw_counts, kw_codes[sel], 1)
        order = np.argsort(-kw_counts, kind="stable")[:10]
        dh = np.zeros(n_dh, np.int64)
        sel2 = m & (dh_codes >= 0)
        np.add.at(dh, dh_codes[sel2], 1)
        return np.asarray(top_s), kw_counts[order], order, dh

    # --- bucket-equality gate vs the numpy oracle ---
    matched = np.zeros(nd1, bool)
    for t in terms:
        start = int(corpus["term_block_start"][t])
        cnt = int(corpus["n_blocks_per_term"][t])
        blk = corpus["block_docs"][start: start + cnt]
        tfs = corpus["block_tfs"][start: start + cnt]
        matched[blk[tfs > 0]] = True
    matched &= live1
    oracle_kw = np.zeros(n_kw, np.int64)
    np.add.at(oracle_kw, kw_codes[matched & (kw_codes >= 0)], 1)
    oracle_dh = np.zeros(n_dh, np.int64)
    np.add.at(oracle_dh, dh_codes[matched & (dh_codes >= 0)], 1)
    out_f = fused(*args)
    got_kw_c, got_kw_o = np.asarray(out_f[2]), np.asarray(out_f[3])
    got_dh = np.asarray(out_f[4]).astype(np.int64)
    oracle_top = np.sort(oracle_kw)[::-1][:10]
    equality = (bool(np.array_equal(np.sort(got_kw_c)[::-1].astype(
        np.int64), oracle_top))
        and bool(np.array_equal(
            oracle_kw[got_kw_o].astype(np.int64),
            got_kw_c.astype(np.int64)))
        and bool(np.array_equal(got_dh, oracle_dh)))

    def wall_p50(fn, reps=9):
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            np.asarray(out[0])
            lat.append(time.perf_counter() - t0)
        return pctl(lat[2:], 50)  # pctl converts seconds -> ms

    fused_p50 = wall_p50(lambda: fused(*args))
    host_p50 = wall_p50(host_roundtrip)
    return {
        "agg_p50_ms": round(fused_p50, 3),
        "agg_host_p50_ms": round(host_p50, 3),
        "agg_host_roundtrip_saved_ms": round(host_p50 - fused_p50, 3),
        # doc-value column bytes one fused query streams on device (the
        # second corpus read the host path performs host-side instead)
        "bytes_per_query_mb_agg": round(dv_bytes / 1e6, 3),
        "bucket_equality": equality,
        "terms_buckets": 10,
        "date_histogram_buckets": n_dh,
        "matched_docs": int(matched.sum()),
        "path": path,
        "method": ("wall-clock p50 over 7 timed reps (both variants end "
                   "in a host materialization, so marginal device "
                   "timing would hide exactly the round-trip this "
                   "config measures)"),
    }


def failed_phases(result: dict) -> list:
    """Names of the configs that recorded an error instead of numbers."""
    configs = result.get("extra", {}).get("configs") or {}
    return sorted(name for name, cfg in configs.items()
                  if isinstance(cfg, dict) and "error" in cfg)


def child_main():
    try:
        result = run_measurement()
    except Exception as e:  # noqa: BLE001 — the reason belongs on stderr
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"measurement failed: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(result), flush=True)
    failed = failed_phases(result)
    if failed:
        log(f"failed phases: {failed}")
        return 1
    return 0


def main():
    """Run the measurement in ONE child under a watchdog and exit with
    its code: no retry, no other backend."""
    env = dict(os.environ, BENCH_CHILD="1")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=TPU_ATTEMPT_TIMEOUT_S,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        log(f"timeout after {TPU_ATTEMPT_TIMEOUT_S}s "
            f"(backend init or staging hang)")
        return 1
    return proc.returncode


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD") == "1":
        sys.exit(child_main())
    sys.exit(main())
