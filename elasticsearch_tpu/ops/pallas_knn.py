"""Pallas TPU kernel for dense-vector (kNN) retrieval on the MXU.

The BM25 tile-scoring plane (ops/pallas_scoring.py) streams posting bytes
and does almost no arithmetic, so the TPU's matrix units sit idle (it is
not bandwidth-bound on the chip either: score_tiles runs at 0.137 % of
its HBM roofline on msmarco-serial, ledger, PR 30; the cost its own note
names is grid steps, not confirmed on this round's chip). This module
adds the workload TPUs are literally built for: brute-force kNN over a
staged ``[nd_pad, d]`` bf16 embedding matrix, scored tile-by-tile with a
real MXU matmul (ROADMAP item 4; the dense/hybrid retrieval scenario
modern Elasticsearch grew into).

Design, mirroring the BM25 kernel's conventions so the two planes share
the serving machinery (micro-batching, plane ladder, quarantine):

- The doc space is partitioned into tiles of ``W = sub * 128`` docs. The
  kernel grid iterates tiles; each grid step DMAs one ``[W, d_pad]``
  bf16 block of the embedding matrix out of HBM (HALF the bytes of an
  f32 layout — bf16 storage is the codec), converts it to f32 in VMEM
  and contracts it against the whole query batch on the MXU:

      scores[Q, W] = qvecs[Q, d_pad] . emb_tile[W, d_pad]^T

  ONE corpus stream serves all Q queries of the batch — exactly the
  cross-query amortization the MicroBatcher exists for (``q_batch`` is
  the same static dim the BM25 kernel carries).
- Metrics: ``dot_product`` and ``max_inner_product`` score the raw
  inner product; ``cosine`` multiplies by a staged per-doc inverse-norm
  column (the query side is normalized host-side), so one kernel body
  serves all three — the metric is a scale column, not a code path. The
  kernel ranks by that similarity as it is; Elasticsearch's score of it
  (``hit_score``: ``(1 + sim) / 2``, or for ``max_inner_product``
  ``sim < 0 ? 1 / (1 - sim) : sim + 1``) is monotone, so it is applied
  to the k winners and never inside the kernel.
- The per-tile top-k is fused: each tile emits its local top-K (scores,
  doc ids) per query via the same masked-select loop the BM25 kernel
  uses; the [n_tiles * K] candidate pools merge with one tiny
  ``lax.top_k`` per query. The dense score matrix never reaches HBM.
- Live/tombstone masking rides a staged ``[nd_pad, 1]`` f32 mask column
  (live AND has-vector), streamed lane-major as ``(1, W)`` rows: dead
  docs score -inf before the top-k, so deletes are honored without
  touching the embedding staging.
- The matmul runs ``Precision.HIGHEST``: the recall@10 == 1.0 gate vs
  the exact f32 numpy oracle is the bench's acceptance bar, and the
  default single-pass bf16 MXU rounding (~2^-8 relative) can reorder
  near-tied neighbors. Whether the multi-pass product is noise next to
  the stream at 768 wide is what ``knn_roofline.knn`` reads on the chip
  (PERF.md 5).
- A slot of a flat ``[n_slots * nd_pad, d_pad]`` staging is read in
  place: ``row_base`` offsets the block index maps, so no slot is ever
  sliced out (a slice is a copy of ~100 MB a slot a query at 768 wide).

All shapes are static and bucketed (d padded to a lane multiple, Q and K
padded to powers of two by the callers) so compiled programs cache
across queries.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
NEG_INF = float("-inf")

# default tile = 8192 docs: the [W, d_pad] f32-converted block must live
# in VMEM next to the bf16 copy and the [Q, W] score slab; at d=128 that
# is ~6.3 MB — comfortably under the ~16 MB/core budget while keeping
# the per-grid-step fixed cost (which dominates the BM25 kernel too)
# amortized over big tiles
DEFAULT_KNN_SUB = 64
# VMEM budget for the f32-converted embedding block; knn_tile_sub shrinks
# the tile for high-dimensional fields so the block always fits
KNN_TILE_F32_BUDGET = 8 * 1024 * 1024

VALID_KNN_SUBS = (8, 16, 32, 64, 128)

METRICS = ("cosine", "dot_product", "max_inner_product")


def pad_dims(dims: int) -> int:
    """Embedding columns pad to a lane multiple so the bf16 block's last
    dimension tiles cleanly on the VPU/MXU (zeros never change a dot)."""
    return max(((int(dims) + LANE - 1) // LANE) * LANE, LANE)


def knn_tile_sub(nd_pad: int, d_pad: int,
                 pref: int = DEFAULT_KNN_SUB) -> int:
    """Tile sublane count for a kNN launch: the preference (the
    ``search.knn.tile_sub`` setting), shrunk until the f32-converted
    embedding block fits the VMEM budget, floored at 8 (mosaic sublane
    granularity). The geometry helper shrinks further for small doc
    spaces on its own."""
    sub = pref if pref in VALID_KNN_SUBS else DEFAULT_KNN_SUB
    while sub > 8 and sub * LANE * d_pad * 4 > KNN_TILE_F32_BUDGET:
        sub //= 2
    return sub


def knn_geometry(nd_pad: int, d_pad: int, pref: int = DEFAULT_KNN_SUB):
    """TileGeometry for a kNN launch over an ``nd_pad`` doc space —
    reuses the BM25 plane's geometry type so callers share code."""
    from elasticsearch_tpu.ops.pallas_scoring import tile_geometry

    return tile_geometry(max(nd_pad, LANE), knn_tile_sub(nd_pad, d_pad,
                                                         pref))


def bf16_round(vectors: np.ndarray) -> np.ndarray:
    """Round an f32 host matrix to the bf16 grid (what the device stores
    and the kernel decodes) and return it as f32 — the host mirror the
    numpy oracle scores so recall gates compare like with like."""
    import ml_dtypes  # jax dependency; bakes the bf16 rounding rule

    return np.asarray(vectors, np.float32).astype(
        ml_dtypes.bfloat16).astype(np.float32)


def vector_scale_column(vectors_f32: np.ndarray, metric: str) -> np.ndarray:
    """Per-doc score scale [nd_pad, 1] f32: 1/|x| for cosine (docs with
    zero norm scale to 0 → score 0.5, ranked by nothing), all-ones for
    the inner-product metrics. ``vectors_f32``: the bf16-rounded host
    mirror."""
    if metric == "cosine":
        norms = np.linalg.norm(vectors_f32.astype(np.float32), axis=1)
        with np.errstate(divide="ignore"):
            inv = np.where(norms > 0.0, 1.0 / norms, 0.0)
        return inv.astype(np.float32).reshape(-1, 1)
    return np.ones((vectors_f32.shape[0], 1), np.float32)


def normalize_query(qvec: np.ndarray, metric: str,
                    d_pad: int) -> np.ndarray:
    """Query vector ready for the kernel/oracle: f32, zero-padded to
    ``d_pad``; cosine additionally folds 1/|q| into the vector (the doc
    side's 1/|x| rides the staged scale column)."""
    q = np.zeros(d_pad, np.float32)
    v = np.asarray(qvec, np.float32)
    q[: v.shape[0]] = v
    if metric == "cosine":
        n = float(np.linalg.norm(v))
        if n > 0.0:
            q[: v.shape[0]] = v / n
    return q


def hit_score(sim, metric: str, xp=np):
    """Elasticsearch's ``_score`` of a similarity (float32 in, float32
    out; ``xp`` is numpy or jax.numpy): ``(1 + sim) / 2`` for ``cosine``
    and ``dot_product``; for ``max_inner_product``, whose similarity is
    unbounded, ``1 / (1 - sim)`` below zero and ``sim + 1`` from it.
    Monotone, so every rung ranks by ``sim`` and scores the winners;
    ``-inf`` (an unfilled rank) stays ``-inf``."""
    if metric == "max_inner_product":
        score = xp.where(sim < 0, 1 / (1 - xp.minimum(sim, 0)), sim + 1)
    else:
        score = sim * 0.5 + 0.5
    return xp.where(sim == -np.inf, sim, score)


# ----------------------------------------------------------------------
# The kernel
# ----------------------------------------------------------------------


def _make_knn_kernel(sub: int, d_pad: int, k: int, q_batch: int):
    """Kernel body. Mosaic constraints shape the formulation the same way
    they shaped the BM25 kernel (see ops/pallas_scoring._make_kernel):
    every scalar literal is an explicit int32/float32 (weak python
    scalars trace to i64/f64 under the engine's x64 mode and crash the
    TPU compile), and the top-k builds whole (Q, k) blocks with masked
    selects instead of scalar stores. The score slab is [Q, W] — docs on
    the LANE axis — so the per-doc scale and live-mask rows arrive
    lane-major as (1, W) blocks and broadcast along sublanes. A (W, 1)
    column block would pad every doc to a full 128-lane row in VMEM
    (4 MB per column per buffer at W = 8192), which the v5e compiler
    refuses at the default tile."""
    w = sub * LANE

    def kernel(emb_ref, scale_ref, mask_ref, q_ref, out_s_ref, out_d_ref):
        t = pl.program_id(0)
        base = jnp.int32(t) * jnp.int32(w)
        # [W, d_pad] bf16 -> f32 in VMEM, then ONE MXU contraction for
        # the whole query batch: scores[Q, W]. HIGHEST precision — see
        # module docstring (the recall gate is the acceptance bar).
        emb = emb_ref[...].astype(jnp.float32)
        s = lax.dot_general(
            q_ref[...], emb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST)
        # metric scale row (cosine: 1/|x|; inner products: ones);
        # [1, W] broadcasts over Q. The similarity ranks as it is:
        # hit_score is applied to the winners, outside
        s = s * scale_ref[...]
        live = mask_ref[...] > jnp.float32(0.0)  # [1, W]
        ninf = jnp.float32(NEG_INF)
        masked = jnp.where(live, s, ninf)  # [Q, W]
        lin = lax.broadcasted_iota(jnp.int32, (q_batch, w), 1)
        outv_s = jnp.full((q_batch, k), NEG_INF, jnp.float32)
        outv_d = jnp.full((q_batch, k), -1, jnp.int32)
        k_iota = lax.broadcasted_iota(jnp.int32, (q_batch, k), 1)
        for i in range(k):
            mx = jnp.max(masked, axis=1, keepdims=True)  # [Q, 1]
            sel = jnp.where(masked == mx, lin, jnp.int32(w))
            idx = jnp.min(sel, axis=1, keepdims=True)  # [Q, 1]
            outv_s = jnp.where(k_iota == jnp.int32(i), mx, outv_s)
            doc = jnp.where(mx == ninf, jnp.int32(-1), base + idx)
            outv_d = jnp.where(k_iota == jnp.int32(i), doc, outv_d)
            masked = jnp.where(lin == idx, ninf, masked)
        out_s_ref[...] = outv_s.reshape(1, q_batch, k)
        out_d_ref[...] = outv_d.reshape(1, q_batch, k)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("sub", "k", "q_batch", "interpret",
                              "row_base", "n_rows"))
def knn_score_tiles(
    emb,  # [rows, d_pad] bf16 embedding matrix (rows beyond the real
    # docs are zero; the mask column kills them anyway)
    scale,  # [rows, 1] f32 per-doc metric scale (vector_scale_column)
    mask,  # [rows, 1] f32: 1.0 = live AND has a vector
    qvecs,  # [q_batch, d_pad] f32 query batch (normalize_query rows;
    # padding members are all-zero and their outputs are discarded)
    *,
    sub: int,
    k: int = 10,
    q_batch: int = 1,
    interpret: bool = False,
    row_base: int = 0,  # first row of the doc space to score, and
    n_rows: Optional[int] = None,  # its rows: ONE slot of a table that
    # holds several, read in place (both multiples of the tile)
):
    """Run the MXU kNN kernel over a staged embedding matrix.

    Returns (tile_sims [n_tiles, q_batch, k] f32, tile_docs
    [n_tiles, q_batch, k] i32, -1 = empty) — per-tile fused top-k
    candidates by similarity (``hit_score`` is the caller's, on the
    winners), merged per query by ``merge_knn_topk``; doc ids count
    from ``row_base``. The match TOTAL (live docs carrying a vector) is
    metric- and query-independent, so callers count it from the mask
    column instead of a kernel output.
    """
    rows, d_pad = emb.shape
    nd_pad = rows - row_base if n_rows is None else n_rows
    w = sub * LANE
    if nd_pad % w or row_base % w:
        raise ValueError(f"rows [{row_base}, +{nd_pad}) are not whole "
                         f"tiles of {w}")
    n_tiles = nd_pad // w
    k = min(int(k), w)
    q_batch = max(1, int(q_batch))

    # index maps must return int32 (the engine runs with x64 enabled:
    # python-int literals become i64 constants inside mosaic transform
    # functions and crash the TPU compile helper)
    def zero():
        return jnp.int32(0)

    def tile(t):  # the slot's tile t in the table's own count
        return t + jnp.int32(row_base // w)

    in_specs = [
        pl.BlockSpec((w, d_pad), lambda t: (tile(t), zero())),
        pl.BlockSpec((1, w), lambda t: (zero(), tile(t))),
        pl.BlockSpec((1, w), lambda t: (zero(), tile(t))),
        pl.BlockSpec((q_batch, d_pad), lambda t: (zero(), zero())),
    ]
    out_specs = [
        pl.BlockSpec((1, q_batch, k), lambda t: (t, zero(), zero())),
        pl.BlockSpec((1, q_batch, k), lambda t: (t, zero(), zero())),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_tiles, q_batch, k), jnp.float32),
        jax.ShapeDtypeStruct((n_tiles, q_batch, k), jnp.int32),
    ]
    return pl.pallas_call(
        _make_knn_kernel(sub, d_pad, k, q_batch),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # stated, not inherited from a Python function: the device
        # trace's readers find the custom call by this name
        name="knn_tiles",
    )(emb, scale.reshape(1, rows), mask.reshape(1, rows), qvecs)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_knn_topk(tile_scores, tile_docs, k: int):
    """Merge per-tile candidates per query: tile_scores/tile_docs are
    [n_tiles, Q, kk]; returns (top_s [Q, k'], top_d [Q, k'] i32) with
    k' = min(k, n_tiles * kk)."""
    n_tiles, q, kk = tile_scores.shape
    pool_s = tile_scores.transpose(1, 0, 2).reshape(q, -1)
    pool_d = tile_docs.transpose(1, 0, 2).reshape(q, -1)
    k2 = min(int(k), pool_s.shape[1])
    top_s, top_i = lax.top_k(pool_s, k2)
    top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
    return top_s, top_d


# ----------------------------------------------------------------------
# Numpy reference (tests + bench recall gate + CPU fallback parity)
# ----------------------------------------------------------------------


def reference_knn_scores(vectors_f32: np.ndarray, qvec: np.ndarray,
                         metric: str = "cosine",
                         scale: Optional[np.ndarray] = None) -> np.ndarray:
    """Exact f32 scores over the bf16-rounded host mirror — the oracle
    the kernel (and the host plan node) must match. ``qvec`` is the RAW
    user vector; normalization and ``hit_score`` happen here exactly as
    staged."""
    qvec = np.asarray(qvec, np.float32)
    q = normalize_query(qvec, metric, max(vectors_f32.shape[1],
                                          qvec.shape[0]))
    s = vectors_f32.astype(np.float32) @ q[: vectors_f32.shape[1]]
    if scale is None:
        scale = vector_scale_column(vectors_f32, metric)
    return hit_score((s * scale[:, 0]).astype(np.float32),
                     metric).astype(np.float32)


def reference_knn_topk(vectors_f32: np.ndarray, mask: np.ndarray,
                       qvec: np.ndarray, k: int,
                       metric: str = "cosine") -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """Exact top-k (scores, doc ids) over live vector docs."""
    s = reference_knn_scores(vectors_f32, qvec, metric)
    masked = np.where(mask[: len(s)], s, -np.inf)
    k = min(k, len(masked))
    idx = np.argpartition(-masked, k - 1)[:k] if k < len(masked) \
        else np.arange(len(masked))
    idx = idx[np.argsort(-masked[idx], kind="stable")]
    return masked[idx], idx
