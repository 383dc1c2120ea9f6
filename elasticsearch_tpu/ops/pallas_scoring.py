"""Pallas TPU kernel for the BM25 scoring hot loop.

This is the TPU-native replacement for the reference's per-segment scoring
loop — Lucene's ``BulkScorer`` driven from ``QueryPhase.execute``
(core/src/main/java/org/elasticsearch/search/query/QueryPhase.java:272) —
and for the XLA scatter-add formulation it previously compiled to here
(ops/scoring.py:score_term_blocks). XLA lowers a scatter-add with
duplicate indices to a serialized per-element loop on TPU, which made the
chip 4x slower than host numpy (BENCH_r03). This kernel removes the
scatter entirely:

- The doc space is partitioned into tiles of ``W`` docs (W = TILE_SUB*128).
  The kernel grid iterates tiles; each grid step owns one dense
  ``[TILE_SUB, 128]`` f32 score accumulator that lives in VMEM/vregs and
  never round-trips through HBM.
- For each query term lane, the blocks of postings that can intersect the
  tile are a *contiguous* run of block rows (postings are doc-sorted within
  a term), located host-side from per-block [min_doc, max_doc] metadata.
  The run's rows are DMA'd by the BlockSpec index_map from scalar-prefetched
  per-(tile, lane) row bounds — the DMA engine does the gather.
- The scatter "score[doc] += w*frac" becomes a radix-decomposed one-hot
  matmul on the MXU: with local = doc - tile_base, hi = local >> 7,
  lo = local & 127,

      acc[hi, lo] += sum_p [hi_p == hi] * ([lo_p == lo] * w * frac_p)
                   = onehot_hi^T  @  (onehot_lo * w * frac)

  i.e. one (TILE_SUB x R) @ (R x 128) f32 matmul per lane per tile. The
  one-hot generation is O(R * (TILE_SUB + 128)) VPU compares instead of the
  O(R * W) of a direct dense compare — the scatter itself rides the MXU.
- Per-posting BM25 norm factors ``frac = tf*(k1+1)/(tf + k1*(1-b+b*len/avgdl))``
  are precomputed per segment at staging time (Lucene's analog: norms are
  baked into per-doc impacts), so the kernel needs no random doc-length
  gather; a term's score is just ``idf_weight * frac``.
- The top-k is fused: each tile emits its local top-K (scores, doc ids) and
  its live-match count; the host program merges n_tiles*K candidates with
  one tiny ``lax.top_k``. The dense score vector never reaches HBM in the
  top-k variant. A dense variant writes the [nd] scores (and match counts)
  for plan programs that need downstream masking/aggregation.

All shapes are static and bucketed (T_pad lanes, CB covering-blocks, W)
so compiled programs cache across queries (SURVEY.md section 7.3).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticsearch_tpu.index.segment import next_pow2
from elasticsearch_tpu.ops.scoring import B, K1

LANE = 128
# default tile = 16384 docs = 128 sublanes x 128 lanes. Measured on a v5e
# (1M-doc corpus, 4-lane query): per-grid-step fixed cost (~4us + ~2us/lane,
# DMA issue latency) dominates the kernel, so fewer/bigger tiles win: 64
# tiles at sub=128/cb=32 runs ~1.0ms/query vs ~1.8ms at sub=64/cb=16 and
# ~3.2ms at sub=32/cb=8 (same covering-window density).
DEFAULT_TILE_SUB = 128
# segment block arrays are padded with this many sentinel rows so that both
# CB-aligned DMA windows (2*cb rows from the aligned start) stay in bounds
# for any window starting at a real block row; cb <= CB_MAX // 2
CB_MAX = 128

NEG_INF = float("-inf")


# Tiles folded into one grid step of ``score_tiles`` (its
# ``tiles_per_step`` parameter; 2/4/8 let a step's posting-window DMAs
# overlap compute). Every caller passes this one value: the first change
# that lowers the kernel's grid-step cost either derives it from n_tiles
# or deletes tps > 1 from the kernel (ROADMAP A6, C3).
TILES_PER_STEP = 1


# ----------------------------------------------------------------------
# Packed postings codec (ISSUE 6: break the bandwidth wall)
#
# The raw layout streams 8 bytes/posting (doc i32 + frac f32) out of HBM
# for every covering window. On the chip score_tiles runs at 0.137 % of
# its HBM roofline on msmarco-serial (ledger, PR 30), so it is not
# bandwidth-bound there; the cost the note at DEFAULT_TILE_SUB names is
# grid steps, not confirmed on this round's chip. The packed codec
# bit-packs each posting into ONE i32 word:
#
#     word = (doc << PACK_FRAC_BITS) | frac_q        (frac_q in [1, 4095])
#
# and the kernel unpacks it in VMEM with one logical shift + one mask +
# one i32->f32 convert before the existing two-pass scoring — half the
# posting bytes per query (the Lucene analog: the FOR/bit-packed postings
# codec of index/codec, SURVEY §2.3/§6, inverted for lane-parallel
# decode). frac quantizes linearly over (0, K1+1) — BM25's frac =
# tf(k1+1)/(tf + k1*norm) is strictly below k1+1 for any tf/norm, so the
# scale is a static constant and no per-segment metadata rides along.
# frac_q == 0 is the invalid/padding marker (exactly the frac > 0.0 rule
# the raw kernel keys on), so real postings clamp to frac_q >= 1.
#
# Lossiness: |dequant(q) - frac| <= PACK_FRAC_SCALE/2 (~2.7e-4 absolute,
# ~16x tighter than the bf16 rounding the two-pass compensation exists
# for). Whether that reorders near-tied top-10 ranks is corpus-dependent,
# which is why the codec is settings-gated (raw default).
# ----------------------------------------------------------------------

PACK_FRAC_BITS = 12
PACK_FRAC_MASK = (1 << PACK_FRAC_BITS) - 1
PACK_MAX_FRAC = float(K1) + 1.0  # strict upper bound of BM25 frac
PACK_FRAC_SCALE = PACK_MAX_FRAC / PACK_FRAC_MASK
# doc ids must fit the remaining bits (sentinels store doc 0 + frac_q 0)
PACKED_DOC_CAP = 1 << (32 - PACK_FRAC_BITS)


def packed_codec_ok(nd_pad: int) -> bool:
    """The packed word holds 32 - PACK_FRAC_BITS doc bits: real doc ids
    are < nd_pad, so any nd_pad <= 2^20 fits (the 1M bench corpus is
    exactly the boundary); larger doc spaces stay on the raw codec."""
    return nd_pad <= PACKED_DOC_CAP


def quantize_frac(frac: np.ndarray) -> np.ndarray:
    """frac f32 -> 12-bit code; 0 stays 0 (invalid marker), real postings
    clamp to [1, PACK_FRAC_MASK] so frac > 0 survives the round trip."""
    q = np.rint(frac / np.float32(PACK_FRAC_SCALE)).astype(np.int64)
    q = np.clip(q, 1, PACK_FRAC_MASK)
    return np.where(frac > 0.0, q, 0).astype(np.int32)


def dequantize_frac(q: np.ndarray) -> np.ndarray:
    """The exact f32 values the kernel's in-VMEM decode produces (the
    oracle for packed-parity tests)."""
    return (q.astype(np.float32) * np.float32(PACK_FRAC_SCALE)).astype(
        np.float32)


def pack_segment_blocks(block_docs: np.ndarray, block_frac: np.ndarray,
                        sentinel: int,
                        q: Optional[np.ndarray] = None) -> np.ndarray:
    """Bit-pack (docs, frac) into one padded i32 word array — the packed
    analog of pad_segment_blocks (CB_MAX all-zero sentinel rows keep the
    double-window DMA in bounds; word 0 decodes to frac 0 = invalid).
    ``q``: precomputed quantize_frac(block_frac), for callers that also
    need the codes (block-max bounds) — quantization is a full-corpus
    pass and should run once per staging."""
    if not packed_codec_ok(int(sentinel)):
        raise ValueError(
            f"doc space {sentinel} exceeds the packed codec's "
            f"{32 - PACK_FRAC_BITS}-bit doc capacity")
    if q is None:
        q = quantize_frac(block_frac.astype(np.float32))
    docs = np.where(q > 0, block_docs, 0).astype(np.int64)
    words = ((docs.astype(np.uint32) << PACK_FRAC_BITS)
             | q.astype(np.uint32)).view(np.int32)
    pad = np.zeros((CB_MAX, LANE), dtype=np.int32)
    return np.concatenate([words, pad])


def resolve_postings_codec(pref, nd_pad: int) -> str:
    """Effective codec for a segment staging: the caller's preference
    (the index's resolved setting; anything but "packed" is raw),
    demoted to raw when the doc space exceeds the packed word's doc
    capacity."""
    if pref == "packed" and packed_codec_ok(nd_pad):
        return "packed"
    return "raw"


# ----------------------------------------------------------------------
# Host-side geometry: which docs does tile t get from term lane j?
# ----------------------------------------------------------------------


class TileGeometry(NamedTuple):
    """Static tiling of one segment's doc space."""

    nd_pad: int  # padded doc count (power of two)
    tile_sub: int  # sublanes per tile
    n_tiles: int

    @property
    def tile_w(self) -> int:
        return self.tile_sub * LANE


def tile_geometry(nd_pad: int, tile_sub: int = DEFAULT_TILE_SUB) -> TileGeometry:
    """Pick the tile shape for a segment: W = tile_sub*128 docs per tile,
    shrinking for small segments so n_tiles >= 1 and W <= nd_pad. The doc
    space is floored at one LANE (128): segments smaller than that are
    scored over a 128-doc space whose tail is dead (live mask zeros)."""
    nd_pad = max(nd_pad, LANE)
    if nd_pad & (nd_pad - 1) or tile_sub & (tile_sub - 1):
        raise ValueError(
            f"nd_pad={nd_pad} and tile_sub={tile_sub} must be powers of two "
            f"(otherwise tail docs would fall outside every tile)")
    w = tile_sub * LANE
    while w > nd_pad and w > LANE:
        w //= 2
    sub = w // LANE
    n_tiles = max(nd_pad // w, 1)
    assert n_tiles * sub * LANE == nd_pad
    return TileGeometry(nd_pad=nd_pad, tile_sub=sub, n_tiles=n_tiles)


def pad_segment_blocks(
    block_docs: np.ndarray, block_frac: np.ndarray, sentinel: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Append CB_MAX sentinel rows so CB-aligned DMA windows never read out
    of bounds (sentinel docs fail every tile's range check)."""
    pad_docs = np.full((CB_MAX, LANE), sentinel, dtype=np.int32)
    pad_frac = np.zeros((CB_MAX, LANE), dtype=np.float32)
    return (
        np.concatenate([block_docs.astype(np.int32), pad_docs]),
        np.concatenate([block_frac.astype(np.float32), pad_frac]),
    )


def compute_block_frac(
    block_docs: np.ndarray,
    block_tfs: np.ndarray,
    doc_len: np.ndarray,  # [>= nd_pad (+1)] float32 per-doc field length
    avgdl: float,
    k1: float = K1,
    b: float = B,
) -> np.ndarray:
    """Per-posting BM25 norm factor (everything except idf*boost):
    tf*(k1+1) / (tf + k1*(1-b+b*len/avgdl)). Sentinel/padding lanes
    (tf == 0) get exactly 0, which downstream masks key on."""
    tf = block_tfs.astype(np.float32)
    dl = doc_len[np.minimum(block_docs, len(doc_len) - 1)].astype(np.float32)
    denom = tf + k1 * (1.0 - b + b * dl / max(avgdl, 1e-9))
    with np.errstate(divide="ignore", invalid="ignore"):
        frac = np.where(tf > 0.0, tf * (k1 + 1.0) / denom, 0.0)
    return frac.astype(np.float32)


def block_min_max(block_docs: np.ndarray, block_tfs: np.ndarray,
                  sentinel: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block [min_doc, max_doc] over *real* postings (tf > 0).

    A term's block run must have NO all-padding block before its last block
    (SegmentBuilder.seal packs postings densely, so this always holds) —
    an empty mid-run block would get bmax=-1/bmin=sentinel and break the
    sortedness that build_tile_tables' searchsorted coverage relies on;
    build_tile_tables guards this with an explicit monotonicity check."""
    real = block_tfs > 0.0
    bmin = np.where(real, block_docs, sentinel).min(axis=1).astype(np.int64)
    bmax = np.where(real, block_docs, -1).max(axis=1).astype(np.int64)
    return bmin, bmax


class QueryLane(NamedTuple):
    """One scoring lane: a term (or term+field) posting run and its weight."""

    block_start: int  # first block row of the term in the segment
    block_count: int
    weight: float  # idf * boost (0 disables the lane)


# build_tile_tables_sets' per-lane key offset: far above any doc id
# (< 2**31)
_LANE_KEY = np.int64(1) << 40


def build_tile_tables_sets(
    lane_sets: Sequence[Sequence[QueryLane]],
    bmin: np.ndarray,
    bmax: np.ndarray,
    geom: TileGeometry,
    t_pad: Optional[int] = None,
    cb: Optional[int] = None,
    bases: Optional[Sequence[int]] = None,
    ladder: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int, TileGeometry]:
    """Host-side query planning for N lane sets at once, on one shared
    geometry and ``t_pad``: per (set, tile, lane) the absolute block-row
    window [row_lo, row_hi) covering the tile's doc range. Set i's lanes
    index ``bmin``/``bmax`` from row ``bases[i]`` (0 for every set when
    None), so several segments' block bounds may lie in one array, and
    its row windows are relative to that base. Returns (row_lo, row_hi
    [N, n_tiles, t_pad] i32, weights [N, 1, t_pad] f32, CB, geometry)
    where CB is the uniform pow2 window bucket of all N sets.

    The kernel DMAs TWO consecutive CB-aligned windows starting at
    align(row_lo, CB) — rows [align(lo), align(lo) + 2*CB) — so any window
    with row_hi - row_lo <= CB is fully covered regardless of where the
    aligned start lands (it can sit up to CB-1 rows before row_lo).

    Every live lane (blocks and a nonzero weight) of every set is
    planned in one pass: the lanes' block runs are gathered into one
    array whose keys are offset by (set, lane), so one sortedness check
    and one searchsorted per bound answer every (set, tile, lane).
    ``ladder``: where the covering window exceeds the kernel bound at
    ``geom``, halve the tile until it fits (the geometry ladder: big
    tiles are fastest, as the per-grid-step cost dominates), reusing the
    gather; a rung costs the two searchsorted calls. The ladder ends in
    the bound's ValueError at tile_sub 32 (where non-overlapping sorted
    block ranges always fit: need <= sub + 2 blocks) or where the doc
    space shrinks the tile below the rung asked for."""
    n_sets = len(lane_sets)
    t_pad = t_pad or next_pow2(max(max(map(len, lane_sets), default=0), 1))
    # one row a live lane: (set * t_pad + lane, block_start, count, weight)
    live = np.array(
        [(i * t_pad + j, lane.block_start, lane.block_count, lane.weight)
         for i, lanes in enumerate(lane_sets)
         for j, lane in enumerate(lanes)
         if lane.block_count > 0 and lane.weight != 0.0],
        np.float64).reshape(-1, 4)
    lane_id = live[:, 0].astype(np.int64)
    s = live[:, 1].astype(np.int64)
    c = live[:, 2].astype(np.int64)
    first_pos = np.cumsum(c) - c  # each run's place in the gather
    base = 0 if bases is None else np.asarray(bases, np.int64)[
        lane_id // t_pad]
    rows = np.arange(int(c.sum())) + np.repeat(base + s - first_pos, c)
    # a doc id is under 2**31 (and a block's min/max doc lies in
    # [-1, 2**31]), so lane-offset keys keep the runs apart: the
    # gather is sorted exactly where every run is
    key = lane_id * _LANE_KEY
    run_key = np.repeat(key, c)
    kmin = bmin[rows].astype(np.int64) + run_key
    kmax = bmax[rows].astype(np.int64) + run_key
    unsorted = (np.diff(kmin) < 0) | (np.diff(kmax) < 0)
    if unsorted.any():
        bad = int(np.repeat(lane_id, c)[int(unsorted.argmax())])
        where = "" if n_sets == 1 else f"set {bad // t_pad}, "
        raise ValueError(
            f"{where}lane {bad % t_pad}: per-block doc ranges not sorted "
            f"(empty mid-run block or unsorted postings) — coverage would "
            f"be silently wrong")
    sub, g = geom.tile_sub, geom
    while True:
        # first block whose max_doc >= tile start; first block whose
        # min_doc >= tile end — [first, end) covers the tile
        w = g.tile_w
        q = (np.arange(g.n_tiles, dtype=np.int64) * w)[:, None] + key
        first = np.searchsorted(kmax, q, side="left") - first_pos
        end = np.searchsorted(kmin, q + w, side="left") - first_pos
        end = np.maximum(end, first)
        need = max(1, int((end - first).max())) if len(key) else 1
        # mosaic requires sublane block sizes divisible by 8; the
        # double-window scheme covers any alignment as long as cov <= cb,
        # and the segment padding (CB_MAX rows) must fit both windows:
        # cb <= CB_MAX // 2
        cb_req = next_pow2(max(need, 8))
        if cb_req <= CB_MAX // 2:
            break
        if not ladder or sub <= 32 or g.tile_sub < sub:
            raise ValueError(
                f"per-tile covering window of {need} blocks exceeds the "
                f"kernel bound {CB_MAX // 2}; use a smaller tile_sub")
        sub //= 2
        g = tile_geometry(geom.nd_pad, sub)
    if cb is not None:
        if cb < cb_req:
            raise ValueError(f"cb={cb} too small, need {cb_req}")
        if cb > CB_MAX // 2 or cb & (cb - 1):
            raise ValueError(
                f"cb={cb} must be a power of two <= {CB_MAX // 2} (the "
                f"second DMA window must stay inside the sentinel padding)")
        cb_req = cb
    row_lo = np.zeros((n_sets, g.n_tiles, t_pad), dtype=np.int32)
    row_hi = np.zeros((n_sets, g.n_tiles, t_pad), dtype=np.int32)
    weights = np.zeros((n_sets, 1, t_pad), dtype=np.float32)
    at = (lane_id // t_pad, slice(None), lane_id % t_pad)
    row_lo[at] = (s + first).T
    row_hi[at] = (s + end).T
    weights[at] = live[:, 3:]
    return row_lo, row_hi, weights, cb_req, g


def build_tile_tables(
    lanes: Sequence[QueryLane],
    bmin: np.ndarray,
    bmax: np.ndarray,
    geom: TileGeometry,
    t_pad: Optional[int] = None,
    cb: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """``build_tile_tables_sets`` for one lane set at ``geom`` (no
    ladder): (row_lo, row_hi [n_tiles, T_pad] i32, weights [1, T_pad]
    f32, CB)."""
    row_lo, row_hi, weights, cb, _ = build_tile_tables_sets(
        [lanes], bmin, bmax, geom, t_pad=t_pad, cb=cb)
    return row_lo[0], row_hi[0], weights[0], cb


def union_query_lanes(
    lane_sets: Sequence[Sequence[QueryLane]],
) -> Tuple[List[QueryLane], np.ndarray]:
    """Merge Q per-query lane sets into one union lane set plus a
    per-query weight matrix — the host half of cross-query micro-batching
    (ISSUE 5): the union's DMA windows are fetched ONCE per tile and a
    query participates in lane j iff weights[q, j] > 0 (its live-lane
    mask), so a short query in the batch never scores another query's
    terms. Lanes are keyed by their posting run (block_start, block_count)
    — two queries naming the same term share one lane, which is where the
    bandwidth amortization comes from under zipfian traffic."""
    union: List[QueryLane] = []
    index: dict = {}
    rows: List[dict] = []
    for lanes in lane_sets:
        row: dict = {}
        for lane in lanes:
            if lane.block_count <= 0 or lane.weight <= 0.0:
                continue
            key = (lane.block_start, lane.block_count)
            j = index.get(key)
            if j is None:
                j = len(union)
                index[key] = j
                # build coverage with weight 1.0: the union lane is live
                # whenever ANY member uses it
                union.append(QueryLane(lane.block_start, lane.block_count,
                                       1.0))
            row[j] = row.get(j, 0.0) + float(lane.weight)
        rows.append(row)
    t_pad = next_pow2(max(len(union), 1))
    weights = np.zeros((len(lane_sets), t_pad), dtype=np.float32)
    for q, row in enumerate(rows):
        for j, w in row.items():
            weights[q, j] = w
    return union, weights


def build_tile_tables_batched(
    lane_sets: Sequence[Sequence[QueryLane]],
    bmin: np.ndarray,
    bmax: np.ndarray,
    geom: TileGeometry,
    t_pad: Optional[int] = None,
    cb: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Batched form of build_tile_tables: one shared (row_lo, row_hi)
    covering the UNION of Q queries' term lanes plus a [Q, t_pad] weight
    matrix (zero = lane dead for that query). Same geometry-ladder
    contract as the single-query form: raises ValueError when the union's
    covering window exceeds the kernel bound at this tile size."""
    union, weights = union_query_lanes(lane_sets)
    t_pad = max(t_pad or 0, weights.shape[1])
    row_lo, row_hi, _w1, cb_req = build_tile_tables(
        union, bmin, bmax, geom, t_pad=t_pad, cb=cb)
    if weights.shape[1] < t_pad:
        weights = np.concatenate(
            [weights,
             np.zeros((weights.shape[0], t_pad - weights.shape[1]),
                      np.float32)], axis=1)
    return row_lo, row_hi, weights, cb_req


# ----------------------------------------------------------------------
# Block-max pruning (ISSUE 6): per-(tile, lane) upper-bound impacts
# ----------------------------------------------------------------------


def block_frac_max(block_frac: np.ndarray) -> np.ndarray:
    """Per-block max posting impact factor [n_blocks] f32 — the block-max
    metadata of WAND/MaxScore (SURVEY §6), computed at table-build time.

    The max is taken over ALL real postings regardless of the live mask:
    deletes mutate ``Segment.live`` in place after staging, and a bound
    that ignored a since-deleted doc could undercount — keeping tombstoned
    postings in the bound is conservative (a too-high bound only scores a
    tile it could have skipped, never skips one it needed).

    For the packed codec pass the DEQUANTIZED frac (dequantize_frac of
    quantize_frac): rounding can lift a posting up to half a step ABOVE
    its raw value, and the bound must dominate what the kernel actually
    decodes."""
    return block_frac.max(axis=1).astype(np.float32)


def tile_lane_ub(row_lo: np.ndarray, row_hi: np.ndarray,
                 bfmax: np.ndarray) -> np.ndarray:
    """Per-(tile, lane) upper-bound frac over the tile's covering block
    window [row_lo, row_hi) — a superset of the tile's real postings, so
    max over it upper-bounds any in-tile posting's frac. [n_tiles, t_pad]
    f32 (0 for empty windows / dead lanes).

    Vectorized (this runs per slot per pruned query): windows are short
    (<= the covering bucket), so a padded gather over [n_tiles,
    max_window] per lane beats per-window Python slicing."""
    n_tiles, t_pad = row_lo.shape
    out = np.zeros((n_tiles, t_pad), np.float32)
    n_blocks = len(bfmax)
    for j in range(t_pad):
        lo = row_lo[:, j].astype(np.int64)
        hi = row_hi[:, j].astype(np.int64)
        wmax = int((hi - lo).max()) if n_tiles else 0
        if wmax <= 0:
            continue
        idx = lo[:, None] + np.arange(wmax)[None, :]
        valid = idx < hi[:, None]
        vals = np.where(valid,
                        bfmax[np.minimum(idx, n_blocks - 1)], 0.0)
        out[:, j] = vals.max(axis=1)
    return out


def plan_pruned_tiles(row_lo: np.ndarray, row_hi: np.ndarray,
                      weights: np.ndarray, bfmax: np.ndarray,
                      probe_tiles: int = 8,
                      ub: Optional[np.ndarray] = None) -> Optional[dict]:
    """Host half of block-max pruned scoring: order tiles by their summed
    block-max score bound and split them into a PROBE set (scored
    unconditionally, seeds the running top-k threshold) and a REST set
    (scored only if its bound can still beat the threshold — decided
    on-device, see score_tiles_pruned). Returns None when the tile count
    is too small to prune (callers run the exhaustive kernel).

    ``weights`` is the [Q, t_pad] per-query weight matrix (a single query
    passes its [1, t_pad] row); bounds[t, q] = sum_j w[q, j] * ub[t, j]
    upper-bounds ANY doc's score for query q within tile t — the
    tile-granular WAND invariant the pruning tests property-check.

    ``ub`` lets callers supply precomputed (cached) per-(tile, lane)
    bounds — a lane's column depends only on (segment, geometry, posting
    run), so it is invariant across queries naming the same term
    (MeshPlanExecutor.tile_lane_ub_cached)."""
    n_tiles = row_lo.shape[0]
    probe = max(1, min(int(probe_tiles), n_tiles))
    if n_tiles - probe <= 0:
        return None
    if ub is None:
        ub = tile_lane_ub(row_lo, row_hi, bfmax)
    bounds = (ub @ weights.T).astype(np.float32)  # [n_tiles, Q]
    order = np.argsort(-bounds.max(axis=1), kind="stable").astype(np.int32)
    sel_p, sel_r = order[:probe], order[probe:]
    return {
        "tid_probe": sel_p,
        "rl_probe": np.ascontiguousarray(row_lo[sel_p]),
        "rh_probe": np.ascontiguousarray(row_hi[sel_p]),
        "tid_rest": sel_r,
        "rl_rest": np.ascontiguousarray(row_lo[sel_r]),
        "rh_rest": np.ascontiguousarray(row_hi[sel_r]),
        "bounds_rest": np.ascontiguousarray(bounds[sel_r]),
        "n_tiles": n_tiles,
    }


# ----------------------------------------------------------------------
# The kernel
# ----------------------------------------------------------------------


def _make_kernel(t_pad: int, cb: int, sub: int, k: int, dense: bool,
                 with_counts: bool, tps: int = 1, q_batch: int = 1,
                 codec: str = "raw", with_sel: bool = False):
    """Kernel body. Mosaic constraints shape the formulation:

    - only lane-collapsing reshapes ((cb,128) -> (1, cb*128)) lower; the
      column reshape (-> (rows, 1)) crashes the backend compiler, so every
      per-posting vector lives as a (1, rows) row and the accumulator is
      kept TRANSPOSED: accT[lane, sub] with doc local id = sub*128 + lane.
    - the scatter-matmul contracts over the posting axis on the LANES of
      both operands (the q @ k^T pattern):
          accT (LANE, sub) += lovT (LANE, rows) . ohT (sub, rows)^T
      where ohT one-hots the doc's high radix (local >> 7) and lovT
      one-hots the low radix (local & 127) scaled by weight*frac.
    - scalar stores to VMEM are rejected, so the per-tile top-k builds
      (1, k) vectors with masked selects and stores whole blocks.
    - bool -> f32 astype trips a recursive convert_element_type fallback;
      where-selects lower cleanly.

    ``tps`` (tiles per grid step): grid coarsening for DMA double-buffering
    across tiles — one grid step owns tps consecutive tiles, so all of the
    step's posting windows are issued up front and the DMA engine streams
    tile i+1's windows while the MXU works tile i, and the fixed per-step
    dispatch cost (which dominates the kernel — see module docstring) is
    paid once per tps tiles.

    ``q_batch`` (cross-query micro-batching, ISSUE 5): the tables cover
    the UNION of Q concurrent queries' term lanes and ``weights`` is
    [Q, t_pad]. The per-(tile, lane) posting windows are DMA'd ONCE and
    the lane's weight-free contribution matrix (one one-hot build + MXU
    matmul pair) is computed ONCE; each query then folds it in with a
    single f32 scale-add against its own weight — zero weight is the
    per-query live-lane mask, so a query never scores lanes it didn't
    ask for (and its match COUNTS only count its own lanes). Per-query
    state is a [Q*LANE, sub] scratch accumulator, and the top-k variant
    emits per-query candidate rows. q_batch == 1 keeps the historical
    single-query formulation bit-for-bit (weights folded into the
    one-hot before the matmul), so the unbatched path is untouched.

    ``codec`` (bit-packed postings, ISSUE 6): "packed" DMAs ONE i32 word
    per posting — (doc << PACK_FRAC_BITS) | frac_q — and decodes it in
    VMEM with a logical shift + mask + i32->f32 convert before the
    unchanged two-pass scoring, halving the posting-window HBM traffic
    the kernel is bound on. "raw" keeps the historical (docs, frac) pair
    layout untouched.

    ``with_sel`` (block-max pruned scoring, ISSUE 6): the grid runs over
    an arbitrary SUBSET of tiles named by a third scalar-prefetch array
    ``tile_ids`` (row tables arrive pre-gathered in subset order). A
    subset row whose windows are all empty (row_lo == row_hi == 0 — how
    the pruned orchestration marks a skipped tile at runtime) writes
    empty candidate rows without paying the top-k extraction, and its
    window DMAs collapse onto block 0 (consecutive identical block
    indices are not re-fetched by the pipeline), so a pruned tile costs
    neither bandwidth nor MXU work.
    """
    w = sub * LANE
    # two consecutive cb-aligned DMA windows per lane; each processes its
    # cb rows independently so its whole compute block can be skipped
    rows = cb * LANE
    packed = codec == "packed"
    stride = 2 if packed else 4

    def kernel(*all_refs):
        if with_sel:
            rowlo_ref, rowhi_ref, tid_ref = all_refs[:3]
            refs = all_refs[3:]
        else:
            rowlo_ref, rowhi_ref = all_refs[:2]
            refs = all_refs[2:]

        def dref(j, ti, half):
            return refs[stride * (j * tps + ti) + 2 * half]

        def fref(j, ti, half):
            return refs[stride * (j * tps + ti) + 2 * half + 1]

        def pref(j, ti, half):
            return refs[stride * (j * tps + ti) + half]

        base_in = stride * t_pad * tps
        n_live = tps if with_sel else 1
        live_refs = refs[base_in: base_in + n_live]
        w_ref = refs[base_in + n_live]
        n_outs = (1 + int(with_counts)) if dense else 3
        outs = refs[base_in + n_live + 1: base_in + n_live + 1 + n_outs]
        acc_ref = refs[base_in + n_live + 1 + n_outs]
        cnt_ref = (refs[base_in + n_live + 2 + n_outs]
                   if with_counts else None)
        t = pl.program_id(0)

        def tile_topk(accT, live, base):
            """Per-(tile, query) fused top-k extraction (the historical
            inline form, factored so the sel-mode branch shares it)."""
            matched = (accT > jnp.float32(0.0)) & live
            hits = jnp.sum(jnp.where(matched, jnp.float32(1.0),
                                     jnp.float32(0.0)))
            # float literals must be explicit f32: a weak python -inf
            # traces as an f64 scalar inside the kernel and crashes the
            # TPU compiler
            ninf = jnp.float32(NEG_INF)
            masked = jnp.where(matched, accT, ninf)
            # local doc id at accT[lane, s] is s*128 + lane
            lin = (lax.broadcasted_iota(jnp.int32, (LANE, sub), 1)
                   * jnp.int32(LANE)
                   + lax.broadcasted_iota(jnp.int32, (LANE, sub), 0))
            outv_s = jnp.full((1, k), NEG_INF, jnp.float32)
            outv_d = jnp.full((1, k), -1, jnp.int32)
            k_iota = lax.broadcasted_iota(jnp.int32, (1, k), 1)
            for i in range(k):
                mx = jnp.max(masked)
                sel = jnp.where(masked == mx, lin, jnp.int32(w))
                idx = jnp.min(sel)
                outv_s = jnp.where(k_iota == jnp.int32(i), mx, outv_s)
                outv_d = jnp.where(
                    k_iota == jnp.int32(i),
                    jnp.where(mx == ninf, jnp.int32(-1), base + idx),
                    outv_d)
                masked = jnp.where(lin == idx, ninf, masked)
            return hits, outv_s, outv_d

        for ti in range(tps):
            pos = jnp.int32(t) * jnp.int32(tps) + jnp.int32(ti)
            # with_sel: the grid position indexes the pre-gathered row
            # tables; the REAL tile id (doc base, live-mask row) comes
            # from the prefetched selection array
            tile = tid_ref[pos] if with_sel else pos
            base = tile * jnp.int32(w)
            # scratch accumulators persist across grid steps (and tiles
            # within a step): reset first (rows [q*LANE, (q+1)*LANE) hold
            # query q's transposed tile accumulator)
            acc_ref[...] = jnp.zeros((q_batch * LANE, sub), jnp.float32)
            if with_counts:
                cnt_ref[...] = jnp.zeros((q_batch * LANE, sub), jnp.float32)
            for j in range(t_pad):
                rlo = rowlo_ref[pos, j]
                rhi = rowhi_ref[pos, j]
                # aligned first row actually DMA'd (mirrors lane_map below)
                sb = lax.div(rlo, jnp.int32(cb)) * jnp.int32(cb)
                wj = w_ref[0, j]
                for half in (0, 1):
                    start = sb + jnp.int32(half * cb)
                    # skip the whole window when it can't intersect the
                    # lane's covering run: empty lanes skip both halves,
                    # and the second half only runs on the rare misaligned
                    # overflow — this halves the one-hot/MXU work in the
                    # common case
                    needed = (rhi > rlo) & (start < rhi) \
                        & (start + jnp.int32(cb) > rlo)

                    @pl.when(needed)
                    def _(j=j, ti=ti, half=half, start=start, rlo=rlo,
                          rhi=rhi, wj=wj, base=base):
                        if packed:
                            # in-VMEM decode: one logical shift + one mask
                            # + one i32->f32 convert per window — the DMA
                            # streamed HALF the bytes of the raw layout.
                            # shift_right_logical: doc 20 bits + frac 12
                            # bits fills the word, so the sign bit can be
                            # set and an arithmetic shift would smear it
                            word = pref(j, ti, half)[...]
                            docs = lax.shift_right_logical(
                                word, jnp.int32(PACK_FRAC_BITS))
                            fq = jnp.bitwise_and(
                                word, jnp.int32(PACK_FRAC_MASK))
                            frac = fq.astype(jnp.float32) * jnp.float32(
                                PACK_FRAC_SCALE)
                        else:
                            docs = dref(j, ti, half)[...]
                            frac = fref(j, ti, half)[...]
                        blk = start + lax.broadcasted_iota(
                            jnp.int32, (cb, LANE), 0)
                        local = docs - base
                        valid = (
                            (blk >= rlo) & (blk < rhi)
                            & (local >= jnp.int32(0)) & (local < jnp.int32(w))
                            & (frac > jnp.float32(0.0))
                        )
                        # NB every scalar int literal below must be an
                        # explicit int32: inside the kernel trace weak
                        # python ints become i64 scalars, and mosaic's
                        # i64->i32 demotion fallback recurses forever
                        safe = jnp.where(valid, local, jnp.int32(0))
                        hi = jnp.where(valid, lax.shift_right_logical(
                            safe, jnp.int32(7)), jnp.int32(-1))
                        lo = jnp.where(valid, jnp.bitwise_and(
                            safe, jnp.int32(LANE - 1)), jnp.int32(-1))
                        hi_row = hi.reshape(1, rows)
                        lo_row = lo.reshape(1, rows)
                        wf_row = ((frac * wj).reshape(1, rows)
                                  if q_batch == 1 else None)
                        ohT = jnp.where(
                            lax.broadcasted_iota(
                                jnp.int32, (sub, rows), 0) == hi_row,
                            jnp.float32(1.0), jnp.float32(0.0))
                        # two-pass error-compensated matmul: the MXU's
                        # default single bf16 pass rounds w*frac to an
                        # 8-bit mantissa (~0.2% rel error — enough to
                        # reorder near-tied BM25 ranks vs the host oracle),
                        # and Precision.HIGHEST costs 6 passes. bf16-high +
                        # f32-residual summed over two DEFAULT dots gives
                        # ~2^-17 rel error at 1/3 the passes (ohT is 0/1,
                        # bf16-exact).
                        lane_iota = lax.broadcasted_iota(
                            jnp.int32, (LANE, rows), 0)
                        if q_batch == 1:
                            wf_hi = wf_row.astype(jnp.bfloat16).astype(
                                jnp.float32)
                            wf_lo = wf_row - wf_hi
                            lov_hi = jnp.where(lane_iota == lo_row, wf_hi,
                                               jnp.float32(0.0))
                            lov_lo = jnp.where(lane_iota == lo_row, wf_lo,
                                               jnp.float32(0.0))
                            acc_ref[...] = acc_ref[...] + lax.dot_general(
                                lov_hi, ohT, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) + lax.dot_general(
                                lov_lo, ohT, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
                            if with_counts:
                                lovT1 = jnp.where(lane_iota == lo_row,
                                                  jnp.float32(1.0),
                                                  jnp.float32(0.0))
                                cnt_ref[...] = cnt_ref[...] + lax.dot_general(
                                    lovT1, ohT, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                        else:
                            # batched: the lane's weight-free contribution
                            # matrix is built ONCE (same two-pass bf16
                            # error compensation, applied to frac alone —
                            # the f32 weight multiplies after the dot, so
                            # precision matches the single-query path);
                            # each query folds it in with one scale-add,
                            # which is how one DMA + one MXU pass serve
                            # the whole batch
                            f_row = frac.reshape(1, rows)
                            f_hi = f_row.astype(jnp.bfloat16).astype(
                                jnp.float32)
                            f_lo = f_row - f_hi
                            lov_hi = jnp.where(lane_iota == lo_row, f_hi,
                                               jnp.float32(0.0))
                            lov_lo = jnp.where(lane_iota == lo_row, f_lo,
                                               jnp.float32(0.0))
                            contrib = lax.dot_general(
                                lov_hi, ohT, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) + lax.dot_general(
                                lov_lo, ohT, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
                            if with_counts:
                                lovT1 = jnp.where(lane_iota == lo_row,
                                                  jnp.float32(1.0),
                                                  jnp.float32(0.0))
                                ccontrib = lax.dot_general(
                                    lovT1, ohT, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                            for q in range(q_batch):
                                wq = w_ref[q, j]
                                qs = pl.ds(q * LANE, LANE)
                                acc_ref[qs, :] = (acc_ref[qs, :]
                                                  + wq * contrib)
                                if with_counts:
                                    # weight > 0 is the per-query live-
                                    # lane mask: a dead lane must not
                                    # count toward minimum_should_match
                                    cnt_ref[qs, :] = cnt_ref[qs, :] + \
                                        jnp.where(wq > jnp.float32(0.0),
                                                  ccontrib,
                                                  jnp.float32(0.0))
            if with_sel:
                # sel mode serves the fused top-k only. A runtime-skipped
                # tile (all windows empty — the pruned orchestration
                # zeroed its row table) writes empty candidate rows and
                # pays neither the live-mask DMA nor the top-k loop.
                scored = rowhi_ref[pos, 0] > rowlo_ref[pos, 0]
                for j in range(1, t_pad):
                    scored = jnp.logical_or(
                        scored, rowhi_ref[pos, j] > rowlo_ref[pos, j])
                out_s, out_d, out_h = outs

                @pl.when(jnp.logical_not(scored))
                def _(ti=ti):
                    for q in range(q_batch):
                        out_h[pl.ds(ti, 1), pl.ds(q, 1)] = jnp.zeros(
                            (1, 1, 1), jnp.float32)
                        out_s[pl.ds(ti, 1), pl.ds(q, 1)] = jnp.full(
                            (1, 1, k), NEG_INF, jnp.float32)
                        out_d[pl.ds(ti, 1), pl.ds(q, 1)] = jnp.full(
                            (1, 1, k), -1, jnp.int32)

                @pl.when(scored)
                def _(ti=ti, base=base):
                    live = live_refs[ti][...] > jnp.float32(0.0)
                    for q in range(q_batch):
                        accT = (acc_ref[...] if q_batch == 1
                                else acc_ref[pl.ds(q * LANE, LANE), :])
                        hits, outv_s, outv_d = tile_topk(accT, live, base)
                        out_h[pl.ds(ti, 1), pl.ds(q, 1)] = \
                            hits.reshape(1, 1, 1)
                        out_s[pl.ds(ti, 1), pl.ds(q, 1)] = \
                            outv_s.reshape(1, 1, k)
                        out_d[pl.ds(ti, 1), pl.ds(q, 1)] = \
                            outv_d.reshape(1, 1, k)
                continue
            # (LANE, sub) transposed live slab for THIS tile (shared by
            # every query of the batch); tps==1 keeps the historical
            # full-block access pattern
            if tps == 1:
                live = live_refs[0][...] > jnp.float32(0.0)
            else:
                live = live_refs[0][pl.ds(ti * LANE, LANE), :] \
                    > jnp.float32(0.0)
            for q in range(q_batch):
                if q_batch == 1:
                    accT = acc_ref[...]
                    cntT = cnt_ref[...] if with_counts else None
                else:
                    accT = acc_ref[pl.ds(q * LANE, LANE), :]
                    cntT = (cnt_ref[pl.ds(q * LANE, LANE), :]
                            if with_counts else None)
                if dense:
                    sc = jnp.where(live, accT, jnp.float32(0.0))
                    if q_batch == 1:
                        if tps == 1:
                            outs[0][...] = sc
                            if with_counts:
                                outs[1][...] = jnp.where(live, cntT,
                                                         jnp.float32(0.0))
                        else:
                            outs[0][pl.ds(ti * LANE, LANE), :] = sc
                            if with_counts:
                                outs[1][pl.ds(ti * LANE, LANE), :] = jnp.where(
                                    live, cntT, jnp.float32(0.0))
                    else:
                        outs[0][pl.ds(q, 1), pl.ds(ti * LANE, LANE), :] = \
                            sc[None]
                        if with_counts:
                            outs[1][pl.ds(q, 1), pl.ds(ti * LANE, LANE), :] = \
                                jnp.where(live, cntT, jnp.float32(0.0))[None]
                    continue
                out_s, out_d, out_h = outs
                hits, outv_s, outv_d = tile_topk(accT, live, base)
                if q_batch > 1:
                    out_h[pl.ds(ti, 1), pl.ds(q, 1)] = hits.reshape(1, 1, 1)
                    out_s[pl.ds(ti, 1), pl.ds(q, 1)] = outv_s.reshape(1, 1, k)
                    out_d[pl.ds(ti, 1), pl.ds(q, 1)] = outv_d.reshape(1, 1, k)
                elif tps == 1:
                    out_h[...] = hits.reshape(1, 1, 1)
                    out_s[...] = outv_s.reshape(1, 1, k)
                    out_d[...] = outv_d.reshape(1, 1, k)
                else:
                    out_h[pl.ds(ti, 1)] = hits.reshape(1, 1, 1)
                    out_s[pl.ds(ti, 1)] = outv_s.reshape(1, 1, k)
                    out_d[pl.ds(ti, 1)] = outv_d.reshape(1, 1, k)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("t_pad", "cb", "sub", "k", "dense", "with_counts",
                     "interpret", "tiles_per_step", "q_batch", "codec"),
)
def score_tiles(
    docs_padded,  # [n_blocks + CB_MAX, LANE] i32 (pad_segment_blocks);
    # codec="packed": the packed word array (pack_segment_blocks)
    frac_padded,  # [n_blocks + CB_MAX, LANE] f32; codec="packed": None
    live_t,  # [n_tiles * LANE, sub] f32 (1.0 = live; build_live_t)
    row_lo,  # [n_tiles, t_pad] i32
    row_hi,  # [n_tiles, t_pad] i32
    weights,  # [q_batch, t_pad] f32 ([1, t_pad] unbatched)
    *,
    t_pad: int,
    cb: int,
    sub: int,
    k: int = 10,
    dense: bool = False,
    with_counts: bool = False,
    interpret: bool = False,
    tiles_per_step: int = 1,
    q_batch: int = 1,
    codec: str = "raw",
    tile_ids=None,  # [n_sel] i32: score ONLY these tiles (row tables
    # pre-gathered in the same order); fused top-k variant only
    row_base=None,  # i32 scalar: first row of this segment's postings
    # inside docs_padded/frac_padded, where they hold several segments
):
    """Run the tile-scoring kernel over a segment.

    top-k variant (dense=False): returns (tile_scores [n_tiles, q_batch, k]
    f32, tile_docs [n_tiles, q_batch, k] i32 (-1 = empty), tile_hits
    [n_tiles, q_batch, 1]) — q_batch is 1 for a single query, preserving
    the historical shapes.

    dense variant (dense=True): returns scores [n_tiles*LANE, sub] f32 in
    the kernel's transposed tile layout (dense_to_flat -> [nd_pad]) and,
    with_counts, match counts of the same shape (for minimum_should_match
    / conjunction masking). With q_batch > 1 both gain a leading [q_batch]
    axis.

    tiles_per_step > 1 coarsens the grid: each step owns that many
    consecutive tiles, double-buffering their DMA windows against compute
    and amortizing the fixed per-grid-step cost that dominates this kernel
    (the output layouts are unchanged). Clamped down to a divisor of
    n_tiles.

    q_batch > 1 is cross-query micro-batching (ISSUE 5): row_lo/row_hi
    cover the UNION of the batch's term lanes (build_tile_tables_batched)
    and weights carries one row per query (0 = lane dead for that query).
    Corpus bytes stream ONCE per tile for the whole batch; per-query cost
    reduces to one scale-add per live lane plus the per-tile top-k loop.

    codec="packed" streams the bit-packed posting words instead of the
    (docs, frac) pair — HALF the posting bytes — and decodes in-kernel
    (pass the pack_segment_blocks array as docs_padded, frac_padded
    None). tile_ids scores an arbitrary tile SUBSET (block-max pruning,
    ISSUE 6): row_lo/row_hi arrive pre-gathered in subset order, outputs
    have one candidate row per subset entry, and a runtime-zeroed row
    (row_lo == row_hi == 0) is skipped without DMA or compute.

    row_base reads one segment out of a table that holds several,
    concatenated along rows (the mesh executor stages every slot of a
    device that way): row_lo/row_hi arrive relative to the segment and
    become rows of the table here. The index maps and the kernel's
    masks both read them, so nothing else moves — slicing the segment
    out first would copy it per call. A traced operand, not a static
    one: the slots of a device then share ONE trace of the kernel
    (a static offset costs a trace and a mosaic compile per slot).
    """
    if row_base is not None:
        row_base = jnp.asarray(row_base, jnp.int32)
        row_lo = row_lo + row_base
        row_hi = row_hi + row_base
    with_sel = tile_ids is not None
    if with_sel and (dense or with_counts):
        # dense / match-count consumers need every tile's output —
        # pruning's exhaustive-fallback contract lives one level up
        raise ValueError(
            "tile-subset scoring serves the fused top-k variant only")
    n_tiles = row_lo.shape[0]
    w = sub * LANE
    k = min(k, w)
    q_batch = max(1, int(q_batch))
    tps = max(1, int(tiles_per_step))
    while n_tiles % tps:
        tps //= 2

    # index maps must return int32 everywhere (and build the constant INSIDE
    # the lambda — captured tracers are rejected): the engine runs with jax
    # x64 enabled (ops/__init__.py), under which python-int literals become
    # i64 constants in the mosaic transform functions and crash the TPU
    # compile helper
    def zero():
        return jnp.int32(0)

    def lane_map(j, ti, half):
        # lax.div (truncating) == floor-div for the non-negative row indices;
        # jnp's // lowers to a floor_divide jaxpr the mosaic index_map
        # rejects. half=0/1 selects the first/second cb-aligned window of
        # tile t*tps + ti (sel mode: the SUBSET position — tables arrive
        # pre-gathered, so position-indexing is correct there too).
        if with_sel:
            return lambda t, rlo, rhi, tid: (
                lax.div(rlo[jnp.int32(t) * jnp.int32(tps) + jnp.int32(ti),
                            j],
                        jnp.int32(cb)) + jnp.int32(half), zero())
        return lambda t, rlo, rhi: (
            lax.div(rlo[jnp.int32(t) * jnp.int32(tps) + jnp.int32(ti), j],
                    jnp.int32(cb)) + jnp.int32(half), zero())

    in_specs = []
    operands = []
    for j in range(t_pad):
        for ti in range(tps):
            for half in (0, 1):
                in_specs.append(pl.BlockSpec((cb, LANE), lane_map(j, ti, half)))
                operands.append(docs_padded)
                if codec != "packed":
                    in_specs.append(
                        pl.BlockSpec((cb, LANE), lane_map(j, ti, half)))
                    operands.append(frac_padded)
    if with_sel:
        # per-tile live slabs indexed by the REAL tile id from the
        # prefetched selection (a runtime-redirected skipped tile reads
        # row 0 — consecutive identical indices are not re-fetched)
        for ti in range(tps):
            in_specs.append(pl.BlockSpec(
                (LANE, sub),
                (lambda t, rlo, rhi, tid, ti=ti:
                 (tid[jnp.int32(t) * jnp.int32(tps) + jnp.int32(ti)],
                  zero()))))
            operands.append(live_t)
    else:
        in_specs.append(
            pl.BlockSpec((tps * LANE, sub),
                         lambda t, rlo, rhi: (t, zero())))
        operands.append(live_t)
    # the SMEM spec needs an explicit index map: the auto-generated default
    # returns weak python-int zeros, which trace to i64 under x64 and fail
    # mosaic legalization on real hardware (interpret mode doesn't catch it)
    if with_sel:
        smem_map = lambda t, rlo, rhi, tid: (zero(), zero())  # noqa: E731
    else:
        smem_map = lambda t, rlo, rhi: (zero(), zero())  # noqa: E731
    in_specs.append(pl.BlockSpec((q_batch, t_pad), smem_map,
                                 memory_space=pltpu.SMEM))
    operands.append(weights)

    if dense:
        if q_batch == 1:
            out_specs = [
                pl.BlockSpec((tps * LANE, sub),
                             lambda t, rlo, rhi: (t, zero()))]
            out_shape = [
                jax.ShapeDtypeStruct((n_tiles * LANE, sub), jnp.float32)]
            if with_counts:
                out_specs.append(
                    pl.BlockSpec((tps * LANE, sub),
                                 lambda t, rlo, rhi: (t, zero())))
                out_shape.append(
                    jax.ShapeDtypeStruct((n_tiles * LANE, sub), jnp.float32))
        else:
            # per-query dense slabs: the leading q axis rides whole in
            # every block (only the last two dims face mosaic's
            # divisibility-or-full-dim rule, and those are unchanged)
            out_specs = [
                pl.BlockSpec((q_batch, tps * LANE, sub),
                             lambda t, rlo, rhi: (zero(), t, zero()))]
            out_shape = [jax.ShapeDtypeStruct(
                (q_batch, n_tiles * LANE, sub), jnp.float32)]
            if with_counts:
                out_specs.append(
                    pl.BlockSpec((q_batch, tps * LANE, sub),
                                 lambda t, rlo, rhi: (zero(), t, zero())))
                out_shape.append(jax.ShapeDtypeStruct(
                    (q_batch, n_tiles * LANE, sub), jnp.float32))
    else:
        # 3D outputs: the last two dims of each block equal the array dims,
        # satisfying mosaic's (8, 128)-divisibility-or-full-dim rule for
        # small per-tile outputs (the middle dim is the per-query row)
        if with_sel:
            out_map = lambda t, rlo, rhi, tid: (t, zero(), zero())  # noqa: E731
        else:
            out_map = lambda t, rlo, rhi: (t, zero(), zero())  # noqa: E731
        out_specs = [
            pl.BlockSpec((tps, q_batch, k), out_map),
            pl.BlockSpec((tps, q_batch, k), out_map),
            pl.BlockSpec((tps, q_batch, 1), out_map),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((n_tiles, q_batch, k), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, q_batch, k), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, q_batch, 1), jnp.float32),
        ]

    scratch_shapes = [pltpu.VMEM((q_batch * LANE, sub), jnp.float32)]
    if with_counts:
        scratch_shapes.append(pltpu.VMEM((q_batch * LANE, sub), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if with_sel else 2,
        grid=(n_tiles // tps,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    kernel = _make_kernel(t_pad, cb, sub, k, dense, with_counts, tps,
                          q_batch, codec, with_sel)
    prefetch = ((row_lo, row_hi, jnp.asarray(tile_ids, jnp.int32))
                if with_sel else (row_lo, row_hi))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # stated, not inherited from a Python function: the device
        # trace's readers find the custom call by this name
        name="score_tiles",
    )(*prefetch, *operands)
    return out


@functools.partial(jax.jit, static_argnames=("k",))
def merge_tile_topk(tile_scores, tile_docs, tile_hits, k: int):
    """Merge per-tile candidates: global top-k by score (doc id descending
    tiebreak is irrelevant — -1 slots carry -inf) + total live hit count."""
    flat_s = tile_scores.reshape(-1)
    flat_d = tile_docs.reshape(-1)
    kk = min(k, flat_s.shape[0])
    top_s, top_i = lax.top_k(flat_s, kk)
    return top_s, flat_d[top_i], jnp.sum(tile_hits).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_tile_topk_batched(tile_scores, tile_docs, tile_hits, k: int):
    """Per-query merge of a batched top-k launch: tile_scores/tile_docs
    are [n_tiles, Q, k]; returns (top_s [Q, k'], top_d [Q, k'],
    hits [Q] i32) with k' = min(k, n_tiles*k)."""
    n_tiles, q, kk_in = tile_scores.shape
    flat_s = tile_scores.transpose(1, 0, 2).reshape(q, -1)
    flat_d = tile_docs.transpose(1, 0, 2).reshape(q, -1)
    kk = min(k, flat_s.shape[1])
    top_s, top_i = lax.top_k(flat_s, kk)
    top_d = jnp.take_along_axis(flat_d, top_i, axis=1)
    hits = jnp.sum(tile_hits.reshape(n_tiles, q), axis=0).astype(jnp.int32)
    return top_s, top_d, hits


@functools.partial(
    jax.jit,
    static_argnames=("t_pad", "cb", "sub", "k", "q_batch", "q_real",
                     "codec", "interpret", "tiles_per_step"),
)
def score_tiles_pruned(
    docs_padded,  # raw: padded docs; packed: the packed word array
    frac_padded,  # raw: padded frac; packed: None
    live_t,
    rl_probe, rh_probe, tid_probe,  # plan_pruned_tiles outputs
    rl_rest, rh_rest, tid_rest,
    bounds_rest,  # [n_rest, q_batch] f32 per-(tile, query) score bounds
    weights,  # [q_batch, t_pad] f32
    *,
    t_pad: int,
    cb: int,
    sub: int,
    k: int = 10,
    q_batch: int = 1,
    q_real: Optional[int] = None,
    codec: str = "raw",
    interpret: bool = False,
    tiles_per_step: int = 1,
):
    """Block-max pruned top-k scoring (ISSUE 6) — ONE compiled program,
    no host round-trip (the bench backend pays a fixed ~70 ms per D2H
    sync, so a host-side threshold exchange would drown the win):

    1. PROBE pass: score the ``probe`` highest-bound tiles (host-ordered
       by plan_pruned_tiles) and merge their candidates — the k-th best
       score per query is the running threshold theta_q (a lower bound on
       the FINAL k-th score, since the candidate pool only grows).
    2. In-program gate: a rest tile survives iff ANY real member's bound
       can still beat its threshold (bounds[t, q] >= theta_q — per-query
       thresholds over the union lanes, so batching composes without
       cross-member leakage). Non-survivors get their row-table windows
       ZEROED at runtime: the sel-mode kernel then skips their compute
       and their window DMAs collapse onto block 0 (scalar-prefetch row
       tables are runtime values — this is where the bytes are saved).
    3. REST pass over the (masked) remaining tiles; both passes' pools
       merge per query.

    Correctness invariant (property-tested): a pruned tile's bound is an
    upper bound on any of its docs' scores, and it is pruned only when
    strictly below theta_q <= final k-th score — so no true top-k doc is
    ever skipped. Match totals only count SCORED tiles: under pruning
    ``hits`` is a documented lower bound (WAND semantics), which is why
    exact-total consumers take the exhaustive path.

    q_real: how many leading rows of ``weights`` are real members (the
    rest are power-of-two padding); padded members never hold tiles
    alive. Returns (top_s [Q, k'], top_d [Q, k'], hits [Q] i32,
    tiles_scored i32 scalar).
    """
    if q_real is None:
        q_real = q_batch
    kw = dict(t_pad=t_pad, cb=cb, sub=sub, k=k, interpret=interpret,
              tiles_per_step=tiles_per_step, q_batch=q_batch, codec=codec)
    ts1, td1, th1 = score_tiles(
        docs_padded, frac_padded, live_t, rl_probe, rh_probe, weights,
        tile_ids=tid_probe, **kw)
    s1, d1, h1 = merge_tile_topk_batched(ts1, td1, th1, k)
    if s1.shape[1] >= k:
        kth = s1[:, k - 1]
    else:
        # fewer candidate slots than k: no threshold can be claimed
        kth = jnp.full((q_batch,), -jnp.inf, jnp.float32)
    # padding members (q >= q_real) must never keep a tile alive: their
    # bounds are 0 (all-zero weights) and 0 >= -inf would pin every tile
    theta = jnp.where(jnp.arange(q_batch) < q_real, kth,
                      jnp.float32(np.inf))
    survive = jnp.any(bounds_rest >= theta[None, :], axis=1)  # [n_rest]
    rl2 = jnp.where(survive[:, None], rl_rest, jnp.int32(0))
    rh2 = jnp.where(survive[:, None], rh_rest, jnp.int32(0))
    tid2 = jnp.where(survive, tid_rest, jnp.int32(0))
    ts2, td2, th2 = score_tiles(
        docs_padded, frac_padded, live_t, rl2, rh2, weights,
        tile_ids=tid2, **kw)
    s2, d2, h2 = merge_tile_topk_batched(ts2, td2, th2, k)
    pool_s = jnp.concatenate([s1, s2], axis=1)
    pool_d = jnp.concatenate([d1, d2], axis=1)
    top_s, top_i = lax.top_k(pool_s, min(k, pool_s.shape[1]))
    top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
    hits = h1 + h2
    tiles_scored = (jnp.int32(tid_probe.shape[0])
                    + jnp.sum(survive.astype(jnp.int32)))
    return top_s, top_d, hits, tiles_scored


def build_live_t(live: np.ndarray, geom: TileGeometry) -> np.ndarray:
    """Host-side: live mask [>= nd_pad] bool/float -> the kernel's
    transposed tile layout [n_tiles * LANE, sub] f32."""
    sub, n_tiles = geom.tile_sub, geom.n_tiles
    flat = np.zeros(geom.nd_pad, np.float32)
    flat[: len(live)] = live[: geom.nd_pad].astype(np.float32)
    return np.ascontiguousarray(
        flat.reshape(n_tiles, sub, LANE).transpose(0, 2, 1)
    ).reshape(n_tiles * LANE, sub)


@functools.partial(jax.jit, static_argnames=("sub",))
def dense_to_flat(dense, sub: int):
    """Device-side: kernel dense output [n_tiles*LANE, sub] -> [nd_pad]
    in natural doc order (doc = tile*W + s*128 + lane)."""
    n_tiles = dense.shape[0] // LANE
    return dense.reshape(n_tiles, LANE, sub).transpose(0, 2, 1).reshape(-1)


# ----------------------------------------------------------------------
# Numpy reference (tests + CPU fallback parity)
# ----------------------------------------------------------------------


def reference_scores(
    block_docs: np.ndarray,
    block_frac: np.ndarray,
    lanes: Sequence[QueryLane],
    nd_pad: int,
) -> np.ndarray:
    """Dense scores via host scatter-add — the oracle the kernel must match."""
    scores = np.zeros(nd_pad, np.float32)
    for lane in lanes:
        if lane.block_count <= 0 or lane.weight == 0.0:
            continue
        rows = slice(lane.block_start, lane.block_start + lane.block_count)
        docs = block_docs[rows].ravel()
        frac = block_frac[rows].ravel()
        real = (frac > 0) & (docs < nd_pad)
        np.add.at(scores, docs[real], lane.weight * frac[real])
    return scores
