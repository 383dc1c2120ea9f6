"""Query execution plans: a query tree compiled to ONE jitted XLA program.

Role model inversion: the reference executes a query as a virtual-call
tree of Lucene ``Weight``/``Scorer`` objects driven doc-at-a-time by a
collector (search/query/QueryPhase.java:272). Here the whole boolean/
scoring tree is *traced once* into a single XLA program operating on dense
``[nd1]`` score/match vectors (SURVEY.md §7.1): leaves gather posting
blocks or doc-value columns; combiners are elementwise ops; XLA fuses the
lot. Programs are cached by plan *structure* (node types + array shapes);
the same shaped query never recompiles.

Every node emits ``(scores f32[nd1], matched bool[nd1])``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops import masks as mask_ops
from elasticsearch_tpu.ops.scoring import B, K1


class PlanNode:
    """Base: subclasses define emit(ctx), structural key(), arrays()."""

    def emit(self, ctx: "EmitCtx"):
        raise NotImplementedError

    def key(self) -> str:
        raise NotImplementedError

    def arrays(self) -> List:
        return []

    def children(self) -> List["PlanNode"]:
        return []

    def flat_arrays(self) -> List:
        out = list(self.arrays())
        for c in self.children():
            out.extend(c.flat_arrays())
        return out

    def seg_columns(self) -> List[str]:
        """Names of the columns this node reads from ``ctx.seg`` that a
        mesh executor stages on demand (``StagedNumeric*Node``)."""
        return []

    def flat_seg_columns(self) -> List[str]:
        out = list(self.seg_columns())
        for c in self.children():
            out.extend(c.flat_seg_columns())
        return out

    def pad_kinds(self) -> List[str]:
        """How each entry of arrays() pads when per-shard plans for the
        SAME query are stacked onto a device mesh (parallel/plan_exec.py).
        Aligned with arrays(). Kinds:
          "s"     scalar — stacked to [n_dev], never padded
          "z"     pad with 0 / False
          "o"     pad with 1 (divisors: avgdl, similarity params)
          "n"     pad with nan (value columns: nan compares False)
          "m1"    pad with -1 (ordinal ids; -1 never matches a real ord)
          "d"     doc-id array — pad with the stacked sentinel doc
                  (nd1-1, dead in live1) and re-point the shard-local
                  sentinel to the stacked one
          "dense" dense-over-docs [local_nd1,...] — zero-extend to the
                  stacked nd1
        """
        return ["z"] * len(self.arrays())

    def trace_statics(self) -> tuple:
        """Static (non-array) attributes baked into the traced program.
        Per-shard plans for the same query may only be stacked onto one
        mesh template when these agree — array lengths may differ (they
        pad), but a differing static here would score non-template shards
        with the wrong formula."""
        return ()

    def flat_pad_kinds(self) -> List[str]:
        out = list(self.pad_kinds())
        for c in self.children():
            out.extend(c.flat_pad_kinds())
        return out

    def describe(self) -> dict:
        """Profile tree (search/profile/query/ProfileScorer.java analog).
        The whole plan executes as ONE fused XLA program, so child nodes
        carry structure, not separate timings — the root's breakdown owns
        the measured device time and children are marked fused."""
        return {
            "type": type(self).__name__,
            "description": self.key(),
            "children": [c.describe() for c in self.children()],
        }


class EmitCtx:
    """Carries the segment device arrays + the flat plan-array iterator
    during tracing. ``row_base``: first row of this segment's postings
    inside the kernel tables (k_docs / k_frac / k_packed) — None where
    the tables are the segment's own, the slot's offset where the mesh
    executor hands over a device's whole table."""

    def __init__(self, seg_arrays: dict, plan_arrays: List,
                 row_base: Optional[int] = None):
        self.seg = seg_arrays
        self._arrays = plan_arrays
        self._pos = 0
        self.row_base = row_base

    def take(self, n: int) -> List:
        out = self._arrays[self._pos : self._pos + n]
        self._pos += n
        return out

    @property
    def nd1(self) -> int:
        return self.seg["norms"].shape[1]

    def zeros_f(self):
        return jnp.zeros((self.nd1,), jnp.float32)

    def zeros_b(self):
        return jnp.zeros((self.nd1,), bool)


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------


class ScoreTermsNode(PlanNode):
    """Weighted disjunction of term posting blocks with per-lane similarity
    scoring (BM25 default) and a minimum-distinct-match threshold
    (match/term/multi_match leaves).

    Each posting-block lane carries its similarity's host-folded constants
    (weight + p1..p3, see index/similarity.py); the traced formula set is
    selected statically by the node's distinct ``kinds`` tuple, so a plain
    BM25 query compiles exactly the BM25 arithmetic."""

    def __init__(self, q_blocks, q_weights, q_norm_rows, q_avgdl, q_valid,
                 min_match, k1: float = K1, b: float = B,
                 q_p1=None, q_p2=None, q_p3=None, q_kinds=None,
                 kinds: tuple = ("bm25",)):
        from elasticsearch_tpu.index.similarity import STRICTLY_POSITIVE_KINDS

        n = len(q_blocks)
        self.q_blocks = q_blocks
        self.q_weights = q_weights
        self.q_norm_rows = q_norm_rows
        self.q_avgdl = q_avgdl
        self.q_valid = q_valid
        self.min_match = np.float32(min_match)
        # default lane params reproduce classic BM25(k1, b)
        self.q_p1 = q_p1 if q_p1 is not None else np.full(n, k1, np.float32)
        self.q_p2 = q_p2 if q_p2 is not None else np.full(n, b, np.float32)
        self.q_p3 = q_p3 if q_p3 is not None else np.zeros(n, np.float32)
        self.q_kinds = q_kinds if q_kinds is not None else np.zeros(n, np.int32)
        self.kinds = tuple(kinds)
        # single-scatter fast path: only when "matched == score > 0" holds,
        # i.e. plain disjunction AND every live weight strictly positive
        # (a boost of 0 would make a matching doc score 0) AND every
        # similarity in play yields strictly positive contributions
        self._fast = (
            bool(min_match <= 1)
            and bool((np.asarray(q_weights)[np.asarray(q_valid)] > 0).all())
            and all(k in STRICTLY_POSITIVE_KINDS for k in self.kinds)
        )

    def key(self):
        # the fast path + similarity set change the traced program
        return f"terms[{len(self.q_blocks)},{','.join(self.kinds)},{self._fast}]"

    def trace_statics(self):
        return (self.kinds, self._fast)

    def arrays(self):
        return [self.q_blocks, self.q_weights, self.q_norm_rows, self.q_avgdl,
                self.q_valid, self.min_match, self.q_p1, self.q_p2, self.q_p3,
                self.q_kinds]

    def pad_kinds(self):
        return ["z", "z", "z", "o", "z", "s", "o", "o", "z", "z"]

    def emit(self, ctx):
        from elasticsearch_tpu.index.similarity import emit_contrib

        (q_blocks, q_weights, q_norm_rows, q_avgdl, q_valid, min_match,
         q_p1, q_p2, q_p3, q_kinds) = ctx.take(10)
        docs = ctx.seg["block_docs"][q_blocks]
        tfs = ctx.seg["block_tfs"][q_blocks]
        # flat 1-D gather (2-D advanced indexing lowers to a slower general
        # gather on TPU)
        norms = ctx.seg["norms"]
        nd1 = norms.shape[1]
        flat_idx = (q_norm_rows[:, None] * nd1 + docs).ravel()
        doc_len = norms.ravel()[flat_idx].reshape(docs.shape)
        matched = (tfs > 0.0) & q_valid[:, None]
        w = q_weights[:, None]
        avgdl = q_avgdl[:, None]
        p1, p2, p3 = q_p1[:, None], q_p2[:, None], q_p3[:, None]
        if len(self.kinds) == 1:
            contrib = emit_contrib(self.kinds[0], tfs, doc_len, w, avgdl,
                                   p1, p2, p3)
        else:
            contrib = jnp.zeros_like(tfs)
            for i, kind in enumerate(self.kinds):
                lane = (q_kinds == i)[:, None]
                val = emit_contrib(kind, tfs, doc_len, w, avgdl, p1, p2, p3)
                contrib = contrib + jnp.where(lane, val, 0.0)
        contrib = jnp.where(matched, contrib, 0.0)
        scores = ctx.zeros_f().at[docs].add(contrib)
        if self._fast:
            # contributions are strictly positive, so scores > 0 is
            # exactly "any term matched" — saves the second scatter
            return scores, scores > 0.0
        counts = ctx.zeros_f().at[docs].add(matched.astype(jnp.float32))
        return scores, counts >= min_match


class PallasScoreTermsNode(PlanNode):
    """BM25 disjunction executed by the tile-scoring pallas kernel
    (ops/pallas_scoring.py) instead of the XLA scatter-add — the TPU
    replacement for the reference's BulkScorer loop
    (search/query/QueryPhase.java:272). Chosen by score_terms_node when
    every lane is default-constant BM25 and the segment staged kernel
    arrays; the query carries per-(tile, lane) covering-block windows
    computed host-side from per-block doc ranges.

    Mesh form: ``mesh_deferred`` builds the node with the per-shard lane
    set but NO tables; the mesh executor's ``harmonize_kernel_nodes``
    calls ``finalize_mesh`` with the geometry shared by every shard so the
    stacked tables have identical shapes and ONE trace serves all devices
    (the reference runs the same BulkScorer loop on every shard — this is
    that property on a TPU mesh)."""

    def __init__(self, row_lo, row_hi, kweights, min_match, *, cb: int,
                 sub: int, interpret: bool, live_key: str = "k_live_t",
                 tiles_per_step: int = 1, codec: str = "raw"):
        self.row_lo = row_lo  # [n_tiles, t_pad] i32
        self.row_hi = row_hi
        self.kweights = kweights  # [1, t_pad] f32
        self.min_match = np.float32(min_match)
        self.cb = cb
        self.sub = sub
        self.t_pad = int(row_lo.shape[1])
        self.n_tiles = int(row_lo.shape[0])
        self.interpret = interpret
        self.with_counts = min_match > 1
        # live-mask layout key in the segment device dict: the geometry
        # ladder stages per-sub variants for dense-term queries
        self.live_key = live_key
        self.tiles_per_step = tiles_per_step
        # postings codec the segment staged (docs/PRUNING.md): "packed"
        # reads the bit-packed word array and decodes in-kernel
        self.codec = codec
        self._mesh_lanes = None
        self._mesh_base = None

    @classmethod
    def mesh_deferred(cls, lanes, base: int, min_match, *,
                      interpret: bool,
                      codec: str = "raw") -> "PallasScoreTermsNode":
        """Node for the MESH plane with table building deferred: lanes are
        shard-local, but table geometry (tile count, t_pad, cb, sub) must
        be uniform across the whole stacked segment set and is only known
        once every shard's plan exists. ``base`` is the shard segment's
        first row in the kernel session's flat per-block doc ranges
        (tile-size independent)."""
        self = cls.__new__(cls)
        self.row_lo = self.row_hi = self.kweights = None
        self.min_match = np.float32(min_match)
        self.cb = self.sub = self.t_pad = self.n_tiles = None
        self.interpret = interpret
        self.with_counts = min_match > 1
        self.live_key = "k_live_t"
        self.tiles_per_step = 1
        self.codec = codec
        self._mesh_lanes = list(lanes)
        self._mesh_base = base
        return self

    def finalize_mesh(self, row_lo, row_hi, kweights, *, cb: int, sub: int,
                      live_key: str, tiles_per_step: int = 1) -> None:
        self.row_lo = row_lo
        self.row_hi = row_hi
        self.kweights = kweights
        self.cb = cb
        self.sub = sub
        self.t_pad = int(row_lo.shape[1])
        self.n_tiles = int(row_lo.shape[0])
        self.live_key = live_key
        self.tiles_per_step = tiles_per_step

    def key(self):
        return (f"pterms[{self.n_tiles},{self.t_pad},{self.cb},{self.sub},"
                f"{self.with_counts},{self.interpret},{self.live_key},"
                f"{self.tiles_per_step},{self.codec}]")

    def trace_statics(self):
        return (self.cb, self.sub, self.t_pad, self.with_counts,
                self.interpret, self.live_key, self.tiles_per_step,
                self.codec)

    def arrays(self):
        if self.row_lo is None:
            # a mesh_deferred node escaped harmonization — refuse to trace
            # a half-built plan (callers treat this as "no plan form")
            raise NotImplementedError(
                "mesh pallas node used before finalize_mesh")
        return [self.row_lo, self.row_hi, self.kweights, self.min_match]

    def pad_kinds(self):
        # "k": kernel tables — stackable onto a mesh template only when
        # every shard's tables share one shape (harmonize_kernel_nodes
        # guarantees it for mesh-built plans; host-built per-segment
        # geometries differ and fail the stack, keeping the host path)
        return ["k", "k", "k", "s"]

    def emit(self, ctx):
        from elasticsearch_tpu.ops import pallas_scoring as psc

        row_lo, row_hi, kweights, min_match = ctx.take(4)
        if self.codec == "packed":
            corpus = (ctx.seg["k_packed"], None)
        else:
            corpus = (ctx.seg["k_docs"], ctx.seg["k_frac"])
        outs = psc.score_tiles(
            corpus[0], corpus[1], ctx.seg[self.live_key],
            row_lo, row_hi, kweights,
            t_pad=self.t_pad, cb=self.cb, sub=self.sub,
            dense=True, with_counts=self.with_counts,
            interpret=self.interpret,
            tiles_per_step=self.tiles_per_step, codec=self.codec,
            row_base=ctx.row_base)
        nd = ctx.nd1 - 1
        scores = psc.dense_to_flat(outs[0], self.sub)[:nd]
        scores = jnp.concatenate([scores, jnp.zeros(1, jnp.float32)])
        if self.with_counts:
            counts = psc.dense_to_flat(outs[1], self.sub)[:nd]
            counts = jnp.concatenate([counts, jnp.zeros(1, jnp.float32)])
            return scores, counts >= min_match
        return scores, scores > 0.0


class KnnScoreNode(PlanNode):
    """Dense-vector similarity scoring against a staged embedding matrix
    (the host rung of the kNN plane ladder; the mesh_pallas rung runs
    the MXU kernel in ops/pallas_knn.py with identical arithmetic).

    score = hit_score(dot(x, q) * scale) with q pre-normalized for
    cosine and scale the staged per-doc inverse norm (none for the inner
    products): the reference's (1 + sim) / 2, or max_inner_product's
    piecewise score (ops/pallas_knn.hit_score). Every live doc carrying
    the vector field "matches"; ranking is the whole query.

    The embedding matrix is segment-local device state (ctx.seg keys
    staged by Segment.ensure_vector_staged), NOT a plan array — so the
    node cannot stack onto a mesh template (pad kind "x"): the generic
    mesh path cleanly mismatches and the dedicated kNN mesh program
    (IndexMeshSearch.query_knn) owns the distributed form."""

    def __init__(self, field: str, qvec, metric: str, boost: float,
                 emb_key: str, norm_key: str, exists_key: str):
        self.field = field
        self.qvec = qvec  # [1, d_pad] f32 (normalize_query row)
        self.metric = metric
        self.boost = np.float32(boost)
        self.emb_key = emb_key
        self.norm_key = norm_key
        self.exists_key = exists_key

    def key(self):
        return (f"knn[{self.field},{self.metric},{self.qvec.shape[1]},"
                f"{self.emb_key}]")

    def trace_statics(self):
        return (self.field, self.metric, self.emb_key)

    def arrays(self):
        return [self.qvec, self.boost]

    def pad_kinds(self):
        # "x": segment-keyed device state can't stack onto a mesh
        # template — the executor raises PlanStructureMismatch and the
        # ladder serves this query from the host (or the kNN program)
        return ["x", "s"]

    def emit(self, ctx):
        qvec, boost = ctx.take(2)
        emb = ctx.seg[self.emb_key].astype(jnp.float32)  # [nd_pad, d_pad]
        # same contraction shape + HIGHEST precision as the MXU kernel so
        # host and mesh rungs score identical bits (dryrun phase 5 gate)
        s = jax.lax.dot_general(
            emb, qvec, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)[:, 0]
        if self.metric == "cosine":
            s = s * ctx.seg[self.norm_key]
        from elasticsearch_tpu.ops.pallas_knn import hit_score

        s = hit_score(s, self.metric, jnp)
        scores = jnp.concatenate([s, jnp.zeros(1, jnp.float32)])
        matched = ctx.seg[self.exists_key]
        return jnp.where(matched, scores * boost,
                         jnp.float32(0.0)).astype(jnp.float32), matched


class PhraseScoreNode(PlanNode):
    """Pre-verified phrase matches (host position intersection) scored by
    the field's similarity over the phrase frequency — MatchPhraseQuery
    semantics. docs/freqs are [K]-padded (doc = nd1-1 sentinel, freq = 0)."""

    def __init__(self, docs, freqs, weight, norm_row, avgdl,
                 k1: float = K1, b: float = B, kind: str = "bm25",
                 p1=None, p2=None, p3=0.0):
        self.docs = docs
        self.freqs = freqs
        self.weight = np.float32(weight)
        self.norm_row = int(norm_row)
        self.avgdl = np.float32(avgdl)
        self.kind = kind
        # default params reproduce classic BM25(k1, b)
        self.p1 = np.float32(k1 if p1 is None else p1)
        self.p2 = np.float32(b if p2 is None else p2)
        self.p3 = np.float32(p3)

    def key(self):
        return f"phrase[{len(self.docs)},{self.norm_row},{self.kind}]"

    def trace_statics(self):
        return (self.norm_row, self.kind)

    def arrays(self):
        return [self.docs, self.freqs, self.weight, self.avgdl,
                self.p1, self.p2, self.p3]

    def pad_kinds(self):
        return ["d", "z", "s", "s", "s", "s", "s"]

    def emit(self, ctx):
        from elasticsearch_tpu.index.similarity import emit_contrib

        docs, freqs, weight, avgdl, p1, p2, p3 = ctx.take(7)
        doc_len = ctx.seg["norms"][self.norm_row][docs]
        matched_v = freqs > 0
        contrib = jnp.where(
            matched_v,
            emit_contrib(self.kind, freqs, doc_len, weight, avgdl, p1, p2, p3),
            0.0,
        )
        scores = ctx.zeros_f().at[docs].add(contrib)
        matched = ctx.zeros_b().at[docs].max(matched_v)
        return scores, matched


class MatchAllNode(PlanNode):
    def __init__(self, boost: float = 1.0):
        self.boost = np.float32(boost)

    def key(self):
        return "all"

    def arrays(self):
        return [self.boost]

    def pad_kinds(self):
        return ["s"]

    def emit(self, ctx):
        (boost,) = ctx.take(1)
        matched = ctx.seg["live1"]
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


class MatchNoneNode(PlanNode):
    def key(self):
        return "none"

    def emit(self, ctx):
        return ctx.zeros_f(), ctx.zeros_b()


class NumericRangeNode(PlanNode):
    def __init__(self, flat_docs, flat_values, lo: float, hi: float):
        self.flat_docs = flat_docs
        self.flat_values = flat_values
        self.lo = np.float64(lo)
        self.hi = np.float64(hi)

    def key(self):
        return f"nrange[{len(self.flat_docs)}]"

    def arrays(self):
        return [self.flat_docs, self.flat_values, self.lo, self.hi]

    def pad_kinds(self):
        return ["d", "n", "s", "s"]

    def emit(self, ctx):
        flat_docs, flat_values, lo, hi = ctx.take(4)
        cond = (flat_values >= lo) & (flat_values <= hi)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(cond)


# A float64 as an int64 of the same order (Lucene's
# NumericUtils.doubleToSortableLong): what a numeric filter compares on
# the device. A TPU has no float64: XLA emulates it in less than its 53
# bits, so ``np.nextafter(v, -inf)`` rounds back to ``v`` there and an
# exclusive bound on a document's own value let the document in
# (measured, PERF.md 6, PR 33); 64-bit INTEGERS it emulates exactly.
SORTABLE_MISSING = np.int64(np.iinfo(np.int64).max)  # above +inf's


def sortable_int64(values) -> np.ndarray:
    """``values`` (float64) as int64 in the same order; -0.0 as 0.0."""
    bits = (np.asarray(values, np.float64) + 0.0).view(np.int64)
    return bits ^ ((bits >> 63) & np.int64(0x7FFFFFFFFFFFFFFF))


class _StagedNumericNode(PlanNode):
    """A filter on a single-valued numeric field that the mesh executor
    has staged once, dense over documents, as sortable int64
    (``MeshPlanExecutor.ensure_numeric_column``; a document with no
    value holds ``SORTABLE_MISSING``, above every bound). What the query
    asks for is the only plan array: no column travels with it, and the
    match is one elementwise comparison, no scatter."""

    def __init__(self, column: str):
        self.column = column

    def pad_kinds(self):
        return ["s"] * len(self.arrays())

    def trace_statics(self):
        return (self.column,)

    def seg_columns(self):
        return [self.column]


class StagedNumericRangeNode(_StagedNumericNode):
    """``lo <= value <= hi``."""

    def __init__(self, column: str, lo: float, hi: float):
        super().__init__(column)
        self.lo = sortable_int64(lo)
        self.hi = sortable_int64(hi)

    def key(self):
        return f"snrange[{self.column}]"

    def arrays(self):
        return [self.lo, self.hi]

    def emit(self, ctx):
        lo, hi = ctx.take(2)
        v = ctx.seg[self.column]
        return ctx.zeros_f(), (v >= lo) & (v <= hi)


class StagedNumericTermsNode(_StagedNumericNode):
    """``value in values``: [K] float64 padded by repeating an entry;
    never NaN, so never ``SORTABLE_MISSING``."""

    def __init__(self, column: str, values):
        super().__init__(column)
        self.values = sortable_int64(values)

    def key(self):
        return f"snterms[{self.column},{len(self.values)}]"

    def arrays(self):
        return [self.values]

    def emit(self, ctx):
        (values,) = ctx.take(1)
        v = ctx.seg[self.column]
        return ctx.zeros_f(), (v[:, None] == values[None, :]).any(axis=1)


class NumericTermsNode(PlanNode):
    def __init__(self, flat_docs, flat_values, values):
        self.flat_docs = flat_docs
        self.flat_values = flat_values
        self.values = values  # [K] f64 padded with nan

    def key(self):
        return f"nterms[{len(self.flat_docs)},{len(self.values)}]"

    def arrays(self):
        return [self.flat_docs, self.flat_values, self.values]

    def pad_kinds(self):
        return ["d", "n", "n"]

    def emit(self, ctx):
        flat_docs, flat_values, values = ctx.take(3)
        cond = (flat_values[:, None] == values[None, :]).any(axis=1)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(cond)


class OrdTermsNode(PlanNode):
    def __init__(self, flat_docs, flat_ords, ords):
        self.flat_docs = flat_docs
        self.flat_ords = flat_ords
        self.ords = ords  # [K] int32 padded with -1

    def key(self):
        return f"oterms[{len(self.flat_docs)},{len(self.ords)}]"

    def arrays(self):
        return [self.flat_docs, self.flat_ords, self.ords]

    def pad_kinds(self):
        return ["d", "m1", "m1"]

    def emit(self, ctx):
        flat_docs, flat_ords, ords = ctx.take(3)
        cond = (flat_ords[:, None] == ords[None, :]).any(axis=1)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(cond)


class OrdRangeNode(PlanNode):
    def __init__(self, flat_docs, flat_ords, lo_ord: int, hi_ord: int):
        self.flat_docs = flat_docs
        self.flat_ords = flat_ords
        self.lo_ord = np.int32(lo_ord)
        self.hi_ord = np.int32(hi_ord)

    def key(self):
        return f"orange[{len(self.flat_docs)}]"

    def arrays(self):
        return [self.flat_docs, self.flat_ords, self.lo_ord, self.hi_ord]

    def pad_kinds(self):
        return ["d", "m1", "s", "s"]

    def emit(self, ctx):
        flat_docs, flat_ords, lo, hi = ctx.take(4)
        cond = (flat_ords >= lo) & (flat_ords < hi)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(cond)


class RangePairNode(PlanNode):
    """Query against a range *field* (index/mapper/RangeFieldMapper.java
    relation semantics): doc values are (lo, hi) pairs in aligned CSR
    columns; the relation picks the predicate vs the query interval."""

    def __init__(self, flat_docs, lo_vals, hi_vals, q_lo: float, q_hi: float,
                 relation: str = "intersects"):
        self.flat_docs = flat_docs
        self.lo_vals = lo_vals
        self.hi_vals = hi_vals
        self.q_lo = np.float64(q_lo)
        self.q_hi = np.float64(q_hi)
        self.relation = relation

    def key(self):
        return f"rpair[{len(self.flat_docs)},{self.relation}]"

    def trace_statics(self):
        return (self.relation,)

    def arrays(self):
        return [self.flat_docs, self.lo_vals, self.hi_vals, self.q_lo, self.q_hi]

    def pad_kinds(self):
        return ["d", "n", "n", "s", "s"]

    def emit(self, ctx):
        flat_docs, lo_vals, hi_vals, q_lo, q_hi = ctx.take(5)
        if self.relation == "within":
            cond = (lo_vals >= q_lo) & (hi_vals <= q_hi)
        elif self.relation == "contains":
            cond = (lo_vals <= q_lo) & (hi_vals >= q_hi)
        else:  # intersects (default)
            cond = (lo_vals <= q_hi) & (hi_vals >= q_lo)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(cond)


class DenseMaskNode(PlanNode):
    """A precomputed [nd1] bool mask (exists query, ids query)."""

    def __init__(self, mask, label: str = "mask"):
        self.mask = mask
        self.label = label

    def key(self):
        return f"dense[{len(self.mask)}]"

    def arrays(self):
        return [self.mask]

    def pad_kinds(self):
        return ["dense"]

    def emit(self, ctx):
        (mask,) = ctx.take(1)
        return ctx.zeros_f(), mask


class DenseScoreNode(PlanNode):
    """Precomputed dense [nd1] scores + match mask (join queries: scores
    aggregated host-side from the other side of the relation)."""

    def __init__(self, scores, mask, label: str = "join"):
        self.scores = scores
        self.mask = mask
        self.label = label

    def key(self):
        return f"densescore[{len(self.mask)}]"

    def arrays(self):
        return [self.scores, self.mask]

    def pad_kinds(self):
        return ["dense", "dense"]

    def emit(self, ctx):
        scores, mask = ctx.take(2)
        return jnp.where(mask, scores, 0.0).astype(jnp.float32), mask


class GeoDistanceNode(PlanNode):
    def __init__(self, flat_docs, lat, lon, center_lat, center_lon, radius_m):
        self.flat_docs = flat_docs
        self.lat = lat
        self.lon = lon
        self.center_lat = np.float32(center_lat)
        self.center_lon = np.float32(center_lon)
        self.radius_m = np.float32(radius_m)

    def key(self):
        return f"geodist[{len(self.flat_docs)}]"

    def arrays(self):
        return [self.flat_docs, self.lat, self.lon, self.center_lat,
                self.center_lon, self.radius_m]

    def pad_kinds(self):
        return ["d", "z", "z", "s", "s", "s"]

    def emit(self, ctx):
        flat_docs, lat, lon, clat, clon, radius = ctx.take(6)
        d = mask_ops.haversine_distance_m(lat, lon, clat, clon)
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(d <= radius)


class GeoBoxNode(PlanNode):
    def __init__(self, flat_docs, lat, lon, top, left, bottom, right):
        self.flat_docs = flat_docs
        self.lat = lat
        self.lon = lon
        self.box = np.asarray([top, left, bottom, right], dtype=np.float32)

    def key(self):
        return f"geobox[{len(self.flat_docs)}]"

    def arrays(self):
        return [self.flat_docs, self.lat, self.lon, self.box]

    def pad_kinds(self):
        return ["d", "z", "z", "z"]

    def emit(self, ctx):
        flat_docs, lat, lon, box = ctx.take(4)
        top, left, bottom, right = box[0], box[1], box[2], box[3]
        in_lat = (lat <= top) & (lat >= bottom)
        crosses = left > right
        in_lon = jnp.where(crosses, (lon >= left) | (lon <= right),
                           (lon >= left) & (lon <= right))
        return ctx.zeros_f(), ctx.zeros_b().at[flat_docs].max(in_lat & in_lon)


# ---------------------------------------------------------------------------
# Combiners
# ---------------------------------------------------------------------------


class BoolNode(PlanNode):
    """BooleanQuery semantics (org.apache.lucene.search.BooleanQuery as used
    by index/query/BoolQueryBuilder): score = sum of matching scoring
    clauses; filters gate without scoring; minimum_should_match applies to
    should when must/filter present (default 0) else 1."""

    def __init__(self, must: List[PlanNode], filter_: List[PlanNode],
                 should: List[PlanNode], must_not: List[PlanNode],
                 min_should_match: int, boost: float = 1.0):
        self.must = must
        self.filter = filter_
        self.should = should
        self.must_not = must_not
        self.msm = np.float32(min_should_match)
        self.boost = np.float32(boost)

    def key(self):
        return (f"bool[{len(self.must)},{len(self.filter)},{len(self.should)},"
                f"{len(self.must_not)}](" +
                ",".join(c.key() for c in self.children()) + ")")

    def children(self):
        return self.must + self.filter + self.should + self.must_not

    def arrays(self):
        return [self.msm, self.boost]

    def pad_kinds(self):
        return ["s", "s"]

    def emit(self, ctx):
        msm, boost = ctx.take(2)
        matched = ctx.seg["live1"]
        scores = ctx.zeros_f()
        for c in self.must:
            s, m = c.emit(ctx)
            scores = scores + s
            matched = matched & m
        for c in self.filter:
            _, m = c.emit(ctx)
            matched = matched & m
        if self.should:
            s_count = ctx.zeros_f()
            for c in self.should:
                s, m = c.emit(ctx)
                scores = scores + jnp.where(m, s, 0.0)
                s_count = s_count + m.astype(jnp.float32)
            matched = matched & (s_count >= msm)
        for c in self.must_not:
            _, m = c.emit(ctx)
            matched = matched & ~m
        return jnp.where(matched, scores * boost, 0.0).astype(jnp.float32), matched


class ConstantScoreNode(PlanNode):
    def __init__(self, child: PlanNode, boost: float = 1.0):
        self.child = child
        self.boost = np.float32(boost)

    def key(self):
        return f"const({self.child.key()})"

    def children(self):
        return [self.child]

    def arrays(self):
        return [self.boost]

    def pad_kinds(self):
        return ["s"]

    def emit(self, ctx):
        (boost,) = ctx.take(1)
        _, m = self.child.emit(ctx)
        return jnp.where(m, boost, 0.0).astype(jnp.float32), m


class BoostNode(PlanNode):
    def __init__(self, child: PlanNode, boost: float):
        self.child = child
        self.boost = np.float32(boost)

    def key(self):
        return f"boost({self.child.key()})"

    def children(self):
        return [self.child]

    def arrays(self):
        return [self.boost]

    def pad_kinds(self):
        return ["s"]

    def emit(self, ctx):
        (boost,) = ctx.take(1)
        s, m = self.child.emit(ctx)
        return s * boost, m


class DisMaxNode(PlanNode):
    def __init__(self, nodes: List[PlanNode], tie_breaker: float = 0.0):
        self.nodes = nodes
        self.tie_breaker = np.float32(tie_breaker)

    def key(self):
        return "dismax(" + ",".join(c.key() for c in self.nodes) + ")"

    def children(self):
        return self.nodes

    def arrays(self):
        return [self.tie_breaker]

    def pad_kinds(self):
        return ["s"]

    def emit(self, ctx):
        (tie,) = ctx.take(1)
        best = None
        total = ctx.zeros_f()
        matched = ctx.zeros_b()
        for c in self.nodes:
            s, m = c.emit(ctx)
            s = jnp.where(m, s, 0.0)
            best = s if best is None else jnp.maximum(best, s)
            total = total + s
            matched = matched | m
        scores = best + tie * (total - best)
        return scores, matched


class FunctionScoreNode(PlanNode):
    """function_score (index/query/functionscore/): child score combined
    with functions. Round-1 functions: weight, field_value_factor,
    random_score (deterministic hash) — combined multiplicatively; boost_mode
    multiply/replace/sum."""

    MODES = ("multiply", "replace", "sum", "avg", "max", "min")

    def __init__(self, child: PlanNode, factor_columns: List, weight: float,
                 boost_mode: str = "multiply"):
        self.child = child
        self.factor_columns = factor_columns  # list of dense [nd1] f32 factors
        self.weight = np.float32(weight)
        self.boost_mode = boost_mode

    def key(self):
        return f"fscore[{len(self.factor_columns)},{self.boost_mode}]({self.child.key()})"

    def trace_statics(self):
        return (self.boost_mode,)

    def children(self):
        return [self.child]

    def arrays(self):
        return [self.weight] + list(self.factor_columns)

    def pad_kinds(self):
        return ["s"] + ["dense"] * len(self.factor_columns)

    def emit(self, ctx):
        taken = ctx.take(1 + len(self.factor_columns))
        weight, cols = taken[0], taken[1:]
        s, m = self.child.emit(ctx)
        fn = jnp.full_like(s, 1.0) * weight
        for col in cols:
            fn = fn * col
        if self.boost_mode == "multiply":
            out = s * fn
        elif self.boost_mode == "replace":
            out = fn
        elif self.boost_mode == "sum":
            out = s + fn
        elif self.boost_mode == "avg":
            out = (s + fn) / 2.0
        elif self.boost_mode == "max":
            out = jnp.maximum(s, fn)
        else:
            out = jnp.minimum(s, fn)
        return jnp.where(m, out, 0.0).astype(jnp.float32), m


# ---------------------------------------------------------------------------
# Compile + run
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _compiled_for(structure_key: str, plan_holder) -> "jax.stages.Wrapped":
    plan = plan_holder.plan

    @jax.jit
    def run(seg_arrays, plan_arrays):
        ctx = EmitCtx(seg_arrays, plan_arrays)
        scores, matched = plan.emit(ctx)
        matched = matched & ctx.seg["live1"]
        return scores, matched

    return run


class _PlanHolder:
    """Hashable wrapper so lru_cache keys on the structure string only; the
    held plan is the FIRST plan seen with that structure (same trace)."""

    __slots__ = ("plan", "_key")

    def __init__(self, plan: PlanNode):
        self.plan = plan
        self._key = plan.key()

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _PlanHolder) and self._key == other._key


def execute(seg_device: dict, plan: PlanNode):
    """Run a plan against one segment's device arrays.

    seg_device must contain block_docs, block_tfs, norms, live1.
    Returns (scores f32[nd1], matched bool[nd1]) on device.
    """
    shape_sig = f"@nd{seg_device['norms'].shape}"
    run = _compiled_for(plan.key() + shape_sig, _PlanHolder(plan))
    return run(seg_device, plan.flat_arrays())
