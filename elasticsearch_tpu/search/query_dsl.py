"""The query DSL: JSON -> QueryBuilder tree -> per-segment PlanNode.

Role model: the 60+ builders under core/.../index/query/ (parsed via
``AbstractQueryBuilder``/``QueryShardContext``, two-phase rewrite). Each
builder here mirrors one reference builder's JSON shape and semantics;
``to_plan(shard_ctx, segment)`` replaces ``QueryBuilder.toQuery`` — it
resolves terms/ordinals against the segment and produces plan nodes
(search/plan.py) instead of Lucene Query objects.

Multi-term expansion (prefix/wildcard/fuzzy/regexp) happens host-side
against the segment's sorted term dictionary, exactly where Lucene expands
against its terms dict.
"""

from __future__ import annotations

import fnmatch
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.common.errors import (
    IllegalArgumentException,
    MapperParsingException,
    ParsingException,
    QueryShardException,
)
from elasticsearch_tpu.mapper.field_types import (
    BooleanFieldType,
    DateFieldType,
    GeoPointFieldType,
    IpFieldType,
    KeywordFieldType,
    NumberFieldType,
    TextFieldType,
)
from elasticsearch_tpu.ops.scoring import B, K1, bm25_idf
from elasticsearch_tpu.search import plan as P

# default max_expansions for multi-term queries (MultiTermQuery rewrites)
MAX_EXPANSIONS = 1024

# SearchPlugin.getQueries extension point: {query_name: parser(qbody)}
CUSTOM_QUERY_PARSERS: Dict[str, "object"] = {}

# single source of the default BM25 constants for ctx-less callers
from elasticsearch_tpu.index.similarity import BM25Similarity  # noqa: E402
from elasticsearch_tpu.ops.scoring import B as _BM25_B, K1 as _BM25_K1  # noqa: E402

_DEFAULT_BM25 = BM25Similarity(k1=_BM25_K1, b=_BM25_B)


class ShardQueryContext:
    """Per-shard query context (≙ QueryShardContext): mapper + analyzers +
    (optionally) the engine, for queries that join across segments of the
    shard (has_child/has_parent — the reference resolves these through
    shard-wide global ordinals)."""

    def __init__(self, mapper_service, engine=None):
        self.mapper_service = mapper_service
        self.analyzers = mapper_service.analyzers
        self.engine = engine
        # what score_terms_node planned through this context: posting
        # block rows expanded for scatter nodes, lanes of kernel nodes
        self.blocks_expanded = 0
        self.kernel_lanes = 0

    def field_type(self, name: str):
        return self.mapper_service.field_type(name)

    def similarity(self, field: str):
        """The similarity bound to a field (mapping ``similarity`` param,
        else the index default — SimilarityService.java semantics)."""
        svc = getattr(self.mapper_service, "similarity_service", None)
        if svc is None:
            return None
        ft = self.mapper_service.field_type(field)
        return svc.get(getattr(ft, "similarity_name", None))

    def all_segments(self, fallback_segment) -> List:
        """Every searchable segment of the shard (falls back to the one
        segment in contexts without an engine, e.g. percolation)."""
        if self.engine is not None:
            return list(self.engine.searchable_segments())
        return [fallback_segment]

    def default_fields(self) -> List[str]:
        # all text fields (the reference's `_all` is deprecated in 6.0; we
        # approximate all_fields mode: query every text field)
        return [
            f for f, ft in self.mapper_service.mapper.fields.items()
            if isinstance(ft, TextFieldType)
        ]


def _pow2_len(n, min_len=8):
    """The padded length of an n-row array: a power of two, at least
    ``min_len``."""
    p = max(min_len, 1)
    while p < n:
        p *= 2
    return p


def _pad_pow2(lst, pad_value, min_len=8, dtype=None):
    arr = list(lst) + [pad_value] * (_pow2_len(len(lst), min_len) - len(lst))
    return np.asarray(arr, dtype=dtype)


def term_blocks_arrays(segment, weighted_terms, ctx=None):
    """weighted_terms: list of (field, token, boost). Resolves each term
    present in the segment to one lane: ``lanes_meta`` (block_start,
    block_count, weight, kernel_eligible), all a kernel node reads, and
    ``lane_params`` (kind, norm row, avgdl, weight, p1, p2, p3), which
    ``scatter_block_arrays`` repeats over the lane's posting blocks where
    a ScoreTermsNode is built. When ``ctx`` is given, each field's mapped
    similarity folds its per-term constants into the lane params
    (index/similarity.py); without it, classic BM25 defaults apply."""
    lanes_meta = []
    lane_params = []
    for field, token, boost in weighted_terms:
        tid = segment.term_id(field, token)
        if tid < 0:
            continue
        st = segment.field_stats.get(field, {})
        doc_count = st.get("doc_count", 0)
        row = segment.field_norm_idx.get(field, 0)
        avgdl = segment.field_avgdl(field)
        sim = (ctx.similarity(field) if ctx is not None else None) or _DEFAULT_BM25
        kind, w, p1, p2, p3 = sim.lane_params({
            "df": int(segment.term_doc_freq[tid]),
            # total term freq costs an O(postings) host pass — only the
            # DFR/IB/LM family reads it
            "ttf": segment.term_ttf(tid) if sim.needs_ttf else 0,
            "doc_count": doc_count,
            "sum_ttf": st.get("sum_ttf", 0),
            "avgdl": avgdl,
            "boost": boost,
        })
        # the pallas tile kernel precomputes per-posting norm factors
        # with default-constant BM25 and the segment's local stats; any
        # other similarity/params must take the scatter path. (No dfs-
        # adjusted avgdl reaches this builder today; if one ever does,
        # its lane must be marked ineligible here.)
        lanes_meta.append((int(segment.term_block_start[tid]),
                           int(segment.term_block_count[tid]), float(w),
                           kind == "bm25" and p1 == K1 and p2 == B))
        lane_params.append((kind, row, avgdl, w, p1, p2, p3))
    return {
        "n_present": len(lanes_meta),
        "lanes_meta": lanes_meta,
        "lane_params": lane_params,
    }


def _padded_blocks(arrs) -> int:
    """The scatter node's lane count: every lane's posting blocks, padded
    to a power of two (at least 8)."""
    return _pow2_len(sum(c for _, c, _, _ in arrs["lanes_meta"]))


def scatter_block_arrays(arrs):
    """The gather arrays of ScoreTermsNode: one row per posting block of
    every lane, carrying its lane's constants, then padding rows that
    never match. ``kinds`` lists the lanes' similarity kinds in first-seen
    order and ``q_kinds`` indexes it."""
    starts = np.array([s for s, _, _, _ in arrs["lanes_meta"]], np.int64)
    counts = np.array([c for _, c, _, _ in arrs["lanes_meta"]], np.int64)
    lane_kinds, rows, avgdls, weights, p1s, p2s, p3s = (
        list(zip(*arrs["lane_params"])) or [()] * 7)
    kinds = list(dict.fromkeys(lane_kinds))
    total = int(counts.sum())
    n = _padded_blocks(arrs)

    def column(values, pad, dtype):
        out = np.full(n, pad, dtype)
        out[:total] = np.repeat(np.asarray(values, dtype), counts)
        return out

    # lane i's rows are start_i, start_i + 1, ...: a row's position in
    # the expansion, less its lane's first position, plus the lane's start
    first = np.cumsum(counts) - counts
    q_blocks = np.zeros(n, np.int32)
    q_blocks[:total] = np.arange(total) + np.repeat(starts - first, counts)
    return {
        "q_blocks": q_blocks,
        "q_weights": column(weights, 0.0, np.float32),
        "q_norm_rows": column(rows, 0, np.int32),
        "q_avgdl": column(avgdls, 1.0, np.float32),
        "q_valid": column([True] * len(rows), False, bool),
        "q_p1": column(p1s, 1.0, np.float32),
        "q_p2": column(p2s, 1.0, np.float32),
        "q_p3": column(p3s, 0.0, np.float32),
        "q_kinds": column([kinds.index(k) for k in lane_kinds], 0, np.int32),
        "kinds": tuple(kinds) if kinds else ("bm25",),
    }


def score_terms_node(segment, weighted_terms, min_match=1, ctx=None) -> P.PlanNode:
    arrs = term_blocks_arrays(segment, weighted_terms, ctx=ctx)
    if arrs["n_present"] == 0 or min_match > arrs["n_present"]:
        if not getattr(ctx, "for_mesh", False):
            return P.MatchNoneNode()
        # mesh plans must keep the SAME tree skeleton on every shard: a
        # term that happens to miss one shard's dictionary would turn
        # that shard's node into MatchNone and force the whole query off
        # the mesh (PlanStructureMismatch). An all-invalid-lane scorer
        # emits zero matches through the identical trace instead.
        if min_match > max(arrs["n_present"], 1):
            # unsatisfiable even with every lane valid: emit can never
            # match, but the skeleton must still line up — pin the
            # threshold above the padded lane count
            min_match = _padded_blocks(arrs) + 1
    node = None
    if not getattr(ctx, "for_mesh", False):
        node = _pallas_score_terms_node(segment, arrs, min_match)
    elif getattr(ctx, "mesh_kernel", None) is not None:
        # mesh plane with the tile kernel staged: build the stackable
        # (deferred-geometry) kernel node; the executor harmonizes table
        # shapes across shards before stacking. Ineligible lane sets fall
        # through to the scatter node — a cross-shard pallas/scatter mix
        # then fails structure checks and the caller retries all-scatter.
        node = _mesh_pallas_score_terms_node(segment, arrs, min_match,
                                             ctx.mesh_kernel)
    if node is not None:
        if ctx is not None:
            ctx.kernel_lanes += arrs["n_present"]
        return node
    blk = scatter_block_arrays(arrs)
    if ctx is not None:
        ctx.blocks_expanded += int(blk["q_valid"].sum())
    return P.ScoreTermsNode(
        blk["q_blocks"], blk["q_weights"], blk["q_norm_rows"],
        blk["q_avgdl"], blk["q_valid"], min_match,
        q_p1=blk["q_p1"], q_p2=blk["q_p2"], q_p3=blk["q_p3"],
        q_kinds=blk["q_kinds"], kinds=blk["kinds"],
    )


def _pallas_score_terms_node(segment, arrs, min_match):
    """Route eligible BM25 disjunctions through the tile-scoring kernel:
    all lanes default-constant BM25 (positive weights for the score>0
    match rule unless counting), and the segment staged kernel arrays."""
    from elasticsearch_tpu.ops.aggs import _pallas_mode

    mode = _pallas_mode()
    if not mode:
        return None
    lanes = arrs["lanes_meta"]
    if not lanes or not all(ok for _, _, _, ok in lanes):
        return None
    # positive weights always: score>0 is the match rule for min_match<=1,
    # and zero-weight lanes would be dropped from the kernel's match
    # COUNTS too (build_tile_tables skips them) — the scatter path counts
    # them, so they must take it
    if not all(w > 0 for _, _, w, _ in lanes):
        return None
    segment.device_arrays()  # ensure kernel staging ran
    geom = getattr(segment, "kernel_geom", None)
    if geom is None:
        return None
    from elasticsearch_tpu.ops import pallas_scoring as psc

    qlanes = [psc.QueryLane(s, c, w) for s, c, w, _ in lanes]
    # the geometry ladder: a dense term's per-tile covering window can
    # exceed the kernel bound at the staged tile — smaller tiles serve it
    try:
        row_lo, row_hi, kweights, cb, g = psc.build_tile_tables_sets(
            [qlanes], segment.kernel_bmin, segment.kernel_bmax, geom,
            ladder=True)
    except ValueError:
        return None  # malformed ranges; scatter path handles it
    row_lo, row_hi, kweights = row_lo[0], row_hi[0], kweights[0]
    live_key = ("k_live_t" if g.tile_sub == geom.tile_sub
                else segment.kernel_live_t_for(g.tile_sub))
    node = P.PallasScoreTermsNode(
        row_lo, row_hi, kweights, min_match,
        cb=cb, sub=g.tile_sub, interpret=(mode == "interpret"),
        live_key=live_key, tiles_per_step=psc.TILES_PER_STEP,
        codec=getattr(segment, "kernel_codec", "raw"))
    # the cross-query micro-batcher (search/batching.py) unions lane sets
    # across concurrent queries and re-derives shared tables, so the node
    # keeps its lane list alongside the already-built single-query tables
    node._host_lanes = qlanes
    return node


def _mesh_pallas_score_terms_node(segment, arrs, min_match, session):
    """Stackable tile-kernel node for the MESH data plane. ``session`` is
    the executor's staged-kernel context (``MeshPlanExecutor.
    _kernel_session``, with ``mode``). Same lane eligibility rules as the
    host path (_pallas_score_terms_node), but an EMPTY lane set stays on
    the kernel: a term missing from one shard's dictionary must not flip
    that shard's node type (the skeleton must match across the mesh)."""
    from elasticsearch_tpu.ops import pallas_scoring as psc

    lanes = arrs["lanes_meta"]
    if not all(ok for _, _, _, ok in lanes):
        return None
    if not all(w > 0 for _, _, w, _ in lanes):
        return None  # see _pallas_score_terms_node: score>0 match rule
    base = session["bound_base"].get(id(segment))
    if base is None:
        return None  # segment not part of the staged mesh set
    qlanes = [psc.QueryLane(s, c, w) for s, c, w, _ in lanes]
    return P.PallasScoreTermsNode.mesh_deferred(
        qlanes, base, min_match,
        interpret=(session["mode"] == "interpret"),
        codec=session.get("codec", "raw"))


def _numeric_csr(segment, field):
    col = segment.numeric_columns.get(field)
    if col is None:
        return None
    docs = segment.device_column(f"num.{field}.docs", lambda: col.flat_docs)
    vals = segment.device_column(f"num.{field}.vals", lambda: col.flat_values)
    return docs, vals, col


def _staged_numeric(ctx, field):
    """The name of ``field``'s dense sortable column where this plan is
    built for a mesh executor that holds (or can stage) one: a
    single-valued numeric field (``MeshPlanExecutor.
    ensure_numeric_column``). None everywhere else: the CSR nodes, which
    carry their column with the plan, serve."""
    columns = getattr(ctx, "mesh_columns", None)
    return None if columns is None else columns.ensure_numeric_column(field)


def _numeric_terms_node(ctx, segment, field, nums):
    """``field in nums`` over a numeric doc-value column, or None where
    the segment has none."""
    staged = _staged_numeric(ctx, field)
    if staged is not None:
        return P.StagedNumericTermsNode(
            staged, _pad_pow2(nums, nums[0], min_len=1, dtype=np.float64))
    csr = _numeric_csr(segment, field)
    if csr is None:
        return None
    docs, vals, _ = csr
    return P.NumericTermsNode(
        docs, vals, _pad_pow2(nums, np.nan, min_len=1, dtype=np.float64))


def _ordinal_csr(segment, field):
    col = segment.ordinal_columns.get(field)
    if col is None:
        return None
    docs = segment.device_column(f"ord.{field}.docs", lambda: col.flat_docs)
    ords = segment.device_column(f"ord.{field}.ords", lambda: col.flat_ords)
    return docs, ords, col


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------


class QueryBuilder:
    name = "base"

    def __init__(self, boost: float = 1.0, _name: Optional[str] = None):
        self.boost = boost
        self.query_name = _name

    def to_plan(self, ctx: ShardQueryContext, segment) -> P.PlanNode:
        raise NotImplementedError

    def explain_terms(self, ctx) -> Optional[List[Tuple[str, str, float]]]:
        """(field, token, boost) lanes for the explain API's per-term BM25
        breakdown; None when this query type has no term-lane expansion
        (the explain response then stays summary-level)."""
        return None

    def _wrap_boost(self, node: P.PlanNode) -> P.PlanNode:
        if self.boost != 1.0:
            return P.BoostNode(node, self.boost)
        return node


class MatchAllQueryBuilder(QueryBuilder):
    name = "match_all"

    def to_plan(self, ctx, segment):
        return P.MatchAllNode(self.boost)


class MatchNoneQueryBuilder(QueryBuilder):
    name = "match_none"

    def to_plan(self, ctx, segment):
        return P.MatchNoneNode()


class KnnQueryBuilder(QueryBuilder):
    """Dense-vector kNN clause: score every live doc carrying the field
    by its embedding similarity to ``query_vector`` (the mapped field's
    ``similarity`` picks the metric). Mirrors the reference's knn search
    surface grown after 6.x (KnnSearchBuilder / the top-level ``knn``
    request section, which IndexService normalizes into this clause).

    Execution is exhaustive (exact, recall 1.0 — no ANN graph): the
    mesh_pallas rung scores the staged bf16 embedding matrix with the
    MXU kernel (ops/pallas_knn.py), the host rung with an identical XLA
    matmul (plan.KnnScoreNode). ``k`` sizes the result (the top-level
    knn section defaults the response size to it); ``num_candidates``
    is accepted for reference-API compatibility only — exhaustive exact
    scoring makes an ANN candidate bound moot, so it has no effect."""

    name = "knn"

    def __init__(self, field: str, query_vector, k: int = 10,
                 num_candidates: Optional[int] = None,
                 filter: Optional[list] = None, **kw):
        super().__init__(**kw)
        self.field = field
        self.query_vector = query_vector
        self.k = int(k)
        self.num_candidates = (int(num_candidates)
                               if num_candidates is not None else None)
        # pre-filter clauses (the reference's knn `filter`): restrict
        # WHICH docs may rank — under exhaustive scoring pre- and
        # post-filtering are equivalent, so they gate the matched mask
        self.filter = list(filter or [])

    def _field_type(self, ctx):
        from elasticsearch_tpu.mapper.field_types import DenseVectorFieldType

        ft = ctx.field_type(self.field)
        if ft is None:
            raise QueryShardException(
                f"failed to create query: field [{self.field}] does not "
                f"exist in the mapping")
        if not isinstance(ft, DenseVectorFieldType):
            raise QueryShardException(
                f"[knn] queries are only supported on [dense_vector] "
                f"fields; [{self.field}] is [{ft.type_name}]")
        try:
            # finiteness matters: a NaN query poisons every score and
            # drives the kernel's tie-select out of the doc range —
            # reject with the same 400 the index path gives NaN vectors
            ft.parse_vector(self.query_vector)
        except MapperParsingException:
            raise IllegalArgumentException(
                f"[knn] query_vector must be an array of {ft.dims} "
                f"finite numbers for field [{self.field}]") from None
        return ft

    def to_plan(self, ctx, segment):
        from elasticsearch_tpu.ops import pallas_knn as pkn

        ft = self._field_type(ctx)
        keys = segment.ensure_vector_staged(self.field, ft.similarity)
        if keys is None:
            # no doc of THIS segment carries the field: nothing can match
            return P.MatchNoneNode()
        emb_key, norm_key, exists_key, d_pad = keys
        qvec = pkn.normalize_query(
            np.asarray(self.query_vector, np.float32), ft.similarity,
            d_pad).reshape(1, d_pad)
        node = P.KnnScoreNode(self.field, qvec, ft.similarity, self.boost,
                              emb_key, norm_key, exists_key)
        if self.filter:
            # filtered kNN: the vector score ranks, the filter gates —
            # exact BoolQuery must+filter semantics (the mesh MXU
            # program doesn't cover filtered specs: knn_batch_spec
            # rejects them, so this plan always runs the host rung)
            node = P.BoolNode(
                must=[node],
                filter_=[f.to_plan(ctx, segment) for f in self.filter],
                should=[], must_not=[], min_should_match=0)
        return node


class MatchQueryBuilder(QueryBuilder):
    """Full-text match (index/query/MatchQueryBuilder): analyze the text
    with the field's search analyzer; OR (default) or AND over terms;
    minimum_should_match supported."""

    name = "match"

    def __init__(self, field: str, query, operator: str = "or",
                 minimum_should_match: Optional[str] = None,
                 analyzer: Optional[str] = None, **kw):
        super().__init__(**kw)
        self.field = field
        self.query = query
        self.operator = operator.lower()
        self.minimum_should_match = minimum_should_match
        # explicit search analyzer override (MatchQueryBuilder#analyzer)
        self.analyzer = analyzer

    def _analyzed_terms(self, ctx) -> List[str]:
        ft = ctx.field_type(self.field)
        if self.analyzer is not None:
            # explicit analyzer override beats the field's search analyzer
            return ctx.analyzers.get(self.analyzer).analyze(str(self.query))
        if ft is None:
            return [str(self.query)]
        if isinstance(ft, TextFieldType):
            return ft.query_terms(self.query, ctx.analyzers)
        return ft.index_terms(self.query, ctx.analyzers) or [
            ft.term_for_query(self.query, ctx.analyzers)
        ]

    def explain_terms(self, ctx):
        ft = ctx.field_type(self.field)
        if ft is None or not isinstance(ft, TextFieldType):
            return None
        return [(self.field, t, self.boost)
                for t in self._analyzed_terms(ctx)]

    def to_plan(self, ctx, segment):
        ft = ctx.field_type(self.field)
        if ft is not None and isinstance(ft, NumberFieldType):
            return TermQueryBuilder(self.field, self.query, boost=self.boost).to_plan(ctx, segment)
        if ft is not None and isinstance(ft, (DateFieldType, BooleanFieldType, IpFieldType)):
            return TermQueryBuilder(self.field, self.query, boost=self.boost).to_plan(ctx, segment)
        terms = self._analyzed_terms(ctx)
        if not terms:
            return P.MatchNoneNode()
        if self.operator == "and":
            min_match = len(terms)
        else:
            min_match = parse_min_should_match(self.minimum_should_match, len(terms)) or 1
        node = score_terms_node(
            segment, [(self.field, t, 1.0) for t in terms], min_match, ctx=ctx
        )
        return self._wrap_boost(node)


class MatchPhraseQueryBuilder(QueryBuilder):
    name = "match_phrase"

    def __init__(self, field: str, query, slop: int = 0,
                 analyzer: Optional[str] = None, **kw):
        super().__init__(**kw)
        self.field = field
        self.query = query
        self.slop = slop
        self.analyzer = analyzer

    def to_plan(self, ctx, segment):
        ft = ctx.field_type(self.field)
        if self.analyzer is not None:
            terms = ctx.analyzers.get(self.analyzer).analyze(str(self.query))
        elif isinstance(ft, TextFieldType):
            terms = ft.query_terms(self.query, ctx.analyzers)
        else:
            terms = [str(self.query)]
        if not terms:
            return P.MatchNoneNode()
        if len(terms) == 1:
            return MatchQueryBuilder(self.field, self.query, boost=self.boost).to_plan(ctx, segment)
        # host-side position intersection (SURVEY §7: strings/pointer-chasing
        # stay host-side); scored on device by phrase frequency
        tids = [segment.term_id(self.field, t) for t in terms]
        if any(t < 0 for t in tids):
            return P.MatchNoneNode()
        pos_maps = [segment.positions.get(t, {}) for t in tids]
        candidates = set(pos_maps[0])
        for pm in pos_maps[1:]:
            candidates &= set(pm)
        docs, freqs = [], []
        for doc in sorted(candidates):
            freq = _phrase_freq([pm[doc] for pm in pos_maps], self.slop)
            if freq > 0:
                docs.append(doc)
                freqs.append(float(freq))
        if not docs:
            return P.MatchNoneNode()
        # phrase weight under the field's similarity: sum of per-term
        # weights (Lucene PhraseQuery combines term stats similarly); the
        # non-weight lane params come from the rarest term (approximation
        # for the stat-dependent DFR/IB/LM params)
        st = segment.field_stats.get(self.field, {})
        doc_count = st.get("doc_count", 0)
        sim = (ctx.similarity(self.field) if ctx is not None else None) or _DEFAULT_BM25
        lanes = [
            sim.lane_params({
                "df": int(segment.term_doc_freq[t]),
                "ttf": segment.term_ttf(t) if sim.needs_ttf else 0,
                "doc_count": doc_count,
                "sum_ttf": st.get("sum_ttf", 0),
                "avgdl": segment.field_avgdl(self.field),
                "boost": 1.0,
            })
            for t in tids
        ]
        kind = lanes[0][0]
        weight = sum(l[1] for l in lanes) * self.boost
        _, _, p1, p2, p3 = max(lanes, key=lambda l: l[1])
        sentinel = segment.nd_pad
        return P.PhraseScoreNode(
            _pad_pow2(docs, sentinel, dtype=np.int32),
            _pad_pow2(freqs, 0.0, dtype=np.float32),
            weight,
            segment.field_norm_idx.get(self.field, 0),
            segment.field_avgdl(self.field),
            kind=kind, p1=p1, p2=p2, p3=p3,
        )


def _phrase_freq(positions_per_term: List[np.ndarray], slop: int) -> int:
    """Exact phrase (slop=0) or sloppy within-window match count."""
    first = positions_per_term[0]
    count = 0
    if slop == 0:
        others = [set(p.tolist()) for p in positions_per_term[1:]]
        for p in first.tolist():
            if all((p + i + 1) in s for i, s in enumerate(others)):
                count += 1
        return count
    # sloppy: greedy window check (approximation of Lucene's sloppy freq)
    for p in first.tolist():
        ok = True
        prev = p
        for i, arr in enumerate(positions_per_term[1:]):
            target = p + i + 1
            diffs = np.abs(arr - target)
            if diffs.size == 0 or diffs.min() > slop:
                ok = False
                break
        if ok:
            count += 1
    return count


class MatchPhrasePrefixQueryBuilder(QueryBuilder):
    name = "match_phrase_prefix"

    def __init__(self, field: str, query, max_expansions: int = 50, **kw):
        super().__init__(**kw)
        self.field = field
        self.query = query
        self.max_expansions = max_expansions

    def to_plan(self, ctx, segment):
        ft = ctx.field_type(self.field)
        terms = (ft.query_terms(self.query, ctx.analyzers)
                 if isinstance(ft, TextFieldType) else [str(self.query)])
        if not terms:
            return P.MatchNoneNode()
        prefix = terms[-1]
        expansions = [t for t, _ in segment.terms_for_field(self.field)
                      if t.startswith(prefix)][: self.max_expansions]
        if len(terms) == 1:
            if not expansions:
                return P.MatchNoneNode()
            return score_terms_node(
                segment, [(self.field, t, self.boost) for t in expansions], 1,
                ctx=ctx,
            )
        subs = []
        for exp in expansions:
            phrase_terms = terms[:-1] + [exp]
            subs.append(MatchPhraseQueryBuilder(
                self.field, " ".join(phrase_terms), boost=self.boost
            ))
        if not subs:
            return P.MatchNoneNode()
        return BoolQueryBuilder(should=subs).to_plan(ctx, segment)


class MultiMatchQueryBuilder(QueryBuilder):
    """multi_match (index/query/MultiMatchQueryBuilder): best_fields
    (dis_max over per-field match, default), most_fields (sum), and
    cross_fields (approximated as most_fields)."""

    name = "multi_match"

    def __init__(self, query, fields: List[str], type_: str = "best_fields",
                 operator: str = "or", tie_breaker: float = 0.0,
                 analyzer: Optional[str] = None, **kw):
        super().__init__(**kw)
        self.query = query
        self.fields = fields
        self.type = type_
        self.operator = operator
        self.tie_breaker = tie_breaker
        self.analyzer = analyzer

    def to_plan(self, ctx, segment):
        field_boosts = []
        for f in self.fields:
            if "^" in f:
                name, b = f.split("^", 1)
                for resolved in ctx.mapper_service.mapper.simple_match_to_fields(name) or [name]:
                    field_boosts.append((resolved, float(b)))
            else:
                for resolved in ctx.mapper_service.mapper.simple_match_to_fields(f) or [f]:
                    field_boosts.append((resolved, 1.0))
        per_field = [
            MatchQueryBuilder(f, self.query, operator=self.operator,
                              analyzer=self.analyzer, boost=b)
            .to_plan(ctx, segment)
            for f, b in field_boosts
        ]
        per_field = [n for n in per_field if not isinstance(n, P.MatchNoneNode)]
        if not per_field:
            return P.MatchNoneNode()
        if self.type in ("best_fields", "phrase", "phrase_prefix"):
            node = P.DisMaxNode(per_field, self.tie_breaker)
        else:  # most_fields / cross_fields: sum of field scores
            node = P.BoolNode([], [], per_field, [], 1)
        return self._wrap_boost(node)


class TermQueryBuilder(QueryBuilder):
    name = "term"

    def __init__(self, field: str, value, **kw):
        super().__init__(**kw)
        self.field = field
        self.value = value

    def to_plan(self, ctx, segment):
        if self.field == "_id":
            # term on the _id metadata field == ids query (the reference
            # routes both through IdFieldMapper's term query)
            vals = (self.value if isinstance(self.value, list)
                    else [self.value])
            return IdsQueryBuilder(
                [str(v) for v in vals], boost=self.boost).to_plan(
                    ctx, segment)
        ft = ctx.field_type(self.field)
        from elasticsearch_tpu.mapper.field_types import RangeFieldType

        if isinstance(ft, RangeFieldType):
            # point-containment: the stored range must contain the term
            v = ft.numeric_for_query(self.value)
            return _range_pair_node(segment, self.field, v, v, "intersects",
                                    self.boost)
        if isinstance(ft, (NumberFieldType, DateFieldType, IpFieldType)):
            if isinstance(ft, IpFieldType):
                from elasticsearch_tpu.mapper.field_types import parse_ip

                v = float(parse_ip(self.value))
            else:
                v = ft.numeric_for_query(self.value)
            node = _numeric_terms_node(ctx, segment, self.field, [v])
            if node is None:
                return P.MatchNoneNode()
            return P.ConstantScoreNode(node, self.boost)
        # term against the inverted index (keyword/boolean/text-raw-token)
        token = (ft.term_for_query(self.value, ctx.analyzers)
                 if ft is not None and not isinstance(ft, TextFieldType)
                 else str(self.value))
        node = score_terms_node(segment, [(self.field, token, self.boost)], 1,
                                ctx=ctx)
        return node

    def explain_terms(self, ctx):
        ft = ctx.field_type(self.field)
        from elasticsearch_tpu.mapper.field_types import (
            BooleanFieldType,
            KeywordFieldType,
        )

        if isinstance(ft, (KeywordFieldType, BooleanFieldType)) or ft is None:
            token = (ft.term_for_query(self.value, ctx.analyzers)
                     if ft is not None else str(self.value))
            return [(self.field, token, self.boost)]
        if isinstance(ft, TextFieldType):
            return [(self.field, str(self.value), self.boost)]
        return None


class TermsQueryBuilder(QueryBuilder):
    name = "terms"

    def __init__(self, field: str, values: List, **kw):
        super().__init__(**kw)
        self.field = field
        self.values = values

    def to_plan(self, ctx, segment):
        if self.field == "_id":
            return IdsQueryBuilder(
                [str(v) for v in self.values], boost=self.boost).to_plan(
                    ctx, segment)
        ft = ctx.field_type(self.field)
        if isinstance(ft, (NumberFieldType, DateFieldType)):
            nums = [ft.numeric_for_query(v) for v in self.values]
            node = (_numeric_terms_node(ctx, segment, self.field, nums)
                    if nums else None)
            if node is None:
                return P.MatchNoneNode()
            return P.ConstantScoreNode(node, self.boost)
        # constant-score terms over ordinals if the field has them, else
        # inverted-index disjunction
        col = segment.ordinal_columns.get(self.field)
        if col is not None:
            csr = _ordinal_csr(segment, self.field)
            docs, ords, col = csr
            norm = (ft.term_for_query if ft is not None else (lambda v, a: str(v)))
            o = [col.ord_of(norm(v, ctx.analyzers)) for v in self.values]
            o = [x for x in o if x >= 0]
            if not o:
                return P.MatchNoneNode()
            return P.ConstantScoreNode(P.OrdTermsNode(
                docs, ords, _pad_pow2(o, -1, min_len=1, dtype=np.int32)
            ), self.boost)
        tokens = [
            (ft.term_for_query(v, ctx.analyzers) if ft is not None else str(v))
            for v in self.values
        ]
        node = score_terms_node(
            segment, [(self.field, t, self.boost) for t in tokens], 1, ctx=ctx
        )
        return P.ConstantScoreNode(node, self.boost)


def _range_pair_node(segment, field, q_lo, q_hi, relation, boost) -> P.PlanNode:
    """Build a RangePairNode against a range field's aligned #lo/#hi columns."""
    lo_col = segment.numeric_columns.get(f"{field}#lo")
    hi_col = segment.numeric_columns.get(f"{field}#hi")
    if lo_col is None or hi_col is None:
        return P.MatchNoneNode()
    docs = segment.device_column(f"num.{field}#lo.docs", lambda: lo_col.flat_docs)
    lo_vals = segment.device_column(f"num.{field}#lo.vals", lambda: lo_col.flat_values)
    hi_vals = segment.device_column(f"num.{field}#hi.vals", lambda: hi_col.flat_values)
    return P.ConstantScoreNode(
        P.RangePairNode(docs, lo_vals, hi_vals, q_lo, q_hi, relation), boost
    )


class RangeQueryBuilder(QueryBuilder):
    name = "range"

    def __init__(self, field: str, gte=None, gt=None, lte=None, lt=None,
                 format: Optional[str] = None, relation: str = "intersects", **kw):
        super().__init__(**kw)
        self.field = field
        self.gte, self.gt, self.lte, self.lt = gte, gt, lte, lt
        self.relation = str(relation).lower()
        if self.relation not in ("intersects", "within", "contains"):
            raise ParsingException(
                f"[range] query does not support relation [{relation}]"
            )

    def to_plan(self, ctx, segment):
        ft = ctx.field_type(self.field)
        from elasticsearch_tpu.mapper.field_types import RangeFieldType

        if isinstance(ft, RangeFieldType):
            spec = {}
            for k, v in (("gte", self.gte), ("gt", self.gt),
                         ("lte", self.lte), ("lt", self.lt)):
                if v is not None:
                    spec[k] = v
            q_lo, q_hi = ft.parse_range(spec)
            return _range_pair_node(segment, self.field, q_lo, q_hi,
                                    self.relation, self.boost)
        if isinstance(ft, (NumberFieldType, DateFieldType, BooleanFieldType, IpFieldType)) or (
            ft is None and segment.numeric_columns.get(self.field) is not None
        ):
            staged = _staged_numeric(ctx, self.field)
            csr = None if staged else _numeric_csr(segment, self.field)
            if staged is None and csr is None:
                return P.MatchNoneNode()
            conv = (ft.numeric_for_query if ft is not None else float)
            if isinstance(ft, IpFieldType):
                from elasticsearch_tpu.mapper.field_types import parse_ip
                conv = lambda v: float(parse_ip(v))  # noqa: E731
            lo = -np.inf
            hi = np.inf
            if self.gte is not None:
                lo = conv(self.gte)
            if self.gt is not None:
                lo = np.nextafter(conv(self.gt), np.inf)
            if self.lte is not None:
                hi = conv(self.lte)
            if self.lt is not None:
                hi = np.nextafter(conv(self.lt), -np.inf)
            if staged is not None:
                return P.ConstantScoreNode(
                    P.StagedNumericRangeNode(staged, lo, hi), self.boost)
            docs, vals, _ = csr
            return P.ConstantScoreNode(P.NumericRangeNode(docs, vals, lo, hi), self.boost)
        col = segment.ordinal_columns.get(self.field)
        if col is not None:
            docs, ords, col = _ordinal_csr(segment, self.field)
            lo_ord, hi_ord = col.ord_range(
                str(self.gte) if self.gte is not None else (
                    str(self.gt) if self.gt is not None else None),
                str(self.lte) if self.lte is not None else (
                    str(self.lt) if self.lt is not None else None),
                include_lo=self.gt is None,
                include_hi=self.lt is None,
            )
            return P.ConstantScoreNode(P.OrdRangeNode(docs, ords, lo_ord, hi_ord), self.boost)
        raise QueryShardException(
            f"field [{self.field}] does not support range queries "
            "(no doc values in this segment)"
        )


class ExistsQueryBuilder(QueryBuilder):
    name = "exists"

    def __init__(self, field: str, **kw):
        super().__init__(**kw)
        self.field = field

    def to_plan(self, ctx, segment):
        fields = ctx.mapper_service.mapper.simple_match_to_fields(self.field) or [self.field]
        masks = []
        for f in fields:
            if f in segment.exists_masks:
                masks.append(segment.device_column(
                    f"exists.{f}",
                    lambda f=f: np.concatenate(
                        [segment.exists_masks[f], np.zeros(1, dtype=bool)]
                    ),
                ))
        if not masks:
            return P.MatchNoneNode()
        combined = masks[0]
        for m in masks[1:]:
            combined = combined | m
        return P.ConstantScoreNode(P.DenseMaskNode(combined, f"exists:{self.field}"), self.boost)


class IdsQueryBuilder(QueryBuilder):
    name = "ids"

    def __init__(self, values: List[str], **kw):
        super().__init__(**kw)
        self.values = values

    def to_plan(self, ctx, segment):
        id_map = segment.id_to_doc()
        docs = [id_map[v] for v in self.values if v in id_map]
        if not docs:
            return P.MatchNoneNode()
        mask = np.zeros(segment.nd_pad + 1, dtype=bool)
        for d in docs:
            mask[d] = True
        return P.ConstantScoreNode(P.DenseMaskNode(mask, "ids"), self.boost)


class MultiTermExpandingBuilder(QueryBuilder):
    """Shared base for prefix/wildcard/regexp/fuzzy: expand against the
    segment term dictionary, then constant-score disjunction (Lucene
    MultiTermQuery CONSTANT_SCORE rewrite)."""

    def matches(self, token: str) -> bool:
        raise NotImplementedError

    def __init__(self, field: str, **kw):
        super().__init__(**kw)
        self.field = field

    def to_plan(self, ctx, segment):
        expansions = [
            t for t, _ in segment.terms_for_field(self.field) if self.matches(t)
        ][:MAX_EXPANSIONS]
        if not expansions:
            return P.MatchNoneNode()
        node = score_terms_node(
            segment, [(self.field, t, 1.0) for t in expansions], 1, ctx=ctx
        )
        return P.ConstantScoreNode(node, self.boost)


class PrefixQueryBuilder(MultiTermExpandingBuilder):
    name = "prefix"

    def __init__(self, field: str, value: str, **kw):
        super().__init__(field, **kw)
        self.value = str(value)

    def matches(self, token):
        return token.startswith(self.value)


class WildcardQueryBuilder(MultiTermExpandingBuilder):
    name = "wildcard"

    def __init__(self, field: str, value: str, **kw):
        super().__init__(field, **kw)
        self.value = str(value)

    def matches(self, token):
        return fnmatch.fnmatchcase(token, self.value)


class RegexpQueryBuilder(MultiTermExpandingBuilder):
    name = "regexp"

    def __init__(self, field: str, value: str, **kw):
        super().__init__(field, **kw)
        try:
            self._rx = re.compile(value)
        except re.error as e:
            raise ParsingException(f"failed to parse regexp [{value}]: {e}") from e

    def matches(self, token):
        return self._rx.fullmatch(token) is not None


def _levenshtein_leq(a: str, b: str, k: int) -> bool:
    """Edit distance <= k with early exit (banded DP)."""
    if abs(len(a) - len(b)) > k:
        return False
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        row_min = i
        for j, cb in enumerate(b, 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb))
            row_min = min(row_min, cur[j])
        if row_min > k:
            return False
        prev = cur
    return prev[-1] <= k


class FuzzyQueryBuilder(MultiTermExpandingBuilder):
    name = "fuzzy"

    def __init__(self, field: str, value: str, fuzziness="AUTO",
                 prefix_length: int = 0, **kw):
        super().__init__(field, **kw)
        self.value = str(value)
        self.prefix_length = prefix_length
        if fuzziness in ("AUTO", "auto", None):
            n = len(self.value)
            self.max_edits = 0 if n <= 2 else (1 if n <= 5 else 2)
        else:
            self.max_edits = int(fuzziness)

    def matches(self, token):
        if self.prefix_length and not token.startswith(self.value[: self.prefix_length]):
            return False
        return _levenshtein_leq(token, self.value, self.max_edits)


class BoolQueryBuilder(QueryBuilder):
    name = "bool"

    def __init__(self, must=None, filter=None, should=None, must_not=None,
                 minimum_should_match=None, **kw):
        super().__init__(**kw)
        self.must = must or []
        self.filter = filter or []
        self.should = should or []
        self.must_not = must_not or []
        self.minimum_should_match = minimum_should_match

    def explain_terms(self, ctx):
        lanes = []
        for child in list(self.must) + list(self.should):
            sub = child.explain_terms(ctx)
            if sub:
                lanes.extend(sub)
        return lanes or None

    def to_plan(self, ctx, segment):
        must = [q.to_plan(ctx, segment) for q in self.must]
        filter_ = [q.to_plan(ctx, segment) for q in self.filter]
        should = [q.to_plan(ctx, segment) for q in self.should]
        must_not = [q.to_plan(ctx, segment) for q in self.must_not]
        if self.minimum_should_match is not None:
            msm = parse_min_should_match(self.minimum_should_match, len(should))
        elif not self.must and not self.filter:
            msm = 1 if should else 0
        else:
            msm = 0
        return P.BoolNode(must, filter_, should, must_not, msm, self.boost)


class ConstantScoreQueryBuilder(QueryBuilder):
    name = "constant_score"

    def __init__(self, filter: QueryBuilder, **kw):
        super().__init__(**kw)
        self.filter = filter

    def to_plan(self, ctx, segment):
        return P.ConstantScoreNode(self.filter.to_plan(ctx, segment), self.boost)


class DisMaxQueryBuilder(QueryBuilder):
    name = "dis_max"

    def __init__(self, queries: List[QueryBuilder], tie_breaker: float = 0.0, **kw):
        super().__init__(**kw)
        self.queries = queries
        self.tie_breaker = tie_breaker

    def to_plan(self, ctx, segment):
        nodes = [q.to_plan(ctx, segment) for q in self.queries]
        return self._wrap_boost(P.DisMaxNode(nodes, self.tie_breaker))


class FunctionScoreQueryBuilder(QueryBuilder):
    name = "function_score"

    def __init__(self, query: QueryBuilder, functions: List[dict],
                 boost_mode: str = "multiply", score_mode: str = "multiply", **kw):
        super().__init__(**kw)
        self.query = query
        self.functions = functions
        self.boost_mode = boost_mode
        self.score_mode = score_mode

    def to_plan(self, ctx, segment):
        child = self.query.to_plan(ctx, segment)
        weight = 1.0
        factor_columns = []
        for fn in self.functions:
            if "weight" in fn and len(fn) == 1:
                weight *= float(fn["weight"])
                continue
            if "field_value_factor" in fn:
                spec = fn["field_value_factor"]
                col = segment.numeric_columns.get(spec["field"])
                factor = float(spec.get("factor", 1.0))
                missing = float(spec.get("missing", 1.0))
                modifier = spec.get("modifier", "none")
                if col is None:
                    vals = np.full(segment.nd_pad + 1, missing, dtype=np.float32)
                else:
                    base = np.where(col.exists, col.first_value, missing)
                    vals = np.concatenate([base, [missing]]).astype(np.float32)
                vals = vals * factor
                if modifier == "log1p":
                    vals = np.log1p(np.maximum(vals, 0))
                elif modifier == "ln":
                    vals = np.log(np.maximum(vals, 1e-9))
                elif modifier == "sqrt":
                    vals = np.sqrt(np.maximum(vals, 0))
                elif modifier == "square":
                    vals = vals * vals
                elif modifier == "reciprocal":
                    vals = 1.0 / np.maximum(vals, 1e-9)
                factor_columns.append(vals.astype(np.float32))
                if "weight" in fn:
                    weight *= float(fn["weight"])
            elif "random_score" in fn:
                seed = int(fn["random_score"].get("seed", 0))
                rng = np.random.RandomState(seed if seed else 42)
                factor_columns.append(
                    rng.uniform(0, 1, segment.nd_pad + 1).astype(np.float32)
                )
            elif "weight" in fn:
                weight *= float(fn["weight"])
            else:
                raise ParsingException(
                    f"unsupported function_score function: {sorted(fn)}"
                )
        return self._wrap_boost(P.FunctionScoreNode(
            child, factor_columns, weight, self.boost_mode
        ))


class QueryStringQueryBuilder(QueryBuilder):
    """Simplified query_string: supports `field:value`, quoted phrases,
    AND/OR/NOT, +/-, wildcards in terms. (The reference's full Lucene
    syntax is larger; this covers the common subset. simple_query_string
    maps here too.)"""

    name = "query_string"

    def __init__(self, query: str, default_field: Optional[str] = None,
                 fields: Optional[List[str]] = None,
                 default_operator: str = "or",
                 analyzer: Optional[str] = None,
                 lenient: bool = False, **kw):
        super().__init__(**kw)
        self.query = query
        self.default_field = default_field
        self.fields = fields
        self.default_operator = default_operator.lower()
        self.analyzer = analyzer
        self.lenient = lenient

    def _leaf(self, field: Optional[str], text: str, is_phrase: bool, ctx) -> QueryBuilder:
        if field is None:
            fields = self.fields or (
                [self.default_field] if self.default_field else None
            )
            if fields is None:
                fields = ctx.default_fields() or ["*"]
            if len(fields) > 1:
                return MultiMatchQueryBuilder(text, fields,
                                              analyzer=self.analyzer)
            field = fields[0]
        if self.lenient:
            # lenient=true drops clauses whose value can't parse for the
            # field's type instead of failing the request
            ft = ctx.field_type(field) if field else None
            if ft is not None and not isinstance(ft, TextFieldType):
                try:
                    ft.term_for_query(text.strip('"'), ctx.analyzers)
                    if isinstance(ft, NumberFieldType):
                        float(text.strip('"'))
                except Exception:  # noqa: BLE001 — the lenient contract
                    return MatchNoneQueryBuilder()
        if is_phrase:
            return MatchPhraseQueryBuilder(field, text,
                                           analyzer=self.analyzer)
        if "*" in text or "?" in text:
            # analyzed (text) fields hold lowercased terms; the classic
            # query_string parser lowercases expanded terms to match
            ft = ctx.field_type(field)
            if ft is None or isinstance(ft, TextFieldType):
                text = text.lower()
            return WildcardQueryBuilder(field, text)
        return MatchQueryBuilder(field, text, analyzer=self.analyzer)

    def to_plan(self, ctx, segment):
        tokens = re.findall(r'\S*"[^"]*"|\S+', self.query)
        # first pass: clauses with modifiers; AND marks its neighbors as must
        clauses = []  # list of [builder, kind] where kind in must/should/must_not
        i = 0
        while i < len(tokens):
            tok = tokens[i]
            if tok.upper() == "AND":
                if clauses:
                    clauses[-1][1] = "must" if clauses[-1][1] == "should" else clauses[-1][1]
                # mark: next clause must too
                i += 1
                if i < len(tokens):
                    nxt, kind = self._clause(tokens[i], ctx)
                    if nxt is not None:
                        clauses.append([nxt, "must" if kind == "should" else kind])
                    i += 1
                continue
            if tok.upper() == "OR":
                i += 1
                continue
            if tok.upper() == "NOT":
                i += 1
                if i < len(tokens):
                    qb, _ = self._clause(tokens[i], ctx)
                    if qb is not None:
                        clauses.append([qb, "must_not"])
                    i += 1
                continue
            qb, kind = self._clause(tok, ctx)
            if qb is not None:
                clauses.append([qb, kind])
            i += 1
        must = [c for c, k in clauses if k == "must"]
        should = [c for c, k in clauses if k == "should"]
        must_not = [c for c, k in clauses if k == "must_not"]
        if self.default_operator == "and" and should:
            must.extend(should)
            should = []
        return BoolQueryBuilder(
            must=must, should=should, must_not=must_not, boost=self.boost
        ).to_plan(ctx, segment)

    def _clause(self, tok: str, ctx):
        """-> (builder or None, kind)."""
        kind = "should"
        if tok.startswith("+"):
            tok, kind = tok[1:], "must"
        elif tok.startswith("-"):
            tok, kind = tok[1:], "must_not"
        field = None
        if ":" in tok and not tok.startswith('"'):
            field, tok = tok.split(":", 1)
            if not tok:
                return None, kind
        is_phrase = tok.startswith('"') and tok.endswith('"') and len(tok) > 1
        text = tok.strip('"')
        if not text:
            return None, kind
        return self._leaf(field, text, is_phrase, ctx), kind


class GeoDistanceQueryBuilder(QueryBuilder):
    name = "geo_distance"

    def __init__(self, field: str, center, distance, **kw):
        super().__init__(**kw)
        self.field = field
        self.center = GeoPointFieldType.parse_point(center)
        self.distance_m = parse_distance(distance)

    def to_plan(self, ctx, segment):
        col = segment.geo_columns.get(self.field)
        if col is None:
            return P.MatchNoneNode()
        docs = segment.device_column(f"geo.{self.field}.docs", lambda: col.flat_docs)
        lat = segment.device_column(f"geo.{self.field}.lat", lambda: col.lat)
        lon = segment.device_column(f"geo.{self.field}.lon", lambda: col.lon)
        return P.ConstantScoreNode(P.GeoDistanceNode(
            docs, lat, lon, self.center[0], self.center[1], self.distance_m
        ), self.boost)


class GeoBoundingBoxQueryBuilder(QueryBuilder):
    name = "geo_bounding_box"

    def __init__(self, field: str, top_left, bottom_right, **kw):
        super().__init__(**kw)
        self.field = field
        tl = GeoPointFieldType.parse_point(top_left)
        br = GeoPointFieldType.parse_point(bottom_right)
        self.top, self.left = tl
        self.bottom, self.right = br

    def to_plan(self, ctx, segment):
        col = segment.geo_columns.get(self.field)
        if col is None:
            return P.MatchNoneNode()
        docs = segment.device_column(f"geo.{self.field}.docs", lambda: col.flat_docs)
        lat = segment.device_column(f"geo.{self.field}.lat", lambda: col.lat)
        lon = segment.device_column(f"geo.{self.field}.lon", lambda: col.lon)
        return P.ConstantScoreNode(P.GeoBoxNode(
            docs, lat, lon, self.top, self.left, self.bottom, self.right
        ), self.boost)


class GeoPolygonQueryBuilder(QueryBuilder):
    """geo_polygon (index/query/GeoPolygonQueryBuilder.java): docs whose
    point lies inside the polygon. Host-side vectorized ray casting over
    the geo column (a doc matches if ANY of its points is inside)."""

    name = "geo_polygon"

    def __init__(self, field: str, points, **kw):
        super().__init__(**kw)
        self.field = field
        if not points or len(points) < 3:
            raise ParsingException(
                "too few points defined for geo_polygon query"
            )
        self.points = [GeoPointFieldType.parse_point(p) for p in points]

    def to_plan(self, ctx, segment):
        col = segment.geo_columns.get(self.field)
        if col is None:
            return P.MatchNoneNode()
        n = col.count
        lat = col.lat[:n].astype(np.float64)
        lon = col.lon[:n].astype(np.float64)
        inside = np.zeros(n, dtype=bool)
        # ray casting: count edge crossings of a horizontal ray (vectorized
        # over all points per edge)
        pts = self.points + [self.points[0]]
        for (lat1, lon1), (lat2, lon2) in zip(pts[:-1], pts[1:]):
            cond = (lat1 > lat) != (lat2 > lat)
            with np.errstate(divide="ignore", invalid="ignore"):
                x = (lon2 - lon1) * (lat - lat1) / (lat2 - lat1) + lon1
            inside ^= cond & (lon < x)
        mask = np.zeros(segment.nd_pad + 1, dtype=bool)
        docs = col.flat_docs[:n][inside]
        mask[docs] = True
        mask[segment.nd_pad] = False
        return P.ConstantScoreNode(P.DenseMaskNode(mask, "geo_polygon"), self.boost)


class ScriptQueryBuilder(QueryBuilder):
    """script query (index/query/ScriptQueryBuilder.java): filter docs by
    a numeric expression over doc values. The reference compiles Painless
    per doc; here the expression evaluates ONCE over whole-segment
    columns (script/expression.py execute_columns)."""

    name = "script"

    def __init__(self, script_spec, **kw):
        super().__init__(**kw)
        from elasticsearch_tpu.script.expression import compile_script

        self.script = compile_script(script_spec)
        self.params = (script_spec.get("params") or {}
                       if isinstance(script_spec, dict) else {})

    def to_plan(self, ctx, segment):
        from elasticsearch_tpu.script.expression import segment_columns

        nd = segment.nd_pad
        columns = segment_columns(segment, self.script.doc_fields)
        result = self.script.execute_columns(columns, self.params)
        if result is None:
            return P.MatchNoneNode()
        result = np.asarray(result)
        mask = np.zeros(nd + 1, dtype=bool)
        if result.ndim == 0:  # constant expression
            mask[:nd] = bool(result)
        else:
            mask[:nd] = np.nan_to_num(result[:nd]) != 0
        return P.ConstantScoreNode(P.DenseMaskNode(mask, "script"), self.boost)


class MoreLikeThisQueryBuilder(QueryBuilder):
    """more_like_this (index/query/MoreLikeThisQueryBuilder): extract the
    top-idf terms from the liked text/docs and run a disjunction."""

    name = "more_like_this"

    def __init__(self, fields: List[str], like, max_query_terms: int = 25,
                 min_term_freq: int = 2, minimum_should_match: str = "30%", **kw):
        super().__init__(**kw)
        self.fields = fields
        self.like = like if isinstance(like, list) else [like]
        self.max_query_terms = max_query_terms
        self.min_term_freq = min_term_freq
        self.minimum_should_match = minimum_should_match

    def to_plan(self, ctx, segment):
        from collections import Counter

        texts: List[str] = []
        for item in self.like:
            if isinstance(item, str):
                texts.append(item)
            elif isinstance(item, dict) and "_id" in item:
                local = segment.id_to_doc().get(item["_id"])
                if local is not None:
                    src = segment.sources[local]
                    for f in self.fields:
                        v = src.get(f)
                        if isinstance(v, str):
                            texts.append(v)
        selected: List[tuple] = []
        for field in self.fields:
            ft = ctx.field_type(field)
            counts: Counter = Counter()
            for text in texts:
                if isinstance(ft, TextFieldType):
                    counts.update(ft.query_terms(text, ctx.analyzers))
                else:
                    counts.update(ctx.analyzers.get("standard").analyze(text))
            doc_count = segment.field_stats.get(field, {}).get("doc_count", 0)
            for tok, tf in counts.items():
                if tf < self.min_term_freq and len(texts) > 0 and len(counts) > 10:
                    continue
                tid = segment.term_id(field, tok)
                if tid < 0:
                    continue
                idf = bm25_idf(int(segment.term_doc_freq[tid]), doc_count)
                selected.append((idf, field, tok))
        selected.sort(reverse=True)
        selected = selected[: self.max_query_terms]
        if not selected:
            return P.MatchNoneNode()
        msm = parse_min_should_match(self.minimum_should_match, len(selected)) or 1
        return self._wrap_boost(score_terms_node(
            segment, [(f, t, 1.0) for _, f, t in selected], msm, ctx=ctx
        ))


class GeoShapeQueryBuilder(QueryBuilder):
    """geo_shape query (index/query/GeoShapeQueryBuilder.java): relate the
    query shape to each doc's indexed shapes — INTERSECTS (default),
    DISJOINT, WITHIN, CONTAINS. Vectorized bbox prefilter over the
    segment's dense bbox table, exact planar predicates on candidates
    (utils/geometry.py). ``indexed_shape`` references are resolved by a
    coordinator rewrite before shard execution (node.py)."""

    name = "geo_shape"

    def __init__(self, field: str, shape=None, relation: str = "intersects",
                 ignore_unmapped: bool = False, **kw):
        from elasticsearch_tpu.utils.geometry import parse_shape

        super().__init__(**kw)
        self.field = field
        self.shape = shape
        self.relation = str(relation).lower()
        self.ignore_unmapped = ignore_unmapped
        if self.relation not in ("intersects", "disjoint", "within", "contains"):
            raise ParsingException(
                f"Unknown geo_shape relation [{relation}]")
        if shape is None:
            raise ParsingException(
                "[geo_shape] requires a shape or indexed_shape")
        self._geom = parse_shape(shape)  # parse once per query, not per segment

    def to_plan(self, ctx, segment):
        from elasticsearch_tpu.mapper.field_types import GeoShapeFieldType

        ft = ctx.field_type(self.field)
        if not isinstance(ft, GeoShapeFieldType):
            if self.ignore_unmapped:
                return P.MatchNoneNode()
            raise QueryShardException(
                f"failed to find geo_shape field [{self.field}]")
        col = segment.shape_column(self.field)
        nd1 = segment.nd_pad + 1
        mask = np.zeros(nd1, dtype=bool)
        if col is not None:
            q = self._geom
            qb = q.bbox()
            bbox, exists = col["bbox"], col["exists"]
            with np.errstate(invalid="ignore"):
                overlap = exists & ~(
                    (bbox[:, 0] > qb[2]) | (qb[0] > bbox[:, 2])
                    | (bbox[:, 1] > qb[3]) | (qb[1] > bbox[:, 3])
                )
            if self.relation == "disjoint":
                # all docs with the field are candidates; non-overlapping
                # bboxes are immediately disjoint
                mask[: segment.nd_pad] = exists & ~overlap
                candidates = np.flatnonzero(overlap)
            elif self.relation == "contains":
                # a containing shape's own bbox covers the query bbox, so
                # the doc's combined bbox does too — safe prefilter
                with np.errstate(invalid="ignore"):
                    covers = exists & (
                        (bbox[:, 0] <= qb[0]) & (bbox[:, 1] <= qb[1])
                        & (bbox[:, 2] >= qb[2]) & (bbox[:, 3] >= qb[3])
                    )
                candidates = np.flatnonzero(covers)
            else:
                # intersects AND within use the overlap prefilter: within
                # matches if ANY doc shape sits inside the query shape, and
                # the doc's combined multi-shape bbox may exceed the query
                # bbox even when one shape qualifies
                candidates = np.flatnonzero(overlap)
            for doc in candidates:
                gs = col["geoms"][int(doc)]
                if self.relation == "disjoint":
                    mask[doc] = not any(g.intersects(q) for g in gs)
                else:
                    mask[doc] = any(g.relate(q, self.relation) for g in gs)
        return P.ConstantScoreNode(
            P.DenseMaskNode(mask, label=f"geo_shape.{self.field}"), self.boost)


class PercolateQueryBuilder(QueryBuilder):
    """Inverse search (modules/percolator — PercolateQueryBuilder:86): find
    stored queries (percolator-typed fields) matching a candidate document.
    The candidate is indexed into a one-doc in-memory segment; every stored
    query is planned against it and matched queries' docs become hits."""

    name = "percolate"

    def __init__(self, field: str, document: dict, **kw):
        super().__init__(**kw)
        self.field = field
        self.document = document

    def to_plan(self, ctx, segment):
        from elasticsearch_tpu.index.segment import SegmentBuilder

        # one-doc memory index of the candidate, parsed with a scratch
        # mapper (dynamic mapping) so stored queries see typed fields
        from elasticsearch_tpu.analysis.analyzers import AnalysisRegistry
        from elasticsearch_tpu.mapper.mapping import MapperService

        scratch = MapperService(AnalysisRegistry(),
                                ctx.mapper_service.mapping_dict())
        builder = SegmentBuilder("_percolate")
        builder.add_document(scratch.parse_document("_candidate", self.document), 0)
        temp_seg = builder.seal()
        temp_ctx = ShardQueryContext(scratch)
        temp_dev = temp_seg.device_arrays()

        from elasticsearch_tpu.search import plan as PL

        matching = []
        for local in range(segment.num_docs):
            if not segment.live[local]:
                continue
            stored = segment.sources[local].get(self.field)
            if not isinstance(stored, dict):
                continue
            try:
                qb = parse_query(stored)
                node = qb.to_plan(temp_ctx, temp_seg)
                _, m = PL.execute(temp_dev, node)
                if bool(np.asarray(m)[0]):
                    matching.append(local)
            except Exception:
                continue  # malformed stored query never matches
        if not matching:
            return P.MatchNoneNode()
        mask = np.zeros(segment.nd_pad + 1, dtype=bool)
        for d in matching:
            mask[d] = True
        return P.ConstantScoreNode(P.DenseMaskNode(mask, "percolate"), self.boost)


def _require_join_field(ctx):
    from elasticsearch_tpu.mapper.field_types import join_field_of

    jf = join_field_of(ctx.mapper_service)
    if jf is None:
        raise QueryShardException(
            "no [join] field declared in the mapping of this index"
        )
    return jf


def join_columns(segment, join_field: str):
    """(relation ordinal column, parent-id ordinal column) or None — the
    single place that knows the '<field>#parent' encoding."""
    col = segment.ordinal_columns.get(join_field)
    pcol = segment.ordinal_columns.get(f"{join_field}#parent")
    if col is None or pcol is None:
        return None
    return col, pcol


def join_children(segment, join_field: str, child_names) -> Tuple[np.ndarray, List[str]]:
    """Vectorized child-doc selection: live docs whose relation is one of
    child_names and that carry a parent id. -> (local docs, parent ids)."""
    cols = join_columns(segment, join_field)
    if cols is None:
        return np.empty(0, dtype=np.int64), []
    col, pcol = cols
    child_ords = [o for o in (col.ord_of(c) for c in child_names) if o >= 0]
    if not child_ords:
        return np.empty(0, dtype=np.int64), []
    sel = (np.isin(col.first_ord, child_ords) & pcol.exists
           & segment.live[: segment.nd_pad])
    locals_ = np.nonzero(sel)[0]
    pids = [pcol.terms[pcol.first_ord[int(d)]] for d in locals_]
    return locals_, pids


def parent_id_of(segment, join_field: str, local: int) -> Optional[str]:
    cols = join_columns(segment, join_field)
    if cols is None:
        return None
    _, pcol = cols
    if not pcol.exists[local]:
        return None
    return pcol.terms[pcol.first_ord[local]]


def _matched_by_relation(ctx, segment, query: QueryBuilder, jf,
                         relation_name: str):
    """Run `query` over every segment of the shard, restricted to docs of
    the given join relation. Yields (segment, local_doc, score)."""
    for seg2 in ctx.all_segments(segment):
        col = seg2.ordinal_columns.get(jf.name)
        if col is None:
            continue
        rel_ord = col.ord_of(relation_name)
        if rel_ord < 0:
            continue
        node = query.to_plan(ctx, seg2)
        scores_d, matched_d = P.execute(seg2.device_arrays(), node)
        scores = np.asarray(scores_d)
        matched = np.asarray(matched_d)[: seg2.nd_pad]
        sel = matched & seg2.live[: seg2.nd_pad] & (col.first_ord == rel_ord)
        for local in np.nonzero(sel)[0]:
            yield seg2, int(local), float(scores[local])


def _combine_child_scores(scores: List[float], mode: str) -> float:
    if mode == "min":
        return min(scores)
    if mode == "max":
        return max(scores)
    if mode == "sum":
        return sum(scores)
    if mode == "avg":
        return sum(scores) / len(scores)
    return 1.0  # none: constant


class HasChildQueryBuilder(QueryBuilder):
    """has_child (modules/parent-join — HasChildQueryBuilder:62): match
    parent docs having >=min_children..<=max_children children of `type`
    matching the inner query; child scores fold into the parent per
    score_mode. The reference joins via shard-global ordinals; here child
    hits map to parent _ids host-side and scatter into a dense parent
    score column."""

    name = "has_child"

    def __init__(self, type_: str, query: QueryBuilder, score_mode: str = "none",
                 min_children: int = 1, max_children: Optional[int] = None,
                 inner_hits: Optional[dict] = None, **kw):
        super().__init__(**kw)
        self.type = type_
        self.query = query
        if score_mode not in ("none", "min", "max", "sum", "avg"):
            raise ParsingException(
                f"[has_child] query does not support [score_mode] = [{score_mode}]"
            )
        self.score_mode = score_mode
        self.min_children = max(int(min_children), 1)
        self.max_children = int(max_children) if max_children else None
        self.inner_hits = inner_hits
        # pid -> list of (score, child segment, child local doc)
        self._cached_child_hits: Optional[Dict[str, List[tuple]]] = None

    def _child_hits(self, ctx, segment, jf) -> Dict[str, List[tuple]]:
        """Child-side pass, computed ONCE per query execution (builders are
        parsed fresh per request; to_plan runs per segment — memoizing here
        avoids O(segments^2) inner-query executions)."""
        if self._cached_child_hits is None:
            child_hits: Dict[str, List[tuple]] = {}
            for seg2, local, score in _matched_by_relation(
                    ctx, segment, self.query, jf, self.type):
                pid = parent_id_of(seg2, jf.name, local)
                if pid is not None:
                    child_hits.setdefault(pid, []).append((score, seg2, local))
            self._cached_child_hits = child_hits
        return self._cached_child_hits

    def inner_hits_for(self, ctx, segment, local_doc: int, index_name: str):
        """Matching child docs of one parent hit."""
        spec = self.inner_hits if isinstance(self.inner_hits, dict) else {}
        jf = _require_join_field(ctx)
        entries = self._child_hits(ctx, segment, jf).get(
            segment.doc_ids[local_doc], [])
        entries = sorted(entries, key=lambda e: (-e[0], e[2]))
        name = spec.get("name", self.type)
        frm = int(spec.get("from", 0) or 0)
        size = int(spec.get("size", 3) if spec.get("size") is not None else 3)
        hits = [
            {
                "_index": index_name,
                "_type": "_doc",
                "_id": seg2.doc_ids[loc],
                "_score": float(score),
                "_source": seg2.sources[loc],
            }
            for score, seg2, loc in entries[frm:frm + size]
        ]
        max_score = float(entries[0][0]) if entries else None
        return name, {"hits": {"total": len(entries), "max_score": max_score,
                               "hits": hits}}

    def to_plan(self, ctx, segment):
        jf = _require_join_field(ctx)
        parent_name = jf.parent_of(self.type)
        if parent_name is None:
            raise QueryShardException(
                f"[has_child] join relation [{self.type}] is not a child"
            )
        child_hits = self._child_hits(ctx, segment, jf)

        col = segment.ordinal_columns.get(jf.name)
        parent_ord = col.ord_of(parent_name) if col is not None else -1
        if parent_ord < 0:
            return P.MatchNoneNode()
        id_map = segment.id_to_doc()
        nd1 = segment.nd_pad + 1
        mask = np.zeros(nd1, dtype=bool)
        sc = np.zeros(nd1, dtype=np.float32)
        for pid, entries in child_hits.items():
            ss = [e[0] for e in entries]
            if len(ss) < self.min_children:
                continue
            if self.max_children is not None and len(ss) > self.max_children:
                continue
            local = id_map.get(pid)
            if local is None or col.first_ord[local] != parent_ord:
                continue
            mask[local] = True
            sc[local] = _combine_child_scores(ss, self.score_mode)
        if not mask.any():
            return P.MatchNoneNode()
        return self._wrap_boost(P.DenseScoreNode(sc, mask, "has_child"))


class HasParentQueryBuilder(QueryBuilder):
    """has_parent (modules/parent-join — HasParentQueryBuilder): match
    child docs whose parent matches the inner query; score=true copies the
    parent's score onto each child."""

    name = "has_parent"

    def __init__(self, parent_type: str, query: QueryBuilder,
                 score: bool = False, inner_hits: Optional[dict] = None, **kw):
        super().__init__(**kw)
        self.parent_type = parent_type
        self.query = query
        self.score = bool(score)
        self.inner_hits = inner_hits
        # pid -> (score, parent segment, parent local doc)
        self._cached_parent_hits: Optional[Dict[str, tuple]] = None

    def _parent_hits(self, ctx, segment, jf) -> Dict[str, tuple]:
        if self._cached_parent_hits is None:
            parent_hits: Dict[str, tuple] = {}
            for seg2, local, score in _matched_by_relation(
                    ctx, segment, self.query, jf, self.parent_type):
                parent_hits[seg2.doc_ids[local]] = (score, seg2, local)
            self._cached_parent_hits = parent_hits
        return self._cached_parent_hits

    def inner_hits_for(self, ctx, segment, local_doc: int, index_name: str):
        """The matched parent of one child hit."""
        spec = self.inner_hits if isinstance(self.inner_hits, dict) else {}
        jf = _require_join_field(ctx)
        name = spec.get("name", self.parent_type)
        pid = parent_id_of(segment, jf.name, local_doc)
        entry = self._parent_hits(ctx, segment, jf).get(pid) if pid else None
        if entry is None:
            return name, {"hits": {"total": 0, "max_score": None, "hits": []}}
        score, seg2, loc = entry
        hits = [{
            "_index": index_name,
            "_type": "_doc",
            "_id": seg2.doc_ids[loc],
            "_score": float(score),
            "_source": seg2.sources[loc],
        }]
        return name, {"hits": {"total": 1, "max_score": float(score), "hits": hits}}

    def to_plan(self, ctx, segment):
        jf = _require_join_field(ctx)
        if not jf.is_parent(self.parent_type):
            raise QueryShardException(
                f"[has_parent] join relation [{self.parent_type}] is not a parent"
            )
        parent_hits = self._parent_hits(ctx, segment, jf)
        if not parent_hits:
            return P.MatchNoneNode()
        child_names = jf.relations.get(self.parent_type, [])
        locals_, pids = join_children(segment, jf.name, child_names)
        nd1 = segment.nd_pad + 1
        mask = np.zeros(nd1, dtype=bool)
        sc = np.zeros(nd1, dtype=np.float32)
        for local, pid in zip(locals_, pids):
            if pid in parent_hits:
                mask[int(local)] = True
                sc[int(local)] = parent_hits[pid][0] if self.score else 1.0
        if not mask.any():
            return P.MatchNoneNode()
        return self._wrap_boost(P.DenseScoreNode(sc, mask, "has_parent"))


class ParentIdQueryBuilder(QueryBuilder):
    """parent_id (modules/parent-join — ParentIdQueryBuilder): children of
    `type` whose parent is exactly `id`."""

    name = "parent_id"

    def __init__(self, type_: str, id_: str, **kw):
        super().__init__(**kw)
        self.type = type_
        self.id = str(id_)

    def to_plan(self, ctx, segment):
        jf = _require_join_field(ctx)
        col = segment.ordinal_columns.get(jf.name)
        pcol = segment.ordinal_columns.get(f"{jf.name}#parent")
        if col is None or pcol is None:
            return P.MatchNoneNode()
        child_ord = col.ord_of(self.type)
        pid_ord = pcol.ord_of(self.id)
        if child_ord < 0 or pid_ord < 0:
            return P.MatchNoneNode()
        mask = np.zeros(segment.nd_pad + 1, dtype=bool)
        sel = ((col.first_ord == child_ord) & pcol.exists
               & (pcol.first_ord == pid_ord) & segment.live[: segment.nd_pad])
        mask[: segment.nd_pad] = sel
        return P.ConstantScoreNode(P.DenseMaskNode(mask, "parent_id"), self.boost)


class NestedQueryBuilder(QueryBuilder):
    """nested (index/query/NestedQueryBuilder.java): run the inner query
    over the nested objects of `path` and join matches to parent docs.

    The reference delegates to Lucene's ToParentBlockJoinQuery (child docs
    interleaved in the parent's block). TPU inversion: nested objects are a
    separate dense sub-segment with a ``parent_of`` pointer column
    (index/segment.py NestedContext); the child→parent join is a scatter
    by parent id — no cross-object match leakage (a bool must over two
    nested fields only matches when one *object* satisfies both)."""

    name = "nested"

    def __init__(self, path: str, query: QueryBuilder, score_mode: str = "avg",
                 ignore_unmapped: bool = False, inner_hits: Optional[dict] = None,
                 **kw):
        super().__init__(**kw)
        self.path = path
        self.query = query
        if score_mode not in ("none", "min", "max", "sum", "avg"):
            raise ParsingException(
                f"[nested] query does not support [score_mode] = [{score_mode}]"
            )
        self.score_mode = score_mode
        self.ignore_unmapped = bool(ignore_unmapped)
        self.inner_hits = inner_hits
        self._cache: Dict[str, tuple] = {}

    def _nested_matches(self, ctx, segment):
        """Inner-query pass over the path's sub-segment (once per segment
        per request): -> (NestedContext, matched bool[n_objs], scores) or
        None when the segment has no objects at the path."""
        if segment.name in self._cache:
            return self._cache[segment.name]
        nctx = segment.nested.get(self.path)
        if nctx is None or nctx.segment.num_docs == 0:
            self._cache[segment.name] = None
            return None
        nseg = nctx.segment
        node = self.query.to_plan(ShardQueryContext(ctx.mapper_service), nseg)
        scores_d, matched_d = P.execute(nseg.device_arrays(), node)
        n = nctx.parent_of.shape[0]
        scores = np.asarray(scores_d)[:n]
        matched = np.asarray(matched_d)[:n] & nseg.live[:n]
        # objects die with their parent
        matched = matched & segment.live[nctx.parent_of]
        out = (nctx, matched, scores)
        self._cache[segment.name] = out
        return out

    def to_plan(self, ctx, segment):
        if self.path not in ctx.mapper_service.mapper.nested_paths:
            if self.ignore_unmapped:
                return P.MatchNoneNode()
            raise QueryShardException(
                f"[nested] failed to find nested object under path [{self.path}]"
            )
        res = self._nested_matches(ctx, segment)
        if res is None:
            return P.MatchNoneNode()
        nctx, matched, scores = res
        objs = np.nonzero(matched)[0]
        if objs.size == 0:
            return P.MatchNoneNode()
        parents = nctx.parent_of[objs]
        nd1 = segment.nd_pad + 1
        mask = np.zeros(nd1, dtype=bool)
        mask[parents] = True
        sc = np.zeros(nd1, dtype=np.float32)
        obj_scores = scores[objs].astype(np.float32)
        if self.score_mode == "sum":
            np.add.at(sc, parents, obj_scores)
        elif self.score_mode == "avg":
            counts = np.zeros(nd1, dtype=np.float32)
            np.add.at(sc, parents, obj_scores)
            np.add.at(counts, parents, 1.0)
            sc = np.where(counts > 0, sc / np.maximum(counts, 1.0), 0.0)
        elif self.score_mode == "min":
            sc[:] = np.inf
            np.minimum.at(sc, parents, obj_scores)
            sc = np.where(mask, sc, 0.0).astype(np.float32)
        elif self.score_mode == "max":
            sc[:] = -np.inf
            np.maximum.at(sc, parents, obj_scores)
            sc = np.where(mask, sc, 0.0).astype(np.float32)
        # "none": parents score 0 (ToParentBlockJoinQuery ScoreMode.None)
        return self._wrap_boost(P.DenseScoreNode(sc.astype(np.float32), mask, "nested"))

    def inner_hits_for(self, ctx, segment, local_doc: int, index_name: str):
        """Matched nested objects of one parent hit, as an inner-hits
        entry (search/fetch/subphase/InnerHitsFetchSubPhase)."""
        spec = self.inner_hits if isinstance(self.inner_hits, dict) else {}
        res = self._nested_matches(ctx, segment) \
            if self.path in ctx.mapper_service.mapper.nested_paths else None
        name = spec.get("name", self.path)
        if res is None:
            return name, {"hits": {"total": 0, "max_score": None, "hits": []}}
        nctx, matched, scores = res
        objs = np.nonzero(matched & (nctx.parent_of == local_doc))[0]
        order = sorted(objs, key=lambda o: (-scores[o], nctx.offset_of[o]))
        total = len(order)
        frm = int(spec.get("from", 0) or 0)
        size = int(spec.get("size", 3) if spec.get("size") is not None else 3)
        sel = order[frm:frm + size]
        hits = [
            {
                "_index": index_name,
                "_type": "_doc",
                "_id": segment.doc_ids[local_doc],
                "_nested": {"field": self.path, "offset": int(nctx.offset_of[o])},
                "_score": float(scores[o]),
                "_source": nctx.segment.sources[o],
            }
            for o in sel
        ]
        max_score = float(scores[order[0]]) if order else None
        return name, {"hits": {"total": total, "max_score": max_score, "hits": hits}}


def sub_queries(qb: QueryBuilder) -> List[QueryBuilder]:
    """Immediate child builders of a compound query (for tree walks)."""
    if isinstance(qb, BoolQueryBuilder):
        return [*qb.must, *qb.filter, *qb.should, *qb.must_not]
    if isinstance(qb, ConstantScoreQueryBuilder):
        return [qb.filter]
    if isinstance(qb, DisMaxQueryBuilder):
        return list(qb.queries)
    if isinstance(qb, (FunctionScoreQueryBuilder, NestedQueryBuilder,
                       HasChildQueryBuilder, HasParentQueryBuilder)):
        return [qb.query]
    return []


def collect_inner_hits(qb: Optional[QueryBuilder]) -> List[QueryBuilder]:
    """Builders carrying an inner_hits spec anywhere in the query tree
    (the reference registers InnerHitContextBuilders during rewrite —
    index/query/InnerHitContextBuilder)."""
    if qb is None:
        return []
    out = []
    if getattr(qb, "inner_hits", None) is not None and hasattr(qb, "inner_hits_for"):
        out.append(qb)
    for child in sub_queries(qb):
        out.extend(collect_inner_hits(child))
    return out


# ---------------------------------------------------------------------------
# Parsing (JSON -> builders)
# ---------------------------------------------------------------------------


def parse_distance(d) -> float:
    """'10km', '500m', number (meters) -> meters. One unit table for
    geo_distance queries/sorts and geo_shape circle radii."""
    from elasticsearch_tpu.utils.geometry import _parse_radius

    return _parse_radius(d)


def parse_min_should_match(spec, n_clauses: int) -> int:
    """'2', '30%', '-25%' -> concrete clause count (Queries.calculateMinShouldMatch)."""
    if spec is None:
        return 0
    s = str(spec).strip()
    if s.endswith("%"):
        pct = float(s[:-1])
        if pct < 0:
            return n_clauses - int(-pct / 100.0 * n_clauses)
        return int(pct / 100.0 * n_clauses)
    v = int(s)
    if v < 0:
        return max(n_clauses + v, 0)
    return min(v, n_clauses)


def _field_and_params(body: dict, value_key: str):
    """Handle {"field": "val"} and {"field": {value_key: ..., opts}}."""
    if len(body) != 1:
        raise ParsingException(f"query body must reference one field, got {sorted(body)}")
    field, spec = next(iter(body.items()))
    if isinstance(spec, dict):
        params = dict(spec)
        value = params.pop(value_key, None)
        return field, value, params
    return field, spec, {}


def parse_query(body) -> QueryBuilder:
    """Parse the JSON query DSL (the ``"query": {...}`` object)."""
    if body is None:
        return MatchAllQueryBuilder()
    if not isinstance(body, dict) or len(body) != 1:
        raise ParsingException(
            "[query] malformed query, expected a single query clause object"
        )
    qtype, qbody = next(iter(body.items()))

    if qtype == "match_all":
        return MatchAllQueryBuilder(boost=float((qbody or {}).get("boost", 1.0)))
    if qtype == "match_none":
        return MatchNoneQueryBuilder()
    if qtype == "match":
        field, value, params = _field_and_params(qbody, "query")
        return MatchQueryBuilder(
            field, value, operator=params.get("operator", "or"),
            minimum_should_match=params.get("minimum_should_match"),
            analyzer=params.get("analyzer"),
            boost=float(params.get("boost", 1.0)),
        )
    if qtype == "knn":
        if not isinstance(qbody, dict) or "field" not in qbody:
            raise ParsingException("[knn] requires [field]")
        if "query_vector" not in qbody:
            raise ParsingException("[knn] requires [query_vector]")
        unknown = set(qbody) - {"field", "query_vector", "k",
                                "num_candidates", "filter", "boost",
                                "_name"}
        if unknown:
            # strict parsing (AbstractQueryBuilder contract): a
            # misspelled parameter must 400, never silently drop
            raise ParsingException(
                f"[knn] unknown parameter(s) {sorted(unknown)}")
        flt = qbody.get("filter")
        filters = ([parse_query(f) for f in flt]
                   if isinstance(flt, list)
                   else [parse_query(flt)] if flt is not None else [])
        return KnnQueryBuilder(
            qbody["field"], qbody["query_vector"],
            k=int(qbody.get("k", 10) or 10),
            num_candidates=qbody.get("num_candidates"),
            filter=filters,
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "match_phrase":
        field, value, params = _field_and_params(qbody, "query")
        return MatchPhraseQueryBuilder(
            field, value, slop=int(params.get("slop", 0)),
            boost=float(params.get("boost", 1.0)),
        )
    if qtype == "match_phrase_prefix":
        field, value, params = _field_and_params(qbody, "query")
        return MatchPhrasePrefixQueryBuilder(
            field, value, max_expansions=int(params.get("max_expansions", 50)),
            boost=float(params.get("boost", 1.0)),
        )
    if qtype == "multi_match":
        return MultiMatchQueryBuilder(
            qbody.get("query"), qbody.get("fields") or ["*"],
            type_=qbody.get("type", "best_fields"),
            operator=qbody.get("operator", "or"),
            tie_breaker=float(qbody.get("tie_breaker", 0.0)),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "term":
        field, value, params = _field_and_params(qbody, "value")
        return TermQueryBuilder(field, value, boost=float(params.get("boost", 1.0)))
    if qtype == "terms":
        body2 = dict(qbody)
        boost = float(body2.pop("boost", 1.0))
        if len(body2) != 1:
            raise ParsingException("[terms] query requires exactly one field")
        field, values = next(iter(body2.items()))
        return TermsQueryBuilder(field, values, boost=boost)
    if qtype == "range":
        field, _, params = _field_and_params(qbody, "__none__")
        known = {k: params.get(k) for k in ("gte", "gt", "lte", "lt")}
        # legacy from/to/include_lower/include_upper
        if "from" in params:
            known["gte" if params.get("include_lower", True) else "gt"] = params["from"]
        if "to" in params:
            known["lte" if params.get("include_upper", True) else "lt"] = params["to"]
        return RangeQueryBuilder(
            field, boost=float(params.get("boost", 1.0)),
            relation=params.get("relation", "intersects"), **known,
        )
    if qtype == "exists":
        return ExistsQueryBuilder(qbody["field"], boost=float(qbody.get("boost", 1.0)))
    if qtype == "ids":
        return IdsQueryBuilder(qbody.get("values", []))
    if qtype == "prefix":
        field, value, params = _field_and_params(qbody, "value")
        return PrefixQueryBuilder(field, value, boost=float(params.get("boost", 1.0)))
    if qtype == "wildcard":
        field, value, params = _field_and_params(qbody, "value")
        if value is None:
            value = params.pop("wildcard", None)
        return WildcardQueryBuilder(field, value, boost=float(params.get("boost", 1.0)))
    if qtype == "regexp":
        field, value, params = _field_and_params(qbody, "value")
        return RegexpQueryBuilder(field, value, boost=float(params.get("boost", 1.0)))
    if qtype == "fuzzy":
        field, value, params = _field_and_params(qbody, "value")
        return FuzzyQueryBuilder(
            field, value, fuzziness=params.get("fuzziness", "AUTO"),
            prefix_length=int(params.get("prefix_length", 0)),
            boost=float(params.get("boost", 1.0)),
        )
    if qtype == "bool":
        def many(key):
            v = qbody.get(key)
            if v is None:
                return []
            if isinstance(v, list):
                return [parse_query(q) for q in v]
            return [parse_query(v)]

        return BoolQueryBuilder(
            must=many("must"), filter=many("filter"), should=many("should"),
            must_not=many("must_not"),
            minimum_should_match=qbody.get("minimum_should_match"),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "constant_score":
        return ConstantScoreQueryBuilder(
            parse_query(qbody["filter"]), boost=float(qbody.get("boost", 1.0))
        )
    if qtype == "dis_max":
        return DisMaxQueryBuilder(
            [parse_query(q) for q in qbody.get("queries", [])],
            tie_breaker=float(qbody.get("tie_breaker", 0.0)),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "function_score":
        inner = parse_query(qbody.get("query")) if qbody.get("query") else MatchAllQueryBuilder()
        functions = qbody.get("functions")
        if functions is None:
            functions = []
            for k in ("field_value_factor", "random_score", "script_score", "weight"):
                if k in qbody:
                    functions.append({k: qbody[k]})
        return FunctionScoreQueryBuilder(
            inner, functions, boost_mode=qbody.get("boost_mode", "multiply"),
            score_mode=qbody.get("score_mode", "multiply"),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype in ("query_string", "simple_query_string"):
        return QueryStringQueryBuilder(
            qbody["query"], default_field=qbody.get("default_field"),
            fields=qbody.get("fields"),
            default_operator=qbody.get("default_operator", "or"),
            analyzer=qbody.get("analyzer"),
            lenient=bool(qbody.get("lenient", False)),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "geo_distance":
        params = dict(qbody)
        distance = params.pop("distance")
        params.pop("distance_type", None)
        params.pop("validation_method", None)
        if len(params) != 1:
            raise ParsingException("[geo_distance] requires exactly one field")
        field, center = next(iter(params.items()))
        return GeoDistanceQueryBuilder(field, center, distance)
    if qtype == "geo_bounding_box":
        params = dict(qbody)
        params.pop("validation_method", None)
        params.pop("type", None)
        if len(params) != 1:
            raise ParsingException("[geo_bounding_box] requires exactly one field")
        field, box = next(iter(params.items()))
        return GeoBoundingBoxQueryBuilder(field, box["top_left"], box["bottom_right"])
    if qtype == "geo_shape":
        params = dict(qbody)
        ignore_unmapped = bool(params.pop("ignore_unmapped", False))
        boost = float(params.pop("boost", 1.0))
        if len(params) != 1:
            raise ParsingException("[geo_shape] requires exactly one field")
        field, spec = next(iter(params.items()))
        if "indexed_shape" in spec:
            raise ParsingException(
                "[geo_shape] indexed_shape must be resolved by the "
                "coordinator rewrite before shard execution")
        return GeoShapeQueryBuilder(
            field, shape=spec.get("shape"),
            relation=spec.get("relation", "intersects"),
            ignore_unmapped=ignore_unmapped, boost=boost)
    if qtype == "geo_polygon":
        params = dict(qbody)
        params.pop("validation_method", None)
        if len(params) != 1:
            raise ParsingException("[geo_polygon] requires exactly one field")
        field, spec = next(iter(params.items()))
        return GeoPolygonQueryBuilder(field, spec.get("points") or [])
    if qtype == "script":
        return ScriptQueryBuilder(
            qbody.get("script", qbody), boost=float(qbody.get("boost", 1.0))
        )
    if qtype == "more_like_this":
        return MoreLikeThisQueryBuilder(
            qbody.get("fields", []), qbody.get("like", []),
            max_query_terms=int(qbody.get("max_query_terms", 25)),
            min_term_freq=int(qbody.get("min_term_freq", 2)),
            minimum_should_match=qbody.get("minimum_should_match", "30%"),
        )
    if qtype == "percolate":
        doc = qbody.get("document")
        if doc is None and "documents" in qbody:
            doc = qbody["documents"][0]
        return PercolateQueryBuilder(qbody["field"], doc or {})
    if qtype == "has_child":
        return HasChildQueryBuilder(
            qbody["type"], parse_query(qbody.get("query")),
            score_mode=qbody.get("score_mode", "none"),
            min_children=int(qbody.get("min_children", 1) or 1),
            max_children=qbody.get("max_children"),
            inner_hits=qbody.get("inner_hits"),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "has_parent":
        return HasParentQueryBuilder(
            qbody["parent_type"], parse_query(qbody.get("query")),
            score=bool(qbody.get("score", False)),
            inner_hits=qbody.get("inner_hits"),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "parent_id":
        return ParentIdQueryBuilder(
            qbody["type"], qbody["id"], boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "nested":
        return NestedQueryBuilder(
            qbody["path"], parse_query(qbody["query"]),
            score_mode=qbody.get("score_mode", "avg"),
            ignore_unmapped=bool(qbody.get("ignore_unmapped", False)),
            inner_hits=qbody.get("inner_hits"),
            boost=float(qbody.get("boost", 1.0)),
        )
    if qtype == "type":
        return MatchAllQueryBuilder()  # single doc type in 6.x
    from elasticsearch_tpu.search.spans import SPAN_TYPES, parse_span_query

    if qtype in SPAN_TYPES:
        return parse_span_query(body)
    custom = CUSTOM_QUERY_PARSERS.get(qtype)
    if custom is not None:
        return custom(qbody)
    raise ParsingException(f"no [query] registered for [{qtype}]")
