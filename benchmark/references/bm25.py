"""The plain reference of lexical search: ``match`` queries (an OR of
terms) scored by BM25, top-k, with terms and date-histogram buckets.

A numpy BM25 (Lucene's idf, k1 1.2, b 0.75, exact f32 lengths) with one
set of statistics per shard: the engine scores with its segment's
df / doc count / avgdl and runs no DFS round, and a base index holds one
segment per shard. Bucket counts for the aggregations are plain numpy
counts over the matched documents. Nothing here imports the program or
takes anything the program has made.

``Reference`` is what the harness drives (the contract is in
``references/__init__.py``). Every number it compares has a limit of its
own in the configuration's ``limits``; the readings the limits were set
from are in PERF.md section 2. The control is the same BM25 with every
operand and every result rounded to bfloat16, the nearest precision
below the float32 the scores are stated in.
"""

from __future__ import annotations

import math

import numpy as np

from harness.corpus import TextField

K1, B = 1.2, 0.75
HOUR_MS = 3_600_000
POSTING_BYTES = 8  # one i32 doc id and one f32 impact: the raw codec's


def bf16(x) -> np.ndarray:
    """Round f32 to bfloat16 (nearest even), returned as f32."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1)))
    return (u & np.uint64(0xFFFF0000)).astype(np.uint32).view(np.float32)


def _f32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


class Bm25:
    """BM25 of one text field with per-shard statistics."""

    def __init__(self, field: TextField, shard: np.ndarray, n_shards: int):
        self.field = field
        self.shard = np.asarray(shard, np.int32)
        self.n_shards = n_shards
        self.shard_docs = np.bincount(self.shard, minlength=n_shards)
        sum_ttf = np.bincount(self.shard, weights=field.doc_len,
                              minlength=n_shards)
        avgdl = (sum_ttf / np.maximum(self.shard_docs, 1)).astype(np.float32)
        self.norm = (np.float32(K1) * (
            np.float32(1.0 - B) + np.float32(B)
            * field.doc_len.astype(np.float32) / avgdl[self.shard]))

    def _term(self, term: int, rnd):
        docs, tf = self.field.postings(term)
        df = np.bincount(self.shard[docs], minlength=self.n_shards)
        idf = np.asarray(
            [math.log(1.0 + (n - d + 0.5) / (d + 0.5))
             for n, d in zip(self.shard_docs, df)], np.float32)
        tf = rnd(tf)
        num = rnd(rnd(idf)[self.shard[docs]] * tf)
        num = rnd(num * rnd(np.float32(K1 + 1.0)))
        return docs, rnd(num / rnd(tf + rnd(self.norm[docs])))

    def match(self, terms, precision: str = "float32"):
        """(scores[n_docs] f32, matched[n_docs] bool) of an OR of terms.
        ``precision="bfloat16"`` rounds every operand and every result
        to bfloat16: the control, never the reference."""
        rnd = bf16 if precision == "bfloat16" else _f32
        total = np.zeros(self.field.n_docs, np.float32)
        matched = np.zeros(self.field.n_docs, bool)
        for t in terms:
            docs, s = self._term(int(t), rnd)
            total[docs] = rnd(total[docs] + s)
            matched[docs] = True
        return total, matched

    def matched(self, terms) -> np.ndarray:
        out = np.zeros(self.field.n_docs, bool)
        for t in terms:
            out[self.field.postings(int(t))[0]] = True
        return out


def top_k(scores: np.ndarray, matched: np.ndarray, k: int):
    """Ids and scores of a top-k, best first (ties by id, as a merge by
    (score desc, doc asc) gives)."""
    n = int(matched.sum())
    k = min(k, n)
    if k == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.float32)
    ref = np.where(matched, scores, -np.inf)
    cand = np.argpartition(-ref, k - 1)[:k]
    order = np.lexsort((cand, -ref[cand]))
    ids = cand[order]
    return ids.astype(np.int64), ref[ids].astype(np.float32)


def bucket_counts(spec: dict, columns: dict, matched: np.ndarray) -> dict:
    """Reference buckets {key: count > 0} of one aggregation."""
    col = columns[spec["column"]][matched]
    if spec["kind"] == "date_histogram_hour":
        col = col // HOUR_MS * HOUR_MS
    elif spec["kind"] != "terms":
        raise ValueError(f"no reference for aggregation {spec['kind']!r}")
    keys, counts = np.unique(col, return_counts=True)
    return {int(k): int(c) for k, c in zip(keys, counts)}


# ----------------------------------------------------------------------
# The comparison of one answer
# ----------------------------------------------------------------------


def compare_hits(cmp, what: str, answer: dict,
                 scores: np.ndarray, matched: np.ndarray, size: int,
                 check_scores: bool = True) -> None:
    """One ``_search`` answer against the reference: ``hits.total``
    equal; the hits distinct, matching, in order, as many as due; each
    score that of the same document in the reference; the top-k a top-k
    of the reference (ids free only among scores tied within the
    limit)."""
    cmp.compared += 1
    n_match = int(matched.sum())
    cmp.note("total_abs_diff", abs(answer["total"] - n_match), what)
    ids = np.asarray(answer["ids"], np.int64)
    got = np.asarray(answer["scores"], np.float32)
    k = min(size, n_match)
    bad = abs(len(ids) - k) + (len(ids) - len(set(ids.tolist())))
    in_range = (ids >= 0) & (ids < len(matched))
    bad += int((~in_range).sum())
    ids, got = ids[in_range], got[in_range]
    bad += int((~matched[ids]).sum())
    bad += int((np.diff(got) > 0).sum())
    cmp.note("bad_hits", bad, what)
    if not check_scores or not len(ids):
        return
    keep = matched[ids]
    own = scores[ids[keep]]
    if len(own):
        cmp.note("score_rel_err",
                 float(np.max(np.abs(got[keep] - own) / np.abs(own))), what)
    _, ref_top = top_k(scores, matched, k)
    m = min(len(ref_top), len(got))
    if m:
        cmp.note("rank_rel_err", float(np.max(
            np.abs(got[:m] - ref_top[:m]) / np.abs(ref_top[:m]))), what)


def compare_buckets(cmp, what: str, got: dict, ref: dict) -> None:
    """Bucket counts equal; empty buckets count as absent."""
    keys = set(got) | set(ref)
    diff = max((abs(got.get(k, 0) - ref.get(k, 0)) for k in keys), default=0)
    cmp.note("bucket_abs_diff", diff, what)


# ----------------------------------------------------------------------
# What the harness drives
# ----------------------------------------------------------------------


class Reference:
    """``match`` requests on one view: ``ref`` holds ``field``,
    ``terms``, ``size`` and ``aggs`` ({name: {kind, column}})."""

    controls = ("bfloat16",)

    def __init__(self, view: dict, config: dict):
        self.view = view
        self.n_shards = int(view["shard"].max()) + 1
        self._bm25 = {}
        self._last = (None, None)

    def _field(self, name: str) -> Bm25:
        if name not in self._bm25:
            self._bm25[name] = Bm25(self.view["text_fields"][name],
                                    self.view["shard"], self.n_shards)
        return self._bm25[name]

    def matched(self, ref: dict) -> np.ndarray:
        return self._field(ref["field"]).matched(ref["terms"])

    def buckets(self, ref: dict, matched: np.ndarray) -> dict:
        return {name: bucket_counts(spec, self.view["columns"], matched)
                for name, spec in ref["aggs"].items()}

    def _expected(self, ref: dict):
        """(scores, matched, buckets) of one request; the last one is
        kept, since a window asks the same request more than once."""
        if self._last[0] is not ref:
            scores, matched = self._field(ref["field"]).match(ref["terms"])
            self._last = (ref, (scores, matched, self.buckets(ref, matched)))
        return self._last[1]

    def compare(self, cmp, what: str, answer: dict, ref: dict,
                control=None, among=None) -> None:
        if among is not None:
            compare_hits(cmp, what, answer, None, among, ref["size"],
                         check_scores=False)
            buckets = self.buckets(ref, among)
        else:
            scores, matched, buckets = self._expected(ref)
            if control:
                low, _ = self._field(ref["field"]).match(ref["terms"],
                                                         precision=control)
                ids, top = top_k(low, matched, ref["size"])
                answer = {"total": int(matched.sum()), "ids": ids.tolist(),
                          "scores": top.tolist(), "aggs": buckets}
            compare_hits(cmp, what, answer, scores, matched, ref["size"])
        for name, want in buckets.items():
            compare_buckets(cmp, f"{what}.{name}",
                            answer["aggs"].get(name, {}), want)

    def work(self, ref: dict) -> dict:
        """A ``match`` query's postings, read once: the sum over its
        terms of the term's document frequency in the corpus, times 8
        bytes, and one f32 add a posting. The adds are held against the
        chip's highest published floating-point rate, which no f32 rate
        exceeds, so the bound stays a least time; the bytes set it."""
        n = int(self.view["text_fields"][ref["field"]].doc_freq(
            ref["terms"]).sum())
        return {"bytes": n * POSTING_BYTES, "flops": n,
                "peak": "bf16_flops_per_s"}
