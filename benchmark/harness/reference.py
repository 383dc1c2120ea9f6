"""The plain reference and the comparison that decides ``correct``.

A numpy BM25 (Lucene's idf, k1 1.2, b 0.75, exact f32 lengths) with one
set of statistics per shard: the engine scores with its segment's
df / doc count / avgdl and runs no DFS round, and a base index holds one
segment per shard. Bucket counts for the aggregations are plain numpy
counts over the matched documents. Nothing here imports the program or
takes anything the program has made.

Every number compared has a limit of its own (``Limits``); the readings
the limits were set from are in PERF.md section 2.
"""

from __future__ import annotations

import math

import numpy as np

from harness.corpus import TextField

K1, B = 1.2, 0.75
HOUR_MS = 3_600_000


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
# The comparison
# ----------------------------------------------------------------------


class Comparison:
    """Worst reading of every number compared, over all answers.

    ``limits`` maps a number's name to its limit; a number above its
    limit makes the run not correct. Names not in ``limits`` are
    refused: a number is never compared without a limit of its own."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.worst = {name: 0.0 for name in limits}
        self.where = {}
        self.compared = 0

    def note(self, name: str, value: float, what: str) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for compared number {name!r}")
        if not value <= self.worst[name]:  # NaN counts as worse
            self.worst[name] = float(value) if value == value else math.inf
            self.where[name] = what

    def numbers(self) -> dict:
        """{name: {"value", "limit"}}; ``nothing_compared`` guards a run
        whose window returned no answer to compare."""
        out = {name: {"value": self.worst[name], "limit": self.limits[name]}
               for name in self.limits}
        out["nothing_compared"] = {
            "value": 0 if self.compared else 1, "limit": 0}
        return out

    def correct(self) -> bool:
        return all(n["value"] <= n["limit"] for n in self.numbers().values())


def compare_hits(cmp: Comparison, what: str, answer: dict,
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


def compare_buckets(cmp: Comparison, what: str, got: dict, ref: dict) -> None:
    """Bucket counts equal; empty buckets count as absent."""
    keys = set(got) | set(ref)
    diff = max((abs(got.get(k, 0) - ref.get(k, 0)) for k in keys), default=0)
    cmp.note("bucket_abs_diff", diff, what)


def compare_between(cmp: Comparison, name: str, what: str, got: dict,
                    lo: dict, hi: dict) -> None:
    """Counts that a reader under ingest may have seen: no fewer than
    before the first append, no more than after the last."""
    out = 0
    for k in set(got) | set(lo):
        v = got.get(k, 0)
        out = max(out, lo.get(k, 0) - v, v - hi.get(k, 0))
    cmp.note(name, max(out, 0), what)
