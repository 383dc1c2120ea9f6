"""The plain reference of exact vector search: the top-k of inner
products between one query vector and every document's vector, scored
as Elasticsearch scores ``max_inner_product``.

The deployment stores its vectors in bfloat16 and multiplies and sums in
float32 (the configuration states it). The reference states the same
storage and does the arithmetic a step ABOVE the program's: every
document vector is rounded to bfloat16 by the reference's own arithmetic
(round to nearest even on the float32 bits, no library's rounding), the
query stays the float32 it was sent as, and products and sums are
float64, in blocks of documents. The score of a similarity ``s`` is
``1 / (1 - s)`` below zero and ``s + 1`` from it; the top-k is exact, by
(score descending, shard, document); ``hits.total`` is the number of
documents that carry the field, which here is all of them.

Nothing here imports the program or takes anything the program has made.
The view (``generators/cohere_vector.py``) gives ``vectors`` [n, dims]
and ``queries`` [m, dims] in float32 and the documents' ``shard``; a
request's ``ref`` holds ``n`` (which query) and ``size``.

Controls, each a precision below the one stated, that have to come out
``correct: false``:

- ``bfloat16_products``: the query rounded to bfloat16 too and every
  product taken once, bfloat16 by bfloat16 summed in float32: what a
  matrix unit gives at its default precision;
- ``int8_vectors``: every document quantised to int8 with one scale a
  vector (the ``int8_flat`` of later Elasticsearch versions), products
  and sums exact.
"""

from __future__ import annotations

import numpy as np

BLOCK = 8192  # documents a block of the scan
KEEP = 32  # candidates kept a query: more than any size asked for


def bf16(x) -> np.ndarray:
    """Round f32 to bfloat16 (nearest even), returned as f32."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1)))
    return (u & np.uint64(0xFFFF0000)).astype(np.uint32).view(np.float32)


def int8_rows(x: np.ndarray) -> np.ndarray:
    """Each row as int8 with one scale (its largest magnitude over 127),
    returned as the float32 values the quantised row stands for."""
    scale = np.abs(x).max(axis=1, keepdims=True) / 127.0
    scale = np.where(scale > 0, scale, 1.0)
    return (np.clip(np.rint(x / scale), -127, 127) * scale).astype(np.float32)


def score_of(sim: np.ndarray) -> np.ndarray:
    """Elasticsearch's ``max_inner_product`` score, in float64."""
    sim = np.asarray(sim, np.float64)
    return np.where(sim < 0, 1.0 / (1.0 - np.minimum(sim, 0.0)), sim + 1.0)


class Reference:
    """``knn`` requests on one view, answered for all the seed's queries
    in one scan of the documents (a window asks each many times)."""

    controls = ("bfloat16_products", "int8_vectors")

    def __init__(self, view: dict, config: dict):
        self.vectors = np.asarray(view["vectors"], np.float32)
        self.queries = np.asarray(view["queries"], np.float32)
        self.shard = np.asarray(view["shard"], np.int64)
        self.n_docs, self.dims = self.vectors.shape
        self._top = {}

    # -- the arithmetic, by precision ----------------------------------

    def _operands(self, precision):
        """(how a block of documents is held, the queries [m, dims], the
        dtype products are summed in)."""
        if precision is None:
            return bf16, self.queries.astype(np.float64), np.float64
        if precision == "bfloat16_products":
            return bf16, bf16(self.queries), np.float32
        if precision == "int8_vectors":
            return int8_rows, self.queries.astype(np.float64), np.float64
        raise ValueError(f"no control {precision!r}")

    def similarities(self, docs: np.ndarray, n: int,
                     precision=None) -> np.ndarray:
        """float64 inner products of query ``n`` and documents ``docs``."""
        hold, queries, acc = self._operands(precision)
        return (hold(self.vectors[docs]).astype(acc)
                @ queries[n].astype(acc)).astype(np.float64)

    def top(self, precision=None):
        """(ids [m, KEEP], sims [m, KEEP]) of every query: the exact
        order by (similarity descending, shard, document)."""
        if precision not in self._top:
            hold, queries, acc = self._operands(precision)
            q_t = np.ascontiguousarray(queries.T.astype(acc))
            m = len(queries)
            best_s = np.full((m, 0), -np.inf)
            best_i = np.zeros((m, 0), np.int64)
            for lo in range(0, self.n_docs, BLOCK):
                block = hold(self.vectors[lo: lo + BLOCK]).astype(acc)
                sims = (block @ q_t).astype(np.float64).T  # [m, block]
                ids = np.broadcast_to(
                    np.arange(lo, lo + sims.shape[1]), sims.shape)
                best_s = np.concatenate([best_s, sims], axis=1)
                best_i = np.concatenate([best_i, ids], axis=1)
                if best_s.shape[1] > KEEP:  # a similarity tied at the
                    # cut with one kept is not resolved by shard here:
                    # float64 sums of 768 random products do not tie
                    part = np.argpartition(-best_s, KEEP - 1, axis=1)[:, :KEEP]
                    best_s = np.take_along_axis(best_s, part, axis=1)
                    best_i = np.take_along_axis(best_i, part, axis=1)
            order = np.stack([
                np.lexsort((best_i[q], self.shard[best_i[q]], -best_s[q]))
                for q in range(m)])
            self._top[precision] = (
                np.take_along_axis(best_i, order, axis=1),
                np.take_along_axis(best_s, order, axis=1))
        return self._top[precision]

    # -- what the harness drives ---------------------------------------

    def compare(self, cmp, what: str, answer: dict, ref: dict,
                control=None, among=None) -> None:
        """One ``_search`` answer against the reference: ``hits.total``
        equal; the hits distinct documents, in descending order, as many
        as due; each score that of the same document in the reference;
        the top-k a top-k of the reference, rank by rank (ids free only
        among scores tied within the limit)."""
        n, size = ref["n"], ref["size"]
        k = min(size, self.n_docs)
        ref_ids, ref_sims = self.top()
        if control:
            ids, sims = self.top(control)
            answer = {"total": self.n_docs, "ids": ids[n][:k].tolist(),
                      "scores": score_of(sims[n][:k]).tolist()}
        cmp.compared += 1
        cmp.note("total_abs_diff", abs(answer["total"] - self.n_docs), what)
        ids = np.asarray(answer["ids"], np.int64)
        got = np.asarray([np.nan if s is None else s
                          for s in answer["scores"]], np.float64)
        bad = abs(len(ids) - k) + (len(ids) - len(set(ids.tolist())))
        in_range = (ids >= 0) & (ids < self.n_docs)
        bad += int((~in_range).sum())
        ids, got = ids[in_range], got[in_range]
        bad += int((np.diff(got) > 0).sum()) + int(np.isnan(got).sum())
        cmp.note("bad_hits", bad, what)
        if not len(ids):
            return
        own = score_of(self.similarities(ids, n))
        cmp.note("score_rel_err",
                 float(np.max(np.abs(got - own) / np.abs(own))), what)
        want = score_of(ref_sims[n][:k])
        m = min(len(want), len(got))
        cmp.note("rank_rel_err", float(np.max(
            np.abs(got[:m] - want[:m]) / np.abs(want[:m]))), what)

    def work(self, ref: dict) -> dict:
        """The deployment's work as stated, the same whatever implements
        it and however many padded rows or dead slots a program reads:
        every document's vector once, at the 2 bytes a component it is
        stored in, and a multiply and an add a component, held against
        the chip's bfloat16 rate (its highest floating-point rate, which
        no float32 rate exceeds, so the bound stays a least time; at one
        query at a time the bytes set it)."""
        return {"bytes": self.n_docs * self.dims * 2,
                "flops": 2 * self.n_docs * self.dims,
                "peak": "bf16_flops_per_s"}
