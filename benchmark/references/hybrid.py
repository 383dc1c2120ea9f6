"""The plain reference of hybrid search: a ``match`` query and a ``knn``
section in one ``_search``, fused as Elasticsearch 8 documents it
(guide, *kNN search*, *Combine approximate kNN with other features*):
the ``k`` global nearest neighbours join the query's matches as a
disjunction, a hit scores its query score plus ``knn.boost`` times its
kNN score, and ``hits.total`` counts the union.

Built from the two plain references that are here, and nothing else:
``bm25.py`` scores the text (numpy BM25 with per-shard statistics, in
float32 as the configuration states its scores) and ``knn_exact.py``
the vectors (float64 inner products over the same vectors rounded to
bfloat16, ``max_inner_product``'s score). The union, the fused scores in
float64, the exact union count and the top-``size`` are computed here.
Nothing here imports the program or takes anything the program has made.

At the ``k``-th nearest neighbour a document whose similarity lies
within ``KNN_TIE`` (relative) of the ``k``-th's may take either side:
the answer is held to the best of those choices. Ids are free only among
fused scores tied within the limit (``bm25.compare_hits``).

Controls, each of which has to come out ``correct: false``:

- ``bfloat16``: the BM25 part in bfloat16 (``bm25``'s control);
- ``bfloat16_products``: the kNN part from bfloat16 products
  (``knn_exact``'s control: the query rounded too, products summed in
  float32), its winners and scores;
- ``lexical_window``: the fusion of two separate top lists, the
  lexical side cut at ``max(size, k)`` hits, so that a winner past it
  scores its kNN part alone, and the larger of the two sides' totals;
- ``no_knn``: the kNN side left out.
"""

from __future__ import annotations

import itertools

import numpy as np

from references import bm25, knn_exact

# relative gap within which two similarities tie at the k-th neighbour:
# the limit cohere-768-knn holds a kNN score to
KNN_TIE = 1e-5


class Reference:
    """Hybrid requests on one view: ``ref`` holds ``field``, ``terms``,
    ``n`` (which query vector), ``k``, ``boost`` and ``size``."""

    controls = ("bfloat16", "bfloat16_products", "lexical_window", "no_knn")

    def __init__(self, view: dict, config: dict):
        self.text = bm25.Reference(view, config)
        self.knn = knn_exact.Reference(view, config)
        self.n_docs = self.knn.n_docs
        self._last = (None, None)

    # -- the two sides ---------------------------------------------------

    def _lexical(self, ref: dict, precision: str = "float32"):
        return self.text._field(ref["field"]).match(ref["terms"],
                                                    precision=precision)

    def _winner_sets(self, ref: dict, precision=None) -> list:
        """Every set of ``k`` winners the answer may hold, as
        ``(ids, similarities)``: one, unless documents tie at the k-th."""
        ids, sims = (a[ref["n"]] for a in self.knn.top(precision))
        k = min(int(ref["k"]), len(ids))
        if k == 0:
            return [(ids[:0], sims[:0])]
        edge = sims[k - 1]
        band = np.abs(sims - edge) <= KNN_TIE * abs(edge)
        sure = np.flatnonzero(~band[:k])
        tied = np.flatnonzero(band)
        return [(ids[np.r_[sure, list(c)].astype(np.int64)],
                 sims[np.r_[sure, list(c)].astype(np.int64)])
                for c in itertools.combinations(tied, k - len(sure))]

    @staticmethod
    def _fuse(scores, matched, winners, boost):
        """(fused scores float32 [docs], the union [docs])."""
        ids, sims = winners
        fused = np.where(matched, scores, 0.0).astype(np.float64)
        fused[ids] += boost * knn_exact.score_of(sims)
        union = matched.copy()
        union[ids] = True
        return fused.astype(np.float32), union

    def _answer_of(self, ref, scores, matched, winners, total=None):
        fused, union = self._fuse(scores, matched, winners, ref["boost"])
        ids, top = bm25.top_k(fused, union, ref["size"])
        return {"total": int(union.sum()) if total is None else total,
                "ids": ids.tolist(), "scores": top.tolist()}

    def _control(self, ref: dict, control: str) -> dict:
        """The answer the control gives in the program's place."""
        if control == "bfloat16":
            scores, matched = self._lexical(ref, "bfloat16")
            return self._answer_of(ref, scores, matched,
                                   self._winner_sets(ref)[0])
        scores, matched = self._lexical(ref)
        if control == "bfloat16_products":
            return self._answer_of(ref, scores, matched, self._winner_sets(
                ref, "bfloat16_products")[0])
        if control == "no_knn":
            ids, top = bm25.top_k(scores, matched, ref["size"])
            return {"total": int(matched.sum()), "ids": ids.tolist(),
                    "scores": top.tolist()}
        if control == "lexical_window":
            window, _ = bm25.top_k(scores, matched,
                                   max(ref["size"], ref["k"]))
            kept = np.zeros_like(matched)
            kept[window] = True
            return self._answer_of(
                ref, scores, kept, self._winner_sets(ref)[0],
                total=max(int(matched.sum()), self.n_docs))
        raise ValueError(f"no control {control!r}")

    # -- what the harness drives ---------------------------------------

    def compare(self, cmp, what: str, answer: dict, ref: dict,
                control=None, among=None) -> None:
        """One ``_search`` answer against the reference: ``hits.total``
        the union's count (rendered ``{"value", "relation"}`` or bare);
        the hits distinct, in the union, in order, as many as due; each
        score the fused score of the same document; the top-``size`` a
        top-``size`` of the reference rank by rank."""
        if control:
            answer = self._control(ref, control)
        total = answer["total"]
        answer = dict(answer, total=int(
            total["value"] if isinstance(total, dict) else total))
        if self._last[0] is not ref:  # a window asks a request many times
            self._last = (ref, (self._lexical(ref), self._winner_sets(ref)))
        (scores, matched), winner_sets = self._last[1]
        best = None
        for winners in winner_sets:
            fused, union = self._fuse(scores, matched, winners, ref["boost"])
            trial = type(cmp)(cmp.limits)
            bm25.compare_hits(trial, what, answer, fused, union, ref["size"])
            reading = tuple(trial.worst.get(n, 0.0) for n in (
                "total_abs_diff", "bad_hits", "rank_rel_err",
                "score_rel_err"))
            if best is None or reading < best[0]:
                best = (reading, trial)
        cmp.compared += 1
        for name, value in best[1].worst.items():
            if name in best[1].where:
                cmp.note(name, value, what)

    def work(self, ref: dict) -> dict:
        """The request's work as stated, the same whatever implements it:
        the match's postings read once (``bm25``: document frequencies x
        8 bytes, an add a posting) and every document's vector once, at
        the 2 bytes of its bfloat16 components, a multiply and an add a
        component (``knn_exact``). The operations are held against the
        chip's bfloat16 rate, which no float32 rate exceeds, so the bound
        stays a least time; at one query at a time the bytes set it."""
        text, vec = self.text.work(ref), self.knn.work(ref)
        return {"bytes": text["bytes"] + vec["bytes"],
                "flops": text["flops"] + vec["flops"],
                "peak": "bf16_flops_per_s"}
