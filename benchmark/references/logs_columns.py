"""The plain reference of log search and dashboards: requests that score
no text, answered from the view's ``columns`` alone, in int64.

A request's ``ref`` (``generators/http_logs_search.py``) holds ``range``
([gte, lt) on ``@timestamp``, or None), ``status`` (an integer the
``status`` column has to equal, or None), ``sort`` (``asc``/``desc`` by
``@timestamp``, or None), ``size`` and ``aggs``. The expected answer:

- matched: ``gte <= @timestamp < lt`` and ``status == s``, whichever the
  request has; every document where it has neither;
- scores as Lucene gives them: 1.0 for every constant-scoring clause of
  a ``must`` (a range, a term on a number), summed; ``match_all`` 1.0;
- hits: without a sort the top ``size`` by score, where every matching
  document ties, so any ``size`` distinct matching documents will do;
  with a sort the first ``size`` by (``@timestamp``, shard, document)
  exactly (descending: ``@timestamp`` descending, ties still ascending);
- buckets: counts of ``date_histogram(hour)`` (key: the hour's first
  millisecond, UTC) and ``terms(status)`` over the matched documents.

Nothing here imports the program or takes anything the program has made.
The control ``float32_dates`` is the same with timestamps and bounds
rounded to float32, the nearest precision below the float64 (exact for
epoch milliseconds) the program states: 65,536 ms at 1998's epoch.
"""

from __future__ import annotations

import numpy as np

HOUR_MS = 3_600_000
WIDTH = {"@timestamp": 8, "status": 4, "bucket_code": 4}  # bytes a document


def _f32(x):
    """Epoch milliseconds as float32 holds them (returned as float64)."""
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


class Reference:
    controls = ("float32_dates",)

    def __init__(self, view: dict, config: dict):
        cols = view["columns"]
        self.ts = np.asarray(cols["@timestamp"], np.int64)
        self.status = np.asarray(cols["status"], np.int64)
        self.shard = np.asarray(view["shard"], np.int64)
        self.n_docs = len(self.ts)
        self._kept = {}

    # -- the expected answer -------------------------------------------

    def matched(self, ref: dict, ts=None, rnd=lambda x: x) -> np.ndarray:
        ts = self.ts if ts is None else ts
        out = np.ones(self.n_docs, bool)
        if ref["range"] is not None:
            gte, lt = (rnd(b) for b in ref["range"])
            out &= (ts >= gte) & (ts < lt)
        if ref["status"] is not None:
            out &= self.status == ref["status"]
        return out

    def score(self, ref: dict) -> float:
        clauses = (ref["range"] is not None) + (ref["status"] is not None)
        return float(max(clauses, 1))

    def buckets(self, ref: dict, matched: np.ndarray, ts=None) -> dict:
        ts = self.ts if ts is None else ts
        out = {}
        for name, spec in ref["aggs"].items():
            if spec["kind"] == "date_histogram_hour":
                col = np.floor_divide(ts[matched], HOUR_MS).astype(
                    np.int64) * HOUR_MS
            elif spec["kind"] == "terms":
                col = self.status[matched]
            else:
                raise ValueError(f"no reference for {spec['kind']!r}")
            keys, counts = np.unique(col, return_counts=True)
            out[name] = {int(k): int(c) for k, c in zip(keys, counts)}
        return out

    def sorted_ids(self, ref: dict, matched: np.ndarray, ts=None):
        """The first ``size`` matching documents by (@timestamp in the
        request's order, shard, document)."""
        ts = self.ts if ts is None else ts
        ids = np.flatnonzero(matched)
        key = ts[ids] if ref["sort"] == "asc" else -ts[ids]
        order = np.lexsort((ids, self.shard[ids], key))
        return ids[order[: ref["size"]]]

    def _expected(self, ref: dict, control=None) -> dict:
        kept = self._kept.get((ref["n"], control))
        if kept is None:
            ts, rnd = (_f32(self.ts), _f32) if control else (self.ts, int)
            matched = self.matched(ref, ts, rnd)
            kept = {"matched": matched if ref["size"] else None,
                    "total": int(matched.sum()),
                    "buckets": self.buckets(ref, matched, ts),
                    "ids": (self.sorted_ids(ref, matched, ts)
                            if ref["sort"] else None)}
            self._kept[(ref["n"], control)] = kept
        return kept

    # -- what the harness drives ---------------------------------------

    def answer(self, ref: dict, control=None) -> dict:
        """What the reference itself answers (``control``: in that lower
        precision), in the form of a reply the harness has read."""
        want = self._expected(ref, control)
        k = min(ref["size"], want["total"])
        ids = (want["ids"] if ref["sort"] else np.flatnonzero(
            want["matched"])[:k] if k else [])
        return {"total": want["total"], "ids": [int(i) for i in ids],
                "scores": [None if ref["sort"] else self.score(ref)]
                * len(ids), "aggs": want["buckets"]}

    def compare(self, cmp, what: str, answer: dict, ref: dict,
                control=None, among=None) -> None:
        want = self._expected(ref)
        if control:
            answer = self.answer(ref, control)
        cmp.compared += 1
        cmp.note("total_abs_diff", abs(answer["total"] - want["total"]), what)
        ids = np.asarray(answer["ids"], np.int64)
        k = min(ref["size"], want["total"])
        bad = abs(len(ids) - k) + (len(ids) - len(set(ids.tolist())))
        in_range = (ids >= 0) & (ids < self.n_docs)
        bad += int((~in_range).sum())
        if want["matched"] is not None:
            bad += int((~want["matched"][ids[in_range]]).sum())
        if ref["sort"]:
            m = min(len(ids), len(want["ids"]))
            bad += int((ids[:m] != want["ids"][:m]).sum())
        elif len(ids):
            got = np.asarray([np.nan if s is None else s
                              for s in answer["scores"]], np.float64)
            err = np.abs(got - self.score(ref)) / self.score(ref)
            err = float(np.max(np.where(np.isnan(err), np.inf, err)))
            cmp.note("score_rel_err", err, what)
            cmp.note("rank_rel_err", err, what)  # every match ties
        cmp.note("bad_hits", bad, what)
        for name, counts in want["buckets"].items():
            got = answer["aggs"].get(name, {})
            diff = max((abs(got.get(key, 0) - counts.get(key, 0))
                        for key in set(got) | set(counts)), default=0)
            cmp.note("bucket_abs_diff", diff, f"{what}.{name}")

    def work(self, ref: dict) -> dict:
        """The least one request has to read: every document's value of
        each column it filters, sorts or buckets by, once (8 bytes a
        timestamp, 4 a status or a bucket code), and one operation a
        document a column. The operations are integer comparisons and
        counts, held against the chip's highest published integer rate,
        which no int32 or int64 rate exceeds; the bytes set the bound."""
        columns = []
        if ref["range"] is not None or ref["sort"]:
            columns.append("@timestamp")
        if ref["status"] is not None:
            columns.append("status")
        for spec in ref["aggs"].values():
            columns.append("status" if spec["kind"] == "terms"
                           else "bucket_code")
        return {"bytes": self.n_docs * sum(WIDTH[c] for c in columns),
                "flops": self.n_docs * len(columns),
                "peak": "int8_ops_per_s"}
