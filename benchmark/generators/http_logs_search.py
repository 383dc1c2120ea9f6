"""The search operations of Rally's http_logs track that score no text,
over the corpus of ``generators/http_logs.py``: ``range``,
``200s-in-range``, ``400s-in-range`` (here 404: the corpus draws 200,
304, 404, 206, 500), ``hourly_agg``, ``desc_sort_timestamp`` and
``asc_sort_timestamp``, and the panel a dashboard puts under its time
picker (the hourly histogram and ``terms(status)`` under a time range).

The corpus is the parent class's, document for document; this module
adds only requests. One operation, ``logs_search``: ``requests`` a seed,
mixed in the shares of ``generator_params.shares``. The time ranges come
from the seed: start uniform over the base's days, width uniform from
``range_width_min_ms`` to the base's whole span, and each bound, with
probability ``bounds_on_a_document``, moved onto a document's own
timestamp, so that a comparison in a lower precision moves a total.
Timestamps are sent and queried as epoch milliseconds.
"""

from __future__ import annotations

from generators import http_logs
from harness.corpus import rng_for

HOURS = {"date_histogram": {"field": "@timestamp", "interval": "hour"}}


class Dataset(http_logs.Dataset):
    def __init__(self, config: dict, seed: int, n_shards: int):
        super().__init__(config, seed, n_shards)
        self._requests = self._draw(rng_for(seed, 4),
                                    config["generator_params"])

    def _time_range(self, rng, p) -> list:
        """[gte, lt) in epoch milliseconds."""
        first = int(self.timestamp[0])
        span = p["base_days"] * 86_400_000
        step = int(self.timestamp[1] - self.timestamp[0])
        lo = first + int(rng.randint(0, span))
        hi = lo + int(rng.randint(p["range_width_min_ms"], span + 1))
        out = []
        for bound in (lo, hi):
            if rng.random_sample() < p["bounds_on_a_document"]:
                k = min(max((bound - first + step // 2) // step, 0),
                        self.n_docs - 1)
                bound = first + k * step
            out.append(bound)
        return out

    def _draw(self, rng, p) -> list:
        path = f"/{self.index}/_search?request_cache=false"
        n = int(p["requests"])
        counts = {kind: int(round(share * n))
                  for kind, share in p["shares"].items()}
        assert sum(counts.values()) == n, counts
        out = []

        def add(kind, body, **ref):
            ref = {"kind": kind, "n": len(out), "range": None,
                   "status": None, "sort": None, "size": 10, "aggs": {},
                   **ref}
            out.append({"method": "POST", "path": path, "body": body,
                        "ref": ref})

        def between(r):
            return {"range": {"@timestamp": {"gte": r[0], "lt": r[1]}}}

        hours = {"kind": "date_histogram_hour", "column": "@timestamp"}
        for _ in range(counts["hourly_agg"]):  # Rally's, verbatim
            add("hourly_agg", {"size": 0, "aggs": {"by_hour": HOURS}},
                size=0, aggs={"by_hour": hours})
        for _ in range(counts["panel"]):
            r = self._time_range(rng, p)
            add("panel", {"size": 0, "query": between(r), "aggs": {
                "by_hour": HOURS,
                "status": {"terms": {"field": "status"}}}},
                range=r, size=0, aggs={
                    "by_hour": hours,
                    "status": {"kind": "terms", "column": "status"}})
        for _ in range(counts["range"]):
            r = self._time_range(rng, p)
            add("range", {"query": between(r)}, range=r)
        for kind, status in (("200s-in-range", 200), ("404s-in-range", 404)):
            for _ in range(counts[kind]):
                r = self._time_range(rng, p)
                add(kind, {"query": {"bool": {"must": [
                    between(r), {"match": {"status": status}}]}}},
                    range=r, status=status)
        for order in ("desc", "asc"):  # Rally's, verbatim
            for _ in range(counts[f"{order}_sort_timestamp"]):
                add(f"{order}_sort_timestamp", {
                    "query": {"match_all": {}},
                    "sort": [{"@timestamp": order}]}, sort=order)
        return out

    def operations(self) -> dict:
        return {"logs_search": self._requests}
