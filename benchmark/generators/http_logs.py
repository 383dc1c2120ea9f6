"""Rally's http_logs track (1998 World Cup web server logs), generated
to its shape: @timestamp ascending at a steady rate, clientip zipfian
over a fixed set of clients, request ``GET /w3/w17/w1234 HTTP`` with
2-7 zipfian path words, status from a fixed mix, size lognormal.

Documents [0, docs) are the base index; [docs, docs + append_pool) are
the stream the append clients send, in timestamp order. The protocol
version is left off the request line: see PERF.md, Open questions.

As in ``msmarco_passage``, the structure (which path-word rank, client
rank, status and size stands where) comes from ``structure_seed``; the
``--seed`` names the path words and the clients (permutations of the
labels) and draws the dashboard's queries.
"""

from __future__ import annotations

import numpy as np

from harness.corpus import TextField, rng_for, shard_of_ids, zipf_probs


class Dataset:
    def __init__(self, config: dict, seed: int, n_shards: int):
        p = config["generator_params"]
        self.index = config["index"]
        self.n_docs = int(config["docs"])
        self.append_pool = int(p["append_pool_docs"])
        n = self.n_docs + self.append_pool
        rng = rng_for(p["structure_seed"], 1)
        step_ms = p["base_days"] * 86_400_000 // self.n_docs
        self.timestamp = (p["start_epoch_ms"]
                          + np.arange(n, dtype=np.int64) * step_ms)
        names = rng_for(seed, 3)
        self.client = names.permutation(p["clients"])[rng.choice(
            p["clients"], n, p=zipf_probs(p["clients"]))].astype(np.int64)
        codes = np.asarray(p["status_codes"], np.int64)
        self.status = codes[rng.choice(
            len(codes), n, p=np.asarray(p["status_shares"]))]
        self.size = np.where(
            self.status == 304, 0,
            rng.lognormal(np.log(p["size_median"]), p["size_sigma"], n)
        ).astype(np.int64)
        n_words = p["path_words"]
        self.word = [f"w{i}" for i in names.permutation(n_words)] \
            + ["get", "http"]
        get, http = n_words, n_words + 1
        segs = rng.randint(p["path_segments_min"],
                           p["path_segments_max"] + 1, n)
        self.path_probs = zipf_probs(n_words)
        path = rng.choice(n_words, int(segs.sum()), p=self.path_probs)
        doc_len = segs + 2
        tokens = np.empty(int(doc_len.sum()), np.int64)
        ends = np.cumsum(doc_len)
        starts = ends - doc_len
        tokens[starts] = get
        tokens[ends - 1] = http
        inner = np.ones(len(tokens), bool)
        inner[starts] = inner[ends - 1] = False
        tokens[inner] = path
        self._all = TextField(tokens, doc_len, n_words + 2)
        self.n_shards = n_shards
        self._shard_all = shard_of_ids(np.arange(n), n_shards)
        self._queries(rng_for(seed, 2), p)

    # the reference sees the base, or the base with appended documents
    def view(self, n: int) -> dict:
        """Fields, columns and routing of documents [0, n)."""
        f = self._all
        return {
            "text_fields": {"request": TextField(
                f.tokens[: f.ends[n - 1]], f.doc_len[:n], f.vocab)},
            "columns": {"@timestamp": self.timestamp[:n],
                        "status": self.status[:n], "size": self.size[:n]},
            "shard": self._shard_all[:n]}

    @property
    def text_fields(self):
        return self.view(self.n_docs)["text_fields"]

    @property
    def columns(self):
        return self.view(self.n_docs)["columns"]

    @property
    def shard(self):
        return self._shard_all[: self.n_docs]

    def source(self, i: int) -> dict:
        toks = self._all.doc_tokens(i)
        c = int(self.client[i])
        return {"@timestamp": int(self.timestamp[i]),
                "clientip": f"10.{c >> 16 & 255}.{c >> 8 & 255}.{c & 255}",
                "request": "GET /" + "/".join(
                    self.word[t] for t in toks[1:-1]) + " HTTP",
                "status": int(self.status[i]), "size": int(self.size[i])}

    def bulk_body(self, lo: int, hi: int) -> str:
        lines = []
        for i in range(lo, hi):
            s = self.source(i)
            lines.append('{"index":{"_type":"_doc","_id":"%d"}}' % i)
            lines.append(
                '{"@timestamp":%d,"clientip":"%s","request":"%s",'
                '"status":%d,"size":%d}' % (
                    s["@timestamp"], s["clientip"], s["request"],
                    s["status"], s["size"]))
        return "\n".join(lines) + "\n"

    def _queries(self, rng, p) -> None:
        n_words = p["path_words"]
        self.match_terms = [
            sorted(set(rng.choice(p["query_word_ranks"], 2).tolist()))
            for _ in range(p["queries"])]
        self.agg_terms = [[int(t)] for t in rng.choice(
            p["query_word_ranks"], p["queries"])]
        assert p["query_word_ranks"] <= n_words

    def operations(self) -> dict:
        def text(terms):
            return " ".join(self.word[t] for t in terms)

        path = f"/{self.index}/_search"
        match = [{
            "method": "POST", "path": path,
            "body": {"query": {"match": {"request": text(terms)}},
                     "size": 10},
            "ref": {"kind": "match", "field": "request", "terms": terms,
                    "size": 10, "aggs": {}},
        } for terms in self.match_terms]
        hourly = [{
            "method": "POST", "path": path + "?request_cache=false",
            "body": {"size": 0,
                     "query": {"match": {"request": text(terms)}},
                     "aggs": {
                         "hours": {"date_histogram": {
                             "field": "@timestamp", "interval": "hour"}},
                         "status": {"terms": {"field": "status"}}}},
            "ref": {"kind": "match", "field": "request", "terms": terms,
                    "size": 0, "aggs": {
                        "hours": {"kind": "date_histogram_hour",
                                  "column": "@timestamp"},
                        "status": {"kind": "terms", "column": "status"}}},
        } for terms in self.agg_terms]
        return {"match_top10": match, "hourly_agg": hourly}
