"""MS MARCO passage ranking, generated to the source's shape.

No network here, so the passages are generated: lengths
lognormal with mean ~56 tokens clipped to 8-250, terms zipfian
(exponent 1) over a vocabulary that grows with the corpus by Heaps' law
V = heaps_k * tokens ** heaps_beta, words ``t<rank>``. Queries are
2-12 distinct terms (2 + Binomial(10, 0.4), mean 6) drawn from the
corpus's own unigram distribution, so function-word ranks appear in
most of them. Every size is in the configuration file under ``assumed``.

The corpus's STRUCTURE (lengths, which rank stands where) comes from the
configuration's ``structure_seed`` and is the same for every ``--seed``;
the seed gives the words their names (a permutation of the labels
``t<n>``), draws the queries and orders them. The program compiles its
mesh programs for the exact row count of the staged postings table, so a
corpus of another structure is ~40 cold compiles of 8-10 s each inside
set-up; with one structure every seed does the same work on differently
named terms and finds the programs in the compile cache (PERF.md 6).
"""

from __future__ import annotations

import json

import numpy as np

from harness.corpus import (TextField, lognormal_lengths, rng_for,
                            shard_of_ids, words, zipf_probs)


class Dataset:
    def __init__(self, config: dict, seed: int, n_shards: int):
        p = config["generator_params"]
        self.index = config["index"]
        self.field = p["field"]
        self.top_k = p["top_k"]
        self.n_docs = n = int(config["docs"])
        rng = rng_for(p["structure_seed"], 1)
        doc_len = lognormal_lengths(rng, n, p["length_median"],
                                    p["length_sigma"], p["length_min"],
                                    p["length_max"])
        n_tokens = int(doc_len.sum())
        self.vocab = int(p["heaps_k"] * n_tokens ** p["heaps_beta"])
        self.probs = zipf_probs(self.vocab, p["zipf_exponent"])
        tokens = rng.choice(self.vocab, n_tokens, p=self.probs)
        self.text_fields = {self.field: TextField(tokens, doc_len, self.vocab)}
        self.columns = {}
        self.n_shards = n_shards
        self.shard = shard_of_ids(np.arange(n), n_shards)
        self.append_pool = 0
        self.label = rng_for(seed, 3).permutation(self.vocab)
        self._queries(rng_for(seed, 2), p)

    def _queries(self, rng, p) -> None:
        lengths = p["query_terms_min"] + rng.binomial(
            p["query_terms_max"] - p["query_terms_min"],
            p["query_terms_binomial_p"], p["queries"])
        self.queries = []
        for n_terms in lengths:
            terms = []
            while len(terms) < n_terms:  # distinct terms, in drawn order
                for t in rng.choice(self.vocab, int(n_terms), p=self.probs):
                    if int(t) not in terms and len(terms) < n_terms:
                        terms.append(int(t))
            self.queries.append(terms)

    def view(self, n: int) -> dict:
        """Fields, columns and routing of documents [0, n): the base is
        all there is, this deployment is read-only."""
        if n != self.n_docs:
            raise ValueError("msmarco-passage has no appended documents")
        return {"text_fields": self.text_fields, "columns": self.columns,
                "shard": self.shard}

    def bulk_body(self, lo: int, hi: int) -> str:
        field = self.text_fields[self.field]
        lines = []
        for i in range(lo, hi):
            lines.append('{"index":{"_type":"_doc","_id":"%d"}}' % i)
            lines.append(json.dumps(
                {self.field: words("t", self.label[field.doc_tokens(i)])}))
        return "\n".join(lines) + "\n"

    def operations(self) -> dict:
        """{operation: [request]}; a request carries what the reference
        needs to answer it under ``ref``."""
        return {"match_top10": [{
            "method": "POST", "path": f"/{self.index}/_search",
            "body": {"query": {"match": {
                self.field: words("t", self.label[terms])}},
                     "size": self.top_k},
            "ref": {"kind": "match", "field": self.field, "terms": terms,
                    "size": self.top_k, "aggs": {}},
        } for terms in self.queries]}
