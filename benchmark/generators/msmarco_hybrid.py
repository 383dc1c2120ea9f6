"""MS MARCO passages with a dense embedding of each passage and query,
asked as Elasticsearch 8's documented hybrid search: ``query`` (a
``match`` on the text) and ``knn`` in one ``_search``.

The corpus's text is ``msmarco_passage``'s: this class subclasses its
generator, so a seed gives the passages, queries and term names that
generator makes at the configuration's size (``msmarco-serial``'s own,
document for document, where the two sizes agree; at another size the
vocabulary follows Heaps' law and the passages differ). It adds one
``dense_vector`` field a document, made as ``cohere_vector`` makes its
vectors (a Gaussian direction times a lognormal length, every component
a whole number of millionths, the view's floats those of the printed
text), from streams of their own: documents in blocks from the seed's
streams 1000 and up, their lengths from the vectors' own
``structure_seed``, and the query vectors from streams 6 and 7, so that
they are drawn independently of the query text (streams 2 and 3 of the
seed draw and name the text).

One operation, ``hybrid_top10``: the configuration's first ``requests``
of the seed's msmarco queries, each with a query vector of its own and
the configuration's ``knn`` section (``k``, ``num_candidates``,
``boost``), ``size`` ``top_k`` and ``_source: false``.
"""

from __future__ import annotations

import json

import numpy as np

from generators import cohere_vector, msmarco_passage
from harness.corpus import rng_for, words


class _Vectors(cohere_vector.Dataset):
    """``cohere_vector``'s documents and numbers over this corpus's ids,
    with the query vectors drawn from streams the text does not use."""

    def __init__(self, config: dict, seed: int, n_shards: int):
        super().__init__(config, seed, n_shards)
        self._queries = self._fixed(rng_for(seed, 6), int(self.p["queries"]),
                                    rng_for(seed, 7))


class Dataset(msmarco_passage.Dataset):
    def __init__(self, config: dict, seed: int, n_shards: int):
        super().__init__(config, seed, n_shards)
        p = config["generator_params"]
        self.knn = p["knn"]
        self.requests = int(config["requests"])
        self.vec = _Vectors({"generator_params": dict(
            p["vectors"], field=self.knn["field"], queries=self.requests),
            "index": self.index, "docs": self.n_docs}, seed, n_shards)

    def view(self, n: int) -> dict:
        """The text's view and the vectors': ``vectors`` [docs, dims] and
        ``queries`` [requests, dims] in float32."""
        vec = self.vec.view(n)
        return dict(super().view(n), vectors=vec["vectors"],
                    queries=vec["queries"])

    def bulk_body(self, lo: int, hi: int) -> str:
        field = self.text_fields[self.field]
        head = ('{"index":{"_type":"_doc","_id":"%d"}}\n{"' + self.field
                + '":%s,"' + self.knn["field"] + '":[')
        parts = []
        block = self.vec.block
        for b in range(lo // block, (hi - 1) // block + 1):
            first = b * block
            start = max(lo, first)
            text = self.vec._text(self.vec._block(b)[start - first:
                                                     hi - first])
            for j, row in enumerate(text):
                i = start + j
                parts.append((head % (i, json.dumps(words(
                    "t", self.label[field.doc_tokens(i)])))).encode("ascii"))
                parts.append(row.tobytes())
                parts.append(b"}\n")
        return b"".join(parts).decode("ascii")

    def operations(self) -> dict:
        """{operation: [request]}: ``ref`` holds what the reference needs
        (the terms, which query vector, ``k``, ``boost``, ``size``)."""
        vectors = (self.vec._queries.astype(np.float64)
                   / self.vec.scale).tolist()
        knn = {key: self.knn[key] for key in ("k", "num_candidates",
                                              "boost")}
        return {"hybrid_top10": [{
            "method": "POST", "path": f"/{self.index}/_search",
            "body": {"query": {"match": {
                self.field: words("t", self.label[terms])}},
                     "knn": dict(knn, field=self.knn["field"],
                                 query_vector=vector),
                     "size": self.top_k, "_source": False},
            "ref": {"kind": "hybrid", "field": self.field, "terms": terms,
                    "n": n, "size": self.top_k, "aggs": {}, **knn},
        } for n, (terms, vector) in enumerate(
            zip(self.queries[: self.requests], vectors))]}
