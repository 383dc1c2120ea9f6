"""Rally's cohere_vector track, generated to the source's shape: one
``dense_vector`` field of 768 dimensions a document, compared by inner
product, and the track's search operation ``knn-search-10-100``.

No network here, so the embeddings are generated: a Gaussian direction
times a lognormal length (Cohere's multilingual-22-12 embeddings are not
unit length; every size is in the configuration file under ``assumed``).
Such vectors have no cluster structure. An exact scan reads every vector
whatever the structure, and near-ties among the winners are MORE
frequent than in real embeddings: the harder case for the comparison.

**The numbers are those of the printed text.** A component is a whole
number of millionths, at most 7 significant digits (``decimals``), sent
as ``-d.dddddd``; the view's ``vectors`` and ``queries`` are the float32
values of exactly that text (the double nearest the decimal, rounded to
float32: what a JSON parser and ``numpy.asarray(..., float32)`` make of
it), so the reference and the program start from the same numbers.

Documents are made in blocks of ``block_docs`` from streams of their own,
so a bulk body costs its own documents and nothing else, and a run that
only opens a stored base generates nothing until the comparison. The
lengths come from the configuration's ``structure_seed`` and are the
same for every ``--seed``; the seed draws the directions and the
queries. (The program's shapes depend on the documents a shard only, so
every seed finds its programs compiled.) Bulk lines are built with
numpy, digit by digit: ``json.dumps`` of 768 floats a document would
cost more than the ingest it feeds.
"""

from __future__ import annotations

import numpy as np

from harness.corpus import rng_for, shard_of_ids


_THREE_DIGITS = np.asarray(
    [[ord(c) for c in "%03d" % i] for i in range(1000)], np.uint8)


class Dataset:
    def __init__(self, config: dict, seed: int, n_shards: int):
        p = config["generator_params"]
        self.p = p
        self.seed = seed
        self.index = config["index"]
        self.field = p["field"]
        self.dims = int(p["dims"])
        self.n_docs = int(config["docs"])
        self.n_shards = n_shards
        self.append_pool = 0
        self.scale = 10 ** int(p["decimals"])
        self.block = int(p["block_docs"])
        self.shard = shard_of_ids(np.arange(self.n_docs), n_shards)
        self._view = None
        self._queries = self._fixed(rng_for(seed, 2), int(p["queries"]),
                                    rng_for(seed, 5))

    # -- the numbers ----------------------------------------------------

    def _fixed(self, rng, n: int, length_rng) -> np.ndarray:
        """``n`` vectors as whole millionths, int32 [n, dims]: a Gaussian
        direction times a lognormal length."""
        p = self.p
        g = rng.standard_normal((n, self.dims))
        length = length_rng.lognormal(np.log(p["length_median"]),
                                      p["length_sigma"], n)
        g *= (length * self.scale
              / np.sqrt(np.einsum("ij,ij->i", g, g)))[:, None]
        limit = 10 * self.scale - 1  # one digit before the point
        return np.clip(np.rint(g, out=g), -limit, limit).astype(np.int32)

    def _block(self, b: int) -> np.ndarray:
        """Documents [b * block, (b + 1) * block) as whole millionths."""
        lo = b * self.block
        n = min(self.block, self.n_docs - lo)
        return self._fixed(rng_for(self.seed, 1000 + b), n,
                           rng_for(self.p["structure_seed"], 1000 + b))

    def _floats(self, fixed: np.ndarray) -> np.ndarray:
        """The float32 values of the printed text."""
        return (fixed.astype(np.float64) / self.scale).astype(np.float32)

    def _text(self, fixed: np.ndarray) -> np.ndarray:
        """uint8 [n, dims * width]: each row ``-d.dddddd, d.dddddd,...]``
        (a space stands where a sign is not: JSON allows it). Digits
        come three at a time from a table of 000 to 999."""
        n, dims = fixed.shape
        decimals = int(self.p["decimals"])
        width = decimals + 4  # sign, digit, point, decimals, comma
        out = np.empty((n, dims, width), np.uint8)
        out[:, :, 0] = np.where(fixed < 0, np.uint8(ord("-")),
                                np.uint8(ord(" ")))
        out[:, :, 2] = ord(".")
        out[:, :, -1] = ord(",")
        rest = np.abs(fixed)
        at = width - 1  # digits end here, least significant last
        while at - 3 >= 3:  # whole groups of three decimals
            rest, group = np.divmod(rest, 1000)
            out[:, :, at - 3: at] = _THREE_DIGITS[group]
            at -= 3
        while at > 3:
            rest, digit = np.divmod(rest, 10)
            out[:, :, at - 1] = digit + ord("0")
            at -= 1
        out[:, :, 1] = rest + ord("0")
        out[:, -1, -1] = ord("]")
        return out.reshape(n, dims * width)

    # -- what the harness drives ---------------------------------------

    def view(self, n: int) -> dict:
        """Vectors, queries and routing of documents [0, n): the base is
        all there is, this deployment is read-only."""
        if n != self.n_docs:
            raise ValueError("cohere-768-knn has no appended documents")
        if self._view is None:
            blocks = range((self.n_docs + self.block - 1) // self.block)
            self._view = {
                "vectors": np.concatenate(
                    [self._floats(self._block(b)) for b in blocks]),
                "queries": self._floats(self._queries),
                "shard": self.shard}
        return self._view

    def bulk_body(self, lo: int, hi: int) -> str:
        parts = []
        head = '{"index":{"_type":"_doc","_id":"%d"}}\n{"' + self.field \
            + '":['
        for b in range(lo // self.block, (hi - 1) // self.block + 1):
            first = b * self.block
            fixed = self._block(b)[max(lo - first, 0): hi - first]
            text = self._text(fixed)
            start = max(lo, first)
            for j in range(len(fixed)):
                parts.append((head % (start + j)).encode("ascii"))
                parts.append(text[j].tobytes())
                parts.append(b"}\n")
        return b"".join(parts).decode("ascii")

    def operations(self) -> dict:
        """{operation: [request]}: the track's ``knn-search-10-100`` over
        the seed's queries, ``_source`` left out of the answer."""
        p = self.p
        vectors = (self._queries.astype(np.float64) / self.scale).tolist()
        return {"knn_top10": [{
            "method": "POST", "path": f"/{self.index}/_search",
            "body": {"knn": {"field": self.field, "query_vector": vector,
                             "k": int(p["k"]),
                             "num_candidates": int(p["num_candidates"])},
                     "size": int(p["k"]), "_source": False},
            "ref": {"kind": "knn", "n": n, "size": int(p["k"])},
        } for n, vector in enumerate(vectors)]}
