"""Seeded text, routing and postings shared by the generators and the
plain reference. Standard library and numpy only: the load generator's
side of the benchmark never imports JAX, and nothing here imports the
program under test.
"""

from __future__ import annotations

import numpy as np

_M32 = np.uint64(0xFFFFFFFF)


def rng_for(seed: int, stream: int) -> np.random.RandomState:
    """One independent stream per purpose; ``seed`` may exceed 2**31."""
    return np.random.RandomState(
        [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF, stream])


def zipf_probs(vocab: int, exponent: float = 1.0) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return p / p.sum()


def lognormal_lengths(rng, n: int, median: float, sigma: float,
                      lo: int, hi: int) -> np.ndarray:
    return np.clip(rng.lognormal(np.log(median), sigma, n), lo, hi
                   ).astype(np.int64)


# ----------------------------------------------------------------------
# Routing: murmur3_x86_32 of the id's UTF-16LE bytes (as Elasticsearch's
# Murmur3HashFunction hashes a Java string), floorMod shards.
# Copied in arithmetic from elasticsearch_tpu/utils/murmur3.py (listed
# under Open questions in PERF.md), vectorised over ids of one length.
# ----------------------------------------------------------------------


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return ((x << np.uint64(r)) | (x >> np.uint64(32 - r))) & _M32


def _murmur3_rows(data: np.ndarray) -> np.ndarray:
    """murmur3_32 (seed 0) of each row of a uint8 matrix, as uint64
    holding the unsigned 32-bit hash."""
    n, length = data.shape
    c1, c2 = np.uint64(0xCC9E2D51), np.uint64(0x1B873593)
    d = data.astype(np.uint64)
    h = np.zeros(n, np.uint64)
    nblocks = length // 4
    for i in range(nblocks):
        k = (d[:, 4 * i] | (d[:, 4 * i + 1] << np.uint64(8))
             | (d[:, 4 * i + 2] << np.uint64(16))
             | (d[:, 4 * i + 3] << np.uint64(24)))
        k = (k * c1) & _M32
        k = _rotl(k, 15)
        k = (k * c2) & _M32
        h ^= k
        h = _rotl(h, 13)
        h = (h * np.uint64(5) + np.uint64(0xE6546B64)) & _M32
    tail = d[:, 4 * nblocks:]
    if tail.shape[1]:
        k = np.zeros(n, np.uint64)
        for j in range(tail.shape[1] - 1, -1, -1):
            k ^= tail[:, j] << np.uint64(8 * j)
        k = (k * c1) & _M32
        k = _rotl(k, 15)
        k = (k * c2) & _M32
        h ^= k
    h ^= np.uint64(length)
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & _M32
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & _M32
    h ^= h >> np.uint64(16)
    return h


def shard_of_ids(ids: np.ndarray, n_shards: int) -> np.ndarray:
    """The shard each decimal id (a non-negative integer, sent as its
    decimal string) is routed to."""
    ids = np.asarray(ids, np.int64)
    out = np.zeros(len(ids), np.int32)
    if not len(ids):
        return out
    digits = np.char.encode(ids.astype(str), "ascii")
    lengths = np.char.str_len(digits)
    for length in np.unique(lengths):
        rows = np.flatnonzero(lengths == length)
        ascii_ = np.frombuffer(
            b"".join(digits[rows].tolist()), np.uint8).reshape(-1, length)
        mat = np.zeros((len(rows), 2 * length), np.uint8)  # UTF-16LE
        mat[:, 0::2] = ascii_
        h = _murmur3_rows(mat).astype(np.int64)
        h = np.where(h >= (1 << 31), h - (1 << 32), h)  # Java's signed int
        out[rows] = np.mod(h, n_shards)
    return out


# ----------------------------------------------------------------------
# One analysed text field: tokens per document, postings per term
# ----------------------------------------------------------------------


class TextField:
    """Token ids per document, in document order, and CSR postings built
    once (term -> sorted doc ids with term frequencies)."""

    def __init__(self, tokens: np.ndarray, doc_len: np.ndarray, vocab: int):
        self.tokens = np.asarray(tokens, np.int32)
        self.doc_len = np.asarray(doc_len, np.int64)
        self.vocab = int(vocab)
        self.n_docs = len(self.doc_len)
        self.ends = np.cumsum(self.doc_len)
        self._csr = None

    def doc_tokens(self, i: int) -> np.ndarray:
        return self.tokens[self.ends[i] - self.doc_len[i]: self.ends[i]]

    def csr(self):
        """(indptr[vocab+1], docs, tf): postings of term t are
        docs[indptr[t]:indptr[t+1]], ascending, with their tf."""
        if self._csr is None:
            doc_of = np.repeat(np.arange(self.n_docs, dtype=np.int64),
                               self.doc_len)
            key = self.tokens.astype(np.int64) * self.n_docs + doc_of
            uniq, tf = np.unique(key, return_counts=True)
            terms = uniq // self.n_docs
            docs = (uniq % self.n_docs).astype(np.int32)
            indptr = np.zeros(self.vocab + 1, np.int64)
            np.cumsum(np.bincount(terms, minlength=self.vocab), out=indptr[1:])
            self._csr = (indptr, docs, tf.astype(np.float32))
        return self._csr

    def postings(self, term: int):
        indptr, docs, tf = self.csr()
        lo, hi = indptr[term], indptr[term + 1]
        return docs[lo:hi], tf[lo:hi]

    def doc_freq(self, terms) -> np.ndarray:
        indptr = self.csr()[0]
        terms = np.asarray(terms, np.int64)
        return indptr[terms + 1] - indptr[terms]


def words(prefix: str, tokens) -> str:
    return " ".join(f"{prefix}{int(t)}" for t in tokens)
