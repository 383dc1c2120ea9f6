#!/usr/bin/env python3
"""Prove the served path on the chip: _bulk -> _forcemerge -> _search.

One process starts a ``Node`` behind its ``HttpServer``, ingests a seeded
zipfian corpus over HTTP, merges each shard to one segment, and asks
``_search`` for seeded ``match`` queries, one ``bool`` and one
aggregation. Every answer must come from the ``mesh_pallas`` plane and
agree with a numpy BM25 that shares no scoring code with the engine
(Lucene idf, k1 1.2, b 0.75, exact f32 lengths, statistics per shard:
the engine scores with its segment's df/doc count/avgdl and runs no DFS
round, so after ``_forcemerge`` a shard is one segment and the
reference is one set of statistics per shard).

    python chip_smoke.py             # one chip: the whole smoke
    python chip_smoke.py --chips 4   # four chips: 8 shards over the mesh

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check, or no TPU, exits non-zero with the reason and prints
no such line. There is no CPU option: tests patch ``require_tpu``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

VOCAB = 50_000
AVG_DOC_LEN = 80
N_KEYWORDS = 2000
N_QUERY_TERMS = 3
TOP_K = 10
BULK_DOCS = 5000
# one shard on one chip is answered by the host plane (host.single_shard)
SHARDS_PER_CHIP = 2
K1, B = 1.2, 0.75
SCORE_RTOL = 1e-5
INDEX = "smoke"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ----------------------------------------------------------------------
# Set-up: device, native library, server
# ----------------------------------------------------------------------


def require_tpu(chips: int):
    """The devices JAX reports; fails unless they are ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform == {devices[0].platform!r}")
    check(len(devices) == chips,
          f"--chips {chips} but JAX reports {len(devices)} devices")
    return devices


def build_native() -> None:
    """Build the tokenizer library on the machine that loads it."""
    try:
        proc = subprocess.run(
            ["make", "-C", os.path.join(ROOT, "native"), "clean", "all"],
            capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        raise SmokeFailure(f"native build did not run: {e}") from e
    check(proc.returncode == 0,
          f"native build failed: {proc.stderr.strip()[-500:]}")


class Client:
    """JSON over HTTP with urllib: what a user of the node sends."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def request(self, method: str, path: str, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        data = body.encode("utf-8") if body is not None else None
        ctype = ("application/x-ndjson" if path.endswith("_bulk")
                 else "application/json")
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": ctype})
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} -> HTTP {e.code}: "
                f"{e.read()[:500]!r}") from e


# ----------------------------------------------------------------------
# Corpus and numpy reference
# ----------------------------------------------------------------------


class Corpus:
    """Seeded zipfian corpus: lognormal lengths around 80 tokens over a
    50,000-term vocabulary, one of 2,000 zipfian keyword values and a
    seeded integer per doc."""

    def __init__(self, n_docs: int, seed: int, n_shards: int):
        from elasticsearch_tpu.utils.murmur3 import shard_id_for

        rng = np.random.RandomState(seed)
        self.n_docs = n_docs
        self.doc_len = np.clip(
            rng.lognormal(np.log(AVG_DOC_LEN), 0.4, n_docs), 5, 500
        ).astype(np.int64)
        probs = 1.0 / np.arange(1, VOCAB + 1)
        self.tokens = rng.choice(
            VOCAB, int(self.doc_len.sum()), p=probs / probs.sum()
        ).astype(np.int32)
        self.doc_of_token = np.repeat(
            np.arange(n_docs, dtype=np.int32), self.doc_len)
        kprobs = 1.0 / np.arange(1, N_KEYWORDS + 1)
        self.keyword = rng.choice(
            N_KEYWORDS, n_docs, p=kprobs / kprobs.sum()).astype(np.int32)
        self.number = rng.randint(0, 1000, n_docs).astype(np.int64)
        self.rng = rng
        # routing, not scoring: which shard the engine puts each doc on
        self.shard = np.asarray(
            [shard_id_for(str(i), n_shards) for i in range(n_docs)],
            np.int32)
        self.n_shards = n_shards
        self.shard_docs = np.bincount(self.shard, minlength=n_shards)
        sum_ttf = np.bincount(self.shard, weights=self.doc_len,
                              minlength=n_shards)
        self.shard_avgdl = sum_ttf / np.maximum(self.shard_docs, 1)

    def bulk_bodies(self):
        """ndjson ``_bulk`` bodies of BULK_DOCS documents each."""
        ends = np.cumsum(self.doc_len)
        for lo in range(0, self.n_docs, BULK_DOCS):
            hi = min(lo + BULK_DOCS, self.n_docs)
            lines = []
            for i in range(lo, hi):
                toks = self.tokens[ends[i] - self.doc_len[i]: ends[i]]
                lines.append('{"index":{"_type":"_doc","_id":"%d"}}' % i)
                lines.append(json.dumps({
                    "body": " ".join(f"t{t}" for t in toks.tolist()),
                    "tag": f"k{self.keyword[i]}",
                    "n": int(self.number[i])}))
            yield hi - lo, "\n".join(lines) + "\n"

    def term_scores(self, term: int) -> np.ndarray:
        """BM25 of one term for every doc (0 where absent), f32, with
        the statistics of the doc's own shard."""
        docs = self.doc_of_token[self.tokens == term]
        tf = np.bincount(docs, minlength=self.n_docs).astype(np.float32)
        df = np.bincount(self.shard[tf > 0], minlength=self.n_shards)
        idf = np.asarray(
            [math.log(1.0 + (n - d + 0.5) / (d + 0.5))
             for n, d in zip(self.shard_docs, df)], np.float32)
        norm = (np.float32(K1) * (np.float32(1.0 - B) + np.float32(B)
                * self.doc_len.astype(np.float32)
                / self.shard_avgdl.astype(np.float32)[self.shard]))
        return idf[self.shard] * tf * np.float32(K1 + 1.0) / (tf + norm)

    def match_scores(self, terms) -> np.ndarray:
        total = np.zeros(self.n_docs, np.float32)
        for t in terms:
            total += self.term_scores(int(t))
        return total


def text_of(terms) -> str:
    return " ".join(f"t{int(t)}" for t in terms)


def check_response_plane(what: str, resp: dict) -> None:
    check(resp.get("_plane") == "mesh_pallas",
          f"{what}: served by _plane={resp.get('_plane')!r}, "
          f"not mesh_pallas")
    check(resp.get("timed_out") is False, f"{what}: timed_out")
    shards = resp.get("_shards", {})
    check(not shards.get("failures") and shards.get("failed") == 0,
          f"{what}: shard failures {shards}")


def check_hits(what: str, resp: dict, scores: np.ndarray,
               matched: np.ndarray) -> dict:
    """hits.total equal; the top-k is a top-k of the reference (ids free
    only among scores tied within tolerance); scores within SCORE_RTOL.
    Returns {"bit_equal", "max_rel_err"} over the returned hits."""
    check_response_plane(what, resp)
    total = resp["hits"]["total"]
    check(total == int(matched.sum()),
          f"{what}: hits.total {total} != reference {int(matched.sum())}")
    hits = resp["hits"]["hits"]
    k = min(TOP_K, int(matched.sum()))
    check(len(hits) == k, f"{what}: {len(hits)} hits, expected {k}")
    ref = np.where(matched, scores, -np.inf)
    ref_top = np.sort(ref[np.argpartition(-ref, k - 1)[:k]])[::-1]
    got_ids = np.asarray([int(h["_id"]) for h in hits])
    got = np.asarray([h["_score"] for h in hits], np.float32)
    check(len(set(got_ids.tolist())) == k, f"{what}: duplicate hits")
    check(bool(matched[got_ids].all()),
          f"{what}: a hit does not match in the reference")
    own = scores[got_ids]
    rel_own = np.abs(got - own) / np.abs(own)
    rel_rank = np.abs(got - ref_top) / np.abs(ref_top)
    check(float(rel_own.max()) <= SCORE_RTOL,
          f"{what}: score differs from the reference's score of the same "
          f"doc by {float(rel_own.max()):.3g} relative")
    check(float(rel_rank.max()) <= SCORE_RTOL,
          f"{what}: top-{k} is not the reference's: rank-wise scores "
          f"differ by {float(rel_rank.max()):.3g} relative")
    return {"bit_equal": bool(np.array_equal(got, own)),
            "max_rel_err": float(rel_own.max())}


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------


def ingest(client: Client, corpus: Corpus, n_shards: int) -> dict:
    client.request("PUT", f"/{INDEX}", {
        "settings": {"index": {"number_of_shards": n_shards,
                               "number_of_replicas": 0}},
        "mappings": {"_doc": {"properties": {
            "body": {"type": "text"},
            "tag": {"type": "keyword"},
            "n": {"type": "long"}}}}})
    t0 = time.perf_counter()
    for n, body in corpus.bulk_bodies():
        resp = client.request("POST", f"/{INDEX}/_bulk", body)
        check(resp.get("errors") is False and len(resp["items"]) == n,
              f"_bulk reported errors: {json.dumps(resp)[:500]}")
    bulk_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for path in (f"/{INDEX}/_forcemerge?max_num_segments=1",
                 f"/{INDEX}/_refresh"):
        resp = client.request("POST", path)
        check(resp["_shards"]["failed"] == 0, f"POST {path}: {resp}")
    merge_s = time.perf_counter() - t0
    stats = client.request("GET", f"/{INDEX}/_stats")["indices"][INDEX]
    count = stats["primaries"]["docs"]["count"]
    check(count == corpus.n_docs,
          f"_stats counts {count} docs, {corpus.n_docs} were sent")
    return {"docs": corpus.n_docs, "shards": n_shards,
            "bulk_seconds": bulk_s, "docs_per_s": corpus.n_docs / bulk_s,
            "forcemerge_refresh_seconds": merge_s}


def search_stats(client: Client) -> dict:
    stats = client.request("GET", f"/{INDEX}/_stats")
    return stats["indices"][INDEX]["total"]["search"]


def staging_report(client: Client) -> dict:
    memory = search_stats(client)["memory"]
    events = [e for e in memory["staging_events"] if e["index"] == INDEX]
    rows = client.request("GET", "/_cat/staging?format=json")
    return {"staged_bytes_total": memory["staged_bytes_total"],
            "staged_bytes": memory["staged_bytes"],
            "staging_seconds": sum(e["duration_ms"] for e in events) / 1e3,
            "slots": {r["segment"]: r["bytes"] for r in rows
                      if r["index"] == INDEX and r["kind"] == "slot"}}


def device_bytes(devices) -> dict:
    """What each device holds: the bytes of the live arrays' shards on
    it, and the bytes in use as the runtime reports them (TPU only)."""
    import jax

    held = {d.id: 0 for d in devices}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return {"live_array_bytes": [held[d.id] for d in devices],
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in devices]}


def phase_p50_us(before: dict, after: dict) -> dict:
    """p50 per phase over the queries between two ``_stats`` readings:
    the upper bound of the log2 bucket that holds the median."""
    out = {}
    for phase, buckets in after.items():
        delta = {int(le[3:]): n - before.get(phase, {}).get(le, 0)
                 for le, n in buckets.items() if le[3:].isdigit()}
        n_total, seen = sum(delta.values()), 0
        for le in sorted(delta):
            seen += delta[le]
            if n_total and 2 * seen >= n_total:
                out[phase] = le
                break
    return out


def run_queries(client: Client, corpus: Corpus, n_queries: int,
                n_warm: int) -> None:
    rng = corpus.rng
    term_sets = [rng.choice(np.arange(50, 1000), N_QUERY_TERMS,
                            replace=False) for _ in range(n_queries)]

    def match_body(terms):
        return {"query": {"match": {"body": text_of(terms)}},
                "size": TOP_K}

    # ---- seeded match queries; the first one stages and compiles ----
    t0 = time.perf_counter()
    first = client.request("POST", f"/{INDEX}/_search",
                           match_body(term_sets[0]))
    cold_s = time.perf_counter() - t0
    bit_equal, max_rel = True, 0.0
    for i, terms in enumerate(term_sets):
        resp = first if i == 0 else client.request(
            "POST", f"/{INDEX}/_search", match_body(terms))
        scores = corpus.match_scores(terms)
        got = check_hits(f"match[{i}] {text_of(terms)!r}", resp, scores,
                         scores > 0)
        bit_equal &= got["bit_equal"]
        max_rel = max(max_rel, got["max_rel_err"])
    say("match", queries=n_queries, cold_first_query_seconds=cold_s,
        scores_bit_equal=bit_equal, max_rel_err=max_rel)

    # ---- bool must/should/filter ----
    must, should = [60], [120, 340]
    tag = int(np.bincount(corpus.keyword).argmax())
    resp = client.request("POST", f"/{INDEX}/_search", {
        "size": TOP_K,
        "query": {"bool": {
            "must": [{"match": {"body": text_of(must)}}],
            "should": [{"match": {"body": text_of(should)}}],
            "filter": [{"term": {"tag": f"k{tag}"}}]}}})
    must_scores = corpus.match_scores(must)
    got = check_hits("bool", resp, must_scores
                     + corpus.match_scores(should),
                     (must_scores > 0) & (corpus.keyword == tag))
    say("bool", hits_total=resp["hits"]["total"], **got)

    # ---- size 0: terms + avg over the matched docs ----
    terms = term_sets[0]
    resp = client.request("POST", f"/{INDEX}/_search", {
        "size": 0, "query": {"match": {"body": text_of(terms)}},
        "aggs": {"tags": {"terms": {"field": "tag", "size": TOP_K}},
                 "avg_n": {"avg": {"field": "n"}}}})
    check_response_plane("aggregation", resp)
    matched = corpus.match_scores(terms) > 0
    check(resp["hits"]["total"] == int(matched.sum()),
          "aggregation: hits.total differs from the reference")
    counts = np.bincount(corpus.keyword[matched], minlength=N_KEYWORDS)
    buckets = resp["aggregations"]["tags"]["buckets"]
    got_counts = [b["doc_count"] for b in buckets]
    check(got_counts == sorted(counts.tolist(), reverse=True)[:TOP_K],
          f"aggregation: bucket counts {got_counts} are not the "
          f"reference's top {TOP_K}")
    check(all(counts[int(b["key"][1:])] == b["doc_count"]
              for b in buckets),
          "aggregation: a bucket's doc_count differs from the reference")
    avg = float(corpus.number[matched].mean())
    got_avg = resp["aggregations"]["avg_n"]["value"]
    check(abs(got_avg - avg) <= SCORE_RTOL * abs(avg),
          f"aggregation: avg {got_avg} != reference {avg}")
    say("aggregation", buckets=len(buckets), matched=int(matched.sum()),
        avg=got_avg)

    # ---- warm serial latency, client side: printed, not gated ----
    if n_warm:
        before = search_stats(client)
        lat = []
        for i in range(n_warm):
            body = match_body(term_sets[i % n_queries])
            t0 = time.perf_counter()
            resp = client.request("POST", f"/{INDEX}/_search", body)
            lat.append(time.perf_counter() - t0)
            check_response_plane(f"warm[{i}]", resp)
        after = search_stats(client)
        hist = "histogram_us"
        say("warm", queries=n_warm,
            client_p50_ms=float(np.median(lat)) * 1e3,
            client_min_ms=min(lat) * 1e3, client_max_ms=max(lat) * 1e3,
            compiles_in_window=(
                after["compile"]["query_path_first_compile_total"]
                - before["compile"]["query_path_first_compile_total"]),
            phase_p50_le_us=phase_p50_us(
                before["phases"][hist].get("mesh_pallas", {}),
                after["phases"][hist].get("mesh_pallas", {})))


def check_planes(client: Client) -> None:
    """Every query of the run was served by mesh_pallas and no plane
    faulted: a quarantine demotion answers 200 and must not pass."""
    search = search_stats(client)
    decisions = search["phases"]["decisions"]
    check(list(decisions) == ["mesh_pallas.served"],
          f"ladder decisions other than mesh_pallas.served: {decisions}")
    failures = search["planes"]["plane_failures_total"]
    check(failures == {"mesh_pallas": 0, "mesh": 0},
          f"plane failures: {failures}")
    say("planes", decisions=decisions, plane_failures_total=failures,
        agg_fused_query_total=search["planes"]["agg_fused_query_total"])


def run(args, devices) -> None:
    """The smoke proper, on the devices ``require_tpu`` returned."""
    from elasticsearch_tpu.common import compile_cache
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.http_server import HttpServer
    from elasticsearch_tpu.utils import native

    compile_cache.configure_compile_cache(
        compile_cache.checkout_cache_dir())
    say("setup", native_available=native.available(),
        compile_cache=compile_cache.compile_cache_path())
    n_shards = SHARDS_PER_CHIP * args.chips
    t0 = time.perf_counter()
    corpus = Corpus(args.docs, args.seed, n_shards)
    say("corpus", docs=args.docs, tokens=int(corpus.doc_len.sum()),
        seconds=time.perf_counter() - t0)
    server = HttpServer(Node(), port=0)
    server.start()
    try:
        client = Client(server.port)
        say("ingest", **ingest(client, corpus, n_shards))
        run_queries(client, corpus, args.queries, args.warm)
        check_planes(client)
        staged = staging_report(client)
        per_device = device_bytes(devices)
        say("staging", per_device=per_device, **staged)
        # code that never saw a second chip may put every slot on the
        # first: each device must hold its share of the staged tables
        check(all(b > 0 for b in per_device["live_array_bytes"]),
              f"a device holds nothing: {per_device}")
    finally:
        server.stop()


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--docs", type=int, default=100_000,
                   help="documents to ingest over HTTP")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the 8-shard mesh path and its reference")
    p.add_argument("--queries", type=int, default=16,
                   help="seeded 3-term match queries")
    p.add_argument("--warm", type=int, default=50,
                   help="warm serial queries timed from the client")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.perf_counter()
    try:
        devices = require_tpu(args.chips)
        say("device", platform=devices[0].platform,
            kind=devices[0].device_kind, count=len(devices))
        build_native()
        run(args, devices)
        say("done", seconds=time.perf_counter() - t0)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
