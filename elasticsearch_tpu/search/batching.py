"""Cross-query micro-batching for the Pallas scoring plane (ISSUE 5).

Every query streams the same corpus posting windows out of HBM and pays
the kernel's per-grid-step cost alone. (On the chip score_tiles runs at
0.137 % of its HBM roofline on msmarco-serial, ledger, PR 30: it is not
bandwidth-bound there; the cost the kernel's own note names is grid
steps, ops/pallas_scoring.py at DEFAULT_TILE_SUB, not confirmed on this
round's chip; no cell has timed a batch.) The classic serving fix
(cf. Orca's iteration-level continuous batching for LLM serving, and
shared block-max traversal in IR) is to amortize one corpus-stream pass
across the queries that are in flight AT THE SAME TIME: score Q queries
per DMA window instead of 1.

Three pieces live here:

- ``MicroBatcher``: a bounded-window collector in front of the search
  path. A query arriving while no other search is in flight takes the
  existing unbatched path immediately (ZERO added latency — the
  batcher's hot check is one lock + one counter read). Under
  concurrency, the first arrival becomes the group leader and waits up
  to ``search.batch.window_ms`` (default 0.2 ms) for peers, bounded by
  ``search.batch.max_queries``; the leader then executes the batch and
  demultiplexes per-member results (a member's failure — cancellation,
  request error — is delivered to that member alone).
- ``BatchStats``: the ``search.batch`` observability block exported via
  ``_stats`` (batched_query_total, batch_size_histogram,
  batch_window_waits_total).
- ``batched_segment_scores``: the host-plane batched launch — given the
  per-query host kernel plans for ONE segment, it unions their term
  lanes (ops/pallas_scoring.union_query_lanes), walks the same geometry
  ladder as the single-query path, and runs ONE ``score_tiles`` call
  with ``q_batch=Q``, returning each query's dense (scores, matched)
  pair. ``ShardSearcher.query`` consumes those through its
  ``score_cache`` parameter, so every downstream per-query semantic
  (min_score, sort, aggs, post_filter, rescore, collapse) is byte-
  identical to serial execution.

The mesh-plane (``mesh_pallas``) batched rung lives in
``parallel/plan_exec.IndexMeshSearch.query_batch``; the rung selection
and per-member deadline/cancellation handling live in
``IndexService.search_batch``. See docs/BATCHING.md.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# request-body keys the host batched path understands: batching only
# replaces the main query's scoring program with a cached per-query score
# vector — everything else (sort, aggs, post_filter, rescore, fetch-phase
# options) runs the normal per-query pipeline on top of it. profile IS
# batchable (ISSUE 8 plane-truthfulness): a profiled member must run on
# whatever plane would serve it unprofiled and report THAT plane's phase
# spans; on the host batched rung the member's per-segment score cache is
# skipped (ShardSearcher.query) so its engine/timing breakdown still
# reflects a real per-query execution. scroll/pit/collapse-expansion
# style keys stay excluded — their contexts are keyed to one request.
_BATCHABLE_KEYS = frozenset({
    "query", "size", "from", "sort", "aggs", "aggregations", "post_filter",
    "min_score", "timeout", "allow_partial_search_results", "stats",
    "terminate_after", "rescore", "search_after", "track_scores",
    "_source", "docvalue_fields", "stored_fields", "script_fields",
    "highlight", "version", "profile",
    # NB track_total_hits is deliberately NOT batchable: the mesh
    # batched rung rejects whole batches containing any unknown key, so
    # one flagged member would demote its 15 peers off the mesh_pallas
    # launch — it runs solo instead (exhaustive either way)
})


# pure-kNN request shapes the batched kNN MXU launch covers (the body
# either carries the top-level `knn` section alone or the bare `knn`
# query clause); hybrid (query + knn) requests run serially — each side
# then rides its own plane's batching
_KNN_BATCHABLE_KEYS = frozenset({
    "knn", "query", "size", "from", "timeout",
    "allow_partial_search_results", "stats", "_source", "profile",
})


# knn spec parameters the parser accepts (search/query_dsl.KnnQueryBuilder
# strict-parses the same set): the mesh gate must reject anything else so
# an unknown parameter gets the SAME 400 whichever plane is healthy
_KNN_SPEC_KEYS = frozenset({
    "field", "query_vector", "k", "num_candidates", "filter", "boost",
    "_name",
})


def _knn_shaped(body: dict) -> Optional[dict]:
    """The knn spec of a knn-SHAPED request (top-level section with no
    lexical query, or the sole knn query clause), eligible or not."""
    if isinstance(body.get("knn"), dict) and body.get("query") is None:
        return body["knn"]
    q = body.get("query")
    if (isinstance(q, dict) and set(q) == {"knn"}
            and isinstance(q["knn"], dict) and "knn" not in body):
        return q["knn"]
    return None


def knn_batch_spec(body: Optional[dict]) -> Optional[dict]:
    """The knn spec when this request is a pure top-k vector search a
    batched kNN launch could serve (same shape the mesh program covers),
    else None."""
    body = body or {}
    if any(key not in _KNN_BATCHABLE_KEYS for key in body):
        return None
    spec = _knn_shaped(body)
    if spec is None or float(spec.get("boost", 1.0)) != 1.0:
        return None
    if spec.get("filter"):
        return None  # filtered kNN runs the host plan rung (exact)
    if any(key not in _KNN_SPEC_KEYS for key in spec):
        return None  # unknown parameter: the parser owns the 400
    return spec


def batchable_body(body: Optional[dict]) -> bool:
    """Cheap body-shape precheck run at submit time: can this request
    ride a micro-batch at all? (Per-segment kernel eligibility is decided
    later, per query, by the plan builder — an ineligible member simply
    executes serially inside the batch.)"""
    body = body or {}
    if _knn_shaped(body) is not None:
        # pure kNN: batchable only when the MXU launch covers it — a
        # filtered/boosted/malformed spec runs SOLO rather than joining
        # the lexical batch and demoting its peers off the mesh rung
        return knn_batch_spec(body) is not None
    if not isinstance(body.get("query"), dict):
        return False  # match_all / missing query: nothing to amortize
    if body.get("knn") is not None:
        return False  # hybrid: each side batches on its own plane
    return all(key in _BATCHABLE_KEYS for key in body)


class BatchStats:
    """The ``search.batch`` stats block (thread-safe counters)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.batched_query_total = 0
        self.batch_window_waits_total = 0
        self.batch_size_histogram: Dict[int, int] = {}
        # last collection window a leader actually used, in ms — the
        # adaptive-window observability gauge (docs/OVERLOAD.md): widens
        # under admission-queue pressure, narrows back as it drains
        self.batch_window_effective_ms = 0.0

    def note_window_wait(self) -> None:
        with self._lock:
            self.batch_window_waits_total += 1

    def note_effective_window(self, window_s: float) -> None:
        with self._lock:
            self.batch_window_effective_ms = round(window_s * 1000.0, 4)

    def note_batch(self, size: int) -> None:
        """One batched dispatch of ``size`` members served via a shared
        launch."""
        with self._lock:
            self.batched_query_total += size
            self.batch_size_histogram[size] = (
                self.batch_size_histogram.get(size, 0) + 1)

    def as_dict(self) -> dict:
        with self._lock:
            return {
                "batched_query_total": self.batched_query_total,
                "batch_window_waits_total": self.batch_window_waits_total,
                "batch_window_effective_ms": self.batch_window_effective_ms,
                "batch_size_histogram": {
                    str(size): count for size, count
                    in sorted(self.batch_size_histogram.items())},
            }


def counts_safe_for_union(node) -> bool:
    """False when a with_counts (minimum_should_match / operator:and)
    member names the same posting run in two lanes: the union dedupes the
    run (summing weights — exact for SCORES), so that member's match
    COUNT would see one lane where the serial kernel counts two and
    every matching doc could fall below its threshold. Such members
    execute serially; score-only members (min_match <= 1) are unaffected
    because summed weights reproduce their scores exactly."""
    if not node.with_counts:
        return True
    lanes = node._host_lanes
    return len({(l.block_start, l.block_count)
                for l in lanes}) == len(lanes)


class _Group:
    __slots__ = ("items", "results", "done", "sealed", "opened_at")

    def __init__(self):
        self.items: List[Any] = []
        self.results: Optional[List[Any]] = None
        self.done = threading.Event()
        self.sealed = False
        # window-wait telemetry anchor (docs/OBSERVABILITY.md): how long
        # the leader held the group open collecting peers
        self.opened_at = time.monotonic()


class MicroBatcher:
    """Bounded-window cross-query collector.

    ``run(key, item, single_fn, batch_fn)``:

    - no other search in flight -> ``single_fn(item)`` immediately (the
      zero-added-latency contract for unloaded indices);
    - otherwise the item joins (or opens) the pending group for ``key``;
      the group's first member leads: it waits up to ``window_s`` (or
      until ``max_queries`` members arrived), then executes
      ``batch_fn(items) -> [result|Exception, ...]`` and publishes each
      member's entry. Exception entries re-raise in their own caller's
      thread — one member's cancellation or request error never fails
      its peers.
    """

    def __init__(self, window_s: float = 0.0002, max_queries: int = 16,
                 enabled: bool = True,
                 stats: Optional[BatchStats] = None):
        self.window_s = float(window_s)
        self.max_queries = int(max_queries)
        self.enabled = bool(enabled)
        self.stats = stats or BatchStats()
        self._cv = threading.Condition()
        self._groups: Dict[Any, _Group] = {}
        self._inflight = 0
        # optional telemetry hook, called once per member right before
        # the leader dispatches: annotate(item, window_wait_s,
        # batch_size, member_index) — IndexService points it at each
        # member's QueryTracer (docs/OBSERVABILITY.md)
        self.annotate: Optional[Callable[[Any, float, int, int],
                                         None]] = None
        # adaptive collection window (docs/OVERLOAD.md): when set, the
        # leader sizes its wait from this callable instead of window_s —
        # IndexService points it at the admission controller, which
        # widens the window with queue pressure (bounded by
        # search.batch.max_window_ms). A lone query still never waits.
        self.window_fn: Optional[Callable[[], float]] = None

    def run(self, key, item, single_fn: Callable[[Any], Any],
            batch_fn: Callable[[List[Any]], List[Any]]):
        if not self.enabled or self.max_queries < 2:
            return single_fn(item)
        with self._cv:
            group = self._groups.get(key)
            if group is None and self._inflight == 0:
                # the common unloaded case: no concurrency, no window
                self._inflight += 1
                direct = True
                leader = False
                my_idx = 0
            elif group is None:
                group = _Group()
                group.items.append(item)
                self._groups[key] = group
                self._inflight += 1
                direct = False
                leader = True
                my_idx = 0
            else:
                group.items.append(item)
                my_idx = len(group.items) - 1
                self._inflight += 1
                direct = False
                leader = False
                if len(group.items) >= self.max_queries:
                    # full: seal so the leader dispatches now and new
                    # arrivals open a fresh group
                    group.sealed = True
                    self._groups.pop(key, None)
                    self._cv.notify_all()
        try:
            if direct:
                return single_fn(item)
            if leader:
                self.stats.note_window_wait()
                window_s = self.window_s
                if self.window_fn is not None:
                    try:
                        window_s = max(float(self.window_fn()), 0.0)
                    except Exception:  # noqa: BLE001 — sizing is
                        pass  # advisory; never fail the query
                self.stats.note_effective_window(window_s)
                deadline = time.monotonic() + window_s
                with self._cv:
                    while (not group.sealed
                           and len(group.items) < self.max_queries):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                    group.sealed = True
                    # a filling member may have sealed+removed this group
                    # already AND a newer group may be pending under the
                    # same key — only remove OUR group, never evict the
                    # successor mid-collection
                    if self._groups.get(key) is group:
                        self._groups.pop(key)
                    items = list(group.items)
                if self.annotate is not None:
                    wait_s = time.monotonic() - group.opened_at
                    for idx, it in enumerate(items):
                        try:
                            self.annotate(it, wait_s, len(items), idx)
                        except Exception:  # noqa: BLE001 — telemetry
                            pass  # must never fail the query
                try:
                    if len(items) == 1:
                        # nobody joined: plain unbatched execution
                        try:
                            results = [single_fn(items[0])]
                        except Exception as e:  # noqa: BLE001
                            results = [e]
                    else:
                        results = list(batch_fn(items))
                        if len(results) != len(items):
                            raise RuntimeError(
                                f"batch_fn returned {len(results)} results "
                                f"for {len(items)} members")
                except BaseException as e:  # noqa: BLE001 — followers must
                    # never hang on a leader fault; every member sees it
                    results = [e] * len(items)
                group.results = results
                group.done.set()
                out = results[my_idx]
                if isinstance(out, BaseException):
                    raise out
                return out
            # follower: the leader publishes our result
            if not group.done.wait(timeout=300.0):
                # defensive: a wedged leader must not hang the caller
                return single_fn(item)
            out = group.results[my_idx]
            if isinstance(out, BaseException):
                raise out
            return out
        finally:
            with self._cv:
                self._inflight -= 1


# ----------------------------------------------------------------------
# Host-plane batched launch
# ----------------------------------------------------------------------


_FLAT_BATCH = None


def _flat_batch(dense):
    """[Q, n_tiles*LANE, sub] kernel layout -> [Q, nd_pad] doc order
    (jit specializes per input shape; built lazily so this module never
    imports jax at import time)."""
    global _FLAT_BATCH
    if _FLAT_BATCH is None:
        import jax

        from elasticsearch_tpu.ops import pallas_scoring as psc

        @jax.jit
        def flat(d):
            q, rows, s = d.shape
            n_tiles = rows // psc.LANE
            return d.reshape(q, n_tiles, psc.LANE, s).transpose(
                0, 1, 3, 2).reshape(q, -1)

        _FLAT_BATCH = flat
    return _FLAT_BATCH(dense)


def batched_segment_scores(segment, nodes: Sequence) -> Optional[
        List[Tuple[np.ndarray, np.ndarray]]]:
    """One batched ``score_tiles`` launch for Q queries over ONE segment.

    ``nodes``: the per-query host-built ``PallasScoreTermsNode``s (each
    carries its ``_host_lanes``). Returns one (scores [nd1] f32,
    matched [nd1] bool) numpy pair per query — exactly what
    ``PallasScoreTermsNode.emit`` + the live mask would have produced
    serially — or None when no shared geometry exists (callers fall back
    to serial execution; the same contract as the single-query ladder).
    """
    from elasticsearch_tpu.ops import pallas_scoring as psc

    from elasticsearch_tpu.index.segment import next_pow2

    geom = getattr(segment, "kernel_geom", None)
    if geom is None:
        return None
    lane_sets = [list(n._host_lanes) for n in nodes]
    # pad the batch to a power of two with empty (all-zero-weight) lane
    # sets: q_batch is a jit-static dim, and arrival timing would
    # otherwise compile one kernel variant per batch size
    q_pad = next_pow2(len(nodes))
    lane_sets.extend([] for _ in range(q_pad - len(nodes)))
    # collective geometry ladder (same walk as the single-query path in
    # query_dsl._pallas_score_terms_node): big tiles are fastest, but the
    # UNION's covering window must fit the kernel bound
    sub = geom.tile_sub
    while True:
        g = geom if sub == geom.tile_sub else psc.tile_geometry(
            geom.nd_pad, sub)
        try:
            row_lo, row_hi, weights, cb = psc.build_tile_tables_batched(
                lane_sets, segment.kernel_bmin, segment.kernel_bmax, g)
            break
        except ValueError:
            if sub <= 32 or g.tile_sub < sub:
                return None
            sub //= 2
    live_key = ("k_live_t" if g.tile_sub == geom.tile_sub
                else segment.kernel_live_t_for(g.tile_sub))
    dev = segment.device_arrays()
    codec = getattr(segment, "kernel_codec", "raw")
    if codec == "packed":
        if "k_packed" not in dev:
            return None
        corpus = (dev["k_packed"], None)
    else:
        if "k_docs" not in dev:
            return None
        corpus = (dev["k_docs"], dev["k_frac"])
    with_counts = any(n.with_counts for n in nodes)
    interpret = bool(nodes[0].interpret)
    outs = psc.score_tiles(
        corpus[0], corpus[1], dev[live_key],
        row_lo, row_hi, weights,
        t_pad=row_lo.shape[1], cb=cb, sub=g.tile_sub,
        dense=True, with_counts=with_counts, interpret=interpret,
        tiles_per_step=psc.TILES_PER_STEP,
        q_batch=q_pad, codec=codec)
    nd = segment.nd_pad
    scores_all = np.asarray(_flat_batch(outs[0]))[:, :nd]
    counts_all = (np.asarray(_flat_batch(outs[1]))[:, :nd]
                  if with_counts else None)
    results: List[Tuple[np.ndarray, np.ndarray]] = []
    zero = np.zeros(1, np.float32)
    for q, node in enumerate(nodes):
        scores = np.concatenate([scores_all[q], zero]).astype(np.float32)
        if node.with_counts:
            counts = np.concatenate([counts_all[q], zero])
            matched = counts >= float(node.min_match)
        else:
            matched = scores > 0.0
        results.append((scores, matched))
    return results
