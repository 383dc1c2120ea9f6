"""IndexService: one index = N shards + mapping + routing + search fan-out.

Role model: ``IndexService`` (core/.../index/IndexService.java) for shard
ownership, ``OperationRouting`` (cluster/routing/OperationRouting.java:232)
for doc->shard routing, and ``TransportSearchAction`` +
``SearchPhaseController`` for the scatter-gather + merge. In the
single-node path the "network boundary" between coordinator and shards is
a method call; the distributed path (parallel/) replaces the per-shard
loop with a shard_map over a device mesh.
"""

from __future__ import annotations

import copy
import os
import time
from typing import Dict, List, Optional

from elasticsearch_tpu.analysis.analyzers import AnalysisRegistry
from elasticsearch_tpu.common.errors import (
    DocumentMissingException,
    IllegalArgumentException,
    SearchPhaseExecutionException,
    TaskCancelledException,
)
from elasticsearch_tpu.common.settings import (
    INDEX_NUMBER_OF_REPLICAS,
    INDEX_NUMBER_OF_SHARDS,
    INDEX_SEEDED_PREFIXES,
    INDEX_TRANSLOG_DURABILITY,
    LayeredSettings,
    Settings,
)
from elasticsearch_tpu.common.integrity import integrity_service
from elasticsearch_tpu.index.shard import IndexShard
from elasticsearch_tpu.index.store import CorruptIndexException
from elasticsearch_tpu.mapper.mapping import MapperService
from elasticsearch_tpu.search.aggregations import parse_aggs, run_aggregations
from elasticsearch_tpu.search.service import fetch_hits, merge_refs, normalize_sort
from elasticsearch_tpu.utils.murmur3 import shard_id_for


class IndexService:
    def __init__(self, name: str, settings: Settings = Settings.EMPTY,
                 mapping: Optional[dict] = None, data_path: Optional[str] = None):
        self.name = name
        # 6.x single-type name (custom names deprecated, echoed in
        # document/search/mapping responses; _doc canonical)
        self.doc_type = "_doc"
        self.settings = settings
        # the one way a dynamic search setting reaches its reader: the
        # explicit cluster values (set_cluster_overrides), then this
        # index's own Settings, then the reader's default
        self.cluster_explicit = Settings.EMPTY
        self.live = LayeredSettings(lambda: self.cluster_explicit,
                                    lambda: self.settings)
        self.creation_date = int(time.time() * 1000)
        self.uuid = f"{name}-{self.creation_date:x}"
        self.num_shards = INDEX_NUMBER_OF_SHARDS.get(settings)
        self.num_replicas = INDEX_NUMBER_OF_REPLICAS.get(settings)
        self.analyzers = AnalysisRegistry(settings)
        from elasticsearch_tpu.index.similarity import SimilarityService
        self.mapper_service = MapperService(
            self.analyzers, mapping,
            similarity_service=SimilarityService(settings),
            dense_vector_max_dims=settings.get_int(
                "index.mapping.dense_vector.max_dims", 1024))
        self.data_path = data_path
        from elasticsearch_tpu.index.index_sort import parse_index_sort
        self.index_sort = parse_index_sort(settings, self.mapper_service)
        durability = INDEX_TRANSLOG_DURABILITY.get(settings)
        slowlog_warn = settings.get_time("index.search.slowlog.threshold.query.warn")
        slowlog_info = settings.get_time("index.search.slowlog.threshold.query.info")
        # index-level search slowlog thresholds for mesh-plane-served
        # queries (no ShardSearcher runs there); negative = disabled
        self._slowlog_warn_s = (slowlog_warn if slowlog_warn is not None
                                and slowlog_warn >= 0 else None)
        self._slowlog_info_s = (slowlog_info if slowlog_info is not None
                                and slowlog_info >= 0 else None)
        idx_slow_warn = settings.get_time(
            "index.indexing.slowlog.threshold.index.warn")
        idx_slow_info = settings.get_time(
            "index.indexing.slowlog.threshold.index.info")
        idx_slow_source = settings.get_int("index.indexing.slowlog.source", 1000)
        gc_deletes = settings.get_time("index.gc_deletes")
        # postings codec preference for the tile-kernel staging
        # (docs/PRUNING.md): the index key unless "default", else the
        # node's search.pallas.postings_codec, which the Node lays over
        # these Settings at creation and at recovery, else raw
        self.postings_codec_pref = settings.get_str(
            "index.search.pallas.postings_codec", "default")
        if self.postings_codec_pref == "default":
            self.postings_codec_pref = settings.get_str(
                "search.pallas.postings_codec", "raw")
        self.shards: Dict[int, IndexShard] = {}
        for sid in range(self.num_shards):
            shard_path = os.path.join(data_path, str(sid)) if data_path else None
            shard = IndexShard(name, sid, self.mapper_service, shard_path,
                               durability=durability,
                               slowlog_warn_s=slowlog_warn,
                               slowlog_info_s=slowlog_info,
                               index_sort=self.index_sort,
                               indexing_slowlog_warn_s=idx_slow_warn,
                               indexing_slowlog_info_s=idx_slow_info,
                               indexing_slowlog_source_chars=idx_slow_source)
            if gc_deletes is not None:
                shard.engine.gc_deletes = gc_deletes
            shard.engine.postings_codec = self.postings_codec_pref
            # slice resolution is shard-count-aware (SliceBuilder)
            shard.searcher.num_shards = self.num_shards
            shard.searcher.max_slices = settings.get_int(
                "index.max_slices_per_scroll", 1024)
            self.shards[sid] = shard
            try:
                if shard_path and shard.engine.store.read_commit() is not None:
                    shard.recover_from_store()
                elif shard_path and os.path.exists(
                    os.path.join(shard_path, "translog", "translog.ckp")
                ):
                    shard.recover_from_store()
                else:
                    shard.start_fresh()
            except CorruptIndexException as e:
                # boot over corrupt/marked bytes (ISSUE 16): quarantine
                # the copy instead of crashing index open — the shard
                # stays allocated but every query against it fails into
                # failures[] (never silent empty hits), and a healthy
                # copy elsewhere (replica / snapshot) is the way back
                self._quarantine_shard(sid, e, site="load")
        # periodic NRT refresh (index.refresh_interval, default 1s; -1
        # disables — IndexService#getRefreshInterval + refresh scheduling)
        # mesh-executed query phase (parallel/plan_exec.IndexMeshSearch):
        # lazy — staged on the first eligible search; the setting gates it
        # (index.search.mesh: true default; false = host merge only)
        self._mesh_search = None
        self._mesh_enabled = settings.get_bool("index.search.mesh", True)
        # cross-query micro-batching (search/batching.py; docs/BATCHING.md):
        # concurrent compatible searches share one batched kernel launch on
        # the mesh_pallas / host-pallas rungs. A query with no concurrency
        # takes the unbatched path with zero added latency.
        from elasticsearch_tpu.search.batching import BatchStats, MicroBatcher

        self.batch_stats = BatchStats()
        self._batcher = MicroBatcher(
            window_s=settings.get_float("search.batch.window_ms", 0.2)
            / 1000.0,
            max_queries=settings.get_int("search.batch.max_queries", 16),
            enabled=settings.get_bool("search.batch.enabled", True),
            stats=self.batch_stats)
        # phase-attributed query telemetry (search/telemetry.py,
        # docs/OBSERVABILITY.md): always-on span tracing drained into
        # per-plane × per-phase histograms; search.telemetry.enabled is
        # the dynamic kill switch
        from elasticsearch_tpu.search.telemetry import SearchTelemetry

        self.telemetry = SearchTelemetry()
        # multi-tenant overload control (search/admission.py, ISSUE 12,
        # docs/OVERLOAD.md): bounded admission queue + per-tenant DRR +
        # the brownout ladder, consulted at dispatch before any
        # staging/launch work; also sizes the batcher's ADAPTIVE window
        from elasticsearch_tpu.search.admission import (
            SearchAdmissionController,
        )

        self.admission = SearchAdmissionController(name, self.live)
        self._batcher.window_fn = (
            lambda: self.admission.effective_batch_window_s(
                self._batcher.window_s))
        # device-memory budget (search.memory.hbm_budget_bytes, ISSUE 9):
        # the accountant is a process resource — an explicitly-set value
        # here (node-file seed / direct-service tests) configures it, the
        # same way node startup and PUT _cluster/settings do
        if settings.get("search.memory.hbm_budget_bytes") is not None:
            from elasticsearch_tpu.common.memory import memory_accountant

            memory_accountant().set_budget(
                settings.get_bytes("search.memory.hbm_budget_bytes", 0))
        # batch items are (body, deadline, tracer): stamp window-wait +
        # batch shape onto each member's tracer at dispatch time
        self._batcher.annotate = self._annotate_batch_member
        import threading as _threading

        self._stats_lock = _threading.Lock()
        # shard request cache (IndicesRequestCache.java:64): size==0
        # (agg/count) responses cached against the shards' visibility
        # epochs; index.requests.cache.enable gates it (default on)
        from elasticsearch_tpu.index.request_cache import RequestCache

        self._request_cache_enabled = settings.get_bool(
            "index.requests.cache.enable", True)
        # stats counters (IndexingStats/GetStats/RefreshStats/FlushStats)
        self._get_total = 0
        self._refresh_total = 0
        self._host_query_total = 0
        # convex hybrid requests (``query`` + ``knn``, docs/VECTOR.md):
        # all of them, those the one-launch mesh program answered, those
        # the host rung fused, and the mesh launches made for them
        self._hybrid_stats = dict.fromkeys(
            ("hybrid_query_total", "hybrid_fused_total",
             "hybrid_host_fallback_total", "hybrid_launches_total"), 0)
        # legacy _parent metadata field values (ParentFieldMapper):
        # doc_id -> parent id, surfaced via stored_fields [_parent].
        # Values persist with the document (translog/store record
        # alongside routing) and are rebuilt here after recovery.
        self.parents: Dict[str, str] = {}
        self._rebuild_parents()
        self._flush_total = 0
        cache_bytes = settings.get_int(
            "index.requests.cache.size_in_bytes", 8 * 1024 * 1024)
        self.request_cache = RequestCache(max_bytes=cache_bytes)
        iv = settings.get_time("index.refresh_interval")
        self.refresh_interval = 1.0 if iv is None else iv
        self._refresh_stop = None
        if self.refresh_interval and self.refresh_interval > 0:
            import threading

            self._refresh_stop = threading.Event()

            import logging

            logger = logging.getLogger("elasticsearch_tpu.index.refresh")

            def _refresh_loop():
                while not self._refresh_stop.wait(self.refresh_interval):
                    for s in list(self.shards.values()):
                        try:
                            s.refresh()
                        except Exception:
                            # a closing shard can race the timer; anything
                            # else must be visible to the operator
                            logger.warning(
                                "[%s][%s] scheduled refresh failed",
                                name, s.shard_id, exc_info=True)

            threading.Thread(target=_refresh_loop, daemon=True,
                             name=f"refresh[{name}]").start()
        # background store/device scrubber (ISSUE 16, docs/RESILIENCE.md
        # "Data integrity"): index.scrub.interval, off by default. The
        # thread always runs (cheap idle poll) so turning the knob on
        # dynamically — via _settings or the cluster-level value —
        # needs no thread lifecycle management; each wake re-reads the
        # effective interval.
        import threading as _scrub_threading

        self._scrub_stop = _scrub_threading.Event()
        _scrub_threading.Thread(target=self._scrub_loop, daemon=True,
                                name=f"scrub[{name}]").start()
        # background slot compaction (ISSUE 20): no polling loop — the
        # mesh plane nudges maybe_compact_async() after a delta commit;
        # the lock makes the pass single-flight (a second trigger while
        # one runs is a no-op, never a queue)
        self._compact_lock = _scrub_threading.Lock()
        self._closing = False

    def _rebuild_parents(self) -> None:
        """Re-derive the _parent registry from recovered shard state: the
        sealed segments' per-doc parent column and the (translog-replayed)
        buffer — so stored_fields [_parent] survives restart/restore
        (round-5 advisor finding: the registry was memory-only)."""
        for shard in self.shards.values():
            eng = shard.engine
            for seg in eng.segments:
                parents = getattr(seg, "parents", None)
                if not parents:
                    continue
                for local, doc_id in enumerate(seg.doc_ids):
                    p = parents[local] if local < len(parents) else None
                    if p is not None and seg.live[local]:
                        self.parents[str(doc_id)] = str(p)
            buf = eng.buffer
            for local, p in enumerate(getattr(buf, "parents", []) or []):
                if p is not None and local not in eng._buffer_deletes:
                    self.parents[str(buf.doc_ids[local])] = str(p)

    # ------------------------------------------------------------------
    # Corruption quarantine + the background scrubber (ISSUE 16)
    # ------------------------------------------------------------------

    def _quarantine_shard(self, sid: int, exc: Exception,
                          site: str = "query") -> None:
        """Quarantine a corrupt shard copy (Store.markStoreCorrupted +
        IndexShard#failShard parity): write the ``corrupted_*`` marker
        (once — first cause wins), record the detection, flag the shard
        so the query path fails it into failures[] per the PR-4 partial
        contract, and release the copy's device staging through the
        PR-9 accountant — a quarantined copy must not pin HBM, and the
        ledger must return to baseline exactly (no leak)."""
        shard = self.shards.get(sid)
        if shard is None:
            return
        store = shard.engine.store
        integ = integrity_service()
        integ.record_corruption(self.name, sid, site, str(exc))
        already = store.is_corrupted()
        marker = store.mark_corrupted(str(exc), site=site)
        if not already:
            integ.record_marker(self.name, sid, marker, action="marked")
        shard.store_corrupted = True
        for seg in list(shard.engine.segments):
            try:
                seg.release_device_staging()
            except Exception:  # noqa: BLE001 — release is best-effort
                pass  # the index-level release_index backstop covers it

    def unquarantine_shard(self, sid: int) -> None:
        """A successful re-recovery installed a verified byte set over
        the quarantined copy: clear the markers + flag (the ONLY legal
        transition out of quarantine — never called on load)."""
        shard = self.shards.get(sid)
        if shard is None:
            return
        store = shard.engine.store
        for marker in store.corruption_markers():
            integrity_service().record_marker(
                self.name, sid, marker, action="cleared")
        store.clear_corruption_markers()
        shard.store_corrupted = False

    def set_cluster_overrides(self, committed: Settings) -> None:
        """Install the committed cluster settings' EXPLICIT per-index
        keys as the top layer of ``self.live``: the whole map each time,
        so a key cleared from the cluster settings hands control back to
        this index's own Settings. Called by Node for every index on PUT
        _cluster/settings and for a new index at creation."""
        prefixes = INDEX_SEEDED_PREFIXES + ("index.scrub.interval",)
        self.cluster_explicit = Settings({
            key: committed.get(key) for key in committed.keys()
            if key.startswith(prefixes)})

    def _scrub_effective_interval(self) -> Optional[float]:
        """index.scrub.interval; None/<=0 disables."""
        return self.live.get_time("index.scrub.interval")

    def _scrub_loop(self) -> None:
        import logging

        logger = logging.getLogger("elasticsearch_tpu.index.scrub")
        while True:
            iv = self._scrub_effective_interval()
            wait = iv if iv is not None and iv > 0 else 5.0
            if self._scrub_stop.wait(wait):
                return
            iv = self._scrub_effective_interval()
            if iv is None or iv <= 0:
                continue  # disabled (or disabled mid-wait): idle poll
            try:
                self.scrub_now()
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.warning("[%s] scrub pass failed", self.name,
                               exc_info=True)

    def scrub_now(self) -> dict:
        """One synchronous scrubber pass (the loop body; tests call it
        directly for determinism). Two checks per shard:

        - **disk**: re-verify every committed segment's checksums
          recursively (sealed files are immutable — any mismatch is
          at-rest corruption) → quarantine with site=``scrub``;
        - **device drift**: digest device-staged base tables
          (block_docs / block_tfs / norms) against host truth cast to
          the staged dtype — drift invalidates the staging (restage
          classifies with the ``scrub`` lifecycle reason) and counts,
          never serves.
        """
        import hashlib

        import numpy as np

        bytes_verified = 0
        checksum_failures = 0
        drift_count = 0
        for sid, shard in list(self.shards.items()):
            store = shard.engine.store
            if getattr(shard, "store_corrupted", False) \
                    or store.is_corrupted():
                continue  # already quarantined — heal, don't re-verify
            commit = store.read_commit() or {}
            for seg_name in commit.get("segments", []):
                try:
                    bytes_verified += store.verify_segment(seg_name)
                except CorruptIndexException as e:
                    checksum_failures += 1
                    self._quarantine_shard(sid, e, site="scrub")
                    break
                except OSError:
                    continue  # raced a concurrent merge/commit GC
            if getattr(shard, "store_corrupted", False):
                continue
            for seg in list(shard.engine.segments):
                dev = getattr(seg, "_device", None)
                if not dev:
                    continue
                for key, host in (("block_docs", seg.block_docs),
                                  ("block_tfs", seg.block_tfs),
                                  ("norms", seg.norms)):
                    staged = dev.get(key)
                    if staged is None:
                        continue
                    dev_np = np.asarray(staged)
                    bytes_verified += int(dev_np.nbytes)
                    # host truth cast to the staged dtype: staging used
                    # the same conversion, so a clean table matches
                    # bit-for-bit and x64 downcasts never false-positive
                    host_np = np.asarray(host).astype(dev_np.dtype,
                                                      copy=False)
                    if (hashlib.sha256(dev_np.tobytes()).digest()
                            != hashlib.sha256(host_np.tobytes()).digest()):
                        drift_count += 1
                        integrity_service().record_scrub_drift(
                            self.name, sid, seg.name, key)
                        # invalidate: the restage re-adopts host truth
                        # and classifies as `scrub` in the ledger ring
                        seg.stage_reason_initial = "scrub"
                        seg.release_device_staging()
                        break
        integrity_service().record_scrub_run(bytes_verified)
        return {"bytes_verified": bytes_verified,
                "checksum_failures": checksum_failures,
                "drift": drift_count}

    # ------------------------------------------------------------------
    # Background slot compaction (ISSUE 20)
    # ------------------------------------------------------------------

    def _compact_threshold(self) -> float:
        """index.staging.compact.threshold; <= 0 disables compaction."""
        return float(self.live.get_float(
            "index.staging.compact.threshold", 0.25))

    def _compaction_due(self) -> bool:
        """Tombstone density or slot fragmentation crossed the
        threshold on the live staged generation (cheap: host-side
        counters only, no device work)."""
        threshold = self._compact_threshold()
        if threshold <= 0:
            return False
        ms = self._mesh_search
        stats = (ms.staging_slot_stats() if ms is not None else None)
        if not stats or not stats["slots"]:
            return False
        if any(s["tombstone_density"] >= threshold
               for s in stats["slots"]):
            return True
        # fragmentation: occupied slots beyond what the live docs need —
        # sparse slots (delete-heavy or many tiny appended segments)
        # waste HBM rows and merge-loop work; when the occupied count
        # exceeds the post-merge slot need by more than the threshold
        # fraction, a compaction pass would shrink the generation
        occupied = len(stats["slots"])
        needed = max(1, -(-sum(s["live"] for s in stats["slots"])
                          // max(max(s["docs"] for s in stats["slots"]),
                                 1)))
        return occupied > needed and (
            (occupied - needed) / occupied >= threshold)

    def maybe_compact_async(self) -> bool:
        """Delta-commit hook (called by the mesh plane, possibly under
        its stage lock): decide cheaply, then run the pass on a
        background thread — compaction never runs on the query path.
        Returns True when a pass was kicked off."""
        if (self._closing or self.admission.draining
                or not self._compaction_due()):
            return False
        if self._compact_lock.locked():
            return False  # single-flight: a pass is already running
        import threading as _t

        _t.Thread(target=self.compact_now, daemon=True,
                  name=f"compact[{self.name}]").start()
        return True

    def compact_now(self) -> dict:
        """One synchronous compaction pass (the background thread body;
        tests call it directly for determinism). Force-merges the
        tombstone-dense shards (expunging deletes), then restages a
        FRESH generation with fresh slot headroom and releases the old
        one — ledger-exact through the transactional staging path.
        Single-flight via ``_compact_lock``; interruptible by drain
        (docs/RESILIENCE.md): a drain beginning mid-pass aborts between
        shards, leaving a consistent (merely uncompacted) staging."""
        if not self._compact_lock.acquire(blocking=False):
            return {"ran": False, "reason": "already_running"}
        try:
            if self._closing:
                return {"ran": False, "reason": "closing"}
            if self.admission.draining:
                return {"ran": False, "reason": "draining"}
            threshold = self._compact_threshold()
            merged_shards = []
            for sid, shard in list(self.shards.items()):
                if self._closing:
                    return {"ran": False, "reason": "closing",
                            "merged_shards": merged_shards}
                if self.admission.draining:
                    return {"ran": False, "reason": "draining",
                            "merged_shards": merged_shards}
                eng = shard.engine
                total = sum(int(s.num_docs) for s in eng.segments)
                live = sum(int(s.live_doc_count) for s in eng.segments)
                dense = (total > 0 and threshold > 0
                         and (total - live) / total >= threshold)
                frag = len(eng.segments) > 1
                if dense or frag:
                    eng.force_merge(stage_reason="compaction")
                    merged_shards.append(sid)
            if self._closing:
                return {"ran": False, "reason": "closing",
                        "merged_shards": merged_shards}
            ms = self._mesh_search
            restaged = (ms.restage_for_compaction()
                        if ms is not None else False)
            if ms is not None:
                ms.note_compaction_run()
            return {"ran": True, "merged_shards": merged_shards,
                    "restaged": bool(restaged)}
        finally:
            self._compact_lock.release()

    # ------------------------------------------------------------------
    # Routing + document ops
    # ------------------------------------------------------------------

    def _route(self, doc_id: str, routing: Optional[str] = None) -> int:
        return shard_id_for(routing if routing is not None else doc_id,
                            self.num_shards)

    def index_doc(self, doc_id: str, source: dict, routing: Optional[str] = None,
                  parent: Optional[str] = None, **kw) -> dict:
        routing = self._check_join_routing(doc_id, source, routing)
        shard = self.shards[self._route(doc_id, routing)]
        r = shard.index_doc(doc_id, source, routing, parent=parent, **kw)
        if parent is not None:
            # the registry serves stored_fields [_parent]; the value also
            # rides the engine record (translog + segment) so it survives
            # restart/restore — rebuilt in _rebuild_parents()
            self.parents[str(doc_id)] = str(parent)
        return r

    def _check_join_routing(self, doc_id: str, source: dict,
                            routing: Optional[str]) -> Optional[str]:
        """Child docs of a join field MUST be colocated with their parent
        (modules/parent-join: RoutingMissingException when a child is
        indexed without routing). On multi-shard indices a missing routing
        is an error; we follow the reference and additionally default the
        routing to the parent id, which is always correct."""
        from elasticsearch_tpu.mapper.field_types import join_field_of

        jf = join_field_of(self.mapper_service)
        if jf is None:
            return routing
        value = source.get(jf.name)
        if not isinstance(value, (str, dict)):
            return routing
        try:
            name, parent = jf.parse_join(value)
        except Exception:
            return routing  # parse errors surface in the mapper with context
        if parent is None:
            return routing
        if routing is None:
            if self.num_shards > 1:
                raise IllegalArgumentException(
                    f"[routing] is missing for join field [{jf.name}]: child "
                    f"document [{doc_id}] must be routed to its parent's shard"
                )
            routing = parent
        return routing

    def get_doc(self, doc_id: str, routing: Optional[str] = None,
                realtime: bool = True):
        with self._stats_lock:
            self._get_total += 1
        shard = self.shards[self._route(doc_id, routing)]
        return shard.get_doc(doc_id, realtime=realtime)

    def delete_doc(self, doc_id: str, routing: Optional[str] = None, **kw) -> dict:
        shard = self.shards[self._route(doc_id, routing)]
        return shard.delete_doc(doc_id, **kw)

    def update_doc(self, doc_id: str, body: dict, routing: Optional[str] = None,
                   version: Optional[int] = None) -> dict:
        """Update API (action/update/TransportUpdateAction): partial doc
        merge, upsert, doc_as_upsert; scripted updates run painless over
        ctx._source with ctx.op semantics (UpdateHelper.executeScripts).
        ``version``: internal optimistic-concurrency check against the
        CURRENT doc version (UpdateRequest versioning)."""
        shard = self.shards[self._route(doc_id, routing)]
        existing = shard.get_doc(doc_id)
        if version is not None and existing.found \
                and existing.version != version:
            from elasticsearch_tpu.common.errors import (
                VersionConflictEngineException,
            )

            raise VersionConflictEngineException(
                doc_id, existing.version, version)
        if not existing.found:
            # upserts go through index_doc so join-routing checks apply
            if body.get("doc_as_upsert") and "doc" in body:
                return self.index_doc(doc_id, body["doc"], routing)
            if "upsert" in body:
                if "script" in body and body.get("scripted_upsert"):
                    return self._scripted_update(
                        doc_id, body, dict(body["upsert"]), routing,
                        version=0)
                return self.index_doc(doc_id, body["upsert"], routing)
            raise DocumentMissingException(self.name, doc_id)
        if "script" in body:
            # deep copy: engine.get returns the live buffer/segment source,
            # and a script may mutate nested objects then set ctx.op='none' —
            # a shallow copy would corrupt the stored doc in place, bypassing
            # versioning and the translog (same hazard _apply_byquery_script
            # guards against in index/reindex.py)
            return self._scripted_update(
                doc_id, body, copy.deepcopy(existing.source), routing,
                version=existing.version)
        if "doc" in body:
            merged = _deep_merge(dict(existing.source), body["doc"])
            if merged == existing.source and body.get("detect_noop", True):
                return {
                    "_index": self.name, "_id": doc_id,
                    "_version": existing.version, "result": "noop",
                }
            return self.index_doc(doc_id, merged, routing)
        raise DocumentMissingException(self.name, doc_id)

    def _scripted_update(self, doc_id: str, body: dict, source: dict,
                         routing: Optional[str], version: int) -> dict:
        from elasticsearch_tpu.common.errors import IllegalArgumentException
        from elasticsearch_tpu.script.expression import compile_script
        from elasticsearch_tpu.script.painless import execute_update_script

        spec = body["script"]
        script = compile_script(spec)
        if not hasattr(script, "run"):
            raise IllegalArgumentException(
                "update scripts must be painless (the numeric expression "
                "engine has no ctx mutation surface)")
        params = (spec.get("params") if isinstance(spec, dict) else None) or {}
        new_source, op = execute_update_script(
            script, source, params,
            doc_meta={"_index": self.name, "_id": doc_id,
                      "_version": version})
        if op == "none":
            return {"_index": self.name, "_id": doc_id,
                    "_version": version, "result": "noop"}
        if op == "delete":
            return self.delete_doc(doc_id, routing=routing)
        return self.index_doc(doc_id, new_source, routing)

    def refresh(self) -> None:
        with self._stats_lock:
            self._refresh_total += 1
        for shard in self.shards.values():
            shard.refresh()

    def flush(self) -> None:
        with self._stats_lock:
            self._flush_total += 1
        for shard in self.shards.values():
            shard.flush()

    def synced_flush(self) -> Dict[int, str]:
        """Flush + synced-flush marker per shard (ISSUE 14 graceful
        drain; the reference's _flush/synced): after it a warm restart
        over the same data path recovers ops-free. Returns
        {shard_id: sync_id}."""
        with self._stats_lock:
            self._flush_total += 1
        return {sid: shard.synced_flush()
                for sid, shard in self.shards.items()}

    def force_merge(self) -> None:
        for shard in self.shards.values():
            shard.force_merge()

    # ------------------------------------------------------------------
    # Compiled program-variant lattice (ISSUE 14, docs/RESILIENCE.md
    # "Rollout & drain"): record the query shapes the mesh plane served,
    # so a restart can warm their compiled variants off the query path.
    # ------------------------------------------------------------------

    def _record_warm_variant(self, kind: str, bodies: List[dict],
                             plane: str) -> None:
        if plane not in ("mesh_pallas", "mesh") or not bodies:
            return
        from elasticsearch_tpu.common import compile_cache as cc

        if cc.in_warming():
            return  # a warm replay must not re-record itself
        import json as _json

        try:
            # dedup BEFORE any copying/serialization: on the steady
            # state every query's variant is already recorded and this
            # is one skeleton hash + one dict probe
            key = (kind + "|" + str(min(len(bodies), 16)) + "|"
                   + "|".join(sorted({cc.body_skeleton(b)
                                      for b in bodies[:16]})))
            registry = cc.variant_registry()
            if registry.has_warm(self.name, key):
                return
            clean = [{k: v for k, v in (b or {}).items()
                      if k not in ("profile", "preference")}
                     for b in bodies[:16]]
            _json.dumps(clean)  # only JSON-serializable bodies persist
            registry.record_warm(self.name, key,
                                 {"kind": kind, "bodies": clean})
        except (TypeError, ValueError):
            pass  # unserializable body: this variant just isn't warmable

    def warm_compile_variants(self) -> int:
        """Replay this index's recorded program-variant lattice under
        the warming context — first compiles (or persistent-cache
        deserializations) land in ``programs_warmed_total``, never on
        the query path. Called in the background on node start / index
        open; returns how many warm specs replayed cleanly."""
        from elasticsearch_tpu.common import compile_cache as cc

        warmed = 0
        for spec in cc.variant_registry().warm_entries(self.name):
            try:
                with cc.warming():
                    bodies = [dict(b) for b in spec.get("bodies") or []]
                    if not bodies:
                        continue
                    if spec.get("kind") == "search_batch":
                        self.search_batch(bodies)
                    else:
                        for body in bodies:
                            self._search_uncached(body)
                warmed += 1
            except Exception:  # noqa: BLE001 — warming must never fail
                # the node; a stale spec (deleted field, changed
                # mapping) just warms nothing
                continue
        return warmed

    # ------------------------------------------------------------------
    # Search (scatter -> merge -> fetch; §3.2 of SURVEY.md)
    # ------------------------------------------------------------------

    def _telemetry_enabled(self) -> bool:
        """search.telemetry.enabled — the dynamic kill switch for the
        always-on phase tracer (docs/OBSERVABILITY.md)."""
        return self.live.get_bool("search.telemetry.enabled", True)

    def _tracer(self):
        """One QueryTracer per request (NULL_TRACER when the kill switch
        is off), stamped with the request's X-Opaque-Id so the id
        survives the batch leader's thread hop."""
        from elasticsearch_tpu.search.telemetry import get_opaque_id

        tracer = self.telemetry.tracer(self._telemetry_enabled())
        oid = get_opaque_id()
        if oid:
            tracer.annotate("opaque_id", oid)
        return tracer

    def _request_tracer(self):
        """The tracer of a top-level search and whether this call owns
        it: the one the HTTP front door opened at the socket where there
        is one (rest/http_server.py; its spans drain into this index
        when the front door finishes it), else a new one as ``_tracer``
        makes it (direct callers, tests), finished by ``search``."""
        from elasticsearch_tpu.search.telemetry import (
            NULL_TRACER,
            get_opaque_id,
            request_tracer,
        )

        if not self._telemetry_enabled():
            return NULL_TRACER, False
        tracer = request_tracer()
        owned = not tracer.enabled
        if owned:
            tracer = self._tracer()
        elif get_opaque_id():
            tracer.annotate("opaque_id", get_opaque_id())
        if tracer.sink is None:
            tracer.sink = self.telemetry
        return tracer, owned

    @staticmethod
    def _annotate_batch_member(item, wait_s: float, batch_size: int,
                               member_index: int) -> None:
        """MicroBatcher telemetry hook: items are (body, deadline,
        tracer, opaque_id) — stamp the collection-window wait onto the
        member's tracer before the leader dispatches. The LAUNCH sites
        own batch_size/batch_member_index: only members that actually
        share a launch report a batch shape, a member that falls to
        serial execution must not claim one (docs/OBSERVABILITY.md)."""
        tracer = item[2] if len(item) > 2 else None
        if tracer is not None and getattr(tracer, "enabled", False):
            tracer.annotate("batch_window_wait_ms",
                            round(wait_s * 1000.0, 3))
            now = time.monotonic_ns()
            tracer.record("batch.window_wait",
                          now - int(wait_s * 1e9), now)

    def _maybe_search_slowlog(self, took_s: float, body: dict,
                              plane: str, tracer) -> None:
        """Search slowlog for mesh-plane-served queries (the host path's
        per-shard ShardSearcher slowlog never runs there): same logger,
        same thresholds, enriched with plane + top-3 phase spans + the
        request's X-Opaque-Id (docs/OBSERVABILITY.md)."""
        from elasticsearch_tpu.search.service import emit_search_slowlog

        emit_search_slowlog(self._slowlog_warn_s, self._slowlog_info_s,
                            took_s, "index", self.name, plane, tracer,
                            body)

    def _finish_query_response(self, resp: dict, body: dict, tracer,
                               plane: str, took_s: float) -> dict:
        """One choke point for per-query observability: count the
        query, attach the plane-truthful profile section, and emit the
        (mesh-plane) slowlog line."""
        # from here to the end of search.request: this count, the warm
        # spec, the profile, the slowlog, the admission slot's release
        tracer.fill("search.respond")
        self.telemetry.record_query(tracer)
        # program-variant warm spec (ISSUE 14, docs/RESILIENCE.md): a
        # mesh-served query shape joins the index's recorded lattice so
        # the next restart can warm its compiled variant off the query
        # path (deduped by structure — one record per variant)
        self._record_warm_variant("search", [body], plane)
        if body.get("profile"):
            prof = resp.setdefault("profile", {"shards": []})
            prof["plane"] = plane
            prof["phases"] = tracer.spans()
            prof["annotations"] = tracer.annotations()
            launches = tracer.launches()
            if launches:
                prof["annotations"]["launches"] = launches
            prof["request_id"] = tracer.request_id
            prof["spans"] = tracer.span_tree()
        if plane != "host":
            self._maybe_search_slowlog(took_s, body, plane, tracer)
        return resp

    def _mesh_allowed(self, body: dict) -> bool:
        """Whether a search of every shard's live segments may try the
        mesh plane: it is on, the request is no scroll (the mesh stages
        the LIVE segment set), and no copy is quarantined — a
        quarantined copy must FAIL, not serve: the mesh plane
        executes all shards as one program and cannot report a per-shard
        failure, so any corrupt-flagged shard forces the host path,
        where the flag becomes a failures[] entry."""
        return (self._mesh_enabled and not body.get("scroll")
                and not any(getattr(s, "store_corrupted", False)
                            for s in self.shards.values()))

    def _try_mesh_search(self, body: dict, k: int,
                         deadline=None, tracer=None) -> Optional[dict]:
        """Mesh query phase + host fetch phase. None = ineligible."""
        import time as _time

        from elasticsearch_tpu.search.service import fetch_hits
        from elasticsearch_tpu.search.telemetry import NULL_TRACER

        t0 = _time.monotonic()
        if tracer is None:
            tracer = NULL_TRACER
        if self._mesh_search is None:
            from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

            self._mesh_search = IndexMeshSearch(self)
        out = self._mesh_search.query(body, max(k, 1), deadline=deadline,
                                      tracer=tracer)
        if out is None:
            return None
        from_ = int(body.get("from", 0) or 0)
        size = int(body.get("size")) if body.get("size") is not None else 10
        refs = out["refs"]
        refs_window = refs[from_: from_ + size] if size >= 0 else refs[from_:]
        t_fetch = tracer.start("fetch")
        hits = fetch_hits(refs_window, self.shards, body, self.name)
        tracer.stop("fetch", t_fetch)
        resp = {
            "took": int((_time.monotonic() - t0) * 1000),
            "timed_out": False,
            # which data plane served the query phase (execution-plane
            # observability; mirrored as counters in _stats):
            # "mesh_pallas" = the tile kernel scored inside the mesh
            # program (the unified fast plane), "mesh" = scatter mesh
            "_plane": out.get("plane", "mesh"),
            "_shards": {"total": len(self.shards),
                        "successful": len(self.shards),
                        "skipped": 0, "failed": 0},
            "hits": {"total": out["total"], "max_score": out["max_score"],
                     "hits": hits},
        }
        if out.get("terminated_early") is not None:
            resp["terminated_early"] = bool(out["terminated_early"])
        if out.get("pruned") is not None:
            # block-max pruned scoring served the query phase: surface
            # the tile economy (and the gte-total semantics marker) next
            # to _plane so bench/tests can assert pruning actually fired
            resp["_pruned"] = out["pruned"]
        if out["aggregations"] is not None:
            resp["aggregations"] = out["aggregations"]
        if body.get("suggest"):
            # suggest is its own phase beside the query program
            # (SuggestPhase) — same host code as the fallback path
            from elasticsearch_tpu.search.suggest import run_suggest

            resp["suggest"] = run_suggest(
                body["suggest"], self.shards, self.mapper_service)
        return self._finish_query_response(
            resp, body, tracer, resp["_plane"],
            _time.monotonic() - t0)

    def _try_mesh_knn(self, body: dict, spec: dict, k: int,
                      deadline=None, tracer=None) -> Optional[dict]:
        """kNN query phase on the mesh_pallas MXU plane + host fetch
        phase. None = ineligible (callers run the host plan-node rung —
        the same ladder shape as _try_mesh_search). Response assembly is
        shared with the batched form (_mesh_batch_response) so the
        serial and batched kNN shapes can never diverge."""
        if self._mesh_search is None:
            from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

            self._mesh_search = IndexMeshSearch(self)
        out = self._mesh_search.query_knn(spec, max(k, 1),
                                          deadline=deadline,
                                          stats=body.get("stats"),
                                          tracer=tracer)
        if out is None:
            return None
        return self._mesh_batch_response(body, out, tracer=tracer)

    # the request keys both sides of a hybrid request's host rung carry:
    # a hit found only by the vector ranking must be fetched as the
    # lexical side's are (a _source spec withheld stays withheld)
    _HYBRID_PASSTHROUGH = ("timeout", "allow_partial_search_results",
                           "stats", "_source", "docvalue_fields",
                           "stored_fields", "script_fields", "highlight",
                           "version")

    def _search_hybrid(self, body: dict, deadline=None,
                       tracer=None) -> dict:
        """Hybrid ranking: the lexical ``query`` and the ``knn`` section
        fused as Elasticsearch 8 fuses them:

        - default — convex score fusion (``_search_convex``): the ``k``
          global nearest neighbours join the query's matches as a
          disjunction; a hit scores its query score plus ``knn.boost``
          times its kNN score, and ``hits.total`` counts the union,
          exactly;
        - ``rank: {rrf: {...}}`` — reciprocal rank fusion, score =
          Σ_sides 1 / (rank_constant + rank) (the reference's RRF
          retriever): each side retrieves a top-``window`` list through
          its own plane ladder and the lists fuse on the host. Its
          total is a LOWER BOUND (the union's count is not computed),
          marked by ``_total_relation: gte``, which the REST layer
          renders as ``{"value", "relation": "gte"}``.
        """
        import time as _time

        t0 = _time.monotonic()
        spec = body["knn"]
        if not isinstance(spec, dict) or "field" not in spec \
                or "query_vector" not in spec:
            raise IllegalArgumentException(
                "[knn] must be an object with [field] and [query_vector]")
        rank = body.get("rank")
        rrf = None
        if rank is not None:
            if not isinstance(rank, dict) or set(rank) != {"rrf"}:
                raise IllegalArgumentException(
                    "[rank] supports exactly one method: [rrf]")
            rrf = dict(rank.get("rrf") or {})
            unknown = set(rrf) - {"rank_constant", "window_size",
                                  "rank_window_size"}
            if unknown:
                # strict parsing, same contract as the knn clause: a
                # misspelled tuning knob must 400, never silently
                # fall back to defaults
                raise IllegalArgumentException(
                    f"[rrf] unknown parameter(s) {sorted(unknown)}")
            if "window_size" not in rrf and "rank_window_size" in rrf:
                # the reference's 8.x name for the same knob
                rrf["window_size"] = rrf["rank_window_size"]
            if int(rrf.get("rank_constant", 60)) < 1:
                raise IllegalArgumentException(
                    "[rank_constant] must be >= 1")
            if int(rrf.get("window_size", 1)) < 1:
                raise IllegalArgumentException(
                    "[window_size] must be >= 1")
        from_ = int(body.get("from", 0) or 0)
        size = int(body.get("size")) if body.get("size") is not None else 10
        k = max(from_ + size, 1)
        knn_k = int(spec.get("k", 10) or 10)
        if rrf is None:
            return self._search_convex(body, spec, from_, size, knn_k,
                                       deadline, tracer, t0)
        window = max(k, knn_k, int(rrf.get("window_size", k)))
        rank_constant = int(rrf.get("rank_constant", 60))

        lex_body = {key: v for key, v in body.items()
                    if key not in ("knn", "rank", "from", "size")}
        lex_body["size"] = window
        knn_body = {"query": {"knn": {key: v for key, v in spec.items()
                                      if key != "boost"}},
                    "size": window}
        for key in self._HYBRID_PASSTHROUGH:
            if key in body:
                knn_body[key] = body[key]
        lex_resp = self._search_uncached(lex_body, deadline=deadline)
        knn_resp = self._search_uncached(knn_body, deadline=deadline)

        def ranked(resp):
            return {h["_id"]: (i + 1, h)
                    for i, h in enumerate(resp["hits"]["hits"])}

        lex_hits, knn_hits = ranked(lex_resp), ranked(knn_resp)
        fused = []
        for doc_id in set(lex_hits) | set(knn_hits):
            lex_rank, lex_hit = lex_hits.get(doc_id, (None, None))
            knn_rank, knn_hit = knn_hits.get(doc_id, (None, None))
            score = sum(1.0 / (rank_constant + r)
                        for r in (lex_rank, knn_rank) if r is not None)
            hit = dict(lex_hit if lex_hit is not None else knn_hit)
            hit["_score"] = float(score)
            hit.pop("sort", None)
            fused.append(hit)
        fused.sort(key=lambda h: (-h["_score"], h["_id"]))
        page = fused[from_: from_ + size] if size >= 0 else fused[from_:]
        total = max(int(lex_resp["hits"]["total"]),
                    int(knn_resp["hits"]["total"]))
        resp = {
            "took": int((_time.monotonic() - t0) * 1000),
            "timed_out": bool(lex_resp.get("timed_out")
                              or knn_resp.get("timed_out")),
            "_plane": knn_resp.get("_plane", "host"),
            # per-side execution-plane observability + fusion mode
            "_hybrid": {"lexical_plane": lex_resp.get("_plane", "host"),
                        "knn_plane": knn_resp.get("_plane", "host"),
                        "fusion": "rrf"},
            # union count not computed: the fused total is a documented
            # lower bound (REST renders {"value", "relation": "gte"})
            "_total_relation": "gte",
            "_shards": self._merged_shards([lex_resp, knn_resp]),
            "hits": {"total": total,
                     "max_score": (page[0]["_score"] if page else None),
                     "hits": page},
        }
        # aggregations/suggest are request-level features orthogonal to
        # the ranking fusion: they are computed by the LEXICAL side
        # (whose window query saw the full matched set) and ride the
        # fused response unchanged — docs/VECTOR.md
        for key in ("aggregations", "suggest"):
            if key in lex_resp:
                resp[key] = lex_resp[key]
        return resp

    def _merged_shards(self, resps: list) -> dict:
        """The shard header of a response fused from sub-searches of the
        SAME shards: failures dedup'd by shard, so failed ==
        len(failures) and successful + failed == total stay consistent
        even where a shard failed on every side."""
        shards = dict(resps[0]["_shards"])
        seen = set()
        failures = []
        for f in (f for r in resps
                  for f in (r["_shards"].get("failures") or [])):
            key = (f.get("index"), f.get("shard"))
            if key not in seen:
                seen.add(key)
                failures.append(f)
        shards["failed"] = len(failures)
        shards["successful"] = max(
            int(shards.get("total", len(self.shards))) - len(failures), 0)
        shards.pop("failures", None)
        if failures:
            shards["failures"] = failures
        return shards

    def _count_hybrid(self, **adds) -> None:
        with self._stats_lock:
            for name, n in adds.items():
                self._hybrid_stats[name] += n

    def _search_convex(self, body: dict, spec: dict, from_: int, size: int,
                       knn_k: int, deadline, tracer, t0: float) -> dict:
        """A convex hybrid request, exact on either rung: ONE launch of
        the mesh hybrid program (``IndexMeshSearch.query_hybrid``) where
        the mesh plane serves, else the host rung (``_host_convex``).
        Either way one response, counted and profiled as one request;
        ``hits.total`` is the union's exact count (``_total_relation:
        eq``, rendered ``{"value", "relation": "eq"}``)."""
        import time as _time

        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )
        from elasticsearch_tpu.search.telemetry import NULL_TRACER

        if tracer is None:
            tracer = NULL_TRACER
        self._count_hybrid(hybrid_query_total=1)
        launched = tracer.launch_count()
        resp = None
        if self._mesh_allowed(body):
            try:
                resp = self._try_mesh_hybrid(body, spec, from_, size,
                                             deadline, tracer)
            except TimeExceededException:
                resp = None  # the host rung's sub-searches time out too
        if resp is None:
            resp = self._host_convex(body, spec, from_, size, knn_k,
                                     deadline, tracer)
        # the mesh launches made for it, where they were made (a faulted
        # rung's too)
        self._count_hybrid(
            hybrid_launches_total=tracer.launch_count() - launched)
        took_s = _time.monotonic() - t0
        resp["took"] = int(took_s * 1000)
        return self._finish_query_response(resp, body, tracer,
                                           resp["_plane"], took_s)

    def _try_mesh_hybrid(self, body: dict, spec: dict, from_: int,
                         size: int, deadline, tracer) -> Optional[dict]:
        """The convex hybrid's query phase as one mesh launch, and its
        fetch phase. None = ineligible (the host rung serves)."""
        if self._mesh_search is None:
            from elasticsearch_tpu.parallel.plan_exec import IndexMeshSearch

            self._mesh_search = IndexMeshSearch(self)
        lex_body = {key: v for key, v in body.items() if key != "knn"}
        out = self._mesh_search.query_hybrid(
            lex_body, spec, max(from_ + size, 1), deadline=deadline,
            tracer=tracer)
        if out is None:
            return None
        self._count_hybrid(hybrid_fused_total=1)
        refs = out["refs"]
        refs = refs[from_: from_ + size] if size >= 0 else refs[from_:]
        t_fetch = tracer.start("fetch")
        hits = fetch_hits(refs, self.shards, body, self.name)
        tracer.stop("fetch", t_fetch)
        return {
            "timed_out": False,
            "_plane": out["plane"],
            "_hybrid": {"lexical_plane": out["plane"],
                        "knn_plane": "mesh_pallas", "fusion": "convex"},
            "_total_relation": "eq",
            "_shards": {"total": len(self.shards),
                        "successful": len(self.shards),
                        "skipped": 0, "failed": 0},
            "hits": {"total": out["total"], "max_score": out["max_score"],
                     "hits": hits},
        }

    def _host_convex(self, body: dict, spec: dict, from_: int, size: int,
                     knn_k: int, deadline, tracer) -> dict:
        """The convex hybrid on the host rung, with the mesh program's
        answer: the lexical top ``from + size + k`` (at most ``k`` of
        them winners, so the page's other hits are among them), the
        ``k`` winners, and those of the winners past the lexical window
        scored lexically through an ``ids`` filter; a hit's score is the
        program's float32 sum, and the union total the lexical total
        plus the winners that matched no term. The fusion is the
        ``hybrid.fuse`` span."""
        import numpy as np

        sub = {key: body[key] for key in self._HYBRID_PASSTHROUGH
               if key in body}
        lex_body = {key: v for key, v in body.items()
                    if key not in ("knn", "rank", "from", "size",
                                   "profile")}
        lex_body["size"] = from_ + max(size, 0) + knn_k
        knn_body = dict(sub, query={"knn": {key: v for key, v in spec.items()
                                            if key != "boost"}},
                        size=knn_k)
        # (each sub-search has a tracer of its own, read for its
        # launches and drained nowhere)
        tracers = []

        def sub_search(sub_body):
            tracers.append(self._tracer())
            return self._search_uncached(sub_body, deadline=deadline,
                                         tracer=tracers[-1])

        resps = [sub_search(lex_body), sub_search(knn_body)]
        t_fuse = tracer.start("hybrid.fuse")
        lex_hits = resps[0]["hits"]["hits"]
        winners = resps[1]["hits"]["hits"][:knn_k]
        lexical = {h["_id"]: h for h in lex_hits}
        unseen = [h["_id"] for h in winners if h["_id"] not in lexical]
        if unseen:
            resps.append(sub_search(dict(
                sub, size=len(unseen), query={"bool": {
                    "must": [body["query"]],
                    "filter": [{"ids": {"values": unseen}}]}})))
            lexical.update((h["_id"], h) for h in resps[2]["hits"]["hits"])
        boost = np.float32(spec.get("boost", 1.0))
        knn_part = {h["_id"]: boost * np.float32(h["_score"] or 0.0)
                    for h in winners}
        fused = []
        for hit in {**{h["_id"]: h for h in winners},
                    **{h["_id"]: h for h in lex_hits}}.values():
            doc_id = hit["_id"]
            lex_score = (np.float32(lexical[doc_id]["_score"] or 0.0)
                         if doc_id in lexical else np.float32(0.0))
            hit = dict(lexical.get(doc_id, hit))
            hit["_score"] = float(lex_score
                                  + knn_part.get(doc_id, np.float32(0.0)))
            hit.pop("sort", None)
            fused.append(hit)
        fused.sort(key=lambda h: (-h["_score"], h["_id"]))
        page = fused[from_: from_ + size] if size >= 0 else fused[from_:]
        total = int(resps[0]["hits"]["total"]) + sum(
            1 for h in winners if h["_id"] not in lexical)
        tracer.stop("hybrid.fuse", t_fuse)
        self._count_hybrid(
            hybrid_host_fallback_total=1,
            hybrid_launches_total=sum(t.launch_count() for t in tracers))
        resp = {
            "timed_out": any(bool(r.get("timed_out")) for r in resps),
            "_plane": "host",
            "_hybrid": {"lexical_plane": resps[0].get("_plane", "host"),
                        "knn_plane": resps[1].get("_plane", "host"),
                        "fusion": "convex"},
            "_total_relation": "eq",
            "_shards": self._merged_shards(resps),
            "hits": {"total": total,
                     "max_score": fused[0]["_score"] if fused else None,
                     "hits": page},
        }
        # aggregations/suggest are the lexical side's, computed over the
        # query's whole match set — docs/VECTOR.md
        for key in ("aggregations", "suggest"):
            if key in resps[0]:
                resp[key] = resps[0][key]
        return resp

    def search(self, body: Optional[dict] = None,
               preference_shards: Optional[List[int]] = None,
               pinned_segments: Optional[Dict[int, list]] = None,
               deadline=None) -> dict:
        """pinned_segments: {shard_id: [PinnedSegmentView]} from an open
        scroll context — bypasses the request cache, can_match, and the
        mesh plane (all keyed to the LIVE segment set).
        deadline: SearchDeadline threaded from the coordinator — expiry
        degrades to partial results (timed_out: true), cancellation
        raises TaskCancelledException at the next checkpoint."""
        tracer, owned = self._request_tracer()
        t_request = tracer.start_parent("search.request")
        try:
            return self._search_traced(body, preference_shards,
                                       pinned_segments, deadline, tracer)
        finally:
            tracer.stop("search.request", t_request)
            if owned:
                tracer.finish()

    def _search_traced(self, body, preference_shards, pinned_segments,
                       deadline, tracer) -> dict:
        from elasticsearch_tpu.index.request_cache import (
            RequestCache,
            cacheable,
            shard_epoch,
        )

        # request-cache lookup, admission, brownout shaping: up to
        # where _admitted_dispatch opens search.route
        tracer.fill("search.admit")
        t0 = time.monotonic()
        body = body or {}
        if deadline is None and body.get("timeout") is not None:
            # direct IndexService.search callers get the same timeout
            # contract as the coordinator path
            from elasticsearch_tpu.search.cancellation import (
                SearchDeadline,
                parse_search_timeout,
            )

            deadline = SearchDeadline(parse_search_timeout(body))
        cache_key = None
        use_cache = self._request_cache_enabled
        if "request_cache" in body:
            # the URL's ?request_cache=, carried here by the REST layer:
            # false opts this request out; it is no part of the search
            use_cache = use_cache and body["request_cache"] is not False
            body = {k: v for k, v in body.items() if k != "request_cache"}
        # (a cached COMPLETE response is always valid under a deadline;
        # only the put below filters — partial/timed-out responses must
        # not poison the cache)
        if (use_cache and preference_shards is None
                and pinned_segments is None and cacheable(body)):
            epochs = [shard_epoch(self.shards[sid])
                      for sid in sorted(self.shards)]
            cache_key = RequestCache.key_for(body, epochs)
            if cache_key is not None:
                cached = self.request_cache.get(cache_key)
                if cached is not None:
                    cached["took"] = int((time.monotonic() - t0) * 1000)
                    return cached
        resp = self._search_dispatch(body, preference_shards,
                                     pinned_segments, deadline=deadline,
                                     tracer=tracer)
        if (cache_key is not None and not resp.get("timed_out")
                and not resp["_shards"].get("failed")
                and not resp.get("_degraded")):
            # browned-out responses (shed aggs/rescore, forced pruning)
            # must not poison the cache: once pressure drains the same
            # body must serve full-precision, full-feature again
            self.request_cache.put(cache_key, resp)
        return resp

    def _search_dispatch(self, body: dict,
                         preference_shards: Optional[List[int]] = None,
                         pinned_segments: Optional[Dict[int, list]] = None,
                         deadline=None, tracer=None) -> dict:
        """Overload-control choke point (search/admission.py, ISSUE 12):
        every top-level search acquires an admission slot here BEFORE
        any staging/launch work. Overflow raises the 429 rejection; a
        deadline that expired while queued is shed pre-execution and
        serves its partial timed-out response; admitted queries execute
        shaped by the brownout ladder (forced pruning eligibility /
        shed rescore / shed aggs+suggest, marked ``_degraded``)."""
        from elasticsearch_tpu.search.service import expired_queue_response

        token = self.admission.acquire(deadline=deadline, tracer=tracer)
        if token.shed_expired:
            if deadline is not None:
                deadline.timed_out = True
            return expired_queue_response(self.name, len(self.shards),
                                          body)
        try:
            shaped, degraded = self.admission.apply_brownout(body, token)
            resp = self._admitted_dispatch(shaped, preference_shards,
                                           pinned_segments,
                                           deadline=deadline, tracer=tracer)
            if degraded and isinstance(resp, dict):
                # the degradation marker ALSO keeps the response out of
                # the request cache (IndexService.search): a browned-out
                # response must never be replayed after pressure drains
                resp["_degraded"] = degraded
            return resp
        finally:
            self.admission.release(token)

    def _admitted_dispatch(self, body: dict,
                           preference_shards: Optional[List[int]] = None,
                           pinned_segments: Optional[Dict[int, list]]
                           = None, deadline=None, tracer=None) -> dict:
        """Route the query phase through the cross-query micro-batcher
        when eligible (search/batching.py): a concurrent burst of
        compatible queries shares one batched kernel launch; a lone query
        takes the unbatched path with zero added latency."""
        from elasticsearch_tpu.search.batching import batchable_body
        from elasticsearch_tpu.search.telemetry import get_opaque_id

        if tracer is None:
            tracer = self._tracer()
        # from here to the serving plane's first phase: batcher, ladder
        tracer.fill("search.route")
        if (not self._batcher.enabled or preference_shards is not None
                or pinned_segments is not None or body.get("scroll")
                or not batchable_body(body)):
            return self._search_uncached(body, preference_shards,
                                         pinned_segments, deadline=deadline,
                                         tracer=tracer)
        # the member's X-Opaque-Id rides the ITEM: the batch executes on
        # the leader's thread, whose own request context must not stamp
        # other members' slowlog lines (NULL_TRACER under the kill
        # switch carries no annotation to correct it)
        return self._batcher.run(
            self.name, (body, deadline, tracer, get_opaque_id()),
            single_fn=lambda it: self._search_uncached(
                it[0], deadline=it[1], tracer=it[2]),
            batch_fn=lambda items: self.search_batch(
                [it[0] for it in items], [it[1] for it in items],
                [it[2] for it in items], [it[3] for it in items]))

    def _search_uncached(self, body: dict,
                         preference_shards: Optional[List[int]] = None,
                         pinned_segments: Optional[Dict[int, list]] = None,
                         deadline=None, score_caches: Optional[dict] = None,
                         skip_mesh: bool = False, tracer=None) -> dict:
        """score_caches: {(shard_id, segment_name): (scores, matched)}
        from a cross-query batched kernel launch (search_batch) — cached
        segments skip plan execution inside ShardSearcher.query.
        skip_mesh: the query already went through the batch's plane
        ladder; don't re-probe the mesh plane per member.
        tracer: this request's QueryTracer (created here when absent);
        spans attribute to whichever plane ends up serving."""
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )
        from elasticsearch_tpu.search.service import (
            allow_partial_results,
            shard_failure_entry,
        )

        if tracer is None:
            tracer = self._tracer()
        body = body or {}
        # device-plane fault injection consult point (ISSUE 10): the
        # EvictionStormScheme forces the accountant's LRU evictor here,
        # under real query load
        from elasticsearch_tpu.testing.disruption import on_query_begin

        on_query_begin(self.name)
        if body.get("knn") is not None:
            # top-level ``knn`` section (the reference's knn search
            # surface): alone it is a pure vector search — normalize to
            # the ``knn`` query clause so the whole pipeline (plane
            # ladder, deadlines, partial results, fetch) serves it;
            # combined with ``query`` it is HYBRID ranking (RRF or
            # convex fusion) — see docs/VECTOR.md
            if not isinstance(body["knn"], dict):
                raise IllegalArgumentException(
                    "[knn] must be an object with [field] and "
                    "[query_vector]")
            if body.get("query") is not None:
                return self._search_hybrid(body, deadline=deadline,
                                           tracer=tracer)
            body = dict(body)
            spec = body.pop("knn")
            if body.pop("rank", None) is not None:
                raise IllegalArgumentException(
                    "[rank] requires both [query] and [knn] sections")
            body["query"] = {"knn": spec}
            if body.get("size") is None and spec.get("k") is not None:
                body["size"] = int(spec["k"])

        t0 = time.monotonic()
        from_ = int(body.get("from", 0) or 0)
        size = int(body.get("size")) if body.get("size") is not None else 10
        k = from_ + size
        shard_ids = preference_shards or sorted(self.shards)
        sort_spec = normalize_sort(body.get("sort"))
        allow_partial = allow_partial_results(body)
        timed_out = False

        # mesh data plane: eligible searches over all shards run as ONE
        # multi-device program (query + DFS-free scoring + global top-k
        # merge in-XLA); fallback is the per-shard host merge below.
        # Pinned (scroll) searches stay on the host path: the mesh stages
        # the LIVE segment set.
        if (not skip_mesh and preference_shards is None
                and pinned_segments is None and self._mesh_allowed(body)):
            try:
                knn_clause = _pure_knn_mesh_clause(body)
                if knn_clause is not None:
                    mesh_resp = self._try_mesh_knn(body, knn_clause, k,
                                                   deadline=deadline,
                                                   tracer=tracer)
                else:
                    mesh_resp = self._try_mesh_search(body, k,
                                                      deadline=deadline,
                                                      tracer=tracer)
            except TimeExceededException:
                # deadline expired inside the mesh plane: the host loop
                # below breaks at its first checkpoint and reports the
                # accumulated (empty) partial result
                mesh_resp = None
                timed_out = True
            if mesh_resp is not None:
                return mesh_resp
        with self._stats_lock:
            self._host_query_total += 1

        shard_results = []
        failures = []
        # can_match prefilter (SearchService.canMatch /
        # TransportSearchAction pre-filtering): shards whose doc-value
        # bounds cannot satisfy a pure range query are skipped without
        # executing the query phase
        skipped = 0
        active_ids = []
        for sid in shard_ids:
            if (preference_shards is None and pinned_segments is None
                    and not _can_match(self.shards[sid], body)):
                # (pinned searches bypass can_match: its bounds come from
                # the live segment set, not the pinned view)
                skipped += 1
                continue
            active_ids.append(sid)
        if not active_ids and shard_ids:
            # keep at least one shard so the response shape (empty hits,
            # empty agg frames) is produced by a real query phase
            active_ids = [shard_ids[0]]
            skipped -= 1
        for sid in active_ids:
            if timed_out or (deadline is not None and deadline.expired):
                # accumulated shard results stand; the fan-out stops
                timed_out = True
                if deadline is not None:
                    deadline.timed_out = True
                break
            try:
                if getattr(self.shards[sid], "store_corrupted", False):
                    # quarantined copy (ISSUE 16): fail the shard into
                    # failures[] — never silent empty hits, never a
                    # re-read of the marked bytes
                    raise CorruptIndexException(
                        f"shard [{self.name}][{sid}] store is marked "
                        f"corrupted — awaiting re-recovery from a "
                        f"healthy copy")
                shard_cache = None
                if score_caches:
                    shard_cache = {
                        name: pair for (s, name), pair
                        in score_caches.items() if s == sid}
                shard_results.append(
                    self.shards[sid].searcher.query(
                        body, size_hint=max(k, 1),
                        segments=(pinned_segments.get(sid, [])
                                  if pinned_segments is not None else None),
                        deadline=deadline, score_cache=shard_cache,
                        tracer=tracer)
                )
            except TaskCancelledException:
                raise  # _tasks/_cancel: a clean request-level error
            except TimeExceededException:
                timed_out = True
                break
            except Exception as e:  # noqa: BLE001 — per-shard isolation
                if _is_request_error(e):
                    # request-level validation (parse/mapping/argument):
                    # deterministic on every shard — surface it with its
                    # own 4xx status instead of masking it as failures
                    raise
                if (isinstance(e, CorruptIndexException)
                        and not getattr(self.shards[sid],
                                        "store_corrupted", False)):
                    # first detection on the query path: quarantine the
                    # copy (marker + staging release) — subsequent
                    # queries fail fast on the flag without recounting
                    self._quarantine_shard(sid, e, site="query")
                # one bad shard (corrupt segment, injected fault, compile
                # error) becomes a failures[] entry + _shards.failed, not
                # a 500 (AbstractSearchAsyncAction.onShardFailure)
                failures.append(shard_failure_entry(self.name, sid, e))
        timed_out = timed_out or any(r.timed_out for r in shard_results)
        if failures and not shard_results and not timed_out:
            # every shard failed: no results to degrade to
            # (SearchPhaseExecutionException "all shards failed")
            raise SearchPhaseExecutionException(
                "query", "all shards failed", failures)
        if not allow_partial and (failures or timed_out):
            raise SearchPhaseExecutionException(
                "query",
                "Partial shards failure"
                + (" (request timed out)" if timed_out else ""),
                failures)
        total = sum(r.total_hits for r in shard_results)
        max_score = None
        for r in shard_results:
            if r.max_score is not None:
                max_score = r.max_score if max_score is None else max(max_score, r.max_score)
        collapse_body = body.get("collapse") or {}
        collapse_field = collapse_body.get("field")
        merge_k = max(k, 0)
        if collapse_field:
            merge_k = 0  # keep all candidates; collapsing shrinks the list
        t_merge = tracer.start("merge")
        all_refs = [ref for r in shard_results for ref in r.refs]
        refs = merge_refs(all_refs, sort_spec, merge_k or len(all_refs))
        if collapse_field:
            from elasticsearch_tpu.search.service import collapse_refs

            refs = collapse_refs(refs, collapse_field, self.shards)[: max(k, 0)]
        refs_window = refs[from_: from_ + size] if size >= 0 else refs[from_:]
        tracer.stop("merge", t_merge)

        aggregations = None
        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        if agg_specs:
            # host-path agg execution gets its own phase span (ISSUE 13:
            # the `aggregate` taxonomy entry) so phase_attribution_p50_ms
            # can show what the fused plane removes
            t_agg = tracer.start("aggregate")
            views = [v for r in shard_results for v in r.agg_views]
            aggregations = run_aggregations(agg_specs, views)
            tracer.stop("aggregate", t_agg)

        t_fetch = tracer.start("fetch")
        hits = fetch_hits(refs_window, self.shards, body, self.name,
                          pinned_segments=pinned_segments)
        tracer.stop("fetch", t_fetch)
        if collapse_field:
            from elasticsearch_tpu.search.service import expand_collapsed_hits

            expand_collapsed_hits(
                hits, refs_window, collapse_body, body,
                lambda sub: self.search(sub, deadline=deadline))
        took = int((time.monotonic() - t0) * 1000)
        resp = {
            "took": took,
            "timed_out": timed_out,
            "_plane": "host",
            "_shards": {
                # shards the deadline cut before they ran count successful
                # (they did not fail — the reference reports responded +
                # unreached alike against the timeout flag)
                "total": len(shard_ids),
                "successful": len(shard_ids) - len(failures),
                "skipped": skipped,
                "failed": len(failures),
            },
            "hits": {
                "total": total,
                "max_score": max_score,
                "hits": hits,
            },
        }
        if failures:
            resp["_shards"]["failures"] = failures
        if any(r.terminated_early is not None for r in shard_results):
            resp["terminated_early"] = any(
                bool(r.terminated_early) for r in shard_results
            )
        if aggregations is not None:
            resp["aggregations"] = aggregations
        if body.get("profile"):
            resp["profile"] = {"shards": [
                s for r in shard_results for s in (r.profile or [])
            ]}
        if body.get("suggest"):
            from elasticsearch_tpu.search.suggest import run_suggest

            resp["suggest"] = run_suggest(
                body["suggest"], self.shards, self.mapper_service
            )
        return self._finish_query_response(resp, body, tracer, "host",
                                           took / 1000.0)

    # ------------------------------------------------------------------
    # Cross-query micro-batching (search/batching.py; docs/BATCHING.md)
    # ------------------------------------------------------------------

    def search_batch(self, bodies: List[dict],
                     deadlines: Optional[list] = None,
                     tracers: Optional[list] = None,
                     oids: Optional[list] = None) -> list:
        """Execute Q concurrent search requests as one micro-batch.

        Returns one entry per member: the response dict, or the
        exception that member alone should raise (cancellation, request
        error) — peers are never failed by one member's fate.

        Plane ladder, mirroring the serial path:
        1. an expired member is served its partial (timed_out) result
           individually and a cancelled member gets its
           TaskCancelledException — both are DROPPED from the batch;
        2. mesh_pallas rung: eligible batches run as ONE batched kernel
           launch inside the mesh program (IndexMeshSearch.query_batch);
           a batch-wide plane fault feeds the PlaneHealth quarantine
           ONCE and the batch falls to the next rung;
        3. host-pallas rung: one batched launch per segment feeds each
           member's normal per-query pipeline via score caches;
        4. members ineligible for any shared launch execute serially.
        """
        from elasticsearch_tpu.search.batching import batchable_body
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )
        from elasticsearch_tpu.search.telemetry import (
            get_opaque_id,
            set_opaque_id,
        )

        n = len(bodies)
        deadlines = list(deadlines) if deadlines else [None] * n
        # direct callers (tests, dryrun) pass no tracers: create per-
        # member ones so batched profile/phase attribution still works
        tracers = (list(tracers) if tracers
                   else [self._tracer() for _ in bodies])
        # every member executes on THIS (the leader's) thread: its own
        # X-Opaque-Id must be the contextvar while its result is built,
        # or its slowlog line logs the leader's client id; the leader's
        # context is restored before returning
        leader_oid = get_opaque_id()
        oids = list(oids) if oids else [leader_oid] * n
        results: list = [None] * n
        live: List[int] = []
        for i, body in enumerate(bodies):
            dl = deadlines[i]
            if dl is not None:
                try:
                    dl.checkpoint()
                except TaskCancelledException as e:
                    # _tasks/_cancel of one member: its own clean error,
                    # the batch proceeds without it
                    results[i] = e
                    continue
                except TimeExceededException:
                    # expired before dispatch: serve its accumulated
                    # (empty) partial result — the serial path hits the
                    # same checkpoint immediately and reports timed_out
                    set_opaque_id(oids[i])
                    results[i] = self._batch_member_single(body, dl,
                                                           tracer=tracers[i])
                    continue
            if not batchable_body(body):
                set_opaque_id(oids[i])
                results[i] = self._batch_member_single(body, dl,
                                                       tracer=tracers[i])
                continue
            live.append(i)

        # pure-kNN members split off onto the kNN MXU plane: the batched
        # dense-matmul launch streams the embedding matrix once for the
        # whole vector burst (IndexMeshSearch.query_knn_batch); members
        # it can't serve fall back to their serial pipeline one by one
        from elasticsearch_tpu.search.batching import knn_batch_spec

        knn_live = [i for i in live if knn_batch_spec(bodies[i])]
        if knn_live:
            live = [i for i in live if i not in set(knn_live)]
            self._dispatch_knn_batch(bodies, deadlines, knn_live, results,
                                     tracers, oids=oids)

        if len(live) < 2:
            for i in live:
                set_opaque_id(oids[i])
                results[i] = self._batch_member_single(bodies[i],
                                                       deadlines[i],
                                                       tracer=tracers[i])
            set_opaque_id(leader_oid)
            return results

        live_bodies = [bodies[i] for i in live]
        # rung 1: batched mesh_pallas launch (one program, Q queries).
        # A plane fault inside quarantines mesh_pallas exactly once.
        mesh_out = None
        if (self._mesh_enabled and len(self.shards) >= 2
                # quarantined copies fail per-shard on the host path
                # (ISSUE 16) — same gate as the serial mesh dispatch
                and not any(getattr(s, "store_corrupted", False)
                            for s in self.shards.values())):
            if self._mesh_search is None:
                from elasticsearch_tpu.parallel.plan_exec import (
                    IndexMeshSearch,
                )

                self._mesh_search = IndexMeshSearch(self)
            mesh_out = self._mesh_search.query_batch(
                live_bodies, tracers=[tracers[i] for i in live])
        if mesh_out is not None:
            for j, i in enumerate(live):
                set_opaque_id(oids[i])
                try:
                    results[i] = self._mesh_batch_response(
                        bodies[i], mesh_out[j], tracer=tracers[i])
                except Exception as e:  # noqa: BLE001 — per-member fetch
                    results[i] = e
            self.batch_stats.note_batch(len(live))
            # batched program-variant warm spec (ISSUE 14): record the
            # burst's shape so restart warming replays a same-shaped
            # batch through query_batch (the batched q_pad/kk variants
            # are distinct compiled programs from the serial ones)
            self._record_warm_variant("search_batch", live_bodies,
                                      "mesh_pallas")
            set_opaque_id(leader_oid)
            return results

        # rung 2: host-pallas batched scoring, then each member's normal
        # per-query pipeline on top of its cached score vectors
        caches, launches = self._host_batch_scores(live_bodies)
        # count only the members that actually shared a launch — kernel-
        # ineligible members executed fully serially and must not inflate
        # the batching-coverage telemetry (same rule for the batch-shape
        # annotations below)
        shared = sum(1 for c in caches if c)
        member_idx = 0
        for j, i in enumerate(live):
            set_opaque_id(oids[i])
            if caches[j]:
                tr = tracers[i]
                if tr is not None and getattr(tr, "enabled", False):
                    tr.annotate("batch_size", shared)
                    tr.annotate("batch_member_index", member_idx)
                member_idx += 1
            results[i] = self._batch_member_single(
                bodies[i], deadlines[i], score_caches=caches[j] or None,
                skip_mesh=bool(caches[j]), tracer=tracers[i])
        if launches and shared:
            self.batch_stats.note_batch(shared)
        set_opaque_id(leader_oid)
        return results

    @staticmethod
    def _knn_member_body(body) -> dict:
        """The serial path's top-level-knn size normalization (size
        defaults to the spec's k), applied to a batch member so a
        request returns the SAME hit count whether or not it happened
        to share a batch window."""
        body = dict(body or {})
        spec = body.get("knn")
        if (isinstance(spec, dict) and body.get("query") is None
                and body.get("size") is None
                and spec.get("k") is not None):
            body["size"] = int(spec["k"])
        return body

    def _dispatch_knn_batch(self, bodies, deadlines, knn_live, results,
                            tracers=None, oids=None):
        """Serve a burst of pure-kNN members: one batched MXU launch
        when they target the same field and the mesh plane is up, else
        per-member serial execution (which still rides the serial kNN
        ladder). Fills ``results`` in place."""
        from elasticsearch_tpu.search.batching import knn_batch_spec

        from elasticsearch_tpu.search.telemetry import scoped_opaque_id

        if tracers is None:
            tracers = [None] * len(bodies)
        if oids is None:
            oids = [None] * len(bodies)

        specs = [knn_batch_spec(bodies[i]) for i in knn_live]
        norm_bodies = {i: self._knn_member_body(bodies[i])
                       for i in knn_live}
        ks = []
        for i in knn_live:
            body = norm_bodies[i]
            from_ = int(body.get("from", 0) or 0)
            size = (int(body.get("size"))
                    if body.get("size") is not None else 10)
            ks.append(max(from_ + size, 1))
        mesh_out = None
        if (self._mesh_enabled and len(self.shards) >= 2
                and len(knn_live) >= 2
                and len({str(s.get("field")) for s in specs}) == 1):
            if self._mesh_search is None:
                from elasticsearch_tpu.parallel.plan_exec import (
                    IndexMeshSearch,
                )

                self._mesh_search = IndexMeshSearch(self)
            mesh_out = self._mesh_search.query_knn_batch(
                specs, ks,
                stats=[norm_bodies[i].get("stats") for i in knn_live],
                tracers=[tracers[i] for i in knn_live])
        # scoped stamps (PR-15 contract-lint fix): the bare set_opaque_id
        # shape left the LAST member's id in the leader's context on both
        # exit paths, mis-attributing its later slowlog/profile lines
        if mesh_out is not None:
            for j, i in enumerate(knn_live):
                with scoped_opaque_id(oids[i]):
                    try:
                        results[i] = self._mesh_batch_response(
                            norm_bodies[i], mesh_out[j],
                            tracer=tracers[i])
                    except Exception as e:  # noqa: BLE001 — per-member
                        results[i] = e  # fetch isolation
            self.batch_stats.note_batch(len(knn_live))
            return
        for i in knn_live:
            with scoped_opaque_id(oids[i]):
                results[i] = self._batch_member_single(
                    bodies[i], deadlines[i], tracer=tracers[i])

    def _batch_member_single(self, body, deadline, score_caches=None,
                             skip_mesh=False, tracer=None):
        """One member's serial execution inside a batch: exceptions are
        captured as that member's result instead of failing its peers."""
        try:
            return self._search_uncached(
                body, deadline=deadline, score_caches=score_caches,
                skip_mesh=skip_mesh, tracer=tracer)
        except Exception as e:  # noqa: BLE001 — per-member isolation
            return e

    def _host_batch_scores(self, bodies: List[dict]):
        """Per-segment batched kernel launches for the host rung.

        Returns ([per-member {(shard_id, seg_name): (scores, matched)}],
        n_launches). A member whose plan on a segment isn't a pure
        kernel-scored disjunction simply gets no cache entry there and
        executes that segment serially — per-query semantics are owned
        by the normal pipeline either way."""
        from elasticsearch_tpu.search.batching import (
            batched_segment_scores,
            counts_safe_for_union,
        )
        from elasticsearch_tpu.search.plan import PallasScoreTermsNode
        from elasticsearch_tpu.search.query_dsl import parse_query

        caches: List[dict] = [dict() for _ in bodies]
        launches = 0
        qbs = []
        for body in bodies:
            try:
                qbs.append(parse_query(body.get("query")))
            except Exception:  # noqa: BLE001 — parse errors surface with
                # their proper status when the member executes serially
                qbs.append(None)
        for sid in sorted(self.shards):
            shard = self.shards[sid]
            ctx = shard.searcher.ctx
            for seg in shard.engine.searchable_segments():
                if seg.num_docs == 0:
                    continue
                plans = []
                for qb in qbs:
                    node = None
                    if qb is not None:
                        try:
                            p = qb.to_plan(ctx, seg)
                            if (isinstance(p, PallasScoreTermsNode)
                                    and getattr(p, "_host_lanes", None)
                                    and counts_safe_for_union(p)):
                                node = p
                        except Exception:  # noqa: BLE001 — serial path
                            # owns this member's error shape
                            node = None
                    plans.append(node)
                idxs = [i for i, p in enumerate(plans) if p is not None]
                if len(idxs) < 2:
                    continue  # nothing to amortize on this segment
                try:
                    outs = batched_segment_scores(
                        seg, [plans[i] for i in idxs])
                except Exception:  # noqa: BLE001 — batched launch fault:
                    # every member still serves serially (and a kernel
                    # fault on the serial path feeds its own quarantine)
                    outs = None
                if outs is None:
                    continue
                launches += 1
                for j, i in enumerate(idxs):
                    caches[i][(sid, seg.name)] = outs[j]
        return caches, launches

    def _mesh_batch_response(self, body: dict, out: dict,
                             tracer=None) -> dict:
        """Assemble one member's full response from its slice of a
        batched mesh launch (same shape as _try_mesh_search)."""
        import time as _time

        from elasticsearch_tpu.search.service import fetch_hits
        from elasticsearch_tpu.search.telemetry import NULL_TRACER

        if tracer is None:
            tracer = NULL_TRACER
        t0 = _time.monotonic()
        t_demux = tracer.start("batch_demux")
        from_ = int(body.get("from", 0) or 0)
        size = int(body.get("size")) if body.get("size") is not None else 10
        refs = out["refs"]
        refs_window = (refs[from_: from_ + size] if size >= 0
                       else refs[from_:])
        tracer.stop("batch_demux", t_demux)
        t_fetch = tracer.start("fetch")
        hits = fetch_hits(refs_window, self.shards, body, self.name)
        tracer.stop("fetch", t_fetch)
        resp = {
            "took": int((_time.monotonic() - t0) * 1000),
            "timed_out": False,
            # per-query truth: every member of the batch was scored by
            # the batched mesh_pallas launch
            "_plane": out.get("plane", "mesh_pallas"),
            "_shards": {"total": len(self.shards),
                        "successful": len(self.shards),
                        "skipped": 0, "failed": 0},
            "hits": {"total": out["total"], "max_score": out["max_score"],
                     "hits": hits},
        }
        if out.get("aggregations") is not None:
            # fused on-device aggregations computed inside the batched
            # launch (ISSUE 13, docs/AGGS.md)
            resp["aggregations"] = out["aggregations"]
        if out.get("pruned") is not None:
            resp["_pruned"] = out["pruned"]
        return self._finish_query_response(
            resp, body, tracer, resp["_plane"], _time.monotonic() - t0)

    def count(self, body: Optional[dict] = None) -> dict:
        body = dict(body or {})
        body["size"] = 0
        r = self.search(body)
        return {"count": r["hits"]["total"], "_shards": r["_shards"]}

    # ------------------------------------------------------------------

    @property
    def num_docs(self) -> int:
        return sum(s.num_docs for s in self.shards.values())

    def search_stats(self, shard_stats: Optional[dict] = None) -> dict:
        """The ``search`` stats block alone (SearchStats + the TPU-plane
        extensions) — reused verbatim by ``stats()`` and aggregated
        across indices into the ``_nodes/stats`` search section
        (docs/OBSERVABILITY.md)."""
        if shard_stats is None:
            shard_stats = {sid: s.stats() for sid, s in self.shards.items()}
        groups: Dict[str, dict] = {}
        for s in shard_stats.values():
            for g, gs in (s["search"].get("groups") or {}).items():
                agg = groups.setdefault(g, {k: 0 for k in gs})
                for k, v in gs.items():
                    agg[k] += v
        search = {
            "open_contexts": 0,
            "query_total": sum(s["search"]["query_total"]
                               for s in shard_stats.values()),
            "query_time_in_millis": sum(s["search"]["query_time_in_millis"]
                                        for s in shard_stats.values()),
            "fetch_total": sum(s["search"].get("fetch_total", 0)
                               for s in shard_stats.values()),
            # execution-plane counters (VERDICT r4 weak 3): on a TPU
            # deployment "did we use the chip?" must be observable —
            # which data plane served each query (mesh program vs host
            # scatter-merge) and which engine scored each segment
            "planes": {
                "mesh_query_total": (self._mesh_search.query_total
                                     if self._mesh_search is not None
                                     else 0),
                "mesh_pallas_query_total": (
                    self._mesh_search.pallas_query_total
                    if self._mesh_search is not None else 0),
                "host_query_total": self._host_query_total,
                # convex hybrid requests: served by the one-launch mesh
                # program, fused on the host rung, and the mesh launches
                # made for them (docs/VECTOR.md)
                **self._hybrid_stats,
                "pallas_segments_total": sum(
                    s["search"]["planes"]["pallas_segments_total"]
                    for s in shard_stats.values()),
                "scatter_segments_total": sum(
                    s["search"]["planes"]["scatter_segments_total"]
                    for s in shard_stats.values()),
                # plane-health quarantine (docs/RESILIENCE.md): per-plane
                # fault counters + which planes are currently benched
                **(self._mesh_search.plane_health.stats()
                   if self._mesh_search is not None else
                   {"plane_failures_total": {"mesh_pallas": 0, "mesh": 0},
                    "plane_failures_by_reason": {},
                    "plane_probes_total": 0,
                    "plane_quarantined": [], "quarantine_events": []}),
                # block-max pruned scoring + postings codec observability
                # (docs/PRUNING.md): queries served pruned, the tile
                # economy, and what representation the postings stream as
                # dense-vector retrieval (docs/VECTOR.md): kNN queries
                # served by the mesh MXU program
                "knn_query_total": (
                    self._mesh_search.knn_query_total
                    if self._mesh_search is not None else 0),
                # the slots whose kNN pass ran, a query each (a slot
                # with no live vector is skipped), and the bf16
                # embedding bytes those passes streamed, a launch
                "knn_slots_scanned_total": (
                    self._mesh_search.knn_slots_scanned_total
                    if self._mesh_search is not None else 0),
                "embedding_bytes_streamed_total": (
                    self._mesh_search.embedding_bytes_streamed_total
                    if self._mesh_search is not None else 0),
                # the mesh's devices a launch ran on, and those holding
                # a segment: the share of the chips given work
                "mesh_devices_total": (
                    self._mesh_search.mesh_devices_total
                    if self._mesh_search is not None else 0),
                "mesh_devices_live_total": (
                    self._mesh_search.mesh_devices_live_total
                    if self._mesh_search is not None else 0),
                # where the staged generation's segments sit: device d's
                # slots holding one at [d] ([] with nothing staged)
                "mesh_live_slots_per_device": (
                    self._mesh_search._executor.live_slots_per_device()
                    if self._mesh_search is not None
                    and self._mesh_search._executor is not None else []),
                # scored terms as the serial mesh rungs planned them:
                # block rows expanded for scatter nodes, kernel lanes
                "plan_blocks_expanded_total": (
                    self._mesh_search.plan_blocks_expanded_total
                    if self._mesh_search is not None else 0),
                "kernel_lanes_planned_total": (
                    self._mesh_search.kernel_lanes_planned_total
                    if self._mesh_search is not None else 0),
                # the kernel node groups' one-pass tile tables, and the
                # geometry ladder's rungs below the staged tile
                "kernel_table_passes_total": (
                    self._mesh_search.kernel_table_passes_total
                    if self._mesh_search is not None else 0),
                "kernel_ladder_rungs_total": (
                    self._mesh_search.kernel_ladder_rungs_total
                    if self._mesh_search is not None else 0),
                # fused on-device aggregations (ISSUE 13, docs/AGGS.md):
                # agg'd queries whose whole agg set reduced inside the
                # mesh program vs those that fell back to the host
                # reduce, per documented reason
                "agg_fused_query_total": (
                    self._mesh_search.agg_fused_query_total
                    if self._mesh_search is not None else 0),
                "agg_host_fallback_total": (
                    self._mesh_search.agg_host_fallback_total
                    if self._mesh_search is not None else 0),
                # the fused queries' bucket counts, by formulation: a
                # dense compare-and-sum up to
                # fused_aggs.DENSE_COUNT_MAX_BUCKETS, a one-hot product
                # above
                "agg_bucket_dense_total": (
                    self._mesh_search.agg_bucket_dense_total
                    if self._mesh_search is not None else 0),
                "agg_bucket_product_total": (
                    self._mesh_search.agg_bucket_product_total
                    if self._mesh_search is not None else 0),
                # field sorts ranked inside the mesh program (the ladder's
                # host.sort_ineligible counts those that left it)
                "sort_device_query_total": (
                    self._mesh_search.sort_device_query_total
                    if self._mesh_search is not None else 0),
                "agg_host_fallback_by_reason": (
                    dict(self._mesh_search.agg_host_fallback_by_reason)
                    if self._mesh_search is not None else {}),
                "pruned_query_total": (
                    self._mesh_search.pruned_query_total
                    if self._mesh_search is not None else 0),
                # delta device staging (ISSUE 20): incremental appends
                # served without a geometry rebuild, in-place tombstone
                # mask updates, and background compaction passes
                "delta_restage_total": (
                    self._mesh_search.delta_restage_total
                    if self._mesh_search is not None else 0),
                "tombstone_update_total": (
                    self._mesh_search.tombstone_update_total
                    if self._mesh_search is not None else 0),
                "compaction_runs_total": (
                    self._mesh_search.compaction_runs_total
                    if self._mesh_search is not None else 0),
                "tiles_scored_total": (
                    self._mesh_search.tiles_scored_total
                    if self._mesh_search is not None else 0),
                "tiles_pruned_total": (
                    self._mesh_search.tiles_pruned_total
                    if self._mesh_search is not None else 0),
                "postings_codec": (
                    self._mesh_search._executor.postings_codec
                    if self._mesh_search is not None
                    and self._mesh_search._executor is not None
                    else None),
                # staged posting bytes: the mesh-plane staging plus every
                # shard segment's host-plane kernel staging (raw stages
                # 8 B/posting, packed 4 B — the restage cost ROADMAP
                # item 3 tracks shrinks with it)
                "postings_bytes_staged": (
                    (self._mesh_search._executor.postings_bytes_staged
                     if self._mesh_search is not None
                     and self._mesh_search._executor is not None else 0)
                    + sum(int(getattr(seg, "kernel_postings_bytes", 0))
                          for sh in self.shards.values()
                          for seg in sh.engine.searchable_segments())),
            },
            # cross-query micro-batching (docs/BATCHING.md): how much of
            # the traffic shared batched kernel launches, the dispatched
            # batch-size distribution, and how often a leader paid the
            # collection window
            "batch": self.batch_stats.as_dict(),
            # multi-tenant overload control (ISSUE 12, docs/OVERLOAD.md):
            # admission queue occupancy, admitted/rejected/expired
            # counters, brownout ladder state + per-step shed counts,
            # the computed Retry-After, and per-tenant accounting
            "admission": self.admission.stats_dict(),
            # phase-attributed telemetry (ISSUE 8, docs/OBSERVABILITY.md):
            # per-plane × per-phase log2 latency histograms, byte/tile
            # counters, and plane-ladder decision counters with reasons
            "phases": self.telemetry.phases_dict(),
            # the request span trees, per span name (ISSUE 25): exact
            # {count, sum_ns, self_ns}, so two readings subtract
            "spans": self.telemetry.spans_dict(),
            # device-memory ledger (ISSUE 9, docs/OBSERVABILITY.md):
            # per-kind staged bytes (sum EXACTLY to staged_bytes_total),
            # staging/eviction lifecycle event rings, and the
            # restage-amplification metric ROADMAP item 3 drives down
            "memory": _memory_stats(self.name),
            # compile plane (ISSUE 14, docs/OBSERVABILITY.md): the
            # persistent-cache hit/miss counters, warmed-program count,
            # query-path first compiles, and the first-compile-stall
            # histogram — a PROCESS resource like the memory ledger
            # (_nodes/stats re-exports the same node-wide block)
            "compile": _compile_stats(),
            # data integrity (ISSUE 16, docs/OBSERVABILITY.md): detected
            # corruptions by site, corrupted_* marker lifecycle events,
            # and the background scrubber's verified-bytes/drift counters
            # — counters node-global, marker_events filtered per index
            "integrity": integrity_service().stats(self.name),
        }
        if groups:
            search["groups"] = groups
        return search

    def stats(self) -> dict:
        """Full CommonStats section set (action/admin/indices/stats) —
        every section present so metric filtering can subset; untracked
        counters report zero rather than omitting the section."""
        shard_stats = {sid: s.stats() for sid, s in self.shards.items()}
        index_total = sum(s["indexing"]["index_total"]
                          for s in shard_stats.values())
        delete_total = sum(s["indexing"]["delete_total"]
                           for s in shard_stats.values())
        mem_bytes = sum(s["segments"]["memory_in_bytes"]
                        for s in shard_stats.values())
        fielddata_bytes = sum(
            sum(seg.breaker_charges.values())
            for sh in self.shards.values()
            for seg in sh.engine.searchable_segments())
        search = self.search_stats(shard_stats)
        totals = {
            "docs": {"count": self.num_docs, "deleted": 0},
            "store": {"size_in_bytes": mem_bytes,
                      "throttle_time_in_millis": 0},
            "indexing": {
                "index_total": index_total,
                "index_time_in_millis": 0,
                "delete_total": delete_total,
                "index_failed": 0,
                "types": {self.doc_type or "_doc": {
                    "index_total": index_total,
                    "index_time_in_millis": 0,
                    "delete_total": delete_total,
                }},
            },
            "get": {"total": self._get_total, "time_in_millis": 0,
                    "exists_total": 0, "missing_total": 0, "current": 0},
            "search": search,
            "merges": {"current": 0, "current_docs": 0, "total": 0,
                       "total_time_in_millis": 0, "total_docs": 0},
            "refresh": {"total": self._refresh_total,
                        "total_time_in_millis": 0, "listeners": 0},
            "flush": {"total": self._flush_total,
                      "total_time_in_millis": 0},
            "warmer": {"current": 0, "total": 0, "total_time_in_millis": 0},
            "query_cache": {"memory_size_in_bytes": 0, "total_count": 0,
                            "hit_count": 0, "miss_count": 0,
                            "cache_count": 0, "evictions": 0},
            "fielddata": {"memory_size_in_bytes": fielddata_bytes,
                          "evictions": 0},
            "completion": {"size_in_bytes": 0},
            "segments": {
                "count": sum(s["segments"]["count"]
                             for s in shard_stats.values()),
                "memory_in_bytes": mem_bytes,
            },
            "translog": {
                "operations": sum(s["translog"]["operations"]
                                  for s in shard_stats.values()),
                "size_in_bytes": sum(
                    s["translog"].get("size_in_bytes", 0)
                    for s in shard_stats.values()),
            },
            "recovery": {"current_as_source": 0, "current_as_target": 0,
                         "throttle_time_in_millis": 0},
            "request_cache": self.request_cache.stats(),
        }
        return {"primaries": totals, "total": totals, "shards": shard_stats}

    def mapping_dict(self) -> dict:
        return self.mapper_service.mapping_dict()

    def put_mapping(self, mapping: dict) -> None:
        self.mapper_service.merge(mapping)

    def close(self) -> None:
        self._closing = True
        # wait out an in-flight background compaction pass: its restage
        # must not re-stage bytes after the releases below (the
        # leak-check contract) — new passes see _closing and bail
        with self._compact_lock:
            pass
        if self._refresh_stop is not None:
            self._refresh_stop.set()
        self._scrub_stop.set()
        # wake queued admission waiters with a clean rejection so no
        # caller hangs on a closing index
        self.admission.shutdown()
        # structured device-memory releases first (mesh plane, then every
        # shard's segments via engine.close), then the index-level ledger
        # backstop — close/delete must return the ledger to baseline
        # exactly (the leak-check contract, docs/OBSERVABILITY.md)
        if self._mesh_search is not None:
            self._mesh_search._drop_staging()
        for shard in self.shards.values():
            shard.close()
        from elasticsearch_tpu.common.memory import memory_accountant

        memory_accountant().release_index(self.name)


def _memory_stats(index: Optional[str]) -> dict:
    from elasticsearch_tpu.common.memory import memory_accountant

    return memory_accountant().stats(index)


def _compile_stats() -> dict:
    from elasticsearch_tpu.common.compile_cache import compile_stats

    return compile_stats().stats()


def _pure_knn_mesh_clause(body: dict) -> Optional[dict]:
    """The knn spec when this request is a plain top-k vector search the
    mesh kNN program can serve whole, else None. The eligibility rules
    (sole knn clause, simple body keys, default boost — a non-default
    boost stays on the host rung for byte-parity) are SHARED with the
    batched dispatcher so the serial and batched paths can never drift
    (search/batching.knn_batch_spec)."""
    from elasticsearch_tpu.search.batching import knn_batch_spec

    q = body.get("query")
    if not (isinstance(q, dict) and set(q) == {"knn"}):
        return None  # here only the already-normalized clause form runs
    return knn_batch_spec(body)


def _is_request_error(exc: Exception) -> bool:
    """True for 4xx engine exceptions — request-level validation errors
    (malformed query, unmapped field, bad argument) that every shard
    would raise identically; the reference rejects these on the
    coordinator before the fan-out, so they keep their own status."""
    from elasticsearch_tpu.common.errors import ElasticsearchTpuException

    return (isinstance(exc, ElasticsearchTpuException)
            and exc.status_code < 500)


def _deep_merge(base: dict, patch: dict) -> dict:
    for key, value in patch.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            base[key] = _deep_merge(dict(base[key]), value)
        else:
            base[key] = value
    return base


def _can_match(shard, body: dict) -> bool:
    """Shard-level rewrite of a PURE range query against the shard's
    doc-value bounds (the reference's canMatch phase rewrites the query
    against min/max points). Conservative: anything but a bare range
    query matches."""
    query = (body or {}).get("query")
    if not isinstance(query, dict) or set(query) != {"range"}:
        return True
    (field, cond), = query["range"].items()
    if not isinstance(cond, dict):
        return True
    lo = cond.get("gte", cond.get("gt"))
    hi = cond.get("lte", cond.get("lt"))
    if not all(isinstance(v, (int, float)) or v is None for v in (lo, hi)):
        return True  # dates/strings need parsing context; don't prefilter
    any_col = False
    for seg in shard.engine.searchable_segments():
        col = seg.numeric_columns.get(field)
        if col is None or col.count == 0:
            continue
        any_col = True
        seg_min = float(col.min_value[seg.live[: seg.nd_pad]].min()) \
            if seg.live[: seg.num_docs].any() else float("inf")
        seg_max = float(col.max_value[seg.live[: seg.nd_pad]].max()) \
            if seg.live[: seg.num_docs].any() else float("-inf")
        if (lo is None or seg_max >= lo) and (hi is None or seg_min <= hi):
            return True
    if not any_col:
        # no doc values for the field on this shard: only unrefreshed
        # buffer docs could match, and the query phase reads sealed
        # segments only — but match the conservative default
        return True
    return False
