"""Node: the composition root.

Role model: ``Node`` (core/.../node/Node.java:246) — wires settings,
cluster service, indices service, ingest, snapshots, tasks; plus the
index-lifecycle parts of ``IndicesService``/``MetaDataCreateIndexService``
(auto-create, templates, aliases) and the coordination-level APIs
(bulk, mget, msearch, scroll) that live under action/ in the reference.
"""

from __future__ import annotations

import os
import threading
import time
import uuid as _uuid
from typing import Dict, List, Optional

from elasticsearch_tpu.cluster.state import (
    ClusterService,
    ClusterState,
    DiscoveryNode,
    IndexMetadata,
    cluster_health,
)
from elasticsearch_tpu.common.errors import (
    ActionRequestValidationException,
    IllegalArgumentException,
    IndexAlreadyExistsException,
    IndexNotFoundException,
    InvalidIndexNameException,
    ResourceNotFoundException,
)
from elasticsearch_tpu.common import monitor
from elasticsearch_tpu.common.settings import (
    CLUSTER_NAME,
    NODE_NAME,
    INDEX_SEEDED_PREFIXES,
    LayeredSettings,
    PATH_DATA,
    SEARCH_PALLAS_POSTINGS_CODEC,
    Settings,
    cluster_settings,
    index_scoped_settings,
)
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.ingest.pipeline import IngestService
from elasticsearch_tpu.tasks.task_manager import TaskManager
from elasticsearch_tpu.version import __version__

_INVALID_INDEX_CHARS = set(' "*\\<>|,/?#')


class Node:
    def __init__(self, settings: Settings = Settings.EMPTY,
                 data_path: Optional[str] = None,
                 plugins: Optional[list] = None):
        self.settings = settings
        # process-level dynamic settings (REST search queue, HBM budget,
        # staging retry): the cluster settings' explicit values win,
        # clearing one reverts to the node file (put_cluster_settings)
        self.live = LayeredSettings(self._committed_cluster_settings,
                                    lambda: self.settings)
        # search.pallas.postings_codec (docs/PRUNING.md) is static and
        # node-scope: an index whose own key is "default" follows THIS
        # node's file, so it is laid over every index's Settings, at
        # creation and again at recovery from disk
        self._node_codec = Settings({
            SEARCH_PALLAS_POSTINGS_CODEC.key:
                SEARCH_PALLAS_POSTINGS_CODEC.get(settings)})
        self.node_id = _uuid.uuid4().hex[:20]
        self.node_name = NODE_NAME.get(settings)
        self.cluster_settings = cluster_settings()
        self.index_scoped_settings = index_scoped_settings()
        # cross-query micro-batching knobs are DYNAMIC (docs/BATCHING.md):
        # a cluster-settings update must reach every index's live batcher
        # (an operator disabling batching mid-incident can't wait for a
        # restart) — apply_settings fires these on PUT _cluster/settings
        from elasticsearch_tpu.common.settings import (
            SEARCH_BATCH_ENABLED,
            SEARCH_BATCH_MAX_QUERIES,
            SEARCH_BATCH_WINDOW_MS,
        )

        def _batchers(apply):
            def consume(value):
                for svc in self.indices.values():
                    apply(svc._batcher, value)
            return consume

        self.cluster_settings.add_settings_update_consumer(
            SEARCH_BATCH_ENABLED,
            _batchers(lambda b, v: setattr(b, "enabled", bool(v))))
        self.cluster_settings.add_settings_update_consumer(
            SEARCH_BATCH_WINDOW_MS,
            _batchers(lambda b, v: setattr(b, "window_s",
                                           float(v) / 1000.0)))
        self.cluster_settings.add_settings_update_consumer(
            SEARCH_BATCH_MAX_QUERIES,
            _batchers(lambda b, v: setattr(b, "max_queries", int(v))))
        # (every other dynamic search setting needs EXPLICITNESS — a
        # cluster value must clear when its key is removed so the
        # index's own Settings win again — which a value-only consumer
        # can't see: put_cluster_settings hands each index the committed
        # settings and readers go through IndexService.live)
        self.data_path = data_path or PATH_DATA.get(settings)
        self.persistent_path = data_path is not None or "path.data" in settings
        # zero-downtime rollout (ISSUE 14, docs/RESILIENCE.md "Rollout &
        # drain"): enable JAX's persistent compilation cache
        # (search.compile.cache_path) and install the program-variant
        # registry persisted beside the store, so restart never pays a
        # query-path first compile. The process-global registry follows
        # the last-constructed Node.
        from elasticsearch_tpu.common import compile_cache as _cc

        cache_path = settings.get_str("search.compile.cache_path", "")
        if cache_path:
            _cc.configure_compile_cache(cache_path)
        if self.persistent_path:
            _cc.set_variant_registry(_cc.VariantRegistry(
                os.path.join(self.data_path, "_state",
                             "compile_variants.json")))
        self._draining = False
        # secure settings from the encrypted keystore (KeyStoreWrapper):
        # kept OUT of the displayed settings (filtered) — consumers read
        # node.secure_settings explicitly, like the reference's
        # SecureSettings surface
        self.secure_settings: Dict[str, str] = {}
        if self.persistent_path and os.path.isdir(self.data_path or ""):
            from elasticsearch_tpu.common.keystore import KeyStore

            ks = KeyStore.load_if_exists(
                self.data_path, os.environ.get("ES_TPU_KEYSTORE_PASS", ""))
            if ks is not None:
                self.secure_settings = ks.as_settings_dict()
        node = DiscoveryNode(self.node_id, self.node_name, "127.0.0.1:9300")
        initial = ClusterState(
            CLUSTER_NAME.get(settings),
            nodes={self.node_id: node},
            master_node_id=self.node_id,
        )
        self.cluster_service = ClusterService(initial)
        # named bounded executors (ThreadPool.java) — the REST layer runs
        # handler work on the action's pool; full queues reject with 429
        from elasticsearch_tpu.common.thread_pool import ThreadPool

        # (the search executor's queue is sized from search.queue.size
        # by _sync_process_settings, here and on every cluster PUT)
        self.thread_pool = ThreadPool()
        from elasticsearch_tpu.common.breaker import configure_breaker_service

        # hierarchical memory circuit breakers (indices.breaker.*)
        self.breaker_service = configure_breaker_service(settings)
        self._sync_process_settings()
        self.indices: Dict[str, IndexService] = {}
        self.ingest = IngestService(self)
        self.tasks = TaskManager(self.node_id)
        from elasticsearch_tpu.snapshots.service import SnapshotsService

        self.snapshots = SnapshotsService(self)
        self.scrolls: Dict[str, dict] = {}
        self._scroll_lock = threading.Lock()
        # keep-alive reaper (SearchService's keepAliveReaper): expired
        # scroll contexts pin segment views + device arrays, so they must
        # be freed on TIME, not only when another scroll request arrives
        self._reaper_stop = threading.Event()
        self._reaper = threading.Thread(
            target=self._reap_expired_scrolls_loop,
            name=f"scroll-reaper[{self.node_name}]", daemon=True)
        self._reaper.start()
        self.start_time = time.time()
        self._closed = False
        from elasticsearch_tpu.transport.remote_cluster import (
            RemoteClusterService,
            register_node,
        )

        register_node(self)
        self.remote_clusters = RemoteClusterService(self, settings)
        from elasticsearch_tpu.plugins import PluginsService

        self.plugins_service = PluginsService(self, settings, plugins)
        self.plugins_service.on_node_start()
        if self.persistent_path:
            # GatewayMetaState analog: global metadata first (templates,
            # persistent settings, stored scripts, pipelines,
            # repositories — gateway/GatewayMetaState.java:61,117), THEN
            # per-index recovery, matching the reference's recovery order;
            # the applier keeps the on-disk copy current from here on
            self.cluster_service.add_applier(self._persist_global_meta)
            self._recover_global_meta()
            self._recover_indices_from_disk()
            # AOT variant warming (ISSUE 14): replay the recorded
            # program-variant lattice in the background, off the query
            # path — a warmed restart serves zero query-path first
            # compiles (the rolling-restart soak's headline invariant)
            if settings.get_bool("search.compile.warm_on_start", True):
                self._start_compile_warming()

    # ------------------------------------------------------------------
    # Index lifecycle (MetaDataCreateIndexService / MetaDataDeleteIndexService)
    # ------------------------------------------------------------------

    def _validate_index_name(self, name: str) -> None:
        if not name or name != name.lower():
            raise InvalidIndexNameException(name, "must be lowercase")
        if name.startswith(("_", "-", "+")):
            raise InvalidIndexNameException(name, "must not start with '_', '-', or '+'")
        if any(c in _INVALID_INDEX_CHARS for c in name):
            raise InvalidIndexNameException(name, "must not contain special characters")

    def _index_data_path(self, name: str) -> Optional[str]:
        if not self.persistent_path:
            return None
        return os.path.join(self.data_path, "indices", name)

    def create_index(self, name: str, body: Optional[dict] = None) -> dict:
        body = body or {}
        self._validate_index_name(name)
        if name in self.indices or any(
            name in md.aliases for md in self.cluster_service.state.indices.values()
        ):
            raise IndexAlreadyExistsException(name)
        settings = Settings.from_dict(
            body.get("settings") or {}).with_index_prefix()
        mappings = body.get("mappings") or {}
        mappings, doc_type = _unwrap_typed_mapping(mappings)
        aliases = {a: (spec or {}) for a, spec in (body.get("aliases") or {}).items()}

        # apply matching templates, lowest order first (MetaDataCreateIndexService)
        templates = sorted(
            (t for t in self.cluster_service.state.templates.values()
             if _template_matches(t, name)),
            key=lambda t: t.get("order", 0),
        )
        merged_settings = Settings.EMPTY
        merged_mappings: dict = {}
        for t in templates:
            merged_settings = merged_settings.merged_with(
                Settings.from_dict(t.get("settings") or {}).with_index_prefix()
            )
            t_map = t.get("mappings") or {}
            if "_doc" in t_map:
                t_map = t_map["_doc"]
            _merge_mapping_dicts(merged_mappings, t_map)
            for a, spec in (t.get("aliases") or {}).items():
                aliases.setdefault(a, spec or {})
        merged_settings = merged_settings.merged_with(settings)
        _merge_mapping_dicts(merged_mappings, mappings)
        # node-level micro-batching + pallas-plane config (search.batch.*
        # / search.pallas.* — node scope, docs/BATCHING.md +
        # docs/PRUNING.md) seeds each index at lowest precedence, with
        # the CURRENT dynamic cluster settings on top: an index created
        # after PUT _cluster/settings {search.batch.*, search.pallas.*}
        # must honor the live value, not the node file's (the update
        # consumers only reach batchers alive at update time; the pruning
        # knobs are re-read per query from the index's Settings map)
        cluster_dynamic = self._committed_cluster_settings()
        # (search.staging.retry.* deliberately NOT seeded per index: the
        # retry config is process-level — a create-time snapshot in the
        # index Settings would shadow later dynamic cluster updates)
        for prefix in INDEX_SEEDED_PREFIXES:
            merged_settings = self.settings.filtered_by_prefix(
                prefix).merged_with(
                cluster_dynamic.filtered_by_prefix(prefix)).merged_with(
                merged_settings)
        merged_settings = merged_settings.merged_with(self._node_codec)

        self.index_scoped_settings.validate(merged_settings, allow_unknown=True)
        svc = IndexService(name, merged_settings, merged_mappings,
                           self._index_data_path(name))
        svc.doc_type = doc_type  # 6.x custom type name echoed in responses
        # an index created AFTER a cluster-level commit follows the live
        # explicit values like its older peers (the put_cluster_settings
        # sync only reaches indices alive then)
        svc.set_cluster_overrides(cluster_dynamic)
        if self._draining:
            # an index created while the node drains (auto-create from a
            # straggling write) joins the drain: its searches get the
            # same clean 503 instead of silently serving on a node the
            # orchestrator believes is quiescing
            svc.admission.begin_drain()
        self.indices[name] = svc

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            new.indices[name] = IndexMetadata(
                name, merged_settings, svc.mapping_dict(), aliases,
                creation_date=svc.creation_date,
            )
            return new

        self.cluster_service.submit_state_update_task(f"create-index [{name}]", update)
        return {"acknowledged": True, "shards_acknowledged": True, "index": name}

    def delete_index(self, expression: str,
                     ignore_unavailable: bool = False,
                     allow_no_indices: bool = True) -> dict:
        state = self.cluster_service.state
        alias_parts = set()
        for part in str(expression).split(","):
            for md in state.indices.values():
                if part and part in md.aliases:
                    if ignore_unavailable:
                        alias_parts.add(part)  # silently skipped (6.x)
                        break
                    raise IllegalArgumentException(
                        f"The provided expression [{part}] matches an "
                        f"alias, specify the corresponding concrete "
                        f"indices instead.")
        # wildcard patterns in a DELETE only expand over concrete index
        # names — a pattern matching only aliases is a no-op
        # (TransportDeleteIndexAction + IndicesOptions for destructive ops)
        import fnmatch as _fn

        names = []
        for p in str(expression).split(","):
            if not p or p in alias_parts:
                continue
            if "*" in p or p == "_all":
                pat = "*" if p == "_all" else p
                matched = [n for n in state.indices
                           if _fn.fnmatchcase(n, pat)]
                if not matched and not allow_no_indices:
                    # a dead wildcard fails the WHOLE request before any
                    # deletion (IndicesOptions.fromOptions strictness)
                    raise IndexNotFoundException(p)
                names.extend(matched)
            else:
                try:
                    names.extend(state.resolve_index_names(p))
                except IndexNotFoundException:
                    if not ignore_unavailable:
                        raise
        names = list(dict.fromkeys(names))
        if not names:
            if not allow_no_indices:
                raise IndexNotFoundException(str(expression))
            return {"acknowledged": True}
        for name in names:
            svc = self.indices.pop(name, None)
            if svc is not None:
                svc.close()
            if self.persistent_path:
                import shutil

                path = self._index_data_path(name)
                if path and os.path.exists(path):
                    shutil.rmtree(path, ignore_errors=True)

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            for name in names:
                new.indices.pop(name, None)
            return new

        self.cluster_service.submit_state_update_task(f"delete-index {names}", update)
        return {"acknowledged": True}

    def close_index(self, expression: str) -> dict:
        names = self.cluster_service.state.resolve_index_names(expression)

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            for n in names:
                new.indices[n].state = "close"
            return new

        self.cluster_service.submit_state_update_task(f"close-index {names}", update)
        return {"acknowledged": True}

    def open_index(self, expression: str) -> dict:
        names = self.cluster_service.state.resolve_index_names(expression)

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            for n in names:
                new.indices[n].state = "open"
            return new

        self.cluster_service.submit_state_update_task(f"open-index {names}", update)
        return {"acknowledged": True}

    @staticmethod
    def _global_meta_slice(state: ClusterState) -> dict:
        """The durable global MetaData: everything a full-cluster restart
        must bring back that is not per-index (the reference persists it
        via MetaDataStateFormat atomic _state files —
        gateway/GatewayMetaState.java:61). Transient settings are
        deliberately NOT here: the reference drops them on full restart."""
        return {
            "templates": state.templates,
            "persistent_settings": state.persistent_settings.as_nested_dict(),
            "stored_scripts": state.stored_scripts,
            "ingest_pipelines": state.ingest_pipelines,
            "repositories": state.repositories,
        }

    def _persist_global_meta(self, old: ClusterState,
                             new: ClusterState) -> None:
        """Cluster-state applier: atomically rewrite the global _state
        file whenever a durable slice changed (MetaDataStateFormat's
        write-tmp-then-rename discipline)."""
        if not self.persistent_path:
            return
        import json

        payload = self._global_meta_slice(new)
        if old is not None and self._global_meta_slice(old) == payload:
            return
        state_dir = os.path.join(self.data_path, "_state")
        os.makedirs(state_dir, exist_ok=True)
        tmp = os.path.join(state_dir, "global-meta.json.tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(state_dir, "global-meta.json"))

    def _recover_global_meta(self) -> None:
        """Boot-time restore of the global MetaData slice, re-driven
        through each component's normal write path so side effects
        (settings consumers, repository object construction, remote
        cluster registration) re-fire exactly as they did originally."""
        path = os.path.join(self.data_path, "_state", "global-meta.json")
        if not os.path.exists(path):
            return
        import json

        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if data.get("persistent_settings"):
            self.put_cluster_settings(
                {"persistent": data["persistent_settings"]})

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            new.templates.update(data.get("templates") or {})
            new.stored_scripts.update(data.get("stored_scripts") or {})
            new.ingest_pipelines.update(data.get("ingest_pipelines") or {})
            return new

        self.cluster_service.submit_state_update_task(
            "recover global metadata", update)
        for name, body in (data.get("repositories") or {}).items():
            try:
                self.snapshots.put_repository(name, body)
            except Exception:  # noqa: BLE001 — e.g. missing plugin type
                # an unregisterable repository must not block node boot
                # (the reference logs and continues; snapshots into it
                # fail with repository-missing at use time)
                pass

    def _recover_indices_from_disk(self) -> None:
        """GatewayService analog: restore index metadata + shard data from
        the data path on startup (gateway/GatewayMetaState.java)."""
        root = os.path.join(self.data_path, "indices")
        if not os.path.isdir(root):
            return
        import json

        for name in sorted(os.listdir(root)):
            meta_path = os.path.join(root, name, "_meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path, encoding="utf-8") as f:
                meta = json.load(f)
            # (what was seeded under another node file does not outlive
            # the restart: see _node_codec)
            settings = Settings(meta.get("settings", {})).merged_with(
                self._node_codec)
            svc = IndexService(name, settings, meta.get("mappings"),
                               self._index_data_path(name))
            self.indices[name] = svc

            def update(state: ClusterState, name=name, settings=settings,
                       svc=svc, meta=meta) -> ClusterState:
                new = state.copy()
                new.indices[name] = IndexMetadata(
                    name, settings, svc.mapping_dict(), meta.get("aliases", {}),
                )
                return new

            self.cluster_service.submit_state_update_task(f"recover [{name}]", update)

    def _persist_index_meta(self, name: str) -> None:
        if not self.persistent_path:
            return
        import json

        md = self.cluster_service.state.indices.get(name)
        svc = self.indices.get(name)
        if md is None or svc is None:
            return
        path = self._index_data_path(name)
        os.makedirs(path, exist_ok=True)
        tmp = os.path.join(path, "_meta.json.tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({
                "settings": md.settings.as_dict(),
                "mappings": svc.mapping_dict(),
                "aliases": md.aliases,
            }, f)
        os.replace(tmp, os.path.join(path, "_meta.json"))

    # ------------------------------------------------------------------
    # Resolution helpers
    # ------------------------------------------------------------------

    def index_service(self, name: str, auto_create: bool = False) -> IndexService:
        state = self.cluster_service.state
        if name in self.indices:
            if state.indices.get(name) and state.indices[name].state == "close":
                raise IllegalArgumentException(f"index [{name}] is closed")
            return self.indices[name]
        for idx_name, md in state.indices.items():
            if name in md.aliases:
                return self.indices[idx_name]
        if auto_create:
            from elasticsearch_tpu.common.settings import ACTION_AUTO_CREATE_INDEX

            if ACTION_AUTO_CREATE_INDEX.get(self.settings):
                self.create_index(name)
                return self.indices[name]
        raise IndexNotFoundException(name)

    def resolve_search_indices(self, expression: str) -> List[IndexService]:
        state = self.cluster_service.state
        out: List[IndexService] = []
        seen = set()
        parts = [p for p in str(expression or "_all").split(",") if p]             or ["_all"]
        for part in parts:
            wildcard = "*" in part or part in ("_all", "")
            for n in state.resolve_index_names(part):
                if n in seen:
                    continue
                if state.indices[n].state != "open":
                    # wildcard EXPANSION skips closed indices, but a
                    # closed index named explicitly is a request error
                    # (IndexClosedException)
                    if wildcard:
                        continue
                    raise IllegalArgumentException(
                        f"closed index [{n}] - IndexClosedException")
                seen.add(n)
                out.append(self.indices[n])
        return out

    # ------------------------------------------------------------------
    # Document APIs
    # ------------------------------------------------------------------

    def index_doc(self, index: str, doc_id: Optional[str], source: dict,
                  routing: Optional[str] = None, refresh=None,
                  pipeline: Optional[str] = None,
                  wait_for_active_shards=None,
                  parent: Optional[str] = None, **kw) -> dict:
        if doc_id is not None:
            if doc_id == "":
                raise IllegalArgumentException(
                    "if _id is specified it must not be empty")
            if len(doc_id.encode("utf-8")) > 512:
                raise ActionRequestValidationException(
                    f"Validation Failed: 1: id is too long, must be no "
                    f"longer than 512 bytes but was: "
                    f"{len(doc_id.encode('utf-8'))};")
        svc = self.index_service(index, auto_create=True)
        if wait_for_active_shards is not None:
            self._check_active_shards(svc, wait_for_active_shards)
        if pipeline:
            # (a plain dict: what a pipeline makes of a source is no
            # longer the text it was sent as)
            source = self.ingest.run_pipeline(pipeline, dict(source),
                                              doc_id, index)
            if source is None:  # dropped by pipeline
                return {"_index": index, "_id": doc_id, "result": "noop"}
        if doc_id is None:
            doc_id = _uuid.uuid4().hex[:20]
            kw.setdefault("op_type", "create")
        r = svc.index_doc(doc_id, source, routing, parent=parent, **kw)
        self._maybe_refresh(svc, refresh, doc_id=doc_id, routing=routing)
        self._maybe_update_mapping_meta(index)
        return r

    def _check_active_shards(self, svc: IndexService, wanted) -> None:
        """wait_for_active_shards gate (ActiveShardsObserver +
        TransportWriteAction): on this single-node topology the active
        count per shard is 1 (the started primary; replicas are
        unassigned), so a larger requirement fails like the reference's
        UnavailableShardsException timeout."""
        from elasticsearch_tpu.index.seqno import check_active_shards

        check_active_shards(wanted, 1, 1 + svc.num_replicas, f"[{svc.name}]")

    def _maybe_refresh(self, svc: IndexService, refresh,
                       doc_id=None, routing=None) -> None:
        """Write-op refresh policy (TransportWriteAction). A write's
        ``refresh=true`` refreshes ONLY the written shard — another
        shard's still-buffered deletes must not become visible as a side
        effect (the reference refreshes the shard the op ran on)."""
        if refresh in (True, "true", ""):
            if doc_id is not None:
                svc.shards[svc._route(doc_id, routing)].refresh()
            else:
                svc.refresh()
        elif refresh == "wait_for":
            # refresh=wait_for (RefreshListeners): block until the periodic
            # refresh makes the write visible; force one when the scheduler
            # is disabled (the listener-cap forced refresh analog)
            if not svc.refresh_interval or svc.refresh_interval <= 0:
                if doc_id is not None:
                    svc.shards[svc._route(doc_id, routing)].refresh()
                else:
                    svc.refresh()
                return
            import threading

            events = []
            for shard in svc.shards.values():
                ev = threading.Event()
                shard.engine.add_refresh_listener(ev.set)
                events.append(ev)
            deadline = svc.refresh_interval * 2 + 0.5
            for ev in events:
                if not ev.wait(deadline):
                    svc.refresh()
                    break

    def _maybe_update_mapping_meta(self, index: str) -> None:
        # dynamic mapping updates flow back into cluster state (the master
        # round-trip in §3.3 of SURVEY.md)
        svc = self.indices.get(index)
        if svc is None:
            return
        state = self.cluster_service.state
        md = state.indices.get(index)
        if md is not None and md.mappings != svc.mapping_dict():
            def update(st: ClusterState) -> ClusterState:
                new = st.copy()
                new.indices[index].mappings = svc.mapping_dict()
                new.indices[index].version += 1
                return new

            self.cluster_service.submit_state_update_task(
                f"update-mapping [{index}]", update
            )
            self._persist_index_meta(index)

    def get_doc(self, index: str, doc_id: str, routing=None,
                realtime=True, refresh=None) -> dict:
        svc = self.index_service(index)
        if refresh in (True, "true", ""):
            # GET ?refresh=true forces a refresh before reading
            svc.refresh()
        g = svc.get_doc(doc_id, routing, realtime=realtime)
        out = {
            "_index": svc.name,
            "_type": "_doc",
            "_id": doc_id,
            "found": g.found,
        }
        if g.found:
            out["_version"] = g.version
            out["_seq_no"] = g.seqno
            out["_source"] = g.source
            # the STORED routing (a parent-only write stores the parent
            # as routing); fall back to echoing the request param
            stored_routing = getattr(g, "routing", None)
            if stored_routing is not None:
                out["_routing"] = stored_routing
            elif routing is not None:
                out["_routing"] = routing
        return out

    def delete_doc(self, index: str, doc_id: str, routing=None, refresh=None, **kw) -> dict:
        svc = self.index_service(index)
        r = svc.delete_doc(doc_id, routing, **kw)
        self._maybe_refresh(svc, refresh, doc_id=doc_id, routing=routing)
        return r

    def update_doc(self, index: str, doc_id: str, body: dict, routing=None,
                   refresh=None, version=None) -> dict:
        # upserts auto-create the index like every other write
        # (TransportUpdateAction resolves through auto-create)
        auto = "upsert" in (body or {}) or (body or {}).get("doc_as_upsert")
        svc = self.index_service(index, auto_create=bool(auto))
        r = svc.update_doc(doc_id, body, routing, version=version)
        self._maybe_refresh(svc, refresh, doc_id=doc_id, routing=routing)
        self._maybe_update_mapping_meta(index)
        return r

    def mget(self, body: dict, default_index: Optional[str] = None,
             default_type: Optional[str] = None, realtime: bool = True,
             refresh=None, stored_fields=None) -> dict:
        specs = body.get("docs")
        if specs is None and "ids" in body:
            # short form: {"ids": [...]} against the URL's index
            specs = [{"_id": i} for i in body["ids"]]
        # whole-request validation (MultiGetRequest.validate): any bad
        # item fails the REQUEST, not just the item
        problems = []
        if not specs:
            problems.append("no documents to get")
        for i, spec in enumerate(specs or []):
            if "_id" not in spec:
                problems.append("id is missing")
            if spec.get("_index", default_index) is None:
                problems.append("index is missing")
        if problems:
            raise ActionRequestValidationException(
                "Validation Failed: " + " ".join(
                    f"{i + 1}: {p};" for i, p in enumerate(problems)))
        docs = []
        for spec in specs:
            index = spec.get("_index", default_index)
            routing = spec.get("routing", spec.get("_routing"))
            if routing is None:
                # legacy _parent: the parent id routes the doc
                routing = spec.get("parent", spec.get("_parent"))
            if routing is not None:
                routing = str(routing)
            try:
                d = self.get_doc(index, str(spec["_id"]), routing,
                                 realtime=realtime, refresh=refresh)
                try:
                    svc = self.index_service(index)
                except Exception:  # noqa: BLE001 — handled as missing
                    svc = None
                stored = (spec.get("stored_fields") or spec.get("fields")
                          or stored_fields)
                if isinstance(stored, str):
                    # MultiGetRequest accepts a single field name / CSV
                    stored = [f for f in stored.split(",") if f]
                if d.get("found") and stored and svc is not None:
                    if "_parent" in stored:
                        p = svc.parents.get(str(spec["_id"]))
                        if p is not None:
                            d["_parent"] = p
                    src = d.get("_source") or {}
                    fields = {}
                    for f in stored:
                        if f in ("_source", "_parent", "_routing"):
                            continue
                        ft = svc.mapper_service.field_type(f)
                        if (ft is None or not ft.params.get("store", False)
                                or f not in src):
                            continue
                        v = src[f]
                        fields[f] = v if isinstance(v, list) else [v]
                    if fields:
                        d["fields"] = fields
                    if "_source" not in stored:
                        d.pop("_source", None)
                if d.get("found") and "_source" in spec:
                    # per-doc source filtering (FetchSourceContext)
                    from elasticsearch_tpu.search.service import (
                        _parse_source_spec,
                        filter_source,
                    )

                    inc, exc, enabled = _parse_source_spec(spec["_source"])
                    if not enabled:
                        d.pop("_source", None)
                    elif "_source" in d:
                        d["_source"] = filter_source(d["_source"], inc, exc)
                want_type = spec.get("_type", default_type)
                d["_type"] = want_type or "_doc"
                if want_type not in (None, "_all", "_doc"):
                    # a typed request only matches the index's actual type
                    # (alias-aware resolution, like get_doc itself)
                    actual = getattr(svc, "doc_type", "_doc") or "_doc"
                    if want_type != actual:
                        d = {"_index": index, "_type": want_type,
                             "_id": str(spec["_id"]), "found": False}
                docs.append(d)
            except IndexNotFoundException:
                docs.append({
                    "_index": index, "_id": str(spec["_id"]),
                    "_type": spec.get("_type", default_type) or "_doc",
                    "error": {"type": "index_not_found_exception",
                              "reason": f"no such index [{index}]"},
                })
        return {"docs": docs}

    # ------------------------------------------------------------------
    # Bulk (action/bulk/TransportBulkAction: group by shard, per-item results)
    # ------------------------------------------------------------------

    def bulk(self, operations: List[tuple], refresh=None,
             pipeline: Optional[str] = None) -> dict:
        """operations: list of (action, meta, source_or_None)."""
        t0 = time.monotonic()
        items = []
        errors = False
        touched = set()
        for action, meta, source in operations:
            index = meta.get("_index")
            doc_id = meta.get("_id")
            routing = meta.get("routing") or meta.get("_routing")
            parent = meta.get("parent") or meta.get("_parent")
            if routing is None and parent is not None:
                # legacy _parent: the parent id routes the doc
                routing = str(parent)
            item_pipeline = meta.get("pipeline", pipeline)
            try:
                if action == "index":
                    r = self.index_doc(index, doc_id, source, routing,
                                       pipeline=item_pipeline,
                                       parent=(str(parent)
                                               if parent is not None else None))
                    status = 201 if r.get("result") == "created" else 200
                elif action == "create":
                    r = self.index_doc(index, doc_id, source, routing,
                                       op_type="create", pipeline=item_pipeline,
                                       parent=(str(parent)
                                               if parent is not None else None))
                    status = 201
                elif action == "update":
                    r = self.update_doc(index, doc_id, source, routing)
                    status = 200
                elif action == "delete":
                    r = self.delete_doc(index, doc_id, routing)
                    status = 200 if r.get("found") else 404
                else:
                    raise ActionRequestValidationException(
                        f"Malformed action/metadata line, expected one of "
                        f"[create, delete, index, update] but found [{action}]"
                    )
                if (parent is not None and r.get("_id")
                        and action in ("index", "create", "update")):
                    svc_p = self.indices.get(index)
                    if svc_p is not None:
                        svc_p.parents[str(r["_id"])] = str(parent)
                touched.add(r.get("_index", index))
                item = {action: {**{k: v for k, v in r.items() if k != "found"},
                                 "status": status}}
            except Exception as e:  # per-item failure (reference behavior)
                errors = True
                from elasticsearch_tpu.common.errors import ElasticsearchTpuException

                if isinstance(e, ElasticsearchTpuException):
                    err = e.to_dict()["error"]
                    status = e.status_code
                else:
                    err = {"type": type(e).__name__, "reason": str(e)}
                    status = 500
                item = {action: {
                    "_index": index, "_id": doc_id, "status": status, "error": err,
                }}
            items.append(item)
        if refresh in (True, "true", "", "wait_for"):
            for name in touched:
                if name in self.indices:
                    self.indices[name].refresh()
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "errors": errors,
            "items": items,
        }

    # ------------------------------------------------------------------
    # Search (+ msearch, scroll)
    # ------------------------------------------------------------------

    def search(self, expression: str, body: Optional[dict] = None,
               scroll: Optional[str] = None) -> dict:
        pairs, clusters = self._resolve_search_groups(expression or "_all")
        body = body or {}
        body = self._rewrite_indexed_shapes(body)
        if scroll and body.get("collapse"):
            raise IllegalArgumentException(
                "cannot use `collapse` in a scroll context")
        if scroll and int(body.get("from", 0) or 0):
            # SearchRequest.validate(): paging within a scroll is the
            # scroll itself; an offset would silently desync the pages
            raise IllegalArgumentException(
                "using [from] is not allowed in a scroll context")
        # point-in-time pin (ScrollContext analog): freeze every local
        # shard's segment set + live masks BEFORE the first page, so all
        # pages (including this one) read the same snapshot. CCS scrolls
        # keep cursor semantics — remote segments can't be pinned.
        pinned = None
        if scroll and clusters is None:
            pinned = self._pin_scroll_segments(pairs)
        # deadline + degradation policy: the request's `timeout` (or
        # search.default_search_timeout) bounds the query phase; the
        # registered task's cancellation trips the same checkpoints
        # (_tasks/_cancel). allow_partial_search_results defaults from
        # search.default_allow_partial_results.
        from elasticsearch_tpu.common.settings import (
            SEARCH_ALLOW_PARTIAL_RESULTS,
        )
        from elasticsearch_tpu.search.cancellation import (
            SearchDeadline,
            parse_search_timeout,
        )

        if "allow_partial_search_results" not in body:
            if not SEARCH_ALLOW_PARTIAL_RESULTS.get(self.settings):
                body = dict(body)
                body["allow_partial_search_results"] = False
        task = self.tasks.register("indices:data/read/search", f"search [{expression}]")
        deadline = SearchDeadline(parse_search_timeout(body, self.settings),
                                  task)
        try:
            if len(pairs) == 1 and pairs[0][0] == "" and clusters is None:
                resp = pairs[0][1].search(
                    body, pinned_segments=(pinned or {}).get(
                        pairs[0][1].name) if pinned else None,
                    deadline=deadline)
            else:
                resp = self._multi_index_search(pairs, body, pinned=pinned,
                                                deadline=deadline)
                if clusters is not None:
                    resp["_clusters"] = clusters
        finally:
            self.tasks.unregister(task)
        if scroll:
            if pinned is not None:
                resp["_scroll_id"] = self._open_pit_scroll(
                    pairs, body, resp, scroll, pinned)
            else:
                resp["_scroll_id"] = self._open_scroll(expression, body,
                                                       resp, scroll)
        return resp

    @staticmethod
    def _pin_scroll_segments(pairs) -> Dict[str, Dict[int, list]]:
        from elasticsearch_tpu.index.segment import PinnedSegmentView

        pinned: Dict[str, Dict[int, list]] = {}
        for _prefix, svc in pairs:
            per_shard: Dict[int, list] = {}
            for sid in sorted(svc.shards):
                per_shard[sid] = [
                    PinnedSegmentView(s)
                    for s in svc.shards[sid].engine.searchable_segments()
                ]
            pinned[svc.name] = per_shard
        return pinned

    def _resolve_search_groups(self, expression: str):
        """Split ``alias:index`` cross-cluster groups (TransportSearchAction
        resolving remote indices via RemoteClusterService, reference
        action/search/TransportSearchAction.java:177). Returns
        ([(display_prefix, IndexService)], _clusters dict or None)."""
        from elasticsearch_tpu.common.errors import NodeNotConnectedException

        groups = self.remote_clusters.group_indices(expression)
        pairs = []
        n_remote = sum(1 for alias, _ in groups if alias is not None)
        if n_remote == 0:
            return [("", svc) for svc in
                    self.resolve_search_indices(expression)], None
        skipped = 0
        has_local = False
        for alias, expr in groups:
            if alias is None:
                has_local = True
                pairs.extend(("", svc)
                             for svc in self.resolve_search_indices(expr))
                continue
            rnode, skip_unavailable = self.remote_clusters.get_remote(alias)
            if rnode is None:
                if skip_unavailable:
                    skipped += 1
                    continue
                raise NodeNotConnectedException(
                    f"unable to connect to remote cluster [{alias}]")
            try:
                pairs.extend((f"{alias}:", svc)
                             for svc in rnode.resolve_search_indices(expr))
            except IndexNotFoundException:
                if skip_unavailable:
                    skipped += 1
                    continue
                raise
        total = n_remote + (1 if has_local else 0)
        return pairs, {"total": total, "successful": total - skipped,
                       "skipped": skipped}

    def _rewrite_indexed_shapes(self, body: dict) -> dict:
        """Coordinator rewrite (GeoShapeQueryBuilder's Rewriteable): fetch
        each geo_shape query's ``indexed_shape`` reference document and
        inline its shape before shard execution."""
        import json as _json

        if "indexed_shape" not in _json.dumps(body.get("query") or {}):
            return body
        import copy as _copy

        from elasticsearch_tpu.common.errors import ResourceNotFoundException

        body = _copy.deepcopy(body)

        def walk(obj):
            if isinstance(obj, dict):
                gs = obj.get("geo_shape")
                if isinstance(gs, dict):
                    for fname, spec in gs.items():
                        if isinstance(spec, dict) and "indexed_shape" in spec:
                            ref = spec.pop("indexed_shape")
                            if not isinstance(ref, dict) or "index" not in ref \
                                    or "id" not in ref:
                                raise IllegalArgumentException(
                                    "[indexed_shape] requires index and id")
                            g = self.get_doc(ref["index"], ref["id"])
                            if not g.get("found"):
                                raise ResourceNotFoundException(
                                    f"indexed document [{ref['index']}/"
                                    f"{ref['id']}] not found")
                            val = g["_source"]
                            path = str(ref.get("path", "shape"))
                            for part in path.split("."):
                                if not isinstance(val, dict) or part not in val:
                                    raise IllegalArgumentException(
                                        f"field [{path}] not found in indexed "
                                        f"document [{ref['index']}/{ref['id']}]")
                                val = val[part]
                            spec["shape"] = val
                for v in obj.values():
                    walk(v)
            elif isinstance(obj, list):
                for v in obj:
                    walk(v)

        walk(body.get("query"))
        return body

    def _multi_index_search(self, pairs: List[tuple], body: dict,
                            pinned=None, deadline=None) -> dict:
        """Cross-index search: fan out, merge like cross-shard merge.
        ``pairs`` are (display_prefix, IndexService) — the prefix carries
        the remote-cluster alias into hit ``_index`` values (CCS).
        ``pinned``: {index_name: {shard_id: [segment views]}} from an
        open scroll context."""
        from elasticsearch_tpu.common.errors import (
            SearchPhaseExecutionException,
            TaskCancelledException,
        )
        from elasticsearch_tpu.search.aggregations import parse_aggs, run_aggregations
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )
        from elasticsearch_tpu.search.service import (
            allow_partial_results,
            fetch_hits,
            merge_refs,
            normalize_sort,
            shard_failure_entry,
        )

        t0 = time.monotonic()
        from_ = int(body.get("from", 0) or 0)
        size = int(body.get("size")) if body.get("size") is not None else 10
        k = from_ + size
        sort_spec = normalize_sort(body.get("sort"))
        from elasticsearch_tpu.search.service import validate_collapse

        collapse_body = body.get("collapse") or {}
        collapse_field = validate_collapse(body)
        all_refs = []
        total = 0
        max_score = None
        views = []
        n_shards = 0
        n_ok = 0
        failures = []
        timed_out = False
        for prefix, svc in pairs:
            display = f"{prefix}{svc.name}"
            svc_pins = (pinned or {}).get(svc.name)
            for sid in sorted(svc.shards):
                n_shards += 1
                if timed_out or (deadline is not None and deadline.expired):
                    # accumulated shard results stand; remaining shards
                    # are skipped under the expired deadline
                    timed_out = True
                    if deadline is not None:
                        deadline.timed_out = True
                    continue
                try:
                    res = svc.shards[sid].searcher.query(
                        body, size_hint=max(k, 1),
                        segments=(svc_pins.get(sid, [])
                                  if svc_pins is not None else None),
                        deadline=deadline)
                except TaskCancelledException:
                    raise
                except TimeExceededException:
                    timed_out = True
                    continue
                except Exception as e:  # noqa: BLE001 — per-shard isolation
                    from elasticsearch_tpu.index.index_service import (
                        _is_request_error,
                    )

                    if _is_request_error(e):
                        raise  # 4xx validation: keeps its own status
                    failures.append(shard_failure_entry(display, sid, e))
                    continue
                n_ok += 1
                timed_out = timed_out or res.timed_out
                total += res.total_hits
                if res.max_score is not None:
                    max_score = (res.max_score if max_score is None
                                 else max(max_score, res.max_score))
                for ref in res.refs:
                    ref.shard_id = (display, ref.shard_id)
                    all_refs.append(ref)
                views.extend(res.agg_views)
        if failures and n_ok == 0 and not timed_out:
            raise SearchPhaseExecutionException(
                "query", "all shards failed", failures)
        if not allow_partial_results(body) and (failures or timed_out):
            raise SearchPhaseExecutionException(
                "query",
                "Partial shards failure"
                + (" (request timed out)" if timed_out else ""),
                failures)
        shard_map = {}
        for prefix, svc in pairs:
            for sid, shard in svc.shards.items():
                shard_map[(f"{prefix}{svc.name}", sid)] = shard
        if collapse_field:
            from elasticsearch_tpu.search.service import collapse_refs

            refs = merge_refs(all_refs, sort_spec, len(all_refs))
            refs = collapse_refs(refs, collapse_field, shard_map)
            refs = refs[from_: from_ + size]
        else:
            refs = merge_refs(all_refs, sort_spec, max(k, 0))[from_: from_ + size]
        hits = []
        by_index: Dict[str, List] = {}
        for ref in refs:
            by_index.setdefault(ref.shard_id[0], []).append(ref)
        ordered_hits = {}
        for idx_name, idx_refs in by_index.items():
            sub_shards = {r.shard_id: shard_map[r.shard_id] for r in idx_refs}
            # refs carry (display, sid) composite ids here; re-key the
            # pinned views the same way for the fetch-phase lookup
            sub_pins = None
            if pinned is not None and idx_name in pinned:
                sub_pins = {(idx_name, sid): views
                            for sid, views in pinned[idx_name].items()}
            for ref, hit in zip(idx_refs,
                                fetch_hits(idx_refs, sub_shards, body,
                                           idx_name,
                                           pinned_segments=sub_pins)):
                ordered_hits[id(ref)] = hit
        hits = [ordered_hits[id(r)] for r in refs if id(r) in ordered_hits]
        if collapse_field:
            from elasticsearch_tpu.search.service import expand_collapsed_hits

            # ExpandSearchPhase across all clusters/indices of the request
            expand_collapsed_hits(
                hits, refs, collapse_body, body,
                lambda sub: self._multi_index_search(pairs, sub,
                                                     deadline=deadline))
        resp = {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": timed_out,
            "_shards": {"total": n_shards,
                        "successful": n_shards - len(failures),
                        "skipped": 0,
                        "failed": len(failures)},
            "hits": {"total": total, "max_score": max_score, "hits": hits},
        }
        if failures:
            resp["_shards"]["failures"] = failures
        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        if agg_specs:
            resp["aggregations"] = run_aggregations(agg_specs, views)
        return resp

    def msearch(self, searches: List[tuple]) -> dict:
        """searches: list of (header, body)."""
        responses = []
        for header, body in searches:
            try:
                responses.append(self.search(header.get("index", "_all"), body))
            except Exception as e:
                from elasticsearch_tpu.common.errors import ElasticsearchTpuException

                if isinstance(e, ElasticsearchTpuException):
                    responses.append(e.to_dict())
                else:
                    responses.append({"error": {"type": type(e).__name__,
                                                "reason": str(e)}, "status": 500})
        return {"responses": responses}

    # --- scroll: POINT-IN-TIME search context (search/internal/
    # ScrollContext, SearchService.java:874). Each local shard's segment
    # set + live masks are pinned (PinnedSegmentView) when the scroll
    # opens; every page pages through that frozen snapshot with a stored
    # search_after cursor, so concurrent writes/deletes/refreshes/merges
    # never skip or duplicate docs. Keep-alive expiry and clear_scroll
    # drop the views, releasing the pinned arrays. ---

    def _reap_expired_scrolls(self) -> int:
        now = time.time()
        freed = 0
        with self._scroll_lock:
            for sid, ctx in list(self.scrolls.items()):
                if ctx["expire_at"] < now:
                    del self.scrolls[sid]
                    freed += 1
        return freed

    def _reap_expired_scrolls_loop(self, interval: float = 5.0) -> None:
        while not self._reaper_stop.wait(interval):
            self._reap_expired_scrolls()

    def _register_scroll(self, ctx: dict, keep_alive: str) -> str:
        from elasticsearch_tpu.common.units import parse_time_value

        scroll_id = _uuid.uuid4().hex
        ttl = parse_time_value(keep_alive or "5m", "scroll")
        now = time.time()
        ctx["expire_at"] = now + ttl
        with self._scroll_lock:
            # keep-alive reaper: opening a scroll sweeps expired contexts
            # (frees their pinned segment views)
            for sid_, ctx_ in list(self.scrolls.items()):
                if ctx_["expire_at"] < now:
                    del self.scrolls[sid_]
            self.scrolls[scroll_id] = ctx
        return scroll_id

    def _open_pit_scroll(self, pairs, body: dict, first_resp: dict,
                         keep_alive: str, pinned) -> str:
        """Ordered result over the pinned snapshot as a LAZILY EXTENDED
        PREFIX: opening a size=10 scroll over a large index materializes
        only the first pages' worth of DocRefs, not O(corpus). Deeper
        pages re-query the pinned views with a geometrically growing
        top-k and append only refs not already in the prefix (identity =
        (index, shard, segment, local doc)), so page boundaries never
        skip or duplicate — across ties too, because the served prefix is
        authoritative and the pinned snapshot is immutable. (A plain
        search_after cursor cannot page ties or the sortless relevance
        order safely; the prefix scheme can.)"""
        size = int(body.get("size")) if body.get("size") is not None else 10
        size = max(size, 0)
        # aggregations were already computed by the first-page search;
        # the materialization pass only needs the ordered doc refs
        q_body = {k: v for k, v in body.items()
                  if k not in ("aggs", "aggregations")}
        nd_total = 0
        sources = []
        for prefix, svc in pairs:
            sources.append((prefix, svc.name))
            pins = pinned.get(svc.name) or {}
            for views in pins.values():
                nd_total += sum(v.live_doc_count for v in views)
        ctx = {
            "mode": "pit",
            "entries": [],        # materialized ordered prefix
            "seen": set(),        # identity keys of materialized refs
            "sources": sources,
            "nd_total": nd_total,
            "last_target": 0,
            "exhausted": nd_total == 0,
            "lock": threading.Lock(),  # serializes extension + paging
            "pos": size,
            "body": dict(body),
            "q_body": q_body,
            "pinned": pinned,
            "total": first_resp["hits"]["total"],
            "max_score": first_resp["hits"]["max_score"],
        }
        self._extend_pit_entries(ctx, size)
        # the first page comes from the SAME materialized order, so page
        # boundaries can never skip or duplicate across ties
        first_resp["hits"]["hits"] = self._fetch_scroll_page(
            ctx["entries"][:size], body, pinned)
        return self._register_scroll(ctx, keep_alive)

    def _extend_pit_entries(self, ctx: dict, upto: int) -> None:
        """Grow the materialized prefix to cover [0, upto). Each round
        re-queries every pinned shard with a geometrically larger top-k
        and appends unseen refs in merged order; geometric growth keeps
        total re-query work O(final depth), and a fully drained target
        (target >= pinned live docs, or fewer refs returned than asked)
        marks the context exhausted."""
        from elasticsearch_tpu.search.service import merge_refs, normalize_sort

        sort_spec = normalize_sort(ctx["q_body"].get("sort"))
        while len(ctx["entries"]) < upto and not ctx["exhausted"]:
            target = min(ctx["nd_total"],
                         max(upto, 2 * ctx["last_target"], 32))
            per_ref = []
            for prefix, name in ctx["sources"]:
                svc = self.indices.get(name)
                if svc is None:
                    continue  # index deleted mid-scroll: its docs drop
                pins = ctx["pinned"].get(name) or {}
                for sid in sorted(svc.shards):
                    views = pins.get(sid, [])
                    nd = sum(v.live_doc_count for v in views)
                    if nd == 0:
                        continue
                    res = svc.shards[sid].searcher.query(
                        dict(ctx["q_body"]), size_hint=min(target, nd),
                        segments=views)
                    for ref in res.refs:
                        per_ref.append((prefix, name, ref))
            by_id = {id(r): (p, n) for p, n, r in per_ref}
            merged = merge_refs([r for _, _, r in per_ref], sort_spec,
                                target)
            for r in merged:
                prefix, name = by_id[id(r)]
                key = (prefix, name, r.shard_id, r.segment_name,
                       r.local_doc)
                if key in ctx["seen"]:
                    continue
                ctx["seen"].add(key)
                ctx["entries"].append((prefix, name, r))
            if target >= ctx["nd_total"] or len(merged) < target:
                ctx["exhausted"] = True
            ctx["last_target"] = target

    def _fetch_scroll_page(self, entries, body: dict, pinned) -> List[dict]:
        from elasticsearch_tpu.search.service import fetch_hits

        by_index: Dict[tuple, list] = {}
        for prefix, name, ref in entries:
            by_index.setdefault((prefix, name), []).append(ref)
        ordered = {}
        for (prefix, name), refs in by_index.items():
            svc = self.indices.get(name)
            if svc is None:
                continue  # index deleted mid-scroll: its pinned docs drop
            hits = fetch_hits(refs, svc.shards, body, f"{prefix}{name}",
                              pinned_segments=pinned.get(name))
            for ref, hit in zip(refs, hits):
                ordered[id(ref)] = hit
        return [ordered[id(r)] for _p, _n, r in entries if id(r) in ordered]

    def _open_scroll(self, expression: str, body: dict, first_resp: dict,
                     keep_alive: str) -> str:
        """Cursor-mode scroll (CCS only — remote segments can't be
        pinned): stored search_after state; results can shift with
        remote NRT refreshes, the documented delta vs pinned contexts."""
        body = dict(body)
        if "sort" not in body:
            body["sort"] = [{"_doc": "asc"}]
        return self._register_scroll({
            "mode": "cursor",
            "expression": expression,
            "body": body,
            "last_hits": first_resp["hits"]["hits"],
        }, keep_alive)

    def scroll(self, scroll_id: str, keep_alive: Optional[str] = None) -> dict:
        from elasticsearch_tpu.common.units import parse_time_value

        with self._scroll_lock:
            ctx = self.scrolls.get(scroll_id)
            if ctx is None or ctx["expire_at"] < time.time():
                self.scrolls.pop(scroll_id, None)
                raise ResourceNotFoundException(f"No search context found for id [{scroll_id}]")
        if ctx.get("mode") == "pit":
            t0 = time.monotonic()
            size = (int(ctx["body"].get("size"))
                    if ctx["body"].get("size") is not None else 10)
            size = max(size, 0)
            # extend the materialized prefix on demand (outside the
            # global scroll lock: extension re-queries the pinned views;
            # the per-context lock serializes pagers of THIS scroll)
            with ctx["lock"]:
                pos = ctx["pos"]
                self._extend_pit_entries(ctx, pos + size)
                page = ctx["entries"][pos: pos + size]
                ctx["pos"] = pos + len(page)
            with self._scroll_lock:
                if keep_alive:
                    ctx["expire_at"] = (time.time()
                                        + parse_time_value(keep_alive,
                                                           "scroll"))
            hits = self._fetch_scroll_page(page, ctx["body"], ctx["pinned"])
            return {
                "_scroll_id": scroll_id,
                "took": int((time.monotonic() - t0) * 1000),
                "timed_out": False,
                "hits": {"total": ctx["total"],
                         "max_score": ctx["max_score"], "hits": hits},
            }
        # cursor mode (CCS)
        last_hits = ctx["last_hits"]
        if not last_hits:
            resp = {"_scroll_id": scroll_id, "hits": {"total": 0, "hits": []},
                    "timed_out": False, "took": 0}
            return resp
        body = dict(ctx["body"])
        last_sort = last_hits[-1].get("sort")
        if last_sort is None:
            # relevance-sorted scroll: cursor on score
            body["search_after"] = [last_hits[-1]["_score"]]
        else:
            body["search_after"] = last_sort
        body.pop("from", None)
        resp = self.search(ctx["expression"], body)
        with self._scroll_lock:
            if scroll_id in self.scrolls:
                self.scrolls[scroll_id]["last_hits"] = resp["hits"]["hits"]
                if keep_alive:
                    self.scrolls[scroll_id]["expire_at"] = (
                        time.time() + parse_time_value(keep_alive, "scroll")
                    )
        resp["_scroll_id"] = scroll_id
        return resp

    def clear_scroll(self, scroll_ids: List[str]) -> dict:
        n = 0
        with self._scroll_lock:
            if scroll_ids == ["_all"]:
                n = len(self.scrolls)
                self.scrolls.clear()
            else:
                for sid in scroll_ids:
                    if self.scrolls.pop(sid, None) is not None:
                        n += 1
        return {"succeeded": True, "num_freed": n}

    # ------------------------------------------------------------------
    # Admin / cluster APIs
    # ------------------------------------------------------------------

    def health(self) -> dict:
        return cluster_health(self.cluster_service.state, self.indices)

    def reroute(self, body: Optional[dict] = None, dry_run: bool = False,
                explain: bool = False) -> dict:
        """_cluster/reroute (TransportClusterRerouteAction +
        cluster/routing/allocation/command/): parse the command list,
        apply each against the routing table, then run the allocator to
        normalize (fill unassigned, balance), committing the new table to
        cluster state unless dry_run. Returns the RESULTING state — not a
        blind ack."""
        import copy as _copy

        from elasticsearch_tpu.cluster import allocation as alloc
        from elasticsearch_tpu.common.errors import IllegalArgumentException

        state = self.cluster_service.state
        data_nodes = [nid for nid, n in state.nodes.items()
                      if "data" in n.roles]
        # accepted node addresses: id or name (the reference resolves
        # both through DiscoveryNodes.resolveNode)
        node_ids = {nid: nid for nid in state.nodes}
        node_ids.update({n.name: nid for nid, n in state.nodes.items()})
        open_meta = {name: md for name, md in state.indices.items()}
        table = state.routing
        if table is None:
            table = alloc.allocate(open_meta, data_nodes)
        table = _copy.deepcopy(table)
        explanations = []
        for cmd in (body or {}).get("commands") or []:
            if not isinstance(cmd, dict) or len(cmd) != 1:
                raise IllegalArgumentException(
                    f"malformed reroute command {cmd!r}")
            (name, args), = cmd.items()
            try:
                explanations.append(alloc.apply_command(
                    table, open_meta, node_ids, name, dict(args or {})))
            except alloc.RerouteException as e:
                raise IllegalArgumentException(str(e)) from None
        # normalize: the allocator keeps sticky placements, fills
        # unassigned copies and retires finished relocations
        new_table = alloc.allocate(open_meta, data_nodes, previous=table)
        # single-node reality check: a primary routed to THIS node is
        # backed by a live local shard — report it STARTED (the recovery
        # that would move INITIALIZING->STARTED already happened)
        for shards in new_table.values():
            for copies in shards.values():
                for c in copies:
                    if c.primary and c.node_id == self.node_id:
                        c.state = "STARTED"
        if dry_run:
            preview = state.copy(routing=new_table)
            resp = {"acknowledged": True, "state": preview.to_dict()}
        else:
            new_state = self.cluster_service.submit_state_update_task(
                "cluster_reroute (api)",
                lambda s: s.copy(routing=new_table))
            resp = {"acknowledged": True, "state": new_state.to_dict()}
        if explain:
            resp["explanations"] = explanations
        return resp

    def cluster_stats(self) -> dict:
        state = self.cluster_service.state
        total_docs = sum(svc.num_docs for svc in self.indices.values())
        return {
            "cluster_name": state.cluster_name,
            "status": self.health()["status"],
            "indices": {
                "count": len(self.indices),
                "docs": {"count": total_docs},
                "shards": {
                    "total": sum(s.num_shards for s in self.indices.values()),
                },
            },
            "nodes": {
                "count": {"total": 1, "data": 1, "master": 1, "ingest": 1},
                "versions": [__version__],
            },
        }

    def node_info(self) -> dict:
        return {
            "cluster_name": self.cluster_service.state.cluster_name,
            "nodes": {
                self.node_id: {
                    "name": self.node_name,
                    "version": __version__,
                    "roles": ["master", "data", "ingest"],
                    "settings": self.settings.as_nested_dict(),
                    "plugins": self.plugins_service.info(),
                    "http": {
                        "publish_address": getattr(
                            self, "http_publish_address", None),
                    },
                }
            },
        }

    def node_stats(self) -> dict:
        # node-level search section (ISSUE 8, docs/OBSERVABILITY.md):
        # per-index search blocks — phase histograms, plane/ladder
        # counters, quarantine events, batching — merged into one view
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.search.telemetry import merge_phase_stats
        from elasticsearch_tpu.transport.local import (
            aggregate_transport_stats,
        )

        search = merge_phase_stats(
            [svc.search_stats() for svc in self.indices.values()])
        # the device-memory ledger is a NODE resource: report the
        # node-wide view instead of summed per-index blocks (summing
        # restage_amplification ratios would be meaningless)
        search["memory"] = memory_accountant().stats(None)
        # the compile plane is a process resource too: re-export the
        # node-wide block instead of the per-index sum (ISSUE 14)
        from elasticsearch_tpu.common.compile_cache import compile_stats

        search["compile"] = compile_stats().stats()
        return {
            "cluster_name": self.cluster_service.state.cluster_name,
            "nodes": {
                self.node_id: {
                    "name": self.node_name,
                    "indices": {
                        "docs": {"count": sum(s.num_docs for s in self.indices.values())},
                        "search": search,
                    },
                    "jvm": {"uptime_in_millis": int((time.time() - self.start_time) * 1000)},
                    # monitor probes (OsProbe/ProcessProbe/FsProbe analogs)
                    "os": monitor.os_stats(),
                    "process": monitor.process_stats(),
                    "fs": monitor.fs_stats(
                        self.data_path if self.persistent_path else "."),
                    "thread_pool": self.thread_pool.stats(),
                    "breakers": self.breaker_service.stats(),
                    # PR-2 transport resilience counters (RetryPolicy
                    # retries/backoff waits, send timeouts,
                    # ConnectionHealth fast-fails), aggregated across
                    # every in-process TransportService — they existed
                    # but were never exported (docs/RESILIENCE.md)
                    "transport": aggregate_transport_stats(),
                }
            },
        }

    def put_template(self, name: str, body: dict) -> dict:
        body = dict(body)
        body.setdefault("index_patterns", body.pop("template", None) or [])
        if isinstance(body["index_patterns"], str):
            body["index_patterns"] = [body["index_patterns"]]

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            new.templates[name] = body
            return new

        self.cluster_service.submit_state_update_task(f"put-template [{name}]", update)
        return {"acknowledged": True}

    def delete_template(self, name: str) -> dict:
        if name not in self.cluster_service.state.templates:
            raise ResourceNotFoundException(
                f"index_template [{name}] missing"
            )

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            new.templates.pop(name, None)
            return new

        self.cluster_service.submit_state_update_task(f"delete-template [{name}]", update)
        return {"acknowledged": True}

    def update_aliases(self, actions: List[dict]) -> dict:
        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            for action in actions:
                ((verb, spec),) = action.items()
                indices = spec.get("indices") or [spec.get("index")]
                aliases = spec.get("aliases") or [spec.get("alias")]
                for idx_expr in indices:
                    for idx in new.resolve_index_names(idx_expr):
                        for alias in aliases:
                            if verb == "add":
                                meta = {k: spec[k]
                                        for k in ("filter", "routing",
                                                  "index_routing",
                                                  "search_routing")
                                        if k in spec}
                                new.indices[idx].aliases[alias] = meta
                            elif verb == "remove":
                                new.indices[idx].aliases.pop(alias, None)
                            else:
                                raise IllegalArgumentException(
                                    f"[aliases] unknown action [{verb}]"
                                )
            return new

        self.cluster_service.submit_state_update_task("update-aliases", update)
        return {"acknowledged": True}

    def _committed_cluster_settings(self) -> Settings:
        state = self.cluster_service.state
        return state.persistent_settings.merged_with(
            state.transient_settings)

    def put_cluster_settings(self, body: dict) -> dict:
        persistent = Settings.from_dict(body.get("persistent") or {})
        transient = Settings.from_dict(body.get("transient") or {})
        # a malformed value is refused before anything is committed
        for scoped in (self.cluster_settings, self.index_scoped_settings):
            scoped.validate(persistent, allow_unknown=True)
            scoped.validate(transient, allow_unknown=True)

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            old_merged = state.persistent_settings.merged_with(state.transient_settings)
            new.persistent_settings = state.persistent_settings.merged_with(persistent)
            new.transient_settings = state.transient_settings.merged_with(transient)
            merged = new.persistent_settings.merged_with(new.transient_settings)
            self.cluster_settings.apply_settings(old_merged, merged)
            return new

        self.cluster_service.submit_state_update_task("update-settings", update)
        state = self.cluster_service.state
        committed = self._committed_cluster_settings()
        # dynamic remote-cluster registration (search.remote.<alias>.seeds)
        self.remote_clusters.apply_settings(committed)
        # an EXPLICIT cluster value wins over each index's (or the node
        # file's) own, and clearing it hands control back: every index
        # takes the committed map as the top layer of its live view, as
        # the node's own view has it (the value-only update consumers
        # can't see explicitness)
        for svc in self.indices.values():
            svc.set_cluster_overrides(committed)
        self._sync_process_settings()
        return {
            "acknowledged": True,
            "persistent": state.persistent_settings.as_nested_dict(),
            "transient": state.transient_settings.as_nested_dict(),
        }

    def _sync_process_settings(self) -> None:
        """Point the process-level resources at ``self.live``: the REST
        search pool's queue (search.queue.size bounds both backpressure
        points, docs/OVERLOAD.md), the device-memory accountant's HBM
        budget (ISSUE 9; lowering it LRU-evicts at once, over budget
        stagings demote to the host rung — docs/OBSERVABILITY.md) and
        the device-staging retry config (ISSUE 10, docs/RESILIENCE.md)."""
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.common.settings import (
            SEARCH_STAGING_RETRY_BACKOFF_MS,
            SEARCH_STAGING_RETRY_MAX_ATTEMPTS,
        )
        from elasticsearch_tpu.common.staging import configure_staging_retry

        live = self.live
        self.thread_pool.executor("search").resize_queue(
            live.get_int("search.queue.size", 1000))
        memory_accountant().set_budget(
            live.get_bytes("search.memory.hbm_budget_bytes", 0))
        configure_staging_retry(
            max_attempts=live.get_int(
                SEARCH_STAGING_RETRY_MAX_ATTEMPTS.key,
                SEARCH_STAGING_RETRY_MAX_ATTEMPTS.default),
            backoff_ms=live.get_float(
                SEARCH_STAGING_RETRY_BACKOFF_MS.key,
                SEARCH_STAGING_RETRY_BACKOFF_MS.default))

    def update_index_settings(self, expression: str, body: dict) -> dict:
        normalized = Settings.from_dict(
            body.get("settings", body) or {}).with_index_prefix()
        self.index_scoped_settings.validate_dynamic_update(normalized)
        names = self.cluster_service.state.resolve_index_names(expression)

        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            for n in names:
                md = new.indices[n]
                md.settings = md.settings.merged_with(normalized)
                md.version += 1
            return new

        self.cluster_service.submit_state_update_task("update-index-settings", update)
        for n in names:
            svc = self.indices[n]
            svc.settings = svc.settings.merged_with(normalized)
            # dynamic knobs consumed at query time re-read per request
            # through svc.settings; per-searcher cached ones re-sync here
            for shard in svc.shards.values():
                shard.searcher.max_slices = svc.settings.get_int(
                    "index.max_slices_per_scroll", 1024)
            self._persist_index_meta(n)
        return {"acknowledged": True}

    def termvectors(self, index: str, doc_id: str,
                    fields: Optional[List[str]] = None) -> dict:
        """_termvectors (action/termvectors/TransportTermVectorsAction):
        per-field terms with freq + positions for one doc."""
        svc = self.index_service(index)
        shard = svc.shards[svc._route(doc_id)]
        shard.refresh()
        term_vectors: Dict[str, dict] = {}
        found = False
        for seg in shard.engine.searchable_segments():
            local = seg.id_to_doc().get(doc_id)
            if local is None or not seg.live[local]:
                continue
            found = True
            by_field: Dict[str, dict] = {}
            for tid, per_doc in seg.positions.items():
                if local not in per_doc:
                    continue
                key = seg.term_keys[tid]
                fname, token = key.split("\x1f", 1)
                if fields and fname not in fields:
                    continue
                f = by_field.setdefault(fname, {"terms": {}})
                f["terms"][token] = {
                    "term_freq": int(len(per_doc[local])),
                    "doc_freq": int(seg.term_doc_freq[tid]),
                    "tokens": [{"position": int(p)} for p in per_doc[local]],
                }
            for fname, f in by_field.items():
                st = seg.field_stats.get(fname, {})
                f["field_statistics"] = {
                    "sum_ttf": st.get("sum_ttf", 0),
                    "doc_count": st.get("doc_count", 0),
                }
                term_vectors[fname] = f
            break
        return {
            "_index": svc.name,
            "_id": doc_id,
            "found": found,
            "term_vectors": term_vectors,
        }

    def rollover(self, alias: str, body: Optional[dict] = None) -> dict:
        """_rollover (action/admin/indices/rollover): when conditions are
        met, create the next index in the series and move the write alias."""
        body = body or {}
        state = self.cluster_service.state
        sources = [n for n, md in state.indices.items() if alias in md.aliases]
        if len(sources) != 1:
            raise IllegalArgumentException(
                f"source alias [{alias}] must point to exactly one index, "
                f"found {sources}"
            )
        source = sources[0]
        import re as _re

        m = _re.search(r"-(\d+)$", source)
        if body.get("new_index"):
            target = body["new_index"]
        elif m:
            n = int(m.group(1)) + 1
            target = f"{source[:m.start()]}-{n:06d}"
        else:
            target = f"{source}-000002"
        svc = self.indices[source]
        conditions = body.get("conditions") or {}
        results = {}
        met = not conditions
        from elasticsearch_tpu.common.units import parse_byte_size, parse_time_value

        if "max_docs" in conditions:
            ok = svc.num_docs >= int(conditions["max_docs"])
            results["[max_docs: {}]".format(conditions["max_docs"])] = ok
            met = met or ok
        if "max_age" in conditions:
            age = time.time() - svc.creation_date / 1000.0
            ok = age >= parse_time_value(conditions["max_age"], "max_age")
            results["[max_age: {}]".format(conditions["max_age"])] = ok
            met = met or ok
        if "max_size" in conditions:
            size = sum(s.stats()["segments"]["memory_in_bytes"]
                       for s in svc.shards.values())
            ok = size >= parse_byte_size(conditions["max_size"], "max_size")
            results["[max_size: {}]".format(conditions["max_size"])] = ok
            met = met or ok
        resp = {
            "old_index": source,
            "new_index": target,
            "rolled_over": False,
            "dry_run": bool(body.get("dry_run", False)),
            "conditions": results,
            "acknowledged": False,
            "shards_acknowledged": False,
        }
        if not met or body.get("dry_run"):
            return resp
        create_body = {k: v for k, v in body.items()
                       if k in ("settings", "mappings", "aliases")}
        self.create_index(target, create_body)
        self.update_aliases([
            {"remove": {"index": source, "alias": alias}},
            {"add": {"index": target, "alias": alias}},
        ])
        resp.update({"rolled_over": True, "acknowledged": True,
                     "shards_acknowledged": True})
        return resp

    def shrink_index(self, source: str, target: str,
                     body: Optional[dict] = None) -> dict:
        """_shrink (action/admin/indices/shrink): re-partition into fewer
        shards. The reference hard-links segment files; we re-route docs
        (offline repartition, same semantics: SURVEY.md §5.7)."""
        body = body or {}
        svc = self.index_service(source)
        settings = dict((body.get("settings") or {}))
        target_shards = int(
            Settings.from_dict(settings).with_index_prefix()
            .get("index.number_of_shards", 1)
        )
        # pin the validated count into the create body: the index-level
        # DEFAULT is 5 (6.x), so an unset value must not silently build
        # an unshrunk 5-shard target
        settings.setdefault("index.number_of_shards", target_shards)
        if svc.num_shards % target_shards != 0:
            raise IllegalArgumentException(
                f"the number of source shards [{svc.num_shards}] must be a "
                f"multiple of [{target_shards}]"
            )
        svc.refresh()
        self.create_index(target, {
            "settings": settings,
            "mappings": svc.mapping_dict(),
            "aliases": body.get("aliases") or {},
        })
        tgt = self.indices[target]
        for shard in svc.shards.values():
            for seg in shard.engine.searchable_segments():
                for local in range(seg.num_docs):
                    if seg.live[local]:
                        tgt.index_doc(seg.doc_ids[local], seg.sources[local],
                                      seg.routings[local])
        tgt.refresh()
        return {"acknowledged": True, "shards_acknowledged": True, "index": target}

    HOT_THREADS_INTERVAL_S = 0.05

    @staticmethod
    def _thread_cpu_seconds() -> dict:
        """Per-thread CPU time (user+system seconds) via the kernel's
        per-task accounting: python thread -> its native tid ->
        /proc/self/task/<tid>/stat fields 14/15. Returns {} on platforms
        without procfs (the dump then reports stacks without CPU%)."""
        import os
        import threading

        out = {}
        try:
            tick = os.sysconf("SC_CLK_TCK")
        except (ValueError, OSError, AttributeError):
            return out
        for th in threading.enumerate():
            tid = getattr(th, "native_id", None)
            if tid is None:
                continue
            try:
                with open(f"/proc/self/task/{tid}/stat", "rb") as f:
                    # comm can contain spaces/parens: split AFTER the
                    # closing paren; utime/stime are then fields 11/12
                    parts = f.read().rpartition(b")")[2].split()
                out[th.ident] = (int(parts[11]) + int(parts[12])) / tick
            except (OSError, IndexError, ValueError):
                continue
        return out

    def hot_threads(self) -> str:
        """_nodes/hot_threads (monitor/jvm/HotThreads): REAL per-thread
        CPU sampling + stacks, busiest first. Two CPU-time snapshots
        bracket a short sleep; each live thread reports its measured CPU%
        over the interval, its name, and its current stack — so a waiter
        stuck on _MESH_EXEC_LOCK (or any other contended lock) is
        directly visible with 0% CPU and the acquire frame on top."""
        import sys
        import threading
        import traceback

        interval = self.HOT_THREADS_INTERVAL_S
        cpu0 = self._thread_cpu_seconds()
        time.sleep(interval)
        cpu1 = self._thread_cpu_seconds()
        frames = sys._current_frames()
        rows = []
        known = set()
        for th in threading.enumerate():
            cpu = max(cpu1.get(th.ident, 0.0) - cpu0.get(th.ident, 0.0),
                      0.0)
            rows.append((cpu, th.ident, th.name, th.daemon))
            known.add(th.ident)
        # sys._current_frames() also sees threads never registered with
        # the threading module (C-extension/backend callback threads
        # running Python code): report them too, CPU unattributed
        for ident in frames.keys() - known:
            rows.append((0.0, ident, "<non-threading>", False))
        rows.sort(key=lambda r: (-r[0], r[2]))
        out = [
            f"::: {{{self.node_name}}}{{{self.node_id}}}",
            f"   Hot threads sampled over {interval * 1000:.0f}ms, "
            f"{len(rows)} live threads, busiest first:",
        ]
        for cpu, ident, name, daemon in rows:
            pct = cpu / interval * 100.0 if interval else 0.0
            flags = " (daemon)" if daemon else ""
            out.append(
                f"\n   {pct:6.1f}% ({cpu * 1000:.1f}ms out of "
                f"{interval * 1000:.0f}ms) cpu usage by thread id "
                f"[{ident}] '{name}'{flags}:")
            frame = frames.get(ident)
            if frame is None:
                out.append("     <no stack available>")
                continue
            out.extend("     " + line.rstrip("\n") for line in
                       traceback.format_stack(frame, limit=12))
        return "\n".join(out)

    def put_stored_script(self, script_id: str, body: dict) -> dict:
        def update(state: ClusterState) -> ClusterState:
            new = state.copy()
            new.stored_scripts[script_id] = body.get("script", body)
            return new

        self.cluster_service.submit_state_update_task(f"put-script [{script_id}]", update)
        return {"acknowledged": True}

    def get_stored_script(self, script_id: str) -> dict:
        script = self.cluster_service.state.stored_scripts.get(script_id)
        if script is None:
            raise ResourceNotFoundException(f"unable to find script [{script_id}]")
        return {"_id": script_id, "found": True, "script": script}

    def _start_compile_warming(self) -> None:
        """Background AOT warming of every recovered index's recorded
        program-variant lattice (daemon thread — never blocks boot or
        the first query; the query path simply finds warm programs)."""
        from elasticsearch_tpu.common import compile_cache as _cc

        targets = [svc for svc in self.indices.values()
                   if _cc.variant_registry().warm_entries(svc.name)]
        if not targets:
            return

        def warm():
            for svc in targets:
                try:
                    svc.warm_compile_variants()
                except Exception:  # noqa: BLE001 — warming is best-effort
                    pass

        threading.Thread(target=warm, daemon=True,
                         name=f"compile-warm[{self.node_name}]").start()

    # ------------------------------------------------------------------
    # Graceful drain + shutdown (ISSUE 14, docs/RESILIENCE.md
    # "Rollout & drain")
    # ------------------------------------------------------------------

    def _drain_deadline_s(self) -> float:
        committed = self.cluster_service.state.persistent_settings \
            .merged_with(self.cluster_service.state.transient_settings)
        source = (committed if committed.get("search.drain.deadline")
                  is not None else self.settings)
        v = source.get_time("search.drain.deadline", 30.0)
        return float(v) if v is not None else 30.0

    def drain(self, deadline_s: Optional[float] = None) -> dict:
        """Enter the draining state (the rollout API): every index's
        admission controller stops admitting (clean 503 + Retry-After;
        queued entries shed with the same contract), in-flight searches
        finish within the drain deadline, then every shard flushes with
        a synced-flush marker so warm restart recovery is ops-free.
        Idempotent; ``undrain()`` aborts. Returns the drain report."""
        t0 = time.monotonic()
        deadline_s = (self._drain_deadline_s() if deadline_s is None
                      else float(deadline_s))
        self._draining = True
        shed = 0
        for svc in self.indices.values():
            shed += svc.admission.begin_drain()
        deadline_at = time.monotonic() + deadline_s
        drained = True
        for svc in self.indices.values():
            remaining = max(deadline_at - time.monotonic(), 0.0)
            drained = svc.admission.await_drained(remaining) and drained
        # flush + synced-flush marker AFTER the in-flight work finished:
        # the commit then covers every acked op (ops-free warm restart).
        # Only a persistent data path benefits — a tempdir-backed node
        # has nothing to warm-restart into, so skip the commit I/O.
        if self.persistent_path:
            for name in list(self.indices):
                self._persist_index_meta(name)
                try:
                    self.indices[name].synced_flush()
                except Exception:  # noqa: BLE001 — a failed flush must
                    # not block shutdown; translog replay covers the gap
                    pass
        return {
            "draining": True,
            "drained": drained,
            "queued_shed": shed,
            "in_flight_remaining": sum(
                svc.admission.in_flight for svc in self.indices.values()),
            "took_ms": int((time.monotonic() - t0) * 1000),
        }

    def undrain(self) -> dict:
        """Abort a drain (rollout cancelled): indices admit again."""
        self._draining = False
        for svc in self.indices.values():
            svc.admission.end_drain()
        return {"draining": False}

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._reaper_stop.set()
        # shutdown ordering (ISSUE 14): FIRST stop admitting and shed
        # the admission queues (queued entries get the clean rejection
        # contract, not a silent drop), drain in-flight searches within
        # the deadline, and stamp synced-flush markers — all BEFORE the
        # thread pool goes down, so no queued work is stranded behind a
        # dead executor and no index closes under an in-flight search
        self.drain()
        self.thread_pool.shutdown()
        from elasticsearch_tpu.transport.remote_cluster import unregister_node

        unregister_node(self)
        self.plugins_service.close()
        self.snapshots.close()
        for name in list(self.indices):
            self.indices[name].close()


MAPPING_TOP_LEVEL_KEYS = {
    "properties", "dynamic", "dynamic_templates", "_source", "_meta",
    "_routing", "_all", "_field_names", "_size", "_parent",
    "date_detection", "numeric_detection", "dynamic_date_formats",
}


def _unwrap_typed_mapping(mappings):
    """6.x typed mapping form: {"my_type": {...}} wraps the real mapping
    in a single custom type name (deprecated; _doc canonical). Returns
    (mapping, type_name)."""
    if (isinstance(mappings, dict) and len(mappings) == 1):
        (key, inner), = mappings.items()
        if (key not in MAPPING_TOP_LEVEL_KEYS and isinstance(inner, dict)
                and (not inner or set(inner) & MAPPING_TOP_LEVEL_KEYS)):
            return inner, key
    return mappings, "_doc"


def _template_matches(template: dict, index_name: str) -> bool:
    import fnmatch

    patterns = template.get("index_patterns") or []
    if isinstance(patterns, str):
        patterns = [patterns]
    return any(fnmatch.fnmatchcase(index_name, p) for p in patterns)


def _merge_mapping_dicts(base: dict, incoming: dict) -> None:
    for k, v in incoming.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge_mapping_dicts(base[k], v)
        else:
            base[k] = v
