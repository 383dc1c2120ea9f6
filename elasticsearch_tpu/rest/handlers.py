"""All REST handlers (the reference registers 105 in ActionModule:332).

Grouped like the reference: document CRUD, search family, index admin,
cluster admin, cat API, ingest, snapshots, tasks, scripts. Handlers are
(node, request) -> (status, payload). The cat API returns text tables
(rest/action/cat/RestTable) unless ?format=json.
"""

from __future__ import annotations

import time
from typing import List, Tuple

from elasticsearch_tpu.common.errors import (
    ActionRequestValidationException,
    IllegalArgumentException,
    VersionConflictEngineException,
)
from elasticsearch_tpu.version import __version__


def register_all(c) -> None:
    r = c.register
    # --- root ---
    r("GET", "/", _root)
    r("HEAD", "/", lambda n, q: (200, {}))

    # --- document CRUD ---
    r("PUT", "/{index}/_doc/{id}", _index_doc)
    r("POST", "/{index}/_doc/{id}", _index_doc)
    r("POST", "/{index}/_doc", _index_doc_auto_id)
    r("POST", "/{index}/{type}", _index_doc_auto_id)
    r("GET", "/{index}/_doc/{id}", _get_doc)
    r("HEAD", "/{index}/_doc/{id}", _head_doc)
    r("DELETE", "/{index}/_doc/{id}", _delete_doc)
    r("POST", "/{index}/_update/{id}", _update_doc)
    r("GET", "/{index}/_source/{id}", _get_source)
    # 6.x typed forms
    r("PUT", "/{index}/{type}/{id}", _index_doc)
    r("POST", "/{index}/{type}/{id}", _index_doc)
    r("GET", "/{index}/{type}/{id}", _get_doc)
    r("HEAD", "/{index}/{type}/{id}", _head_doc)
    r("DELETE", "/{index}/{type}/{id}", _delete_doc)
    r("POST", "/{index}/{type}/{id}/_update", _update_doc)
    r("PUT", "/{index}/{type}/{id}/_create", _create_doc)
    r("POST", "/{index}/{type}/{id}/_create", _create_doc)
    r("PUT", "/{index}/_create/{id}", _create_doc)
    r("POST", "/{index}/_create/{id}", _create_doc)
    r("GET", "/{index}/{type}/{id}/_explain", _explain)
    r("POST", "/{index}/{type}/{id}/_explain", _explain)
    r("GET", "/{index}/{type}/{id}/_source", _get_source)
    r("POST", "/_mget", _mget)
    r("POST", "/{index}/_mget", _mget)
    r("POST", "/{index}/{type}/_mget", _mget)
    # explicit literal route: "/{index}/_doc/{id}" is MORE specific than
    # "/{index}/{type}/_mget", so type "_doc" would otherwise index the
    # mget body as a document with _id "_mget"
    r("POST", "/{index}/_doc/_mget", _mget)
    r("GET", "/_mget", _mget)
    r("GET", "/{index}/{type}/_mget", _mget)
    r("GET", "/{index}/_doc/_mget", _mget)

    # --- bulk ---
    r("POST", "/_bulk", _bulk)
    r("PUT", "/_bulk", _bulk)
    r("POST", "/{index}/_bulk", _bulk)

    # --- search family (typed 6.x forms included) ---
    r("GET", "/{index}/{type}/_search", _search)
    r("POST", "/{index}/{type}/_search", _search)
    r("GET", "/{index}/{type}/_count", _count)
    r("POST", "/{index}/{type}/_count", _count)
    r("GET", "/_search", _search)
    r("POST", "/_search", _search)
    r("GET", "/{index}/_search", _search)
    r("POST", "/{index}/_search", _search)
    r("POST", "/_search/scroll", _scroll)
    r("GET", "/_search/scroll", _scroll)
    r("POST", "/_search/scroll/{scroll_id}", _scroll)
    r("GET", "/_search/scroll/{scroll_id}", _scroll)
    r("DELETE", "/_search/scroll", _clear_scroll)
    r("DELETE", "/_search/scroll/{scroll_id}", _clear_scroll)
    r("POST", "/_msearch", _msearch)
    r("GET", "/_msearch", _msearch)
    r("POST", "/{index}/_msearch", _msearch)
    r("GET", "/_count", _count)
    r("POST", "/_count", _count)
    r("GET", "/{index}/_count", _count)
    r("POST", "/{index}/_count", _count)
    r("GET", "/{index}/_validate/query", _validate_query)
    r("POST", "/{index}/_validate/query", _validate_query)
    r("GET", "/_field_caps", _field_caps)
    r("POST", "/_field_caps", _field_caps)
    r("GET", "/{index}/_field_caps", _field_caps)
    r("POST", "/{index}/_field_caps", _field_caps)
    r("GET", "/{index}/_explain/{id}", _explain)
    r("POST", "/{index}/_explain/{id}", _explain)

    # --- templates / termvectors / rollover / shrink / hot_threads ---
    r("GET", "/_search/template", _search_template)
    r("POST", "/_search/template", _search_template)
    r("GET", "/{index}/_search/template", _search_template)
    r("POST", "/{index}/_search/template", _search_template)
    r("GET", "/_render/template", _render_template)
    r("POST", "/_render/template", _render_template)
    r("GET", "/{index}/_termvectors/{id}", _termvectors)
    r("POST", "/{index}/_termvectors/{id}", _termvectors)
    r("GET", "/{index}/{type}/{id}/_termvectors", _termvectors)
    r("POST", "/{index}/_rollover", _rollover)
    r("POST", "/{index}/_rollover/{new_index}", _rollover)
    r("POST", "/{index}/_shrink/{target}", _shrink)
    r("PUT", "/{index}/_shrink/{target}", _shrink)
    r("GET", "/_nodes/hot_threads", lambda n, q: (200, n.hot_threads()))
    r("GET", "/_nodes/{node_id}/hot_threads", lambda n, q: (200, n.hot_threads()))
    # zero-downtime rollout (ISSUE 14, docs/RESILIENCE.md "Rollout &
    # drain"): enter/abort the draining state — the operator's (or the
    # orchestrator's preStop hook's) API for a graceful restart
    r("POST", "/_nodes/_local/_drain", lambda n, q: (200, n.drain()))
    r("DELETE", "/_nodes/_local/_drain", lambda n, q: (200, n.undrain()))

    # --- reindex family ---
    r("POST", "/_reindex", _reindex)
    r("POST", "/{index}/_update_by_query", _update_by_query)
    r("POST", "/{index}/_delete_by_query", _delete_by_query)

    # --- index admin ---
    r("PUT", "/{index}", _create_index)
    r("DELETE", "/{index}", _delete_index)
    r("GET", "/{index}", _get_index)
    r("HEAD", "/{index}", _head_index)
    r("POST", "/{index}/_open", lambda n, q: (200, n.open_index(q.param("index"))))
    r("POST", "/{index}/_close", lambda n, q: (200, n.close_index(q.param("index"))))
    r("POST", "/{index}/_refresh", _refresh)
    r("GET", "/{index}/_refresh", _refresh)
    r("POST", "/_refresh", _refresh)
    r("POST", "/{index}/_flush", _flush)
    r("GET", "/{index}/_flush", _flush)
    r("POST", "/_flush", _flush)
    # synced flush: durability already implies a sync point here, so it
    # degrades to a flush with the sync-shaped response
    r("POST", "/{index}/_flush/synced", _flush_synced)
    r("POST", "/_flush/synced", _flush_synced)
    r("GET", "/{index}/_flush/synced", _flush_synced)
    r("POST", "/{index}/_forcemerge", _forcemerge)
    r("POST", "/_forcemerge", _forcemerge)
    r("GET", "/{index}/_stats", _index_stats)
    r("GET", "/_stats", _index_stats)
    r("GET", "/{index}/_stats/{metric}", _index_stats)
    r("GET", "/_stats/{metric}", _index_stats)
    r("GET", "/{index}/_segments", _segments)
    r("GET", "/_segments", _segments)
    r("PUT", "/{index}/_mapping", _put_mapping)
    r("PUT", "/{index}/_mapping/{type}", _put_mapping)
    r("POST", "/{index}/_mapping", _put_mapping)
    r("GET", "/{index}/_mapping", _get_mapping)
    r("GET", "/_mapping", _get_mapping)
    r("GET", "/{index}/_mapping/{type}", _get_mapping)
    r("PUT", "/{index}/_settings", _put_index_settings)
    r("PUT", "/_settings", _put_index_settings)
    r("GET", "/{index}/_settings", _get_index_settings)
    r("GET", "/_settings", _get_index_settings)
    r("GET", "/{index}/_settings/{setting}", _get_index_settings)
    r("GET", "/_settings/{setting}", _get_index_settings)
    r("GET", "/_analyze", _analyze)
    r("POST", "/_analyze", _analyze)
    r("GET", "/{index}/_analyze", _analyze)
    r("POST", "/{index}/_analyze", _analyze)
    r("POST", "/_aliases", _update_aliases)
    r("GET", "/_alias", _get_alias)
    r("GET", "/_alias/{name}", _get_alias)
    r("GET", "/{index}/_alias", _get_alias)
    r("GET", "/{index}/_alias/{name}", _get_alias)
    r("PUT", "/{index}/_alias/{name}", _put_alias)
    r("DELETE", "/{index}/_alias/{name}", _delete_alias)
    r("HEAD", "/_alias/{name}", _head_alias)
    r("HEAD", "/{index}/_alias/{name}", _head_alias)
    r("PUT", "/_template/{name}", _put_template)
    r("GET", "/_template", _get_template)
    r("GET", "/_template/{name}", _get_template)
    r("DELETE", "/_template/{name}", _delete_template)
    r("HEAD", "/_template/{name}", _head_template)
    r("POST", "/{index}/_cache/clear", _clear_cache)
    r("POST", "/_cache/clear", _clear_cache)

    # --- cluster admin ---
    r("GET", "/_cluster/health", lambda n, q: (200, n.health()))
    r("GET", "/_cluster/health/{index}", lambda n, q: (200, n.health()))
    r("GET", "/_cluster/state", _cluster_state)
    r("GET", "/_cluster/state/{metrics}", _cluster_state)
    r("GET", "/_cluster/stats", lambda n, q: (200, n.cluster_stats()))
    r("GET", "/_cluster/settings", _get_cluster_settings)
    r("PUT", "/_cluster/settings", lambda n, q: (200, n.put_cluster_settings(q.json_body({}))))
    r("POST", "/_cluster/reroute", lambda n, q: (200, n.reroute(
        q.json_body({}) or {},
        dry_run=q.bool_param("dry_run", False),
        explain=q.bool_param("explain", False))))
    r("GET", "/_cluster/allocation/explain", _allocation_explain)
    r("GET", "/_nodes", lambda n, q: (200, n.node_info()))
    r("GET", "/_nodes/stats", lambda n, q: (200, n.node_stats()))
    r("GET", "/_nodes/stats/{metric}", lambda n, q: (200, n.node_stats()))
    r("GET", "/_nodes/stats/{metric}/{index_metric}",
      lambda n, q: (200, n.node_stats()))
    r("GET", "/_nodes/{node_id}", lambda n, q: (200, n.node_info()))
    r("GET", "/_nodes/{node_id}/stats", lambda n, q: (200, n.node_stats()))
    r("GET", "/_nodes/{node_id}/stats/{metric}",
      lambda n, q: (200, n.node_stats()))
    r("GET", "/_nodes/{node_id}/stats/{metric}/{index_metric}",
      lambda n, q: (200, n.node_stats()))
    r("GET", "/_remote/info", lambda n, q: (200, n.remote_clusters.info()))

    # --- tasks ---
    r("GET", "/_tasks", lambda n, q: (200, n.tasks.list_tasks(q.param("actions"))))
    r("GET", "/_tasks/{task_id}", _get_task)
    r("POST", "/_tasks/{task_id}/_cancel", _cancel_task)

    # --- scripts ---
    r("PUT", "/_scripts/{id}", lambda n, q: (200, n.put_stored_script(
        q.param("id"), q.json_body({}))))
    r("GET", "/_scripts/{id}", lambda n, q: (200, n.get_stored_script(q.param("id"))))
    r("DELETE", "/_scripts/{id}", _delete_script)

    # --- ingest ---
    r("PUT", "/_ingest/pipeline/{id}", lambda n, q: (200, n.ingest.put_pipeline(
        q.param("id"), q.json_body({}))))
    r("GET", "/_ingest/pipeline", lambda n, q: (200, n.ingest.get_pipeline()))
    r("GET", "/_ingest/pipeline/{id}", lambda n, q: (200, n.ingest.get_pipeline(q.param("id"))))
    r("DELETE", "/_ingest/pipeline/{id}", lambda n, q: (200, n.ingest.delete_pipeline(q.param("id"))))
    r("POST", "/_ingest/pipeline/_simulate", lambda n, q: (200, n.ingest.simulate(q.json_body({}))))
    r("GET", "/_ingest/pipeline/_simulate", lambda n, q: (200, n.ingest.simulate(q.json_body({}))))
    r("POST", "/_ingest/pipeline/{id}/_simulate", _simulate_pipeline_by_id)

    # --- snapshots ---
    r("PUT", "/_snapshot/{repo}", lambda n, q: (200, n.snapshots.put_repository(
        q.param("repo"), q.json_body({}))))
    r("POST", "/_snapshot/{repo}", lambda n, q: (200, n.snapshots.put_repository(
        q.param("repo"), q.json_body({}))))
    r("GET", "/_snapshot", lambda n, q: (200, n.snapshots.get_repository()))
    r("GET", "/_snapshot/{repo}", lambda n, q: (200, n.snapshots.get_repository(q.param("repo"))))
    r("DELETE", "/_snapshot/{repo}", lambda n, q: (200, n.snapshots.delete_repository(q.param("repo"))))
    r("PUT", "/_snapshot/{repo}/{snapshot}", lambda n, q: (200, n.snapshots.create_snapshot(
        q.param("repo"), q.param("snapshot"), q.json_body({}),
        wait_for_completion=q.bool_param("wait_for_completion", True))))
    r("GET", "/_snapshot/{repo}/_status", lambda n, q: (200, n.snapshots.snapshot_status(
        q.param("repo"))))
    r("GET", "/_snapshot/{repo}/{snapshot}/_status", lambda n, q: (200, n.snapshots.snapshot_status(
        q.param("repo"), q.param("snapshot"))))
    r("GET", "/_snapshot/{repo}/{snapshot}", lambda n, q: (200, n.snapshots.get_snapshot(
        q.param("repo"), q.param("snapshot"))))
    r("DELETE", "/_snapshot/{repo}/{snapshot}", lambda n, q: (200, n.snapshots.delete_snapshot(
        q.param("repo"), q.param("snapshot"))))
    r("POST", "/_snapshot/{repo}/{snapshot}/_restore", lambda n, q: (200, n.snapshots.restore_snapshot(
        q.param("repo"), q.param("snapshot"), q.json_body({}))))
    # repository verification probe (ISSUE 16): write/read/delete a
    # probe blob and report the nodes that could see it
    r("POST", "/_snapshot/{repo}/_verify", lambda n, q: (200, n.snapshots.verify_repository(
        q.param("repo"))))

    # --- cat API (rest/action/cat/, 22 handlers in the reference) ---
    r("GET", "/_cat", _cat_help)
    r("GET", "/_cat/indices", _cat_indices)
    r("GET", "/_cat/indices/{index}", _cat_indices)
    r("GET", "/_cat/health", _cat_health)
    r("GET", "/_cat/nodes", _cat_nodes)
    r("GET", "/_cat/shards", _cat_shards)
    r("GET", "/_cat/shards/{index}", _cat_shards)
    r("GET", "/_cat/staging", _cat_staging)
    r("GET", "/_cat/count", _cat_count)
    r("GET", "/_cat/count/{index}", _cat_count)
    r("GET", "/_cat/aliases", _cat_aliases)
    r("GET", "/_cat/aliases/{name}", _cat_aliases)
    r("GET", "/_cat/templates", _cat_templates)
    r("GET", "/_cat/templates/{name}", _cat_templates)
    r("GET", "/_cat/master", _cat_master)
    r("GET", "/_cat/segments", _cat_segments)
    r("GET", "/_cat/plugins", lambda n, q: _cat_table(
        q,
        [[n.node_id, n.node_name, p["name"], p["version"], "-"]
         for p in n.plugins_service.info()],
        ["id", "name", "component", "version", "description"]))
    r("GET", "/_cat/tasks", _cat_tasks)
    r("GET", "/_cat/pending_tasks", lambda n, q: _cat_table(
        q, [], ["insertOrder", "timeInQueue", "priority", "source"]))
    r("GET", "/_cat/allocation", _cat_allocation)
    r("GET", "/_cat/recovery", _cat_recovery)
    r("GET", "/_cat/thread_pool", _cat_thread_pool)
    r("GET", "/_cat/fielddata", lambda n, q: _cat_table(
        q, [], ["id", "host", "ip", "node", "field", "size"]))
    r("GET", "/_cat/fielddata/{fields}", lambda n, q: _cat_table(
        q, [], ["id", "host", "ip", "node", "field", "size"]))
    r("GET", "/_cat/nodeattrs", lambda n, q: _cat_table(
        q, [], ["node", "id", "pid", "host", "ip", "port", "attr", "value"]))
    r("GET", "/_cat/repositories", _cat_repositories)
    r("GET", "/_cat/snapshots/{repo}", _cat_snapshots)


# ---------------------------------------------------------------------------
# Root / info
# ---------------------------------------------------------------------------


def _root(node, req):
    return 200, {
        "name": node.node_name,
        "cluster_name": node.cluster_service.state.cluster_name,
        "cluster_uuid": node.node_id,
        "version": {
            "number": __version__,
            "lucene_version": "tpu-block-packed-1",
            "build_flavor": "tpu",
        },
        "tagline": "You Know, for Search (on TPUs)",
    }


# ---------------------------------------------------------------------------
# Document CRUD
# ---------------------------------------------------------------------------


_DEPRECATION = None


def _typed_api_warning(req) -> None:
    """Custom type names in document API paths are deprecated
    (6.x single-type enforcement, DeprecationLogger usage in
    RestIndexAction et al.)."""
    global _DEPRECATION
    t = req.param("type")
    if t is not None and t != "_doc":
        if _DEPRECATION is None:
            from elasticsearch_tpu.common.deprecation import DeprecationLogger

            _DEPRECATION = DeprecationLogger("rest.typed_api")
        _DEPRECATION.deprecated(
            "specifying a custom type in document API paths is deprecated; "
            "use /{index}/_doc/{id} instead")


def _doc_type_of(node, index):
    svc = node.indices.get(index)
    return getattr(svc, "doc_type", "_doc") if svc is not None else "_doc"


def _echo_type(req, r, node=None):
    """6.x typed-path compatibility: document API responses echo the
    type from the request path (custom types are deprecated but legal);
    type `_all` resolves to the index's actual type."""
    if isinstance(r, dict):
        t = req.param("type")
        if (t is None or t == "_all") and node is not None:
            t = _doc_type_of(node, req.param("index"))
        r["_type"] = t or "_doc"
    return r


def _write_shards_header(node, req, r):
    """Single-doc write responses carry the replication-group header
    (ReplicationResponse.ShardInfo): total = 1 primary + replicas."""
    if isinstance(r, dict) and "_shards" not in r:
        try:
            svc = node.index_service(req.param("index"))
            total = 1 + svc.num_replicas
        except Exception:  # noqa: BLE001 — header is best-effort
            total = 1
        r["_shards"] = {"total": total, "successful": 1, "failed": 0}
    return r


def _forced_refresh(req, r):
    """refresh=true responses carry forced_refresh
    (TransportWriteAction.WriteResponse.setForcedRefresh)."""
    if isinstance(r, dict) and req.param("refresh") in ("", "true", True):
        r["forced_refresh"] = True
    return r


def _validate_type_param(req):
    """MapperService.validateTypeName: type names can't start with '_'
    (only the canonical _doc is allowed)."""
    t = req.param("type")
    if t is not None and t.startswith("_") and t != "_doc":
        raise IllegalArgumentException(
            f"Document mapping type name can't start with '_', "
            f"found: [{t}]")


def _record_doc_type(node, req):
    """6.x first-write-wins type naming: indexing through a typed path
    onto an index whose type is still the default records the custom
    name, so later responses echo it (even via untyped/_all paths)."""
    t = req.param("type")
    if t in (None, "_doc", "_all"):
        return
    try:
        svc = node.index_service(req.param("index"))
    except Exception:
        return
    if svc.doc_type == "_doc":
        svc.doc_type = t


def _parent_routing(node, req):
    """Legacy ``_parent`` metadata field (ParentFieldMapper): the
    ``parent`` param acts as the routing value, and a parent-mapped type
    REQUIRES parent/routing on every single-doc op
    (RoutingMissingException). Returns (effective_routing, parent)."""
    from elasticsearch_tpu.common.errors import RoutingMissingException

    routing = req.param("routing")
    parent = req.param("parent")
    eff = routing if routing is not None else parent
    if eff is None:
        svc = node.indices.get(req.param("index"))
        if (svc is not None
                and svc.mapper_service.parent_type is not None):
            raise RoutingMissingException(
                svc.doc_type or "_doc", req.param("id") or "")
    return eff, parent


def _record_parent(node, req, doc_id, parent):
    if parent is None or doc_id is None:
        return
    svc = node.indices.get(req.param("index"))
    if svc is not None:
        svc.parents[str(doc_id)] = str(parent)


def _index_doc(node, req, force_create: bool = False):
    _validate_type_param(req)
    _typed_api_warning(req)
    body = req.json_body()
    if body is None:
        raise ActionRequestValidationException(
            "request body is required")
    kw = {}
    if req.param("version") is not None:
        kw["version"] = int(req.param("version"))
        kw["version_type"] = req.param("version_type", "internal")
    if force_create or req.param("op_type") == "create":
        kw["op_type"] = "create"
    routing, parent = _parent_routing(node, req)
    r = node.index_doc(req.param("index"), req.param("id"), body,
                       routing=routing, refresh=req.param("refresh"),
                       pipeline=req.param("pipeline"),
                       wait_for_active_shards=req.param("wait_for_active_shards"),
                       parent=parent, **kw)
    _record_parent(node, req, r.get("_id"), parent)
    _record_doc_type(node, req)
    _echo_type(req, _forced_refresh(req, _write_shards_header(node, req, r)))
    return (201 if r.get("result") == "created" else 200), r


def _create_doc(node, req):
    return _index_doc(node, req, force_create=True)


def _index_doc_auto_id(node, req):
    t = req.param("type")
    if t is not None:
        # the POST /{index}/{type} route would otherwise swallow typoed
        # or unregistered /{index}/_endpoint POSTs as documents: type
        # names may not start with '_' (MapperService.validateTypeName)
        _validate_type_param(req)
        _typed_api_warning(req)
    body = req.json_body()
    if body is None:
        raise ActionRequestValidationException("Validation Failed: 1: source is missing;")
    routing, parent = _parent_routing(node, req)
    r = node.index_doc(req.param("index"), None, body,
                       routing=routing, refresh=req.param("refresh"),
                       pipeline=req.param("pipeline"),
                       wait_for_active_shards=req.param("wait_for_active_shards"),
                       parent=parent)
    _record_parent(node, req, r.get("_id"), parent)
    _record_doc_type(node, req)
    _echo_type(req, _forced_refresh(req, _write_shards_header(node, req, r)))
    return 201, r


def _apply_source_filtering(req, r):
    """_source=false / _source=a,b / _source_include(s) / _source_exclude(s)
    on single-doc GETs (FetchSourceContext.parseFromRestRequest) — same
    filter_source the search fetch phase uses, so dotted paths and
    wildcards behave identically on both surfaces."""
    if not isinstance(r, dict) or "_source" not in r:
        return r
    from elasticsearch_tpu.search.service import filter_source

    src_param = req.param("_source")
    includes = req.param("_source_includes") or req.param("_source_include")
    excludes = req.param("_source_excludes") or req.param("_source_exclude")
    if src_param is None and includes is None and excludes is None:
        return r
    if src_param is not None and src_param.lower() == "false":
        del r["_source"]
        return r
    if src_param is not None and src_param.lower() != "true":
        includes = src_param
    inc = [f.strip() for f in includes.split(",")] if includes else None
    exc = [f.strip() for f in excludes.split(",")] if excludes else None
    r["_source"] = filter_source(r["_source"], inc, exc)
    return r


def _realtime_params(req):
    rt = req.param("realtime")
    return {
        "realtime": not (rt is not None and rt.lower() == "false"),
        "refresh": req.param("refresh"),
    }


def _get_doc(node, req):
    _typed_api_warning(req)
    routing, _parent = _parent_routing(node, req)
    r = node.get_doc(req.param("index"), req.param("id"),
                     routing, **_realtime_params(req))
    if r["found"] and req.param("version") is not None:
        # GetRequest version check: reading a stale version conflicts
        try:
            want = int(req.param("version"))
        except ValueError:
            raise IllegalArgumentException(
                f"failed to parse version [{req.param('version')}]") from None
        have = r.get("_version")
        # reads conflict on ANY mismatch for every version_type
        # (VersionType.isVersionConflictForReads: only equality passes)
        ok = (want == have)
        if not ok:
            raise VersionConflictEngineException(
                req.param("id"), have, want)
    stored = req.param("stored_fields")
    if r["found"] and stored is not None:
        wanted = [f for f in str(stored).split(",") if f]
        src = r.get("_source") or {}
        svc = node.index_service(req.param("index"))
        fields = {}
        for f in wanted:
            if f == "_source":
                continue
            if f == "_parent":
                p = svc.parents.get(str(req.param("id")))
                if p is not None:
                    r["_parent"] = p
                continue
            if f == "_routing":
                continue  # node.get_doc already set the stored value
            ft = svc.mapper_service.field_type(f)
            if (ft is None or not ft.params.get("store", False)
                    or f not in src):
                continue
            v = src[f]
            fields[f] = v if isinstance(v, list) else [v]
        if fields:
            r["fields"] = fields
        if "_source" not in wanted:
            r.pop("_source", None)
    _echo_type(req, _apply_source_filtering(req, r), node)
    return (200 if r["found"] else 404), r


def _head_doc(node, req):
    routing, _parent = _parent_routing(node, req)
    r = node.get_doc(req.param("index"), req.param("id"),
                     routing, **_realtime_params(req))
    return (200 if r["found"] else 404), {}


def _get_source(node, req):
    routing, _parent = _parent_routing(node, req)
    r = node.get_doc(req.param("index"), req.param("id"),
                     routing, **_realtime_params(req))
    if not r["found"]:
        return 404, {}
    _apply_source_filtering(req, r)
    return 200, r.get("_source", {})


def _delete_doc(node, req):
    _typed_api_warning(req)
    kw = {}
    if req.param("version") is not None:
        kw["version"] = int(req.param("version"))
        kw["version_type"] = req.param("version_type", "internal")
    routing, _parent = _parent_routing(node, req)
    r = node.delete_doc(req.param("index"), req.param("id"),
                        routing=routing,
                        refresh=req.param("refresh"), **kw)
    _echo_type(req, _forced_refresh(req, _write_shards_header(node, req, r)))
    return (200 if r.get("found") else 404), r


def _update_doc(node, req):
    _typed_api_warning(req)
    routing, parent = _parent_routing(node, req)
    version = req.param("version")
    if version is not None and req.param(
            "version_type", "internal") != "internal":
        # UpdateRequest.validate(): only internal versioning applies
        raise ActionRequestValidationException(
            "Validation Failed: 1: version type [force/external] is not "
            "supported by the update API;")
    r = node.update_doc(req.param("index"), req.param("id"), req.json_body({}),
                        routing=routing, refresh=req.param("refresh"),
                        version=int(version) if version is not None else None)
    _record_parent(node, req, r.get("_id"), parent)
    _echo_type(req, _forced_refresh(req, _write_shards_header(node, req, r)))
    src_param = req.param("_source")
    want_get = (req.param("fields")
                or (src_param is not None and src_param.lower() != "false"))
    if want_get and r.get("result") != "noop":
        from elasticsearch_tpu.search.service import filter_source

        g = node.get_doc(req.param("index"), req.param("id"),
                         req.param("routing"))
        if g.get("found"):
            src = g["_source"]
            if src_param and src_param.lower() != "true":
                src = filter_source(src, src_param.split(","), None)
            get_sec = {"found": True, "_source": src}
            if req.param("fields"):
                want = req.param("fields").split(",")
                get_sec["fields"] = {f: [g["_source"][f]]
                                     for f in want if f in g["_source"]}
            r["get"] = get_sec
    return 200, r


def _mget(node, req):
    rp = _realtime_params(req)
    stored = req.param("stored_fields")
    return 200, node.mget(req.json_body({}), req.param("index"),
                          req.param("type"), realtime=rp["realtime"],
                          refresh=rp["refresh"],
                          stored_fields=([f for f in str(stored).split(",")
                                          if f] if stored else None))


def _bulk(node, req):
    lines = req.ndjson_lines()
    if not lines:
        raise ActionRequestValidationException("request body is required")
    default_index = req.param("index")
    ops = []
    i = 0
    while i < len(lines):
        action_line = lines[i]
        if not action_line:
            # an empty {} action object (BulkRequest.add: the parser
            # expects the action FIELD_NAME immediately)
            raise IllegalArgumentException(
                f"Malformed action/metadata line [{i + 1}], expected "
                f"FIELD_NAME but found [END_OBJECT]")
        ((action, meta),) = action_line.items()
        meta = dict(meta or {})
        meta.setdefault("_index", default_index)
        i += 1
        if action in ("index", "create", "update"):
            if i >= len(lines):
                raise ActionRequestValidationException(
                    "Validation Failed: 1: no requests added;"
                )
            ops.append((action, meta, lines[i]))
            i += 1
        else:
            ops.append((action, meta, None))
    resp = node.bulk(ops, refresh=req.param("refresh"), pipeline=req.param("pipeline"))
    return 200, resp


# ---------------------------------------------------------------------------
# Search family
# ---------------------------------------------------------------------------


def _search_body(req):
    body = req.json_body({}) or {}
    # URI search: ?q=...&size=...&from=...&sort=f:asc
    q = req.param("q")
    if q is not None:
        qs = {"query": q}
        for name, key in (("df", "default_field"),
                          ("default_operator", "default_operator"),
                          ("analyzer", "analyzer")):
            if req.param(name) is not None:
                qs[key] = req.param(name)
        if req.param("lenient") is not None:
            qs["lenient"] = req.bool_param("lenient")
        body["query"] = {"query_string": qs}
    for p in ("size", "from"):
        if req.param(p) is not None:
            body[p] = int(req.param(p))
    # query-phase fault-tolerance params (RestSearchAction): a deadline
    # on the query phase and the partial-results degradation policy
    if req.param("timeout") is not None:
        body["timeout"] = req.param("timeout")
    if req.param("allow_partial_search_results") is not None:
        body["allow_partial_search_results"] = req.bool_param(
            "allow_partial_search_results")
    if req.param("request_cache") is not None:
        # RestSearchAction's request_cache: false keeps this request out
        # of the shard request cache (IndexService takes the key off the
        # body before anything else reads it)
        body["request_cache"] = req.bool_param("request_cache")
    if req.param("track_total_hits") is not None:
        # boolean OR the reference's integer-threshold form; an explicit
        # false is the default behavior, so the key is simply not set
        # (setting it would needlessly demote the request off the
        # batchable fast path)
        raw = req.param("track_total_hits")
        try:
            body["track_total_hits"] = int(raw)
        except (TypeError, ValueError):
            if req.bool_param("track_total_hits"):
                body["track_total_hits"] = True
    if req.param("sort") is not None:
        sort = []
        for part in req.param("sort").split(","):
            if ":" in part:
                f, o = part.split(":", 1)
                sort.append({f: o})
            else:
                sort.append(part)
        body["sort"] = sort
    if req.param("_source") is not None:
        v = req.param("_source")
        body["_source"] = False if v == "false" else (True if v == "true" else v.split(","))
    return body


def _search(node, req):
    body = _search_body(req)
    resp = node.search(req.param("index", "_all"), body,
                       scroll=req.param("scroll"))
    _echo_hit_types(node, resp)
    _render_total_hits(resp, body)
    return 200, resp


def _render_total_hits(resp, body) -> None:
    """track_total_hits-style REST surfacing of inexact totals: the 6.x
    response keeps ``hits.total`` a bare int, but block-max pruned
    scoring (docs/PRUNING.md) and hybrid RRF fusion (docs/VECTOR.md)
    report LOWER BOUNDS — previously visible only through the
    response-internal ``_pruned``/``_total_relation`` markers. Whenever
    the total is inexact, the response states its relation (a convex
    hybrid's exact union count: ``eq``), or the request explicitly asked
    with ``track_total_hits``, it renders as the modern object form
    ``{"value": N, "relation": "eq"|"gte"}``. Passing
    ``track_total_hits: true`` (or the reference's integer-threshold
    form — totals here are exact whenever the count ran exhaustively,
    so any positive threshold is satisfied) also forces the EXACT
    total: the key is outside the pruned fast path's allowed body keys,
    so such requests execute exhaustively by construction."""
    hits = (resp or {}).get("hits")
    if not isinstance(hits, dict) or not isinstance(hits.get("total"), int):
        return
    relation = "eq"
    pruned = resp.get("_pruned")
    if isinstance(pruned, dict) and pruned.get("total_relation"):
        relation = str(pruned["total_relation"])
    elif resp.get("_total_relation"):
        relation = str(resp["_total_relation"])
    tth = (body or {}).get("track_total_hits")
    opted_in = tth is True or (isinstance(tth, int)
                               and not isinstance(tth, bool) and tth > 0)
    if relation != "eq" or opted_in or resp.get("_total_relation"):
        hits["total"] = {"value": hits["total"], "relation": relation}


def _echo_hit_types(node, resp):
    """Hits echo their index's 6.x type name (custom types deprecated)."""
    for hit in (resp.get("hits", {}) or {}).get("hits", []):
        if isinstance(hit, dict) and hit.get("_type") == "_doc":
            hit["_type"] = _doc_type_of(node, hit.get("_index"))


def _scroll(node, req):
    body = req.json_body({}) or {}
    scroll_id = body.get("scroll_id") or req.param("scroll_id")
    return 200, node.scroll(scroll_id, body.get("scroll") or req.param("scroll"))


def _clear_scroll(node, req):
    body = req.json_body({}) or {}
    ids = body.get("scroll_id") or req.param("scroll_id") or ["_all"]
    if isinstance(ids, str):
        ids = [i for i in ids.split(",") if i]
    r = node.clear_scroll(ids)
    # clearing ids none of which existed is a 404 (RestClearScrollAction
    # maps num_freed == 0 to NOT_FOUND); _all always acknowledges
    status = 200 if (r.get("num_freed", 0) > 0 or ids == ["_all"]) else 404
    return status, r


def _msearch(node, req):
    lines = req.ndjson_lines()
    searches = []
    i = 0
    while i + 1 <= len(lines):
        header = lines[i] if isinstance(lines[i], dict) else {}
        body = lines[i + 1] if i + 1 < len(lines) else {}
        header.setdefault("index", req.param("index", "_all"))
        searches.append((header, body))
        i += 2
    resp = node.msearch(searches)
    # the same inexact-total rendering as _search, per entry (a pruned
    # or hybrid member's gte lower bound must not present as exact)
    for (header, body), entry in zip(searches,
                                     resp.get("responses") or []):
        if isinstance(entry, dict):
            _render_total_hits(entry, body)
    return 200, resp


def _count(node, req):
    body = _search_body(req)
    body["size"] = 0
    resp = node.search(req.param("index", "_all"), body)
    return 200, {"count": resp["hits"]["total"], "_shards": resp["_shards"]}


def _validate_query(node, req):
    from elasticsearch_tpu.search.query_dsl import parse_query

    body = req.json_body({}) or {}
    try:
        parse_query(body.get("query"))
        return 200, {"valid": True, "_shards": {"total": 1, "successful": 1, "failed": 0}}
    except Exception as e:
        resp = {"valid": False, "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if req.bool_param("explain"):
            resp["explanations"] = [{"index": req.param("index"), "valid": False,
                                     "error": str(e)}]
        return 200, resp


def _field_caps(node, req):
    from elasticsearch_tpu.mapper.field_types import NUMERIC_TYPES

    fields_param = req.param("fields") or (req.json_body({}) or {}).get("fields", "*")
    if isinstance(fields_param, str):
        fields_param = fields_param.split(",")
    out = {}
    # cross-cluster field caps: alias:index groups resolve on the remote
    pairs, _ = node._resolve_search_groups(req.param("index", "_all"))
    for _prefix, svc in pairs:
        for pattern in fields_param:
            for fname in svc.mapper_service.mapper.simple_match_to_fields(pattern):
                ft = svc.mapper_service.field_type(fname)
                t = ft.type_name
                entry = out.setdefault(fname, {}).setdefault(t, {
                    "type": t,
                    "searchable": bool(ft.index),
                    "aggregatable": bool(ft.doc_values) or t == "text" and ft.fielddata,
                })
    return 200, {"fields": out}


def _explain(node, req):
    body = req.json_body({}) or {}
    if body and "query" not in body:
        # a bare query object at the top level is a parse error
        # (RestExplainAction expects the "query" element)
        raise ActionRequestValidationException(
            "Validation Failed: 1: query is missing;")
    svc = node.index_service(req.param("index"))
    doc_id = req.param("id")
    inner = body.get("query")
    if inner is None and req.param("q") is not None:
        # URI-search form: ?q= with df/default_operator/analyzer/lenient
        inner = {"query_string": {
            "query": req.param("q"),
            **({"default_field": req.param("df")} if req.param("df")
               else {}),
            **({"default_operator": req.param("default_operator")}
               if req.param("default_operator") else {}),
            **({"analyzer": req.param("analyzer")}
               if req.param("analyzer") else {}),
            **({"lenient": req.bool_param("lenient")}
               if req.param("lenient") is not None else {}),
        }}
    q = dict(body)
    q["query"] = {"bool": {"must": [inner or {"match_all": {}}],
                           "filter": [{"ids": {"values": [doc_id]}}]}}
    q["size"] = 1
    resp = svc.search(q)
    matched = resp["hits"]["total"] > 0
    score = resp["hits"]["hits"][0]["_score"] if matched else 0.0
    details = _bm25_explanation_details(
        svc, doc_id, body.get("query")) if matched else []
    out = {
        "_index": svc.name,
        "_id": doc_id,
        "matched": matched,
        "explanation": {
            "value": score,
            "description": ("sum of:" if details else
                            "score via the fused TPU query program"),
            "details": details,
        },
    }
    # the `get` section carries the (filtered) source when any _source
    # param was given (RestExplainAction -> GetResult); reuses the same
    # FetchSourceContext param parsing as single-doc GETs
    if any(req.param(p) is not None for p in (
            "_source", "_source_include", "_source_includes",
            "_source_exclude", "_source_excludes")):
        g = svc.get_doc(doc_id, routing=req.param("routing"))
        if g.found:
            get_out = {"found": True, "_source": dict(g.source)}
            _apply_source_filtering(req, get_out)
            out["get"] = get_out
    _echo_type(req, out)
    return 200, out


def _bm25_explanation_details(svc, doc_id, query_body):
    """Per-term BM25 breakdown (BM25Similarity.explain's tree: boost *
    idf * tfNorm with their inputs) for queries that expand to term
    lanes; other query shapes keep the summary-level explanation."""
    import math

    from elasticsearch_tpu.ops.scoring import B, K1, bm25_idf
    from elasticsearch_tpu.search.query_dsl import (
        ShardQueryContext,
        parse_query,
    )

    try:
        qb = parse_query(query_body)
    except Exception:  # noqa: BLE001 — summary fallback
        return []
    shard = svc.shards[svc._route(doc_id)]
    ctx = ShardQueryContext(svc.mapper_service, engine=shard.engine)
    lanes = qb.explain_terms(ctx)
    if not lanes:
        return []
    entry = shard.engine.version_map.get(doc_id)
    if entry is None or entry.segment is None:
        return []
    segment = next((s for s in shard.engine.searchable_segments()
                    if s.name == entry.segment), None)
    if segment is None:
        return []
    local = entry.local_doc
    details = []
    for field, token, boost in lanes:
        tid = segment.term_id(field, token)
        if tid < 0:
            continue
        start = int(segment.term_block_start[tid])
        count = int(segment.term_block_count[tid])
        blk = segment.block_docs[start:start + count]
        sel = blk == local
        if not sel.any():
            continue
        freq = float(segment.block_tfs[start:start + count][sel][0])
        row = segment.field_norm_idx.get(field, 0)
        dl = float(segment.norms[row][local])
        avgdl = segment.field_avgdl(field)
        st = segment.field_stats.get(field, {})
        n_docs = int(st.get("doc_count", segment.num_docs))
        df = int(segment.term_doc_freq[tid])
        idf = bm25_idf(df, n_docs)
        tf_norm = freq * (K1 + 1) / (freq + K1 * (1 - B + B * dl / avgdl))
        details.append({
            "value": boost * idf * tf_norm,
            "description": f"weight({field}:{token} in {local}) "
                           f"[PerFieldSimilarity], result of:",
            "details": [{
                "value": boost * idf * tf_norm,
                "description": f"score(doc={local}, freq={freq}), "
                               f"product of:",
                "details": [
                    {"value": boost, "description": "boost", "details": []},
                    {"value": idf,
                     "description": "idf, computed as log(1 + (N - n + 0.5)"
                                    " / (n + 0.5)) from:",
                     "details": [
                         {"value": df,
                          "description": "n, number of documents containing "
                                         "term", "details": []},
                         {"value": n_docs,
                          "description": "N, total number of documents with "
                                         "field", "details": []}]},
                    {"value": tf_norm,
                     "description": "tfNorm, computed as (freq * (k1 + 1)) /"
                                    " (freq + k1 * (1 - b + b * dl / avgdl))"
                                    " from:",
                     "details": [
                         {"value": freq, "description": "termFreq",
                          "details": []},
                         {"value": K1, "description": "parameter k1",
                          "details": []},
                         {"value": B, "description": "parameter b",
                          "details": []},
                         {"value": avgdl,
                          "description": "avgFieldLength", "details": []},
                         {"value": dl, "description": "fieldLength",
                          "details": []}]},
                ],
            }],
        })
    return details


def _search_template(node, req):
    from elasticsearch_tpu.search.templates import resolve_template

    body = req.json_body({}) or {}
    rendered = resolve_template(node, body)
    return 200, node.search(req.param("index", "_all"), rendered)


def _render_template(node, req):
    from elasticsearch_tpu.search.templates import resolve_template

    return 200, {"template_output": resolve_template(node, req.json_body({}) or {})}


def _termvectors(node, req):
    _typed_api_warning(req)
    body = req.json_body({}) or {}
    fields = body.get("fields") or (
        req.param("fields").split(",") if req.param("fields") else None
    )
    return 200, node.termvectors(req.param("index"), req.param("id"), fields)


def _rollover(node, req):
    body = req.json_body({}) or {}
    if req.param("new_index"):
        body["new_index"] = req.param("new_index")
    if req.bool_param("dry_run"):
        body["dry_run"] = True
    return 200, node.rollover(req.param("index"), body)


def _shrink(node, req):
    return 200, node.shrink_index(req.param("index"), req.param("target"),
                                  req.json_body({}))


def _reindex(node, req):
    from elasticsearch_tpu.index.reindex import reindex

    return 200, reindex(node, req.json_body({}))


def _update_by_query(node, req):
    from elasticsearch_tpu.index.reindex import update_by_query

    return 200, update_by_query(node, req.param("index"), req.json_body({}))


def _delete_by_query(node, req):
    from elasticsearch_tpu.index.reindex import delete_by_query

    return 200, delete_by_query(node, req.param("index"), req.json_body({}))


# ---------------------------------------------------------------------------
# Index admin
# ---------------------------------------------------------------------------


def _create_index(node, req):
    return 200, node.create_index(req.param("index"), req.json_body({}))


def _delete_index(node, req):
    return 200, node.delete_index(
        req.param("index"),
        ignore_unavailable=req.bool_param("ignore_unavailable"),
        allow_no_indices=req.bool_param("allow_no_indices", True))


def _get_index(node, req):
    state = node.cluster_service.state
    out = {}
    expr = req.param("index")
    if req.bool_param("ignore_unavailable"):
        from elasticsearch_tpu.common.errors import IndexNotFoundException

        names = []
        for part in str(expr).split(","):
            try:
                names.extend(state.resolve_index_names(part))
            except IndexNotFoundException:
                continue  # ignore_unavailable skips only missing parts
    else:
        names = state.resolve_index_names(expr)
    for name in names:
        md = state.indices[name]
        out[name] = md.to_dict()
    return 200, out


def _head_index(node, req):
    state = node.cluster_service.state
    try:
        state.resolve_index_names(req.param("index"))
        return 200, {}
    except Exception:
        return 404, {}


def _refresh(node, req):
    names = node.cluster_service.state.resolve_index_names(req.param("index", "_all"))
    for name in names:
        node.indices[name].refresh()
    n = sum(node.indices[x].num_shards for x in names)
    return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}


def _flush(node, req):
    names = node.cluster_service.state.resolve_index_names(req.param("index", "_all"))
    for name in names:
        node.indices[name].flush()
    n = sum(node.indices[x].num_shards for x in names)
    return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}


def _flush_synced(node, req):
    """Synced flush (SyncedFlushService): every flush here commits a
    durable sync point, so the response reports all shards successful in
    the reference's per-index shape."""
    names = node.cluster_service.state.resolve_index_names(
        req.param("index", "_all"))
    out = {"_shards": {"total": 0, "successful": 0, "failed": 0}}
    for name in names:
        node.indices[name].flush()
        n = node.indices[name].num_shards
        out["_shards"]["total"] += n
        out["_shards"]["successful"] += n
        out[name] = {"total": n, "successful": n, "failed": 0}
    return 200, out


def _forcemerge(node, req):
    names = node.cluster_service.state.resolve_index_names(req.param("index", "_all"))
    for name in names:
        node.indices[name].force_merge()
    n = sum(node.indices[x].num_shards for x in names)
    return 200, {"_shards": {"total": n, "successful": n, "failed": 0}}


_STATS_METRICS = {
    "docs": "docs", "store": "store", "indexing": "indexing", "get": "get",
    "search": "search", "merge": "merges", "refresh": "refresh",
    "flush": "flush", "warmer": "warmer", "query_cache": "query_cache",
    "fielddata": "fielddata", "completion": "completion",
    "segments": "segments", "translog": "translog", "recovery": "recovery",
    "request_cache": "request_cache", "suggest": "search",
}


def _filter_named(entries, param):
    """groups=/types= filtering: comma lists, _all, and * wildcards
    (the reference's CommonStatsFlags groups/types patterns)."""
    import fnmatch

    if not param or not entries:
        return None
    wanted = param if isinstance(param, list) else str(param).split(",")
    if "_all" in wanted:
        return dict(entries)
    return {k: v for k, v in entries.items()
            if any(fnmatch.fnmatchcase(k, w) for w in wanted if w)}


def _sum_stats(dicts):
    """Element-wise numeric merge of section dicts (nested)."""
    out = {}
    for d in dicts:
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = _sum_stats([out.get(k, {}), v])
            elif isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
            else:
                out[k] = v
    return out


def _index_stats(node, req):
    names = node.cluster_service.state.resolve_index_names(
        req.param("index", "_all"))
    metric_param = req.param("metric")
    sections = None
    if metric_param and metric_param != "_all":
        parts = (metric_param if isinstance(metric_param, list)
                 else str(metric_param).split(","))
        sections = set()
        for m in parts:
            if not m or m == "_all":
                sections = None
                break
            if m not in _STATS_METRICS:
                import difflib

                near = difflib.get_close_matches(m, _STATS_METRICS, n=3)
                hint = (" -> did you mean " + (
                    f"[{near[0]}]" if len(near) == 1
                    else "any of [" + ", ".join(near) + "]") + "?") \
                    if near else ""
                raise IllegalArgumentException(
                    f"request [{req.path}] contains unrecognized metric: "
                    f"[{m}]{hint}")
            sections.add(_STATS_METRICS[m])
    level = req.param("level", "indices")
    if level not in ("cluster", "indices", "shards"):
        raise IllegalArgumentException(
            f"level parameter must be one of [cluster] or [indices] or "
            f"[shards] but was [{level}]")
    groups_param = req.param("groups")
    types_param = req.param("types")

    def shape(stats_pair):
        """Apply metric/groups/types filters to a {primaries,total} pair."""
        out = {}
        for side in ("primaries", "total"):
            src_side = stats_pair[side]
            side_out = {}
            for key, val in src_side.items():
                if sections is not None and key not in sections:
                    continue
                val = dict(val) if isinstance(val, dict) else val
                if key == "search" and isinstance(val, dict):
                    g = val.pop("groups", None)
                    kept = _filter_named(g, groups_param)
                    if kept:
                        val["groups"] = kept
                if key == "indexing" and isinstance(val, dict):
                    t = val.pop("types", None)
                    kept = _filter_named(t, types_param)
                    if kept:
                        val["types"] = kept
                side_out[key] = val
            out[side] = side_out
        return out

    state = node.cluster_service.state
    indices = {}
    shards_total = shards_ok = 0
    for name in names:
        if name not in node.indices:
            continue
        md = state.indices.get(name)
        replicas = md.num_replicas if md is not None else 0
        svc = node.indices[name]
        # the reference's stats header counts ALL copies in `total`
        # (including unassigned replicas: rest-api-spec
        # indices.stats/10_index.yml expects 18 for 9 primaries + 9
        # unassigned replicas with successful 9) — total here is NOT
        # successful + failed
        shards_total += svc.num_shards * (1 + replicas)
        shards_ok += svc.num_shards
        raw = svc.stats()
        if req.bool_param("include_segment_file_sizes"):
            for side in ("primaries", "total"):
                seg = raw[side].get("segments")
                if seg is not None:
                    seg["file_sizes"] = {"postings": {
                        "size_in_bytes": seg.get("memory_in_bytes", 0),
                        "description": "block-packed postings arrays"}}
        entry = shape(raw)
        if level == "shards":
            def shard_entry(s):
                out = {k: v for k, v in s.items()
                       if sections is None or k in sections
                       or k in ("routing", "commit", "seq_no")}
                return out
            entry["shards"] = {str(sid): [shard_entry(s)]
                               for sid, s in raw["shards"].items()}
        indices[name] = entry
    all_stats = {
        "primaries": _sum_stats([i["primaries"] for i in indices.values()]),
        "total": _sum_stats([i["total"] for i in indices.values()]),
    }
    resp = {
        "_shards": {"total": shards_total, "successful": shards_ok,
                    "failed": 0},
        "_all": all_stats,
    }
    if level != "cluster":
        resp["indices"] = indices
    return 200, resp


def _segments(node, req):
    names = node.cluster_service.state.resolve_index_names(
        req.param("index", "_all"))
    indices = {}
    total = 0
    for name in names:
        svc = node.indices[name]
        shards = {}
        for sid, shard in svc.shards.items():
            shards[str(sid)] = [{
                "segments": {s.name: s.stats()
                             for s in shard.engine.segments},
            }]
            total += 1
        indices[name] = {"shards": shards}
    return 200, {"indices": indices,
                 "_shards": {"total": total, "successful": total,
                             "failed": 0}}


def _put_mapping(node, req):
    svc = node.index_service(req.param("index"))
    body = req.json_body({}) or {}
    if "properties" not in body and len(body) == 1:
        body = next(iter(body.values()))  # typed form {"_doc": {...}}
    svc.put_mapping(body)
    node._maybe_update_mapping_meta(svc.name)
    return 200, {"acknowledged": True}


def _get_mapping(node, req):
    state = node.cluster_service.state
    want_type = req.param("type")
    out = {}
    for name in state.resolve_index_names(req.param("index", "_all")):
        svc = node.indices[name]
        dt = getattr(svc, "doc_type", "_doc")
        if want_type and want_type not in (dt, "_all"):
            continue
        out[name] = {"mappings": {dt: svc.mapping_dict()}}
    if want_type and not out:
        from elasticsearch_tpu.common.errors import (
            ResourceNotFoundException,
        )
        raise ResourceNotFoundException(f"type[[{want_type}]] missing")
    return 200, out


def _flat_field_mappings(props: dict, prefix: str = "") -> dict:
    """Flatten a properties tree to {full_path: leaf_params} (object
    containers themselves are not fields)."""
    out = {}
    for name, params in (props or {}).items():
        path = f"{prefix}{name}"
        child = (params or {}).get("properties")
        if child and "type" not in (params or {}):
            out.update(_flat_field_mappings(child, path + "."))
            continue
        if child:
            out.update(_flat_field_mappings(child, path + "."))
        out[path] = {k: v for k, v in (params or {}).items()
                     if k != "properties"}
        for sub, sub_params in ((params or {}).get("fields") or {}).items():
            out[f"{path}.{sub}"] = dict(sub_params or {})
    return out


def _get_field_mapping(node, req):
    """GET /_mapping/field/{fields} (TransportGetFieldMappingsAction):
    per-index, per-type field mapping extracts with full_name + the
    field's mapping subtree; wildcards match the full path."""
    import fnmatch as _fn

    state = node.cluster_service.state
    fields = [f for f in str(req.param("fields", "")).split(",") if f]
    want_types = [t for t in str(req.param("type") or "").split(",") if t]
    include_defaults = req.bool_param("include_defaults", False)
    out = {}
    matched_type = not want_types
    for name in state.resolve_index_names(req.param("index", "_all")):
        svc = node.indices[name]
        dt = getattr(svc, "doc_type", "_doc") or "_doc"
        if want_types and not any(
                _fn.fnmatchcase(dt, t) for t in want_types):
            continue
        matched_type = True
        flat = _flat_field_mappings(
            svc.mapping_dict().get("properties") or {})
        per_field = {}
        for pattern in fields:
            for path, params in flat.items():
                if path == pattern or _fn.fnmatchcase(path, pattern):
                    leaf = path.rsplit(".", 1)[-1]
                    params = dict(params)
                    if (include_defaults and params.get("type") == "text"
                            and "analyzer" not in params):
                        params["analyzer"] = "default"
                    per_field[path] = {"full_name": path,
                                       "mapping": {leaf: params}}
        if per_field:
            out[name] = {"mappings": {dt: per_field}}
        elif want_types or req.param("index") is not None:
            # index+type resolved but no field matched: empty marker —
            # unless NOTHING matched anywhere, which renders {}
            out[name] = {"mappings": {dt: {}}}
    if want_types and not matched_type:
        raise ResourceNotFoundException(
            f"type[[{','.join(want_types)}]] missing")
    if not any(per for v in out.values()
               for per in v["mappings"].values()):
        return 200, {}  # no field matched anywhere (reference shape)
    return 200, out


def _put_index_settings(node, req):
    return 200, node.update_index_settings(req.param("index", "_all"),
                                           req.json_body({}) or {})


def _settings_values_as_strings(obj):
    """The reference renders every setting value as a string
    (Settings#toXContent); booleans lowercase."""
    if isinstance(obj, dict):
        return {k: _settings_values_as_strings(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_settings_values_as_strings(v) for v in obj]
    if isinstance(obj, bool):
        return "true" if obj else "false"
    return str(obj)


def _render_settings(settings, flat: bool):
    """Settings -> response dict: index.-prefixed, flat or nested,
    string-valued."""
    from elasticsearch_tpu.common.settings import Settings

    if isinstance(settings, dict):
        settings = Settings.from_dict(settings)
    settings = settings.with_index_prefix()
    if flat:
        return _settings_values_as_strings(settings.as_dict())
    return _settings_values_as_strings(settings.as_nested_dict())


def _get_index_settings(node, req):
    import fnmatch

    state = node.cluster_service.state
    flat = req.bool_param("flat_settings")
    name_filter = req.param("setting")
    out = {}
    for name in state.resolve_index_names(req.param("index", "_all")):
        md = state.indices[name]
        settings = md.settings.as_dict()
        settings.setdefault("index.number_of_shards", md.num_shards)
        settings.setdefault("index.number_of_replicas", md.num_replicas)
        settings.setdefault(
            "index.uuid",
            node.indices[name].uuid if name in node.indices else name)
        if name_filter and name_filter != "_all":
            pats = [p for p in str(name_filter).split(",") if p]
            settings = {k: v for k, v in settings.items()
                        if any(fnmatch.fnmatchcase(k, p) for p in pats)}
        out[name] = {"settings": _render_settings(settings, flat)}
    return 200, out


def _analyze(node, req):
    from elasticsearch_tpu.analysis.analyzers import AnalysisRegistry

    body = req.json_body({}) or {}
    text = body.get("text") or req.param("text")
    if text is None:
        raise ActionRequestValidationException("Validation Failed: 1: text is missing;")
    texts = text if isinstance(text, list) else [text]
    index = req.param("index")
    if index is not None:
        registry = node.index_service(index).analyzers
    else:
        registry = AnalysisRegistry()
    analyzer_name = body.get("analyzer") or req.param("analyzer")
    field = body.get("field")
    if analyzer_name is None and field is not None and index is not None:
        ft = node.index_service(index).mapper_service.field_type(field)
        analyzer_name = getattr(ft, "analyzer", None) or "standard"
    analyzer = registry.get(analyzer_name or "standard")
    tokens = []
    for t in texts:
        for pos, (tok, start, end) in enumerate(analyzer.analyze_tokens(t)):
            tokens.append({
                "token": tok,
                "start_offset": start,
                "end_offset": end,
                "type": "<ALPHANUM>",
                "position": pos,
            })
    return 200, {"tokens": tokens}


def _update_aliases(node, req):
    body = req.json_body({}) or {}
    return 200, node.update_aliases(body.get("actions", []))


def _get_alias(node, req):
    state = node.cluster_service.state
    name_filter = req.param("name")
    out = {}
    for idx in state.resolve_index_names(req.param("index", "_all")):
        aliases = state.indices[idx].aliases
        if name_filter and name_filter != "_all":
            import fnmatch

            patterns = [p for p in str(name_filter).split(",") if p]
            aliases = {a: v for a, v in aliases.items()
                       if any(fnmatch.fnmatchcase(a, p) for p in patterns)}
            if not aliases:
                continue
        out[idx] = {"aliases": aliases}
    if name_filter and name_filter != "_all":
        # a NAMED (non-wildcard) pattern matching nothing -> 404, but the
        # body still carries whatever did match (GetAliasesResponse)
        found = {a for v in out.values() for a in v["aliases"]}
        import fnmatch as _fn
        missing = [p for p in str(name_filter).split(",")
                   if p and "*" not in p and p not in found]
        if missing:
            return 404, {**out, "error": f"aliases {missing} missing",
                         "status": 404}
    return 200, out


def _put_alias(node, req):
    spec = req.json_body({}) or {}
    return 200, node.update_aliases([{"add": {
        "index": req.param("index"), "alias": req.param("name"), **spec}}])


def _delete_alias(node, req):
    return 200, node.update_aliases([{"remove": {
        "index": req.param("index"), "alias": req.param("name")}}])


def _head_alias(node, req):
    state = node.cluster_service.state
    index = req.param("index")
    names = (state.resolve_index_names(index) if index else
             list(state.indices))
    for n in names:
        md = state.indices.get(n)
        if md is not None and req.param("name") in md.aliases:
            return 200, {}
    return 404, {}


def _put_template(node, req):
    name = req.param("name")
    if req.bool_param("create") and \
            name in node.cluster_service.state.templates:
        raise IllegalArgumentException(
            f"index_template [{name}] already exists")
    return 200, node.put_template(name, req.json_body({}) or {})


def _get_template(node, req):
    import fnmatch

    templates = node.cluster_service.state.templates
    name = req.param("name")
    flat = req.bool_param("flat_settings")

    def render(t):
        t = dict(t)
        if "settings" in t:
            t["settings"] = _render_settings(t["settings"] or {}, flat)
        if t.get("aliases"):
            # AliasMetaData normalizes `routing` into index_routing +
            # search_routing on output
            out = {}
            for a, spec in t["aliases"].items():
                spec = dict(spec or {})
                routing = spec.pop("routing", None)
                if routing is not None:
                    spec.setdefault("index_routing", routing)
                    spec.setdefault("search_routing", routing)
                out[a] = spec
            t["aliases"] = out
        return t

    if name:
        matched = {k: render(v) for k, v in templates.items()
                   if fnmatch.fnmatchcase(k, name)}
        if not matched:
            return 404, {"error": f"index_template [{name}] missing", "status": 404}
        return 200, matched
    return 200, {k: render(v) for k, v in templates.items()}


def _delete_template(node, req):
    return 200, node.delete_template(req.param("name"))


def _head_template(node, req):
    return (200 if req.param("name") in node.cluster_service.state.templates else 404), {}


def _clear_cache(node, req):
    for svc in node.resolve_search_indices(req.param("index", "_all")):
        for shard in svc.shards.values():
            for seg in shard.engine.segments:
                seg.dev_cache.clear()
    return 200, {"_shards": {"total": 0, "successful": 0, "failed": 0}}


# ---------------------------------------------------------------------------
# Cluster admin
# ---------------------------------------------------------------------------


def _cluster_state(node, req):
    return 200, node.cluster_service.state.to_dict()


def _get_cluster_settings(node, req):
    state = node.cluster_service.state
    return 200, {
        "persistent": state.persistent_settings.as_nested_dict(),
        "transient": state.transient_settings.as_nested_dict(),
    }


def _allocation_explain(node, req):
    # corruption markers (ISSUE 16): a quarantined copy is unusable for
    # allocation, so explain surfaces every marked (index, shard) — the
    # operator-visible trail for a RED last-copy corruption
    corrupted = []
    for name, svc in node.indices.items():
        for sid, shard in svc.shards.items():
            for marker in shard.engine.store.corruption_markers():
                corrupted.append({
                    "index": name, "shard": sid,
                    "marker": marker.get("marker", "corrupted"),
                    "site": marker.get("site", "load"),
                    "reason": marker.get("reason", ""),
                })
    out = {
        "note": "single-node cluster: all primaries allocated locally",
        "can_allocate": "yes",
    }
    if corrupted:
        out["can_allocate"] = "no"
        out["note"] = ("corrupted store copies are unusable for "
                       "allocation until re-recovered from a healthy "
                       "copy (docs/RESILIENCE.md \"Data integrity\")")
        out["corrupted_copies"] = corrupted
    return 200, out


def _get_task(node, req):
    task = node.tasks.get(req.param("task_id"))
    return 200, {"completed": False, "task": task.to_dict()}


def _cancel_task(node, req):
    task = node.tasks.cancel(req.param("task_id"))
    return 200, {"nodes": {node.node_id: {"tasks": {task.id_string: task.to_dict()}}}}


def _delete_script(node, req):
    node.get_stored_script(req.param("id"))  # 404 if missing

    def update(state):
        new = state.copy()
        new.stored_scripts.pop(req.param("id"), None)
        return new

    node.cluster_service.submit_state_update_task("delete-script", update)
    return 200, {"acknowledged": True}


def _simulate_pipeline_by_id(node, req):
    body = req.json_body({}) or {}
    body["id"] = req.param("id")
    return 200, node.ingest.simulate(body)


# ---------------------------------------------------------------------------
# cat API
# ---------------------------------------------------------------------------


def _cat_table(req, rows: List[List], headers: List[str]) -> Tuple[int, object]:
    if req.bool_param("help"):
        # RestTable help: one line per column — name | alias | description
        w = max(len(h) for h in headers)
        return 200, "".join(f"{h.ljust(w)} | - | {h}\n" for h in headers)
    # s: sort by column(s), `name` or `name:desc`, comma list
    sort_spec = req.param("s")
    if sort_spec:
        keys = sort_spec if isinstance(sort_spec, list) \
            else str(sort_spec).split(",")
        for key in reversed([k for k in keys if k]):
            name, _, direction = key.partition(":")
            if name not in headers:
                raise IllegalArgumentException(
                    f"Unable to sort by unknown sort key `{name}`")
            i = headers.index(name)

            def sort_key(row, _i=i):
                v = row[_i]
                try:
                    return (0, float(v), "")
                except (TypeError, ValueError):
                    return (1, 0.0, str(v))
            rows = sorted(rows, key=sort_key, reverse=direction == "desc")
    # h: select/reorder columns
    h_spec = req.param("h")
    if h_spec:
        wanted = h_spec if isinstance(h_spec, list) \
            else str(h_spec).split(",")
        wanted = [w for w in wanted if w]
        idx = []
        for name in wanted:
            if name not in headers:
                raise IllegalArgumentException(
                    f"Field [{name}] not found in the cat table")
            idx.append(headers.index(name))
        headers = [headers[i] for i in idx]
        rows = [[row[i] for i in idx] for row in rows]
    if req.param("format") == "json":
        return 200, [dict(zip(headers, row)) for row in rows]
    verbose = req.bool_param("v")
    cols = [[str(c) for c in row] for row in rows]
    if verbose:
        cols = [headers] + cols
    if not cols:
        return 200, ""
    widths = [max(len(r[i]) for r in cols) for i in range(len(headers))]
    lines = [" ".join(c.ljust(w) for c, w in zip(row, widths))
             for row in cols]
    return 200, "\n".join(lines) + "\n"


def _cat_help(node, req):
    paths = sorted({r.pattern for r in node.rest_controller.routes
                    if r.pattern.startswith("/_cat")})
    return 200, "\n".join(f"{p}" for p in paths) + "\n"


def _cat_indices(node, req):
    state = node.cluster_service.state
    rows = []
    names = state.resolve_index_names(req.param("index", "_all"))
    for name in names:
        md = state.indices[name]
        svc = node.indices.get(name)
        health = "green" if md.num_replicas == 0 else "yellow"
        deleted = 0
        store = 0
        if svc is not None:
            for shard in svc.shards.values():
                for seg in shard.engine.segments:
                    deleted += seg.num_docs - seg.live_doc_count
                store += shard.stats()["segments"]["memory_in_bytes"]
        rows.append([
            health, md.state, name, svc.uuid if svc else "-",
            md.num_shards, md.num_replicas,
            svc.num_docs if svc else 0, deleted,
            f"{store}b", f"{store}b",
        ])
    return _cat_table(req, rows, [
        "health", "status", "index", "uuid", "pri", "rep", "docs.count",
        "docs.deleted", "store.size", "pri.store.size",
    ])


def _cat_health(node, req):
    h = node.health()
    if req.param("ts") in ("false", False, "0"):
        rows = [[h["cluster_name"], h["status"], h["number_of_nodes"],
                 h["number_of_data_nodes"], h["active_shards"],
                 h["active_primary_shards"], h["relocating_shards"],
                 h["initializing_shards"], h["unassigned_shards"], 0, "-",
                 f"{h['active_shards_percent_as_number']:.1f}%"]]
        return _cat_table(req, rows, [
            "cluster", "status", "node.total", "node.data", "shards", "pri",
            "relo", "init", "unassign", "pending_tasks",
            "max_task_wait_time", "active_shards_percent"])
    rows = [[int(time.time()), time.strftime("%H:%M:%S"), h["cluster_name"],
             h["status"], h["number_of_nodes"], h["number_of_data_nodes"],
             h["active_shards"], h["active_primary_shards"],
             h["relocating_shards"], h["initializing_shards"],
             h["unassigned_shards"], 0, "-",
             f"{h['active_shards_percent_as_number']:.1f}%"]]
    return _cat_table(req, rows, [
        "epoch", "timestamp", "cluster", "status", "node.total", "node.data",
        "shards", "pri", "relo", "init", "unassign", "pending_tasks",
        "max_task_wait_time", "active_shards_percent",
    ])


def _cat_nodes(node, req):
    rows = [["127.0.0.1", 0, 0, "mdi", "*", node.node_name]]
    return _cat_table(req, rows, ["ip", "heap.percent", "cpu", "node.role",
                                  "master", "name"])


def _cat_shards(node, req):
    state = node.cluster_service.state
    rows = []
    for name in state.resolve_index_names(req.param("index", "_all")):
        svc = node.indices.get(name)
        if svc is None:
            continue
        for sid, shard in svc.shards.items():
            store = shard.stats()["segments"]["memory_in_bytes"]
            # integrity column (ISSUE 16): newest corruption marker name,
            # or "-" for a healthy copy — operators see quarantined
            # copies directly in _cat/shards
            markers = shard.engine.store.corruption_markers()
            integrity = markers[0].get("marker", "corrupted") \
                if markers else "-"
            rows.append([name, sid, "p", shard.state, shard.num_docs,
                         f"{store}b", "127.0.0.1", node.node_name,
                         integrity])
    return _cat_table(req, rows, ["index", "shard", "prirep", "state", "docs",
                                  "store", "ip", "node", "integrity"])


def _cat_staging(node, req):
    """_cat/staging (ISSUE 9 + 20, docs/OBSERVABILITY.md): the
    at-a-glance per-(index, segment/plane, kind) view of the
    device-memory ledger — what is staged in HBM right now, how big,
    how hot, and whether the budget breaker may evict it — plus the
    mesh generation's slot occupancy: per-device free slot capacity
    (``free/dev`` on the generation's scope rows) and per-slot
    tombstone density (``tombs`` on its slot rows), so operators can
    see when the ISSUE-20 background compaction will trigger."""
    from elasticsearch_tpu.common.memory import memory_accountant

    # mesh slot occupancy, keyed by the generation scope the ledger
    # rows carry in their segment column (e.g. "mesh#3")
    scope_meta: dict = {}
    for name in node.cluster_service.state.resolve_index_names("_all"):
        svc = node.indices.get(name)
        ms = getattr(svc, "_mesh_search", None) if svc else None
        stats = ms.staging_slot_stats() if ms is not None else None
        if not stats:
            continue
        scope = ms._executor.scope if ms._executor is not None else None
        if scope is None:
            continue
        scope_meta[(name, scope)] = stats["free_slots_per_device"]
    rows = []
    for row in memory_accountant().table():
        free_dev = scope_meta.get((row["index"], row["segment"]))
        # kind rows under a mesh scope show the generation's headroom;
        # the scope summary columns stay "-" for host-plane
        # (per-segment) rows, which have no slot allocator
        rows.append([
            row["index"], row["segment"], row["kind"],
            f"{row['bytes']}b", row["tables"], row["stage_count"],
            "-" if row["idle_s"] is None else f"{row['idle_s']:.1f}s",
            "*" if row["evictable"] else "-",
            "-" if free_dev is None else f"{free_dev}",
            "-",
        ])
    # one summary row per staged slot (ISSUE 20): slot → segment →
    # live/total docs → tombstone density, the compaction trigger's
    # exact inputs
    for (name, scope), free_dev in sorted(scope_meta.items()):
        svc = node.indices.get(name)
        stats = svc._mesh_search.staging_slot_stats() if svc else None
        if not stats:
            continue
        for s in stats["slots"]:
            rows.append([
                name, f"{scope}/slot{s['slot']}", "slot",
                f"{s['live']}/{s['docs']}d", 1, "-", "-", "-",
                f"{free_dev}", f"{s['tombstone_density']}",
            ])
    return _cat_table(req, rows, [
        "index", "segment", "kind", "bytes", "tables", "stage_count",
        "idle", "evictable", "free_slots_per_dev", "tombstone_density",
    ])


def _cat_count(node, req):
    total = sum(
        node.indices[n].num_docs
        for n in node.cluster_service.state.resolve_index_names(req.param("index", "_all"))
        if n in node.indices
    )
    rows = [[int(time.time()), time.strftime("%H:%M:%S"), total]]
    return _cat_table(req, rows, ["epoch", "timestamp", "count"])


def _cat_aliases(node, req):
    rows = []
    for name, md in node.cluster_service.state.indices.items():
        for alias, spec in md.aliases.items():
            spec = spec or {}
            routing = spec.get("routing")
            rows.append([
                alias, name,
                "*" if spec.get("filter") else "-",
                spec.get("index_routing") or routing or "-",
                spec.get("search_routing") or routing or "-",
            ])
    return _cat_table(req, rows, ["alias", "index", "filter", "routing.index",
                                  "routing.search"])


def _cat_templates(node, req):
    import fnmatch

    pat = req.param("name")
    rows = []
    for name, t in node.cluster_service.state.templates.items():
        if pat and not fnmatch.fnmatchcase(name, pat):
            continue
        rows.append([name, "[" + ", ".join(t.get("index_patterns", [])) + "]",
                     t.get("order", 0), t.get("version", "")])
    return _cat_table(req, rows, ["name", "index_patterns", "order", "version"])


def _cat_master(node, req):
    rows = [[node.node_id, "127.0.0.1", "127.0.0.1", node.node_name]]
    return _cat_table(req, rows, ["id", "host", "ip", "node"])


def _cat_segments(node, req):
    rows = []
    for name, svc in node.indices.items():
        for sid, shard in svc.shards.items():
            for seg in shard.engine.segments:
                st = seg.stats()
                rows.append([name, sid, "p", "127.0.0.1", node.node_id,
                             seg.name, 1, st["num_docs"],
                             st["deleted_docs"], f"{st['memory_in_bytes']}b",
                             f"{st['memory_in_bytes']}b", "true", "true",
                             __version__, "false"])
    return _cat_table(req, rows, ["index", "shard", "prirep", "ip", "id",
                                  "segment", "generation", "docs.count",
                                  "docs.deleted", "size", "size.memory",
                                  "committed", "searchable", "version",
                                  "compound"])


def _cat_tasks(node, req):
    listing = node.tasks.list_tasks()
    rows = []
    for nid, data in listing["nodes"].items():
        for tid, t in data["tasks"].items():
            rows.append([t["action"], tid, "-", t["type"],
                         t["start_time_in_millis"], t["running_time_in_nanos"]])
    return _cat_table(req, rows, ["action", "task_id", "parent_task_id", "type",
                                  "start_time", "running_time"])


def _cat_allocation(node, req):
    n_shards = sum(s.num_shards for s in node.indices.values())
    from elasticsearch_tpu.common.monitor import fs_stats

    fs = fs_stats(node.data_path if node.persistent_path else ".")
    tot = fs.get("total", {})
    total_b = tot.get("total_in_bytes", 0)
    free_b = tot.get("free_in_bytes", 0)
    used_b = max(total_b - free_b, 0)
    rows = [[n_shards, "0b", f"{used_b // (1 << 30)}gb",
             f"{free_b // (1 << 30)}gb", f"{total_b // (1 << 30)}gb",
             int(used_b * 100 / total_b) if total_b else 0,
             "127.0.0.1", "127.0.0.1", node.node_name]]
    return _cat_table(req, rows, ["shards", "disk.indices", "disk.used",
                                  "disk.avail", "disk.total", "disk.percent",
                                  "host", "ip", "node"])


def _cat_recovery(node, req):
    """_cat/recovery (ISSUE 10 satellite): per shard copy, the local
    store recoveries plus live/finished PEER recoveries from the
    multinode recovery sessions (stage init → index → translog →
    finalize → done, file/byte/op progress, source → target) — the
    RecoveryState surface of RestCatRecoveryAction."""
    from elasticsearch_tpu.cluster.multinode import recovery_progress_rows

    def pct(done, total):
        if not total:
            return "100.0%" if done == total else "0.0%"
        return f"{min(done / total, 1.0) * 100:.1f}%"

    rows = []
    for name, svc in node.indices.items():
        for sid, shard in svc.shards.items():
            rows.append([name, sid, "0ms", "store", "done", "-",
                         node.node_name, 0, "100.0%", "0b", "100.0%",
                         0, 0, "100.0%"])
    now_ms = int(time.time() * 1000)
    for r in recovery_progress_rows():
        took_ms = (r["stop_ms"] or now_ms) - (r["start_ms"] or now_ms)
        rows.append([
            r["index"], r["shard"], f"{max(took_ms, 0)}ms", r["type"],
            r["stage"], r["source"] or "-", r["target"],
            r["files_total"],
            pct(r["files_recovered"], r["files_total"]),
            f"{r['bytes_total']}b",
            pct(r["bytes_recovered"], r["bytes_total"]),
            r["ops_total"], r["ops_recovered"],
            pct(r["ops_recovered"], r["ops_total"]),
        ])
    return _cat_table(req, rows, [
        "index", "shard", "time", "type", "stage", "source_node",
        "target_node", "files", "files_percent", "bytes",
        "bytes_percent", "translog_ops", "translog_ops_recovered",
        "translog_ops_percent"])


def _cat_thread_pool(node, req):
    stats = node.thread_pool.stats()
    rows = [[node.node_name, pool, st["active"], st["queue"], st["rejected"]]
            for pool, st in stats.items()]
    return _cat_table(req, rows, ["node_name", "name", "active", "queue", "rejected"])


def _cat_repositories(node, req):
    rows = [[name, body.get("type", "fs")]
            for name, body in node.cluster_service.state.repositories.items()]
    return _cat_table(req, rows, ["id", "type"])


def _cat_snapshots(node, req):
    snaps = node.snapshots.get_snapshot(req.param("repo"))["snapshots"]
    rows = []
    for s in snaps:
        t0 = int(s.get("start_time_in_millis", 0) // 1000)
        t1 = int(s.get("end_time_in_millis", 0) // 1000)
        ns = s.get("shards_total", len(s["indices"]))
        rows.append([s["snapshot"], s["state"], t0,
                     time.strftime("%H:%M:%S", time.gmtime(t0)), t1,
                     time.strftime("%H:%M:%S", time.gmtime(t1)),
                     f"{max(t1 - t0, 0)}s", len(s["indices"]),
                     ns, 0, ns, "-"])
    return _cat_table(req, rows, ["id", "status", "start_epoch", "start_time",
                                  "end_epoch", "end_time", "duration",
                                  "indices", "successful_shards",
                                  "failed_shards", "total_shards", "reason"])
