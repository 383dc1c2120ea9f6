"""REST controller: route registry + dispatch.

Role model: ``RestController`` (core/.../rest/RestController.java:65,
dispatchRequest:168) + ``BaseRestHandler``. Routes use the same
path-template syntax as the reference's handlers; handlers receive
(node, params, body) and return (status, payload). Errors map to status
codes through the exception taxonomy (common/errors.py), serialized in the
reference's {"error": {...}, "status": N} shape.
"""

from __future__ import annotations

import contextvars
import json
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from elasticsearch_tpu.common.errors import (
    ElasticsearchTpuException,
    ParsingException,
)

Handler = Callable[..., Tuple[int, Any]]

# response-header side channel (the deprecation Warning-collector
# pattern): dispatch seeds a mutable dict per request; anything on the
# request path may set a header (Retry-After on 429 rejections —
# docs/OVERLOAD.md); the HTTP front door drains it into the response
_resp_headers_var: "contextvars.ContextVar[Optional[dict]]" = \
    contextvars.ContextVar("estpu_response_headers", default=None)


def begin_response_headers() -> None:
    _resp_headers_var.set({})


def set_response_header(name: str, value: str) -> None:
    headers = _resp_headers_var.get()
    if headers is not None:
        headers[name] = value


def collect_response_headers() -> Dict[str, str]:
    out = dict(_resp_headers_var.get() or {})
    _resp_headers_var.set({})
    return out


def header_value(headers: Optional[Dict[str, str]], name: str,
                 default=None):
    """Case-insensitive lookup in a raw request-header dict (HTTP header
    names are case-insensitive; clients send X-Opaque-Id in any case)."""
    lowered = name.lower()
    for k, v in (headers or {}).items():
        if k.lower() == lowered:
            return v
    return default


class RestRequest:
    def __init__(self, method: str, path: str, params: Dict[str, str],
                 body: Optional[bytes], content_type: Optional[str] = None,
                 headers: Optional[Dict[str, str]] = None):
        self.method = method
        self.path = path
        self.params = params  # query params + path params merged
        self.raw_body = body or b""
        self.content_type = content_type
        self.headers = dict(headers or {})

    def header(self, name: str, default=None):
        """Case-insensitive request-header lookup."""
        return header_value(self.headers, name, default)

    def json_body(self, default=None):
        """Parse the structured request body — despite the historical
        name, JSON/YAML/CBOR all parse here via content negotiation
        (XContentFactory semantics; Content-Type first, sniffing second)."""
        if not self.raw_body.strip():
            return default
        from elasticsearch_tpu.common.xcontent import (
            XContentParseError,
            parse,
        )

        try:
            return parse(self.raw_body, self.content_type)
        except XContentParseError as e:
            raise ParsingException(f"request body is not valid: {e}") from e

    def ndjson_lines(self) -> List[dict]:
        from elasticsearch_tpu.common.xcontent import JsonTextDict

        out = []
        for line in self.raw_body.split(b"\n"):
            line = line.strip()
            if line:
                try:
                    parsed = json.loads(line)
                    if type(parsed) is dict and b"\r" not in line:
                        # (a document keeps the text it was sent as; a
                        # carriage return would not survive a text file)
                        parsed = JsonTextDict(parsed)
                        parsed.text = line.decode("utf-8")
                    out.append(parsed)
                except json.JSONDecodeError as e:
                    raise ParsingException(
                        f"Malformed content, found invalid json line: {e}"
                    ) from e
        return out

    def param(self, name: str, default=None):
        return self.params.get(name, default)

    def bool_param(self, name: str, default=False) -> bool:
        v = self.params.get(name)
        if v is None:
            return default
        return v in ("", "true", True)


class Route:
    _PARAM_RE = re.compile(r"\{(\w+)\}")

    def __init__(self, method: str, pattern: str, handler: Handler):
        self.method = method
        self.pattern = pattern
        self.handler = handler
        regex = "^"
        for part in pattern.strip("/").split("/"):
            m = self._PARAM_RE.fullmatch(part)
            if m:
                if m.group(1) == "index":
                    # index names/aliases cannot start with '_' — keeps API
                    # endpoints from being swallowed by /{index} routes.
                    # `_all` is the one legal underscore expression in
                    # index position (/_all/_refresh etc.)
                    regex += f"/(?P<{m.group(1)}>_all|[^_/][^/]*)"
                else:
                    regex += f"/(?P<{m.group(1)}>[^/]+)"
            else:
                regex += "/" + re.escape(part)
        regex += "$"
        self.regex = re.compile(regex)
        # literal segments score higher for route priority
        self.specificity = sum(
            1 for p in pattern.strip("/").split("/") if not self._PARAM_RE.fullmatch(p)
        )

    def match(self, path: str) -> Optional[Dict[str, str]]:
        m = self.regex.match("/" + path.strip("/"))
        if m is None:
            return None
        return m.groupdict()


_SEARCH_MARKERS = ("_search", "_count", "_msearch", "_explain",
                   "_validate", "_field_caps", "_suggest", "_percolate")
_GET_MARKERS = ("_doc", "_mget", "_source", "_termvectors")


def is_search_path(path: str) -> bool:
    """The front door's test for opening a request tracer: the paths
    of the search family (the routes ``_executor_for`` puts on the
    search pool)."""
    return any(m in path for m in _SEARCH_MARKERS)


def _executor_for(method: str, pattern: str) -> str:
    """Route -> named pool, mirroring the per-action executor choices of
    the reference's transport actions (ThreadPool.Names)."""
    if any(m in pattern for m in _SEARCH_MARKERS):
        return "search"
    if "_bulk" in pattern or "_update" in pattern:
        return "write"
    if any(m in pattern for m in _GET_MARKERS):
        return "get" if method in ("GET", "HEAD") else "write"
    if "{type}/{id}" in pattern or pattern.endswith("/{id}"):
        return "get" if method in ("GET", "HEAD") else "write"
    return "management"


class RestController:
    def __init__(self, node):
        self.node = node
        self.routes: List[Route] = []
        from elasticsearch_tpu.rest import handlers

        handlers.register_all(self)
        # ActionPlugin.getRestHandlers: plugin-provided endpoints
        svc = getattr(node, "plugins_service", None)
        if svc is not None:
            for method, pattern, handler in svc.rest_handlers:
                self.register(method, pattern, handler)

    def register(self, method: str, pattern: str, handler: Handler) -> None:
        self.routes.append(Route(method, pattern, handler))
        self.routes.sort(key=lambda r: -r.specificity)

    def dispatch(self, method: str, path: str, query: Dict[str, str],
                 body: Optional[bytes],
                 content_type: Optional[str] = None,
                 headers: Optional[Dict[str, str]] = None) -> Tuple[int, Any]:
        from urllib.parse import unquote

        from elasticsearch_tpu.common.deprecation import begin_request
        from elasticsearch_tpu.search.telemetry import set_opaque_id

        begin_request()  # per-request Warning-header collector
        begin_response_headers()  # Retry-After etc. (docs/OVERLOAD.md)
        # X-Opaque-Id rides the request context (contextvars copied into
        # the executor thread below): tasks, slowlog lines, and profile
        # output read it back to join work to the client that sent it
        hdrs = headers or {}
        set_opaque_id(header_value(hdrs, "x-opaque-id"))

        path = unquote(path.split("?")[0])
        method_routes = [r for r in self.routes if r.method == method]
        for route in method_routes:
            path_params = route.match(path)
            if path_params is not None:
                params = dict(query)
                params.update(path_params)
                req = RestRequest(method, path, params, body, content_type,
                                  headers=hdrs)
                inflight = None
                reserved = False
                if body and hasattr(self.node, "breaker_service"):
                    # in-flight requests breaker: the buffered request body
                    # counts against memory until the response is built
                    from elasticsearch_tpu.common.breaker import (
                        CircuitBreaker,
                    )
                    inflight = self.node.breaker_service.get_breaker(
                        CircuitBreaker.IN_FLIGHT_REQUESTS)
                try:
                    if inflight is not None:
                        inflight.add_estimate_bytes_and_maybe_break(
                            len(body), "<http_request>")
                        # only a SUCCESSFUL reservation may be released —
                        # a tripped add already rolled itself back, and
                        # releasing it again would drive used negative
                        reserved = True
                    pool = getattr(self.node, "thread_pool", None)
                    if pool is None:
                        return route.handler(self.node, req)
                    # run handler work on the action's named executor; a
                    # full bounded queue rejects with 429 (ThreadPool +
                    # EsRejectedExecutionException semantics). The copied
                    # contextvars context carries the request's
                    # deprecation-warning collector across the thread hop.
                    import contextvars

                    ctx = contextvars.copy_context()
                    return pool.run(
                        _executor_for(method, route.pattern),
                        lambda: ctx.run(route.handler, self.node, req))
                except ElasticsearchTpuException as e:
                    # 429 backpressure contract (docs/OVERLOAD.md): a
                    # rejection carrying a drain-rate-derived
                    # retry_after_s renders it as the Retry-After header
                    # (never in the reference-shaped error body)
                    retry_after = getattr(e, "retry_after_s", None)
                    if retry_after is not None:
                        from elasticsearch_tpu.search.admission import (
                            retry_after_header_value,
                        )

                        set_response_header(
                            "Retry-After",
                            retry_after_header_value(retry_after))
                    return e.status_code, e.to_dict()
                except Exception as e:  # uncaught -> 500, reference behavior
                    return 500, {
                        "error": {"type": type(e).__name__, "reason": str(e)},
                        "status": 500,
                    }
                finally:
                    if reserved:
                        inflight.add_without_breaking(-len(body))
        # path matched under another method -> 405
        for route in self.routes:
            if route.method != method and route.match(path) is not None:
                allowed = sorted({
                    r.method for r in self.routes if r.match(path) is not None
                })
                return 405, {
                    "error": f"Incorrect HTTP method for uri [{path}] and method "
                             f"[{method}], allowed: {allowed}",
                    "status": 405,
                }
        return 400, {
            "error": {
                "type": "illegal_argument_exception",
                "reason": f"no handler found for uri [{path}] and method [{method}]",
            },
            "status": 400,
        }
