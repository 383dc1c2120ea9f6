"""HTTP server: the port-9200 front door.

Role model: ``Netty4HttpServerTransport`` (modules/transport-netty4/).
The reference's event-loop server maps to a threading HTTP server here —
the HTTP layer is control-plane I/O, never the perf path (queries spend
their time in compiled TPU programs; SURVEY.md §7.1). Content negotiation:
JSON bodies in/out; cat API emits text/plain.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qsl, urlparse

from elasticsearch_tpu.rest.controller import (
    RestController,
    is_search_path,
)
from elasticsearch_tpu.search.telemetry import (
    NULL_TRACER,
    QueryTracer,
    reset_request_tracer,
    set_request_tracer,
)


class _Handler(BaseHTTPRequestHandler):
    controller: RestController = None  # set by serve()
    protocol_version = "HTTP/1.1"

    def _handle(self, method: str) -> None:
        """One request, request line parsed to response written. A
        search carries one span tree all the way (search/telemetry.py):
        the root opens here, the controller's copied context takes the
        tracer across the thread-pool hop, IndexService.search adopts
        it, and it is drained after the last byte. Every other request
        carries NULL_TRACER and pays nothing."""
        parsed = urlparse(self.path)
        tracer = (QueryTracer() if is_search_path(parsed.path)
                  else NULL_TRACER)
        t_root = tracer.start_parent("http.request")
        # body read, route match, pool hop, body decode, the node's
        # resolving of the index: up to where search.request begins
        tracer.fill("http.inbound")
        ctx_token = set_request_tracer(tracer)
        try:
            self._serve(method, parsed, tracer)
        finally:
            reset_request_tracer(ctx_token)
            tracer.stop("http.request", t_root)
            tracer.finish()

    def _serve(self, method: str, parsed, tracer) -> None:
        query = dict(parse_qsl(parsed.query, keep_blank_values=True))
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length) if length else b""
        status, payload = self.controller.dispatch(
            method, parsed.path, query, body,
            content_type=self.headers.get("Content-Type"),
            headers=dict(self.headers.items()))
        t_out = tracer.start("http.outbound")
        from elasticsearch_tpu.common.deprecation import (
            collect_warnings,
            warning_header_value,
        )

        warnings = collect_warnings()
        if isinstance(payload, str):
            data = payload.encode("utf-8")
            ctype = "text/plain; charset=UTF-8"
        else:
            from elasticsearch_tpu.common.xcontent import (
                response_format,
                serialize,
            )

            fmt = response_format(query, self.headers.get("Accept"))
            data, ctype = serialize(payload, fmt, pretty="pretty" in query)
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        # echo the client's correlation id back (reference behavior:
        # X-Opaque-Id is a passthrough header — docs/OBSERVABILITY.md)
        opaque = self.headers.get("X-Opaque-Id")
        if opaque:
            self.send_header("X-Opaque-Id", opaque)
        # dispatch-collected response headers (rest/controller.py):
        # Retry-After on 429 rejections (docs/OVERLOAD.md)
        from elasticsearch_tpu.rest.controller import (
            collect_response_headers,
        )

        for name, value in collect_response_headers().items():
            self.send_header(name, value)
        for w in warnings:
            self.send_header("Warning", warning_header_value(w))
        self.end_headers()
        if method != "HEAD":
            self.wfile.write(data)
        tracer.stop("http.outbound", t_out)

    def do_GET(self):
        self._handle("GET")

    def do_POST(self):
        self._handle("POST")

    def do_PUT(self):
        self._handle("PUT")

    def do_DELETE(self):
        self._handle("DELETE")

    def do_HEAD(self):
        self._handle("HEAD")

    def log_message(self, fmt, *args):  # quiet by default
        pass


class HttpServer:
    def __init__(self, node, host: str = "127.0.0.1", port: int = 9200):
        self.node = node
        self.controller = RestController(node)
        node.rest_controller = self.controller
        handler = type("BoundHandler", (_Handler,), {"controller": self.controller})
        self.server = ThreadingHTTPServer((host, port), handler)
        self.port = self.server.server_address[1]
        # the sniffer reads this from /_nodes/http (publish_address).
        # Wildcard binds fall back to loopback: hostname resolution can
        # yield 127.0.1.1 (Debian /etc/hosts) or stale-DNS addresses the
        # machine doesn't own, which would poison a sniffing client's
        # host list; multi-host deployments should bind a concrete
        # address (http.publish_host in the reference)
        publish_host = host if host not in ("", "0.0.0.0", "::") \
            else "127.0.0.1"
        node.http_publish_address = f"{publish_host}:{self.port}"
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self.server.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()
