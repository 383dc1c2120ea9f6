"""The system under test, held by the one process that holds the chip:
device check, native library, compile cache, the node behind its HTTP
server, and the stored base index of a (configuration, seed).
"""

from __future__ import annotations

import http.client
import json
import os
import shutil
import subprocess
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE = os.path.join(ROOT, "benchmark", ".cache")
MAX_STORED_BASES = 8  # per checkout; least recently used go first


class HarnessFailure(Exception):
    """The run cannot give a result: no chip, a failed set-up step."""


def require_tpu(chips: int):
    """The devices JAX reports; fails unless they are ``chips`` TPUs.
    There is no CPU option: a sandbox rehearsal patches this function
    from outside."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise HarnessFailure(
            f"no TPU: jax.devices()[0].platform == {devices[0].platform!r}")
    if len(devices) != chips:
        raise HarnessFailure(
            f"the cell asks for {chips} chips, JAX reports {len(devices)}")
    return devices


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def build_native() -> None:
    """Build the tokenizer library where it is absent: it is not
    committed, and one built for another CPU must not travel."""
    lib = os.path.join(ROOT, "native", "libestpu_native.so")
    if os.path.exists(lib):
        return
    try:
        proc = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                              capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        raise HarnessFailure(f"native build did not run: {e}") from e
    if proc.returncode != 0:
        raise HarnessFailure(
            f"native build failed: {proc.stderr.strip()[-500:]}")


def configure_compile_cache() -> str:
    from elasticsearch_tpu.common import compile_cache

    compile_cache.configure_compile_cache(compile_cache.checkout_cache_dir())
    return compile_cache.compile_cache_path()


class Http:
    """JSON over one keep-alive connection, for set-up and read-back."""

    def __init__(self, port: int):
        self.port = port
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)

    def request(self, method: str, path: str, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        ctype = ("application/x-ndjson" if "_bulk" in path
                 else "application/json")
        self.conn.request(method, path,
                          body=body.encode("utf-8") if body else None,
                          headers={"Content-Type": ctype})
        resp = self.conn.getresponse()
        data = resp.read()
        if resp.status not in (200, 201):
            raise HarnessFailure(
                f"{method} {path} -> HTTP {resp.status}: {data[:400]!r}")
        return json.loads(data)

    def close(self) -> None:
        self.conn.close()


class Served:
    """A ``Node`` on a data path behind its ``HttpServer``."""

    def __init__(self, data_path: str):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.http_server import HttpServer

        self.node = Node(data_path=data_path)
        self.server = HttpServer(self.node, port=0)
        self.server.start()
        self.port = self.server.port
        self.http = Http(self.port)

    def search_stats(self, index: str) -> dict:
        stats = self.http.request("GET", f"/{index}/_stats")
        return stats["indices"][index]["total"]["search"]

    def translog_durabilities(self, index: str) -> list:
        """What each shard's live translog object reports: the index
        setting is read at construction only."""
        svc = self.node.indices[index]
        return [svc.shards[s].engine.translog.durability
                for s in sorted(svc.shards)]

    def close(self) -> None:
        self.http.close()
        self.server.stop()
        self.node.close()


def _flat(settings: dict) -> dict:
    return {"index": {k[len("index."):]: v for k, v in settings.items()}}


def build_base(path: str, config: dict, dataset, n_shards: int, say) -> None:
    """Seeded corpus -> _bulk over HTTP with the configuration's load
    settings -> _refresh -> _forcemerge where a shard holds more than
    ``max_num_segments`` -> _flush -> the deployment's settings restored
    -> close."""
    index, load = config["index"], config["load"]
    served = Served(path)
    try:
        http = served.http
        settings = {"index.number_of_shards": n_shards,
                    "index.number_of_replicas": config["replicas"]}
        settings.update(load["settings"])
        http.request("PUT", f"/{index}", {
            "settings": _flat(settings), "mappings": config["mapping"]})
        t0 = time.monotonic()
        for lo in range(0, dataset.n_docs, load["bulk_docs"]):
            hi = min(lo + load["bulk_docs"], dataset.n_docs)
            resp = http.request("POST", f"/{index}/_bulk",
                                dataset.bulk_body(lo, hi))
            if resp.get("errors") is not False or len(resp["items"]) != hi - lo:
                raise HarnessFailure(
                    f"_bulk reported errors: {json.dumps(resp)[:400]}")
        bulk_s = time.monotonic() - t0
        t0 = time.monotonic()
        http.request("POST", f"/{index}/_refresh")
        segs = http.request("GET", f"/{index}/_segments")
        most = max(len(copy["segments"])
                   for copies in segs["indices"][index]["shards"].values()
                   for copy in copies)
        merged = most > load["max_num_segments"]
        if merged:
            http.request("POST", f"/{index}/_forcemerge?max_num_segments="
                         f"{load['max_num_segments']}")
            http.request("POST", f"/{index}/_refresh")
        http.request("POST", f"/{index}/_flush")
        http.request("PUT", f"/{index}/_settings", _flat(config["settings"]))
        count = http.request("GET", f"/{index}/_stats")[
            "indices"][index]["primaries"]["docs"]["count"]
        if count != dataset.n_docs:
            raise HarnessFailure(
                f"_stats counts {count} docs, {dataset.n_docs} were sent")
        say("build", docs=dataset.n_docs, shards=n_shards,
            bulk_seconds=bulk_s, bulk_docs_per_s=dataset.n_docs / bulk_s,
            segments_per_shard_after_refresh=most, force_merged=merged,
            refresh_merge_flush_seconds=time.monotonic() - t0)
    finally:
        served.close()


def ensure_base(config: dict, dataset, seed: int, chips: int,
                n_shards: int, say) -> str:
    """The stored base index of (configuration, seed, chips): built on
    the first run that asks for it, reopened by every other."""
    path = os.path.join(CACHE, f"{config['name']}-{seed}-{chips}")
    ready = os.path.join(path, "READY")
    if os.path.exists(ready):
        os.utime(ready)
        say("base", path=os.path.relpath(path, ROOT), built=False)
        return path
    os.makedirs(CACHE, exist_ok=True)
    shutil.rmtree(path, ignore_errors=True)  # a build that was cut
    stored = sorted(
        (os.path.getmtime(os.path.join(CACHE, d, "READY")), d)
        for d in os.listdir(CACHE)
        if os.path.exists(os.path.join(CACHE, d, "READY")))
    for _, d in stored[: max(0, len(stored) - MAX_STORED_BASES + 1)]:
        shutil.rmtree(os.path.join(CACHE, d), ignore_errors=True)
    build_base(path, config, dataset, n_shards, say)
    with open(ready, "w", encoding="utf-8") as f:
        f.write("built\n")
    say("base", path=os.path.relpath(path, ROOT), built=True)
    return path
