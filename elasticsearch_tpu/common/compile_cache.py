"""Persistent compilation cache + AOT program-variant warming (ISSUE 14).

Production rollout means nodes restart constantly — and today every
restart pays a 2–27 s first-compile stall per program variant (geometry
× q_batch × codec/pruning/sel × knn × agg) before the fast plane serves
again (ROADMAP item 4). This module makes restart a non-event for the
compile plane:

- **persistent compilation cache** — ``configure_compile_cache(path)``
  enables JAX's on-disk executable cache (``search.compile.cache_path``)
  so a restarted process deserializes XLA executables instead of
  recompiling them;
- **variant registry** — every compiled mesh-program variant records a
  stable key (and, per index, a replayable warm spec) into a JSON file
  persisted beside the store, so the NEXT process knows the whole
  variant lattice before the first query arrives;
- **AOT warming** — on node start / index open / post-failover
  promotion, the recorded lattice is replayed in the background under
  :func:`warming` so first-call stalls (cache deserialization included)
  are absorbed OFF the query path;
- **telemetry** — ``compile_cache_{hit,miss}_total``,
  ``programs_warmed_total``, ``query_path_first_compile_total`` and a
  log2-ms first-compile-stall histogram, exported as the ``compile``
  block of ``_stats`` / ``_nodes/stats`` (docs/OBSERVABILITY.md).

Accounting semantics: a variant's FIRST invocation in a process is its
compile (or persistent-cache deserialization). It counts as a *hit*
when the variant key was already in the registry persisted by a prior
process AND the persistent cache is enabled (the executable should be
on disk); otherwise a *miss* (a full XLA compile). Independently it
counts as *warmed* when it ran under the warming context, else as a
query-path first compile — the number a warmed rolling restart must
hold at zero (the ChaosSoak rolling-restart phase asserts exactly
that).
"""

from __future__ import annotations

import contextvars
import hashlib
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

# log2-ish ms buckets for the first-compile stall histogram; the le_*
# naming matches the telemetry histograms (bucket labels are skipped by
# the observability lint, the block keys themselves are documented)
_STALL_BUCKETS_MS = (1.0, 8.0, 64.0, 512.0, 4096.0, 32768.0)
_EVENT_RING = 64

# warming context: first compiles under it are the warmer's, not the
# query path's (the contextvar survives same-thread nested calls)
_WARMING: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "es_tpu_compile_warming", default=False)

# JAX's own variable: where it is set, the cache is placed from outside
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CACHE_PATH: Optional[str] = None


def in_warming() -> bool:
    return _WARMING.get()


@contextmanager
def warming():
    """Mark first compiles in this context as background warming (they
    count into ``programs_warmed_total``, never into
    ``query_path_first_compile_total``)."""
    token = _WARMING.set(True)
    try:
        yield
    finally:
        _WARMING.reset(token)


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: where the entry scripts that run on
    the chip (``chip_smoke.py``, ``benchmark/run.py``) keep the cache when
    ``JAX_COMPILATION_CACHE_DIR`` does not place it. Fixed, because the
    path is part of the cache key: a directory that moves never hits."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def configure_compile_cache(path: Optional[str]) -> bool:
    """Enable JAX's persistent compilation cache at ``path``
    (``search.compile.cache_path``); ``None`` turns it off. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: JAX reads the variable itself, ``path`` yields to it and
    no directory is set in code. Thresholds are dropped to zero so
    every mesh program caches — the 2–27 s stalls this kills are
    exactly the big-program compiles. Returns whether the cache is
    on."""
    global _CACHE_PATH
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    placed = os.environ.get(CACHE_DIR_ENV)
    if not placed:
        if path:
            os.makedirs(path, exist_ok=True)
        if (path or None) != jax.config.jax_compilation_cache_dir:
            jax.config.update("jax_compilation_cache_dir", path or None)
            # JAX opens its cache once; a new directory needs a reset
            compilation_cache.reset_cache()
        _CACHE_PATH = path or None
    if compile_cache_enabled():
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compile_cache_enabled()


def compile_cache_enabled() -> bool:
    return compile_cache_path() is not None


def compile_cache_path() -> Optional[str]:
    return os.environ.get(CACHE_DIR_ENV) or _CACHE_PATH


def variant_key(family: str, *parts) -> str:
    """Stable cross-process key for one compiled program variant: the
    family plus a digest of its shape-defining parts (the same strings
    the lru_cache keys are built from are deterministic across
    processes)."""
    digest = hashlib.sha1(
        "|".join(str(p) for p in parts).encode("utf-8")).hexdigest()[:16]
    return f"{family}:{digest}"


class VariantRegistry:
    """The persisted program-variant lattice: every compiled variant's
    key, plus per-index replayable warm specs (the query shapes that
    compiled them). ``path=None`` keeps it in-memory (tests, nodes
    without a data path)."""

    MAX_WARM_PER_INDEX = 64
    MAX_PROGRAMS = 1024

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self.programs: set = set()
        # warm specs: {index: {dedup_key: spec}}
        self.warm: Dict[str, Dict[str, dict]] = {}
        if path and os.path.exists(path):
            try:
                with open(path, encoding="utf-8") as f:
                    data = json.load(f)
                self.programs = set(data.get("programs") or [])
                self.warm = {
                    idx: dict(entries)
                    for idx, entries in (data.get("warm") or {}).items()}
            except (OSError, json.JSONDecodeError, TypeError):
                pass  # a corrupt registry warms nothing; it rebuilds
        # hit/miss baseline: what a PRIOR process had compiled (and the
        # persistent cache should therefore serve from disk)
        self._preexisting = frozenset(self.programs)

    def program_known(self, key: str) -> bool:
        return key in self._preexisting

    def record_program(self, key: str) -> None:
        with self._lock:
            if key in self.programs:
                return
            if len(self.programs) >= self.MAX_PROGRAMS:
                return  # runaway-variant backstop; warming stays bounded
            self.programs.add(key)
            self._persist_locked()

    def has_warm(self, index: str, dedup_key: str) -> bool:
        """Lock-free membership probe for the query hot path: dict
        reads are atomic, and a rare stale False only costs one
        record_warm call that dedups under the lock anyway."""
        entries = self.warm.get(index)
        return entries is not None and dedup_key in entries

    def record_warm(self, index: str, dedup_key: str, spec: dict) -> None:
        with self._lock:
            entries = self.warm.setdefault(index, {})
            if dedup_key in entries:
                return
            if len(entries) >= self.MAX_WARM_PER_INDEX:
                return
            entries[dedup_key] = spec
            self._persist_locked()

    def warm_entries(self, index: str) -> List[dict]:
        with self._lock:
            return [dict(s) for s in self.warm.get(index, {}).values()]

    def indices(self) -> List[str]:
        with self._lock:
            return sorted(self.warm)

    def forget_index(self, index: str) -> None:
        with self._lock:
            if self.warm.pop(index, None) is not None:
                self._persist_locked()

    def _persist_locked(self) -> None:
        if not self.path:
            return
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"programs": sorted(self.programs),
                           "warm": self.warm}, f)
            os.replace(tmp, self.path)
        except OSError:
            pass  # registry persistence is best-effort; warming degrades


_REGISTRY = VariantRegistry(None)
_REGISTRY_LOCK = threading.Lock()


def variant_registry() -> VariantRegistry:
    return _REGISTRY


def set_variant_registry(registry: VariantRegistry) -> VariantRegistry:
    """Install the node's persisted registry (last constructed node
    wins — one registry per process)."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        _REGISTRY = registry
    return registry


class CompileCacheStats:
    """Process-global compile-plane telemetry — the ``compile`` block of
    ``_stats``/``_nodes/stats`` (docs/OBSERVABILITY.md)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compile_cache_hit_total = 0
        self.compile_cache_miss_total = 0
        self.programs_warmed_total = 0
        self.query_path_first_compile_total = 0
        self._stall_hist = {f"le_{int(b)}": 0 for b in _STALL_BUCKETS_MS}
        self._stall_hist["le_inf"] = 0
        self._events: deque = deque(maxlen=_EVENT_RING)

    def record_first_call(self, family: str, variant: str, seconds: float,
                          warmed: bool, cache_hit: bool) -> None:
        ms = seconds * 1000.0
        with self._lock:
            if cache_hit:
                self.compile_cache_hit_total += 1
            else:
                self.compile_cache_miss_total += 1
            if warmed:
                self.programs_warmed_total += 1
            else:
                self.query_path_first_compile_total += 1
            for bound in _STALL_BUCKETS_MS:
                if ms <= bound:
                    self._stall_hist[f"le_{int(bound)}"] += 1
                    break
            else:
                self._stall_hist["le_inf"] += 1
            self._events.append({
                "family": family, "variant": variant,
                "stall_ms": round(ms, 3), "warmed": bool(warmed),
                "cache_hit": bool(cache_hit),
                "ts_ms": int(time.time() * 1000),
            })

    def stats(self) -> dict:
        with self._lock:
            return {
                "cache_enabled": compile_cache_enabled(),
                "cache_path": _CACHE_PATH,
                "variants_recorded": len(variant_registry().programs),
                "compile_cache_hit_total": self.compile_cache_hit_total,
                "compile_cache_miss_total": self.compile_cache_miss_total,
                "programs_warmed_total": self.programs_warmed_total,
                "query_path_first_compile_total":
                    self.query_path_first_compile_total,
                "first_compile_stall_ms": dict(self._stall_hist),
                "first_compile_events": list(self._events),
            }

    def reset_for_tests(self) -> None:
        with self._lock:
            self.compile_cache_hit_total = 0
            self.compile_cache_miss_total = 0
            self.programs_warmed_total = 0
            self.query_path_first_compile_total = 0
            for k in self._stall_hist:
                self._stall_hist[k] = 0
            self._events.clear()


_STATS = CompileCacheStats()


def compile_stats() -> CompileCacheStats:
    return _STATS


def instrument_program(run, family: str, key: str):
    """Wrap one compiled-program entry (an lru_cache'd jitted function):
    its FIRST invocation is the XLA compile / persistent-cache
    deserialization — time it, classify it hit/miss + warmed/query-path,
    and record the variant key in the registry. Later calls go straight
    through (one flag check)."""
    state = {"done": False}
    lock = threading.Lock()

    def wrapped(*args, **kwargs):
        if state["done"]:
            return run(*args, **kwargs)
        with lock:  # serialize racers onto ONE timed compile
            if state["done"]:
                return run(*args, **kwargs)
            t0 = time.perf_counter()
            out = run(*args, **kwargs)
            dt = time.perf_counter() - t0
            registry = variant_registry()
            known = registry.program_known(key)
            registry.record_program(key)
            _STATS.record_first_call(
                family, key, dt, warmed=in_warming(),
                cache_hit=known and compile_cache_enabled())
            state["done"] = True
            return out

    wrapped.__wrapped__ = run
    wrapped.variant_key = key
    # the launch site marks its kernel.dispatch span first_call with it
    wrapped.first_call_pending = lambda: not state["done"]
    return wrapped


def body_skeleton(body: dict) -> str:
    """Shape signature of a query body: the warm-spec dedup key — two
    bodies produce the same skeleton exactly when they compile the same
    program variant. Keys and SHAPE-relevant values survive (numbers:
    size/from/k/window are compile-time shapes; strings reduce to their
    token count: a 2-term match compiles a different plan than a 1-term
    one); free-text VALUES are dropped, so a hot query template records
    once, not once per term."""

    def walk(obj):
        if isinstance(obj, dict):
            return {k: walk(v) for k, v in sorted(obj.items())}
        if isinstance(obj, list):
            return [len(obj)] + [walk(v) for v in obj[:4]]
        if isinstance(obj, bool):
            return "b"
        if isinstance(obj, (int, float)):
            return obj
        if isinstance(obj, str):
            return f"s{len(obj.split())}"
        return "x"

    return json.dumps(walk(body), separators=(",", ":"))
