"""Phase-attributed query telemetry (ISSUE 8, docs/OBSERVABILITY.md).

On the chip score_tiles runs at 0.137 % of its HBM roofline on
msmarco-serial (ledger, PR 30): the query is not bandwidth-bound, the
cost the kernel's own note names is grid steps (ops/pallas_scoring.py at
DEFAULT_TILE_SUB), not confirmed on this round's chip,
and every remaining tuning lever — packed-codec/pruning default flips,
the ICI serving loop, kNN tile tuning — needs to know WHERE a query's
sub-millisecond budget goes. The reference spends a whole subsystem on
exactly this (SURVEY §2.4: profile API, slowlog, node stats); here the
fast planes are compiled device programs, so the observable unit is the
PHASE around each program, not Lucene's per-scorer counters.

Three pieces:

- ``QueryTracer``: a low-overhead span tracer threaded through one
  REQUEST's execution, from the HTTP socket to the last byte written
  (ISSUE 25). A span is a record: name, start and end in
  ``time.monotonic_ns()``, the index of the span that caused it; the
  tracer carries the request's identifier (one integer per request
  from a process-wide counter). Records live in a preallocated list
  capped at ``MAX_SPANS`` (``spans_dropped`` counts the overflow). The
  fixed phase taxonomy (``PHASES``) keeps its per-phase ACCUMULATORS
  (bounded by the taxonomy size — a thousand-segment shard still
  records at most one accumulator per phase): they feed
  ``profile.phases`` and hold no other name.
  ``start``/``stop`` are two clock reads and one list store — no
  per-posting work, safe to leave always-on in the scoring hot path.
  Leaf spans are also entered as ``jax.profiler.TraceAnnotation`` so a
  device trace shows what the host was doing, on the profiler's clock.
  ``NULL_TRACER`` is the disabled singleton
  (``search.telemetry.enabled`` kill switch, and every request that is
  not a search): every call is a no-op so call sites stay
  unconditional.

- ``SearchTelemetry``: the per-index registry the tracers drain into —
  byte counters (postings/embedding bytes staged/streamed/skipped),
  plane-ladder decision counters with reasons, exported as the
  ``search.phases`` block of ``_stats`` and aggregated into
  ``_nodes/stats``; and per span name ``{count, sum_ns, self_ns}``, the
  ``search.spans`` block.

- the ``X-Opaque-Id`` context: the REST layer stamps the request
  header into a contextvar; the search task, slowlog lines, and profile
  output read it back so a slow query joins to its client.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import threading
import time
from typing import Dict, List, Optional

# Fixed phase taxonomy (docs/OBSERVABILITY.md): the names whose time
# ``profile.phases`` accumulates.
#
#   parse_rewrite  query DSL parse + coordinator rewrites
#   plan_build     per-shard plan / kernel lane-table construction
#   staging        host->device transfer of plan arrays / union tables
#   kernel         device program dispatch -> the launch's one wait,
#                  the copy of the outputs its caller reads (includes
#                  first-call compilation; fused on-device agg
#                  reduction executes inside this span)
#   merge          ICI/host top-k merge + DocRef assembly
#   aggregate      aggregation reduce OUTSIDE the device program: the
#                  host-path agg execution over segment views, the mesh
#                  with_views fallback reduce, and the fused plane's
#                  tiny partial-accumulator finalize (ISSUE 13 — what
#                  fusion removes shows up as this span collapsing)
#   batch_demux    micro-batch member demultiplex / response split
#   fetch          fetch phase (_source, highlight, sort values)
PHASES = ("parse_rewrite", "plan_build", "staging", "kernel", "merge",
          "aggregate", "batch_demux", "fetch")

_now_ns = time.monotonic_ns
_PHASE_SET = frozenset(PHASES)

# one integer per request, process-wide (X-Opaque-Id stays an annotation)
_REQUEST_IDS = itertools.count(1)

# ``jax.profiler.TraceAnnotation`` and the profiler's own "is a session
# running" flag, resolved once. Only a process that has imported JAX can
# run a profiler session, so this module never imports it first.
_ANNOTATION = None
_PROFILING = None


def _profiling() -> bool:
    """True while a ``jax.profiler`` session records. One C call once
    resolved; False where JAX is absent or not yet imported."""
    global _ANNOTATION, _PROFILING
    if _PROFILING is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax.profiler import TraceAnnotation

            _ANNOTATION, _PROFILING = (TraceAnnotation,
                                       TraceAnnotation.is_enabled)
        except (ImportError, AttributeError):  # a JAX without it
            _PROFILING = bool  # bool() is False: never annotates
    return _PROFILING()


# span record fields
_NAME, _START, _END, _PARENT, _ATTRS = range(5)


class QueryTracer:
    """Span tracer for ONE request. Not thread-safe by design — a
    request's spans are written by one thread at a time (the HTTP
    thread hands over to its pool thread and takes over again when that
    is done; the batch leader records into a batch tracer and
    ``merge_from`` folds it into each waiting member's)."""

    MAX_SPANS = 64  # cap of the per-request span list
    __slots__ = ("enabled", "request_id", "sink", "_acc", "_counts",
                 "_spans", "_n", "_open", "_filler", "spans_dropped",
                 "_annotations", "_ann")

    def __init__(self):
        self.enabled = True
        self.request_id = next(_REQUEST_IDS)
        # the SearchTelemetry this request's spans drain into
        # (``finish``): the first index that adopts the tracer
        self.sink = None
        self._acc: Dict[str, int] = {}      # phase -> accumulated ns
        self._counts: Dict[str, int] = {}   # phase -> span count
        # records [name, start_ns, end_ns (0 = open), parent, attrs]
        self._spans: List[Optional[list]] = [None] * self.MAX_SPANS
        self._n = 0
        self._open: List[int] = []          # open parent spans, innermost last
        self._filler = -1                   # the open ``fill`` leaf
        self.spans_dropped = 0
        self._annotations: Dict[str, object] = {}
        self._ann = None                    # the open TraceAnnotation

    # -- hot path ------------------------------------------------------

    def start(self, name: str) -> int:
        """Open a LEAF span (no span starts under it). Returns the token
        ``stop`` takes: the record's index, or minus the start time
        where the list is full."""
        self._annotate(name)
        return self._record(name)

    def _annotate(self, name: str) -> None:
        """Move the profiler's annotation to the leaf ``name``."""
        if self._ann is not None:
            self._exit_annotation()
        if _profiling():
            self._ann = _ANNOTATION("es:" + name, request=self.request_id)
            self._ann.__enter__()

    def start_parent(self, name: str) -> int:
        """Open a span that other spans start under until its ``stop``
        (in a ``finally`` wherever the request goes on after an
        exception: an open parent adopts what follows).
        Not entered in the profiler's trace: leaves tile the request
        there, and a parent would overlap every one of them."""
        tok = self._record(name)
        if tok >= 0:
            self._open.append(tok)
        return tok

    def fill(self, name: str) -> None:
        """Open a leaf that lasts until the next span starts or its
        parent stops: the name of a stretch of code between spans, whose
        end lies wherever the next layer begins (``http.inbound``: from
        the socket to ``search.request``; ``search.route``: from
        admission to the serving plane's first phase)."""
        self._filler = self.start(name)

    def _record(self, name: str, now: int = 0) -> int:
        now = now or _now_ns()
        if self._filler >= 0:
            self._end_filler(now)
        i = self._n
        if i >= self.MAX_SPANS:
            self.spans_dropped += 1
            return -now
        self._n = i + 1
        self._spans[i] = [name, now, 0,
                          self._open[-1] if self._open else -1, None]
        return i

    def _end_filler(self, now: int) -> None:
        rec = self._spans[self._filler]
        rec[_END] = max(now, rec[_START])
        self._filler = -1

    def stop(self, name: str, tok: int) -> None:
        now = _now_ns()
        if self._ann is not None:
            self._exit_annotation()
        self._close(name, tok, now)

    def _close(self, name: str, tok: int, now: int) -> None:
        if self._filler >= 0:
            self._end_filler(now)
        if tok >= 0:
            rec = self._spans[tok]
            rec[_END] = now
            dur = now - rec[_START]
            if self._open and tok in self._open:
                # parents left open beneath it (an exception skipped
                # their stop) end with it
                while True:
                    i = self._open.pop()
                    if i == tok:
                        break
                    self._spans[i][_END] = now
        else:
            dur = now + tok
        if name in _PHASE_SET:
            self._acc[name] = self._acc.get(name, 0) + dur
            self._counts[name] = self._counts.get(name, 0) + 1

    def switch(self, name: str, tok: int, next_name: str) -> int:
        """End one leaf and start the next at one instant, read once the
        profiler's annotation has moved to the next: leaves that tile
        their parent (the lock wait, the dispatch, the device wait), so
        that their durations sum to the stretch they cover."""
        self._annotate(next_name)
        now = _now_ns()
        self._close(name, tok, now)
        return self._record(next_name, now)

    def record(self, name: str, start_ns: int, end_ns: int) -> None:
        """A closed leaf span measured elsewhere (a wait another thread
        timed), cut to start no earlier than its parent."""
        parent = self._open[-1] if self._open else -1
        if parent >= 0:
            start_ns = max(start_ns, self._spans[parent][_START])
        if self._filler >= 0:
            self._end_filler(start_ns)
        if self._n >= self.MAX_SPANS:
            self.spans_dropped += 1
            return
        self._spans[self._n] = [name, start_ns, max(end_ns, start_ns),
                                parent, None]
        self._n += 1

    def mark(self, tok: int, key: str, value) -> None:
        """An attribute on one span (``first_call: true``)."""
        if tok >= 0:
            rec = self._spans[tok]
            if rec[_ATTRS] is None:
                rec[_ATTRS] = {}
            rec[_ATTRS][key] = value

    def _exit_annotation(self) -> None:
        ann, self._ann = self._ann, None
        ann.__exit__(None, None, None)

    # -- annotations ---------------------------------------------------

    def annotate(self, key: str, value) -> None:
        self._annotations[key] = value

    def merge_from(self, other: "QueryTracer") -> None:
        """Fold a shared (batch) tracer's accumulators and spans into
        this one — every member of a batched launch is attributed the
        launch's phase durations (they all waited on it). The spans keep
        their own times and hang under this tracer's open parent."""
        for phase, ns in other._acc.items():
            self._acc[phase] = self._acc.get(phase, 0) + ns
            self._counts[phase] = (self._counts.get(phase, 0)
                                   + other._counts.get(phase, 1))
        self._annotations.update(other._annotations)
        under = self._open[-1] if self._open else -1
        moved: Dict[int, int] = {}
        for i in range(other._n):
            rec = other._spans[i]
            if rec[_END] == 0:
                continue
            if self._n >= self.MAX_SPANS:
                self.spans_dropped += 1
                continue
            moved[i] = self._n
            self._spans[self._n] = [rec[_NAME], rec[_START], rec[_END],
                                    moved.get(rec[_PARENT], under),
                                    rec[_ATTRS]]
            self._n += 1
        self.spans_dropped += other.spans_dropped

    def finish(self) -> None:
        """The request is over: drain its spans into the index that
        served it (``_stats`` ``search.spans``). The owner of the
        tracer calls it once, after its root span has closed."""
        if self._ann is not None:
            self._exit_annotation()
        if self.sink is not None:
            self.sink.record_spans(self)

    # -- output --------------------------------------------------------

    def spans(self) -> List[dict]:
        """Per-phase accumulated spans in taxonomy order (the profile
        output's ``phases`` array)."""
        out = []
        for phase in PHASES:
            if phase in self._acc:
                out.append({"phase": phase,
                            "time_in_nanos": int(self._acc[phase]),
                            "count": int(self._counts.get(phase, 1))})
        return out

    def closed_spans(self, now: int = 0):
        """[(name, start, end, parent, self_ns, attrs, index)] of the
        spans that have ended (and, where ``now`` is given, of the
        parents still open, ending now), ``self_ns`` being the duration
        less the part its children cover."""
        spans = self._spans
        covered = [0] * self._n
        ends = [0] * self._n
        for i in range(self._n):
            rec = spans[i]
            end = rec[_END] or (now if i in self._open else 0)
            ends[i] = end
            if end and rec[_PARENT] >= 0:
                covered[rec[_PARENT]] += end - rec[_START]
        return [(spans[i][_NAME], spans[i][_START], ends[i],
                 spans[i][_PARENT],
                 max(ends[i] - spans[i][_START] - covered[i], 0),
                 spans[i][_ATTRS], i)
                for i in range(self._n) if ends[i]]

    def span_tree(self) -> List[dict]:
        """This request's spans so far (the profile output's ``spans``
        array): what has ended, and the parents still open."""
        rows = self.closed_spans(_now_ns())
        if not rows:
            return []
        root = min(r[1] for r in rows)
        out = []
        for name, start, end, parent, _self_ns, attrs, i in rows:
            span = {"id": i, "parent": parent if parent >= 0 else None,
                    "name": name, "start_offset_nanos": start - root,
                    "time_in_nanos": end - start}
            if self._spans[i][_END] == 0:
                span["open"] = True
            if attrs:
                span.update(attrs)
            out.append(span)
        return out

    def annotations(self) -> dict:
        out = dict(self._annotations)
        if self.spans_dropped:
            out["spans_dropped"] = self.spans_dropped
        return out

    def launch_count(self) -> int:
        """The mesh launches this request made: its ``kernel.dispatch``
        spans that carry a ``launch`` id (``plan_exec._launch_locked``)
        and ended, the program dispatched, whether or not its outputs
        then reached the host."""
        return sum(1 for i in range(self._n)
                   if self._spans[i][_NAME] == "kernel.dispatch"
                   and self._spans[i][_END]
                   and "launch" in (self._spans[i][_ATTRS] or ()))

    def launches(self) -> List[list]:
        """``[launch, dispatch_start_ns, wait_start_ns, wait_end_ns]``
        of every mesh launch this request waited on, in
        ``time.monotonic_ns()``: a ``kernel.dispatch`` span that carries
        the ``launch`` attribute (``plan_exec._launch_locked``) and the
        ``kernel.device_wait`` that follows it under the same parent.
        The members of a batched launch share its id."""
        out, spans, pending = [], self._spans, {}
        for i in range(self._n):
            name, start, end, parent, attrs = spans[i]
            if name == "kernel.dispatch" and attrs and "launch" in attrs:
                pending[parent] = (attrs["launch"], start)
            elif name == "kernel.device_wait" and end and parent in pending:
                launch, dispatch_start = pending.pop(parent)
                out.append([launch, dispatch_start, start, end])
        return out

    def top_phases(self, n: int = 3) -> str:
        """``kernel.device_wait:0.52ms, staging:0.11ms, fetch:0.03ms`` —
        the slowlog enrichment string: the leaves that took longest
        (the phases where no span was kept)."""
        parents = {r[_PARENT] for r in self._spans[:self._n]}
        by_name: Dict[str, int] = {}
        for i in range(self._n):
            rec = self._spans[i]
            if rec[_END] and i not in parents:
                by_name[rec[_NAME]] = (by_name.get(rec[_NAME], 0)
                                       + rec[_END] - rec[_START])
        items = sorted((by_name or self._acc).items(),
                       key=lambda kv: -kv[1])[:n]
        return ", ".join(f"{p}:{ns / 1e6:.2f}ms" for p, ns in items)


class _NullTracer:
    """Disabled tracer: every method a no-op, shared singleton."""

    __slots__ = ()
    enabled = False
    request_id = 0
    sink = None
    spans_dropped = 0
    _acc: Dict[str, int] = {}
    _annotations: Dict[str, object] = {}

    def start(self, name: str) -> int:
        return 0

    start_parent = start

    def stop(self, name: str, tok: int) -> None:
        pass

    def switch(self, name: str, tok: int, next_name: str) -> int:
        return 0

    def fill(self, name: str) -> None:
        pass

    def record(self, name: str, start_ns: int, end_ns: int) -> None:
        pass

    def mark(self, tok: int, key: str, value) -> None:
        pass

    def annotate(self, key: str, value) -> None:
        pass

    def merge_from(self, other) -> None:
        pass

    def finish(self) -> None:
        pass

    def spans(self) -> List[dict]:
        return []

    def closed_spans(self, now: int = 0) -> list:
        return []

    def span_tree(self) -> List[dict]:
        return []

    def annotations(self) -> dict:
        return {}

    def launch_count(self) -> int:
        return 0

    def launches(self) -> list:
        return []

    def top_phases(self, n: int = 3) -> str:
        return ""


NULL_TRACER = _NullTracer()


class SearchTelemetry:
    """Per-index phase-telemetry registry (thread-safe counters).

    Exported as the ``search.phases`` block of ``_stats`` and merged
    across indices into the ``_nodes/stats`` search section."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {}
        self.decisions: Dict[str, int] = {}
        self.queries_recorded = 0
        # span name -> [count, sum_ns, self_ns]
        self._span_stats: Dict[str, List[int]] = {}

    def tracer(self, enabled: bool = True):
        return QueryTracer() if enabled else NULL_TRACER

    def record_query(self, tracer) -> None:
        """Count one finished query (launch-level byte/tile totals arrive
        separately via ``add_counters`` — once per launch, never per
        member; its spans via ``record_spans``)."""
        if not getattr(tracer, "enabled", False):
            return
        with self._lock:
            self.queries_recorded += 1

    def record_spans(self, tracer) -> None:
        """Fold one finished request's span records into the per-name
        totals (``tracer.finish``: once per request, after its root
        span has closed)."""
        rows = tracer.closed_spans()
        with self._lock:
            for name, start, end, _parent, self_ns, _attrs, _i in rows:
                st = self._span_stats.get(name)
                if st is None:
                    st = self._span_stats[name] = [0, 0, 0]
                st[0] += 1
                st[1] += end - start
                st[2] += self_ns

    def spans_dict(self) -> dict:
        """The ``search.spans`` block of ``_stats``: exact integers, so
        two readings subtract to a window's totals."""
        with self._lock:
            return {name: {"count": st[0], "sum_ns": st[1],
                           "self_ns": st[2]}
                    for name, st in sorted(self._span_stats.items())}

    def add_counters(self, mapping: Dict[str, int]) -> None:
        """Fold LAUNCH-level totals (bytes streamed/skipped, tiles) in
        exactly once — a batched launch must not multiply its byte
        counters by the number of members sharing it."""
        with self._lock:
            for key, n in mapping.items():
                total = key if key.endswith("_total") else key + "_total"
                self.counters[total] = self.counters.get(total, 0) + int(n)

    def note_decision(self, plane: str, reason: str, n: int = 1) -> None:
        """Plane-ladder decision counter: which plane a query landed on
        (or was turned away from) and WHY — ``mesh_pallas.served``,
        ``mesh_pallas.quarantined``, ``host.unsupported_body``, ...

        Units are PER QUERY: a batched launch's decision counts once per
        member (``n`` = batch size), so batch-path and serial-path counts
        stay comparable. A query descending the ladder may record more
        than one decision (``shape_mismatch`` then ``served``), so
        decision totals are not a partition of ``queries_recorded``."""
        key = f"{plane}.{reason}"
        with self._lock:
            self.decisions[key] = self.decisions.get(key, 0) + int(n)

    def phases_dict(self) -> dict:
        with self._lock:
            return {
                "taxonomy": list(PHASES),
                "queries_recorded": self.queries_recorded,
                "counters": dict(self.counters),
                "decisions": dict(sorted(self.decisions.items())),
            }


def merge_phase_stats(blocks: List[dict]) -> dict:
    """Merge per-index ``search`` stats blocks into one node-level block
    (counters sum; scalars sum; lists concatenate except the
    shared taxonomy; strings keep the first non-null value)."""

    def merge(a, b):
        if isinstance(a, dict) and isinstance(b, dict):
            out = dict(a)
            for k, v in b.items():
                out[k] = merge(out[k], v) if k in out else v
            return out
        if isinstance(a, bool) or isinstance(b, bool):
            return a or b
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            return a + b
        if isinstance(a, list) and isinstance(b, list):
            return a if a == b else a + b
        return a if a is not None else b

    out: dict = {}
    for block in blocks:
        out = merge(out, block) if out else dict(block)
    return out


# ---------------------------------------------------------------------------
# X-Opaque-Id request context (Task headers / slowlog / profile join key)
# ---------------------------------------------------------------------------

_OPAQUE_ID: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "es_tpu_x_opaque_id", default=None)


def set_opaque_id(value: Optional[str]) -> None:
    _OPAQUE_ID.set(value if value else None)


def get_opaque_id() -> Optional[str]:
    return _OPAQUE_ID.get()


# The request's tracer rides the same context: the HTTP front door opens
# it at the socket, the controller's copied context carries it across
# the thread-pool hop, IndexService.search adopts it.
_TRACER: contextvars.ContextVar = contextvars.ContextVar(
    "es_tpu_request_tracer", default=NULL_TRACER)


def set_request_tracer(tracer):
    """Returns the token ``reset_request_tracer`` takes."""
    return _TRACER.set(tracer)


def reset_request_tracer(token) -> None:
    _TRACER.reset(token)


def request_tracer():
    """The tracer the front door opened for this request, or
    ``NULL_TRACER`` where none was (direct callers, non-search
    requests)."""
    return _TRACER.get()


@contextlib.contextmanager
def scoped_opaque_id(value: Optional[str]):
    """Stamp a MEMBER's X-Opaque-Id for the duration of the block and
    restore the previous (leader's) id on every exit path — the safe
    idiom for batch leaders building member results on their own
    thread. The contract-lint thread-local-hygiene pass flags bare
    ``set_opaque_id`` member stamps whose early returns skip the
    restore (the PR-9 stale-contextvar bug class); prefer this."""
    prev = _OPAQUE_ID.get()
    _OPAQUE_ID.set(value if value else None)
    try:
        yield
    finally:
        _OPAQUE_ID.set(prev)
