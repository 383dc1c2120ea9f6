"""The production mesh data plane: ANY query plan over a device mesh.

Round-1's `parallel/distributed.py` proved the collectives pattern on one
hardcoded disjunction kernel; this module generalizes it to the full query
DSL. The per-shard plans built by ``QueryBuilder.to_plan`` (identical tree
structure, shard-local arrays) are STACKED — every plan array padded to a
common shape with a leading ``[n_devices]`` axis — and the template plan's
``emit`` is traced ONCE inside ``shard_map``. The result is one compiled
XLA program executing the whole scatter-gather:

  per-device:  plan.emit -> (scores, matched) over the local shard
               -> local lax.top_k
  collective:  all_gather(top-k) over ICI -> global top-k on every device
               (the TopDocs.merge analog,
               action/search/SearchPhaseController.java:408)
               psum(total_hits) (+ psum'd agg partials, aggs_mesh.py)

Per-array padding semantics come from ``PlanNode.pad_kinds`` — padded
lanes either carry ``valid=False`` masks or scatter onto the stacked
sentinel doc (``nd1-1``), which ``live1`` kills.

Reference: the RPC fan-out this replaces is
action/search/AbstractSearchAsyncAction.java + SearchTransportService
("indices:data/read/search[phase/query]"), per SURVEY.md §5.7/§5.8.
"""

from __future__ import annotations

import functools
import itertools
import logging
import threading
import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from elasticsearch_tpu.search.plan import EmitCtx, PlanNode
from elasticsearch_tpu.search.telemetry import NULL_TRACER


class PlanStructureMismatch(Exception):
    """Per-shard plans for the same query diverged structurally (e.g. a
    field exists on one shard only with a different similarity) — the
    caller falls back to the host-merge path."""


from elasticsearch_tpu.common.staging import StagingBail  # noqa: E402


class _KnnStructuralError(StagingBail):
    """A dense_vector field cannot stage on this segment set (dims
    mismatch vs the mapping): permanent structural inability, never a
    device fault — ensure_knn pins the field to the host rung."""


class _DeltaIneligible(StagingBail):
    """A delta staging attempt hit a structural surprise the cheap
    eligibility pre-check could not see (ISSUE 20): not a device fault —
    run_staged re-raises it untouched (StagingBail contract) and
    IndexMeshSearch falls back to the full geometry rebuild."""


_plane_logger = logging.getLogger("elasticsearch_tpu.parallel.plane")

# Two mesh programs in flight at once interleave their collective
# rendezvous on the multi-device CPU backend (all_gather participants
# from different run_ids wait on each other — observed as a hang when
# concurrent REST threads each launch a shard_map program). A single
# chip executes programs serially anyway, so mesh-program EXECUTION is
# serialized process-wide, which makes concurrent search traffic safe
# everywhere. What that costs on TPU is not measured until a concurrent
# cell reads ``kernel.lock_wait`` (the lock is held through completion,
# so a second query cannot even enqueue). Staging stays unlocked.
_MESH_EXEC_LOCK = threading.Lock()


def _launch_locked(tracer, run, *args):
    """One mesh program under ``_MESH_EXEC_LOCK``, the one launch site
    of all five programs: the ``kernel`` span and what it is made of —
    ``kernel.lock_wait`` (asking for the lock to holding it),
    ``kernel.dispatch`` (the jitted call until it returns: enqueue, and
    trace + compile on a first call, marked ``first_call``) and
    ``kernel.device_wait`` (``block_until_ready``). Dispatch is async:
    the collectives execute after ``run`` returns, so completion must
    happen INSIDE the lock (callers fetch the results at once anyway).
    (Asking for the host copies here, ``copy_to_host_async`` before the
    wait, was measured and left out: 0.11 ms of 8.0, PERF.md 6 PR 30.)"""
    t_kernel = tracer.start_parent("kernel")
    try:
        first_call = getattr(run, "first_call_pending", bool)()
        t = tracer.start("kernel.lock_wait")
        with _MESH_EXEC_LOCK:
            t = t_dispatch = tracer.switch("kernel.lock_wait", t,
                                           "kernel.dispatch")
            outs = run(*args)
            t = tracer.switch("kernel.dispatch", t, "kernel.device_wait")
            jax.block_until_ready(outs)
        tracer.stop("kernel.device_wait", t)
        if first_call:
            tracer.mark(t_dispatch, "first_call", True)
    finally:
        tracer.stop("kernel", t_kernel)
    return outs


def _fetch(tracer, outs, telemetry):
    """A program's output(s) as numpy: ONE array or a sequence of them,
    every copy to the host asked for before the first is waited for
    (``jax.device_get``), as the ``merge.d2h`` span. Counts the arrays
    copied (``search.phases.counters`` ``d2h_arrays_total``): over
    ``merge.d2h``'s count, the arrays fetched a query."""
    t = tracer.start("merge.d2h")
    arrays = jax.device_get(outs)
    tracer.stop("merge.d2h", t)
    if telemetry is not None:
        telemetry.add_counters({"d2h_arrays": (
            1 if isinstance(arrays, np.ndarray) else len(arrays))})
    return arrays


def _pack_answer(keys, slots, docs, total, scores, raws):
    """The serial program's merged answer as ONE ``int32[2 + 5k]``
    (device side), so that it reaches the host in one transfer: the
    total's two words, then five rows of ``k``. Everything goes in by
    its BITS — the float32 rows (``-inf`` keys of unfilled ranks,
    negative zero and all) and the int64 total (the engine runs x64: a
    sum of int32 counts is an int64) — nothing is converted."""
    def words(x, dtype):
        if x.dtype != dtype:
            raise TypeError(f"{x.dtype} where the packed answer holds "
                            f"{jnp.dtype(dtype)}: it would not survive "
                            "bit for bit")
        if dtype == jnp.int32:
            return x
        return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)

    return jnp.concatenate([
        words(total, jnp.int64), words(keys, jnp.float32),
        words(slots, jnp.int32), words(docs, jnp.int32),
        words(scores, jnp.float32), words(raws, jnp.float32)])


def _unpack_answer(packed: np.ndarray):
    """``_pack_answer`` undone on the host: (keys, slots, docs, total,
    scores, raws) as views of the one array, no copy, no arithmetic."""
    rows = packed[2:].reshape(5, -1)
    floats = rows.view(np.float32)
    return (floats[0], rows[1], rows[2], packed[:2].view(np.int64)[0],
            floats[3], floats[4])


# The dtypes a plan array crosses to the device in by its BITS. Anything
# else (a float64: the TPU keeps it in another form than its 64 bits,
# PERF.md 7 (7)) stays an argument of its own.
_PACKED_DTYPES = frozenset(
    np.dtype(t) for t in (np.int32, np.float32, np.int64, np.bool_))


def _pack_words(tail: tuple, dtype) -> int:
    """int32 words a slot's row of one packed array takes (a bool row is
    padded to whole words)."""
    return -(-int(np.prod(tail, dtype=np.int64)) * dtype.itemsize // 4)


def _pack_plan_arrays(arrays: List[np.ndarray], n_slots: int):
    """A serial launch's per-slot host arrays (``[n_slots, ...]`` each:
    the stacked plan arrays, a traced scalar a slot) as ONE
    ``int32[n_slots, W]``: one transfer a launch where each array was
    one (~0.14 ms each inside the jitted call on a v5e, PERF.md 6 PR
    36). Everything goes in by its bits (``ndarray.view``: an int64 is
    two words, four bools one), ``_unpack_plan_arrays`` takes it apart
    in the program. Returns (packed, the arrays whose dtype cannot go
    in, the layout: per array ``(tail shape, dtype name)`` or None)."""
    rows, loose, layout = [], [], []
    for a in arrays:
        if a.dtype not in _PACKED_DTYPES:
            loose.append(a)
            layout.append(None)
            continue
        layout.append((a.shape[1:], a.dtype.name))
        row = np.ascontiguousarray(a).reshape(n_slots, -1)
        if a.dtype == np.bool_:
            whole = np.zeros((n_slots, 4 * _pack_words(a.shape[1:], a.dtype)),
                             np.bool_)
            whole[:, :row.shape[1]] = row
            row = whole
        rows.append(row.view(np.int32))
    packed = (np.concatenate(rows, axis=1) if rows
              else np.zeros((n_slots, 0), np.int32))
    return packed, loose, tuple(layout)


def _unpack_plan_arrays(layout: tuple, packed, loose) -> list:
    """``_pack_plan_arrays`` undone in the program, on a device's
    ``[spd, W]`` rows of the pack: static slices at offsets that follow
    from the layout, bits reinterpreted, nothing converted."""
    lead, off, out, loose = packed.shape[:1], 0, [], iter(loose)
    for spec in layout:
        if spec is None:
            out.append(next(loose))
            continue
        tail, dtype = spec[0], np.dtype(spec[1])
        n = _pack_words(tail, dtype)
        words = packed[:, off:off + n]
        off += n
        if dtype == np.int64:
            words = jax.lax.bitcast_convert_type(
                words.reshape(lead + tail + (2,)), jnp.int64)
        elif dtype == np.float32:
            words = jax.lax.bitcast_convert_type(words, jnp.float32)
        elif dtype == np.bool_:
            n_bools = int(np.prod(tail, dtype=np.int64))
            words = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
                lead + (-1,))[:, :n_bools] != 0
        out.append(words.reshape(lead + tail))
    return out


class PlaneHealth:
    """Per-index execution-plane failure tracking + quarantine.

    A mesh_pallas / mesh plane that RAISES (compile error, device OOM,
    runtime fault — as opposed to a clean PlanStructureMismatch shape
    fallback) is benched for ``cooldown_s``: queries serve from the next
    rung of the ladder without re-paying the failure. After the cooldown
    the plane is HALF-OPEN: exactly ONE query is admitted as the probe
    (single-flight — ISSUE 10) while its peers keep serving the healthy
    rung, so a concurrent burst arriving at cooldown expiry never
    re-pays the fault N times. The probe's success re-opens the plane;
    its failure re-benches it for another cooldown. A probe that bails
    without executing (shape fallback, deadline) releases its admission;
    a prober that dies silently is covered by a bounded lease
    (``PROBE_LEASE_S``). Counters export via _stats planes
    (`plane_failures_total`, `plane_failures_by_reason`,
    `plane_quarantined`, `plane_probes_total`)."""

    PLANES = ("mesh_pallas", "mesh")
    MAX_EVENTS = 32
    # a probe admission expires after this long if the prober never
    # reported back (crashed thread) — the backstop, not the contract
    PROBE_LEASE_S = 30.0

    def __init__(self, cooldown_s: float = 60.0):
        self.cooldown_s = float(cooldown_s)
        self.failures_total: Dict[str, int] = {p: 0 for p in self.PLANES}
        # per-reason fault counters (ISSUE 10): `kernel_fault` = the
        # compiled program raised; `staging_fault` = a device staging
        # faulted terminally (classified transient-exhausted or
        # deterministic — see docs/RESILIENCE.md)
        self.failures_by_reason: Dict[str, int] = {}
        self.probes_total = 0
        self._quarantined_until: Dict[str, float] = {}
        self._probe_until: Dict[str, float] = {}
        self._lock = threading.Lock()
        # quarantine event log (docs/OBSERVABILITY.md): wall-clock
        # timestamps so operators can join a latency regression to the
        # fault that demoted the plane; capped, oldest dropped
        self.events: List[dict] = []

    def record_failure(self, plane: str,
                       reason: str = "kernel_fault") -> None:
        with self._lock:
            self.failures_total[plane] = \
                self.failures_total.get(plane, 0) + 1
            self.failures_by_reason[reason] = \
                self.failures_by_reason.get(reason, 0) + 1
            self._quarantined_until[plane] = (_time.monotonic()
                                              + self.cooldown_s)
            self._probe_until.pop(plane, None)
            self.events.append({
                "plane": plane,
                "reason": reason,
                "timestamp_ms": int(_time.time() * 1000),
                "cooldown_s": self.cooldown_s,
            })
            if len(self.events) > self.MAX_EVENTS:
                del self.events[0]

    def admit(self, plane: str) -> str:
        """Single-flight admission gate for the ladder: ``"open"`` =
        plane healthy, attempt freely; ``"probe"`` = the caller is THE
        post-cooldown probe (it must end in note_success /
        record_failure / release_probe); ``""`` (falsy) = benched, or a
        peer's probe is in flight — serve the next rung."""
        now = _time.monotonic()
        with self._lock:
            until = self._quarantined_until.get(plane)
            if until is None:
                return "open"
            if now < until:
                return ""
            lease = self._probe_until.get(plane, 0.0)
            if now < lease:
                return ""  # a peer is probing: single-flight
            self._probe_until[plane] = now + self.PROBE_LEASE_S
            self.probes_total += 1
            return "probe"

    def note_success(self, plane: str) -> None:
        """The plane served a query to completion: fully re-open it
        (clears any quarantine + probe lease; no-op when healthy)."""
        if plane not in self._quarantined_until:
            return  # lock-free fast path for the healthy hot path
        with self._lock:
            self._quarantined_until.pop(plane, None)
            self._probe_until.pop(plane, None)

    def release_probe(self, plane: str) -> None:
        """The probe bailed without executing the plane (shape
        fallback, staging ineligibility, deadline): hand the admission
        back so the next query may probe. Idempotent; never clears a
        quarantine record_failure re-armed. An un-consumed admission is
        also un-COUNTED — ``plane_probes_total`` reports probes that
        actually reached a verdict (success or failure), so a plane
        that turned structurally ineligible while benched doesn't grow
        the counter one admission per query forever."""
        with self._lock:
            if self._probe_until.pop(plane, None) is not None:
                self.probes_total -= 1

    def available(self, plane: str) -> bool:
        """Non-consuming view (stats + cheap pre-checks): False only
        while benched inside the cooldown. A half-open plane reads as
        available — use ``admit`` on the serving path."""
        return _time.monotonic() >= self._quarantined_until.get(plane, 0.0)

    def quarantined(self) -> List[str]:
        now = _time.monotonic()
        return [p for p, until in sorted(self._quarantined_until.items())
                if now < until]

    def stats(self) -> dict:
        return {
            "plane_failures_total": dict(self.failures_total),
            "plane_failures_by_reason": dict(self.failures_by_reason),
            "plane_probes_total": self.probes_total,
            "plane_quarantined": self.quarantined(),
            "quarantine_events": list(self.events),
        }


def _check_same_structure(plans: List[PlanNode]) -> None:
    def skeleton(p: PlanNode):
        # trace_statics participates: a static parameter baked into the
        # template's trace (similarity kinds, range relation, boost_mode)
        # that diverges per shard would silently score non-template
        # shards with the wrong formula
        return (type(p).__name__, len(p.arrays()), p.trace_statics(),
                tuple(skeleton(c) for c in p.children()))

    first = skeleton(plans[0])
    for p in plans[1:]:
        if skeleton(p) != first:
            raise PlanStructureMismatch(
                f"{skeleton(p)} != {first}")


_PAD_VALUES = {"z": 0, "o": 1, "n": np.nan, "m1": -1}


def stack_plans(plans: List[PlanNode], local_nd_pads: List[int],
                stacked_nd1: int, n_slots: int) -> List[np.ndarray]:
    """Stack per-shard plan arrays to mesh-ready arrays.

    Returns a flat list aligned with ``template.flat_arrays()`` where every
    entry has a leading [n_slots] axis (slots = device x segments-packed-
    per-device). Slots beyond len(plans) replicate shard 0's arrays, for
    the shapes' sake only: their seg arrays have live1 all-False, so the
    serial program skips their pass and never reads these rows (the
    batched programs still score them, against zero kernel frac, and
    mask the result away).
    """
    _check_same_structure(plans)
    kinds = plans[0].flat_pad_kinds()
    try:
        flats = [[np.asarray(a) for a in p.flat_arrays()] for p in plans]
    except NotImplementedError:
        # an unfinalized mesh kernel node — not stackable in this form
        raise PlanStructureMismatch("plan contains unfinalized arrays")
    n_arrays = len(kinds)
    for f in flats:
        if len(f) != n_arrays:
            raise PlanStructureMismatch("flat array count mismatch")
    sentinel = stacked_nd1 - 1
    stacked: List[np.ndarray] = []
    for i, kind in enumerate(kinds):
        if kind == "x":
            # non-stackable node — the host per-shard path serves these
            raise PlanStructureMismatch("plan contains non-stackable arrays")
        parts = [f[i] for f in flats]
        if kind == "k":
            # kernel tables: stack verbatim, but ONLY when every shard's
            # tables were harmonized to one shape (the kernel trace is
            # shared — a shape divergence means harmonization didn't run
            # and the plan must not reach the mesh program)
            if len({(p.shape, str(p.dtype)) for p in parts}) != 1:
                raise PlanStructureMismatch("kernel table shapes diverge")
            parts = parts + [parts[0]] * (n_slots - len(parts))
            stacked.append(np.stack(parts))
            continue
        # replicate shard 0 into unused slots
        parts = parts + [parts[0]] * (n_slots - len(parts))
        if kind == "s" or parts[0].ndim == 0:
            stacked.append(np.stack([np.asarray(p) for p in parts]))
            continue
        if kind == "dense":
            tail = parts[0].shape[1:]
            out = np.zeros((n_slots, stacked_nd1) + tail, parts[0].dtype)
            for d, a in enumerate(parts):
                out[d, : a.shape[0]] = a
            stacked.append(out)
            continue
        max_shape = tuple(
            max(p.shape[j] for p in parts) for j in range(parts[0].ndim)
        )
        if kind == "d":
            out = np.full((n_slots,) + max_shape, sentinel,
                          dtype=parts[0].dtype)
        else:
            out = np.full((n_slots,) + max_shape, _PAD_VALUES[kind],
                          dtype=parts[0].dtype)
        for d, a in enumerate(parts):
            if kind == "d":
                # re-point the shard-local sentinel doc to the stacked
                # one (replicated filler slots came from shard 0)
                src_shard = d if d < len(plans) else 0
                a = np.where(a == local_nd_pads[src_shard], sentinel, a)
            out[(d,) + tuple(slice(0, s) for s in a.shape)] = a
        stacked.append(out)
    return stacked


def _strip_plan(p: PlanNode) -> PlanNode:
    """Structural clone with data arrays dropped.

    emit() reads data exclusively through ``ctx.take`` during tracing;
    only static attributes (kinds, relation, boost_mode, child lists,
    ``len(factor_columns)``) are consulted on ``self``. Caching the full
    template would pin up to maxsize copies of doc-sized numpy columns
    (e.g. FunctionScoreNode factor columns) for the process lifetime."""
    import copy

    q = copy.copy(p)
    for name, val in vars(q).items():
        if isinstance(val, np.ndarray) and val.size > 8:
            setattr(q, name, None)
        elif isinstance(val, PlanNode):
            setattr(q, name, _strip_plan(val))
        elif isinstance(val, list) and val:
            if all(isinstance(v, PlanNode) for v in val):
                setattr(q, name, [_strip_plan(c) for c in val])
            elif all(isinstance(v, np.ndarray) for v in val):
                # length is trace-relevant (ctx.take count); contents not
                setattr(q, name, [None] * len(val))
    return q


class _TemplateHolder:
    """lru_cache key: plan structure + stacked shapes; holds the
    array-stripped template plans (main, post_filter, rescore) whose
    emit() defines the trace (same pattern as plan.py)."""

    __slots__ = ("plan", "pf_plan", "rs_plan", "_key")

    def __init__(self, plan: PlanNode, key: str,
                 pf_plan: Optional[PlanNode] = None,
                 rs_plan: Optional[PlanNode] = None):
        self.plan = plan
        self.pf_plan = pf_plan
        self.rs_plan = rs_plan
        self._key = key

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _TemplateHolder) and self._key == other._key


# The tile kernel's posting tables. A device holds each as ONE 2-D array
# [slots_per_dev * n_rows_pad, LANE], its slots concatenated along rows,
# and the kernel reads a slot's rows in place (score_tiles' row_base):
# a slice of a stacked [slots, n_rows, LANE] table is a copy of the
# slot's whole table, on the device, in every query.
_KERNEL_TABLES = ("k_docs", "k_frac", "k_packed")


# name prefixes of the slot columns staged on a request's demand
_ON_DEMAND = ("msort.", "mslice.", "maggs.", "mnum.")


def _slot_row_base(table, i: int, spd: int) -> int:
    """First row of a device's slot ``i`` in its flat kernel table."""
    return i * (table.shape[0] // spd)


def _dead_slot(shapes):
    """What a slot's pass yields (``shapes``: a tuple, the candidates'
    keys first) for a slot with no live document, without the pass:
    every key ``-inf`` (the host drops such lanes, so their docs and
    scores are free) and everything else zero (counts, views)."""
    keys, *rest = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes)
    return (jnp.full_like(keys, -jnp.inf), *rest)


def _live_slots_only(spd: int, live, slot, dead=_dead_slot) -> list:
    """Each of a device's ``spd`` slots' ``slot(i)``, paid only where
    ``live(i)`` holds at run time: a slot pays for its pass (kernel,
    masks, top-k) only if it holds a live document; a headroom slot, or
    a segment whose documents are all deleted, costs the predicate's
    reduction and a branch (``dead(shapes)``). The predicate reads what
    is staged, so a delta append into a free slot is served by the same
    compiled program; the slot is read inside the branch taken."""
    shapes = jax.eval_shape(lambda: slot(0))
    return [jax.lax.cond(live(i), functools.partial(slot, i),
                         lambda: dead(shapes)) for i in range(spd)]


@functools.lru_cache(maxsize=128)
def _mesh_query_program(mesh: Mesh, holder: _TemplateHolder, k: int,
                        spd: int = 1,
                        sort_keys: Optional[Tuple[str, str]] = None,
                        with_views: bool = False,
                        features: frozenset = frozenset(),
                        slice_col: Optional[str] = None,
                        rescore_static: Optional[Tuple[int, str]] = None,
                        agg_static: tuple = (),
                        packing: tuple = ((), (0, 0, 0), ())):
    """One compiled scatter-gather program covering the collector-chain
    semantics of the reference's query phase (QueryPhase.java:179-268) as
    fused mask stages:

      [slot holds a live document?] -> emit -> live -> min_score ->
      slice -> [agg view] -> post_filter -> total psum -> search_after
      cut -> (rescore window pass) -> local top-k -> all_gather global
      merge

    The guard in front is a ``lax.cond`` on ``any(live1)`` of the slot:
    a free slot (delta-staging headroom) or a segment whose documents
    are all deleted skips the whole per-slot pass and hands the merge
    what such a slot yields anyway (``dead_slot``). The predicate is
    read from the staged mask at run time, so it is in no program key.

    spd: SLOTS per device. A device packs spd segments (the reference's
    data node searching any number of Lucene leaves per shard,
    search/internal/ContextIndexSearcher.java:53); the per-slot query
    phases are unrolled into the device program, their candidates
    concatenated before the ICI merge. spd=1 is the historical
    one-segment-per-device layout.
    sort_keys: None ranks by score; (key_name, raw_name) ranks by the
    staged oriented key column and carries the raw field values for the
    response's per-hit ``sort`` array (FieldSortBuilder semantics).
    with_views: additionally return the per-slot matched masks and
    scores (sharded, no collective) — the aggregation reduce consumes
    them as SegmentViews exactly like the host path's shard partials.
    features: which traced scalars participate ("min_score",
    "search_after"); their VALUES arrive via the `scalars` argument so
    pagination does not recompile.
    rescore_static: (window_size, score_mode) — QueryRescorer's window
    pass over the per-slot (== per-segment, matching the host's
    per-segment window) top candidates; weights are traced scalars.
    agg_static: fused-aggregation descriptors (search/fused_aggs.py) —
    each slot's agg-visible matched mask reduces into tiny per-spec
    partial accumulators INSIDE this program (same launch as scoring;
    the masks never leave the device), returned sharded per slot like
    the views. Mutually exclusive with with_views.
    packing: (``_pack_plan_arrays``' layout of what the launch carries:
    the main plan's arrays, the post_filter's, the rescore's, then one
    ``float32[n_slots]`` a traced scalar; how many of each of the
    three; the scalars' names). It follows from the shapes and features
    the holder's key already names.
    """
    layout, n_arrays, scalar_names = packing
    plan = holder.plan
    pf_plan = holder.pf_plan
    rs_plan = holder.rs_plan

    def per_slot(seg, row_base, plan_arrays, pf_arrays, rs_arrays,
                 scalars):
        """One segment's query phase: emit -> mask stages -> local top-k.
        Returns (loc_keys, loc_docs, loc_scores, loc_raw|None,
        local_count, (agg_matched, scores) | (), agg_parts)."""
        ctx = EmitCtx(seg, plan_arrays, row_base)
        scores, matched = plan.emit(ctx)
        matched = matched & seg["live1"]
        # stage order mirrors the host path (search/service.py query()):
        # min_score and slice filter BEFORE aggs see the mask;
        # post_filter only narrows hits+total, never aggregations
        if "min_score" in features:
            matched = matched & (scores >= scalars["min_score"])
        if slice_col is not None:
            matched = matched & seg[slice_col]
        agg_matched = matched
        if pf_plan is not None:
            pf_ctx = EmitCtx(seg, pf_arrays, row_base)
            _, pf_matched = pf_plan.emit(pf_ctx)
            matched = matched & pf_matched
        # per-slot matched count is also returned sharded: a slot is
        # one SEGMENT, but terminate_after caps per SHARD — the caller
        # groups segment counts by shard and applies the cap host-side
        local_count = jnp.sum(matched.astype(jnp.int32))
        if sort_keys is None:
            rank_key = scores
        else:
            rank_key = seg[sort_keys[0]]
        masked = jnp.where(matched, rank_key, -jnp.inf)
        if "search_after" in features:
            # strict 'after' cut in oriented-key space: desc keys are the
            # raw values, asc keys their negation, so "comes after the
            # cursor" is uniformly key < after_key (hits only — total is
            # unaffected, same as TopFieldCollector paging)
            masked = jnp.where(rank_key < scalars["search_after"],
                               masked, -jnp.inf)
        nd = masked.shape[0]
        if rs_plan is not None:
            # QueryRescorer window pass. Candidates = the host path's
            # k_select = max(k, window) per segment; the first `window`
            # of them (by original rank) get combined scores, the rest
            # keep their original score; ranking then happens over the
            # candidate set ONLY — a doc outside it can never re-enter,
            # exactly like the host's seg_refs list.
            window, score_mode = rescore_static
            ksel = min(max(k, window), nd)
            sel_keys, sel_docs = jax.lax.top_k(masked, ksel)
            rs_ctx = EmitCtx(seg, rs_arrays, row_base)
            rs_scores, _ = rs_plan.emit(rs_ctx)
            w = min(window, ksel)
            rs_sel = rs_scores[sel_docs[:w]]
            qw = scalars["query_weight"]
            rqw = scalars["rescore_query_weight"]
            base = sel_keys[:w] * qw
            resc = rs_sel * rqw
            if score_mode == "total":
                comb = base + resc
            elif score_mode == "multiply":
                comb = jnp.where(rs_sel != 0.0, base * rs_sel, base)
            elif score_mode == "avg":
                comb = (base + resc) / 2.0
            elif score_mode == "max":
                comb = jnp.maximum(base, resc)
            elif score_mode == "min":
                comb = jnp.minimum(base, resc)
            else:
                raise ValueError(f"score_mode {score_mode}")
            # max/min could resurrect a -inf (unmatched/padding) lane
            comb = jnp.where(sel_keys[:w] == -jnp.inf, -jnp.inf, comb)
            cand_keys = jnp.concatenate([comb, sel_keys[w:]])
            kk = min(k, ksel)
            # rescoring reorders candidates, so ties in the COMBINED
            # score must re-break by doc id to match the host's
            # (-score, local_doc) sort — a plain top_k would keep
            # original-rank order for ties (score_mode max/min produce
            # exact ties routinely). Lexicographic (-score, doc) sort:
            neg_sorted, docs_sorted = jax.lax.sort(
                (-cand_keys, sel_docs), num_keys=2)
            loc_keys = -neg_sorted[:kk]
            loc_docs = docs_sorted[:kk]
            loc_scores = loc_keys  # the rescored score IS the hit score
        else:
            kk = min(k, nd)
            loc_keys, loc_docs = jax.lax.top_k(masked, kk)
            loc_scores = scores[loc_docs]
        loc_raw = None
        if sort_keys is not None:
            loc_raw = seg[sort_keys[1]][loc_docs]
        agg_parts = ()
        if agg_static:
            from elasticsearch_tpu.search.fused_aggs import (
                emit_agg_partials,
            )

            agg_parts = tuple(emit_agg_partials(agg_static, seg,
                                                agg_matched))
        views = (agg_matched, scores) if with_views else ()
        return (loc_keys, loc_docs, loc_scores, loc_raw, local_count,
                views, agg_parts)

    def dead_slot(seg, shapes):
        """``_dead_slot`` with each fused-agg partial at its identity:
        what ``emit_agg_partials`` makes of an all-false mask, asked of
        a one-document stand-in for the slot (no partial's shape depends
        on the document count)."""
        *rest, _agg_parts = _dead_slot(shapes)
        agg_parts = ()
        if agg_static:
            from elasticsearch_tpu.search.fused_aggs import (
                emit_agg_partials,
            )

            one_doc = {name: jnp.zeros((1,) + a.shape[2:], a.dtype)
                       for name, a in seg.items()
                       if name not in _KERNEL_TABLES}
            agg_parts = tuple(emit_agg_partials(
                agg_static, one_doc, jnp.zeros((1,), bool)))
        return (*rest, agg_parts)

    def per_device(seg, packed, loose):
        dev = jax.lax.axis_index("shards")
        arrays = iter(_unpack_plan_arrays(layout, packed, loose))
        plan_arrays, pf_arrays, rs_arrays = (
            list(itertools.islice(arrays, n)) for n in n_arrays)
        # (a scalar rode in every slot's row: any of them is it)
        scalars = {name: a[0] for name, a in zip(scalar_names, arrays)}
        # (no kernel table staged: the scatter plane, no row to offset)
        k_rows = next((seg[name].shape[0] // spd
                       for name in _KERNEL_TABLES if name in seg), 0)

        def slot(i):
            seg_i = {name: a if name in _KERNEL_TABLES else a[i]
                     for name, a in seg.items()}
            return per_slot(
                seg_i, i * k_rows, [a[i] for a in plan_arrays],
                [a[i] for a in pf_arrays], [a[i] for a in rs_arrays],
                scalars)

        slot_out = _live_slots_only(
            spd, lambda i: jnp.any(seg["live1"][i]), slot,
            functools.partial(dead_slot, seg))
        kk = slot_out[0][0].shape[0]
        cand_keys = jnp.concatenate([o[0] for o in slot_out])
        cand_docs = jnp.concatenate([o[1] for o in slot_out])
        cand_scores = jnp.concatenate([o[2] for o in slot_out])
        # GLOBAL slot id per candidate: shard_map splits the [n_slots]
        # leading axis contiguously, so device d owns slots [d*spd, ...)
        cand_slot = (dev.astype(jnp.int32) * jnp.int32(spd)
                     + jnp.repeat(jnp.arange(spd, dtype=jnp.int32), kk))
        counts = jnp.stack([o[4] for o in slot_out])  # [spd]
        total = jax.lax.psum(jnp.sum(counts), "shards")
        # global merge over ICI: every device holds the same global top-k.
        # The merged pool holds n_slots*kk candidates, so the global cut
        # is min(k, pool) — NOT kk: when k exceeds one segment's padded
        # doc count, hits beyond the largest segment are still real.
        all_keys = jax.lax.all_gather(cand_keys, "shards").reshape(-1)
        all_docs = jax.lax.all_gather(cand_docs, "shards").reshape(-1)
        all_scores = jax.lax.all_gather(cand_scores, "shards").reshape(-1)
        all_slot = jax.lax.all_gather(cand_slot, "shards").reshape(-1)
        top_keys, top_idx = jax.lax.top_k(
            all_keys, min(k, all_keys.shape[0]))
        top_slot = all_slot[top_idx]
        top_doc = all_docs[top_idx]
        top_score = all_scores[top_idx]
        if sort_keys is None:
            top_raw = top_keys if rs_plan is None else top_score
        else:
            cand_raw = jnp.concatenate([o[3] for o in slot_out])
            all_raw = jax.lax.all_gather(cand_raw, "shards").reshape(-1)
            top_raw = all_raw[top_idx]
        outs = [_pack_answer(top_keys, top_slot, top_doc, total,
                             top_score, top_raw)[None],
                counts]
        if with_views:
            outs.extend([jnp.stack([o[5][0] for o in slot_out]),
                         jnp.stack([o[5][1] for o in slot_out])])
        if agg_static:
            n_agg = len(slot_out[0][6])
            outs.extend(jnp.stack([o[6][j] for o in slot_out])
                        for j in range(n_agg))
        return tuple(outs)

    # ONE replicated merge output (the packed answer); local_count
    # (index 1), the optional views, and the fused-agg partials stay
    # SHARDED (a row per slot)
    from elasticsearch_tpu.search.fused_aggs import n_agg_outputs

    n_out = 2 + (2 if with_views else 0) + n_agg_outputs(agg_static)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(PS("shards"), PS("shards"), PS("shards")),
        out_specs=(PS("shards"),) * n_out,
        check_vma=False,
    )

    @jax.jit
    def run(seg, packed, loose):
        # (the scope puts the program's name into the op metadata of
        # every device operation: a trace's fusions say whose they are)
        with jax.named_scope("mesh_query"):
            outs = mapped(seg, packed, loose)
        # the packed answer is replicated (row 0 == row i); the other
        # outputs keep their sharded leading axis
        return (outs[0][0],) + tuple(outs[1:])

    from elasticsearch_tpu.common.compile_cache import (
        instrument_program,
        variant_key,
    )

    return instrument_program(
        run, "serial",
        variant_key("serial", holder._key, len(mesh.devices)))


def _shapes_sig(arrays) -> str:
    return ";".join(f"{a.shape}{a.dtype}" for a in arrays)


@functools.lru_cache(maxsize=32)
def _mesh_batched_kernel_program(mesh: Mesh, spd: int, q_batch: int,
                                 kk: int, t_pad: int, cb: int, sub: int,
                                 tps: int, interpret: bool,
                                 codec: str = "raw"):
    """One compiled scatter-gather serving Q CONCURRENT queries (ISSUE 5
    cross-query micro-batching on the mesh_pallas rung): per slot, ONE
    batched ``score_tiles`` launch streams the slot's posting windows
    once and emits per-query per-tile top-k candidates; the per-query
    pools merge locally, then over ICI via one all_gather — the same
    collective shape as _mesh_query_program's merge, with a leading
    query axis instead of a leading 1. codec="packed" streams the
    bit-packed posting words (one corpus operand instead of two)."""
    from elasticsearch_tpu.ops import pallas_scoring as psc

    packed = codec == "packed"

    def per_device(*args):
        if packed:
            kp, lt, rl, rh, w = args
        else:
            kd, kf, lt, rl, rh, w = args
        dev = jax.lax.axis_index("shards")
        cand_s, cand_d, cand_slot = [], [], []
        hits = None
        corpus = (kp, None) if packed else (kd, kf)
        for i in range(spd):
            ts_, td_, th_ = psc.score_tiles(
                corpus[0], corpus[1], lt[i], rl[i], rh[i], w[i],
                t_pad=t_pad, cb=cb, sub=sub, k=kk, interpret=interpret,
                tiles_per_step=tps, q_batch=q_batch, codec=codec,
                row_base=_slot_row_base(corpus[0], i, spd))
            s_i, d_i, h_i = psc.merge_tile_topk_batched(ts_, td_, th_, kk)
            cand_s.append(s_i)  # [Q, kk']
            cand_d.append(d_i)
            cand_slot.append(
                jnp.zeros(s_i.shape, jnp.int32)
                + (dev.astype(jnp.int32) * jnp.int32(spd) + jnp.int32(i)))
            hits = h_i if hits is None else hits + h_i
        cs = jnp.concatenate(cand_s, axis=1)
        cd = jnp.concatenate(cand_d, axis=1)
        cslot = jnp.concatenate(cand_slot, axis=1)
        total = jax.lax.psum(hits, "shards")  # [Q]
        all_s = jax.lax.all_gather(cs, "shards")  # [n_dev, Q, spd*kk']
        all_d = jax.lax.all_gather(cd, "shards")
        all_slot = jax.lax.all_gather(cslot, "shards")
        pool_s = all_s.transpose(1, 0, 2).reshape(q_batch, -1)
        pool_d = all_d.transpose(1, 0, 2).reshape(q_batch, -1)
        pool_slot = all_slot.transpose(1, 0, 2).reshape(q_batch, -1)
        top_s, top_i = jax.lax.top_k(pool_s, min(kk, pool_s.shape[1]))
        top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
        top_slot = jnp.take_along_axis(pool_slot, top_i, axis=1)
        return top_s[None], top_d[None], top_slot[None], total[None]

    n_in = 5 if packed else 6
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(PS("shards"),) * n_in,
        out_specs=(PS("shards"),) * 4,
        check_vma=False,
    )

    @jax.jit
    def run(*args):
        with jax.named_scope("mesh_batched_kernel"):
            outs = mapped(*args)
        return tuple(o[0] for o in outs)  # replicated: row 0 == row i

    from elasticsearch_tpu.common.compile_cache import (
        instrument_program,
        variant_key,
    )

    return instrument_program(
        run, "batched",
        variant_key("batched", len(mesh.devices), spd, q_batch, kk,
                    t_pad, cb, sub, tps, interpret, codec))


@functools.lru_cache(maxsize=32)
def _mesh_batched_dense_agg_program(mesh: Mesh, spd: int, q_batch: int,
                                    kk: int, t_pad: int, cb: int, sub: int,
                                    tps: int, interpret: bool, codec: str,
                                    agg_statics: tuple, nd1: int):
    """The batched mesh program for agg-carrying bursts (ISSUE 13):
    ONE dense ``score_tiles`` launch streams each slot's posting
    windows once for the whole batch, and the SAME pass both ranks and
    aggregates — per member, the dense score vector yields the matched
    mask on device, the mask reduces the staged doc-value columns into
    per-spec partial accumulators (search/fused_aggs.py), and hits
    merge with the serial mesh program's exact collector semantics
    (per-slot ``lax.top_k`` over doc-ordered dense scores, pool concat
    in slot order, ICI all_gather, global top-k — byte-identical ties
    to the host path). ``agg_statics``: one fused-agg descriptor tuple
    per member (empty = member carries no aggs); heterogeneous bodies
    compile per combination, bucketed by the same q_pad/kk shape keys
    as the fused-top-k program. Aggs force this exhaustive dense form —
    pruning never composes with aggregations (docs/PRUNING.md)."""
    from elasticsearch_tpu.ops import pallas_scoring as psc
    from elasticsearch_tpu.search.fused_aggs import emit_agg_partials

    packed = codec == "packed"

    def per_device(*args):
        if packed:
            kp, lt, rl, rh, w, cols = args
        else:
            kd, kf, lt, rl, rh, w, cols = args
        dev = jax.lax.axis_index("shards")
        cand_s, cand_d, cand_slot = [], [], []
        counts = None
        agg_parts = None
        corpus = (kp, None) if packed else (kd, kf)
        for i in range(spd):
            dense = psc.score_tiles(
                corpus[0], corpus[1], lt[i], rl[i], rh[i], w[i],
                t_pad=t_pad, cb=cb, sub=sub, dense=True,
                interpret=interpret, tiles_per_step=tps,
                q_batch=q_batch, codec=codec,
                row_base=_slot_row_base(corpus[0], i, spd))[0]
            rows = dense.shape[1] // psc.LANE
            flat = dense.reshape(q_batch, rows, psc.LANE, sub).transpose(
                0, 1, 3, 2).reshape(q_batch, -1)[:, : nd1 - 1]
            # sentinel column: dead like the serial program's live1 tail
            flat = jnp.concatenate(
                [flat, jnp.zeros((q_batch, 1), jnp.float32)], axis=1)
            matched = flat > 0.0  # [Q, nd1] (live folded in-kernel)
            masked = jnp.where(matched, flat, -jnp.inf)
            s_i, d_i = jax.lax.top_k(masked, min(kk, masked.shape[1]))
            cand_s.append(s_i)
            cand_d.append(d_i)
            cand_slot.append(
                jnp.zeros(s_i.shape, jnp.int32)
                + (dev.astype(jnp.int32) * jnp.int32(spd) + jnp.int32(i)))
            c = jnp.sum(matched.astype(jnp.int32), axis=1)  # [Q]
            counts = c if counts is None else counts + c
            cols_i = {name: a[i] for name, a in cols.items()}
            slot_parts = []
            for q in range(q_batch):
                if agg_statics[q]:
                    slot_parts.extend(emit_agg_partials(
                        agg_statics[q], cols_i, matched[q]))
            if agg_parts is None:
                agg_parts = [[p] for p in slot_parts]
            else:
                for j, p in enumerate(slot_parts):
                    agg_parts[j].append(p)
        cs = jnp.concatenate(cand_s, axis=1)
        cd = jnp.concatenate(cand_d, axis=1)
        cslot = jnp.concatenate(cand_slot, axis=1)
        total = jax.lax.psum(counts, "shards")  # [Q]
        all_s = jax.lax.all_gather(cs, "shards")
        all_d = jax.lax.all_gather(cd, "shards")
        all_slot = jax.lax.all_gather(cslot, "shards")
        pool_s = all_s.transpose(1, 0, 2).reshape(q_batch, -1)
        pool_d = all_d.transpose(1, 0, 2).reshape(q_batch, -1)
        pool_slot = all_slot.transpose(1, 0, 2).reshape(q_batch, -1)
        top_s, top_i = jax.lax.top_k(pool_s, min(kk, pool_s.shape[1]))
        top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
        top_slot = jnp.take_along_axis(pool_slot, top_i, axis=1)
        outs = [top_s[None], top_d[None], top_slot[None], total[None]]
        if agg_parts:
            outs.extend(jnp.stack(parts) for parts in agg_parts)
        return tuple(outs)

    from elasticsearch_tpu.search.fused_aggs import n_agg_outputs

    n_agg_out = sum(n_agg_outputs(s) for s in agg_statics)
    n_in = 6 if packed else 7
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(PS("shards"),) * n_in,
        out_specs=(PS("shards"),) * (4 + n_agg_out),
        check_vma=False,
    )

    @jax.jit
    def run(*args):
        with jax.named_scope("mesh_batched_dense_agg"):
            outs = mapped(*args)
        # merged outputs replicated; agg partials stay sharded per slot
        return tuple(o[0] for o in outs[:4]) + tuple(outs[4:])

    from elasticsearch_tpu.common.compile_cache import (
        instrument_program,
        variant_key,
    )

    return instrument_program(
        run, "batched_agg",
        variant_key("batched_agg", len(mesh.devices), spd, q_batch, kk,
                    t_pad, cb, sub, tps, interpret, codec, agg_statics,
                    nd1))


@functools.lru_cache(maxsize=32)
def _mesh_batched_pruned_program(mesh: Mesh, spd: int, q_batch: int,
                                 kk: int, t_pad: int,
                                 cb: int, sub: int, tps: int,
                                 interpret: bool, codec: str,
                                 probe: int, n_rest: int):
    """Block-max pruned batched scoring on the mesh (ISSUE 6), ONE
    compiled program with NO host round-trip:

    - probe pass: every slot scores its ``probe`` highest-bound tiles
      (host-ordered); the per-query candidate pools merge over ICI via
      all_gather — the k-th best merged score is the GLOBAL running
      threshold theta_q, identical on every device (deterministic merge
      of a replicated pool).
    - rest pass: each slot keeps only the rest tiles whose per-(tile,
      query) bound can still beat theta (a tile survives when ANY real
      member needs it — per-member thresholds over the union lanes, no
      cross-member leakage); non-survivors get their runtime row tables
      zeroed, which the sel-mode kernel turns into skipped DMA + compute.
    - both pools merge per query over ICI; totals are the psum of SCORED
      tiles' match counts (a documented lower bound under pruning).

    ``q_real`` (how many leading weight rows are real members — the rest
    are power-of-two padding) and ``slot_real`` (1 for staged segment
    slots, 0 for replication filler) are RUNTIME operands, not cache
    keys: arrival-timing-dependent batch sizes must not compile a
    program variant each, and filler slots must not inflate the tile
    counters (their bounds would otherwise survive any -inf threshold).

    Returns (top_s [Q, kk], top_d, top_slot, total [Q],
    tiles_scored scalar, tiles_total scalar)."""
    from elasticsearch_tpu.ops import pallas_scoring as psc

    packed = codec == "packed"

    def per_device(*args):
        if packed:
            (kp, lt, rl_p, rh_p, tid_p, rl_r, rh_r, tid_r, bounds_r,
             w, slot_real, q_real) = args
        else:
            (kd, kf, lt, rl_p, rh_p, tid_p, rl_r, rh_r, tid_r, bounds_r,
             w, slot_real, q_real) = args
        dev = jax.lax.axis_index("shards")
        kw = dict(t_pad=t_pad, cb=cb, sub=sub, k=kk, interpret=interpret,
                  tiles_per_step=tps, q_batch=q_batch, codec=codec)
        corpus = (kp, None) if packed else (kd, kf)

        def slot_pass(i, rl, rh, tid):
            ts_, td_, th_ = psc.score_tiles(
                corpus[0], corpus[1], lt[i], rl, rh, w[i],
                tile_ids=tid,
                row_base=_slot_row_base(corpus[0], i, spd), **kw)
            s_i, d_i, h_i = psc.merge_tile_topk_batched(ts_, td_, th_, kk)
            slot = (jnp.zeros(s_i.shape, jnp.int32)
                    + (dev.astype(jnp.int32) * jnp.int32(spd)
                       + jnp.int32(i)))
            return s_i, d_i, slot, h_i

        def gather_pool(cand):
            cs = jnp.concatenate([c[0] for c in cand], axis=1)
            cd = jnp.concatenate([c[1] for c in cand], axis=1)
            cslot = jnp.concatenate([c[2] for c in cand], axis=1)
            all_s = jax.lax.all_gather(cs, "shards")
            all_d = jax.lax.all_gather(cd, "shards")
            all_slot = jax.lax.all_gather(cslot, "shards")
            return (all_s.transpose(1, 0, 2).reshape(q_batch, -1),
                    all_d.transpose(1, 0, 2).reshape(q_batch, -1),
                    all_slot.transpose(1, 0, 2).reshape(q_batch, -1))

        probe_out = [slot_pass(i, rl_p[i], rh_p[i], tid_p[i])
                     for i in range(spd)]
        hits = sum(o[3] for o in probe_out[1:]) + probe_out[0][3]
        pool_s, pool_d, pool_slot = gather_pool(probe_out)
        # global running threshold: k-th best of the merged probe pool
        # (replicated — every device computes the identical theta)
        kth_s, _ = jax.lax.top_k(pool_s, min(kk, pool_s.shape[1]))
        if kth_s.shape[1] >= kk:
            kth = kth_s[:, kk - 1]
        else:
            kth = jnp.full((q_batch,), -jnp.inf, jnp.float32)
        theta = jnp.where(jnp.arange(q_batch) < q_real, kth,
                          jnp.float32(np.inf))
        # filler slots (slot_real == 0) must never survive: their -inf
        # bounds would pass a member's -inf threshold and inflate the
        # counters (their tables are all-zero, so scoring them is only
        # an accounting bug — but the pruned fraction is this feature's
        # headline observable)
        real_mask = slot_real > jnp.int32(0)  # [spd]
        survive = (jnp.any(bounds_r >= theta[None, None, :], axis=2)
                   & real_mask[:, None])
        rest_out = []
        for i in range(spd):
            sv = survive[i]
            rl2 = jnp.where(sv[:, None], rl_r[i], jnp.int32(0))
            rh2 = jnp.where(sv[:, None], rh_r[i], jnp.int32(0))
            tid2 = jnp.where(sv, tid_r[i], jnp.int32(0))
            rest_out.append(slot_pass(i, rl2, rh2, tid2))
        hits = hits + sum(o[3] for o in rest_out[1:]) + rest_out[0][3]
        rs, rd, rslot = gather_pool(rest_out)
        pool_s = jnp.concatenate([pool_s, rs], axis=1)
        pool_d = jnp.concatenate([pool_d, rd], axis=1)
        pool_slot = jnp.concatenate([pool_slot, rslot], axis=1)
        top_s, top_i = jax.lax.top_k(pool_s, min(kk, pool_s.shape[1]))
        top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
        top_slot = jnp.take_along_axis(pool_slot, top_i, axis=1)
        total = jax.lax.psum(hits, "shards")
        n_real = jnp.sum(slot_real)
        scored = jax.lax.psum(
            n_real * jnp.int32(probe)
            + jnp.sum(survive.astype(jnp.int32)), "shards")
        tiles_total = jax.lax.psum(
            n_real * jnp.int32(probe + n_rest), "shards")
        return (top_s[None], top_d[None], top_slot[None], total[None],
                scored[None], tiles_total[None])

    n_in = 11 if packed else 12
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(PS("shards"),) * n_in + (PS(),),
        out_specs=(PS("shards"),) * 6,
        check_vma=False,
    )

    @jax.jit
    def run(*args):
        with jax.named_scope("mesh_batched_pruned"):
            outs = mapped(*args)
        return tuple(o[0] for o in outs)  # replicated: row 0 == row i

    from elasticsearch_tpu.common.compile_cache import (
        instrument_program,
        variant_key,
    )

    return instrument_program(
        run, "pruned",
        variant_key("pruned", len(mesh.devices), spd, q_batch, kk, t_pad,
                    cb, sub, tps, interpret, codec, probe, n_rest))


@functools.lru_cache(maxsize=32)
def _mesh_knn_program(mesh: Mesh, spd: int, q_pad: int, kk: int,
                      sub: int, d_pad: int, nd_knn: int, metric: str,
                      interpret: bool):
    """One compiled scatter-gather serving Q concurrent kNN queries on
    the MXU (ROADMAP item 4): per slot, ONE ``knn_score_tiles`` launch
    streams the slot's bf16 embedding matrix once for the whole batch
    and emits per-query per-tile top-k candidates; pools merge locally,
    then over ICI via one all_gather — the same collective shape as
    ``_mesh_batched_kernel_program``, with the posting windows replaced
    by a dense matmul.

    The embeddings, scale and mask are staged FLAT (``[spd * nd_knn,
    ...]`` a device, one slot after the other) and the kernel reads a
    slot in place at its row base, as the tile kernel reads its postings
    (``_KERNEL_TABLES``); a slot whose mask holds no live vector skips
    its pass (``_live_slots_only``); the candidates rank by the raw
    similarity and the winners alone are scored (``hit_score``); each
    query's answer leaves as one ``_pack_answer`` row, so the batch is
    ONE array and one transfer. The match total (live docs carrying the
    vector field) is query-independent: it is the psum of the staged
    mask sums, not a kernel output."""
    from elasticsearch_tpu.ops import pallas_knn as pkn

    def per_device(emb, scale, mask, qv):
        dev = jax.lax.axis_index("shards")
        slot_mask = mask.reshape(spd, nd_knn)  # (a view: no copy)

        def slot(i):
            ts, td = pkn.knn_score_tiles(
                emb, scale, mask, qv, sub=sub, k=kk, q_batch=q_pad,
                interpret=interpret,
                row_base=_slot_row_base(emb, i, spd), n_rows=nd_knn)
            s_i, d_i = pkn.merge_knn_topk(ts, td, kk)  # [q_pad, kk']
            return s_i, d_i, jnp.sum(slot_mask[i]).astype(jnp.int32)

        slot_out = _live_slots_only(
            spd, lambda i: jnp.any(slot_mask[i] > 0), slot)
        cs = jnp.concatenate([o[0] for o in slot_out], axis=1)
        cd = jnp.concatenate([o[1] for o in slot_out], axis=1)
        cslot = jnp.concatenate([
            jnp.zeros(o[0].shape, jnp.int32)
            + (dev.astype(jnp.int32) * jnp.int32(spd) + jnp.int32(i))
            for i, o in enumerate(slot_out)], axis=1)
        total = jax.lax.psum(sum(o[2] for o in slot_out), "shards")
        all_s = jax.lax.all_gather(cs, "shards")
        all_d = jax.lax.all_gather(cd, "shards")
        all_slot = jax.lax.all_gather(cslot, "shards")
        pool_s = all_s.transpose(1, 0, 2).reshape(q_pad, -1)
        pool_d = all_d.transpose(1, 0, 2).reshape(q_pad, -1)
        pool_slot = all_slot.transpose(1, 0, 2).reshape(q_pad, -1)
        top_s, top_i = jax.lax.top_k(pool_s, min(kk, pool_s.shape[1]))
        top_d = jnp.take_along_axis(pool_d, top_i, axis=1)
        top_slot = jnp.take_along_axis(pool_slot, top_i, axis=1)
        scores = pkn.hit_score(top_s, metric, jnp)
        total = total.astype(jnp.int64)
        # (the sixth row carries a field sort's raw values in the serial
        # program; here it repeats the scores)
        packed = jax.vmap(lambda keys, slots, docs, sc: _pack_answer(
            keys, slots, docs, total, sc, sc))(
                top_s, top_slot, top_d, scores)
        return packed[None]

    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(PS("shards"), PS("shards"), PS("shards"), PS()),
        out_specs=PS("shards"),
        check_vma=False,
    )

    @jax.jit
    def run(*args):
        with jax.named_scope("mesh_knn"):
            return mapped(*args)[0]  # replicated: row 0 == row i

    from elasticsearch_tpu.common.compile_cache import (
        instrument_program,
        variant_key,
    )

    return instrument_program(
        run, "knn",
        variant_key("knn", len(mesh.devices), spd, q_pad, kk, sub,
                    d_pad, nd_knn, metric, interpret))


def clear_compiled_programs() -> None:
    """Drop every cached compiled-program entry (all five lru_cache'd
    mesh-program builders). Used by the rolling-restart soak and the
    cold_start bench to simulate a fresh process: the next query (or
    warm replay) re-traces and re-compiles — against the persistent
    compilation cache when one is configured."""
    for builder in (_mesh_query_program, _mesh_batched_kernel_program,
                    _mesh_batched_dense_agg_program,
                    _mesh_batched_pruned_program, _mesh_knn_program):
        builder.cache_clear()


class IndexMeshSearch:
    """Routes an index's production query phase through the mesh.

    Owned by IndexService. Eligible searches (plain query + top-k by
    score) run as ONE multi-device program over all (shard, segment)
    pairs; anything the program doesn't cover yet returns None and the
    caller uses the host-merge path — same shape as the reference
    choosing between query-then-fetch variants per request.

    Staging is cached against the identity of the segment set and
    invalidated automatically when any shard refreshes/merges."""

    # request keys the mesh program does not cover — presence of any of
    # them falls back to the host path. Everything else in the query
    # phase runs in-program: single-field f32-exact numeric/_doc/_score
    # AND keyword (global-ordinal) sorts, aggregations (reduced over the
    # program's per-device matched masks), post_filter / min_score /
    # slice as fused mask stages, search_after as an oriented-key cut,
    # rescore as an in-program window pass, terminate_after as the
    # host-identical reported-total cap. suggest and highlight are
    # host-side phases orthogonal to the query program (fetch/suggest
    # phases), served on the mesh path by the same code as the host path.
    # "profile" is NOT here (ISSUE 8): a profiled query runs on whatever
    # plane would serve it unprofiled and reports THAT plane's phase
    # spans — plane-truthful, never plane-demoting (docs/OBSERVABILITY.md).
    UNSUPPORTED = ("collapse",)

    def __init__(self, index_service, mesh: Optional[Mesh] = None):
        self.svc = index_service
        self._mesh = mesh
        self._executor: Optional[MeshPlanExecutor] = None
        self._staged_key = None
        self._pairs: List[Tuple[int, object]] = []  # (shard_id, segment)
        self.query_total = 0
        # queries whose scoring ran on the tile kernel inside the mesh
        # program (the unified fast plane) vs the XLA scatter formulation
        self.pallas_query_total = 0
        # cross-query micro-batching on the mesh_pallas rung
        # (query_batch): launches and member-queries served batched
        self.batched_launch_total = 0
        self.batched_query_total = 0
        # dense-vector retrieval on the MXU (docs/VECTOR.md): queries
        # whose kNN side ran the mesh kNN program
        self.knn_query_total = 0
        # slots whose kNN pass ran, a query each (one that holds a live
        # vector; the program skips the others): over knn_query_total,
        # the slots a query paid for
        self.knn_slots_scanned_total = 0
        # and the bf16 embedding bytes those passes streamed, a launch
        self.embedding_bytes_streamed_total = 0
        # fused on-device aggregations (ISSUE 13, docs/AGGS.md):
        # queries whose whole agg set reduced inside the mesh program,
        # vs agg'd mesh queries that fell back to the host reduce over
        # device views — per documented reason (docs/OBSERVABILITY.md)
        self.agg_fused_query_total = 0
        # their bucket counts by the formulation the program traced for
        # each (fused_aggs.DENSE_COUNT_MAX_BUCKETS)
        self.agg_bucket_dense_total = 0
        self.agg_bucket_product_total = 0
        # queries whose field sort ranked inside the mesh program
        self.sort_device_query_total = 0
        self.agg_host_fallback_total = 0
        self.agg_host_fallback_by_reason: Dict[str, int] = {}
        # block-max pruned scoring observability (docs/PRUNING.md):
        # queries served by the pruned program, and its tile economy
        self.pruned_query_total = 0
        self.tiles_scored_total = 0
        self.tiles_pruned_total = 0
        # delta device staging (ISSUE 20, docs/MESH.md): refreshes
        # served by a slot append instead of a rebuild, deletes served
        # by in-place tombstone mask updates, and background compaction
        # passes that rebuilt a compact generation
        self.delta_restage_total = 0
        self.tombstone_update_total = 0
        self.compaction_runs_total = 0
        settings = getattr(index_service, "settings", None)
        # packing limit: segments are packed max_slots-deep per device
        # before the index falls back to the host path (registered as
        # index.search.mesh.max_slots_per_device)
        self.max_slots = 4
        # plane override: auto = kernel when stageable, scatter fallback;
        # pallas = kernel or host (never the scatter mesh); scatter =
        # never build kernel plans (index.search.mesh.plane)
        self.plane_pref = "auto"
        quarantine_cooldown = 60.0
        if settings is not None:
            self.max_slots = settings.get_int(
                "index.search.mesh.max_slots_per_device", 4)
            self.plane_pref = settings.get_str(
                "index.search.mesh.plane", "auto")
            quarantine_cooldown = settings.get_time(
                "index.search.plane_quarantine.cooldown", 60.0)
        # plane-health quarantine (index.search.plane_quarantine.cooldown)
        self.plane_health = PlaneHealth(quarantine_cooldown)
        # set by _ensure_staged when the HBM budget (not an infra gap)
        # turned the mesh staging away — exported as the ladder
        # decision reason so operators can tell demotion from fault.
        # THREAD-local: concurrent queries each read the reason their
        # own _ensure_staged call produced (a shared field would let one
        # thread's reset misattribute another's hbm_budget decision)
        self._denied = threading.local()
        # counter updates must be atomic: concurrent batch leaders /
        # serial queries increment from different threads (ISSUE 8
        # stats-consistency contract — docs/OBSERVABILITY.md)
        self._counter_lock = threading.Lock()
        # serializes the executor build/swap in _ensure_staged: two
        # concurrent first-queries must not both construct a generation
        # (the loser's staged bytes would leak in the ledger until index
        # close). _drop_staging deliberately does NOT take this lock —
        # the accountant invokes it under its own lock and a stager
        # inside this lock may be waiting on the accountant's.
        self._stage_lock = threading.Lock()
        # staging-fault bench state (ISSUE 10): a terminal (classified)
        # staging fault benches the mesh staging until this monotonic
        # deadline; after it, exactly one query probes the restage
        # (_stage_probing) while peers serve the host rung
        self._staging_fault_until = 0.0
        self._staging_faulted = False
        self._stage_probing = False

    @property
    def staging_denied_reason(self):
        return getattr(self._denied, "reason", None)

    @staging_denied_reason.setter
    def staging_denied_reason(self, value) -> None:
        self._denied.reason = value

    @property
    def _telemetry(self):
        """The index's SearchTelemetry (None for a bare test double)."""
        return getattr(self.svc, "telemetry", None)

    def _note(self, plane: str, reason: str, n: int = 1) -> None:
        """Plane-ladder decision counter (search.phases.decisions).
        ``n``: member count — batch-path decisions count per QUERY so
        they stay comparable with the serial ladder's counts."""
        tel = self._telemetry
        if tel is not None:
            tel.note_decision(plane, reason, n)

    def _mesh_or_default(self) -> Mesh:
        if self._mesh is None:
            from elasticsearch_tpu.parallel.mesh import shard_mesh

            self._mesh = shard_mesh()
        return self._mesh

    def _current_pairs(self) -> List[Tuple[int, object]]:
        pairs = []
        for sid in sorted(self.svc.shards):
            eng = self.svc.shards[sid].engine
            for seg in eng.searchable_segments():
                if seg.num_docs > 0:
                    pairs.append((sid, seg))
        return pairs

    def _drop_staging(self) -> None:
        """HBM-budget eviction callback: drop the staged mesh plane (it
        restages on the next eligible query — or demotes to the host
        rung if the budget still can't fit it)."""
        executor, self._executor = self._executor, None
        self._staged_key = None
        if executor is not None:
            self._evicted_since = True
            executor.release()

    def _restage_reason(self, old_key, new_key, old_executor,
                        n_slots_needed: int) -> str:
        """Classify WHY the mesh plane restages (the staging lifecycle
        event reason, docs/OBSERVABILITY.md): a slot-geometry change,
        a segment-set change (refresh/merge), an in-place live-mask
        invalidation (deletes), or a re-stage after a budget eviction
        (probe — each executor generation is a fresh ledger scope, so
        the accountant cannot infer this one itself)."""
        if old_key is None or old_executor is None:
            if getattr(self, "_evicted_since", False):
                self._evicted_since = False
                return "probe"
            return "initial"
        if old_executor.n_slots != n_slots_needed:
            return "geometry_change"
        if ({(sid, seg_id) for sid, seg_id, _n in old_key}
                != {(sid, seg_id) for sid, seg_id, _n in new_key}):
            return "refresh"
        return "delete_invalidation"

    @staticmethod
    def _key_for(pairs) -> frozenset:
        """Staged-set identity: ORDER-INDEPENDENT (a frozenset), so a
        delta-append successor — whose slot order appends new segments
        at the tail instead of re-sorting — compares equal to the same
        logical set (ISSUE 20). live_doc_count participates: deletes
        mutate a sealed segment's live mask in place, which must
        invalidate (tombstone-update) the staged live1."""
        return frozenset((sid, id(seg), seg.live_doc_count)
                         for sid, seg in pairs)

    def _delta_enabled(self) -> bool:
        """index.staging.delta.enabled, live."""
        return bool(self.svc.live.get_bool("index.staging.delta.enabled",
                                           True))

    def _classify_delta(self, old, pairs, codec):
        """Decide whether the staged-key change is servable as a DELTA
        on the live generation (ISSUE 20). Returns
        ``("tombstone", [], changed_slots)`` when only live-doc counts
        changed, ``("append", new_pairs, changed_slots)`` when segments
        were added within free slot capacity (deletes may ride along),
        or None for the full-rebuild fallback (segments retired, slots
        exhausted, tile-geometry mismatch, codec change)."""
        staged_counts = {(sid, kid): n
                         for sid, kid, n in self._staged_key}
        slot_of = {(sid, id(seg)): slot
                   for slot, (sid, seg) in enumerate(old.pairs)}
        if set(slot_of) != set(staged_counts):
            return None  # key/generation disagree: rebuild from truth
        new_ids = {(sid, id(seg)) for sid, seg in pairs}
        if not set(slot_of) <= new_ids:
            return None  # segments retired (merge): rebuild
        if codec != old.postings_codec_pref:
            return None  # codec change: rebuild fallback
        append_pairs = [(sid, seg) for sid, seg in pairs
                        if (sid, id(seg)) not in slot_of]
        changed = sorted(
            slot_of[(sid, id(seg))] for sid, seg in pairs
            if (sid, id(seg)) in slot_of
            and staged_counts[(sid, id(seg))] != seg.live_doc_count)
        if not append_pairs:
            return ("tombstone", [], changed) if changed else None
        if not MeshPlanExecutor.delta_append_compatible(
                old, [seg for _sid, seg in append_pairs]):
            return None
        return ("append", append_pairs, changed)

    def _apply_delta(self, old, delta, key) -> Optional[bool]:
        """Serve a classified delta on/over the live generation (caller
        holds ``_stage_lock``). Returns True on success, False on a
        terminal fault (staging benched — host rung serves), or None
        when a structural surprise says fall back to the rebuild."""
        from elasticsearch_tpu.common.errors import \
            TaskCancelledException
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.common.staging import run_staged
        from elasticsearch_tpu.search.cancellation import \
            TimeExceededException

        # thread-local hygiene (PR-9 bug class): this is a staging
        # attempt in its own right — reset before any denial below
        self.staging_denied_reason = None
        kind_of, append_pairs, changed_slots = delta
        try:
            if kind_of == "tombstone":
                run_staged(
                    lambda: old.apply_tombstones(changed_slots),
                    index=self.svc.name, kind="live_mask", plane="mesh")
                self._staged_key = key
                with self._counter_lock:
                    self.tombstone_update_total += 1
                old.touch()
                self._maybe_compact()
                return True
            # append: budget-gate the DELTA rows only (the carried
            # arrays are already in the ledger under the old scope)
            estimate = sum(
                seg.block_docs.nbytes + seg.block_tfs.nbytes
                + seg.norms.nbytes + seg.nd_pad + 1
                for _sid, seg in append_pairs)
            if not memory_accountant().try_reserve(
                    self.svc.name, estimate, exclude_scope=old.scope):
                self.staging_denied_reason = "hbm_budget"
                return False
            staged = run_staged(
                lambda: MeshPlanExecutor.delta_append(
                    old, append_pairs, changed_slots,
                    index_name=self.svc.name),
                index=self.svc.name, kind="mesh_slot_tables",
                plane="mesh")
            old.release()
            self._pairs = list(staged.pairs)
            self._executor = staged
            self._staged_key = key
            with self._counter_lock:
                self.delta_restage_total += 1
                if changed_slots:
                    self.tombstone_update_total += 1
            staged.make_evictable(self._drop_staging)
            self._maybe_compact()
            return True
        except _DeltaIneligible:
            return None  # structural surprise: full rebuild fallback
        except (TaskCancelledException, TimeExceededException):
            raise  # PR-4 contract: caller owns partial/cancel
        except Exception:  # noqa: BLE001 — terminal classified staging
            # fault: same bench + quarantine as a full-rebuild fault
            # (the attempt rolled back; pre-attempt ledger is exact)
            _plane_logger.warning(
                "[%s] mesh delta staging failed; serving from the host "
                "rung for %.1fs (reason staging_fault)",
                self.svc.name, self.plane_health.cooldown_s,
                exc_info=True)
            self._staging_faulted = True
            self._staging_fault_until = (
                _time.monotonic() + self.plane_health.cooldown_s)
            self.plane_health.record_failure(
                "mesh_pallas", reason="staging_fault")
            self.staging_denied_reason = "staging_fault"
            return False

    def _maybe_compact(self) -> None:
        """Opportunistic compaction trigger after a delta commit: the
        owner decides (threshold/fragmentation/drain) and runs it OFF
        the query path (ISSUE 20 — no polling loop to leak)."""
        hook = getattr(self.svc, "maybe_compact_async", None)
        if hook is not None:
            hook()

    def _ensure_staged(self) -> bool:
        self.staging_denied_reason = None
        # staging-fault backoff (ISSUE 10, docs/RESILIENCE.md): after a
        # terminal staging fault the mesh staging is benched for the
        # quarantine cooldown — every query until then demotes to the
        # host rung (reason staging_fault) instead of re-paying the
        # multi-second staging attempt per query
        if _time.monotonic() < self._staging_fault_until:
            self.staging_denied_reason = "staging_fault"
            return False
        pairs = self._current_pairs()
        if not pairs:
            return False
        mesh = self._mesh_or_default()
        if len(pairs) > mesh.devices.size * max(self.max_slots, 1):
            # packing bound (not a one-segment-per-device cap)
            self.staging_denied_reason = "slots_exceeded"
            return False
        key = self._key_for(pairs)
        # the "or executor is None" leg self-heals any state where the
        # staged key survived but the executor didn't (an eviction
        # racing an install): the next query restages instead of being
        # stuck demoted until the segment set changes
        if key != self._staged_key or self._executor is None:
            if self._stage_probing:
                # single-flight restage probe: a post-fault restage
                # attempt is in flight on a peer — don't pile onto the
                # lock behind a staging that may fault again; serve the
                # host rung until the probe commits (racy read: worst
                # case we wait on the lock like any cold staging)
                self.staging_denied_reason = "staging_fault"
                return False
            with self._stage_lock:
                executor = self._executor
                if key == self._staged_key and executor is not None:
                    # another query staged this exact segment set while
                    # we waited — reuse its generation
                    executor.touch()
                    return True
                if _time.monotonic() < self._staging_fault_until:
                    # a concurrent attempt faulted while we waited
                    self.staging_denied_reason = "staging_fault"
                    return False
                codec = self.svc.postings_codec_pref
                # ---- delta paths (ISSUE 20): tombstone a delete /
                # append new segments into free slots, keeping the
                # collective geometry — the rebuild below becomes the
                # FALLBACK (slots exhausted, tile-geometry mismatch,
                # codec change), not the default
                old = self._executor
                if (old is not None and self._staged_key is not None
                        and not self._staging_faulted
                        and self._delta_enabled()):
                    delta = self._classify_delta(old, pairs, codec)
                    if delta is not None:
                        handled = self._apply_delta(old, delta, key)
                        if handled is not None:
                            return handled
                return self._stage_rebuild(mesh, pairs, key, codec)
        else:
            executor = self._executor
            if executor is not None:
                executor.touch()
        return self._executor is not None

    def _stage_rebuild(self, mesh, pairs, key, codec,
                       reason: Optional[str] = None) -> bool:
        """Full-generation build + install (caller holds _stage_lock).
        The pre-ISSUE-20 default, now the delta paths' fallback — and
        the compaction pass's restage (reason="compaction")."""
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.common.staging import run_staged

        # thread-local hygiene (PR-9 bug class): a fresh staging
        # attempt — reset before any denial below (also covers the
        # compaction thread entering via restage_for_compaction)
        self.staging_denied_reason = None
        n_dev = mesh.devices.size
        spd = max(1, -(-len(pairs) // n_dev))
        if self._delta_enabled() and spd < max(self.max_slots, 1):
            # slot-allocator headroom (ISSUE 20): spare slots for ONE
            # refresh's worth of appended segments (a refresh seals at
            # most one per shard) so the NEXT refresh delta-appends
            # instead of rebuilding — bounded by the packing limit
            extra = max(1, -(-len(self.svc.shards) // n_dev))
            spd = min(spd + extra, max(self.max_slots, 1))
        n_slots = spd * n_dev
        # HBM budget gate (search.memory.hbm_budget_bytes): the
        # gate uses a cheap per-slot estimate — the ledger
        # records the EXACT bytes once staged. Denial demotes
        # this query (and every one until the budget frees) to
        # the host rung with ladder decision reason hbm_budget
        # — degrade, never 5xx.
        estimate = n_slots * max(
            seg.block_docs.nbytes + seg.block_tfs.nbytes
            + seg.norms.nbytes + seg.nd_pad + 1
            for _sid, seg in pairs)
        if not memory_accountant().try_reserve(self.svc.name,
                                               estimate):
            self.staging_denied_reason = "hbm_budget"
            return False
        if reason is None:
            reason = self._restage_reason(self._staged_key, key,
                                          self._executor, n_slots)
        if self._staging_faulted:
            self._stage_probing = True
        old = self._executor
        # construct UNARMED (not yet evictable), install, THEN
        # arm: a budget eviction firing mid-construction would
        # otherwise run _drop_staging against the PREVIOUS
        # generation and the install below would pin a staged
        # key whose executor is gone (see make_evictable).
        # The construction is one transactional staging attempt
        # (register-then-commit: a constructor fault registers
        # nothing) run through the classified retry loop —
        # transient device faults back off and retry, terminal
        # faults bench the staging AND quarantine the kernel
        # plane with reason staging_fault. The retry budget is
        # the PROCESS-level config (node file + live cluster
        # updates via configure_staging_retry) — NOT the index's
        # create-time Settings snapshot, which would freeze it
        # against later dynamic updates.
        from elasticsearch_tpu.common.errors import \
            TaskCancelledException
        from elasticsearch_tpu.search.cancellation import \
            TimeExceededException

        try:
            staged = run_staged(
                lambda: MeshPlanExecutor(
                    [seg for _, seg in pairs], mesh,
                    postings_codec=codec,
                    index_name=self.svc.name,
                    stage_reason=reason,
                    slots_per_dev=spd),
                index=self.svc.name, kind="mesh_slot_tables",
                plane="mesh")
        except (TaskCancelledException, TimeExceededException):
            raise  # PR-4 contract: caller owns partial/cancel —
            # never bench the staging for a dead query
        except Exception:  # noqa: BLE001 — terminal classified
            # staging fault: bench the staging for the cooldown
            # and quarantine the plane so _stats planes tells
            # staging_fault from kernel_fault (docs/RESILIENCE.md)
            _plane_logger.warning(
                "[%s] mesh staging failed; serving from the host "
                "rung for %.1fs (reason staging_fault)",
                self.svc.name, self.plane_health.cooldown_s,
                exc_info=True)
            self._staging_faulted = True
            self._staging_fault_until = (
                _time.monotonic() + self.plane_health.cooldown_s)
            self.plane_health.record_failure(
                "mesh_pallas", reason="staging_fault")
            self.staging_denied_reason = "staging_fault"
            return False
        finally:
            self._stage_probing = False
        staged.pairs = pairs
        if old is not None:
            old.release()
        self._pairs = pairs
        self._executor = staged
        self._staged_key = key
        self._staging_faulted = False
        self._staging_fault_until = 0.0
        staged.make_evictable(self._drop_staging)
        return True

    def staging_slot_stats(self) -> Optional[dict]:
        """Live-generation slot occupancy (ISSUE 20): per-device free
        slot capacity + per-slot tombstone density — the _cat/staging
        operator surface and the compaction trigger's inputs. None when
        nothing is staged."""
        executor = self._executor
        if executor is None:
            return None
        slots = []
        for slot, (sid, seg) in enumerate(executor.pairs):
            total = int(seg.num_docs)
            live = int(seg.live_doc_count)
            slots.append({
                "slot": slot, "shard": int(sid), "segment": seg.name,
                "docs": total, "live": live,
                "tombstone_density": (round(1.0 - live / total, 4)
                                      if total else 0.0),
            })
        free = executor.free_slots()
        return {
            "n_slots": executor.n_slots,
            "slots_per_device": executor.slots_per_dev,
            "free_slots": free,
            "free_slots_per_device": round(free / executor.n_dev, 2),
            "slots": slots,
        }

    def note_compaction_run(self) -> None:
        with self._counter_lock:
            self.compaction_runs_total += 1

    def restage_for_compaction(self) -> bool:
        """Background slot compaction's restage (ISSUE 20): build a
        FRESH generation over the current segment set with fresh slot
        headroom, classified ``compaction`` — merges sparse slots into
        fresh ones and releases the old generation. Off the query path
        (the owner's single-flight pass calls it); ledger-exact through
        the same register-then-commit rebuild as any staging."""
        self.staging_denied_reason = None
        pairs = self._current_pairs()
        mesh = self._mesh_or_default()
        if not pairs:
            return False
        if len(pairs) > mesh.devices.size * max(self.max_slots, 1):
            self.staging_denied_reason = "slots_exceeded"
            return False
        key = self._key_for(pairs)
        with self._stage_lock:
            if self._executor is None:
                return False  # nothing staged: the next query goes cold
            return self._stage_rebuild(mesh, pairs, key,
                                       self.svc.postings_codec_pref,
                                       reason="compaction")

    @staticmethod
    def _needs_counts(q) -> bool:
        """Cheap body-level pre-check for the Q==1 pruned fast path:
        queries carrying minimum_should_match / operator clauses are
        likely to need the dense-counts kernel variant, which query_batch
        rejects AFTER building every shard's plan — skipping them here
        avoids paying that planning twice (false positives only cost the
        fast path, never correctness)."""
        if isinstance(q, dict):
            return any(k in ("minimum_should_match", "operator")
                       or IndexMeshSearch._needs_counts(v)
                       for k, v in q.items())
        if isinstance(q, list):
            return any(IndexMeshSearch._needs_counts(v) for v in q)
        return False

    def _pruning_config(self):
        """(enabled, probe_tiles) from the live settings — block-max
        pruned scoring is dynamic (search.pallas.pruning.*,
        docs/PRUNING.md)."""
        live = self.svc.live
        enabled = live.get_bool("search.pallas.pruning.enabled", False)
        # brownout step 1 (ISSUE 12, docs/OVERLOAD.md): under admission-
        # queue pressure the overload plane forces pruned / gte-totals
        # eligibility — cheaper tiles before shedding features — and
        # releases it as the queue drains
        if not enabled and self.svc.admission.brownout_forces_pruning:
            enabled = True
        probe = live.get_int("search.pallas.pruning.probe_tiles", 8)
        if probe not in (2, 4, 8, 16, 32):
            probe = 8
        return bool(enabled), probe

    def _fused_aggs_enabled(self) -> bool:
        """search.aggs.fused resolution (docs/AGGS.md): an explicit
        cluster-level search.aggs.fused wins, then the index's
        index.search.aggs.fused ("default" follows the node), then the
        seeded node default (on)."""
        if self.svc.cluster_explicit.get("search.aggs.fused") is None:
            # the index's key has a name of its own, so this one reader
            # has to ask whether the cluster layer holds the node key
            idx = self.svc.settings.get_str("index.search.aggs.fused",
                                            "default")
            if idx in ("true", "false"):
                return idx == "true"
        return self.svc.live.get_bool("search.aggs.fused", True)

    def _note_agg_fallback(self, reason: str, n: int = 1) -> None:
        with self._counter_lock:
            self.agg_host_fallback_total += n
            self.agg_host_fallback_by_reason[reason] = \
                self.agg_host_fallback_by_reason.get(reason, 0) + n

    def _note_fused_aggs(self, plans) -> None:
        """Queries served fused, and their bucket counts on either side
        of ``fused_aggs.DENSE_COUNT_MAX_BUCKETS``."""
        from elasticsearch_tpu.search.fused_aggs import (
            DENSE_COUNT_MAX_BUCKETS,
        )

        sizes = [op[2] for plan in plans for op in plan.statics
                 if op[0] == "bucket"]
        dense = sum(nb <= DENSE_COUNT_MAX_BUCKETS for nb in sizes)
        with self._counter_lock:
            self.agg_fused_query_total += len(plans)
            self.agg_bucket_dense_total += dense
            self.agg_bucket_product_total += len(sizes) - dense

    def _resolve_fused_aggs(self, agg_specs, executor,
                            tracer=NULL_TRACER):
        """(FusedAggPlan | None, fallback reason | None) for a mesh-
        served query's agg set — all-or-nothing (docs/AGGS.md). A
        terminal doc-value staging fault demotes the AGGS (not the
        query) to the host reduce (reason ``staging_fault``, classified
        inside resolve_fused_aggs around the staging step only): the
        scoring launch proceeds either way."""
        if not self._fused_aggs_enabled():
            return None, "disabled"
        from elasticsearch_tpu.search.fused_aggs import resolve_fused_aggs

        try:
            return resolve_fused_aggs(agg_specs, executor, tracer)
        except Exception:  # noqa: BLE001 — defensive: an unexpected
            # RESOLUTION error (not a device fault — those classify as
            # staging_fault inside resolve_fused_aggs) must degrade to
            # the host reduce, visibly labeled as a resolver defect
            # rather than device-fault telemetry
            _plane_logger.warning(
                "[%s] fused-agg resolution raised; aggregations serve "
                "from the host reduce", self.svc.name, exc_info=True)
            return None, "resolve_error"

    def _knn_config(self):
        """(enabled, tile_sub preference) from the live settings —
        search.knn.* is dynamic (docs/VECTOR.md)."""
        from elasticsearch_tpu.ops.pallas_knn import (
            DEFAULT_KNN_SUB,
            VALID_KNN_SUBS,
        )

        enabled = self.svc.live.get_bool("search.knn.enabled", True)
        sub = self.svc.live.get_int("search.knn.tile_sub", DEFAULT_KNN_SUB)
        if sub not in VALID_KNN_SUBS:
            sub = DEFAULT_KNN_SUB
        return bool(enabled), int(sub)

    def query_knn(self, spec: dict, k: int, deadline=None,
                  stats=None, tracer=None) -> Optional[dict]:
        """One kNN query on the mesh MXU plane (the Q == 1 form of
        query_knn_batch). Returns {total, refs, max_score, plane} or
        None when ineligible (callers run the host plan-node rung)."""
        out = self.query_knn_batch([spec], [max(k, 1)], deadline=deadline,
                                   stats=[stats], tracers=[tracer])
        return out[0] if out is not None else None

    def query_knn_batch(self, specs: List[dict], ks: List[int],
                        deadline=None,
                        stats: Optional[list] = None,
                        tracers: Optional[list] = None) -> Optional[list]:
        """Cross-query micro-batching on the kNN MXU plane: Q concurrent
        vector queries against ONE dense_vector field scored by ONE
        batched ``knn_score_tiles`` launch inside one shard_map program —
        the embedding matrix streams out of HBM once for the whole batch
        (the q_batch contract the MicroBatcher feeds, exactly like the
        BM25 rung). Returns one {total, refs, max_score, plane} dict per
        member, or None when the batch can't run here. A plane FAULT
        quarantines mesh_pallas exactly ONCE for the whole batch.
        ``stats``: one request-body "stats" groups list per member (the
        per-shard group counters must not depend on which plane served
        the query)."""
        if self.plane_pref not in ("auto", "pallas"):
            return None
        # single-flight admission (ISSUE 10): after a quarantine's
        # cooldown exactly ONE batch probes the plane; peers serve the
        # healthy rung until the probe commits or fails
        adm = self.plane_health.admit("mesh_pallas")
        if not adm:
            self._note("mesh_pallas", "quarantined", len(specs))
            return None
        try:
            return self._query_knn_batch_admitted(specs, ks, deadline,
                                                  stats, tracers)
        finally:
            if adm == "probe":
                # idempotent: a served batch already re-opened the plane
                # (note_success) and a fault re-benched it
                self.plane_health.release_probe("mesh_pallas")

    def _query_knn_batch_admitted(self, specs, ks, deadline, stats,
                                  tracers) -> Optional[list]:
        from elasticsearch_tpu.common.errors import MapperParsingException
        from elasticsearch_tpu.index.segment import next_pow2
        from elasticsearch_tpu.mapper.field_types import DenseVectorFieldType
        from elasticsearch_tpu.ops import pallas_knn as pkn
        from elasticsearch_tpu.ops import pallas_scoring as psc
        from elasticsearch_tpu.search.service import DocRef
        from elasticsearch_tpu.testing.disruption import (
            on_kernel_launch,
            on_plane_execute,
        )

        from elasticsearch_tpu.search.telemetry import (
            NULL_TRACER,
            QueryTracer,
        )

        if len(self.svc.shards) < 2:
            return None
        enabled, sub_pref = self._knn_config()
        if not enabled:
            self._note("host", "knn_disabled", len(specs))
            return None
        # shared batch tracer: the launch's phase spans are folded into
        # every member tracer at the end (each member waited on them)
        bt = (QueryTracer() if any(getattr(t, "enabled", False)
                                   for t in (tracers or [])) else NULL_TRACER)
        # field uniformity + request validation OUTSIDE the fault-
        # recording try: a malformed spec (unknown field, wrong dims) is
        # a REQUEST error the serial path owns with its own 4xx, never a
        # plane fault to quarantine on (same split as query_batch)
        try:
            fields = {str(spec["field"]) for spec in specs}
            if len(fields) != 1:
                return None
            field = next(iter(fields))
            ft = self.svc.mapper_service.field_type(field)
            if not isinstance(ft, DenseVectorFieldType):
                return None
            # incl. NaN/inf: the serial path owns the 400 (a NaN would
            # poison scores and drive the kernel's tie-select past the
            # doc range)
            qvecs = [ft.parse_vector(spec["query_vector"])
                     for spec in specs]
        except (KeyError, TypeError, MapperParsingException):
            return None
        if deadline is not None:
            deadline.checkpoint()
        # (``staging.knn_embeddings`` opens under this parent only on
        # the request that stages the field's embeddings)
        t_stage = bt.start_parent("staging")
        try:
            if not self._ensure_staged():
                self._note("host", self.staging_denied_reason
                           or "knn_staging_unavailable", len(specs))
                return None
            executor = self._executor
            if executor is None:
                self._note("host", "knn_staging_unavailable", len(specs))
                return None
            session = executor.ensure_knn(field, ft.dims, ft.similarity,
                                          tracer=bt)
            if session is None:
                reason = executor.kernel_denied_reason
                self._note("host", reason or "knn_staging_unavailable",
                           len(specs))
                if reason == "staging_fault":
                    # a terminal classified staging fault: bench the
                    # plane so peers don't re-pay the staging attempt per
                    # query (the post-cooldown probe restages —
                    # docs/RESILIENCE.md)
                    self.plane_health.record_failure(
                        "mesh_pallas", reason="staging_fault")
                return None
            q_batch = len(specs)
            q_pad = next_pow2(q_batch)
            kk = next_pow2(max(max(ks), 1))
            d_pad = session["d_pad"]
            nd_knn = session["nd_pad"]
            g = psc.tile_geometry(
                nd_knn, pkn.knn_tile_sub(nd_knn, d_pad, sub_pref))
            qmat = np.zeros((q_pad, d_pad), np.float32)
            for q, qvec in enumerate(qvecs):
                qmat[q] = pkn.normalize_query(qvec, ft.similarity, d_pad)
        finally:
            bt.stop("staging", t_stage)
        from elasticsearch_tpu.common.errors import TaskCancelledException
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )

        try:
            on_plane_execute(self.svc.name, "mesh_pallas")
            run = _mesh_knn_program(
                executor.mesh, executor.slots_per_dev,
                q_pad, kk, g.tile_sub, d_pad, nd_knn, ft.similarity,
                session["mode"] == "interpret")
            args = (session["emb"], session["scale"], session["mask"],
                    jnp.asarray(qmat))
            if deadline is not None:
                # a first call compiles the program (seconds): honor the
                # deadline before committing to the launch
                deadline.checkpoint()
            on_kernel_launch(self.svc.name, "knn")
            outs = _launch_locked(bt, run, *args)
        except (PlanStructureMismatch, NotImplementedError):
            self._note("mesh_pallas", "shape_mismatch", q_batch)
            return None  # shape ineligibility: next rung, no penalty
        except (TaskCancelledException, TimeExceededException):
            raise  # PR-4 contract: the caller owns partial/cancel
        except Exception:  # noqa: BLE001 — plane fault, not a shape miss
            _plane_logger.warning(
                "[%s] kNN execution plane [mesh_pallas] failed; "
                "quarantined for %.1fs", self.svc.name,
                self.plane_health.cooldown_s, exc_info=True)
            self.plane_health.record_failure("mesh_pallas")
            self._note("mesh_pallas", "fault", q_batch)
            return None
        # the launch committed: fully re-open the plane (a probe's
        # success ends the quarantine — single-flight contract)
        self.plane_health.note_success("mesh_pallas")
        with self._counter_lock:
            self.query_total += q_batch
            self.pallas_query_total += q_batch
            self.knn_query_total += q_batch
            if q_batch > 1:
                self.batched_launch_total += 1
                self.batched_query_total += q_batch
        self._note("mesh_pallas",
                   "knn_served_batched" if q_batch > 1 else "knn_served",
                   q_batch)
        # the whole batch streams the bf16 embedding matrix of each
        # slot whose pass ran (one that holds a live vector) once
        scanned = session["slots_scanned"]
        launch_adds = {"embedding_bytes_streamed":
                       scanned * nd_knn * d_pad * 2}
        with self._counter_lock:
            self.knn_slots_scanned_total += scanned * q_batch
            self.embedding_bytes_streamed_total += \
                launch_adds["embedding_bytes_streamed"]
        # (no finally: an exception in here ends the request)
        t_merge = bt.start_parent("merge")
        # (the batch's answers are ONE array, a row a query: one transfer)
        packed = _fetch(bt, outs, self._telemetry)
        t_assemble = bt.start("merge.assemble")
        results = []
        for q in range(q_batch):
            for sid in self.svc.shards:
                self.svc.shards[sid].searcher.note_query(
                    stats[q] if stats is not None else None)
            keys, slots, docs, total, scores, _ = _unpack_answer(packed[q])
            refs = []
            max_score = None
            for i in range(min(ks[q], len(keys))):
                if keys[i] == -np.inf:
                    continue
                sid, seg = executor.pairs[int(slots[i])]
                score = float(scores[i])
                refs.append(DocRef(sid, seg.name, int(docs[i]), score, ()))
                if max_score is None:
                    max_score = score
            results.append({"total": int(total), "refs": refs,
                            "max_score": max_score,
                            "plane": "mesh_pallas"})
        bt.stop("merge.assemble", t_assemble)
        bt.stop("merge", t_merge)
        tel = self._telemetry
        if tel is not None:
            tel.add_counters(launch_adds)
        for q, tr in enumerate(tracers or []):
            if tr is not None and getattr(tr, "enabled", False):
                tr.merge_from(bt)
                tr.annotate("batch_size", q_batch)
                tr.annotate("batch_member_index", q)
                for key, v in launch_adds.items():
                    tr.annotate(key, int(v))
        return results

    def _sort_plan(self, body: dict, executor: "MeshPlanExecutor",
                   tracer=NULL_TRACER):
        """Resolve the request's sort to staged mesh key columns.

        Returns (sort_keys, sort_spec) — sort_keys None for relevance —
        or the string "fallback" when the sort can't run on the mesh."""
        from elasticsearch_tpu.search.service import normalize_sort

        sort_spec = normalize_sort(body.get("sort"))
        if sort_spec is None:
            return None, None
        if len(sort_spec) != 1:
            return "fallback", None
        field, order, missing = sort_spec[0]
        if not isinstance(field, str) or field == "_geo_distance":
            return "fallback", None
        # (a single _score sort never reaches here: normalize_sort
        # collapses it to relevance ranking already)
        if isinstance(missing, dict):
            return "fallback", None
        if isinstance(missing, str) and missing not in ("_last", "_first"):
            return "fallback", None  # host path owns the error shape
        if isinstance(missing, (int, float)) and not isinstance(
                missing, bool):
            # the fill participates in the f32 rank key like any value
            if float(np.float32(missing)) != float(missing):
                return "fallback", None
        keys = executor.ensure_sort_column(field, order, missing, tracer)
        if keys is None:
            return "fallback", None
        return keys, sort_spec

    def _search_after_key(self, search_after, sort_spec,
                          sort_keys, executor) -> Optional[float]:
        """Map the request's search_after cursor to the oriented-key
        space of the staged rank column (strictly-after == key < value),
        or None when the cursor can't cut exactly on the mesh."""
        import bisect

        if not isinstance(search_after, (list, tuple)):
            return None
        if len(search_after) != 1:
            return None  # must match the (single-field) sort length
        after = search_after[0]
        big = 3.0e38
        if sort_spec is None:
            # relevance paging: scores strictly below the cursor score
            try:
                v = float(after)
            except (TypeError, ValueError):
                return None
            if float(np.float32(v)) != v:
                return None  # f32 rounding could move the boundary
            return v
        _field, order, missing = sort_spec[0]
        meta = executor.sort_meta.get(sort_keys[0]) or {}
        vocab = meta.get("vocab")
        if vocab is not None:
            if after is None:
                # a null cursor is a missing-value doc's rendered key:
                # anchor at the same fill ensure_sort_column staged
                if missing == "_first":
                    anchor = big if order == "desc" else -big
                else:
                    anchor = -big if order == "desc" else big
            else:
                # anchor the cursor in global-ordinal space; one that
                # lies between two entries lands at bisect-position - 0.5
                # so the strict cut stays exact either way
                if isinstance(vocab, np.ndarray):
                    try:
                        s = float(after)
                    except (TypeError, ValueError):
                        return None
                else:
                    s = str(after)
                pos = bisect.bisect_left(vocab, s)
                present = pos < len(vocab) and vocab[pos] == s
                anchor = float(pos) if present else pos - 0.5
                if float(np.float32(anchor)) != anchor:
                    return None  # pos-0.5 loses exactness past 2^23
            oriented = anchor if order == "desc" else -anchor
            return float(np.clip(oriented, -big, big))
        if after is None:
            from elasticsearch_tpu.search.service import _missing_fill

            anchor = _missing_fill(missing, order)
        else:
            try:
                anchor = float(after)
            except (TypeError, ValueError):
                return None
            if float(np.float32(anchor)) != anchor:
                return None
        oriented = anchor if order == "desc" else -anchor
        return float(np.clip(oriented, -big, big))

    def query(self, body: dict, k: int, deadline=None, tracer=None):
        """Returns {total, refs, max_score, aggregations,
        terminated_early} or None if ineligible.
        deadline: SearchDeadline — checkpointed between staging steps and
        plane attempts (timeout raises TimeExceededException for the
        caller's partial-result path; cancellation raises
        TaskCancelledException).
        tracer: QueryTracer — phase spans (parse_rewrite / plan_build /
        staging / kernel / merge) recorded against whichever plane ends
        up serving (docs/OBSERVABILITY.md)."""
        from elasticsearch_tpu.search.aggregations import (
            SegmentView,
            parse_aggs,
            run_aggregations,
        )
        from elasticsearch_tpu.search.query_dsl import (
            ShardQueryContext,
            parse_query,
        )
        from elasticsearch_tpu.search.service import (
            _STR_SENTINEL_HIGH,
            _STR_SENTINEL_LOW,
            DocRef,
            _normalize_rescore,
        )

        from elasticsearch_tpu.search.telemetry import NULL_TRACER

        if tracer is None:
            tracer = NULL_TRACER
        body = body or {}
        if any(body.get(key) is not None for key in self.UNSUPPORTED):
            self._note("host", "unsupported_body")
            return None
        if len(self.svc.shards) < 2:
            self._note("host", "single_shard")
            return None  # single shard: host path is already one program
        if any(getattr(self.svc.shards[s].engine, "index_sort", None)
               for s in self.svc.shards):
            self._note("host", "index_sorted")
            return None  # index-sorted early termination beats top-k
        if deadline is not None:
            deadline.checkpoint()
        if not self._ensure_staged():
            self._note("host", self.staging_denied_reason
                       or "staging_unavailable")
            return None
        executor = self._executor
        if executor is None:
            self._note("host", "staging_unavailable")
            return None
        if deadline is not None:
            deadline.checkpoint()  # staging can compile/transfer
        settings = getattr(self.svc, "settings", None)
        if settings is not None:
            # the cooldown is a DYNAMIC index setting: re-read per query
            # so a live settings update takes effect without a restart
            self.plane_health.cooldown_s = settings.get_time(
                "index.search.plane_quarantine.cooldown", 60.0)
        pruning_on, _probe = self._pruning_config()
        if (pruning_on and isinstance(body.get("query"), dict)
                and all(key in self.BATCHABLE_KEYS for key in body)
                and int(body.get("size", 10) if body.get("size")
                        is not None else 10) > 0
                and not self._needs_counts(body.get("query"))
                and self.plane_pref in ("auto", "pallas")
                and self.plane_health.available("mesh_pallas")):
            # block-max pruned single-query fast path (docs/PRUNING.md):
            # a plain relevance-ranked query rides the batched rung's
            # pruned program with Q == 1, skipping tiles whose bound
            # cannot beat the running top-k threshold. Anything needing
            # every tile's dense output (aggs, sort, counts, rescore)
            # fails the key filter above and executes exhaustively.
            out = self.query_batch([body], deadline=deadline,
                                   tracers=[tracer])
            if out is not None:
                r = out[0]
                return {"total": r["total"], "refs": r["refs"],
                        "max_score": r["max_score"], "aggregations": None,
                        "terminated_early": None, "plane": r["plane"],
                        "pruned": r.get("pruned")}
        t_parse = tracer.start("parse_rewrite")
        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        sort_keys = sort_spec = agg_plan = agg_reason = None
        if agg_specs or body.get("sort") is not None:
            # what a sort or an aggregation reads besides the base slot
            # tables is staged once a generation: the leaves
            # ``staging.sort_column`` and ``staging.doc_values`` open
            # under this parent only on the request that stages
            tracer.stop("parse_rewrite", t_parse)
            t_stage = tracer.start_parent("staging")
            try:
                sort_keys, sort_spec = self._sort_plan(body, executor,
                                                       tracer)
                # fused on-device aggregations (ISSUE 13, docs/AGGS.md):
                # when every spec is fused-eligible the agg reduction
                # rides INSIDE the mesh program (doc-value columns staged
                # per slot, ledger kind doc_values) and the [n_slots,
                # nd1] matched masks never cross to the host; otherwise
                # the previous with_views host reduce serves, counted
                # per fallback reason
                if agg_specs and sort_keys != "fallback":
                    agg_plan, agg_reason = self._resolve_fused_aggs(
                        agg_specs, executor, tracer)
            finally:
                tracer.stop("staging", t_stage)
            t_parse = tracer.start("parse_rewrite")
        if sort_keys == "fallback":
            self._note("host", "sort_ineligible")
            return None

        features = set()
        scalars: Dict[str, float] = {}
        min_score = body.get("min_score")
        if min_score is not None:
            ms = float(min_score)
            if float(np.float32(ms)) != ms:
                self._note("host", "feature_ineligible")
                return None  # f32 compare could move the cut boundary
            features.add("min_score")
            scalars["min_score"] = ms
        slice_col = None
        slice_spec = body.get("slice")
        if slice_spec is not None:
            if (not isinstance(slice_spec, dict)
                    or "id" not in slice_spec or "max" not in slice_spec):
                return None  # host path owns the error shape
            try:
                slice_col = executor.ensure_slice_column(
                    slice_spec, [sid for sid, _seg in executor.pairs],
                    len(self.svc.shards))
            except Exception:  # noqa: BLE001 — host path owns errors
                return None
            if slice_col is None:
                return None
        search_after = body.get("search_after")
        if search_after is not None:
            after_key = self._search_after_key(search_after, sort_spec,
                                               sort_keys, executor)
            if after_key is None:
                self._note("host", "feature_ineligible")
                return None
            features.add("search_after")
            scalars["search_after"] = after_key
        terminate_after = body.get("terminate_after")
        rescore_static = None
        rs_qb = None
        rescore_specs = _normalize_rescore(body.get("rescore"))
        if rescore_specs and sort_spec is None:
            if len(rescore_specs) != 1:
                self._note("host", "feature_ineligible")
                return None  # chained rescorers: host path
            spec = rescore_specs[0]
            rescore_static = (spec["window_size"], spec["score_mode"])
            scalars["query_weight"] = spec["query_weight"]
            scalars["rescore_query_weight"] = spec["rescore_query_weight"]
            rs_qb = parse_query(spec["rescore_query"])
        # (rescore with an explicit sort is a no-op on the host path too)

        qb = parse_query(body.get("query"))
        pf_qb = (parse_query(body["post_filter"])
                 if body.get("post_filter") else None)
        tracer.stop("parse_rewrite", t_parse)
        # plane ladder: try the tile-kernel plane first (one fast plane
        # for distributed queries — the reference runs the same BulkScorer
        # hot loop on every shard), falling back to the scatter mesh when
        # the kernel can't serve this query shape, then to the host path.
        # A plane under quarantine (plane_health) is skipped outright —
        # its last failure already paid the cost — and probed again once
        # the cooldown elapses.
        from elasticsearch_tpu.common.errors import TaskCancelledException
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )
        from elasticsearch_tpu.testing.disruption import (
            on_kernel_launch,
            on_plane_execute,
        )

        # single-flight admission per plane (ISSUE 10): "open" attempts
        # freely; "probe" is the one post-cooldown trial whose admission
        # must be handed back if it bails without executing; "" skips
        admissions: Dict[str, str] = {}
        kernel_session = None
        if self.plane_pref in ("auto", "pallas"):
            admissions["mesh_pallas"] = self.plane_health.admit(
                "mesh_pallas")
            if admissions["mesh_pallas"]:
                kernel_session = executor.ensure_kernel()
                if (kernel_session is None
                        and executor.kernel_denied_reason):
                    # HBM budget / staging fault turned the kernel
                    # staging away: the ladder's next rung serves
                    # (docs/OBSERVABILITY.md)
                    reason = executor.kernel_denied_reason
                    self._note("mesh_pallas", reason)
                    if reason == "staging_fault":
                        self.plane_health.record_failure(
                            "mesh_pallas", reason="staging_fault")
            else:
                self._note("mesh_pallas", "quarantined")
        attempts = []
        if kernel_session is not None:
            attempts.append(("mesh_pallas", kernel_session))
        if self.plane_pref != "pallas":
            admissions["mesh"] = self.plane_health.admit("mesh")
            if admissions["mesh"]:
                # plane=pallas pins "kernel or host": when the kernel is
                # unavailable OR quarantined, the ladder's next rung is
                # the host path, never the scatter mesh the operator
                # excluded
                attempts.append(("mesh", None))
        outs = None
        used_pallas = False
        try:
            for plane, session in attempts:
                if deadline is not None:
                    deadline.checkpoint()
                try:
                    on_plane_execute(self.svc.name, plane)
                    t_plan = tracer.start("plan_build")
                    plans = []
                    pf_plans = [] if pf_qb is not None else None
                    rs_plans = [] if rs_qb is not None else None
                    ctxs = {}
                    for sid, seg in executor.pairs:
                        shard = self.svc.shards[sid]
                        ctx = ShardQueryContext(shard.mapper_service,
                                                engine=shard.engine)
                        # mesh plans must stack across shards: scorer
                        # nodes keep one skeleton on every shard, and
                        # kernel nodes defer table geometry to
                        # harmonization below
                        ctx.for_mesh = True
                        ctx.mesh_kernel = session
                        # numeric filters read columns this generation
                        # stages once (ensure_numeric_column)
                        ctx.mesh_columns = executor
                        ctxs[sid] = ctx
                        plans.append(qb.to_plan(ctx, seg))
                        # post_filter/rescore plans stay on scatter
                        # nodes: they gate/adjust, the main scorer is
                        # the hot loop
                        ctx.mesh_kernel = None
                        if pf_qb is not None:
                            pf_plans.append(pf_qb.to_plan(ctx, seg))
                        if rs_qb is not None:
                            rs_plans.append(rs_qb.to_plan(ctx, seg))
                        # (the context outlives the launch: the host
                        # fallback of the aggregations plans a ``filter``
                        # body with it, over the segment's own arrays)
                        ctx.mesh_columns = None
                    used_pallas = False
                    if session is not None:
                        used_pallas = executor.harmonize_kernel_nodes(
                            plans) > 0
                    tracer.stop("plan_build", t_plan)
                    on_kernel_launch(self.svc.name, plane)
                    outs = executor.execute(
                        plans, k, sort_keys=sort_keys,
                        with_views=bool(agg_specs) and agg_plan is None,
                        pf_plans=pf_plans,
                        rs_plans=rs_plans, scalars=scalars,
                        features=frozenset(features), slice_col=slice_col,
                        rescore_static=rescore_static, tracer=tracer,
                        agg_static=(agg_plan.statics
                                    if agg_plan is not None else ()),
                        telemetry=self._telemetry)
                    # the plane served: fully re-open it (ends a probe's
                    # quarantine — single-flight contract)
                    self.plane_health.note_success(plane)
                    break
                except (PlanStructureMismatch, NotImplementedError):
                    self._note(plane, "shape_mismatch")
                    continue  # shape ineligibility: next plane (no penalty)
                except (TaskCancelledException, TimeExceededException):
                    raise
                except Exception:  # noqa: BLE001 — plane fault, not a
                    # shape miss: compile error / device OOM / runtime
                    # fault (or injected scheme) — bench the plane for
                    # the cooldown and serve from the next rung
                    _plane_logger.warning(
                        "[%s] execution plane [%s] failed; quarantined "
                        "for %.1fs", self.svc.name, plane,
                        self.plane_health.cooldown_s, exc_info=True)
                    self.plane_health.record_failure(plane)
                    self._note(plane, "fault")
                    continue
        finally:
            # any probe admission not consumed by note_success /
            # record_failure (shape fallback, deadline, early bail)
            # hands its single-flight slot back — idempotent after
            # either of those
            for plane, adm in admissions.items():
                if adm == "probe":
                    self.plane_health.release_probe(plane)
        if outs is None:
            self._note("host", "no_mesh_plane")
            return None
        # (no finally: an exception in here ends the request)
        t_merge = tracer.start_parent("merge")
        # (the answer is ONE array, the total in it: one transfer)
        packed = _fetch(tracer, outs[0], self._telemetry)
        seg_counts = outs[1]
        t_assemble = tracer.start("merge.assemble")
        keys, slots, docs, total, scores, raws = _unpack_answer(packed)
        total = int(total)
        # terminate_after caps per SHARD (each shard's collector stops
        # after N docs) while a mesh device holds one SEGMENT: group the
        # per-device counts by shard before capping — host-path contract
        # (search/service.py query(): cap reported total, set the flag)
        terminated_early = None
        if terminate_after:
            ta = int(terminate_after)
            counts = np.asarray(seg_counts)
            by_shard: Dict[int, int] = {}
            for i, (sid, _seg) in enumerate(executor.pairs):
                by_shard[sid] = by_shard.get(sid, 0) + int(counts[i])
            total = sum(min(c, ta) for c in by_shard.values())
            terminated_early = any(c >= ta for c in by_shard.values())
        with self._counter_lock:
            self.query_total += 1
            if used_pallas:
                self.pallas_query_total += 1
            if sort_keys is not None:
                self.sort_device_query_total += 1
        self._note("mesh_pallas" if used_pallas else "mesh", "served")
        # per-shard search stats stay attributed even though the mesh
        # executes all shards as one program (SearchStats semantics)
        for sid in self.svc.shards:
            self.svc.shards[sid].searcher.note_query(body.get("stats"))
        vocab = None
        if sort_keys is not None:
            vocab = (executor.sort_meta.get(sort_keys[0])
                     or {}).get("vocab")
        refs = []
        max_score = None
        for i, (key, slot, d) in enumerate(zip(keys, slots, docs)):
            if key == -np.inf:
                continue
            sid, seg = executor.pairs[int(slot)]
            score = float(scores[i])
            if sort_keys is None:
                sv = (score,) if rescore_static is not None else ()
            elif vocab is not None:
                # global ordinal back to the term or the stored number;
                # missing-fill sentinels render as the host path's
                # (string sentinels, +/-inf: all serialize to null)
                raw = float(raws[i])
                numeric = isinstance(vocab, np.ndarray)
                if abs(raw) >= 3.0e38:
                    sv = ((np.inf if raw > 0 else -np.inf,) if numeric
                          else (_STR_SENTINEL_HIGH if raw > 0
                                else _STR_SENTINEL_LOW,))
                else:
                    v = vocab[int(round(raw))]
                    sv = (float(v) if numeric else v,)
            else:
                # missing-fill sentinels surface as +/-inf, which
                # fetch_hits renders as null (same as the host path)
                raw = float(raws[i])
                if abs(raw) >= 3.0e38:
                    raw = np.inf if raw > 0 else -np.inf
                sv = (raw,)
            refs.append(DocRef(sid, seg.name, int(d), score, sv))
            if max_score is None and sort_spec is None:
                max_score = score
        tracer.stop("merge.assemble", t_assemble)
        tracer.stop("merge", t_merge)
        aggregations = None
        if agg_specs:
            # ``aggregate.fetch``: what the reduce reads of the program's
            # outputs, device to host (the fused partials, or the masks
            # and scores of the host fallback), every copy asked for
            # before the first is waited for; ``aggregate.finalize``: the
            # reduce on the host
            t_agg = tracer.start_parent("aggregate")
            t = tracer.start("aggregate.fetch")
            agg_outs = jax.device_get(list(outs[2:]))
            t = tracer.switch("aggregate.fetch", t, "aggregate.finalize")
            if agg_plan is not None:
                from elasticsearch_tpu.search.fused_aggs import (
                    finalize_fused,
                )

                aggregations = finalize_fused(agg_plan, agg_outs,
                                              len(executor.pairs))
                self._note_fused_aggs([agg_plan])
                tel = self._telemetry
                if tel is not None:
                    # doc-value column bytes the fused launch read in
                    # place of the host round-trip (docs/AGGS.md)
                    tel.add_counters({
                        "doc_values_bytes_streamed":
                            agg_plan.staged_bytes(executor._seg_staged)})
            else:
                matched_np, scores_np = agg_outs
                views = []
                for i, (sid, seg) in enumerate(executor.pairs):
                    nd1 = seg.nd_pad + 1
                    views.append(SegmentView(
                        seg, matched_np[i, :nd1], ctxs[sid],
                        scores_np[i, :nd1]))
                aggregations = run_aggregations(agg_specs, views)
                self._note_agg_fallback(agg_reason or "field_ineligible")
            tracer.stop("aggregate.finalize", t)
            tracer.stop("aggregate", t_agg)
        return {"total": total, "refs": refs, "max_score": max_score,
                "aggregations": aggregations,
                "terminated_early": terminated_early,
                # which scoring engine the mesh program ran — surfaced as
                # the response's _plane marker and the planes counters
                "plane": "mesh_pallas" if used_pallas else "mesh"}

    # request keys the BATCHED mesh_pallas program covers: plain
    # relevance-ranked queries (the high-QPS traffic shape the batching
    # exists for). Anything richer falls to the host-batched rung, whose
    # per-query pipeline covers the full request surface.
    # ("profile" rides along: a profiled member executes identically —
    # byte-identical hits — and additionally reports its phase spans)
    BATCHABLE_KEYS = frozenset({
        "query", "size", "from", "timeout",
        "allow_partial_search_results", "stats", "profile",
    })

    def query_batch(self, bodies: List[dict],
                    deadline=None,
                    tracers: Optional[list] = None) -> Optional[list]:
        """Cross-query micro-batching on the mesh_pallas rung: Q
        concurrent queries scored by ONE batched kernel launch inside
        one shard_map program (per-tile DMA windows fetched once for the
        whole batch — see ops/pallas_scoring.score_tiles q_batch).

        Returns one {total, refs, max_score, plane} dict per member, or
        None when the batch can't run here (callers fall to the
        host-batched rung). A plane FAULT quarantines mesh_pallas
        exactly ONCE for the whole batch — not Q times.

        deadline: SearchDeadline of the SINGLE-query pruned fast path
        (IndexMeshSearch.query routes through here with Q == 1) —
        checkpointed before table building and before the launch, same
        contract as the serial ladder. Batch callers (search_batch)
        handle per-member deadlines themselves and pass None."""
        if self.plane_pref not in ("auto", "pallas"):
            return None
        # single-flight admission (ISSUE 10): after cooldown exactly
        # ONE batch probes the benched plane; peers serve the next rung
        adm = self.plane_health.admit("mesh_pallas")
        if not adm:
            self._note("mesh_pallas", "quarantined", len(bodies))
            return None
        try:
            return self._query_batch_admitted(bodies, deadline, tracers)
        finally:
            if adm == "probe":
                self.plane_health.release_probe("mesh_pallas")

    def _query_batch_admitted(self, bodies, deadline,
                              tracers) -> Optional[list]:
        from elasticsearch_tpu.index.segment import next_pow2
        from elasticsearch_tpu.ops import pallas_scoring as psc
        from elasticsearch_tpu.search.plan import PallasScoreTermsNode
        from elasticsearch_tpu.search.query_dsl import (
            ShardQueryContext,
            parse_query,
        )
        from elasticsearch_tpu.search.service import DocRef
        from elasticsearch_tpu.search.telemetry import (
            NULL_TRACER,
            QueryTracer,
        )
        from elasticsearch_tpu.testing.disruption import (
            on_kernel_launch,
            on_plane_execute,
        )

        if len(self.svc.shards) < 2:
            return None
        for body in bodies:
            body = body or {}
            if not isinstance(body.get("query"), dict):
                return None
            # agg bodies no longer fail the key filter (ISSUE 13): an
            # agg-carrying member rides the batched DENSE program when
            # its whole agg set is fused-eligible (resolved below)
            if any(key not in self.BATCHABLE_KEYS
                   and key not in ("aggs", "aggregations")
                   for key in body):
                return None
        if any(getattr(self.svc.shards[s].engine, "index_sort", None)
               for s in self.svc.shards):
            return None
        # shared batch tracer: one set of launch-phase spans, folded into
        # every member's tracer below (they all waited on the launch)
        bt = (QueryTracer() if any(getattr(t, "enabled", False)
                                   for t in (tracers or [])) else NULL_TRACER)
        t_stage0 = bt.start("staging")
        if not self._ensure_staged():
            self._note("host", self.staging_denied_reason
                       or "staging_unavailable", len(bodies))
            return None
        executor = self._executor
        if executor is None:
            self._note("host", "staging_unavailable", len(bodies))
            return None
        session = executor.ensure_kernel()
        bt.stop("staging", t_stage0)
        if session is None:
            reason = executor.kernel_denied_reason
            self._note("host", reason or "staging_unavailable",
                       len(bodies))
            if reason == "staging_fault":
                # terminal classified staging fault: quarantine so the
                # next queries skip straight to the healthy rung and the
                # post-cooldown probe restages (docs/RESILIENCE.md)
                self.plane_health.record_failure("mesh_pallas",
                                                 reason="staging_fault")
            return None
        q_batch = len(bodies)
        ks = []
        for body in bodies:
            from_ = int(body.get("from", 0) or 0)
            size = (int(body.get("size"))
                    if body.get("size") is not None else 10)
            ks.append(max(from_ + size, 1))
        # bucket the compiled-program key: batch size is set by arrival
        # timing (2..max_queries) and kk by the members' size params, so
        # raw values would compile a fresh shard_map+kernel program per
        # combination. Pad q_batch to the next power of two (extra weight
        # rows are all-zero = dead queries) and kk likewise — at most
        # ~4x4 program variants instead of one per traffic pattern.
        kk = next_pow2(max(ks))
        q_pad = next_pow2(q_batch)
        geom = session["geom"]
        n_pairs = len(executor.pairs)
        # per-member, per-slot kernel lane sets via the same deferred
        # plan builder the serial mesh path uses — the plan must be
        # EXACTLY one kernel-scored disjunction (no wrapper nodes).
        # Built OUTSIDE the fault-recording try: a malformed member body
        # (parse/mapping error) is a REQUEST error the serial path owns
        # with its own 4xx, never a plane fault to quarantine on — same
        # split as the serial ladder, which parses before its attempts.
        t_plan = bt.start("plan_build")
        try:
            lane_sets = [[None] * q_batch for _ in range(n_pairs)]
            for q, body in enumerate(bodies):
                qb = parse_query(body.get("query"))
                for slot, (sid, seg) in enumerate(executor.pairs):
                    shard = self.svc.shards[sid]
                    ctx = ShardQueryContext(shard.mapper_service,
                                            engine=shard.engine)
                    ctx.for_mesh = True
                    ctx.mesh_kernel = session
                    plan = qb.to_plan(ctx, seg)
                    if (not isinstance(plan, PallasScoreTermsNode)
                            or plan._mesh_lanes is None
                            or plan.with_counts):
                        # minimum_should_match > 1 needs the dense-counts
                        # variant the fused top-k kernel doesn't emit
                        return None
                    lane_sets[slot][q] = plan._mesh_lanes
        except Exception:  # noqa: BLE001 — request-shaped error: serial
            # execution surfaces it per member with the right status
            return None
        bt.stop("plan_build", t_plan)
        # fused aggs for batched members (ISSUE 13, docs/AGGS.md):
        # ALL-or-nothing per batch — if any agg'd member's set is not
        # fused-eligible the whole batch falls to the host rung (whose
        # per-member pipeline owns the full agg surface); heterogeneous
        # eligible bodies each reduce their own specs in the shared
        # dense launch (member isolation)
        member_agg_plans = [None] * q_batch
        agg_members = [bool((b or {}).get("aggs")
                            or (b or {}).get("aggregations"))
                       for b in bodies]
        if any(agg_members):
            if not self._fused_aggs_enabled():
                self._note_agg_fallback("disabled", sum(agg_members))
                return None
            from elasticsearch_tpu.search.aggregations import parse_aggs

            t_aggstage = bt.start("staging")
            try:
                for q, body in enumerate(bodies):
                    if not agg_members[q]:
                        continue
                    body = body or {}
                    try:
                        specs = parse_aggs(body.get("aggs")
                                           or body.get("aggregations"))
                    except Exception:  # noqa: BLE001 — request error:
                        # serial execution surfaces the member's 400
                        return None
                    plan, reason = self._resolve_fused_aggs(specs,
                                                            executor)
                    if plan is None:
                        self._note_agg_fallback(
                            reason or "field_ineligible")
                        return None
                    member_agg_plans[q] = plan
            finally:
                bt.stop("staging", t_aggstage)
        has_aggs = any(p is not None for p in member_agg_plans)
        pruning, probe = self._pruning_config()
        if has_aggs:
            # pruning x aggs mutual exclusion (docs/PRUNING.md): WAND-
            # skipped tiles would corrupt buckets — agg batches always
            # run the exhaustive dense formulation
            pruning = False
        if pruning and any(
                int((b or {}).get("size", 10)
                    if (b or {}).get("size") is not None else 10) <= 0
                for b in bodies):
            # a size:0 member is a total/count-only consumer (_count,
            # agg-less counts): exact totals are the contract
            # (docs/PRUNING.md), so the batch runs exhaustively
            pruning = False
        codec = session.get("codec", "raw")
        pruned_stats = None
        from elasticsearch_tpu.common.errors import TaskCancelledException
        from elasticsearch_tpu.search.cancellation import (
            TimeExceededException,
        )

        if deadline is not None:
            deadline.checkpoint()
        try:
            on_plane_execute(self.svc.name, "mesh_pallas")
            t_stage = bt.start("staging")
            # shared batched tables: per-slot unions on ONE collective
            # geometry (a dense union on ANY slot shrinks everyone's
            # tile); build_tile_tables_batched owns the union/pad
            # contract — same code the host rung runs
            unions = [psc.union_query_lanes(lane_sets[slot])[0]
                      for slot in range(n_pairs)]
            t_pad = max(next_pow2(max(len(u), 1)) for u in unions)
            sub = geom.tile_sub
            if pruning:
                # pruning wants enough tiles to split probe/rest: shrink
                # the tile until the doc space yields at least 2*probe
                # tiles (the 1M bench corpus already has 64 at the
                # default tile — only small corpora shrink). Floor the
                # shrink at sub=8 on real hardware (mosaic sublane
                # granularity; interpret mode has no such constraint),
                # and if even the floor can't yield enough tiles, keep
                # the ORIGINAL geometry and run exhaustively — the
                # ladder's geometry must never degrade for a pruning
                # attempt that then doesn't happen.
                sub_floor = 1 if session["mode"] == "interpret" else 8
                sub_p = sub
                while (sub_p > sub_floor and psc.tile_geometry(
                        geom.nd_pad, sub_p).n_tiles < 2 * probe):
                    sub_p //= 2
                if psc.tile_geometry(geom.nd_pad,
                                     sub_p).n_tiles >= 2 * probe:
                    sub = sub_p
                else:
                    pruning = False  # corpus too small to prune here
            while True:
                g = geom if sub == geom.tile_sub else psc.tile_geometry(
                    geom.nd_pad, sub)
                try:
                    tables = []
                    for slot, (sid, seg) in enumerate(executor.pairs):
                        bmin, bmax = session["meta"][id(seg)][:2]
                        tables.append(psc.build_tile_tables_batched(
                            lane_sets[slot], bmin, bmax, g, t_pad=t_pad))
                    break
                except ValueError:
                    if sub <= 32 or g.tile_sub < sub:
                        return None  # no shared geometry: host rung
                    sub //= 2
            cb = max(t[3] for t in tables)
            live_key = ("k_live_t" if g.tile_sub == geom.tile_sub
                        else executor.ensure_kernel_live(g.tile_sub))
            n_slots = executor.n_slots
            n_tiles = tables[0][0].shape[0]
            rl = np.zeros((n_slots, n_tiles, t_pad), np.int32)
            rh = np.zeros((n_slots, n_tiles, t_pad), np.int32)
            w_all = np.zeros((n_slots, q_pad, t_pad), np.float32)
            for slot in range(n_pairs):
                rl[slot] = tables[slot][0]
                rh[slot] = tables[slot][1]
                w_all[slot, : q_batch] = tables[slot][2]
            # filler slots/queries keep zero tables/weights: their live
            # masks are all-dead and zero weights score nothing
            tps = psc.TILES_PER_STEP
            sharding = executor._sharding
            staged = executor._seg_staged
            corpus = ((staged["k_packed"],) if codec == "packed"
                      else (staged["k_docs"], staged["k_frac"]))
            plans_p = None
            if pruning and n_tiles > probe:
                # per-slot block-max pruning plans (host side: order
                # tiles by bound, split probe/rest) — the threshold
                # exchange itself stays on-device in the program
                plans_p = []
                for slot in range(n_pairs):
                    seg = executor.pairs[slot][1]
                    bfmax = session["meta"][id(seg)][2]
                    ub = executor.tile_lane_ub_cached(
                        seg, unions[slot], rl[slot], rh[slot], bfmax,
                        g.tile_sub)
                    plan = psc.plan_pruned_tiles(
                        rl[slot], rh[slot], w_all[slot], bfmax, probe,
                        ub=ub)
                    if plan is None:
                        plans_p = None
                        break
                    plans_p.append(plan)
            if plans_p is not None:
                n_rest = n_tiles - probe
                rl_p = np.zeros((n_slots, probe, t_pad), np.int32)
                rh_p = np.zeros((n_slots, probe, t_pad), np.int32)
                tid_p = np.zeros((n_slots, probe), np.int32)
                rl_r = np.zeros((n_slots, n_rest, t_pad), np.int32)
                rh_r = np.zeros((n_slots, n_rest, t_pad), np.int32)
                tid_r = np.zeros((n_slots, n_rest), np.int32)
                bounds_r = np.full((n_slots, n_rest, q_pad), -np.inf,
                                   np.float32)
                for slot, plan in enumerate(plans_p):
                    rl_p[slot] = plan["rl_probe"]
                    rh_p[slot] = plan["rh_probe"]
                    tid_p[slot] = plan["tid_probe"]
                    rl_r[slot] = plan["rl_rest"]
                    rh_r[slot] = plan["rh_rest"]
                    tid_r[slot] = plan["tid_rest"]
                    bounds_r[slot] = plan["bounds_rest"]
                run = _mesh_batched_pruned_program(
                    executor.mesh, executor.slots_per_dev,
                    q_pad, kk, t_pad, cb, g.tile_sub, tps,
                    session["mode"] == "interpret", codec, probe, n_rest)
                slot_real = np.zeros(n_slots, np.int32)
                slot_real[:n_pairs] = 1
                args = corpus + (
                    staged[live_key],
                    jax.device_put(rl_p, sharding),
                    jax.device_put(rh_p, sharding),
                    jax.device_put(tid_p, sharding),
                    jax.device_put(rl_r, sharding),
                    jax.device_put(rh_r, sharding),
                    jax.device_put(tid_r, sharding),
                    jax.device_put(bounds_r, sharding),
                    jax.device_put(w_all, sharding),
                    jax.device_put(slot_real, sharding),
                    jnp.int32(q_batch))
                bt.stop("staging", t_stage)
                if deadline is not None:
                    # a first call compiles the pruned program (seconds):
                    # honor the deadline before committing to the launch
                    deadline.checkpoint()
                on_kernel_launch(self.svc.name, "pruned")
                outs = _launch_locked(bt, run, *args)
                keys, docs, slots, totals, scored, tiles_total = _fetch(
                    bt, outs, self._telemetry)
                pruned_stats = {
                    "tiles_scored": int(scored),
                    "tiles_pruned": int(tiles_total) - int(scored),
                }
                # DMA economy of this launch: every scored tile streams
                # t_pad cb-block posting windows; pruned tiles skip them
                wb = 4 if codec == "packed" else 8
                tile_bytes = t_pad * cb * psc.LANE * wb
                launch_adds = {
                    "postings_bytes_streamed":
                        pruned_stats["tiles_scored"] * tile_bytes,
                    "postings_bytes_skipped":
                        pruned_stats["tiles_pruned"] * tile_bytes,
                    "tiles_scored": pruned_stats["tiles_scored"],
                    "tiles_pruned": pruned_stats["tiles_pruned"],
                }
            elif has_aggs:
                # agg-carrying batch: ONE dense launch both ranks and
                # aggregates — the posting windows and the doc-value
                # columns stream once for the whole burst, the matched
                # masks reduce on device (ISSUE 13, docs/AGGS.md)
                agg_statics = tuple(
                    (member_agg_plans[q].statics
                     if q < q_batch and member_agg_plans[q] is not None
                     else ())
                    for q in range(q_pad))
                agg_keys = sorted({key for p in member_agg_plans
                                   if p is not None
                                   for key in p.column_keys()})
                agg_cols = {key: staged[key] for key in agg_keys}
                run = _mesh_batched_dense_agg_program(
                    executor.mesh, executor.slots_per_dev,
                    q_pad, kk, t_pad, cb, g.tile_sub, tps,
                    session["mode"] == "interpret", codec,
                    agg_statics, executor.nd1)
                args = corpus + (staged[live_key],
                                 jax.device_put(rl, sharding),
                                 jax.device_put(rh, sharding),
                                 jax.device_put(w_all, sharding),
                                 agg_cols)
                bt.stop("staging", t_stage)
                if deadline is not None:
                    deadline.checkpoint()
                on_kernel_launch(self.svc.name, "batched")
                outs = _launch_locked(bt, run, *args)
                keys, docs, slots, totals, *agg_raw = _fetch(
                    bt, outs, self._telemetry)
                wb = 4 if codec == "packed" else 8
                launch_adds = {
                    "postings_bytes_streamed":
                        n_tiles * n_pairs * t_pad * cb * psc.LANE * wb,
                    "doc_values_bytes_streamed":
                        sum(int(staged[key].nbytes) for key in agg_keys),
                }
            else:
                run = _mesh_batched_kernel_program(
                    executor.mesh, executor.slots_per_dev,
                    q_pad, kk, t_pad, cb, g.tile_sub, tps,
                    session["mode"] == "interpret", codec)
                args = corpus + (staged[live_key],
                                 jax.device_put(rl, sharding),
                                 jax.device_put(rh, sharding),
                                 jax.device_put(w_all, sharding))
                bt.stop("staging", t_stage)
                if deadline is not None:
                    deadline.checkpoint()
                on_kernel_launch(self.svc.name, "batched")
                outs = _launch_locked(bt, run, *args)
                keys, docs, slots, totals = _fetch(bt, outs,
                                                   self._telemetry)
                wb = 4 if codec == "packed" else 8
                launch_adds = {
                    "postings_bytes_streamed":
                        n_tiles * n_pairs * t_pad * cb * psc.LANE * wb,
                }
        except (PlanStructureMismatch, NotImplementedError):
            self._note("mesh_pallas", "shape_mismatch", q_batch)
            return None  # shape ineligibility: next rung, no penalty
        except (TaskCancelledException, TimeExceededException):
            # deadline/cancel tripped a checkpoint (single-query fast
            # path): the PR-4 contract — partial/timed_out or a clean
            # cancellation error — belongs to the caller, never a
            # quarantine
            raise
        except Exception:  # noqa: BLE001 — plane fault, not a shape miss
            # batch-wide fault: bench the plane ONCE (not Q times) and
            # let the caller serve the members from the next rung
            _plane_logger.warning(
                "[%s] batched execution plane [mesh_pallas] failed; "
                "quarantined for %.1fs", self.svc.name,
                self.plane_health.cooldown_s, exc_info=True)
            self.plane_health.record_failure("mesh_pallas")
            self._note("mesh_pallas", "fault", q_batch)
            return None
        # the launch committed: fully re-open the plane (a probe's
        # success ends the quarantine — single-flight contract)
        self.plane_health.note_success("mesh_pallas")
        with self._counter_lock:
            self.query_total += q_batch
            self.pallas_query_total += q_batch
            if q_batch > 1:
                # the Q==1 pruned fast path is not cross-query batching:
                # it must not inflate the batching-adoption telemetry
                # (docs/BATCHING.md counts launch-SHARING members only)
                self.batched_launch_total += 1
                self.batched_query_total += q_batch
            if pruned_stats is not None:
                self.pruned_query_total += q_batch
                self.tiles_scored_total += pruned_stats["tiles_scored"]
                self.tiles_pruned_total += pruned_stats["tiles_pruned"]
        self._note("mesh_pallas",
                   "served_batched" if q_batch > 1 else
                   ("served_pruned" if pruned_stats is not None
                    else "served"), q_batch)
        member_aggs = [None] * q_batch
        if has_aggs:
            from elasticsearch_tpu.search.fused_aggs import (
                finalize_fused,
                n_agg_outputs,
            )

            t_aggf = bt.start("aggregate")
            pos = 0
            for q in range(q_batch):
                plan = member_agg_plans[q]
                if plan is None:
                    continue
                n = n_agg_outputs(plan.statics)
                member_aggs[q] = finalize_fused(
                    plan, agg_raw[pos: pos + n], n_pairs)
                pos += n
            bt.stop("aggregate", t_aggf)
            self._note_fused_aggs(
                [p for p in member_agg_plans if p is not None])
        t_merge = bt.start("merge")
        results = []
        for q, body in enumerate(bodies):
            # per-shard search stats stay attributed per MEMBER (the
            # batch is an execution detail, not a stats unit)
            for sid in self.svc.shards:
                self.svc.shards[sid].searcher.note_query(
                    (body or {}).get("stats"))
            refs = []
            max_score = None
            for key, slot, d in zip(keys[q][: ks[q]], slots[q][: ks[q]],
                                    docs[q][: ks[q]]):
                if key == -np.inf or d < 0:
                    continue
                sid, seg = executor.pairs[int(slot)]
                score = float(key)
                refs.append(DocRef(sid, seg.name, int(d), score, ()))
                if max_score is None:
                    max_score = score
            result = {"total": int(totals[q]), "refs": refs,
                      "max_score": max_score, "plane": "mesh_pallas"}
            if member_aggs[q] is not None:
                result["aggregations"] = member_aggs[q]
            if pruned_stats is not None:
                # per-query debug marker (the response's _pruned field):
                # under pruning `total` counts matches in SCORED tiles
                # only — a documented lower bound, which the marker's
                # total_relation records (WAND semantics, docs/PRUNING.md;
                # the ES6 response shape keeps hits.total a bare int)
                result["pruned"] = dict(pruned_stats,
                                        total_relation="gte")
            results.append(result)
        bt.stop("merge", t_merge)
        # launch-level byte/tile totals fold into the registry ONCE (a
        # batch must not multiply them); members see them as profile
        # annotations of the launch they shared
        tel = self._telemetry
        if tel is not None:
            tel.add_counters(launch_adds)
        for q, tr in enumerate(tracers or []):
            if tr is not None and getattr(tr, "enabled", False):
                tr.merge_from(bt)
                tr.annotate("batch_size", q_batch)
                tr.annotate("batch_member_index", q)
                for key, v in launch_adds.items():
                    tr.annotate(key, int(v))
        return results


def _knn_live(seg, col) -> np.ndarray:
    """A segment's kNN mask rows: live AND carries the vector, f32."""
    return (col.exists & seg.live[: col.vectors.shape[0]]).astype(
        np.float32)


def _knn_slot_rows(table, n_slots: int):
    """A flat kNN table as ``[n_slots, rows a slot, width]``, to set
    whole slots of it by index (delta append, tombstones)."""
    return table.reshape(n_slots, table.shape[0] // n_slots,
                         table.shape[1])


class MeshPlanExecutor:
    """Stage N sealed segments onto a device mesh once; run any query
    plan as one compiled multi-device program.

    Segments PACK: with more segments than devices, each device owns
    ``slots_per_dev = ceil(N / n_dev)`` slots in the stacked leading axis
    and the per-device program unrolls its slots — a realistically-
    refreshed index (many NRT segments per shard) stays on the mesh plane
    instead of silently falling back to the host path. A slot with no
    live document (padding, delta-staging headroom, a segment deleted
    whole) has an all-false ``live1`` row: the serial program branches
    around its pass, the batched and kNN programs mask its result."""

    _SCOPE_SEQ = itertools.count(1)

    def __init__(self, segments: List, mesh: Optional[Mesh] = None,
                 postings_codec: Optional[str] = None,
                 index_name: Optional[str] = None,
                 stage_reason: str = "initial",
                 slots_per_dev: Optional[int] = None):
        from elasticsearch_tpu.parallel.distributed import stack_shard_arrays
        from elasticsearch_tpu.parallel.mesh import shard_mesh

        self.mesh = mesh or shard_mesh()
        self.n_dev = self.mesh.devices.size
        self.segments = segments
        # device-memory accountant identity (ISSUE 9): one LRU scope per
        # executor generation; every rebuild is a fresh scope so the old
        # generation's release is exact (next() is atomic — concurrent
        # first-queries must never share a scope id)
        self.index_name = index_name or "_unassigned"
        self.scope = f"mesh#{next(self._SCOPE_SEQ)}"
        # (shard_id, segment) per slot — owned by THIS generation so a
        # query that pinned an executor never reads a concurrently
        # restaged pair list (IndexMeshSearch._ensure_staged overwrites
        # with the real shard ids; the positional default serves direct
        # constructions in tests/bench)
        self.pairs: List[Tuple[int, object]] = list(enumerate(segments))
        # armed by the owner via make_evictable AFTER install — a
        # generation under construction is deliberately not evictable
        self._evict_cb = None
        # why this generation staged (initial / refresh /
        # delete_invalidation / geometry_change) — every table this
        # executor stages inherits it
        self._stage_reason = stage_reason
        # postings codec preference for the kernel-plane staging
        # (index.search.pallas.postings_codec; resolved against the doc
        # space at ensure_kernel time — docs/PRUNING.md)
        self.postings_codec_pref = postings_codec
        # staged posting bytes + effective codec, exported via _stats
        self.postings_bytes_staged = 0
        self.postings_codec = "raw"
        # slot-allocator headroom (ISSUE 20): the owner may hint MORE
        # slots per device than the segment set needs — the extra slots
        # stage as dead rows (all-zero live masks) and give incremental
        # refreshes free capacity to delta-append into without a
        # geometry rebuild
        self.slots_per_dev = max(1, -(-len(segments) // self.n_dev))
        if slots_per_dev is not None:
            self.slots_per_dev = max(self.slots_per_dev,
                                     int(slots_per_dev))
        self.n_slots = self.slots_per_dev * self.n_dev
        # set by release(): a query pinned to a replaced generation may
        # still lazily stage tables — those must NOT re-register under
        # the already-released ledger scope (see _account)
        self._released = False
        # serializes the lazy kernel/kNN cold stagings: two concurrent
        # first-queries must not both pay the transfer (and the loser's
        # re-registration would misclassify as a restage)
        self._kernel_stage_lock = threading.Lock()
        t0 = _time.monotonic()
        stacked = stack_shard_arrays(segments, self.n_slots)
        self.nd_pad = stacked.pop("nd_pad")
        self.nd1 = self.nd_pad + 1
        sharding = NamedSharding(self.mesh, PS("shards"))
        from elasticsearch_tpu.testing.disruption import on_device_staging

        # injection point for the base mesh staging (ISSUE 10): a raise
        # here aborts the constructor with nothing registered — the
        # owner's run_staged loop retries/classifies
        on_device_staging(self.index_name, "mesh_slot_tables",
                          "seg_stacked")
        self._seg_staged = {
            name: jax.device_put(arr, sharding)
            for name, arr in stacked.items()
        }
        self._sharding = sharding
        self._account("mesh_slot_tables", "seg_stacked",
                      sum(int(a.nbytes) for a in stacked.values()),
                      duration_ms=(_time.monotonic() - t0) * 1000.0)
        # per staged sort column: {"vocab": [terms] | array | None} —
        # keyword sorts, and numeric ones whose values f32 cannot hold,
        # rank by GLOBAL ordinals built over the staged segment set and
        # the caller maps ordinals back to values for the response
        self.sort_meta: Dict[str, dict] = {}
        self._numeric_ords: Dict[str, np.ndarray] = {}
        # lazily-staged tile-kernel plane (ensure_kernel): False =
        # unavailable, dict = {geom, meta: {id(seg): (bmin, bmax)}, mode}
        self._kernel = None
        # set when the HBM budget (not a fault) turned a staging away —
        # the ladder reports the demotion as decision reason hbm_budget.
        # Thread-local: each query reads the reason from ITS ensure_*
        # call, not a concurrent thread's reset
        self._denied = threading.local()
        # lazily-staged kNN plane per dense_vector field (ensure_knn):
        # field -> False | {emb, scale, mask, d_pad, nd_pad, metric}
        self._knn: Dict[str, object] = {}
        # per-(segment, geometry, lane posting-run) block-max bound
        # columns for pruning (invariant across queries — under zipfian
        # traffic the same hot terms recompute identical columns);
        # lifetime bounded by this executor (rebuilt on segment change)
        self._ub_cache: Dict[tuple, np.ndarray] = {}

    # ------------------------------------------------------------------
    # Device-memory accounting (ISSUE 9, docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------

    @property
    def kernel_denied_reason(self):
        return getattr(self._denied, "reason", None)

    @kernel_denied_reason.setter
    def kernel_denied_reason(self, value) -> None:
        self._denied.reason = value

    def make_evictable(self, evict) -> None:
        """Arm the HBM-budget eviction callback — called by the owner
        AFTER this generation is installed as current. Arming during
        construction would let another thread's budget reservation evict
        this scope while the owner's executor pointer still names the
        PREVIOUS generation: the callback would drop and release the
        wrong one, and the owner's subsequent install would pin a staged
        key with no executor behind it (permanent host demotion)."""
        from elasticsearch_tpu.common.memory import memory_accountant

        self._evict_cb = evict
        memory_accountant().set_evict(self.index_name, self.scope, evict)

    def _account(self, kind: str, table: str, nbytes: int,
                 reason: Optional[str] = None, duration_ms: float = 0.0,
                 quiet: bool = False,
                 amplify_bytes: Optional[int] = None) -> None:
        from elasticsearch_tpu.common.memory import memory_accountant

        if self._released:
            # a query that pinned this generation before a concurrent
            # refresh replaced it may lazily stage MORE tables while
            # finishing: registering them would resurrect the released
            # scope (ledger bytes backed only by the query's transient
            # references, with an evict callback that would drop the
            # CURRENT generation). The arrays free with the query's
            # references; the ledger stays exact.
            return
        memory_accountant().register(
            self.index_name, self.scope, kind, table, int(nbytes),
            reason=reason or self._stage_reason, duration_ms=duration_ms,
            plane="mesh", evict=self._evict_cb, quiet=quiet,
            amplify_bytes=amplify_bytes)

    def release(self) -> int:
        """This executor generation is being replaced/dropped: return
        its staged bytes to the ledger. The arrays themselves free when
        the last in-flight query drops its references (refcounting)."""
        from elasticsearch_tpu.common.memory import memory_accountant

        self._released = True
        return memory_accountant().release_scope(self.index_name,
                                                 self.scope)

    def touch(self) -> None:
        from elasticsearch_tpu.common.memory import memory_accountant

        memory_accountant().touch(self.index_name, self.scope)

    # ------------------------------------------------------------------
    # Delta staging (ISSUE 20): incremental append + tombstone deletes
    # ------------------------------------------------------------------

    def free_slots(self) -> int:
        """Unoccupied slots in this generation (the append headroom)."""
        return self.n_slots - len(self.segments)

    @staticmethod
    def delta_append_compatible(old: "MeshPlanExecutor",
                                new_segments: List) -> bool:
        """Cheap structural pre-check: can ``new_segments`` delta-append
        into ``old``'s free slots without a geometry rebuild? False on
        any of the ISSUE 20 rebuild-fallback conditions (slots
        exhausted, tile-geometry mismatch); codec changes are the
        owner's check (it knows the live settings value)."""
        if old._released:
            return False
        if len(old.segments) + len(new_segments) > old.n_slots:
            return False  # slots exhausted
        bd = old._seg_staged.get("block_docs")
        nm = old._seg_staged.get("norms")
        if bd is None or nm is None:
            return False
        n_blocks, blk = int(bd.shape[1]), int(bd.shape[2])
        n_norm = int(nm.shape[1])
        kernel = old._kernel if isinstance(old._kernel, dict) else None
        n_rows = None
        if kernel is not None:
            from elasticsearch_tpu.ops import pallas_scoring as psc

            k_arr = old._seg_staged.get(
                "k_packed" if kernel["codec"] == "packed" else "k_docs")
            if k_arr is None:
                return False
            n_rows = int(k_arr.shape[0]) // old.n_slots - psc.CB_MAX
        for seg in new_segments:
            if (seg.nd_pad > old.nd_pad
                    or seg.block_docs.shape[0] > n_blocks
                    or seg.block_docs.shape[1] != blk
                    or seg.norms.shape[0] > n_norm):
                return False  # tile-geometry mismatch
            if n_rows is not None and seg.block_docs.shape[0] > n_rows:
                return False  # kernel posting window would overflow
        return True

    @classmethod
    def delta_append(cls, old: "MeshPlanExecutor", append_pairs: List,
                     refresh_slots: List[int] = (),
                     index_name: Optional[str] = None
                     ) -> "MeshPlanExecutor":
        """Copy-on-write SUCCESSOR generation for an incremental refresh
        (ISSUE 20): stage ONLY the new segments' tables (postings, live
        masks, bound tables, embeddings) into free slots — every
        already-staged slot's arrays are shared with the old generation
        untouched (non-donating ``.at[slot].set`` scatters), so queries
        pinned to the old generation keep serving from intact arrays
        until the last reference drops.

        ``refresh_slots``: already-occupied slots whose live masks must
        also refresh (deletes riding along with the append).

        One transactional attempt inside the owner's run_staged loop:
        nothing publishes or registers until every array is built — a
        fault mid-way discards the half-built successor with the old
        generation and the ledger exactly as they were. The delta row
        bytes feed the amplification counters (reason ``delta_append``);
        the successor scope's full array bytes land in the ledger so
        release stays exact. Derived columns the append invalidates
        (sort keys — keyword global ordinals change with the vocab —
        slice masks, fused-agg doc values) are dropped and rebuild
        lazily. Raises ``_DeltaIneligible`` (a StagingBail: no retry, no
        fault accounting) on structural surprises the pre-check missed."""
        from elasticsearch_tpu.testing.disruption import on_device_staging

        new_segs = [seg for _sid, seg in append_pairs]
        if not cls.delta_append_compatible(old, new_segs):
            raise _DeltaIneligible("segment set cannot delta-append")
        self = cls.__new__(cls)
        self.mesh = old.mesh
        self.n_dev = old.n_dev
        self.index_name = index_name or old.index_name
        self.scope = f"mesh#{next(cls._SCOPE_SEQ)}"
        self.segments = list(old.segments) + new_segs
        self.pairs = list(old.pairs) + list(append_pairs)
        self._evict_cb = None
        # lazy stagings AFTER install classify as refresh (the segment
        # set did change); the construction below registers its delta
        # rows explicitly as delta_append
        self._stage_reason = "refresh"
        self.postings_codec_pref = old.postings_codec_pref
        self.postings_bytes_staged = old.postings_bytes_staged
        self.postings_codec = old.postings_codec
        self.slots_per_dev = old.slots_per_dev
        self.n_slots = old.n_slots
        self.nd_pad = old.nd_pad
        self.nd1 = old.nd1
        self._sharding = old._sharding
        self._released = False
        self._kernel_stage_lock = threading.Lock()
        self.sort_meta = {}
        self._numeric_ords = {}
        self._kernel = None
        self._denied = threading.local()
        self._knn = {}
        self._ub_cache = {}
        self._seg_staged = {}

        t0 = _time.monotonic()
        base = old._seg_staged
        first_new = len(old.segments)
        new_slots = list(range(first_new, len(self.segments)))
        # live-mask rows refresh for appended slots AND tombstoned ones
        live_slots = sorted(set(refresh_slots)) + new_slots
        nd_pad = self.nd_pad

        # injection point (ISSUE 10 schemes): a raise here aborts the
        # attempt with nothing registered and the old generation intact
        on_device_staging(self.index_name, "mesh_slot_tables",
                          "delta_append")

        # --- base slot tables: delta rows at stacked geometry ---------
        n_blocks, blk = int(base["block_docs"].shape[1]), \
            int(base["block_docs"].shape[2])
        n_norm = int(base["norms"].shape[1])
        bd_rows = np.full((len(new_slots), n_blocks, blk), nd_pad,
                          np.int32)
        bt_rows = np.zeros((len(new_slots), n_blocks, blk), np.float32)
        nm_rows = np.ones((len(new_slots), n_norm, nd_pad + 1),
                          np.float32)
        for j, seg in enumerate(new_segs):
            bd = seg.block_docs.copy()
            bd[bd == seg.nd_pad] = nd_pad  # re-point sentinel
            bd_rows[j, : bd.shape[0]] = bd
            bt_rows[j, : seg.block_tfs.shape[0]] = seg.block_tfs
            nm_rows[j, : seg.norms.shape[0], : seg.norms.shape[1] - 1] \
                = seg.norms[:, :-1]
            nm_rows[j, :, nd_pad] = 1.0
        lv_rows = np.zeros((len(live_slots), nd_pad + 1), bool)
        for j, slot in enumerate(live_slots):
            seg = self.segments[slot]
            lv_rows[j, : seg.live.shape[0]] = seg.live
        idx_new = jnp.asarray(np.asarray(new_slots, np.int32))
        idx_live = jnp.asarray(np.asarray(live_slots, np.int32))
        staged = {
            "block_docs": jax.device_put(
                base["block_docs"].at[idx_new].set(jnp.asarray(bd_rows)),
                self._sharding),
            "block_tfs": jax.device_put(
                base["block_tfs"].at[idx_new].set(jnp.asarray(bt_rows)),
                self._sharding),
            "norms": jax.device_put(
                base["norms"].at[idx_new].set(jnp.asarray(nm_rows)),
                self._sharding),
            "live1": jax.device_put(
                base["live1"].at[idx_live].set(jnp.asarray(lv_rows)),
                self._sharding),
        }
        amp_base = int(bd_rows.nbytes + bt_rows.nbytes + nm_rows.nbytes
                       + lv_rows.nbytes)

        # --- kernel plane: delta posting windows + live_t rows --------
        kernel = old._kernel if isinstance(old._kernel, dict) else None
        live_t_amp: Dict[str, int] = {}
        amp_postings = 0
        amp_bounds = 0
        meta = None
        if kernel is not None:
            from elasticsearch_tpu.ops import pallas_scoring as psc

            geom, codec = kernel["geom"], kernel["codec"]
            k_key = "k_packed" if codec == "packed" else "k_docs"
            # the flat table's rows per slot: the new slots are
            # consecutive, so their rows are ONE range of it
            n_rows = int(base[k_key].shape[0]) // self.n_slots
            meta = dict(kernel["meta"])

            def with_new_slots(table, rows):
                return jax.device_put(
                    jax.lax.dynamic_update_slice(
                        table, jnp.asarray(rows.reshape(-1, psc.LANE)),
                        (jnp.int32(first_new * n_rows), jnp.int32(0))),
                    self._sharding)

            if codec == "packed":
                pk_rows = np.zeros((len(new_slots), n_rows, psc.LANE),
                                   np.int32)
            else:
                dc_rows = np.full((len(new_slots), n_rows, psc.LANE),
                                  nd_pad, np.int32)
                fr_rows = np.zeros((len(new_slots), n_rows, psc.LANE),
                                   np.float32)
            for j, seg in enumerate(new_segs):
                f = seg._block_frac()
                bmin, bmax = psc.block_min_max(
                    seg.block_docs, seg.block_tfs, seg.nd_pad)
                if codec == "packed":
                    fq = psc.quantize_frac(f)
                    pk = psc.pack_segment_blocks(seg.block_docs, f,
                                                 seg.nd_pad, q=fq)
                    if pk.shape[0] > n_rows:
                        raise _DeltaIneligible(
                            "packed posting window exceeds the staged "
                            "kernel rows")
                    pk_rows[j, : pk.shape[0]] = pk
                    bfmax = psc.block_frac_max(psc.dequantize_frac(fq))
                else:
                    dp, fp = psc.pad_segment_blocks(seg.block_docs, f,
                                                    seg.nd_pad)
                    if dp.shape[0] > n_rows:
                        raise _DeltaIneligible(
                            "raw posting window exceeds the staged "
                            "kernel rows")
                    dc_rows[j, : dp.shape[0]] = dp
                    fr_rows[j, : fp.shape[0]] = fp
                    bfmax = psc.block_frac_max(f)
                meta[id(seg)] = (bmin, bmax, bfmax)
                amp_bounds += sum(int(b.nbytes) for b in meta[id(seg)])
            if codec == "packed":
                staged["k_packed"] = with_new_slots(base["k_packed"],
                                                    pk_rows)
                amp_postings = int(pk_rows.nbytes)
            else:
                staged["k_docs"] = with_new_slots(base["k_docs"], dc_rows)
                staged["k_frac"] = with_new_slots(base["k_frac"], fr_rows)
                amp_postings = int(dc_rows.nbytes + fr_rows.nbytes)
            for key in [k for k in base if k.startswith("k_live_t")]:
                g = (geom if key == "k_live_t" else psc.tile_geometry(
                    geom.nd_pad, int(key.rsplit("_", 1)[1])))
                lt_rows = np.zeros(
                    (len(live_slots), g.n_tiles * psc.LANE, g.tile_sub),
                    np.float32)
                for j, slot in enumerate(live_slots):
                    seg = self.segments[slot]
                    live = np.zeros(g.nd_pad, np.float32)
                    live[: seg.nd_pad] = seg.live.astype(np.float32)
                    lt_rows[j] = psc.build_live_t(live, g)
                staged[key] = jax.device_put(
                    base[key].at[idx_live].set(jnp.asarray(lt_rows)),
                    self._sharding)
                live_t_amp[key] = int(lt_rows.nbytes)

        # --- kNN planes: delta embedding/scale/mask rows per field ----
        knn_new: Dict[str, object] = {}
        knn_amp: Dict[str, Tuple[int, int, int]] = {}
        for field, entry in old._knn.items():
            if not isinstance(entry, dict):
                # None/False: the successor re-evaluates lazily (a new
                # segment may change the structural verdict either way)
                continue
            dims = entry.get("dims")
            if dims is None or any(
                    seg.vector_columns.get(field) is not None
                    and seg.vector_columns[field].dims != dims
                    for seg in new_segs):
                continue  # dims surprise: lazy restage decides
            import ml_dtypes

            from elasticsearch_tpu.ops import pallas_knn as pkn

            d_pad, nd_knn = entry["d_pad"], entry["nd_pad"]
            emb_rows = np.zeros((len(new_slots), nd_knn, d_pad),
                                ml_dtypes.bfloat16)
            sc_rows = np.zeros((len(new_slots), nd_knn, 1), np.float32)
            for j, seg in enumerate(new_segs):
                col = seg.vector_columns.get(field)
                if col is None:
                    continue  # slot stays dead
                emb_rows[j, : col.vectors.shape[0], : dims] = col.vectors
                sc_rows[j, : col.vectors.shape[0]] = \
                    pkn.vector_scale_column(col.vectors, entry["metric"])
            mk_rows = np.zeros((len(live_slots), nd_knn, 1), np.float32)
            for j, slot in enumerate(live_slots):
                seg = self.segments[slot]
                col = seg.vector_columns.get(field)
                if col is not None:
                    mk_rows[j, : col.vectors.shape[0], 0] = \
                        _knn_live(seg, col)

            def with_slots(table, idx, rows):
                return jax.device_put(
                    _knn_slot_rows(table, self.n_slots).at[idx].set(
                        jnp.asarray(rows)).reshape(table.shape),
                    self._sharding)

            slot_live = entry["slot_live"].copy()
            slot_live[live_slots] = mk_rows.any(axis=(1, 2))
            knn_new[field] = dict(
                entry,
                emb=with_slots(entry["emb"], idx_new, emb_rows),
                scale=with_slots(entry["scale"], idx_new, sc_rows),
                mask=with_slots(entry["mask"], idx_live, mk_rows),
                slot_live=slot_live,
                slots_scanned=int(slot_live.sum()))
            knn_amp[field] = (int(emb_rows.nbytes), int(sc_rows.nbytes),
                              int(mk_rows.nbytes))

        # --- commit: publish, then register (register-then-commit) ----
        self._seg_staged = staged
        self._knn = knn_new
        if kernel is not None:
            self._kernel = {"geom": kernel["geom"], "meta": meta,
                            "codec": kernel["codec"]}
        dur = (_time.monotonic() - t0) * 1000.0
        self._account(
            "mesh_slot_tables", "seg_stacked",
            sum(int(staged[k].nbytes) for k in
                ("block_docs", "block_tfs", "norms", "live1")),
            reason="delta_append", amplify_bytes=amp_base,
            duration_ms=dur)
        if kernel is not None:
            kind_postings = ("postings_packed"
                             if kernel["codec"] == "packed"
                             else "postings_raw")
            self._account(kind_postings, "k_postings",
                          self.postings_bytes_staged,
                          reason="delta_append",
                          amplify_bytes=amp_postings, duration_ms=dur)
            for key, amp in live_t_amp.items():
                self._account("live_mask", key,
                              int(staged[key].nbytes),
                              reason="delta_append", amplify_bytes=amp,
                              duration_ms=dur)
            self._account("bound_tables", "k_bounds",
                          sum(int(b.nbytes) for t in meta.values()
                              for b in t),
                          reason="delta_append",
                          amplify_bytes=amp_bounds)
        for field, entry in knn_new.items():
            e_amp, s_amp, m_amp = knn_amp[field]
            self._account("embeddings", f"knn:{field}",
                          int(entry["emb"].nbytes),
                          reason="delta_append", amplify_bytes=e_amp,
                          duration_ms=dur)
            self._account("scale_norm", f"knn_scale:{field}",
                          int(entry["scale"].nbytes),
                          reason="delta_append", amplify_bytes=s_amp,
                          duration_ms=dur)
            self._account("live_mask", f"knn_mask:{field}",
                          int(entry["mask"].nbytes),
                          reason="delta_append", amplify_bytes=m_amp,
                          duration_ms=dur)
        return self

    def apply_tombstones(self, slots: List[int]) -> int:
        """Tombstone deletes (ISSUE 20): recompute ONLY the given
        slots' live-mask columns — the base ``live1`` row (which also
        feeds the fused-agg matched masks), every staged kernel
        transposed-mask layout (``k_live_t`` + per-sub variants), and
        each staged kNN field's exists∧live mask — and publish them IN
        PLACE on this generation. No geometry rebuild, no scope change:
        the same ledger keys re-register at their (unchanged) full
        bytes with the changed ROW bytes as the amplification truth
        (reason ``tombstone``).

        One transactional attempt inside the owner's run_staged loop:
        every replacement array is built before anything publishes, so
        a fault leaves the generation serving the old masks and the
        ledger at its exact pre-attempt state. In-flight queries see
        either the old or the new masks — both are valid point-in-time
        views (the reference's flip-a-live-bit-under-readers contract).
        Returns the mask bytes actually restaged."""
        from elasticsearch_tpu.testing.disruption import on_device_staging

        with self._kernel_stage_lock:
            if self._released or not slots:
                return 0
            t0 = _time.monotonic()
            slots = sorted(slots)
            idx = jnp.asarray(np.asarray(slots, np.int32))
            # injection point (ISSUE 10): a raise here leaves nothing
            # published and nothing registered
            on_device_staging(self.index_name, "live_mask",
                              "tombstone_masks")
            nd_pad = self.nd_pad
            lv_rows = np.zeros((len(slots), nd_pad + 1), bool)
            for j, slot in enumerate(slots):
                seg = self.segments[slot]
                lv_rows[j, : seg.live.shape[0]] = seg.live
            updates = {"live1": jax.device_put(
                self._seg_staged["live1"].at[idx].set(
                    jnp.asarray(lv_rows)), self._sharding)}
            amp: Dict[str, int] = {"live1": int(lv_rows.nbytes)}
            if isinstance(self._kernel, dict):
                from elasticsearch_tpu.ops import pallas_scoring as psc

                geom = self._kernel["geom"]
                for key in [k for k in self._seg_staged
                            if k.startswith("k_live_t")]:
                    g = (geom if key == "k_live_t"
                         else psc.tile_geometry(
                             geom.nd_pad, int(key.rsplit("_", 1)[1])))
                    lt = np.zeros(
                        (len(slots), g.n_tiles * psc.LANE, g.tile_sub),
                        np.float32)
                    for j, slot in enumerate(slots):
                        seg = self.segments[slot]
                        live = np.zeros(g.nd_pad, np.float32)
                        live[: seg.nd_pad] = seg.live.astype(np.float32)
                        lt[j] = psc.build_live_t(live, g)
                    updates[key] = jax.device_put(
                        self._seg_staged[key].at[idx].set(
                            jnp.asarray(lt)), self._sharding)
                    amp[key] = int(lt.nbytes)
            knn_updates: Dict[str, dict] = {}
            knn_amp: Dict[str, int] = {}
            for field, entry in self._knn.items():
                if not isinstance(entry, dict):
                    continue
                nd_knn = entry["nd_pad"]
                mk = np.zeros((len(slots), nd_knn, 1), np.float32)
                for j, slot in enumerate(slots):
                    seg = self.segments[slot]
                    col = seg.vector_columns.get(field)
                    if col is not None:
                        mk[j, : col.vectors.shape[0], 0] = \
                            _knn_live(seg, col)
                slot_live = entry["slot_live"].copy()
                slot_live[slots] = mk.any(axis=(1, 2))
                knn_updates[field] = dict(
                    entry,
                    mask=jax.device_put(
                        _knn_slot_rows(entry["mask"], self.n_slots)
                        .at[idx].set(jnp.asarray(mk))
                        .reshape(entry["mask"].shape), self._sharding),
                    slot_live=slot_live,
                    slots_scanned=int(slot_live.sum()))
                knn_amp[field] = int(mk.nbytes)
            restaged = sum(amp.values()) + sum(knn_amp.values())
            # commit: publish every replacement, then re-register the
            # same keys (full bytes unchanged; amplification = rows)
            self._seg_staged.update(updates)
            for field, entry in knn_updates.items():
                self._knn[field] = entry
            dur = (_time.monotonic() - t0) * 1000.0
            self._account(
                "mesh_slot_tables", "seg_stacked",
                sum(int(self._seg_staged[k].nbytes) for k in
                    ("block_docs", "block_tfs", "norms", "live1")),
                reason="tombstone", amplify_bytes=amp.pop("live1"),
                duration_ms=dur)
            for key, a in amp.items():
                self._account("live_mask", key,
                              int(self._seg_staged[key].nbytes),
                              reason="tombstone", amplify_bytes=a,
                              duration_ms=dur)
            for field, entry in knn_updates.items():
                self._account("live_mask", f"knn_mask:{field}",
                              int(entry["mask"].nbytes),
                              reason="tombstone",
                              amplify_bytes=knn_amp[field],
                              duration_ms=dur)
            return restaged

    # ------------------------------------------------------------------
    # Tile-kernel plane staging (the unified fast plane)
    # ------------------------------------------------------------------

    def ensure_kernel(self) -> Optional[dict]:
        """Stage the pallas tile-scoring plane over the stacked segment
        set: one SHARED tile geometry covering the stacked doc space, the
        per-segment posting windows (docs + per-posting BM25 norm factors,
        sentinel-padded so every CB-aligned DMA window is in bounds), one
        slot after the other along the rows of ONE 2-D table (see
        ``_KERNEL_TABLES``), and the per-slot transposed live masks. Returns
        the kernel session (plan builders consult it via
        ``ctx.mesh_kernel``) or None when the kernel can't run (pallas
        off / non-TPU backend without interpret mode).

        Staging is TRANSACTIONAL (ISSUE 10, docs/RESILIENCE.md): a fault
        mid-sequence drops every partially-published ``_seg_staged``
        entry (nothing registers with the accountant until the whole
        group staged — no orphaned HBM bytes); transient device faults
        retry with bounded backoff (``search.staging.retry.*``), and a
        terminal fault sets ``kernel_denied_reason = "staging_fault"``
        (the caller quarantines the plane) while ``_kernel`` stays None
        so the post-cooldown probe can restage once the fault clears."""
        from elasticsearch_tpu.ops.aggs import _pallas_mode

        # reset FIRST — before every early return: a thread whose last
        # call was a budget denial must not keep reporting hbm_budget
        # for what is now a mode gap or staging fault (the reason is
        # thread-local, so only its own reset clears it)
        self.kernel_denied_reason = None
        mode = _pallas_mode()
        if not mode:
            return None
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.common.staging import run_staged
        from elasticsearch_tpu.ops import pallas_scoring as psc

        if self._kernel is None:
            with self._kernel_stage_lock:
                if isinstance(self._kernel, dict):  # a racing cold
                    return dict(self._kernel, mode=mode)  # stager built it
                geom = psc.tile_geometry(max(self.nd_pad, psc.LANE))
                # codec resolution against the STACKED doc space: every
                # slot's doc ids must fit the packed word's doc bits
                codec = psc.resolve_postings_codec(
                    self.postings_codec_pref, geom.nd_pad)
                # rows per slot: the longest segment and its sentinel
                # rows, rounded up so that every slot of the flat table
                # starts on a block boundary of every cb of the ladder
                n_rows = max(s.block_docs.shape[0] for s in self.segments)
                n_rows = (-(-n_rows // psc.CB_MAX) + 1) * psc.CB_MAX
                # HBM budget gate: the kernel tables are the big mesh
                # allocation — over budget (after LRU eviction) the
                # ladder serves from the scatter mesh / host rung with
                # decision reason hbm_budget; _kernel stays None so a
                # freed budget lets a later query stage them
                # packed: one i32 word/posting; raw: i32 docs + f32 frac
                word = 4 if codec == "packed" else 8
                estimate = (self.n_slots * n_rows * psc.LANE * word
                            + self.n_slots * geom.n_tiles * psc.LANE
                            * geom.tile_sub * 4)
                if not memory_accountant().try_reserve(
                        self.index_name, estimate,
                        exclude_scope=self.scope):
                    self.kernel_denied_reason = "hbm_budget"
                    return None
                try:
                    run_staged(
                        lambda: self._stage_kernel_plane(geom, codec,
                                                         n_rows),
                        index=self.index_name, kind="postings_" + (
                            "packed" if codec == "packed" else "raw"),
                        plane="mesh")  # retry: process-level config
                except Exception:  # noqa: BLE001 — classified terminal
                    # staging fault (rollback already ran): the caller
                    # demotes + quarantines; retryable on the probe
                    _plane_logger.warning(
                        "[%s] mesh kernel staging failed; plane demotes "
                        "with reason staging_fault", self.index_name,
                        exc_info=True)
                    self.kernel_denied_reason = "staging_fault"
                    return None
        return dict(self._kernel, mode=mode)

    def _stage_kernel_plane(self, geom, codec: str, n_rows: int) -> None:
        """One staging ATTEMPT of the kernel plane (runs inside
        run_staged's retry loop — the injection hooks below re-consult
        the schemes on every retry). Publishes ``_seg_staged`` entries
        and ledger registrations only on full success; any fault rolls
        both back before re-raising."""
        from elasticsearch_tpu.ops import pallas_scoring as psc
        from elasticsearch_tpu.testing.disruption import on_device_staging

        t0 = _time.monotonic()
        kind_postings = ("postings_packed" if codec == "packed"
                         else "postings_raw")
        try:
            if codec == "packed":
                packed = np.zeros((self.n_slots, n_rows, psc.LANE),
                                  np.int32)
            else:
                docs = np.full((self.n_slots, n_rows, psc.LANE),
                               self.nd_pad, np.int32)
                frac = np.zeros((self.n_slots, n_rows, psc.LANE),
                                np.float32)
            live_t = np.zeros(
                (self.n_slots, geom.n_tiles * psc.LANE, geom.tile_sub),
                np.float32)
            meta = {}
            for i, seg in enumerate(self.segments):
                f = seg._block_frac()
                bmin, bmax = psc.block_min_max(
                    seg.block_docs, seg.block_tfs, seg.nd_pad)
                if codec == "packed":
                    fq = psc.quantize_frac(f)  # one pass serves both
                    pk = psc.pack_segment_blocks(seg.block_docs, f,
                                                 seg.nd_pad, q=fq)
                    packed[i, : pk.shape[0]] = pk
                    # bounds must dominate the DEQUANTIZED values the
                    # kernel decodes (rounding can lift a posting up
                    # to half a quantization step)
                    bfmax = psc.block_frac_max(psc.dequantize_frac(fq))
                else:
                    dp, fp = psc.pad_segment_blocks(seg.block_docs, f,
                                                    seg.nd_pad)
                    docs[i, : dp.shape[0]] = dp
                    frac[i, : fp.shape[0]] = fp
                    bfmax = psc.block_frac_max(f)
                live = np.zeros(geom.nd_pad, np.float32)
                live[: seg.nd_pad] = seg.live.astype(np.float32)
                live_t[i] = psc.build_live_t(live, geom)
                meta[id(seg)] = (bmin, bmax, bfmax)
            on_device_staging(self.index_name, kind_postings, "k_postings")
            if codec == "packed":
                self._seg_staged["k_packed"] = jax.device_put(
                    packed.reshape(-1, psc.LANE), self._sharding)
                self.postings_bytes_staged = int(packed.nbytes)
            else:
                self._seg_staged["k_docs"] = jax.device_put(
                    docs.reshape(-1, psc.LANE), self._sharding)
                self._seg_staged["k_frac"] = jax.device_put(
                    frac.reshape(-1, psc.LANE), self._sharding)
                self.postings_bytes_staged = int(docs.nbytes + frac.nbytes)
            on_device_staging(self.index_name, "live_mask", "k_live_t")
            self._seg_staged["k_live_t"] = jax.device_put(
                live_t, self._sharding)
        except BaseException:
            # transactional rollback: no partially-published table may
            # survive the attempt (a half-staged plane would serve a
            # later query with missing arrays) and nothing was
            # registered with the accountant yet — no orphaned bytes
            for key in ("k_packed", "k_docs", "k_frac", "k_live_t"):
                self._seg_staged.pop(key, None)
            self.postings_bytes_staged = 0
            raise
        # commit: publish the session, THEN register the exact bytes
        # (register-then-commit — the ledger never holds bytes for a
        # generation that failed to install)
        self.postings_codec = codec
        self._kernel = {"geom": geom, "meta": meta, "codec": codec}
        dur = (_time.monotonic() - t0) * 1000.0
        self._account(kind_postings, "k_postings",
                      self.postings_bytes_staged, duration_ms=dur)
        self._account("live_mask", "k_live_t", int(live_t.nbytes),
                      duration_ms=dur)
        # per-segment block min/max/frac-max bound columns stay
        # host-resident but scale with the staged plane
        self._account("bound_tables", "k_bounds", sum(
            int(b.nbytes) for t in meta.values() for b in t))

    def ensure_knn(self, field: str, dims: int, metric: str,
                   tracer=NULL_TRACER) -> Optional[dict]:
        """Stage a dense_vector field's kNN plane over the stacked
        segment set: the slots' bf16 embedding matrices one after the
        other along the rows of ONE ``[n_slots * nd_pad, d_pad]`` table
        (the kernel reads a slot in place at its row base, see
        ``_KERNEL_TABLES``), the metric scale column (cosine inverse
        norms / ones) and the live∧has-vector mask column laid out the
        same way — on the SAME collective geometry as the postings
        staging, so the kNN program reuses the executor's
        mesh/sharding/slot mapping verbatim. Staged once a generation
        (the ``staging.knn_embeddings`` span): a warm request finds the
        session and stages nothing. Deletes are honored through the
        mask (``apply_tombstones``). Returns the session dict or None
        when the kernel can't run here."""
        from elasticsearch_tpu.ops.aggs import _pallas_mode

        # reset FIRST — before every early return (same contract as
        # ensure_kernel: a stale thread-local hbm_budget must not
        # relabel a mode gap or staging fault)
        self.kernel_denied_reason = None
        mode = _pallas_mode()
        if not mode:
            return None
        entry = self._knn.get(field)
        if entry is None:
            # (the span around the lock, not under it: a request that
            # finds the field unstaged says so, whoever stages it)
            t = tracer.start("staging.knn_embeddings")
            try:
                entry = self._stage_knn_once(field, dims, metric)
            except Exception:  # noqa: BLE001 — classified terminal
                # staging fault (rollback ran): demote + quarantine;
                # the entry stays None so the probe restages
                _plane_logger.warning(
                    "[%s] mesh kNN staging failed for [%s]; plane "
                    "demotes with reason staging_fault",
                    self.index_name, field, exc_info=True)
                self.kernel_denied_reason = "staging_fault"
                return None
            finally:
                tracer.stop("staging.knn_embeddings", t)
        if not isinstance(entry, dict):
            return None
        return dict(entry, mode=mode)

    def _stage_knn_once(self, field: str, dims: int, metric: str):
        """``ensure_knn``'s cold path under the staging lock: the
        session entry, False where the field cannot stage on this
        segment set, None where the budget denied the attempt (the next
        request asks again); a terminal staging fault is the
        caller's."""
        from elasticsearch_tpu.common.staging import run_staged

        with self._kernel_stage_lock:
            entry = self._knn.get(field)
            if entry is not None:  # a racing cold stager decided
                return entry
            try:
                return run_staged(
                    lambda: self._stage_knn_plane(field, dims, metric),
                    index=self.index_name, kind="embeddings",
                    plane="mesh")  # retry: process-level config
            except _KnnStructuralError:
                # a REQUEST/mapping-shaped inability (dims mismatch
                # across segments): permanent for this segment set,
                # never a device fault — plane stays host quietly
                self._knn[field] = False
                return False

    def _stage_knn_plane(self, field: str, dims: int,
                         metric: str) -> Optional[dict]:
        """One staging ATTEMPT of a dense_vector field's kNN plane
        (inside run_staged's retry loop). Returns the session entry, or
        None on an HBM-budget denial; register-then-commit like
        _stage_kernel_plane."""
        import ml_dtypes

        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.ops import pallas_knn as pkn
        from elasticsearch_tpu.ops import pallas_scoring as psc
        from elasticsearch_tpu.testing.disruption import on_device_staging

        t0 = _time.monotonic()
        d_pad = pkn.pad_dims(dims)
        nd_knn = max(self.nd_pad, psc.LANE)
        # HBM budget gate (same demotion contract as ensure_kernel):
        # over budget the kNN batch serves from the host plan-node
        # rung, reason hbm_budget
        estimate = self.n_slots * nd_knn * (d_pad * 2 + 8)
        if not memory_accountant().try_reserve(
                self.index_name, estimate, exclude_scope=self.scope):
            self.kernel_denied_reason = "hbm_budget"
            return None
        emb = np.zeros((self.n_slots, nd_knn, d_pad), ml_dtypes.bfloat16)
        scale = np.zeros((self.n_slots, nd_knn, 1), np.float32)
        mask = np.zeros((self.n_slots, nd_knn, 1), np.float32)
        for i, seg in enumerate(self.segments):
            col = seg.vector_columns.get(field)
            if col is None:
                continue  # slot stays dead (mask all-zero)
            if col.dims != dims:
                raise _KnnStructuralError(
                    f"segment [{seg.name}] stores [{field}] at "
                    f"dims={col.dims}, mapping says {dims}")
            # the host mirror is already on the bf16 grid: the
            # astype below is exact
            emb[i, : col.vectors.shape[0], : dims] = col.vectors
            scale[i, : col.vectors.shape[0]] = pkn.vector_scale_column(
                col.vectors, metric)
            mask[i, : col.vectors.shape[0], 0] = _knn_live(seg, col)
        on_device_staging(self.index_name, "embeddings", f"knn:{field}")
        # all three device transfers must land before anything
        # publishes: a fault between them leaves only unreferenced
        # arrays for the GC (nothing in _seg_staged / the ledger)
        entry = {
            # flat: slot after slot along the rows, read in place
            "emb": jax.device_put(emb.reshape(-1, d_pad), self._sharding),
            "scale": jax.device_put(scale.reshape(-1, 1), self._sharding),
            "mask": jax.device_put(mask.reshape(-1, 1), self._sharding),
            "d_pad": d_pad,
            "nd_pad": nd_knn,
            "metric": metric,
            # mapping dims: delta_append verifies a new segment's column
            # against it before carrying this plane forward (ISSUE 20)
            "dims": dims,
            # which slots hold a live vector: those whose pass a query
            # pays for (the program reads the same mask on the device)
            "slot_live": mask.any(axis=(1, 2)),
        }
        entry["slots_scanned"] = int(entry["slot_live"].sum())
        self._knn[field] = entry
        dur = (_time.monotonic() - t0) * 1000.0
        self._account("embeddings", f"knn:{field}",
                      int(emb.nbytes), duration_ms=dur)
        self._account("scale_norm", f"knn_scale:{field}",
                      int(scale.nbytes), duration_ms=dur)
        self._account("live_mask", f"knn_mask:{field}",
                      int(mask.nbytes), duration_ms=dur)
        return entry

    def tile_lane_ub_cached(self, seg, union_lanes, row_lo, row_hi,
                            bfmax, sub: int) -> np.ndarray:
        """Per-(tile, lane) block-max bounds with per-lane caching: a
        lane's column depends only on (segment, tile geometry, posting
        run) — row windows come deterministically from the run's
        per-block doc ranges — so repeat queries on hot terms reuse it
        instead of re-gathering on the query hot path."""
        from elasticsearch_tpu.ops import pallas_scoring as psc

        n_tiles, t_pad = row_lo.shape
        ub = np.zeros((n_tiles, t_pad), np.float32)
        grew = False
        for j, lane in enumerate(union_lanes):
            key = (id(seg), sub, lane.block_start, lane.block_count)
            col = self._ub_cache.get(key)
            if col is None or col.shape[0] != n_tiles:
                if len(self._ub_cache) > 4096:  # runaway-vocab backstop
                    self._ub_cache.clear()
                col = psc.tile_lane_ub(row_lo[:, j: j + 1],
                                       row_hi[:, j: j + 1], bfmax)[:, 0]
                self._ub_cache[key] = col
                grew = True
            ub[:, j] = col
        if grew:
            # accumulator-style ledger entry: re-register the cache's
            # CURRENT total (quiet — per-lane growth is not a staging
            # lifecycle event, docs/OBSERVABILITY.md)
            self._account("bound_tables", "ub_cache",
                          sum(int(c.nbytes)
                              for c in self._ub_cache.values()),
                          quiet=True)
        return ub

    def ensure_kernel_live(self, sub: int) -> str:
        """Per-sub live-mask layout for a shrunk tile geometry (dense-term
        queries — the geometry ladder); mirrors Segment.kernel_live_t_for
        but over the stacked slot axis."""
        from elasticsearch_tpu.ops import pallas_scoring as psc

        key = f"k_live_t_{sub}"
        if key not in self._seg_staged:
            from elasticsearch_tpu.testing.disruption import (
                on_device_staging,
            )

            t0 = _time.monotonic()
            geom = psc.tile_geometry(self._kernel["geom"].nd_pad, sub)
            live_t = np.zeros(
                (self.n_slots, geom.n_tiles * psc.LANE, geom.tile_sub),
                np.float32)
            for i, seg in enumerate(self.segments):
                live = np.zeros(geom.nd_pad, np.float32)
                live[: seg.nd_pad] = seg.live.astype(np.float32)
                live_t[i] = psc.build_live_t(live, geom)
            # a raise here lands in the calling launch's fault handler
            # (per-sub mask variants stage inside the launch try)
            on_device_staging(self.index_name, "live_mask", key)
            self._seg_staged[key] = jax.device_put(live_t, self._sharding)
            self._account("live_mask", key, int(live_t.nbytes),
                          reason="geometry_change",
                          duration_ms=(_time.monotonic() - t0) * 1000.0)
        return key

    def harmonize_kernel_nodes(self, plans: List[PlanNode]) -> int:
        """Finalize every deferred mesh kernel node so table shapes agree
        across the whole segment set: one (tile_sub, t_pad, cb) for each
        aligned node group, chosen by the geometry ladder collectively
        (a dense term on ANY shard shrinks everyone's tile). Returns the
        number of kernel node groups finalized; raises
        PlanStructureMismatch when no shared geometry exists (caller
        retries with scatter nodes)."""
        from elasticsearch_tpu.index.segment import next_pow2
        from elasticsearch_tpu.ops import pallas_scoring as psc
        from elasticsearch_tpu.search.plan import PallasScoreTermsNode

        groups: List[List[PlanNode]] = []

        def walk(nodes):
            if all(isinstance(n, PallasScoreTermsNode) for n in nodes):
                groups.append(list(nodes))
            kids = [n.children() for n in nodes]
            if len({len(ks) for ks in kids}) != 1:
                raise PlanStructureMismatch("tree arity diverges")
            for child_set in zip(*kids):
                walk(list(child_set))

        walk(plans)
        if not groups:
            return 0
        session = self._kernel
        if not isinstance(session, dict):
            raise PlanStructureMismatch("kernel plane not staged")
        geom = session["geom"]
        tps = psc.TILES_PER_STEP
        for nodes in groups:
            if any(n._mesh_lanes is None for n in nodes):
                raise PlanStructureMismatch(
                    "kernel/scatter node mix across shards")
            t_pad = max(next_pow2(max(len(n._mesh_lanes), 1))
                        for n in nodes)
            sub = geom.tile_sub
            while True:
                g = geom if sub == geom.tile_sub else psc.tile_geometry(
                    geom.nd_pad, sub)
                try:
                    tables = [psc.build_tile_tables(
                        n._mesh_lanes, n._mesh_bmin, n._mesh_bmax, g,
                        t_pad=t_pad) for n in nodes]
                    break
                except ValueError:
                    # covering window exceeded the kernel bound somewhere
                    # (or malformed ranges at the ladder floor)
                    if sub <= 32 or g.tile_sub < sub:
                        raise PlanStructureMismatch(
                            "no shared kernel geometry for this query")
                    sub //= 2
            cb = max(t[3] for t in tables)
            live_key = ("k_live_t" if g.tile_sub == geom.tile_sub
                        else self.ensure_kernel_live(g.tile_sub))
            for n, (rl, rh, w, _cb) in zip(nodes, tables):
                n.finalize_mesh(rl, rh, w, cb=cb, sub=g.tile_sub,
                                live_key=live_key, tiles_per_step=tps)
        return len(groups)

    def numeric_ordinals(self, field: str) -> np.ndarray:
        """Sorted distinct values of a numeric field over the staged
        segments (``index/global_ordinals.numeric_global_ordinals``),
        kept for this generation: a value's position in it is its global
        ordinal, which numeric ``terms`` count by and rank-keyed sorts
        order by."""
        values = self._numeric_ords.get(field)
        if values is None:
            from elasticsearch_tpu.index.global_ordinals import (
                numeric_global_ordinals,
            )

            values = self._numeric_ords[field] = numeric_global_ordinals(
                [s.numeric_columns.get(field) for s in self.segments])
        return values

    def ensure_numeric_column(self, field: str) -> Optional[str]:
        """Stage a single-valued numeric field as ONE dense int64 column
        in sortable order (``search/plan.sortable_int64``; a document
        with no value: ``SORTABLE_MISSING``) and return its seg-dict
        name, or None where the field is absent, multi-valued in some
        segment, or the budget turns the staging away: the CSR filter
        nodes then carry their column with each query, as before. Staged
        once a generation under the ``doc_values`` ledger kind; a warm
        request pays a dict lookup."""
        name = f"mnum.{field}"
        if name in self._seg_staged:
            return name
        from elasticsearch_tpu.search.fused_aggs import _metric_field_checks
        from elasticsearch_tpu.search.plan import (
            SORTABLE_MISSING,
            sortable_int64,
        )

        facts = _metric_field_checks(self, field)  # kept a generation
        if not (facts["present"] and facts["single"]):
            return None
        cols = [s.numeric_columns.get(field) for s in self.segments]

        def build():
            out = np.full((self.n_slots, self.nd1), SORTABLE_MISSING,
                          np.int64)
            for i, c in enumerate(cols):
                if c is not None:
                    n = c.exists.shape[0]
                    out[i, :n] = np.where(
                        c.exists, sortable_int64(c.first_value),
                        SORTABLE_MISSING)
            return {name: out}

        # (a budget denial is not kept: the next query asks again)
        return name if self.stage_doc_value_columns({name: build}) else None

    def ensure_sort_column(self, field: str, order: str, missing,
                           tracer=NULL_TRACER) -> Optional[Tuple[str, str]]:
        """Stage (oriented key, raw values) columns for a single-field sort
        and return their seg-dict names, or None if the field can't sort
        exactly on the mesh. One pair per (field, order, missing), staged
        once a generation (the ``staging.sort_column`` span): a warm
        request finds the names and stages nothing.

        The in-program rank key is f32. A numeric column whose values are
        all exactly f32-representable (a status, a size) is its own key.
        One whose values are not (epoch milliseconds: f32 resolves 65,536
        ms at 1998) ranks by each document's position in the sorted union
        of the staged segments' distinct values (``numeric_ordinals``),
        exact below 2^24 distinct values; the response's ``sort`` value is
        that union's entry, the stored number. The oriented key follows
        _sort_keys: negate for asc, missing-fill with finite sentinels so
        -inf stays reserved for "not matched".

        Keyword fields rank the same way by GLOBAL ordinals: per-segment
        ordinal spaces are meaningless across shards (the reference's
        global-ordinals problem, fielddata/ordinals/GlobalOrdinalsBuilder),
        so the staged key is each doc's position in the sorted union of
        every staged segment's terms."""
        token = (repr(missing) if isinstance(missing, (int, float))
                 else str(missing or "_last"))
        name = f"msort.{field}.{order}.{token}"
        if name in self._seg_staged:
            return name, name + ".raw"
        t = tracer.start("staging.sort_column")
        try:
            built = self._sort_column_values(field, order, missing)
            if built is None:
                return None
            per_seg, vocab = built
            big = np.float32(3.0e38)
            keys = np.zeros((self.n_slots, self.nd1), np.float32)
            raws = np.zeros((self.n_slots, self.nd1), np.float32)
            for i, (seg, raw) in enumerate(zip(self.segments, per_seg)):
                key = np.clip(raw if order == "desc" else -raw, -big, big)
                keys[i, : seg.nd_pad] = key.astype(np.float32)
                keys[i, seg.nd_pad:] = -big  # padding never outranks real docs
                raws[i, : seg.nd_pad] = raw.astype(np.float32)
            self._seg_staged[name] = jax.device_put(keys, self._sharding)
            self._seg_staged[name + ".raw"] = jax.device_put(
                raws, self._sharding)
            # sort key columns are doc-values-plane tables (ISSUE 13): they
            # derive from the same sealed columns the fused aggs stage, so
            # they account under the doc_values ledger kind (docs/AGGS.md)
            self._account("doc_values", name,
                          int(keys.nbytes + raws.nbytes))
            self.sort_meta[name] = {"vocab": vocab}
            return name, name + ".raw"
        finally:
            tracer.stop("staging.sort_column", t)

    def _sort_column_values(self, field: str, order: str, missing):
        """(per-segment float64 [nd_pad] of what ranks each document, the
        missing-fill applied; vocab) or None. ``vocab`` None: the values
        rank themselves. A list of terms or an array of numbers: the
        values are positions in it (see ensure_sort_column)."""
        big = np.float64(3.0e38)
        if missing is None or missing == "_last":
            fill = -big if order == "desc" else big
        elif missing == "_first":
            fill = big if order == "desc" else -big
        else:
            fill = None  # a custom value: ranks among the real ones
        ords = [s.ordinal_columns.get(field)
                or s.ordinal_columns.get(f"{field}.keyword")
                for s in self.segments]
        if any(o is not None for o in ords):
            if fill is None:
                return None  # custom-string missing ranks mid-vocab: host
            vocab: List[str] = sorted(
                set().union(*(o.terms for o in ords if o is not None)))
            if len(vocab) >= (1 << 24):
                return None  # ordinal not f32-exact
            per_seg = []
            for seg, ocol in zip(self.segments, ords):
                if ocol is None:  # every doc in that segment is missing
                    per_seg.append(np.full(seg.nd_pad, fill))
                    continue
                # local ordinal -> global ordinal (terms are sorted, so
                # searchsorted is the OrdinalMap build)
                g = np.searchsorted(vocab, ocol.terms).astype(np.float64)
                per_seg.append(np.where(ocol.exists, g[ocol.first_ord],
                                        fill))
            return per_seg, vocab
        if field == "_doc":
            if any(seg.nd_pad > (1 << 24) for seg in self.segments):
                return None  # doc id not f32-exact
            return [np.arange(seg.nd_pad, dtype=np.float64)
                    for seg in self.segments], None
        cols = [seg.numeric_columns.get(field) for seg in self.segments]
        if any(c is None for c in cols):
            return None
        raws = [(c.min_value if order == "asc" else c.max_value
                 ).astype(np.float64) for c in cols]
        exact = all(np.array_equal(r[c.exists], r[c.exists].astype(
            np.float32).astype(np.float64)) for r, c in zip(raws, cols))
        vocab = None
        if not exact:
            # not f32-representable (a NaN among them neither): rank by
            # global ordinal, which no custom fill has a place in
            vocab = self.numeric_ordinals(field)
            if (fill is None or len(vocab) >= (1 << 24)
                    or not np.all(np.isfinite(vocab))):
                return None
            raws = [np.searchsorted(vocab, r).astype(np.float64)
                    for r in raws]
        if fill is None:
            fill = np.float64(missing)
        return [np.where(c.exists, r, fill)
                for r, c in zip(raws, cols)], vocab

    def ensure_slice_column(self, slice_spec: dict,
                            shard_of_device: List[int],
                            num_shards: int) -> Optional[str]:
        """Stage the deterministic slice doc partition as a boolean mask
        column, shard-aware like the host path (SliceBuilder.toFilter's
        three regimes — see search/service.resolve_slice); shares the
        host path's per-segment mask cache."""
        from elasticsearch_tpu.search.service import resolve_slice
        from elasticsearch_tpu.utils.murmur3 import hash_slice_id

        sid = int(slice_spec["id"])
        smax = int(slice_spec["max"])
        name = f"mslice.{smax}.{sid}.{num_shards}"
        if name in self._seg_staged:
            return name
        out = np.zeros((self.n_slots, self.nd1), bool)
        for i, seg in enumerate(self.segments):
            resolved = resolve_slice(slice_spec, shard_of_device[i],
                                     num_shards)
            if resolved == "skip":
                continue  # all-False row
            if resolved is None:
                out[i, : seg.nd_pad] = True  # whole shard in the slice
                continue
            rid, rmax = int(resolved["id"]), int(resolved["max"])
            cache_key = f"slice.{rmax}.{rid}"  # same key the host uses
            mask = seg.dev_cache.get(cache_key)
            if mask is None:
                mask = np.zeros(seg.nd_pad + 1, dtype=bool)
                for local, doc_id in enumerate(seg.doc_ids):
                    if hash_slice_id(doc_id) % rmax == rid:
                        mask[local] = True
                seg.dev_cache[cache_key] = mask
            out[i, : mask.shape[0]] = mask
        self._seg_staged[name] = jax.device_put(out, self._sharding)
        self._account("mesh_slot_tables", name, int(out.nbytes))
        return name

    def stage_doc_value_columns(self, builds: Dict[str, object],
                                tracer=NULL_TRACER) -> bool:
        """Stage fused-aggregation doc-value columns (ISSUE 13,
        docs/AGGS.md): ``builds`` maps a representative table name to a
        callable producing ``{name: np.ndarray}`` groups of per-slot
        columns. Registered under the ``doc_values`` ledger kind with
        the PR-9/PR-10 contracts: budget-gated (``try_reserve`` — a
        denial returns False and the caller demotes the aggs to the
        host reduce with reason ``hbm_budget``), TRANSACTIONAL
        (register-then-commit: nothing publishes or registers until
        every transfer landed; a fault mid-group leaves no trace), and
        evictable with this executor generation's scope. Transient
        device faults retry with the classified backoff
        (``search.staging.retry.*``); a terminal fault propagates to
        the caller (fallback reason ``staging_fault``). The whole of it,
        building the columns on the host included, is the request's
        ``staging.doc_values`` span."""
        t = tracer.start("staging.doc_values")
        try:
            return self._stage_doc_value_columns(builds)
        finally:
            tracer.stop("staging.doc_values", t)

    def _stage_doc_value_columns(self, builds: Dict[str, object]) -> bool:
        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.common.staging import run_staged

        with self._kernel_stage_lock:
            arrays: Dict[str, np.ndarray] = {}
            for fn in builds.values():
                for name, arr in fn().items():
                    if name not in self._seg_staged:
                        arrays[name] = arr
            if not arrays:
                return True
            estimate = sum(int(a.nbytes) for a in arrays.values())
            if not memory_accountant().try_reserve(
                    self.index_name, estimate, exclude_scope=self.scope):
                return False

            def _attempt():
                from elasticsearch_tpu.testing.disruption import (
                    on_device_staging,
                )

                t0 = _time.monotonic()
                on_device_staging(self.index_name, "doc_values",
                                  "agg_columns")
                staged = {name: jax.device_put(a, self._sharding)
                          for name, a in arrays.items()}
                # publish atomically-enough (dict.update under the GIL)
                # AFTER every transfer landed, then register the exact
                # bytes — a fault above leaves nothing behind
                self._seg_staged.update(staged)
                dur = (_time.monotonic() - t0) * 1000.0
                for name, a in arrays.items():
                    self._account("doc_values", name, int(a.nbytes),
                                  duration_ms=dur)

            run_staged(_attempt, index=self.index_name,
                       kind="doc_values", plane="mesh")
        return True

    def execute(self, plans: List[PlanNode], k: int,
                sort_keys: Optional[Tuple[str, str]] = None,
                with_views: bool = False,
                pf_plans: Optional[List[PlanNode]] = None,
                rs_plans: Optional[List[PlanNode]] = None,
                scalars: Optional[dict] = None,
                features: frozenset = frozenset(),
                slice_col: Optional[str] = None,
                rescore_static: Optional[Tuple[int, str]] = None,
                tracer=NULL_TRACER, agg_static: tuple = (),
                telemetry=None):
        """plans: one per shard, same query. Returns (packed int32
        [2 + 5k] — ``_unpack_answer`` gives top_keys [k], top_slot [k],
        top_doc [k], total, top_score [k], top_raw [k] —, counts
        [n_slots] [, matched [n_slots, nd1], scores [n_slots, nd1]]
        [, fused-agg partials...]) — doc ids are in the STACKED doc
        space (valid per-shard ids since every shard zero-bases).

        pf_plans / rs_plans: optional per-shard post_filter and rescore
        query plans; scalars: traced values for `features` and rescore
        weights (compiled once per feature SET, not per value).
        agg_static: fused-agg descriptors (search/fused_aggs.py) whose
        staged doc-value columns reduce inside the program."""
        if len(plans) != len(self.segments):
            raise ValueError("one plan per staged shard required")
        t_stage = tracer.start("staging")
        local_pads = [s.nd_pad for s in self.segments]
        stacked = stack_plans(plans, local_pads, self.nd1, self.n_slots)
        key_parts = [plans[0].key(), _shapes_sig(stacked)]
        stacked_pf: List[np.ndarray] = []
        stacked_rs: List[np.ndarray] = []
        pf_tpl = rs_tpl = None
        if pf_plans:
            stacked_pf = stack_plans(pf_plans, local_pads, self.nd1,
                                     self.n_slots)
            pf_tpl = _strip_plan(pf_plans[0])
            key_parts += ["pf:" + pf_plans[0].key(), _shapes_sig(stacked_pf)]
        if rs_plans:
            stacked_rs = stack_plans(rs_plans, local_pads, self.nd1,
                                     self.n_slots)
            rs_tpl = _strip_plan(rs_plans[0])
            key_parts += ["rs:" + rs_plans[0].key(), _shapes_sig(stacked_rs)]
        key = ("|".join(key_parts)
               + f"|k{k}|n{self.n_dev}|p{self.slots_per_dev}"
               + f"|s{sort_keys}|v{with_views}"
               + f"|f{sorted(features)}|sl{slice_col}|r{rescore_static}"
               + f"|a{agg_static}")
        # What the request brings rides the launch as ONE host array (the
        # jitted call places it as ``shard_map``'s in_specs say): a sharded
        # ``device_put`` an array ahead of the launch costs ~0.3 ms of
        # Python each on a v5e's host, a host array of its own inside the
        # call ~0.14 (PERF.md 6 PR 36).
        names = tuple(sorted(scalars or ()))
        packed, loose, layout = _pack_plan_arrays(
            [*stacked, *stacked_pf, *stacked_rs,
             *(np.full(self.n_slots, scalars[n], np.float32)
               for n in names)], self.n_slots)
        run = _mesh_query_program(
            self.mesh,
            _TemplateHolder(_strip_plan(plans[0]), key, pf_tpl, rs_tpl), k,
            spd=self.slots_per_dev,
            sort_keys=sort_keys, with_views=with_views, features=features,
            slice_col=slice_col, rescore_static=rescore_static,
            agg_static=agg_static, packing=(
                layout, (len(stacked), len(stacked_pf), len(stacked_rs)),
                names))
        tracer.stop("staging", t_stage)
        wanted = set(sort_keys or ())
        if slice_col is not None:
            wanted.add(slice_col)
        if agg_static:
            from elasticsearch_tpu.search.fused_aggs import agg_column_keys

            wanted.update(agg_column_keys(agg_static))
        for tpl in (plans[0], pf_tpl, rs_tpl):
            if tpl is not None:
                wanted.update(tpl.flat_seg_columns())
        # The program's argument: the base slot tables, and of the
        # columns staged on demand (sort keys, slice masks, doc values,
        # filter columns: ``_ON_DEMAND``) those this request names. The
        # argument's keys are part of the jitted function's cache key:
        # handed the whole of ``_seg_staged``, every program was traced
        # and compiled again after a later request staged a column.
        seg = {name: a for name, a in self._seg_staged.items()
               if name in wanted or not name.startswith(_ON_DEMAND)}
        outs = _launch_locked(tracer, run, seg, packed, loose)
        if telemetry is not None:
            # host arrays the launch carried (the pack, and what could
            # not go in) / arrays put on the device ahead of it; occupied
            # slots over all: the share a query pays for
            telemetry.add_counters({
                "h2d_arrays": 1 + len(loose), "explicit_puts": 0,
                "mesh_slots": self.n_slots,
                "mesh_slots_occupied": len(self.segments)})
        return outs
