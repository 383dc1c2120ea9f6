"""Fused on-device aggregations: the columnar doc-values plane (ISSUE 13).

Role model: the reference spends ~1/3 of its search subsystem on doc
values + the aggs framework (SURVEY §2.4 — ``index/fielddata/``,
``search/aggregations/``), collecting doc-at-a-time on the heap AFTER
the query phase returned candidates. Our inversion until this module
kept that shape on the accelerator: the mesh program scored tiles on
device, then shipped every slot's dense matched mask back to the host
(``with_views``) and re-read the doc-value columns there — an agg'd
query paid a full host round-trip plus a second corpus read.

This module moves eligible aggregations INTO the compiled mesh program
(``parallel/plan_exec._mesh_query_program`` and the batched dense
program): per-segment doc-value columns are sealed at segment build,
staged per slot as device arrays under the ``doc_values`` ledger kind
(``MeshPlanExecutor.stage_doc_value_columns`` — transactional,
budget-gated, evictable), and each slot's matched mask reduces into
tiny per-spec partial accumulators inside the same launch that scored
the corpus. Only the accumulators (a few KB) cross to the host; the
masks never leave the device.

Byte-identity with the host oracle (docs/AGGS.md) is engineered, not
hoped for:

- **bucket codes are precomputed host-side at staging time** with the
  exact arithmetic the host reduce uses (global-ordinal mapping for
  terms; the f64 ``floor((v - offset) / interval)`` bucket formula for
  histogram/date_histogram), cached per (field, interval, offset) on
  the executor — the device only counts int32 codes, so bucketing can
  never diverge by f32 rounding;
- **counts** accumulate in int32 (exact), by a compare-and-sum or a
  one-hot product, never a scatter (``_bucket_counts``);
- **sums** ride an exact integer-digit decomposition: each value
  ``v`` (eligible only when every value is an integer with
  ``|v| < 2^48`` and the column's ``sum(|v|) < 2^53`` — epoch-millis
  dates, counters, prices) is offset to ``u = v + 2^49`` and split
  into six 9-bit digits staged as int16 columns; per-slot digit sums
  stay below 2^31 (int32-exact for any mask), and the host
  reconstructs the exact integer sum with Python bignums. The
  ``sum(|v|) < 2^53`` bound also makes the host's own f64 reduction
  exact, so both sides land on the same float;
- **min/max** split each value into ``(floor(v / 2^24), remainder)``
  f32 pairs (exact for the same integer range) and reduce
  lexicographically on device.

Anything outside the engineered-exact envelope — sub-aggregations,
multi-valued fields, calendar intervals, non-integer metric values,
text fielddata, bucket ranges past the caps — falls back STRUCTURALLY
to the host reduce over the program's matched views (the previous
behavior, and the parity oracle), counted per reason in
``agg_host_fallback_by_reason`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.search.aggregations import (
    AggSpec,
    _date_interval_ms,
    _finalize_metric,
    finalize_histogram,
    finalize_terms,
)
from elasticsearch_tpu.search.telemetry import NULL_TRACER

# metric sums: v is offset to u = v + VALUE_OFFSET and split into
# N_DIGITS base-2^DIGIT_BITS digits; 6 * 9 bits cover u < 2^54 and a
# per-slot digit sum stays < 512 * nd_pad < 2^31 for nd_pad <= 2^21
DIGIT_BITS = 9
DIGIT_BASE = 1 << DIGIT_BITS
N_DIGITS = 6
VALUE_OFFSET = 1 << 49
MAX_ABS_VALUE = 1 << 48
MAX_ABS_SUM = 1 << 53  # f64-exact bound for the host oracle's own sum
MAX_SLOT_DOCS = 1 << 21  # int32-exactness bound for per-slot digit sums
MM_SPLIT = float(1 << 24)  # min/max hi/lo split point (both halves f32-exact)

MAX_HIST_BUCKETS = 4096
MAX_TERMS_ORDS = 1 << 16
# The largest bucket count that ``_bucket_counts`` takes by the dense
# compare-and-sum; above it the one-hot product. Device time of one
# count of 65,537 documents, us (TPU v5e; PERF.md 6, PR 34):
#
#   buckets            5    96   128   1024   2048   3072   4096   65536
#   scatter-add      577   577   577    577    479    456    449     444
#   dense compare      2     5    39     51    109    163    249   6,243
#   one-hot product   76    76    76    121    121    123    124      99
#
# (of 1,048,577 documents at 2,048 buckets: 7,611 / 1,727 / 1,926). The
# dense count grows with the buckets and the product hardly does; they
# cross just above 2,048, whatever the number of documents.
DENSE_COUNT_MAX_BUCKETS = 2048

FUSED_BUCKET_TYPES = ("terms", "histogram", "date_histogram")
FUSED_METRIC_TYPES = ("min", "max", "sum", "avg", "stats", "value_count")

# request-body keys the fused formulation covers per agg type; anything
# else (missing, script, shard_size, calendar intervals, ...) keeps the
# host reduce, which owns the full surface
_ALLOWED_BODY = {
    "terms": {"field", "size", "order"},
    "histogram": {"field", "interval", "offset", "min_doc_count"},
    "date_histogram": {"field", "interval", "fixed_interval", "offset",
                       "min_doc_count", "time_zone"},
    "min": {"field"}, "max": {"field"}, "sum": {"field"},
    "avg": {"field"}, "stats": {"field"}, "value_count": {"field"},
}


class FusedAggPlan:
    """One query's resolved fused aggregation set.

    ``ops`` (aligned with ``specs``) are the STATIC per-spec descriptors
    baked into the compiled program's cache key:

      ("empty",)                      field absent everywhere — no device
                                      work, finalize emits the empty frame
      ("bucket", col_key, nb)         terms / histogram / date_histogram:
                                      count int32 codes into [nb] buckets
      ("metric", base, mm, dig)       stats family over base+".ex" /
                                      ".mm" / ".dig" columns

    ``metas`` carry the host-side finalize context (vocab, bucket-key
    reconstruction parameters)."""

    __slots__ = ("specs", "ops", "metas")

    def __init__(self, specs: List[AggSpec], ops: List[tuple],
                 metas: List[dict]):
        self.specs = specs
        self.ops = ops
        self.metas = metas

    @property
    def statics(self) -> tuple:
        return tuple(self.ops)

    def column_keys(self) -> List[str]:
        return agg_column_keys(self.statics)

    def staged_bytes(self, seg_staged: dict) -> int:
        return sum(int(seg_staged[k].nbytes) for k in self.column_keys()
                   if k in seg_staged)


def agg_column_keys(statics: tuple) -> List[str]:
    """The staged doc-value columns a descriptor set reads."""
    keys: List[str] = []
    for op in statics:
        if op[0] == "bucket":
            keys.append(op[1])
        elif op[0] == "metric":
            _, base, want_mm, want_dig = op
            keys.append(base + ".ex")
            if want_mm:
                keys.append(base + ".mm")
            if want_dig:
                keys.append(base + ".dig")
    return keys


def n_agg_outputs(statics: tuple) -> int:
    n = 0
    for op in statics:
        if op[0] == "bucket":
            n += 1
        elif op[0] == "metric":
            n += 1 + int(op[2]) + int(op[3])
    return n


# ---------------------------------------------------------------------------
# Device-side partial emission (traced inside the mesh programs)
# ---------------------------------------------------------------------------


def _bucket_counts(codes, mask, nb: int):
    """int32 [nb]: how many of the masked documents carry each bucket
    code. ``codes``: int32 [nd1], -1 = no value. Not a scatter-add: the
    chip runs its updates one after the other (0.44-0.58 ms for 65,537
    documents, whatever ``nb``). Both forms below give the same exact
    integers and compile to one fusion that writes nothing of
    ``nb x nd1`` to memory; ``DENSE_COUNT_MAX_BUCKETS`` has the table
    that chooses."""
    import jax
    import jax.numpy as jnp

    # a masked document counts like one with no value: -1 is no bucket
    codes = jnp.where(mask, codes, jnp.int32(-1))
    if nb <= DENSE_COUNT_MAX_BUCKETS:
        # every code against every bucket, summed over the documents
        buckets = jnp.arange(nb, dtype=jnp.int32)[:, None]
        return jnp.sum(codes[None, :] == buckets, axis=1, dtype=jnp.int32)
    # code = 128 * hi + lo, and counts[hi, lo] = sum over the documents
    # of onehot(hi) * onehot(lo): nd1 x (nb / 128 + 128) compares and a
    # matrix product of int8 one-hots accumulated in int32 on the MXU
    # (-1 >> 7 = -1 is no row)
    n_hi = -(-nb // 128)
    hi = (codes >> 7)[:, None] == jnp.arange(n_hi, dtype=jnp.int32)
    lo = (codes & 127)[:, None] == jnp.arange(128, dtype=jnp.int32)
    counts = jax.lax.dot_general(
        hi.astype(jnp.int8), lo.astype(jnp.int8),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return counts.reshape(-1)[:nb]


def emit_agg_partials(statics: tuple, seg: dict, mask):
    """Per-slot partial accumulators for one (slot, mask) pair, traced
    into the mesh program. ``mask``: bool [nd1] — the agg-visible
    matched mask (post min_score/slice, pre post_filter, live applied).
    Output order matches ``n_agg_outputs``; every array is tiny (bucket
    counts / digit sums / min-max pairs), int32-exact or f32-exact per
    the module contract."""
    import jax.numpy as jnp

    outs = []
    for op in statics:
        if op[0] == "empty":
            continue
        if op[0] == "bucket":
            _, key, nb = op
            outs.append(_bucket_counts(seg[key], mask, nb))
            continue
        _, base, want_mm, want_dig = op
        sel = mask & seg[base + ".ex"]
        outs.append(jnp.sum(sel.astype(jnp.int32))[None])  # [1] count
        if want_mm:
            mm = seg[base + ".mm"]  # [nd1, 2] f32: (floor(v/2^24), rest)
            hi, lo = mm[:, 0], mm[:, 1]
            inf = jnp.float32(jnp.inf)
            minhi = jnp.min(jnp.where(sel, hi, inf))
            minlo = jnp.min(jnp.where(sel & (hi == minhi), lo, inf))
            maxhi = jnp.max(jnp.where(sel, hi, -inf))
            maxlo = jnp.max(jnp.where(sel & (hi == maxhi), lo, -inf))
            outs.append(jnp.stack([minhi, minlo, maxhi, maxlo]))
        if want_dig:
            dig = seg[base + ".dig"].astype(jnp.int32)  # [nd1, N_DIGITS]
            outs.append(jnp.sum(jnp.where(sel[:, None], dig, 0), axis=0))
    return outs


# ---------------------------------------------------------------------------
# Eligibility + column builds (host side, once per executor generation)
# ---------------------------------------------------------------------------


def _metric_field_checks(executor, field: str) -> dict:
    """Column-wide eligibility facts for a numeric field, cached on the
    executor (one scan per field per staged generation)."""
    cache = getattr(executor, "_agg_field_checks", None)
    if cache is None:
        cache = executor._agg_field_checks = {}
    hit = cache.get(field)
    if hit is not None:
        return hit
    cols = [s.numeric_columns.get(field) for s in executor.segments]
    present = [c for c in cols if c is not None and c.count > 0]
    facts = {"present": bool(present), "single": True, "finite": True,
             "int48": True, "abs_sum_ok": True}
    abs_sum = 0.0
    for c in present:
        vals = c.flat_values[: c.count]
        if c.count != int(c.exists.sum()):
            facts["single"] = False
        if not np.all(np.isfinite(vals)):
            facts["finite"] = False
            continue
        if not (np.all(vals == np.floor(vals))
                and np.all(np.abs(vals) < MAX_ABS_VALUE)):
            facts["int48"] = False
        abs_sum += float(np.abs(vals).sum())
    if abs_sum >= MAX_ABS_SUM:
        facts["abs_sum_ok"] = False
    cache[field] = facts
    return facts


def _build_bucket_codes(executor, per_seg_codes) -> np.ndarray:
    """[n_slots, nd1] int32 codes column from per-segment local code
    arrays (length seg.nd_pad, -1 = no value), each at its segment's
    slot."""
    out = np.full((executor.n_slots, executor.nd1), -1, np.int32)
    for i, codes in zip(executor.slots, per_seg_codes):
        if codes is not None:
            out[i, : codes.shape[0]] = codes
    return out


def _resolve_terms(spec, executor, ops, metas, builds) -> Optional[str]:
    from elasticsearch_tpu.index.global_ordinals import global_ordinals

    field = spec.body.get("field")
    segs = executor.segments
    ocols = [s.ordinal_columns.get(field)
             or s.ordinal_columns.get(f"{field}.keyword") for s in segs]
    if all(o is None for o in ocols):
        if any(s.numeric_columns.get(field) is not None for s in segs):
            return _resolve_numeric_terms(field, executor, ops, metas,
                                          builds)
        if any(s.terms_for_field(field) for s in segs):
            # text fielddata builds lazily on the host (breaker-gated) —
            # the fused plane stages sealed keyword ordinals only
            return "field_ineligible"
        ops.append(("empty",))
        metas.append({"kind": "terms"})
        return None
    cache = getattr(executor, "_agg_field_checks", None)
    if cache is None:
        cache = executor._agg_field_checks = {}
    single = cache.get(("ord_single", field))
    if single is None:
        single = all(o is None or o.count == int(o.exists.sum())
                     for o in ocols)
        cache[("ord_single", field)] = single
    if not single:
        return "multi_valued"
    gords = global_ordinals(segs, field, columns=ocols)
    nb = len(gords.terms)
    if nb > MAX_TERMS_ORDS:
        return "bucket_range"
    if nb == 0:
        ops.append(("empty",))
        metas.append({"kind": "terms"})
        return None
    name = f"maggs.ord.{field}"
    if name not in executor._seg_staged and name not in builds:
        def build(gords=gords, ocols=list(ocols), name=name):
            per_seg = []
            for s, o in zip(segs, ocols):
                if o is None:
                    per_seg.append(None)
                    continue
                gmap = gords.seg_map(s)
                codes = np.where(
                    o.exists, gmap[np.clip(o.first_ord, 0, None)],
                    np.int32(-1)).astype(np.int32)
                per_seg.append(codes)
            return {name: _build_bucket_codes(executor, per_seg)}

        builds[name] = build
    ops.append(("bucket", name, nb))
    # read-only reference: the GlobalOrdinals cache owns the list
    metas.append({"kind": "terms", "vocab": gords.terms})
    return None


def _resolve_numeric_terms(field, executor, ops, metas,
                           builds) -> Optional[str]:
    """``terms`` on a single-valued numeric column: bucket codes are the
    ordinals of the sorted union of the staged segments' distinct values
    (``MeshPlanExecutor.numeric_ordinals``), as keyword terms go through
    ``global_ordinals``; keys come back as the host reduce renders them
    (``_partial_terms``: an int where the value is whole)."""
    facts = _metric_field_checks(executor, field)
    if not facts["single"]:
        return "multi_valued"
    if not facts["finite"]:
        return "values_not_fusable"  # NaN has no ordinal
    values = executor.numeric_ordinals(field)
    nb = len(values)
    if nb > MAX_TERMS_ORDS:
        return "bucket_range"
    if nb == 0:
        ops.append(("empty",))
        metas.append({"kind": "terms"})
        return None
    name = f"maggs.nord.{field}"
    if name not in executor._seg_staged and name not in builds:
        def build(name=name):
            per_seg = []
            for s in executor.segments:
                c = s.numeric_columns.get(field)
                per_seg.append(None if c is None else np.where(
                    c.exists, np.searchsorted(values, c.first_value),
                    -1).astype(np.int32))
            return {name: _build_bucket_codes(executor, per_seg)}

        builds[name] = build
    cache = executor._agg_field_checks
    vocab = cache.get(("nord_keys", field))
    if vocab is None:
        vocab = cache[("nord_keys", field)] = [
            int(v) if v.is_integer() else v for v in values.tolist()]
    ops.append(("bucket", name, nb))
    metas.append({"kind": "terms", "vocab": vocab})
    return None


def _resolve_histogram(spec, executor, ops, metas, builds) -> Optional[str]:
    from elasticsearch_tpu.common.errors import ParsingException

    is_date = spec.type == "date_histogram"
    body = spec.body
    field = body.get("field")
    if is_date:
        if body.get("time_zone") not in (None, "UTC"):
            return "unsupported_params"  # buckets are cut in UTC only
        interval_spec = body.get("interval") or body.get("fixed_interval")
        if interval_spec is None:
            return "unsupported_params"
        try:
            ms = _date_interval_ms(interval_spec)
        except ParsingException:
            return "field_ineligible"  # host path owns the 400
        if ms is None:
            return "unsupported_params"  # week, month, quarter, year
        interval = float(ms)
    else:
        try:
            interval = float(body["interval"])
        except (KeyError, TypeError, ValueError):
            return "field_ineligible"  # host path owns the 400
        if not (interval > 0):
            return "field_ineligible"
    offset = body.get("offset", 0) or 0
    if isinstance(offset, bool) or not isinstance(offset, (int, float)):
        return "unsupported_params"
    offset = float(offset)
    segs = executor.segments
    cols = [s.numeric_columns.get(field) for s in segs]
    if all(c is None or c.count == 0 for c in cols):
        ops.append(("empty",))
        metas.append({"kind": "hist", "is_date": is_date})
        return None
    facts = _metric_field_checks(executor, field)
    if not facts["single"]:
        return "multi_valued"
    if not facts["finite"]:
        return "values_not_fusable"
    # bucket-range resolution is an O(corpus) column scan: cache the
    # verdict per (field, interval, offset) on the executor generation
    # (zipfian dashboard traffic repeats the same histogram params), so
    # repeat queries pay a dict hit, not a corpus pass
    cache = getattr(executor, "_agg_field_checks", None)
    if cache is None:
        cache = executor._agg_field_checks = {}
    name = (f"maggs.hist.{field}.{spec.type}.{interval!r}.{offset!r}")
    cached = cache.get(("hist", name))
    if cached is None:
        b_min = b_max = None
        for c in cols:
            if c is None or c.count == 0:
                continue
            b = np.floor((c.first_value - offset)
                         / interval).astype(np.int64)
            bv = b[c.exists]
            if bv.size:
                lo, hi = int(bv.min()), int(bv.max())
                b_min = lo if b_min is None else min(b_min, lo)
                b_max = hi if b_max is None else max(b_max, hi)
        if b_min is None:
            cached = ("empty",)
        else:
            nb = b_max - b_min + 1
            if nb <= 0 or nb > MAX_HIST_BUCKETS:
                # <= 0 only under int64-overflowed bucket indices from
                # extreme values — same fallback as an oversized range
                cached = ("reason", "bucket_range")
            else:
                cached = ("ok", int(b_min), int(nb))
        cache[("hist", name)] = cached
    if cached[0] == "empty":
        ops.append(("empty",))
        metas.append({"kind": "hist", "is_date": is_date})
        return None
    if cached[0] == "reason":
        return cached[1]
    _tag, b_min, nb = cached
    if name not in executor._seg_staged and name not in builds:
        # exact HOST-side bucketing inside the build (the oracle's own
        # f64 formula) — runs once per staged generation, the device
        # only counts the precomputed int32 codes
        def build(cols=list(cols), b_min=b_min, name=name):
            per_seg = []
            for c in cols:
                if c is None or c.count == 0:
                    per_seg.append(None)
                    continue
                b = np.floor((c.first_value - offset)
                             / interval).astype(np.int64)
                codes = np.where(c.exists, b - b_min,
                                 np.int64(-1)).astype(np.int32)
                per_seg.append(codes)
            return {name: _build_bucket_codes(executor, per_seg)}

        builds[name] = build
    ops.append(("bucket", name, int(nb)))
    metas.append({"kind": "hist", "is_date": is_date, "interval": interval,
                  "offset": offset, "min_b": int(b_min)})
    return None


def _resolve_metric(spec, executor, ops, metas, builds) -> Optional[str]:
    field = spec.body.get("field")
    segs = executor.segments
    cols = [s.numeric_columns.get(field) for s in segs]
    if all(c is None or c.count == 0 for c in cols):
        if any(s.ordinal_columns.get(field) is not None
               or s.ordinal_columns.get(f"{field}.keyword") is not None
               or s.terms_for_field(field) for s in segs):
            # the host oracle computes metrics over the ORDINAL values
            # of a keyword/text field (search/aggregations.py
            # _metric_values) — keep that surface on the host reduce
            return "field_ineligible"
        ops.append(("empty",))
        metas.append({"kind": "metric"})
        return None
    want_mm = spec.type in ("min", "max", "stats")
    want_dig = spec.type in ("sum", "avg", "stats")
    facts = _metric_field_checks(executor, field)
    if not facts["single"]:
        return "multi_valued"
    if not facts["finite"]:
        return "values_not_fusable"
    if (want_mm or want_dig) and not facts["int48"]:
        return "values_not_fusable"
    if want_dig and not facts["abs_sum_ok"]:
        return "values_not_fusable"
    if executor.nd1 > MAX_SLOT_DOCS:
        return "values_not_fusable"  # per-slot digit sums exceed int32
    base = f"maggs.num.{field}"
    staged = executor._seg_staged
    needed = [base + ".ex"]
    if want_mm:
        needed.append(base + ".mm")
    if want_dig:
        needed.append(base + ".dig")
    missing = [n for n in needed if n not in staged]
    if missing:
        # ONE build closure per field, keyed by `base`: a second spec on
        # the same field with different component needs extends the
        # shared closure's name set instead of enqueueing a duplicate
        # build (the digit decomposition is the expensive part)
        entry = builds.get(base)
        if entry is not None:
            entry.names.update(missing)
        else:
            def build_all(cols=list(cols)):
                n_slots, nd1 = executor.n_slots, executor.nd1
                names = build_all.names
                out = {}
                if base + ".ex" in names:
                    out[base + ".ex"] = np.zeros((n_slots, nd1), bool)
                if base + ".mm" in names:
                    out[base + ".mm"] = np.zeros((n_slots, nd1, 2),
                                                 np.float32)
                if base + ".dig" in names:
                    out[base + ".dig"] = np.zeros(
                        (n_slots, nd1, N_DIGITS), np.int16)
                for i, c in zip(executor.slots, cols):
                    if c is None:
                        continue
                    n = c.exists.shape[0]
                    if base + ".ex" in out:
                        out[base + ".ex"][i, :n] = c.exists
                    v = c.first_value
                    if base + ".mm" in out:
                        hi = np.floor(v / MM_SPLIT)
                        out[base + ".mm"][i, :n, 0] = hi
                        out[base + ".mm"][i, :n, 1] = v - hi * MM_SPLIT
                    if base + ".dig" in out:
                        u = np.where(c.exists, v, 0.0).astype(np.int64) \
                            + np.int64(VALUE_OFFSET)
                        for k in range(N_DIGITS):
                            out[base + ".dig"][i, :n, k] = (
                                (u >> (DIGIT_BITS * k))
                                & (DIGIT_BASE - 1)).astype(np.int16)
                return out

            build_all.names = set(missing)
            builds[base] = build_all
    ops.append(("metric", base, want_mm, want_dig))
    metas.append({"kind": "metric"})
    return None


def resolve_fused_aggs(specs: List[AggSpec], executor, tracer=NULL_TRACER
                       ) -> Tuple[Optional[FusedAggPlan], Optional[str]]:
    """Resolve a query's agg set against the staged segment set.

    Returns ``(plan, None)`` when EVERY spec is fused-eligible (staging
    any missing doc-value columns as a side effect), else
    ``(None, reason)`` — all-or-nothing, so a response never mixes
    fused and host-reduced frames. Reasons are the documented fallback
    vocabulary (docs/OBSERVABILITY.md). Budget denials return
    ``hbm_budget``; a terminal staging fault propagates to the caller
    (which reports ``staging_fault``). ``tracer``: the request's, for the
    ``staging.doc_values`` span of a request that stages."""
    ops: List[tuple] = []
    metas: List[dict] = []
    builds: Dict[str, object] = {}
    for spec in specs:
        if spec.type in FUSED_BUCKET_TYPES:
            pass
        elif spec.type in FUSED_METRIC_TYPES:
            pass
        else:
            return None, "unsupported_agg"
        if spec.subs:
            return None, "sub_aggs"
        allowed = _ALLOWED_BODY[spec.type]
        if not isinstance(spec.body, dict) or set(spec.body) - allowed:
            return None, "unsupported_params"
        if not isinstance(spec.body.get("field"), str):
            return None, "field_ineligible"
        if spec.type == "terms":
            reason = _resolve_terms(spec, executor, ops, metas, builds)
        elif spec.type in ("histogram", "date_histogram"):
            reason = _resolve_histogram(spec, executor, ops, metas, builds)
        else:
            reason = _resolve_metric(spec, executor, ops, metas, builds)
        if reason is not None:
            return None, reason
    if builds:
        try:
            staged = executor.stage_doc_value_columns(builds, tracer)
        except Exception:  # noqa: BLE001 — classified terminal staging
            # fault (run_staged already retried/recorded): ONLY the
            # device staging step may report staging_fault — a
            # resolution bug must never masquerade as a device fault
            import logging

            logging.getLogger("elasticsearch_tpu.search.fused_aggs"
                              ).warning(
                "fused-agg doc-value staging failed; aggregations serve "
                "from the host reduce", exc_info=True)
            return None, "staging_fault"
        if not staged:
            return None, "hbm_budget"
    return FusedAggPlan(list(specs), ops, metas), None


# ---------------------------------------------------------------------------
# Host-side finalize (exact reconstruction + shared bucket assembly)
# ---------------------------------------------------------------------------


def finalize_fused(plan: FusedAggPlan, outs: List[np.ndarray],
                   real: List[int]) -> dict:
    """Reduce the program's per-slot partials (``outs``: one
    [n_slots, ...] array per ``n_agg_outputs`` entry, of whose rows those
    at the slots ``real`` are staged segments) into the response dict —
    byte-identical to the host oracle by the module's exactness
    contract (integer counts, bignum sum reconstruction, lexicographic
    min/max merge, shared bucket assembly)."""
    result: dict = {}
    pos = 0
    for spec, op, meta in zip(plan.specs, plan.ops, plan.metas):
        kind = meta["kind"]
        if op[0] == "empty":
            if kind == "terms":
                result[spec.name] = finalize_terms(spec, {})
            elif kind == "hist":
                result[spec.name] = finalize_histogram(
                    spec, {}, meta["is_date"])
            else:
                result[spec.name] = _finalize_metric(spec, [])
            continue
        if op[0] == "bucket":
            counts = np.asarray(outs[pos],
                                np.int64)[real].sum(axis=0)
            pos += 1
            if kind == "terms":
                vocab = meta["vocab"]
                merged = {vocab[i]: int(c)
                          for i, c in enumerate(counts.tolist()) if c > 0}
                result[spec.name] = finalize_terms(spec, merged)
            else:
                interval, offset = meta["interval"], meta["offset"]
                merged = {}
                for i, c in enumerate(counts.tolist()):
                    if c <= 0:
                        continue
                    b = np.float64(meta["min_b"] + i)
                    if meta["is_date"]:
                        # the oracle's per-value expression with the
                        # bucket index substituted — identical f64 ops
                        key = int(np.int64(b * interval + offset))
                    else:
                        key = float(b * interval + offset)
                    merged[key] = int(c)
                result[spec.name] = finalize_histogram(
                    spec, merged, meta["is_date"])
            continue
        # metric
        _, _base, want_mm, want_dig = op
        count = int(np.asarray(outs[pos], np.int64)[real].sum())
        pos += 1
        vmin, vmax, total = math.inf, -math.inf, 0.0
        if want_mm:
            mm = np.asarray(outs[pos], np.float64)[real]
            pos += 1
            # lexicographic (hi, lo) merge across slots; empty slots
            # carry inf/-inf sentinels and drop here
            mins = [(r[0], r[1]) for r in mm if np.isfinite(r[0])]
            maxs = [(r[2], r[3]) for r in mm if np.isfinite(r[2])]
            if mins:
                h, l = min(mins)
                vmin = float(h) * MM_SPLIT + float(l)
            if maxs:
                h, l = max(maxs)
                vmax = float(h) * MM_SPLIT + float(l)
        if want_dig:
            digs = np.asarray(outs[pos], np.int64)[real]
            pos += 1
            tot_u = 0
            for k in range(N_DIGITS):
                tot_u += int(digs[:, k].sum()) << (DIGIT_BITS * k)
            # exact integer sum via Python bignums; < 2^53 by the
            # eligibility bound, so the float conversion is exact
            total = float(tot_u - count * VALUE_OFFSET)
        result[spec.name] = _finalize_metric(spec, [{
            "count": count, "sum": total, "min": vmin, "max": vmax,
            "sq": 0.0}])
    return result
