"""Immutable block-packed segments — the TPU-native "Lucene segment".

Role model: a Lucene segment (postings + norms + doc values + stored
fields) as used through ``index/engine/InternalEngine.java`` and
``index/store/Store.java`` in the reference. The design is inverted for
TPU execution (SURVEY.md §7.1):

- Postings are **block-packed dense arrays**: every term's postings are
  padded to multiples of BLOCK=128 docs and laid out in one big
  ``[n_blocks, 128]`` int32 matrix (lane dimension = 128, matching the VPU
  lane width). A query gathers its terms' block rows and scores them in one
  fused program — no skip lists, no branchy iteration.
- Norms are exact float32 per-field doc-length columns (Lucene's lossy
  1-byte SmallFloat encoding is unnecessary in HBM).
- Doc values are columnar: numerics/dates as float64 CSR (value, doc)
  pairs plus a dense first-value column for sorting; keywords as ordinal
  CSR against a sorted per-field term dictionary (the reference's
  per-segment ordinals, index/fielddata/).
- Stored fields (_source) stay host-side; only ids/doc-values/postings are
  staged to device.

All shapes are padded to power-of-two buckets so XLA programs cache across
segments of similar size.
"""

from __future__ import annotations

import bisect
import itertools
import json
import logging
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from elasticsearch_tpu.common.xcontent import json_text
from elasticsearch_tpu.common.memory import (
    KIND_BOUND_TABLES,
    KIND_DOC_VALUES,
    KIND_EMBEDDINGS,
    KIND_LIVE_MASK,
    KIND_POSTINGS_PACKED,
    KIND_POSTINGS_RAW,
    KIND_SCALE_NORM,
)

BLOCK = 128  # posting block width == TPU lane count

# ledger-scope uniquifier (itertools.count.__next__ is atomic under the
# GIL): see Segment.ledger_scope
_LEDGER_SEQ = itertools.count(1)

# Field-name separator in composite term keys ("field\x1ftoken").
FIELD_SEP = "\x1f"


def next_pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


@dataclass
class NumericColumn:
    """CSR numeric doc values + dense sort columns (host numpy)."""

    flat_values: np.ndarray  # [n_vals] float64, padded with 0
    flat_docs: np.ndarray  # [n_vals] int32, padded with sentinel doc
    first_value: np.ndarray  # [nd_pad] float64 (first value per doc, 0 if missing)
    min_value: np.ndarray  # [nd_pad] float64 (for asc sort)
    max_value: np.ndarray  # [nd_pad] float64 (for desc sort)
    exists: np.ndarray  # [nd_pad] bool
    count: int  # real number of values


@dataclass
class OrdinalColumn:
    """String doc values as ordinals against a sorted term list."""

    terms: List[str]  # sorted unique values; ordinal = index
    flat_ords: np.ndarray  # [n_vals] int32
    flat_docs: np.ndarray  # [n_vals] int32
    first_ord: np.ndarray  # [nd_pad] int32, -1 if missing (sorts last)
    exists: np.ndarray  # [nd_pad] bool
    count: int

    def ord_of(self, term: str) -> int:
        i = bisect.bisect_left(self.terms, term)
        if i < len(self.terms) and self.terms[i] == term:
            return i
        return -1

    def ord_range(self, lo: Optional[str], hi: Optional[str],
                  include_lo: bool, include_hi: bool) -> Tuple[int, int]:
        """[lo_ord, hi_ord) half-open ordinal range for a term range query."""
        lo_ord = 0
        if lo is not None:
            lo_ord = (bisect.bisect_left(self.terms, lo) if include_lo
                      else bisect.bisect_right(self.terms, lo))
        hi_ord = len(self.terms)
        if hi is not None:
            hi_ord = (bisect.bisect_right(self.terms, hi) if include_hi
                      else bisect.bisect_left(self.terms, hi))
        return lo_ord, hi_ord


class StoredSources:
    """``_source`` of a run of documents, by local doc id: each kept as
    the JSON text it was sent or stored as until someone reads it, then
    as the parsed object (a bulk-loaded segment is flushed, reopened and
    searched without its sources ever being parsed or serialised again;
    a hit's source is parsed on its first fetch and found parsed after).
    Reads give the parsed object, as the list of dicts this replaces
    did."""

    __slots__ = ("_items",)

    def __init__(self, items=()):
        self._items = []
        for item in items:
            self.append(item)

    def append(self, source) -> None:
        """A source as text (a ``str``, or a ``JsonTextDict``'s) or as
        the parsed object."""
        self._items.append(getattr(source, "text", None) or source)

    def __len__(self) -> int:
        return len(self._items)

    def __getitem__(self, doc: int):
        item = self._items[doc]
        if isinstance(item, str):
            item = self._items[doc] = json.loads(item)
        return item

    def __iter__(self):
        return (self[doc] for doc in range(len(self._items)))

    def texts(self):
        """Each document's source as one line of JSON."""
        return (item if isinstance(item, str) else json_text(item)
                for item in self._items)

    def reordered(self, perm) -> "StoredSources":
        out = StoredSources()
        out._items = [self._items[p] for p in perm]
        return out


@dataclass
class VectorColumn:
    """Dense-vector doc values: one fixed-dimension embedding per doc.

    ``vectors`` is the bf16-rounded HOST mirror kept as f32 (every value
    sits exactly on the bf16 grid — what the device staging stores as
    real bf16 and the MXU kNN kernel decodes), so numpy oracles and the
    kernel score identical bits. See ops/pallas_knn.py / docs/VECTOR.md."""

    vectors: np.ndarray  # [nd_pad, dims] f32, bf16-grid values, 0 = missing
    exists: np.ndarray  # [nd_pad] bool
    dims: int
    count: int  # docs carrying a vector


@dataclass
class NestedContext:
    """A nested path's sub-segment + the join to parent docs.

    The reference interleaves nested child docs into the parent's Lucene
    block and joins with ToParentBlockJoinQuery (modules/parent-join uses
    the same machinery). The TPU-native inversion: nested objects form a
    separate dense table with an explicit ``parent_of`` pointer column;
    the child→parent join is a scatter (segment-sum) by parent id — a
    single vectorized pass instead of per-doc block walking.
    """

    segment: "Segment"  # rows = nested objects; columns keyed by full path
    parent_of: np.ndarray  # [n_objs] int32 local doc in the enclosing segment
    offset_of: np.ndarray  # [n_objs] int32 index within the parent's array


@dataclass
class GeoColumn:
    lat: np.ndarray  # [n_vals] float32
    lon: np.ndarray  # [n_vals] float32
    flat_docs: np.ndarray  # [n_vals] int32
    first_lat: np.ndarray  # [nd_pad] float32
    first_lon: np.ndarray  # [nd_pad] float32
    exists: np.ndarray  # [nd_pad] bool
    count: int


class Segment:
    """An immutable sealed segment.

    Host numpy arrays; ``device_arrays()`` stages the query-relevant subset
    to the default JAX device once and caches it (HBM staging ≙ the
    reference's filesystem page cache warming at shard open).
    """

    def __init__(
        self,
        name: str,
        num_docs: int,
        doc_ids: List[str],
        sources: List[dict],
        routings: List[Optional[str]],
        seqnos: np.ndarray,
        versions: np.ndarray,
        term_keys: List[str],
        term_block_start: np.ndarray,
        term_block_count: np.ndarray,
        term_doc_freq: np.ndarray,
        block_docs: np.ndarray,
        block_tfs: np.ndarray,
        field_stats: Dict[str, dict],
        field_norm_idx: Dict[str, int],
        norms: np.ndarray,
        numeric_columns: Dict[str, NumericColumn],
        ordinal_columns: Dict[str, OrdinalColumn],
        geo_columns: Dict[str, GeoColumn],
        exists_masks: Dict[str, np.ndarray],
        positions: Optional[Dict[int, dict]] = None,
        nested: Optional[Dict[str, NestedContext]] = None,
        shapes: Optional[Dict[str, Dict[int, list]]] = None,
        parents: Optional[List[Optional[str]]] = None,
        vector_columns: Optional[Dict[str, "VectorColumn"]] = None,
    ):
        self.name = name
        self.num_docs = num_docs
        self.nd_pad = next_pow2(max(num_docs, 1))
        self.doc_ids = doc_ids
        self.sources = (sources if isinstance(sources, StoredSources)
                        else StoredSources(sources))
        self.routings = routings
        # legacy _parent metadata value per doc (None = no parent) —
        # persisted with the segment like routings (ParentFieldMapper)
        self.parents = parents if parents is not None else [None] * num_docs
        self.seqnos = seqnos
        self.versions = versions
        # sorted composite term keys; term_id = position
        self.term_keys = term_keys
        self.term_block_start = term_block_start
        self.term_block_count = term_block_count
        self.term_doc_freq = term_doc_freq
        self.block_docs = block_docs  # [n_blocks, BLOCK] int32, pad = nd_pad
        self.block_tfs = block_tfs  # [n_blocks, BLOCK] float32
        # field -> {"doc_count": int, "sum_ttf": int} for BM25 stats
        self.field_stats = field_stats
        # text field -> row in the stacked norms matrix
        self.field_norm_idx = field_norm_idx
        self.norms = norms  # [n_norm_fields, nd_pad + 1] float32, last col = 1
        self.numeric_columns = numeric_columns
        self.ordinal_columns = ordinal_columns
        self.geo_columns = geo_columns
        # dense_vector embeddings (field -> VectorColumn); staged to the
        # device lazily by ensure_vector_staged (bf16 matrix + metric
        # scale columns for the kNN planes)
        self.vector_columns = vector_columns or {}
        self.exists_masks = exists_masks  # field -> [nd_pad] bool
        # term_id -> {local_doc: np.ndarray positions} for phrase queries
        self.positions = positions or {}
        # nested path -> NestedContext (sub-segment + parent pointers)
        self.nested = nested or {}
        # geo_shape field -> {doc: [raw GeoJSON/WKT]}; geometry objects +
        # bbox tables build lazily (shape_column)
        self.shapes = shapes or {}
        self._shape_cols: Dict[str, dict] = {}
        # tombstones for deleted docs (set by the engine on update/delete)
        self.live = np.ones(self.nd_pad, dtype=bool)
        self.live[num_docs:] = False
        self._id_to_doc: Optional[Dict[str, int]] = None
        # circuit-breaker bytes charged for lazily-built per-segment
        # structures (text fielddata); released when the segment is
        # dropped (merge/close) — see release_breaker_charges()
        self.breaker_charges: Dict[str, int] = {}
        # which index owns this segment (stamped by the engine before
        # staging; the DeviceMemoryAccountant's top hierarchy level)
        self.owner_index: Optional[str] = None
        # per-OBJECT ledger scope: segment names repeat across in-process
        # cluster nodes (primary + replica copies share "idx_0_seg_N"),
        # and the accountant keys scopes by string — a shared name would
        # let one copy's register/release clobber the other's entries
        self.ledger_scope = f"{name}@{next(_LEDGER_SEQ)}"
        # how this segment's FIRST staging classifies in the lifecycle
        # event ring: a merge product carries the same logical corpus as
        # the segments it retired, so its staging is a restage
        # ("refresh" — the engine's merge path overrides this), not new
        # logical bytes; translog-replay/recovery segments stay
        # "initial" (first staging of that data in this process)
        self.stage_reason_initial = "initial"
        self._device: Optional[dict] = None
        # generic device-array cache for doc-value columns (key -> jnp array)
        self.dev_cache: Dict[str, Any] = {}
        # guards lazy per-sub live-mask staging vs delete_docs' restage
        self._live_t_lock = threading.Lock()
        # serializes COLD builds (base/kernel/vector/column stagings):
        # two queries racing a cold segment would both pay the
        # multi-second device transfer AND double-register it (the
        # second "initial" reclassifies as a restage, inflating
        # restage_amplification with zero actual restaging). Cached
        # fast paths stay lock-free; never held while taking
        # _live_t_lock, and the eviction callback
        # (release_device_staging) never takes it — so the accountant
        # lock is only ever acquired UNDER it, never the reverse
        self._device_stage_lock = threading.Lock()

    # ------------------------------------------------------------------

    @property
    def live_doc_count(self) -> int:
        return int(self.live[: self.num_docs].sum())

    def id_to_doc(self) -> Dict[str, int]:
        if self._id_to_doc is None:
            self._id_to_doc = {i: d for d, i in enumerate(self.doc_ids)}
        return self._id_to_doc

    def delete_doc(self, local_doc: int) -> None:
        self.delete_docs(np.asarray([local_doc], dtype=np.int64))

    def delete_docs(self, locals_: np.ndarray) -> None:
        if locals_.size == 0:
            return
        self.live[locals_] = False
        for nctx in self.nested.values():
            # nested objects die with their parent (Lucene deletes the
            # whole block); keeps the sub-segment's live masks consistent
            # recursively, one restage per level
            objs = np.nonzero(np.isin(nctx.parent_of, locals_))[0]
            nctx.segment.delete_docs(objs)
        dev = self._device
        if dev is not None:  # restage only the live masks
            import time as _time

            import jax.numpy as jnp

            t0 = _time.monotonic()
            dev["live"] = jnp.asarray(self.live)
            dev["live1"] = jnp.asarray(
                np.concatenate([self.live, np.zeros(1, dtype=bool)])
            )
            with self._live_t_lock:
                if "k_live_t" in dev:
                    dev["k_live_t"] = self._build_live_t_device(
                        self.kernel_geom.tile_sub)
                # per-sub variants staged by kernel_live_t_for (dense-term
                # queries that shrank the tile) restage the same way
                for key in [k for k in dev
                            if k.startswith("k_live_t_")]:
                    sub = int(key.rsplit("_", 1)[1])
                    dev[key] = self._build_live_t_device(sub)
            # the live-mask restage is the canonical delete-invalidation
            # event: the logical change is one tombstone bit per doc, the
            # restaged bytes are every dependent mask layout
            from elasticsearch_tpu.common.memory import memory_accountant

            memory_accountant().note_logical_change(
                self.owner_index or "_unassigned", int(locals_.size))
            self._account_live_masks(
                "delete_invalidation",
                duration_ms=(_time.monotonic() - t0) * 1000.0)

    def term_id(self, field_name: str, token: str) -> int:
        key = f"{field_name}{FIELD_SEP}{token}"
        i = bisect.bisect_left(self.term_keys, key)
        if i < len(self.term_keys) and self.term_keys[i] == key:
            return i
        return -1

    def terms_for_field(self, field_name: str) -> List[Tuple[str, int]]:
        """All (token, term_id) of a field, in sorted token order."""
        prefix = f"{field_name}{FIELD_SEP}"
        lo = bisect.bisect_left(self.term_keys, prefix)
        hi = bisect.bisect_left(self.term_keys, prefix + "￿")
        return [(self.term_keys[i][len(prefix):], i) for i in range(lo, hi)]

    def term_ttf(self, tid: int) -> int:
        """Total term frequency (sum of tfs over the term's postings) —
        collection stat for DFR/IB/LM similarities and DFS. Computed lazily
        from the packed tf blocks and cached."""
        cache = getattr(self, "_ttf_cache", None)
        if cache is None:
            cache = self._ttf_cache = {}
        hit = cache.get(tid)
        if hit is None:
            start = int(self.term_block_start[tid])
            cnt = int(self.term_block_count[tid])
            hit = cache[tid] = int(self.block_tfs[start:start + cnt].sum())
        return hit

    def shape_column(self, field_name: str) -> Optional[dict]:
        """Lazy geo_shape column: parsed geometry per doc + dense bbox
        table [nd_pad, 4] (min_lon, min_lat, max_lon, max_lat) for the
        vectorized prefilter. None if the field has no shapes here."""
        per_doc = self.shapes.get(field_name)
        if not per_doc:
            return None
        col = self._shape_cols.get(field_name)
        if col is None:
            from elasticsearch_tpu.utils.geometry import parse_shape

            geoms = {doc: [parse_shape(v) for v in vals]
                     for doc, vals in per_doc.items()}
            bbox = np.full((self.nd_pad, 4), np.nan, np.float64)
            exists = np.zeros(self.nd_pad, bool)
            for doc, gs in geoms.items():
                bs = [g.bbox() for g in gs]
                bbox[doc] = (min(b[0] for b in bs), min(b[1] for b in bs),
                             max(b[2] for b in bs), max(b[3] for b in bs))
                exists[doc] = True
            col = self._shape_cols[field_name] = {
                "geoms": geoms, "bbox": bbox, "exists": exists}
        return col

    def field_avgdl(self, field_name: str) -> float:
        st = self.field_stats.get(field_name)
        if not st or st["doc_count"] == 0:
            return 1.0
        return max(st["sum_ttf"] / st["doc_count"], 1.0)

    # ------------------------------------------------------------------
    # Device staging
    # ------------------------------------------------------------------

    def _account(self, kind: str, table: str, nbytes: int,
                 reason: str = "initial", duration_ms: float = 0.0) -> None:
        """Register one staged table group with the device-memory
        accountant (ISSUE 9, docs/OBSERVABILITY.md). The whole segment
        staging is one LRU-evictable scope: over HBM budget, the
        accountant drops the coldest segment's arrays (they restage
        lazily on next use)."""
        from elasticsearch_tpu.common.memory import memory_accountant

        if reason == "initial":
            # a merge product's first staging is a restage of retired
            # segments' corpus, not new logical bytes (see
            # stage_reason_initial) — without this the exact full-corpus
            # restage ROADMAP item 3 targets would land in the
            # amplification DENOMINATOR and read as ~0 amplification
            reason = self.stage_reason_initial
        memory_accountant().register(
            self.owner_index or "_unassigned", self.ledger_scope, kind,
            table,
            int(nbytes), reason=reason, duration_ms=duration_ms,
            plane="host", evict=self.release_device_staging)

    def _account_live_masks(self, reason: str,
                            duration_ms: float = 0.0) -> None:
        """(Re-)register every staged live-mask layout (live, live1,
        k_live_t, per-sub variants) — mask mutations restage all
        dependent layouts at once, one ledger entry per layout so the
        restaged-bytes accounting is exact."""
        dev = self._device
        if dev is None:
            return
        # snapshot: concurrent stagers (kernel_live_t_for, vector
        # staging) add keys to the live dict while we iterate
        for key, v in list(dev.items()):
            if key in ("live", "live1") or key.startswith("k_live_t"):
                self._account(KIND_LIVE_MASK, key, int(v.nbytes),
                              reason=reason, duration_ms=duration_ms)

    def device_arrays(self) -> dict:
        """Stage postings/norms/live-mask to the default device (cached).
        When the pallas scoring kernel is active (TPU, or interpret mode
        in tests) the kernel's tile-layout arrays ride along."""
        from elasticsearch_tpu.common.memory import memory_accountant

        # capture a LOCAL reference: a concurrent HBM-budget eviction may
        # null self._device at any point (another thread's try_reserve),
        # and an in-flight query must keep serving from the dict it
        # staged — the arrays stay alive through normal refcounting
        dev = self._device
        if dev is None:
            with self._device_stage_lock:
                dev = self._device  # a racing cold query built it
                if dev is None:
                    from elasticsearch_tpu.common.staging import run_staged

                    # transient device faults retry with bounded backoff
                    # (search.staging.retry.*); a terminal fault
                    # propagates — the base staging is MANDATORY for
                    # this shard's query phase, so the shard-failure
                    # isolation path (PR 4) owns it: partial results,
                    # never a 5xx. Nothing publishes or registers until
                    # the whole group staged (register-then-commit).
                    dev = run_staged(
                        self._stage_base_arrays,
                        index=self.owner_index or "_unassigned",
                        kind=KIND_POSTINGS_RAW, plane="host")
        else:
            memory_accountant().touch(self.owner_index or "_unassigned",
                                      self.ledger_scope)
        if "k_docs" not in dev and "k_packed" not in dev:
            # lazy: the pallas mode may turn on after the first staging
            # (ES_TPU_PALLAS flips in tests; backend selection at runtime)
            with self._device_stage_lock:
                if "k_docs" not in dev and "k_packed" not in dev:
                    from elasticsearch_tpu.common.staging import run_staged

                    try:
                        run_staged(
                            lambda: self._stage_kernel_arrays(dev),
                            index=self.owner_index or "_unassigned",
                            kind="postings", plane="host")
                    except Exception:  # noqa: BLE001 — terminal
                        # classified staging fault: the kernel tables
                        # are an OPTIONAL fast plane for the host rung —
                        # this query's segments score on the scatter
                        # engine (byte-level parity contract) and the
                        # next query retries the staging (self-heal once
                        # the fault clears; docs/RESILIENCE.md)
                        logging.getLogger(
                            "elasticsearch_tpu.index.segment").warning(
                            "[%s] kernel staging failed; segment [%s] "
                            "scores on the scatter engine this query",
                            self.owner_index or "_unassigned", self.name,
                            exc_info=True)
        return dev

    def _stage_base_arrays(self) -> dict:
        """One cold-build ATTEMPT of the base staging (under
        _device_stage_lock, inside run_staged's retry loop)."""
        import time as _time

        import jax.numpy as jnp

        from elasticsearch_tpu.testing.disruption import on_device_staging

        t0 = _time.monotonic()
        live1 = np.concatenate([self.live, np.zeros(1, dtype=bool)])
        on_device_staging(self.owner_index or "_unassigned",
                          KIND_POSTINGS_RAW, "base_postings")
        dev = {
            "block_docs": jnp.asarray(self.block_docs),
            "block_tfs": jnp.asarray(self.block_tfs),
            "norms": jnp.asarray(self.norms),
            "live": jnp.asarray(self.live),
            "live1": jnp.asarray(live1),
        }
        self._device = dev
        dur = (_time.monotonic() - t0) * 1000.0
        self._account(
            KIND_POSTINGS_RAW, "base_postings",
            self.block_docs.nbytes + self.block_tfs.nbytes,
            duration_ms=dur)
        self._account(KIND_SCALE_NORM, "norms", self.norms.nbytes)
        self._account_live_masks("initial")
        return dev

    def _stage_kernel_arrays(self, dev: dict) -> None:
        from elasticsearch_tpu.ops.aggs import _pallas_mode

        if not _pallas_mode():
            return
        import time as _time

        import jax.numpy as jnp

        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.ops import pallas_scoring as psc

        # HBM budget pressure valve: a MANDATORY staging (the host rung
        # scores byte-identically to the mesh kernel only through these
        # tables) — the reservation LRU-evicts colder scopes to make
        # room but a denial never blocks it; budget DENIAL lives at the
        # optional mesh-plane staging (ladder reason hbm_budget)
        memory_accountant().try_reserve(
            self.owner_index or "_unassigned",
            self.block_docs.nbytes + self.block_tfs.nbytes,
            exclude_scope=self.ledger_scope, mandatory=True)
        t0 = _time.monotonic()
        geom = psc.tile_geometry(self.nd_pad)
        frac = self._block_frac()
        bmin, bmax = psc.block_min_max(self.block_docs, self.block_tfs,
                                       self.nd_pad)
        # postings codec (ISSUE 6, docs/PRUNING.md): "packed" stages ONE
        # bit-packed i32 word per posting instead of the (docs i32,
        # frac f32) pair — half the staged postings bytes AND half the
        # per-query posting-window DMA traffic. The preference is the
        # per-segment stamp (the engine inherits the index's resolved
        # setting; no stamp is raw), demoted to raw when the doc space
        # exceeds the packed word's doc capacity.
        codec = psc.resolve_postings_codec(
            getattr(self, "postings_codec", None), self.nd_pad)
        # stage fully, then publish atomically: a concurrent search thread
        # must never observe k_docs without k_frac/k_live_t (dict.update
        # of a prebuilt dict is atomic under the GIL), and kernel_geom is
        # the eligibility signal so it is set LAST. Register-then-commit
        # (ISSUE 10): a fault anywhere before dev.update publishes
        # nothing and registers nothing — the attempt leaves no trace
        # and run_staged's retry loop re-runs it (hooks re-consulted)
        from elasticsearch_tpu.testing.disruption import on_device_staging

        kind_postings = (KIND_POSTINGS_PACKED if codec == "packed"
                         else KIND_POSTINGS_RAW)
        owner = self.owner_index or "_unassigned"
        on_device_staging(owner, KIND_LIVE_MASK, "k_live_t")
        staged = {
            "k_live_t": jnp.asarray(
                psc.build_live_t(self.live.astype(np.float32), geom)),
        }
        on_device_staging(owner, kind_postings, "k_postings")
        if codec == "packed":
            pk = psc.pack_segment_blocks(self.block_docs, frac,
                                         self.nd_pad)
            staged["k_packed"] = jnp.asarray(pk)
            postings_bytes = int(pk.nbytes)
        else:
            dp, fp = psc.pad_segment_blocks(self.block_docs, frac,
                                            self.nd_pad)
            staged["k_docs"] = jnp.asarray(dp)
            staged["k_frac"] = jnp.asarray(fp)
            postings_bytes = int(dp.nbytes + fp.nbytes)
        self.kernel_postings_bytes = postings_bytes
        self.kernel_bmin = bmin
        self.kernel_bmax = bmax
        self.kernel_codec = codec
        dev.update(staged)
        self.kernel_geom = geom
        dur = (_time.monotonic() - t0) * 1000.0
        self._account(kind_postings, "k_postings",
                      self.kernel_postings_bytes, duration_ms=dur)
        # bmin/bmax stay host-resident but scale with the plane: tracked
        # under bound_tables so the per-kind sums explain the footprint
        self._account(KIND_BOUND_TABLES, "k_bounds",
                      int(bmin.nbytes + bmax.nbytes))
        self._account(KIND_LIVE_MASK, "k_live_t",
                      int(staged["k_live_t"].nbytes), duration_ms=dur)

    def _build_live_t_device(self, sub: int):
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import pallas_scoring as psc

        return jnp.asarray(psc.build_live_t(
            self.live.astype(np.float32),
            psc.tile_geometry(self.nd_pad, sub)))

    def kernel_live_t_for(self, sub: int) -> str:
        """Lazily stage the live-mask tile layout for a non-default
        tile_sub and return its device-dict key. Queries containing a
        dense (high-df) term shrink the tile so the per-tile covering
        window fits the kernel bound (see query_dsl's geometry ladder);
        docs/frac/bmin/bmax are tile-size independent, only this mask
        layout changes. Locked against delete_docs' restage so a stale
        mask can never be published after a concurrent delete."""
        key = f"k_live_t_{sub}"
        dev = self.device_arrays()  # restages if the budget evicted us
        staged_nbytes = dur = 0
        with self._live_t_lock:
            if key not in dev:
                import time as _time

                t0 = _time.monotonic()
                arr = self._build_live_t_device(sub)
                dev[key] = arr
                staged_nbytes = int(arr.nbytes)
                dur = (_time.monotonic() - t0) * 1000.0
        if staged_nbytes:
            # a shrunk tile is a geometry change: the same mask data
            # restages in a new layout (docs/OBSERVABILITY.md). Accounted
            # OUTSIDE _live_t_lock — the budget evictor holds the
            # accountant lock when it drops stagings, so taking the
            # accountant lock under _live_t_lock would invert lock order
            self._account(KIND_LIVE_MASK, key, staged_nbytes,
                          reason="geometry_change", duration_ms=dur)
        return key

    def _block_frac(self) -> np.ndarray:
        """Per-posting BM25 norm factors, computed per FIELD (each field's
        avgdl and doc-length column differ; a block belongs to exactly one
        term and thus one field)."""
        from elasticsearch_tpu.ops import pallas_scoring as psc

        frac = np.zeros_like(self.block_tfs)
        for field, row in self.field_norm_idx.items():
            prefix = f"{field}{FIELD_SEP}"
            lo = bisect.bisect_left(self.term_keys, prefix)
            hi = bisect.bisect_left(self.term_keys, prefix + "￿")
            if lo >= hi:
                continue
            b0 = int(self.term_block_start[lo])
            b1 = int(self.term_block_start[hi - 1]
                     + self.term_block_count[hi - 1])
            frac[b0:b1] = psc.compute_block_frac(
                self.block_docs[b0:b1], self.block_tfs[b0:b1],
                self.norms[row], self.field_avgdl(field))
        return frac

    def ensure_vector_staged(self, field: str, metric: str = "cosine"):
        """Lazily stage a dense_vector field's kNN arrays to the device
        and return their device-dict keys: (emb bf16 [nd_pad, d_pad],
        inverse-norm f32 [nd_pad] — the cosine scale column, staged only
        when the metric needs it, exists1 bool [nd_pad + 1]) plus the
        padded dim count, or None when no doc of this segment carries
        the field. The arrays are immutable (deletes ride the live mask
        applied outside the plan), so no restage hook is needed."""
        col = self.vector_columns.get(field)
        if col is None:
            return None
        emb_key = f"k_vec_{field}"
        norm_key = f"k_vecnorm_{field}"
        exists_key = f"k_vecexists_{field}"
        dev = self.device_arrays()  # ensure the base staging dict exists
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import pallas_knn as pkn

        from elasticsearch_tpu.common.staging import run_staged

        if emb_key not in dev:
            with self._device_stage_lock:
                if emb_key not in dev:  # racing cold stager built it
                    # transient faults retry with backoff; a terminal
                    # fault propagates (the host kNN rung needs these
                    # arrays — shard-failure isolation owns it)
                    run_staged(
                        lambda: self._stage_vector_arrays(
                            dev, col, emb_key, exists_key),
                        index=self.owner_index or "_unassigned",
                        kind=KIND_EMBEDDINGS, plane="host")
        if metric == "cosine" and norm_key not in dev:
            # only cosine reads the inverse-norm column — a dot_product
            # field skips the norm pass and the staged bytes entirely
            with self._device_stage_lock:
                if norm_key not in dev:
                    def _stage_norm():
                        from elasticsearch_tpu.testing.disruption import (
                            on_device_staging,
                        )

                        on_device_staging(
                            self.owner_index or "_unassigned",
                            KIND_SCALE_NORM, norm_key)
                        inv = pkn.vector_scale_column(
                            col.vectors, "cosine")[:, 0]
                        dev[norm_key] = jnp.asarray(inv)
                        self._account(KIND_SCALE_NORM, norm_key,
                                      int(inv.nbytes))

                    run_staged(_stage_norm,
                               index=self.owner_index or "_unassigned",
                               kind=KIND_SCALE_NORM, plane="host")
        d_pad = int(dev[emb_key].shape[1])
        return emb_key, norm_key, exists_key, d_pad

    def _stage_vector_arrays(self, dev: dict, col, emb_key: str,
                             exists_key: str) -> None:
        """Cold-build a dense_vector field's embedding + exists arrays
        (called under _device_stage_lock — see its init comment)."""
        import time as _time

        import jax.numpy as jnp

        from elasticsearch_tpu.common.memory import memory_accountant
        from elasticsearch_tpu.ops import pallas_knn as pkn

        from elasticsearch_tpu.testing.disruption import on_device_staging

        t0 = _time.monotonic()
        d_pad = pkn.pad_dims(col.dims)
        # a MANDATORY staging (the host kNN rung reads it): the
        # reservation may LRU-evict colder scopes but a denial never
        # blocks it — correctness over budget (docs/OBSERVABILITY.md)
        memory_accountant().try_reserve(
            self.owner_index or "_unassigned",
            self.nd_pad * d_pad * 2, exclude_scope=self.ledger_scope,
            mandatory=True)
        on_device_staging(self.owner_index or "_unassigned",
                          KIND_EMBEDDINGS, emb_key)
        emb = np.zeros((self.nd_pad, d_pad), np.float32)
        emb[:, : col.dims] = col.vectors
        exists1 = np.zeros(self.nd_pad + 1, bool)
        exists1[: self.nd_pad] = col.exists
        # publish atomically-enough (dict.update under the GIL): a
        # concurrent reader must never see emb without its mask
        dev.update({
            emb_key: jnp.asarray(emb, jnp.bfloat16),
            exists_key: jnp.asarray(exists1),
        })
        dur = (_time.monotonic() - t0) * 1000.0
        self._account(KIND_EMBEDDINGS, emb_key,
                      int(dev[emb_key].nbytes), duration_ms=dur)
        self._account(KIND_LIVE_MASK, exists_key, exists1.nbytes,
                      duration_ms=dur)

    def device_column(self, key: str, build) -> Any:
        """Cached device staging for a doc-value array (build() -> np array)."""
        cache = self.dev_cache  # eviction rebinds; serve from our capture
        if key not in cache:
            with self._device_stage_lock:
                if key in cache:  # racing cold stager built it
                    return cache[key]

                def _stage_column():
                    import time as _time

                    import jax.numpy as jnp

                    from elasticsearch_tpu.testing.disruption import (
                        on_device_staging,
                    )

                    t0 = _time.monotonic()
                    on_device_staging(self.owner_index or "_unassigned",
                                      KIND_DOC_VALUES, f"col:{key}")
                    cache[key] = jnp.asarray(build())
                    try:
                        nbytes = int(cache[key].nbytes)
                    except (TypeError, AttributeError):
                        nbytes = 0  # non-array values (slice masks etc.)
                    if nbytes:
                        self._account(
                            KIND_DOC_VALUES, f"col:{key}", nbytes,
                            duration_ms=(_time.monotonic() - t0) * 1000.0)

                from elasticsearch_tpu.common.staging import run_staged

                # transient faults retry with backoff; a terminal fault
                # propagates (the sort/agg consumer needs the column —
                # shard-failure isolation owns it, PR 4)
                run_staged(_stage_column,
                           index=self.owner_index or "_unassigned",
                           kind=KIND_DOC_VALUES, plane="host")
        return cache[key]

    def release_device_staging(self) -> None:
        """Drop every cached device staging (HBM eviction / segment
        retirement): the arrays lazily restage on next use, so this is
        always safe — in-flight queries keep their captured references
        alive through normal refcounting. Returns the ledger for this
        segment to zero.

        Runs as the accountant's eviction callback WITH the accountant
        lock held, so it must not take _live_t_lock (kernel_live_t_for
        takes the locks in the opposite order); plain rebinds are atomic
        under the GIL and concurrent stagers hold their own reference."""
        self._device = None
        self.dev_cache = {}
        # search_stats sums this attribute for postings_bytes_staged
        self.kernel_postings_bytes = 0
        from elasticsearch_tpu.common.memory import memory_accountant

        memory_accountant().release_scope(
            self.owner_index or "_unassigned", self.ledger_scope)
        for nctx in self.nested.values():
            nctx.segment.release_device_staging()

    def release_breaker_charges(self) -> None:
        """The segment is being dropped (merge replaced it / shard close):
        give its accounted fielddata bytes back to the breaker."""
        if not self.breaker_charges:
            return
        from elasticsearch_tpu.common.breaker import (
            CircuitBreaker,
            breaker_service,
        )

        total = sum(self.breaker_charges.values())
        self.breaker_charges.clear()
        breaker_service().get_breaker(
            CircuitBreaker.FIELDDATA).add_without_breaking(-total)

    def memory_bytes(self) -> int:
        total = self.block_docs.nbytes + self.block_tfs.nbytes + self.norms.nbytes
        for c in self.numeric_columns.values():
            total += c.flat_values.nbytes + c.flat_docs.nbytes + c.first_value.nbytes
        for c in self.ordinal_columns.values():
            total += c.flat_ords.nbytes + c.flat_docs.nbytes + c.first_ord.nbytes
        for c in self.vector_columns.values():
            # device staging is bf16: half the host mirror's f32 bytes
            total += c.vectors.nbytes // 2 + c.exists.nbytes
        return total

    def stats(self) -> dict:
        return {
            "name": self.name,
            "num_docs": self.num_docs,
            "deleted_docs": self.num_docs - self.live_doc_count,
            "num_terms": len(self.term_keys),
            "num_posting_blocks": int(self.block_docs.shape[0]),
            "memory_in_bytes": self.memory_bytes(),
        }


class SegmentBuilder:
    """Accumulates parsed documents, seals into a Segment.

    Role model: Lucene's in-memory indexing buffer inside ``IndexWriter``
    as driven by ``InternalEngine.indexIntoLucene``
    (index/engine/InternalEngine.java:763). Documents are buffered as
    Python/numpy structures; ``seal()`` performs the "flush to segment":
    sort terms, block-pack postings, build columns.
    """

    def __init__(self, name: str, index_sort=None):
        self.name = name
        # index.sort.* spec [(field, order, missing, mode)] — applied as a
        # doc permutation at seal() (IndexSortConfig.java semantics)
        self.index_sort = index_sort
        self.doc_ids: List[str] = []
        self.sources = StoredSources()
        self.routings: List[Optional[str]] = []
        self.parents: List[Optional[str]] = []
        self.seqnos: List[int] = []
        self.versions: List[int] = []
        # term_key -> list[(doc, tf)] — appended in doc order, so sorted by doc
        self.postings: Dict[str, List[Tuple[int, int]]] = {}
        # term_key -> {doc: [positions]}
        self.positions: Dict[str, Dict[int, List[int]]] = {}
        # field -> {doc: token_count}
        self.field_lengths: Dict[str, Dict[int, int]] = {}
        self.numeric_values: Dict[str, List[Tuple[int, float]]] = {}
        self.string_values: Dict[str, List[Tuple[int, str]]] = {}
        self.geo_values: Dict[str, List[Tuple[int, float, float]]] = {}
        # dense_vector field -> {doc: float32 [dims] row} (+ dims per
        # field): rows as the mapper parsed them, stacked once at seal
        self.vector_values: Dict[str, Dict[int, np.ndarray]] = {}
        self.vector_dims: Dict[str, int] = {}
        # geo_shape field -> {doc: [raw GeoJSON/WKT values]}
        self.shape_values: Dict[str, Dict[int, list]] = {}
        self.field_docs: Dict[str, set] = {}
        # nested path -> {"builder": SegmentBuilder, "parent_of": [...],
        #                 "offset_of": [...]}
        self.nested_builders: Dict[str, dict] = {}

    @property
    def num_docs(self) -> int:
        return len(self.doc_ids)

    def add_document(self, parsed, seqno: int, version: int = 1,
                     parent: Optional[str] = None) -> int:
        """parsed: mapper.ParsedDocument. Returns the local doc id."""
        doc = len(self.doc_ids)
        self.doc_ids.append(parsed.doc_id)
        self.sources.append(parsed.source)
        self.routings.append(parsed.routing)
        self.parents.append(parent)
        self.seqnos.append(seqno)
        self.versions.append(version)
        for field_name, tokens in parsed.terms.items():
            self.field_lengths.setdefault(field_name, {})[doc] = len(tokens)
            self.field_docs.setdefault(field_name, set()).add(doc)
            counts: Dict[str, int] = {}
            for pos, tok in enumerate(tokens):
                counts[tok] = counts.get(tok, 0) + 1
                key = f"{field_name}{FIELD_SEP}{tok}"
                self.positions.setdefault(key, {}).setdefault(doc, []).append(pos)
            for tok, tf in counts.items():
                key = f"{field_name}{FIELD_SEP}{tok}"
                self.postings.setdefault(key, []).append((doc, tf))
        for field_name, vals in parsed.numeric_values.items():
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.numeric_values.setdefault(field_name, []).extend(
                (doc, v) for v in vals
            )
        for field_name, vals in parsed.string_values.items():
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.string_values.setdefault(field_name, []).extend(
                (doc, v) for v in vals
            )
        for field_name, pts in parsed.geo_values.items():
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.geo_values.setdefault(field_name, []).extend(
                (doc, lat, lon) for lat, lon in pts
            )
        for field_name, vals in getattr(parsed, "shape_values", {}).items():
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.shape_values.setdefault(field_name, {}).setdefault(
                doc, []).extend(vals)
        for field_name, vec in getattr(parsed, "vector_values", {}).items():
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.vector_values.setdefault(field_name, {})[doc] = vec
            self.vector_dims[field_name] = len(vec)
        for field_name, pairs in getattr(parsed, "range_values", {}).items():
            # two parallel numeric columns stay aligned: both appended once
            # per value, in the same order (stable doc sort in seal())
            self.field_docs.setdefault(field_name, set()).add(doc)
            self.numeric_values.setdefault(f"{field_name}#lo", []).extend(
                (doc, lo) for lo, _ in pairs
            )
            self.numeric_values.setdefault(f"{field_name}#hi", []).extend(
                (doc, hi) for _, hi in pairs
            )
        self._add_nested(getattr(parsed, "nested", None) or {}, doc)
        return doc

    def _add_nested(self, nested: dict, root_doc: int) -> None:
        """Flatten nested (and nested-in-nested) sub-documents into
        per-path builders joined to the root doc."""
        for path, subdocs in nested.items():
            entry = self.nested_builders.setdefault(
                path,
                {"builder": SegmentBuilder(f"{self.name}#{path}"),
                 "parent_of": [], "offset_of": [],
                 "_per_parent": {}},
            )
            for sub in subdocs:
                offset = entry["_per_parent"].get(root_doc, 0)
                entry["_per_parent"][root_doc] = offset + 1
                # the sub-builder keeps the inner nested docs too (via its
                # own add_document recursion): relative joins for
                # nested-in-nested queries/aggs...
                inner = getattr(sub, "nested", None)
                entry["builder"].add_document(sub, seqno=-1)
                entry["parent_of"].append(root_doc)
                entry["offset_of"].append(offset)
                # ...while ALSO flattening them to the root doc, so a
                # root-level nested path "a.b" query/agg works directly
                if inner:
                    self._add_nested(inner, root_doc)

    # ------------------------------------------------------------------

    def _remap_docs(self, perm: np.ndarray) -> np.ndarray:
        """Reorder documents by ``perm`` (new position -> old doc),
        rewriting every doc-id reference so doc order becomes sort order.
        Returns the old->new map (callers holding pre-seal local doc ids —
        version map, buffered deletes — must translate through it)."""
        inv = np.empty(len(perm), np.int64)
        inv[perm] = np.arange(len(perm))  # old doc -> new doc

        def reorder(lst):
            return [lst[p] for p in perm]

        self.doc_ids = reorder(self.doc_ids)
        self.sources = self.sources.reordered(perm)
        self.routings = reorder(self.routings)
        self.parents = reorder(self.parents)
        self.seqnos = reorder(self.seqnos)
        self.versions = reorder(self.versions)
        self.postings = {
            k: sorted((int(inv[d]), tf) for d, tf in plist)
            for k, plist in self.postings.items()
        }
        self.positions = {
            k: {int(inv[d]): pos for d, pos in per_doc.items()}
            for k, per_doc in self.positions.items()
        }
        self.field_lengths = {
            f: {int(inv[d]): ln for d, ln in per_doc.items()}
            for f, per_doc in self.field_lengths.items()
        }
        # stable doc sort keeps multi-value order (and #lo/#hi alignment)
        for store in (self.numeric_values, self.string_values):
            for f, vals in store.items():
                store[f] = sorted(
                    ((int(inv[d]),) + tuple(rest) for d, *rest in vals),
                    key=lambda t: t[0],
                )
        for f, vals in self.geo_values.items():
            self.geo_values[f] = sorted(
                ((int(inv[d]), lat, lon) for d, lat, lon in vals),
                key=lambda t: t[0],
            )
        self.field_docs = {
            f: {int(inv[d]) for d in docs} for f, docs in self.field_docs.items()
        }
        self.shape_values = {
            f: {int(inv[d]): vals for d, vals in per_doc.items()}
            for f, per_doc in self.shape_values.items()
        }
        self.vector_values = {
            f: {int(inv[d]): vec for d, vec in per_doc.items()}
            for f, per_doc in self.vector_values.items()
        }
        for entry in self.nested_builders.values():
            entry["parent_of"] = [int(inv[d]) for d in entry["parent_of"]]
        return inv

    def seal(self) -> Segment:
        # old->new doc map when an index sort permuted this segment
        self.seal_doc_remap = None
        if self.index_sort and self.num_docs > 1:
            from elasticsearch_tpu.index.index_sort import index_sort_permutation

            perm = index_sort_permutation(self, self.index_sort)
            if perm is not None:
                self.seal_doc_remap = self._remap_docs(perm)
        nd = self.num_docs
        nd_pad = next_pow2(max(nd, 1))
        term_keys = sorted(self.postings.keys())
        term_ids = {k: i for i, k in enumerate(term_keys)}

        # --- block-pack postings ---
        n_terms = len(term_keys)
        term_block_start = np.zeros(n_terms, dtype=np.int32)
        term_block_count = np.zeros(n_terms, dtype=np.int32)
        term_doc_freq = np.zeros(n_terms, dtype=np.int32)
        total_blocks = sum(
            (len(p) + BLOCK - 1) // BLOCK for p in self.postings.values()
        )
        total_blocks = max(total_blocks, 1)
        block_docs = np.full((total_blocks, BLOCK), nd_pad, dtype=np.int32)
        block_tfs = np.zeros((total_blocks, BLOCK), dtype=np.float32)
        b = 0
        for key in term_keys:
            plist = self.postings[key]
            tid = term_ids[key]
            term_doc_freq[tid] = len(plist)
            term_block_start[tid] = b
            nblocks = (len(plist) + BLOCK - 1) // BLOCK
            term_block_count[tid] = nblocks
            docs = np.fromiter((d for d, _ in plist), dtype=np.int32, count=len(plist))
            tfs = np.fromiter((t for _, t in plist), dtype=np.float32, count=len(plist))
            for i in range(nblocks):
                chunk = docs[i * BLOCK : (i + 1) * BLOCK]
                block_docs[b, : len(chunk)] = chunk
                block_tfs[b, : len(chunk)] = tfs[i * BLOCK : (i + 1) * BLOCK]
                b += 1

        # --- norms (per text field doc-length columns) ---
        field_norm_idx = {f: i for i, f in enumerate(sorted(self.field_lengths))}
        norms = np.ones((max(len(field_norm_idx), 1), nd_pad + 1), dtype=np.float32)
        field_stats: Dict[str, dict] = {}
        for f, idx in field_norm_idx.items():
            lengths = self.field_lengths[f]
            col = np.zeros(nd_pad + 1, dtype=np.float32)
            for doc, ln in lengths.items():
                col[doc] = ln
            col[nd_pad] = 1.0
            norms[idx] = col
            field_stats[f] = {
                "doc_count": len(lengths),
                "sum_ttf": int(sum(lengths.values())),
            }

        # --- numeric columns ---
        numeric_columns = {}
        for f, pairs in self.numeric_values.items():
            pairs.sort(key=lambda p: p[0])
            n_vals = len(pairs)
            cap = next_pow2(max(n_vals, 1))
            flat_docs = np.full(cap, nd_pad, dtype=np.int32)
            flat_values = np.zeros(cap, dtype=np.float64)
            first_value = np.zeros(nd_pad, dtype=np.float64)
            min_value = np.full(nd_pad, np.inf, dtype=np.float64)
            max_value = np.full(nd_pad, -np.inf, dtype=np.float64)
            exists = np.zeros(nd_pad, dtype=bool)
            for i, (doc, v) in enumerate(pairs):
                flat_docs[i] = doc
                flat_values[i] = v
                if not exists[doc]:
                    first_value[doc] = v
                exists[doc] = True
                min_value[doc] = min(min_value[doc], v)
                max_value[doc] = max(max_value[doc], v)
            numeric_columns[f] = NumericColumn(
                flat_values, flat_docs, first_value, min_value, max_value, exists, n_vals
            )

        # --- ordinal (string) columns ---
        ordinal_columns = {}
        for f, pairs in self.string_values.items():
            # dedupe (doc, value): SortedSetDocValues semantics — a doc holds
            # each distinct value once, in value order (first_ord must be
            # the doc's MIN ordinal: sort keys + early termination rely on
            # it being deterministic)
            pairs = sorted(set(pairs))
            terms = sorted({v for _, v in pairs})
            ord_map = {t: i for i, t in enumerate(terms)}
            n_vals = len(pairs)
            cap = next_pow2(max(n_vals, 1))
            flat_docs = np.full(cap, nd_pad, dtype=np.int32)
            flat_ords = np.zeros(cap, dtype=np.int32)
            first_ord = np.full(nd_pad, -1, dtype=np.int32)
            exists = np.zeros(nd_pad, dtype=bool)
            for i, (doc, v) in enumerate(pairs):
                flat_docs[i] = doc
                flat_ords[i] = ord_map[v]
                if first_ord[doc] < 0:
                    first_ord[doc] = ord_map[v]
                exists[doc] = True
            ordinal_columns[f] = OrdinalColumn(
                terms, flat_ords, flat_docs, first_ord, exists, n_vals
            )

        # --- geo columns ---
        geo_columns = {}
        for f, triples in self.geo_values.items():
            triples.sort(key=lambda p: p[0])
            n_vals = len(triples)
            cap = next_pow2(max(n_vals, 1))
            flat_docs = np.full(cap, nd_pad, dtype=np.int32)
            lat = np.zeros(cap, dtype=np.float32)
            lon = np.zeros(cap, dtype=np.float32)
            first_lat = np.zeros(nd_pad, dtype=np.float32)
            first_lon = np.zeros(nd_pad, dtype=np.float32)
            exists = np.zeros(nd_pad, dtype=bool)
            for i, (doc, la, lo) in enumerate(triples):
                flat_docs[i] = doc
                lat[i], lon[i] = la, lo
                if not exists[doc]:
                    first_lat[doc], first_lon[doc] = la, lo
                exists[doc] = True
            geo_columns[f] = GeoColumn(lat, lon, flat_docs, first_lat, first_lon,
                                       exists, n_vals)

        # --- dense_vector columns ---
        vector_columns: Dict[str, VectorColumn] = {}
        if self.vector_values:
            from elasticsearch_tpu.ops.pallas_knn import bf16_round

            for f, per_doc in self.vector_values.items():
                dims = self.vector_dims[f]
                vecs = np.zeros((nd_pad, dims), np.float32)
                exists = np.zeros(nd_pad, dtype=bool)
                docs = np.fromiter(per_doc, np.int64, len(per_doc))
                vecs[docs] = np.stack(list(per_doc.values()))
                exists[docs] = True
                # round to the bf16 grid ONCE at seal: the host mirror,
                # the numpy oracle and the device bf16 staging all see
                # the same values (docs/VECTOR.md storage contract)
                vector_columns[f] = VectorColumn(
                    bf16_round(vecs), exists, dims, len(per_doc))

        # --- exists masks ---
        exists_masks = {}
        for f, docs in self.field_docs.items():
            mask = np.zeros(nd_pad, dtype=bool)
            for d in docs:
                mask[d] = True
            exists_masks[f] = mask

        # --- positions (host-side, for phrase queries) ---
        positions = {}
        for key, per_doc in self.positions.items():
            positions[term_ids[key]] = {
                doc: np.asarray(pos, dtype=np.int32) for doc, pos in per_doc.items()
            }

        # --- nested sub-segments ---
        nested: Dict[str, NestedContext] = {}
        for path, entry in self.nested_builders.items():
            nested[path] = NestedContext(
                segment=entry["builder"].seal(),
                parent_of=np.asarray(entry["parent_of"], dtype=np.int32),
                offset_of=np.asarray(entry["offset_of"], dtype=np.int32),
            )

        return Segment(
            name=self.name,
            num_docs=nd,
            doc_ids=list(self.doc_ids),
            sources=self.sources,
            routings=list(self.routings),
            seqnos=np.asarray(self.seqnos, dtype=np.int64),
            versions=np.asarray(self.versions, dtype=np.int64),
            term_keys=term_keys,
            term_block_start=term_block_start,
            term_block_count=term_block_count,
            term_doc_freq=term_doc_freq,
            block_docs=block_docs,
            block_tfs=block_tfs,
            field_stats=field_stats,
            field_norm_idx=field_norm_idx,
            norms=norms,
            numeric_columns=numeric_columns,
            ordinal_columns=ordinal_columns,
            geo_columns=geo_columns,
            exists_masks=exists_masks,
            positions=positions,
            nested=nested,
            shapes={f: dict(per_doc) for f, per_doc in self.shape_values.items()},
            parents=list(self.parents),
            vector_columns=vector_columns,
        )


class PinnedSegmentView:
    """Point-in-time view of a sealed segment — the pinned-searcher /
    ScrollContext analog (reference: search/internal/ScrollContext.java,
    SearchService.java:874 keep-alive contexts). Shares every immutable
    array (postings, doc values, stored sources, device stagings) with
    the live segment, but freezes the LIVE MASK at construction:
    concurrent deletes/updates mutate ``Segment.live`` in place and
    merges swap the engine's segment list, yet an open scroll keeps
    seeing exactly the docs that were visible when it opened. Dropping
    the view (clear_scroll / keep-alive expiry) releases the pin — plain
    refcounting via the Python references the view holds."""

    def __init__(self, seg: "Segment"):
        self._seg = seg
        self.live = seg.live.copy()
        self._pin_device: dict = {}
        # device_arrays() must return the SAME dict object every call and
        # mutate it in place when kernel_live_t_for stages a new layout —
        # ShardSearcher.query captures the dict before plan build, and a
        # PallasScoreTermsNode emitted later reads its live_key from that
        # captured snapshot (the Segment._device contract)
        self._merged: dict = {}

    def __getattr__(self, name):
        return getattr(self._seg, name)

    @property
    def live_doc_count(self) -> int:
        return int(self.live[: self._seg.num_docs].sum())

    def device_arrays(self) -> dict:
        base = self._seg.device_arrays()
        if "live1" not in self._pin_device:
            import jax.numpy as jnp

            live1 = np.concatenate([self.live, np.zeros(1, dtype=bool)])
            self._pin_device["live"] = jnp.asarray(self.live)
            self._pin_device["live1"] = jnp.asarray(live1)
        if (("k_docs" in base or "k_packed" in base)
                and "k_live_t" not in self._pin_device):
            self._pin_device["k_live_t"] = self._build_pinned_live_t(
                self._seg.kernel_geom.tile_sub)
        # shared immutable arrays come from the live segment; every
        # (mutable) live-mask entry — including per-sub variants the live
        # segment restages after deletes — comes ONLY from the pin
        for key, val in base.items():
            if key in ("live", "live1") or key.startswith("k_live_t"):
                continue
            self._merged[key] = val
        self._merged.update(self._pin_device)
        return self._merged

    def kernel_live_t_for(self, sub: int) -> str:
        key = f"k_live_t_{sub}"
        if key not in self._pin_device:
            self._pin_device[key] = self._build_pinned_live_t(sub)
            self._merged[key] = self._pin_device[key]
        return key

    def ensure_vector_staged(self, field: str, metric: str = "cosine"):
        """Vector stagings are immutable (the pin only freezes the live
        mask), so the view shares the live segment's arrays — but they
        must be copied into the view's merged dict, which a plan built
        AFTER device_arrays() was captured reads from."""
        keys = self._seg.ensure_vector_staged(field, metric)
        if keys is not None:
            base = self._seg.device_arrays()
            for key in keys[:3]:
                if key in base:
                    self._merged[key] = base[key]
        return keys

    def _build_pinned_live_t(self, sub: int):
        import jax.numpy as jnp

        from elasticsearch_tpu.ops import pallas_scoring as psc

        geom = psc.tile_geometry(self._seg.nd_pad, sub)
        return jnp.asarray(psc.build_live_t(
            self.live[: self._seg.nd_pad].astype(np.float32), geom))
