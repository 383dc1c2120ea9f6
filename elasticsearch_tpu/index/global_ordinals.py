"""Global ordinals: one ordinal space across a shard's segments.

Role model: Lucene's ``OrdinalMap`` via the reference's
``GlobalOrdinalsBuilder`` (index/fielddata/ordinals/GlobalOrdinalsBuilder
.java) and its use by ``GlobalOrdinalsStringTermsAggregator`` — built
lazily per field over the current segment set, cached until that set
changes, so cross-segment terms aggregation merges integer count arrays
instead of string dictionaries.

TPU framing: per-segment local->global maps are dense int32 arrays, and
every local ord is distinct, so a segment's per-ordinal counts fold into
the global array with one vectorized indexed add — no host string
hashing on the query path.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Sequence, Tuple

import numpy as np


class GlobalOrdinals:
    __slots__ = ("field", "terms", "_seg_maps")

    def __init__(self, field: str, terms: List[str],
                 seg_maps: Dict[int, np.ndarray]):
        self.field = field
        self.terms = terms  # global sorted unique terms; global ord = index
        self._seg_maps = seg_maps  # id(segment) -> [n_local_ords] int32

    def seg_map(self, segment) -> np.ndarray:
        return self._seg_maps[id(segment)]

    def fold_counts(self, segment, local_counts: np.ndarray,
                    out: np.ndarray) -> None:
        """Add one segment's per-local-ordinal counts into the global
        array. Local ords map to DISTINCT global ords, so a plain fancy-
        indexed add is exact (no np.add.at scatter needed)."""
        m = self.seg_map(segment)
        out[m] += local_counts[: len(m)]


_CACHE_MAX = 64
_cache: Dict[Tuple, GlobalOrdinals] = {}
_cache_lock = threading.Lock()


def _ordinal_column(segment, field: str):
    return (segment.ordinal_columns.get(field)
            or segment.ordinal_columns.get(f"{field}.keyword"))


def global_ordinals(segments: Sequence, field: str,
                    columns: Sequence = None) -> GlobalOrdinals:
    """Build (or fetch cached) global ordinals for a field over a segment
    set. The cache key includes each segment's identity and live epoch —
    refresh/merge produces new segment objects, which naturally
    invalidates (IndicesFieldDataCache semantics).

    columns: optional pre-resolved per-segment ordinal columns (the
    aggregation layer resolves text fielddata lazily — this module must
    see the SAME columns or a text field would silently map to an empty
    ordinal space)."""
    key = (field, tuple((s.name, id(s)) for s in segments))
    with _cache_lock:
        hit = _cache.get(key)
        if hit is not None:
            return hit[0]
    per_seg: List[Tuple[object, List[str]]] = []
    for i, seg in enumerate(segments):
        ocol = (columns[i] if columns is not None
                else _ordinal_column(seg, field))
        per_seg.append((seg, ocol.terms if ocol is not None else []))
    # merged global term list; per-segment map via searchsorted (each
    # segment's term list is already sorted and unique)
    all_terms = sorted(set().union(*[t for _, t in per_seg])) \
        if per_seg else []
    terms_arr = np.asarray(all_terms, dtype=object)
    seg_maps: Dict[int, np.ndarray] = {}
    for seg, terms in per_seg:
        if terms:
            seg_maps[id(seg)] = np.searchsorted(
                terms_arr, np.asarray(terms, dtype=object)).astype(np.int32)
        else:
            seg_maps[id(seg)] = np.zeros(0, np.int32)
    built = GlobalOrdinals(field, all_terms, seg_maps)

    def _evict(_ref, _key=key):
        # a cached entry must die WITH its segments: the key embeds
        # id(segment), and CPython reuses ids after free — a stale hit
        # would fold counts through the wrong local->global map
        with _cache_lock:
            _cache.pop(_key, None)

    # the weakrefs ride in the cache VALUE: they must stay alive for the
    # eviction callback to ever fire
    refs = [weakref.ref(seg, _evict) for seg in segments]
    with _cache_lock:
        if len(_cache) >= _CACHE_MAX:
            _cache.pop(next(iter(_cache)))
        _cache[key] = (built, refs)
    return built


def numeric_global_ordinals(columns: Sequence) -> np.ndarray:
    """The numeric twin of the merged term list: the sorted distinct
    float64 values over per-segment ``NumericColumn``s (None where a
    segment lacks the field). A value's global ordinal is its
    ``np.searchsorted`` position. Not cached here: the mesh executor
    keeps one per field for its generation."""
    parts = [c.flat_values[: c.count] for c in columns
             if c is not None and c.count]
    if not parts:
        return np.zeros(0, np.float64)
    return np.unique(np.concatenate(parts))
