"""Field types: JSON value -> indexable terms + columnar doc values.

Role model: ``MappedFieldType`` and the concrete mappers
(core/.../index/mapper/TextFieldMapper.java, KeywordFieldMapper.java,
NumberFieldMapper.java, DateFieldMapper.java, BooleanFieldMapper.java,
IpFieldMapper.java, ScaledFloatFieldMapper.java). Each type decides how a
field value is (a) analyzed into inverted-index terms and (b) encoded into
a columnar doc value for sorting/aggregations.

TPU adaptation: doc values are *always* numeric float64/int64 columns
(keywords become ordinals at segment seal), so every aggregation/sort is a
dense vector op. Range queries on numerics run against the column, not a
BKD tree.
"""

from __future__ import annotations

import datetime as _dt
import ipaddress
import math
from typing import Any, List, Optional

import numpy as np

from elasticsearch_tpu.common.errors import (
    IllegalArgumentException,
    MapperParsingException,
)

NUMERIC_TYPES = {
    "long", "integer", "short", "byte", "double", "float", "half_float",
    "scaled_float",
}

_INT_RANGES = {
    "long": (-(2**63), 2**63 - 1),
    "integer": (-(2**31), 2**31 - 1),
    "short": (-(2**15), 2**15 - 1),
    "byte": (-(2**7), 2**7 - 1),
}


def parse_date(value: Any, formats: Optional[List[str]] = None) -> int:
    """Parse a date value to epoch milliseconds (UTC).

    Reference behavior: DateFieldMapper with default format
    ``strict_date_optional_time||epoch_millis``.
    """
    if isinstance(value, bool):
        raise MapperParsingException(f"failed to parse date field [{value}]")
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value).strip()
    if formats:
        for fmt in formats:
            if fmt == "epoch_millis":
                try:
                    return int(s)
                except ValueError:
                    continue
            if fmt == "epoch_second":
                try:
                    return int(s) * 1000
                except ValueError:
                    continue
            try:
                dt = _dt.datetime.strptime(s, _java_to_strptime(fmt))
                return _to_millis(dt)
            except ValueError:
                continue
        raise MapperParsingException(
            f"failed to parse date field [{s}] with format [{'||'.join(formats)}]"
        )
    # default: ISO-8601 (strict_date_optional_time) or epoch_millis
    try:
        return int(s)
    except ValueError:
        pass
    try:
        iso = s.replace("Z", "+00:00")
        if len(iso) == 10:  # yyyy-MM-dd
            dt = _dt.datetime.fromisoformat(iso + "T00:00:00+00:00")
        else:
            dt = _dt.datetime.fromisoformat(iso)
        return _to_millis(dt)
    except ValueError:
        raise MapperParsingException(f"failed to parse date field [{s}]") from None


def _to_millis(dt: _dt.datetime) -> int:
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    return int(dt.timestamp() * 1000)


_JAVA_FMT = {
    "yyyy": "%Y", "MM": "%m", "dd": "%d", "HH": "%H", "mm": "%M", "ss": "%S",
}


def _java_to_strptime(fmt: str) -> str:
    out = fmt
    for j, p in _JAVA_FMT.items():
        out = out.replace(j, p)
    return out


def format_epoch_millis(millis: int) -> str:
    dt = _dt.datetime.fromtimestamp(millis / 1000.0, tz=_dt.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}Z"


def parse_ip(value: Any) -> int:
    """Encode an IP as an integer (IPv4-mapped into IPv6 space, like Lucene's
    16-byte encoding; we keep a python int, stored as the doc value)."""
    try:
        addr = ipaddress.ip_address(str(value))
    except ValueError:
        raise MapperParsingException(f"'{value}' is not an IP string literal.") from None
    if isinstance(addr, ipaddress.IPv4Address):
        addr = ipaddress.IPv6Address(f"::ffff:{addr}")
    return int(addr)


def format_ip(value: int) -> str:
    addr = ipaddress.IPv6Address(int(value))
    v4 = addr.ipv4_mapped
    return str(v4) if v4 is not None else str(addr)


class FieldType:
    """Base field type. Subclasses override value handling.

    Attributes mirror the mapping parameters the reference accepts for the
    type (index, doc_values, store, boost, analyzer, ...).
    """

    type_name = "object"
    # does this type produce inverted-index terms?
    indexable = True
    # does this type produce a numeric doc-value column?
    has_doc_values = True
    # string-ordinal doc values (keyword-family) vs plain numeric
    ordinal_doc_values = False

    def __init__(self, name: str, params: Optional[dict] = None):
        self.name = name
        self.params = dict(params or {})
        self.index = self.params.get("index", True)
        self.doc_values = self.params.get("doc_values", self.has_doc_values)
        self.boost = float(self.params.get("boost", 1.0))
        self.null_value = self.params.get("null_value")

    # --- index-time ---

    def index_terms(self, value: Any, analyzers) -> List[str]:
        """Terms for the inverted index (already analyzed)."""
        raise NotImplementedError

    def doc_value(self, value: Any):
        """Columnar value: float for numerics/dates/bools, str for ordinals."""
        raise NotImplementedError

    # --- query-time ---

    def term_for_query(self, value: Any, analyzers) -> str:
        """Normalize a user-provided term the way index_terms would."""
        return str(value)

    def numeric_for_query(self, value: Any) -> float:
        raise IllegalArgumentException(
            f"Field [{self.name}] of type [{self.type_name}] does not support numeric queries"
        )

    def to_mapping(self) -> dict:
        out = {"type": self.type_name}
        out.update({k: v for k, v in self.params.items() if k != "type"})
        return out


class TextFieldType(FieldType):
    type_name = "text"
    has_doc_values = False  # like ES: text has no doc_values (fielddata opt-in)

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.analyzer = self.params.get("analyzer", "standard")
        self.search_analyzer = self.params.get("search_analyzer", self.analyzer)
        self.fielddata = bool(self.params.get("fielddata", False))
        # per-field similarity name (index/similarity/SimilarityService.java)
        self.similarity_name = self.params.get("similarity")

    def index_terms(self, value, analyzers):
        return analyzers.get(self.analyzer).analyze(str(value))

    def doc_value(self, value):
        return None

    def term_for_query(self, value, analyzers):
        toks = analyzers.get(self.search_analyzer).analyze(str(value))
        return toks[0] if toks else ""

    def query_terms(self, value, analyzers):
        return analyzers.get(self.search_analyzer).analyze(str(value))


class KeywordFieldType(FieldType):
    type_name = "keyword"
    ordinal_doc_values = True

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.ignore_above = int(self.params.get("ignore_above", 2**31 - 1))
        self.normalizer = self.params.get("normalizer")

    def _normalize(self, s: str) -> str:
        if self.normalizer == "lowercase":
            return s.lower()
        return s

    def index_terms(self, value, analyzers):
        s = str(value)
        if len(s) > self.ignore_above:
            return []
        return [self._normalize(s)]

    def doc_value(self, value):
        s = str(value)
        if len(s) > self.ignore_above:
            return None
        return self._normalize(s)

    def term_for_query(self, value, analyzers):
        return self._normalize(str(value))


class NumberFieldType(FieldType):
    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.coerce = bool(self.params.get("coerce", True))

    def _parse(self, value):
        if isinstance(value, bool):
            raise MapperParsingException(
                f"failed to parse field [{self.name}] of type [{self.type_name}]: "
                f"booleans are not numbers"
            )
        try:
            if isinstance(value, str) and not self.coerce:
                raise ValueError(value)
            f = float(value)
        except (TypeError, ValueError):
            raise MapperParsingException(
                f"failed to parse field [{self.name}] of type [{self.type_name}] "
                f"value [{value}]"
            ) from None
        if math.isnan(f) or math.isinf(f):
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: non-finite value"
            )
        return f

    def index_terms(self, value, analyzers):
        # numeric "terms" are the doc values themselves; term queries on
        # numerics run against the column (no BKD analog needed).
        return []

    def numeric_for_query(self, value):
        return self._parse(value)


class IntegerLikeFieldType(NumberFieldType):
    def doc_value(self, value):
        f = self._parse(value)
        i = int(f)
        if not self.coerce and f != i:
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: [{value}] has a decimal part"
            )
        lo, hi = _INT_RANGES[self.type_name]
        if not (lo <= i <= hi):
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: value [{value}] is out of "
                f"range for type [{self.type_name}]"
            )
        return float(i)


class LongFieldType(IntegerLikeFieldType):
    type_name = "long"


class IntegerFieldType(IntegerLikeFieldType):
    type_name = "integer"


class ShortFieldType(IntegerLikeFieldType):
    type_name = "short"


class ByteFieldType(IntegerLikeFieldType):
    type_name = "byte"


class DoubleFieldType(NumberFieldType):
    type_name = "double"

    def doc_value(self, value):
        return self._parse(value)


class FloatFieldType(DoubleFieldType):
    type_name = "float"


class HalfFloatFieldType(DoubleFieldType):
    type_name = "half_float"


class ScaledFloatFieldType(NumberFieldType):
    type_name = "scaled_float"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        if "scaling_factor" not in self.params:
            raise MapperParsingException(
                f"Field [{name}] misses required parameter [scaling_factor]"
            )
        self.scaling_factor = float(self.params["scaling_factor"])

    def doc_value(self, value):
        # stored scaled+rounded, like the reference (value*factor rounded to long)
        return float(round(self._parse(value) * self.scaling_factor)) / self.scaling_factor

    def numeric_for_query(self, value):
        return self._parse(value)


class DateFieldType(FieldType):
    type_name = "date"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        fmt = self.params.get("format")
        self.formats = fmt.split("||") if isinstance(fmt, str) else None

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return float(parse_date(value, self.formats))

    def numeric_for_query(self, value):
        return float(parse_date(value, self.formats))


class BooleanFieldType(FieldType):
    type_name = "boolean"

    def _parse(self, value) -> bool:
        if isinstance(value, bool):
            return value
        s = str(value)
        if s == "true":
            return True
        if s == "false":
            return False
        raise MapperParsingException(
            f"Failed to parse value [{value}] as only [true] or [false] are allowed."
        )

    def index_terms(self, value, analyzers):
        return ["T" if self._parse(value) else "F"]

    def doc_value(self, value):
        return 1.0 if self._parse(value) else 0.0

    def term_for_query(self, value, analyzers):
        return "T" if self._parse(value) else "F"

    def numeric_for_query(self, value):
        return 1.0 if self._parse(value) else 0.0


class IpFieldType(FieldType):
    type_name = "ip"
    ordinal_doc_values = True  # store dotted string as ordinal; range via int

    def index_terms(self, value, analyzers):
        return [format_ip(parse_ip(value))]

    def doc_value(self, value):
        return format_ip(parse_ip(value))

    def term_for_query(self, value, analyzers):
        return format_ip(parse_ip(value))


class GeoPointFieldType(FieldType):
    """geo_point: stored as two numeric columns (<name>.lat / <name>.lon)
    managed by the segment writer; distance/bbox filters are vector math."""

    type_name = "geo_point"

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return self.parse_point(value)

    @staticmethod
    def parse_point(value):
        if isinstance(value, dict):
            lat, lon = value.get("lat"), value.get("lon")
        elif isinstance(value, (list, tuple)) and len(value) == 2:
            lon, lat = value  # GeoJSON order [lon, lat]
        elif isinstance(value, str):
            parts = value.split(",")
            if len(parts) != 2:
                raise MapperParsingException(f"failed to parse geo_point [{value}]")
            lat, lon = float(parts[0]), float(parts[1])
        else:
            raise MapperParsingException(f"failed to parse geo_point [{value}]")
        lat, lon = float(lat), float(lon)
        if not (-90.0 <= lat <= 90.0) or not (-180.0 <= lon <= 180.0):
            raise MapperParsingException(
                f"illegal latitude/longitude value [{lat}, {lon}]"
            )
        return (lat, lon)


class RangeFieldType(FieldType):
    """Range family (index/mapper/RangeFieldMapper.java:73 — RangeType enum
    :435): a value is a {gte/gt/lte/lt} pair. Lucene stores these as
    RangeField BKD points; here each value becomes an aligned (lo, hi) pair
    in two parallel CSR numeric columns (`<field>#lo`, `<field>#hi`) so
    intersects/contains/within relations are elementwise comparisons."""

    has_doc_values = True
    # the scalar type used to parse each bound
    value_parser: str = "double"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.coerce = bool(self.params.get("coerce", True))

    def _bound(self, v):
        raise NotImplementedError

    # exclusive-bound adjustment step (1.0 for int-like, ulp for floats)
    def _next_up(self, v: float) -> float:
        return math.nextafter(v, math.inf)

    def _next_down(self, v: float) -> float:
        return math.nextafter(v, -math.inf)

    def parse_range(self, value) -> tuple:
        """-> (lo, hi) inclusive float bounds."""
        if not isinstance(value, dict):
            raise MapperParsingException(
                f"error parsing field [{self.name}], expected an object but got "
                f"[{value!r}]"
            )
        lo, hi = -math.inf, math.inf
        for k, v in value.items():
            if k == "gte":
                lo = self._bound(v)
            elif k == "gt":
                lo = self._next_up(self._bound(v))
            elif k == "lte":
                hi = self._bound(v)
            elif k == "lt":
                hi = self._next_down(self._bound(v))
            else:
                raise MapperParsingException(
                    f"error parsing field [{self.name}], unknown range parameter [{k}]"
                )
        return lo, hi

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return None

    def numeric_for_query(self, value):
        return self._bound(value)


class IntegerRangeFieldType(RangeFieldType):
    type_name = "integer_range"

    def _bound(self, v):
        return float(int(float(v)))

    def _next_up(self, v):
        return v + 1.0

    def _next_down(self, v):
        return v - 1.0


class LongRangeFieldType(IntegerRangeFieldType):
    type_name = "long_range"


class FloatRangeFieldType(RangeFieldType):
    type_name = "float_range"

    def _bound(self, v):
        return float(v)


class DoubleRangeFieldType(FloatRangeFieldType):
    type_name = "double_range"


class DateRangeFieldType(RangeFieldType):
    type_name = "date_range"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        fmt = self.params.get("format")
        self.formats = fmt.split("||") if isinstance(fmt, str) else None

    def _bound(self, v):
        return float(parse_date(v, self.formats))

    def _next_up(self, v):  # +1ms, like the reference's DATE range type
        return v + 1.0

    def _next_down(self, v):
        return v - 1.0


class IpRangeFieldType(RangeFieldType):
    type_name = "ip_range"

    def _bound(self, v):
        return float(parse_ip(v))

    # exclusive bounds step by one float64 ulp (the base-class default):
    # a +1 integer step is below ulp at IPv6 magnitudes (~2^128), which
    # would silently turn gt/lt into gte/lte; one ulp correctly excludes
    # the (float64-rounded) stored bound itself.

    def parse_range(self, value):
        # CIDR shorthand: "10.0.0.0/8"
        if isinstance(value, str) and "/" in value:
            net = ipaddress.ip_network(value, strict=False)
            lo = net.network_address
            hi = net.broadcast_address
            if isinstance(lo, ipaddress.IPv4Address):
                lo = ipaddress.IPv6Address(f"::ffff:{lo}")
                hi = ipaddress.IPv6Address(f"::ffff:{hi}")
            return float(int(lo)), float(int(hi))
        return super().parse_range(value)


class TokenCountFieldType(NumberFieldType):
    """token_count (index/mapper/TokenCountFieldMapper): analyzes the text
    and indexes the token count as a numeric doc value. Subclasses the
    numeric family so term/range queries run against the column."""

    type_name = "token_count"

    def __init__(self, name, params=None):
        super().__init__(name, params)
        self.analyzer = self.params.get("analyzer", "standard")

    def doc_value(self, value):  # replaced by count_tokens at parse time
        return None

    def count_tokens(self, value, analyzers) -> float:
        # counts emitted tokens; the analysis chain does not track position
        # increments, so enable_position_increments is not supported
        return float(len(analyzers.get(self.analyzer).analyze(str(value))))


class BinaryFieldType(FieldType):
    """binary (index/mapper/BinaryFieldMapper): base64 payload, not
    searchable; doc values keep the base64 string (ordinal column)."""

    type_name = "binary"
    indexable = False
    has_doc_values = False  # like the reference: doc_values default false
    ordinal_doc_values = True

    def __init__(self, name, params=None):
        super().__init__(name, params)

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        if not self.doc_values:
            return None
        s = str(value)
        import base64 as _b64

        try:
            _b64.b64decode(s, validate=True)
        except Exception:
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: invalid base64"
            ) from None
        return s


class Murmur3FieldType(NumberFieldType):
    """murmur3 (plugins/mapper-murmur3 — Murmur3FieldMapper): stores the
    murmur3 hash of the value as a numeric doc value, so cardinality aggs
    skip hashing at query time."""

    type_name = "murmur3"

    def doc_value(self, value):
        from elasticsearch_tpu.utils.murmur3 import murmur3_32

        # murmur3_32 already returns a signed Java-int-style value
        return float(murmur3_32(str(value).encode("utf-8")))


class JoinFieldType(FieldType):
    """join (modules/parent-join — ParentJoinFieldMapper): one relation
    field per index declaring parent->child relations. A doc's value is
    either the relation name (parent) or {"name": ..., "parent": id}
    (child). The relation name lands in the field's ordinal column + the
    inverted index; the parent id in a parallel '<field>#parent' ordinal
    column (standing in for Lucene's per-relation join doc-values field).

    Parent/child joins require same-shard colocation: children must be
    indexed with routing = parent id (enforced at the write path)."""

    type_name = "join"
    ordinal_doc_values = True

    def __init__(self, name, params=None):
        super().__init__(name, params)
        rel = self.params.get("relations") or {}
        # parent -> [children]
        self.relations: dict = {
            p: (c if isinstance(c, list) else [c]) for p, c in rel.items()
        }
        self._parent_of = {
            c: p for p, cs in self.relations.items() for c in cs
        }

    def parent_of(self, child_name: str) -> Optional[str]:
        return self._parent_of.get(child_name)

    def is_parent(self, name: str) -> bool:
        return name in self.relations

    def valid_relation(self, name: str) -> bool:
        return name in self.relations or name in self._parent_of

    def parse_join(self, value) -> tuple:
        """-> (relation_name, parent_id or None)."""
        if isinstance(value, str):
            name, parent = value, None
        elif isinstance(value, dict):
            name = value.get("name")
            parent = value.get("parent")
        else:
            raise MapperParsingException(
                f"failed to parse join field [{self.name}] value [{value!r}]"
            )
        if not self.valid_relation(name):
            raise MapperParsingException(
                f"unknown join name [{name}] for field [{self.name}]"
            )
        if name in self._parent_of and parent is None:
            raise MapperParsingException(
                f"[parent] is missing for join field [{self.name}]"
            )
        if name in self.relations and name not in self._parent_of and parent is not None:
            raise MapperParsingException(
                f"[parent] is specified but the join name [{name}] is a parent"
            )
        return str(name), (str(parent) if parent is not None else None)

    def index_terms(self, value, analyzers):
        name, _ = self.parse_join(value)
        return [name]

    def doc_value(self, value):
        return None  # handled specially in DocumentMapper._index_single


class DenseVectorFieldType(FieldType):
    """dense_vector: a fixed-dimension float embedding per document
    (the reference grew this in 7.x — DenseVectorFieldMapper; the 8.x
    ``similarity`` mapping param picks the kNN metric). Values are NOT
    inverted-index terms or scalar doc values: they land in a dedicated
    per-segment ``[nd_pad, dims]`` column stored bf16 on device and
    scored by the MXU kNN kernel (ops/pallas_knn.py). See
    docs/VECTOR.md."""

    type_name = "dense_vector"
    indexable = False
    has_doc_values = False

    SIMILARITIES = ("cosine", "dot_product", "max_inner_product")
    # index_options.type: this system scans every vector (exact top-k),
    # which is what Elasticsearch answers under ``flat``; a graph or a
    # quantised index would be another answer, not a faster one
    INDEX_TYPES = ("flat",)

    def __init__(self, name, params=None):
        super().__init__(name, params)
        dims = self.params.get("dims")
        if dims is None:
            raise MapperParsingException(
                f"Field [{name}] of type [dense_vector] misses required "
                f"parameter [dims]")
        try:
            self.dims = int(dims)
        except (TypeError, ValueError):
            raise MapperParsingException(
                f"Field [{name}]: [dims] must be an integer, got "
                f"[{dims!r}]") from None
        if self.dims < 1:
            raise MapperParsingException(
                f"Field [{name}]: [dims] must be a positive integer, got "
                f"[{self.dims}]")
        self.similarity = self.params.get("similarity", "cosine")
        if self.similarity not in self.SIMILARITIES:
            raise MapperParsingException(
                f"Field [{name}]: unknown [similarity] "
                f"[{self.similarity}]; expected one of "
                f"{list(self.SIMILARITIES)}")
        options = self.params.get("index_options")
        if options is not None and (
                not isinstance(options, dict)
                or options.get("type") not in self.INDEX_TYPES):
            raise MapperParsingException(
                f"Field [{name}]: unsupported [index_options] "
                f"[{options!r}]: this system scans every vector and "
                f"answers the exact top-k, so the only [type] is "
                f"{list(self.INDEX_TYPES)} (no hnsw, no quantised type)")

    def parse_vector(self, value) -> np.ndarray:
        """Validate one vector (a document's, or a query's): a list of
        exactly ``dims`` finite numbers, returned as ONE float32 row.
        Anything else is a 400 at index time."""
        if not isinstance(value, (list, tuple)):
            raise MapperParsingException(
                f"failed to parse field [{self.name}] of type "
                f"[dense_vector]: expected an array of {self.dims} "
                f"numbers, got [{value!r}]")
        if len(value) != self.dims:
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: the [dims] of the "
                f"vector [{len(value)}] does not match the mapping "
                f"[{self.dims}]")
        # (types first: numpy would take a bool or a numeric string for
        # a number)
        if not set(map(type, value)) <= {int, float}:
            bad = next(v for v in value if type(v) not in (int, float))
            raise MapperParsingException(
                f"failed to parse field [{self.name}] of type "
                f"[dense_vector]: non-numeric element [{bad!r}]")
        try:
            with np.errstate(over="ignore"):  # beyond float32: inf
                row = np.asarray(value, np.float32)
        except OverflowError:  # an int beyond float64
            row = np.full(self.dims, np.inf, np.float32)
        if not np.isfinite(row).all():
            raise MapperParsingException(
                f"failed to parse field [{self.name}]: non-finite "
                f"vector element")
        return row

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return None


class PercolatorFieldType(FieldType):
    """percolator: stores a query DSL object for inverse search
    (modules/percolator — PercolatorFieldMapper). The query lives in
    _source; matching is done by the percolate query executing stored
    queries against an in-memory one-doc index (the reference additionally
    pre-filters via extracted terms; round-1 evaluates all stored queries)."""

    type_name = "percolator"
    has_doc_values = False

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return None


class CompletionFieldType(FieldType):
    """completion: autocomplete inputs (index/mapper/CompletionFieldMapper;
    Lucene stores an FST — here inputs land in the field's sorted ordinal
    column, weights in a parallel '<field>#weight' numeric column)."""

    type_name = "completion"
    ordinal_doc_values = True

    def __init__(self, name, params=None):
        super().__init__(name, params)
        # context mappings (search/suggest/completion/context/*):
        # [{"name": ..., "type": "category"|"geo", "precision": int}]
        self.contexts = {c["name"]: c for c in self.params.get("contexts", [])}

    def parse_completion(self, value):
        """-> (inputs: [str], weight: float, contexts: {name: [str]}).
        Geo context values encode to geohashes (the reference's
        GeoContextMapping prefix encoding)."""
        if isinstance(value, str):
            return [value], 1.0, {}
        if isinstance(value, list):
            return [str(v) for v in value], 1.0, {}
        if isinstance(value, dict):
            inputs = value.get("input", [])
            inputs = [inputs] if isinstance(inputs, str) else [str(v) for v in inputs]
            ctx_out = {}
            for cname, cvals in (value.get("contexts") or {}).items():
                cdef = self.contexts.get(cname)
                if cdef is None:
                    raise MapperParsingException(
                        f"context [{cname}] is not defined on completion "
                        f"field [{self.name}]")
                if not isinstance(cvals, list):
                    cvals = [cvals]
                if cdef.get("type", "category") == "geo":
                    from elasticsearch_tpu.utils.geohash import encode

                    encoded = []
                    for p in cvals:
                        try:
                            if isinstance(p, dict):
                                encoded.append(
                                    encode(float(p["lat"]), float(p["lon"]), 12))
                            elif isinstance(p, str) and "," in p:
                                lat, lon = p.split(",", 1)
                                encoded.append(
                                    encode(float(lat), float(lon), 12))
                            else:  # raw geohash
                                encoded.append(str(p))
                        except (KeyError, TypeError, ValueError) as e:
                            raise MapperParsingException(
                                f"failed to parse geo context [{cname}] of "
                                f"completion field [{self.name}]: {p!r}"
                            ) from e
                    ctx_out[cname] = encoded
                else:
                    ctx_out[cname] = [str(c) for c in cvals]
            return inputs, float(value.get("weight", 1.0)), ctx_out
        raise MapperParsingException(
            f"failed to parse completion field [{self.name}] value [{value!r}]"
        )

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return None


class GeoShapeFieldType(FieldType):
    """geo_shape: GeoJSON/WKT geometries kept host-side per doc with a
    dense bbox table for vectorized prefiltering (reference:
    index/mapper/GeoShapeFieldMapper.java over Lucene spatial prefix
    trees; see utils/geometry.py for the TPU-side design)."""

    type_name = "geo_shape"
    has_doc_values = False

    def index_terms(self, value, analyzers):
        return []

    def doc_value(self, value):
        return None

    def parse_shape_value(self, value):
        """Validate at index time; the raw GeoJSON dict / WKT string is
        stored and geometry objects build lazily at query time."""
        from elasticsearch_tpu.utils.geometry import parse_shape

        parse_shape(value)  # raises MapperParsingException on bad input
        return value


FIELD_TYPES = {
    t.type_name: t
    for t in [
        GeoShapeFieldType,
        CompletionFieldType,
        DenseVectorFieldType,
        PercolatorFieldType,
        TextFieldType, KeywordFieldType, LongFieldType, IntegerFieldType,
        ShortFieldType, ByteFieldType, DoubleFieldType, FloatFieldType,
        HalfFloatFieldType, ScaledFloatFieldType, DateFieldType,
        BooleanFieldType, IpFieldType, GeoPointFieldType,
        IntegerRangeFieldType, LongRangeFieldType, FloatRangeFieldType,
        DoubleRangeFieldType, DateRangeFieldType, IpRangeFieldType,
        TokenCountFieldType, BinaryFieldType, Murmur3FieldType,
        JoinFieldType,
    ]
}


def join_field_of(mapper_service) -> Optional["JoinFieldType"]:
    """The index's single join field, if mapped (ParentJoinFieldMapper
    enforces at most one per index)."""
    for ft in mapper_service.mapper.fields.values():
        if isinstance(ft, JoinFieldType):
            return ft
    return None


def create_field_type(name: str, params: dict) -> FieldType:
    typ = params.get("type")
    if typ is None and "properties" in params:
        typ = "object"
    cls = FIELD_TYPES.get(typ)
    if cls is None:
        raise MapperParsingException(
            f"No handler for type [{typ}] declared on field [{name}]"
        )
    return cls(name, params)
