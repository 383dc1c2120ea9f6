"""Typed, scoped, dynamically-updatable settings.

Role model: ``Setting``/``Settings``/``ClusterSettings``
(core/src/main/java/org/elasticsearch/common/settings/Setting.java,
ClusterSettings.java) — every tunable is a typed ``Setting`` object with a
scope (node or index), a default, optional dynamic updatability, and
registered update listeners. ``Settings`` itself is an immutable string map;
typed access always goes through a ``Setting``.
"""

from __future__ import annotations

import fnmatch
from typing import Any, Callable, Dict, Iterable, Optional

from elasticsearch_tpu.common.errors import IllegalArgumentException
from elasticsearch_tpu.common.units import parse_byte_size, parse_time_value


class Settings:
    """Immutable flat key->value map with typed getters.

    Keys are dotted paths ("index.number_of_shards"). Values are stored as
    given (str/int/float/bool/list); typed getters coerce.
    """

    EMPTY: "Settings"

    def __init__(self, data: Optional[Dict[str, Any]] = None):
        self._data: Dict[str, Any] = dict(data or {})

    @staticmethod
    def of(**kwargs) -> "Settings":
        return Settings({k.replace("__", "."): v for k, v in kwargs.items()})

    @staticmethod
    def from_dict(d: Optional[Dict[str, Any]]) -> "Settings":
        """Flatten a possibly-nested dict into dotted keys."""
        flat: Dict[str, Any] = {}

        def walk(prefix: str, obj):
            for k, v in obj.items():
                if isinstance(v, dict):
                    walk(prefix + k + ".", v)
                else:
                    flat[prefix + k] = v

        walk("", d or {})
        return Settings(flat)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._data)

    def with_index_prefix(self) -> "Settings":
        """Normalize index-level settings: bare keys get the ``index.``
        prefix (the reference accepts both ``number_of_shards`` and
        ``index.number_of_shards`` in create-index/update-settings bodies
        and canonicalizes via IndexScopedSettings prefix normalization —
        silently dropping the bare form loses e.g. the shard count)."""
        out = {}
        for k, v in self._data.items():
            if not k.startswith("index.") and k != "index":
                k = "index." + k
            out[k] = v
        return Settings(out)

    def as_nested_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for key, value in sorted(self._data.items()):
            node = out
            parts = key.split(".")
            for p in parts[:-1]:
                nxt = node.get(p)
                if not isinstance(nxt, dict):
                    nxt = {}
                    node[p] = nxt
                node = nxt
            node[parts[-1]] = value
        return out

    def keys(self) -> Iterable[str]:
        return self._data.keys()

    def __contains__(self, key: str) -> bool:
        return key in self._data

    def __len__(self) -> int:
        return len(self._data)

    def __eq__(self, other) -> bool:
        return isinstance(other, Settings) and self._data == other._data

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self._data.items())))

    def get(self, key: str, default: Any = None) -> Any:
        return self._data.get(key, default)

    def get_str(self, key: str, default: Optional[str] = None) -> Optional[str]:
        v = self._data.get(key)
        return default if v is None else str(v)

    def get_int(self, key: str, default: Optional[int] = None) -> Optional[int]:
        v = self._data.get(key)
        if v is None:
            return default
        try:
            return int(v)
        except (TypeError, ValueError):
            raise IllegalArgumentException(
                f"Failed to parse value [{v}] for setting [{key}]"
            ) from None

    def get_float(self, key: str, default: Optional[float] = None) -> Optional[float]:
        v = self._data.get(key)
        if v is None:
            return default
        try:
            return float(v)
        except (TypeError, ValueError):
            raise IllegalArgumentException(
                f"Failed to parse value [{v}] for setting [{key}]"
            ) from None

    def get_bool(self, key: str, default: Optional[bool] = None) -> Optional[bool]:
        v = self._data.get(key)
        if v is None:
            return default
        if isinstance(v, bool):
            return v
        s = str(v).lower()
        if s == "true":
            return True
        if s == "false":
            return False
        raise IllegalArgumentException(
            f"Failed to parse value [{v}] as only [true] or [false] are allowed for "
            f"setting [{key}]"
        )

    def get_list(self, key: str, default: Optional[list] = None) -> Optional[list]:
        v = self._data.get(key)
        if v is None:
            return default
        if isinstance(v, (list, tuple)):
            return list(v)
        return [p.strip() for p in str(v).split(",") if p.strip()]

    def get_time(self, key: str, default: Optional[float] = None) -> Optional[float]:
        v = self._data.get(key)
        return default if v is None else parse_time_value(v, key)

    def get_bytes(self, key: str, default: Optional[int] = None) -> Optional[int]:
        v = self._data.get(key)
        return default if v is None else parse_byte_size(v, key)

    def filtered_by_prefix(self, prefix: str) -> "Settings":
        return Settings({k: v for k, v in self._data.items() if k.startswith(prefix)})

    def merged_with(self, other: "Settings") -> "Settings":
        d = dict(self._data)
        for k, v in other._data.items():
            if v is None:
                d.pop(k, None)
            else:
                d[k] = v
        return Settings(d)


Settings.EMPTY = Settings()


class LayeredSettings:
    """Live read view over ``Settings`` sources in precedence order: a
    typed getter answers from the first source that HAS the key, else
    the default. The one place the precedence "explicit cluster value,
    then the index's (or node's) own Settings, then the default" is
    written; a reader calls ``get_*`` and does not know there are layers.

    Each source is a zero-argument callable returning the current
    ``Settings`` (both layers are replaced, not mutated: a cluster PUT
    swaps the explicit map, an index-settings update swaps the index's),
    so a view handed out once stays live."""

    def __init__(self, *sources: Callable[[], Settings]):
        self._sources = sources

    def _having(self, key: str) -> Settings:
        for source in self._sources:
            settings = source()
            if settings.get(key) is not None:
                return settings
        return Settings.EMPTY

    def get_str(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._having(key).get_str(key, default)

    def get_int(self, key: str, default: Optional[int] = None) -> Optional[int]:
        return self._having(key).get_int(key, default)

    def get_float(self, key: str, default: Optional[float] = None) -> Optional[float]:
        return self._having(key).get_float(key, default)

    def get_bool(self, key: str, default: Optional[bool] = None) -> Optional[bool]:
        return self._having(key).get_bool(key, default)

    def get_time(self, key: str, default: Optional[float] = None) -> Optional[float]:
        return self._having(key).get_time(key, default)

    def get_bytes(self, key: str, default: Optional[int] = None) -> Optional[int]:
        return self._having(key).get_bytes(key, default)


# Node-scope key prefixes that Node.create_index seeds into every new
# index's own Settings (node file under the live cluster value under the
# index's own), so a per-index reader finds them in one map.
INDEX_SEEDED_PREFIXES = (
    "search.batch.", "search.pallas.", "search.knn.", "search.aggs.",
    "search.telemetry.", "search.queue.", "search.admission.",
    "search.drain.", "index.staging.")


class Scope:
    NODE = "node"
    INDEX = "index"


class Setting:
    """A typed setting definition.

    parser: raw value -> typed value (raises IllegalArgumentException on bad
    input). validator: typed value -> None or raises.
    """

    def __init__(
        self,
        key: str,
        default: Any,
        parser: Callable[[Any], Any],
        scope: str = Scope.NODE,
        dynamic: bool = False,
        validator: Optional[Callable[[Any], None]] = None,
        deprecated: bool = False,
    ):
        self.key = key
        self.default = default
        self.parser = parser
        self.scope = scope
        self.dynamic = dynamic
        self.validator = validator
        self.deprecated = deprecated

    def get(self, settings: Settings) -> Any:
        raw = settings.get(self.key)
        if raw is None:
            value = self.default(settings) if callable(self.default) else self.default
        else:
            try:
                value = self.parser(raw)
            except IllegalArgumentException:
                raise
            except (TypeError, ValueError) as e:
                raise IllegalArgumentException(
                    f"Failed to parse value [{raw}] for setting [{self.key}]"
                ) from e
        if self.validator is not None and value is not None:
            self.validator(value)
        return value

    def exists(self, settings: Settings) -> bool:
        return self.key in settings

    # --- typed constructors, mirroring Setting.intSetting/boolSetting/... ---

    @staticmethod
    def int_setting(key, default, min_value=None, max_value=None, **kw) -> "Setting":
        def validate(v):
            if min_value is not None and v < min_value:
                raise IllegalArgumentException(
                    f"Failed to parse value [{v}] for setting [{key}] must be >= {min_value}"
                )
            if max_value is not None and v > max_value:
                raise IllegalArgumentException(
                    f"Failed to parse value [{v}] for setting [{key}] must be <= {max_value}"
                )

        return Setting(key, default, int, validator=validate, **kw)

    @staticmethod
    def bool_setting(key, default, **kw) -> "Setting":
        def parse(v):
            if isinstance(v, bool):
                return v
            s = str(v).lower()
            if s in ("true", "false"):
                return s == "true"
            raise IllegalArgumentException(
                f"Failed to parse value [{v}] as only [true] or [false] are allowed for "
                f"setting [{key}]"
            )

        return Setting(key, default, parse, **kw)

    @staticmethod
    def float_setting(key, default, min_value=None, **kw) -> "Setting":
        def validate(v):
            if min_value is not None and v < min_value:
                raise IllegalArgumentException(
                    f"Failed to parse value [{v}] for setting [{key}] must be >= {min_value}"
                )

        return Setting(key, default, float, validator=validate, **kw)

    @staticmethod
    def str_setting(key, default, choices=None, **kw) -> "Setting":
        def validate(v):
            if choices is not None and v not in choices:
                raise IllegalArgumentException(
                    f"unknown value [{v}] for setting [{key}], allowed: {sorted(choices)}"
                )

        return Setting(key, default, str, validator=validate, **kw)

    @staticmethod
    def time_setting(key, default, **kw) -> "Setting":
        return Setting(key, default, lambda v: parse_time_value(v, key), **kw)

    @staticmethod
    def bytes_setting(key, default, **kw) -> "Setting":
        return Setting(key, default, lambda v: parse_byte_size(v, key), **kw)

    @staticmethod
    def list_setting(key, default, **kw) -> "Setting":
        def parse(v):
            if isinstance(v, (list, tuple)):
                return list(v)
            return [p.strip() for p in str(v).split(",") if p.strip()]

        return Setting(key, default, parse, **kw)


class AbstractScopedSettings:
    """Registry of known settings for one scope + dynamic update dispatch.

    Role model: ``AbstractScopedSettings`` / ``ClusterSettings``
    (common/settings/ClusterSettings.java:416 is the master list).
    """

    def __init__(self, scope: str, registered: Iterable[Setting]):
        self.scope = scope
        self._settings: Dict[str, Setting] = {}
        self._listeners: list = []  # (setting, callback)
        for s in registered:
            self.register(s)

    def register(self, setting: Setting) -> None:
        if setting.scope != self.scope:
            raise IllegalArgumentException(
                f"setting [{setting.key}] has scope [{setting.scope}], expected "
                f"[{self.scope}]"
            )
        if setting.key in self._settings:
            raise IllegalArgumentException(f"setting [{setting.key}] already registered")
        self._settings[setting.key] = setting

    def get_setting(self, key: str) -> Optional[Setting]:
        return self._settings.get(key)

    def is_registered(self, key: str) -> bool:
        return key in self._settings or any(
            fnmatch.fnmatch(key, pat) for pat in self._settings if "*" in pat
        )

    def is_dynamic(self, key: str) -> bool:
        s = self._settings.get(key)
        return s is not None and s.dynamic

    def validate(self, settings: Settings, allow_unknown: bool = False) -> None:
        for key in settings.keys():
            if not self.is_registered(key):
                if not allow_unknown:
                    raise IllegalArgumentException(f"unknown setting [{key}]")
                continue
            s = self._settings.get(key)
            if s is not None:
                s.get(settings)  # parse+validate

    def validate_dynamic_update(self, settings: Settings) -> None:
        for key in settings.keys():
            s = self._settings.get(key)
            if s is None:
                raise IllegalArgumentException(f"unknown setting [{key}]")
            if not s.dynamic:
                raise IllegalArgumentException(
                    f"final or non-dynamic setting [{key}] cannot be updated"
                )
            s.get(settings)

    def add_settings_update_consumer(self, setting: Setting, consumer) -> None:
        if setting.key not in self._settings:
            raise IllegalArgumentException(f"setting [{setting.key}] not registered")
        self._listeners.append((setting, consumer))

    def apply_settings(self, old: Settings, new: Settings) -> None:
        """Fire update consumers for settings whose value changed.

        The raw string participates alongside the typed value: an
        EXPLICIT update to a value that happens to equal the setting's
        default (e.g. flipping a node-file-enabled boolean back off via
        PUT _cluster/settings) must still reach consumers — the typed
        comparison alone reads absent-and-default == explicit-default
        and would swallow it. Consumers are idempotent setters, so the
        extra fires are harmless."""
        for setting, consumer in self._listeners:
            before, after = setting.get(old), setting.get(new)
            if before != after or old.get(setting.key) != new.get(
                    setting.key):
                consumer(after)


# ---------------------------------------------------------------------------
# The registered node + index settings (growing list; ES has ~400).
# ---------------------------------------------------------------------------

CLUSTER_NAME = Setting.str_setting("cluster.name", "elasticsearch-tpu")
NODE_NAME = Setting.str_setting("node.name", "node-0")
NODE_DATA = Setting.bool_setting("node.data", True)
NODE_MASTER = Setting.bool_setting("node.master", True)
NODE_INGEST = Setting.bool_setting("node.ingest", True)
PATH_DATA = Setting.str_setting("path.data", "data")
PATH_REPO = Setting.list_setting("path.repo", [])
HTTP_PORT = Setting.int_setting("http.port", 9200, min_value=0, max_value=65535)
HTTP_HOST = Setting.str_setting("http.host", "127.0.0.1")
ACTION_AUTO_CREATE_INDEX = Setting.bool_setting(
    "action.auto_create_index", True, dynamic=True
)
ACTION_DESTRUCTIVE_REQUIRES_NAME = Setting.bool_setting(
    "action.destructive_requires_name", False, dynamic=True
)
SEARCH_DEFAULT_SIZE = Setting.int_setting("search.default_size", 10, min_value=0)
SEARCH_MAX_BUCKETS = Setting.int_setting(
    "search.max_buckets", 65536, min_value=1, dynamic=True
)
SEARCH_KEEPALIVE = Setting.time_setting(
    "search.default_keep_alive", "5m", dynamic=True
)
SEARCH_DEFAULT_TIMEOUT = Setting.time_setting(
    # query-phase deadline applied when a request carries no `timeout`
    # param (SearchService.DEFAULT_SEARCH_TIMEOUT_SETTING); None = no
    # timeout. Expired deadlines return accumulated hits with
    # timed_out: true — they do not error (The Tail at Scale degradation)
    "search.default_search_timeout", None, dynamic=True
)
SEARCH_ALLOW_PARTIAL_RESULTS = Setting.bool_setting(
    # TransportSearchAction.SHARD_COUNT... analog of
    # search.default_allow_partial_results: whether shard failures /
    # expired timeouts degrade to partial results (true) or fail the
    # request with search_phase_execution_exception (false); a request's
    # allow_partial_search_results param overrides
    "search.default_allow_partial_results", True, dynamic=True
)
BREAKER_TOTAL_LIMIT = Setting.str_setting(
    "indices.breaker.total.limit", "70%", dynamic=True
)
BREAKER_REQUEST_LIMIT = Setting.str_setting(
    "indices.breaker.request.limit", "60%", dynamic=True
)
BREAKER_FIELDDATA_LIMIT = Setting.str_setting(
    "indices.breaker.fielddata.limit", "60%", dynamic=True
)

# --- transport resilience (transport/local.py RetryPolicy/ConnectionHealth;
# wired through cluster/multinode.py — see docs/RESILIENCE.md) ---

TRANSPORT_REQUEST_TIMEOUT = Setting.time_setting(
    "transport.request.timeout", "30s", dynamic=True
)
TRANSPORT_RETRY_MAX_ATTEMPTS = Setting.int_setting(
    "transport.retry.max_attempts", 3, min_value=1, dynamic=True
)
TRANSPORT_RETRY_INITIAL_BACKOFF = Setting.time_setting(
    "transport.retry.initial_backoff", "50ms", dynamic=True
)
TRANSPORT_RETRY_BACKOFF_MULTIPLIER = Setting.float_setting(
    "transport.retry.backoff_multiplier", 2.0, min_value=1.0, dynamic=True
)
TRANSPORT_RETRY_MAX_BACKOFF = Setting.time_setting(
    "transport.retry.max_backoff", "2s", dynamic=True
)
TRANSPORT_HEALTH_FAILURE_THRESHOLD = Setting.int_setting(
    "transport.health.failure_threshold", 3, min_value=1, dynamic=True
)
TRANSPORT_HEALTH_QUARANTINE = Setting.time_setting(
    "transport.health.quarantine", "1s", dynamic=True
)
FD_PING_TIMEOUT = Setting.time_setting(
    # discovery.zen.fd.ping_timeout: the reference defaults to 30s over
    # real sockets; the in-process cluster detects an unresponsive node in
    # seconds so FD ticks stay cheap
    "discovery.zen.fd.ping_timeout", "5s", dynamic=True
)
FD_PING_RETRIES = Setting.int_setting(
    "discovery.zen.fd.ping_retries", 3, min_value=1, dynamic=True
)
PUBLISH_TIMEOUT = Setting.time_setting(
    "discovery.zen.publish_timeout", "30s", dynamic=True
)
REPLICATION_TIMEOUT = Setting.time_setting(
    # per-replica write fan-out deadline: a blackholed replica is failed
    # (and rerouted by the master) instead of blocking the primary
    "cluster.replication.timeout", "30s", dynamic=True
)
RECOVERY_RETRY_DELAY_NETWORK = Setting.time_setting(
    "indices.recovery.retry_delay_network", "500ms", dynamic=True
)
RECOVERY_MAX_RETRIES = Setting.int_setting(
    "indices.recovery.max_retries", 5, min_value=1, dynamic=True
)
RECOVERY_ACTION_TIMEOUT = Setting.time_setting(
    "indices.recovery.internal_action_timeout", "30s", dynamic=True
)


# --- cross-query micro-batching (search/batching.py; docs/BATCHING.md) ---

SEARCH_BATCH_ENABLED = Setting.bool_setting(
    # amortize one corpus-stream pass of the Pallas scoring plane across
    # concurrent compatible queries (mesh_pallas + host-pallas rungs);
    # false = every query executes unbatched
    "search.batch.enabled", True, dynamic=True
)
SEARCH_BATCH_WINDOW_MS = Setting.float_setting(
    # how long the first query of a concurrent burst waits for peers
    # before dispatching (milliseconds). Only paid under concurrency — a
    # lone query never waits.
    "search.batch.window_ms", 0.2, min_value=0.0, dynamic=True
)
SEARCH_BATCH_MAX_QUERIES = Setting.int_setting(
    # batch size bound (the kernel's q_batch): per-query VMEM
    # accumulators and the per-tile top-k loop grow linearly with this
    "search.batch.max_queries", 16, min_value=1, max_value=64, dynamic=True
)
SEARCH_BATCH_MAX_WINDOW_MS = Setting.float_setting(
    # upper bound of the ADAPTIVE batch window (docs/OVERLOAD.md): under
    # admission-queue pressure the effective window widens linearly from
    # search.batch.window_ms toward this bound, trading p50 for
    # throughput; observable via the batch_window_effective_ms gauge
    "search.batch.max_window_ms", 5.0, min_value=0.0, dynamic=True
)

# --- multi-tenant overload control (search/admission.py;
# docs/OVERLOAD.md) ---

SEARCH_QUEUE_SIZE = Setting.int_setting(
    # bounded search admission queue depth, consulted at IndexService
    # dispatch BEFORE any staging/launch work (the reference's search
    # threadpool queue_size); overflow rejects with HTTP 429
    # es_rejected_execution_exception + a drain-rate-derived Retry-After
    "search.queue.size", 1000, min_value=1, dynamic=True
)
SEARCH_ADMISSION_ENABLED = Setting.bool_setting(
    # the overload-control plane's kill switch: false admits everything
    # unconditionally (no queueing, no brownout, no rejection)
    "search.admission.enabled", True, dynamic=True
)
SEARCH_ADMISSION_MAX_CONCURRENT = Setting.int_setting(
    # in-flight search bound per index; 0 = auto (max(16, 3*cores/2+1),
    # mirroring the search threadpool sizing). Arrivals over the bound
    # queue and drain by weighted deficit-round-robin over tenants.
    "search.admission.max_concurrent", 0, min_value=0, dynamic=True
)
SEARCH_ADMISSION_WEIGHTS = Setting.str_setting(
    # per-tenant DRR weights, "tenantA:4,tenantB:1" (tenant = the
    # request's X-Opaque-Id; unlisted tenants weigh 1)
    "search.admission.weights", "", dynamic=True
)
SEARCH_ADMISSION_BROWNOUT_PRUNED = Setting.float_setting(
    # brownout step 1 threshold (queue pressure = queued/capacity):
    # force pruned/gte-totals eligibility before queueing deeper
    "search.admission.brownout.pruned_threshold", 0.25, min_value=0.0,
    dynamic=True
)
SEARCH_ADMISSION_BROWNOUT_RESCORE = Setting.float_setting(
    # brownout step 2 threshold: shed the rescore phase
    "search.admission.brownout.rescore_threshold", 0.5, min_value=0.0,
    dynamic=True
)
SEARCH_ADMISSION_BROWNOUT_FEATURES = Setting.float_setting(
    # brownout step 3 threshold: shed aggs/suggest (responses marked
    # _degraded); step 4 — rejection — is the queue-overflow 429
    "search.admission.brownout.features_threshold", 0.75, min_value=0.0,
    dynamic=True
)

# --- postings codec + block-max pruned scoring (docs/PRUNING.md) ---

SEARCH_PALLAS_POSTINGS_CODEC = Setting.str_setting(
    # node-wide default postings representation for the tile-scoring
    # kernel's HBM staging: "raw" = (docs i32, frac f32) pairs
    # (historical, bit-exact); "packed" = one bit-packed i32 word per
    # posting (half the staged bytes AND half the per-query posting DMA
    # traffic; frac quantized to 12 bits — see docs/PRUNING.md for the
    # parity trade-off). Static: the Node lays its file's value over each
    # index's Settings at creation and at recovery from disk;
    # index.search.pallas.postings_codec overrides per index.
    "search.pallas.postings_codec", "raw", choices={"raw", "packed"},
)


def _validate_probe_tiles(v):
    # probe counts are shape-bucketed into the compiled pruned program;
    # powers of two keep the variant count bounded.
    if v not in (2, 4, 8, 16, 32):
        raise IllegalArgumentException(
            f"Failed to parse value [{v}] for setting "
            f"[search.pallas.pruning.probe_tiles]: must be one of "
            f"2, 4, 8, 16, 32")


SEARCH_PALLAS_PRUNING_ENABLED = Setting.bool_setting(
    # block-max pruned top-k scoring on the mesh_pallas rung: skip tiles
    # whose summed per-(tile, term) upper-bound impact cannot beat the
    # running k-th score. Under pruning hit TOTALS become a documented
    # lower bound (WAND semantics) — default off; exact-total consumers
    # and dense-output queries (aggs, counts, sort) always run
    # exhaustively regardless.
    "search.pallas.pruning.enabled", False, dynamic=True
)
SEARCH_PALLAS_PRUNING_PROBE_TILES = Setting(
    # how many highest-bound tiles the probe pass scores unconditionally
    # to seed the pruning threshold (the block-size knob of the pruned
    # program; bigger = better threshold, less pruning headroom)
    "search.pallas.pruning.probe_tiles", 8, int,
    validator=_validate_probe_tiles, dynamic=True,
)

# --- dense-vector kNN retrieval on the MXU (docs/VECTOR.md) ---


def _validate_knn_tile_sub(v):
    # tile sublane counts the kNN kernel's geometry helper honors; the
    # doc space and the VMEM budget may still shrink the effective tile
    if v not in (8, 16, 32, 64, 128):
        raise IllegalArgumentException(
            f"Failed to parse value [{v}] for setting "
            f"[search.knn.tile_sub]: must be one of 8, 16, 32, 64, 128")


SEARCH_KNN_ENABLED = Setting.bool_setting(
    # serve eligible kNN queries from the mesh MXU program
    # (ops/pallas_knn.py); false = every vector query runs the host
    # plan-node rung (exact same scores, no MXU batching)
    "search.knn.enabled", True, dynamic=True
)
SEARCH_KNN_TILE_SUB = Setting(
    # doc-tile sublane count of the kNN kernel: W = tile_sub * 128 docs
    # per grid step. Bigger tiles amortize the fixed per-step dispatch
    # cost; the geometry helper shrinks the tile when the f32-converted
    # embedding block would overflow VMEM (high-dimensional fields)
    "search.knn.tile_sub", 64, int,
    validator=_validate_knn_tile_sub, dynamic=True,
)

# --- fused on-device aggregations (ISSUE 13, docs/AGGS.md) ---

SEARCH_AGGS_FUSED = Setting.bool_setting(
    # reduce eligible aggregation bodies INSIDE the mesh program (the
    # columnar doc-values plane) instead of shipping per-slot matched
    # masks to the host; false = every agg runs the host reduce.
    # Results are byte-identical either way (the engineered-exact
    # envelope in docs/AGGS.md gates eligibility structurally).
    "search.aggs.fused", True, dynamic=True
)

# --- device-memory accountant (ISSUE 9, docs/OBSERVABILITY.md) ---

SEARCH_MEMORY_HBM_BUDGET = Setting.bytes_setting(
    # HBM staging budget for the DeviceMemoryAccountant (0 = unlimited).
    # Over budget, a new staging first LRU-evicts the coldest staged
    # scopes (segment tables, mesh executors — both restage lazily),
    # then DEMOTES to the host rung with plane-ladder decision reason
    # hbm_budget: queries degrade, never 429/5xx. The accounting breaker
    # child mirrors the ledger, so the budget also shows as its limit.
    "search.memory.hbm_budget_bytes", "0b", dynamic=True
)

# --- device-staging retry (ISSUE 10, docs/RESILIENCE.md) ---

SEARCH_STAGING_RETRY_MAX_ATTEMPTS = Setting.int_setting(
    # total attempts for one device staging (HBM transfer group) whose
    # fault classified TRANSIENT (RESOURCE_EXHAUSTED / transfer error);
    # deterministic faults (shape/compile) never retry — they demote
    # the plane ladder immediately and quarantine with reason
    # staging_fault. 1 = no retries.
    "search.staging.retry.max_attempts", 3, min_value=1, max_value=10,
    dynamic=True
)
SEARCH_STAGING_RETRY_BACKOFF_MS = Setting.float_setting(
    # first-retry backoff in milliseconds; doubles per retry
    # (exponential). Keep small: staging sits on the query path — the
    # retry only exists to ride out momentary device pressure.
    "search.staging.retry.backoff_ms", 10.0, min_value=0.0, dynamic=True
)

# --- zero-downtime rollout: compile cache + graceful drain (ISSUE 14,
# docs/RESILIENCE.md "Rollout & drain") ---

SEARCH_COMPILE_CACHE_PATH = Setting.str_setting(
    # JAX persistent compilation cache directory: a restarted node
    # deserializes compiled mesh-program executables from disk instead
    # of paying the 2–27 s first-compile stall per variant. Empty =
    # disabled. Startup-only (the XLA cache must configure before the
    # first compile).
    "search.compile.cache_path", ""
)
SEARCH_COMPILE_WARM_ON_START = Setting.bool_setting(
    # replay the persisted program-variant lattice in the background
    # after node start / index recovery (compile_cache.VariantRegistry):
    # first compiles — persistent-cache deserializations included — are
    # absorbed OFF the query path (programs_warmed_total), so a warmed
    # rolling restart serves zero query-path first compiles
    "search.compile.warm_on_start", True
)
SEARCH_DRAIN_DEADLINE = Setting.time_setting(
    # graceful-drain deadline: a draining node stops admitting (clean
    # 503 + Retry-After, queued entries shed with the same contract)
    # and waits at most this long for in-flight searches before it
    # flushes (synced-flush marker) and shuts down; also the
    # Retry-After a drain rejection carries
    "search.drain.deadline", "30s", dynamic=True
)

# --- phase-attributed query telemetry (docs/OBSERVABILITY.md) ---

SEARCH_TELEMETRY_ENABLED = Setting.bool_setting(
    # the always-on phase tracer's kill switch: false stops per-query
    # span recording (profile/_stats phases/slowlog enrichment go
    # quiet); the tracer is bounded-overhead either way — this exists
    # for incident triage, not steady-state tuning
    "search.telemetry.enabled", True, dynamic=True
)

NODE_SETTINGS = [
    CLUSTER_NAME,
    NODE_NAME,
    NODE_DATA,
    NODE_MASTER,
    NODE_INGEST,
    PATH_DATA,
    PATH_REPO,
    HTTP_PORT,
    HTTP_HOST,
    ACTION_AUTO_CREATE_INDEX,
    ACTION_DESTRUCTIVE_REQUIRES_NAME,
    SEARCH_DEFAULT_SIZE,
    SEARCH_MAX_BUCKETS,
    SEARCH_KEEPALIVE,
    SEARCH_DEFAULT_TIMEOUT,
    SEARCH_ALLOW_PARTIAL_RESULTS,
    BREAKER_TOTAL_LIMIT,
    BREAKER_REQUEST_LIMIT,
    BREAKER_FIELDDATA_LIMIT,
    TRANSPORT_REQUEST_TIMEOUT,
    TRANSPORT_RETRY_MAX_ATTEMPTS,
    TRANSPORT_RETRY_INITIAL_BACKOFF,
    TRANSPORT_RETRY_BACKOFF_MULTIPLIER,
    TRANSPORT_RETRY_MAX_BACKOFF,
    TRANSPORT_HEALTH_FAILURE_THRESHOLD,
    TRANSPORT_HEALTH_QUARANTINE,
    FD_PING_TIMEOUT,
    FD_PING_RETRIES,
    PUBLISH_TIMEOUT,
    REPLICATION_TIMEOUT,
    RECOVERY_RETRY_DELAY_NETWORK,
    RECOVERY_MAX_RETRIES,
    RECOVERY_ACTION_TIMEOUT,
    SEARCH_BATCH_ENABLED,
    SEARCH_BATCH_WINDOW_MS,
    SEARCH_BATCH_MAX_QUERIES,
    SEARCH_BATCH_MAX_WINDOW_MS,
    SEARCH_QUEUE_SIZE,
    SEARCH_ADMISSION_ENABLED,
    SEARCH_ADMISSION_MAX_CONCURRENT,
    SEARCH_ADMISSION_WEIGHTS,
    SEARCH_ADMISSION_BROWNOUT_PRUNED,
    SEARCH_ADMISSION_BROWNOUT_RESCORE,
    SEARCH_ADMISSION_BROWNOUT_FEATURES,
    SEARCH_PALLAS_POSTINGS_CODEC,
    SEARCH_PALLAS_PRUNING_ENABLED,
    SEARCH_PALLAS_PRUNING_PROBE_TILES,
    SEARCH_KNN_ENABLED,
    SEARCH_KNN_TILE_SUB,
    SEARCH_AGGS_FUSED,
    SEARCH_MEMORY_HBM_BUDGET,
    SEARCH_STAGING_RETRY_MAX_ATTEMPTS,
    SEARCH_STAGING_RETRY_BACKOFF_MS,
    SEARCH_COMPILE_CACHE_PATH,
    SEARCH_COMPILE_WARM_ON_START,
    SEARCH_DRAIN_DEADLINE,
    SEARCH_TELEMETRY_ENABLED,
]

# --- index-scoped ---

# 6.x default: FIVE primary shards (IndexMetaData.SETTING_NUMBER_OF_SHARDS
# default; 7.0 changed it to 1) — conformance tests encode the 5-shard
# doc distribution
INDEX_NUMBER_OF_SHARDS = Setting.int_setting(
    "index.number_of_shards", 5, min_value=1, max_value=1024, scope=Scope.INDEX
)
INDEX_NUMBER_OF_REPLICAS = Setting.int_setting(
    "index.number_of_replicas", 1, min_value=0, scope=Scope.INDEX, dynamic=True
)
INDEX_REFRESH_INTERVAL = Setting.time_setting(
    "index.refresh_interval", "1s", scope=Scope.INDEX, dynamic=True
)
INDEX_MAX_RESULT_WINDOW = Setting.int_setting(
    "index.max_result_window", 10000, min_value=1, scope=Scope.INDEX, dynamic=True
)
INDEX_MAX_SLICES_PER_SCROLL = Setting.int_setting(
    "index.max_slices_per_scroll", 1024, min_value=1, scope=Scope.INDEX,
    dynamic=True
)
INDEX_BLOCK_SIZE = Setting.int_setting(
    # TPU-specific: posting block width (lane dimension); must stay a
    # multiple of 128 so blocks map onto VPU lanes.
    "index.tpu.posting_block_size",
    128,
    min_value=128,
    scope=Scope.INDEX,
)
INDEX_TRANSLOG_DURABILITY = Setting.str_setting(
    "index.translog.durability",
    "request",
    choices={"request", "async"},
    scope=Scope.INDEX,
    dynamic=True,
)
INDEX_TRANSLOG_FLUSH_THRESHOLD = Setting.bytes_setting(
    "index.translog.flush_threshold_size", "512mb", scope=Scope.INDEX, dynamic=True
)
INDEX_QUERY_DEFAULT_FIELD = Setting.str_setting(
    "index.query.default_field", "_all", scope=Scope.INDEX, dynamic=True
)
INDEX_MAPPING_TOTAL_FIELDS_LIMIT = Setting.int_setting(
    "index.mapping.total_fields.limit", 1000, min_value=1, scope=Scope.INDEX, dynamic=True
)
INDEX_MAPPING_DENSE_VECTOR_MAX_DIMS = Setting.int_setting(
    # upper bound on a dense_vector field's [dims] (validated at mapping
    # compile): staged embedding bytes grow linearly with dims, and the
    # kNN kernel's VMEM tile shrinks with them (docs/VECTOR.md)
    "index.mapping.dense_vector.max_dims", 1024, min_value=1,
    scope=Scope.INDEX,
)

# --- mesh data plane (parallel/plan_exec.py; docs/MESH.md) ---

INDEX_SEARCH_MESH = Setting.bool_setting(
    # serve eligible searches as one multi-device mesh program (true) or
    # always host-merge per shard (false)
    "index.search.mesh", True, scope=Scope.INDEX
)
INDEX_SEARCH_MESH_MAX_SLOTS = Setting.int_setting(
    # packing limit: how many segments may pack onto one device before
    # the index falls back to the host path (slots unroll in the device
    # program, so compile time and per-device work grow with this)
    "index.search.mesh.max_slots_per_device", 4, min_value=1, max_value=64,
    scope=Scope.INDEX
)
INDEX_SEARCH_MESH_PLANE = Setting.str_setting(
    # scoring-plane override inside the mesh program: auto = tile kernel
    # when stageable with scatter fallback; pallas = kernel or host
    # (never the scatter mesh); scatter = never build kernel plans
    "index.search.mesh.plane", "auto",
    choices={"auto", "pallas", "scatter"}, scope=Scope.INDEX
)
INDEX_SEARCH_PALLAS_POSTINGS_CODEC = Setting.str_setting(
    # per-index override of the kernel-plane postings representation
    # ("default" follows the node-wide search.pallas.postings_codec);
    # consulted when segments/mesh tables stage, so a change applies to
    # stagings performed AFTER it (docs/PRUNING.md)
    "index.search.pallas.postings_codec", "default",
    choices={"default", "raw", "packed"}, scope=Scope.INDEX
)
INDEX_SEARCH_AGGS_FUSED = Setting.str_setting(
    # per-index override of the fused on-device aggregation plane
    # ("default" follows the node-wide search.aggs.fused; an EXPLICIT
    # cluster-level search.aggs.fused still wins while set — the
    # put_cluster_settings explicitness contract, docs/AGGS.md)
    "index.search.aggs.fused", "default",
    choices={"default", "true", "false"}, scope=Scope.INDEX, dynamic=True
)
INDEX_SEARCH_PLANE_QUARANTINE_COOLDOWN = Setting.time_setting(
    # plane-health quarantine: after a mesh_pallas / mesh plane failure
    # (compile error, OOM, runtime fault) the plane is benched for this
    # index and queries serve from the next rung of the ladder; after
    # the cooldown one query probes the plane again
    "index.search.plane_quarantine.cooldown", "60s", scope=Scope.INDEX,
    dynamic=True
)
INDEX_STAGING_DELTA_ENABLED = Setting.bool_setting(
    # delta device staging (ISSUE 20, docs/MESH.md "Slot allocator &
    # generations"): refreshes that add segments within free slot
    # capacity append ONLY the new tables, deletes flip only live-mask
    # columns in place; false forces the pre-delta full-rebuild path
    # (the geometry-change fallback becomes the only path)
    "index.staging.delta.enabled", True, scope=Scope.INDEX, dynamic=True
)
INDEX_STAGING_COMPACT_THRESHOLD = Setting.float_setting(
    # background slot compaction trigger: when any staged slot's
    # tombstone density reaches this fraction (or free slots are
    # exhausted), a single-flight background pass merges sparse slots
    # into fresh ones and restages a compact generation; <= 0 disables
    "index.staging.compact.threshold", 0.25, scope=Scope.INDEX,
    dynamic=True
)
INDEX_SCRUB_INTERVAL = Setting.time_setting(
    # background store/device scrubber (ISSUE 16, docs/RESILIENCE.md
    # "Data integrity"): re-verify sealed-segment checksums and compare
    # a sampled digest of device-staged tables against host truth every
    # interval. Off by default (None/negative disables) — scrubbing
    # reads every committed byte, so operators opt in per index or via
    # the cluster-level override like every other dynamic knob
    "index.scrub.interval", None, scope=Scope.INDEX, dynamic=True
)
INDEX_SEARCH_SLOWLOG_WARN = Setting.time_setting(
    "index.search.slowlog.threshold.query.warn", None, scope=Scope.INDEX,
    dynamic=True
)
INDEX_SEARCH_SLOWLOG_INFO = Setting.time_setting(
    "index.search.slowlog.threshold.query.info", None, scope=Scope.INDEX,
    dynamic=True
)

INDEX_SETTINGS = [
    INDEX_SEARCH_MESH,
    INDEX_SEARCH_MESH_MAX_SLOTS,
    INDEX_SEARCH_MESH_PLANE,
    INDEX_SEARCH_PALLAS_POSTINGS_CODEC,
    INDEX_SEARCH_AGGS_FUSED,
    INDEX_SEARCH_PLANE_QUARANTINE_COOLDOWN,
    INDEX_STAGING_DELTA_ENABLED,
    INDEX_STAGING_COMPACT_THRESHOLD,
    INDEX_SCRUB_INTERVAL,
    INDEX_SEARCH_SLOWLOG_WARN,
    INDEX_SEARCH_SLOWLOG_INFO,
    INDEX_NUMBER_OF_SHARDS,
    INDEX_NUMBER_OF_REPLICAS,
    INDEX_REFRESH_INTERVAL,
    INDEX_MAX_RESULT_WINDOW,
    INDEX_MAX_SLICES_PER_SCROLL,
    INDEX_BLOCK_SIZE,
    INDEX_TRANSLOG_DURABILITY,
    INDEX_TRANSLOG_FLUSH_THRESHOLD,
    INDEX_QUERY_DEFAULT_FIELD,
    INDEX_MAPPING_TOTAL_FIELDS_LIMIT,
    INDEX_MAPPING_DENSE_VECTOR_MAX_DIMS,
]


def cluster_settings() -> AbstractScopedSettings:
    return AbstractScopedSettings(Scope.NODE, NODE_SETTINGS)


def index_scoped_settings() -> AbstractScopedSettings:
    return AbstractScopedSettings(Scope.INDEX, INDEX_SETTINGS)
