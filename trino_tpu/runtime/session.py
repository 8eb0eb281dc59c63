"""Session properties (reference: Session.java + SystemSessionProperties.java
— the ~200-knob session-level configuration surface, reduced to the knobs
this engine actually reads).  SET SESSION mutates these per connection."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any


@dataclass
class PropertyMetadata:
    name: str
    description: str
    type: type
    default: Any


SESSION_PROPERTIES: dict[str, PropertyMetadata] = {
    p.name: p
    for p in [
        PropertyMetadata(
            "target_splits", "connector splits per table scan", int, 4
        ),
        PropertyMetadata(
            "page_rows", "max rows per scan page (device batch size)", int, 1 << 20
        ),
        PropertyMetadata(
            "broadcast_join_rows",
            "build sides estimated at or below this are broadcast",
            int,
            50_000,
        ),
        PropertyMetadata(
            "join_distribution_type",
            "AUTOMATIC | BROADCAST | PARTITIONED",
            str,
            "AUTOMATIC",
        ),
        PropertyMetadata(
            "agg_fold_batches",
            "partial-aggregation states folded after this many batches",
            int,
            8,
        ),
        PropertyMetadata(
            "query_max_memory_bytes",
            "per-query device memory budget (0 = unlimited)",
            int,
            0,
        ),
        PropertyMetadata(
            "query_max_memory",
            "per-query device memory budget in bytes (0 = unlimited; the "
            "reservation ceiling blocking operators check before "
            "materializing — exceeding it degrades to partition-wave "
            "execution with filesystem-SPI spill instead of failing; "
            "reference: SystemSessionProperties QUERY_MAX_MEMORY)",
            int,
            0,
        ),
        PropertyMetadata(
            "spill_enabled",
            "spill non-resident partition-wave data host-side through the "
            "filesystem SPI (false = waves stage in host RAM only; "
            "reference: SystemSessionProperties SPILL_ENABLED)",
            bool,
            True,
        ),
        PropertyMetadata(
            "memory_wave_partitions",
            "override the partition-wave fan-out k under memory pressure "
            "(0 = auto: next_pow2(need / budget))",
            int,
            0,
        ),
        PropertyMetadata(
            "query_max_run_time",
            "wall-clock deadline for a whole statement in seconds; the "
            "query aborts with EXCEEDED_TIME_LIMIT at its next cooperative "
            "check (0 = unbounded; reference: QueryTracker.enforceTimeLimits)",
            float,
            0.0,
        ),
        PropertyMetadata(
            "query_max_planning_time",
            "wall-clock deadline for analysis + optimization in seconds "
            "(0 = unbounded)",
            float,
            0.0,
        ),
        PropertyMetadata(
            "query_max_queued_time",
            "wall-clock bound on admission-queue wait in seconds; a query "
            "still queued past it fails with EXCEEDED_QUEUED_TIME_LIMIT "
            "without ever occupying an engine lane (0 = unbounded; "
            "reference: QueryTracker's queued-time sweep)",
            float,
            0.0,
        ),
        PropertyMetadata(
            "retry_policy",
            "NONE | QUERY (re-execute the query) | TASK (per-stage retry "
            "with spooled intermediates)",
            str,
            "NONE",
        ),
        PropertyMetadata(
            "fault_tolerant_execution",
            "spool completed fragment outputs through the filesystem SPI "
            "keyed by (query_id, fragment_id, attempt_id) so a mid-query "
            "worker death resumes from spooled intermediates: only "
            "fragments whose outputs are lost re-run, duplicate attempt "
            "outputs are deduplicated at the consumer (reference: "
            "RetryPolicy.TASK + DeduplicatingDirectExchangeBuffer; false "
            "= today's behavior, retry_policy alone decides)",
            bool,
            False,
        ),
        PropertyMetadata(
            "scan_cache",
            "serve immutable splits from the host/device buffer pool",
            bool,
            True,
        ),
        PropertyMetadata(
            "scan_prefetch_depth",
            "scan batches decoded+transferred ahead of compute (0 = off)",
            int,
            2,
        ),
        PropertyMetadata(
            "profile_dir",
            "write an XLA/jax profiler trace of each query to this "
            "directory (device kernel times; '' = off)",
            str,
            "",
        ),
        PropertyMetadata(
            "task_concurrency",
            "parallel split readers per table scan (local exchange width; "
            "reference: SystemSessionProperties TASK_CONCURRENCY)",
            int,
            4,
        ),
        PropertyMetadata(
            "writer_count",
            "parallel page-building writer threads for INSERT/CTAS "
            "(reference: scaled writers / task_writer_count)",
            int,
            4,
        ),
        PropertyMetadata(
            "verify_plan",
            "plan sanity-checker enforcement: strict (raise PlanViolation) "
            "| warn | off | default (strict under pytest, warn elsewhere)",
            str,
            "default",
        ),
        PropertyMetadata(
            "colocated_join",
            "use table layouts / derived partitioning to elide exchanges "
            "(co-partitioned joins, single-stage aggregations)",
            bool,
            True,
        ),
        PropertyMetadata(
            "join_speculative_capacity",
            "speculative join output capacity: on | off | <initial pow2 "
            "cap override> (off = block on the match-count host sync)",
            str,
            "on",
        ),
        PropertyMetadata(
            "join_capacity_license",
            "honor capacity certificates (verify.capacity): proven joins "
            "compile at the certified fixed capacity with zero runtime "
            "sizing (false = always run the speculative/sizing path)",
            bool,
            True,
        ),
        PropertyMetadata(
            "table_layouts",
            "declared hash-bucketed layouts for generated tables: "
            "'catalog.schema.table:col1+col2:bucket_count', comma-separated",
            str,
            "",
        ),
        PropertyMetadata(
            "global_dictionaries",
            "let plans lean on the global dictionary service "
            "(runtime/dictionary_service): varchar join/group keys whose "
            "two sides share one versioned mesh-wide code assignment "
            "co-locate and elide exchanges like integer keys (false = "
            "producer-local codes only; always sound, just more exchanges)",
            bool,
            True,
        ),
        PropertyMetadata(
            "query_trace",
            "per-query span tracing from admission through SPMD launches "
            "(runner.last_trace / EXPLAIN ANALYZE VERBOSE / "
            "GET /v1/query/{id}/trace; false = zero-overhead off)",
            bool,
            True,
        ),
        PropertyMetadata(
            "decision_regret_ratio",
            "hindsight threshold for the plan-decision ledger "
            "(telemetry/decisions): a decision is stamped 'regret' when "
            "its measured cost exceeds this multiple of the estimated "
            "cost of the alternative it rejected",
            float,
            2.0,
        ),
        PropertyMetadata(
            "decision_regret_min_bytes",
            "byte floor below which the decision ledger never flags "
            "regret (tiny broadcasts are noise, not mistakes)",
            int,
            1 << 20,
        ),
        PropertyMetadata(
            "pallas_agg",
            "use the Pallas MXU one-hot-matmul kernel for eligible "
            "small-domain float aggregations",
            bool,
            False,
        ),
    ]
}


class SessionProperties:
    def __init__(self):
        self._values: dict[str, Any] = {}

    def get(self, name: str):
        meta = SESSION_PROPERTIES.get(name)
        if meta is None:
            raise KeyError(f"unknown session property: {name}")
        return self._values.get(name, meta.default)

    def set(self, name: str, value) -> None:
        meta = SESSION_PROPERTIES.get(name)
        if meta is None:
            raise KeyError(f"unknown session property: {name}")
        try:
            self._values[name] = meta.type(value)
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad value for {name}: {value!r}") from e

    def items(self):
        for name, meta in SESSION_PROPERTIES.items():
            yield name, self._values.get(name, meta.default), meta


#: the executing statement's identity, set by the runner around dispatch
#: (reference: Session.getUser() — threaded as a contextvar because the
#: expression analyzer has no session handle)
import contextvars

CURRENT_USER: "contextvars.ContextVar[str]" = contextvars.ContextVar(
    "trino_tpu_current_user", default="user"
)
