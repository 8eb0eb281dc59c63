"""Unified query telemetry: span tracer + process-wide metrics registry.

Reference roles: the OpenTelemetry Tracer the reference threads from
dispatch through exchange, QueryMonitor/QueryStatistics (the per-query
stats payload event listeners receive), and the JMX/airlift metrics beans
served here as Prometheus text at GET /v1/metrics.

  * `spans` — per-query span trees (query -> analyze -> optimize ->
    fragment -> schedule -> per-fragment SPMD launches), exportable as
    Chrome-trace/Perfetto JSON; zero-overhead NULL_TRACER when off.
  * `programs` — the launch door: `jit_program` names every device program
    and records/counts its launches.  (The host-pull door,
    `columnar.batch.host_pull`, is its counterpart for device->host reads.)
    Both record on every thread that works for a statement: its own, and
    its feed threads' lanes (`runtime/prefetch.Feed`).
  * `metrics` — counters/gauges/histograms registered once and bumped
    everywhere; the single home for the engine's formerly scattered
    counters (MeshProfile, trace cache, buffer pool).

The vocabularies, listed once (tests/test_telemetry.py holds the engine to
them; a new site takes a name from here or adds one here):

span names:
    query, queued, analyze, optimize, fragment, execute, build, result,
    schedule, fragment-N, transfer, launch, compile, host_pull, task, join,
    feed, feed_launch, feed_pull, feed_wait

feed spans (`runtime/prefetch.Feed`; a statement's work away from its own
thread):
    feed (a feed thread's lane, from its first item to its last, under the
    span innermost where the feed was made: the join's for a probe side,
    else build or execute; attribute thread = eager-prefetch (a join's
    probe side), scan-prefetch (a scan's split reader), local-exchange (one
    of a scan's parallel split readers)), feed_launch and feed_pull (the
    launch and host_pull doors on a lane: the attributes of a launch and of
    a host_pull; the statement's own thread keeps `launch` and
    `host_pull`), feed_wait (a consumer blocked on a feed's queue, on the
    consuming thread: attribute feed = the producer's thread kind, by =
    statement or the consuming lane's thread kind)

join span (one per join operator per statement, under `execute`; on the
mesh under its `fragment-N`, from both sides ready to the joined output):
    from the operator's first probe batch to its last output (local runner:
    `ops/join.py` JoinSpan; the build side's work lies before it, under
    `build`).  Attributes: kind (inner | left | full | semi | cross),
    strategy (local: the locate step the build chose, join_locate_table |
    join_locate_sorted | join_nested_expand, or partition_waves for a build
    over the memory budget, which has no build_rows and no counts (its
    operators are made wave by wave); mesh: broadcast |
    partitioned | colocated), locate (mesh: the locate its shards run,
    join_locate_table when the build keys' domain gets direct tables,
    else join_locate_sorted), keys (local: its equi-key columns, 0 for a
    cross join), build_rows and, for a hash join, what it
    reads to size its output anyway: probe_rows, out_rows (matches emitted,
    before a residual filter), null_keys (probe rows dropped for a NULL
    key, counted by the locate program; summed in
    `trino_tpu_join_null_keys_total`).  The launch and pull spans of the
    operator's own batches nest under it: `launch` and `host_pull` on the
    statement's thread, `feed_launch` and `feed_pull` where a join below
    another join runs, on the upper join's probe feed lane.

launch steps (`step=` of a `launch` span; the XLA module is `jit_<step>`):
    local — filter_project, unnest, sample, window, sort, sort_merge,
    compact, row_count, minmax_stats, agg_reduce, agg_range, agg_mark,
    agg_key_stats, agg_pallas_kernel, join_locate_sorted,
    join_locate_table, join_expand, join_expand_unique,
    join_nested_expand, join_semi_mark, join_semi_mark_residual;
    mesh (`cached_spmd_step` kinds; `_x` appended when the program holds a
    collective; `chain_` followed by the kinds a deferred chain fused) —
    chain, scan_pred, dyn_filter, filter, project, agg_partial,
    agg_colocated, recode,
    mark_distinct, window, sort, topn, limit, dynfilters, dyn_counts,
    gather_compact, broadcast_compact, state_compact,
    licensed_probe_compact, licensed_compact, agg_wave_filter,
    licensed_expand, fused_expand, locate, expand, semi_mark, unnest,
    exchange_counts, fused_exchange, agg_final, agg_single, broadcast

launch paths (`path=` of a `launch` span, `+`-joined; each also a label of
`trino_tpu_aggregation_path_total`):
    pallas, onehot, segmented, positional, sort (the grouped-aggregation
    formulation the step chose), dense, scatter (how its segment
    reductions lowered: masked reductions over few segments, or
    `jax.ops.segment_*` over many — `ops/common.segment_reduce`),
    runs, sorted_runs (an `agg_range` step over more than 2 048 slots
    reduced over the runs of its group code, never a scatter: the rows
    arrived in code order, or one 32-bit sort put them there first —
    `ops/common.Runs`, chosen by `_positional_try` from the order
    `agg_key_stats` observed),
    compact_sort (the program packs live rows to the front and found
    each output slot's source row by a one-key sort in blocks, never a
    scatter — `columnar/batch.slot_sources`; on `compact`, every mesh
    `*_compact` / `fused_expand` step that packs rows, and every
    `fused_exchange*`, whose send buffer is one such compaction a
    destination: `parallel/exchange.bucketize`; its `exchange_counts`
    reads `dense`)

launch div128_rows (`div128_rows=` of a `launch` span): the rows the
    program's last trace divided by a long decimal or into a long result
    (`types/int128.divround128`, noted by `programs.note_div128`): its
    batch's static capacity, not the live rows; absent where it holds no
    such division

host_pull why (`why=` of a `host_pull` span: what the host needed it for):
    result (rows for the client), capacity (a count that sizes the next
    program's static shape), overflow_flag (a speculative capacity's
    check), group_stats (key ranges that choose an aggregation or join
    layout), dynamic_filter (build-side key sets, ranges and pruning counts),
    build_to_host, probe_to_host (join sides leaving the device for
    partition waves), spill (operator state to the spill tier),
    sort_compact (a sort run leaving the device), dictionary (codes read
    for host-side string work), host_operator (an operator that runs on
    the host: pattern matching), stage_output (a mesh stage's output to
    the coordinator or the spool), remote_page (a worker task's pages)
"""

from trino_tpu.telemetry.metrics import (
    REGISTRY,
    CallbackGauge,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from trino_tpu.telemetry.spans import NULL_TRACER, NullTracer, Span, SpanTracer, now

__all__ = [
    "REGISTRY",
    "CallbackGauge",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "Span",
    "SpanTracer",
    "now",
]
