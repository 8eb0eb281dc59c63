"""Distributed query runner: fragmented, stage-based SPMD execution.

Reference roles: SqlQueryExecution.planDistribution (plan → SubPlan via
PlanFragmenter) + PipelinedQueryScheduler.start (stage orchestration,
execution/scheduler/PipelinedQueryScheduler.java:249) + AddExchanges'
distribution choices.  The plan is first rewritten with explicit
ExchangeNodes (planner/fragmenter.add_exchanges), cut into PlanFragments
with partitioning handles (SystemPartitioningHandle.java:41-57 analog), and
executed bottom-up: fragment bodies are SPMD programs over the worker mesh,
exchange edges lower to ICI collectives (hash bucketize + all_to_all,
broadcast = all_gather) or an explicit gather/merge to the coordinator —
EXPLAIN (explain_distributed) shows every fragment and its distribution, and
there is no silent per-node fallback: a node without a distributed
implementation forces an explicit SINGLE fragment at plan time.

Stage value forms: a distributed stage yields a `_Dist` (stacked [W, cap]
device batch, sharded over the mesh); a SINGLE/COORDINATOR_ONLY stage yields
materialized host batches via the local engine.

Device-resident fragment pipeline (the mesh fast path):

  * Unary operators (filter/project/window/sort/limit/...) DEFER their
    per-worker step onto the `_Dist` instead of dispatching immediately;
    a chain compiles as ONE SPMD program at the next materialization
    boundary (exchange, join, gather) — no intermediate columns ever hit
    HBM between them, and nothing returns to the host.
  * Every compiled program is held in spmd.TRACE_CACHE keyed on (step
    semantics, pow2 shape bucket, mesh), so repeated executions of the same
    query — and repeated same-bucket batches — reuse traces instead of
    retracing and recompiling per run (the dominant cost of the old path).
  * Scans cache their stacked [W, cap] device batch in the buffer pool's
    device tier keyed by (splits, columns, scan version, mesh): a warm mesh
    query performs ZERO host->device transfers for table data.
  * The bucketize + all_to_all exchange FUSES into the consumer's first
    jitted step (exchange.fused_repartition), so a repartition and the
    final aggregation above it run as one compiled collective program.
  * Small collectives batch: all dynamic-filter summaries of a join build
    side reduce in one program and cross to the host in one transfer.

Observability: a per-fragment, per-phase MeshProfile (trace/compile,
collective, compute, transfer, other) with byte counters — rendered by
EXPLAIN ANALYZE, exposed as runner.last_mesh_profile, and recorded in the
bench JSON so mesh regressions are visible per fragment.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import concat_batches, host_pull
from trino_tpu.connectors.api import CatalogManager
from trino_tpu.expr import ExprCompiler
from trino_tpu.expr.ir import InputRef, and_
from trino_tpu.ops.aggregation import AggregationOperator, AggSpec
from trino_tpu.ops.common import SortKey, next_pow2
from trino_tpu.ops.filter_project import FilterProjectOperator
from trino_tpu.ops.join import (
    HashJoinOperator,
    SemiJoinOperator,
    _canon_probe_device,
    _locate_sorted,
    _sort_build_device,
)
from trino_tpu.ops.sort import OrderByOperator, TopNOperator
from trino_tpu.parallel import exchange as ex
from trino_tpu.parallel.spmd import (
    TRACE_CACHE,
    WorkerMesh,
    bucket_cap,
    cached_spmd_step,
    mesh_key,
    stack_batches,
    unstack_batch,
)
from trino_tpu.partitioning import (
    CAP_HISTORY,
    LayoutResolver,
    bucket_rows,
    initial_cap,
    join_output_placements,
    next_cap,
    scan_partitioning,
    speculation_mode,
)
from trino_tpu.planner import plan as P
from trino_tpu.planner.fragmenter import (
    COORDINATOR_ONLY,
    FIXED_ARBITRARY,
    FIXED_HASH,
    SINGLE,
    SOURCE,
    RemoteSourceNode,
    SubPlan,
    add_exchanges,
    create_subplans,
    fragment_text,
)
from trino_tpu.runtime.lifecycle import check_current
from trino_tpu.runtime.local_planner import (
    LocalExecutionPlanner,
    PhysicalPlan,
    defer_integer_averages,
    divide_deferred,
)
from trino_tpu.runtime.memory import batch_bytes
from trino_tpu.runtime.query_stats import MeshProfile
from trino_tpu.telemetry import now
from trino_tpu.telemetry.compile_events import OBSERVATORY
from trino_tpu.telemetry.decisions import (
    decision_scope,
    observe_decision,
    record_decision,
)
from trino_tpu.telemetry.metrics import (
    collective_async_counter,
    join_capacity_counter,
)
from trino_tpu.runtime.runner import LocalQueryRunner, MaterializedResult
from trino_tpu.planner.functions import HOLISTIC_AGGS, PARTITIONABLE_HOLISTIC

_DIST_KINDS = (SOURCE, FIXED_HASH, FIXED_ARBITRARY)

#: capacity-economy decline threshold: a licensed join whose certified
#: expand capacity exceeds the CapacityHistory-learned tight bucket by
#: more than this factor falls back to the runtime sizing path — the
#: certified width would compile the whole downstream chain that much
#: wider than the data needs, and with licensed-output compaction a
#: license within the factor recovers the width for free.  64 keeps the
#: measured licensed workloads (Q3's 2^20 certified vs 2^15 learned)
#: on the proof path while cutting off pathological certificates.
_LICENSE_WIDTH_FACTOR = 64


class _Dist:
    """A distributed intermediate: stacked [W, cap] batch + symbol layout.

    `pending` holds deferred per-worker steps [(key_part, fn, producer_fid)]
    appended by unary operators; accessing `.stacked` materializes them as
    ONE cached SPMD program (the device-resident fragment pipeline).  Each
    entry records the fragment that PRODUCED the step so the profile charges
    the eventual materialization to the producer, not to whichever consumer
    happens to trigger it.  `cap` tracks the trailing row capacity through
    deferred shape-changing steps so consumers can size their static output
    shapes without materializing.  `placements` carries the partitioning
    property (ordered symbol-name tuples the rows are exchange-hash-placed
    on) so downstream repartitions on already-placed data become no-ops.
    `realigned` records that rows were MOVED off the connector's default
    split alignment (bucketized scan, any exchange, a host re-stack) — the
    residual semi join's historical per-shard contract assumes default
    alignment, so a realigned side without an exact-key placement must be
    hash-repartitioned before per-shard marking."""

    def __init__(self, stacked: Batch, symbols: list, ex=None, pending=(),
                 cap: Optional[int] = None, placements: tuple = (),
                 realigned: bool = False):
        self._stacked = stacked
        self.symbols = list(symbols)
        self.ex = ex
        self.pending = list(pending)
        self.cap = cap if cap is not None else _trailing_cap(stacked)
        self.placements = tuple(placements)
        self.realigned = realigned

    @property
    def stacked(self) -> Batch:
        if self.pending:
            self._stacked = self.ex._run_chain(self._stacked, self.pending)
            self.pending = []
        return self._stacked

    def defer(self, key_part, step, symbols=None, cap: Optional[int] = None,
              placements: Optional[tuple] = None) -> "_Dist":
        """Append a per-worker step lazily (must be a pure Batch -> Batch
        function; `key_part` must fingerprint its semantics).  Placements
        survive symbol-preserving steps; a step that renames its output
        symbols must pass the remapped `placements` explicitly (default:
        dropped — claiming a stale placement is a correctness bug)."""
        fid = self.ex._current_fid if self.ex is not None else -1
        if placements is None:
            placements = self.placements if symbols is None else ()
        return _Dist(
            self._stacked,
            self.symbols if symbols is None else symbols,
            self.ex,
            self.pending + [(key_part, step, fid)],
            cap if cap is not None else self.cap,
            placements,
            self.realigned,
        )

    def channel(self, name: str) -> int:
        for i, s in enumerate(self.symbols):
            if s.name == name:
                return i
        raise KeyError(name)

    def rewrite(self, expr):
        return PhysicalPlan(iter(()), self.symbols).rewrite(expr)


def _sig(symbols) -> tuple:
    """Channel-layout signature for trace-cache keys (types only: steps are
    positional, names don't reach the compiled program)."""
    return tuple(s.type.name for s in symbols)


def _spec_sig(specs) -> tuple:
    """Full AggSpec fingerprint for trace-cache keys — the param matters:
    min_by(x, k) and min_by(x, k, 3) compile different programs."""
    return tuple(
        (s.name, s.arg, s.out_type.name, repr(s.param), s.arg2)
        for s in specs
    )


class DistributedQueryRunner(LocalQueryRunner):
    def __init__(
        self,
        catalogs: Optional[CatalogManager] = None,
        catalog: str = "tpch",
        schema: str = "tiny",
        n_workers: Optional[int] = None,
        devices=None,
    ):
        from trino_tpu.runtime.membership import HeartbeatFailureDetector

        super().__init__(catalogs, catalog=catalog, schema=schema)
        #: device pool resize_mesh slices from (None = jax.devices())
        self._devices = devices
        self.wm = WorkerMesh(devices, n_workers)
        #: coordinator-side worker liveness (HeartbeatFailureDetector.java:78);
        #: in-process mesh workers share our liveness, so they are refreshed
        #: at query start — server-mode remote workers heartbeat over HTTP
        self.failure_detector = HeartbeatFailureDetector()
        for i in range(self.wm.n):
            self.failure_detector.register(f"worker-{i}")
        #: MeshProfile of the most recent distributed query (bench evidence)
        self.last_mesh_profile = None

    # -- mesh growth (grow = new mesh signature = fresh compile-key set) -------

    def resize_mesh(self, n_workers: int) -> None:
        """Re-shape the device mesh for subsequent queries.  A changed W is
        a NEW mesh signature: every trace-cache key re-traces and the old
        signature's device-resident scan entries are dead weight — they are
        dropped here, and the attached prewarm executor (runner.prewarm,
        runtime/prewarm) replays the workload manifest at the new signature
        in the background so the next query arrives warm instead of paying
        the whole compile wall.

        Deliberately NOT named `add_worker`: that name is the coordinator
        register endpoint's protocol (`add_worker(url)` on the multihost
        runner) — an int-growing method under the same name would crash
        `PUT /v1/worker/register` against an in-process runner, which must
        keep answering 400.  Call between queries — resizing does not
        serialize with an execution in flight (a server's engine lock
        already provides that when queries go through it)."""
        from trino_tpu.parallel.spmd import mesh_key
        from trino_tpu.runtime.membership import invalidate_mesh_scans
        from trino_tpu.runtime.prewarm import kick_grow_prewarm

        import jax as _jax

        available = list(
            self._devices if self._devices is not None else _jax.devices()
        )
        if not 1 <= n_workers <= len(available):
            raise ValueError(
                f"mesh size {n_workers} out of range (1..{len(available)} "
                "devices available)"
            )
        if n_workers == self.wm.n:
            return
        old_sig = mesh_key(self.wm)
        old_n = self.wm.n
        self.wm = WorkerMesh(self._devices, n_workers)
        for i in range(self.wm.n):
            self.failure_detector.register(f"worker-{i}")
        # a SHRINK must forget the dropped workers: a stale detector entry
        # would time out and fail every later query's liveness check
        for i in range(self.wm.n, old_n):
            self.failure_detector.unregister(f"worker-{i}")
        invalidate_mesh_scans(old_sig)
        kick_grow_prewarm(self)

    # -- planning -------------------------------------------------------------

    def create_subplan(self, plan: P.OutputNode) -> SubPlan:
        from trino_tpu.verify.capacity import seal_licenses
        from trino_tpu.verify.collectives import collective_signature
        from trino_tpu.verify.schedule import license_schedule

        dplan = add_exchanges(
            plan, self.catalogs, self.properties, n_workers=self.wm.n
        )
        sub = create_subplans(
            dplan,
            properties=self.properties,
            catalogs=self.catalogs,
            n_workers=self.wm.n,
        )
        # seal every capacity certificate for THIS mesh width: the stage
        # executor honors a license only when the seal matches the mesh it
        # is executing on, so a subplan replayed against a shrunk/grown
        # mesh falls back to the runtime sizing path (never a stale cap)
        for frag in sub.all_fragments():
            seal_licenses(frag.root, self.wm.n)
        # the statically enumerated per-fragment collective sequence of the
        # MOST RECENT subplan: verify.device_residency holds warm replays
        # to it (a warm run must issue exactly the recorded collectives)
        self.last_collective_signature = collective_signature(sub)
        # collective-schedule license: divergence-free fragments authorize
        # eager pre-dispatch of independent build-side child fragments
        # (verify/schedule.py); device_residency verifies warm replays
        # against the licensed schedule
        self.last_schedule_license = license_schedule(sub, self.wm.n)
        lic = self.last_schedule_license
        n_async = (
            sum(len(v) for v in lic.async_children.values())
            if lic is not None
            else 0
        )
        record_decision(
            "schedule_license", "planner.create_subplan",
            "async" if n_async else "sync",
            "sync" if n_async else "async",
            {"async_children": n_async},
        )
        return sub

    def explain_distributed(self, sql: str) -> str:
        return fragment_text(self.create_subplan(self.create_plan(sql)))

    # -- execution (all statements inherit LocalQueryRunner.execute dispatch;
    # queries run through the stage executor) ---------------------------------

    def _run_query(self, query, stats=None) -> MaterializedResult:
        # in-process mesh workers share this process's liveness: refresh them
        # BEFORE the dead check, so only genuinely remote/stale registrations
        # (server-mode workers) can fail it
        for i in range(self.wm.n):
            self.failure_detector.heartbeat(f"worker-{i}")
        dead = self.failure_detector.failed_workers()
        if dead:
            raise RuntimeError(f"workers failed heartbeat: {sorted(dead)}")
        tr = self._tracer
        plan = self.plan_query(query)
        # as on the local runner: an avg(integer) that only moves to the
        # client is planned as sum and count, and the host divides
        split, counts = defer_integer_averages(plan)
        with tr.span("fragment"):
            sub = self.create_subplan(split)
        # EXPLAIN ANALYZE runs the SAME distributed path, with the profile
        # in blocking mode so per-phase times measure device work
        profile = MeshProfile(blocking=stats is not None, tracer=tr)
        from trino_tpu.runtime.lifecycle import current_query

        ctx = current_query()
        executor = StageExecutor(
            self.catalogs, self.wm, self.properties,
            # the statement's own id (lane-safe), not the shared runner
            # attribute another lane may have overwritten
            query_id=(
                ctx.query_id if ctx is not None
                else getattr(self, "_current_qid", "q")
            ),
            profile=profile,
            schedule=getattr(self, "last_schedule_license", None),
        )
        #: kept for tests / EXPLAIN evidence (dynamic filter pruning counts)
        self.last_stage_executor = executor
        self.last_mesh_profile = profile
        with tr.span("schedule"):
            host = executor.run(sub)
            rows = []
            with tr.span("result"):
                for batch in host.stream:
                    check_current()  # cancel/deadline between batches
                    rows.extend(tuple(r) for r in batch.to_pylist())
            rows = divide_deferred(rows, counts)
        if stats is not None:
            stats.mesh_profile = profile
        return MaterializedResult(
            list(plan.column_names), rows, [s.type for s in plan.symbols]
        )


class StageExecutor:
    """Executes a SubPlan tree bottom-up (reference role: StageManager +
    SqlStage inside PipelinedQueryScheduler, with collectives as the data
    plane instead of HTTP output buffers)."""

    #: attempts per stage under retry_policy=TASK (reference:
    #: EventDrivenFaultTolerantQueryScheduler task retry budget)
    TASK_ATTEMPTS = 4

    def __init__(self, catalogs, wm: WorkerMesh, properties, query_id: str = "q",
                 profile: Optional[MeshProfile] = None, schedule=None):
        self.catalogs = catalogs
        self.wm = wm
        self.properties = properties
        self.query_id = query_id
        self.profile = profile if profile is not None else MeshProfile()
        #: collective-schedule license (verify/schedule.py): authorizes
        #: eager pre-dispatch of independent build-side child fragments;
        #: None = strictly lazy, order-conservative dispatch
        self.schedule = (
            schedule
            if schedule is not None and schedule.mesh_w == wm.n
            else None
        )
        self._subplans: dict[int, SubPlan] = {}
        self._results: dict[int, object] = {}
        self._root_fid: Optional[int] = None
        self._current_fid: int = -1
        #: per-stage elapsed bookkeeping so fragment walls are SELF time
        self._frame_stack: list[dict] = []
        self._trace_base = (TRACE_CACHE.hits, TRACE_CACHE.misses, TRACE_CACHE.retraces)
        try:
            self.fte = bool(properties.get("fault_tolerant_execution"))
        except KeyError:  # pragma: no cover - older property sets
            self.fte = False
        # fault_tolerant_execution implies the TASK machinery: stage
        # outputs spool, stages retry individually, consumers dedup
        self.retry_task = (
            properties.get("retry_policy") == "TASK" or self.fte
        )
        self.spool = None
        self._spool_meta: dict[int, tuple] = {}
        #: duplicate spooled attempts discarded by consumer-side dedup
        self.dedup_discards = 0
        #: cross-fragment dynamic filters (reference:
        #: server/DynamicFilterService.java:107): probe symbol name ->
        #: (lo, hi) build-side key range, registered when a build fragment
        #: completes, consumed by later probe-side scan fragments
        self.dynamic_filters: dict[str, tuple] = {}
        #: EXPLAIN-able evidence: table -> (rows_before, rows_after) pruning
        self.dynamic_filter_stats: dict[str, tuple] = {}
        #: partitioning-aware execution (table layouts + elision + the
        #: speculative join capacity), all gated by session properties so
        #: regressions bisect by flipping the new paths off
        self.layouts = LayoutResolver(catalogs, properties)
        try:
            self.colocate = bool(properties.get("colocated_join"))
        except KeyError:  # pragma: no cover - older property sets
            self.colocate = True
        try:
            self.license_caps = bool(properties.get("join_capacity_license"))
        except KeyError:  # pragma: no cover - older property sets
            self.license_caps = True
        if self.retry_task:
            from trino_tpu.runtime.fte import SpoolManager

            self.spool = SpoolManager()
        # per-query device budget tree for the MESH path: blocking
        # operators (join builds, the fused-exchange aggregation output)
        # reserve BEFORE materializing; an over-budget reservation degrades
        # to partition waves (runtime/spill) instead of dying.  Lives on
        # the shared process pool when a query is executing, where the
        # revoke tier and the low-memory killer can see it.
        from trino_tpu.runtime.lifecycle import query_memory_context
        from trino_tpu.runtime.spill import session_budget

        self.memory = query_memory_context(session_budget(properties))

    def _budget(self) -> int:
        """Effective device budget (0 = unconstrained), re-read at each
        reservation so a pool limit shrunk mid-query takes effect."""
        from trino_tpu.runtime.spill import effective_budget

        return effective_budget(self.properties, self.memory)

    # -- instrumented step dispatch -------------------------------------------

    def _dist(self, stacked: Batch, symbols: list, placements: tuple = (),
              realigned: bool = False) -> _Dist:
        return _Dist(
            stacked, symbols, ex=self, placements=placements,
            realigned=realigned,
        )

    def _host_pull(self, *vals, why: str = "capacity"):
        """The runner's tiny device->host reads (speculative overflow
        flags, capacity syncs): every value crosses in ONE transfer."""
        out = host_pull(tuple(vals), why)
        return out if len(out) > 1 else out[0]

    def _call(self, fn, *args, phase: str = "compute", fid: Optional[int] = None):
        """Run a (cached-jitted) program with phase attribution: calls that
        trigger a trace are booked as `trace` (trace + XLA compile time);
        blocking mode additionally waits on the result inside the window so
        the phase measures device time.  `fid` overrides the charged
        fragment (deferred chains bill their producer, not the consumer
        that materializes them)."""
        check_current()  # cooperative cancel/deadline point per SPMD launch
        prof = self.profile
        owner = self._current_fid if fid is None else fid
        r0 = TRACE_CACHE.retraces
        tr = prof.tracer
        if tr.enabled:
            tr.last_launch = None
        t0 = now()
        out = fn(*args)
        if prof.blocking:
            out = jax.block_until_ready(out)  # lint: allow(host-transfer)
        dt = now() - t0
        events = ()
        if TRACE_CACHE.retraces > r0:
            TRACE_CACHE.trace_s += dt
            booked = "trace"
            # close the compile events this launch's misses opened (shape
            # bucket read off the first stacked argument — a host-side
            # shape attribute, never a device sync)
            bucket = next(
                (_trailing_cap(a) for a in args if isinstance(a, Batch)),
                None,
            )
            events = OBSERVATORY.close_open(
                dt, bucket=bucket, fragment=owner, mesh=mesh_key(self.wm)
            )
        else:
            booked = phase
        prof.add_phase(owner, booked, dt)
        sp = tr.last_launch if tr.enabled else None
        if sp is not None:
            # the launch door (telemetry/programs.py) recorded this
            # launch's span, with its `step`; book the phase attribution
            # on it rather than record the same launch twice.  (Handed an
            # exchange function that launches several programs, this is
            # the newest: the exchange itself.)
            sp.attrs.update(phase=booked, fragment=owner)
            if prof.blocking:
                sp.end_s = t0 + dt  # the wait on the device was inside
            # compile stalls nest as children of the launch span, so
            # EXPLAIN ANALYZE VERBOSE and Perfetto separate compile from
            # compute instead of one undifferentiated launch block
            for ev in events:
                tr.attach(
                    sp, "compile", t0, t0 + ev.wall_s,
                    {"step": ev.step, "key": ev.key_fp,
                     "bucket": ev.bucket},
                )
        if owner != self._current_fid:
            # cross-fragment attribution: move the wall with the phase so
            # BOTH fragments keep the phases-sum-to-wall invariant — the
            # producer's wall grows by dt, the consuming stage's self time
            # shrinks by booking dt as child time
            prof.fragment(owner).wall_s += dt
            if self._frame_stack:
                self._frame_stack[-1]["child_s"] += dt
        if events:
            # deadline watchdog: a long XLA compile is a host-side wait with
            # no cooperative check inside — re-check as the compile event
            # closes so an overshoot classifies as EXCEEDED_TIME_LIMIT now
            # instead of silently running past query_max_run_time
            check_current()
        return out

    def _run_chain(self, stacked: Batch, pending: list) -> Batch:
        """Materialize a deferred step chain as ONE cached SPMD program,
        charged to the fragment that produced the chain's first step."""
        keys = tuple(k for k, _, _ in pending)
        steps = [s for _, s, _ in pending]
        owner = next((f for _, _, f in pending if f >= 0), None)

        def build():
            def chain(b: Batch) -> Batch:
                for s in steps:
                    b = s(b)
                return b

            return chain

        fn = cached_spmd_step(self.wm, ("chain",) + keys, build)
        return self._call(fn, stacked, fid=owner)

    # -- public ---------------------------------------------------------------

    def run(self, sub: SubPlan) -> PhysicalPlan:
        try:
            self._register(sub)
            self._root_fid = sub.fragment.id
            out = self._fragment_result(sub.fragment.id)
            if isinstance(out, _Dist):  # defensive: root should be SINGLE
                self._current_fid = sub.fragment.id
                host = unstack_batch(
                    host_pull(self._gather_compact(out.stacked), "result")
                )
                self.profile.bump("result_gather")
                self.profile.add_collective(
                    self._root_fid, batch_bytes(host), "gather",
                    "result_gather",
                )
                return PhysicalPlan(iter([host]), out.symbols)
            return out
        finally:
            self._finalize_profile()
            if self.spool is not None:
                self.spool.close()

    def _finalize_profile(self) -> None:
        from trino_tpu.telemetry.metrics import query_retraces_counter

        prof = self.profile
        h0, m0, r0 = self._trace_base
        prof.trace_hits = TRACE_CACHE.hits - h0
        prof.trace_misses = TRACE_CACHE.misses - m0
        prof.retraces = TRACE_CACHE.retraces - r0
        if prof.retraces:
            query_retraces_counter().inc(prof.retraces)
        for fid, sub in self._subplans.items():
            if fid in prof.fragments:
                prof.fragments[fid].kind = str(sub.fragment.partitioning)
        for st in prof.fragments.values():
            st.close()

    # -- stage orchestration --------------------------------------------------

    def _register(self, sub: SubPlan) -> None:
        self._subplans[sub.fragment.id] = sub
        for c in sub.children:
            self._register(c)

    def _fragment_result(self, fid: int):
        """Stage output: a _Dist, or ('host', batches, symbols) for SINGLE
        fragments (materialized so multiple consumers can re-read).  Under
        retry_policy=TASK each stage is a retryable unit: its output is
        spooled host-side, a failed stage re-executes alone, and finished
        children are never re-run (the Tardigrade property)."""
        if fid not in self._results:
            res = self._run_stage(fid)
            if isinstance(res, _Dist) and self.spool is not None:
                # under TASK retry the spool IS the stage-output store (the
                # spooled-exchange property: outputs live host-side, device
                # memory is released, consumers rehydrate on demand)
                self._results[fid] = ("spooled",)
            else:
                self._results[fid] = res
        res = self._results[fid]
        if res == ("spooled",):
            return self._load_spooled(fid)
        if isinstance(res, tuple):
            return PhysicalPlan(iter(res[1]), res[2])
        return res

    def _run_stage(self, fid: int):
        from trino_tpu.runtime.retry import (
            FAILURE_INJECTOR,
            RETRYABLE,
            StageFailedException,
        )

        sub = self._subplans[fid]
        attempts = self.TASK_ATTEMPTS if self.retry_task else 1
        last = None
        prev_fid = self._current_fid
        self._current_fid = fid
        self._frame_stack.append({"child_s": 0.0})
        t0 = now()
        try:
            with self.profile.tracer.span(
                f"fragment-{fid}", kind=str(sub.fragment.partitioning)
            ):
                # schedule-licensed async dispatch (verify/schedule.py):
                # this fragment's independent build-side feeds dispatch
                # eagerly, back to back, so their exchange collectives
                # overlap the consumer body's host work.  Licensed feeds
                # are sync-free and divergence-free by construction, and
                # sit on the body's first-evaluated spine — the lazy
                # order would run them before any of THIS body's dynamic
                # filters register, so pre-dispatch cannot bypass
                # pruning.
                if self.schedule is not None:
                    for cfid in self.schedule.async_children.get(fid, ()):
                        if cfid in self._results or cfid not in self._subplans:
                            continue
                        self._fragment_result(cfid)
                        self.profile.bump("collective_async")
                        collective_async_counter().inc()
                for attempt in range(attempts):
                    check_current()  # fragment-boundary cancellation point
                    try:
                        FAILURE_INJECTOR.maybe_fail(f"stage:{fid}")
                        if sub.fragment.partitioning.kind in _DIST_KINDS:
                            res = self._exec(sub.fragment.root)
                        else:
                            out = self._local_fragment(sub)
                            res = ("host", list(out.stream), out.symbols)
                        # fires after the body ran (children memoized/
                        # spooled): a failure here retries ONLY this stage
                        FAILURE_INJECTOR.maybe_fail(f"stage:{fid}:finish")
                        self._spool(fid, res, attempt)
                        # fires after the attempt's output is durably
                        # spooled: a failure here makes the RETRY spool a
                        # duplicate attempt, exercising consumer dedup
                        FAILURE_INJECTOR.maybe_fail(f"stage:{fid}:spooled")
                        return res
                    except RETRYABLE as e:
                        last = e
                        if self.retry_task and attempt + 1 < attempts:
                            self._record_recovery(fid, e, "retry")
                if not self.retry_task:
                    # keep the original (QUERY-level-retryable) error
                    raise last
                self._record_recovery(fid, last, "fail")
                raise StageFailedException(
                    f"stage {fid} failed after {attempts} attempts: {last}"
                ) from last
        finally:
            elapsed = now() - t0
            frame = self._frame_stack.pop()
            self.profile.fragment(fid).wall_s += elapsed - frame["child_s"]
            if self._frame_stack:
                self._frame_stack[-1]["child_s"] += elapsed
            self._current_fid = prev_fid

    # -- spooled stage outputs (ExchangeManager role) -------------------------

    def _record_recovery(self, fid: int, exc: BaseException,
                         outcome: str) -> None:
        """Book one task-recovery decision: the {outcome} retry metric plus
        a `recovery` entry in the plan-decision ledger (PR 19), so chaos
        runs show WHAT the engine decided per failure, not just that the
        query survived."""
        from trino_tpu.runtime.lifecycle import error_code_of
        from trino_tpu.telemetry.decisions import record_decision
        from trino_tpu.telemetry.metrics import task_retries_counter

        task_retries_counter().labels(outcome).inc()
        record_decision(
            "recovery", f"stage:{fid}", outcome,
            "fail" if outcome == "retry" else "retry",
            {"error_code": error_code_of(exc), "fragment": int(fid)},
        )

    def _spool(self, fid: int, res, attempt_id: int = 0) -> None:
        """Persist a distributed stage's output host-side, keyed by the
        attempt that produced it.  Only _Dist results spool: a stacked
        batch shares one dictionary per column across workers, so
        rehydration is exact; SINGLE-fragment host results already live
        host-side and stay in the memo."""
        if self.spool is None or not isinstance(res, _Dist):
            return
        from trino_tpu.telemetry.metrics import spooled_fragments_counter

        stacked = res.stacked  # deferred chain runs as its own phase
        with self.profile.phase(fid, "transfer"):
            host = host_pull(stacked, "stage_output")
        self.profile.bump("spool_write")
        spooled_fragments_counter().inc()
        self.profile.fragment(fid).bytes_to_host += batch_bytes(host)
        # full-capacity per-worker shards, masks included (the spooled
        # page files of FileSystemExchangeSink)
        shards = [
            jax.tree.map(lambda x, w=w: np.asarray(x)[w], host)
            for w in range(self.wm.n)
        ]
        dicts = (
            [c.dictionary for c in shards[0].columns] if shards else []
        )
        self.spool.save(
            self.query_id, fid, shards, res.symbols, attempt_id=attempt_id
        )
        self._spool_meta[fid] = (
            res.symbols, dicts, res.placements, res.realigned
        )

    def _load_spooled(self, fid: int) -> "_Dist":
        # spooled shards rehydrate worker-for-worker, so the stage output's
        # placements survive the host round-trip.  Consumer-side dedup
        # (DeduplicatingDirectExchangeBuffer): the FIRST committed attempt
        # wins for every consumer of this fragment, and the losing
        # duplicate attempts are deleted unread
        symbols, dicts, placements, realigned = self._spool_meta[fid]
        att = self.spool.dedup.committed(self.query_id, fid)
        if att is None:
            atts = self.spool.attempts(self.query_id, fid)
            att = self.spool.dedup.commit(
                self.query_id, fid, atts[0] if atts else 0
            )
            self.dedup_discards += self.spool.discard_duplicates(
                self.query_id, fid, att
            )
        shards = self.spool.load(
            self.query_id, fid, symbols, dicts, attempt_id=att
        )
        self.profile.bump("spool_read")
        return self._dist(
            stack_batches(shards, self.wm), symbols, placements=placements,
            realigned=realigned,
        )

    def _local_fragment(self, sub: SubPlan) -> PhysicalPlan:
        """SINGLE/COORDINATOR_ONLY fragment: run the local engine over
        gathered inputs (the final/coordinator stage of the reference)."""
        lp = LocalExecutionPlanner(
            self.catalogs,
            target_splits=self.properties.get("target_splits"),
            properties=self.properties,
        )
        saved = lp.plan
        executor = self

        def plan_hook(node: P.PlanNode) -> PhysicalPlan:
            if isinstance(node, RemoteSourceNode):
                return executor._remote_as_host(node)
            if (
                isinstance(node, P.AggregationNode)
                and isinstance(node.source, RemoteSourceNode)
                and node.source.exchange_kind == "gather"
                and not node.group_symbols
                and not any(
                    a.distinct or a.function in HOLISTIC_AGGS
                    for _, a in node.aggregations
                )
            ):
                # global aggregation over a distributed child: partial states
                # per worker, gather the single state rows, merge — never
                # gather raw rows (PushPartialAggregationThroughExchange)
                child = executor._raw_remote(node.source)
                if isinstance(child, _Dist):
                    return executor._global_agg(node, child)
            return saved(node)

        lp.plan = plan_hook
        return lp.plan(sub.fragment.root)

    # -- exchanges ------------------------------------------------------------

    def _register_dynamic_filters(self, criteria, build: "_Dist") -> None:
        """Record build-side key min/max under the probe symbol names.
        Dictionary-coded keys are skipped (codes are producer-local).
        ALL summaries reduce in ONE cached program and cross to the host in
        ONE transfer (batched small collectives): k criteria cost the same
        sync as one."""
        pairs = []  # (probe name, channel)
        # materialize pending steps first: deferred projections may have
        # changed a key column's dictionary, which the skip check reads
        stacked = build.stacked
        for lsym, rsym in criteria:
            try:
                chn = build.channel(rsym.name)
            except KeyError:
                continue
            col = stacked.columns[chn]
            if col.dictionary is not None or jnp.issubdtype(
                col.data.dtype, jnp.floating
            ):
                continue
            pairs.append((lsym.name, chn))
        if not pairs:
            return
        chans = tuple(ch for _, ch in pairs)

        def build_step():
            def step(b: Batch):
                big = jnp.iinfo(jnp.int64).max
                outs = []
                for chn in chans:
                    c = b.columns[chn]
                    live = b.mask()
                    if c.valid is not None:
                        live = jnp.logical_and(live, c.valid)
                    d = c.data.astype(jnp.int64)
                    outs.append(
                        jnp.stack(
                            [
                                jnp.min(jnp.where(live, d, big)),
                                jnp.max(jnp.where(live, d, -big)),
                                jnp.sum(live, dtype=jnp.int64),
                            ]
                        )
                    )
                return jnp.stack(outs)  # [k, 3]

            return step

        fn = cached_spmd_step(
            self.wm,
            ("dynfilters", chans, _sig(build.symbols)),
            build_step,
        )
        reduced = self._call(fn, stacked)
        with self.profile.phase(self._current_fid, "transfer"):
            summ = host_pull(reduced, "dynamic_filter")
        self.profile.bump("dynamic_filter_sync")
        self.profile.add_collective(
            self._current_fid, int(summ.nbytes), "reduce", "dynamic_filter"
        )
        # [W, k, 3] -> per-criterion global (lo, hi, n)
        for i, (name, _) in enumerate(pairs):
            lo = int(summ[:, i, 0].min())
            hi = int(summ[:, i, 1].max())
            n = int(summ[:, i, 2].sum())
            if n == 0:
                continue
            self.dynamic_filters[name] = (lo, hi)

    def _raw_remote(self, node: RemoteSourceNode):
        """Child fragment result WITHOUT the exchange applied."""
        return self._fragment_result(node.fragment_id)

    def _compact_live(self, batch: Batch, tag, history_key=None) -> Batch:
        """Compact a stacked batch to the pow2 bucket of the max
        per-worker live count (live rows may sit at scattered slots, so
        this is a gather, not a slice).  Costs one [W] live-count host
        read under a 'transfer' phase — callers only use it at edges
        where a host sync is already being paid (state edges, host
        boundaries).  `history_key` additionally records the live bucket
        into CapacityHistory (the same floor the runtime sizing path
        records at), so a licensed join's compaction teaches the
        capacity-economy policy the tight width without a knob-off run."""
        cap = _trailing_cap(batch)
        with self.profile.phase(self._current_fid, "transfer"):
            live = self._host_pull(jnp.sum(batch.mask(), axis=-1))
        if history_key is not None:
            CAP_HISTORY.record(
                history_key,
                next_pow2(max(1, int(live.max())), floor=1024),
            )
        cap2 = bucket_cap(int(live.max()), floor=64)
        if cap2 >= cap:
            return batch

        def build():
            def step(b: Batch) -> Batch:
                return b.compact_device(out_capacity=cap2)

            return step

        fn = cached_spmd_step(self.wm, (tag, cap2), build)
        return self._call(fn, batch)

    def _gather_compact(self, stacked: Batch) -> Batch:
        """Compact to the live bucket before a host gather, so the
        device->host pull moves data, not dead capacity.  Matters most
        for proof-licensed joins: their certified (sound,
        data-independent) capacities can sit well above the live row
        count, and shipping the padding to the host would hand the saved
        sizing sync straight back as transfer + host-iteration cost.
        The data is about to cross the host boundary anyway, so the
        live-count read adds no new device-pipeline stall."""
        if _trailing_cap(stacked) <= 64:
            return stacked
        return self._compact_live(stacked, "gather_compact")

    def _remote_as_host(self, node: RemoteSourceNode) -> PhysicalPlan:
        """Apply a gather/merge exchange into host batches."""
        child = self._raw_remote(node)
        if isinstance(child, PhysicalPlan):
            return child
        fid = self._current_fid
        root = fid == self._root_fid
        why = "result" if root else "stage_output"
        if node.exchange_kind == "merge":
            batch = self._merge_gather(child, node, why)
        else:
            stacked = child.stacked  # deferred chain runs as its own phase
            stacked = self._gather_compact(stacked)
            with self.profile.phase(fid, "transfer"):
                batch = unstack_batch(host_pull(stacked, why))
        purpose = "result_gather" if root else "host_gather"
        self.profile.bump(purpose)
        self.profile.fragment(fid).bytes_to_host += batch_bytes(batch)
        self.profile.add_collective(
            fid, batch_bytes(batch), "gather", purpose
        )
        return PhysicalPlan(iter([batch]), child.symbols)

    def _merge_gather(self, child: _Dist, node: RemoteSourceNode,
                      why: str) -> Batch:
        """Merge exchange: per-worker sorted shards -> one ordered host batch
        (MergeOperator/MergeSortedPages role)."""
        from trino_tpu.ops.merge import merge_sorted_shards

        # compaction is STABLE (`slot_sources` keeps live-row order), so
        # the per-worker sorted runs stay sorted for the host merge
        host = host_pull(self._gather_compact(child.stacked), why)
        keys = [
            SortKey(child.channel(s.name), asc, nf)
            for s, asc, nf in node.orderings
        ]
        shards = []
        for w in range(self.wm.n):
            shard = jax.tree.map(lambda x: np.asarray(x)[w], host)
            n_live = int(np.asarray(shard.mask()).sum())
            # partial sort puts dead rows last: the live prefix is the shard
            shards.append(_slice_host(shard, n_live))
        return merge_sorted_shards(shards, keys)

    def _remote_as_dist(self, node: RemoteSourceNode) -> _Dist:
        """Apply a repartition/broadcast exchange into a stacked batch.
        The application runs under the placer decision's scope (child
        execution stays OUTSIDE it — nested exchanges scope themselves),
        so the collective's bytes join the recorded choice."""
        child = self._raw_remote(node)
        stacked = self._to_stacked(child)
        with decision_scope(node.decision_id):
            return self._apply_dist_exchange(node, stacked)

    def _apply_dist_exchange(self, node: RemoteSourceNode,
                             stacked: _Dist) -> _Dist:
        if node.exchange_kind == "broadcast":
            # ship live rows, not static capacity: all_gather replicates
            # the batch W times, so compacting to the live bucket first
            # divides the collective bytes by the dead-padding ratio.
            # The child fragment just completed (its result is being
            # consumed), so the [W] live read sits at an already-paid
            # host boundary; compaction is stable, preserving row order.
            bs = stacked.stacked
            if _trailing_cap(bs) > 64:
                bs = self._compact_live(bs, "broadcast_compact")
            out = self._call(ex.broadcast, bs, self.wm, phase="collective")
            self.profile.add_collective(
                self._current_fid, batch_bytes(out), "all_gather", "broadcast"
            )
            return self._dist(out, stacked.symbols, realigned=True)
        if node.exchange_kind == "repartition":
            names = tuple(s.name for s in node.partition_symbols)
            # runtime exchange elision: the producing fragment's output is
            # already placed on (a subset of) the requested keys — rows
            # with equal key combinations are co-located, the collective
            # would move nothing anywhere new
            if self.colocate and any(
                t and set(t) <= set(names) for t in stacked.placements
            ):
                self.profile.bump("exchange_elided")
                observe_decision(node.decision_id, elided=1)
                return stacked
            chans = [stacked.channel(s.name) for s in node.partition_symbols]
            return self._repartition_side(stacked, chans)
        raise NotImplementedError(
            f"exchange {node.exchange_kind} feeding a distributed fragment"
        )

    def _to_stacked(self, result) -> _Dist:
        if isinstance(result, _Dist):
            return result
        batches = list(result.stream)
        host = concat_batches(batches) if batches else None
        if host is None or not host.width:
            raise NotImplementedError("empty single-fragment feed")
        with self.profile.phase(self._current_fid, "transfer"):
            stacked = stack_batches(
                [host] + [None] * (self.wm.n - 1), self.wm
            )
        # a host batch re-entered the mesh mid-query: the counter the
        # no-host-roundtrip regression test asserts stays ZERO between
        # distributed fragments
        self.profile.bump("host_restack")
        self.profile.fragment(self._current_fid).bytes_to_device += (
            batch_bytes(host)
        )
        return self._dist(stacked, result.symbols, realigned=True)

    # -- distributed node execution -------------------------------------------

    def _exec(self, node: P.PlanNode):
        m = getattr(self, "_x_" + type(node).__name__, None)
        if m is None:
            raise NotImplementedError(
                f"no distributed executor for {type(node).__name__} — "
                "the exchange placer should have made this a SINGLE fragment"
            )
        return m(node)

    def _x_RemoteSourceNode(self, node: RemoteSourceNode) -> _Dist:
        return self._remote_as_dist(node)

    def _x_TableScanNode(self, node: P.TableScanNode) -> _Dist:
        from trino_tpu.ops.scan import ScanOperator
        from trino_tpu.runtime.buffer_pool import POOL, BufferPool
        from trino_tpu.runtime.retry import FAILURE_INJECTOR

        connector = self.catalogs.get(node.handle.catalog)
        names = [c for _, c in node.assignments]
        types = [s.type for s, _ in node.assignments]
        from trino_tpu.connectors.api import scan_predicate_triples

        splits = list(
            connector.splits(
                node.handle,
                target_splits=self.wm.n,
                predicate=scan_predicate_triples(node),
            )
        )
        page_rows = self.properties.get("page_rows")
        use_cache = self.properties.get("scan_cache")
        # bucketed layout: shard rows by the exchange hash of the bucket
        # columns instead of round-robin splits, so the scan output IS a
        # repartition-on-those-keys placement (the co-located join feed)
        part = (
            scan_partitioning(node, self.layouts, self.wm.n)
            if self.colocate
            else None
        )
        placements = (part[1],) if part is not None else ()

        # device-resident stacked-scan cache: a warm mesh query reuses the
        # sharded [W, cap] batch directly from HBM — zero host->device bytes
        version = (
            connector.scan_version(node.handle) if use_cache else None
        )
        cache_key = None
        if version is not None and splits:
            cache_key = (
                "mesh_scan",
                mesh_key(self.wm),
                # layout in the key: the same splits shard differently once
                # a layout is declared (or colocated_join flips)
                None if part is None else ("layout",) + part[1] + part[2],
                tuple(
                    BufferPool.split_key(s, names, page_rows, version)
                    for s in splits
                ),
            )
            cached = POOL.get_device(cache_key)
            if cached is not None:
                self.profile.bump("scan_cache_hit")
                return self._scan_filters(
                    node,
                    self._dist(
                        cached[0], [s for s, _ in node.assignments],
                        placements=placements, realigned=part is not None,
                    ),
                )
            self.profile.bump("scan_cache_miss")

        per_worker: list = [[] for _ in range(self.wm.n)]
        for i, split in enumerate(splits):
            FAILURE_INJECTOR.maybe_fail(
                f"scan:{node.handle.schema}.{node.handle.table}:{split.seq}"
            )
            op = ScanOperator(
                connector, split, names, types,
                page_rows=page_rows, use_cache=use_cache,
            )
            if part is None:
                per_worker[i % self.wm.n].extend(op.host_batches())
            else:
                per_worker[0].extend(op.host_batches())
        if part is not None and per_worker[0]:
            host_batches = self._bucketize_host(
                concat_batches(per_worker[0]), part[2]
            )
        else:
            host_batches = [
                (concat_batches(bs) if bs else None) for bs in per_worker
            ]
        if all(b is None for b in host_batches):
            cols = [
                Column(np.zeros(1, dtype=t.np_dtype), t, np.zeros(1, bool))
                for t in types
            ]
            host_batches[0] = Batch(cols, np.zeros(1, bool))
        with self.profile.phase(self._current_fid, "transfer"):
            stacked = stack_batches(host_batches, self.wm)
        self.profile.fragment(self._current_fid).bytes_to_device += (
            batch_bytes(stacked)
        )
        if cache_key is not None:
            POOL.put_device(cache_key, [stacked])
        return self._scan_filters(
            node,
            self._dist(
                stacked, [s for s, _ in node.assignments],
                placements=placements, realigned=part is not None,
            ),
        )

    def _bucketize_host(self, host: Batch, key_channels: tuple) -> list:
        """Split one host batch into per-worker shards by the layout hash
        (the numpy mirror of the exchange hash — see partitioning.layout),
        so the stacked scan output is exactly what a hash repartition on
        the bucket columns would have produced."""
        self.profile.bump("scan_bucketize")
        dest = bucket_rows(host, key_channels, self.wm.n)
        out = []
        for w in range(self.wm.n):
            idx = np.nonzero(dest == w)[0]
            out.append(_take_host(host, idx) if idx.size else None)
        return out

    def _scan_filters(self, node: P.TableScanNode, out: _Dist) -> _Dist:
        """Defer the pushed predicate + dynamic-filter pruning onto the scan
        output (they fold into the consumer chain's single program)."""
        if node.pushed_predicate is not None:
            pred = out.rewrite(node.pushed_predicate)
            step = FilterProjectOperator(
                pred, [InputRef(i, s.type) for i, s in enumerate(out.symbols)]
            )._make_step()
            out = out.defer(("scan_pred", pred.key(), _sig(out.symbols)), step)
        # dynamic filters from already-completed build fragments prune this
        # scan's feed (reference: DynamicFilterService -> split pruning)
        from trino_tpu.runtime.local_planner import _range_expr

        dyn = []
        ranges = []
        for s, _ in node.assignments:
            rng = self.dynamic_filters.get(s.name)
            if rng is not None:
                dyn.append(out.rewrite(_range_expr(s, *rng)))
                ranges.append((s.name, rng))
        if dyn:
            step = FilterProjectOperator(
                and_(*dyn),
                [InputRef(i, s.type) for i, s in enumerate(out.symbols)],
            )._make_step()
            dkey = ("dyn_filter", tuple(ranges), _sig(out.symbols))
            # before/after pruning counts are LAZY: computed only under
            # EXPLAIN ANALYZE (profile.blocking), where the profile already
            # serializes dispatch.  A plain execution pays NOTHING for the
            # stats — the pre-PR always-on counts cost one extra execution
            # of the whole scan chain per query (the ROADMAP item; the
            # device-residency contract in verify/ proves the plain path
            # stays clean).  Under EXPLAIN ANALYZE the counts run as ONE
            # cached program with ONE host sync, WITHOUT materializing the
            # deferred chain — the scan steps stay pending so they still
            # fold into the consumer's fused program.
            if self.profile.blocking:
                pend = list(out.pending)

                def build_counts():
                    steps = [fn for _, fn, _ in pend]

                    def count_step(b: Batch):
                        for st in steps:
                            b = st(b)
                        nb = jnp.sum(b.mask(), dtype=jnp.int64)
                        na = jnp.sum(step(b).mask(), dtype=jnp.int64)
                        return jnp.stack([nb, na])

                    return count_step

                fn = cached_spmd_step(
                    self.wm,
                    ("dyn_counts", tuple(k for k, _, _ in pend), dkey),
                    build_counts,
                )
                counts = host_pull(
                    self._call(fn, out._stacked), "dynamic_filter"
                )
                self.dynamic_filter_stats[node.handle.table] = (
                    int(counts[:, 0].sum()), int(counts[:, 1].sum())
                )
            out = out.defer(dkey, step)
        return out

    def _x_FilterNode(self, node: P.FilterNode) -> _Dist:
        src = self._exec(node.source)
        pred = src.rewrite(node.predicate)
        step = FilterProjectOperator(
            pred, [InputRef(i, s.type) for i, s in enumerate(src.symbols)]
        )._make_step()
        return src.defer(("filter", pred.key(), _sig(src.symbols)), step)

    def _x_ProjectNode(self, node: P.ProjectNode) -> _Dist:
        from trino_tpu.expr.ir import SymbolRef

        src = self._exec(node.source)
        exprs = [src.rewrite(e) for _, e in node.assignments]
        step = FilterProjectOperator(None, exprs)._make_step()
        # placements rename through identity refs; any placement column the
        # projection drops loses its placement claim
        rename: dict = {}
        for s, e in node.assignments:
            if isinstance(e, SymbolRef):
                rename.setdefault(e.name, s.name)
        placements = tuple(
            tuple(rename[n] for n in t)
            for t in src.placements
            if t and all(n in rename for n in t)
        )
        return src.defer(
            ("project", tuple(e.key() for e in exprs), _sig(src.symbols)),
            step,
            symbols=[s for s, _ in node.assignments],
            placements=placements,
        )

    # -- aggregation ----------------------------------------------------------

    def _agg_partial(self, node: P.AggregationNode, src: _Dist):
        """Per-worker PARTIAL step; returns (stacked states, specs, op).
        The step FUSES onto the source's deferred chain, so e.g.
        scan-filter-project-partial compiles as one SPMD program; the
        output is then compacted to the live-group bucket so downstream
        exchanges move states, not dead capacity."""
        from trino_tpu.runtime.local_planner import build_agg_inputs

        ngroups = len(node.group_symbols)
        proj, specs, input_types = build_agg_inputs(node, src)
        pre = FilterProjectOperator(None, proj)._make_step()
        partial_op = AggregationOperator(
            list(range(ngroups)), specs, input_types, mode="partial"
        )
        part_cap = next_pow2(src.cap, floor=1) if ngroups else 1

        def partial_step(b: Batch) -> Batch:
            return partial_op._reduce_step(pre(b), out_cap=part_cap)

        key = (
            "agg_partial",
            tuple(e.key() for e in proj),
            _spec_sig(specs),
            part_cap,
            _sig(src.symbols),
        )
        states = self._run_chain(
            src._stacked, src.pending + [(key, partial_step, self._current_fid)]
        )
        if ngroups:
            states = self._compact_states(states)
        return states, specs, partial_op

    def _compact_states(self, states: Batch) -> Batch:
        """Compact a [W, cap] partial-state batch down to its live
        bucket; the downstream exchange + final program then run at
        state scale, not input scale."""
        return self._compact_live(states, "state_compact")

    def _final_op(self, specs, partial_op, states) -> AggregationOperator:
        # state types read off the stacked columns directly — the old
        # tree.map(x[0]) gathered the whole sharded batch eagerly just to
        # look at dtypes (2.5s per query on an 8-way CPU mesh)
        state_types = [c.type for c in states.columns]
        merge_specs = [
            AggSpec(
                s.name, partial_op._state_channel(i), s.out_type,
                param=s.param, sum_bound=s.sum_bound,
            )
            for i, s in enumerate(specs)
        ]
        ngroups = len(partial_op.group_channels)
        return AggregationOperator(
            list(range(ngroups)), merge_specs, state_types, mode="final"
        )

    def _x_AggregationNode(self, node: P.AggregationNode) -> _Dist:
        if not isinstance(node.source, RemoteSourceNode):
            # exchange elided by the placer: the child is placed on a
            # subset of the grouping keys, so every group is whole on one
            # worker — single-stage per worker, fused onto the child chain
            return self._colocated_agg(node, self._exec(node.source))
        src = self._raw_remote(node.source)
        src = self._to_stacked(src)
        ngroups = len(node.group_symbols)
        assert ngroups, "grouped aggregation expected in distributed fragment"
        if any(a.distinct for _, a in node.aggregations) or any(
            a.function in PARTITIONABLE_HOLISTIC
            for _, a in node.aggregations
        ):
            # repartition raw rows on the group keys so every group is whole
            # on one worker, then run the single-stage kernel per worker
            # (uniform DISTINCT prepends an in-jit dedupe pre-aggregation) —
            # no partial/merge states and no coordinator gather
            with decision_scope(node.source.decision_id):
                return self._spmd_single_stage(node, src)
        states, specs, partial_op = self._agg_partial(node, src)
        final_op = self._final_op(specs, partial_op, states)
        # fused exchange: bucketize + all_to_all + the FINAL aggregation
        # step run as one compiled program (phase 1 sizes the slot bucket)
        chans = list(range(ngroups))
        cap_s = _trailing_cap(states)
        cert = getattr(node, "capacity_cert", None)
        slot_cap = None
        if (
            self.license_caps
            and cert is not None
            and cert.valid_for(self.wm.n)
        ):
            # group-count license (verify/capacity.py): the partial agg
            # emits at most one state row per group per worker, so no
            # worker ever sends more than group_bound rows to any
            # destination — a proven slot cap with NO [W, W] counts
            # gather.  Accepted only when the resulting [W, W*slot] final
            # footprint stays within the states' own width (or at the
            # floor bucket), so a loose bound can't inflate the program.
            licensed = next_pow2(min(int(cert.group_bound), cap_s), floor=64)
            if self.wm.n * licensed <= max(64 * self.wm.n, cap_s):
                slot_cap = licensed
                self.profile.bump("agg_slot_cap_proven")
        if slot_cap is None:
            slot_cap = ex.exchange_slot_cap(
                states, chans, self.wm, profile=self.profile,
                fid=self._current_fid,
            )
        fcap = self.wm.n * slot_cap
        # budget enforcement: the fused exchange materializes a [W, fcap]
        # output next to the input states — reserve that footprint BEFORE
        # dispatching; over budget, the exchange+final runs in group-hash
        # waves (group-disjoint, so per-wave merges are exact)
        from trino_tpu.runtime import spill as _spill
        from trino_tpu.runtime.memory import ExceededMemoryLimitException

        s_bytes = batch_bytes(states)
        row_bytes = max(1, s_bytes // max(1, self.wm.n * cap_s))
        need = s_bytes + self.wm.n * fcap * row_bytes
        ctx = self.memory.child("agg_final")
        wave_k = 0
        try:
            ctx.add_bytes(need)
        except ExceededMemoryLimitException:
            wave_k = _spill.wave_count(need, self._budget(), self.properties)
        if wave_k:
            wdid = record_decision(
                "wave", "runtime.agg_final", "waves", "direct",
                {"waves": int(wave_k), "need_bytes": int(need),
                 "budget_bytes": int(self._budget() or 0)},
            )
            with decision_scope(wdid):
                out = self._wave_agg_exchange(
                    node, states, chans, final_op, specs, wave_k, ctx
                )
        else:
            def final_step(b: Batch) -> Batch:
                return final_op._reduce_step(b, out_cap=fcap)

            with decision_scope(node.source.decision_id):
                out = self._call(
                    ex.fused_repartition,
                    states,
                    chans,
                    self.wm,
                    final_step,
                    ("agg_final", _spec_sig(specs), fcap,
                     _sig(node.outputs)),
                    slot_cap,
                    phase="collective",
                )
                self.profile.add_collective(
                    self._current_fid, batch_bytes(out), "all_to_all",
                    "repartition",
                )
            ctx.close()
        return self._dist(
            out, node.outputs,
            placements=((tuple(s.name for s in node.group_symbols),)),
            realigned=True,
        )

    def _wave_agg_exchange(self, node, states, chans, final_op, specs,
                           n_waves: int, ctx) -> Batch:
        """Group-hash wave execution of the aggregation's fused exchange
        (HashAggregationOperator.startMemoryRevoke on the mesh): each wave
        device-filters the partial states to the groups whose exchange
        row hash lands in the wave, runs the SAME fused
        repartition+final program shape at the wave's (smaller) slot
        bucket, and the per-wave outputs concatenate.  Hashing the full
        group key keeps every group inside exactly one wave, so results
        are exact; peak exchange-output footprint shrinks ~k-fold."""
        from trino_tpu.runtime import spill as _spill

        observer = _spill.PressureObserver(sink=self.profile)
        observer.waves("aggregation", n_waves)
        fid = self._current_fid
        cap_s = _trailing_cap(states)

        def build_filter(wave):
            def step(b: Batch) -> Batch:
                h = ex._hash_rows(b, chans)
                sel = (h % jnp.uint64(n_waves)).astype(jnp.int64) == wave
                return b.filter(jnp.logical_and(b.mask(), sel))

            return lambda: step

        outs = []
        for wave in range(n_waves):
            fn = cached_spmd_step(
                self.wm,
                ("agg_wave_filter", n_waves, wave, tuple(chans), cap_s,
                 _sig(node.outputs)),
                build_filter(wave),
            )
            filt = self._call(fn, states)
            slot_w = ex.exchange_slot_cap(
                filt, chans, self.wm, profile=self.profile, fid=fid
            )
            fcap_w = self.wm.n * slot_w
            _spill.reserve_wave_working_set(ctx, batch_bytes(filt))

            def final_step(b: Batch, fc=fcap_w) -> Batch:
                return final_op._reduce_step(b, out_cap=fc)

            out_w = self._call(
                ex.fused_repartition,
                filt,
                chans,
                self.wm,
                final_step,
                ("agg_final", _spec_sig(specs), fcap_w, _sig(node.outputs)),
                slot_w,
                phase="collective",
            )
            self.profile.add_collective(
                fid, batch_bytes(out_w), "all_to_all", "repartition"
            )
            outs.append(out_w)
        out = _concat_stacked(outs)
        ctx.close()
        return out

    def _colocated_agg(self, node: P.AggregationNode, src: _Dist) -> _Dist:
        """Single-stage grouped aggregation over an already-placed child
        (no exchange, no partial/final split): groups are whole per worker
        because the child's placement is a subset of the grouping keys.
        Defers onto the child chain, so scan-filter-aggregate still
        compiles as ONE SPMD program."""
        from trino_tpu.runtime.local_planner import build_agg_inputs

        ngroups = len(node.group_symbols)
        assert ngroups, "colocated aggregation needs grouping keys"
        proj, specs, input_types = build_agg_inputs(node, src)
        pre = FilterProjectOperator(None, proj)._make_step()
        op = AggregationOperator(
            list(range(ngroups)), specs, input_types, mode="single"
        )
        out_cap = next_pow2(src.cap, floor=64)

        def step(b: Batch) -> Batch:
            return op._reduce_step(pre(b), out_cap=out_cap)

        self.profile.bump("exchange_elided")
        gnames = {s.name for s in node.group_symbols}
        placements = tuple(
            t for t in src.placements if t and set(t) <= gnames
        )
        return src.defer(
            ("agg_colocated", tuple(e.key() for e in proj),
             _spec_sig(specs), out_cap, _sig(src.symbols)),
            step,
            symbols=node.outputs,
            cap=out_cap,
            placements=placements,
        )

    def _spmd_single_stage(self, node: P.AggregationNode, src: _Dist) -> _Dist:
        """Repartition-on-group-keys + per-worker single-stage aggregation
        (the distributed home of the holistic/DISTINCT shapes; reference:
        single-step aggregation over hash distribution).  The dedupe +
        aggregation consumer fuses into the exchange program."""
        from trino_tpu.runtime.local_planner import (
            build_agg_inputs,
            build_distinct_dedupe,
        )

        ngroups = len(node.group_symbols)
        key_channels = [src.channel(s.name) for s in node.group_symbols]
        stacked = src.stacked
        slot_cap = ex.exchange_slot_cap(
            stacked, key_channels, self.wm, profile=self.profile,
            fid=self._current_fid,
        )
        fcap = self.wm.n * slot_cap
        ex_dist = self._dist(stacked, src.symbols)  # layout proxy
        pre_dd = None
        agg_src = ex_dist
        dedupe = None
        if any(a.distinct for _, a in node.aggregations):
            dd_proj, dd_symbols = build_distinct_dedupe(node, ex_dist)
            dedupe = AggregationOperator(
                list(range(len(dd_proj))), [], [e.type for e in dd_proj],
                mode="single",
            )
            pre_dd = FilterProjectOperator(None, dd_proj)._make_step()
            agg_src = PhysicalPlan(iter(()), dd_symbols)
        proj, specs, input_types = build_agg_inputs(node, agg_src)
        op = AggregationOperator(
            list(range(ngroups)), specs, input_types, mode="single"
        )
        pre_agg = FilterProjectOperator(None, proj)._make_step()

        def single_step(b: Batch) -> Batch:
            if pre_dd is not None:
                b = dedupe._reduce_step(pre_dd(b), out_cap=fcap)
            return op._reduce_step(pre_agg(b), out_cap=fcap)

        out = self._call(
            ex.fused_repartition,
            stacked,
            key_channels,
            self.wm,
            single_step,
            ("agg_single", tuple(e.key() for e in proj),
             _spec_sig(specs), fcap,
             pre_dd is not None, _sig(src.symbols)),
            slot_cap,
            phase="collective",
        )
        self.profile.add_collective(
            self._current_fid, batch_bytes(out), "all_to_all", "repartition"
        )
        return self._dist(
            out, node.outputs,
            placements=((tuple(s.name for s in node.group_symbols),)),
            realigned=True,
        )

    def _global_agg(self, node: P.AggregationNode, src: _Dist) -> PhysicalPlan:
        """Global aggregation over a distributed child: partial per worker,
        gather the (single-row) state shards, final merge on the
        coordinator.  The partial output capacity is 1 — only W state rows
        ever cross to the host."""
        states, specs, partial_op = self._agg_partial(node, src)
        final_op = self._final_op(specs, partial_op, states)
        fid = self._current_fid
        with self.profile.phase(fid, "transfer"):
            gathered = unstack_batch(host_pull(states, "stage_output"))
        self.profile.bump("state_gather")
        self.profile.fragment(fid).bytes_to_host += batch_bytes(gathered)
        from trino_tpu.ops.aggregation import _pad_device

        cap = next_pow2(gathered.capacity, floor=1)
        final = final_op._step(_pad_device(gathered, cap), out_cap=1)
        return PhysicalPlan(iter([final]), node.outputs)

    # -- joins ----------------------------------------------------------------

    def _unify_key_dicts(self, a: _Dist, ak, b: _Dist, bk):
        """Key columns compared across the two sides must share a dictionary
        (codes are ranks; mixed dictionaries would compare wrongly).  Host
        unions the dictionaries, a jitted take recodes each side."""
        from trino_tpu.columnar.dictionary import union_dictionaries

        def recode(dist: _Dist, ch: int, table, merged, dkey):
            tbl = jnp.asarray(table)

            def step(batch: Batch) -> Batch:
                cols = list(batch.columns)
                c = cols[ch]
                cols[ch] = Column(
                    jnp.take(tbl, c.data.astype(jnp.int64), mode="clip"),
                    c.type,
                    c.valid,
                    merged,
                )
                return Batch(cols, batch.row_mask)

            # the recode table is a closure constant: the dictionary-content
            # hashes in the key pin the cached program to THESE dictionaries
            return dist.defer(("recode", ch, dkey), step)

        for ca, cb in zip(ak, bk):
            # .stacked (not ._stacked): deferred steps may change dictionaries
            da = a.stacked.columns[ca].dictionary
            db = b.stacked.columns[cb].dictionary
            if da is None and db is None:
                continue
            if da is db or da == db:
                continue
            if da is None or db is None:
                raise NotImplementedError(
                    "join key mixes dictionary and plain strings"
                )
            merged, ta, tb = union_dictionaries(da, db)
            # key = (OWN dictionary, other): the two sides bake DIFFERENT
            # translation tables, so their keys must differ even when the
            # channel index coincides (ca == cb is the common case)
            a = recode(a, ca, ta, merged, (hash(da), hash(db)))
            b = recode(b, cb, tb, merged, (hash(db), hash(da)))
        return a, b

    def _join_side(self, side_node):
        """One join input: a child-fragment result (exchange NOT applied)
        or an inline already-placed subtree (elided exchange)."""
        if isinstance(side_node, RemoteSourceNode):
            return self._to_stacked(self._raw_remote(side_node))
        return self._exec(side_node)

    def _place_join_side(self, side_node, side: _Dist, keys):
        """Apply (or elide) the partitioned-join repartition of one side:
        a RemoteSource(repartition) hashes on ITS partition symbols (the
        aligned subset the placer chose); an inline side was already placed
        by a layout or upstream exchange and moves nothing."""
        if (
            isinstance(side_node, RemoteSourceNode)
            and side_node.exchange_kind == "repartition"
        ):
            syms = side_node.partition_symbols or keys
            return self._repartition_side(
                side, [side.channel(s.name) for s in syms]
            )
        self.profile.bump("exchange_elided")
        return side

    def _x_JoinNode(self, node: P.JoinNode) -> _Dist:
        assert node.distribution in (
            "broadcast", "partitioned", "colocated"
        ), node
        probe_node, build_node = node.left, node.right
        # BUILD side first: its fragment completes before the probe side is
        # even pulled, so build-key ranges can prune probe-side scans in
        # later fragments (reference: DynamicFilterService.java:107,126 —
        # filters collected from build tasks reach probe scans before
        # splits feed)
        build = self._join_side(build_node)
        if node.kind == "inner":
            self._register_dynamic_filters(node.criteria, build)
        probe = self._join_side(probe_node)
        pk = [probe.channel(l.name) for l, _ in node.criteria]
        bk = [build.channel(r.name) for _, r in node.criteria]
        probe, build = self._unify_key_dicts(probe, pk, build, bk)
        out_symbols = probe.symbols + build.symbols
        residual = None
        residual_key = None
        if node.filter is not None:
            expr = PhysicalPlan(iter(()), out_symbols).rewrite(node.filter)
            residual_key = expr.key()

            def residual(batch: Batch, _e=expr):
                return ExprCompiler(batch).filter_mask(_e)

        # one `join` span per join operator (the local runner's name): from
        # both sides ready to the joined output; the sides' own work is outside
        with self.profile.tracer.span(
            "join", kind=node.kind, strategy=node.distribution
        ):
            did = node.decision_id
            if node.distribution == "broadcast":
                # partitioned-build economy for the broadcast that remains:
                # all_gather replicates the build's FULL static capacity W
                # times, dead padding included (the measured Q3 wall: a ~20%
                # live filtered build shipped 27 MB).  Compact to the live
                # bucket first — the build boundary already pays a host sync
                # for the dynamic-filter summary, so the [W] live read adds
                # no new dispatch stall, and the collective moves only live
                # rows.  Compaction is stable, so build-row order (and with
                # it the sorted-probe tie-break order) is unchanged.
                bs = build.stacked
                with decision_scope(did):
                    if _trailing_cap(bs) > 64:
                        bs = self._compact_live(bs, "broadcast_compact")
                    build_stacked = self._call(
                        ex.broadcast, bs, self.wm, phase="collective"
                    )
                    self.profile.add_collective(
                        self._current_fid, batch_bytes(build_stacked),
                        "all_gather", "broadcast",
                    )
            else:
                with decision_scope(did):
                    build = self._place_join_side(
                        build_node, build, [r for _, r in node.criteria]
                    )
                    probe = self._place_join_side(
                        probe_node, probe, [l for l, _ in node.criteria]
                    )
                build_stacked = build.stacked

            op = HashJoinOperator(
                node.kind, pk, bk,
                [s.type for s in build.symbols],
                probe_types=[s.type for s in probe.symbols],
                residual=residual,
            )
            cap_b = _trailing_cap(build_stacked)
            jkey = (
                node.kind, tuple(pk), tuple(bk), cap_b,
                _sig(probe.symbols), _sig(build.symbols), residual_key,
            )
            # capacity-history discriminator: two queries can share the same
            # join signature (and compiled programs) while filtering the probe
            # differently — their deferred-chain keys tell them apart so their
            # recorded capacities don't ping-pong
            probe_fp = tuple(k for k, _, _ in probe.pending)
            probe_stacked = probe.stacked
            probe_types = [s.type for s in probe.symbols]
            if did is not None:
                # outcome inputs for the hindsight join (telemetry/decisions):
                # static-shape byte math only, no device sync.  build_bytes is
                # ONE logical build copy (a broadcast's stacked batch holds W
                # replicas); probe_move_bytes is what the rejected partitioned
                # plan would have had to move for an unplaced probe.
                bb = int(batch_bytes(build_stacked))
                observe_decision(
                    did,
                    build_bytes=(
                        bb // max(1, self.wm.n)
                        if node.distribution == "broadcast" else bb
                    ),
                    probe_move_bytes=(
                        0 if (node.distribution == "broadcast"
                              and probe.placements)
                        else int(batch_bytes(probe_stacked))
                    ),
                )

            # budget enforcement: reserve the build's device footprint (raw +
            # sorted copy) BEFORE the expansion materializes; over budget the
            # join degrades to hash-partition waves with filesystem-SPI spill
            # instead of dying (runtime/spill, SURVEY §5.7's k-pass loop)
            from trino_tpu.runtime import spill as _spill
            from trino_tpu.runtime.memory import ExceededMemoryLimitException

            ctx = self.memory.child("join_build")
            need = 2 * batch_bytes(build_stacked)
            wave_k = 0
            try:
                ctx.add_bytes(need)
            except ExceededMemoryLimitException:
                wave_k = _spill.wave_count(need, self._budget(), self.properties)
            if wave_k:
                wdid = record_decision(
                    "wave", "runtime.join_build", "waves", "direct",
                    {"waves": int(wave_k), "need_bytes": int(need),
                     "budget_bytes": int(self._budget() or 0)},
                )
                with decision_scope(wdid):
                    out = self._wave_join(
                        node, op, probe_stacked, build_stacked, pk, bk, jkey,
                        probe_types, wave_k, ctx,
                    )
            else:
                locate, device_emit_total, expand = self._join_step_fns(
                    node, op, pk, bk, _trailing_cap(build_stacked), probe_types
                )
                # proof-licensed capacity (verify/capacity.py): a certificate
                # sealed for THIS mesh width licenses a fixed expand capacity
                # — the sizing gather, overflow flag, and speculative retry
                # are deleted, not skipped.  Any mismatch (mesh shrink, knob
                # off, memory-pressure waves above) falls back to the runtime
                # sizing path: the license is an optimization with a proof,
                # never a correctness dependency.
                cert = getattr(node, "capacity_cert", None)
                if not (
                    self.license_caps
                    and cert is not None
                    and cert.valid_for(self.wm.n)
                ):
                    cert = None
                out = self._sized_expansion(
                    ("join",) + jkey, probe_stacked, build_stacked,
                    locate, device_emit_total, expand, compact_probe=True,
                    stats_key=("join",) + jkey + (probe_fp,),
                    cert=cert,
                )
                ctx.close()
        return self._dist(
            out, out_symbols,
            placements=join_output_placements(
                probe.placements, node.criteria, node.kind
            ),
            realigned=probe.realigned or node.distribution != "broadcast",
        )

    def _join_step_fns(self, node, op, pk, bk, cap_b: int, probe_types):
        """(locate, device_emit_total, expand) closures for one build
        capacity — shared by the direct path and the per-wave path (which
        runs them at the wave's smaller build bucket)."""

        def device_emit_total(pb: Batch, count):
            """Per-worker emitted-row total, ON DEVICE (what the pre-PR
            path synced the whole count matrix to the host to compute)."""
            live = pb.mask()
            emit = (
                jnp.where(live, jnp.maximum(count, 1), 0)
                if node.kind in ("left", "full")
                else jnp.where(live, count, 0)
            )
            return jnp.sum(emit, dtype=jnp.int64)

        def locate(pb: Batch, bb: Batch):
            # per-shard PagesHash analog: sort THIS shard's build once,
            # then binary-search the probe keys against it
            sb, canon, n_match = _sort_build_device(bb, bk)
            pc, pn = _canon_probe_device(pb, pk, canon)
            start, count = _locate_sorted(
                canon, n_match, pc, pn, cap_b=cap_b
            )
            return sb, start, count

        def expand(pb: Batch, sb: Batch, start, count, total, out_cap: int):
            matched0 = (
                jnp.zeros(cap_b, dtype=bool) if node.kind == "full" else None
            )
            out, matched = op._expand_step(
                pb, sb, start, count, matched0, out_cap=out_cap,
                cap_b=cap_b, total_emit=total,
            )
            if node.kind == "full":
                # per-shard unmatched-build tail: with PARTITIONED inputs
                # every build row lives on exactly one shard, so the tail
                # emits each unmatched build row exactly once
                tail_live = jnp.logical_and(sb.mask(), jnp.logical_not(matched))
                ncols = [
                    Column(
                        jnp.zeros(cap_b, dtype=t.np_dtype),
                        t,
                        jnp.zeros(cap_b, dtype=bool),
                        None,
                    )
                    for t in probe_types
                ]
                tail = Batch(ncols + list(sb.columns), tail_live)
                out = concat_batches([out, tail])
            return out

        return locate, device_emit_total, expand

    def _wave_join(self, node, op, probe_stacked, build_stacked, pk, bk,
                   jkey, probe_types, n_waves: int, ctx) -> Batch:
        """Mesh partition-wave join (SpillingJoinProcessor on the mesh):
        both stacked sides pull host-side, hash-partition per worker shard
        by the exchange row-value hash into `n_waves` partitions (spilled
        through the filesystem SPI under `spill_enabled`), and the join
        runs wave by wave at ONE shared shape bucket — the same compiled
        locate/expand programs serve every wave, so after wave 1 the loop
        retraces nothing.  Worker-shard identity is preserved through the
        spill so each wave restacks onto the same mesh alignment."""
        from trino_tpu.parallel.serde import partition_batches
        from trino_tpu.runtime import spill as _spill

        fid = self._current_fid
        observer = _spill.PressureObserver(sink=self.profile)
        spiller = (
            _spill.SpillManager(observer=observer)
            if _spill.spill_to_disk(self.properties)
            else None
        )
        observer.waves("join", n_waves)
        W = self.wm.n
        try:
            with self.profile.phase(fid, "transfer"):
                # the spill tier's declared host boundary
                bh, ph = _spill.pull_host(build_stacked, probe_stacked)
            self.profile.fragment(fid).bytes_to_host += (
                batch_bytes(bh) + batch_bytes(ph)
            )

            def shard_parts(host, keys):
                """([wave][worker] -> host Batch or None, dead template).
                Partitioning runs PER worker shard so wave loads restack
                onto the same mesh alignment."""
                shards = [
                    jax.tree.map(lambda x, w=w: np.asarray(x)[w], host)
                    for w in range(W)
                ]
                template = _dead_batch_like(shards[0])
                per_shard = [
                    partition_batches([s], list(keys), n_waves)
                    for s in shards
                ]
                parts = [
                    [
                        (per_shard[w][wave][0] if per_shard[w][wave] else None)
                        for w in range(W)
                    ]
                    for wave in range(n_waves)
                ]
                return parts, template

            b_parts, b_dead = shard_parts(bh, bk)
            p_parts, p_dead = shard_parts(ph, pk)
            del bh, ph

            def side_cap(parts) -> int:
                rows = max(
                    (b.capacity for wave in parts for b in wave
                     if b is not None),
                    default=1,
                )
                return next_pow2(max(rows, 1), floor=64)

            # ONE shape bucket per side shared by every wave: the compiled
            # locate/expand programs from wave 0/1 serve all later waves
            cap_b = side_cap(b_parts)
            cap_p = side_cap(p_parts)

            def store(tag, parts):
                """Spill each wave's present shards to the SPI; returns a
                loader of [worker] -> Batch|None."""
                if spiller is None:
                    return lambda wave: parts[wave]
                present: dict = {}
                for wave in range(n_waves):
                    real = [
                        (w, b) for w, b in enumerate(parts[wave])
                        if b is not None
                    ]
                    present[wave] = [w for w, _ in real]
                    if real:
                        spiller.save(tag, wave, [b for _, b in real])
                    parts[wave] = None  # free RAM as waves land on disk

                def load(wave):
                    cells: list = [None] * W
                    loaded = spiller.load(tag, wave)
                    for w, b in zip(present[wave], loaded):
                        cells[w] = b
                    return cells

                return load

            b_load = store("jb", b_parts)
            p_load = store("jp", p_parts)

            locate, emit_total, expand = self._join_step_fns(
                node, op, pk, bk, cap_b, probe_types
            )
            wkey = ("join_wave", n_waves, cap_b, cap_p) + jkey
            outs = []
            for wave in range(n_waves):
                b_cells = b_load(wave)
                p_cells = p_load(wave)
                if all(c is None for c in p_cells) and node.kind != "full":
                    continue  # no probe rows and no build tail: no output
                if all(c is None for c in b_cells):
                    b_cells[0] = b_dead  # empty build wave still probes
                if all(c is None for c in p_cells):
                    p_cells[0] = p_dead  # full outer: tail-only wave
                build_w = stack_batches(b_cells, self.wm, cap=cap_b)
                probe_w = stack_batches(p_cells, self.wm, cap=cap_p)
                _spill.reserve_wave_working_set(
                    ctx, 2 * batch_bytes(build_w)
                )
                outs.append(
                    self._sized_expansion(
                        wkey, probe_w, build_w, locate, emit_total, expand,
                        compact_probe=False, stats_key=wkey,
                    )
                )
            if not outs:
                # every wave empty (all-dead inputs): one dead wave still
                # runs so downstream sees a properly-shaped empty output
                build_w = stack_batches(
                    [b_dead] + [None] * (W - 1), self.wm, cap=cap_b
                )
                probe_w = stack_batches(
                    [p_dead] + [None] * (W - 1), self.wm, cap=cap_p
                )
                outs.append(
                    self._sized_expansion(
                        wkey, probe_w, build_w, locate, emit_total, expand,
                        compact_probe=False, stats_key=wkey,
                    )
                )
            out = _concat_stacked(outs)
            ctx.close()
            return out
        finally:
            if spiller is not None:
                spiller.close()

    # -- capacity-sized expansions (joins / residual semi joins) --------------

    def _sized_expansion(self, key, probe_stacked, build_stacked,
                         locate, device_total, expand,
                         compact_probe: bool = False,
                         stats_key=None, cert=None) -> Batch:
        """Run a locate+expand pair whose static output capacity depends on
        the data, under the `join_speculative_capacity` policy:

          * warm (capacity history holds the tight pow2 buckets measured
            before): ONE fused locate+expand program launched speculatively
            with an on-device overflow flag — no host sync before or during
            the join; the post-hoc [W] flag read overlaps completed device
            work, and an overflow (changed data) retries at the next
            bucket.  With `compact_probe`, the program first compacts the
            probe to its recorded live-row bucket (deferred filters leave
            dead capacity: a half-selective scan otherwise doubles every
            downstream locate/expand), guarded by the same overflow flag;
          * cold (no history) or speculation off: a sizing pass — locate
            runs first and its per-worker emitted TOTAL + live count
            (computed on device) cross as one tiny [W, 2] transfer to pick
            the exact buckets; the expand then consumes locate's
            device-resident outputs.  The pre-PR path shipped the whole
            [W, cap] count matrix and stalled dispatch on it.

        Cold and warm paths agree on the expand capacity (the tight
        bucket), so every downstream static shape is identical across runs
        — warm replays retrace nothing.

        A capacity certificate (`cert`, verify/capacity.py) supersedes the
        whole protocol: the proven per-probe-row fanout bounds the emitted
        total by the probe batch's STATIC capacity, so the expand compiles
        at the certified fixed capacity with NO sizing gather, NO overflow
        flag, and NO retry — zero `join_overflow_check`, zero
        `gather/capacity_sizing` bytes, cold and warm alike."""
        cap_p = _trailing_cap(probe_stacked)
        fid = self._current_fid
        spec = speculation_mode(self.properties)
        hist_key = ("cap",) + (stats_key if stats_key is not None else key)
        pkey = ("pcap",) + (stats_key if stats_key is not None else key)

        if cert is not None:  # proof-licensed fixed capacity
            if compact_probe and cap_p > 1024:
                # probe compaction at the host boundary: deferred filters
                # leave dead probe capacity, and the certified output cap
                # scales with the probe's STATIC width — compacting to the
                # measured live bucket (a [W] read, sound by measurement
                # rather than speculation) narrows the whole licensed
                # chain.  The pkey record is the same bucket the runtime
                # path's speculative probe compaction learns from.
                probe_stacked = self._compact_live(
                    probe_stacked, ("licensed_probe_compact",) + key,
                    history_key=pkey,
                )
                cap_p = _trailing_cap(probe_stacked)
            oc = next_pow2(
                cert.licensed_out_cap(cap_p),
                floor=min(1024, next_pow2(cap_p, floor=1)),
            )
            # Economy policy: a license is only worth holding when its
            # certified width is in the neighborhood of the widths the
            # runtime path's own programs would span — the learned output
            # bucket (its expand) and the learned live-probe bucket (its
            # locate).  A sound-but-loose certificate (e.g. a fanout
            # bound of 80 on a probe whose matches are sparse) compiles
            # the whole expand at 80x-wide shapes, and the extra
            # FLOPs/bytes on dead lanes dwarf the sizing sync the license
            # deletes.  Host-side state only: CapacityHistory buckets
            # taught by earlier runtime runs OR by the licensed path's
            # own compactions above/below — the licensed path teaches its
            # own economy decision.
            learned = max(
                CAP_HISTORY.guess(hist_key, 0), CAP_HISTORY.guess(pkey, 0)
            )
            declined = None
            if learned and oc > _LICENSE_WIDTH_FACTOR * learned:
                declined = f"width {oc} > {_LICENSE_WIDTH_FACTOR}x learned {learned}"
            elif not learned and oc > next_pow2(cap_p, floor=1024):
                # cold guard: with no history yet, accept only widths
                # bounded by the probe's own static capacity (fanout<=1
                # certificates).  A multiplicity license (fanout k>1)
                # would compile k*cap_p wide on the very first run —
                # let the runtime path size it once, then relicense.
                declined = f"cold width {oc} > probe capacity {cap_p}"
            cap_inputs = {
                "cert_kind": type(cert).__name__,
                "licensed_cap": int(oc),
                "learned_cap": int(learned),
                "probe_cap": int(cap_p),
            }
            if declined is None:
                did = record_decision(
                    "join_capacity", "runtime.sized_expansion", "licensed",
                    "runtime_check", cap_inputs,
                )

                def build_licensed(_oc=oc):
                    def step(pb: Batch, bb: Batch):
                        sb, start, count = locate(pb, bb)
                        total = device_total(pb, count)
                        return expand(pb, sb, start, count, total, _oc)

                    return step

                fn = cached_spmd_step(
                    self.wm, ("licensed_expand", oc, cap_p) + key,
                    build_licensed,
                )
                with decision_scope(did):
                    out = self._call(fn, probe_stacked, build_stacked)
                    self.profile.bump("join_capacity_proven")
                    join_capacity_counter().labels("proven").inc()
                    if oc > 1024:
                        # compact the licensed output to its live bucket at
                        # this host boundary (the build sync already stalls
                        # here) and record the tight width so the NEXT run's
                        # economy decision sees it — the licensed path
                        # teaches itself
                        out = self._compact_live(
                            out, ("licensed_compact",) + key,
                            history_key=hist_key,
                        )
                observe_decision(
                    did, executed=1,
                    live_cap=int(CAP_HISTORY.guess(hist_key, 0)),
                )
                return out
            self.profile.bump("join_license_declined")
            join_capacity_counter().labels("declined").inc()
            did = record_decision(
                "join_capacity", "runtime.sized_expansion", "declined",
                "licensed", {**cap_inputs, "declined_reason": declined},
            )
        else:
            did = record_decision(
                "join_capacity", "runtime.sized_expansion", "runtime_check",
                "", {"probe_cap": int(cap_p)},
            )

        join_capacity_counter().labels("runtime_check").inc()
        out_cap = (
            initial_cap(hist_key, spec) if spec is not None else None
        )

        while out_cap is not None:  # speculative fused path
            pcap = CAP_HISTORY.guess(pkey, cap_p) if compact_probe else cap_p
            pcap = min(pcap, cap_p)

            def build_fused(oc=out_cap, pc=pcap):
                def step(pb: Batch, bb: Batch):
                    live = jnp.sum(pb.mask(), dtype=jnp.int64)
                    over = live > pc
                    if pc < cap_p:
                        pb = pb.compact_device(out_capacity=pc)
                    sb, start, count = locate(pb, bb)
                    total = device_total(pb, count)
                    over = jnp.logical_or(over, total > oc)
                    return (
                        expand(pb, sb, start, count, total, oc),
                        total,
                        live,
                        over,
                    )

                return step

            fn = cached_spmd_step(
                self.wm, ("fused_expand", out_cap, pcap) + key, build_fused
            )
            with decision_scope(did):
                out, total, live, over = self._call(
                    fn, probe_stacked, build_stacked
                )
                with self.profile.phase(fid, "transfer"):
                    over_h, total_h, live_h = self._host_pull(
                        over, total, live, why="overflow_flag"
                    )
                self.profile.bump("join_overflow_check")
                self.profile.add_collective(
                    fid, int(over_h.nbytes + total_h.nbytes + live_h.nbytes),
                    "gather", "capacity_sizing",
                )
            if not over_h.any():
                CAP_HISTORY.record(hist_key, out_cap)
                if compact_probe:
                    CAP_HISTORY.record(pkey, pcap)
                observe_decision(did, executed=1, runtime_cap=int(out_cap))
                return out
            self.profile.bump("join_speculative_retry")
            if int(live_h.max()) > pcap:
                CAP_HISTORY.record(
                    pkey, next_pow2(int(live_h.max()), floor=1024)
                )
            if int(total_h.max()) > out_cap:
                out_cap = next_cap(int(total_h.max()), out_cap)

        # sizing pass: locate + one [W] totals read + exactly-sized expand
        def build_locate():
            def step(pb: Batch, bb: Batch):
                sb, start, count = locate(pb, bb)
                live = jnp.sum(pb.mask(), dtype=jnp.int64)
                return sb, start, count, device_total(pb, count), live

            return step

        loc = cached_spmd_step(self.wm, ("locate",) + key, build_locate)
        with decision_scope(did):
            sb, start, count, total_dev, live_dev = self._call(
                loc, probe_stacked, build_stacked
            )
            with self.profile.phase(fid, "transfer"):
                totals, lives = self._host_pull(total_dev, live_dev)
            self.profile.bump("join_capacity_sync")
            self.profile.add_collective(
                fid, int(totals.nbytes + lives.nbytes), "gather",
                "capacity_sizing",
            )
        cap = next_pow2(max(1, int(totals.max())), floor=1024)

        def build_expand(oc=cap):
            def step(pb: Batch, sb: Batch, start, count, total):
                return expand(pb, sb, start, count, total, oc)

            return step

        fn = cached_spmd_step(self.wm, ("expand", cap) + key, build_expand)
        with decision_scope(did):
            out = self._call(fn, probe_stacked, sb, start, count, total_dev)
        observe_decision(did, executed=1, runtime_cap=int(cap))
        if spec is not None:
            CAP_HISTORY.record(hist_key, cap)
            if compact_probe:
                CAP_HISTORY.record(
                    pkey,
                    min(cap_p, next_pow2(max(1, int(lives.max())), floor=1024)),
                )
        return out

    def _x_SemiJoinNode(self, node: P.SemiJoinNode) -> _Dist:
        if isinstance(node.source, RemoteSourceNode):
            src = self._to_stacked(self._raw_remote(node.source))
        else:
            src = self._exec(node.source)
        assert isinstance(node.filtering, RemoteSourceNode)
        filt = self._to_stacked(self._raw_remote(node.filtering))
        fk = [filt.channel(node.filtering_key.name)]
        sk = [src.channel(node.source_key.name)]
        src, filt = self._unify_key_dicts(src, sk, filt, fk)
        sk, fk = sk[0], fk[0]

        def _global_has_null(stacked: Batch) -> bool:
            fcol = stacked.columns[fk]
            if fcol.valid is None:
                return False
            live, valid = host_pull(
                (stacked.mask(), fcol.valid), "group_stats"
            )
            return bool(np.any(live & ~valid))

        if node.filter is not None:
            # residual-filtered semi join, PARTITIONED on the key: both
            # sides were repartitioned by the fragmenter, so key-matching
            # candidate pairs are co-located per shard; the residual is the
            # same probe++filtering candidate filter the local operator uses
            out_symbols = src.symbols + filt.symbols
            expr = PhysicalPlan(iter(()), out_symbols).rewrite(node.filter)

            def residual(batch: Batch, _e=expr):
                return ExprCompiler(batch).filter_mask(_e)

            op = SemiJoinOperator(
                sk,
                fk,
                [s.type for s in filt.symbols],
                null_aware=node.null_aware,
                residual=residual,
            )
            # per-shard marking needs key-matching pairs co-located.  With
            # no placements, both sides ride the connector's aligned range
            # splits (the historical contract); once EITHER side is hash-
            # placed (a bucketed layout), range alignment is gone — hash-
            # place the other side too so the shards line up exactly
            src_placed = any(
                t == (node.source_key.name,) for t in src.placements
            )
            filt_placed = any(
                t == (node.filtering_key.name,) for t in filt.placements
            )
            # a REALIGNED side without an exact-key placement (bucketized
            # on other columns, placement claim dropped by a projection, a
            # host re-stack, ...) breaks range alignment just as surely as
            # a placed one — once anything moved, every side must end up
            # exact-key hash-placed
            if self.colocate and (
                src_placed or filt_placed or src.realigned or filt.realigned
            ):
                with decision_scope(node.decision_id):
                    if src_placed:
                        self.profile.bump("exchange_elided")
                        observe_decision(node.decision_id, elided=1)
                    else:
                        src = self._repartition_side(src, [sk])
                    if filt_placed:
                        self.profile.bump("exchange_elided")
                        observe_decision(node.decision_id, elided=1)
                    else:
                        filt = self._repartition_side(filt, [fk])
            filt_stacked = filt.stacked
            has_null = _global_has_null(filt_stacked)
            cap_b = _trailing_cap(filt_stacked)
            skey = (
                sk, fk, cap_b, node.null_aware, has_null, expr.key(),
                _sig(src.symbols), _sig(filt.symbols),
            )

            src_fp = tuple(k for k, _, _ in src.pending)
            src_stacked = src.stacked

            def locate(pb: Batch, bb: Batch):
                sb, canon, n_match = _sort_build_device(bb, [fk])
                pc, pn = _canon_probe_device(pb, [sk], canon)
                st, ct = _locate_sorted(canon, n_match, pc, pn, cap_b=cap_b)
                return sb, st, ct

            def device_total(pb: Batch, ct):
                return jnp.sum(ct, dtype=jnp.int64)

            def mark(pb: Batch, sb: Batch, st, ct, total, out_cap: int):
                return op._mark_residual_step(
                    pb, sb, st, ct,
                    cap_b=cap_b, out_cap=out_cap, total_emit=total,
                    has_null=has_null,
                )

            out = self._sized_expansion(
                ("semi",) + skey, src_stacked, filt_stacked,
                locate, device_total, mark,
                stats_key=("semi",) + skey + (src_fp,),
            )
            return self._dist(
                out, src.symbols + [node.mark], placements=src.placements,
                realigned=src.realigned,
            )

        op = SemiJoinOperator(
            sk, fk, [s.type for s in filt.symbols], null_aware=node.null_aware
        )
        with decision_scope(node.decision_id):
            bcast = self._call(
                ex.broadcast, filt.stacked, self.wm, phase="collective"
            )
            self.profile.add_collective(
                self._current_fid, batch_bytes(bcast), "all_gather",
                "broadcast",
            )
        if node.decision_id is not None:
            observe_decision(
                node.decision_id,
                build_bytes=int(batch_bytes(bcast)) // max(1, self.wm.n),
                probe_move_bytes=(
                    0 if src.placements else int(batch_bytes(src.stacked))
                ),
            )
        cap_b = _trailing_cap(bcast)
        has_null = _global_has_null(bcast)

        def build_mark():
            def mark_step(pb: Batch, bb: Batch) -> Batch:
                _, canon, n_match = _sort_build_device(bb, [fk])
                pc, pn = _canon_probe_device(pb, [sk], canon)
                _, count = _locate_sorted(canon, n_match, pc, pn, cap_b=cap_b)
                return op._mark_step(pb, count, has_null)

            return mark_step

        mark = cached_spmd_step(
            self.wm,
            ("semi_mark", sk, fk, cap_b, node.null_aware, has_null,
             _sig(src.symbols), _sig(filt.symbols)),
            build_mark,
        )
        out = self._call(mark, src.stacked, bcast)
        return self._dist(
            out, src.symbols + [node.mark], placements=src.placements,
            realigned=src.realigned,
        )

    def _repartition_side(self, side: _Dist, chans: list) -> _Dist:
        """Hash-place one operand on `chans` (co-locating it with a side
        that is already layout-placed on the aligned keys)."""
        stacked = self._call(
            ex.repartition, side.stacked, chans, self.wm, phase="collective"
        )
        self.profile.bump("repartition_collective")
        self.profile.add_collective(
            self._current_fid, batch_bytes(stacked), "all_to_all",
            "repartition",
        )
        return self._dist(
            stacked, side.symbols,
            placements=((tuple(side.symbols[c].name for c in chans),)),
            realigned=True,
        )

    def _x_UnnestNode(self, node: P.UnnestNode) -> _Dist:
        from trino_tpu.ops.unnest import UnnestOperator

        src = self._exec(node.source)
        exprs = [src.rewrite(e) for _, e in node.unnest]
        op = UnnestOperator(exprs, with_ordinality=node.ordinality is not None)

        def step(b: Batch) -> Batch:
            cols, mask = op.raw_step(b)
            return Batch(cols, mask)

        # output capacity is element-shape dependent: run eagerly (still a
        # cached program) rather than deferring with an unknown cap
        fn = cached_spmd_step(
            self.wm,
            ("unnest", tuple(e.key() for e in exprs),
             node.ordinality is not None, _sig(src.symbols), src.cap),
            lambda: step,
        )
        out = self._call(fn, src.stacked)
        return self._dist(
            out, node.outputs, placements=src.placements,
            realigned=src.realigned,
        )

    def _x_MarkDistinctNode(self, node: P.MarkDistinctNode) -> _Dist:
        from trino_tpu.ops.aggregation import MarkDistinctOperator

        src = self._exec(node.source)
        chans = tuple(src.channel(s.name) for s in node.key_symbols)
        op = MarkDistinctOperator(list(chans))
        return src.defer(
            ("mark_distinct", chans, _sig(src.symbols)),
            op._mark_step,
            symbols=node.outputs,
            placements=src.placements,
        )

    # -- window ---------------------------------------------------------------

    def _x_WindowNode(self, node: P.WindowNode) -> _Dist:
        from trino_tpu.ops.window import WindowOperator, WindowSpec

        src = self._exec(node.source)
        part = [src.channel(s.name) for s in node.partition_by]
        order = [
            SortKey(src.channel(s.name), asc, nf)
            for s, asc, nf in node.order_by
        ]
        specs = []
        for out_sym, fn in node.functions:
            arg = src.channel(fn.args[0].name) if fn.args else None
            default_ch = (
                src.channel(fn.default.name) if fn.default is not None else None
            )
            specs.append(
                WindowSpec(
                    fn.name if fn.name != "count_star" else "count",
                    arg,
                    out_sym.type,
                    offset=fn.offset,
                    default_channel=default_ch,
                    n_buckets=fn.n_buckets_expr or 1,
                    frame=fn.frame,
                    start_off=fn.start_off,
                    end_off=fn.end_off,
                    ignore_nulls=fn.ignore_nulls,
                    sum_bound=getattr(fn, "sum_bound", None),
                )
            )
        op = WindowOperator(part, order, specs)
        # per-worker window over hash-partitioned rows: every partition is
        # wholly on one worker after the repartition exchange below this node
        return src.defer(
            ("window", tuple(part), tuple(repr(k) for k in order),
             tuple(repr(s) for s in specs), _sig(src.symbols)),
            op._window_step,
            symbols=node.outputs,
            placements=src.placements,
        )

    # -- ordering / limiting (partial steps; merge happens at the exchange) ---

    def _x_SortNode(self, node: P.SortNode) -> _Dist:
        src = self._exec(node.source)
        keys = [
            SortKey(src.channel(s.name), asc, nf)
            for s, asc, nf in node.orderings
        ]
        op = OrderByOperator(keys)
        return src.defer(
            ("sort", tuple(repr(k) for k in keys), _sig(src.symbols)),
            op._sort_step,
        )

    def _x_TopNNode(self, node: P.TopNNode) -> _Dist:
        src = self._exec(node.source)
        keys = [
            SortKey(src.channel(s.name), asc, nf)
            for s, asc, nf in node.orderings
        ]
        op = TopNOperator(keys, node.count)
        out_cap = next_pow2(node.count, floor=1)

        def step(b: Batch) -> Batch:
            return op._merge_step(b, out_cap=out_cap)

        return src.defer(
            ("topn", tuple(repr(k) for k in keys), node.count, out_cap,
             _sig(src.symbols)),
            step,
            cap=out_cap,
        )

    def _x_LimitNode(self, node: P.LimitNode) -> _Dist:
        src = self._exec(node.source)
        n = node.count

        def step(b: Batch) -> Batch:
            live = b.mask()
            rank = jnp.cumsum(live) - 1
            return b.filter(jnp.logical_and(live, rank < n))

        return src.defer(("limit", n, _sig(src.symbols)), step)


def _take_host(batch: Batch, idx: np.ndarray) -> Batch:
    """Row-gather of a HOST batch (bucketized scan sharding)."""
    cols = [
        Column(
            np.asarray(c.data)[idx],
            c.type,
            None if c.valid is None else np.asarray(c.valid)[idx],
            c.dictionary,
            None if c.lengths is None else np.asarray(c.lengths)[idx],
        )
        for c in batch.columns
    ]
    return Batch(cols, np.asarray(batch.mask())[idx])


def _slice_host(batch: Batch, n: int) -> Batch:
    cols = [
        Column(
            np.asarray(c.data)[:n],
            c.type,
            None if c.valid is None else np.asarray(c.valid)[:n],
            c.dictionary,
            None if c.lengths is None else np.asarray(c.lengths)[:n],
        )
        for c in batch.columns
    ]
    return Batch(cols, np.asarray(batch.mask())[:n])


def _trailing_cap(stacked: Batch) -> int:
    """Row capacity of a stacked [W, cap] batch (Batch.capacity would report
    the leading worker axis)."""
    if stacked.columns:
        return stacked.columns[0].data.shape[-1]
    return stacked.row_mask.shape[-1]


def _dead_batch_like(b: Batch) -> Batch:
    """Capacity-1 all-dead host batch with `b`'s schema (shape-compatible
    placeholder for empty wave partitions)."""
    cols = []
    for c in b.columns:
        data = np.asarray(c.data)
        cols.append(
            Column(
                np.zeros((1,) + data.shape[1:], dtype=data.dtype),
                c.type,
                np.zeros(1, dtype=bool) if c.valid is not None else None,
                c.dictionary,
                (
                    np.zeros(1, dtype=np.asarray(c.lengths).dtype)
                    if c.lengths is not None
                    else None
                ),
            )
        )
    return Batch(cols, np.zeros(1, dtype=bool))


def _concat_stacked(batches: list) -> Batch:
    """Concatenate stacked [W, cap_i] batches along the per-worker row axis
    (wave outputs -> one distributed intermediate).  All inputs must share
    schema and per-column dictionaries — wave partitions of one stacked
    source always do."""
    if len(batches) == 1:
        return batches[0]
    first = batches[0]
    cols = []
    for ci, c0 in enumerate(first.columns):
        cs = [b.columns[ci] for b in batches]
        for c in cs[1:]:
            if c.dictionary is not c0.dictionary and c.dictionary != c0.dictionary:
                raise AssertionError(
                    "wave outputs diverged dictionaries; cannot concat"
                )
        data = jnp.concatenate([c.data for c in cs], axis=1)
        valid = None
        if any(c.valid is not None for c in cs):
            valid = jnp.concatenate(
                [
                    c.valid
                    if c.valid is not None
                    else jnp.ones(c.data.shape[:2], dtype=bool)
                    for c in cs
                ],
                axis=1,
            )
        lengths = None
        if any(c.lengths is not None for c in cs):
            lengths = jnp.concatenate([c.lengths for c in cs], axis=1)
        cols.append(Column(data, c0.type, valid, c0.dictionary, lengths))
    mask = jnp.concatenate([b.mask() for b in batches], axis=1)
    return Batch(cols, mask)
