"""Local execution planner: logical PlanNode tree -> operator pipelines.

Reference role: sql/planner/LocalExecutionPlanner.java:516,600 (the seam where
plan nodes become OperatorFactory chains and symbols are laid out as channels).
Here each plan node becomes a (batch-stream, symbol-layout) pair; symbol
references inside expressions are rewritten to positional InputRef channels
exactly like the reference's layout mapping, and join build sides are
materialized by draining their subplan (HashBuilderOperator's role).
"""

from __future__ import annotations

from typing import Iterable, Iterator, Optional

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import COMPACT, host_pull
from trino_tpu.connectors.api import CatalogManager
from trino_tpu.expr.ir import (
    Call,
    Expr,
    Form,
    InputRef,
    Literal,
    SpecialForm,
    SymbolRef,
    visit,
)
from trino_tpu.expr import ExprCompiler
from trino_tpu.ops.aggregation import AggregationOperator, AggSpec
from trino_tpu.ops.common import SortKey
from trino_tpu.ops.filter_project import FilterProjectOperator
from trino_tpu.ops.join import (
    HashJoinOperator,
    JoinSpan,
    NestedLoopJoinOperator,
    SemiJoinOperator,
)
from trino_tpu.ops.scan import ScanOperator
from trino_tpu.ops.sort import LimitOperator, OrderByOperator, TopNOperator
from trino_tpu.ops.values import ValuesOperator
from trino_tpu.planner import plan as P
from trino_tpu.planner.functions import HOLISTIC_AGGS
from trino_tpu.telemetry.programs import jit_program


class PhysicalPlan:
    """A batch stream plus the symbol layout of its channels."""

    def __init__(self, stream: Iterable[Batch], symbols: list):
        self.stream = stream
        self.symbols = list(symbols)

    def channel(self, name: str) -> int:
        for i, s in enumerate(self.symbols):
            if s.name == name:
                return i
        raise KeyError(f"symbol {name} not in layout {[s.name for s in self.symbols]}")

    def rewrite(self, expr: Expr) -> Expr:
        """SymbolRef -> InputRef against this layout."""

        def fn(e: Expr) -> Expr:
            if isinstance(e, SymbolRef):
                return InputRef(self.channel(e.name), e.type)
            return e

        return visit(expr, fn)

    def identity_projections(self) -> list:
        return [InputRef(i, s.type) for i, s in enumerate(self.symbols)]

    def types(self) -> list:
        return [s.type for s in self.symbols]


class LocalExecutionPlanner:
    def __init__(
        self,
        catalogs: CatalogManager,
        target_splits: int = 4,
        stats=None,
        properties=None,
    ):
        from trino_tpu.runtime.lifecycle import query_memory_context
        from trino_tpu.runtime.session import SessionProperties

        self.catalogs = catalogs
        self.target_splits = target_splits
        self.stats = stats  # Optional[StatsCollector] for EXPLAIN ANALYZE
        self.properties = properties or SessionProperties()
        #: per-query device-memory budget tree (reference:
        #: lib/trino-memory-context AggregatedMemoryContext + MemoryPool);
        #: blocking operators reserve through children of this context.
        #: When a query is executing this lives on the SHARED process pool,
        #: where the revoke tier and the LowMemoryKiller can see it.
        self.memory = query_memory_context(self._session_budget())
        if stats is not None:
            stats.memory = self.memory
        self._depth = 0
        #: symbol name -> frozenset of keys or (lo, hi), host values collected
        #: from materialized join build sides (reference: server/
        #: DynamicFilterService.java:107 + DynamicFilterSourceOperator —
        #: build-side key sets and ranges prune probe scans)
        self.dynamic_filters: dict = {}
        #: id(join tree) -> the SharedInput its consumer reads it through
        #: (`share_repeated_inputs`); empty unless the runner asked
        self._shared: dict = {}

    def share_repeated_inputs(self, root: P.PlanNode) -> None:
        """Run a join tree that `root` holds more than once (the input of
        a ROLLUP, CUBE or GROUPING SETS) one time for all its consumers
        (`runtime/shared_input.py`).  Not under a memory budget, where the
        operators spill and what a shared input keeps would not; not under
        EXPLAIN ANALYZE, which reports every operator of the plan."""
        from trino_tpu.runtime.shared_input import SharedInput, repeated_inputs

        if self.stats is not None or self._budget():
            return
        for members in repeated_inputs(root):
            shared = SharedInput(members)
            for node in members:
                self._shared[id(node)] = shared

    def _session_budget(self) -> int:
        """Per-query session budget in bytes (query_max_memory / legacy
        query_max_memory_bytes, whichever is tighter)."""
        from trino_tpu.runtime.spill import session_budget

        return session_budget(self.properties)

    def _budget(self) -> int:
        """The effective device budget blocking operators plan against:
        session budget AND any shared pool limit (memory.pool-limit-bytes),
        whichever is tighter.  0 = unconstrained — no wave machinery runs."""
        from trino_tpu.runtime.spill import effective_budget

        return effective_budget(self.properties, self.memory)

    def _observer(self):
        """Wave/spill event sink: the metrics registry plus EXPLAIN
        ANALYZE's StatsCollector counters when one is attached."""
        from trino_tpu.runtime.spill import PressureObserver

        return PressureObserver(sink=self.stats)

    def _make_spiller(self):
        """A filesystem-SPI spill store for one wave operation, or None
        when the `spill_enabled` session knob stages partitions in host
        RAM instead.  Callers invoke this LAZILY (first spill), so an
        unconstrained query never touches the filesystem."""
        from trino_tpu.runtime.spill import SpillManager, spill_to_disk

        if not spill_to_disk(self.properties):
            return None
        return SpillManager(observer=self._observer())

    def plan(self, node: P.PlanNode) -> PhysicalPlan:
        shared = self._shared.get(id(node))
        if shared is not None:
            return shared.plan_for(self, node)
        return self.plan_unshared(node)

    def plan_unshared(self, node: P.PlanNode) -> PhysicalPlan:
        method = getattr(self, "_visit_" + type(node).__name__, None)
        if method is None:
            raise NotImplementedError(f"no local plan for {type(node).__name__}")
        self._depth += 1
        try:
            out = method(node)
        finally:
            self._depth -= 1
        if self.stats is not None:
            st = self.stats.register(
                type(node).__name__.replace("Node", ""), depth=self._depth
            )
            out = PhysicalPlan(self.stats.instrument(st, out.stream), out.symbols)
        return out

    # -- leaves ---------------------------------------------------------------

    def _visit_TableScanNode(self, node: P.TableScanNode) -> PhysicalPlan:
        connector = self.catalogs.get(node.handle.catalog)
        names = [c for _, c in node.assignments]
        types = [s.type for s, _ in node.assignments]
        from trino_tpu.connectors.api import scan_predicate_triples

        splits = list(
            connector.splits(
                node.handle,
                target_splits=self.target_splits,
                predicate=scan_predicate_triples(node),
            )
        )
        page_rows = self.properties.get("page_rows")
        use_cache = self.properties.get("scan_cache")
        prefetch_depth = self.properties.get("scan_prefetch_depth")
        concurrency = self.properties.get("task_concurrency")

        def split_feed(split):
            def make():
                from trino_tpu.runtime.retry import FAILURE_INJECTOR

                FAILURE_INJECTOR.maybe_fail(
                    f"scan:{node.handle.schema}.{node.handle.table}:{split.seq}"
                )
                op = ScanOperator(
                    connector, split, names, types,
                    page_rows=page_rows, use_cache=use_cache,
                )
                return op.batches()

            return make

        if concurrency > 1 and len(splits) > 1:
            # intra-task parallelism: split readers drain through a local
            # exchange (host-side decode+feed is the parallelizable part;
            # the device stream stays single — XLA owns that).  The exchange
            # is already background-fed + buffered, so no prefetch wrap.
            from trino_tpu.runtime.local_exchange import parallel_feed

            feed = parallel_feed(
                [split_feed(s) for s in splits], workers=concurrency
            )
        else:

            def stream():
                for split in splits:
                    yield from split_feed(split)()

            feed = stream()
            if prefetch_depth > 0:
                from trino_tpu.runtime.prefetch import prefetch_iter

                feed = prefetch_iter(feed, depth=prefetch_depth)
        plan = PhysicalPlan(feed, [s for s, _ in node.assignments])
        pred_expr = node.pushed_predicate
        # dynamic filters registered by upstream join builds (ranges over this
        # scan's output symbols) fuse into the scan's first device step
        dyn = []
        for s, _ in node.assignments:
            domain = self.dynamic_filters.get(s.name)
            if domain is not None:
                dyn.append(_domain_expr(s, domain))
        if dyn:
            from trino_tpu.expr.ir import and_

            pred_expr = and_(*(([pred_expr] if pred_expr is not None else []) + dyn))
        if pred_expr is not None:
            pred = plan.rewrite(pred_expr)
            fp = FilterProjectOperator(pred, plan.identity_projections())
            plan = PhysicalPlan(fp.process(plan.stream), plan.symbols)
        if dyn:
            # dynamic filters are usually very selective; compact so the
            # smaller live set shrinks every downstream static shape
            plan = PhysicalPlan(_compact_stream(plan.stream), plan.symbols)
        return plan

    def _visit_ValuesNode(self, node: P.ValuesNode) -> PhysicalPlan:
        op = ValuesOperator([s.type for s in node.symbols], node.rows)
        return PhysicalPlan(op.batches(), node.symbols)

    # -- row transforms -------------------------------------------------------

    def _visit_FilterNode(self, node: P.FilterNode) -> PhysicalPlan:
        src = self.plan(node.source)
        op = FilterProjectOperator(src.rewrite(node.predicate), src.identity_projections())
        return PhysicalPlan(op.process(src.stream), src.symbols)

    def _visit_ProjectNode(self, node: P.ProjectNode) -> PhysicalPlan:
        src = self.plan(node.source)
        if node.is_identity():
            return PhysicalPlan(src.stream, [s for s, _ in node.assignments])
        exprs = [src.rewrite(e) for _, e in node.assignments]
        op = FilterProjectOperator(None, exprs)
        return PhysicalPlan(op.process(src.stream), [s for s, _ in node.assignments])

    def _visit_UnnestNode(self, node: P.UnnestNode) -> PhysicalPlan:
        from trino_tpu.ops.unnest import UnnestOperator

        src = self.plan(node.source)
        exprs = [src.rewrite(e) for _, e in node.unnest]
        op = UnnestOperator(exprs, with_ordinality=node.ordinality is not None)
        return PhysicalPlan(op.process(src.stream), node.outputs)

    def _visit_SampleNode(self, node: "P.SampleNode") -> PhysicalPlan:
        from trino_tpu.ops.sample import SampleOperator

        src = self.plan(node.source)
        # deterministic per plan position: re-planning the same query (or a
        # retried fragment) samples the same rows
        self._sample_seq = getattr(self, "_sample_seq", 0) + 1
        op = SampleOperator(node.ratio, seed=self._sample_seq)
        return PhysicalPlan(op.process(src.stream), src.symbols)

    def _visit_PatternRecognitionNode(
        self, node: P.PatternRecognitionNode
    ) -> PhysicalPlan:
        from trino_tpu.ops.pattern import PatternRecognitionOperator

        src = self.plan(node.source)
        # defines rewritten to channel space over the SOURCE layout
        rewritten = P.PatternRecognitionNode(
            node.source,
            node.partition_by,
            node.order_by,
            [(v, src.rewrite(e)) for v, e in node.defines],
            node.pattern,
            node.measures,
            node.rows_per_match,
            node.after_match,
        )
        op = PatternRecognitionOperator(rewritten, src.symbols)
        return PhysicalPlan(op.process(src.stream), node.outputs)

    # -- aggregation ----------------------------------------------------------

    def _collapse_agg_source(self, node: P.AggregationNode):
        """Fold a Project*/Filter? chain under an aggregation into the
        aggregation's own input projection (classic projection merging), so
        the whole filter+compute+partial-reduce pipeline compiles as ONE
        XLA program — no intermediate column materialization.  Returns
        (source PhysicalPlan proxy, predicate Expr or None), or None when
        the shape doesn't match."""
        from trino_tpu.expr.ir import substitute_symbols

        maps = []
        inner = node.source
        while isinstance(inner, P.ProjectNode):
            maps.append({s.name: e for s, e in inner.assignments})
            inner = inner.source
        pred = None
        if isinstance(inner, P.FilterNode):
            pred = inner.predicate
            inner = inner.source
        if not maps and pred is None:
            return None
        if not isinstance(inner, P.TableScanNode):
            # conservative: only collapse over scans (other sources may have
            # their own operators with observable behavior)
            return None
        base = self.plan(inner)

        class _Sub:
            stream = base.stream
            symbols = base.symbols

            @staticmethod
            def rewrite(e):
                for m in maps:
                    e = substitute_symbols(e, m)
                return base.rewrite(e)

            @staticmethod
            def channel(name):
                return base.channel(name)

        pred_ir = base.rewrite(pred) if pred is not None else None
        return _Sub, pred_ir

    def _visit_AggregationNode(self, node: P.AggregationNode) -> PhysicalPlan:
        distinct = any(agg.distinct for _, agg in node.aggregations)
        collapsed = None if distinct else self._collapse_agg_source(node)
        if collapsed is not None:
            src, fused_pred = collapsed
        else:
            src = self.plan(node.source)
            fused_pred = None
        if distinct:
            src = self._distinct_preagg(node, src)
        ngroups = len(node.group_symbols)
        proj, specs, input_types = build_agg_inputs(node, src)
        pre = FilterProjectOperator(fused_pred, proj)
        # holistic aggregates need every group row at once: no streaming
        # partials (reference: ArrayAggregationFunction group state)
        streaming = not any(
            s.name in HOLISTIC_AGGS for s in specs
        )

        budget = self._budget()
        # Fuse the agg-input projection INTO the jitted partial-reduce
        # program when possible: projection outputs (decimal products etc.)
        # then never materialize between operators — the whole-fragment
        # fusion XLA is built for.  Group keys must be identity InputRefs so
        # host-side direct-path eligibility can read the RAW batch.
        from trino_tpu.expr.ir import InputRef

        pre_raw = pre_key = group_src = None
        if streaming and not (budget and ngroups):
            if all(isinstance(proj[i], InputRef) for i in range(ngroups)):
                pre_raw, pre_key = pre.fusable_step()
                if pre_raw is not None:
                    group_src = [proj[i].channel for i in range(ngroups)]

        def make_op():
            op = AggregationOperator(
                list(range(ngroups)),
                specs,
                input_types,
                mode=node.step,
                streaming=streaming,
                fold_every=self.properties.get("agg_fold_batches"),
                memory_ctx=self.memory.child("aggregation"),
                use_pallas=self.properties.get("pallas_agg"),
                pre_step=pre_raw,
                pre_key=pre_key,
                pre_jit=pre._step if pre_raw is not None else None,
            )
            op._group_src_channels = group_src
            return op

        feed = src.stream if pre_raw is not None else pre.process(src.stream)
        if budget and ngroups:
            stream = _agg_wave_stream(
                make_op, feed, list(range(ngroups)), int(budget),
                observer=self._observer(), spill_factory=self._make_spiller,
                properties=self.properties,
            )
        else:
            stream = make_op().process(feed)
        return PhysicalPlan(stream, node.outputs)

    def _visit_MarkDistinctNode(self, node: P.MarkDistinctNode) -> PhysicalPlan:
        from trino_tpu.ops.aggregation import MarkDistinctOperator

        src = self.plan(node.source)
        op = MarkDistinctOperator(
            [src.channel(s.name) for s in node.key_symbols]
        )
        return PhysicalPlan(op.process(src.stream), node.outputs)

    def _distinct_preagg(self, node: P.AggregationNode, src: PhysicalPlan) -> PhysicalPlan:
        """DISTINCT aggregates via pre-grouping (reference role: the
        MarkDistinct/pre-aggregation rewrites in AddExchanges/optimizer).
        Supported: every distinct aggregate shares the same argument list and
        non-distinct aggregates are absent."""
        if not supports_uniform_distinct(node):
            raise NotImplementedError("mixed DISTINCT aggregate shapes")
        proj, symbols = build_distinct_dedupe(node, src)
        dedupe = AggregationOperator(
            list(range(len(proj))), [], [e.type for e in proj], mode="single", streaming=True
        )
        pre = FilterProjectOperator(None, proj)
        stream = dedupe.process(pre.process(src.stream))
        return PhysicalPlan(stream, symbols)

    # -- joins ----------------------------------------------------------------

    def _visit_JoinNode(self, node: P.JoinNode) -> PhysicalPlan:
        if node.kind == "cross":
            left = self.plan(node.left)
            right = self.plan(node.right)
            op = NestedLoopJoinOperator(right.types())
            op.set_build(list(right.stream))
            span = JoinSpan("cross", op, keys=0)
            return PhysicalPlan(
                span.wrap(op.process, left.stream), left.symbols + right.symbols
            )
        if node.kind == "right":
            flipped = P.JoinNode(
                "left", node.right, node.left,
                [(r, l) for l, r in node.criteria], node.filter, node.distribution,
            )
            out = self._visit_JoinNode(flipped)
            # restore left ++ right symbol order
            order = [out.channel(s.name) for s in node.outputs]
            proj = FilterProjectOperator(
                None, [InputRef(c, out.symbols[c].type) for c in order]
            )
            return PhysicalPlan(proj.process(out.stream), node.outputs)

        from trino_tpu.runtime.memory import (
            ExceededMemoryLimitException,
            batch_bytes,
        )

        build = self.plan(node.right)
        build_batches = list(build.stream)
        if node.kind == "inner":
            # dynamic filtering: build-side key sets or ranges prune the
            # probe scan (registered before the probe subtree is planned,
            # the DynamicFilterService ordering)
            for lsym, rsym in node.criteria:
                domain = _build_key_domain(
                    build_batches, build.channel(rsym.name)
                )
                if domain is not None:
                    self.dynamic_filters[lsym.name] = domain
        probe = self.plan(node.left)
        # pipeline parallelism (§2.7(4)): the probe feed starts decoding NOW,
        # overlapping the build side's device-side compaction/indexing.
        # Planned AFTER the build drain so dynamic filters still apply.
        # Made under the join's span, so the feed's lane hangs under it.
        from trino_tpu.runtime.prefetch import eager_prefetch

        span = JoinSpan(node.kind, keys=len(node.criteria))
        with span.tracer.entered(span.sp):
            probe = PhysicalPlan(
                eager_prefetch(probe.stream, depth=2), probe.symbols
            )
        out_symbols = probe.symbols + build.symbols
        probe_keys = [probe.channel(l.name) for l, _ in node.criteria]
        build_keys = [build.channel(r.name) for _, r in node.criteria]
        residual = None
        residual_key = None
        if node.filter is not None:
            combined = PhysicalPlan(iter(()), out_symbols)
            res_expr = combined.rewrite(node.filter)
            residual_key = res_expr.key()

            def residual(batch: Batch, _e=res_expr):
                return ExprCompiler(batch).filter_mask(_e)

        def make_op():
            return HashJoinOperator(
                node.kind,
                probe_keys,
                build_keys,
                build.types(),
                probe_types=probe.types(),
                residual=residual,
                residual_key=residual_key,
            )

        # reserve the dense build footprint BEFORE materializing on device;
        # on budget overflow degrade to hash-partitioned waves (the HBM
        # analog of build-side spill: HashBuilderOperator.startMemoryRevoke
        # + GenericPartitioningSpiller + SpillingJoinProcessor)
        from trino_tpu.runtime import spill as _spill

        ctx = self.memory.child("join_build")
        observer = self._observer()
        from trino_tpu.runtime.memory import batches_bytes

        build_bytes = batches_bytes(build_batches)
        need = 2 * build_bytes  # raw batches + compacted copy
        try:
            ctx.add_bytes(need)
        except ExceededMemoryLimitException:
            n_waves = _spill.wave_count(
                need, self._budget(), self.properties
            )
            spiller = self._make_spiller()
            build_host = host_pull(list(build_batches), "build_to_host")
            build_batches.clear()
            build_side = _spill.partition_side(
                build_host, build_keys, n_waves, spiller, "jb"
            )
            del build_host

            def waves(probe_stream):
                try:
                    probe_host = host_pull(list(probe_stream), "probe_to_host")
                    probe_side = _spill.partition_side(
                        probe_host, probe_keys, n_waves, spiller, "jp"
                    )
                    del probe_host
                    yield from _spill.partition_wave_join(
                        make_op, build_side, probe_side, n_waves, ctx,
                        observer,
                    )
                finally:
                    if spiller is not None:
                        spiller.close()

            span.chose(strategy="partition_waves")
            return PhysicalPlan(span.wrap(waves, probe.stream), out_symbols)
        op = make_op()
        op.set_build(build_batches)
        span.chose(op)
        if node.kind == "full":
            # full outer tracks build-side matched flags across the whole
            # probe; a mid-stream revoke cannot split that state exactly,
            # so full joins stay non-revocable (waves still cover them on
            # the up-front over-budget path above)
            def stream():
                yield from span.wrap(op.process, probe.stream, op)
                ctx.close()

            return PhysicalPlan(stream(), out_symbols)

        # register as REVOCABLE (HashBuilderOperator.startMemoryRevoke):
        # under shared-pool pressure — another query reserving, or a pool
        # limit shrunk mid-query — the escalation hook asks this build to
        # spill its partitions and release; the probe loop notices at its
        # next batch and finishes in waves against the spilled build
        holder: dict = {}

        def revoke_spill() -> int:
            # runs on the REQUESTING thread under the handle lock; the
            # owner may be mid-batch against op's device build, so only
            # the raw build batches are copied out here — the owner drops
            # its own device references at its next batch boundary
            spiller = self._make_spiller()
            k = _spill.wave_count(need, self._budget(), self.properties)
            host = host_pull(list(build_batches), "build_to_host")
            holder["side"] = _spill.partition_side(
                host, build_keys, k, spiller, "jb"
            )
            holder["spiller"] = spiller
            holder["k"] = k
            build_batches.clear()
            freed = ctx.reserved
            ctx.set_bytes(0)
            return freed

        handle = _spill.REVOCABLES.register(
            _spill.RevocableOperator("join", ctx, revoke_spill)
        )

        def joined(probe_stream):
            try:
                it = iter(probe_stream)
                for pb in it:
                    if handle.revoked:
                        # build spilled by the revoke tier: drop our device
                        # references, then this batch and the rest of the
                        # probe finish in waves against the spilled build
                        import itertools

                        op.release_build()
                        yield from _revoked_join_remainder(
                            make_op, holder, probe_keys,
                            itertools.chain([pb], it), ctx, observer,
                        )
                        return
                    yield op._join_batch(pb)
                ctx.close()
            finally:
                handle.finish()
                sp = holder.get("spiller")
                if sp is not None:
                    sp.close()

        return PhysicalPlan(span.wrap(joined, probe.stream, op), out_symbols)

    # -- memory-pressure join waves (spill analog) ----------------------------

    def _visit_SemiJoinNode(self, node: P.SemiJoinNode) -> PhysicalPlan:
        src = self.plan(node.source)
        filt = self.plan(node.filtering)
        residual = None
        residual_key = None
        if node.filter is not None:
            combined = PhysicalPlan(iter(()), src.symbols + filt.symbols)
            res_expr = combined.rewrite(node.filter)
            residual_key = res_expr.key()

            def residual(batch: Batch, _e=res_expr):
                return ExprCompiler(batch).filter_mask(_e)

        op = SemiJoinOperator(
            src.channel(node.source_key.name),
            filt.channel(node.filtering_key.name),
            filt.types(),
            null_aware=node.null_aware,
            residual=residual,
            residual_key=residual_key,
        )
        op.set_build(list(filt.stream))
        span = JoinSpan("semi", op)
        return PhysicalPlan(
            span.wrap(op.process, src.stream), src.symbols + [node.mark]
        )

    def _visit_WindowNode(self, node: P.WindowNode) -> PhysicalPlan:
        from trino_tpu.ops.window import WindowOperator, WindowSpec

        src = self.plan(node.source)
        part = [src.channel(s.name) for s in node.partition_by]
        order = [
            SortKey(src.channel(s.name), asc, nf)
            for s, asc, nf in node.order_by
        ]
        specs = []
        for out_sym, fn in node.functions:
            arg = None
            if fn.args:
                a0 = fn.args[0]
                arg = src.channel(a0.name)
            default_ch = None
            if fn.default is not None:
                default_ch = src.channel(fn.default.name)
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
        budget = self._budget()
        if budget and part:
            stream = _window_wave_stream(
                lambda: WindowOperator(part, order, specs),
                src.stream,
                list(part),
                int(budget),
                observer=self._observer(), spill_factory=self._make_spiller,
                properties=self.properties,
            )
        else:
            # global windows (no PARTITION BY) need every row at once —
            # no partition-disjoint wave exists
            op = WindowOperator(part, order, specs)
            stream = op.process(src.stream)
        return PhysicalPlan(stream, node.outputs)

    # -- ordering / limiting --------------------------------------------------

    def _sort_keys(self, plan: PhysicalPlan, orderings) -> list:
        return [
            SortKey(plan.channel(sym.name), ascending, nulls_first)
            for sym, ascending, nulls_first in orderings
        ]

    def _visit_SortNode(self, node: P.SortNode) -> PhysicalPlan:
        src = self.plan(node.source)
        op = OrderByOperator(
            self._sort_keys(src, node.orderings),
            memory_ctx=self.memory.child("sort"),
            spill_factory=self._make_spiller,
            observer=self._observer(),
        )
        return PhysicalPlan(op.process(src.stream), src.symbols)

    def _visit_TopNNode(self, node: P.TopNNode) -> PhysicalPlan:
        src = self.plan(node.source)
        op = TopNOperator(self._sort_keys(src, node.orderings), node.count)
        return PhysicalPlan(op.process(src.stream), src.symbols)

    def _visit_LimitNode(self, node: P.LimitNode) -> PhysicalPlan:
        src = self.plan(node.source)
        op = LimitOperator(node.count, getattr(node, "offset", 0))
        return PhysicalPlan(op.process(src.stream), src.symbols)

    # -- shape nodes ----------------------------------------------------------

    def _visit_UnionNode(self, node: P.UnionNode) -> PhysicalPlan:
        def stream():
            for child, mapping in zip(node.sources, node.source_symbols):
                sub = self.plan(child)
                exprs = []
                for m, out in zip(mapping, node.symbols):
                    if m.type.name == "unknown":
                        # a NULL-literal branch column: no castable values
                        exprs.append(Literal(None, out.type))
                        continue
                    e: Expr = InputRef(sub.channel(m.name), m.type)
                    if m.type.name != out.type.name:
                        # branch type narrower than the union's unified type
                        # (e.g. decimal cents unioned with double): a real
                        # CAST, not a relabel — decimals must descale
                        e = SpecialForm(Form.CAST, [e], out.type)
                    exprs.append(e)
                proj = FilterProjectOperator(None, exprs)
                yield from proj.process(sub.stream)

        return PhysicalPlan(stream(), node.symbols)

    def _visit_EnforceSingleRowNode(self, node: P.EnforceSingleRowNode) -> PhysicalPlan:
        src = self.plan(node.source)

        def stream():
            total = 0
            emitted = False
            for b in src.stream:
                n = b.num_rows_host()
                total += n
                if total > 1:
                    raise RuntimeError("Scalar sub-query has returned multiple rows")
                if n:
                    emitted = True
                    yield b
            if not emitted:
                import numpy as np

                cols = [
                    Column(
                        np.zeros(1, dtype=s.type.np_dtype),
                        s.type,
                        np.zeros(1, dtype=bool),
                    )
                    for s in src.symbols
                ]
                yield Batch(cols, np.ones(1, dtype=bool))

        return PhysicalPlan(stream(), src.symbols)

    def _visit_ExchangeNode(self, node: P.ExchangeNode) -> PhysicalPlan:
        # single-process execution: exchanges are pass-through; merge
        # exchanges re-sort to restore global order
        src = self.plan(node.source)
        if node.kind == "merge" and node.orderings:
            op = OrderByOperator(self._sort_keys(src, node.orderings))
            return PhysicalPlan(op.process(src.stream), src.symbols)
        return PhysicalPlan(src.stream, src.symbols)

    def _visit_OutputNode(self, node: P.OutputNode) -> PhysicalPlan:
        src = self.plan(node.source)
        if [s.name for s in src.symbols] != [s.name for s in node.symbols]:
            proj = FilterProjectOperator(
                None,
                [InputRef(src.channel(s.name), s.type) for s in node.symbols],
            )
            return PhysicalPlan(proj.process(src.stream), node.symbols)
        return PhysicalPlan(src.stream, node.symbols)


def _revoked_join_remainder(make_op, holder, probe_keys, probe_iter, ctx,
                            observer):
    """Finish a revoked join: the build already sits in spilled partitions
    (holder, written by the revoke callback); the unprocessed remainder of
    the probe stream partitions the same way and the join completes in
    waves.  Probe batches emitted BEFORE the revoke were fully joined
    against the complete build, so the split point is exact."""
    from trino_tpu.runtime import spill as _spill

    probe_host = host_pull(list(probe_iter), "probe_to_host")
    probe_side = _spill.partition_side(
        probe_host, probe_keys, holder["k"], holder["spiller"], "jp"
    )
    del probe_host
    yield from _spill.partition_wave_join(
        make_op, holder["side"], probe_side, holder["k"], ctx, observer
    )


def _agg_wave_stream(make_op, feed, key_channels: list, budget: int,
                     observer=None, spill_factory=None, properties=None):
    """Memory-bounded grouped aggregation: group-hash STATE waves.

    Reference role: HashAggregationOperator.startMemoryRevoke:449.  Input
    batches reduce to partial states immediately; when accumulated device
    state crosses a fraction of the budget it SPILLS — through the
    filesystem SPI (runtime/spill.SpillManager npz partitions) when
    `spill_enabled`, host RAM otherwise.  The final merge then runs in
    group-hash waves over the spilled states: hashing by the full group
    key keeps every group inside one wave, so per-wave merges are exact
    and group-disjoint.  Under-budget queries never spill and never copy:
    one device-side merge, identical to the unbudgeted path.

    The accumulating state is registered REVOCABLE: cross-query pressure
    can flush it to the spill tier early instead of killing a query.

    Aggregates without streamable partials (percentile) fall back to
    spooling RAW input and re-feeding each wave — the only shape that
    needs every group row at once.
    """
    import jax

    from trino_tpu.columnar.batch import concat_batches
    from trino_tpu.runtime import spill as _spill
    from trino_tpu.runtime.memory import (
        ExceededMemoryLimitException,
        batch_bytes,
    )

    if observer is None:
        observer = _spill.PressureObserver()
    op = make_op()
    if not op.streaming:
        yield from _agg_raw_wave_stream(
            make_op, op, feed, key_channels, budget, observer,
            spill_factory, properties,
        )
        return
    out_mode = "merge" if op.mode in ("partial", "merge") else "final"
    spill_at = max(budget // 4, 1)
    spiller = None
    spiller_made = False

    def get_spiller():
        nonlocal spiller, spiller_made
        if not spiller_made:
            spiller_made = True
            spiller = spill_factory() if spill_factory is not None else None
        return spiller

    acc: list = [None]  # created on first flush (lazy SpillingAccumulator)
    state = {"device": [], "bytes": 0}

    def flush() -> int:
        """Move accumulated device states to the spill tier; returns bytes
        freed.  Called by the owner (over spill_at) AND by the revoke tier
        (under the handle's reentrant lock)."""
        with handle.lock:
            if not state["device"]:
                return 0
            if acc[0] is None:
                acc[0] = _spill.SpillingAccumulator(get_spiller(), "aggstate")
            acc[0].push_chunk(host_pull(list(state["device"]), "spill"))
            state["device"].clear()
            freed = state["bytes"]
            state["bytes"] = 0
        if op.memory_ctx is not None:
            op.memory_ctx.set_bytes(0)
        return freed

    handle = _spill.REVOCABLES.register(
        _spill.RevocableOperator("aggregation", op.memory_ctx, flush)
    )
    seen_any = False
    try:
        for b in feed:
            seen_any = True
            s = op.reduce_batch(b)
            with handle.lock:
                state["device"].append(s)
                state["bytes"] += batch_bytes(s)
                cur = state["bytes"]
            over = cur > spill_at
            if op.memory_ctx is not None:
                try:
                    op.memory_ctx.set_bytes(cur)
                except ExceededMemoryLimitException:
                    over = True  # the reservation tree is the breach signal
                with handle.lock:
                    # a concurrent revoke may have flushed (and released)
                    # between our read of `cur` and the set_bytes above —
                    # re-sync so freed memory is not re-reserved; at most
                    # one revoke can ever fire per handle, so one
                    # correction pass closes the window
                    resync = (
                        state["bytes"] if state["bytes"] != cur else None
                    )
                if resync is not None:
                    try:
                        op.memory_ctx.set_bytes(resync)
                    except ExceededMemoryLimitException:
                        over = True
            if over:
                flush()
        handle.finish()  # merge phase: no longer revocable
        if not seen_any:
            op._acc = []
            yield op.finish()
            if op.memory_ctx is not None:
                op.memory_ctx.close()
            return
        if acc[0] is None:
            # under budget: plain device-side merge, no host round-trip
            device_states = state["device"]
            yield op._combine(
                device_states[0]
                if len(device_states) == 1
                else concat_batches(device_states),
                out_mode,
            )
            if op.memory_ctx is not None:
                op.memory_ctx.close()
            return
        flush()
        total = acc[0].total_bytes
        n_waves = _spill.wave_count(2 * total, budget, properties)
        observer.waves("aggregation", n_waves)
        for wave in range(n_waves):
            # wave selection happens HOST-side by dictionary VALUE hash
            # (state batches carry batch-local dictionaries, so device
            # code hashes would split one group across waves) and each
            # part is compacted before it returns to the device —
            # per-wave footprint is ~total/n_waves, what the budget bought
            parts = [
                jax.device_put(p)
                for p in acc[0].wave_parts(key_channels, n_waves, wave)
            ]
            if not parts:
                continue
            yield op._combine(
                parts[0] if len(parts) == 1 else concat_batches(parts),
                out_mode,
            )
        if op.memory_ctx is not None:
            op.memory_ctx.close()
    finally:
        handle.finish()
        if spiller is not None:
            spiller.close()


def _window_wave_stream(make_op, feed, key_channels: list, budget: int,
                        observer=None, spill_factory=None, properties=None):
    """Memory-bounded window execution: window functions only ever look
    within ONE partition, so hash-partitioning the input by the PARTITION BY
    keys into waves is exact — each wave materializes and sorts only its
    slice on device (reference role: the spill path of WindowOperator.java/
    PagesIndex, reshaped as partition-disjoint waves).  Over-budget input
    stages through the filesystem SPI when `spill_enabled`."""
    import jax

    from trino_tpu.runtime import spill as _spill
    from trino_tpu.runtime.memory import batch_bytes

    if observer is None:
        observer = _spill.PressureObserver()
    acc_dev: list = []
    store = None
    spiller = None
    total = 0
    seen_dicts: set = set()
    try:
        for b in feed:
            # shared dictionaries counted once across the accumulation
            total += batch_bytes(b, _seen_dicts=seen_dicts)
            if store is not None:
                store.push_chunk(host_pull([b], "spill"))
            else:
                acc_dev.append(b)
                if total > budget:
                    spiller = (
                        spill_factory() if spill_factory is not None else None
                    )
                    store = _spill.SpillingAccumulator(spiller, "window")
                    # device memory -> spill tier
                    store.push_chunk(host_pull(list(acc_dev), "spill"))
                    acc_dev.clear()
        if store is None:
            yield from make_op().process(iter(acc_dev))
            return
        n_waves = _spill.wave_count(2 * total, budget, properties)
        observer.waves("window", n_waves)
        for wave in range(n_waves):
            parts = store.wave_parts(key_channels, n_waves, wave)
            if not parts:
                continue
            yield from make_op().process(jax.device_put(p) for p in parts)
    finally:
        if spiller is not None:
            spiller.close()


def _agg_raw_wave_stream(make_op, op, feed, key_channels: list, budget: int,
                         observer=None, spill_factory=None, properties=None):
    """Raw-input waves for non-streamable aggregates (percentile): spool
    input to the spill tier once the budget is breached, then re-feed per
    wave."""
    import jax

    from trino_tpu.runtime import spill as _spill
    from trino_tpu.runtime.memory import ExceededMemoryLimitException

    if observer is None:
        observer = _spill.PressureObserver()
    it = iter(feed)
    spool = []
    over = False
    for b in it:
        spool.append(host_pull(b, "spill"))
        try:
            op.push(b)
            if op.state_bytes() > budget:
                over = True
        except ExceededMemoryLimitException:
            over = True  # the reservation tree is the breach signal
        if over:
            break
    if not over:
        yield op.finish()
        if op.memory_ctx is not None:
            op.memory_ctx.close()
        return
    consumed = len(spool)
    spool.extend(host_pull(list(it), "spill"))
    frac = consumed / max(len(spool), 1)
    projected = op.state_bytes() / max(frac, 1e-3)
    n_waves = _spill.wave_count(int(2 * projected), budget, properties)
    if op.memory_ctx is not None:
        op.memory_ctx.close()
    del op  # free the over-budget device state before wave 1
    spiller = spill_factory() if spill_factory is not None else None
    # n_waves is known BEFORE anything is written, so the raw input
    # partitions at write time (one file per wave, each read exactly once)
    # — the state-wave accumulator's k-pass re-read would multiply disk
    # I/O by k over data that is the RAW input, not compacted states
    side = _spill.partition_side(spool, key_channels, n_waves, spiller, "aggraw")
    spool = None
    observer.waves("aggregation", n_waves)
    try:
        for wave in range(n_waves):
            wop = make_op()
            for p in side.load_part(wave):
                wop.push(jax.device_put(p))
            yield wop.finish()
            if wop.memory_ctx is not None:
                wop.memory_ctx.close()
    finally:
        if spiller is not None:
            spiller.close()


def supports_uniform_distinct(node: "P.AggregationNode") -> bool:
    """The DISTINCT shape both _distinct_preagg and the distributed
    repartition path can express: every aggregate DISTINCT over one shared
    argument list, no FILTER clauses (the fragmenter and executor consult
    THIS predicate so plan- and run-time envelopes cannot diverge)."""
    distincts = [a for _, a in node.aggregations if a.distinct]
    return bool(distincts) and (
        len(distincts) == len(node.aggregations)
        and len({tuple(x.key() for x in a.args) for a in distincts}) == 1
        and all(a.filter is None for a in distincts)
    )


def build_distinct_dedupe(node: "P.AggregationNode", src) -> tuple:
    """(projection exprs, output symbols) of the DISTINCT dedupe
    pre-aggregation — group keys then the (uniform) distinct argument
    columns.  The ONE place this layout is decided; used by the local
    planner and the distributed single-stage path."""
    args0 = next(a for _, a in node.aggregations if a.distinct).args
    keys = [src.rewrite(s.ref()) for s in node.group_symbols]
    proj = keys + [src.rewrite(a) for a in args0]
    symbols = list(node.group_symbols) + [
        P.Symbol(a.name, a.type) for a in args0
    ]
    return proj, symbols


def defer_integer_averages(root: "P.OutputNode") -> tuple:
    """(plan, {output column: its count column}): every `avg(integer)` whose
    value only MOVES from its aggregation to the client -- through Output,
    Limit, Sort and TopN on other keys, projections that merely rename it,
    and UNION ALL branches that all do the same (ROLLUP's levels) -- is
    planned as `sum` and `count`, two BIGINT symbols that take the same
    way, the count as one more output column at the end; the caller
    divides where the rows reach the host (`divide_deferred`).  The chip
    holds no IEEE double (its float64 is a pair of float32), so a quotient
    that has been on the device is not the nearest double; `int / int` on
    the host is.  An average that anything computes on, sorts by or
    filters by stays a device double, as before."""
    from dataclasses import replace

    def pair(name: str) -> tuple:
        return (
            P.Symbol(name + "$sum", T.BIGINT), P.Symbol(name + "$count", T.BIGINT)
        )

    def split(node, name: str):
        """(node without `name` but with its sum and count, those two
        symbols), or None if anything but moves lies below."""
        if isinstance(node, (P.LimitNode, P.SortNode, P.TopNNode)):
            if any(o[0].name == name for o in getattr(node, "orderings", ())):
                return None
            got = split(node.source, name)
            return got and (node.with_children([got[0]]), *got[1:])
        if isinstance(node, P.ProjectNode):
            at = [i for i, (s, _) in enumerate(node.assignments) if s.name == name]
            ref = node.assignments[at[0]][1] if len(at) == 1 else None
            if not isinstance(ref, SymbolRef) or sum(
                ref.name in _symbol_names(e) for _, e in node.assignments
            ) != 1:
                return None
            got = split(node.source, ref.name)
            if got is None:
                return None
            source, total, count = got
            moved = list(node.assignments)
            moved[at[0]:at[0] + 1] = [(total, total.ref()), (count, count.ref())]
            return P.ProjectNode(source, moved), total, count
        if isinstance(node, P.UnionNode):
            k = [s.name for s in node.symbols].index(name)
            sources, mappings = [], []
            for child, mapping in zip(node.sources, node.source_symbols):
                if sum(m.name == mapping[k].name for m in mapping) != 1:
                    return None
                got = split(child, mapping[k].name)
                if got is None:
                    return None
                sources.append(got[0])
                mappings.append(
                    mapping[:k] + [got[1]] + mapping[k + 1:] + [got[2]]
                )
            total, count = pair(name)
            symbols = node.symbols[:k] + [total] + node.symbols[k + 1:] + [count]
            return P.UnionNode(sources, symbols, mappings), total, count
        if isinstance(node, P.AggregationNode) and node.step == "single":
            for i, (sym, agg) in enumerate(node.aggregations):
                if sym.name != name:
                    continue
                if (
                    agg.function != "avg" or agg.distinct
                    or agg.args[0].type.name not in _INTEGER_TYPES
                ):
                    return None
                total, count = pair(name)
                aggs = list(node.aggregations)
                aggs[i:i + 1] = [
                    (total, replace(agg, function="sum")),
                    (count, replace(agg, function="count")),
                ]
                return replace(node, aggregations=aggs), total, count
        return None

    counts: dict = {}
    if not isinstance(root, P.OutputNode):
        return root, counts
    for k, sym in enumerate(root.symbols):
        if sym.type.name != "double" or root.symbols.count(sym) != 1:
            continue
        got = split(root.source, sym.name)
        if got is None:
            continue
        source, total, count = got
        symbols = list(root.symbols)
        symbols[k] = total
        counts[k] = len(symbols)
        root = P.OutputNode(
            source, list(root.column_names) + [count.name], symbols + [count]
        )
    return root, counts


def divide_deferred(rows: list, counts: dict) -> list:
    """The rows of a plan `defer_integer_averages` split, as the client
    asked for them: each deferred average divided (`int / int` is the
    double nearest the exact quotient; NULL over no rows), the count
    columns dropped."""
    if not counts:
        return rows
    width = min(counts.values())
    out = []
    for r in rows:
        r = list(r)
        for k, c in counts.items():
            r[k] = None if not r[c] else r[k] / r[c]
        out.append(tuple(r[:width]))
    return out


_INTEGER_TYPES = ("tinyint", "smallint", "integer", "bigint")


def _symbol_names(expr) -> set:
    names: set = set()

    def walk(e):
        if isinstance(e, SymbolRef):
            names.add(e.name)
        for k in e.children():
            walk(k)

    walk(expr)
    return names


def build_agg_inputs(node: "P.AggregationNode", src) -> tuple:
    """(projection exprs, AggSpecs, input types) for an AggregationNode —
    the ONE place the aggregate input layout is decided (group keys first,
    then one computed arg per aggregate, FILTER folded as IF(filter, arg,
    NULL), two-input aggregates consuming two channels).  Shared by the
    local planner and the distributed partial-aggregation path so their
    channel layouts can never diverge.  Reference role: AggregationOperator
    input channels + the mask channel."""
    ngroups = len(node.group_symbols)
    proj: list = [src.rewrite(s.ref()) for s in node.group_symbols]
    specs: list = []
    input_types = [s.type for s in node.group_symbols]
    for out_sym, agg in node.aggregations:
        name = agg.function
        arg = src.rewrite(agg.args[0]) if agg.args else None
        if agg.filter is not None:
            f = src.rewrite(agg.filter)
            if name == "count_star":
                name = "count"
                arg = SpecialForm(
                    Form.IF,
                    [f, Literal(1, T.BIGINT), Literal(None, T.BIGINT)],
                    T.BIGINT,
                )
            else:
                arg = SpecialForm(
                    Form.IF, [f, arg, Literal(None, arg.type)], arg.type
                )
        if arg is None:
            specs.append(AggSpec(name, None, out_sym.type))
            continue
        proj.append(arg)
        input_types.append(arg.type)
        arg2_ch = None
        if len(agg.args) > 1:
            # two-input aggregates (map_agg key/value, covar/corr y/x)
            arg2 = src.rewrite(agg.args[1])
            if agg.filter is not None:
                f2 = src.rewrite(agg.filter)
                arg2 = SpecialForm(
                    Form.IF, [f2, arg2, Literal(None, arg2.type)], arg2.type
                )
            proj.append(arg2)
            input_types.append(arg2.type)
            arg2_ch = ngroups + len(specs_args(specs)) + 1
        specs.append(
            AggSpec(
                name,
                ngroups + len(specs_args(specs)),
                out_sym.type,
                param=getattr(agg, "param", None),
                arg2=arg2_ch,
                # planner range-certificate license (verify.numeric
                # license_decimal_sums): rides the plan node so the local,
                # partial, and merge kernels all read the same proof
                sum_bound=getattr(agg, "sum_bound", None),
            )
        )
    return proj, specs, input_types


def specs_args(specs: list) -> list:
    """Channels already consumed by aggregate args (for layout allocation).
    Two-input aggregates (map_agg) consume two slots."""
    out = []
    for s in specs:
        if s.arg is not None:
            out.append(s)
        if getattr(s, "arg2", None) is not None:
            out.append(s)
    return out


_MINMAX_STEP_CACHE: dict = {}

#: a build side of at most this many live rows prunes the probe scan by its
#: key SET; a larger one by its key range (reference:
#: DynamicFilterSourceOperator collects distinct values up to
#: dynamic-filtering.small.max-distinct-values-per-driver and only then
#: falls back to min/max).  The set compiles to that many comparisons
DYNAMIC_FILTER_SET_LIMIT = 64
#: and only a batch this small is read to the host for it
_DYNAMIC_FILTER_SET_CAPACITY = 1 << 17


def _build_key_domain(batches, channel: int):
    """What a materialized build column lets through: a frozenset of its
    live+valid values when they are few and have holes between them, else
    their (lo, hi), or None when the domain is empty/unfilterable
    (dictionary codes aren't portable across scans).

    The reduction runs ON DEVICE and only three scalars come back per batch
    (one host sync).  Pulling the whole column to host moves a build
    batch's bytes over PCIe and blocks the dispatch thread for the whole
    copy, so only a small batch of a build with few live keys is read, to
    tell WHICH keys: stores 1 and 11 as a range let 11/12 of a fact table
    through, as a set 2/12."""
    import numpy as np

    import jax.numpy as jnp

    lo = hi = None
    total = 0
    held = []  # (data, live) of the batches that hold a live key
    for b in batches:
        c = b.columns[channel]
        if c.dictionary is not None:
            return None
        if c.data.ndim > 1:
            return None  # long-decimal limb planes: no scalar range
        dt = np.dtype(c.data.dtype)
        if dt == np.dtype(bool):
            return None  # boolean join keys: range pruning is pointless
        step = _MINMAX_STEP_CACHE.get(dt.str)
        if step is None:

            def _step(data, live):
                if jnp.issubdtype(data.dtype, jnp.floating):
                    big = jnp.asarray(jnp.inf, data.dtype)
                    small = jnp.asarray(-jnp.inf, data.dtype)
                else:
                    info = jnp.iinfo(data.dtype)
                    big = jnp.asarray(info.max, data.dtype)
                    small = jnp.asarray(info.min, data.dtype)
                lo_ = jnp.min(jnp.where(live, data, big))
                hi_ = jnp.max(jnp.where(live, data, small))
                # the count apart, as int32: cast to a narrow key dtype
                # (int8/int16) it wraps to 0 at 256/65536 live rows and
                # would silently skip the batch
                return jnp.stack([lo_, hi_]), jnp.sum(live, dtype=jnp.int32)

            step = jit_program(_step, "minmax_stats")
            _MINMAX_STEP_CACHE[dt.str] = step
        live = b.mask()
        if c.valid is not None:
            live = jnp.logical_and(live, c.valid)
        (blo, bhi), n = host_pull(step(c.data, live), "dynamic_filter")
        if n == 0:
            continue
        total += int(n)
        held.append((c.data, live))
        lo = blo if lo is None else min(lo, blo)
        hi = bhi if hi is None else max(hi, bhi)
    if lo is None:
        return None
    if (
        np.dtype(held[0][0].dtype).kind in "iu"
        and total <= DYNAMIC_FILTER_SET_LIMIT
        and int(hi) - int(lo) >= total  # else the range says as much
        and all(d.shape[0] <= _DYNAMIC_FILTER_SET_CAPACITY for d, _ in held)
    ):
        keys = set()
        for data, live in host_pull(held, "dynamic_filter"):
            keys.update(int(k) for k in data[live])
        return frozenset(keys)
    return (lo, hi)


def _domain_expr(sym, domain) -> Expr:
    """The probe-side predicate of a build-side key domain."""
    if isinstance(domain, frozenset):
        t = sym.type
        if isinstance(t, T.DecimalType):
            from decimal import Decimal

            values = [Decimal(k) / t.scale_factor for k in sorted(domain)]
        else:
            values = sorted(domain)
        return SpecialForm(
            Form.IN, [sym.ref()] + [Literal(v, t) for v in values], T.BOOLEAN
        )
    return _range_expr(sym, *domain)


def _range_expr(sym, lo, hi) -> Expr:
    from decimal import Decimal

    from trino_tpu.expr.ir import and_, comparison

    t = sym.type
    if isinstance(t, T.DecimalType):
        lo_v = Decimal(int(lo)) / t.scale_factor
        hi_v = Decimal(int(hi)) / t.scale_factor
    elif t.np_dtype.kind == "f":
        lo_v, hi_v = float(lo), float(hi)
    else:
        lo_v, hi_v = int(lo), int(hi)
    return and_(
        comparison(">=", sym.ref(), Literal(lo_v, t)),
        comparison("<=", sym.ref(), Literal(hi_v, t)),
    )


def _compact_stream(stream):
    from trino_tpu.ops.common import next_pow2

    for b in stream:
        n = b.num_rows_host()
        cap = next_pow2(max(n, 1), floor=1024)
        if cap >= b.capacity:
            yield b
            continue
        yield COMPACT(b, out_capacity=cap)
