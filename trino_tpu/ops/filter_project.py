"""Filter + project operator (reference: FilterAndProjectOperator +
the generated PageFilter/PageProjection from sql/gen/PageFunctionCompiler).

One jitted step evaluates the predicate and all projections over a batch; XLA
fuses everything into a single device program.  Output stays masked (no
compaction) — downstream operators work on masks; compaction happens only at
exchange/result boundaries.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

from trino_tpu.columnar import Batch
from trino_tpu.expr import ExprCompiler
from trino_tpu.expr.ir import Call, Expr
from trino_tpu.telemetry.programs import jit_program

#: functions that must evaluate eagerly (host-side per-row rendering):
#: projections containing one run the step unjitted
EAGER_FUNCS = frozenset({"array_join", "format", "concat_ws"})


def _needs_eager(e: Expr, _seen: set = None) -> bool:
    if _seen is None:
        _seen = set()
    if id(e) in _seen:  # shared-DAG guard (see ir.visit)
        return False
    _seen.add(id(e))
    if isinstance(e, Call) and e.name in EAGER_FUNCS:
        return True
    return any(_needs_eager(c, _seen) for c in e.children())


#: process-level jitted-step cache, keyed by expression structure — operator
#: instances are per-query, but identical programs (same exprs) reuse one jit
#: wrapper so repeated queries skip retracing (reference analog: the
#: PageFunctionCompiler's generated-class cache, sql/gen/PageFunctionCompiler
#: .java:103)
_STEP_CACHE: dict = {}


class FilterProjectOperator:
    def __init__(self, predicate: Optional[Expr], projections: Sequence[Expr]):
        self.predicate = predicate
        self.projections = list(projections)
        key = (
            None if predicate is None else predicate.key(),
            tuple(e.key() for e in projections),
        )
        cached = _STEP_CACHE.get(key)
        if cached is None:
            step = self._make_step()
            exprs = ([] if predicate is None else [predicate]) + list(
                projections
            )
            # expressions with host-eager functions (per-row string renders
            # that can't trace) run the same step without jit
            cached = (
                step if any(map(_needs_eager, exprs))
                else jit_program(step, "filter_project")
            )
            _STEP_CACHE[key] = cached
        self._step = cached

    def _make_step(self):
        pred, projs = self.predicate, self.projections

        def step(batch: Batch) -> Batch:
            c = ExprCompiler(batch)
            out = batch
            if pred is not None:
                out = out.filter(c.filter_mask(pred))
            cols = [c.column(e) for e in projs]
            if not cols:
                # zero-column projection (`count(*)` over bare rows): the
                # row count must ride the materialized mask, else capacity
                # collapses to 0
                return Batch(cols, out.mask())
            return Batch(cols, out.row_mask)

        return step

    def fusable_step(self):
        """(raw untraced step, structural key) for fusion INTO a downstream
        operator's jitted program (e.g. the aggregation partial step), or
        (None, None) when the expressions need host-eager evaluation.
        Fusion removes the materialize-then-reload of projection outputs —
        on TPU that is HBM traffic, on CPU cache traffic."""
        exprs = ([] if self.predicate is None else [self.predicate]) + list(
            self.projections
        )
        if any(map(_needs_eager, exprs)):
            return None, None
        key = (
            None if self.predicate is None else self.predicate.key(),
            tuple(e.key() for e in self.projections),
        )
        raw = _STEP_CACHE.get(("raw", key))
        if raw is None:
            # cache the RAW closure too: the consumer bakes it into its own
            # jitted program keyed by `key`, so the closure identity must be
            # stable across queries or every query would retrace
            raw = self._make_step()
            _STEP_CACHE[("raw", key)] = raw
        return raw, key

    def process(self, stream):
        for batch in stream:
            yield self._step(batch)
