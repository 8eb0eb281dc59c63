"""One execution for a join tree that a plan holds more than once.

The logical planner lowers `GROUP BY ROLLUP / CUBE / GROUPING SETS` to one
UNION ALL branch per grouping set, each over its own copy of the input
(`planner/logical_planner.py`, "GroupIdNode analog").  The optimizer then
prunes every copy to what its consumer reads, so the copies differ at most
in the columns they carry.  The local runner used to run every copy:
TPC-DS Q27's `ROLLUP(i_item_id, s_state)` scanned store_sales and joined
its four dimensions three times over.

Here the copies are found in the optimized plan (`repeated_inputs`), the
widest is planned once, and every consumer reads its batches through a
`SharedInput`: the first to get there pulls from the one stream, the others
replay what it left, each restricted to its own columns.  Only the local
runner does this (`LocalExecutionPlanner.share_repeated_inputs`); the plan
itself is unchanged, so the mesh and worker runners see what they saw.
"""

from __future__ import annotations

import threading

from trino_tpu.planner import plan as P

#: bytes one shared input may hold for its later readers.  Past it, while no
#: second reader has been planned yet, the input is given up: the first
#: reader goes on alone and the others run their own copy, as before.
SHARED_INPUT_LIMIT = 1 << 30


def covers(a: P.PlanNode, b: P.PlanNode) -> bool:
    """Whether `b` yields `a`'s rows, in `a`'s order, cut to `b`'s columns:
    the same scans under the same predicates, joined and filtered the same
    way, `b` carrying no column that `a` lacks.  Symbols are compared by
    value: copies of one subtree keep their symbols (`plan.copy_tree`),
    while a CTE named twice is planned twice, under other symbols, and so
    is never taken for a copy."""
    if type(a) is not type(b):
        return False
    if isinstance(a, P.TableScanNode):
        return (
            a.handle == b.handle
            and a.pushed_predicate == b.pushed_predicate
            and all(pair in a.assignments for pair in b.assignments)
        )
    if isinstance(a, P.FilterNode):
        return a.predicate == b.predicate and covers(a.source, b.source)
    if isinstance(a, P.ProjectNode):
        return all(
            pair in a.assignments for pair in b.assignments
        ) and covers(a.source, b.source)
    if isinstance(a, P.JoinNode):
        return (
            a.kind == b.kind
            and a.criteria == b.criteria
            and a.filter == b.filter
            and covers(a.left, b.left)
            and covers(a.right, b.right)
        )
    if isinstance(a, P.SemiJoinNode):
        return (
            (a.source_key, a.filtering_key, a.mark, a.filter, a.null_aware)
            == (b.source_key, b.filtering_key, b.mark, b.filter, b.null_aware)
            and covers(a.source, b.source)
            and covers(a.filtering, b.filtering)
        )
    # any other operator below a join (an aggregated sub-query, a VALUES):
    # only an exact copy; a sample draws anew for every reader
    return not isinstance(a, P.SampleNode) and a == b


def repeated_inputs(root: P.PlanNode) -> list:
    """Groups `[widest, other, ...]` of disjoint join trees of `root` of
    which the first covers every other, outermost trees first."""
    tops = [
        n for n in P.walk(root) if isinstance(n, (P.JoinNode, P.SemiJoinNode))
    ]
    claimed: set = set()
    groups = []
    for i, first in enumerate(tops):
        if id(first) in claimed:
            continue
        peers = [first] + [
            n for n in tops[i + 1:]
            if id(n) not in claimed
            and (covers(first, n) or covers(n, first))
        ]
        widest = next(
            (w for w in peers if all(covers(w, n) for n in peers)), None
        )
        if widest is None or len(peers) < 2:
            continue
        groups.append([widest] + [n for n in peers if n is not widest])
        for member in peers:
            claimed.update(id(n) for n in P.walk(member))
    return groups


class SharedInput:
    """The one stream of a group of `repeated_inputs`, and what it has
    yielded so far, kept for the readers that come later."""

    def __init__(self, members: list):
        self.widest = members[0]
        self._members = len(members)
        self._lock = threading.RLock()
        self._layout = None  # the widest's PhysicalPlan, once planned
        self._source = None
        self._kept: list = []
        self._bytes = 0
        self._exhausted = False
        self._handed_out = 0
        self._finished = 0
        self.given_up = False

    def plan_for(self, planner, node: P.PlanNode):
        """The physical plan of `node`, a member of this group.  Planning
        happens here, on the caller's thread, as it would without sharing:
        the widest member the first time, nothing later -- or `node`'s own
        subtree once the input was given up."""
        from trino_tpu.runtime.local_planner import PhysicalPlan

        with self._lock:
            if self.given_up:
                return planner.plan_unshared(node)
            if self._layout is None:
                # a dynamic filter from a join ABOVE one member says nothing
                # of the rows another member's consumer needs
                outer, planner.dynamic_filters = planner.dynamic_filters, {}
                try:
                    self._layout = planner.plan_unshared(self.widest)
                finally:
                    planner.dynamic_filters = outer
                self._source = iter(self._layout.stream)
            self._handed_out += 1
            channels = [self._layout.channel(s.name) for s in node.outputs]
            if channels == list(range(len(self._layout.symbols))):
                channels = None  # the widest member itself: batches as they are
        return PhysicalPlan(self._read(channels), node.outputs)

    def _read(self, channels):
        try:
            at = 0
            while True:
                batch = self._batch(at)
                if batch is None:
                    return
                at += 1
                yield batch if channels is None else batch.project(channels)
        finally:
            with self._lock:
                self._finished += 1
                if self._finished == self._members:
                    self._kept.clear()

    def _batch(self, at: int):
        """Batch number `at` of the stream, pulled if no reader has come
        that far yet; None past the end."""
        from trino_tpu.runtime.memory import batch_bytes

        with self._lock:
            if self.given_up:
                return next(self._source, None)
            if at < len(self._kept):
                return self._kept[at]
            if self._exhausted:
                return None
            batch = next(self._source, None)
            if batch is None:
                self._exhausted = True
                return None
            self._bytes += batch_bytes(batch)
            if self._bytes > SHARED_INPUT_LIMIT and self._handed_out == 1:
                self.given_up = True
                self._kept.clear()
            else:
                self._kept.append(batch)
            return batch
