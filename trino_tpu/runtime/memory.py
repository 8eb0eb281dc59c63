"""Hierarchical memory accounting.

Reference: lib/trino-memory-context (AggregatedMemoryContext.java — the
operator -> driver -> pipeline -> task -> pool reservation tree) +
memory/MemoryPool.java:44.  Device HBM is the scarce resource here; batches
report their device footprint (capacity x dtype width, masks included) and
blocking operators reserve before materializing.  Exceeding the pool raises
ExceededMemoryLimitException — the hook where partition-wave fallback (the
spill analog, SURVEY.md §5.7, runtime/spill.py) takes over.

Thread safety: the tree shares ONE reentrant lock per root (children adopt
their parent's lock at construction), because a reservation mutates every
ancestor counter on the way up — two queries reserving on the shared
process pool concurrently would otherwise corrupt accounting or double-trip
the limit.  The `on_exceeded` hook (revoke tier + low-memory killer,
runtime/lifecycle + runtime/spill) is deliberately invoked OUTSIDE the
lock: revocation spills through operator code that takes its own locks and
re-enters the tree to release.
"""

from __future__ import annotations

import threading
from typing import Optional


class ExceededMemoryLimitException(RuntimeError):
    def __init__(self, message: str, node: Optional["MemoryContext"] = None):
        super().__init__(message)
        #: the tree node whose limit blocked the reservation (the pool root
        #: for cluster-wide pressure, a query context for per-query budgets)
        self.node = node


def dictionary_bytes(d) -> int:
    """Device-adjacent footprint of one dictionary: the i32 code-indexed
    lookup tables string kernels gather through, one validity byte per
    entry, plus the encoded value bytes staged for result rendering.
    PatternDictionary values are lazy (potentially huge); they account the
    fixed per-entry overhead without forcing materialization."""
    n = len(d)
    overhead = n * 4 + n  # i32 table + validity plane
    if not isinstance(d.values, tuple):
        # PatternDictionary: values are lazy and potentially huge — account
        # the fixed per-entry overhead without forcing them
        return overhead
    return overhead + sum(len(v) for v in d.values)


def batch_bytes(batch, _seen_dicts: "set | None" = None) -> int:
    """Device footprint of a Batch (columns + validity + row mask + the
    dictionaries its coded columns reference, each distinct dictionary
    counted once).  `_seen_dicts` lets `batches_bytes` dedupe shared
    dictionaries ACROSS a batch list."""
    total = 0
    seen_dicts = set() if _seen_dicts is None else _seen_dicts
    for c in batch.columns:
        total += c.data.size * c.data.dtype.itemsize
        if c.valid is not None:
            total += c.valid.size
        d = getattr(c, "dictionary", None)
        if d is not None and id(d) not in seen_dicts:
            seen_dicts.add(id(d))
            total += _cached_dictionary_bytes(d)
    if batch.row_mask is not None:
        # a shape attribute: accounting must not move the mask to the host
        total += batch.row_mask.size
    return int(total)


def batches_bytes(batches) -> int:
    """Footprint of a batch LIST with shared dictionaries counted once —
    accumulating operators (sort runs, agg states, join builds) must sum
    through this, or a dictionary shared by every scan batch would be
    multiplied by the batch count and spuriously trip the budget."""
    seen: set = set()
    return sum(batch_bytes(b, _seen_dicts=seen) for b in batches)


def _cached_dictionary_bytes(d) -> int:
    """Dictionary footprints are O(|dict|) walks over value strings;
    memoize ON the (immutable) dictionary object itself — an id()-keyed
    side table would go stale when CPython recycles a dead dictionary's
    address for a new one."""
    v = getattr(d, "_nbytes", None)
    if v is None:
        v = dictionary_bytes(d)
        try:
            # StringDictionary is frozen; write through the same escape
            # hatch its own lazy _hash uses
            object.__setattr__(d, "_nbytes", v)
        except AttributeError:  # no slot (foreign dict type): recompute
            pass
    return v


class MemoryContext:
    """One node in the reservation tree; reservations aggregate to the root
    pool (reference: AggregatedMemoryContext.newLocalMemoryContext).  The
    whole tree is guarded by its root's reentrant lock."""

    def __init__(self, parent: Optional["MemoryContext"] = None, name: str = "root",
                 limit_bytes: int = 0):
        self.parent = parent
        self.name = name
        self.limit_bytes = limit_bytes  # 0 = unlimited (checked at this node)
        self.reserved = 0
        self.peak = 0
        #: pool-root hook (reference: LowMemoryKiller): called as
        #: hook(blocked_node, requesting_ctx, delta) when a reservation
        #: exceeds this node's limit; True = something was freed, retry
        self.on_exceeded = None
        #: query roots registered on a pool root (killer victim candidates)
        self.query_children: list = []
        #: True for per-query root nodes (set by MemoryPool.query_context /
        #: lifecycle.query_memory_context): with resource-group sub-pools
        #: between the pool root and the query layer, depth no longer
        #: identifies the query node — the flag does
        self.is_query_root = False
        #: lifecycle QueryContext for query roots (killed victims abort
        #: through it at their next cooperative check)
        self.owner = None
        #: ONE lock per tree, shared down from the root: reservations climb
        #: ancestors, so per-node locks would deadlock or interleave
        self._lock = parent._lock if parent is not None else threading.RLock()

    def child(self, name: str) -> "MemoryContext":
        return MemoryContext(self, name)

    def query_root(self) -> "MemoryContext":
        """The query-level ancestor of this node: the nearest ancestor
        (or self) flagged `is_query_root`, falling back to the old
        depth-based rule (self when directly under the pool root, or
        detached) for trees built without the flag."""
        with self._lock:
            node = self
            while node is not None:
                if node.is_query_root:
                    return node
                node = node.parent
            node = self
            while node.parent is not None and node.parent.parent is not None:
                node = node.parent
            return node

    def set_bytes(self, n: int) -> None:
        """Set this node's reservation to exactly `n`.  The read-modify-
        write runs UNDER the tree lock (the RLock makes the nested
        `_reserve` climb reentrant) — computing the delta outside would
        let a concurrent set_bytes on the same context (the revoke tier
        zeroing an operator the owner is still accounting) interleave and
        corrupt ancestors with a stale delta.  The escalation hook is
        still invoked outside the lock, and the retry recomputes the
        delta fresh."""
        while True:
            delta = 0
            try:
                with self._lock:
                    delta = n - self.reserved
                    return self._reserve(delta)
            except ExceededMemoryLimitException as e:
                hook = getattr(e.node, "on_exceeded", None)
                if hook is None or delta <= 0 or not hook(e.node, self, delta):
                    raise

    def close(self) -> None:
        self.set_bytes(0)

    def add_bytes(self, delta: int) -> None:
        while True:
            try:
                return self._reserve(delta)
            except ExceededMemoryLimitException as e:
                # the escalation hook (revoke tier, then the low-memory
                # killer) lives on the pool root; a per-query budget (no
                # hook) propagates to the requester, which is the
                # wave/spill fallback's signal.  Called OUTSIDE the tree
                # lock: revocation runs operator spill code.
                hook = getattr(e.node, "on_exceeded", None)
                if (
                    hook is None
                    or delta <= 0
                    or not hook(e.node, self, delta)
                ):
                    raise

    def _reserve(self, delta: int) -> None:
        with self._lock:
            visited = []
            node = self
            try:
                while node is not None:
                    node.reserved += delta
                    visited.append(node)
                    # releases (delta <= 0) NEVER fail: after a mid-query
                    # limit shrink the tree may sit above the new limit,
                    # and refusing to give memory back would wedge it there
                    if (
                        delta > 0
                        and node.limit_bytes
                        and node.reserved > node.limit_bytes
                    ):
                        raise ExceededMemoryLimitException(
                            f"memory limit exceeded at {node.name}: "
                            f"{node.reserved} > {node.limit_bytes} bytes",
                            node=node,
                        )
                    node.peak = max(node.peak, node.reserved)
                    node = node.parent
            except ExceededMemoryLimitException:
                for v in visited:  # undo so accounting stays consistent
                    v.reserved -= delta
                raise

    def force_release(self) -> None:
        """Reclaim this subtree's accounting without cooperating with its
        operators (the killer's reclaim + end-of-statement cleanup): the
        reservation is subtracted from every ancestor and the node DETACHES
        from the tree, so late operator close() calls from a dying query can
        no longer corrupt the shared pool."""
        with self._lock:
            node, delta = self.parent, -self.reserved
            while node is not None:
                # a query root may be registered on BOTH its resource
                # group's sub-pool and the shared pool root — deregister
                # from every ancestor so neither escalation tier can pick
                # a detached victim
                if self in node.query_children:
                    node.query_children.remove(self)
                node.reserved += delta
                node = node.parent
            self.reserved = 0
            self.parent = None


class MemoryPool:
    """Per-query (or per-process) pool root (reference: MemoryPool.java:44)."""

    def __init__(self, limit_bytes: int = 0):
        self.root = MemoryContext(None, "pool", limit_bytes)

    def query_context(self, query_id: str, limit_bytes: int = 0) -> MemoryContext:
        ctx = self.root.child(f"query:{query_id}")
        ctx.limit_bytes = limit_bytes
        ctx.is_query_root = True
        with self.root._lock:
            self.root.query_children.append(ctx)
        return ctx
