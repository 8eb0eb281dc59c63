"""Structured span tracer: one tree of timed spans per query.

Reference roles: io.opentelemetry spans threaded through DispatchManager ->
SqlQueryExecution -> exchange (the reference wires a Tracer through every
layer and tags spans with QueryId/StageId), and the Chrome-trace JSON the
trace is exported as loads directly in Perfetto / chrome://tracing.

Design constraints:

  * zero overhead when off — the shared NULL_TRACER's `span()` returns one
    preallocated no-op context manager and `record()` is a pass; hot paths
    additionally guard on `tracer.enabled` before building attribute dicts;
  * no host syncs — spans time HOST wall only (`now()` below); device work
    is attributed exactly the way MeshProfile already attributes it (the
    phase of the launch that dispatched it), so enabling tracing cannot add
    transfers and `verify.device_residency` holds with tracing on;
  * spans nest by runtime containment: the tracer keeps an open-span stack,
    `span()` pushes/pops, `record()` appends an already-closed child to the
    innermost open span (the shape `parallel/runner.py::_call` needs — it
    knows the duration only after the launch returned);
  * one open-span stack a thread: the statement's own thread records on
    the tracer, and each feed thread that works for the statement (a
    join's probe prefetch, a scan's prefetch, a split reader) on a `Lane`
    of it (`lane()`, from `runtime/prefetch.Feed`).  A lane's spans hang
    under its `feed` span, which hangs under the span that was innermost
    on the thread that made the feed;
  * one clock with the device: `span()` also enters a
    `jax.profiler.TraceAnnotation("tt:<name>")` for the span's lifetime, so
    in any profiler session (the benchmark's traced run, the `profile_dir`
    session property) the engine's spans lie in the host plane of the same
    `.xplane.pb` as the device ops, on the line of the thread that
    opened them.  A span with a `why` carries it as the annotation's
    metadata, formatted only inside a session.  `record()` writes none: a
    launch already has jax's own `PjitFunction(<step>)` host event
    (telemetry/programs.py).  Outside a session an annotation costs a
    flag test.
"""

from __future__ import annotations

import itertools
import json
import time
from threading import get_ident
from typing import Optional

from jax.profiler import TraceAnnotation

#: prefix of the engine's spans in a profiler trace's host plane
ANNOTATION_PREFIX = "tt:"

#: true inside a profiler session (bound once: a test may stand a fake in
#: for TraceAnnotation)
_profiling = TraceAnnotation.is_enabled

#: a feed thread's annotation while it makes an item (Lane.producing)
_FEED_ANNOTATION = ANNOTATION_PREFIX + "feed"

#: THE phase-timing clock.  Every engine-side wall measurement (spans,
#: MeshProfile phases, stage self-time) reads this one callable so span and
#: profile timestamps are directly comparable; tools/lint_tpu.py flags raw
#: `time.perf_counter()` phase timing added to device code outside here.
now = time.perf_counter


class Span:
    """One timed node of the query trace tree."""

    __slots__ = ("span_id", "parent_id", "name", "start_s", "end_s",
                 "attrs", "children")

    def __init__(self, span_id: int, parent_id: int, name: str,
                 start_s: float, attrs: Optional[dict] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_s = start_s
        self.end_s: Optional[float] = None
        self.attrs = attrs if attrs is not None else {}
        self.children: list[Span] = []

    def duration_s(self) -> float:
        end = self.end_s if self.end_s is not None else now()
        return max(0.0, end - self.start_s)

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_s": round(self.start_s, 6),
            "duration_ms": round(self.duration_s() * 1e3, 3),
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }


class _OpenSpan:
    """Context manager returned by SpanTracer.span()."""

    __slots__ = ("tracer", "sp", "annotation")

    def __init__(self, tracer: "SpanTracer", sp: Span):
        self.tracer = tracer
        self.sp = sp
        why = sp.attrs.get("why")
        if why is not None and _profiling():
            self.annotation = TraceAnnotation(
                ANNOTATION_PREFIX + sp.name, why=why
            )
        else:
            self.annotation = TraceAnnotation(ANNOTATION_PREFIX + sp.name)

    def __enter__(self) -> Span:
        self.annotation.__enter__()
        return self.sp

    def __exit__(self, et, ev, tb) -> bool:
        self.annotation.__exit__(et, ev, tb)
        self.sp.end_s = now()
        if et is not None:
            self.sp.attrs["error"] = et.__name__
        stack = self.tracer._stack
        if stack and stack[-1] is self.sp:
            stack.pop()
        return False


class _Restacked:
    """Context manager of SpanTracer.entered() and left(): for the body
    `sp` is pushed on the stack of open spans (or, `push` false, popped)."""

    __slots__ = ("stack", "sp", "push")

    def __init__(self, stack: list, sp: Span, push: bool):
        self.stack = stack
        self.sp = sp
        self.push = push

    def _move(self, push: bool) -> None:
        if push:
            self.stack.append(self.sp)
        elif self.stack and self.stack[-1] is self.sp:
            self.stack.pop()

    def __enter__(self) -> Span:
        self._move(self.push)
        return self.sp

    def __exit__(self, et, ev, tb) -> bool:
        self._move(not self.push)
        return False


class _NullCtx:
    """Shared no-op context manager (the off-path of span())."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *a) -> bool:
        return False


_NULL_CTX = _NullCtx()


class SpanTracer:
    """Per-query span tree, recorded by the thread that created the tracer
    (the statement's own, matching the reference's per-query trace
    context) and by the lanes of the statement's feed threads.

    The lane rule: each recording thread has its own stack of open spans
    and its own thread id -- the tracer's for the statement's thread, a
    `Lane`'s for a feed thread -- and `here()` finds the calling thread's.
    A thread that is neither records nothing.  What the threads share is
    the span ids and the child lists, and both are safe without a lock:
    `next()` of an `itertools.count` and `list.append` are each one step
    under the GIL, so two lanes never take one id and never lose a child.
    """

    enabled = True

    def __init__(self, query_id: str = ""):
        self.query_id = query_id
        self.root: Optional[Span] = None
        self._stack: list[Span] = []
        self._ids = itertools.count(1)
        self.t0 = now()
        self.thread_id = get_ident()
        #: the open lanes of the statement's feed threads, by thread id
        self._lanes: dict = {}
        #: the newest `launch` span the launch door recorded, so that
        #: StageExecutor._call can add its phase booking and compile
        #: children to it instead of recording the launch a second time
        self.last_launch: Optional[Span] = None

    # -- threads --------------------------------------------------------------

    def here(self) -> Optional["SpanTracer"]:
        """The tracer the calling thread records on: this one on the
        statement's thread, its lane on a feed thread that opened one,
        else None."""
        ident = get_ident()
        if ident == self.thread_id:
            return self
        return self._lanes.get(ident)

    def innermost(self) -> Optional[Span]:
        """The span a child recorded now would hang under."""
        return self._stack[-1] if self._stack else self.root

    def lane(self, parent: Optional[Span], kind: str) -> "Lane":
        """Open a lane for the calling (feed) thread: a `feed` span with
        `thread=kind` under `parent` (else the root), the bottom of the
        lane's own stack.  The thread closes it with `Lane.close()` when
        its feed ends."""
        lane = Lane(self, parent if parent is not None else self.root, kind)
        self._lanes[lane.thread_id] = lane
        return lane

    # -- recording ------------------------------------------------------------

    def span(self, name: str, **attrs) -> _OpenSpan:
        """Open a nested span; use as a context manager."""
        parent = self._stack[-1] if self._stack else None
        sp = Span(
            next(self._ids),
            parent.span_id if parent is not None else 0,
            name,
            now(),
            attrs,
        )
        if parent is not None:
            parent.children.append(sp)
        elif self.root is None:
            self.root = sp
        else:  # second top-level span: keep one tree, attach to the root
            sp.parent_id = self.root.span_id
            self.root.children.append(sp)
        self._stack.append(sp)
        return _OpenSpan(self, sp)

    def held(self, name: str, under: str = "", **attrs) -> Span:
        """A span an operator holds across the batches of its stream: a
        child of the innermost open span (of the innermost named `under`,
        if one is open), closed at birth (zero length) and NOT pushed.
        Its holder moves `start_s`/`end_s` as batches pass -- from any
        thread, the span is its own -- and wraps its own work in
        `entered()` so that the doors' spans nest under it."""
        t = now()
        for parent in reversed(self._stack):
            if parent.name == under:
                return self.attach(parent, name, t, t, attrs)
        return self.record(name, t, t, attrs)

    def left(self, sp: Optional[Span]):
        """The inverse of `entered()`: for the body `sp` is off the calling
        thread's stack if it is the innermost open span there (an operator
        pulling its input, whose work is not its own)."""
        here = self.here() if sp is not None else None
        if here is None or not here._stack or here._stack[-1] is not sp:
            return _NULL_CTX
        return _Restacked(here._stack, sp, push=False)

    def entered(self, sp: Optional[Span]):
        """Context manager: `sp` (from `held()`) is the innermost open
        span of the calling thread's stack for the body.  On a thread that
        records nothing it is a no-op."""
        here = self.here() if sp is not None else None
        if here is None:
            return _NULL_CTX
        return _Restacked(here._stack, sp, push=True)

    def record(self, name: str, start_s: float, end_s: float,
               attrs: Optional[dict] = None) -> Optional[Span]:
        """Append an already-measured leaf span under the innermost open
        span (launch sites know their duration only after the fact).
        Returns the span so the caller can attach() children to it (compile
        stalls nest under their launch)."""
        parent = self._stack[-1] if self._stack else self.root
        sp = Span(
            next(self._ids),
            parent.span_id if parent is not None else 0,
            name,
            start_s,
            attrs,
        )
        sp.end_s = end_s
        if parent is not None:
            parent.children.append(sp)
        elif self.root is None:
            self.root = sp
        return sp

    def attach(self, parent: Span, name: str, start_s: float, end_s: float,
               attrs: Optional[dict] = None) -> Span:
        """Graft an already-closed span under an explicit parent (compile
        child spans of a launch; worker span trees merged under the
        coordinator's fragment span by the multi-host scheduler)."""
        sp = Span(next(self._ids), parent.span_id, name, start_s, attrs)
        sp.end_s = end_s
        parent.children.append(sp)
        return sp

    def graft(self, parent: Span, tree: dict, offset_s: float = 0.0) -> Span:
        """Merge a foreign span tree (Span.to_dict form — e.g. a worker
        task's spans pulled over HTTP) under `parent`, re-issuing span ids
        from THIS tracer so the merged trace has one id space.  `offset_s`
        shifts the foreign clock onto ours: worker `now()` readings are
        per-process perf counters with unrelated epochs, so the caller
        anchors the foreign root at a locally-observed instant (task
        submission) and every descendant keeps its relative position."""
        start = float(tree["start_s"]) + offset_s
        sp = self.attach(
            parent, tree["name"], start,
            start + float(tree.get("duration_ms", 0.0)) / 1e3,
            dict(tree.get("attrs") or {}),
        )
        for child in tree.get("children", ()):
            self.graft(sp, child, offset_s)
        return sp

    # -- export ---------------------------------------------------------------

    def _walk(self):
        def rec(sp):
            yield sp
            for c in sp.children:
                yield from rec(c)

        if self.root is not None:
            yield from rec(self.root)

    def count(self) -> int:
        """Spans in the tree (QueryStatistics.spans)."""
        return sum(1 for _ in self._walk())

    def flat_spans(self) -> list:
        """Depth-first flattened spans as plain dicts (the
        system.runtime.spans feed)."""
        out = []
        for sp in self._walk():
            out.append(
                {
                    "query_id": self.query_id,
                    "span_id": sp.span_id,
                    "parent_id": sp.parent_id,
                    "name": sp.name,
                    "start_ms": round((sp.start_s - self.t0) * 1e3, 3),
                    "duration_ms": round(sp.duration_s() * 1e3, 3),
                    "attributes": json.dumps(sp.attrs, default=str)
                    if sp.attrs
                    else "",
                }
            )
        return out

    def to_chrome_trace(self) -> dict:
        """Chrome-trace JSON (the 'traceEvents' array form): loads in
        Perfetto (ui.perfetto.dev) and chrome://tracing.  Complete ('X')
        events; ts/dur in microseconds relative to query admission."""
        events = []
        for sp in self._walk():
            events.append(
                {
                    "ph": "X",
                    "name": sp.name,
                    "cat": "query",
                    "ts": round((sp.start_s - self.t0) * 1e6, 1),
                    "dur": round(sp.duration_s() * 1e6, 1),
                    "pid": 1,
                    "tid": 1,
                    "args": {k: str(v) for k, v in sp.attrs.items()},
                }
            )
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"query_id": self.query_id},
        }

    def render_text(self) -> str:
        """Indented span tree (the EXPLAIN ANALYZE VERBOSE rendering)."""
        lines = [f"Query trace (spans, query_id={self.query_id}):"]

        def rec(sp: Span, depth: int) -> None:
            attrs = ""
            if sp.attrs:
                attrs = " " + " ".join(
                    f"{k}={v}" for k, v in sp.attrs.items()
                )
            lines.append(
                "  " * (depth + 1)
                + f"{sp.name} {sp.duration_s() * 1e3:.2f}ms{attrs}"
            )
            for c in sp.children:
                rec(c, depth + 1)

        if self.root is not None:
            rec(self.root, 0)
        return "\n".join(lines)


class Lane(SpanTracer):
    """The recording state of one feed thread of a statement: its own
    stack of open spans, whose bottom is the lane's `feed` span, over the
    statement tracer's ids and tree (see the lane rule on SpanTracer).
    The `feed` span runs from the thread's first item to its last; the
    producer wraps the making of each item in `producing()`, so that the
    thread's `tt:feed` annotation is open while it works and closed while
    it waits to hand an item over."""

    def __init__(self, owner: SpanTracer, parent: Span, kind: str):
        self.owner = owner
        self.query_id = owner.query_id
        self.root = owner.root
        self._ids = owner._ids
        self._lanes = owner._lanes
        self.t0 = owner.t0
        self.thread_id = get_ident()
        self.last_launch = None
        t = now()
        self.feed = owner.attach(parent, "feed", t, t, {"thread": kind})
        self._stack = [self.feed]

    def here(self) -> Optional[SpanTracer]:
        return self.owner.here()

    def producing(self) -> "_Producing":
        """Context manager around the making of one item."""
        return _Producing(self)

    def close(self) -> None:
        self.feed.end_s = now()
        self._lanes.pop(self.thread_id, None)


class _Producing:
    __slots__ = ("lane", "annotation")

    def __init__(self, lane: Lane):
        self.lane = lane
        self.annotation = TraceAnnotation(_FEED_ANNOTATION)

    def __enter__(self) -> None:
        self.annotation.__enter__()

    def __exit__(self, et, ev, tb) -> bool:
        self.annotation.__exit__(et, ev, tb)
        self.lane.feed.end_s = now()
        return False


class NullTracer:
    """The off state: every operation is a no-op; `span()` hands back one
    shared context manager so the off-path allocates nothing."""

    enabled = False
    query_id = ""
    root = None

    def span(self, name: str, **attrs) -> _NullCtx:
        return _NULL_CTX

    def record(self, name, start_s, end_s, attrs=None) -> None:
        pass

    def held(self, name: str, under: str = "", **attrs) -> None:
        return None

    def entered(self, sp) -> _NullCtx:
        return _NULL_CTX

    left = entered

    def attach(self, parent, name, start_s, end_s, attrs=None) -> None:
        pass

    def graft(self, parent, tree, offset_s=0.0) -> None:
        pass

    def flat_spans(self) -> list:
        return []

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def render_text(self) -> str:
        return "Query trace: tracing disabled (SET SESSION query_trace = true)"


#: the shared off-tracer (identity-comparable: `tracer is NULL_TRACER`)
NULL_TRACER = NullTracer()
