"""Background prefetch for the scan feed.

Reference role: execution/executor/TaskExecutor.java's overlap of IO-bound
split reads with compute — here a feed thread runs host-side page decode,
padding, and `jax.device_put` of batch k+1 while the main thread's XLA step
for batch k executes (device dispatch is async, so the two genuinely
overlap).  SURVEY.md §7's feed/step/drain pipeline.

A feed thread works for the statement that made the feed (`Feed`): it runs
as that statement, so its launches and pulls are counted, its spans are
recorded on a lane of the statement's tracer, cancellation reaches it and
a spill it registers is released with the statement's.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, Optional

from trino_tpu.runtime import lifecycle
from trino_tpu.telemetry.programs import recording_tracer
from trino_tpu.telemetry.spans import now

_SENTINEL = object()


class Feed:
    """The statement a feed works for, captured on the thread that makes
    the feed: its `QueryContext` and the span innermost there (the join's
    span for a probe side, else `build` or `execute`), under which the
    feed thread's lane hangs.  `kind` names the feed: eager-prefetch,
    scan-prefetch or local-exchange."""

    __slots__ = ("kind", "ctx", "parent")

    def __init__(self, kind: str):
        self.kind = kind
        self.ctx = lifecycle.current_query()
        self.parent = None
        if self.ctx is not None:
            tracer = recording_tracer(self.ctx)
            if tracer is not None:
                self.parent = tracer.innermost()

    def run(self, produce, *args):
        """`produce(tally, *args)` on the calling (feed) thread, as the
        statement: the thread's `_CURRENT` is the statement's and its
        `FeedTally` the thread's own; the lane (tracing on) opens first.
        The tally and the lane are handed back when `produce` returns or
        raises, so before the producer's last message can be taken: the
        statement sees every count of a feed it drained to the end."""
        ctx = self.ctx
        if ctx is None:
            return produce(None, *args)
        tracer = ctx.tracer
        lane = (
            tracer.lane(self.parent, self.kind)
            if tracer is not None and tracer.enabled else None
        )
        tally = lifecycle.FeedTally(self.kind, lane)
        token = lifecycle.set_current(ctx)
        feed_token = lifecycle.set_feed(tally)
        try:
            return produce(tally, *args)
        finally:
            lifecycle.reset_feed(feed_token)
            lifecycle.reset_current(token)
            if lane is not None:
                lane.close()
            ctx.feed_tallies.append(tally)

    def items(self, source: Iterable, tally) -> Iterator:
        """`source` on the producer thread: each item made under the
        lane's `tt:feed` annotation, after a cancellation check."""
        it = iter(source)
        lane = tally.tracer if tally is not None else None
        while True:
            if self.ctx is not None:
                self.ctx.check()
            if lane is None:
                item = next(it, _SENTINEL)
            else:
                with lane.producing():
                    item = next(it, _SENTINEL)
            if item is _SENTINEL:
                return
            yield item

    def take(self, q: queue.Queue):
        """The consumer's next message of `q`.  A get that has to wait is
        booked on the waiting thread (its tally, or the statement's
        `stmt_feed_wait_s`) and, tracing on, recorded as a `feed_wait`
        span with `feed` (this feed's kind) and `by` (`statement` or the
        waiting lane's kind)."""
        try:
            return q.get_nowait()
        except queue.Empty:
            pass
        ctx = self.ctx
        if ctx is None:
            return q.get()
        waiter = lifecycle.current_feed()
        tracer = recording_tracer(ctx) if waiter is None else waiter.tracer
        t0 = now()
        if tracer is None:
            item = q.get()
        else:
            by = "statement" if waiter is None else waiter.kind
            with tracer.span("feed_wait", feed=self.kind, by=by):
                item = q.get()
        if waiter is None:
            ctx.stmt_feed_wait_s += now() - t0
        else:
            waiter.wait_s += now() - t0
        return item


def _produce(tally, feed: Feed, source: Iterable, q: queue.Queue,
             stop: threading.Event) -> Optional[object]:
    """Put `source`'s items on `q`; returns the last message to put (None
    when the consumer stopped us)."""
    try:
        for item in feed.items(source, tally):
            if stop.is_set():
                return None
            q.put(item)
    except BaseException as e:  # propagate to consumer
        return (_SENTINEL, e)
    return _SENTINEL


def _start(feed: Feed, source: Iterable, depth: int):
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()

    def run():
        last = feed.run(_produce, feed, source, q, stop)
        if last is not None:
            q.put(last)

    threading.Thread(target=run, daemon=True, name=feed.kind).start()
    return q, stop


def _drain(feed: Feed, q: queue.Queue, stop: threading.Event) -> Iterator:
    try:
        while True:
            item = feed.take(q)
            if item is _SENTINEL:
                return
            if isinstance(item, tuple) and len(item) == 2 and item[0] is _SENTINEL:
                raise item[1]
            yield item
    finally:
        stop.set()
        # drain so a blocked producer can observe `stop` and exit
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


def eager_prefetch(source: Iterable, depth: int = 2) -> Iterator:
    """Like prefetch_iter but the producer thread starts NOW, not at the
    first next() — the pipeline-parallelism seam (reference: §2.7(4)
    build/probe overlap): a probe side wrapped eagerly decodes and feeds
    while the join's build side is still indexing on device.

    Shares prefetch_iter's producer (stop Event + finally-drain), so an
    abandoned consumer (LIMIT, planning failure after the join visit) stops
    the thread instead of leaving it blocked on a full queue."""
    feed = Feed("eager-prefetch")
    return _drain(feed, *_start(feed, source, depth))


def prefetch_iter(source: Iterable, depth: int = 2) -> Iterator:
    """Iterate `source` in a daemon thread, keeping up to `depth` results
    ready.  Exceptions in the producer re-raise at the consuming point."""
    feed = Feed("scan-prefetch")

    def lazy():
        yield from _drain(feed, *_start(feed, source, depth))

    return lazy()
