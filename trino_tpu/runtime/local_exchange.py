"""Local (intra-task) exchange: N producer threads feeding one consumer.

Reference role: operator/exchange/LocalExchange.java + the `task_concurrency`
session property — the reference splits a task's pipeline into parallel
drivers connected by an in-memory exchange.  Here the device pipeline is one
XLA stream (the compiler owns that parallelism), so the concurrency that
matters is HOST-side: split reading, page decoding and host->device feeding.
This exchange runs those producers on a thread pool with a bounded buffer
(backpressure), preserving no particular order (like the reference's
arbitrary-distribution local exchange).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Sequence

from trino_tpu.runtime.prefetch import Feed


_DONE = object()


def parallel_feed(
    makers: Sequence[Callable[[], Iterable]],
    workers: int,
    buffer: int = 8,
) -> Iterator:
    """Drain `makers` (thunks returning iterables) concurrently on `workers`
    threads; yield items as they arrive.

    A producer exception is re-raised at the consumer promptly (in-flight
    items after a failure are dropped, not yielded).  If the CONSUMER
    abandons the generator (LIMIT, downstream error), the finally block
    stops the producers and drains the queue so no thread stays blocked on a
    full buffer pinning device batches.  The producers work for the
    statement that called this (`prefetch.Feed`, kind local-exchange)."""
    if workers <= 1 or len(makers) <= 1:
        return (item for mk in makers for item in mk())
    return _exchange(Feed("local-exchange"), makers, workers, buffer)


def _exchange(feed: Feed, makers, workers: int, buffer: int) -> Iterator:
    q: "queue.Queue" = queue.Queue(maxsize=max(buffer, workers))
    pending = list(enumerate(makers))
    lock = threading.Lock()
    stop = threading.Event()
    n_workers = min(workers, len(makers))
    errors: list = []

    def produce(tally):
        while not stop.is_set():
            with lock:
                if errors or not pending:
                    break
                _, mk = pending.pop(0)
            try:
                for item in feed.items(mk(), tally):
                    if stop.is_set() or errors:
                        break
                    q.put(item)
            except BaseException as e:  # noqa: BLE001 - relayed to consumer
                with lock:
                    errors.append(e)
                break

    def worker():
        feed.run(produce)
        q.put(_DONE)

    threads = [
        threading.Thread(
            target=worker, daemon=True, name=f"local-exchange-{i}"
        )
        for i in range(n_workers)
    ]
    for t in threads:
        t.start()
    try:
        done = 0
        while done < n_workers:
            item = feed.take(q)
            if item is _DONE:
                done += 1
                continue
            with lock:
                failed = bool(errors)
            if failed:
                continue  # drop in-flight items after a failure
            yield item
        if errors:
            raise errors[0]
    finally:
        stop.set()
        # unblock any producer waiting on a full queue
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
