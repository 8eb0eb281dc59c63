"""The measured window: replays the mix against the served system and keeps
what every statement returned to the client.

Rule of the window.  It starts when set-up is done, and runs whole passes
of the mix: a closed loop stops at the first pass boundary at or after
`--seconds` (so every window of a cell holds the same mix of statements,
whatever the seed's order); an open loop issues the statements that are due
within `--seconds`, cut down to whole passes, and waits for the last answer.
Every statement counts, and the window's time is from its start to the last
completion.
"""

from __future__ import annotations

import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark.trace_reduce import STATEMENT_PREFIX, WINDOW_ANNOTATION

#: an answer is waited for this long past the close of an open window; later
#: is counted as missing
LATE_S = 60.0


class DeviceTrace:
    """A `jax.profiler` trace of the first part of the window, ended on a
    pass boundary.  Python tracing is off (it would dwarf the work of a
    host-bound statement); host TraceMe events are on, so that the
    benchmark's own annotations land in the same file as the device ops."""

    def __init__(self, directory: str, min_seconds: float):
        self.directory = directory
        self.min_seconds = min_seconds
        self.active = False
        self.started_s = 0.0
        self.stopped_s = 0.0
        self._outer = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._outer = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._outer.__enter__()
        self.started_s = time.perf_counter()
        self.active = True

    def pass_done(self) -> None:
        if self.active and (
            time.perf_counter() - self.started_s >= self.min_seconds
        ):
            self.stop()

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self._outer.__exit__(None, None, None)
        self.stopped_s = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    def covered(self, statements: list) -> list:
        """The statements that ran wholly inside the traced part."""
        return [
            st for st in statements
            if st.start_s >= self.started_s and st.end_s <= self.stopped_s
        ]


class GcWatch:
    """Seconds the interpreter spent in garbage collection (the harness and
    the served system share one process, so a collection stalls both).  A
    fact for the statement lines, not a metric."""

    def __init__(self):
        self.total_s = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.total_s += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._event)


def _execute(served, client, st, annotate: bool, clear_pool: bool,
             gc_watch: GcWatch | None = None) -> None:
    """One client call, timed from the call to the last row in hand."""
    if clear_pool:
        served.clear_pool()
    gc0 = gc_watch.total_s if gc_watch else 0.0
    cpu0 = time.process_time()
    if annotate:
        import jax

        scope = jax.profiler.TraceAnnotation(
            f"{STATEMENT_PREFIX}{st.query}#{st.seq}"
        )
    else:
        scope = None
    st.start_s = time.perf_counter()
    try:
        if scope is not None:
            with scope:
                _, st.rows = client.execute(st.sql)
        else:
            _, st.rows = client.execute(st.sql)
    except Exception as exc:  # a failed statement is counted, not raised
        st.error = f"{type(exc).__name__}: {exc}"[:500]
    st.end_s = time.perf_counter()
    # what the host was doing meanwhile: process CPU seconds (all threads)
    # and seconds inside the garbage collector
    st.extra["cpu_s"] = time.process_time() - cpu0
    if gc_watch:
        st.extra["gc_s"] = gc_watch.total_s - gc0


def run(served, mix, seconds: float, trace: DeviceTrace | None,
        collect: bool) -> dict:
    """Drive the window.  `collect` reads the program's spans, counters and
    mesh profile after each statement (the traced run only)."""
    done: list = []
    gc_watch = GcWatch()
    lock = threading.Lock()
    spans: list = []
    t0 = time.perf_counter()
    if trace is not None:
        trace.start()

    def tracing() -> bool:
        return trace is not None and trace.active

    def after(st) -> None:
        if collect:
            st.extra["mesh_profile"] = served.mesh_profile()
        with lock:
            if collect:
                spans.extend(served.drain_spans())
            done.append(st)

    def closed_stream(stream: int) -> None:
        client = served.client()
        while True:
            for st in mix.next_pass(stream):
                _execute(served, client, st, tracing(), mix.clear_pool,
                         gc_watch)
                after(st)
            if stream == 0 and trace is not None:
                trace.pass_done()
            if time.perf_counter() - t0 >= seconds:
                return

    def open_loop() -> None:
        per_pass = len(mix.queries)
        total = int(seconds * float(mix.open_rate)) // per_pass * per_pass
        total = max(total, per_pass)
        todo = []
        while len(todo) < total:
            todo.extend(mix.next_pass(0))
        gap = 1.0 / float(mix.open_rate)
        clients = threading.local()

        def one(st) -> None:
            if not hasattr(clients, "c"):
                clients.c = served.client()
            _execute(served, clients.c, st, tracing(), mix.clear_pool,
                     gc_watch)
            after(st)

        with ThreadPoolExecutor(max_workers=64) as pool:
            futures = []
            for i, st in enumerate(todo):
                st.due_s = t0 + i * gap
                wait = st.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                futures.append(pool.submit(one, st))
            deadline = time.perf_counter() + LATE_S
            for f, st in zip(futures, todo):
                try:
                    f.result(timeout=max(0.0, deadline - time.perf_counter()))
                except Exception as exc:
                    st.error = st.error or f"no answer: {type(exc).__name__}"
                    st.end_s = time.perf_counter()
                    after(st)

    if mix.loop == "open":
        open_loop()
    elif mix.streams <= 1:
        closed_stream(0)
    else:
        threads = [
            threading.Thread(target=closed_stream, args=(s,),
                             name=f"bench-stream-{s}")
            for s in range(mix.streams)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if trace is not None:
        trace.stop()
    gc_watch.close()
    t_end = max((st.end_s for st in done), default=time.perf_counter())
    return {"statements": done, "t0": t0, "t_end": t_end, "spans": spans}
