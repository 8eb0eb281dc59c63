"""The engine's one launch door: every device program the engine compiles
is built by `jit_program`, which names it and counts its launches.

A `Program` is what the step caches (`ops/*._STEP_CACHE`, `TRACE_CACHE`)
store in place of a bare `jax.jit` wrapper.  It does three things:

  * it names the program: XLA's module is `jit_<step>`, so the profiler
    trace, the compile observatory and the benchmark's device-op breakdown
    carry the operator and step, not `jit_step` / `jit_local`;
  * every call bumps `QueryContext.launches` of the executing statement
    when it is made on the statement's own thread, and the thread's
    `FeedTally.launches` when it is made on one of its feed threads
    (always on: two contextvar reads and one attribute write);
  * with `query_trace` on it records a `launch` span (`feed_launch` on a
    feed thread's lane) carrying `step=` (and `path=`, the kernel path
    the step chose while it traced, see `note_path`).  The span is HOST
    DISPATCH time — the jit call to its return, tracing and compiling
    included when the call traced; the device runs the program
    afterwards, and its time is the profiler trace's, under the same
    name.

A launch on a thread that is neither the statement's nor a feed's (one
that copied the statement's context) is counted as the statement's and
records no span: only the statement's thread and the lanes keep a stack.
"""

from __future__ import annotations

import contextvars
import functools
from typing import Callable, Optional

import jax

from trino_tpu.runtime.lifecycle import current_feed, current_query
from trino_tpu.telemetry.spans import now

#: the Program whose step function jax is tracing right now (set by the
#: named wrapper, which runs only while a call traces — never per launch)
_TRACING: "contextvars.ContextVar[Optional[Program]]" = contextvars.ContextVar(
    "trino_tpu_tracing_program", default=None
)


def recording_tracer(ctx):
    """The tracer a door may record on for the statement `ctx`: the
    statement's tracer on its own thread, the lane on a feed thread that
    opened one; None with tracing off or on any other thread."""
    tracer = ctx.tracer
    if tracer is None or not tracer.enabled:
        return None
    return tracer.here()


class Program:
    """A named, jitted step.  Unknown attributes (`lower`, `trace`,
    `clear_cache`) resolve on the jitted function."""

    __slots__ = ("step", "path", "div128_rows", "jitted")

    def __init__(self, step: str):
        self.step = step
        #: kernel path(s) the step chose while tracing, "+"-joined
        self.path = ""
        #: rows its last trace divided by `int128.divround128` (see
        #: `note_div128`); 0 in a program that holds no such division
        self.div128_rows = 0
        self.jitted: Callable = None

    def __getattr__(self, name):
        return getattr(self.jitted, name)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Program({self.step!r})"

    def __call__(self, *args, **kwargs):
        ctx = current_query()
        tracer = None
        if ctx is not None:
            feed = current_feed()
            if feed is None:
                ctx.launches += 1
                tracer = recording_tracer(ctx)
            else:
                feed.launches += 1
                tracer = feed.tracer
        if tracer is None:
            out = self.jitted(*args, **kwargs)
        else:
            t0 = now()
            out = self.jitted(*args, **kwargs)
            attrs = {"step": self.step}
            if self.path:
                attrs["path"] = self.path
            if self.div128_rows:
                attrs["div128_rows"] = self.div128_rows
            tracer.last_launch = tracer.record(
                "launch" if feed is None else "feed_launch", t0, now(), attrs
            )
        if self.path:
            _count_paths(self.path)
        return out


def jit_program(fn: Callable, step: str, **jit_kwargs) -> Program:
    """`jax.jit(fn, **jit_kwargs)` behind the launch door, named `step`
    (operator and step, e.g. `agg_reduce`, `join_expand_unique`; the
    vocabulary is listed in `trino_tpu.telemetry`'s docstring).  `fn` may
    be a bound method or a shared function: it is wrapped, not renamed."""
    program = Program(step)

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        program.div128_rows = 0
        token = _TRACING.set(program)
        try:
            return fn(*args, **kwargs)
        finally:
            _TRACING.reset(token)

    traced.__name__ = traced.__qualname__ = step
    program.jitted = jax.jit(traced, **jit_kwargs)
    return program


def _count_paths(paths: str) -> None:
    from trino_tpu.telemetry.metrics import aggregation_path_counter

    counter = aggregation_path_counter()
    for p in paths.split("+"):
        counter.labels(p).inc()


def note_path(path: str) -> None:
    """Called by a step at the point it chooses a kernel path.  Under a
    tracing Program the choice is remembered on it and replayed on every
    launch (`path=` on the span, one bump of
    `trino_tpu_aggregation_path_total` per execution, no device read);
    a step that runs eagerly is counted here, once per run."""
    program = _TRACING.get()
    if program is None:
        _count_paths(path)
    elif path not in program.path.split("+"):
        program.path = f"{program.path}+{path}" if program.path else path


def note_div128(rows: int) -> None:
    """Called where an expression traces `int128.divround128` over `rows`
    rows (the batch's static capacity): the tracing Program adds them to
    its `div128_rows`, which every later launch carries on its span."""
    program = _TRACING.get()
    if program is not None:
        program.div128_rows += rows
