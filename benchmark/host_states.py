"""The device's idle time inside statements, charged to what the host's
threads were doing then: a reading of a traced run's `.xplane.pb` beside
`trace_reduce.py`, which it reuses and leaves as it is.

    python3 benchmark/host_states.py <.xplane.pb, or a directory holding one>

prints `statement_idle_s` (the busiest device's idle seconds inside the
benchmark's `stmt:` annotations, within the traced window), `idle_by_host`
(those seconds split over `dispatch`, `host` and `pull`) and
`idle_top_by_host` (the ten largest shares, each labelled
`<stmt>: <state> <innermost tt: name>[ why=<why>]`).

What a host plane holds (jax 0.9.0, `host_tracer_level` 2, as
`harness/window.py` traces): one line a Python thread; the program's spans
as `tt:<name>` events on the line of the thread that opened them
(trino_tpu/telemetry/spans.py: the statement's thread and the lanes of its
feed threads), a pull's `why` as the event's stat; jax's
`PjitFunction(<program>)` event around each jit dispatch.  At each instant
a thread is in the state of its innermost such event (`thread_states`),
and an idle instant goes to the first state some thread is in, in the
order dispatch, host, pull (`charge_idle`).

The traced run's result line carries none of this yet: `trace_reduce.reduce`
would have to call `charge_idle` with the busiest device's busy intervals
(a `benchmark` change; PERF.md section 7).
"""

from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

from benchmark.trace_reduce import (  # noqa: E402
    OP_LINES,
    STATEMENT,
    WINDOW_ANNOTATION,
    _clip,
    _union,
    find_xplane,
    read,
)

#: the program's spans (trino_tpu/telemetry/spans.py) and jax's event of
#: a jit dispatch, on the line of the thread that made them
ENGINE_PREFIX = "tt:"
DISPATCH_PREFIX = "PjitFunction("
#: the engine's spans that wait on a device->host transfer, and on a feed
PULL_SPANS = ("tt:host_pull", "tt:feed_pull")
WAIT_SPAN = "tt:feed_wait"
#: the host states idle time is charged to
HOST_STATES = ("dispatch", "host", "pull")


def host_threads(path: str) -> dict:
    """{"<plane>#<line>": [(name, start_ns, dur_ns, why)]}: each host
    thread's `tt:` and `PjitFunction(` events, `why` the stat of a pull
    ("" elsewhere)."""
    from jax.profiler import ProfileData

    threads: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events = []
            for ev in line.events:
                name = ev.name
                if name.startswith(ENGINE_PREFIX) or name.startswith(
                    DISPATCH_PREFIX
                ):
                    why = ""
                    if name in PULL_SPANS:
                        why = str(dict(ev.stats).get("why", ""))
                    events.append((name, float(ev.start_ns),
                                   float(ev.duration_ns), why))
            if events:
                threads[f"{plane.name}#{i}"] = events
    return threads


def _state(name: str) -> str:
    if name.startswith(DISPATCH_PREFIX):
        return "dispatch"
    if name in PULL_SPANS:
        return "pull"
    if name == WAIT_SPAN:
        return "wait"
    return "host"


def thread_states(events: list) -> list:
    """One thread's events -> [(start, end, state, label)]: the pieces of
    time in which its innermost open event is one and the same.  `state`
    is dispatch (a `PjitFunction`), pull (`tt:host_pull`, `tt:feed_pull`),
    wait (`tt:feed_wait`) or host (any other `tt:` span); `label` is the
    state and the innermost `tt:` span's name (with the pull's `why`)."""
    out: list = []
    stack: list = []  # [end, state, label, innermost tt: name]
    at = 0.0

    def run_to(t):
        nonlocal at
        if stack and t > at:
            out.append((at, t, stack[-1][1], stack[-1][2]))
        at = max(at, t)

    for name, start, dur, why in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            run_to(stack[-1][0])
            stack.pop()
        run_to(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][0])  # a child ends inside its parent
        state = _state(name)
        if state == "dispatch":
            tt = stack[-1][3] if stack else "(none)"
        else:
            tt = name[len(ENGINE_PREFIX):]
        label = f"{state} {tt}" + (f" why={why}" if why else "")
        stack.append([end, state, label, tt])
    while stack:
        run_to(stack[-1][0])
        stack.pop()
    return out


def charge_idle(busy: list, statements: list, threads: dict,
                top: int = 10) -> tuple:
    """The device's idle time inside statements, charged to host states.

    `busy`: the device's busy intervals ([start, end], merged, sorted);
    `statements`: [(start, end, name)]; `threads`: {line: [(name, start,
    dur, why)]} as `host_threads()` gives them (any time unit; seconds out when
    nanoseconds go in).  Each idle instant inside a statement goes to
    `dispatch` if any thread dispatches, else to `host` if any thread is
    in another engine span, else to `pull` if any thread waits on a
    transfer, else to `host` (threads that only wait on a feed, or no
    engine span at all: protocol, HTTP, JSON).  Returns ({state: seconds}
    over HOST_STATES, which sum to the idle time; [[label, seconds]] of
    the `top` largest, labelled `<stmt>: <state> <innermost tt: name>`)."""
    # the sweep's events: (time, order, kind, payload); at one instant
    # ends (order 0) go before starts (order 1)
    events: list = []
    for a, b, name in statements:
        if b > a:
            where = STATEMENT.sub("", name).split("#")[0]
            events.append((a, 1, "stmt", where))
            events.append((b, 0, "stmt", where))
    for a, b in busy:
        events.append((a, 1, "busy", None))
        events.append((b, 0, "busy", None))
    for line, evs in threads.items():
        for a, b, state, label in thread_states(evs):
            events.append((a, 1, "thread", (line, state, label)))
            events.append((b, 0, "thread", (line, state, label)))
    events.sort(key=lambda e: (e[0], e[1]))
    open_stmts: list = []
    busy_depth = 0
    by_state: dict = {s: {} for s in ("dispatch", "host", "pull", "wait")}
    totals = {s: 0.0 for s in HOST_STATES}
    labels: dict = {}
    prev = None
    for t, order, kind, payload in events:
        if prev is not None and t > prev and open_stmts and not busy_depth:
            dt = (t - prev) / 1e9
            for state in ("dispatch", "host", "pull"):
                lines = by_state[state]
                if lines:
                    label = lines[next(reversed(lines))]
                    break
            else:
                state = "host"
                lines = by_state["wait"]
                label = (
                    lines[next(reversed(lines))].replace("wait", "host", 1)
                    if lines else "host (none)"
                )
            totals[state] += dt
            key = f"{open_stmts[0]}: {label}"
            labels[key] = labels.get(key, 0.0) + dt
        prev = t
        if kind == "stmt":
            if order:
                open_stmts.append(payload)
            else:
                open_stmts.remove(payload)
        elif kind == "busy":
            busy_depth += 1 if order else -1
        else:
            line, state, label = payload
            if order:
                by_state[state][line] = label
            else:
                by_state[state].pop(line, None)
    ranked = sorted(labels.items(), key=lambda kv: -kv[1])[:top]
    return totals, [[k, v] for k, v in ranked]


def split(path: str, top: int = 10) -> dict | None:
    """The idle split of one trace, over the window and the busiest device
    as `trace_reduce.reduce` takes them; None without a device op."""
    raw = read(path)
    busy_by = {}
    for name, lines in raw["devices"].items():
        ops = [(s, s + d) for n in OP_LINES for _, s, d in lines.get(n, [])]
        if ops:
            busy_by[name] = ops
    if not busy_by:
        return None
    window = [
        (s, s + d) for n, s, d in raw["annotations"]
        if n == WINDOW_ANNOTATION
    ]
    if window:
        lo, hi = window[0]
    else:
        every = [iv for ops in busy_by.values() for iv in ops]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    busy = {}
    for name, ops in busy_by.items():
        busy[name] = _union(
            [c for a, b in ops if (c := _clip(a, b, lo, hi)) is not None]
        )
    busiest = max(busy, key=lambda n: sum(b - a for a, b in busy[n]))
    statements = [
        (c[0], c[1], n) for n, s, d in raw["annotations"]
        if STATEMENT.match(n) and (c := _clip(s, s + d, lo, hi)) is not None
    ]
    by_state, ranked = charge_idle(
        busy[busiest], statements, host_threads(path), top
    )
    return {
        "busiest_device": busiest,
        "statements": len(statements),
        "statement_idle_s": sum(by_state.values()),
        "idle_by_host": by_state,
        "idle_top_by_host": ranked,
    }


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    print(json.dumps(split(target), indent=1))
