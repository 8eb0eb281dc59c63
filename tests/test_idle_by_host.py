"""The traced run's split of the device's idle time by host state
(benchmark/host_states.charge_idle), on hand-built event lists: each idle
instant inside a statement goes to `dispatch` if any thread dispatches,
else `host` if any thread works in an engine span, else `pull` if any
thread waits on a transfer, else `host`; the three sum to the idle time."""

import pytest

from benchmark.host_states import HOST_STATES, charge_idle, thread_states

S = 1e9  # the trace's unit is the nanosecond


def ev(name, start, end, why=""):
    return (name, start * S, (end - start) * S, why)


def idle_inside(busy, statements):
    """Idle seconds inside statements, counted directly on a fine grid."""
    step = 0.001
    total = 0
    for i in range(int(20 / step)):
        t = (i + 0.5) * step
        if any(a <= t < b for a, b, _ in statements) and not any(
            a <= t < b for a, b in busy
        ):
            total += 1
    return total * step


def charge(busy, statements, threads):
    return charge_idle(
        [[a * S, b * S] for a, b in busy],
        [(a * S, b * S, n) for a, b, n in statements],
        threads,
    )


def test_a_thread_is_its_innermost_event():
    pieces = thread_states([
        ev("tt:execute", 0, 10),
        ev("PjitFunction(agg_reduce)", 1, 2),
        ev("tt:host_pull", 3, 5, why="capacity"),
        ev("PjitFunction(row_count)", 3.5, 4),  # nested in the pull
        ev("tt:feed_wait", 6, 7),
    ])
    got = [(a / S, b / S, state, label) for a, b, state, label in pieces]
    assert got == [
        (0, 1, "host", "host execute"),
        (1, 2, "dispatch", "dispatch execute"),
        (2, 3, "host", "host execute"),
        (3, 3.5, "pull", "pull host_pull why=capacity"),
        (3.5, 4, "dispatch", "dispatch host_pull"),
        (4, 5, "pull", "pull host_pull why=capacity"),
        (5, 6, "host", "host execute"),
        (6, 7, "wait", "wait feed_wait"),
        (7, 10, "host", "host execute"),
    ]


def test_precedence_dispatch_then_host_then_pull():
    statements = [(0, 10, "stmt:q5#1")]
    threads = {
        "statement": [ev("tt:execute", 0, 10),
                      ev("tt:host_pull", 1, 9, why="result")],
        "lane": [ev("tt:feed", 2, 8),
                 ev("PjitFunction(join_expand)", 3, 4),
                 ev("tt:feed_pull", 5, 7, why="capacity")],
    }
    totals, top = charge([], statements, threads)
    # 0-1 host (execute), 1-2 pull, 2-3 host (the lane's feed), 3-4
    # dispatch, 4-5 host, 5-7 pull (both pull), 7-8 host, 8-9 pull,
    # 9-10 host
    assert totals["dispatch"] == pytest.approx(1)
    assert totals["host"] == pytest.approx(5)
    assert totals["pull"] == pytest.approx(4)
    labels = dict(top)
    assert labels["q5: dispatch feed"] == pytest.approx(1)
    assert labels["q5: host feed"] == pytest.approx(3)
    assert labels["q5: host execute"] == pytest.approx(2)
    pulls = {k: v for k, v in labels.items() if ": pull " in k}
    assert sum(pulls.values()) == pytest.approx(4)
    assert set(pulls) <= {"q5: pull host_pull why=result",
                          "q5: pull feed_pull why=capacity"}


def test_waits_alone_and_no_engine_span_are_host():
    statements = [(0, 6, "stmt:q1#3")]
    threads = {
        "statement": [ev("tt:query", 1, 5), ev("tt:feed_wait", 2, 4)],
        "lane": [ev("tt:feed_wait", 3, 4)],
    }
    totals, top = charge([], statements, threads)
    assert totals == {"dispatch": 0.0, "host": pytest.approx(6),
                      "pull": 0.0}
    labels = dict(top)
    assert labels["q1: host (none)"] == pytest.approx(2)  # 0-1, 5-6
    assert labels["q1: host query"] == pytest.approx(2)  # 1-2, 4-5
    assert labels["q1: host feed_wait"] == pytest.approx(2)


def test_busy_time_and_gaps_between_statements_are_left_out():
    busy = [(1, 2), (4.5, 7), (12, 13)]
    statements = [(0, 5, "stmt:q3#1"), (6, 10, "stmt:q18#2")]
    threads = {
        "statement": [ev("tt:query", 0, 5), ev("tt:host_pull", 3, 4),
                      ev("tt:query", 6, 10),
                      ev("PjitFunction(sort)", 8, 9)],
    }
    totals, top = charge(busy, statements, threads)
    assert sum(totals.values()) == pytest.approx(idle_inside(
        busy, statements), abs=1e-6)
    assert totals["pull"] == pytest.approx(1)
    assert totals["dispatch"] == pytest.approx(1)
    assert totals["host"] == pytest.approx(1 + 1 + 0.5 + 1 + 1)
    assert {k.split(":")[0] for k, _ in top} == {"q3", "q18"}


def test_overlapping_lanes_sum_to_the_idle_time():
    """Many threads whose events overlap in every way: the states still
    partition the idle time inside statements."""
    busy = [(0.5, 1.5), (2.25, 2.75), (6, 6.5), (11, 12), (15, 15.1)]
    statements = [(0, 7, "stmt:q7#1"), (8, 16, "stmt:q8#2")]
    threads = {
        "statement": [ev("tt:query", 0, 7), ev("tt:execute", 0.2, 6.8),
                      ev("tt:feed_wait", 1, 3),
                      ev("tt:host_pull", 4, 4.5, why="result"),
                      ev("tt:query", 8, 16),
                      ev("PjitFunction(compact)", 9, 9.5)],
        "eager": [ev("tt:feed", 0.4, 2), ev("PjitFunction(join)", 0.6, 0.9),
                  ev("tt:feed_pull", 1.2, 1.9, why="capacity"),
                  ev("tt:feed", 2.5, 5), ev("tt:feed_wait", 3, 4.2),
                  ev("tt:feed", 9.2, 14)],
        "split": [ev("tt:feed", 0.1, 0.3), ev("tt:feed", 3.5, 4.4),
                  ev("PjitFunction(filter_project)", 3.6, 3.7),
                  ev("tt:feed_wait", 13, 15.5)],
    }
    totals, top = charge(busy, statements, threads)
    assert set(totals) == set(HOST_STATES)
    assert sum(totals.values()) == pytest.approx(
        idle_inside(busy, statements), abs=1e-6
    )
    assert all(v >= 0 for v in totals.values())
    assert sum(v for _, v in top) <= sum(totals.values()) + 1e-9


def test_split_of_the_recorded_trace_sums_to_its_statement_idle_time():
    from benchmark import trace_reduce
    from benchmark.host_states import split

    got = split(trace_reduce.SELF_CHECK_TRACE)
    assert got["statements"] == 4
    assert sum(got["idle_by_host"].values()) == pytest.approx(
        got["statement_idle_s"]
    )
    whole = trace_reduce.reduce(trace_reduce.SELF_CHECK_TRACE)
    idle_in_window = whole["window_s"] - whole["busy_s"]
    assert 0 < got["statement_idle_s"] <= idle_in_window
    # a trace recorded before the engine's spans were annotated: no
    # thread was in an engine span, so all of it is host
    assert got["idle_by_host"]["host"] == pytest.approx(
        got["statement_idle_s"]
    )


def test_each_thread_is_a_line_with_its_spans_and_dispatches(tmp_path):
    """In a profiler session the statement's spans and a feed lane's land
    on their own threads' lines, a pull carries its `why`, and jax marks
    each jit dispatch with a `PjitFunction` event."""
    import threading

    import jax
    import jax.numpy as jnp

    from benchmark.host_states import host_threads
    from benchmark.trace_reduce import find_xplane
    from trino_tpu.telemetry import SpanTracer

    double = jax.jit(lambda x: x * 2)
    double(jnp.arange(4))
    tr = SpanTracer(query_id="q")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.span("execute") as execute:
            def lane_work():
                lane = tr.lane(execute, "eager-prefetch")
                with lane.producing():
                    with lane.span("feed_pull", why="capacity", bytes=8):
                        jax.device_get(double(jnp.arange(4)))
                lane.close()

            t = threading.Thread(target=lane_work, name="eager-prefetch")
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
            with tr.span("host_pull", why="result", bytes=32):
                jax.device_get(double(jnp.arange(4)))
    finally:
        jax.profiler.stop_trace()
    threads = host_threads(find_xplane(str(tmp_path)))
    lines = {
        line: {(n, why) for n, _, _, why in evs}
        for line, evs in threads.items()
    }
    statement = [ln for ln, ev in lines.items() if ("tt:execute", "") in ev]
    lane = [ln for ln, ev in lines.items() if ("tt:feed", "") in ev]
    assert len(statement) == len(lane) == 1 and statement != lane
    assert ("tt:host_pull", "result") in lines[statement[0]]
    assert ("tt:feed_pull", "capacity") in lines[lane[0]]
    for line in (statement[0], lane[0]):
        assert any(n.startswith("PjitFunction(") for n, _ in lines[line])
