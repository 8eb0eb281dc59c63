"""Feed lanes: every thread that works for a statement is on its record.

A join's probe prefetch (`eager-prefetch`), a scan's prefetch
(`scan-prefetch`) and its split readers (`local-exchange`) run as the
statement that made them (runtime/prefetch.Feed): the launch and host-pull
doors count and record there (`feed_launch`, `feed_pull` on the thread's
lane), cancellation reaches them, and a spill registered there is released
with the statement's.  TPC-H `tiny` at `target_splits=8`, the setting of
the benchmark's cells."""

import json
import sys
import threading
import time
from collections import Counter

import pytest

from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.telemetry import NULL_TRACER, SpanTracer
from trino_tpu.telemetry.spans import Lane

FEED_KINDS = {"eager-prefetch", "scan-prefetch", "local-exchange"}


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=8)


def _tpch(n):
    from trino_tpu.connectors.tpch.queries import QUERIES

    return QUERIES[n]


def _attrs(span):
    return json.loads(span["attributes"]) if span["attributes"] else {}


def _run(r, sql):
    """Execute `sql`; returns (its QueryContext, its flat spans or [])."""
    got = []
    r._query_context_cb = got.append
    n = len(r.traces)
    r.execute(sql)
    spans = r.traces[-1][1] if len(r.traces) > n else []
    return got[0], spans


@pytest.fixture
def door_calls(monkeypatch):
    """Every call of the launch door and of the host-pull door, counted
    by the name of the thread that made it (wrapped where each module
    holds the door, so no call goes around the count)."""
    from trino_tpu.columnar import batch as batch_mod
    from trino_tpu.telemetry.programs import Program

    calls = Counter()
    real_call = Program.__call__
    real_pull = batch_mod.host_pull

    def call(self, *a, **k):
        calls["launch", threading.current_thread().name] += 1
        return real_call(self, *a, **k)

    def pull(tree, why):
        calls["pull", threading.current_thread().name] += 1
        return real_pull(tree, why)

    monkeypatch.setattr(Program, "__call__", call)
    for mod in list(sys.modules.values()):
        if (
            getattr(mod, "__name__", "").startswith("trino_tpu")
            and getattr(mod, "host_pull", None) is real_pull
        ):
            monkeypatch.setattr(mod, "host_pull", pull)
    return calls


@pytest.mark.parametrize("query", [5, 7])
def test_door_spans_are_every_call_of_the_statement(runner, door_calls, query):
    """`launch` + `feed_launch` spans are every Program call the statement
    makes, `host_pull` + `feed_pull` spans every host_pull; each kind
    equals its QueryContext field, and the feed threads did a share."""
    runner.execute(_tpch(query))  # warm: the programs and the pool
    door_calls.clear()
    ctx, flat = _run(runner, _tpch(query))
    names = Counter(s["name"] for s in flat)
    launches = sum(v for (door, _), v in door_calls.items() if door == "launch")
    pulls = sum(v for (door, _), v in door_calls.items() if door == "pull")
    assert names["launch"] + names["feed_launch"] == launches
    assert names["host_pull"] + names["feed_pull"] == pulls
    assert (ctx.launches, ctx.feed_launches) == (
        names["launch"], names["feed_launch"]
    )
    assert (ctx.host_pulls, ctx.feed_pulls) == (
        names["host_pull"], names["feed_pull"]
    )
    # the statement's fields count its own thread alone
    here = threading.current_thread().name
    assert ctx.launches == door_calls["launch", here]
    assert ctx.host_pulls == door_calls["pull", here]
    assert ctx.feed_launches > 0 and ctx.feed_pulls > 0
    assert ctx.feed_pull_s > 0
    pull_ms = sum(s["duration_ms"] for s in flat if s["name"] == "feed_pull")
    assert ctx.feed_pull_s * 1e3 == pytest.approx(pull_ms, rel=0.05, abs=1.0)


def test_statistics_carry_the_feed_counts(runner):
    from trino_tpu.runtime.events import CollectingEventListener

    runner.execute(_tpch(5))
    listener = CollectingEventListener()
    runner.events.add(listener)
    try:
        ctx, _ = _run(runner, _tpch(5))
    finally:
        runner.events.listeners.remove(listener)
    st = listener.completed[-1].statistics
    assert st.feed_launches == ctx.feed_launches > 0
    assert st.feed_pulls == ctx.feed_pulls > 0
    assert st.feed_pull_s == pytest.approx(ctx.feed_pull_s, abs=1e-6)
    assert st.feed_wait_s == pytest.approx(ctx.feed_wait_s, abs=1e-6)
    assert st.launches == ctx.launches and st.host_pulls == ctx.host_pulls


def test_a_probe_feed_hangs_under_the_join_that_made_it(runner):
    """Each join's probe prefetch lane is a child of the join's span; a
    join below another join runs on the upper one's lane, and its own
    launches and pulls nest under its span there."""
    _, flat = _run(runner, _tpch(7))
    by_id = {s["span_id"]: s for s in flat}
    feeds = [s for s in flat if s["name"] == "feed"]
    assert {_attrs(f)["thread"] for f in feeds} <= FEED_KINDS
    probes = [f for f in feeds if _attrs(f)["thread"] == "eager-prefetch"]
    joins = [s for s in flat if s["name"] == "join"]
    assert len(probes) == len(joins) == 5
    assert {by_id[f["parent_id"]]["name"] for f in probes} == {"join"}
    assert len({f["parent_id"] for f in probes}) == len(probes)
    lower = [
        j for j in joins
        if any(s["parent_id"] == j["span_id"] and s["name"] == "feed_launch"
               for s in flat)
    ]
    assert lower, "a join below a join records on the upper join's lane"
    for j in lower:
        kids = {s["name"] for s in flat if s["parent_id"] == j["span_id"]}
        assert not kids & {"launch", "host_pull"}
    # the scan feeds made while planning hang under `build`
    scans = [f for f in feeds if _attrs(f)["thread"] != "eager-prefetch"]
    assert scans and {by_id[f["parent_id"]]["name"] for f in scans} == {"build"}
    # the door spans of a lane keep the doors' attributes
    for s in flat:
        if s["name"] == "feed_launch":
            assert "step" in _attrs(s)
        elif s["name"] == "feed_pull":
            assert set(_attrs(s)) == {"why", "bytes"}


def test_concurrent_statements_keep_their_lanes_apart(runner):
    """Two statements at once on one LocalQueryRunner: each trace holds
    its own lanes and door spans, counted as when it ran alone."""
    from trino_tpu.runtime.events import CollectingEventListener

    sqls = {5: _tpch(5), 7: _tpch(7)}
    alone = {}
    for q, sql in sqls.items():
        runner.execute(sql)
        ctx, flat = _run(runner, sql)
        alone[q] = Counter(s["name"] for s in flat)
    listener = CollectingEventListener()
    runner.events.add(listener)
    errors = []

    def run(sql):
        try:
            runner.execute(sql)
        except Exception as e:  # pragma: no cover - diagnostic
            errors.append(e)

    try:
        threads = [
            threading.Thread(target=run, args=(sql,), daemon=True)
            for sql in sqls.values()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        runner.events.listeners.remove(listener)
    assert not errors, errors
    traces = dict(runner.traces)
    for done in listener.completed:
        q = next(q for q, sql in sqls.items() if done.sql == sql)
        flat = traces[done.query_id]
        assert {s["query_id"] for s in flat} == {done.query_id}
        ids = [s["span_id"] for s in flat]
        assert len(ids) == len(set(ids))
        names = Counter(s["name"] for s in flat)
        for name in ("launch", "feed_launch", "host_pull", "feed_pull",
                     "feed", "join"):
            assert names[name] == alone[q][name], (q, name)
        st = done.statistics
        assert (st.launches, st.feed_launches) == (
            names["launch"], names["feed_launch"]
        )
        assert (st.host_pulls, st.feed_pulls) == (
            names["host_pull"], names["feed_pull"]
        )


def test_with_query_trace_off_lanes_record_nothing_and_count(
    runner, monkeypatch
):
    from trino_tpu.telemetry import spans as spans_mod

    sql = _tpch(5)
    runner.execute(sql)
    on, flat = _run(runner, sql)
    made = []

    class Counting(spans_mod.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    real_init = spans_mod.Span.__init__

    def counting_init(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    monkeypatch.setattr(spans_mod, "TraceAnnotation", Counting)
    monkeypatch.setattr(spans_mod.Span, "__init__", counting_init)
    runner.execute("set session query_trace = false")
    try:
        del made[:]
        off, none = _run(runner, sql)
        assert made == []
    finally:
        runner.execute("set session query_trace = true")
    assert none == [] and off.tracer is NULL_TRACER
    assert (off.feed_launches, off.feed_pulls) == (
        on.feed_launches, on.feed_pulls
    ) == (
        sum(1 for s in flat if s["name"] == "feed_launch"),
        sum(1 for s in flat if s["name"] == "feed_pull"),
    )
    assert off.feed_launches > 0 and off.feed_pull_s > 0
    assert (off.launches, off.host_pulls) == (on.launches, on.host_pulls)


def test_cancel_stops_a_probe_feed_and_releases_its_spill(runner, monkeypatch):
    """Canceled while a join's probe feed runs: the feed's thread sees the
    cancellation and ends, and a spill registered on it is released when
    the statement ends."""
    from trino_tpu.runtime.lifecycle import (
        QueryCanceledException,
        current_feed,
        current_query,
    )
    from trino_tpu.runtime.spill import SpillManager
    from trino_tpu.telemetry.programs import Program

    sql = _tpch(7)
    runner.execute(sql)
    fired = {}
    real_call = Program.__call__

    def call(self, *a, **k):
        feed = current_feed()
        if not fired and feed is not None and feed.kind == "eager-prefetch":
            fired["thread"] = threading.current_thread()
            fired["spill"] = SpillManager()
            current_query().cancel("canceled by test")
        return real_call(self, *a, **k)

    monkeypatch.setattr(Program, "__call__", call)
    with pytest.raises(QueryCanceledException):
        runner.execute(sql)
    assert fired, "a probe feed thread launched a program"
    fired["thread"].join(timeout=30)
    assert not fired["thread"].is_alive()
    assert fired["spill"]._closed
    monkeypatch.undo()
    assert runner.execute(sql).rows  # the engine runs on


def test_concurrent_lanes_share_ids_children_and_tallies():
    """Many feed threads record into one tree and book into one statement
    at once, switching threads as often as the interpreter allows: no
    span id is taken twice, no child and no count is lost, and each
    lane's door spans hang under its own `feed` span."""
    import jax.numpy as jnp

    from trino_tpu.columnar.batch import host_pull
    from trino_tpu.runtime import lifecycle
    from trino_tpu.runtime.prefetch import Feed
    from trino_tpu.telemetry.programs import jit_program

    ctx = lifecycle.QueryContext("q-lanes")
    ctx.tracer = SpanTracer(query_id="q-lanes")
    double = jit_program(lambda x: x * 2, "filter_project")
    double(jnp.arange(2))  # compiled before the lanes start
    n_lanes, per_lane = 16, 40
    start = threading.Barrier(n_lanes)

    def produce(tally):
        assert isinstance(ctx.tracer.here(), Lane)
        start.wait(timeout=60)
        for _ in range(per_lane):
            host_pull(double(jnp.arange(2)), "capacity")

    token = lifecycle.set_current(ctx)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ctx.tracer.span("query") as root:
            feeds = [Feed("local-exchange") for _ in range(n_lanes)]
            threads = [
                threading.Thread(target=f.run, args=(produce,),
                                 name=f"local-exchange-{i}", daemon=True)
                for i, f in enumerate(feeds)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
        lifecycle.reset_current(token)
    assert ctx.feed_launches == ctx.feed_pulls == n_lanes * per_lane
    assert ctx.launches == ctx.host_pulls == 0
    assert ctx.tracer.here() is ctx.tracer and not ctx.tracer._lanes
    flat = ctx.tracer.flat_spans()
    ids = [s["span_id"] for s in flat]
    assert len(ids) == len(set(ids)) == 1 + n_lanes * (1 + 2 * per_lane)
    by_id = {s["span_id"]: s for s in flat}
    feeds = [s for s in flat if s["name"] == "feed"]
    assert len(feeds) == n_lanes
    assert all(f["parent_id"] == root.span_id for f in feeds)
    doors = [s for s in flat if s["name"] in ("feed_launch", "feed_pull")]
    assert {by_id[s["parent_id"]]["name"] for s in doors} == {"feed"}
    assert set(Counter(s["parent_id"] for s in doors).values()) == {
        2 * per_lane
    }


def test_a_statement_waits_on_a_slow_feed(monkeypatch):
    """A consumer blocked on an empty feed queue records `feed_wait` (feed
    = the producer's kind, by = the waiting thread's) and books the
    seconds on the waiting thread's counts."""
    from trino_tpu.runtime import lifecycle
    from trino_tpu.runtime.prefetch import eager_prefetch

    ctx = lifecycle.QueryContext("q-wait")
    ctx.tracer = SpanTracer(query_id="q-wait")

    def slow():
        for i in range(3):
            time.sleep(0.05)
            yield i

    token = lifecycle.set_current(ctx)
    try:
        with ctx.tracer.span("query"):
            upper = eager_prefetch(
                (x for x in eager_prefetch(slow(), depth=1)), depth=1
            )
            assert list(upper) == [0, 1, 2]
    finally:
        lifecycle.reset_current(token)
    flat = ctx.tracer.flat_spans()
    waits = [_attrs(s) for s in flat if s["name"] == "feed_wait"]
    assert {"feed": "eager-prefetch", "by": "statement"} in waits
    assert {"feed": "eager-prefetch", "by": "eager-prefetch"} in waits
    assert ctx.stmt_feed_wait_s > 0.05
    assert ctx.feed_wait_s > ctx.stmt_feed_wait_s
    assert len(ctx.feed_tallies) == 2
