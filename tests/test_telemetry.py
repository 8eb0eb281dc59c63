"""Unified query telemetry: span tracer, metrics registry, Prometheus text,
Perfetto export, system tables, and the MeshProfile JSON contract
(reference style: TestQueryStats + the opentelemetry span assertions of
TestTracing, plus jmx_exporter text-format checks)."""

import json
import os
import re
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from trino_tpu.parallel import DistributedQueryRunner
from trino_tpu.runtime.query_stats import MESH_PHASES, FragmentStats, MeshProfile
from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.telemetry import (
    NULL_TRACER,
    REGISTRY,
    MetricsRegistry,
    SpanTracer,
)


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=2)


@pytest.fixture(scope="module")
def dist():
    return DistributedQueryRunner(n_workers=8)


# -- metrics registry ---------------------------------------------------------


def test_counter_register_once_bump_everywhere():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help text")
    c2 = reg.counter("x_total")
    assert c1 is c2
    c1.inc()
    c2.inc(4)
    assert c1.value() == 5


def test_labeled_counter_and_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("events_total", "events", labelnames=("kind",))
    c.labels("a").inc(2)
    c.labels(kind="b").inc()
    text = reg.render_prometheus()
    assert "# TYPE events_total counter" in text
    assert 'events_total{kind="a"} 2' in text
    assert 'events_total{kind="b"} 1' in text


def test_kind_mismatch_rejected():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert h.value() == 3


def test_callback_gauge_and_snapshot():
    reg = MetricsRegistry()
    reg.gauge_fn("live_things", "pull-style", lambda: 7)
    reg.counter("c_total").inc(3)
    snap = reg.snapshot()
    assert snap["live_things"] == 7
    assert snap["c_total"] == 3
    rows = dict((r[0], r[3]) for r in reg.rows())
    assert rows["live_things"] == 7.0


def test_concurrent_scrape_vs_bump():
    """HTTP handler threads scrape /v1/metrics while the query thread
    inserts new series — the series lock must keep renders from tripping
    over dict resizes."""
    import threading

    reg = MetricsRegistry()
    h = reg.histogram("x_seconds", "t")
    c = reg.counter("y_total", "t", labelnames=("k",))
    stop = False
    errs = []

    def scrape():
        while not stop:
            try:
                reg.render_prometheus()
                reg.snapshot()
            except Exception as e:  # pragma: no cover - the regression
                errs.append(e)
                break

    t = threading.Thread(target=scrape)
    t.start()
    try:
        for i in range(5000):
            h.observe(i * 0.001)
            c.labels(str(i % 499)).inc()
    finally:
        stop = True
        t.join()
    assert not errs
    assert c.labels("0").value() >= 1


def test_prometheus_text_shape():
    """Every non-comment line of the engine registry parses as
    `name{labels} value` — the exposition-format contract /v1/metrics
    serves."""
    line_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$"
    )
    for line in REGISTRY.render_prometheus().splitlines():
        if not line or line.startswith("#"):
            continue
        assert line_re.match(line), f"bad exposition line: {line!r}"


def test_engine_vocabulary_preregistered():
    """Exchange/speculation counters render before any query bumps them."""
    text = REGISTRY.render_prometheus()
    for label in ("exchange_elided", "join_capacity_sync", "host_restack"):
        assert f'counter="{label}"' in text
    assert "trino_tpu_trace_cache_hits_total" in text
    assert 'trino_tpu_buffer_pool_bytes{tier="device"}' in text


# -- span tracer --------------------------------------------------------------


def test_span_nesting_and_chrome_export():
    tr = SpanTracer(query_id="q_test")
    with tr.span("query", query_id="q_test"):
        with tr.span("analyze"):
            pass
        tr.record("launch", tr.t0, tr.t0 + 0.001, {"phase": "compute"})
    d = tr.root.to_dict()
    assert d["name"] == "query"
    assert [c["name"] for c in d["children"]] == ["analyze", "launch"]
    chrome = tr.to_chrome_trace()
    assert chrome["displayTimeUnit"] == "ms"
    names = [e["name"] for e in chrome["traceEvents"]]
    assert names == ["query", "analyze", "launch"]
    for e in chrome["traceEvents"]:
        assert e["ph"] == "X" and "ts" in e and "dur" in e
    # the export round-trips through JSON (what Perfetto ingests)
    assert json.loads(json.dumps(chrome))["traceEvents"]


def test_span_error_attribution():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("query"):
            raise RuntimeError("boom")
    assert tr.root.attrs["error"] == "RuntimeError"
    assert tr.root.end_s is not None


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("anything", a=1) as sp:
        assert sp is None
    NULL_TRACER.record("x", 0.0, 1.0)
    assert NULL_TRACER.flat_spans() == []
    assert NULL_TRACER.to_chrome_trace()["traceEvents"] == []


# -- query instrumentation (local) -------------------------------------------


def test_local_query_trace_structure(runner):
    runner.execute("select count(*) from nation")
    trace = runner.last_trace
    assert trace is not None
    names = [e["name"] for e in trace["traceEvents"]]
    assert names[0] == "query"
    assert "analyze" in names and "optimize" in names and "execute" in names


def test_query_trace_off_is_zero_overhead(runner):
    runner.execute("set session query_trace = false")
    before = runner.last_trace
    try:
        runner.execute("select count(*) from region")
        assert runner.last_trace is before  # nothing recorded
    finally:
        runner.execute("set session query_trace = true")


def test_completion_metrics_and_statistics(runner):
    from trino_tpu.runtime.events import CollectingEventListener

    listener = CollectingEventListener()
    runner.events.add(listener)
    c = REGISTRY.counter("trino_tpu_queries_total")
    before = c.value(("FINISHED", ""))
    runner.execute("select count(*) from nation")
    assert c.value(("FINISHED", "")) == before + 1
    done = listener.completed[-1]
    assert done.statistics is not None
    assert done.statistics.wall_s > 0
    assert done.statistics.rows == 1
    assert done.statistics.spans >= 4  # query + analyze/optimize/execute
    assert REGISTRY.histogram("trino_tpu_query_wall_seconds").value() > 0
    runner.events.listeners.remove(listener)


def test_explain_analyze_verbose_exports_trace(runner):
    res = runner.execute(
        "explain analyze verbose select count(*) from nation"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "Query trace (spans" in text
    json_lines = [
        r[0] for r in res.rows if r[0].startswith("Trace JSON: ")
    ]
    assert json_lines, "VERBOSE must embed the Chrome-trace JSON"
    chrome = json.loads(json_lines[0][len("Trace JSON: "):])
    assert any(e["name"] == "query" for e in chrome["traceEvents"])


def test_plain_explain_analyze_has_no_trace(runner):
    res = runner.execute("explain analyze select count(*) from nation")
    assert not any("Trace JSON" in r[0] for r in res.rows)


# -- query instrumentation (distributed) --------------------------------------


def test_mesh_trace_nests_query_fragment_launch(dist):
    sql = "select count(*) from lineitem"
    dist.execute(sql)
    dist.execute(sql)  # warm: spans must exist without retraces
    trace = dist.last_trace
    assert trace is not None
    assert any(
        e["name"] == "query" for e in trace["traceEvents"]
    ), "chrome export must carry the root span"
    # structural validation on the flattened span tree
    qid = trace["otherData"]["query_id"]
    flat = None
    for q, s in dist.traces:
        if q == qid:
            flat = s
    assert flat, "trace history must hold the served query"
    by_id = {s["span_id"]: s for s in flat}
    roots = [s for s in flat if s["parent_id"] == 0]
    assert len(roots) == 1 and roots[0]["name"] == "query"
    frag = [s for s in flat if s["name"].startswith("fragment-")]
    assert frag, "per-stage fragment spans expected"
    launches = [s for s in flat if s["name"] == "launch"]
    assert launches, "per-launch child spans expected"
    for l in launches:
        # every launch sits under a fragment span under the query root
        cur = by_id[l["parent_id"]]
        seen = set()
        while cur["parent_id"] != 0 and cur["span_id"] not in seen:
            seen.add(cur["span_id"])
            cur = by_id[cur["parent_id"]]
        assert cur["name"] in ("query",) or cur["name"].startswith(
            "fragment-"
        )
        attrs = json.loads(l["attributes"])
        assert attrs["step"]  # the launch door names every program
        # a launch booked by StageExecutor._call carries its phase; the
        # coordinator fragment's local operators and the exchange's counts
        # pass launch outside it
        if "phase" in attrs:
            assert attrs["phase"] in MESH_PHASES
            assert "fragment" in attrs
    assert any("phase" in json.loads(l["attributes"]) for l in launches)


def test_mesh_events_mirrored_to_registry(dist):
    c = REGISTRY.counter("trino_tpu_mesh_events_total")
    before = c.value(("result_gather",)) + c.value(("host_gather",)) + c.value(
        ("state_gather",)
    )
    dist.execute("select count(*) from orders")
    after = c.value(("result_gather",)) + c.value(("host_gather",)) + c.value(
        ("state_gather",)
    )
    assert after > before


def test_residency_holds_with_tracing_enabled(dist):
    """The telemetry-on contract: spans add no host syncs or retraces."""
    from trino_tpu import verify as V

    assert bool(dist.properties.get("query_trace")) is True
    report = V.device_residency(
        dist, "select sum(l_extendedprice) from lineitem"
    )
    assert report["retraces"] == 0
    assert report["tracing_enabled"] is True
    assert report["spans"] > 0


# -- MeshProfile / FragmentStats JSON contract (the EXPLAIN ANALYZE and
# profile-artifact schema, asserted instead of documented) --------------------

FRAGMENT_JSON_KEYS = {
    "fragment", "kind", "wall_s", "phases_ms",
    "bytes_to_device", "bytes_to_host", "collective_bytes",
    "collective_bytes_by",
}


def test_fragment_stats_json_schema():
    st = FragmentStats(3, kind="SOURCE")
    st.wall_s = 0.01
    st.phases["compute"] = 0.004
    st.close()
    doc = st.to_json()
    assert set(doc) == FRAGMENT_JSON_KEYS
    assert set(doc["phases_ms"]) == set(MESH_PHASES)
    assert doc["fragment"] == 3 and doc["kind"] == "SOURCE"


def test_mesh_profile_json_schema():
    prof = MeshProfile()
    prof.add_phase(0, "compute", 0.002)
    prof.fragment(0).wall_s = 0.003
    prof.bump("scan_cache_hit")
    prof.fragment(0).close()
    doc = prof.to_json()
    assert set(doc) == {
        "fragments", "trace_cache", "counters", "collective_bytes_by",
    }
    assert set(doc["trace_cache"]) == {"hits", "misses", "retraces"}
    assert doc["counters"]["scan_cache_hit"] == 1
    assert doc["fragments"][0]["phases_ms"]["compute"] == pytest.approx(2.0)


def test_phases_sum_to_wall_after_close():
    st = FragmentStats(0)
    st.wall_s = 0.010
    st.phases["compute"] = 0.004
    st.phases["transfer"] = 0.001
    st.close()
    assert sum(st.phases.values()) == pytest.approx(st.wall_s, abs=1e-12)
    assert st.phases["other"] == pytest.approx(0.005, abs=1e-12)


def test_phases_sum_to_wall_on_real_mesh_profile(dist):
    """The cross-fragment `_call` attribution invariant, asserted on a live
    profile: deferred chains bill their PRODUCER fragment, and walls move
    with the phases, so every fragment's phases still sum to its wall."""
    sql = "select count(*), sum(l_quantity) from lineitem where l_quantity < 30"
    dist.execute(sql)
    dist.execute(sql)
    prof = dist.last_mesh_profile
    assert prof.fragments, "distributed query must profile fragments"
    for fid, st in prof.fragments.items():
        assert st.phases["other"] >= 0.0
        assert sum(st.phases.values()) == pytest.approx(
            st.wall_s, abs=1e-4
        ), f"fragment {fid} phases do not sum to wall"


def test_phase_totals_rollup():
    prof = MeshProfile()
    prof.add_phase(0, "compute", 0.002)
    prof.add_phase(1, "compute", 0.003)
    prof.add_phase(1, "transfer", 0.001)
    totals = prof.phase_totals()
    assert totals["compute"] == pytest.approx(0.005)
    assert totals["transfer"] == pytest.approx(0.001)


# -- compile observatory (PR 6: trace-cache misses as structured events) ------


def test_trace_cache_evictions_counted_and_stats_consistent():
    """The LRU bound's drops are visible (manifest coverage vs cache
    pressure) and stats() reads entry count under the lock."""
    from trino_tpu.parallel.spmd import TraceCache
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    tc = TraceCache(limit=2)
    for i in range(3):
        tc.get(("unit_evict", i), lambda i=i: (lambda: i))
    # drain the open events this unit cache leaked into the process
    # observatory so a later REAL traced launch doesn't inherit them
    if OBSERVATORY._open:
        OBSERVATORY.close_open(0.0)
    st = tc.stats()
    assert st["entries"] == 2
    assert st["misses"] == 3
    assert st["evictions"] == 1
    # the evicted key recompiles: another miss, another eviction
    tc.get(("unit_evict", 0), lambda: (lambda: 0))
    if OBSERVATORY._open:
        OBSERVATORY.close_open(0.0)
    assert tc.stats()["evictions"] == 2
    # the process-wide cache exports the same stat as a registry series
    assert "trino_tpu_trace_cache_evictions_total" in REGISTRY.snapshot()


def test_compile_observatory_warm_replay_adds_zero_events(dist):
    """The coldstart contract: a warm replay's key set is closed — the
    observatory records ZERO new compile events (the assertable fact the
    prewarm manifest depends on)."""
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    sql = (
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_quantity < 25"
    )
    dist.execute(sql)  # first run may compile
    mark = OBSERVATORY.mark()
    dist.execute(sql)  # warm replay must not
    assert OBSERVATORY.count == mark, (
        "warm replay recorded new compile events"
    )
    # the module's earlier distributed queries DID compile: the ring and
    # the histogram both carry the evidence
    events = OBSERVATORY.events()
    assert events, "distributed executions must record compile events"
    closed = [e for e in events if e.closed]
    assert closed, "launch sites must close the events their misses opened"
    for e in closed:
        assert e.step and isinstance(e.step, str)
        assert e.wall_s >= 0.0
    assert REGISTRY.histogram("trino_tpu_compile_seconds").value() > 0


def test_compile_manifest_shape_and_stability(dist):
    """compile_manifest() is the AOT-prewarm enumeration: deduplicated,
    most-expensive-first, and closed under warm replay."""
    sql = "select count(*) from lineitem"
    dist.execute(sql)  # ensure THIS statement's keys are in the manifest
    m1 = dist.compile_manifest()
    assert m1, "a warmed mesh runner must have a non-empty manifest"
    for entry in m1:
        assert set(entry) >= {
            "key_fp", "step", "mesh", "key", "buckets", "count", "compile_s",
        }
        assert entry["count"] >= 1 and entry["compile_s"] >= 0.0
    walls = [e["compile_s"] for e in m1]
    assert walls == sorted(walls, reverse=True)
    dist.execute(sql)  # warm replay
    m2 = dist.compile_manifest()
    assert {e["key_fp"] for e in m2} == {e["key_fp"] for e in m1}, (
        "a warm replay must not grow the manifest key set"
    )


def test_system_compilations_table(dist):
    rows = dist.execute(
        "select seq, step, mesh, query_id, wall_s, key_fp "
        "from system.runtime.compilations"
    ).rows
    assert rows, "compile events must be queryable from SQL"
    assert all(r[4] is None or r[4] >= 0 for r in rows)
    assert any(r[1] and r[1] != "retrace" for r in rows), (
        "parsed step labels expected in the ring"
    )


def test_compile_spans_nest_under_launch(dist):
    """A cold launch's trace shows the compile stall as a CHILD of the
    launch span (EXPLAIN ANALYZE VERBOSE / Perfetto separate compile from
    compute)."""
    # a fresh filter constant forces new compile keys for this query shape
    sql = "select count(*) from lineitem where l_quantity < 13.37"
    dist.execute(sql)
    qid, flat = dist.traces[-1]
    by_id = {s["span_id"]: s for s in flat}
    compiles = [s for s in flat if s["name"] == "compile"]
    if not compiles:  # the constant may ride as a traced arg: nothing cold
        pytest.skip("query compiled nothing new (fully warm cache)")
    for c in compiles:
        assert by_id[c["parent_id"]]["name"] == "launch"
        attrs = json.loads(c["attributes"])
        assert "step" in attrs


# -- per-collective byte attribution (PR 6) -----------------------------------


def test_collective_breakdown_sums_to_aggregate(dist):
    """Every fragment's mesh-collective (kind, purpose) entries sum to its
    aggregate collective_bytes by construction; gather entries (host pulls,
    already in bytes_to_host) are attributed in the split WITHOUT inflating
    the aggregate; and the labeled registry counter moves by exactly the
    query's attributed bytes."""
    from trino_tpu.runtime.query_stats import COLLECTIVE_KINDS
    from trino_tpu.telemetry.metrics import COLLECTIVE_VOCABULARY

    c = REGISTRY.counter("trino_tpu_collective_bytes_total")

    def registry_total():
        return sum(c.labels(k, p).value() for k, p in COLLECTIVE_VOCABULARY)

    before = registry_total()
    dist.execute(
        "select l_suppkey, sum(l_quantity) from lineitem group by l_suppkey"
    )
    prof = dist.last_mesh_profile
    assert prof is not None
    totals = prof.collective_totals()
    assert totals, "a distributed group-by must attribute collective bytes"
    for fid, st in prof.fragments.items():
        coll = sum(
            b for (k, _p), b in st.collective_by.items()
            if k in COLLECTIVE_KINDS
        )
        assert coll == st.collective_bytes, (
            f"fragment {fid}: collective entries do not sum to the aggregate"
        )
    assert registry_total() - before == sum(totals.values())
    # the exchange repartition is a real collective; the result gather is
    # attributed in the split only
    assert any(k == "all_to_all" for (k, _p) in totals)
    assert any(k == "gather" for (k, _p) in totals)
    doc = prof.to_json()
    assert doc["collective_bytes_by"] == {
        f"{k}/{p}": b for (k, p), b in sorted(totals.items())
    }


def test_compile_close_rechecks_deadline(dist, monkeypatch):
    """The compile-overshoot watchdog (PR-5 carried gap): every compile
    event close is immediately followed by a cooperative cancellation
    check, so a long XLA compile classifies as EXCEEDED_TIME_LIMIT when
    the stall ends instead of silently running past query_max_run_time."""
    import trino_tpu.parallel.runner as pr
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    log = []
    orig_close = OBSERVATORY.close_open
    orig_check = pr.check_current

    def close_spy(*a, **k):
        events = orig_close(*a, **k)
        log.append(("close", len(events)))
        return events

    def check_spy():
        log.append(("check", 0))
        return orig_check()

    monkeypatch.setattr(OBSERVATORY, "close_open", close_spy)
    monkeypatch.setattr(pr, "check_current", check_spy)
    # a fresh literal so THIS query stands a chance of compiling cold
    dist.execute("select count(*) from lineitem where l_quantity < 48.25")
    closes = [i for i, (kind, n) in enumerate(log) if kind == "close" and n]
    if not closes:
        pytest.skip("query compiled nothing new (fully warm cache)")
    for i in closes:
        assert i + 1 < len(log) and log[i + 1][0] == "check", (
            "a compile-event close must be followed by a deadline check"
        )


# -- plan-decision metrics: coordinator/worker parity + lane isolation --------


def _metric_names(text: str) -> set:
    return {
        line.split("{", 1)[0].split(" ", 1)[0]
        for line in text.splitlines()
        if line and not line.startswith("#")
    }


def test_worker_metrics_expose_decision_counters():
    """Satellite: a worker's GET /v1/metrics exposes the SAME decision
    counters as the coordinator — fleet dashboards aggregate one name
    set, whichever node they scrape."""
    import urllib.request

    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    srv = CoordinatorServer(port=0)
    srv.start()
    w = WorkerServer(port=0).start()
    try:
        texts = {}
        for name, port in (("coord", srv.port), ("worker", w.port)):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                texts[name] = resp.read().decode()
        names = {k: _metric_names(t) for k, t in texts.items()}
        assert names["coord"] == names["worker"]
        assert "trino_tpu_plan_decisions_total" in names["worker"]
        # the pre-registered label grid is visible on BOTH surfaces
        for text in texts.values():
            assert (
                'trino_tpu_plan_decisions_total{kind="join_distribution",'
                'outcome="broadcast",hindsight="regret"}'
            ) in text
            assert (
                'trino_tpu_plan_decisions_total{kind="join_capacity",'
                'outcome="licensed",hindsight="vindicated"}'
            ) in text
    finally:
        w.shutdown()
        srv.shutdown()


def test_concurrent_statements_isolate_spans_and_ledgers(dist):
    """Concurrent statements on one engine: every span and every decision
    lands in ITS OWN statement's trace/ledger (the lifecycle-contextvar
    lane-safety contract), and each ledger stays complete."""
    import threading

    from trino_tpu.telemetry.profile_store import (
        ProfileStore,
        attach_profile_store,
    )

    store = ProfileStore()
    attach_profile_store(dist, store)
    try:
        sqls = [
            "select count(*) from customer join orders on c_custkey = o_custkey",
            "select count(*) from nation",
            "select c_mktsegment, count(*) from customer join orders "
            "on c_custkey = o_custkey group by c_mktsegment",
            "select count(*) from region",
        ]
        errors = []

        def run(sql):
            try:
                dist.execute(sql)
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append((sql, e))

        threads = [
            threading.Thread(target=run, args=(s,), daemon=True)
            for s in sqls
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert not errors, errors
        arts = [store.get(ref["key"]) for ref in store.refs()[-4:]]
        by_sql = {a["sql"]: a for a in arts}
        assert len(by_sql) == 4
        for a in arts:
            led = a["decisions"]
            # the ledger is the STATEMENT's own: its id matches, finalized,
            # and no exchange byte leaked into (or out of) another lane
            assert led["query_id"] == a["query_id"]
            assert led["finalized"] is True
            assert led["unattributed_bytes_by"] == {}
            kinds = {d["kind"] for d in led["decisions"]}
            if "join" in a["sql"]:
                assert "join_distribution" in kinds
            else:
                assert "join_distribution" not in kinds
        # span isolation: every span in a statement's trace carries that
        # statement's query id (flat_spans stamps the owning tracer's)
        traced = {qid: spans for qid, spans in dist.traces}
        for a in arts:
            spans = traced.get(a["query_id"])
            if not spans:
                continue
            assert {sp["query_id"] for sp in spans} == {a["query_id"]}
            assert sum(1 for sp in spans if sp["name"] == "query") == 1
    finally:
        dist.profile_store = None


# -- the launch / host-pull boundary (telemetry/programs.py jit_program,
# columnar/batch.py host_pull): named programs, `launch` and `host_pull`
# spans on both runners, always-on counts on the QueryContext -----------------


def _vocabulary(section: str) -> set:
    """The names listed under `section` in trino_tpu.telemetry's docstring
    (the one list of span names, launch steps and host_pull reasons)."""
    import trino_tpu.telemetry as telemetry

    body = telemetry.__doc__.split(section, 1)[1].split(":\n", 1)[1]
    body = body.split("\n\n", 1)[0]
    body = re.sub(r"\([^)]*\)", " ", body)  # explanations in parentheses
    return set(re.findall(r"\b[a-z][a-z0-9_]*(?:-N)?\b", body)) - {
        "local", "mesh", "and", "the", "when", "a", "by", "followed",
        "appended", "program", "holds", "collective", "kinds", "deferred",
        "fused",
    }


def _step_in_vocabulary(step: str, steps: set) -> bool:
    """`step` is a listed name; or a mesh name: listed kinds joined by `_`
    (`chain_scan_pred_project`, `fused_exchange_agg_final`), `_x` last when
    the program holds a collective."""
    if step in steps:
        return True
    if step.endswith("_x"):
        step = step[:-2]

    def splits(rest: str) -> bool:
        if not rest:
            return True
        return any(
            rest == k or (rest.startswith(k + "_") and splits(rest[len(k) + 1:]))
            for k in steps
        )

    return splits(step)


def _run_with_context(r, sql):
    """Execute `sql`; returns (its QueryContext, its flat spans or [])."""
    got = []
    r._query_context_cb = got.append
    n = len(r.traces)
    r.execute(sql)
    spans = r.traces[-1][1] if len(r.traces) > n else []
    return got[0], spans


def _attrs(span):
    return json.loads(span["attributes"]) if span["attributes"] else {}


def _tpch(n):
    from trino_tpu.connectors.tpch.queries import QUERIES

    return QUERIES[n]


def test_vocabulary_parses():
    steps = _vocabulary("launch steps")
    assert {"filter_project", "agg_reduce", "join_expand_unique",
            "chain", "fused_exchange", "row_count"} <= steps
    assert not steps & {"local", "traced", "step"}
    assert {"result", "capacity", "overflow_flag", "spill"} <= _vocabulary(
        "host_pull why"
    )
    assert {"execute", "build", "result", "launch", "host_pull",
            "fragment-N", "join"} <= _vocabulary("span names")
    from trino_tpu.telemetry.metrics import AGGREGATION_PATHS

    assert _vocabulary("launch paths") == set(AGGREGATION_PATHS)
    assert _step_in_vocabulary("chain_scan_pred_dyn_filter", steps)
    assert _step_in_vocabulary("fused_exchange_agg_final_x", steps)
    assert not _step_in_vocabulary("chain_local", steps)


def test_feed_span_vocabulary(runner):
    """The feed spans, their attributes and values are listed once, and
    the engine keeps to them on every thread kind it runs."""
    import trino_tpu.telemetry as telemetry

    section = telemetry.__doc__.split("feed spans", 1)[1].split("\n\n", 1)[0]
    listed = set(re.findall(r"[a-z][a-z_-]+", section))
    assert {"feed", "feed_launch", "feed_pull", "feed_wait"} <= (
        _vocabulary("span names") & listed
    )
    assert {"thread", "feed", "by", "statement", "eager-prefetch",
            "scan-prefetch", "local-exchange"} <= listed
    _, flat = _run_with_context(runner, _tpch(3))
    seen = set()
    for s in flat:
        a = _attrs(s)
        if s["name"] == "feed":
            assert set(a) == {"thread"} and a["thread"] in listed
            seen.add(a["thread"])
        elif s["name"] == "feed_wait":
            assert set(a) == {"feed", "by"}
            assert a["feed"] in listed and a["by"] in listed
        elif s["name"] == "feed_pull":
            assert a["why"] in _vocabulary("host_pull why")
    assert "eager-prefetch" in seen


def test_join_span_vocabulary_and_counter(runner):
    """The `join` span's attributes and values are listed once, and the
    engine keeps to them; its NULL-key count is a registered counter."""
    import trino_tpu.telemetry as telemetry
    from trino_tpu.telemetry import REGISTRY

    section = telemetry.__doc__.split("join span", 1)[1].split("\n\n", 1)[0]
    listed = set(re.findall(r"[a-z][a-z_]+", section))
    _, flat = _run_with_context(runner, _tpch(3))
    joins = [s for s in flat if s["name"] == "join"]
    assert len(joins) == 2
    steps = _vocabulary("launch steps")
    for j in joins:
        attrs = _attrs(j)
        assert set(attrs) == {"kind", "strategy", "keys", "build_rows",
                              "probe_rows", "out_rows", "null_keys"} <= listed
        assert attrs["kind"] in listed and attrs["strategy"] in listed
        assert attrs["strategy"] in steps
        assert attrs["keys"] == 1  # Q3 joins on one key a side
        assert attrs["null_keys"] == 0  # TPC-H has no NULL
    assert "trino_tpu_join_null_keys_total" in section
    assert "trino_tpu_join_null_keys_total" in REGISTRY.render_prometheus()


def test_local_execute_has_build_result_launch_and_pull(runner):
    ctx, flat = _run_with_context(runner, _tpch(6))
    by_id = {s["span_id"]: s for s in flat}
    execute = [s for s in flat if s["name"] == "execute"]
    assert len(execute) == 1
    kids = [s["name"] for s in flat if s["parent_id"] == execute[0]["span_id"]]
    assert kids == ["build", "result"]
    steps = _vocabulary("launch steps")
    launches = [s for s in flat if s["name"] == "launch"]
    assert launches
    for l in launches:
        assert _attrs(l)["step"] in steps, _attrs(l)
    pulls = [s for s in flat if s["name"] == "host_pull"]
    whys = _vocabulary("host_pull why")
    assert all(_attrs(p)["why"] in whys for p in pulls)
    results = [p for p in pulls if _attrs(p)["why"] == "result"]
    assert results and all(_attrs(p)["bytes"] > 0 for p in results)
    # the pull of the rows sits in `result`, and carries no guess at what
    # the device ran before it: the profiler trace tells that
    last = results[-1]
    assert by_id[last["parent_id"]]["name"] == "result"
    assert set(_attrs(last)) == {"why", "bytes"}
    # the scan's split readers ran on lanes of their own, under `build`
    # (where the feed was made), each its door spans beneath it
    feeds = [s for s in flat if s["name"] == "feed"]
    assert feeds
    for f in feeds:
        assert _attrs(f)["thread"] in ("local-exchange", "scan-prefetch")
        assert by_id[f["parent_id"]]["name"] == "build"
    for s in flat:
        if s["name"] in ("feed_launch", "feed_pull"):
            assert by_id[s["parent_id"]]["name"] == "feed"
    assert {s["name"] for s in flat} <= _vocabulary("span names")


@pytest.mark.parametrize("which, query", [
    ("runner", 6), ("runner", 3), ("dist", 3),
])
def test_launches_and_pulls_lie_inside_execute(request, which, query):
    """Every launch and host_pull of a statement is a descendant of its
    `execute` (`schedule`) span and lies inside it in time."""
    r = request.getfixturevalue(which)
    _, flat = _run_with_context(r, _tpch(query))
    by_id = {s["span_id"]: s for s in flat}
    outer = [s for s in flat if s["name"] in ("execute", "schedule")]
    assert len(outer) == 1
    o = outer[0]
    inner = [s for s in flat if s["name"] in ("launch", "host_pull")]
    assert inner
    for s in inner:
        cur = s
        while cur["parent_id"] and cur["span_id"] != o["span_id"]:
            cur = by_id[cur["parent_id"]]
        assert cur["span_id"] == o["span_id"], s
        assert s["start_ms"] >= o["start_ms"] - 1e-3
        assert (
            s["start_ms"] + s["duration_ms"]
            <= o["start_ms"] + o["duration_ms"] + 2e-3
        )
    # host_pull spans never nest: host_active_ms subtracts their sum
    for s in inner:
        if s["name"] == "host_pull":
            assert by_id[s["parent_id"]]["name"] != "host_pull"


def test_mesh_launches_carry_step_and_are_counted_once(dist):
    ctx, flat = _run_with_context(dist, _tpch(3))
    by_id = {s["span_id"]: s for s in flat}
    steps = _vocabulary("launch steps")
    launches = [s for s in flat if s["name"] == "launch"]
    booked = [l for l in launches if "phase" in _attrs(l)]
    assert booked, "StageExecutor._call books its phase on the door's span"
    for l in launches:
        assert _step_in_vocabulary(_attrs(l)["step"], steps), _attrs(l)
    for l in booked:
        parent = by_id[l["parent_id"]]
        if parent["name"] == "join":  # a join's launches nest under its span
            parent = by_id[parent["parent_id"]]
        assert parent["name"].startswith("fragment-")
    # one `join` span per join operator, inside its fragment
    joins = [s for s in flat if s["name"] == "join"]
    assert len(joins) == 2
    for j in joins:
        assert by_id[j["parent_id"]]["name"].startswith("fragment-")
        assert _attrs(j)["kind"] == "inner"
        assert _attrs(j)["strategy"] in (
            "broadcast", "partitioned", "colocated"
        )
    # one span per launch: _call adds to the door's span, it records none
    assert ctx.launches == len(launches)
    assert ctx.host_pulls == len([s for s in flat if s["name"] == "host_pull"])
    assert any(_attrs(s)["why"] == "result"
               for s in flat if s["name"] == "host_pull")
    assert "result" in [s["name"] for s in flat]


@pytest.mark.parametrize("which", ["runner", "dist"])
def test_counts_are_the_same_with_query_trace_off(request, which):
    r = request.getfixturevalue(which)
    sql = _tpch(6)
    r.execute(sql)  # warm: the same programs both times
    on, flat = _run_with_context(r, sql)
    r.execute("set session query_trace = false")
    try:
        off, none = _run_with_context(r, sql)
    finally:
        r.execute("set session query_trace = true")
    assert none == [] and off.tracer is NULL_TRACER
    assert on.launches == len([s for s in flat if s["name"] == "launch"]) > 0
    assert (off.launches, off.host_pulls, off.d2h_bytes) == (
        on.launches, on.host_pulls, on.d2h_bytes
    )
    assert off.host_pulls >= 1 and off.d2h_bytes > 0 and off.host_pull_s > 0


def test_query_trace_off_allocates_no_span_and_no_annotation(runner, monkeypatch):
    from trino_tpu.telemetry import spans as spans_mod

    made = []

    class Counting(spans_mod.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    real_init = spans_mod.Span.__init__

    def counting_init(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    sql = "select count(*) from region"
    runner.execute(sql)
    monkeypatch.setattr(spans_mod, "TraceAnnotation", Counting)
    monkeypatch.setattr(spans_mod.Span, "__init__", counting_init)
    runner.execute(sql)
    assert made, "with query_trace on, spans and annotations are made"
    runner.execute("set session query_trace = false")
    try:
        del made[:]
        runner.execute(sql)
        assert made == []
    finally:
        runner.execute("set session query_trace = true")
    # and NULL_TRACER's own span() is the shared no-op context
    assert NULL_TRACER.span("x") is NULL_TRACER.span("y")
    assert made == []


def test_statistics_carry_the_boundary_counts(runner):
    from trino_tpu.runtime.events import CollectingEventListener

    listener = CollectingEventListener()
    runner.events.add(listener)
    try:
        ctx, _ = _run_with_context(runner, _tpch(6))
    finally:
        runner.events.listeners.remove(listener)
    st = listener.completed[-1].statistics
    assert st.launches == ctx.launches > 0
    assert st.host_pulls == ctx.host_pulls >= 1
    assert st.d2h_bytes == ctx.d2h_bytes > 0
    assert st.host_pull_s > 0


def test_spans_are_annotations_on_the_profiler_clock(monkeypatch):
    """span() enters TraceAnnotation("tt:<name>") for the span's lifetime;
    record() (launches) enters none."""
    from trino_tpu.telemetry import spans as spans_mod

    events = []

    class Fake:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *a):
            events.append(("exit", self.name))

    monkeypatch.setattr(spans_mod, "TraceAnnotation", Fake)
    tr = SpanTracer(query_id="q")
    with tr.span("query"):
        with tr.span("host_pull", why="result"):
            pass
        tr.record("launch", tr.t0, tr.t0 + 0.001, {"step": "sort"})
    assert events == [
        ("enter", "tt:query"), ("enter", "tt:host_pull"),
        ("exit", "tt:host_pull"), ("exit", "tt:query"),
    ]


def test_agg_path_is_replayed_per_execution(runner):
    """The kernel path a step chose while tracing rides every later launch
    of that program (`path=`), and the aggregation-path counter counts
    executions, not traces."""
    from trino_tpu.telemetry.metrics import (
        AGGREGATION_PATHS,
        aggregation_path_counter,
    )

    sql = (
        "select l_returnflag, l_linestatus, sum(l_quantity) from lineitem "
        "group by l_returnflag, l_linestatus"
    )
    runner.execute(sql)  # traces (or finds the programs cached)
    c = aggregation_path_counter()
    total = lambda: sum(c.value((p,)) for p in AGGREGATION_PATHS)  # noqa: E731
    before = total()
    _, flat = _run_with_context(runner, sql)
    with_path = [
        _attrs(s) for s in flat
        if s["name"] == "launch" and "path" in _attrs(s)
    ]
    assert with_path, "a warm grouped aggregation replays its path"
    for a in with_path:
        assert set(a["path"].split("+")) <= set(AGGREGATION_PATHS)
    assert total() - before >= len(with_path)


@pytest.mark.parametrize(
    "keys,form",
    [("l_orderkey", "runs"), ("l_partkey, l_suppkey", "sorted_runs")],
    ids=["clustered_key", "shuffled_keys"],
)
def test_many_group_form_rides_the_agg_range_launch(runner, keys, form):
    """An `agg_range` launch over more than 2 048 slots says how it reduced
    its groups: over the runs of the group code as the rows came (lineitem
    is clustered on l_orderkey), or sorted into code order first — chosen
    from the order `agg_key_stats` observed — and never by a scatter."""
    from trino_tpu.telemetry.metrics import aggregation_path_counter

    sql = f"select {keys}, sum(l_quantity) from lineitem group by {keys}"
    runner.execute(sql)  # traces (or finds the programs cached)
    c = aggregation_path_counter()
    before = c.value((form,))
    _, flat = _run_with_context(runner, sql)
    paths = [
        set(_attrs(s)["path"].split("+")) for s in flat
        if s["name"] == "launch" and _attrs(s)["step"] == "agg_range"
    ]
    assert paths, "the statement takes the range-positional path"
    for path in paths:
        assert "positional" in path and "scatter" not in path, path
        assert len(path & {"dense", "runs", "sorted_runs"}) == 1, path
    took = [path for path in paths if form in path]
    assert took, paths
    assert c.value((form,)) - before >= len(took)


def test_compaction_form_rides_the_compact_launch(runner):
    """Every `compact` launch says how it found its slots' source rows
    (`path=compact_sort`: a one-key sort, never a scatter), counted per
    execution; a streaming aggregation's fold goes through the same door."""
    from trino_tpu.telemetry.metrics import aggregation_path_counter

    runner.execute(_tpch(18))  # traces (or finds the programs cached)
    c = aggregation_path_counter()
    before = c.value(("compact_sort",))
    _, flat = _run_with_context(runner, _tpch(18))
    paths = [
        _attrs(s).get("path") for s in flat
        if s["name"] == "launch" and _attrs(s)["step"] == "compact"
    ]
    assert paths and set(paths) == {"compact_sort"}, paths
    assert c.value(("compact_sort",)) - before >= len(paths)
    _, flat = _run_with_context(runner, _tpch(1))  # its partial states fold
    assert any(
        s["name"] == "launch" and _attrs(s)["step"] == "compact" for s in flat
    )


def test_repartition_forms_ride_the_exchange_launches(dist):
    """A partitioned join's repartition says how it placed and counted its
    rows: `fused_exchange*` launches carry `compact_sort` (one stable
    compaction a destination), `exchange_counts` ones `dense`; no launch of
    the statement names a scatter."""
    dist.execute("set session join_distribution_type = 'PARTITIONED'")
    try:
        _, flat = _run_with_context(dist, _tpch(3))
    finally:
        dist.execute("set session join_distribution_type = 'AUTOMATIC'")
    paths = [
        (a["step"], set(a.get("path", "").split("+")))
        for a in (_attrs(s) for s in flat if s["name"] == "launch")
    ]
    exchanges = [p for step, p in paths if step.startswith("fused_exchange")]
    counts = [p for step, p in paths if step.startswith("exchange_counts")]
    assert exchanges and all("compact_sort" in p for p in exchanges), paths
    assert counts and all(p == {"dense"} for p in counts), paths
    assert not any("scatter" in p for _, p in paths), paths


def test_pull_off_the_statement_thread_is_counted_without_a_span():
    import contextvars
    import threading

    import jax.numpy as jnp

    from trino_tpu.columnar.batch import host_pull
    from trino_tpu.runtime import lifecycle
    from trino_tpu.telemetry.programs import jit_program

    ctx = lifecycle.QueryContext("q-thread")
    ctx.tracer = SpanTracer(query_id="q-thread")
    double = jit_program(lambda x: x * 2, "filter_project")
    token = lifecycle.set_current(ctx)
    try:
        with ctx.tracer.span("query"):
            snapshot = contextvars.copy_context()

            def off_thread():
                snapshot.run(
                    lambda: host_pull(double(jnp.arange(4)), "spill")
                )

            t = threading.Thread(target=off_thread, name="t", daemon=True)
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
            host_pull(double(jnp.arange(4)), "result")
    finally:
        lifecycle.reset_current(token)
    assert (ctx.launches, ctx.host_pulls, ctx.d2h_bytes) == (2, 2, 64)
    names = [s["name"] for s in ctx.tracer.flat_spans()]
    assert names == ["query", "launch", "host_pull"]


def test_every_compiled_program_is_named_from_the_vocabulary(runner, dist):
    """After Q1, Q3, Q6, Q18 on both runners, everything the step caches
    and TRACE_CACHE hold is a named Program: no `local`, `traced`, `step`."""
    from trino_tpu.ops import aggregation, filter_project, join, sort, unnest, window
    from trino_tpu.parallel.spmd import TRACE_CACHE
    from trino_tpu.runtime import local_planner
    from trino_tpu.telemetry.programs import Program

    for n in (1, 3, 6, 18):
        runner.execute(_tpch(n))
    for n in (1, 3, 6):
        dist.execute(_tpch(n))
    steps = _vocabulary("launch steps")
    cached = list(TRACE_CACHE._fns.values())
    for cache in (
        aggregation._STEP_CACHE, filter_project._STEP_CACHE,
        join._STEP_CACHE, sort._STEP_CACHE, unnest._STEP_CACHE,
        window._WINDOW_STEP_CACHE, local_planner._MINMAX_STEP_CACHE,
    ):
        cached += [v for k, v in cache.items()
                   if not (isinstance(k, tuple) and k and k[0] == "raw")]
    programs = [f for f in cached if isinstance(f, Program)]
    assert len(programs) > 20
    # what is cached un-jitted is a host-eager filter/project step only
    assert all(isinstance(f, Program) or f.__name__ == "step" for f in cached)
    for p in programs:
        assert _step_in_vocabulary(p.step, steps), p.step
        assert p.step not in ("local", "traced", "step")
        # and jax sees the name: the XLA module is jit_<step>
        assert p.jitted.__name__ == p.step


def test_lint_knows_the_two_doors(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import lint_tpu
    finally:
        sys.path.pop(0)
    bad = tmp_path / "trino_tpu" / "ops" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import functools\nimport jax\nimport jax.numpy as jnp\n"
        "from trino_tpu.columnar.batch import host_pull\n"
        "f = jax.jit(lambda x: x)\n"
        "@jax.jit\ndef g(x):\n    return x\n"
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def h(x, n):\n    return x\n"
        "a = jax.device_get(f(1))\n"
        "n = int(host_pull(jnp.sum(f(1)), 'capacity'))\n"
        "m = int(jnp.sum(f(1)))\n"
    )
    found = [(f.rule, f.line) for f in lint_tpu.lint_file(str(bad))]
    assert found == [
        ("raw-jit", 5), ("raw-jit", 6), ("raw-jit", 9),
        ("host-transfer", 12), ("host-sync-cast", 14),
    ]
    assert lint_tpu._rules_for_path(
        "trino_tpu/runtime/local_planner.py"
    ) == {"raw-jit", "host-transfer"}
