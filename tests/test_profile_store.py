"""Query performance observatory tests: the persistent per-query profile
archive (telemetry/profile_store), device-gate contention telemetry
(runtime/dispatcher device_slice), differential drift attribution
(tools/profile_diff), the JSONL
audit log (telemetry/audit), and the lane-safety contract for
last_mesh_profile / last_trace under concurrent engine lanes."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.telemetry import REGISTRY
from trino_tpu.telemetry.profile_store import (
    ARTIFACT_PHASES,
    ProfileStore,
    attach_profile_store,
    build_artifact,
    sql_hash,
)


@pytest.fixture(scope="module")
def dist():
    from trino_tpu.parallel import DistributedQueryRunner

    return DistributedQueryRunner(n_workers=8, schema="tiny")


def _artifact(query_id="query_1", sql="select 1", wall=1.0, phases=None,
              fragments=(), gate_wait=0.0, counters=None, coll=None):
    """Hand-built artifact with chosen phase values (via a stub profile)."""

    class _Prof:
        def to_json(self):
            return {
                "fragments": list(fragments),
                "counters": dict(counters or {}),
                "trace_cache": {"hits": 0, "misses": 0, "retraces": 0},
                "collective_bytes_by": dict(coll or {}),
            }

        def phase_totals(self):
            return dict(phases or {})

    return build_artifact(
        query_id=query_id, sql=sql, state="FINISHED", wall_s=wall,
        mesh_profile=_Prof() if phases is not None or fragments else None,
        gate_wait_s=gate_wait,
    )


# -- artifact assembly ---------------------------------------------------------


class TestArtifact:
    def test_phases_sum_to_wall_exactly(self):
        art = _artifact(
            wall=2.5,
            phases={"trace": 0.5, "compute": 1.0, "transfer": 0.25},
            gate_wait=0.125,
        )
        assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-12
        assert art["phases"]["gate_wait"] == 0.125
        # the remainder is NAMED, not dropped
        assert art["phases"]["unattributed"] == pytest.approx(0.625)

    def test_unattributed_can_go_negative_but_still_sums(self):
        # overlapping measurements can exceed wall; the invariant is the
        # SUM, and a negative remainder is a visible fact, not a lie
        art = _artifact(wall=1.0, phases={"compute": 1.5})
        assert art["phases"]["unattributed"] == pytest.approx(-0.5)
        assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-12

    def test_artifact_key_and_hash(self):
        a = _artifact(sql="select  1")
        b = _artifact(query_id="query_2", sql="SELECT 1")
        assert a["sql_hash"] == b["sql_hash"]  # normalized
        assert a["key"] != b["key"]  # query id in the key
        assert a["version"] == 1

    def test_local_artifact_has_empty_mesh_sections(self):
        art = _artifact()
        assert art["fragments"] == []
        assert art["mesh"] == "local"
        assert art["phases"]["unattributed"] == pytest.approx(1.0)


# -- the store -----------------------------------------------------------------


class TestProfileStore:
    def test_archive_ring_and_rows(self):
        store = ProfileStore()
        ref = store.archive(_artifact())
        assert ref["path"] is None  # memory-only store
        assert store.get("query_1")["query_id"] == "query_1"
        assert store.get(ref["key"]) is not None
        rows = store.rows()
        assert len(rows) == 1 and rows[0][0] == "query_1"

    def test_archive_to_disk_through_spi(self, tmp_path):
        store = ProfileStore(archive_dir=str(tmp_path))
        ref = store.archive(_artifact())
        assert store.flush(5.0)
        assert os.path.exists(ref["path"])
        on_disk = json.loads(open(ref["path"]).read())
        assert on_disk["query_id"] == "query_1"

    def test_get_from_disk_survives_restart(self, tmp_path):
        store = ProfileStore(archive_dir=str(tmp_path), synchronous=True)
        store.archive(_artifact())
        fresh = ProfileStore(archive_dir=str(tmp_path))  # new incarnation
        art = fresh.get("query_1")
        assert art is not None and art["query_id"] == "query_1"

    def test_concurrent_archives_produce_distinct_wellformed_files(
        self, tmp_path
    ):
        # the satellite contract: K lanes completing simultaneously ->
        # K distinct artifacts, no torn JSON (SPI write is atomic publish)
        store = ProfileStore(archive_dir=str(tmp_path))
        K = 8

        def complete(i):
            for j in range(5):
                store.archive(
                    _artifact(
                        query_id=f"query_{i}_{j}",
                        sql=f"select {i * 100 + j}",
                        wall=0.01 * (i + 1),
                    )
                )

        threads = [
            threading.Thread(target=complete, args=(i,), daemon=True,
                             name=f"lane-{i}")
            for i in range(K)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert store.flush(10.0)
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == K * 5
        for f in files:  # every artifact parses and carries the invariant
            art = json.loads(open(tmp_path / f).read())
            assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-9

    def test_retention_sweep_deletes_only_expired(self, tmp_path):
        t = [1000.0]
        store = ProfileStore(
            archive_dir=str(tmp_path), retention_max_age_s=100.0,
            synchronous=True, clock=lambda: t[0],
        )
        old = store.archive(_artifact(query_id="query_old"))
        os.utime(old["path"], (800.0, 800.0))  # mtime 200s in the past
        young = store.archive(_artifact(query_id="query_young"))
        os.utime(young["path"], (950.0, 950.0))
        deleted = store.sweep()
        assert deleted == [old["path"]]
        assert os.path.exists(young["path"])
        assert not os.path.exists(old["path"])

    def test_retention_count_prunes_oldest_first(self, tmp_path):
        store = ProfileStore(
            archive_dir=str(tmp_path), retention_max_count=2,
            synchronous=True,
        )
        refs = []
        for i in range(4):
            r = store.archive(_artifact(query_id=f"query_{i}"))
            os.utime(r["path"], (100.0 + i, 100.0 + i))
            refs.append(r)
        deleted = store.sweep(now_s=200.0)
        assert sorted(deleted) == sorted([refs[0]["path"], refs[1]["path"]])
        assert os.path.exists(refs[2]["path"])
        assert os.path.exists(refs[3]["path"])

    def test_sweep_ignores_non_artifacts(self, tmp_path):
        (tmp_path / "spool.npz").write_bytes(b"not a profile")
        store = ProfileStore(
            archive_dir=str(tmp_path), retention_max_count=1,
            synchronous=True,
        )
        store.sweep(now_s=1e12)
        assert (tmp_path / "spool.npz").exists()

    def test_ring_bounded(self):
        store = ProfileStore(ring_limit=3)
        for i in range(5):
            store.archive(_artifact(query_id=f"query_{i}"))
        assert len(store.refs()) == 3
        assert store.get("query_0") is None  # rotated out, no disk tier


# -- device-gate telemetry -----------------------------------------------------


def _hist_count(name):
    return REGISTRY.histogram("trino_tpu_" + name).value()


class TestDeviceGate:
    def test_uncontended_step_observes_nothing(self):
        from trino_tpu.runtime.dispatcher import device_slice

        w0 = _hist_count("device_gate_wait_seconds")
        h0 = _hist_count("device_gate_hold_seconds")
        for _ in range(100):
            with device_slice():
                pass
        # zero-cost-when-idle: no wait observed, no hold observed
        assert _hist_count("device_gate_wait_seconds") == w0
        assert _hist_count("device_gate_hold_seconds") == h0

    def test_contended_acquire_observes_wait_and_hold(self):
        from trino_tpu.runtime.dispatcher import device_slice, gate_holder

        w0 = _hist_count("device_gate_wait_seconds")
        h0 = _hist_count("device_gate_hold_seconds")
        holding = threading.Event()
        release = threading.Event()
        seen_holder = []

        def holder():
            with device_slice():
                holding.set()
                release.wait(5.0)

        t = threading.Thread(target=holder, daemon=True, name="gate-holder")
        t.start()
        holding.wait(5.0)
        seen_holder.append(gate_holder())

        def waiter():
            with device_slice():
                pass

        t2 = threading.Thread(target=waiter, daemon=True, name="gate-waiter")
        t2.start()
        time.sleep(0.05)  # let the waiter block
        release.set()
        t.join(5.0)
        t2.join(5.0)
        assert seen_holder == [0]  # occupancy readable while held
        assert gate_holder() == -1  # idle again
        assert _hist_count("device_gate_wait_seconds") == w0 + 1
        # the hold during which the waiter waited was observed
        assert _hist_count("device_gate_hold_seconds") >= h0 + 1

    def test_gate_wait_attributed_to_executing_query(self):
        from trino_tpu.runtime import lifecycle
        from trino_tpu.runtime.dispatcher import device_slice

        ctx = lifecycle.QueryContext("query_gate")
        holding = threading.Event()
        release = threading.Event()

        def holder():
            with device_slice():
                holding.set()
                release.wait(5.0)

        t = threading.Thread(target=holder, daemon=True, name="gate-holder2")
        t.start()
        holding.wait(5.0)
        token = lifecycle.set_current(ctx)
        try:
            done = threading.Event()

            def releaser():
                time.sleep(0.02)
                release.set()
                done.set()

            threading.Thread(
                target=releaser, daemon=True, name="gate-releaser"
            ).start()
            with device_slice():
                pass
        finally:
            lifecycle.reset_current(token)
        t.join(5.0)
        assert ctx.gate_wait_s > 0.0

    def test_reentrant_hold_counts_once(self):
        from trino_tpu.runtime.dispatcher import device_slice, gate_holder

        with device_slice():
            with device_slice():
                assert gate_holder() == 0
            assert gate_holder() == 0  # inner exit must not clear holder
        assert gate_holder() == -1

    def test_uncontended_overhead_measured(self):
        # "measured, not asserted": the timed gate's per-step cost vs the
        # raw RLock it replaced, on this machine, under a VERY generous
        # bound (the budget is one clock read + one non-blocking acquire;
        # 50us/step would be two orders of magnitude over it)
        from trino_tpu.runtime.dispatcher import device_slice

        n = 5000
        raw = threading.RLock()
        t0 = time.perf_counter()
        for _ in range(n):
            with raw:
                pass
        raw_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            with device_slice():
                pass
        timed_s = time.perf_counter() - t0
        per_step_overhead = max(0.0, timed_s - raw_s) / n
        assert per_step_overhead < 50e-6, (
            f"timed gate overhead {per_step_overhead * 1e6:.2f}us/step "
            f"(timed {timed_s:.4f}s vs raw {raw_s:.4f}s over {n} steps)"
        )

    def test_gate_vocabulary_preregistered(self):
        text = REGISTRY.render_prometheus()
        for name in (
            "trino_tpu_device_gate_wait_seconds",
            "trino_tpu_device_gate_hold_seconds",
            "trino_tpu_device_gate_occupied",
            "trino_tpu_profiles_archived_total",
            "trino_tpu_profiles_pruned_total",
            "trino_tpu_audit_events_total",
        ):
            assert name in text


# -- runner integration --------------------------------------------------------


class TestRunnerIntegration:
    def test_local_execute_archives_artifact(self):
        r = LocalQueryRunner()
        store = attach_profile_store(r, ProfileStore())
        res = r.execute("select count(*) from region")
        assert res.rows == [(5,)]
        art = store.get("query_1")
        assert art is not None
        assert art["state"] == "FINISHED"
        assert art["rows"] == 1
        assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-9
        assert art["spans"]  # query_trace defaults on

    def test_failed_statement_archives_with_error_code(self):
        r = LocalQueryRunner()
        store = attach_profile_store(r, ProfileStore())
        with pytest.raises(Exception):
            r.execute("select * from no_such_table")
        arts = [store.get(ref["query_id"]) for ref in store.refs()]
        assert any(a["state"] == "FAILED" for a in arts)

    def test_no_store_means_no_archiving_cost(self):
        r = LocalQueryRunner()
        assert r.profile_store is None  # default: off
        c0 = REGISTRY.counter("trino_tpu_profiles_archived_total").value()
        r.execute("select 1")
        assert (
            REGISTRY.counter("trino_tpu_profiles_archived_total").value()
            == c0
        )

    def test_system_table_and_statistics(self):
        from trino_tpu.runtime.events import CollectingEventListener

        r = LocalQueryRunner()
        attach_profile_store(r, ProfileStore())
        ev = CollectingEventListener()
        r.events.add(ev)
        r.execute("select count(*) from nation")
        rows = r.execute(
            "select query_id, state, wall_s, resource_group, gate_wait_s "
            "from system.runtime.query_profiles"
        ).rows
        assert any(row[0] == "query_1" and row[1] == "FINISHED"
                   for row in rows)
        stats = ev.completed[0].statistics
        assert stats.gate_wait_s == 0.0
        assert stats.profile_key  # the event names its artifact

    def test_mesh_artifact_carries_fragments_and_collectives(self, dist):
        store = attach_profile_store(dist, ProfileStore())
        try:
            dist.execute(
                "select l_returnflag, count(*) from lineitem "
                "group by l_returnflag"
            )
            art = store.get(store.refs()[-1]["query_id"])
            assert art["mesh"].startswith("(8,")
            assert len(art["fragments"]) >= 2
            assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-9
            # phases carry the mesh decomposition, not just unattributed
            tracked = sum(
                art["phases"][p]
                for p in ("trace", "compute", "collective", "transfer",
                          "other")
            )
            assert tracked > 0
        finally:
            dist.profile_store = None

    def test_two_warm_archives_of_a_statement_diff_conservatively(self, dist):
        """tools/profile_diff over what the engine archived: two warm runs
        of one statement are comparable, each one's phases sum to its
        wall, so the phase deltas sum to the wall delta, and nothing the
        engine counts (collective bytes, counters) differs between them.
        How small the wall delta is, is a timing and is not asserted."""
        pd = _tool("profile_diff")
        store = attach_profile_store(dist, ProfileStore())
        sql = (
            "select l_returnflag, count(*) from lineitem "
            "group by l_returnflag"
        )
        try:
            for _ in range(3):  # cold, then two warm
                dist.execute(sql)
            a, b = (store.get(ref["key"]) for ref in store.refs()[-2:])
        finally:
            dist.profile_store = None
        rep = pd.diff_artifacts(a, b)
        assert rep["comparable"] and rep["sums_to_wall"] is True
        assert sum(rep["phases_delta_s"].values()) == pytest.approx(
            rep["wall_delta_s"], abs=1e-6
        )
        assert rep["collective_bytes_delta"] == {}
        assert rep["counters_delta"] == {}

    def test_coordinator_profile_endpoint(self):
        import urllib.request

        from trino_tpu.server.coordinator import CoordinatorServer

        r = LocalQueryRunner()
        attach_profile_store(r, ProfileStore())
        server = CoordinatorServer(runner=r, port=0)
        server.start()
        try:
            from trino_tpu.client import Client

            c = Client(f"http://127.0.0.1:{server.port}")
            _, rows = c.execute("select count(*) from region")
            assert [list(r) for r in rows] == [[5]]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/v1/query/q_1/profile",
                timeout=10,
            ) as resp:
                art = json.loads(resp.read().decode())
            assert art["state"] == "FINISHED"
            assert abs(sum(art["phases"].values()) - art["wall_s"]) < 1e-9
        finally:
            server.shutdown()


# -- profile_diff --------------------------------------------------------------


class TestProfileDiff:
    def _pair(self):
        frag_a = [{
            "fragment": 0, "kind": "SOURCE", "wall_s": 0.5,
            "phases_ms": {"compute": 400.0, "transfer": 100.0},
        }]
        frag_b = [{
            "fragment": 0, "kind": "SOURCE", "wall_s": 1.5,
            "phases_ms": {"compute": 400.0, "transfer": 1100.0},
        }]
        a = _artifact(
            query_id="query_a", wall=1.0,
            phases={"compute": 0.4, "transfer": 0.1}, fragments=frag_a,
            coll={"all_gather/broadcast": 1000},
            counters={"exchange_elided": 3},
        )
        b = _artifact(
            query_id="query_b", wall=2.2,
            phases={"compute": 0.4, "transfer": 1.1}, fragments=frag_b,
            gate_wait=0.2, coll={"all_gather/broadcast": 5000},
            counters={"exchange_elided": 1},
        )
        return a, b

    def test_diff_sums_to_wall_delta(self):
        pd = _tool("profile_diff")
        a, b = self._pair()
        rep = pd.diff_artifacts(a, b)
        assert rep["comparable"]
        assert rep["wall_delta_s"] == pytest.approx(1.2)
        assert rep["sums_to_wall"] is True
        assert sum(rep["phases_delta_s"].values()) == pytest.approx(
            rep["wall_delta_s"], abs=1e-9
        )

    def test_dominant_phase_and_fragment_named(self):
        pd = _tool("profile_diff")
        a, b = self._pair()
        rep = pd.diff_artifacts(a, b)
        assert rep["dominant_phase"] == "transfer"
        assert rep["dominant_fragment"] == 0
        assert rep["dominant"]["phase"] == "transfer"
        assert rep["collective_bytes_delta"] == {
            "all_gather/broadcast": 4000
        }
        assert rep["counters_delta"] == {"exchange_elided": -2}
        assert rep["gate_wait_delta_s"] == pytest.approx(0.2)

    def test_null_diff_contract(self):
        pd = _tool("profile_diff")
        a, _ = self._pair()
        rep = pd.diff_artifacts(a, a)
        assert rep["wall_delta_s"] == 0.0
        assert all(v == 0.0 for v in rep["phases_delta_s"].values())
        assert pd.null_diff_ok(rep)

    def test_null_diff_rejects_real_drift(self):
        pd = _tool("profile_diff")
        a, b = self._pair()
        assert not pd.null_diff_ok(pd.diff_artifacts(a, b))

    def test_incompatible_versions_refused(self):
        pd = _tool("profile_diff")
        a, b = self._pair()
        b = dict(b, version=99)
        with pytest.raises(ValueError):
            pd.diff_artifacts(a, b)

    def test_different_statements_flagged_not_comparable(self):
        pd = _tool("profile_diff")
        a, _ = self._pair()
        b = _artifact(query_id="query_c", sql="select 2", wall=1.0)
        assert pd.diff_artifacts(a, b)["comparable"] is False

    def test_cli_threshold_exit_codes(self, tmp_path):
        pd = _tool("profile_diff")
        a, b = self._pair()
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        # 120% drift over a 10% threshold -> exit 2
        assert pd.main([str(pa), str(pb)]) == 2
        # same artifact -> inside threshold -> exit 0
        assert pd.main([str(pa), str(pa)]) == 0
        # generous threshold swallows the drift
        assert pd.main([str(pa), str(pb), "--threshold", "5.0"]) == 0


# -- audit log -----------------------------------------------------------------


class TestAuditLog:
    def test_one_line_per_completion_with_fields(self, tmp_path):
        from trino_tpu.telemetry.audit import QueryAuditLog

        path = str(tmp_path / "audit.jsonl")
        r = LocalQueryRunner()
        r.events.add(QueryAuditLog(path))
        r.execute("select count(*) from region")
        with pytest.raises(Exception):
            r.execute("select * from missing_table")
        lines = [
            json.loads(l)
            for l in open(path).read().splitlines() if l
        ]
        assert len(lines) == 2
        ok, bad = lines
        assert ok["state"] == "FINISHED" and ok["rows"] == 1
        assert ok["wall_s"] > 0
        assert "gate_wait_s" in ok and "peak_memory_bytes" in ok
        assert bad["state"] == "FAILED"
        assert bad["error_type"] == "USER_ERROR"

    def test_size_based_rotation(self, tmp_path):
        from trino_tpu.runtime.events import QueryCompletedEvent
        from trino_tpu.telemetry.audit import QueryAuditLog

        path = str(tmp_path / "audit.jsonl")
        log = QueryAuditLog(path, rotate_bytes=600, rotate_keep=2)
        for i in range(12):
            log.query_completed(
                QueryCompletedEvent(
                    f"query_{i}", "select 1", "FINISHED", 0.0, 0.1
                )
            )
        assert os.path.exists(path + ".1")  # rotation happened
        # live segment stays under the knob
        assert os.path.getsize(path) <= 600
        # every surviving line still parses (rotation never tears lines)
        for p in (path, path + ".1"):
            for line in open(p).read().splitlines():
                if line:
                    json.loads(line)
        # rotate_keep bounds the segment chain
        assert not os.path.exists(path + ".3")

    def test_unwritable_path_fails_at_startup(self, tmp_path):
        from trino_tpu.telemetry.audit import QueryAuditLog

        blocker = tmp_path / "blocker"
        blocker.write_text("a file where a directory must go")
        with pytest.raises(OSError):
            QueryAuditLog(str(blocker / "x.jsonl"))

    def test_config_attach_is_noop_without_knob(self):
        from trino_tpu.telemetry.audit import attach_audit_log

        r = LocalQueryRunner()
        assert attach_audit_log(r) is None

    def test_audit_counts_metric(self, tmp_path):
        from trino_tpu.telemetry.audit import QueryAuditLog

        c0 = REGISTRY.counter("trino_tpu_audit_events_total").value()
        r = LocalQueryRunner()
        r.events.add(QueryAuditLog(str(tmp_path / "a.jsonl")))
        r.execute("select 1")
        assert (
            REGISTRY.counter("trino_tpu_audit_events_total").value()
            == c0 + 1
        )


# -- lane safety ---------------------------------------------------------------


class TestLaneSafety:
    def test_per_statement_handles_resolve_through_contextvar(self):
        from trino_tpu.runtime import lifecycle

        r = LocalQueryRunner()
        prof_a, prof_b = object(), object()
        ctx_a = lifecycle.QueryContext("query_a")
        ctx_b = lifecycle.QueryContext("query_b")
        ctx_a.mesh_profile = prof_a
        ctx_b.mesh_profile = prof_b
        results = {}

        def read(name, ctx):
            token = lifecycle.set_current(ctx)
            try:
                results[name] = r.last_mesh_profile
            finally:
                lifecycle.reset_current(token)

        ta = threading.Thread(target=read, args=("a", ctx_a), daemon=True,
                              name="lane-a")
        tb = threading.Thread(target=read, args=("b", ctx_b), daemon=True,
                              name="lane-b")
        ta.start(); tb.start(); ta.join(5.0); tb.join(5.0)
        assert results["a"] is prof_a
        assert results["b"] is prof_b
        assert r.last_mesh_profile is None  # no fallback written

    def test_concurrent_traced_statements_keep_their_own_traces(self):
        # K lanes racing EXPLAIN ANALYZE VERBOSE on ONE shared runner:
        # each rendered trace must carry ITS OWN statement's sql (the
        # pre-fix shared runner._tracer attribute raced and could render a
        # neighbor's tree)
        r = LocalQueryRunner()
        K, iters = 4, 3
        failures = []

        def client(i):
            sql = f"explain analyze verbose select {i} + 0"
            for _ in range(iters):
                try:
                    text = "\n".join(
                        row[0] for row in r.execute(sql).rows
                    )
                    tj = text.split("Trace JSON: ", 1)[1]
                    trace = json.loads(tj)
                    sqls = [
                        e["args"]["sql"]
                        for e in trace["traceEvents"]
                        if e["name"] == "query"
                    ]
                    if sqls != [sql]:  # a neighbor's sql = crossed tracer
                        failures.append((i, sqls))
                except Exception as e:
                    failures.append((i, repr(e)))

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True,
                             name=f"explain-lane-{i}")
            for i in range(K)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not failures, failures[:3]

    def test_concurrent_lanes_archive_distinct_artifacts(self, tmp_path):
        # K lanes completing simultaneously through ONE shared runner +
        # store: K distinct artifacts, each attributed to its own sql
        r = LocalQueryRunner()
        store = attach_profile_store(
            r, ProfileStore(archive_dir=str(tmp_path))
        )
        K = 4
        errors = []

        def client(i):
            try:
                r.execute(f"select {i} * 10")
            except Exception as e:
                errors.append(repr(e))

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True,
                             name=f"archive-lane-{i}")
            for i in range(K)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not errors
        assert store.flush(10.0)
        arts = [store.get(ref["query_id"]) for ref in store.refs()]
        sqls = sorted(a["sql"] for a in arts)
        assert sqls == sorted(f"select {i} * 10" for i in range(K))
        # each artifact's rows/wall belong to its own statement
        for a in arts:
            assert a["state"] == "FINISHED"
            assert abs(sum(a["phases"].values()) - a["wall_s"]) < 1e-9

    def test_queries_system_table_sees_gate_columns(self):
        # QueryStatistics carries the new gate/admission fields end to end
        from trino_tpu.runtime.events import CollectingEventListener
        from trino_tpu.runtime.resource_groups import (
            ResourceGroupConfig,
            ResourceGroupManager,
        )
        from trino_tpu.runtime.dispatcher import QueryDispatcher

        r = LocalQueryRunner()
        ev = CollectingEventListener()
        r.events.add(ev)
        mgr = ResourceGroupManager(
            ResourceGroupConfig("global", hard_concurrency=2, max_queued=8)
        )
        d = QueryDispatcher(r, mgr, lanes=2)
        ticket = d.enqueue()
        ticket.wait()
        d.run_admitted(ticket, lambda lane: lane.execute("select 7"))
        stats = ev.completed[-1].statistics
        assert stats.group == "global"
        assert stats.queued_s >= 0.0
        assert stats.gate_wait_s >= 0.0
