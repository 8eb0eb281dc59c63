"""Plan-decision ledger (telemetry/decisions): decision-time recording,
collective byte attribution under decision scopes, hindsight verdicts,
the profile-artifact / system-table / HTTP surfaces, and the ledger's
completeness over running statements (reference style: TestQueryStats'
reorderedJoin/replicatedJoin flags, generalized to every choice)."""

import json
import os
import sys
import threading

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.runtime import lifecycle
from trino_tpu.runtime.lifecycle import QueryContext
from trino_tpu.telemetry.decisions import (
    DECISION_KINDS,
    EXCHANGE_KINDS,
    HINDSIGHT,
    DecisionLedger,
    current_decision,
    decision_scope,
    ensure_ledger,
    observe_collective,
    observe_decision,
    record_decision,
)


# -- ledger unit behaviour ----------------------------------------------------


class TestLedger:
    def test_record_assigns_stable_ids_and_copies_inputs(self):
        led = DecisionLedger("q_t")
        seen = {"estimated_build_rows": 5}
        d0 = led.record("join_distribution", "planner", "broadcast",
                        "partitioned", seen)
        d1 = led.record("exchange", "planner", "repartition", "")
        assert (d0, d1) == ("d000", "d001")
        seen["estimated_build_rows"] = 999  # the ledger keeps what was SEEN
        assert led.decisions[0].inputs == {"estimated_build_rows": 5}
        # audit watermark stamped at decision time (cross-ref key is
        # (query_id, seq): audit lines with a higher seq happened after)
        assert isinstance(led.decisions[0].audit_seq, int)
        assert led.decisions[1].audit_seq >= led.decisions[0].audit_seq

    def test_observe_merges_and_ignores_unknown(self):
        led = DecisionLedger("q_t")
        did = led.record("join_capacity", "runtime", "licensed", "runtime_check")
        led.observe(did, live_cap=128)
        led.observe(did, executed=1)
        led.observe("d999", bogus=1)  # unknown id: dropped, never raises
        led.observe(None, bogus=1)
        assert led.decisions[0].measured == {"live_cap": 128, "executed": 1}

    def test_collective_attribution_and_unattributed_bucket(self):
        led = DecisionLedger("q_t")
        did = led.record("join_distribution", "planner", "broadcast", "partitioned")
        led.observe_collective(did, 0, 1000, "all_gather", "broadcast")
        led.observe_collective(did, 0, 24, "all_gather", "broadcast")
        led.observe_collective(did, 1, 8, "gather", "capacity_sizing")
        d = led.decisions[0]
        assert d.bytes_by == {
            ("all_gather", "broadcast"): 1024,
            ("gather", "capacity_sizing"): 8,
        }
        # exchange_bytes counts only the exchange plane, not host pulls
        assert d.exchange_bytes == 1024
        assert sorted(set(d.fragments)) == [0, 1]
        # scopeless exchange bytes land in the unattributed bucket...
        led.observe_collective(None, 2, 77, "all_to_all", "repartition")
        assert led.unattributed == {("all_to_all", "repartition"): 77}
        # ...but scopeless host pulls are not placements: dropped
        led.observe_collective(None, 2, 5, "gather", "result")
        assert ("gather", "result") not in led.unattributed

    def test_to_json_shape(self):
        led = DecisionLedger("q_t")
        did = led.record("exchange", "planner", "repartition", "broadcast")
        led.observe_collective(did, 3, 64, "all_to_all", "repartition")
        led.finalize()
        doc = led.to_json()
        assert doc["query_id"] == "q_t" and doc["finalized"] is True
        (d,) = doc["decisions"]
        assert d["kind"] == "exchange" and d["choice"] == "repartition"
        assert d["bytes_by"] == {"all_to_all/repartition": 64}
        assert d["exchange_bytes"] == 64 and d["fragments"] == [3]
        assert d["hindsight"] in HINDSIGHT
        json.dumps(doc)  # artifact-ready: plain JSON types throughout

    def test_finalize_idempotent(self):
        from trino_tpu.telemetry.metrics import plan_decisions_counter

        led = DecisionLedger("q_t")
        did = led.record("exchange", "planner", "repartition", "")
        led.observe_collective(did, 0, 10, "all_to_all", "repartition")
        c = plan_decisions_counter().labels("exchange", "repartition", "vindicated")
        before = c.value()
        led.finalize()
        led.finalize()  # second call: no re-stamp, no double counting
        assert c.value() == before + 1
        assert led.decisions[0].hindsight == "vindicated"

    def test_fragment_wall_join(self):
        led = DecisionLedger("q_t")
        did = led.record("exchange", "planner", "repartition", "")
        led.observe_collective(did, 0, 10, "all_to_all", "repartition")
        led.observe_collective(did, 0, 10, "all_to_all", "repartition")
        led.observe_collective(did, 2, 10, "all_to_all", "repartition")
        led.finalize(fragment_phases={0: 1.5, 1: 9.0, 2: 0.25})
        # fragment 0 counts ONCE despite two collectives; fragment 1
        # never attributed here, so its wall never bleeds in
        assert led.decisions[0].measured["fragment_wall_s"] == pytest.approx(1.75)


# -- hindsight rules ----------------------------------------------------------


def _finalized(kind, choice, alternative="", inputs=None, bytes_by=(),
               measured=None, w=8, ratio=2.0, floor=1 << 20):
    led = DecisionLedger("q_h")
    did = led.record(kind, "site", choice, alternative, inputs)
    for collective_kind, purpose, nbytes in bytes_by:
        led.observe_collective(did, 0, nbytes, collective_kind, purpose)
    led.observe(did, **(measured or {}))
    led.finalize(n_workers=w, regret_ratio=ratio, min_bytes=floor)
    return led.decisions[0]


class TestHindsight:
    def test_broadcast_regret_when_partitioned_was_cheaper(self):
        # 8 MiB replicated 8x; the rejected partitioned plan would have
        # shipped one copy (1 MiB) plus a placed probe (0) — 8x worse
        d = _finalized(
            "join_distribution", "broadcast", "partitioned",
            bytes_by=[("all_gather", "broadcast", 8 << 20)],
            measured={"probe_move_bytes": 0},
        )
        assert d.hindsight == "regret"
        assert "broadcast moved" in d.hindsight_detail

    def test_broadcast_under_floor_never_flags(self):
        d = _finalized(
            "join_distribution", "broadcast", "partitioned",
            bytes_by=[("all_gather", "broadcast", 4096)],
            measured={"probe_move_bytes": 0},
        )
        assert d.hindsight == "vindicated" and "floor" in d.hindsight_detail

    def test_broadcast_vindicated_when_probe_move_dominates(self):
        # the rejected plan would repartition a 32 MiB probe: broadcast won
        d = _finalized(
            "join_distribution", "broadcast", "partitioned",
            bytes_by=[("all_gather", "broadcast", 8 << 20)],
            measured={"probe_move_bytes": 32 << 20},
        )
        assert d.hindsight == "vindicated"

    def test_broadcast_without_bytes_is_unmeasured(self):
        d = _finalized("join_distribution", "broadcast", "partitioned")
        assert d.hindsight == "unmeasured"

    def test_partitioned_regret_when_broadcast_was_cheaper(self):
        d = _finalized(
            "join_distribution", "partitioned", "broadcast",
            bytes_by=[("all_to_all", "repartition", 64 << 20)],
            measured={"build_bytes": 1 << 20},  # 8 copies = 8 MiB rejected
        )
        assert d.hindsight == "regret"

    def test_partitioned_vindicated(self):
        d = _finalized(
            "join_distribution", "partitioned", "broadcast",
            bytes_by=[("all_to_all", "repartition", 2 << 20)],
            measured={"build_bytes": 1 << 20},
        )
        assert d.hindsight == "vindicated"

    def test_licensed_regret_when_width_overshoots_live(self):
        d = _finalized(
            "join_capacity", "licensed", "runtime_check",
            inputs={"licensed_cap": 65536},
            measured={"executed": 1, "live_cap": 2048},
        )
        assert d.hindsight == "regret"

    def test_licensed_vindicated_at_live_width(self):
        d = _finalized(
            "join_capacity", "licensed", "runtime_check",
            inputs={"licensed_cap": 4096},
            measured={"executed": 1, "live_cap": 4096},
        )
        assert d.hindsight == "vindicated"

    def test_declined_regret_when_decline_bought_nothing(self):
        d = _finalized(
            "join_capacity", "declined", "licensed",
            inputs={"licensed_cap": 4096},
            measured={"executed": 1, "runtime_cap": 4096},
        )
        assert d.hindsight == "regret"
        assert "bought nothing" in d.hindsight_detail

    def test_declined_vindicated_when_runtime_sized_smaller(self):
        d = _finalized(
            "join_capacity", "declined", "licensed",
            inputs={"licensed_cap": 4096},
            measured={"executed": 1, "runtime_cap": 512},
        )
        assert d.hindsight == "vindicated"

    def test_runtime_check_vindicated_once_measured(self):
        d = _finalized(
            "join_capacity", "runtime_check", "",
            measured={"executed": 1, "runtime_cap": 512},
        )
        assert d.hindsight == "vindicated"
        assert _finalized("join_capacity", "runtime_check", "").hindsight == (
            "unmeasured"
        )

    def test_mechanical_kinds_vindicate_on_any_outcome(self):
        d = _finalized(
            "exchange", "repartition", "",
            bytes_by=[("all_to_all", "repartition", 100)],
        )
        assert d.hindsight == "vindicated"
        assert _finalized("schedule_license", "sync", "async").hindsight == (
            "unmeasured"
        )


# -- ambient resolution (lane safety) -----------------------------------------


class TestAmbient:
    def test_record_decision_noops_outside_statement(self):
        assert lifecycle.current_query() is None
        assert record_decision("exchange", "s", "repartition") is None
        observe_collective(0, 10, "all_to_all", "repartition")  # no-op
        observe_decision("d000", x=1)  # no-op

    def test_decision_scope_innermost_wins(self):
        ctx = QueryContext("q_scope")
        led = ensure_ledger(ctx)
        token = lifecycle.set_current(ctx)
        try:
            outer = record_decision("join_distribution", "s", "partitioned")
            inner = record_decision("exchange", "s", "repartition")
            assert current_decision() is None
            with decision_scope(outer):
                observe_collective(0, 100, "all_to_all", "repartition")
                with decision_scope(inner):
                    assert current_decision() == inner
                    observe_collective(0, 7, "all_to_all", "repartition")
                # decision_scope(None) is transparent: the outer holds
                with decision_scope(None):
                    assert current_decision() == outer
                    observe_collective(0, 1, "all_gather", "broadcast")
            assert current_decision() is None
        finally:
            lifecycle.reset_current(token)
        assert led._by_id[outer].bytes_by == {
            ("all_to_all", "repartition"): 100,
            ("all_gather", "broadcast"): 1,
        }
        assert led._by_id[inner].bytes_by == {("all_to_all", "repartition"): 7}

    def test_ledgers_isolate_across_threads(self):
        """Two statement threads (dispatcher lanes) record concurrently:
        each ledger sees only its own decisions."""
        results = {}

        def lane(qid):
            ctx = QueryContext(qid)
            led = ensure_ledger(ctx)
            token = lifecycle.set_current(ctx)
            try:
                for _ in range(50):
                    did = record_decision("exchange", qid, "repartition")
                    with decision_scope(did):
                        observe_collective(0, 10, "all_to_all", "repartition")
            finally:
                lifecycle.reset_current(token)
            results[qid] = led

        ts = [
            threading.Thread(target=lane, args=(f"q_iso_{i}",))
            for i in range(4)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(results) == 4
        for qid, led in results.items():
            assert len(led.decisions) == 50
            assert led.unattributed == {}
            assert all(d.site == qid for d in led.decisions)


# -- distributed integration: completeness + the Q3-broadcast regret ----------


def _store_runner():
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.telemetry.profile_store import (
        ProfileStore,
        attach_profile_store,
    )

    r = DistributedQueryRunner(n_workers=8, schema="tiny")
    store = ProfileStore()
    attach_profile_store(r, store)
    return r, store


@pytest.fixture(scope="module")
def dist_store():
    return _store_runner()


JOIN_SQL = (
    "select c_mktsegment, count(*) from customer "
    "join orders on c_custkey = o_custkey group by c_mktsegment"
)


class TestDistributedLedger:
    #: statement, and the decision kinds its ledger must hold
    WARM_SET = {
        "join": (JOIN_SQL, {"join_distribution"}),
        "q6": (QUERIES[6], set()),
        "q3": (QUERIES[3], {"join_distribution", "join_capacity"}),
    }

    @pytest.mark.parametrize("name", sorted(WARM_SET))
    def test_warm_ledger_is_complete(self, dist_store, name):
        r, store = dist_store
        sql, kinds_wanted = self.WARM_SET[name]
        r.execute(sql)  # settle: learned capacities change the choices
        r.execute(sql)
        art = store.get(store.refs()[-1]["key"])
        led = art["decisions"]
        assert led["finalized"] is True
        assert led["unattributed_bytes_by"] == {}
        assert led["decisions"], "a distributed statement records decisions"
        kinds = {d["kind"] for d in led["decisions"]}
        assert kinds_wanted <= kinds <= set(DECISION_KINDS)
        # completeness: per exchange kind, decision-attributed bytes equal
        # the profile's collective totals — every byte maps to ONE choice
        by_kind = {k: 0 for k in EXCHANGE_KINDS}
        for d in led["decisions"]:
            assert d["hindsight"] in HINDSIGHT
            for key, b in d["bytes_by"].items():
                kind = key.split("/", 1)[0]
                if kind in by_kind:
                    by_kind[kind] += int(b)
        profile_by = art["collective_bytes_by"]
        for kind in EXCHANGE_KINDS:
            total = sum(
                int(b) for key, b in profile_by.items()
                if key.split("/", 1)[0] == kind
            )
            assert by_kind[kind] == total, (kind, led, profile_by)

    def test_forced_broadcast_of_big_build_flags_regret(self):
        """The PR 14 Q3 shape: broadcasting the orders build side moved W
        full copies when partitioned would have moved one — the ledger
        must stamp that choice `regret` (with the floor lowered; tiny
        schema bytes sit under the 1 MiB default noise floor)."""
        r, store = _store_runner()
        r.execute("set session join_distribution_type = 'BROADCAST'")
        r.execute("set session decision_regret_min_bytes = 1024")
        r.execute(
            "select count(*) from customer join orders on c_custkey = o_custkey"
        )
        art = store.get(store.refs()[-1]["key"])
        led = art["decisions"]
        bcasts = [
            d for d in led["decisions"]
            if d["kind"] == "join_distribution" and d["choice"] == "broadcast"
        ]
        assert bcasts, led
        d = bcasts[0]
        assert d["alternative"] == "partitioned"
        assert d["inputs"]["join_distribution_type"] == "BROADCAST"
        assert d["exchange_bytes"] > 1024
        assert d["hindsight"] == "regret", d
        assert "broadcast moved" in d["hindsight_detail"]

    def test_partitioned_choice_vindicated_same_query(self):
        """The counterfactual to the regret test: a partitioned plan for
        the same join moves each side once — never a regret, even with
        the noise floor lowered to the regret test's 1 KiB."""
        r, store = _store_runner()
        r.execute("set session join_distribution_type = 'PARTITIONED'")
        r.execute("set session decision_regret_min_bytes = 1024")
        r.execute(
            "select count(*) from customer join orders on c_custkey = o_custkey"
        )
        art = store.get(store.refs()[-1]["key"])
        dists = [
            d for d in art["decisions"]["decisions"]
            if d["kind"] == "join_distribution"
        ]
        assert dists, art["decisions"]
        assert all(d["choice"] != "broadcast" for d in dists)
        assert all(d["hindsight"] == "vindicated" for d in dists), dists

    def test_plan_decisions_system_table(self, dist_store):
        r, store = dist_store
        r.execute(JOIN_SQL)
        res = r.execute(
            "select query_id, decision_id, kind, choice, hindsight, "
            "exchange_bytes from system.runtime.plan_decisions"
        )
        assert res.rows, "archived ledgers must feed the system table"
        kinds = {row[2] for row in res.rows}
        assert "join_distribution" in kinds
        for qid, did, kind, choice, hindsight, xbytes in res.rows:
            assert did.startswith("d") and kind in DECISION_KINDS
            assert hindsight in HINDSIGHT
            assert isinstance(xbytes, int) and xbytes >= 0
        # one row per ledger entry: (query_id, decision_id) never repeats
        pairs = [(row[0], row[1]) for row in res.rows]
        assert len(pairs) == len(set(pairs))


# -- HTTP surface -------------------------------------------------------------


def test_decisions_endpoint():
    import urllib.request
    from urllib.error import HTTPError

    from trino_tpu.client import Client
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.telemetry.profile_store import (
        ProfileStore,
        attach_profile_store,
    )

    r = LocalQueryRunner()
    attach_profile_store(r, ProfileStore())
    server = CoordinatorServer(runner=r, port=0)
    server.start()
    try:
        c = Client(f"http://127.0.0.1:{server.port}")
        _, rows = c.execute("select count(*) from region")
        assert [list(x) for x in rows] == [[5]]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/v1/query/q_1/decisions",
            timeout=10,
        ) as resp:
            led = json.loads(resp.read().decode())
        assert led["finalized"] is True
        assert isinstance(led["decisions"], list)
        assert led["unattributed_bytes_by"] == {}
        with pytest.raises(HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/v1/query/nope/decisions",
                timeout=10,
            )
    finally:
        server.shutdown()


# -- decision_report ----------------------------------------------------------


def _artifact(decisions, unattributed=None, finalized=True):
    return {
        "query_id": "query_7",
        "sql": "select 1",
        "wall_s": 2.0,
        "decisions": {
            "query_id": "query_7",
            "decisions": decisions,
            "unattributed_bytes_by": unattributed or {},
            "finalized": finalized,
        },
    }


def _d(did, hindsight="vindicated", wall=0.0, xbytes=0, kind="exchange",
       choice="repartition"):
    return {
        "decision_id": did, "kind": kind, "site": "s", "choice": choice,
        "alternative": "broadcast", "inputs": {}, "audit_seq": 0,
        "measured": {"fragment_wall_s": wall} if wall else {},
        "bytes_by": {"all_to_all/repartition": xbytes} if xbytes else {},
        "exchange_bytes": xbytes, "fragments": [0],
        "hindsight": hindsight, "hindsight_detail": "",
    }


class TestDecisionReport:
    def test_report_sorts_by_measured_cost(self):
        dr = _tool("decision_report")
        rep = dr.report(_artifact([
            _d("d000", wall=0.1, xbytes=10),
            _d("d001", hindsight="regret", wall=1.5, xbytes=999),
            _d("d002", wall=0.1, xbytes=500),
        ]))
        assert [r["decision_id"] for r in rep["rows"]] == [
            "d001", "d002", "d000"
        ]
        assert [r["decision_id"] for r in rep["regrets"]] == ["d001"]
        assert rep["finalized"] is True

    def test_render_flags_regrets_and_unattributed(self):
        dr = _tool("decision_report")
        text = dr.render(dr.report(_artifact(
            [_d("d000", hindsight="regret", xbytes=4096)],
            unattributed={"all_gather/broadcast": 55},
        )))
        assert "!! d000" in text
        assert "UNATTRIBUTED" in text and "55" in text

    def test_main_exit_codes(self, tmp_path, capsys):
        dr = _tool("decision_report")
        clean = tmp_path / "clean.json"
        clean.write_text(json.dumps(_artifact([_d("d000")])))
        assert dr.main([str(clean)]) == 0
        bad = tmp_path / "regret.json"
        bad.write_text(json.dumps(_artifact([_d("d000", hindsight="regret")])))
        assert dr.main([str(bad), "--regrets-only"]) == 2
        assert "d000" in capsys.readouterr().out
        assert dr.main([str(tmp_path / "missing.json")]) == 1


# -- audit-log cross-reference ------------------------------------------------


class TestAuditCrossReference:
    def test_audit_lines_carry_monotonic_sequence(self, tmp_path):
        """Satellite: every audit line carries the next process-wide
        sequence number — an external tail detects gaps, and the ledger
        cross-references by (query_id, seq)."""
        from trino_tpu.runtime.runner import LocalQueryRunner
        from trino_tpu.telemetry.audit import QueryAuditLog

        path = str(tmp_path / "audit.jsonl")
        r = LocalQueryRunner()
        r.events.add(QueryAuditLog(path))
        for _ in range(3):
            r.execute("select count(*) from region")
        lines = [
            json.loads(l) for l in open(path).read().splitlines() if l
        ]
        seqs = [l["seq"] for l in lines]
        assert len(seqs) == 3
        assert seqs == sorted(seqs) and len(set(seqs)) == 3
        # consecutive lines from ONE writer: contiguous (no silent drop)
        assert seqs[2] - seqs[0] == 2

    def test_decision_watermark_orders_against_audit_lines(self, tmp_path):
        """A decision's audit_seq watermark partitions the audit stream:
        lines with seq <= watermark happened before the choice, lines
        with seq > watermark after — the shed/kill forensics join key."""
        from trino_tpu.parallel import DistributedQueryRunner
        from trino_tpu.telemetry.audit import QueryAuditLog
        from trino_tpu.telemetry.profile_store import (
            ProfileStore,
            attach_profile_store,
        )

        path = str(tmp_path / "audit.jsonl")
        r = DistributedQueryRunner(n_workers=8, schema="tiny")
        store = ProfileStore()
        attach_profile_store(r, store)
        r.events.add(QueryAuditLog(path))
        r.execute("select count(*) from region")  # audit line 1
        r.execute(JOIN_SQL)                       # decisions, then line 2
        lines = [
            json.loads(l) for l in open(path).read().splitlines() if l
        ]
        assert len(lines) == 2
        art = store.get(store.refs()[-1]["key"])
        decisions = art["decisions"]["decisions"]
        assert decisions
        seqs = [d["audit_seq"] for d in decisions]
        # recorded in ledger order: the watermark never goes backwards
        assert seqs == sorted(seqs)
        # every decision of query 2 falls AFTER query 1's completion line
        # and BEFORE its own completion line
        assert all(lines[0]["seq"] <= s < lines[1]["seq"] for s in seqs)
