"""Concurrent dispatcher + admission control (runtime/dispatcher).

Tier-1 serving tests: weighted-fair scheduling, per-group concurrency and
memory sub-pools, queue deadlines, load shedding (HTTP 429 + Retry-After
before the body is read), queued-query cancel racing admission, graceful
drain, spill release on abort, and the system.runtime.resource_groups SQL
surface.  Deterministic where possible (counter-driven clocks, events);
real timeouts kept to tens of milliseconds.  The HTTP-worker chaos
composition (worker kill at W-1 x pool shrink x K clients) lives in
tests/test_chaos.py (slow).
"""

import threading
import time

import pytest

from trino_tpu.runtime.dispatcher import (
    DispatcherStoppedError,
    QueryDispatcher,
    QueryShedError,
)
from trino_tpu.runtime.lifecycle import (
    QueryCanceledException,
    QueryQueuedTimeExceeded,
)
from trino_tpu.runtime.resource_groups import (
    GroupMemoryEscalation,
    ResourceGroupConfig,
    ResourceGroupManager,
)


class _DummyRunner:
    """Engine stand-in for scheduler-only tests: cloneable, no device."""

    def clone_for_dispatch(self):
        return _DummyRunner()


def _manager(*configs):
    mgr = ResourceGroupManager()
    for c in configs:
        mgr.add(c)
    return mgr


def _run_all(dispatcher, tickets, fn):
    """One thread per ticket: wait for admission, run fn(group_name)."""
    threads = []
    for t in tickets:
        def work(t=t):
            try:
                t.wait()
            except Exception:
                return
            dispatcher.run_admitted(t, lambda _r: fn(t.group_name))

        th = threading.Thread(target=work, daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=30)
        assert not th.is_alive(), "admission wait hung"


# -- weighted-fair scheduling --------------------------------------------------


def test_weighted_fair_ratio_across_saturated_groups():
    """Two saturated groups with weights 3:1 share one lane 3:1 — the
    scheduler picks by weighted virtual time, not round-robin or FIFO."""
    mgr = _manager(
        ResourceGroupConfig("a", hard_concurrency=1, weight=3),
        ResourceGroupConfig("b", hard_concurrency=1, weight=1),
    )
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=1)
    gate = threading.Event()
    blocker = d.enqueue(group_name="global")
    blocker.wait()
    done = threading.Thread(
        target=lambda: d.run_admitted(blocker, lambda _r: gate.wait(10)),
        daemon=True,
    )
    done.start()
    tickets = []
    for _ in range(9):
        tickets.append(d.enqueue(group_name="a"))
    for _ in range(3):
        tickets.append(d.enqueue(group_name="b"))
    order = []
    lock = threading.Lock()

    def record(group):
        with lock:
            order.append(group)

    gate.set()  # release the lane: admissions begin
    _run_all(d, tickets, record)
    done.join(timeout=10)
    # single lane => execution order == admission order; first 8 picks
    # must honor the 3:1 weights (6 a's, 2 b's)
    assert order.count("a") == 9 and order.count("b") == 3
    first8 = order[:8]
    assert first8.count("a") == 6 and first8.count("b") == 2, order


def test_group_hard_concurrency_bounds_parallelism():
    mgr = _manager(ResourceGroupConfig("g", hard_concurrency=2, max_queued=16))
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=4)
    peak = {"now": 0, "max": 0}
    lock = threading.Lock()

    def tracked(_group):
        with lock:
            peak["now"] += 1
            peak["max"] = max(peak["max"], peak["now"])
        time.sleep(0.02)
        with lock:
            peak["now"] -= 1

    tickets = [d.enqueue(group_name="g") for _ in range(6)]
    _run_all(d, tickets, tracked)
    assert peak["max"] == 2  # 4 lanes free, but the group caps at 2


def test_lanes_overlap_execution():
    """With 2 lanes, two admitted statements genuinely overlap (the old
    global engine lock could never pass this barrier)."""
    mgr = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=2)
    )
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=2)
    barrier = threading.Barrier(2, timeout=10)
    tickets = [d.enqueue() for _ in range(2)]
    _run_all(d, tickets, lambda _g: barrier.wait())
    assert not barrier.broken  # both statements were inside at once


# -- shedding + queue deadlines ------------------------------------------------


def test_full_queue_sheds_with_retry_after():
    from trino_tpu.telemetry.metrics import queries_shed_counter

    mgr = _manager(ResourceGroupConfig("g", hard_concurrency=1, max_queued=1))
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=1)
    t1 = d.enqueue(group_name="g")  # runs
    d.enqueue(group_name="g")  # queues (1/1)
    shed0 = queries_shed_counter().value(("g",))
    with pytest.raises(QueryShedError) as ei:
        d.enqueue(group_name="g")  # queue full -> shed
    assert ei.value.retryable and ei.value.retry_after_s > 0
    assert ei.value.error_code == "QUERY_QUEUE_FULL"
    assert queries_shed_counter().value(("g",)) == shed0 + 1
    # shed_probe (the pre-body HTTP check) agrees while full
    mgr.add_user_rule("u", "g")
    assert d.shed_probe("u") is not None


def test_shed_probe_admits_when_idle_even_with_zero_queue():
    """max_queued=0 means 'never queue', not 'never run': an idle group
    admits immediately and the probe must not shed it."""
    mgr = _manager(ResourceGroupConfig("g", hard_concurrency=1, max_queued=0))
    mgr.add_user_rule("u", "g")
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=1)
    assert d.shed_probe("u") is None
    t = d.enqueue(group_name="g")
    assert t.wait() is not None
    assert d.shed_probe("u") is not None  # slot held -> now it sheds
    d.release(t)


def test_queue_deadline_raises_exceeded_queued_time():
    from trino_tpu.telemetry.metrics import query_queued_histogram

    d = QueryDispatcher(_DummyRunner(), _manager(), lanes=1)
    blocker = d.enqueue()
    blocker.wait()
    n0 = query_queued_histogram().value()
    t = d.enqueue(queue_deadline_s=0.05)
    with pytest.raises(QueryQueuedTimeExceeded) as ei:
        t.wait()
    assert ei.value.error_code == "EXCEEDED_QUEUED_TIME_LIMIT"
    assert query_queued_histogram().value() == n0 + 1  # wait observed
    d.release(blocker)
    # the expired ticket left the queue: the group is clean
    assert d.stats()[0]["queued"] == 0 or all(
        s["queued"] == 0 for s in d.stats()
    )


# -- queued-query cancel -------------------------------------------------------


def test_cancel_while_queued_never_acquires_slot():
    d = QueryDispatcher(_DummyRunner(), _manager(), lanes=1)
    blocker = d.enqueue()
    blocker.wait()
    admitted_before = d.stats()[0]["total_admitted"]
    t = d.enqueue()
    t.cancel()
    with pytest.raises(QueryCanceledException):
        t.wait()
    d.release(blocker)
    # the canceled ticket was dequeued, not admitted
    stats = {s["name"]: s for s in d.stats()}
    assert stats["global"]["total_admitted"] == admitted_before
    assert stats["global"]["queued"] == 0


def test_cancel_racing_admission_hands_slot_back():
    """A DELETE that lands after the grant but before execution must hand
    the lane and group slot straight back — zero engine time consumed."""
    d = QueryDispatcher(_DummyRunner(), _manager(), lanes=1)
    t = d.enqueue()  # free lane: admitted synchronously
    t.cancel()
    with pytest.raises(QueryCanceledException):
        t.wait()
    stats = {s["name"]: s for s in d.stats()}
    assert stats["global"]["running"] == 0
    # the returned slot admits the next query immediately
    t2 = d.enqueue()
    assert t2.wait() is not None
    d.release(t2)


# -- drain ---------------------------------------------------------------------


def test_drain_fails_queued_classified_and_force_kills_running():
    d = QueryDispatcher(_DummyRunner(), _manager(), lanes=1)
    running_ev = threading.Event()
    blocker = d.enqueue()
    blocker.wait()
    blocker.on_force_kill = running_ev.set

    th = threading.Thread(
        target=lambda: d.run_admitted(
            blocker, lambda _r: running_ev.wait(10)
        ),
        daemon=True,
    )
    th.start()
    queued = d.enqueue()
    clean = d.drain(wait_s=0.05, grace_s=5.0)
    with pytest.raises(DispatcherStoppedError) as ei:
        queued.wait()
    assert ei.value.error_code == "SERVER_SHUTTING_DOWN"
    assert running_ev.is_set()  # force-kill reached the running statement
    assert clean  # ... and it released inside the grace window
    th.join(timeout=10)
    with pytest.raises(DispatcherStoppedError):
        d.enqueue()  # admission is closed for good


# -- legacy interop ------------------------------------------------------------


def test_legacy_release_wakes_queued_dispatcher_ticket():
    """A slot freed through the OLD blocking API must wake tickets waiting
    in the dispatcher's queue — both admission surfaces share one slot
    counter, so both must schedule (regression: the ticket used to wait
    until some unrelated dispatcher event happened to fire)."""
    mgr = _manager(ResourceGroupConfig("g", hard_concurrency=1, max_queued=4))
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=2)
    g = mgr.groups["g"]
    g.acquire()  # legacy holder takes the only slot
    t = d.enqueue(group_name="g")  # dispatcher ticket queues behind it
    admitted = threading.Event()

    def waiter():
        t.wait()
        admitted.set()

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(0.05)
    assert not admitted.is_set()
    g.release()  # LEGACY release: must kick the dispatcher's scheduler
    assert admitted.wait(timeout=5), "legacy release never woke the ticket"
    d.release(t)
    th.join(timeout=5)


def test_lanes_share_transaction_state():
    """BEGIN on one lane, COMMIT on another: the HTTP protocol has no
    session affinity, so every lane must see ONE TransactionManager
    (the shared pre-dispatcher runner's semantics)."""
    from trino_tpu.runtime.runner import LocalQueryRunner

    primary = LocalQueryRunner()
    lane = primary.clone_for_dispatch()
    assert lane.transactions is primary.transactions
    lane.execute("start transaction")
    assert primary.in_transaction
    primary.execute("commit")
    assert not lane.in_transaction


def test_legacy_acquire_shares_the_concurrency_limit():
    """A slot held through the old blocking ResourceGroup.acquire() (dbapi
    sessions) counts against dispatcher admissions: one limit, two
    admission surfaces."""
    mgr = _manager(ResourceGroupConfig("g", hard_concurrency=1, max_queued=0))
    d = QueryDispatcher(_DummyRunner(), mgr, lanes=2)
    g = mgr.groups["g"]
    g.acquire()
    with pytest.raises(QueryShedError):
        d.enqueue(group_name="g")
    g.release()
    t = d.enqueue(group_name="g")
    assert t.wait() is not None
    d.release(t)


# -- system.prewarm admission --------------------------------------------------


def test_system_admission_holds_primary_lane_while_users_flow():
    d = QueryDispatcher(_DummyRunner(), _manager(), lanes=2)
    with d.system_admission() as runner:
        assert runner is d.runner  # primary lane granted
        stats = {s["name"]: s for s in d.stats()}
        assert stats["system.prewarm"]["running"] == 1
        # a user statement still admits on the second lane mid-replay
        t = d.enqueue()
        assert t.wait() is not None
        d.release(t)
    stats = {s["name"]: s for s in d.stats()}
    assert stats["system.prewarm"]["running"] == 0


# -- resource-group properties file --------------------------------------------


def test_resource_groups_from_properties():
    mgr = ResourceGroupManager.from_properties({
        "resource-groups.global.max-concurrency": "4",
        "resource-groups.etl.weight": "2",
        "resource-groups.etl.max-queued": "7",
        "resource-groups.etl.memory-limit-bytes": "1048576",
        "resource-groups.user.batch": "etl",
        "unrelated.key": "x",
    })
    assert mgr.default.config.hard_concurrency == 4
    etl = mgr.groups["etl"].config
    assert (etl.weight, etl.max_queued, etl.memory_limit_bytes) == (
        2, 7, 1048576
    )
    assert mgr.select("batch").config.name == "etl"
    assert mgr.select("adhoc").config.name == "global"
    with pytest.raises(ValueError):
        ResourceGroupManager.from_properties(
            {"resource-groups.g.max-concurency": "4"}  # typo must raise
        )
    with pytest.raises(ValueError):
        ResourceGroupManager.from_properties(
            {"resource-groups.user.u": "nope"}
        )


# -- per-group memory sub-pools ------------------------------------------------


def _pool_with_groups():
    from trino_tpu.runtime.memory import MemoryPool

    pool = MemoryPool(limit_bytes=0)
    ga = ResourceGroupConfig("a", memory_limit_bytes=1000)
    gb = ResourceGroupConfig("b", memory_limit_bytes=1000)
    from trino_tpu.runtime.resource_groups import ResourceGroup

    a = ResourceGroup(ga).memory_context(pool.root)
    b = ResourceGroup(gb).memory_context(pool.root)
    return pool, a, b


def _query_under(group_ctx, pool, name):
    q = group_ctx.child(name)
    q.is_query_root = True
    with pool.root._lock:
        group_ctx.query_children.append(q)
        pool.root.query_children.append(q)
    return q


class _Killable:
    def __init__(self):
        self.killed = None

    def kill(self, reason, detail=None):
        self.killed = (reason, detail)


def test_group_limit_kills_largest_in_group_never_bystander():
    pool, a, b = _pool_with_groups()
    q1 = _query_under(a, pool, "query:q1")
    q2 = _query_under(a, pool, "query:q2")
    q2.owner = _Killable()
    bystander = _query_under(b, pool, "query:by")
    bystander.owner = _Killable()
    bystander.add_bytes(900)  # group b, nearly at ITS limit
    q2.add_bytes(600)
    # q1's reservation breaches group a's 1000-byte limit; escalation
    # (installed by memory_context) kills q2 — the largest IN GROUP A —
    # and the reservation then fits
    q1.add_bytes(600)
    assert q2.owner.killed is not None and q2.owner.killed[0] == "memory"
    assert bystander.owner.killed is None  # never a cross-group kill
    assert bystander.reserved == 900
    assert a.reserved == 600 and q1.reserved == 600
    esc = a.on_exceeded
    assert esc.kill_log == [("a", "query:q2")]


def test_group_limit_requester_largest_fails_own_reservation():
    pool, a, _b = _pool_with_groups()
    q1 = _query_under(a, pool, "query:q1")
    q1.owner = _Killable()
    from trino_tpu.runtime.memory import ExceededMemoryLimitException

    q1.add_bytes(800)
    with pytest.raises(ExceededMemoryLimitException):
        q1.add_bytes(800)  # largest is the requester: no kill, raise
    assert q1.owner.killed is None
    assert q1.reserved == 800  # failed reservation fully rolled back


def test_group_revoke_tier_spills_own_group_only():
    from trino_tpu.runtime.spill import REVOCABLES, RevocableOperator

    pool, a, b = _pool_with_groups()
    qa = _query_under(a, pool, "query:qa")
    qb = _query_under(b, pool, "query:qb")
    qa_op = qa.child("join_build")
    qb_op = qb.child("join_build")
    qa_op.add_bytes(700)
    qb_op.add_bytes(900)
    freed = {"a": 0, "b": 0}

    def spill_a():
        freed["a"] += 1
        qa_op.set_bytes(0)
        return 700

    def spill_b():
        freed["b"] += 1
        qb_op.set_bytes(0)
        return 900

    ha = REVOCABLES.register(RevocableOperator("join", qa_op, spill_a))
    hb = REVOCABLES.register(RevocableOperator("join", qb_op, spill_b))
    try:
        # breach group a's limit: b's (larger) revocable must NOT be the
        # victim — only a's own operator spills
        qa.add_bytes(600)
        assert freed == {"a": 1, "b": 0}
        assert qa.reserved == 600
        assert qb_op.reserved == 900
    finally:
        ha.finish()
        hb.finish()


def test_sibling_group_pools_never_overadmit_root():
    """Satellite: N threads reserving against sibling group sub-pools can
    never push the shared root past its limit, even transiently at the
    accounting level (the check-and-reserve is atomic up the tree)."""
    from trino_tpu.runtime.memory import (
        ExceededMemoryLimitException,
        MemoryPool,
    )
    from trino_tpu.runtime.resource_groups import ResourceGroup

    pool = MemoryPool(limit_bytes=10_000)
    pool.root.on_exceeded = None
    groups = [
        ResourceGroup(
            ResourceGroupConfig(f"g{i}", memory_limit_bytes=8_000)
        ).memory_context(pool.root)
        for i in range(4)
    ]
    for g in groups:
        g.on_exceeded = None  # pure accounting: no escalation
    violations = []

    def hammer(g):
        q = _query_under(g, pool, "query:h")
        for _ in range(200):
            try:
                q.add_bytes(173)
            except ExceededMemoryLimitException:
                q.set_bytes(0)
            with pool.root._lock:
                if pool.root.reserved > pool.root.limit_bytes:
                    violations.append(pool.root.reserved)
        q.set_bytes(0)

    threads = [
        threading.Thread(target=hammer, args=(g,), daemon=True)
        for g in groups
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not violations
    assert pool.root.reserved == 0


def test_query_root_resolves_through_group_layer():
    pool, a, _b = _pool_with_groups()
    q = _query_under(a, pool, "query:q")
    op = q.child("aggregation")
    assert op.query_root() is q  # NOT the group node
    q.add_bytes(10)
    q.force_release()
    # deregistered from BOTH the group and the pool root
    assert q not in a.query_children
    assert q not in pool.root.query_children
    assert a.reserved == 0 and pool.root.reserved == 0


# -- coordinator integration ---------------------------------------------------


def test_coordinator_serves_concurrent_statements():
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(port=0)
    srv.start()
    try:
        assert srv.dispatcher.lanes >= 2  # LocalQueryRunner is cloneable
        qs = [
            srv.submit(f"select {i} + {i}") for i in range(4)
        ]
        for i, q in enumerate(qs):
            assert q.done.wait(timeout=30)
            assert q.state == "FINISHED", q.error
            assert q.result.rows == [(2 * i,)]
        # distinct engine query ids even across lanes (shared counter)
        hist = srv.runner.query_history.entries
        qids = [e["query_id"] for e in hist]
        assert len(qids) == len(set(qids))
    finally:
        srv.shutdown()


def test_coordinator_http_shed_429_with_retry_after():
    import urllib.request
    from urllib.error import HTTPError

    from trino_tpu.client import Client, QueryShed
    from trino_tpu.server.coordinator import CoordinatorServer

    rg = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=1, max_queued=0)
    )
    srv = CoordinatorServer(port=0, resource_groups=rg)
    srv.start()
    try:
        rg.default.acquire()  # hold the only slot
        # raw HTTP: 429 + Retry-After, body never read
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/statement",
            data=b"select 1", method="POST",
        )
        with pytest.raises(HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        # client surface: a typed retryable error
        with pytest.raises(QueryShed) as ci:
            Client(f"http://127.0.0.1:{srv.port}").execute("select 1")
        assert ci.value.retryable and ci.value.retry_after_s >= 1
        rg.default.release()
        # recovered: the same client round-trips
        names, rows = Client(
            f"http://127.0.0.1:{srv.port}"
        ).execute("select 1 as x")
        assert rows == [(1,)]
    finally:
        srv.shutdown()


def test_client_retries_race_window_shed():
    """The shed race window: shed_probe passes, the queue fills before the
    statement thread's enqueue, and the query fails through the POLL loop
    with a retryable QUERY_QUEUE_FULL object.  Client.execute(...,
    shed_retries=N) must retry that surface too, not just the 429."""
    from trino_tpu.client import Client, QueryShed
    from trino_tpu.server import protocol

    polled_error = protocol.query_results(
        "q_1",
        state="FAILED",
        error={
            "message": "shed in the race window",
            "errorName": "QUERY_QUEUE_FULL",
            "retryable": True,
            "retryAfterSeconds": 0.0,
        },
    )
    ok = protocol.query_results(
        "q_2", columns=[{"name": "x", "type": "bigint"}],
        data=protocol.encode_rows([(1,)]), state="FINISHED",
    )
    responses = [polled_error, ok]
    c = Client("http://unused")
    c._request = lambda method, path, body=None: responses.pop(0)
    names, rows = c.execute("select 1", shed_retries=1)
    assert rows == [(1,)]
    # without retries the typed shed error surfaces
    responses = [dict(polled_error)]
    with pytest.raises(QueryShed):
        c.execute("select 1")


def test_coordinator_queued_time_limit_classified():
    from trino_tpu.server.coordinator import CoordinatorServer

    rg = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=1, max_queued=5)
    )
    srv = CoordinatorServer(port=0, resource_groups=rg)
    srv.runner.properties.set("query_max_queued_time", 0.05)
    srv.start()
    try:
        rg.default.acquire()
        q = srv.submit("select 1")
        assert q.done.wait(timeout=10)
        assert q.state == "FAILED"
        assert q.error["errorCode"] == "EXCEEDED_QUEUED_TIME_LIMIT"
        rg.default.release()
    finally:
        srv.shutdown()


def test_coordinator_cancel_while_queued_never_admits():
    from trino_tpu.server.coordinator import CoordinatorServer

    rg = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=1, max_queued=5)
    )
    srv = CoordinatorServer(port=0, resource_groups=rg)
    srv.start()
    try:
        rg.default.acquire()
        before = {
            s["name"]: s["total_admitted"] for s in srv.dispatcher.stats()
        }
        q = srv.submit("select 1")
        time.sleep(0.05)  # let the statement thread enqueue
        q.cancel()
        assert q.done.wait(timeout=10)
        assert q.state == "CANCELED"
        assert q.error["errorCode"] == "USER_CANCELED"
        after = {
            s["name"]: s["total_admitted"] for s in srv.dispatcher.stats()
        }
        assert after == before  # never acquired an admission slot
        rg.default.release()
    finally:
        srv.shutdown()


def test_system_resource_groups_table():
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(port=0)
    srv.start()
    try:
        q = srv.submit(
            "select name, max_concurrency, weight from "
            "system.runtime.resource_groups order by name"
        )
        assert q.done.wait(timeout=30) and q.state == "FINISHED", q.error
        names = [r[0] for r in q.result.rows]
        assert "global" in names and "system.prewarm" in names
    finally:
        srv.shutdown()


def test_queued_span_recorded_in_trace():
    from trino_tpu.runtime import lifecycle
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner()
    token = lifecycle.set_admission_info(("global", 0.01))
    try:
        r.execute("select 1")
    finally:
        lifecycle.reset_admission_info(token)
    names = [e["name"] for e in r.last_trace["traceEvents"]]
    assert "queued" in names and "query" in names


# -- spill release on abort (satellite) ----------------------------------------


def test_mid_wave_kill_leaves_spill_dir_empty(tmp_path):
    """A query killed mid-wave releases its SpillManager partitions
    through the filesystem SPI at statement end — not at GC, not at the
    hours-scale orphan sweep."""
    from trino_tpu.config import install_config, load_cluster_config, reset_config
    from trino_tpu.runtime.lifecycle import QueryDeadlineExceeded
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.telemetry.metrics import spill_bytes_counter

    spill_dir = tmp_path / "spill"
    spill_dir.mkdir()
    install_config(
        load_cluster_config({"memory.spill-dir": str(spill_dir)}, env={})
    )
    try:
        r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=2)
        r.properties.set("query_max_memory", 200_000)
        r.properties.set("memory_wave_partitions", 2)
        r.properties.set("query_max_run_time", 5.0)
        spill0 = spill_bytes_counter().value()

        def clock():
            # deadline blows exactly when the first partition hits disk:
            # deterministically "mid-wave", however fast the machine
            return 1000.0 if spill_bytes_counter().value() > spill0 else 0.0

        r.query_tracker.clock = clock
        with pytest.raises(QueryDeadlineExceeded):
            r.execute(
                "select o_orderpriority, count(*) from orders join "
                "lineitem on o_orderkey = l_orderkey group by "
                "o_orderpriority"
            )
        assert spill_bytes_counter().value() > spill0  # it DID spill
        leftovers = list(spill_dir.rglob("*.npz"))
        assert leftovers == [], f"leaked spill files: {leftovers}"
    finally:
        reset_config()


# -- fast serve-chaos (the CI step's core) -------------------------------------


def test_serve_chaos_fast():
    """K concurrent clients against one coordinator with small queues:
    every statement finishes with correct rows OR fails classified
    (shed | canceled | queued-time) — zero hangs, inside a short wall."""
    from trino_tpu.server.coordinator import CoordinatorServer

    rg = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=2, max_queued=4)
    )
    srv = CoordinatorServer(port=0, resource_groups=rg)
    srv.start()
    oracle = {
        "select count(*) from tpch.tiny.region": (5,),
        "select count(*) from tpch.tiny.nation": (25,),
        "select 40 + 2": (42,),
    }
    allowed = {
        "QUERY_QUEUE_FULL", "USER_CANCELED", "EXCEEDED_QUEUED_TIME_LIMIT",
        "SERVER_SHUTTING_DOWN",
    }
    outcomes = []
    lock = threading.Lock()

    def client(i):
        sqls = list(oracle)
        for j in range(3):
            sql = sqls[(i + j) % len(sqls)]
            q = srv.submit(sql)
            if (i + j) % 7 == 3:
                q.cancel()  # cancel storms ride along
            assert q.done.wait(timeout=60), "hang"
            with lock:
                if q.state == "FINISHED":
                    assert q.result.rows == [oracle[sql]]
                    outcomes.append("ok")
                else:
                    code = (q.error or {}).get("errorCode") or (
                        q.error or {}
                    ).get("errorName")
                    assert code in allowed, q.error
                    outcomes.append(code)

    try:
        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "serve chaos hung"
        assert outcomes.count("ok") >= 1  # progress under churn
    finally:
        srv.shutdown()


def test_concurrent_lanes_isolate_decision_ledgers():
    """Dispatcher lanes serve statements concurrently: every archived
    profile carries ITS OWN statement's finalized decision ledger (the
    lifecycle-contextvar resolution — never a shared runner attribute a
    neighboring lane could overwrite)."""
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.telemetry.profile_store import (
        ProfileStore,
        attach_profile_store,
    )

    r = LocalQueryRunner()
    store = ProfileStore()
    attach_profile_store(r, store)
    srv = CoordinatorServer(runner=r, port=0)
    srv.start()
    try:
        assert srv.dispatcher.lanes >= 2
        qs = [srv.submit(f"select {i} + {i}") for i in range(6)]
        for i, q in enumerate(qs):
            assert q.done.wait(timeout=30)
            assert q.state == "FINISHED", q.error
        arts = [store.get(ref["key"]) for ref in store.refs()]
        assert len(arts) == 6
        for a in arts:
            led = a["decisions"]
            assert led is not None and led["finalized"] is True
            assert led["query_id"] == a["query_id"]
            assert led["unattributed_bytes_by"] == {}
        # six statements, six distinct ledgers — ids never collide even
        # when lanes interleave
        qids = [a["decisions"]["query_id"] for a in arts]
        assert len(qids) == len(set(qids))
    finally:
        srv.shutdown()


# -- the mesh served to concurrent clients -------------------------------------


def _serve(dispatcher, mix, oracle, clients=4, rounds=3):
    """K client threads through the dispatcher, each statement checked
    against the serial oracle; returns (answered, shed, errors)."""
    answered, shed, errors = [0], [0], []
    lock = threading.Lock()

    def client(i):
        for j in range(rounds):
            sql = mix[(i + j) % len(mix)]
            try:
                ticket = dispatcher.enqueue()
                ticket.wait()
                res = dispatcher.run_admitted(
                    ticket, lambda r: r.execute(sql)
                )
            except QueryShedError:
                with lock:
                    shed[0] += 1
                continue
            except Exception as e:  # a classified failure is still wrong
                with lock:
                    errors.append(f"{type(e).__name__}: {e}"[:200])
                continue
            with lock:
                if sorted(map(str, res.rows)) == oracle[sql]:
                    answered[0] += 1
                else:
                    errors.append(f"rows differ: {sql[:60]}")

    threads = [
        threading.Thread(
            target=client, args=(i,), daemon=True, name=f"serve-client-{i}"
        )
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "a served client hung"
    return answered[0], shed[0], errors


@pytest.fixture(scope="module")
def served_mesh():
    """The 8-worker mesh behind a one-lane dispatcher, a TPC-H mix (Q1, Q6,
    Q3 at tiny) warmed by serial passes, and the serial oracle."""
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.runtime.prewarm import replay_statements

    dist = DistributedQueryRunner(n_workers=8, schema="tiny")
    mix = [QUERIES[q] for q in (1, 6, 3)]
    oracle = {
        sql: sorted(map(str, dist.execute(sql).rows)) for sql in mix
    }
    # a statement that learns a join capacity compiles its fused expand
    # once more on its next run: settle before any watermark
    replay_statements(dist, mix)
    mgr = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=1, max_queued=32)
    )
    return dist, QueryDispatcher(dist, mgr, lanes=1), mix, oracle


def test_warm_mesh_serving_compiles_nothing(served_mesh):
    """Concurrent clients of the warmed mesh share its one trace-cache key
    set: every statement answers the serial oracle (or is shed) and the
    compile observatory records no event above the warm-up watermark."""
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    _, dispatcher, mix, oracle = served_mesh
    watermark = OBSERVATORY.mark()
    answered, shed, errors = _serve(dispatcher, mix, oracle)
    assert not errors, errors
    assert answered + shed == 12 and answered >= 1
    assert OBSERVATORY.mark() - watermark == 0


def test_served_mesh_recovers_a_killed_stage_from_the_spool(served_mesh):
    """A stage killed mid-statement while the mesh serves concurrent
    clients, with fault_tolerant_execution on: every statement still
    answers the serial oracle, the kill is classified as a task retry and
    never a failure, the retry resumes from spooled stage outputs, and the
    query is never re-planned."""
    from trino_tpu.runtime.retry import FAILURE_INJECTOR, InjectedFailure
    from trino_tpu.telemetry.metrics import (
        membership_events_counter,
        mesh_events_counter,
        spooled_fragments_counter,
        task_retries_counter,
    )

    dist, dispatcher, mix, oracle = served_mesh

    def recovery():
        retries = task_retries_counter()
        return {
            "retry": retries.labels("retry").value(),
            "fail": retries.labels("fail").value(),
            "spooled": spooled_fragments_counter().value(),
            "spool_read": mesh_events_counter().labels("spool_read").value(),
            "replans": membership_events_counter().labels(
                "shrink_replan"
            ).value(),
        }

    q3_first = [mix[2], mix[0], mix[1]]  # client 0 opens with the join
    fired = [0]
    orig = FAILURE_INJECTOR.maybe_fail

    def kill_once(point):
        # the finish hook of a stage whose children already completed and
        # spooled, in client 0's first statement
        if (
            not fired[0]
            and point.startswith("stage:")
            and point.endswith(":finish")
            and not point.startswith("stage:0:")
            and threading.current_thread().name == "serve-client-0"
        ):
            fired[0] += 1
            raise InjectedFailure(f"chaos: worker killed at {point}")
        return orig(point)

    dist.properties.set("fault_tolerant_execution", True)
    try:
        for sql in mix:  # the spooled execution's own programs
            dist.execute(sql)
        before = recovery()
        FAILURE_INJECTOR.maybe_fail = kill_once
        try:
            answered, shed, errors = _serve(dispatcher, q3_first, oracle)
        finally:
            FAILURE_INJECTOR.maybe_fail = orig
        after = recovery()
    finally:
        dist.properties.set("fault_tolerant_execution", False)
    assert fired[0] == 1
    assert not errors, errors
    assert answered + shed == 12 and answered >= 1
    assert after["retry"] - before["retry"] >= 1
    assert after["fail"] == before["fail"]
    assert after["spooled"] > before["spooled"]
    assert after["spool_read"] > before["spool_read"]
    assert after["replans"] == before["replans"]
