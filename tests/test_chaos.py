"""Chaos suite: injected task failures, latency spikes, flaky connections,
and dead workers across the multi-host + FTE paths.

The contract under test (the tentpole's acceptance bar): EVERY query either
returns rows equal to the local runner or fails/cancels with a CLASSIFIED
error before its deadline — never hangs, never returns wrong rows.

Marked `slow` (excluded from tier-1): these tests run real HTTP workers and
real injected latency.  The deterministic-clock halves of the machinery
(state machine, breaker transitions, backoff schedule, memory-kill victim
choice) run in tier-1 via tests/test_lifecycle.py.
"""

import threading
import time
import urllib.request

import pytest

from tests.test_e2e import assert_rows_match
from trino_tpu.parallel.remote import MultiHostQueryRunner
from trino_tpu.runtime.lifecycle import (
    QueryAbortedException,
    QueryCanceledException,
    QueryDeadlineExceeded,
)
from trino_tpu.runtime.retry import BREAKERS, FAILURE_INJECTOR, InjectedFailure
from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.server.worker import WorkerServer

pytestmark = [pytest.mark.slow, pytest.mark.heavy]

#: generous wall deadline: chaos queries must finish (or abort) well inside
#: it — a hang is the one outcome this suite exists to forbid
DEADLINE_S = 60.0


@pytest.fixture(scope="module", autouse=True)
def lockgraph():
    """Instrumented-lock mode (verify.lockgraph): every lock created
    during the chaos module — servers, runners, registries, background
    waiters — reports its acquisition order, and the module fails if the
    recorded graph has a cycle.  An order inversion is a deadlock waiting
    for the right interleaving, so this gate fires even on runs where the
    chaos happened not to hang."""
    from trino_tpu.verify import lockgraph as lg

    with lg.capture() as graph:
        yield graph
    graph.assert_acyclic()


@pytest.fixture(scope="module", autouse=True)
def no_spool_leaks(tmp_path_factory):
    """Chaos kills must never leak spool directories: every query-owned
    spool (fault-tolerant recovery included) is removed when its query
    ends, so the module's own temporary root (not the shared /tmp, where
    another xdist worker's live spool would read as a leak) holds zero
    orphan .npz spools after the module."""
    import glob
    import os
    import tempfile

    root = str(tmp_path_factory.mktemp("spool_root"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tempfile, "tempdir", root)
        yield
    leaked = glob.glob(os.path.join(root, "trino_tpu_spool_*"))
    assert not leaked, f"spool directories leaked: {sorted(leaked)}"


@pytest.fixture(scope="module")
def workers(lockgraph):
    ws = [WorkerServer(port=0).start() for _ in range(2)]
    yield ws
    for w in ws:
        w.shutdown()


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner(catalog="tpch", schema="tiny")


@pytest.fixture()
def mh(workers):
    r = MultiHostQueryRunner(
        [w.url for w in workers], catalog="tpch", schema="tiny"
    )
    r.properties.set("query_max_run_time", DEADLINE_S)
    return r


@pytest.fixture(autouse=True)
def clean_chaos():
    FAILURE_INJECTOR.clear()
    BREAKERS.reset()
    yield
    FAILURE_INJECTOR.clear()
    BREAKERS.reset()


QUERIES = [
    # source fragment + gather
    "select count(*), sum(l_quantity) from lineitem",
    # hash-partitioned aggregation over an exchange
    "select l_returnflag, count(*), sum(l_extendedprice) "
    "from lineitem group by l_returnflag",
    # partitioned join (both sides repartition on the key hash)
    "select count(*) from lineitem, orders where l_orderkey = o_orderkey",
]

#: (injection point pattern, mode, times) — the sweep axis.  Points cover
#: task submission and the HTTP pull data plane (result pulls AND worker
#: input pulls share the `fetch:` hook).
INJECTIONS = [
    ("submit:", "flap", 1),
    ("submit:", "flap", 2),
    ("submit:", "error", 1),
    ("fetch:", "flap", 1),
    ("fetch:", "flap", 3),
    ("fetch:", "error", 1),
    ("fetch:", "latency", 1),
]


def _run_bounded(mh, local, sql):
    """The chaos contract: rows == local, or a classified error, and either
    way the query resolves well before its deadline."""
    t0 = time.monotonic()
    try:
        got = mh.execute(sql).rows
    except (QueryAbortedException, RuntimeError, OSError) as e:
        # classified abort, or a task/worker failure the engine surfaced
        # loudly — acceptable; silence and wrong rows are not
        assert str(e), "failure must carry a message"
        return time.monotonic() - t0, None
    wall = time.monotonic() - t0
    assert_rows_match(got, local.execute(sql).rows, ordered=False)
    return wall, got


@pytest.mark.parametrize("point,mode,times", INJECTIONS)
def test_chaos_sweep_multihost(mh, local, point, mode, times):
    """Sweep failure/latency/flaky-connection injections across the
    multi-host path: every query matches local or fails classified — and
    resolves inside the deadline either way."""
    for sql in QUERIES:
        FAILURE_INJECTOR.clear()
        BREAKERS.reset()
        if mode == "flap":
            FAILURE_INJECTOR.inject_connection_flap(point, times=times)
        elif mode == "latency":
            FAILURE_INJECTOR.inject_latency(point, delay_s=0.5, times=times)
        else:
            FAILURE_INJECTOR.inject(point, times=times, error=InjectedFailure)
        wall, got = _run_bounded(mh, local, sql)
        assert wall < DEADLINE_S, f"{point}/{mode} blew the deadline on {sql}"
        if mode in ("flap", "latency"):
            # transient chaos must be ABSORBED (retry w/ backoff, task
            # replacement), not surfaced: rows equal local
            assert got is not None, f"{point}/{mode}({times}) failed {sql}"


def test_chaos_latency_spike_absorbed(mh, local):
    """A one-off latency spike on the data plane stalls but does not break
    or mis-answer the query."""
    FAILURE_INJECTOR.inject_latency("fetch:", delay_s=1.0, times=1)
    sql = QUERIES[1]
    wall, got = _run_bounded(mh, local, sql)
    assert got is not None and wall < DEADLINE_S


def test_chaos_deadline_cuts_off_stalled_query(mh, local):
    """With the data plane stalled past query_max_run_time, the query fails
    CLASSIFIED (EXCEEDED_TIME_LIMIT) promptly after the stall — it neither
    hangs nor burns the full injected latency budget."""
    mh.properties.set("query_max_run_time", 0.5)
    FAILURE_INJECTOR.inject_latency("fetch:", delay_s=1.0, times=50)
    t0 = time.monotonic()
    with pytest.raises(QueryDeadlineExceeded) as ei:
        mh.execute(QUERIES[0])
    wall = time.monotonic() - t0
    mh.properties.set("query_max_run_time", DEADLINE_S)
    assert ei.value.error_code == "EXCEEDED_TIME_LIMIT"
    assert wall < 15.0, "deadline abort must not drain the whole stall budget"
    # the engine recovered: a clean follow-up query runs normally
    FAILURE_INJECTOR.clear()
    wall, got = _run_bounded(mh, local, QUERIES[0])
    assert got is not None


def test_chaos_dead_worker_breaker_opens_and_queries_survive(local):
    """A worker that dies keeps failing its probes/submits: the per-worker
    circuit breaker trips OPEN (visible in system.runtime.metrics) and
    queries keep answering correctly from the surviving workers."""
    ws = [WorkerServer(port=0).start() for _ in range(3)]
    victim = ws[2]
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("query_max_run_time", DEADLINE_S)
        victim.shutdown()
        for sql in QUERIES:
            # fresh probe evidence each query (the TTL cache would hide
            # the repeated failures the breaker needs to see)
            mh._worker_health.clear()
            wall, got = _run_bounded(mh, local, sql)
            assert got is not None and wall < DEADLINE_S
        states = BREAKERS.states()
        assert states.get(victim.url) == "open", states
        # the OPEN breaker is queryable as a labeled gauge (the system
        # catalog is coordinator-resident: query it through the local
        # runner — the breaker registry is process-wide)
        rows = local.execute(
            "select labels, value from system.runtime.metrics "
            "where name = 'trino_tpu_breaker_state'"
        ).rows
        assert any(victim.url in labels and value == 2.0
                   for labels, value in rows), rows
    finally:
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass


def test_chaos_worker_task_cancel_is_real(workers):
    """DELETE /v1/task/{id} aborts a RUNNING task at its next cooperative
    check instead of letting it burn the slot to completion."""
    from trino_tpu.server.worker import _http_get

    # no deadline on the descriptor: the long-poll would wait RESULT_WAIT_S
    url = workers[0].url
    with urllib.request.urlopen(f"{url}/v1/info", timeout=5.0) as r:
        r.read()
    # a task id that was never submitted: DELETE must still answer 200
    req = urllib.request.Request(f"{url}/v1/task/never_there", method="DELETE")
    with urllib.request.urlopen(req, timeout=5.0) as r:
        assert r.status == 200


def test_chaos_coordinator_delete_cancels_running_query(workers, local):
    """DELETE /v1/query/{id} is a REAL cancel: the running statement aborts
    at its next cooperative check, shows CANCELED on the protocol, and the
    engine survives to run the next query."""
    from trino_tpu.server.coordinator import CoordinatorServer

    mh = MultiHostQueryRunner(
        [w.url for w in workers], catalog="tpch", schema="tiny"
    )
    server = CoordinatorServer(runner=mh, port=0)
    server.start()
    try:
        # stall the data plane so the query is mid-flight when DELETE lands
        FAILURE_INJECTOR.inject_latency("fetch:", delay_s=1.5, times=10)
        q = server.submit(QUERIES[0])
        time.sleep(0.3)  # let the executor enter the stalled fetch
        req = urllib.request.Request(
            f"http://{server.host}:{server.port}/v1/query/{q.id}",
            method="DELETE",
        )
        with urllib.request.urlopen(req, timeout=5.0) as r:
            assert r.status == 204
        assert q.done.wait(timeout=30.0), "canceled query must terminate"
        assert q.state == "CANCELED"
        assert q.error["errorCode"] == "USER_CANCELED"
        assert q.error["errorType"] == "USER_ERROR"
        # the engine is healthy afterwards
        FAILURE_INJECTOR.clear()
        q2 = server.submit("select count(*) from region")
        assert q2.done.wait(timeout=30.0) and q2.state == "FINISHED"
        # the query history records the CANCELED state + kill reason (the
        # system catalog is coordinator-resident — read it directly rather
        # than distributing a system scan to the workers)
        entries = [
            (e["state"], e["error_code"]) for e in mh.query_history.entries
        ]
        assert ("CANCELED", "USER_CANCELED") in entries
    finally:
        server.shutdown()


def test_chaos_coordinator_delete_while_queued(workers):
    """A DELETE racing statement submission cancels the query BEFORE it
    occupies the engine (cancel-while-queued)."""
    from trino_tpu.server.coordinator import CoordinatorServer

    mh = MultiHostQueryRunner(
        [w.url for w in workers], catalog="tpch", schema="tiny"
    )
    server = CoordinatorServer(runner=mh, port=0)
    server.start()
    try:
        FAILURE_INJECTOR.inject_latency("fetch:", delay_s=1.0, times=5)
        q1 = server.submit(QUERIES[0])  # occupies the engine lock
        q2 = server.submit(QUERIES[1])  # queued behind it
        q2.cancel()
        assert q2.done.wait(timeout=30.0) or q2.state == "QUEUED"
        assert q1.done.wait(timeout=30.0)
        assert q2.done.wait(timeout=30.0)
        assert q2.state == "CANCELED"
    finally:
        server.shutdown()


def test_chaos_worker_killed_mid_query_replans_at_w_minus_1(local):
    """The tentpole's acceptance bar: a worker dying MID-QUERY (tasks
    already placed on it) triggers mesh-shrink re-planning — the query
    re-fragments against the survivors (W-1) and still answers rows ==
    local inside the deadline, instead of retrying forever against the
    corpse."""
    ws = [WorkerServer(port=0).start() for _ in range(3)]
    victim = ws[2]
    killed = {"done": False}
    orig = FAILURE_INJECTOR.maybe_fail

    def kill_hook(point):
        # first data-plane pull: the victim dies under the running query
        if point.startswith("fetch:") and not killed["done"]:
            killed["done"] = True
            threading.Thread(target=victim.shutdown, daemon=True).start()
            time.sleep(0.2)  # let the socket actually close
        return orig(point)

    FAILURE_INJECTOR.maybe_fail = kill_hook
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("query_max_run_time", DEADLINE_S)
        sql = QUERIES[1]
        t0 = time.monotonic()
        got = mh.execute(sql).rows
        wall = time.monotonic() - t0
        assert wall < DEADLINE_S
        assert_rows_match(got, local.execute(sql).rows, ordered=False)
        assert killed["done"], "the kill hook never fired"
        assert mh.membership.state(victim.url) == "DEAD"
        assert len(mh.last_plan_workers) == 2, mh.last_plan_workers
        # the shrunk mesh is stable: the next query plans at W-1 directly
        FAILURE_INJECTOR.maybe_fail = orig
        got = mh.execute(sql).rows
        assert_rows_match(got, local.execute(sql).rows, ordered=False)
        assert mh.last_replans == 0 and len(mh.last_plan_workers) == 2
    finally:
        FAILURE_INJECTOR.maybe_fail = orig
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass


def test_chaos_drain_mid_query_finishes_or_replans(local):
    """Graceful drain landing mid-query: the draining worker finishes its
    running tasks but refuses new submissions (503/REFUSED, no breaker
    vote), so the query either completes on the old mesh or re-plans
    without the drainee — rows == local either way, inside the deadline."""
    ws = [WorkerServer(port=0).start() for _ in range(3)]
    drainee = ws[1]
    drained = {"done": False}
    orig = FAILURE_INJECTOR.maybe_fail

    def drain_hook(point):
        # drain lands while the coordinator is mid-submission fan-out
        if point.startswith(f"submit:{drainee.url}") and not drained["done"]:
            drained["done"] = True
            drainee.begin_drain(exit_on_idle=False)
        return orig(point)

    FAILURE_INJECTOR.maybe_fail = drain_hook
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("query_max_run_time", DEADLINE_S)
        for sql in QUERIES:
            t0 = time.monotonic()
            got = mh.execute(sql).rows
            wall = time.monotonic() - t0
            assert wall < DEADLINE_S
            assert_rows_match(got, local.execute(sql).rows, ordered=False)
        assert drained["done"], "the drain hook never fired"
        # the drain was by choice, not failure: no breaker opened for it
        assert BREAKERS.states().get(drainee.url, "closed") != "open"
        assert drainee.url not in mh.last_plan_workers
    finally:
        FAILURE_INJECTOR.maybe_fail = orig
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass


def test_chaos_grow_mid_query_joins_next_mesh_only(local):
    """A worker registering while a query runs never mutates the running
    mesh: the in-flight query completes on the mesh it was planned for,
    and the NEW worker serves from the next query on."""
    ws = [WorkerServer(port=0).start() for _ in range(2)]
    w3 = WorkerServer(port=0).start()
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("query_max_run_time", DEADLINE_S)
        # stall the data plane so the grow lands mid-flight
        FAILURE_INJECTOR.inject_latency("fetch:", delay_s=0.5, times=2)
        grown = threading.Timer(0.2, mh.add_worker, args=(w3.url,))
        grown.start()
        sql = QUERIES[0]
        got = mh.execute(sql).rows
        grown.join()
        assert_rows_match(got, local.execute(sql).rows, ordered=False)
        assert w3.url not in mh.last_plan_workers, (
            "a grow must never join a running query's mesh"
        )
        # ... but the next query's mesh includes it
        FAILURE_INJECTOR.clear()
        got = mh.execute(sql).rows
        assert_rows_match(got, local.execute(sql).rows, ordered=False)
        assert w3.url in mh.last_plan_workers
        assert len(mh.last_plan_workers) == 3
    finally:
        for w in ws + [w3]:
            try:
                w.shutdown()
            except Exception:
                pass


def test_chaos_membership_sweep_kill_each_worker(local):
    """Kill sweep: whichever worker dies mid-query, the answer is rows ==
    local or a classified failure — never a hang, never wrong rows."""
    for victim_idx in range(3):
        ws = [WorkerServer(port=0).start() for _ in range(3)]
        orig = FAILURE_INJECTOR.maybe_fail
        fired = {"done": False}

        def kill_hook(point, _v=ws[victim_idx]):
            if point.startswith("fetch:") and not fired["done"]:
                fired["done"] = True
                threading.Thread(target=_v.shutdown, daemon=True).start()
                time.sleep(0.2)
            return orig(point)

        FAILURE_INJECTOR.maybe_fail = kill_hook
        try:
            BREAKERS.reset()
            mh = MultiHostQueryRunner(
                [w.url for w in ws], catalog="tpch", schema="tiny"
            )
            mh.properties.set("query_max_run_time", DEADLINE_S)
            wall, got = _run_bounded(mh, local, QUERIES[2])
            assert wall < DEADLINE_S, f"victim {victim_idx} blew the deadline"
            assert got is not None, (
                f"victim {victim_idx}: a single death must be absorbed by "
                "mesh-shrink re-planning"
            )
        finally:
            FAILURE_INJECTOR.maybe_fail = orig
            for w in ws:
                try:
                    w.shutdown()
                except Exception:
                    pass


def test_chaos_fte_stage_failures_and_latency(local):
    """The in-mesh FTE path (retry_policy=TASK, spooled stages) under the
    new injection modes: stage failures + latency spikes retry/absorb and
    the answer still equals local."""
    from trino_tpu.parallel import DistributedQueryRunner

    r = DistributedQueryRunner(n_workers=8)
    r.properties.set("retry_policy", "TASK")
    r.properties.set("query_max_run_time", DEADLINE_S)
    sql = (
        "select l_returnflag, count(*) c, sum(l_quantity) q "
        "from lineitem group by l_returnflag order by l_returnflag"
    )
    FAILURE_INJECTOR.inject("stage:", times=2, error=InjectedFailure)
    FAILURE_INJECTOR.inject_latency("stage:", delay_s=0.3, times=2)
    t0 = time.monotonic()
    got = r.execute(sql).rows
    wall = time.monotonic() - t0
    assert got == local.execute(sql).rows
    assert wall < DEADLINE_S


def test_chaos_cancel_inmesh_mid_query():
    """Cooperative cancellation on the in-mesh SPMD path: a cancel armed
    between fragment launches aborts the query with CANCELED classification
    instead of finishing it."""
    from trino_tpu.parallel import DistributedQueryRunner

    r = DistributedQueryRunner(n_workers=8)
    cancel_after = {"n": 2}
    orig = FAILURE_INJECTOR.maybe_fail

    def cancel_hook(point):
        if point.startswith("stage:"):
            cancel_after["n"] -= 1
            if cancel_after["n"] == 0:
                ctx = __import__(
                    "trino_tpu.runtime.lifecycle", fromlist=["current_query"]
                ).current_query()
                if ctx is not None:
                    ctx.cancel("chaos cancel")
        return orig(point)

    FAILURE_INJECTOR.maybe_fail = cancel_hook
    try:
        with pytest.raises(QueryCanceledException):
            r.execute(
                "select count(*) from lineitem, orders "
                "where l_orderkey = o_orderkey"
            )
    finally:
        FAILURE_INJECTOR.maybe_fail = orig
    # the engine survives: the next statement runs clean
    assert r.execute("select count(*) from region").rows == [(5,)]


def test_chaos_pool_shrink_mid_query_revokes_join_into_waves(local):
    """Memory-pressure chaos (a): the shared pool limit SHRINKS while a
    join is mid-probe — the escalation's revoke tier asks the running
    build to spill, the probe remainder finishes in partition waves, and
    rows still equal the unconstrained local oracle (exceed -> revoke ->
    wave, killer never fires)."""
    from trino_tpu.ops.join import HashJoinOperator
    from trino_tpu.runtime import spill as S
    from trino_tpu.runtime.lifecycle import set_memory_pool_limit
    from trino_tpu.telemetry.metrics import (
        memory_kills_counter,
        memory_revocations_counter,
    )

    sql = (
        "select o_orderpriority, count(*), sum(l_quantity) from orders "
        "join lineitem on o_orderkey = l_orderkey group by o_orderpriority"
    )
    base = sorted(local.execute(sql).rows)
    rev0 = memory_revocations_counter().value()
    kills0 = memory_kills_counter().value()
    shrunk = threading.Event()
    shrinkers: list = []
    orig = HashJoinOperator._join_batch

    def shrinking(self, pb):
        out = orig(self, pb)
        if not shrunk.is_set():
            shrunk.set()
            # an operator watchdog shrinking the pool under live queries
            # to well below the join build's reservation (the query's
            # residual state still fits, so it can finish degraded)
            t = threading.Thread(
                target=set_memory_pool_limit, args=(400_000,),
                name="chaos-shrink", daemon=True,
            )
            shrinkers.append(t)
            t.start()
        return out

    HashJoinOperator._join_batch = shrinking
    try:
        r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=4)
        t0 = time.monotonic()
        rows = sorted(r.execute(sql).rows)
        wall = time.monotonic() - t0
    finally:
        HashJoinOperator._join_batch = orig
        for t in shrinkers:
            t.join()  # a late shrink must not land AFTER the reset below
        set_memory_pool_limit(0)
    assert shrunk.is_set()
    assert wall < DEADLINE_S
    assert rows == base
    assert memory_revocations_counter().value() > rev0
    assert memory_kills_counter().value() == kills0  # killer never fired
    assert not S.REVOCABLES.live()


def test_chaos_pool_pressure_and_worker_kill_compose(local):
    """Memory-pressure chaos (b): a constrained budget AND a mid-query
    worker kill compose — the W-1 re-plan re-executes under the SAME
    budget (waves and all) and still answers rows == local, or fails
    classified inside its deadline.  Degradation tiers must not interfere
    with elastic membership."""
    ws = [WorkerServer(port=0).start() for _ in range(3)]
    victim = ws[2]
    killed = {"done": False}
    orig = FAILURE_INJECTOR.maybe_fail

    def kill_hook(point):
        if point.startswith("fetch:") and not killed["done"]:
            killed["done"] = True
            threading.Thread(target=victim.shutdown, daemon=True).start()
            time.sleep(0.2)
        return orig(point)

    FAILURE_INJECTOR.maybe_fail = kill_hook
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("query_max_run_time", DEADLINE_S)
        mh.properties.set("query_max_memory", 250_000)
        sql = QUERIES[2]
        t0 = time.monotonic()
        try:
            got = mh.execute(sql).rows
        except (QueryAbortedException, RuntimeError, OSError) as e:
            assert str(e), "failure must carry a message"
            got = None
        wall = time.monotonic() - t0
        assert wall < DEADLINE_S
        assert killed["done"], "the kill hook never fired"
        if got is not None:
            assert_rows_match(got, local.execute(sql).rows, ordered=False)
            assert len(mh.last_plan_workers) == 2
        # the shrunk mesh keeps answering under the same budget
        FAILURE_INJECTOR.maybe_fail = orig
        got = mh.execute(sql).rows
        assert_rows_match(got, local.execute(sql).rows, ordered=False)
    finally:
        FAILURE_INJECTOR.maybe_fail = orig
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass


def test_chaos_concurrent_serving_kill_and_pool_shrink(local):
    """PR 13 acceptance composition: K=8 concurrent clients admitted
    through weighted-fair resource groups x a worker kill at W-1 x a
    mid-run shared-pool shrink.  Every statement either answers the local
    oracle's rows or fails CLASSIFIED (canceled | queued-time | deadline |
    memory | shed | loud worker failure) inside its deadline — zero
    hangs, and ZERO cross-group memory kills (each group's escalation
    log only ever names its own group)."""
    from trino_tpu.runtime.dispatcher import QueryDispatcher, QueryShedError
    from trino_tpu.runtime.lifecycle import set_memory_pool_limit
    from trino_tpu.runtime.resource_groups import (
        ResourceGroupConfig,
        ResourceGroupManager,
    )

    ws = [WorkerServer(port=0).start() for _ in range(3)]
    mh = MultiHostQueryRunner(
        [w.url for w in ws], catalog="tpch", schema="tiny"
    )
    mh.properties.set("query_max_run_time", DEADLINE_S)
    mh.properties.set("query_max_queued_time", DEADLINE_S)
    mgr = ResourceGroupManager(
        ResourceGroupConfig("global", hard_concurrency=2, max_queued=16)
    )
    mgr.add(
        ResourceGroupConfig(
            "a", hard_concurrency=2, max_queued=16, weight=2,
            memory_limit_bytes=64 << 20,
        )
    )
    mgr.add(
        ResourceGroupConfig(
            "b", hard_concurrency=2, max_queued=16, weight=1,
            memory_limit_bytes=64 << 20,
        )
    )
    mgr.add_user_rule("ua", "a")
    mgr.add_user_rule("ub", "b")
    dispatcher = QueryDispatcher(mh, mgr)  # multi-host: one lane
    oracles = {sql: local.execute(sql).rows for sql in QUERIES}
    outcomes = []
    olock = threading.Lock()

    def serve_client(i):
        user = "ua" if i % 2 == 0 else "ub"
        for j in range(2):
            sql = QUERIES[(i + j) % len(QUERIES)]
            t0 = time.monotonic()
            try:
                ticket = dispatcher.enqueue(user=user)
                ticket.wait()
                got = dispatcher.run_admitted(
                    ticket, lambda r: r.execute(sql)
                ).rows
            except QueryShedError:
                got = "shed"
            except (QueryAbortedException, RuntimeError, OSError) as e:
                assert str(e), "failure must carry a message"
                got = None
            wall = time.monotonic() - t0
            assert wall < DEADLINE_S, f"client {i} blew its deadline"
            with olock:
                if got not in (None, "shed"):
                    assert_rows_match(got, oracles[sql], ordered=False)
                    outcomes.append("ok")
                else:
                    outcomes.append(got or "classified")

    def chaos_monkey():
        time.sleep(0.3)
        ws[2].shutdown()  # worker kill: survivors re-plan at W-1
        time.sleep(0.2)
        set_memory_pool_limit(1 << 20)  # mid-run pool shrink
        time.sleep(0.3)
        set_memory_pool_limit(0)

    monkey = threading.Thread(target=chaos_monkey, daemon=True)
    try:
        clients = [
            threading.Thread(target=serve_client, args=(i,), daemon=True)
            for i in range(8)
        ]
        monkey.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=DEADLINE_S * 3)
            assert not t.is_alive(), "serving hung under chaos"
        monkey.join(timeout=10)
        assert outcomes.count("ok") >= 1, outcomes  # progress under chaos
        # zero cross-group memory kills: every group-escalation kill (if
        # any fired) names its OWN group — a bystander group was never
        # shot for another group's pressure
        from trino_tpu.runtime.lifecycle import memory_pool

        root = memory_pool().root
        for name in ("a", "b"):
            ctx = mgr.groups[name].memory_context(root)
            esc = ctx.on_exceeded
            assert all(g == name for g, _victim in esc.kill_log), (
                name, esc.kill_log
            )
    finally:
        set_memory_pool_limit(0)
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass
