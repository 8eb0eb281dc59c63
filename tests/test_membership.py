"""Elastic cluster membership (PR 7): heartbeat failure detection, drain,
grow, mesh-shrink re-planning, and the typed config system.

Everything here is tier-1: deterministic clocks and probers for the
detector state machine, real-but-instant HTTP workers for the drain/shrink
plan-shape tests (no sleeps, no injected latency — the mid-query
kill/drain/grow sweeps live in test_chaos.py behind `slow`).
"""

import urllib.error
import urllib.request

import pytest

from trino_tpu.config import (
    BreakerConfig,
    ClusterConfig,
    HeartbeatConfig,
    get_config,
    install_config,
    load_cluster_config,
    reset_config,
)
from trino_tpu.runtime.membership import (
    ACTIVE,
    DEAD,
    DRAINING,
    ClusterMembership,
    HeartbeatDetector,
    MeshChangedError,
    WorkerDrainingError,
    invalidate_mesh_scans,
)
from trino_tpu.runtime.retry import BREAKERS


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def _clean():
    reset_config()
    BREAKERS.reset()
    yield
    reset_config()
    BREAKERS.reset()


def _events(kind: str) -> float:
    from trino_tpu.telemetry.metrics import membership_events_counter

    return membership_events_counter().value((kind,))


# -- typed config --------------------------------------------------------------


def test_config_defaults_preserve_pr5_constants():
    """With nothing set, every knob is the PR 5 compiled-in constant —
    loading the config system must not change behaviour."""
    cfg = ClusterConfig()
    assert cfg.breaker.failure_threshold == 3
    assert cfg.breaker.cooldown_s == 5.0
    assert cfg.lifecycle.request_timeout_s == 600.0
    assert cfg.lifecycle.submit_timeout_s == 60.0
    assert cfg.remote.submit_attempts == 3
    assert cfg.remote.fetch_attempts == 3
    assert cfg.remote.probe_ttl_s == 15.0
    assert cfg.worker.result_wait_s == 600.0
    assert cfg.heartbeat.miss_threshold == 3


def test_config_resolution_order_env_props_default():
    """env TRINO_TPU_* > properties file > dataclass default."""
    props = {"breaker.failure-threshold": "5", "breaker.cooldown": "2.5"}
    env = {"TRINO_TPU_BREAKER_FAILURE_THRESHOLD": "9"}
    cfg = load_cluster_config(props, env=env)
    assert cfg.breaker.failure_threshold == 9  # env wins
    assert cfg.breaker.cooldown_s == 2.5  # properties
    assert cfg.heartbeat.miss_threshold == 3  # default


def test_config_per_worker_override_longest_token_wins():
    props = {
        "breaker.failure-threshold": "4",
        "breaker.failure-threshold@8123": "7",
        "breaker.failure-threshold@127.0.0.1:8123": "8",
    }
    cfg = load_cluster_config(props, env={})
    assert cfg.breaker.failure_threshold == 4
    assert cfg.breaker_for("http://127.0.0.1:8123").failure_threshold == 8
    assert cfg.breaker_for("http://10.0.0.2:8123").failure_threshold == 7
    assert cfg.breaker_for("http://10.0.0.2:9999").failure_threshold == 4


def test_config_bad_value_is_loud():
    with pytest.raises(ValueError, match="breaker.failure-threshold"):
        load_cluster_config({"breaker.failure-threshold": "many"}, env={})


def test_config_describe_lists_keys():
    keys = [k for k, _, _ in BreakerConfig().describe()]
    assert keys == ["breaker.failure-threshold", "breaker.cooldown"]


def test_load_etc_installs_cluster_config(tmp_path):
    """The launcher path: etc/config.properties feeds the typed config."""
    from trino_tpu.runtime.config import load_etc

    etc = tmp_path / "etc"
    etc.mkdir()
    (etc / "config.properties").write_text(
        "heartbeat.miss-threshold=6\nbreaker.cooldown=1.5\n"
    )
    loaded = load_etc(str(etc))
    assert loaded.cluster.heartbeat.miss_threshold == 6
    assert get_config().heartbeat.miss_threshold == 6
    assert get_config().breaker.cooldown_s == 1.5


def test_breakers_read_config_at_creation_time():
    """Breakers are created lazily per worker, so a config installed after
    import still applies — the PR 5 process-wide-constant gap, closed."""
    install_config(
        load_cluster_config({"breaker.failure-threshold": "1"}, env={})
    )
    b = BREAKERS.get("http://configured-worker")
    b.record_failure()
    assert b.state == "open"  # threshold 1 from the installed config
    # explicit constructor knobs (tests, embedded registries) still win
    from trino_tpu.runtime.retry import CircuitBreakerRegistry

    reg = CircuitBreakerRegistry(failure_threshold=2)
    b2 = reg.get("w")
    b2.record_failure()
    assert b2.state == "closed"


# -- membership registry -------------------------------------------------------


def test_membership_state_machine_and_events():
    clock = FakeClock()
    m = ClusterMembership(clock=clock)
    j0, d0, x0, r0 = (
        _events("join"), _events("drain"), _events("death"), _events("rejoin")
    )
    m.register("w1")
    m.register("w2")
    assert m.active_workers() == ["w1", "w2"]
    assert _events("join") == j0 + 2
    # drain: out of the next mesh, still a probe target
    assert m.drain("w1") is True
    assert m.state("w1") == DRAINING
    assert m.active_workers() == ["w2"]
    assert m.probe_targets() == ["w1", "w2"]
    assert _events("drain") == d0 + 1
    # draining twice is a no-op
    assert m.drain("w1") is False
    # death is sticky until an explicit re-register
    assert m.mark_dead("w2") is True
    assert m.mark_dead("w2") is False
    m.heartbeat("w2")  # a late heartbeat cannot resurrect a corpse
    assert m.state("w2") == DEAD
    assert m.active_workers() == []
    assert _events("death") == x0 + 1
    # rejoin: the grow path for a restarted worker
    m.register("w2")
    assert m.state("w2") == ACTIVE
    assert m.active_workers() == ["w2"]
    assert _events("rejoin") == r0 + 1


def test_mark_dead_trips_breaker_and_rejoin_resets_it():
    m = ClusterMembership(["w1"])
    m.mark_dead("w1")
    assert BREAKERS.get("w1").state == "open"
    m.register("w1")
    assert BREAKERS.get("w1").state == "closed"


def test_snapshot_matches_nodes_table_shape():
    clock = FakeClock()
    m = ClusterMembership(["w1"], clock=clock)
    clock.advance(2.0)
    ((wid, state, age, breaker),) = m.snapshot()
    assert (wid, state, breaker) == ("w1", ACTIVE, "closed")
    assert age == pytest.approx(2.0)


# -- heartbeat failure detector ------------------------------------------------


def _detector(m, prober, threshold=3):
    return HeartbeatDetector(
        m, prober=prober, config=HeartbeatConfig(miss_threshold=threshold)
    )


def test_detector_declares_dead_at_miss_threshold():
    m = ClusterMembership(["w1", "w2"], clock=FakeClock())
    down = {"w1"}
    det = _detector(m, lambda w: w not in down, threshold=3)
    assert det.tick() == []
    assert det.tick() == []
    assert det.tick() == ["w1"]  # third consecutive miss
    assert m.state("w1") == DEAD
    assert m.state("w2") == ACTIVE
    assert BREAKERS.get("w1").state == "open"
    assert BREAKERS.get("w2").state == "closed"
    # DEAD workers leave the probe set; nothing else dies
    assert m.probe_targets() == ["w2"]
    assert det.tick() == []


def test_detector_success_resets_miss_count():
    m = ClusterMembership(["w1"], clock=FakeClock())
    answers = iter([False, False, True, False, False, True])
    det = _detector(m, lambda w: next(answers), threshold=3)
    for _ in range(6):
        det.tick()
    # two misses, a success, two misses, a success: never reaches 3
    assert m.state("w1") == ACTIVE
    assert det.rounds == 6


def test_flapping_worker_never_oscillates():
    """A worker alternating miss/answer inside one probe window either
    stays ACTIVE (misses reset) or — once declared — stays DEAD (sticky
    until re-register).  It can never flap ACTIVE<->DEAD."""
    m = ClusterMembership(["w1"], clock=FakeClock())
    flap = {"n": 0}

    def prober(w):
        flap["n"] += 1
        return flap["n"] % 2 == 0  # miss, answer, miss, answer ...

    det = _detector(m, prober, threshold=2)
    states = []
    for _ in range(10):
        det.tick()
        states.append(m.state("w1"))
    assert all(s == ACTIVE for s in states), states
    # now a real outage: two consecutive misses declare it DEAD, and the
    # flapping prober answering again must NOT resurrect it
    det2 = _detector(m, lambda w: False, threshold=2)
    det2.tick(), det2.tick()
    assert m.state("w1") == DEAD
    det3 = _detector(m, lambda w: True, threshold=2)
    for _ in range(5):
        det3.tick()
    assert m.state("w1") == DEAD  # only register() resurrects


def test_detector_success_never_closes_open_breaker():
    """/v1/info answering is process liveness, not task-tier health: a
    detector probe success must not short-circuit the cooldown an OPEN
    breaker earned from real request failures."""
    m = ClusterMembership(["wob"], clock=FakeClock())
    BREAKERS.get("wob").trip()
    det = _detector(m, lambda w: True, threshold=3)
    for _ in range(5):
        det.tick()
    assert BREAKERS.get("wob").state == "open"
    assert m.state("wob") == ACTIVE  # the heartbeat side still lands


def test_draining_worker_death_never_trips_breaker():
    """A DRAINING worker's exit — detector threshold or scheduler evidence
    — is the drain completing by choice: death is recorded, the breaker is
    NOT tripped (it narrates failures, not retirements)."""
    m = ClusterMembership(["wdx"], clock=FakeClock())
    m.drain("wdx")
    # default thresholds on purpose: miss-threshold (3) >= the breaker's
    # failure-threshold (3), so per-miss breaker votes would trip it
    # BEFORE mark_dead's retirement carve-out ever ran
    det = _detector(m, lambda w: False, threshold=3)
    for _ in range(4):
        det.tick()
    assert m.state("wdx") == DEAD
    assert BREAKERS.get("wdx").state != "open"


def test_spurious_503_does_not_retire_worker(cluster3):
    """A 503 that does NOT come from a real drain (proxy/overload) must not
    stickily exclude the worker: /v1/info still says ACTIVE, so another
    worker takes the task and the mesh keeps all W members."""
    from trino_tpu.runtime.retry import FAILURE_INJECTOR

    mh = _mh(cluster3)
    victim = cluster3[0].url
    # the client-side mapping of an HTTP 503 — but the worker's /v1/info
    # still answers ACTIVE, so the drain claim must not be believed
    FAILURE_INJECTOR.inject(
        f"submit:{victim}", times=1, error=WorkerDrainingError
    )
    try:
        assert sorted(mh.execute(SQL).rows) == WANT
    finally:
        FAILURE_INJECTOR.clear()
    assert mh.membership.state(victim) == ACTIVE
    assert mh.last_replans == 0
    assert len(mh.last_plan_workers) == 3


def test_register_resurrects_draining_worker():
    """Registration is an explicit grow intent: a worker drained for
    maintenance and restarted must be able to rejoin (not just DEAD ones)."""
    m = ClusterMembership(["wd"], clock=FakeClock())
    m.drain("wd")
    assert m.active_workers() == []
    m.register("wd")
    assert m.state("wd") == ACTIVE
    assert m.active_workers() == ["wd"]


def test_detector_restart_does_not_leak_probe_loop():
    """stop()/start() must never leave two live probe loops: each loop owns
    its stop event, so a stopped loop can never observe the new one's."""
    import threading

    m = ClusterMembership(["wl"], clock=FakeClock())
    release = threading.Event()
    det = HeartbeatDetector(
        m,
        prober=lambda w: True,
        config=HeartbeatConfig(miss_threshold=3),
        sleep=lambda s: release.wait(5.0),
    )
    det.start()
    first_stop = det._stop
    det.stop()
    det.start()
    assert det._stop is not first_stop
    assert first_stop.is_set()  # the old loop exits at its next wakeup
    det.stop()
    release.set()


def test_detector_sets_alive_gauge():
    from trino_tpu.telemetry.metrics import worker_alive_gauge

    m = ClusterMembership(["wg1"], clock=FakeClock())
    assert worker_alive_gauge().value(("wg1",)) == 1
    det = _detector(m, lambda w: False, threshold=1)
    det.tick()
    assert worker_alive_gauge().value(("wg1",)) == 0
    m.register("wg1")
    assert worker_alive_gauge().value(("wg1",)) == 1


def test_membership_event_vocabulary_preregistered():
    """Scrapes must see join/drain/death/rejoin/shrink_replan at 0 before
    any transition fires (the PR 4 counter-vocabulary convention)."""
    from trino_tpu.telemetry.metrics import (
        MEMBERSHIP_EVENT_KINDS,
        MetricsRegistry,
        _register_engine_metrics,
    )

    reg = MetricsRegistry()
    _register_engine_metrics(reg)
    snap = reg.snapshot()
    for kind in MEMBERSHIP_EVENT_KINDS:
        key = 'trino_tpu_membership_events_total{kind="%s"}' % kind
        assert snap.get(key) == 0, (key, sorted(snap))
    assert set(MEMBERSHIP_EVENT_KINDS) >= {"join", "drain", "death"}


# -- drain refusal semantics (real worker, no sleeps) --------------------------


def test_drain_refuses_new_tasks_with_503():
    from trino_tpu.parallel.remote import RemoteTaskClient
    from trino_tpu.server.worker import TaskDescriptor, WorkerServer

    w = WorkerServer(port=0).start()
    try:
        # keep the HTTP server alive so the refusal itself is observable
        w.begin_drain(exit_on_idle=False)
        assert w.state == "DRAINING"
        # /v1/info advertises the drain so probes/dashboards see it
        with urllib.request.urlopen(f"{w.url}/v1/info", timeout=5.0) as r:
            assert b"DRAINING" in r.read()
        # raw POST: refused before the body is even unpickled
        req = urllib.request.Request(
            f"{w.url}/v1/task", data=b"ignored", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5.0)
        assert ei.value.code == 503
        # the coordinator client maps 503 to WorkerDrainingError — REFUSED
        # classification (skip this worker) WITHOUT a breaker vote
        client = RemoteTaskClient(w.url, "t-drain")
        with pytest.raises(WorkerDrainingError):
            client.submit(TaskDescriptor("t-drain", None, []))
        assert isinstance(WorkerDrainingError("x"), ConnectionRefusedError)
        assert BREAKERS.get(w.url).state == "closed"
        # idle worker: the drain waiter has already signalled completion
        assert w.drained.wait(timeout=10.0)
    finally:
        w.shutdown()


def test_shutdown_endpoint_drains_and_exits():
    import threading

    from trino_tpu.server.worker import WorkerServer

    w = WorkerServer(port=0).start()
    # the drained server must LINGER (worker.drain-grace) before exiting:
    # task completion is not result delivery — consumers still pull
    lingered = threading.Event()
    grace_seen = []

    def fake_sleep(s):
        grace_seen.append(s)
        lingered.set()

    w._sleep = fake_sleep
    req = urllib.request.Request(f"{w.url}/v1/worker/shutdown", method="PUT")
    with urllib.request.urlopen(req, timeout=5.0) as r:
        assert r.read() == b"DRAINING"
    # no running tasks: the waiter finishes the drain and stops the server
    assert w.drained.wait(timeout=10.0)
    assert lingered.wait(timeout=10.0)
    assert grace_seen == [get_config().worker.drain_grace_s]


def test_submit_loses_drain_race_atomically():
    """A submission that passes the handler's DRAINING fast-path but loses
    the atomic admission check is refused — it can never slip past the
    drain waiter's task snapshot."""
    from trino_tpu.server.worker import (
        TaskDescriptor,
        WorkerDraining,
        WorkerServer,
    )

    w = WorkerServer(port=0).start()
    try:
        w.begin_drain(exit_on_idle=False)
        with pytest.raises(WorkerDraining):
            w.submit(TaskDescriptor("t-race", None, []))
        assert "t-race" not in w._tasks
    finally:
        w.shutdown()


def test_shutdown_endpoint_requires_cluster_auth(monkeypatch):
    """With a cluster secret configured, an unsigned shutdown PUT is 401 —
    drain is as privileged as task submission."""
    from trino_tpu.server.worker import WorkerServer, sign_body

    monkeypatch.setenv("TRINO_TPU_CLUSTER_SECRET", "s3cret")
    w = WorkerServer(port=0).start()
    try:
        req = urllib.request.Request(
            f"{w.url}/v1/worker/shutdown", method="PUT"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5.0)
        assert ei.value.code == 401
        assert w.state == "ACTIVE"
        req = urllib.request.Request(
            f"{w.url}/v1/worker/shutdown",
            headers={"X-Cluster-Auth": sign_body(b"s3cret", b"")},
            method="PUT",
        )
        with urllib.request.urlopen(req, timeout=5.0) as r:
            assert r.read() == b"DRAINING"
    finally:
        w.shutdown()


# -- shrink / grow plan shape --------------------------------------------------


@pytest.fixture()
def cluster3():
    from trino_tpu.server.worker import WorkerServer

    ws = [WorkerServer(port=0).start() for _ in range(3)]
    yield ws
    for w in ws:
        try:
            w.shutdown()
        except Exception:
            pass


def _mh(ws):
    from trino_tpu.parallel.remote import MultiHostQueryRunner

    return MultiHostQueryRunner(
        [w.url for w in ws], catalog="tpch", schema="tiny"
    )


SQL = "select r_name, count(*) from region group by r_name"
WANT = sorted((n, 1) for n in ("AFRICA", "AMERICA", "ASIA", "EUROPE",
                               "MIDDLE EAST"))


def test_shrink_replan_on_dead_worker(cluster3):
    """A worker discovered dead at scheduling time shrinks the mesh: the
    query re-fragments against W-1 and completes with the right rows."""
    mh = _mh(cluster3)
    assert sorted(mh.execute(SQL).rows) == WANT
    assert len(mh.last_plan_workers) == 3 and mh.last_replans == 0
    cluster3[2].shutdown()
    mh._worker_health.clear()  # fresh probe evidence, no TTL'd verdicts
    assert sorted(mh.execute(SQL).rows) == WANT
    assert len(mh.last_plan_workers) == 2, mh.last_plan_workers
    assert mh.last_replans >= 1
    assert mh.membership.state(cluster3[2].url) == DEAD
    # membership settled: the NEXT query plans at W-1 without re-planning
    assert sorted(mh.execute(SQL).rows) == WANT
    assert len(mh.last_plan_workers) == 2 and mh.last_replans == 0


def test_drain_excluded_from_next_mesh(cluster3):
    mh = _mh(cluster3)
    mh.drain_worker(cluster3[0].url)
    assert sorted(mh.execute(SQL).rows) == WANT
    assert cluster3[0].url not in mh.last_plan_workers
    assert len(mh.last_plan_workers) == 2 and mh.last_replans == 0
    assert mh.membership.state(cluster3[0].url) == DRAINING


def test_grow_joins_next_query_mesh(cluster3):
    from trino_tpu.server.worker import WorkerServer

    mh = _mh(cluster3[:2])
    assert sorted(mh.execute(SQL).rows) == WANT
    assert len(mh.last_plan_workers) == 2
    w4 = cluster3[2]
    mh.add_worker(w4.url)
    assert sorted(mh.execute(SQL).rows) == WANT
    assert w4.url in mh.last_plan_workers
    assert len(mh.last_plan_workers) == 3 and mh.last_replans == 0


def test_shrink_grow_round_trip_leaves_the_warm_path_clean(cluster3):
    """Membership churn must not dirty the warm path: after a worker is
    killed (the query re-plans at W-1) and a replacement has joined (the
    next query plans at W again), a warm repeat at the restored W re-plans
    nothing and retraces nothing, and every state answered the same rows."""
    from trino_tpu.parallel.spmd import TRACE_CACHE
    from trino_tpu.runtime.retry import BREAKERS
    from trino_tpu.server.worker import WorkerServer

    mh = _mh(cluster3)
    assert sorted(mh.execute(SQL).rows) == WANT  # baseline at W
    cluster3[2].shutdown()
    mh._worker_health.clear()  # fresh probe evidence, no TTL'd verdicts
    BREAKERS.reset()
    assert sorted(mh.execute(SQL).rows) == WANT  # shrink
    assert len(mh.last_plan_workers) == 2 and mh.last_replans >= 1
    replacement = WorkerServer(port=0).start()
    try:
        mh.add_worker(replacement.url)
        assert sorted(mh.execute(SQL).rows) == WANT  # grow
        assert len(mh.last_plan_workers) == 3 and mh.last_replans == 0
        retraces = TRACE_CACHE.stats().get("retraces", 0)
        assert sorted(mh.execute(SQL).rows) == WANT  # warm repeat at W
        assert len(mh.last_plan_workers) == 3 and mh.last_replans == 0
        assert TRACE_CACHE.stats().get("retraces", 0) == retraces
    finally:
        replacement.shutdown()


def test_single_refused_submit_does_not_evict_live_worker(cluster3):
    """One ECONNREFUSED on submit (restart blip, backlog overflow) against
    a worker whose probe still answers must NOT sticky-evict it: another
    worker takes the task and the mesh stays W-wide."""
    from trino_tpu.runtime.retry import FAILURE_INJECTOR

    mh = _mh(cluster3)
    victim = cluster3[0].url
    FAILURE_INJECTOR.inject(
        f"submit:{victim}", times=1, error=ConnectionRefusedError
    )
    try:
        assert sorted(mh.execute(SQL).rows) == WANT
    finally:
        FAILURE_INJECTOR.clear()
    assert mh.membership.state(victim) == ACTIVE
    assert mh.last_replans == 0
    assert len(mh.last_plan_workers) == 3


def test_breaker_open_worker_is_not_evicted(cluster3):
    """A worker whose breaker is merely OPEN (cooling down from transient
    flaps) is ALIVE: tasks route around it for the cooldown, but it must
    not be declared DEAD — sticky death would evict a healthy worker over
    a 5-second blip."""
    mh = _mh(cluster3)
    cooling = cluster3[1].url
    BREAKERS.get(cooling).trip()
    assert sorted(mh.execute(SQL).rows) == WANT
    assert mh.membership.state(cooling) == ACTIVE
    assert mh.last_replans == 0
    # the mesh still includes it (plans stay W-wide; submission skips it
    # per-task until the breaker's half-open window re-admits it)
    assert cooling in mh.last_plan_workers


def test_registry_partial_explicit_knobs_still_read_config():
    """Pinning ONE breaker knob in the constructor must not mute the typed
    config for the other."""
    from trino_tpu.runtime.retry import CircuitBreakerRegistry

    install_config(load_cluster_config({"breaker.cooldown": "30"}, env={}))
    reg = CircuitBreakerRegistry(failure_threshold=5)
    b = reg.get("w-partial")
    assert b.failure_threshold == 5  # explicit wins
    assert b.cooldown_s == 30.0  # config still consulted


def test_mesh_changed_error_is_not_retryable():
    """Retry machinery must never absorb a mesh change (it would retry
    forever against a corpse — the exact PR 5 gap this PR closes)."""
    from trino_tpu.runtime.retry import RETRYABLE

    assert not isinstance(MeshChangedError(dead=["w"]), RETRYABLE)
    assert not isinstance(MeshChangedError(dead=["w"]), ConnectionError)


def test_nodes_table_queryable_through_multihost_runner(cluster3):
    """System tables are coordinator-resident: a system-only query through
    the MULTIHOST runner executes locally (workers don't mount the system
    catalog), so membership is visible exactly where it lives."""
    mh = _mh(cluster3)
    mh.drain_worker(cluster3[1].url)
    rows = mh.execute(
        "select node_id, state, breaker_state from system.runtime.nodes"
    ).rows
    states = {r[0]: r[1] for r in rows}
    assert states[cluster3[0].url] == ACTIVE
    assert states[cluster3[1].url] == DRAINING
    # non-system queries still distribute (the local path is system-only)
    assert sorted(mh.execute(SQL).rows) == WANT
    assert len(mh.last_plan_workers) == 2


def test_nodes_table_reports_membership():
    from trino_tpu.connectors.system import SystemConnector

    class _Stub:
        membership = ClusterMembership(["wa", "wb"], clock=FakeClock())
        prewarm = None

    _Stub.membership.drain("wb")
    conn = SystemConnector(runner=_Stub())
    rows = {r[0]: r for r in conn._rows("nodes")}
    assert rows["wa"][1] == ACTIVE and rows["wb"][1] == DRAINING
    # no prewarm executor attached: the prewarm column is NULL
    assert rows["wa"][4] is None
    # column count matches the declared system.runtime.nodes schema
    from trino_tpu.connectors.system import _TABLES

    assert all(len(r) == len(_TABLES["nodes"]) for r in rows.values())


# -- mesh-signature cache invalidation -----------------------------------------


def test_invalidate_mesh_scans_by_signature():
    from trino_tpu.runtime.buffer_pool import POOL

    with POOL.lock:
        POOL.device.entries[("mesh_scan", "sigA", None, ("s1",))] = (["b"], 0)
        POOL.device.entries[("mesh_scan", "sigA", None, ("s2",))] = (["b"], 0)
        POOL.device.entries[("mesh_scan", "sigB", None, ("s1",))] = (["b"], 0)
        POOL.device.entries[("other", "sigA")] = (["b"], 0)
    try:
        assert invalidate_mesh_scans("sigA") == 2
        with POOL.lock:
            keys = list(POOL.device.entries)
        assert ("mesh_scan", "sigB", None, ("s1",)) in keys
        assert ("other", "sigA") in keys
        # None = every mesh signature (what a shrink re-plan uses)
        assert invalidate_mesh_scans() == 1
        with POOL.lock:
            assert ("other", "sigA") in POOL.device.entries
    finally:
        with POOL.lock:
            POOL.device.entries.pop(("other", "sigA"), None)


# -- speculative-capacity persistence (the PR 6 Q3 prewarm gap) ----------------


def test_capacity_history_version_and_seed_roundtrip():
    from trino_tpu.partitioning.speculative import CapacityHistory

    h = CapacityHistory()
    v0 = h.version
    h.record(("join", "l_orderkey", 8), 4096)
    assert h.version == v0 + 1
    h.record(("join", "l_orderkey", 8), 4096)  # same value: no new learning
    assert h.version == v0 + 1
    h.record(("join", "l_orderkey", 8), 8192)  # re-learned: version moves
    assert h.version == v0 + 2
    snap = h.snapshot()
    h2 = CapacityHistory()
    assert h2.seed(snap) == 1
    assert h2.guess(("join", "l_orderkey", 8), 1024) == 8192
    # corrupt/foreign entries are skipped, never fatal
    assert h2.seed([{"key": "not (valid", "cap": 1}, {"cap": 2}]) == 0
    assert h2.seed(None) == 0


# -- the module-level-knob lint rule -------------------------------------------


def _lint_mod():
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        return importlib.import_module("lint_tpu")
    finally:
        sys.path.pop(0)


def test_remote_module_has_no_knob_literals():
    """The satellite's teeth: parallel/remote.py holds ZERO module-level
    numeric knobs — they all moved to trino_tpu/config."""
    import os

    lint_tpu = _lint_mod()
    path = os.path.join(
        os.path.dirname(__file__), "..", "trino_tpu", "parallel", "remote.py"
    )
    assert "module-level-knob" in lint_tpu._rules_for_path(
        "trino_tpu/parallel/remote.py"
    )
    knobs = [
        f for f in lint_tpu.lint_file(path) if f.rule == "module-level-knob"
    ]
    assert knobs == [], knobs


def test_knob_rule_flags_module_literals(tmp_path):
    lint_tpu = _lint_mod()
    bad = tmp_path / "remote.py"
    bad.write_text(
        "TIMEOUT_S = 5.0\n"
        "class C:\n"
        "    ATTEMPTS = 3\n"
        "def f():\n"
        "    local_ok = 7\n"
        "    return local_ok\n"
        "NAMES = ('a', 'b')\n"
        "FLAG = True\n"
    )
    src = bad.read_text()
    import ast

    linter = lint_tpu._Linter(
        str(bad), src, rules=frozenset({"module-level-knob"})
    )
    linter.visit(ast.parse(src))
    flagged = sorted(f.line for f in linter.findings)
    # module + class level numerics flagged; function locals, tuples, and
    # booleans are not knobs
    assert flagged == [1, 3], linter.findings
