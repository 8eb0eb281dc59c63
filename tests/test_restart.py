"""Restart resilience (PR 8): persistent compile cache, prewarm executor,
auto-started failure detection, worker auto-rejoin, and bounded drain.

Everything here is tier-1: tmpdir caches, deterministic/injected clocks and
sleeps, trivial statements (`select count(*) from region`) so compiles stay
sub-second, and real-but-instant HTTP servers where the wire is the thing
under test (the mid-query kill sweeps stay in test_chaos.py behind `slow`).

The acceptance assertions live here:
  * a "restarted" process (fresh runner + cleared TRACE_CACHE) replaying
    the persisted manifest records ZERO compile events above its closure
    watermark;
  * after a mesh grow, the background prewarm re-traces at the NEW mesh
    signature before the next query;
  * a drain with a wedged task force-cancels it through its task-lifecycle
    token and the server still exits inside wait+grace;
  * a restarted worker PUTs /v1/worker/register at its coordinator and
    resurrects its membership entry without operator action.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from trino_tpu.config import (
    ClusterConfig,
    install_config,
    load_cluster_config,
    reset_config,
)
from trino_tpu.runtime.prewarm import (
    PrewarmExecutor,
    WorkloadManifest,
    attach_prewarm,
    enable_persistent_compile_cache,
    load_manifest,
    save_manifest,
)
from trino_tpu.runtime.retry import BREAKERS
from trino_tpu.telemetry.compile_events import OBSERVATORY

SQL = "select count(*) from region"


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # these tests place the cache through `compile-cache.dir`; an ambient
    # JAX_COMPILATION_CACHE_DIR would (rightly) win over it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    reset_config()
    BREAKERS.reset()
    yield
    reset_config()
    BREAKERS.reset()
    # a tmpdir cache must never outlive its directory into later tests:
    # back to the suite's default placement
    from trino_tpu.parallel.spmd import configure_persistent_cache

    configure_persistent_cache()


# -- persistent compile cache --------------------------------------------------


def test_compile_cache_config_defaults():
    cc = ClusterConfig().compile_cache
    assert cc.dir == "" and cc.enabled is True
    assert cc.min_compile_time_s == 0.0 and cc.min_entry_size_bytes == -1
    pw = ClusterConfig().prewarm
    assert pw.manifest_path == "" and pw.on_start and pw.on_grow


def test_enable_persistent_cache_local_dir(tmp_path):
    from trino_tpu.parallel import spmd

    cache = tmp_path / "xla-cache"
    cfg = load_cluster_config({"compile-cache.dir": str(cache)})
    assert enable_persistent_compile_cache(cfg) == str(cache)
    assert cache.is_dir()
    assert spmd.PERSISTENT_CACHE_DIR == str(cache)
    # a compile lands entries on disk — the half of a cold start that now
    # survives process death
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))
    assert any(cache.iterdir()), "expected persisted XLA cache entries"
    assert spmd.configure_persistent_cache(enabled=False) is None
    assert spmd.PERSISTENT_CACHE_DIR is None


def test_enable_persistent_cache_remote_scheme_is_graceful_noop():
    msgs = []
    cfg = load_cluster_config({"compile-cache.dir": "s3://bucket/cache"})
    assert enable_persistent_compile_cache(cfg, warn=msgs.append) is None
    assert msgs and "s3://" in msgs[0]


def test_enable_persistent_cache_respects_master_switch(tmp_path):
    cfg = load_cluster_config(
        {
            "compile-cache.dir": str(tmp_path / "cc"),
            "compile-cache.enabled": "false",
        }
    )
    assert enable_persistent_compile_cache(cfg) is None
    assert not (tmp_path / "cc").exists()


def test_install_config_applies_compile_cache(tmp_path):
    from trino_tpu.parallel import spmd

    cache = tmp_path / "cc"
    install_config(load_cluster_config({"compile-cache.dir": str(cache)}))
    assert spmd.PERSISTENT_CACHE_DIR == str(cache)


# -- workload manifest ---------------------------------------------------------


def test_manifest_save_load_roundtrip(tmp_path):
    loc = str(tmp_path / "m.json")
    m = WorkloadManifest(
        statements=[SQL], cap_history=[{"key": "('a',)", "cap": 8}],
        watermark=7, closed=True, workers=2,
    )
    save_manifest(m, loc, extra={"schema": "tiny"})
    got = load_manifest(loc)
    assert got.statements == [SQL] and got.watermark == 7
    assert got.closed is True and got.workers == 2
    # the saved doc keeps the tool's extra fields too
    with open(loc) as fh:
        doc = json.load(fh)
    assert doc["schema"] == "tiny" and doc["sql"] == [SQL]


def test_manifest_load_is_tolerant(tmp_path):
    loc = str(tmp_path / "m.json")
    (tmp_path / "m.json").write_text('{"sql": ["select 1"]}')
    got = load_manifest(loc)
    assert got.statements == ["select 1"] and got.watermark == 0
    assert load_manifest(str(tmp_path / "missing.json")) is None
    (tmp_path / "bad.json").write_text("{not json")
    assert load_manifest(str(tmp_path / "bad.json")) is None


def test_record_filters_and_dedups(tmp_path):
    class _R:
        pass

    ex = PrewarmExecutor(_R(), str(tmp_path / "m.json"))
    assert ex.record(SQL) is True
    assert ex.record(SQL) is False  # dedup
    assert ex.record("  WITH t as (select 1) select * from t") is True
    assert ex.record("set session query_trace = false") is False
    assert ex.record("insert into t values (1)") is False
    assert ex.manifest().statements == [
        SQL, "  WITH t as (select 1) select * from t",
    ]


def test_save_never_clobbers_operator_manifest(tmp_path):
    """save() persists the UNION of the on-disk manifest and this
    process's recordings — a server that never ran its replay (on-start
    off, early shutdown) must not shrink the operator's manifest."""
    loc = str(tmp_path / "m.json")
    save_manifest(WorkloadManifest(statements=[SQL, "select 9"]), loc)

    class _R:
        pass

    ex = PrewarmExecutor(_R(), loc)
    ex.save()  # nothing recorded: the seed manifest survives intact
    assert load_manifest(loc).statements == [SQL, "select 9"]
    ex.record("select 10")
    assert ex.save() is True
    assert load_manifest(loc).statements == [SQL, "select 9", "select 10"]
    # an executor with NO location is a clean no-op
    assert PrewarmExecutor(_R(), None).save() is False


# -- the restart-closure acceptance bar ----------------------------------------


@pytest.fixture(scope="module")
def mesh2():
    from trino_tpu.parallel import DistributedQueryRunner

    return DistributedQueryRunner(n_workers=2, schema="tiny")


def test_restarted_process_prewarm_closure(tmp_path, mesh2):
    """Kill-and-restart simulation: the first incarnation records + saves a
    manifest; the process-local TRACE_CACHE dies; the restarted incarnation
    replays the manifest to WARM and its first real query records zero
    compile events above the closure watermark."""
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.parallel.spmd import TRACE_CACHE

    loc = str(tmp_path / "manifest.json")
    mesh2.execute(SQL)
    ex = PrewarmExecutor(mesh2, loc)
    ex.record(SQL)
    assert ex.save() is True

    # "restart": spmd.TRACE_CACHE is process-local and dies with the
    # process; the persisted manifest (and, in production, the on-disk XLA
    # cache) is what survives
    TRACE_CACHE.clear()
    restarted = DistributedQueryRunner(n_workers=2, schema="tiny")
    ex2 = attach_prewarm(restarted, loc)
    ex2.run(reason="start", wait=True)
    assert ex2.state == "WARM"
    assert ex2.verify_events == 0
    assert ex2.watermark is not None

    mark = OBSERVATORY.mark()
    restarted.execute(SQL)
    assert OBSERVATORY.mark() - mark == 0, (
        "a prewarmed replay must record zero compile events above the "
        "closure watermark"
    )


def test_restart_resolves_dictionary_codes_from_manifest(tmp_path, mesh2):
    """Global dictionary restart bar: the manifest carries the versioned
    code assignment (`dictionaries` doc), the restarted process adopts it
    BEFORE replaying, and a warm varchar statement then records zero
    compile events above the closure watermark — warm paths never block
    on (or re-derive differently-versioned) code resolution."""
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.parallel.spmd import TRACE_CACHE
    from trino_tpu.runtime.dictionary_service import DICTIONARY_SERVICE

    vsql = (
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority"
    )
    loc = str(tmp_path / "manifest.json")
    mesh2.execute(vsql)
    ex = PrewarmExecutor(mesh2, loc)
    ex.record(vsql)
    assert ex.save() is True
    m = load_manifest(loc)
    assert m.dictionaries and m.dictionaries.get("entries"), (
        "the saved manifest must carry the global dictionary snapshot"
    )

    # "restart": the trace cache AND the dictionary registry are
    # process-local; only the manifest survives
    TRACE_CACHE.clear()
    DICTIONARY_SERVICE.reset()
    restarted = DistributedQueryRunner(n_workers=2, schema="tiny")
    ex2 = attach_prewarm(restarted, loc)
    ex2.run(reason="start", wait=True)
    assert ex2.state == "WARM"
    assert DICTIONARY_SERVICE.stats()["versions"] > 0, (
        "replay must re-adopt the recorded code assignment"
    )

    mark = OBSERVATORY.mark()
    restarted.execute(vsql)
    assert OBSERVATORY.mark() - mark == 0


def test_grow_prewarms_at_new_mesh_signature(tmp_path, mesh2):
    """PR 7 gap (d): after add_worker grows the mesh, the background
    prewarm re-traces the manifest at the NEW mesh signature, so the next
    query compiles nothing even though every trace-cache key changed."""
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.parallel.spmd import mesh_key

    loc = str(tmp_path / "manifest.json")
    runner = DistributedQueryRunner(n_workers=2, schema="tiny")
    runner.execute(SQL)
    ex = attach_prewarm(runner, loc)
    ex.record(SQL)
    ex.save()

    old_sig = mesh_key(runner.wm)
    runner.resize_mesh(3)  # 2 -> 3: a NEW mesh signature
    assert runner.wm.n == 3 and mesh_key(runner.wm) != old_sig
    t = ex._thread
    assert t is not None, "grow must kick a background prewarm"
    t.join(timeout=120)
    assert ex.state == "WARM"

    mark = OBSERVATORY.mark()
    runner.execute(SQL)
    assert OBSERVATORY.mark() - mark == 0


def test_resize_mesh_validates_and_noop():
    from trino_tpu.parallel import DistributedQueryRunner

    runner = DistributedQueryRunner(n_workers=2, schema="tiny")
    with pytest.raises(ValueError):
        runner.resize_mesh(0)
    with pytest.raises(ValueError):
        runner.resize_mesh(99)
    wm = runner.wm
    runner.resize_mesh(2)  # same W: the mesh object (and its keys) survive
    assert runner.wm is wm


def test_shrink_unregisters_detector_entries():
    """A shrink must forget the dropped workers' detector entries — a
    stale one would time out and fail EVERY later query's liveness check
    (the runner would be permanently bricked)."""
    from trino_tpu.parallel import DistributedQueryRunner

    runner = DistributedQueryRunner(n_workers=4, schema="tiny")
    runner.resize_mesh(2)
    # the detector is a facade over the membership registry — the dropped
    # workers' entries must be gone from it entirely
    assert sorted(runner.failure_detector.active_workers()) == [
        "worker-0", "worker-1",
    ]
    # push the clock past timeout_s: surviving workers re-heartbeat at
    # query start, dropped ones must simply be gone
    runner.failure_detector.clock = (
        lambda base=runner.failure_detector.clock: base() + 60.0
    )
    assert runner.execute(SQL).rows == [(5,)]


def test_grow_respects_on_grow_knob(tmp_path):
    from trino_tpu.parallel import DistributedQueryRunner

    install_config(
        load_cluster_config({"prewarm.on-grow": "false"})
    )
    runner = DistributedQueryRunner(n_workers=2, schema="tiny")
    ex = attach_prewarm(runner, str(tmp_path / "m.json"))
    runner.resize_mesh(3)
    assert ex._thread is None  # no replay kicked


def test_register_endpoint_still_400s_for_inprocess_runner():
    """The mesh runner must NOT grow a url-shaped `add_worker` — the
    coordinator register protocol probes for that exact name, and an
    in-process runner has to keep answering 400, not crash on int+str."""
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    r = DistributedQueryRunner(n_workers=2, schema="tiny")
    assert not hasattr(r, "add_worker")
    srv = CoordinatorServer(runner=r, port=0)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://{srv.host}:{srv.port}/v1/worker/register",
            data=b"http://127.0.0.1:9", method="PUT",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc.value.code == 400
    finally:
        srv.shutdown()


def test_multihost_add_worker_kicks_prewarm():
    """The multihost grow path consults the same executor hook (no HTTP
    needed: registration is coordinator-local)."""
    from trino_tpu.parallel.remote import MultiHostQueryRunner

    mh = MultiHostQueryRunner(["http://127.0.0.1:1"], schema="tiny")
    kicked = []

    class _Stub:
        def run(self, reason="manual", **kw):
            kicked.append(reason)

    mh.prewarm = _Stub()
    mh.add_worker("http://127.0.0.1:2")
    assert kicked == ["grow"]
    assert mh.membership.state("http://127.0.0.1:2") == "ACTIVE"


def test_prewarm_unclosed_workload_is_flagged(tmp_path):
    """A manifest whose replay still compiles on the verify pass must say
    so (UNCLOSED), never claim WARM."""

    class _Runner:
        def execute(self, sql):
            # every execution records a fresh compile event: never closes.
            # abort() keeps the count (the closure math) but removes the
            # event from the pending set so no later REAL launch inherits it
            OBSERVATORY.abort(
                OBSERVATORY.open_miss(
                    ("spmd", False, False, (1,), "leaky", sql)
                )
            )

    ex = PrewarmExecutor(_Runner(), None)
    ex.run(statements=["select 1"], wait=True)
    assert ex.state == "UNCLOSED"
    assert ex.verify_events == 1


def test_run_queues_kick_racing_live_replay():
    """A grow kick racing an in-flight replay must be QUEUED, not dropped
    — otherwise the new mesh signature goes un-prewarmed while state
    still says WARM."""
    import threading as _threading

    gate = _threading.Event()
    ran = []

    class _Runner:
        def execute(self, sql):
            ran.append(sql)
            gate.wait(timeout=10.0)

    ex = PrewarmExecutor(_Runner(), None, verify=False)
    t1 = ex.run(reason="start", statements=["select 1"])
    deadline = time.monotonic() + 5.0
    while not ran and time.monotonic() < deadline:
        time.sleep(0.001)
    assert ran, "first replay must be in flight"
    ex.run(reason="grow", statements=["select 2"])  # races the live one
    gate.set()
    t1.join(timeout=10.0)
    with ex._state_lock:
        follow = ex._thread
    assert follow is not None
    follow.join(timeout=10.0)
    assert ran == ["select 1", "select 2"], (
        "the queued grow kick must run after the start replay"
    )
    assert ex.runs == 2


def test_install_config_disable_detaches_cache(tmp_path):
    """The master switch is a switch: reinstalling a config with the
    cache off must detach a previously-enabled one."""
    from trino_tpu.parallel import spmd

    cache = tmp_path / "cc"
    install_config(load_cluster_config({"compile-cache.dir": str(cache)}))
    assert spmd.PERSISTENT_CACHE_DIR == str(cache)
    install_config(
        load_cluster_config(
            {
                "compile-cache.dir": str(cache),
                "compile-cache.enabled": "false",
            }
        )
    )
    assert spmd.PERSISTENT_CACHE_DIR is None


def test_prewarm_failure_is_flagged(tmp_path):
    class _Runner:
        def execute(self, sql):
            raise RuntimeError("boom")

    ex = PrewarmExecutor(_Runner(), None)
    ex.run(statements=["select 1"], wait=True)
    assert ex.state == "FAILED"
    assert "boom" in ex.last_error


# -- bounded drain with forced-kill escalation ---------------------------------


def test_drain_force_kill_bounded():
    """A wedged task cannot wedge a drain: when worker.drain-task-wait
    expires the task is canceled through its task-lifecycle token and the
    server still exits inside wait+grace."""
    from trino_tpu.server.worker import TaskDescriptor, WorkerServer, _Task
    from trino_tpu.telemetry.metrics import drain_force_kills_counter

    install_config(
        load_cluster_config(
            {"worker.drain-task-wait": "0.05", "worker.drain-grace": "0.0"}
        )
    )
    w = WorkerServer(port=0).start()
    sleeps = []
    w._sleep = sleeps.append
    # a wedged task: registered, RUNNING, never finishes (its thread never
    # runs — the extreme of a task stuck in a non-cooperative region)
    stuck = _Task(TaskDescriptor("t_stuck", None, []))
    w._tasks["t_stuck"] = stuck
    t0 = time.monotonic()
    before = drain_force_kills_counter().value()
    w.begin_drain()
    assert w.drained.wait(timeout=5.0), "drain must complete despite the task"
    assert time.monotonic() - t0 < 5.0
    # the escalation: canceled through the task-lifecycle token...
    assert stuck.lifecycle.canceled
    assert "drain force-kill" in stuck.lifecycle.kill_detail
    assert drain_force_kills_counter().value() == before + 1
    # ...and the server exited after (injected) grace, not wedged forever
    deadline = time.monotonic() + 5.0
    while w._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not w._thread.is_alive(), "server must exit inside wait+grace"
    assert sleeps == [0.0]  # the grace linger ran (injected, instant)


def test_drain_without_tasks_still_graceful():
    from trino_tpu.server.worker import WorkerServer

    install_config(
        load_cluster_config(
            {"worker.drain-task-wait": "0.05", "worker.drain-grace": "0.0"}
        )
    )
    w = WorkerServer(port=0).start()
    w._sleep = lambda s: None
    w.begin_drain()
    assert w.drained.wait(timeout=5.0)


# -- coordinator-owned background services -------------------------------------


def test_coordinator_starts_and_stops_detector():
    """PR 7 gap (a): CoordinatorServer.start() launches the runner's
    heartbeat detector itself; shutdown() stops it."""
    from trino_tpu.parallel.remote import MultiHostQueryRunner
    from trino_tpu.runtime.membership import HeartbeatDetector
    from trino_tpu.server.coordinator import CoordinatorServer

    mh = MultiHostQueryRunner(["http://127.0.0.1:1"], schema="tiny")
    # deterministic detector: stub prober, instant sleep
    mh.failure_detector = HeartbeatDetector(
        mh.membership, prober=lambda w: True, sleep=lambda s: time.sleep(0.001)
    )
    srv = CoordinatorServer(runner=mh, port=0)
    srv.start()
    try:
        assert srv._detector_started
        deadline = time.monotonic() + 5.0
        while mh.failure_detector.rounds == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert mh.failure_detector.rounds > 0, "probe loop must be running"
    finally:
        srv.shutdown()
    assert mh.failure_detector._thread is None
    assert not srv._detector_started


def test_coordinator_start_without_detector_is_fine():
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(port=0)  # LocalQueryRunner: no start()able one
    srv.start()
    try:
        assert not srv._detector_started
    finally:
        srv.shutdown()


def test_coordinator_prewarm_on_start_and_records(tmp_path):
    """start() attaches a PrewarmExecutor from prewarm.manifest-path,
    replays it in the background, surfaces state in system.runtime.nodes,
    and shutdown() persists the union of seed + observed statements."""
    from trino_tpu.server.coordinator import CoordinatorServer

    loc = str(tmp_path / "manifest.json")
    save_manifest(WorkloadManifest(statements=["select 41 + 1"]), loc)
    install_config(load_cluster_config({"prewarm.manifest-path": loc}))
    srv = CoordinatorServer(port=0)
    srv.start()
    try:
        pw = srv.runner.prewarm
        assert pw is not None
        pw._thread.join(timeout=30)
        assert pw.state == "WARM"  # local runner: trivially closed
        # the prewarm column on system.runtime.nodes
        rows = srv.runner.execute(
            "select prewarm from system.runtime.nodes"
        ).rows
        assert rows and all(r[0] == "WARM" for r in rows)
        # live traffic joins the replay set
        q = srv.submit("select 2 + 2")
        assert q.done.wait(timeout=30) and q.state == "FINISHED"
    finally:
        srv.shutdown()
    got = load_manifest(loc)
    assert set(got.statements) == {"select 41 + 1", "select 2 + 2"}


def test_coordinator_adopts_preattached_executor_lock(tmp_path):
    """An executor attached BEFORE the server (runner_from_etc) must adopt
    the server's dispatcher admission (the system.prewarm resource group),
    or prewarm replays would interleave with live queries on the primary
    runner instead of queueing fairly for its lane."""
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer

    loc = str(tmp_path / "m.json")
    save_manifest(WorkloadManifest(statements=["select 1"]), loc)
    r = LocalQueryRunner()
    pre = attach_prewarm(r, loc)  # private lock, like runner_from_etc
    srv = CoordinatorServer(runner=r, port=0)
    srv.start()
    try:
        assert r.prewarm is pre
        assert pre._admission is not None  # dispatcher admission adopted
        pre._thread.join(timeout=30)
        assert pre.state == "WARM"
        # the replay went through the system.prewarm group, not a lock
        stats = {s["name"]: s for s in srv.dispatcher.stats()}
        assert stats["system.prewarm"]["total_admitted"] >= 1
    finally:
        srv.shutdown()


def test_coordinator_register_requires_hmac_when_secret_set(monkeypatch):
    from trino_tpu.parallel.remote import MultiHostQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import sign_body

    monkeypatch.setenv("TRINO_TPU_CLUSTER_SECRET", "s3cret")
    mh = MultiHostQueryRunner(["http://127.0.0.1:1"], schema="tiny")
    srv = CoordinatorServer(runner=mh, port=0)
    srv.start()
    try:
        base = f"http://{srv.host}:{srv.port}"
        body = b"http://127.0.0.1:2"
        req = urllib.request.Request(
            f"{base}/v1/worker/register", data=body, method="PUT"
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc.value.code == 401
        req = urllib.request.Request(
            f"{base}/v1/worker/register", data=body, method="PUT",
            headers={"X-Cluster-Auth": sign_body(b"s3cret", body)},
        )
        with urllib.request.urlopen(req, timeout=5.0) as r:
            assert r.status == 200
        assert mh.membership.state("http://127.0.0.1:2") == "ACTIVE"
    finally:
        srv.shutdown()


# -- worker auto-rejoin --------------------------------------------------------


def test_worker_auto_rejoin_after_restart():
    """A killed worker's replacement announces itself at the coordinator
    (PUT /v1/worker/register) and resurrects its membership entry without
    operator action; the next query's mesh includes it."""
    from trino_tpu.parallel.remote import MultiHostQueryRunner
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    ws = [WorkerServer(port=0).start() for _ in range(2)]
    mh = MultiHostQueryRunner([w.url for w in ws], schema="tiny")
    srv = CoordinatorServer(runner=mh, port=0)
    srv.start()
    restarted = None
    try:
        coord = f"http://{srv.host}:{srv.port}"
        assert sorted(mh.execute(
            "select r_name, count(*) from region group by r_name"
        ).rows)
        # kill w1 hard; the coordinator marks it dead at next contact
        dead_url = ws[1].url
        ws[1].shutdown()
        mh.membership.mark_dead(dead_url)
        assert mh.membership.state(dead_url) == "DEAD"
        # the "restarted" worker: a fresh process on a fresh port whose
        # start() announces to the configured coordinator
        restarted = WorkerServer(port=0, coordinator_url=coord).start()
        assert restarted.registered.wait(timeout=10.0), (
            "worker must register itself with the coordinator"
        )
        assert mh.membership.state(restarted.url) == "ACTIVE"
        rows = mh.execute(
            "select r_name, count(*) from region group by r_name"
        ).rows
        assert sorted(rows) == sorted(
            (n, 1)
            for n in ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
        )
        assert len(mh.last_plan_workers) == 2  # W restored
    finally:
        srv.shutdown()
        for w in ws[:1] + ([restarted] if restarted else []):
            try:
                w.shutdown()
            except Exception:
                pass


def test_worker_announce_gives_up_quietly():
    """A worker must come up even when its coordinator is unreachable —
    the announce is bounded best-effort, not a startup dependency."""
    from trino_tpu.server.worker import WorkerServer

    w = WorkerServer(port=0).start()
    w._sleep = lambda s: None  # no real backoff waits in tier-1
    assert w.announce("http://127.0.0.1:1", attempts=2) is False
    assert not w.registered.is_set()
    w.shutdown()
