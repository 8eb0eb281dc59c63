"""Fault-tolerant execution: stage retry with spooled outputs + heartbeats.

Reference style: BaseFailureRecoveryTest (testing/trino-testing/.../
BaseFailureRecoveryTest.java:78) — inject failures at chosen stages and
assert queries still succeed under retry_policy=TASK, without re-running
finished stages."""

import pytest


from trino_tpu.parallel import DistributedQueryRunner
from trino_tpu.runtime.retry import FAILURE_INJECTOR, InjectedFailure
from trino_tpu.runtime.runner import LocalQueryRunner

pytestmark = pytest.mark.heavy


@pytest.fixture(autouse=True)
def clean_injector():
    FAILURE_INJECTOR.clear()
    yield
    FAILURE_INJECTOR.clear()


@pytest.fixture(autouse=True)
def no_spool_leaks(tmp_path, monkeypatch):
    """Every query-owned spool directory must be gone when the query ends
    (SpoolManager.close): chaos tests that leak orphan .npz spools fail
    HERE, not as unbounded /tmp growth in a long-lived deployment.  The
    spools are made under this test's own temporary root, so that another
    xdist worker's live spool is never taken for a leak."""
    import glob
    import os
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    yield
    leaked = glob.glob(os.path.join(str(tmp_path), "trino_tpu_spool_*"))
    assert not leaked, f"spool directories leaked: {sorted(leaked)}"


SQL = (
    "select n_regionkey, count(*) c, sum(n_nationkey) s from nation "
    "group by n_regionkey"
)


def _task_runner():
    r = DistributedQueryRunner(n_workers=8)
    r.properties.set("retry_policy", "TASK")
    return r


def test_stage_failure_retried_without_full_rerun():
    """A stage killed mid-query (after its children finished) re-executes
    alone; finished stages are served from memo/spool and never re-run."""
    r = _task_runner()
    expected = sorted(LocalQueryRunner().execute(SQL).rows)
    # fail the FINAL stage once, after its body ran
    FAILURE_INJECTOR.inject("stage:2:finish", times=1)
    res = r.execute(SQL)
    assert sorted(res.rows) == expected
    # the scan stage (fragment 0) started exactly once
    starts = {
        k: v for k, v in FAILURE_INJECTOR.visits.items()
        if k.startswith("stage:") and not k.endswith(":finish")
    }
    assert starts.get("stage:0") == 1, starts
    assert starts.get("stage:2") == 2, starts  # failed once, retried once


def test_stage_failure_at_start_retried():
    r = _task_runner()
    FAILURE_INJECTOR.inject("stage:1", times=2)
    res = r.execute(SQL)
    assert res.row_count == 5


def test_retry_budget_exhausted_fails():
    from trino_tpu.runtime.retry import StageFailedException

    r = _task_runner()
    FAILURE_INJECTOR.inject("stage:0", times=99)
    with pytest.raises(StageFailedException):
        r.execute(SQL)
    # the budget is per-stage, not multiplicative across consumers
    assert FAILURE_INJECTOR.visits.get("stage:0", 0) == 4


def test_spool_roundtrip_serves_stage_output(tmp_path):
    """Spooled fragment outputs rehydrate exactly (ExchangeManager role)."""
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.planner.plan import Symbol
    from trino_tpu.runtime.fte import SpoolManager

    sp = SpoolManager(str(tmp_path))
    cols = [
        Column(np.arange(8, dtype=np.int64), T.BIGINT, None),
        Column(
            np.linspace(0, 1, 8), T.DOUBLE, np.arange(8) % 2 == 0
        ),
    ]
    b = Batch(cols, np.arange(8) < 5)
    sp.save("q1", 3, [b], None)
    syms = [Symbol("a", T.BIGINT), Symbol("b", T.DOUBLE)]
    out = sp.load("q1", 3, syms, [None, None])
    assert len(out) == 1
    assert out[0].to_pylist() == b.to_pylist()


def test_heartbeat_detector():
    from trino_tpu.runtime.fte import HeartbeatFailureDetector

    now = [0.0]
    det = HeartbeatFailureDetector(timeout_s=5.0, clock=lambda: now[0])
    det.register("w0")
    det.register("w1")
    assert det.failed_workers() == set()
    now[0] = 3.0
    det.heartbeat("w1")
    now[0] = 6.0  # w0 last seen at 0 -> stale; w1 at 3 -> alive
    assert det.failed_workers() == {"w0"}
    assert det.active_workers() == ["w1"]
    det.heartbeat("w0")  # recovery clears the failure mark
    assert det.failed_workers() == set()


def test_dead_worker_blocks_query():
    """In-process mesh workers are always alive; a stale REMOTE registration
    (server-mode worker) blocks scheduling."""
    r = _task_runner()
    r.failure_detector.register("remote-worker-9")
    # age the registration far past the timeout (the detector is a facade
    # over the membership registry — last_heartbeat lives on its entry)
    r.failure_detector.membership._workers[
        "remote-worker-9"
    ].last_heartbeat = -1e9
    with pytest.raises(RuntimeError, match="heartbeat"):
        r.execute(SQL)
    # recovery: the remote worker heartbeats again and queries proceed
    r.failure_detector.heartbeat("remote-worker-9")
    assert r.execute(SQL).row_count == 5

def test_spool_rides_filesystem_spi(tmp_path):
    """The spool resolves its storage through the filesystem SPI; remote
    schemes fail loudly at configuration time."""
    import pytest as _pt

    from trino_tpu.runtime.fte import SpoolManager

    s = SpoolManager(str(tmp_path / "spool"))
    import numpy as np

    from trino_tpu.columnar import Batch, Column
    from trino_tpu import types as T

    b = Batch([Column(np.arange(4), T.BIGINT)], np.ones(4, bool))
    from trino_tpu.planner.plan import Symbol

    syms = [Symbol("x", T.BIGINT)]
    s.save("q1", 0, [b], syms)
    assert s.exists("q1", 0)
    out = s.load("q1", 0, syms, [None])
    assert np.array_equal(np.asarray(out[0].columns[0].data), np.arange(4))

    with _pt.raises(NotImplementedError, match="s3"):
        SpoolManager("s3://bucket/spool")


def _one_batch(n: int = 4):
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.planner.plan import Symbol

    b = Batch(
        [Column(np.arange(n, dtype=np.int64), T.BIGINT)], np.ones(n, bool)
    )
    return b, [Symbol("x", T.BIGINT)]


def test_crash_atomic_save_leaves_no_torn_npz(tmp_path, monkeypatch):
    """A writer killed mid-save must leave NOTHING a retrying consumer
    could load: the partial bytes live in a .tmp sibling that is deleted
    on the way out, and the committed .npz name never appears."""
    import os

    from trino_tpu.runtime import fte as fmod

    sp = fmod.SpoolManager(str(tmp_path / "spool"))
    b, syms = _one_batch()

    class Killed(RuntimeError):
        pass

    real_savez = fmod.np.savez

    def torn_savez(f, **arrays):
        f.write(b"\x93NUMPY-torn")  # partial bytes, then the "crash"
        raise Killed("writer killed mid-save")

    monkeypatch.setattr(fmod.np, "savez", torn_savez)
    with pytest.raises(Killed):
        sp.save("q1", 0, [b], syms)
    # no committed file, no torn sibling, nothing to load
    assert not sp.exists("q1", 0)
    assert os.listdir(sp.dir) == []
    assert sp.load("q1", 0, syms, [None]) is None
    # the next (surviving) writer succeeds on the same key
    monkeypatch.setattr(fmod.np, "savez", real_savez)
    sp.save("q1", 0, [b], syms)
    out = sp.load("q1", 0, syms, [None])
    assert out[0].to_pylist() == b.to_pylist()


def test_duplicate_attempts_dedup_and_discard(tmp_path):
    """Speculative/duplicate attempt outputs for one (query, fragment):
    the first COMMITTED attempt wins for every consumer, a later commit is
    a no-op, and the losing attempts are deleted unread."""
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.planner.plan import Symbol
    from trino_tpu.runtime.fte import SpoolManager

    sp = SpoolManager(str(tmp_path / "spool"))
    syms = [Symbol("x", T.BIGINT)]
    b0 = Batch([Column(np.arange(4), T.BIGINT)], np.ones(4, bool))
    b1 = Batch([Column(np.arange(4) + 100, T.BIGINT)], np.ones(4, bool))
    sp.save("q1", 2, [b0], syms, attempt_id=0)
    sp.save("q1", 2, [b1], syms, attempt_id=1)
    assert sp.attempts("q1", 2) == [0, 1]
    assert sp.dedup.commit("q1", 2, 0) == 0
    # a duplicate attempt's commit is told which attempt won
    assert sp.dedup.commit("q1", 2, 1) == 0
    assert sp.dedup.committed("q1", 2) == 0
    assert sp.discard_duplicates("q1", 2, 0) == 1
    assert sp.attempts("q1", 2) == [0]
    out = sp.load("q1", 2, syms, [None], attempt_id=0)
    assert out[0].to_pylist() == b0.to_pylist()


def test_recovery_classification_table():
    """Per-error-code recovery classification: worker death/drain and
    transient fetch RETRY (same plan, lost tasks only); a mesh truly
    shrunk below the plan's requirement RE-PLANS; user/semantic errors
    FAIL and are never retried."""
    from trino_tpu.runtime.lifecycle import (
        FAIL,
        RECOVERY_CLASSIFICATION,
        REPLAN,
        RETRY,
        error_code_of,
        recovery_action,
    )
    from trino_tpu.runtime.membership import (
        MeshChangedError,
        WorkerDrainingError,
    )
    from trino_tpu.runtime.retry import StageFailedException

    dead = MeshChangedError(dead=("w1",))
    assert error_code_of(dead) == "WORKER_DEATH"
    assert recovery_action(dead) == RETRY
    drained = MeshChangedError(drained=("w2",))
    assert error_code_of(drained) == "WORKER_DRAIN"
    assert recovery_action(drained) == RETRY
    # WorkerDrainingError subclasses ConnectionRefusedError; it must
    # classify as the drain, not the generic transient fetch
    assert error_code_of(WorkerDrainingError("503")) == "WORKER_DRAIN"
    assert recovery_action(ConnectionError("reset")) == RETRY
    assert recovery_action(TimeoutError("fetch")) == RETRY
    assert RECOVERY_CLASSIFICATION["MESH_SHRINK_BELOW_REQUIREMENT"] == REPLAN
    # stage budget exhaustion and unknown errors are terminal
    assert recovery_action(StageFailedException("stage 0 failed")) == FAIL
    assert recovery_action(ValueError("semantic")) == FAIL


def test_fte_property_enables_task_retry():
    """fault_tolerant_execution=true turns on the whole TASK machinery
    (spooled outputs + per-stage retry) without touching retry_policy;
    finished stages are never re-run."""
    r = DistributedQueryRunner(n_workers=8)
    assert r.properties.get("retry_policy") == "NONE"
    r.properties.set("fault_tolerant_execution", True)
    expected = sorted(LocalQueryRunner().execute(SQL).rows)
    FAILURE_INJECTOR.inject("stage:2:finish", times=1)
    res = r.execute(SQL)
    assert sorted(res.rows) == expected
    starts = {
        k: v for k, v in FAILURE_INJECTOR.visits.items()
        if k.startswith("stage:") and not k.endswith(":finish")
    }
    assert starts.get("stage:0") == 1, starts
    assert starts.get("stage:2") == 2, starts


def test_duplicate_attempt_spool_consumer_dedup():
    """A stage killed AFTER its output durably spooled retries and spools
    a SECOND attempt for the same fragment — the consumer commits exactly
    one and the query answers exactly once (DeduplicatingDirectExchange-
    Buffer role)."""
    from trino_tpu.telemetry.metrics import task_retries_counter

    r = _task_runner()
    expected = sorted(LocalQueryRunner().execute(SQL).rows)
    retries_before = task_retries_counter().labels("retry").value()
    # fires after attempt 0's spool save: the retry's spool is a duplicate
    FAILURE_INJECTOR.inject("stage:0:spooled", times=1)
    res = r.execute(SQL)
    assert sorted(res.rows) == expected
    assert FAILURE_INJECTOR.visits.get("stage:0") == 2
    assert (
        task_retries_counter().labels("retry").value() == retries_before + 1
    )


def test_spooled_dictionary_refs_rehydrate_after_restart(tmp_path):
    """Satellite: a spooled fragment whose varchar column ships dictionary
    CODES round-trips a coordinator restart — the (key, version) ref
    resolves through the dictionary service snapshot, and a mismatched
    dictionary raises instead of silently mis-decoding."""
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.columnar.dictionary import StringDictionary
    from trino_tpu.planner.plan import Symbol
    from trino_tpu.runtime.dictionary_service import GlobalDictionaryService
    from trino_tpu.runtime.fte import SpoolManager

    svc = GlobalDictionaryService()
    d = StringDictionary(["APAC", "EMEA", "LATAM"])
    key, version = svc.register("tpch", "tiny", "region", "r_name", d).ref
    syms = [Symbol("r", T.VARCHAR)]
    codes = np.array([0, 2, 1, 2], dtype=np.int64)
    b = Batch([Column(codes, T.VARCHAR, None, d)], np.ones(4, bool))
    # the spool persists CODES + the (key, version) ref's dictionary
    sp = SpoolManager(str(tmp_path / "spool"))
    sp.save("q7", 1, [b], syms)
    assert svc.ref_of(d) == (key, version)

    # coordinator restart: snapshot -> fresh process state -> load
    snap = str(tmp_path / "dictionaries.json")
    svc.save_snapshot(snap)
    svc.reset()
    assert svc.ref_of(d) is None  # registry is empty post-restart
    assert svc.load_snapshot(snap) >= 1
    d2 = svc.resolve(key, version)
    assert tuple(d2.values) == ("APAC", "EMEA", "LATAM")

    # a NEW spool manager over the same directory (the restarted
    # coordinator) decodes the spooled codes through the resolved ref
    out = SpoolManager(str(tmp_path / "spool")).load("q7", 1, syms, [d2])
    assert out[0].to_pylist() == b.to_pylist()

    # never silently wrong: a dictionary too small for the stored codes
    # fails the load validation loudly
    wrong = StringDictionary(["A", "B"])
    with pytest.raises(ValueError, match="dictionary"):
        SpoolManager(str(tmp_path / "spool")).load("q7", 1, syms, [wrong])


def test_remote_fte_resumes_from_spooled_fragments():
    """Multi-host tentpole e2e: a worker killed mid-query under
    fault_tolerant_execution RETRIES the same plan on the survivors —
    the already-fetched fragment resumes from its spooled output
    (spool hit), only the lost fragment re-runs, and the query is NEVER
    re-planned."""
    from trino_tpu.parallel import remote as rmod
    from trino_tpu.parallel.remote import MultiHostQueryRunner
    from trino_tpu.server.worker import WorkerServer

    ws = [WorkerServer(port=0).start() for _ in range(3)]
    victim = ws[1]
    try:
        mh = MultiHostQueryRunner(
            [w.url for w in ws], catalog="tpch", schema="tiny"
        )
        mh.properties.set("fault_tolerant_execution", True)
        # two coordinator-consumed gather fragments: frag 0 (nation) is
        # fully fetched + spooled before frag 1 (region) starts
        q = (
            "select count(*) from nation "
            "union all select count(*) from region"
        )
        expected = LocalQueryRunner(catalog="tpch", schema="tiny").execute(
            q
        ).rows
        orig_fetch = rmod._fetch_ok
        state = {"calls": 0}

        def killing_fetch(task, *a, **kw):
            state["calls"] += 1
            # frag 0's three producers are calls 1-3; kill the victim as
            # frag 1's first result is pulled, so its loss cannot touch
            # the finished (spooled) fragment
            if state["calls"] == 4:
                victim.shutdown()
            return orig_fetch(task, *a, **kw)

        rmod._fetch_ok = killing_fetch
        try:
            got = mh.execute(q).rows
        finally:
            rmod._fetch_ok = orig_fetch
        assert sorted(got) == sorted(expected)
        assert mh.last_task_retries >= 1  # classified retry, not replan
        assert mh.last_spool_hits >= 1  # frag 0 resumed from the spool
        assert mh.last_replans == 0  # finished work never re-planned
    finally:
        for w in ws:
            try:
                w.shutdown()
            except Exception:
                pass
