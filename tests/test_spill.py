"""Memory-pressure degradation tests: budget -> revoke -> wave -> kill
(runtime/spill + the reservation points in the local planner and the mesh
runner).  Reference behaviors: HashBuilderOperator.startMemoryRevoke,
GenericPartitioningSpiller, SpillingJoinProcessor, LowMemoryKiller.

Everything here is tier-1: injected budgets, tmpdir spools, no sleeps."""

import threading

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.dictionary import StringDictionary
from trino_tpu.runtime import spill as S
from trino_tpu.runtime.memory import (
    ExceededMemoryLimitException,
    MemoryContext,
    MemoryPool,
    batch_bytes,
)
from trino_tpu.telemetry.metrics import (
    memory_revocations_counter,
    memory_waves_counter,
    spill_bytes_counter,
)

pytestmark = pytest.mark.smoke


# -- budget arithmetic ---------------------------------------------------------


def test_wave_count_next_pow2_of_need_over_budget():
    assert S.wave_count(1000, 300) == 4  # ceil(3.33) -> 4
    assert S.wave_count(1000, 500) == 2
    assert S.wave_count(10, 1000) == 2  # floor is 2
    assert S.wave_count(1 << 40, 1) == S.MAX_WAVES


def test_wave_count_session_override():
    class Props:
        def get(self, k):
            assert k == "memory_wave_partitions"
            return 8

    assert S.wave_count(1000, 1, Props()) == 8


def test_effective_budget_prefers_tightest():
    class Props:
        def get(self, k):
            return {"query_max_memory": 500,
                    "query_max_memory_bytes": 0}.get(k, 0)

    pool = MemoryPool(limit_bytes=900)
    q = pool.query_context("q")
    assert S.effective_budget(Props(), q.child("op")) == 500
    pool2 = MemoryPool(limit_bytes=300)
    q2 = pool2.query_context("q")
    assert S.effective_budget(Props(), q2.child("op")) == 300
    assert S.session_budget(Props()) == 500


# -- thread-safe reservation tree (satellite) ----------------------------------


def test_concurrent_reservations_never_over_admit():
    """Two threads racing one pool slot: the pool lock makes the
    check-and-reserve atomic, so at most one wins (pre-fix the unlocked
    ancestor climb could admit both past the limit)."""
    pool = MemoryPool(limit_bytes=1000)
    wins, errors = [], []
    barrier = threading.Barrier(4)

    def worker(i):
        ctx = pool.query_context(f"q{i}")
        barrier.wait()
        try:
            ctx.child("op").add_bytes(600)
            wins.append(i)
        except ExceededMemoryLimitException:
            errors.append(i)

    threads = [
        threading.Thread(target=worker, args=(i,), name=f"resv-{i}",
                         daemon=True)
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(wins) == 1 and len(errors) == 3
    assert pool.root.reserved == 600


def test_concurrent_reservation_stress_accounting_consistent():
    """Hammer the shared pool from several threads; accounting must return
    to exactly zero after symmetric releases (no corrupted ancestors)."""
    pool = MemoryPool()
    n_threads, iters = 6, 300

    def worker(i):
        q = pool.query_context(f"q{i}")
        ctx = q.child("op")
        for j in range(iters):
            ctx.add_bytes((j % 7) + 1)
            ctx.add_bytes(-((j % 7) + 1))
        ctx.close()
        q.force_release()

    threads = [
        threading.Thread(target=worker, args=(i,), name=f"stress-{i}",
                         daemon=True)
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert pool.root.reserved == 0
    assert not pool.root.query_children


# -- dictionary accounting (satellite) -----------------------------------------


def test_batch_bytes_counts_dictionary_storage():
    d = StringDictionary(["aa", "bbb", "cccc"])  # 9 value bytes, 3 entries
    b = Batch(
        [
            Column(np.zeros(8, np.int32), T.VARCHAR, np.ones(8, bool), d),
            Column(np.zeros(8, np.int64), T.BIGINT),
        ],
        np.ones(8, bool),
    )
    # codes 8*4 + valid 8 + bigint 8*8 + mask 8, plus the dictionary:
    # i32 table 3*4 + validity 3 + value bytes 9
    assert batch_bytes(b) == (8 * 4 + 8 + 8 * 8 + 8) + (3 * 4 + 3 + 9)


def test_batch_bytes_shared_dictionary_counted_once():
    d = StringDictionary(["x", "y"])
    col = lambda: Column(np.zeros(4, np.int32), T.VARCHAR, None, d)
    one = batch_bytes(Batch([col()], np.ones(4, bool)))
    two = batch_bytes(Batch([col(), col()], np.ones(4, bool)))
    # second column adds codes (4*4) only, not a second dictionary copy
    assert two == one + 4 * 4


# -- heartbeat refresh race (satellite) ----------------------------------------


def test_heartbeat_refresh_survives_concurrent_registrations():
    from trino_tpu.runtime.fte import HeartbeatFailureDetector

    det = HeartbeatFailureDetector(timeout_s=0.0)  # everyone times out
    det.register("seed")
    stop = threading.Event()
    raised = []

    def hammer():
        # bounded: enough fresh keys to force many dict resizes, without
        # growing refresh() into a quadratic crawl
        for i in range(20_000):
            if stop.is_set():
                return
            det.heartbeat(f"w{i}")  # new keys -> dict resizes

    t = threading.Thread(target=hammer, name="hb-hammer", daemon=True)
    t.start()
    try:
        while t.is_alive():
            try:
                det.refresh()
                det.failed_workers()
            except RuntimeError as e:  # pragma: no cover - the old bug
                raised.append(e)
                break
    finally:
        stop.set()
        t.join()
    assert not raised


# -- SpillManager / spool SPI (satellites + tentpole plumbing) -----------------


def _dict_batch():
    d = StringDictionary(["a", "b", "c"])
    return Batch(
        [
            Column(np.array([2, 0, 1, 2], np.int32), T.VARCHAR,
                   np.array([True, True, False, True]), d),
            Column(np.arange(4, dtype=np.int64), T.BIGINT),
        ],
        np.ones(4, bool),
    )


def test_spill_manager_roundtrip_preserves_dictionary_columns(tmp_path):
    sp = S.SpillManager(directory=str(tmp_path))
    b = _dict_batch()
    n = sp.save("t", 0, [b])
    assert n == batch_bytes(b) and sp.bytes_spilled == n
    out = sp.load("t", 0)
    assert len(out) == 1
    got = out[0]
    assert got.columns[0].dictionary is not None
    assert list(got.columns[0].data) == [2, 0, 1, 2]
    assert got.columns[0].dictionary.values == ("a", "b", "c")
    assert sp.load("t", 3) == []  # never-written partition
    sp.close()


def test_spill_manager_cleans_shared_directory(tmp_path):
    """A CONFIGURED spill dir is shared (the spool won't remove it);
    close() must still delete this manager's own partition files, or
    sustained pressure fills the disk between orphan sweeps."""
    import os

    sp = S.SpillManager(directory=str(tmp_path))
    sp.save("t", 0, [_dict_batch()])
    sp.save("u", 1, [_dict_batch()])
    assert len([p for p in os.listdir(tmp_path) if p.endswith(".npz")]) == 2
    sp.close()
    assert [p for p in os.listdir(tmp_path) if p.endswith(".npz")] == []
    assert os.path.isdir(tmp_path)  # the shared directory itself survives


def test_spool_load_validates_dictionaries(tmp_path):
    from trino_tpu.planner import plan as P
    from trino_tpu.runtime.fte import SpoolManager

    spool = SpoolManager(directory=str(tmp_path))
    b = _dict_batch()
    symbols = [P.Symbol("s", T.VARCHAR), P.Symbol("k", T.BIGINT)]
    spool.save("q", 0, [b], symbols)
    # wrong dictionary count
    with pytest.raises(ValueError, match="dictionaries"):
        spool.load("q", 0, symbols, [b.columns[0].dictionary])
    # dictionary too small for the stored codes
    small = StringDictionary(["a"])
    with pytest.raises(ValueError, match="out of range"):
        spool.load("q", 0, symbols, [small, None])
    ok = spool.load("q", 0, symbols, [b.columns[0].dictionary, None])
    assert ok is not None and list(ok[0].columns[0].data) == [2, 0, 1, 2]


def test_spool_close_routes_through_filesystem_spi():
    import os

    from trino_tpu.planner import plan as P
    from trino_tpu.runtime.fte import SpoolManager

    spool = SpoolManager()  # own tmpdir -> close() removes it via the SPI
    calls = []
    orig = spool.fs.delete_recursive
    spool.fs.delete_recursive = lambda p: (calls.append(p), orig(p))
    b = _dict_batch()
    spool.save("q", 0, [b], [P.Symbol("s", T.VARCHAR), P.Symbol("k", T.BIGINT)])
    d = spool.dir
    spool.close()
    assert calls == [d]
    assert not os.path.exists(d)


# -- escalation ladder: exceed -> revoke -> kill -------------------------------


class _Owner:
    def __init__(self):
        self.killed = None

    def kill(self, reason, detail=None):
        self.killed = reason


def _escalated_pool(limit):
    from trino_tpu.runtime.lifecycle import LowMemoryKiller

    pool = MemoryPool(limit_bytes=limit)
    pool.root.on_exceeded = S.MemoryEscalation(LowMemoryKiller())
    return pool


def test_revoke_runs_before_killer_and_query_survives():
    pool = _escalated_pool(1000)
    victim_owner = _Owner()
    q1 = pool.query_context("q1")
    q1.owner = victim_owner
    held = q1.child("build")
    held.set_bytes(800)

    def spill():
        freed = held.reserved
        held.set_bytes(0)
        return freed

    h = S.REVOCABLES.register(S.RevocableOperator("join", held, spill))
    rev0 = memory_revocations_counter().value()
    try:
        q2 = pool.query_context("q2")
        q2.child("op").add_bytes(600)  # exceeds -> revoke tier frees 800
    finally:
        h.finish()
    assert h.revoked
    assert victim_owner.killed is None  # the killer never fired
    assert memory_revocations_counter().value() == rev0 + 1
    assert pool.root.reserved == 600


def test_killer_last_resort_when_revocation_cannot_free_shortfall():
    pool = _escalated_pool(1000)
    small_owner, big_owner = _Owner(), _Owner()
    q_small = pool.query_context("qs")
    q_small.owner = small_owner
    held = q_small.child("agg")
    held.set_bytes(50)  # revocable, but far too small

    q_big = pool.query_context("qb")
    q_big.owner = big_owner
    q_big.child("op").set_bytes(900)

    def spill():
        freed = held.reserved
        held.set_bytes(0)
        return freed

    h = S.REVOCABLES.register(S.RevocableOperator("agg", held, spill))
    try:
        q2 = pool.query_context("q2")
        q2.child("op").add_bytes(600)
    finally:
        h.finish()
    # revocation freed 50 (and was consumed), but the killer still had to
    # shoot the LARGEST query — victim choice unchanged
    assert h.revoked
    assert big_owner.killed == "memory"
    assert small_owner.killed is None
    assert q_big.parent is None  # force-released / detached


def test_killer_refuses_when_requester_is_largest():
    pool = _escalated_pool(1000)
    q1 = pool.query_context("q1")
    with pytest.raises(ExceededMemoryLimitException):
        q1.child("op").add_bytes(1200)  # nothing to revoke, nobody smaller
    assert pool.root.reserved == 0


def test_registry_revokes_largest_first():
    pool = MemoryPool()
    q = pool.query_context("q")
    a, b = q.child("a"), q.child("b")
    a.set_bytes(100)
    b.set_bytes(900)
    order = []

    def mk(name, ctx):
        def spill():
            order.append(name)
            freed = ctx.reserved
            ctx.set_bytes(0)
            return freed

        return S.REVOCABLES.register(S.RevocableOperator(name, ctx, spill))

    ha, hb = mk("a", a), mk("b", b)
    try:
        assert S.REVOCABLES.revoke_largest() == 900
        assert order == ["b"]
        assert S.REVOCABLES.revoke_largest() == 100
    finally:
        ha.finish()
        hb.finish()


# -- local wave execution with filesystem-SPI spill ----------------------------


JOIN_SQL = (
    "select o_orderpriority, count(*) c from orders join lineitem "
    "on o_orderkey = l_orderkey group by o_orderpriority"
)


def _runner(**props):
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=2)
    for k, v in props.items():
        r.properties.set(k, v)
    return r


@pytest.fixture(scope="module")
def join_oracle():
    return sorted(_runner().execute(JOIN_SQL).rows)


def test_wave_join_spills_through_spi_and_matches(join_oracle):
    """Over-budget join degrades to k hash-partition waves spilled through
    the filesystem SPI; `memory_wave_partitions` pins k (the override
    knob), and rows equal the unconstrained oracle."""
    spill0 = spill_bytes_counter().value()
    waves0 = memory_waves_counter().value(("join",))
    r = _runner(query_max_memory=200_000, memory_wave_partitions=2)
    rows = sorted(r.execute(JOIN_SQL).rows)
    assert rows == join_oracle
    assert memory_waves_counter().value(("join",)) == waves0 + 2
    assert spill_bytes_counter().value() > spill0  # disk spill, not RAM


def test_wave_join_spill_disabled_stays_in_ram(join_oracle):
    spill0 = spill_bytes_counter().value()
    r = _runner(query_max_memory=200_000, spill_enabled=False,
                memory_wave_partitions=2)
    rows = sorted(r.execute(JOIN_SQL).rows)
    assert rows == join_oracle
    assert spill_bytes_counter().value() == spill0  # bisection knob works


def test_agg_waves_spill_through_spi():
    sql = (
        "select l_orderkey, count(*), sum(l_quantity) from lineitem "
        "group by l_orderkey"
    )
    base = sorted(map(repr, _runner().execute(sql).rows))
    spill0 = spill_bytes_counter().value()
    waves0 = memory_waves_counter().value(("aggregation",))
    r = _runner(query_max_memory=150_000, memory_wave_partitions=2)
    rows = sorted(map(repr, r.execute(sql).rows))
    assert rows == base
    assert memory_waves_counter().value(("aggregation",)) > waves0
    assert spill_bytes_counter().value() > spill0


def test_explain_analyze_shows_pressure_counters():
    # same budget/k as the wave-join test above: compiled wave programs
    # are already cached, this exercises only the stats surface
    r = _runner(query_max_memory=200_000, memory_wave_partitions=2)
    res = r.execute("explain analyze " + JOIN_SQL)
    out = "\n".join(row[0] for row in res.rows)
    assert "memory_wave=" in out and "spill_bytes=" in out


def test_revocation_mid_query_finishes_in_waves(join_oracle):
    """A running join's build is revoked mid-probe (the pool limit shrinks
    under it); the probe remainder finishes in waves and rows still match
    — chaos test (a)'s deterministic tier-1 core."""
    from trino_tpu.ops.join import HashJoinOperator
    from trino_tpu.runtime.lifecycle import set_memory_pool_limit

    rev0 = memory_revocations_counter().value()
    calls = []
    orig = HashJoinOperator._join_batch

    def tripping(self, pb):
        out = orig(self, pb)
        if not calls:
            # shrink the shared pool BELOW the join build's reservation
            # (but above the query's small residual state): the NEXT
            # reservation (the agg above this join) trips the escalation
            # and the revoke tier asks THIS build to spill
            set_memory_pool_limit(300_000)
        calls.append(1)
        return out

    HashJoinOperator._join_batch = tripping
    try:
        r = _runner(memory_wave_partitions=2)
        rows = sorted(r.execute(JOIN_SQL).rows)
    finally:
        HashJoinOperator._join_batch = orig
        set_memory_pool_limit(0)
    assert rows == join_oracle
    assert memory_revocations_counter().value() > rev0
    assert not S.REVOCABLES.live()  # handles cleaned up


# -- mesh wave execution -------------------------------------------------------


def test_mesh_wave_join_matches_local(join_oracle):
    from trino_tpu.parallel import DistributedQueryRunner

    # mesh-8: the signature every other tier-1 mesh test warms, so the
    # unconstrained run rides the shared trace cache
    d = DistributedQueryRunner(n_workers=8, schema="tiny")
    waves0 = memory_waves_counter().value(("join",))
    spill0 = spill_bytes_counter().value()
    rev0 = memory_revocations_counter().value()
    base = sorted(d.execute(JOIN_SQL).rows)
    assert base == join_oracle
    # unconstrained mesh execution is wave/spill/revocation free
    # (zero-cost-when-idle)
    assert memory_waves_counter().value(("join",)) == waves0
    assert spill_bytes_counter().value() == spill0
    assert memory_revocations_counter().value() == rev0
    d.properties.set("query_max_memory", 250_000)
    d.properties.set("memory_wave_partitions", 2)
    rows = sorted(d.execute(JOIN_SQL).rows)
    assert rows == join_oracle
    assert memory_waves_counter().value(("join",)) > waves0
    assert spill_bytes_counter().value() > spill0
    prof = d.last_mesh_profile
    assert prof.counters.get("memory_wave", 0) > 0
    assert prof.counters.get("spill_bytes", 0) > 0
