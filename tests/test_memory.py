"""Memory accounting tests (reference: TestAggregatedMemoryContext +
TestMemoryPools)."""

import numpy as np
import pytest

pytestmark = pytest.mark.smoke

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.runtime.memory import (
    ExceededMemoryLimitException,
    MemoryContext,
    MemoryPool,
    batch_bytes,
)


def test_reservation_tree():
    pool = MemoryPool()
    q = pool.query_context("q1")
    op1, op2 = q.child("op1"), q.child("op2")
    op1.set_bytes(100)
    op2.set_bytes(50)
    assert q.reserved == 150 and pool.root.reserved == 150
    op1.set_bytes(20)
    assert pool.root.reserved == 70
    op1.close()
    op2.close()
    assert pool.root.reserved == 0
    assert pool.root.peak == 150


def test_limit_enforced_and_consistent():
    pool = MemoryPool(limit_bytes=100)
    q = pool.query_context("q1")
    op = q.child("op")
    op.set_bytes(90)
    with pytest.raises(ExceededMemoryLimitException):
        op.add_bytes(20)
    # failed reservation must leave the tree unchanged
    assert op.reserved == 90 and pool.root.reserved == 90
    op.add_bytes(5)
    assert pool.root.reserved == 95


def test_query_limit():
    pool = MemoryPool()
    q = pool.query_context("q1", limit_bytes=10)
    with pytest.raises(ExceededMemoryLimitException):
        q.child("op").set_bytes(11)
    assert pool.root.reserved == 0


def test_batch_bytes():
    b = Batch(
        [
            Column(np.zeros(8, np.int64), T.BIGINT, np.ones(8, bool)),
            Column(np.zeros(8, np.int32), T.INTEGER),
        ],
        np.ones(8, bool),
    )
    assert batch_bytes(b) == 8 * 8 + 8 + 8 * 4 + 8


def test_batch_bytes_reads_shapes_and_pulls_nothing():
    """Accounting a device batch must not move it: a row mask counts by its
    `.size`, never through `np.asarray` (a blocking device->host read per
    accounted batch, found by PR 26's `host_active_ms`)."""

    class DeviceOnly:
        size = 8
        dtype = np.dtype(bool)

        def __array__(self, *a, **k):
            raise AssertionError("batch_bytes pulled a device value")

    data = DeviceOnly()
    data.dtype = np.dtype(np.int64)
    assert batch_bytes(Batch([Column(data, T.BIGINT)], DeviceOnly())) == 72


def test_batch_bytes_includes_dictionary_footprint():
    """Dictionary-coded columns account their dictionary (i32 lookup table
    + validity byte per entry + value bytes), not just the code column —
    the round-3 accounting ignored dictionary storage entirely."""
    from trino_tpu.columnar.dictionary import StringDictionary
    from trino_tpu.runtime.memory import dictionary_bytes

    d = StringDictionary(["ab", "cde", "f"])  # 6 value bytes, 3 entries
    assert dictionary_bytes(d) == 3 * 4 + 3 + 6
    plain = Batch(
        [Column(np.zeros(4, np.int32), T.VARCHAR, np.ones(4, bool))],
        np.ones(4, bool),
    )
    coded = Batch(
        [Column(np.zeros(4, np.int32), T.VARCHAR, np.ones(4, bool), d)],
        np.ones(4, bool),
    )
    assert batch_bytes(coded) == batch_bytes(plain) + dictionary_bytes(d)


# -- wired into the query path (round-3: operators reserve through the pool,
# join builds overflow into partition waves) ---------------------------------


def _mem_runner(limit_bytes: int):
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=2)
    r.properties.set("query_max_memory_bytes", limit_bytes)
    return r


JOIN_SQL = (
    "select o_orderpriority, count(*) c from orders join lineitem "
    "on o_orderkey = l_orderkey group by o_orderpriority"
)

OUTER_JOIN_SQL = (
    "select count(*), count(l_orderkey) from orders left join "
    "(select l_orderkey from lineitem where l_quantity > 45) t "
    "on o_orderkey = l_orderkey"
)


def test_wave_join_exact_under_budget():
    """A join whose build side exceeds the budget falls back to hash-
    partitioned waves and still returns exact results (the spill analog)."""
    unlimited = _mem_runner(0).execute(JOIN_SQL)
    # ~60k lineitem rows * several columns >> 200 KB: forces several waves
    limited = _mem_runner(200_000).execute(JOIN_SQL)
    assert sorted(limited.rows) == sorted(unlimited.rows)


def test_wave_left_join_exact():
    unlimited = _mem_runner(0).execute(OUTER_JOIN_SQL)
    limited = _mem_runner(300_000).execute(OUTER_JOIN_SQL)
    assert limited.rows == unlimited.rows


def test_query_memory_limit_observed():
    """SET SESSION query_max_memory_bytes is actually read: a tiny budget
    forces the wave path rather than being silently ignored (before round 3
    the property existed but nothing read it)."""
    r = _mem_runner(50_000)
    res = r.execute(JOIN_SQL)
    assert res.row_count == 5


def test_agg_fold_batches_read():
    r = _mem_runner(0)
    r.properties.set("agg_fold_batches", 1)
    res = r.execute(
        "select l_returnflag, count(*) from lineitem group by l_returnflag"
    )
    assert res.row_count == 3


@pytest.mark.smoke
def test_external_sort_spills_and_matches():
    """ORDER BY over budget falls back to an external sort: device-sorted
    runs spill to host RAM and merge at finish (round-3 gap: sort had no
    memory fallback)."""
    import trino_tpu.ops.sort as S
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=6)
    sql = "select l_orderkey, l_comment from lineitem order by l_comment, l_orderkey"
    base = r.execute(sql).rows

    spills = []
    orig = S.OrderByOperator._spill_chunk

    def counting(self):
        spills.append(1)
        return orig(self)

    S.OrderByOperator._spill_chunk = counting
    try:
        r.properties.set("query_max_memory_bytes", 300_000)
        spilled = r.execute(sql).rows
    finally:
        S.OrderByOperator._spill_chunk = orig
    assert len(spills) >= 2  # the budget genuinely forced runs
    assert spilled == base


@pytest.mark.smoke
def test_window_waves_exact_under_budget():
    """Windows over budget execute in partition-disjoint hash waves
    (round-3 gap: window had no memory fallback)."""
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=4)
    sql = (
        "select o_custkey, o_orderkey, "
        "row_number() over (partition by o_custkey "
        "  order by o_orderdate, o_orderkey) rn, "
        "sum(o_totalprice) over (partition by o_custkey "
        "  order by o_orderdate, o_orderkey) s from orders"
    )
    base = sorted(r.execute(sql).rows)
    r.properties.set("query_max_memory_bytes", 400_000)
    assert sorted(r.execute(sql).rows) == base


@pytest.mark.smoke
def test_external_sort_array_columns():
    """Array channels survive a spilled sort (per-run widths unify, lengths
    ride the merge permutation).  Tie order is not asserted — ORDER BY on a
    non-unique key permits any tie order."""
    from trino_tpu.runtime.runner import LocalQueryRunner

    r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=6)
    sql = (
        "select o_totalprice, o_orderkey, "
        "array[o_custkey, o_shippriority] a from orders order by o_totalprice"
    )
    base = r.execute(sql).rows
    r.properties.set("query_max_memory_bytes", 260_000)
    spilled = r.execute(sql).rows
    assert sorted(map(repr, base)) == sorted(map(repr, spilled))
    keys = [row[0] for row in spilled]
    assert all(a <= b for a, b in zip(keys, keys[1:]))
