"""TPC-DS connector + query tests (reference style: TestTpcdsMetadata +
tpcds query smoke suites)."""

import numpy as np
import pytest


from trino_tpu.connectors.tpcds import TpcdsConnector
from trino_tpu.connectors.tpcds.queries import QUERIES
from trino_tpu.connectors.tpcds.schema import TABLES
from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.testing import connector_table_to_pandas


@pytest.fixture(scope="module", autouse=True)
def _fresh_caches():
    """The TPC-DS module compiles hundreds of fragment kernels; entering it
    with the whole suite's accumulated executables has hit allocator-level
    XLA crashes late in the run.  Start from a clean compile cache and an
    empty buffer pool (everything recompiles on demand).

    (The periodic purge below also keeps the allocator fresh enough that the
    persistent-cache writer — which segfaulted when hundreds of executables
    had accumulated — stays safe, and purged kernels RELOAD from disk
    instead of recompiling.)"""
    import jax

    from trino_tpu.runtime.buffer_pool import POOL

    jax.clear_caches()
    POOL.clear()
    yield
    jax.clear_caches()
    POOL.clear()


_TEST_TICK = {"n": 0}


@pytest.fixture(autouse=True)
def _periodic_executable_purge():
    """The allocator corruption above is reached WITHIN this module too
    (XLA:CPU segfaults compiling around the ~45th query with hundreds of
    live executables).  Purge every few tests; queries recompile their own
    kernels, correctness is unaffected."""
    yield
    _TEST_TICK["n"] += 1
    if _TEST_TICK["n"] % 10 == 0:
        import jax

        from trino_tpu.runtime.buffer_pool import POOL

        jax.clear_caches()
        POOL.clear()


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner(catalog="tpcds", schema="tiny", target_splits=2)


def test_all_tables_scan(runner):
    for table in sorted(TABLES):
        res = runner.execute(f"select count(*) from {table}")
        assert res.rows[0][0] > 0, table


def test_schema_columns(runner):
    cols = dict(runner.execute("describe item").rows)
    assert cols["i_item_sk"] == "bigint"
    assert cols["i_current_price"] == "decimal(7,2)"
    assert len(cols) == 22


def test_calendar_dimension(runner):
    rows = runner.execute(
        "select min(d_year), max(d_year), count(*) from date_dim"
    ).rows
    assert rows == [(1900, 2099, 73049)]
    # d_date_sk is a julian day number aligned with d_date
    rows = runner.execute(
        "select count(*) from date_dim where d_year = 2000 and d_moy = 2"
    ).rows
    assert rows == [(29,)]  # Feb 2000 (leap)


def test_fact_dimension_fk(runner):
    joined = runner.execute(
        "select count(*), min(d_year), max(d_year) "
        "from store_sales, date_dim where ss_sold_date_sk = d_date_sk"
    ).rows
    n, lo, hi = joined[0]
    assert n > 25_000 and lo >= 1998 and hi <= 2003


def test_returns_link_to_sales(runner):
    # every store_returns row copies its parent sale's (item, ticket) keys,
    # so the sales<->returns join matches every return row at least once
    total = runner.execute("select count(*) from store_returns").rows[0][0]
    joined = runner.execute(
        "select count(*) from store_sales, store_returns "
        "where ss_item_sk = sr_item_sk and ss_ticket_number = sr_ticket_number"
    ).rows[0][0]
    # ~4% of fact FKs are NULL (spec-shaped), so a small fraction of return
    # rows carry a NULL item key and cannot join
    assert total > 0 and joined >= 0.9 * total


def test_demographics_crossproduct(runner):
    rows = runner.execute("select count(*) from customer_demographics").rows
    assert rows == [(1_920_800,)]
    g = runner.execute(
        "select count(distinct cd_gender) from customer_demographics"
    ).rows
    assert g == [(2,)]


def _norm(v):
    import datetime
    import decimal
    import math

    if isinstance(v, decimal.Decimal):
        return float(v)
    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()
    if isinstance(v, float) and math.isnan(v):
        return None
    return v


def _approx(a, b, atol=0.02):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        try:
            fa, fb = float(a), float(b)
        except (TypeError, ValueError):
            return False
        return abs(fa - fb) <= atol + 1e-6 * max(abs(fa), abs(fb))
    return a == b


def assert_same_rows(actual, expected):
    actual = [tuple(_norm(v) for v in r) for r in actual]
    expected = [tuple(_norm(v) for v in r) for r in expected]
    assert len(actual) == len(expected), (
        f"row count {len(actual)} != {len(expected)}\n"
        f"actual[:3]={actual[:3]}\nexpected[:3]={expected[:3]}"
    )
    key = lambda r: tuple("\0" if v is None else str(v) for v in r)
    for i, (ra, re_) in enumerate(
        zip(sorted(actual, key=key), sorted(expected, key=key))
    ):
        assert len(ra) == len(re_), f"row {i} width"
        for j, (va, ve) in enumerate(zip(ra, re_)):
            assert _approx(va, ve), (
                f"row {i} col {j}: {va!r} != {ve!r}\n{ra}\n{re_}"
            )


#: engine gaps, one per query (tests/KNOWN_FAILURES.md): strict, so the
#: case fails the day the gap closes and the entry has to go
KNOWN_GAPS = {
    51: pytest.mark.xfail(
        strict=True,
        raises=NotImplementedError,
        reason="window min/max over a long-decimal input column "
        "(trino_tpu/ops/window.py)",
    ),
}


@pytest.mark.parametrize(
    "qid",
    [
        pytest.param(q, marks=KNOWN_GAPS[q]) if q in KNOWN_GAPS else q
        for q in sorted(QUERIES)
    ],
)
def test_tpcds_query_vs_oracle(runner, qid):
    """Every workload query executes end-to-end AND matches the independent
    sqlite3 oracle (reference style: H2QueryRunner assertQuery).

    ROLLUP queries (sqlite has no grouping sets) check through a chain:
    engine(rollup) == engine(union-expansion) == sqlite(union-expansion) —
    see tests/tpcds_rollup_equiv.py."""
    from tests.tpcds_oracle import run_sqlite
    from tests.tpcds_rollup_equiv import EQUIV

    engine = runner.execute(QUERIES[qid])
    if qid in EQUIV:
        expanded = runner.execute(EQUIV[qid])
        assert_same_rows(engine.rows, expanded.rows)
        oracle = run_sqlite(EQUIV[qid])
        assert_same_rows(expanded.rows, oracle)
    else:
        oracle = run_sqlite(QUERIES[qid])
        assert_same_rows(engine.rows, oracle)


def test_q96_matches_pandas(runner):
    conn = runner.catalogs.get("tpcds")
    t = lambda name: connector_table_to_pandas(conn, "tiny", name)
    ss, hd, td, s = t("store_sales"), t("household_demographics"), t("time_dim"), t("store")
    j = (
        ss.merge(td, left_on="ss_sold_time_sk", right_on="t_time_sk")
        .merge(hd, left_on="ss_hdemo_sk", right_on="hd_demo_sk")
        .merge(s, left_on="ss_store_sk", right_on="s_store_sk")
    )
    j = j[(j.t_hour == 20) & (j.t_minute >= 30) & (j.hd_dep_count == 7) & (j.s_store_name == "ese")]
    expected = len(j)
    got = runner.execute(QUERIES[96]).rows[0][0]
    assert got == expected
