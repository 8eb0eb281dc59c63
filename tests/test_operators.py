"""Operator tests against the pandas oracle (reference style:
operator/TestHashAggregationOperator.java etc. with RowPagesBuilder input)."""

import datetime
from decimal import Decimal

import numpy as np
import pandas as pd
import pytest

pytestmark = pytest.mark.smoke

from trino_tpu import types as T
from trino_tpu.columnar import batch_from_rows
from trino_tpu.connectors.api import TableHandle
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.expr import InputRef, Literal, Call
from trino_tpu.expr.ir import and_, comparison
from trino_tpu.ops.aggregation import AggregationOperator, AggSpec
from trino_tpu.ops.filter_project import FilterProjectOperator
from trino_tpu.ops.scan import ScanOperator
from trino_tpu.ops.sort import LimitOperator, OrderByOperator, TopNOperator
from trino_tpu.ops.common import SortKey
from trino_tpu.runtime.driver import Driver
from trino_tpu.testing import tpch_pandas

DEC = T.DecimalType(12, 2)


def _batches(types, rows, chunk=3):
    """Yield device batches in chunks (tests multi-batch streaming)."""
    out = []
    for i in range(0, len(rows), chunk):
        out.append(batch_from_rows(types, rows[i : i + chunk]).device_put())
    return out


def test_grouped_agg_vs_pandas():
    rows = [
        ["a", 1, 10.0], ["b", 2, None], ["a", 3, 30.0], ["c", None, 5.0],
        ["b", 5, 50.0], ["a", None, None], ["c", 7, 70.0], ["a", 8, 80.0],
    ]
    types = [T.VARCHAR, T.BIGINT, T.DOUBLE]
    op = AggregationOperator(
        [0],
        [
            AggSpec("count_star", None, T.BIGINT),
            AggSpec("sum", 1, T.BIGINT),
            AggSpec("avg", 2, T.DOUBLE),
            AggSpec("min", 1, T.BIGINT),
            AggSpec("max", 2, T.DOUBLE),
            AggSpec("count", 1, T.BIGINT),
        ],
        types,
    )
    got = Driver(_batches(types, rows), [op]).rows()
    got.sort(key=lambda r: r[0])
    df = pd.DataFrame(rows, columns=["k", "x", "y"])
    exp = (
        df.groupby("k")
        .agg(
            n=("k", "size"), sx=("x", "sum"), ay=("y", "mean"),
            mn=("x", "min"), mx=("y", "max"), cx=("x", "count"),
        )
        .reset_index()
        .sort_values("k")
    )
    for g, e in zip(got, exp.itertuples(index=False)):
        assert g[0] == e.k and g[1] == e.n
        assert g[2] == (None if pd.isna(e.sx) else int(e.sx))
        assert g[3] == pytest.approx(e.ay) if not pd.isna(e.ay) else g[3] is None
        assert g[4] == (None if pd.isna(e.mn) else int(e.mn))
        assert g[5] == (pytest.approx(e.mx) if not pd.isna(e.mx) else None)
        assert g[6] == e.cx


def test_streaming_agg_matches_materialized():
    rows = [[i % 4, i] for i in range(50)]
    types = [T.BIGINT, T.BIGINT]
    aggs = [AggSpec("sum", 1, T.BIGINT), AggSpec("avg", 1, T.DOUBLE),
            AggSpec("count_star", None, T.BIGINT)]
    a = Driver(_batches(types, rows, chunk=7),
               [AggregationOperator([0], aggs, types, streaming=True)]).rows()
    b = Driver(_batches(types, rows, chunk=7),
               [AggregationOperator([0], aggs, types, streaming=False)]).rows()
    assert sorted(a) == sorted(b)


def test_global_agg_empty_input():
    types = [T.BIGINT]
    op = AggregationOperator([], [AggSpec("count_star", None, T.BIGINT),
                                  AggSpec("sum", 0, T.BIGINT)], types)
    got = Driver(iter(()), [op]).rows()
    assert got == [[0, None]]


def test_partial_final_roundtrip():
    rows = [[i % 3, i * 10] for i in range(30)]
    types = [T.BIGINT, T.BIGINT]
    aggs = [AggSpec("avg", 1, T.DOUBLE), AggSpec("count", 1, T.BIGINT)]
    partial = AggregationOperator([0], aggs, types, mode="partial")
    pbatches = list(Driver(_batches(types, rows, chunk=9), [partial]).run())
    state_types = [c.type for c in pbatches[0].columns]
    # final agg over states: args point at state channel offsets
    final = AggregationOperator(
        [0],
        [AggSpec("avg", 1, T.DOUBLE), AggSpec("count", 3, T.BIGINT)],
        state_types,
        mode="final",
    )
    got = Driver(iter(pbatches), [final]).rows()
    single = Driver(
        _batches(types, rows, chunk=9), [AggregationOperator([0], aggs, types)]
    ).rows()
    assert sorted(got) == sorted(single)


def test_orderby_topn_limit():
    rows = [[i, (i * 37) % 11, None if i % 5 == 0 else i % 3] for i in range(20)]
    types = [T.BIGINT, T.BIGINT, T.BIGINT]
    keys = [SortKey(2, ascending=True), SortKey(1, ascending=False)]
    got = Driver(_batches(types, rows, chunk=6), [OrderByOperator(keys)]).rows()
    df = pd.DataFrame(rows, columns=["i", "a", "b"])
    exp = df.sort_values(["b", "a"], ascending=[True, False],
                         na_position="last", kind="stable")
    assert [r[0] for r in got] == exp["i"].tolist()
    # TopN == first 5 of full sort
    topn = Driver(_batches(types, rows, chunk=6), [TopNOperator(keys, 5)]).rows()
    assert [r[0] for r in topn] == exp["i"].tolist()[:5]
    # limit
    lim = Driver(_batches(types, rows, chunk=6), [LimitOperator(7)]).rows()
    assert len(lim) == 7 and [r[0] for r in lim] == [r[0] for r in rows[:7]]


def test_scan_filter_agg_q6_tiny():
    """TPC-H Q6 as a hand-built pipeline (reference: HandTpchQuery6.java)."""
    conn = TpchConnector()
    h = TableHandle("tpch", "tiny", "lineitem")
    meta = conn.metadata().table_metadata("tiny", "lineitem")
    cols = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    types = [meta.column(c).type for c in cols]
    d0 = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    d1 = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    ship, disc, qty, price = (InputRef(i, t) for i, t in enumerate(types))
    pred = and_(
        comparison(">=", ship, Literal(d0, T.DATE)),
        comparison("<", ship, Literal(d1, T.DATE)),
        comparison(">=", disc, Literal(Decimal("0.05"), DEC)),
        comparison("<=", disc, Literal(Decimal("0.07"), DEC)),
        comparison("<", qty, Literal(24, DEC)),
    )
    proj = [Call("$mul", [price, disc], T.DecimalType(18, 4))]

    def source():
        for split in conn.splits(h, target_splits=3):
            yield from ScanOperator(conn, split, cols, types).batches()

    ops = [
        FilterProjectOperator(pred, proj),
        AggregationOperator([], [AggSpec("sum", 0, T.DecimalType(18, 4))],
                            [T.DecimalType(18, 4)], streaming=True),
    ]
    got = Driver(source(), ops).rows()

    li = tpch_pandas("tiny", "lineitem")
    m = (
        (li["l_shipdate"].values.astype("datetime64[D]")
         >= np.datetime64("1994-01-01"))
        & (li["l_shipdate"].values.astype("datetime64[D]")
           < np.datetime64("1995-01-01"))
        & (li["l_discount__cents"] >= 5) & (li["l_discount__cents"] <= 7)
        & (li["l_quantity__cents"] < 2400)
    )
    exp_units = int((li["l_extendedprice__cents"][m] * li["l_discount__cents"][m]).sum())
    assert got[0][0] == Decimal(exp_units).scaleb(-4)


def test_desc_sort_int64_min_and_nan():
    rows = [[-(2**63), 1.5], [0, float("nan")], [5, -2.0]]
    types = [T.BIGINT, T.DOUBLE]
    got = Driver(_batches(types, rows, chunk=3),
                 [OrderByOperator([SortKey(0, ascending=False)])]).rows()
    assert [r[0] for r in got] == [5, 0, -(2**63)]
    # NaN sorts largest: first under DESC, last under ASC
    got = Driver(_batches(types, rows, chunk=3),
                 [OrderByOperator([SortKey(1, ascending=False)])]).rows()
    assert np.isnan(got[0][1])
    got = Driver(_batches(types, rows, chunk=3),
                 [OrderByOperator([SortKey(1, ascending=True)])]).rows()
    assert np.isnan(got[-1][1])


def test_integer_sum_widens():
    rows = [[0, 2_000_000_000], [0, 2_000_000_000]]
    types = [T.BIGINT, T.INTEGER]
    got = Driver(_batches(types, rows),
                 [AggregationOperator([0], [AggSpec("sum", 1, T.BIGINT)], types)]).rows()
    assert got == [[0, 4_000_000_000]]


def test_any_value_skips_nulls():
    rows = [["a", None], ["a", 42], ["b", 7]]
    types = [T.VARCHAR, T.BIGINT]
    got = Driver(_batches(types, rows, chunk=3),
                 [AggregationOperator([0], [AggSpec("any_value", 1, T.BIGINT)], types)]).rows()
    assert sorted(got) == [["a", 42], ["b", 7]]


def test_streaming_folds_state():
    rows = [[i % 3, i] for i in range(100)]
    types = [T.BIGINT, T.BIGINT]
    op = AggregationOperator([0], [AggSpec("sum", 1, T.BIGINT)], types, streaming=True)
    got = Driver(_batches(types, rows, chunk=5), [op]).rows()  # 20 batches > FOLD_EVERY
    df = pd.DataFrame(rows, columns=["k", "x"]).groupby("k")["x"].sum()
    assert sorted(got) == [[k, int(v)] for k, v in df.items()]


# -- the range-positional path over many groups (`_range_step`, forms `runs`
# and `sorted_runs`): a per-batch key domain above DENSE_SEGMENT_LIMIT slots


def _range_feed(keys, shuffled: bool, nullable: bool, rows_per_batch=3000, nbatches=10):
    """(types, device batches): batch b holds keys of [b * rows, (b + 1) *
    rows) — two rows a key, every 11th value NULL, and every 7th first key
    NULL where `nullable` — clustered and ascending, NULLS LAST, or shuffled
    within the batch with the batches out of order too (so the fold's
    concatenation of per-batch states is out of order as well)."""
    rng = np.random.default_rng(33 + keys)
    types = [T.BIGINT] * keys + [T.BIGINT, DEC]
    batches = []
    for b in range(nbatches):
        rows = []
        for i in range(rows_per_batch):
            k = b * rows_per_batch // 2 + i // 2
            key = [None if nullable and k % 7 == 0 else 1000 + k]
            if keys == 2:
                key.append(i % 2)
            v = int(rng.integers(-500, 500))
            rows.append(
                key + [None if i % 11 == 0 else v, Decimal(v) / 100]
            )
        if shuffled:
            rows = [rows[j] for j in rng.permutation(len(rows))]
        else:
            rows.sort(key=lambda r: r[0] is None)  # stable: NULL keys last
        batches.append(batch_from_rows(types, rows).device_put())
    if shuffled:
        batches = [batches[j] for j in rng.permutation(nbatches)]
    return types, batches


def _range_specs(keys):
    return [
        AggSpec("sum", keys, T.BIGINT),
        AggSpec("count", keys, T.BIGINT),
        AggSpec("min", keys, T.BIGINT),
        AggSpec("max", keys, T.BIGINT),
        AggSpec("sum", keys + 1, T.DecimalType(38, 2)),
        AggSpec("count_star", None, T.BIGINT),
    ]


def _partial_merge_final(types, batches, keys):
    """Rows of partial (streaming, folds after FOLD_EVERY batches) ->
    merge -> final, and the `agg_range` forms the steps took."""
    from trino_tpu.ops.aggregation import _STEP_CACHE, _primitives

    _STEP_CACHE.clear()
    specs = _range_specs(keys)
    partial = AggregationOperator(
        list(range(keys)), specs, types, mode="partial", streaming=True
    )
    states = list(Driver(iter(batches), [partial]).run())
    state_types = [c.type for c in states[0].columns]
    state_specs, ch = [], keys
    for s in specs:
        state_specs.append(AggSpec(s.name, ch, s.out_type))
        ch += len(_primitives(s))
    merge = AggregationOperator(
        list(range(keys)), state_specs, state_types, mode="merge"
    )
    merged = list(Driver(iter(states), [merge]).run())
    final = AggregationOperator(
        list(range(keys)), state_specs, state_types, mode="final"
    )
    rows = Driver(iter(merged), [final]).rows()
    forms = {
        part
        for key, program in _STEP_CACHE.items() if key[0] == "range"
        for part in program.path.split("+")
    }
    return sorted(rows, key=lambda r: [(v is None, v) for v in r[:keys]]), forms


@pytest.mark.parametrize("keys", [1, 2], ids=["one_key", "two_keys"])
@pytest.mark.parametrize("nullable", [False, True], ids=["not_null", "nullable_key"])
@pytest.mark.parametrize("shuffled", [False, True], ids=["clustered", "shuffled"])
def test_range_positional_many_groups_matches_sort_path(
    monkeypatch, shuffled, nullable, keys
):
    types, batches = _range_feed(keys, shuffled, nullable)
    got, forms = _partial_merge_final(types, batches, keys)
    assert "positional" in forms and "scatter" not in forms, forms
    assert ("sorted_runs" if shuffled else "runs") in forms, forms
    if not (shuffled or nullable):
        # (a batch's NULL group sorts last, so the fold's concatenation of
        # a nullable key's states is out of order and is sorted, rightly)
        assert "sorted_runs" not in forms, forms
    monkeypatch.setattr(AggregationOperator, "_positional_try", lambda self, b: None)
    want, sort_forms = _partial_merge_final(types, batches, keys)
    assert not sort_forms
    assert len(got) > 10000
    assert got == want
