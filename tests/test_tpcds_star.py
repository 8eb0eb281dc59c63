"""The `tpcds_sf1` configuration of the benchmark, at `tpcds.tiny` on the CPU:
the engine against the benchmark's plain reference for the four store-channel
statements, the benchmark's copy of the data arithmetic against the
connector, NULL join keys, the `join` span, a ROLLUP's input run once, the
controls, and the CPU rehearsal of the cell this configuration came with.

One module-scoped runner serves every engine test, and the rehearsal of
`star_report` runs the same seed's statements again, so their programs are
compiled once for the module.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import control, rehearse  # noqa: E402
from benchmark.datagen import tpcds as gen  # noqa: E402
from benchmark.harness import compare, spec, traffic  # noqa: E402
from benchmark.reference import tpcds as ref  # noqa: E402

CELL = "tpcds_sf1.star_report"
QUERIES = ("q3", "q7", "q27", "q89")
#: above 2**31, as the driver's seeds are; it draws q27's state as 'AL',
#: the one of the cell's two that has a store at `tiny` too
SEED = 3000000031


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.runtime.runner import LocalQueryRunner

    return LocalQueryRunner(catalog="tpcds", schema="tiny", target_splits=8)


@pytest.fixture(scope="module")
def suite():
    return ref.Suite("tiny")


@pytest.fixture(scope="module")
def statements():
    """The seed's statements, as the cell's mix draws them."""
    mix = traffic.Mix(spec.Cell(CELL).traffic, SEED)
    return {st.query: st for st in mix.warmup()}


def _spans(runner, name):
    _, flat = runner.traces[-1]
    return [
        json.loads(s["attributes"]) for s in flat if s["name"] == name
    ]


# -- the engine against the plain reference ----------------------------------


@pytest.mark.parametrize("query", QUERIES)
def test_engine_equals_reference(runner, suite, statements, query):
    st = statements[query]
    (answer,) = suite.answers([(st.query, st.params)])
    result = runner.execute(st.sql)
    assert compare.wrong(result.rows, answer) == "", st.params
    assert answer["ordered"] and len(answer["rows"]) <= answer["limit"]
    # one `join` span per join operator: a ROLLUP of three levels plans its
    # input three times, and the local runner runs it once
    joins = {"q3": 2, "q7": 4, "q27": 4, "q89": 3}[query]
    spans = _spans(runner, "join")
    assert len(spans) == joins
    for a in spans:
        if a["kind"] == "cross":  # q89: date_dim x store, one build of two
            assert a["strategy"] == "join_nested_expand" and query == "q89"
            continue
        assert a["kind"] == "inner"
        assert a["strategy"] in ("join_locate_table", "join_locate_sorted")
        # a key is unique on one side (at `tiny` the planner may probe
        # with the dimension, its rows cut to the fact side's key set)
        assert a["out_rows"] <= max(a["probe_rows"], a["build_rows"])
        assert a["null_keys"] <= a["probe_rows"] and a["build_rows"] >= 0


def test_q27_answers_from_no_rows(runner, suite, statements):
    """A state no store has: ROLLUP's grand total is still a row."""
    st = statements["q27"]
    params = {**st.params, "state": "GA"}
    sql = st.sql.replace(f"'{st.params['state']}'", "'GA'")
    (answer,) = suite.answers([("q27", params)])
    assert answer["rows"] == [(None, None, 1, None, None, None, None)]
    assert compare.wrong(runner.execute(sql).rows, answer) == ""


# -- a join tree the plan holds more than once runs once ----------------------


def _scan(columns, where=None):
    from trino_tpu import types as T
    from trino_tpu.connectors.api import TableHandle
    from trino_tpu.expr.ir import Literal, comparison
    from trino_tpu.planner import plan as P

    syms = {c: P.Symbol(c, T.BIGINT) for c in ("a", "b", "k")}
    pred = None
    if where is not None:
        pred = comparison("=", syms["a"].ref(), Literal(where, T.BIGINT))
    return P.TableScanNode(
        TableHandle("tpcds", "tiny", "t"), None,
        [(syms[c], c) for c in columns], pred,
    )


def _join(left, right, kind="inner"):
    from trino_tpu import types as T
    from trino_tpu.planner import plan as P

    k = P.Symbol("k", T.BIGINT)
    return P.JoinNode(kind, left, right, [(k, k)])


@pytest.mark.parametrize("wide, narrow, covered", [
    (("a", "b", "k"), ("a", "k"), True),    # a pruned copy
    (("a", "k"), ("a", "b", "k"), False),   # a column the other lacks
    (("a", "k"), ("a", "k"), True),
])
def test_a_scan_covers_its_pruned_copy(wide, narrow, covered):
    from trino_tpu.runtime.shared_input import covers

    assert covers(_scan(wide, 1), _scan(narrow, 1)) is covered
    assert not covers(_scan(wide, 1), _scan(narrow, 2))  # another predicate
    assert not covers(_scan(wide, 1), _scan(narrow))
    assert covers(
        _join(_scan(wide), _scan(("k",), 3)),
        _join(_scan(narrow), _scan(("k",), 3)),
    ) is covered


def test_only_alike_join_trees_are_grouped():
    from trino_tpu.planner import plan as P
    from trino_tpu.runtime.shared_input import covers, repeated_inputs

    wide = _join(_scan(("a", "b", "k")), _scan(("k",), 3))
    narrow = _join(_scan(("a", "k")), _scan(("k",), 3))
    other = _join(_scan(("a", "k")), _scan(("k",), 4))
    left = _join(_scan(("a", "k")), _scan(("k",), 3), kind="left")
    assert not covers(wide, other) and not covers(narrow, left)
    sample = P.SampleNode(_scan(("a", "k")), 0.5)
    assert not covers(sample, sample)
    out = [P.Symbol("a", wide.outputs[0].type)]
    union = P.UnionNode(
        [narrow, other, wide, left], out, [out, out, out, out]
    )
    assert repeated_inputs(union) == [[wide, narrow]]
    assert repeated_inputs(P.UnionNode([narrow, other], out, [out, out])) == []


_ROLLUP = (
    "select i_manufact_id, sum(ss_quantity), grouping(i_manufact_id) "
    "from store_sales, item where ss_item_sk = i_item_sk "
    "group by rollup(i_manufact_id) order by 3, 1"
)


def _planned_rows(runner, plan, share, outer_filters):
    """`plan`'s rows from a planner that holds `outer_filters`, as one
    planning below a join whose build it has read would."""
    from trino_tpu.runtime.local_planner import LocalExecutionPlanner

    lp = LocalExecutionPlanner(runner.catalogs, properties=runner.properties)
    lp.dynamic_filters.update(outer_filters)
    if share:
        lp.share_repeated_inputs(plan)
    return [
        tuple(r) for b in lp.plan(plan).stream for r in b.to_pylist()
    ]


def test_a_filter_from_above_one_reader_spares_the_shared_input(runner):
    """A dynamic filter that a join above registered is about that join's
    probe side; the one stream every reader shares is planned without it
    (a copy planned for itself picks it up, as it always did)."""
    rows = runner.execute(_ROLLUP).rows
    assert len(_spans(runner, "join")) == 1 and rows[-1][2] == 1
    plan = runner.create_plan(_ROLLUP)
    none_left = {"ss_quantity": (-2, -1)}
    assert _planned_rows(runner, plan, True, none_left) == rows
    assert _planned_rows(runner, plan, False, none_left) == [(None, None, 1)]
    assert _planned_rows(runner, plan, False, {}) == rows


def test_an_input_too_large_to_keep_is_run_by_each_reader(
    runner, statements, monkeypatch
):
    from trino_tpu.runtime import shared_input

    sql = statements["q27"].sql
    rows = runner.execute(sql).rows
    assert len(_spans(runner, "join")) == 4
    monkeypatch.setattr(shared_input, "SHARED_INPUT_LIMIT", 0)
    assert runner.execute(sql).rows == rows
    assert len(_spans(runner, "join")) == 12


def test_nothing_is_shared_under_a_memory_budget(runner, statements):
    from trino_tpu.runtime.local_planner import LocalExecutionPlanner

    plan = runner.create_plan(statements["q27"].sql)
    lp = LocalExecutionPlanner(runner.catalogs, properties=runner.properties)
    lp.share_repeated_inputs(plan)
    assert len(lp._shared) == 3 and len(set(map(id, lp._shared.values()))) == 1
    runner.properties.set("query_max_memory_bytes", 1_000_000)
    try:
        lp = LocalExecutionPlanner(
            runner.catalogs, properties=runner.properties
        )
        lp.share_repeated_inputs(plan)
    finally:
        runner.properties.set("query_max_memory_bytes", 0)
    assert lp._shared == {}


# -- NULL join keys ----------------------------------------------------------


def test_null_probe_keys_join_nothing(runner):
    """A fact row whose key is NULL joins no dimension row.  An inner join
    never sees most of them (the build's key domain, pushed into the probe
    scan as a dynamic filter, drops a NULL first); an outer join does, and
    its `join` span counts them, in its locate program."""
    from trino_tpu.telemetry.metrics import join_null_keys_counter

    fact = gen.Tpcds("tiny").store_sales(["ss_item_sk", "ss_store_sk"])
    valid = fact["ss_item_sk.valid"]
    nulls = int((~valid).sum())
    assert 0 < nulls < len(valid) // 20
    result = runner.execute(
        "select count(*), count(ss_store_sk) from store_sales, item "
        "where ss_item_sk = i_item_sk"
    )
    both = valid & fact["ss_store_sk.valid"]
    assert result.rows == [(int(valid.sum()), int(both.sum()))]
    (span,) = _spans(runner, "join")
    assert span["kind"] == "inner"
    assert span["probe_rows"] + span["null_keys"] <= len(valid)
    assert span["out_rows"] == int(valid.sum())
    assert span["build_rows"] == gen.Tpcds("tiny").rows["item"]
    before = join_null_keys_counter().value()
    result = runner.execute(
        "select count(*), count(i_item_sk) from store_sales "
        "left join item on ss_item_sk = i_item_sk"
    )
    assert result.rows == [(len(valid), int(valid.sum()))]
    (span,) = _spans(runner, "join")
    assert span["kind"] == "left" and span["null_keys"] == nulls
    assert span["probe_rows"] == span["out_rows"] == len(valid)
    assert join_null_keys_counter().value() - before == nulls


def test_join_span_holds_the_operators_launches_and_pulls(runner):
    """On the statement's thread the operator's own launches and host pulls
    nest under its `join` span, which lies inside `execute`."""
    runner.execute(
        "select count(*) from store_sales, item where ss_item_sk = i_item_sk"
    )
    _, flat = runner.traces[-1]
    by_id = {s["span_id"]: s for s in flat}
    (join,) = [s for s in flat if s["name"] == "join"]
    execute = by_id[join["parent_id"]]
    assert execute["name"] == "execute"
    assert join["start_ms"] >= execute["start_ms"]
    assert (join["start_ms"] + join["duration_ms"]
            <= execute["start_ms"] + execute["duration_ms"] + 1e-3)
    under = [s for s in flat if s["parent_id"] == join["span_id"]]
    steps = {
        json.loads(s["attributes"])["step"]
        for s in under if s["name"] == "launch"
    }
    assert {"join_expand_unique"} <= steps
    assert steps & {"join_locate_table", "join_locate_sorted"}
    whys = [
        json.loads(s["attributes"])["why"]
        for s in under if s["name"] == "host_pull"
    ]
    assert whys and set(whys) == {"capacity"}
    # the build side's work lies before the span, under `build`
    assert join["duration_ms"] < execute["duration_ms"]


# -- dynamic filters: a small build prunes the probe by its key SET -----------


def test_few_build_keys_prune_the_probe_scan_as_a_set(runner):
    """An item filter leaves a handful of keys scattered over the whole key
    range: as a range they would let nearly every fact row through, as a
    set only the rows that join (q3's build, and q27's stores 1 and 11)."""
    t = gen.Tpcds("tiny")
    item = t.item()
    keys = item["i_item_sk"][item["i_manufact_id"] < 10]
    assert 1 < len(keys) <= 64 and keys.max() - keys.min() >= len(keys)
    fact = t.store_sales(["ss_item_sk"])
    joins = fact["ss_item_sk.valid"] & np.isin(fact["ss_item_sk"], keys)
    in_range = fact["ss_item_sk.valid"] & (
        (fact["ss_item_sk"] >= keys.min()) & (fact["ss_item_sk"] <= keys.max())
    )
    assert 0 < joins.sum() < in_range.sum() // 10
    result = runner.execute(
        "select count(*) from store_sales, item "
        "where ss_item_sk = i_item_sk and i_manufact_id < 10"
    )
    assert result.rows == [(int(joins.sum()),)]
    (span,) = _spans(runner, "join")
    assert span["build_rows"] == len(keys)
    assert span["probe_rows"] == span["out_rows"] == int(joins.sum())


@pytest.mark.parametrize("keys, domain", [
    ([1, 11], frozenset({1, 11})),            # holes: the set
    ([5, 3, 4, 4], (3, 5)),                   # dense: the range says as much
    (list(range(0, 130, 2)), (0, 128)),       # over the limit: the range
    ([], None),
])
def test_build_key_domain(keys, domain):
    from trino_tpu import types as T
    from trino_tpu.columnar import RowBatchBuilder
    from trino_tpu.runtime import local_planner as lp

    assert lp.DYNAMIC_FILTER_SET_LIMIT == 64
    b = RowBatchBuilder([T.BIGINT]).row(None)
    for k in keys:
        b = b.row(k)
    got = lp._build_key_domain([b.build().device_put()], 0)
    assert got == domain and type(got) is type(domain)


# -- avg(integer): the double nearest the exact quotient ---------------------


@pytest.mark.parametrize("sql, left_to_host", [
    ("select ss_store_sk, avg(ss_quantity) a from store_sales "
     "group by ss_store_sk order by 1 limit 3", True),
    ("select avg(ss_quantity) a, avg(ss_list_price) b from store_sales", True),
    ("select avg(ss_quantity) filter (where ss_quantity > 50) a "
     "from store_sales where ss_quantity < 0", True),
    # whatever sorts by it or computes on it reads a device double, as before
    ("select ss_store_sk, avg(ss_quantity) a from store_sales "
     "group by ss_store_sk order by a limit 3", False),
    ("select ss_store_sk, avg(ss_quantity) + 1 a from store_sales "
     "group by ss_store_sk order by 1", False),
])
def test_avg_of_integers_is_divided_on_the_host(
    runner, sql, left_to_host, monkeypatch
):
    """An avg(integer) that only travels to the client is PLANNED as sum and
    count, two BIGINT columns, and the host divides the rows: the chip's
    float64 is a pair of float32, so a quotient that has been there is not
    the nearest double.  No column is of another form than its type says."""
    from trino_tpu.planner import plan as P
    from trino_tpu.runtime.local_planner import defer_integer_averages
    from trino_tpu.sql import parse_statement

    plan = runner.plan_query(parse_statement(sql).query)
    split, counts = defer_integer_averages(plan)
    assert bool(counts) is left_to_host
    if left_to_host:
        assert len(split.symbols) == len(plan.symbols) + len(counts)
        assert all(
            split.symbols[k].type.name == split.symbols[c].type.name == "bigint"
            for k, c in counts.items()
        )

        def functions(node):
            if isinstance(node, P.AggregationNode):
                yield from (a.function for _, a in node.aggregations)
            for child in node.children:
                yield from functions(child)

        assert "avg" not in [
            f for f in functions(split) if "b from" not in sql
        ] and {"sum", "count"} <= set(functions(split))
    else:
        assert split is plan
    fact = gen.Tpcds("tiny").store_sales(["ss_store_sk", "ss_quantity"])
    store = np.where(fact["ss_store_sk.valid"], fact["ss_store_sk"], 0)
    from trino_tpu.columnar.column import Column

    forms = []
    decode = Column.to_pylist

    def spy(self, row_mask=None):
        forms.append((self.type.name, self.data.ndim, str(self.data.dtype)))
        return decode(self, row_mask)

    monkeypatch.setattr(Column, "to_pylist", spy)
    result = runner.execute(sql)
    assert [t.name for t in result.types][-1] in ("double", "decimal(7,2)")
    assert all(len(row) == len(result.column_names) for row in result.rows)
    # what reaches the host: integers, or a double
    assert all(ndim == 1 for _, ndim, _ in forms)
    assert (("double", 1, "float64") in forms) is not left_to_host
    if "< 0" in sql:
        assert result.rows == [(None,)]  # over no rows: NULL, no 0 / 0
        return
    for row in result.rows:
        if "group by" not in sql:
            of = np.ones(len(store), bool)
        else:
            of = store == (row[0] or 0)
        want = int(fact["ss_quantity"][of].sum()) / int(of.sum())
        assert row[-2 if "b from" in sql else -1] == want + ("+ 1" in sql)


def test_the_cells_averages_are_left_to_the_host(runner, statements):
    from trino_tpu.runtime.local_planner import defer_integer_averages
    from trino_tpu.sql import parse_statement

    counts = {}
    for q in QUERIES:
        plan = runner.plan_query(parse_statement(statements[q].sql).query)
        counts[q] = len(defer_integer_averages(plan)[1])
    # q27's one column crosses the UNION ALL of ROLLUP's levels
    assert counts == {"q3": 0, "q7": 1, "q27": 1, "q89": 0}


def test_a_star_is_exact_with_a_join_spilled(runner):
    """A star of q7's shape (two dimensions, `avg(integer)` and
    `avg(decimal)` by a string key, top 100) under a memory budget that
    sends a join build to partition waves (the spill tier): the same rows
    -- the average's sum and count are plain BIGINT columns to whatever
    moves them -- and the `join` span of the build over the budget says
    `partition_waves`.  (The whole of q7 that way compiles for 100 s.)"""
    sql = (
        "select i_item_id, avg(ss_quantity) a, avg(ss_list_price) b "
        "from store_sales, item, promotion where ss_item_sk = i_item_sk "
        "and ss_promo_sk = p_promo_sk and p_channel_email = 'N' "
        "group by i_item_id order by i_item_id limit 100"
    )
    rows = runner.execute(sql).rows
    assert [a["strategy"] for a in _spans(runner, "join")] == [
        "join_locate_table", "join_locate_table"
    ]
    runner.properties.set("query_max_memory_bytes", 1_000_000)
    try:
        spilled = runner.execute(sql).rows
    finally:
        runner.properties.set("query_max_memory_bytes", 0)
    assert spilled == rows and len(rows) == 100
    assert isinstance(rows[0][1], float)
    strategies = [a["strategy"] for a in _spans(runner, "join")]
    assert sorted(strategies) == ["join_locate_table", "partition_waves"]


# -- the benchmark's copy of the data arithmetic -----------------------------

_STRINGS = {
    "i_item_id": lambda c: gen.Tpcds.item_id(c),
    "i_brand": lambda c: gen.Tpcds.brand(c),
    "i_class": lambda c: gen.CLASSES[c],
    "i_category": lambda c: gen.CATEGORIES[c],
    "cd_gender": lambda c: gen.GENDER[c],
    "cd_marital_status": lambda c: gen.MARITAL[c],
    "cd_education_status": lambda c: gen.EDUCATION[c],
    "p_channel_email": lambda c: "NY"[int(c)],
    "p_channel_event": lambda c: "NY"[int(c)],
    "s_store_name": lambda c: gen.STORE_NAMES[c],
    "s_company_name": lambda c: gen.COMPANY_NAMES[c],
    "s_state": lambda c: gen.STORE_STATES[c],
}


def _copied_columns():
    t = gen.Tpcds("tiny")
    tables = {
        "date_dim": t.date_dim, "item": t.item,
        "customer_demographics": t.customer_demographics,
        "promotion": t.promotion, "store": t.store,
    }
    out = [
        ("store_sales", c)
        for c in tuple(gen.Tpcds.FACT_KEYS) + ("ss_quantity",)
        + gen.Tpcds.FACT_MONEY
    ]
    for table, make in tables.items():
        out += [(table, c) for c in make()]
    return out


@pytest.mark.parametrize("table, column", _copied_columns())
def test_datagen_equals_the_connector(table, column):
    from trino_tpu.connectors.tpcds.generator import generator

    t = gen.Tpcds("tiny")
    g = generator(gen.SCHEMAS["tiny"])
    n = g.row_count(table)
    assert t.rows[table] == n
    # customer_demographics has 1.92 M rows at every scale, its attributes
    # a mixed radix that repeats every 70 rows: the last 2^17 rows
    first = max(0, n - (1 << 17)) if table == "customer_demographics" else 0
    theirs = g.column(table, column, first, n - first)
    values = np.asarray(theirs.values)
    if table == "store_sales":
        mine = t.store_sales([column])
        if column in gen.Tpcds.FACT_KEYS:
            assert (np.asarray(theirs.valid) == mine[column + ".valid"]).all()
            assert not mine[column + ".valid"].all()
        else:
            assert theirs.valid is None
        assert (values == mine[column]).all()
        return
    mine = getattr(t, table)()[column][first:]
    assert theirs.valid is None
    if column not in _STRINGS:
        assert (values == mine).all()
        return
    # a string column: the same text row by row (on the distinct pairs of
    # codes, so that 1.9 M rows cost a handful of look-ups)
    pairs = np.unique(np.stack([values, np.asarray(mine, np.int64)]), axis=1)
    for theirs_code, mine_code in pairs.T:
        assert (
            theirs.dictionary.values[int(theirs_code)]
            == _STRINGS[column](int(mine_code))
        )


def test_config_rows_are_the_generators():
    config = spec.Cell(CELL).config
    assert config["rows"] == gen.Tpcds("sf1").rows
    assert config["schema"] == "sf1" and config["session"] == {}
    for q in QUERIES:
        for table, columns in config["queries"][q]["scans"].items():
            assert table in config["rows"]
            assert set(columns) <= set(config["column_bytes"])
    assert len(config["guarantees"]) == 7


def test_reference_imports_nothing_of_the_program():
    for module in (gen, ref):
        with open(module.__file__) as f:
            assert "trino_tpu" not in f.read().replace(
                "`trino_tpu/connectors/tpcds/generator.py`", ""
            )


# -- the controls ------------------------------------------------------------


def test_controls_come_out_not_correct():
    """`benchmark/control.py` on the cell, at `tiny`: every broken guarantee
    reads not correct.  `float32_sums` cannot touch a sum of a few rows of
    at most 100.00, which is every decimal sum of these statements at
    `tiny` and nearly every one at SF1 (groups of 1 to 50 rows: PERF.md);
    it fails by an `avg(integer)` whose quotient has no float32 -- this
    seed's q27 names a state `tiny` has a store in, so q27 has rows."""
    result = control.control_cell(CELL, SEED, schema="tiny")
    assert set(result["controls"]) == {"none"} | set(ref.FAULTS)
    assert control.verdict(result) == []
    assert result["controls"]["none"]["correct"]
    for fault in ref.FAULTS:
        assert not result["controls"][fault]["correct"], fault


def test_float32_loses_the_quotient(suite, statements):
    """q27's grand total, an average over 37 rows: float32 holds the three
    decimal averages and loses the integer one."""
    params = statements["q27"].params
    assert params["state"] == "AL"
    (exact,) = suite.answers([("q27", params)])
    (f32,) = suite.answers([("q27", params)], fault="float32_sums")
    assert exact["rows"][-1][:3] == (None, None, 1)
    assert compare.wrong(f32["rows"], exact) != ""
    assert isinstance(exact["rows"][-1][3], float)
    assert exact["rows"][-1][3] != f32["rows"][-1][3]
    assert exact["rows"][-1][4:] == f32["rows"][-1][4:]


# -- the CPU rehearsal of the new cell ---------------------------------------


def test_cell_rehearses(capsys):
    """`benchmark/rehearse.py`: the harness's own `run_cell` end to end at
    `tiny`, one traced run (so the per-layer line is checked too)."""
    assert rehearse.rehearse([CELL], seeds=(SEED,), seconds=0.5) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["problems"] == [] and line["trace"] == 1
    assert {"joins_per_stmt", "join_ms", "join_pulls_per_stmt",
            "window_launches_per_stmt"} <= set(line["metrics"])
