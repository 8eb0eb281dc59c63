"""Numeric-safety verifier tests (trino_tpu/verify/numeric.py + ranges.py):
the interval lattice, the per-rule negative tests the acceptance demands
(a hand-built overflow / scale-mismatch / dropped-validity expression each
raises the right rule), the plan-level licensing pass, and the TPC-H +
TPC-DS sweep gate (full sweep marked slow; CI also runs it directly via
`python -m trino_tpu.verify.numeric`)."""

from decimal import Decimal

import pytest

from trino_tpu import types as T
from trino_tpu.expr.ir import Call, Form, InputRef, Literal, SpecialForm
from trino_tpu.verify import ranges as R
from trino_tpu.verify.numeric import (
    Analyzer,
    Env,
    Fact,
    analyze_expr,
    license_decimal_sums,
    row_upper_bound,
    sum_certificate,
)

pytestmark = pytest.mark.smoke


# -- the interval lattice ------------------------------------------------------


class TestInterval:
    def test_arith(self):
        a = R.Interval(-3, 10)
        b = R.Interval(2, 5)
        assert a.add(b) == R.Interval(-1, 15)
        assert a.sub(b) == R.Interval(-8, 8)
        assert a.mul(b) == R.Interval(-15, 50)
        assert a.neg() == R.Interval(-10, 3)

    def test_unbounded_propagates(self):
        top = R.Interval.top()
        assert R.Interval(1, 2).add(top) == top
        assert R.Interval(1, 2).mul(top) == top
        assert top.max_abs() is None

    def test_union_and_within(self):
        a = R.Interval(0, 5)
        b = R.Interval(-2, 3)
        assert a.union(b) == R.Interval(-2, 5)
        assert b.within(R.Interval(-10, 10))
        assert not R.Interval(-11, 0).within(R.Interval(-10, 10))
        assert a.within(R.Interval.top())

    def test_scale_pow10(self):
        assert R.Interval(-3, 7).scale_pow10(2) == R.Interval(-300, 700)
        # downscale is conservative (never tightens below the truth)
        d = R.Interval(-150, 250).scale_pow10(-2)
        assert d.lo <= -2 and d.hi >= 3

    def test_exactness_soundness_exhaustive(self):
        """Interval ops over small ranges contain every concrete result."""
        import itertools

        vals = [-7, -1, 0, 2, 9]
        for lo1, hi1, lo2, hi2 in itertools.product(vals, repeat=4):
            if lo1 > hi1 or lo2 > hi2:
                continue
            a, b = R.Interval(lo1, hi1), R.Interval(lo2, hi2)
            for x in range(lo1, hi1 + 1):
                for y in range(lo2, hi2 + 1):
                    assert a.add(b).lo <= x + y <= a.add(b).hi
                    assert a.mul(b).lo <= x * y <= a.mul(b).hi


# -- the rule negative tests (acceptance: each hazard raises its rule) ---------


class TestRules:
    def test_int_overflow_flagged(self):
        e = Call("$mul", [InputRef(0, T.BIGINT), InputRef(1, T.BIGINT)],
                 T.BIGINT)
        _, issues = analyze_expr(e)
        assert [i.rule for i in issues] == ["int-overflow"]

    def test_int32_add_overflow_flagged(self):
        e = Call("$add", [InputRef(0, T.INTEGER), InputRef(1, T.INTEGER)],
                 T.INTEGER)
        _, issues = analyze_expr(e)
        assert [i.rule for i in issues] == ["int-overflow"]

    def test_decimal_overflow_flagged(self):
        d = T.DecimalType(15, 2)
        e = Call("$mul", [InputRef(0, d), InputRef(1, d)], T.DecimalType(18, 4))
        _, issues = analyze_expr(e)
        assert any(i.rule == "decimal-overflow" for i in issues)

    def test_scale_mismatch_flagged(self):
        e = SpecialForm(
            Form.IF,
            [
                InputRef(0, T.BOOLEAN),
                InputRef(1, T.DecimalType(10, 2)),
                Literal(Decimal(0), T.DecimalType(10, 0)),
            ],
            T.DecimalType(10, 0),
        )
        _, issues = analyze_expr(e)
        assert [i.rule for i in issues] == ["scale-mismatch"]

    def test_float_contamination_flagged(self):
        e = SpecialForm(
            Form.CAST, [InputRef(0, T.DOUBLE)], T.DecimalType(12, 2)
        )
        _, issues = analyze_expr(e)
        assert [i.rule for i in issues] == ["float-contamination"]

    def test_dropped_validity_flagged(self):
        e = SpecialForm(
            Form.ARRAY, [InputRef(0, T.BIGINT)], T.ArrayType(T.BIGINT)
        )
        _, issues = analyze_expr(e)
        assert [i.rule for i in issues] == ["dropped-validity"]

    def test_safe_expression_raises_nothing(self):
        d = T.DecimalType(12, 2)
        e = Call(
            "$mul",
            [
                InputRef(0, d),
                Call("$sub", [Literal(Decimal(1), d), InputRef(1, d)],
                     T.DecimalType(13, 2)),
            ],
            T.DecimalType(25, 4),
        )
        fact, issues = analyze_expr(e)
        assert issues == []
        assert fact.interval.bounded

    def test_stats_env_narrows_to_proven(self):
        """A by-type hazard becomes PROVEN-SAFE under stats bounds."""
        e = Call("$mul", [InputRef(0, T.BIGINT), InputRef(1, T.BIGINT)],
                 T.BIGINT)
        env = Env(channels={
            0: Fact(T.BIGINT, R.Interval(0, 100), True),
            1: Fact(T.BIGINT, R.Interval(0, 1000), True),
        })
        _, issues = analyze_expr(e, env)
        assert issues == []

    def test_untracked_operand_never_false_positives(self):
        """Unknown-function results keep honest type-wide intervals but do
        not RAISE overflow (no evidence of a hazard)."""
        inner = Call("some_udf", [InputRef(0, T.BIGINT)], T.BIGINT)
        e = Call("$mul", [inner, Literal(10**6, T.BIGINT)], T.BIGINT)
        _, issues = analyze_expr(e)
        assert issues == []

    def test_case_without_else_is_nullable(self):
        """CASE with pairs only carries the compiler's implicit NULL
        default: the fact must be nullable even over non-null inputs, so
        ARRAY[CASE WHEN c THEN 1 END] still raises dropped-validity."""
        case = SpecialForm(
            Form.CASE,
            [Literal(True, T.BOOLEAN), Literal(1, T.BIGINT)],
            T.BIGINT,
        )
        fact, issues = analyze_expr(case)
        assert fact.nullable and issues == []
        arr = SpecialForm(Form.ARRAY, [case], T.ArrayType(T.BIGINT))
        _, issues = analyze_expr(arr)
        assert [i.rule for i in issues] == ["dropped-validity"]

    def test_null_literal_branch_not_scale_mismatched(self):
        e = SpecialForm(
            Form.IF,
            [
                InputRef(0, T.BOOLEAN),
                InputRef(1, T.DecimalType(10, 2)),
                Literal(None, T.DecimalType(10, 2)),
            ],
            T.DecimalType(10, 2),
        )
        _, issues = analyze_expr(e)
        assert issues == []


# -- certificates and the licensing pass ---------------------------------------


class TestLicensing:
    def test_sum_certificate_q1_shape(self):
        d = T.DecimalType(12, 2)
        env = Env(channels={
            0: Fact(d, R.Interval(90_000, 10_500_000), True),
            1: Fact(d, R.Interval(0, 10), True),
        })
        prod = Call(
            "$mul",
            [
                InputRef(0, d),
                Call("$sub", [Literal(Decimal(1), d), InputRef(1, d)],
                     T.DecimalType(13, 2)),
            ],
            T.DecimalType(25, 4),
        )
        cert = sum_certificate(prod, env, rows_bound=6_000_000)
        assert cert is not None
        assert cert.licensed_i64_sum_bound() is not None
        assert cert.to_json()["licenses_i64_sum"] is True

    def test_no_rows_bound_no_license(self):
        d = T.DecimalType(12, 2)
        cert = sum_certificate(InputRef(0, d), Env(), rows_bound=None)
        assert cert is not None and cert.licensed_i64_sum_bound() is None

    def test_untracked_refuses(self):
        cert = sum_certificate(
            Call("some_udf", [], T.DecimalType(12, 2)), Env(), 100
        )
        assert cert is None

    def test_q1_plan_is_licensed(self):
        from trino_tpu.connectors.tpch.queries import QUERIES
        from trino_tpu.planner import plan as P
        from trino_tpu.runtime.runner import LocalQueryRunner

        r = LocalQueryRunner(catalog="tpch", schema="tiny")
        plan = r.create_plan(QUERIES[1])

        def walk(n, seen):
            if id(n) in seen:
                return
            seen.add(id(n))
            yield n
            for c in n.children:
                yield from walk(c, seen)

        sums = [
            agg
            for node in walk(plan, set())
            if isinstance(node, P.AggregationNode)
            for _, agg in node.aggregations
            if agg.function in ("sum", "avg") and agg.args
            and isinstance(agg.args[0].type, T.DecimalType)
        ]
        assert sums, "Q1 must contain decimal sums"
        assert all(a.sum_bound is not None for a in sums), [
            (a.function, a.sum_bound) for a in sums
        ]
        # the license is a REAL i64 proof
        assert all(a.sum_bound < (1 << 63) for a in sums)

    def test_q1_traces_only_the_proven_sum_path(self):
        """The licence reaches the kernels: a Q1 that traces afresh (its
        date is no other test's, and a literal is a jit key) selects the
        proven single-plane i64 sum for its decimal sums and never the
        runtime fits probe.  Path selection is counted while a step
        traces (ops/aggregation._sum128)."""
        from trino_tpu.runtime.runner import LocalQueryRunner
        from trino_tpu.telemetry.metrics import decimal_fastpath_counter

        counter = decimal_fastpath_counter()
        before = {p: counter.value((p,)) for p in ("proven", "runtime_check")}
        r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=2)
        rows = r.execute(
            "select l_returnflag, l_linestatus, sum(l_quantity), "
            "sum(l_extendedprice * (1 - l_discount)), "
            "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
            "from lineitem where l_shipdate <= date '1998-08-17' "
            "group by l_returnflag, l_linestatus"
        ).rows
        assert len(rows) == 4
        assert counter.value(("proven",)) > before["proven"]
        assert counter.value(("runtime_check",)) == before["runtime_check"]

    def test_row_upper_bound_sound_shapes(self):
        from trino_tpu.connectors.tpch.queries import QUERIES
        from trino_tpu.runtime.runner import LocalQueryRunner

        r = LocalQueryRunner(catalog="tpch", schema="tiny")
        plan = r.create_plan(QUERIES[1])
        b = row_upper_bound(plan, r.catalogs)
        # Q1 is scan->filter->project->agg: bounded by the lineitem count
        assert b is not None and b > 0

    def test_memory_catalog_never_licenses(self):
        """No admissible stats source -> no certificate -> unchanged
        kernels (the conservative default for user tables)."""
        from trino_tpu.planner import plan as P
        from trino_tpu.runtime.runner import LocalQueryRunner

        r = LocalQueryRunner(catalog="memory", schema="default")
        r.execute("create table lic (k bigint, v decimal(12,2))")
        r.execute("insert into lic values (1, decimal '1.00')")
        plan = r.create_plan("select k, sum(v) from lic group by k")

        def walk(n, seen):
            if id(n) in seen:
                return
            seen.add(id(n))
            yield n
            for c in n.children:
                yield from walk(c, seen)

        for node in walk(plan, set()):
            if isinstance(node, P.AggregationNode):
                for _, agg in node.aggregations:
                    assert getattr(agg, "sum_bound", None) is None

    def test_licensed_q1_results_match_unlicensed(self):
        """The license changes the kernel, never the answer: Q1 grouped
        sums with certificates equal a forced-certificate-free run."""
        from trino_tpu.runtime.runner import LocalQueryRunner

        sql = (
            "select l_returnflag, sum(l_extendedprice * (1 - l_discount)) "
            "from lineitem group by l_returnflag order by l_returnflag"
        )
        r = LocalQueryRunner(catalog="tpch", schema="tiny")
        licensed = r.execute(sql).rows
        import trino_tpu.verify.numeric as VN

        orig = VN.license_decimal_sums
        VN.license_decimal_sums = lambda plan, catalogs=None: 0
        try:
            r2 = LocalQueryRunner(catalog="tpch", schema="tiny")
            unlicensed = r2.execute(sql).rows
        finally:
            VN.license_decimal_sums = orig
        assert licensed == unlicensed


# -- the sweep gate -------------------------------------------------------------


def test_sweep_smoke_q1_q6():
    """Fast in-tier-1 slice of the CI sweep: Q1 + Q6 expressions all
    PROVEN-SAFE (no baseline needed for the headline queries)."""
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.verify.numeric import SweepResult, sweep_plan

    r = LocalQueryRunner(catalog="tpch", schema="tiny")
    res = SweepResult()
    for q in (1, 6):
        sweep_plan(r.create_plan(QUERIES[q]), r.catalogs, {}, res, f"tpch:{q}")
    assert res.violations == [], res.violations
    assert res.proven == res.expressions and res.expressions > 0


@pytest.mark.slow
def test_sweep_all_benchmarks_zero_unbaselined():
    """The full acceptance gate: every TPC-H + TPC-DS plan expression is
    PROVEN-SAFE or BASELINED; any unbaselined VIOLATION fails (CI runs the
    same sweep via `python -m trino_tpu.verify.numeric`)."""
    import os

    from trino_tpu.verify.numeric import verify_benchmarks

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = verify_benchmarks(root=root)
    assert res.violations == [], [
        (w, str(i)) for w, i in res.violations[:10]
    ]
    assert res.expressions > 4000
