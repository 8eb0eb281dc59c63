"""Proof-licensed execution: capacity certificates + schedule licenses.

Fast tier: certificate derivation over TPC-H plans (uniqueness sources,
preservation through joins, exact-filter row bounds, key-range proofs),
the verifier's unsound-claim rejection, seal/mesh-validity, the
filter-refinement extension of range certificates, schedule-license shape,
the stats-vs-generator soundness audit, and the stale-baseline detector.

Mesh tier (still tier-1; tiny data): licensed Q3 runs with ZERO runtime
sizing (no overflow check, no capacity_sizing gather) and rows == local;
the build-at-exactly-certified-capacity / rows_bound == 2**n edge; a cert
whose seal doesn't match the executing mesh (the mid-query-shrink hazard)
falls back to the runtime sizing path with rows == local.
"""

import json

import numpy as np
import pytest

from trino_tpu.planner import plan as P
from trino_tpu.verify.capacity import (
    CapacityCertificate,
    check_capacity_certificates,
    license_join_capacities,
    rows_bound,
    seal_licenses,
    unique_sets,
    _walk,
)

LINEITEM_ORDERS = (
    "tpch.tiny.lineitem:l_orderkey:8,tpch.tiny.orders:o_orderkey:8"
)

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""


@pytest.fixture(scope="module")
def local():
    from trino_tpu.runtime.runner import LocalQueryRunner

    return LocalQueryRunner(catalog="tpch", schema="tiny")


@pytest.fixture(scope="module")
def tpcds():
    from trino_tpu.runtime.runner import LocalQueryRunner

    return LocalQueryRunner(catalog="tpcds", schema="tiny")


@pytest.fixture(scope="module")
def dist():
    from trino_tpu.parallel import DistributedQueryRunner

    d = DistributedQueryRunner(n_workers=8, catalog="tpch", schema="tiny")
    d.execute(f"set session table_layouts = '{LINEITEM_ORDERS}'")
    return d


#: a join whose build key (l_suppkey) is not unique
SUPPKEY_JOIN = (
    "select count(*) from customer c join lineitem l "
    "on c.c_custkey = l.l_suppkey"
)


def _joins(plan):
    return [n for n in _walk(plan) if isinstance(n, P.JoinNode)]


def _suppkey_join(plan):
    return next(
        j for j in _joins(plan)
        if "l_suppkey" in {s.name for pair in j.criteria for s in pair}
    )


def _scan(plan, table):
    for n in _walk(plan):
        if isinstance(n, P.TableScanNode) and n.handle.table == table:
            return n
    raise AssertionError(f"no {table} scan in plan")


# -- derivation: uniqueness sources and preservation ---------------------------


class TestDerivation:
    def test_q3_both_joins_licensed(self, local):
        plan = local.create_plan(Q3)
        joins = _joins(plan)
        assert len(joins) == 2
        for j in joins:
            cert = j.capacity_cert
            assert cert is not None, f"join on {j.criteria} not licensed"
            assert cert.fanout_bound == 1
            assert cert.mesh_w is None  # not sealed until fragmentation
        keys = {j.capacity_cert.key for j in joins}
        assert keys == {("o_orderkey",), ("c_custkey",)}

    def test_uniqueness_preserved_through_key_unique_join(self, local):
        # the lineitem join's build side is orders x customer: o_orderkey
        # stays unique through that join BECAUSE c_custkey is unique —
        # the preservation rule, witnessed by the attached provenance
        plan = local.create_plan(Q3)
        j = next(
            x for x in _joins(plan)
            if x.capacity_cert.key == ("o_orderkey",)
        )
        assert any(
            "unique:build[o_orderkey]" in p for p in j.capacity_cert.provenance
        )
        # and the build side of that join is itself a join subtree
        assert any(isinstance(n, P.JoinNode) for n in _walk(j.right))

    def test_scan_uniqueness_requires_exact_distinct(self, tpcds):
        # i_item_sk: dense surrogate PK, structurally exact -> unique
        plan = tpcds.create_plan("select i_item_sk from item")
        u = unique_sets(_scan(plan, "item"), tpcds.catalogs)
        assert any(u_set == frozenset({"i_item_sk"}) for u_set in u)
        # s_closed_date_sk: random FK whose ndv claim (min(rows, days))
        # equals rows on a tiny table — probabilistic, NOT an admissible
        # uniqueness witness (the exact_distinct gate)
        plan = tpcds.create_plan("select s_closed_date_sk from store")
        u = unique_sets(_scan(plan, "store"), tpcds.catalogs)
        assert not any(
            "s_closed_date_sk" in u_set for u_set in u
        ), "a random FK ndv bound must never prove uniqueness"

    def test_aggregation_group_keys_unique(self, local):
        plan = local.create_plan(
            "select o_custkey, count(*) from orders group by o_custkey"
        )
        agg = next(
            n for n in _walk(plan) if isinstance(n, P.AggregationNode)
        )
        assert frozenset({"o_custkey"}) in unique_sets(agg, local.catalogs)

    def test_non_unique_build_key_gets_only_the_wide_cert(self, local):
        # build side keyed on a non-unique column: there is no uniqueness
        # proof, so the width-adaptive derivation grants only the bound
        # that is sound by counting -- one probe row matches at most every
        # build row -- and leaves holding it to the runner's economy policy
        # (TestMeshExecution.test_wide_cert_is_declined_cold)
        plan = local.create_plan(SUPPKEY_JOIN)
        j = _suppkey_join(plan)
        cert = j.capacity_cert
        assert cert is not None
        build_rows = rows_bound(j.right, local.catalogs)
        assert cert.fanout_bound == cert.build_rows_bound == build_rows
        assert cert.provenance[0] == f"multiplicity:build<={build_rows}/key"
        assert not any(p.startswith("unique:build") for p in cert.provenance)
        assert check_capacity_certificates(plan, local.catalogs) == []

    def test_witness_columns_actually_unique_in_generated_data(self, local):
        # empirical audit of the proof's ground truth: the generator
        # really does emit each key once
        for col, table in (("c_custkey", "customer"), ("o_orderkey", "orders")):
            res = local.execute(
                f"select count(*), count(distinct {col}) from {table}"
            )
            total, distinct = res.rows[0]
            assert total == distinct, f"{table}.{col} not unique: stats lie"


# -- sound row bounds ----------------------------------------------------------


class TestRowsBound:
    def test_scan_bound_is_generator_row_count(self, local):
        plan = local.create_plan("select o_orderkey from orders")
        assert rows_bound(_scan(plan, "orders"), local.catalogs) == 15000

    def test_eq_literal_on_unique_key_bounds_to_one(self, local):
        plan = local.create_plan(
            "select * from orders where o_orderkey = 42"
        )
        assert rows_bound(plan, local.catalogs) == 1

    def test_key_range_proof_bounds_by_width(self, local):
        # o_orderkey is dense-unique on [1, 15000]: <= 1024 admits at most
        # 1024 integer values, each occurring at most once
        plan = local.create_plan(
            "select * from orders where o_orderkey <= 1024"
        )
        assert rows_bound(plan, local.catalogs) == 1024

    def test_in_list_bound(self, local):
        plan = local.create_plan(
            "select * from orders where o_orderkey in (1, 2, 3)"
        )
        assert rows_bound(plan, local.catalogs) == 3

    def test_fanout_aware_join_bound(self, local):
        # probe(lineitem) x unique-key build(orders): out <= probe rows,
        # not the |L|x|R| structural product
        plan = local.create_plan(
            "select count(*) from lineitem l join orders o "
            "on l.l_orderkey = o.o_orderkey"
        )
        j = _joins(plan)[0]
        b = rows_bound(j, local.catalogs)
        lineitem_rows = rows_bound(_scan(plan, "lineitem"), local.catalogs)
        assert b is not None and b <= lineitem_rows + 15000

    def test_left_join_preserved_side_never_tightens_the_bound(self, local):
        # customer LEFT JOIN region on c_custkey = r_regionkey: c_custkey
        # is unique, but a left join PRESERVES every customer row — a
        # bound of |region| (the pre-fix claim) would be unsound by 300x
        plan = local.create_plan(
            "select * from customer left join region on c_custkey = r_regionkey"
        )
        j = _joins(plan)[0]
        assert j.kind == "left"
        customer_rows = rows_bound(_scan(plan, "customer"), local.catalogs)
        b = rows_bound(j, local.catalogs)
        assert b is not None and b >= customer_rows

    def test_full_join_unknown_preserved_side_makes_no_claim(
        self, local, monkeypatch
    ):
        # full join whose preserved build side has NO row bound: the
        # unmatched-build tail is unbounded, so no sound claim exists —
        # unknown must never be treated as zero
        import trino_tpu.verify.capacity as C

        plan = local.create_plan(
            "select * from orders o join customer c "
            "on o.o_custkey = c.c_custkey"
        )
        j = _joins(plan)[0]
        j.kind = "full"
        real = C.rows_bound

        def no_build_bound(node, catalogs=None, ctx=None):
            if node is j.right:
                return None
            return real(node, catalogs, ctx)

        monkeypatch.setattr(C, "rows_bound", no_build_bound)
        assert C._join_rows_bound(j, local.catalogs, None) is None

    def test_range_predicate_on_non_unique_column_makes_no_claim(self, local):
        # l_suppkey <= 5 admits 5 VALUES but each value repeats: only the
        # scan row count bounds the output
        plan = local.create_plan(
            "select * from lineitem where l_suppkey <= 5"
        )
        scan_rows = rows_bound(_scan(plan, "lineitem"), local.catalogs)
        assert rows_bound(plan, local.catalogs) == scan_rows


# -- the license record and the verifier rule ----------------------------------


class TestCertificateAndVerifier:
    def test_licensed_out_cap_arithmetic(self):
        cert = CapacityCertificate(
            fanout_bound=1, probe_rows_bound=1024, mesh_w=8
        )
        # rows_bound == 2**n boundary: the licensed capacity lands exactly
        # on the bucket, no off-by-one into the next power of two
        assert cert.licensed_out_cap(4096) == 1024
        assert cert.licensed_out_cap(512) == 512  # cap_p tighter
        loose = CapacityCertificate(fanout_bound=1, probe_rows_bound=None)
        assert loose.licensed_out_cap(2048) == 2048

    def test_seal_and_mesh_validity(self, local):
        plan = local.create_plan(Q3)
        n = seal_licenses(plan, 8)
        assert n == 3  # two joins and the grouped aggregation
        for j in _joins(plan):
            assert j.capacity_cert.valid_for(8)
            assert not j.capacity_cert.valid_for(7)
        unsealed = CapacityCertificate(fanout_bound=1)
        assert not unsealed.valid_for(8)

    def test_sound_certs_verify(self, local):
        plan = local.create_plan(Q3)
        assert check_capacity_certificates(plan, local.catalogs) == []

    def test_unsound_tighter_rows_bound_rejected(self, local):
        plan = local.create_plan(Q3)
        j = _joins(plan)[0]
        provable = j.capacity_cert.probe_rows_bound
        j.capacity_cert = CapacityCertificate(
            fanout_bound=1,
            probe_rows_bound=max(1, provable // 2),  # tighter than provable
            key=j.capacity_cert.key,
        )
        violations = check_capacity_certificates(plan, local.catalogs)
        assert violations and violations[0].rule == "capacity-unsound"

    def test_cert_without_uniqueness_witness_rejected(self, local):
        # the derivable certificate of a non-unique build key is the wide
        # one; a claim of fanout 1 there has no witness and is tighter
        # than anything admissible
        plan = local.create_plan(SUPPKEY_JOIN)
        j = _suppkey_join(plan)
        assert j.capacity_cert.fanout_bound > 1
        j.capacity_cert = CapacityCertificate(fanout_bound=1)
        violations = check_capacity_certificates(plan, local.catalogs)
        assert violations and violations[0].rule == "capacity-unsound"
        assert "tighter than the provable bound" in str(violations[0])

    def test_cert_on_unprovable_join_rejected(self, local):
        # a 'right' join is never licensed before its sides flip (the
        # certificate would describe the wrong build side): no proof is
        # derivable, so any attached claim is rejected outright
        plan = local.create_plan(
            "select count(*) from customer c right join lineitem l "
            "on c.c_custkey = l.l_suppkey"
        )
        j = _suppkey_join(plan)
        assert j.kind == "right" and j.capacity_cert is None
        j.capacity_cert = CapacityCertificate(fanout_bound=1)
        violations = check_capacity_certificates(plan, local.catalogs)
        assert violations and violations[0].rule == "capacity-unsound"
        assert "no admissible proof" in str(violations[0])

    def test_looser_than_provable_is_sound(self, local):
        plan = local.create_plan(Q3)
        j = _joins(plan)[0]
        cert = j.capacity_cert
        j.capacity_cert = CapacityCertificate(
            fanout_bound=5,  # weaker true statement
            probe_rows_bound=cert.probe_rows_bound * 10,
            key=cert.key,
        )
        assert check_capacity_certificates(plan, local.catalogs) == []

    def test_license_pass_is_idempotent_and_counts(self, local):
        def certs(plan):
            return [
                n.capacity_cert for n in _walk(plan)
                if getattr(n, "capacity_cert", None) is not None
            ]

        plan = local.create_plan(Q3)
        before = certs(plan)
        # Q3's two joins and its grouped aggregation
        assert license_join_capacities(plan, local.catalogs) == 3
        assert certs(plan) == before  # the planner's own pass derived these


# -- part (c): range certificates for filter/join outputs ----------------------


class TestRangeExtension:
    def test_filter_refinement_narrows_facts(self, local):
        from trino_tpu import types as T
        from trino_tpu.expr.ir import Call, Literal, SymbolRef
        from trino_tpu.verify.numeric import Env, Fact, refine_env
        from trino_tpu.verify.ranges import Interval

        env = Env({"x": Fact(T.BIGINT, Interval(-100, 100), True, True)})
        pred = Call("$lt", [SymbolRef("x", T.BIGINT), Literal(10, T.BIGINT)],
                    T.BOOLEAN)
        out = refine_env(env, pred)
        f = out.sym("x")
        assert f.interval.hi == 9 and f.interval.lo == -100
        assert f.nullable is False  # comparisons reject NULL

    def test_decimal_sum_above_join_is_licensed(self, local):
        # Q3's revenue sum aggregates a decimal product ABOVE two joins:
        # only the fanout-aware join row bound makes the i64 certificate
        # provable (the structural |L|x|R| bound would overflow it)
        plan = local.create_plan(Q3)
        agg = next(
            n for n in _walk(plan) if isinstance(n, P.AggregationNode)
        )
        sums = [a for _, a in agg.aggregations if a.function == "sum"]
        assert sums and all(a.sum_bound is not None for a in sums)

    def test_scan_pushed_predicate_refines_scan_env(self, local):
        from trino_tpu.verify.numeric import _scan_env

        plan = local.create_plan(
            "select o_totalprice from orders where o_orderkey <= 100"
        )
        scan = _scan(plan, "orders")
        assert scan.pushed_predicate is not None
        env = _scan_env(scan, local.catalogs)
        f = env.sym("o_orderkey")
        assert f is not None and f.interval.hi <= 100


# -- stats soundness audit -----------------------------------------------------


class TestStatsAudit:
    def test_tpcds_stats_claims_hold_on_generated_data(self, tpcds):
        """Every (low, high) claim the connector makes must contain the
        actually generated values — the audit that caught the unsound
        d_date_sk and *_returned_date_sk claims this PR fixed."""
        from trino_tpu import types as T
        from trino_tpu.connectors.tpcds import schema as S
        from trino_tpu.connectors.tpcds.generator import generator

        gen = generator(S.schema_scale("tiny"))
        meta = tpcds.catalogs.get("tpcds").metadata()
        for table in sorted(S.TABLES):
            ts = meta.table_statistics("tiny", table)
            n = min(ts.row_count, 4000)
            for name, cs in sorted(ts.columns.items()):
                if cs.low is None or cs.high is None:
                    continue
                cd = gen.column(table, name, 0, n)
                vals = np.asarray(cd.values)
                if vals.dtype.kind not in "iu":
                    continue
                t = dict(S.column_types(table))[name]
                if cd.valid is not None:
                    vals = vals[np.asarray(cd.valid)]
                if not len(vals):
                    continue
                if isinstance(t, T.DecimalType):
                    # scaled-unit claims allow one unit of scale rounding
                    f = t.scale_factor
                    assert vals.min() >= float(cs.low) * f - 1, (table, name)
                    assert vals.max() <= float(cs.high) * f + 1, (table, name)
                else:
                    # integer claims are EXACT containment — a one-off
                    # claim is unsound (this strictness caught t_time_sk's
                    # 0-based PK against the dense [1, rows] rule)
                    assert vals.min() >= cs.low, (table, name)
                    assert vals.max() <= cs.high, (table, name)


# -- schedule licenses ---------------------------------------------------------


class TestScheduleLicense:
    def test_q3_license_shape(self, dist):
        from trino_tpu.verify.schedule import license_schedule

        sub = dist.create_subplan(dist.create_plan(Q3))
        lic = license_schedule(sub, dist.wm.n)
        assert lic is not None
        assert lic.mesh_w == dist.wm.n
        # the probe fragment's broadcast build feed (customer) is licensed
        # for eager pre-dispatch
        assert lic.licensed_count() >= 1
        for parent, children in lic.async_children.items():
            assert parent not in children
        # the witness matches the runner's recorded static signature
        assert lic.fragments == dist.last_collective_signature

    def test_sync_free_requires_license_or_no_gather(self, dist):
        from trino_tpu.verify.schedule import _sync_free

        plan = dist.create_plan(Q3)
        sub = dist.create_subplan(plan)

        def probe_fragment(s):
            for cand in [s] + list(s.children):
                if any(
                    isinstance(n, P.JoinNode)
                    for n in _walk(cand.fragment.root)
                ):
                    return cand
            raise AssertionError("no join fragment")

        frag = probe_fragment(sub)
        assert _sync_free(frag)  # capacity certs make the gathers elidable
        for n in _walk(frag.fragment.root):
            if isinstance(n, P.JoinNode):
                n.capacity_cert = None
        assert not _sync_free(frag)  # unlicensed sizing gather = a sync


# -- mesh execution: the deleted runtime checks --------------------------------


class TestMeshExecution:
    def test_q3_runs_with_zero_runtime_sizing(self, dist, local):
        from trino_tpu.telemetry.metrics import join_capacity_counter

        dist.execute(Q3)  # settle
        runtime_checks = join_capacity_counter().value(("runtime_check",))
        res = dist.execute(Q3)
        assert (
            join_capacity_counter().value(("runtime_check",))
            == runtime_checks
        )
        prof = dist.last_mesh_profile
        counters = dict(prof.counters)
        # under the co-partitioned layouts the probe is not repartitioned
        assert counters.get("repartition_collective", 0) == 0
        assert counters.get("exchange_elided", 0) > 0
        assert counters.get("join_overflow_check", 0) == 0
        assert counters.get("join_capacity_sync", 0) == 0
        assert counters.get("join_speculative_retry", 0) == 0
        assert counters.get("join_capacity_proven", 0) == 2
        bytes_by = prof.to_json()["collective_bytes_by"]
        assert "gather/capacity_sizing" not in bytes_by
        assert sorted(res.rows) == sorted(local.execute(Q3).rows)

    def test_async_predispatch_counts(self, dist):
        dist.execute(Q3)
        counters = dict(dist.last_mesh_profile.counters)
        # fragment 0 (the customer build feed) pre-dispatched under the
        # schedule license
        assert counters.get("collective_async", 0) >= 1

    def test_build_at_exactly_certified_capacity(self, dist, local):
        # probe bounded to EXACTLY 1024 = 2**10 rows by a key-range proof;
        # every probe row matches exactly one customer, so the licensed
        # expand fills its certified capacity to the last row — the
        # boundary where an off-by-one would overflow silently
        sql = (
            "select count(*) from orders join customer "
            "on o_custkey = c_custkey where o_orderkey <= 1024"
        )
        plan = dist.create_plan(sql)
        joins = _joins(plan)
        assert joins and joins[0].capacity_cert is not None
        assert joins[0].capacity_cert.probe_rows_bound == 1024
        res = dist.execute(sql)
        counters = dict(dist.last_mesh_profile.counters)
        assert counters.get("join_overflow_check", 0) == 0
        assert counters.get("join_capacity_proven", 0) >= 1
        assert res.rows == local.execute(sql).rows == [(1024,)]

    def test_stale_seal_falls_back_to_sizing_path(self, dist, local):
        # the mid-query mesh-shrink hazard: a subplan whose certificates
        # were sealed for a DIFFERENT width than the executing mesh (the
        # state a shrink-to-W-1 replan window can produce) must refuse the
        # license and run the runtime sizing path — rows still == local
        from trino_tpu.parallel.runner import StageExecutor

        sql = (
            "select count(*) from orders join customer "
            "on o_custkey = c_custkey"
        )
        sub = dist.create_subplan(dist.create_plan(sql))
        for frag in sub.all_fragments():
            seal_licenses(frag.root, dist.wm.n - 1)  # stale seal
        ex = StageExecutor(dist.catalogs, dist.wm, dist.properties)
        out = ex.run(sub)
        rows = [tuple(r) for b in out.stream for r in b.to_pylist()]
        counters = dict(ex.profile.counters)
        assert counters.get("join_capacity_proven", 0) == 0
        assert (
            counters.get("join_overflow_check", 0)
            + counters.get("join_capacity_sync", 0)
        ) >= 1
        assert rows == local.execute(sql).rows

    def test_wide_cert_is_declined_cold(self, dist, local):
        # the wide certificate of a non-unique build key (fanout bound =
        # the build's row bound) is sound but, with no learned width to
        # hold it against, far wider than the probe: the economy policy
        # declines it and the join sizes itself by the runtime protocol
        from trino_tpu.partitioning.speculative import CAP_HISTORY

        saved = CAP_HISTORY.snapshot()
        CAP_HISTORY.clear()
        try:
            res = dist.execute(SUPPKEY_JOIN)
        finally:
            CAP_HISTORY.seed(saved)
        counters = dict(dist.last_mesh_profile.counters)
        assert counters.get("join_license_declined", 0) == 1
        assert counters.get("join_capacity_proven", 0) == 0
        assert (
            counters.get("join_overflow_check", 0)
            + counters.get("join_capacity_sync", 0)
        ) >= 1
        assert res.rows == local.execute(SUPPKEY_JOIN).rows

    def test_license_knob_off_runs_runtime_path(self, dist, local):
        sql = (
            "select count(*) from orders join customer "
            "on o_custkey = c_custkey"
        )
        dist.execute("set session join_capacity_license = false")
        try:
            res = dist.execute(sql)
            counters = dict(dist.last_mesh_profile.counters)
            assert counters.get("join_capacity_proven", 0) == 0
            assert rows_ok(res, local, sql)
        finally:
            dist.execute("set session join_capacity_license = true")
        res = dist.execute(sql)
        assert dist.last_mesh_profile.counters.get("join_capacity_proven", 0) >= 1
        assert rows_ok(res, local, sql)


def rows_ok(res, local, sql):
    return sorted(res.rows) == sorted(local.execute(sql).rows)


# -- residency: warm replays follow the licensed schedule ----------------------


class TestResidency:
    def test_warm_q3_residency_with_licenses(self, dist):
        from trino_tpu import verify as V

        report = V.device_residency(dist, Q3, warmups=1)
        assert report["retraces"] == 0
        assert report["counters"].get("join_overflow_check", 0) == 0
        assert report["counters"].get("join_capacity_proven", 0) == 2


# -- the stale-baseline detector -----------------------------------------------


class TestStaleBaseline:
    def _root(self, tmp_path, baseline):
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "lint_baseline.json").write_text(
            json.dumps(baseline)
        )
        for d in ("ops", "parallel", "expr", "server"):
            p = tmp_path / "trino_tpu" / d
            p.mkdir(parents=True)
            (p / "__init__.py").write_text("")
        return str(tmp_path)

    def test_stale_entry_fails_under_check_stale(self, tmp_path, capsys):
        import tools.lint_tpu as L

        root = self._root(tmp_path, {
            "allow_budget": 99,
            "numeric_safety": {
                "trino_tpu/ops/ghost.py:Ghost._gone:astype-narrow": "dead"
            },
        })
        rc = L.main(["--only", "device", "--root", root, "--check-stale"])
        assert rc == 1
        assert "stale baseline entr" in capsys.readouterr().out

    def test_stale_entry_only_warns_without_flag(self, tmp_path, capsys):
        import tools.lint_tpu as L

        root = self._root(tmp_path, {
            "allow_budget": 99,
            "numeric_safety": {
                "trino_tpu/ops/ghost.py:Ghost._gone:astype-narrow": "dead"
            },
        })
        rc = L.main(["--only", "device", "--root", root])
        assert rc == 0
        assert "note: numeric_safety baseline entry" in capsys.readouterr().out

    def test_clean_baseline_passes_check_stale(self, tmp_path):
        import tools.lint_tpu as L

        root = self._root(tmp_path, {"allow_budget": 99})
        assert L.main(["--only", "device", "--root", root, "--check-stale"]) == 0
