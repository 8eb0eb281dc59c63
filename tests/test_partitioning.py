"""Table layouts & partitioning-aware execution.

Fast tier: layout registry/session declarations, the host/device hash
mirror, property derivation, plan-level exchange elision (planning only —
no mesh execution), the CREATE TABLE WITH surface, the new session knobs,
the partitioning plan invariants, and the lint suppression budget.

Slow tier (excluded from tier-1): mesh-8 execution equivalence of
co-partitioned joins on TPC-H Q3/Q7/Q10 and a TPC-DS subset, plus the
`verify.device_residency` acceptance over the warm partitioned-join path.
"""

import numpy as np
import pytest

from trino_tpu import partitioning as PT
from trino_tpu.connectors.api import TableHandle
from trino_tpu.partitioning import (
    GLOBAL_LAYOUTS,
    LayoutResolver,
    TableLayout,
    declare_layout,
    derive_partitioning,
    drop_layout,
    parse_layout_property,
)

LINEITEM_ORDERS = (
    "tpch.tiny.lineitem:l_orderkey:8,tpch.tiny.orders:o_orderkey:8"
)


@pytest.fixture()
def clean_layouts():
    saved = dict(GLOBAL_LAYOUTS)
    GLOBAL_LAYOUTS.clear()
    yield
    GLOBAL_LAYOUTS.clear()
    GLOBAL_LAYOUTS.update(saved)


@pytest.fixture(scope="module")
def dist():
    from trino_tpu.parallel import DistributedQueryRunner

    d = DistributedQueryRunner(n_workers=8)
    d.execute(f"set session table_layouts = '{LINEITEM_ORDERS}'")
    return d


@pytest.fixture(scope="module")
def local():
    from trino_tpu.runtime.runner import LocalQueryRunner

    return LocalQueryRunner(target_splits=3)


# -- layouts: registry, session property, resolver ---------------------------


@pytest.mark.smoke
class TestLayouts:
    def test_parse_session_property(self):
        got = parse_layout_property(LINEITEM_ORDERS)
        assert got[("tpch", "tiny", "lineitem")] == TableLayout(("l_orderkey",), 8)
        assert got[("tpch", "tiny", "orders")] == TableLayout(("o_orderkey",), 8)
        multi = parse_layout_property("c.s.t:a+b:16")
        assert multi[("c", "s", "t")] == TableLayout(("a", "b"), 16)
        with pytest.raises(ValueError):
            parse_layout_property("not-an-entry")

    def test_registry_and_resolver_precedence(self, clean_layouts):
        h = TableHandle("tpch", "tiny", "lineitem")
        declare_layout("tpch.tiny.lineitem", ["l_orderkey"], 8)
        r = LayoutResolver(None, None)
        assert r(h) == TableLayout(("l_orderkey",), 8)

        class _Props:
            def get(self, name):
                if name == "global_dictionaries":
                    return True
                assert name == "table_layouts"
                return "tpch.tiny.lineitem:l_orderkey:16"

        # session declaration wins over the process registry
        r2 = LayoutResolver(None, _Props())
        assert r2(h).bucket_count == 16
        drop_layout("tpch.tiny.lineitem")
        assert r(h) is None

    def test_host_hash_mirrors_device_exchange_hash(self):
        import jax.numpy as jnp

        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.parallel.exchange import _hash_rows

        rng = np.random.default_rng(7)
        data = rng.integers(-(10**12), 10**12, size=257, dtype=np.int64)
        valid = rng.random(257) > 0.1
        mask = np.ones(257, dtype=bool)
        host = Batch([Column(data, T.BIGINT, valid)], mask)
        dev = Batch(
            [Column(jnp.asarray(data), T.BIGINT, jnp.asarray(valid))],
            jnp.asarray(mask),
        )
        hh = PT.host_bucket_hash([data], [valid], 257)
        dh = np.asarray(_hash_rows(dev, [0]))
        assert (hh == dh).all(), "host layout hash must equal the device hash"
        dest = PT.bucket_rows(host, (0,), 8)
        assert (dest == (hh % np.uint64(8)).astype(np.int64)).all()

    def test_scan_partitioning_eligibility(self, clean_layouts, local):
        declare_layout("tpch.tiny.lineitem", ["l_orderkey"], 8)
        declare_layout("tpch.tiny.orders", ["o_comment"], 8)  # string key
        r = LayoutResolver(local.catalogs, None)
        plan = local.create_plan(
            "select l_orderkey, o_comment from lineitem, orders"
        )
        from trino_tpu.planner import plan as P

        scans = {
            n.handle.table: n
            for n in P.walk(plan)
            if isinstance(n, P.TableScanNode)
        }
        hit = PT.scan_partitioning(scans["lineitem"], r, 8)
        assert hit is not None and hit[1] == ("l_orderkey",)
        # string bucket column: usable ONLY through a global dictionary
        # code assignment (tpch registers one per string column, so codes
        # hash-mirror like integers); with the service gated off the
        # layout is unusable again — producer-local codes don't mirror
        hit_o = PT.scan_partitioning(scans["orders"], r, 8)
        assert hit_o is not None and hit_o[1] == ("o_comment",)
        r_off = LayoutResolver(local.catalogs, None)
        r_off.global_dicts = False
        assert PT.scan_partitioning(scans["orders"], r_off, 8) is None
        # bucket_count must be a multiple of the worker count
        assert PT.scan_partitioning(scans["lineitem"], r, 3) is None
        # bucket column not scanned: no placement
        plan2 = local.create_plan("select l_quantity from lineitem")
        scan2 = next(
            n for n in P.walk(plan2) if isinstance(n, P.TableScanNode)
        )
        assert PT.scan_partitioning(scan2, r, 8) is None


# -- property derivation ------------------------------------------------------


@pytest.mark.smoke
class TestDerivation:
    def _placed_plan(self, dist, sql):
        from trino_tpu.planner.fragmenter import ExchangePlacer

        plan = dist.create_plan(sql)
        placer = ExchangePlacer(dist.catalogs, dist.properties, 8)
        return placer.place(plan), placer

    def test_scan_filter_project_inherit_and_rename(self, dist):
        placed, placer = self._placed_plan(
            dist,
            "select l_orderkey as k from lineitem where l_quantity > 10",
        )
        from trino_tpu.planner import plan as P

        proj = next(
            n
            for n in P.walk(placed)
            if isinstance(n, P.ProjectNode)
            and [s.name for s in n.outputs] == ["k"]
        )
        props = derive_partitioning(proj, placer.resolver, 8)
        assert ("k",) in props  # renamed through the projection

    def test_join_and_agg_derivation(self, dist):
        placed, placer = self._placed_plan(
            dist,
            "select l_orderkey, count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey group by l_orderkey",
        )
        from trino_tpu.planner import plan as P

        join = next(n for n in P.walk(placed) if isinstance(n, P.JoinNode))
        assert join.distribution == "colocated"
        props = derive_partitioning(join, placer.resolver, 8)
        assert ("l_orderkey",) in props and ("o_orderkey",) in props
        agg = next(
            n for n in P.walk(placed) if isinstance(n, P.AggregationNode)
        )
        assert ("l_orderkey",) in derive_partitioning(agg, placer.resolver, 8)

    def test_outer_join_placement_rules(self):
        from trino_tpu.partitioning import join_output_placements
        from trino_tpu.planner.plan import Symbol
        from trino_tpu import types as T

        crit = [(Symbol("a", T.BIGINT), Symbol("b", T.BIGINT))]
        probe = (("a",),)
        assert join_output_placements(probe, crit, "inner") == (("a",), ("b",))
        # left joins null the build side: only probe placements survive
        assert join_output_placements(probe, crit, "left") == (("a",),)
        # full joins null both sides: nothing survives
        assert join_output_placements(probe, crit, "full") == ()


# -- plan-level exchange elision (planning only) ------------------------------


@pytest.mark.smoke
class TestElision:
    def test_colocated_join_elides_both_exchanges(self, dist):
        sql = (
            "select count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey"
        )
        txt = dist.explain_distributed(sql)
        assert "dist=colocated" in txt
        assert "repartition" not in txt

    def test_agg_on_covering_keys_plans_single_stage(self, dist):
        txt = dist.explain_distributed(
            "select l_orderkey, sum(l_quantity) from lineitem "
            "group by l_orderkey"
        )
        # no repartition exchange; the aggregation runs in the scan fragment
        assert "repartition" not in txt
        assert "Aggregation[single]" in txt
        # the fragment's partitioning handle shows the layout-derived keys
        assert "SOURCE[l_orderkey" in txt

    def test_colocated_join_off_restores_exchanges(self, dist):
        sql = (
            "select count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey"
        )
        dist.execute("set session colocated_join = false")
        try:
            txt = dist.explain_distributed(sql)
            assert "colocated" not in txt
        finally:
            dist.execute("set session colocated_join = true")

    def test_partial_colocation_repartitions_aligned_build(self, dist):
        # customer has no layout: the lineitem side stays put, customer's
        # join with orders still exchanges somewhere — but lineitem must
        # never repartition (the Q3 gap: the probe side is the big one)
        dist.execute("set session join_distribution_type = 'PARTITIONED'")
        try:
            txt = dist.explain_distributed(
                "select count(*) from lineitem join orders "
                "on l_orderkey = o_orderkey join customer "
                "on o_custkey = c_custkey"
            )
        finally:
            dist.execute("set session join_distribution_type = 'AUTOMATIC'")
        import re

        for frag in re.split(r"(?=Fragment \d)", txt):
            if "lineitem" in frag:
                assert "RemoteSource" not in frag.split("Join", 1)[0]


# -- session knobs ------------------------------------------------------------


@pytest.mark.smoke
class TestSessionKnobs:
    def test_speculation_mode_parse(self):
        from trino_tpu.partitioning import speculation_mode

        class _P:
            def __init__(self, v):
                self.v = v

            def get(self, name):
                return self.v

        assert speculation_mode(_P("on")) == 0
        assert speculation_mode(_P("off")) is None
        assert speculation_mode(_P("4096")) == 4096
        assert speculation_mode(_P("1000")) == 1024  # pow2 bucketed
        with pytest.raises(ValueError):
            speculation_mode(_P("sometimes"))

    def test_properties_registered_and_settable(self, local):
        local.execute("set session colocated_join = false")
        assert local.properties.get("colocated_join") is False
        local.execute("set session colocated_join = true")
        local.execute("set session join_speculative_capacity = 'off'")
        assert local.properties.get("join_speculative_capacity") == "off"
        local.execute("set session join_speculative_capacity = 'on'")
        local.execute(f"set session table_layouts = '{LINEITEM_ORDERS}'")
        assert "lineitem" in local.properties.get("table_layouts")
        local.execute("set session table_layouts = ''")
        rows = local.execute("show session").rows
        names = {r[0] for r in rows}
        assert {
            "colocated_join", "join_speculative_capacity", "table_layouts"
        } <= names


# -- CREATE TABLE WITH (bucketed_by, bucket_count) ----------------------------


@pytest.mark.smoke
class TestCreateTableWith:
    def test_parse_with_properties(self):
        from trino_tpu.sql.parser import parse_statement

        stmt = parse_statement(
            "create table memory.default.t (a bigint, b varchar) "
            "with (bucketed_by = array['a'], bucket_count = 8)"
        )
        assert dict(stmt.properties) == {
            "bucketed_by": ("a",), "bucket_count": 8
        }

    def test_create_registers_layout(self, local, clean_layouts):
        local.execute(
            "create table memory.default.bt (k bigint, v double) "
            "with (bucketed_by = array['k'], bucket_count = 8)"
        )
        h = TableHandle("memory", "default", "bt")
        try:
            # the memory connector OWNS the layout (transactional with the
            # table via snapshots) — the engine registry stays clean
            assert local.catalogs.get("memory").table_layout(h) == TableLayout(
                ("k",), 8
            )
            assert ("memory", "default", "bt") not in GLOBAL_LAYOUTS
            assert LayoutResolver(local.catalogs, None)(h) == TableLayout(
                ("k",), 8
            )
        finally:
            local.execute("drop table memory.default.bt")
        assert LayoutResolver(local.catalogs, None)(h) is None

    def test_bad_properties_rejected(self, local):
        with pytest.raises(ValueError, match="unknown table properties"):
            local.execute(
                "create table memory.default.bad (k bigint) "
                "with (compression = 'zstd')"
            )
        with pytest.raises(ValueError, match="unknown columns"):
            local.execute(
                "create table memory.default.bad (k bigint) "
                "with (bucketed_by = array['nope'], bucket_count = 8)"
            )

    def test_ctas_with_layout(self, local, clean_layouts):
        local.execute(
            "create table memory.default.nat_b "
            "with (bucketed_by = array['n_nationkey'], bucket_count = 8) "
            "as select n_nationkey, n_name from nation"
        )
        h = TableHandle("memory", "default", "nat_b")
        try:
            assert LayoutResolver(local.catalogs, None)(h).bucket_columns == (
                "n_nationkey",
            )
            assert local.execute(
                "select count(*) from memory.default.nat_b"
            ).rows == [(25,)]
        finally:
            local.execute("drop table memory.default.nat_b")


# -- verify: partitioning invariants ------------------------------------------


@pytest.mark.smoke
class TestPartitioningInvariants:
    def test_bogus_colocated_join_flagged(self, local):
        from trino_tpu.planner import plan as P
        from trino_tpu.verify import check_partitioning
        from trino_tpu.verify.plan_checker import PlanViolation

        plan = local.create_plan(
            "select count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey"
        )
        join = next(n for n in P.walk(plan) if isinstance(n, P.JoinNode))
        join.distribution = "colocated"  # claim with no producing layout
        vs = check_partitioning(plan, LayoutResolver(local.catalogs, None), 8)
        assert vs and vs[0].rule == "partitioning-unproduced"
        assert all(isinstance(v, PlanViolation) for v in vs)

    def test_legit_colocated_plan_passes(self, dist):
        from trino_tpu.planner.fragmenter import add_exchanges

        plan = dist.create_plan(
            "select count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey"
        )
        # add_exchanges runs check_partitioning in strict mode under pytest
        add_exchanges(plan, dist.catalogs, dist.properties, n_workers=8)


# -- lint suppression budget --------------------------------------------------


@pytest.mark.smoke
class TestLintBudget:
    def test_repo_within_budget(self):
        import os
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "tools"))
        try:
            import lint_tpu
        finally:
            sys.path.pop(0)
        assert lint_tpu.check_suppression_budget(None, root) == []
        #: the PR that introduced the budget also had to pay one down
        assert lint_tpu.suppression_budget(root) <= 33

    def test_over_budget_fails(self, tmp_path):
        import json
        import os
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "tools"))
        try:
            import lint_tpu
        finally:
            sys.path.pop(0)
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "lint_baseline.json").write_text(
            json.dumps({"allow_budget": 0})
        )
        code = tmp_path / "mod.py"
        code.write_text("x = 1  # lint: allow(host-transfer)\n")
        errs = lint_tpu.check_suppression_budget([str(code)], str(tmp_path))
        assert errs and "suppression budget exceeded" in errs[0]


# -- co-located execution (tier-1: tiny data, two joins) ----------------------


class TestColocatedExecution:
    def test_colocated_join_zero_repartitions(self, dist, local):
        sql = (
            "select count(*), sum(l_quantity) from lineitem join orders "
            "on l_orderkey = o_orderkey"
        )
        assert dist.execute(sql).rows == local.execute(sql).rows
        c = dist.last_mesh_profile.counters
        assert c.get("repartition_collective", 0) == 0
        assert c.get("exchange_elided", 0) >= 2

    def test_varchar_key_colocated_join_via_global_dictionary(self, local):
        """End-to-end claim of the global dictionary service: a varchar
        business key under a layout co-locates and elides exchanges like
        an integer key (codes hash-mirror under the shared versioned
        assignment), and the dictionary-backed `unique` entry licenses
        the join's capacity — zero repartition collectives, zero runtime
        sizing, rows identical to local."""
        from trino_tpu.parallel import DistributedQueryRunner

        d = DistributedQueryRunner(n_workers=8, catalog="tpcds")
        d.execute(
            "set session table_layouts = 'tpcds.tiny.customer:c_customer_id:8'"
        )
        sql = (
            "select count(*) from tpcds.tiny.customer c1 "
            "join tpcds.tiny.customer c2 "
            "on c1.c_customer_id = c2.c_customer_id"
        )
        dr = d.execute(sql).rows
        lr = local.execute(sql).rows
        assert dr == lr
        c = d.last_mesh_profile.counters
        assert c.get("repartition_collective", 0) == 0
        assert c.get("exchange_elided", 0) > 0
        assert c.get("join_capacity_proven", 0) >= 1
        # the lift is session-gated: turned off, plans fall back to
        # producer-local codes — more exchanges, same rows
        d.execute("set session global_dictionaries = false")
        assert d.execute(sql).rows == lr


# -- mesh execution (slow ring: excluded from tier-1) -------------------------


@pytest.mark.slow
class TestMeshExecution:
    @pytest.mark.parametrize("qid", [3, 7, 10])
    def test_tpch_copartitioned_matches_local(self, dist, local, qid):
        from tests.test_e2e import assert_rows_match
        from trino_tpu.connectors.tpch.queries import QUERIES

        d = dist.execute(QUERIES[qid])
        l = local.execute(QUERIES[qid])
        assert_rows_match(d.rows, l.rows, ordered=(qid == 3))

    def test_q3_device_residency_warm(self, dist):
        """The acceptance harness over the warm partitioned-join path:
        zero warm retraces, zero host re-entries, zero host capacity
        syncs, zero speculative retries."""
        from trino_tpu import verify as V
        from trino_tpu.connectors.tpch.queries import QUERIES

        # warmups=2: run 1 sizes capacities cold (the one-time [W] totals
        # read) and run 2 compiles the fused speculative program at the
        # recorded bucket; the measured run must then be fully cached
        rep = V.device_residency(dist, QUERIES[3], warmups=2)
        assert rep["retraces"] == 0
        assert rep["counters"].get("host_restack", 0) == 0
        assert rep["counters"].get("join_capacity_sync", 0) == 0
        assert rep["counters"].get("join_speculative_retry", 0) == 0

    def test_tpcds_subset_under_layouts(self, local):
        from trino_tpu.parallel import DistributedQueryRunner

        d = DistributedQueryRunner(n_workers=8, catalog="tpcds")
        d.execute(
            "set session table_layouts = "
            "'tpcds.tiny.store_sales:ss_item_sk:8,"
            "tpcds.tiny.store_returns:sr_item_sk:8'"
        )
        sql = (
            "select count(*), sum(ss_quantity) from tpcds.tiny.store_sales "
            "join tpcds.tiny.store_returns on ss_item_sk = sr_item_sk "
            "and ss_ticket_number = sr_ticket_number"
        )
        dr = d.execute(sql).rows
        lr = local.execute(sql).rows
        assert dr == lr

    def test_residual_semi_with_misaligned_bucketized_scan(self, local):
        """A side bucketized on OTHER columns than the semi key (lineitem
        placed by l_orderkey, semi keyed on l_partkey) must be hash-placed
        on the key before per-shard marking — the historical range-split
        alignment is gone once any side moved (review finding)."""
        from trino_tpu.parallel import DistributedQueryRunner

        d = DistributedQueryRunner(n_workers=8)
        d.execute(
            "set session table_layouts = 'tpch.tiny.lineitem:l_orderkey:8'"
        )
        sql = (
            "select count(*) from partsupp where ps_partkey in "
            "(select l_partkey from lineitem "
            "where l_orderkey > partsupp.ps_availqty)"
        )
        assert d.execute(sql).rows == local.execute(sql).rows

    def test_speculative_off_matches_on(self, dist, local):
        sql = (
            "select o_orderstatus, count(*) from lineitem join orders "
            "on l_orderkey = o_orderkey group by o_orderstatus"
        )
        on = dist.execute(sql).rows
        dist.execute("set session join_speculative_capacity = 'off'")
        try:
            off = dist.execute(sql).rows
            assert dist.last_mesh_profile.counters.get(
                "join_capacity_sync", 0
            ) >= 1
        finally:
            dist.execute("set session join_speculative_capacity = 'on'")
        assert sorted(on) == sorted(off) == sorted(local.execute(sql).rows)
