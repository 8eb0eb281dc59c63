"""Distributed (8-virtual-worker mesh) vs local runner equivalence.

Reference style: AbstractTestDistributedQueries / the DistributedQueryRunner
multi-node-in-one-JVM trick (testing/trino-testing/.../
DistributedQueryRunner.java:84) — N workers are N host devices, exchanges run
as real collectives (all_to_all / all_gather) over the virtual mesh, and the
plan is cut into fragments with explicit partitioning handles
(PlanFragmenter.java:116 analog, planner/fragmenter.py).
"""

import pytest


from tests.test_e2e import assert_rows_match
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.parallel import DistributedQueryRunner
from trino_tpu.runtime.runner import LocalQueryRunner

pytestmark = pytest.mark.heavy


@pytest.fixture(scope="module")
def dist():
    return DistributedQueryRunner(n_workers=8)


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner(target_splits=3)


CASES = [
    "select count(*), sum(n_nationkey), min(n_name), max(n_name) from nation",
    "select n_regionkey, count(*), sum(n_nationkey) from nation group by n_regionkey",
    "select r_name, count(*) c from nation join region on n_regionkey = r_regionkey group by r_name",
    "select count(*) from customer where c_custkey in (select o_custkey from orders)",
    "select o_orderstatus, count(*) from orders where o_totalprice > 100000 group by o_orderstatus",
    "select c_mktsegment, count(*) from customer join orders on c_custkey = o_custkey group by c_mktsegment",
    # distributed window: repartition on partition keys, per-worker kernel
    "select n_name, row_number() over (partition by n_regionkey order by n_name) from nation",
    # distributed topN: per-worker partial top-k + merge exchange
    "select o_orderkey, o_totalprice from orders order by o_totalprice desc limit 5",
    # distributed sort: per-worker partial sort + ordered merge of shards
    "select c_name from customer order by c_name",
    # distributed limit: per-worker partial limit + final limit
    "select count(*) from (select o_orderkey from orders limit 500) t",
]


@pytest.mark.parametrize("sql", CASES)
def test_dist_matches_local(dist, local, sql):
    d = dist.execute(sql)
    l = local.execute(sql)
    if "limit 500" in sql:  # limit row-set is nondeterministic; count only
        assert d.rows == l.rows
    else:
        assert_rows_match(d.rows, l.rows, ordered=False)


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_dist_tpch(dist, local, qid):
    d = dist.execute(QUERIES[qid])
    l = local.execute(QUERIES[qid])
    assert_rows_match(d.rows, l.rows, ordered=_is_ordered(qid))


def _is_ordered(qid: int) -> bool:
    # queries whose outermost clause is ORDER BY without ties-ambiguity
    return qid in (3,)


def test_explain_shows_fragments(dist):
    text = dist.explain_distributed(
        "select n_regionkey, count(*) from nation group by n_regionkey"
    )
    assert "Fragment 0 [SOURCE]" in text
    assert "FIXED_HASH[n_regionkey]" in text
    assert "RemoteSource" in text and "repartition" in text
    assert "gather" in text


def test_agg_and_join_stay_distributed(dist):
    """Aggregations and joins must execute in distributed fragments — the
    round-2 silent coordinator fallback is structurally gone."""
    text = dist.explain_distributed(
        "select c_mktsegment, count(*) from customer join orders "
        "on c_custkey = o_custkey group by c_mktsegment"
    )
    import re

    # the fragment holding the Aggregation/Join must not be SINGLE
    for frag in re.split(r"(?=Fragment \d)", text):
        if "Aggregation" in frag and "RemoteSource" in frag:
            assert "[SINGLE]" not in frag.splitlines()[0]
        if "Join" in frag:
            assert "[SINGLE]" not in frag.splitlines()[0]


def test_topn_merge_path(dist):
    """ORDER BY + LIMIT plans as per-worker partial TopN below a merge
    exchange — raw rows are never gathered (MergeOperator role)."""
    text = dist.explain_distributed(
        "select o_orderkey from orders order by o_totalprice desc limit 7"
    )
    assert "merge" in text
    # the producing fragment carries the partial TopN
    import re

    frags = re.split(r"(?=Fragment \d)", text)
    partial = [f for f in frags if "TopN" in f and "TableScan" in f]
    assert partial, f"no partial TopN fragment:\n{text}"


def test_sort_merge_exchange(dist, local):
    """Full ORDER BY: per-worker sorted shards merged order-preserving."""
    sql = "select o_totalprice from orders order by o_totalprice"
    d = dist.execute(sql)
    l = local.execute(sql)
    assert d.rows == l.rows  # ordered comparison: merge must preserve order
    text = dist.explain_distributed(sql)
    assert "merge" in text and "Sort" in text


def test_set_session_changes_distribution(dist):
    """join_distribution_type is read by the exchange placer."""
    sql = (
        "select count(*) from lineitem join orders on l_orderkey = o_orderkey"
    )
    dist.execute("set session join_distribution_type = 'PARTITIONED'")
    part = dist.explain_distributed(sql)
    dist.execute("set session join_distribution_type = 'BROADCAST'")
    bc = dist.explain_distributed(sql)
    dist.execute("set session join_distribution_type = 'AUTOMATIC'")
    assert "dist=partitioned" in part
    assert "dist=broadcast" in bc


# -- round 4: distributed full/right joins, filtered semi, dynamic filters --


def test_full_join_distributed_partitioned(dist, local):
    """FULL joins repartition (a broadcast build would duplicate the
    unmatched tail per worker) — reference: AddExchanges join handling."""
    sql = (
        "select s_name, c_name from supplier full outer join customer "
        "on s_nationkey = c_custkey"
    )
    text = dist.explain_distributed(sql)
    assert "dist=partitioned" in text and "FIXED_HASH" in text
    d = sorted(map(str, dist.execute(sql).rows))
    l = sorted(map(str, local.execute(sql).rows))
    assert d == l


def test_right_join_distributed(dist, local):
    sql = (
        "select n_name, s_name from supplier right join nation "
        "on s_nationkey = n_nationkey"
    )
    text = dist.explain_distributed(sql)
    assert "Join[left]" in text  # flipped for distribution
    d = sorted(map(str, dist.execute(sql).rows))
    l = sorted(map(str, local.execute(sql).rows))
    assert d == l


def test_filtered_semi_join_distributed(dist, local):
    """Correlated-EXISTS residual semi joins repartition on the key instead
    of collapsing to SINGLE."""
    sql = (
        "select count(*) from lineitem l1 where l_orderkey in "
        "(select o_orderkey from orders where o_totalprice > l1.l_extendedprice)"
    )
    text = dist.explain_distributed(sql)
    assert "SemiJoin" in text and "repartition" in text
    assert dist.execute(sql).rows == local.execute(sql).rows


def test_dynamic_filter_prunes_distributed_scan(dist, local):
    """Build-side key ranges prune probe scans across fragments
    (reference: server/DynamicFilterService.java:107).  The before/after
    pruning counts are LAZY: a plain execution records none (it would cost
    an extra execution of the whole scan chain); EXPLAIN ANALYZE computes
    them."""
    sql = (
        "select count(*), sum(l_quantity) from lineitem join "
        "(select o_orderkey from orders where o_orderkey < 500) o "
        "on l_orderkey = o_orderkey"
    )
    assert dist.execute(sql).rows == local.execute(sql).rows
    assert dist.last_stage_executor.dynamic_filter_stats == {}
    dist.execute("explain analyze " + sql)
    stats = dist.last_stage_executor.dynamic_filter_stats
    before, after = stats["lineitem"]
    assert after < before  # rows dropped at the feed, not at the join


@pytest.mark.smoke
def test_grouped_percentile_stays_distributed(dist, local):
    """Grouped approx_percentile repartitions whole groups instead of
    gathering all rows to the coordinator (the approx_distinct-style
    scalability trap the round-3 review flagged)."""
    sql = (
        "select l_returnflag, approx_percentile(l_extendedprice, 0.5) "
        "from lineitem group by l_returnflag order by 1"
    )
    txt = dist.explain_distributed(sql)
    assert "FIXED_HASH[l_returnflag]" in txt  # not a SINGLE gather
    assert dist.execute(sql).rows == local.execute(sql).rows


@pytest.mark.smoke
def test_grouped_distinct_stays_distributed(dist, local):
    """Uniform grouped DISTINCT repartitions + dedupes per worker instead of
    gathering (same shape fix as percentile)."""
    sql = (
        "select l_returnflag, count(distinct l_suppkey) from lineitem "
        "group by l_returnflag order by 1"
    )
    txt = dist.explain_distributed(sql)
    assert "FIXED_HASH[l_returnflag]" in txt
    assert dist.execute(sql).rows == local.execute(sql).rows


# -- PR 1: device-resident mesh pipeline + per-fragment profile --


def test_mesh_profile_breakdown(dist):
    """Every distributed query records a per-fragment, per-phase breakdown
    whose phases sum to the fragment wall (the `other` bucket absorbs the
    untracked remainder, so the invariant is exact)."""
    dist.execute(
        "select n_regionkey, count(*), sum(n_nationkey) from nation "
        "group by n_regionkey"
    )
    prof = dist.last_mesh_profile
    assert prof is not None and prof.fragments
    for st in prof.fragments.values():
        assert st.kind, "partitioning handle recorded per fragment"
        assert set(st.phases) >= {
            "trace", "compute", "collective", "transfer", "other"
        }
        # `other` absorbs the untracked remainder, so the sum matches the
        # wall up to timer skew between adjacent perf_counter windows
        assert abs(sum(st.phases.values()) - st.wall_s) <= max(
            0.005, 0.05 * st.wall_s
        )
    # the JSON form (bench evidence) carries the same fields
    js = prof.to_json()
    assert js["fragments"] and "trace_cache" in js
    assert all("phases_ms" in f and "kind" in f for f in js["fragments"])


def test_mesh_no_host_roundtrip_between_fragments(dist):
    """A multi-fragment mesh query hands batches between distributed
    fragments as device-resident sharded arrays: the host_restack counter
    (host batches re-entering the mesh mid-query) and host_gather counter
    (device results pulled to host before the final result read) both stay
    zero — only the root result_gather touches the host."""
    sql = (
        "select n_regionkey, count(*), sum(n_nationkey) from nation "
        "group by n_regionkey"
    )
    dist.execute(sql)
    prof = dist.last_mesh_profile
    assert len(prof.fragments) >= 2, "expected a multi-fragment plan"
    assert prof.counters.get("host_restack", 0) == 0
    assert prof.counters.get("host_gather", 0) == 0
    assert prof.counters.get("result_gather", 0) >= 1


def test_mesh_trace_cache_warm_zero_retraces(dist):
    """Repeated same-bucket batches reuse compiled SPMD programs: after a
    warmup execution, re-running the query performs ZERO retraces and the
    trace cache reports hits (the per-execution recompile was the dominant
    mesh cost before the trace cache)."""
    from trino_tpu.parallel.spmd import TRACE_CACHE

    sql = (
        "select o_orderstatus, count(*) from orders "
        "where o_totalprice > 1000 group by o_orderstatus"
    )
    dist.execute(sql)  # warmup: traces + compiles
    r0 = TRACE_CACHE.retraces
    dist.execute(sql)
    prof = dist.last_mesh_profile
    assert TRACE_CACHE.retraces == r0, "warm run must not retrace"
    assert prof.retraces == 0
    assert prof.trace_hits > 0 and prof.trace_misses == 0


def test_explain_analyze_distributed_shows_fragment_phases(dist):
    """EXPLAIN ANALYZE on a distributed query renders the per-fragment
    collective/compute/transfer timings and the trace-cache counters."""
    out = dist.execute(
        "explain analyze select n_regionkey, count(*) from nation "
        "group by n_regionkey"
    )
    text = "\n".join(r[0] for r in out.rows)
    assert "Mesh execution profile" in text
    assert "Fragment" in text and "collective=" in text
    assert "compute=" in text and "transfer=" in text
    assert "trace cache:" in text


def test_string_join_distinct_dictionaries_recode(dist, local):
    """Each join side bakes its OWN dictionary-recode table into its
    compiled program; the trace-cache keys must differ even when both key
    columns sit at the same channel index (regression: a shared key reused
    side A's translation table for side B, silently corrupting the join)."""
    sql = (
        "select count(*) from "
        "(select l_linestatus s from lineitem where l_orderkey < 100) l "
        "join (select o_orderstatus s2 from orders where o_orderkey < 100) o "
        "on l.s = o.s2"
    )
    d = dist.execute(sql).rows
    l = local.execute(sql).rows
    assert d == l and d[0][0] > 0, (d, l)


# -- the mesh join's locate: direct table over the build keys' domain --------


def _join_locates(dist):
    import json

    _, flat = dist.traces[-1]
    return [
        json.loads(s["attributes"])["locate"]
        for s in flat if s["name"] == "join"
    ]


@pytest.mark.parametrize("distribution", ["AUTOMATIC", "PARTITIONED"])
def test_q3_locates_by_table(dist, local, distribution):
    """Q3's two inner joins key on domains the dynamic-filter summary holds
    and the shared rule admits: each shard indexes its build by direct
    table, broadcast or partitioned, and answers as the local runner."""
    dist.execute(f"set session join_distribution_type = '{distribution}'")
    try:
        d = dist.execute(QUERIES[3])
        locates = _join_locates(dist)
        placed = dist.explain_distributed(QUERIES[3])
    finally:
        dist.execute("set session join_distribution_type = 'AUTOMATIC'")
    assert_rows_match(d.rows, local.execute(QUERIES[3]).rows, ordered=True)
    assert locates == ["join_locate_table"] * 2
    assert ("dist=partitioned" in placed) == (distribution == "PARTITIONED")


_EDGES = (
    "(select case when c_custkey % 7 = 0 then null else c_custkey end k "
    "from customer) c {kind} join (select o_custkey k2, o_totalprice p "
    "from orders where o_custkey between 100 and 200) o on c.k = o.k2"
)


@pytest.mark.parametrize("sql, locate", [
    # probe keys below and above the build's domain, NULL probe keys, and
    # build keys held by many orders (a run longer than one)
    ("select count(*), sum(c.k), sum(o.p) from " + _EDGES.format(kind=""),
     "join_locate_table"),
    # a domain the rule refuses: 150 build keys spread over 1.5e10 codes
    ("select count(*), sum(o.p) from (select o_custkey * 10000000 k2, "
     "o_totalprice p from orders) o join (select c_custkey * 10000000 k "
     "from customer where c_nationkey < 5) c on o.k2 = c.k",
     "join_locate_sorted"),
    # only an inner join has a domain summary
    ("select count(*), sum(c.k), count(o.k2) from "
     + _EDGES.format(kind="left"), "join_locate_sorted"),
], ids=["table_edges", "sparse_domain", "left_join"])
def test_join_locate_follows_the_domain(dist, local, sql, locate):
    d = dist.execute(sql).rows
    assert d == local.execute(sql).rows and d[0][0] > 0
    assert _join_locates(dist) == [locate]
