#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the SQL path still starts on the chip.

One process, one chip.  Drives the engine's main path once through the entry
points a user would call — `LocalQueryRunner`, `DistributedQueryRunner` (a
mesh of 1, so the `shard_map` fragments and their collectives are lowered by
the chip's compiler), `CoordinatorServer` + `trino_tpu.client.Client` over
HTTP — at TPC-H SF1 (and the scan-bound pair at SF10 when the time limit
allows), and checks every answer against a reference that is independent of
the engine: exact int64 arithmetic over the connector's host columns for the
decimal sums of Q1/Q6, the repo's pandas oracle (tests/tpch_oracle.py) for
all four queries.  Data is generated in code by the `tpch` connector.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the cross-chip path only: 4-worker mesh

Earlier lines are free-form JSON facts (walls, compile seconds, cache
hits/misses, memory); the LAST line is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and is printed only when every phase passed.  No accelerator -> non-zero
exit, no `ok` line.  There is no probe subprocess, no re-exec and no retry
on another backend.
"""

from __future__ import annotations

import argparse
import datetime
import functools
import json
import math
import statistics
import sys
import time
import traceback
from decimal import Decimal

import numpy as np
import pandas as pd

#: seconds of the driver's 1200 s limit this script plans to use; optional
#: phases (SF10) are skipped, with the reason printed, when they would not fit
TIME_BUDGET_S = 1000.0

#: TPC-H columns the four smoke queries read (the oracle frames hold only
#: these: a full SF1 lineitem frame is 1.3 GB and 20 s of set-up)
ORACLE_COLUMNS = {
    "lineitem": [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate",
    ],
    "orders": [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
        "o_totalprice",
    ],
    "customer": ["c_custkey", "c_name", "c_mktsegment"],
}

#: ORDER BY ... LIMIT queries compare positionally
ORDERED = {1, 3, 18}

PALLAS_SQL = (
    "select o_orderstatus, o_orderpriority, count(*), "
    "sum(cast(o_totalprice as double)), avg(cast(o_totalprice as double)) "
    "from orders group by o_orderstatus, o_orderpriority"
)

MESH_AGG_SQL = (
    "select o_custkey, count(*), sum(o_totalprice) from orders "
    "group by o_custkey"
)
MESH_BROADCAST_SQL = (
    "select n_name, count(*), sum(c_acctbal) from customer join nation "
    "on c_nationkey = n_nationkey group by n_name"
)


def say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


# -- references ----------------------------------------------------------------


#: lineitem columns of the exact references, ONE set for both queries so
#: the column cache generates them once per schema (SF10: ~40 s)
EXACT_COLUMNS = (
    "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
    "l_discount", "l_tax", "l_shipdate",
)

@functools.lru_cache(maxsize=None)
def _lineitem(schema: str) -> dict:
    """Full host columns of lineitem, concatenated across the connector's
    pages: {name: (values, dictionary or None)}."""
    from trino_tpu.connectors.api import TableHandle
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector()
    names = list(EXACT_COLUMNS)
    parts: dict[str, list] = {n: [] for n in names}
    dicts: dict[str, object] = {}
    handle = TableHandle("tpch", schema, "lineitem")
    for split in conn.splits(handle, target_splits=1):
        src = conn.page_source(split, names, max_rows_per_page=1 << 22)
        for page in src.pages():
            for n, cd in zip(names, page):
                parts[n].append(np.asarray(cd.values))
                dicts[n] = cd.dictionary
    return {n: (np.concatenate(parts[n]), dicts.get(n)) for n in names}


def _days(date: str) -> int:
    return int(
        (np.datetime64(date) - np.datetime64("1970-01-01")).astype(int)
    )


def _avg_half_up(total: int, n: int, scale: int) -> Decimal:
    """avg(decimal) as the engine defines it: rounded half-up to the
    argument's scale (non-negative totals)."""
    return Decimal((2 * total + n) // (2 * n)).scaleb(-scale)


def exact_q1(schema: str) -> list:
    """Q1 in exact int64 arithmetic over the connector's host columns."""
    cols = _lineitem(schema)
    rf, rf_dict = cols["l_returnflag"]
    ls, ls_dict = cols["l_linestatus"]
    qty, price, disc, tax, ship = (
        cols[c][0] for c in
        ("l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_shipdate")
    )
    live = ship <= _days("1998-09-02")
    nls = len(ls_dict.values)
    key = np.where(live, rf.astype(np.int64) * nls + ls, -1)
    rows = []
    for k in np.unique(key[live]):
        sel = key == k
        q, p, d, t = qty[sel], price[sel], disc[sel], tax[sel]
        n = int(sel.sum())
        disc_price = p * (100 - d)  # scale 4
        charge = disc_price * (100 + t)  # scale 6
        rows.append((
            rf_dict.values[int(k) // nls], ls_dict.values[int(k) % nls],
            Decimal(int(q.sum())).scaleb(-2),
            Decimal(int(p.sum())).scaleb(-2),
            Decimal(int(disc_price.sum())).scaleb(-4),
            Decimal(int(charge.sum())).scaleb(-6),
            _avg_half_up(int(q.sum()), n, 2),
            _avg_half_up(int(p.sum()), n, 2),
            _avg_half_up(int(d.sum()), n, 2),
            n,
        ))
    return sorted(rows, key=lambda r: r[:2])


def exact_q6(schema: str) -> list:
    cols = _lineitem(schema)
    price, disc, qty, ship = (
        cols[c][0] for c in
        ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")
    )
    m = (
        (ship >= _days("1994-01-01")) & (ship < _days("1995-01-01"))
        & (disc >= 5) & (disc <= 7) & (qty < 2400)
    )
    return [(Decimal(int((price[m] * disc[m]).sum())).scaleb(-4),)]


EXACT = {1: exact_q1, 6: exact_q6}


class PandasOracle:
    """The repo's independent oracle (tests/tpch_oracle.py) over frames that
    hold only the smoke queries' columns, materialized once per schema."""

    def __init__(self, schema: str):
        self.schema = schema
        self._frames: dict = {}

    def _frame(self, table: str):
        if table not in self._frames:
            from trino_tpu.connectors.tpch import TpchConnector
            from trino_tpu.testing.oracle import connector_table_to_pandas

            self._frames[table] = connector_table_to_pandas(
                TpchConnector(), self.schema, table, ORACLE_COLUMNS[table]
            )
        return self._frames[table]

    def rows(self, query: int) -> list:
        from tests.tpch_oracle import ORACLES

        df = ORACLES[query](self._frame)
        return [
            tuple(
                None if isinstance(v, float) and math.isnan(v) else v
                for v in r
            )
            for r in df.itertuples(index=False)
        ]


def _norm(v):
    if isinstance(v, (datetime.date, pd.Timestamp)):
        return pd.Timestamp(v)
    if isinstance(v, np.generic):
        return v.item()
    return v


def _same(a, e, atol: float) -> bool:
    """`a` from the engine, `e` from a reference.  Equal types compare
    exactly; an engine decimal against the float oracle compares exactly
    after rounding the float to the decimal's scale when it has the
    precision to carry it (15 significant digits), else — and for doubles —
    to the oracle's tolerance (tests/test_e2e.assert_rows_match)."""
    a, e = _norm(a), _norm(e)
    if a is None or e is None:
        return a is None and e is None
    if isinstance(a, Decimal) and isinstance(e, float) and atol < 1e-3:
        quantum = Decimal(1).scaleb(a.as_tuple().exponent)
        if abs(a) < Decimal(10) ** 14 * quantum:
            return a == Decimal(repr(e)).quantize(quantum)
    if isinstance(a, float) or isinstance(e, float):
        return math.isclose(float(a), float(e), rel_tol=1e-9, abs_tol=atol)
    if isinstance(a, Decimal) or isinstance(e, Decimal):
        return Decimal(str(a)) == Decimal(str(e))
    return a == e


def check_rows(what: str, actual, expected, ordered: bool, atol=1e-6) -> None:
    actual, expected = list(actual), list(expected)
    if len(actual) != len(expected):
        raise AssertionError(
            f"{what}: {len(actual)} rows != expected {len(expected)}"
        )
    if not ordered:
        key = lambda r: tuple("\0" if v is None else str(_norm(v)) for v in r)
        actual, expected = sorted(actual, key=key), sorted(expected, key=key)
    for i, (ra, re) in enumerate(zip(actual, expected)):
        if len(ra) != len(re) or not all(
            _same(va, ve, atol) for va, ve in zip(ra, re)
        ):
            raise AssertionError(
                f"{what}: row {i} differs\n  engine   ={tuple(ra)}\n"
                f"  reference={tuple(re)}"
            )


# -- measurement ---------------------------------------------------------------


class CompileWatch:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events (no engine code on this path)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": round(self.compile_s, 3),
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }


def agg_paths() -> dict:
    from trino_tpu.telemetry.metrics import (
        AGGREGATION_PATHS,
        aggregation_path_counter,
    )

    c = aggregation_path_counter()
    return {p: int(c.value((p,))) for p in AGGREGATION_PATHS}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before[k] for k, v in after.items() if v != before[k]}


def memory_facts() -> dict:
    import jax

    from trino_tpu.runtime.buffer_pool import POOL

    stats = jax.devices()[0].memory_stats() or {}
    return {
        "pool_device_bytes": POOL.stats()["device_bytes"],
        "pool_device_budget_bytes": POOL.device.limit_bytes,
        "device_bytes_limit": stats.get("bytes_limit"),
        "device_peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "device_bytes_in_use": stats.get("bytes_in_use"),
    }


def timed_query(runner, watch: CompileWatch, sql: str, warm_runs: int,
                reload: bool) -> tuple:
    """rows + walls of one statement.  `first` compiles, generates and
    transfers; `reload` (pool cleared, programs compiled) is generation +
    host->device transfer + execution; `warm` is the steady state with the
    scan resident in the pool's device tier.  Every wall ends when the
    result rows are materialized on the host."""
    from trino_tpu.runtime.buffer_pool import POOL

    c0, paths0 = watch.compile_s, agg_paths()
    t0 = time.perf_counter()
    rows = runner.execute(sql).rows
    facts = {
        "first_s": round(time.perf_counter() - t0, 4),
        "compile_s": round(watch.compile_s - c0, 3),
        "agg_paths": _delta(agg_paths(), paths0),
    }
    if reload:
        POOL.clear()
        t0 = time.perf_counter()
        runner.execute(sql)
        facts["reload_s"] = round(time.perf_counter() - t0, 4)
    warm = []
    for _ in range(warm_runs):
        t0 = time.perf_counter()
        rows = runner.execute(sql).rows
        warm.append(time.perf_counter() - t0)
    if warm:
        facts["warm_s"] = warm
        facts["warm_median_s"] = statistics.median(warm)
        # set-up (generation + host->device transfer), apart from query time
        basis = facts.get("reload_s", facts["first_s"] - facts["compile_s"])
        facts["load_s"] = round(max(0.0, basis - facts["warm_median_s"]), 4)
    return rows, facts


class Phases:
    """Runs named phases; a failure is printed and remembered, the other
    phases still run, and the script then exits non-zero with no `ok`."""

    def __init__(self):
        self.failed: list = []
        self.t0 = time.perf_counter()

    def remaining(self) -> float:
        return TIME_BUDGET_S - (time.perf_counter() - self.t0)

    def run(self, name: str, fn, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        except Exception as exc:
            traceback.print_exc()
            self.failed.append(name)
            say(phase=name, passed=False,
                wall_s=round(time.perf_counter() - t0, 3),
                error=f"{type(exc).__name__}: {exc}"[:2000])
            return None
        say(phase=name, passed=True, wall_s=round(time.perf_counter() - t0, 3))
        return out


# -- one chip ------------------------------------------------------------------


def local_query(runner, watch, schema: str, q: int, warm_runs: int,
                reload: bool, oracle) -> list:
    """One TPC-H query through LocalQueryRunner, checked against the exact
    integer reference (Q1/Q6) and, given a PandasOracle, against it."""
    from trino_tpu.connectors.tpch.queries import QUERIES

    rows, facts = timed_query(runner, watch, QUERIES[q], warm_runs, reload)
    say(query=f"q{q}", schema=schema, runner="local", rows=len(rows), **facts)
    checked = []
    t0 = time.perf_counter()
    if q in EXACT:
        check_rows(f"{schema} q{q} vs exact", rows, EXACT[q](schema), True, 0.0)
        checked.append("exact_int64")
    if oracle is not None:
        check_rows(
            f"{schema} q{q} vs pandas oracle", rows,
            oracle.rows(q), q in ORDERED,
            # avg(decimal) rounds to scale in the engine; the oracle
            # keeps float precision (tests/test_e2e._DECIMAL_AVG)
            atol=0.0051 if q == 1 else 1e-6,
        )
        checked.append("pandas_oracle")
    say(query=f"q{q}", schema=schema, runner="local", matches=checked,
        reference_s=round(time.perf_counter() - t0, 3))
    return rows


def pallas_agg_query(schema: str) -> None:
    """The Mosaic grouped-aggregation kernel behind `pallas_agg`, compiled
    by the chip (interpret mode only off-TPU), against the default path."""
    from trino_tpu.runtime.runner import LocalQueryRunner

    expected = LocalQueryRunner(
        catalog="tpch", schema=schema, target_splits=8
    ).execute(PALLAS_SQL).rows
    fast = LocalQueryRunner(catalog="tpch", schema=schema, target_splits=8)
    fast.execute("set session pallas_agg = true")
    before = agg_paths()
    actual = fast.execute(PALLAS_SQL).rows
    took = _delta(agg_paths(), before)
    if not took.get("pallas"):
        raise AssertionError(f"pallas kernel did not engage: {took}")
    # f32 accumulation (the property's documented contract)
    norm = lambda rows: [
        tuple(float(v) if isinstance(v, (float, Decimal)) else v for v in r)
        for r in rows
    ]
    a, e = sorted(norm(actual)), sorted(norm(expected))
    if len(a) != len(e):
        raise AssertionError(f"pallas_agg: {len(a)} rows != {len(e)}")
    for ra, re in zip(a, e):
        for va, ve in zip(ra, re):
            ok = (
                math.isclose(va, ve, rel_tol=1e-4)
                if isinstance(va, float) else va == ve
            )
            if not ok:
                raise AssertionError(f"pallas_agg: {ra} != {re}")
    say(query="pallas_agg", schema=schema, rows=len(actual), agg_paths=took)


def mesh_of_one(schema: str, join_schema: str, watch, local_q1) -> None:
    """One grouped aggregation (Q1) and one join through
    DistributedQueryRunner on a mesh of 1: the shard_map fragments and
    their collectives (all_to_all repartition, all_gather broadcast) go
    through the chip's compiler on the one-chip run too.

    The join runs at `join_schema`, not SF1: mesh-1 Q3 at SF1 matched the
    local answer on the v5e but COMPILED for 288 s (sorts at 2^23 rows; PR
    21 chip run 2), which does not fit the 1200 s limit beside local
    Q3/Q18 at SF1.  The four-chip path runs Q3 at SF1 (`--chips 4`)."""
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.runtime.runner import LocalQueryRunner

    from trino_tpu.connectors.tpch.queries import QUERIES

    if local_q1 is None:
        raise AssertionError("no local q1 answer to compare with")
    say(note="mesh-of-1 join runs at a reduced schema", schema=join_schema,
        why="Q3 at SF1 on a mesh of 1 compiled for 288 s on the v5e (PR 21); "
            "it does not fit the 1200 s limit beside local Q3/Q18 at SF1")
    jobs = (
        ("q1", schema, QUERIES[1], True, local_q1),
        ("broadcast_join", join_schema, MESH_BROADCAST_SQL, False, None),
    )
    for name, sch, sql, ordered, expected in jobs:
        dist = DistributedQueryRunner(n_workers=1, schema=sch)
        rows, facts = timed_query(dist, watch, sql, 1, False)
        if expected is None:
            expected = LocalQueryRunner(
                catalog="tpch", schema=sch, target_splits=8
            ).execute(sql).rows
        check_rows(f"{sch} {name} mesh-1 vs local", rows, expected, ordered, 0.0)
        say(query=name, schema=sch, runner="distributed", workers=1,
            rows=len(rows), matches=["local"],
            collective_bytes_by=dist.last_mesh_profile.to_json()[
                "collective_bytes_by"],
            **facts)


def server_path(runner, schema: str, local_answers: dict) -> None:
    """The same statements over HTTP: dispatcher, paging, result serde."""
    from trino_tpu.client import Client
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.server.coordinator import CoordinatorServer

    server = CoordinatorServer(runner=runner, port=0)
    server.start()
    try:
        client = Client(f"http://127.0.0.1:{server.port}")
        for q in sorted(local_answers):
            t0 = time.perf_counter()
            _, rows = client.execute(QUERIES[q])
            wall = time.perf_counter() - t0
            check_rows(f"{schema} q{q} over http vs local",
                       [tuple(r) for r in rows], local_answers[q],
                       q in ORDERED, 0.0)
            say(query=f"q{q}", schema=schema, runner="server+client",
                rows=len(rows), wall_s=round(wall, 4), matches=["local"])
    finally:
        server.shutdown()


def one_chip(phases: Phases, watch, schema: str, big_schema) -> None:
    from trino_tpu.runtime.buffer_pool import POOL
    from trino_tpu.runtime.runner import LocalQueryRunner

    runner = LocalQueryRunner(catalog="tpch", schema=schema, target_splits=8)
    oracle = PandasOracle(schema)
    answers = {}
    for q in (1, 6, 3, 18):
        rows = phases.run(
            f"local:{schema}:q{q}", local_query, runner, watch, schema, q,
            3, True, oracle,
        )
        if rows is not None:
            answers[q] = rows
    say(memory=memory_facts(), after=f"local:{schema}")
    phases.run(f"pallas_agg:{schema}", pallas_agg_query, schema)
    phases.run(f"server:{schema}", server_path, runner, schema, answers)
    phases.run(f"mesh1:{schema}", mesh_of_one, schema, "tiny", watch,
               answers.get(1))
    say(memory=memory_facts(), after=f"mesh1:{schema}")
    if big_schema is None:
        return
    # The north star is ~SF12 a chip: the scan-bound pair once at SF10,
    # if generating and loading it fits the limit.  Generation scales with
    # rows; budget it from what SF1 measured.
    POOL.clear()
    need = 200.0
    if phases.remaining() < need:
        say(phase=f"local:{big_schema}", skipped=True,
            why=f"{phases.remaining():.0f}s of the time budget left, "
                f"generation + load + compile of {big_schema} needs ~{need:.0f}s")
        return
    big = LocalQueryRunner(catalog="tpch", schema=big_schema, target_splits=8)
    for q in (1, 6):
        phases.run(
            f"local:{big_schema}:q{q}", local_query, big, watch, big_schema,
            q, 2, False, None,
        )
    say(memory=memory_facts(), after=f"local:{big_schema}")


# -- four chips ----------------------------------------------------------------


def four_chips(phases: Phases, watch, schema: str, chips: int,
               session: dict) -> None:
    """The cross-chip path, and nothing else: a hash-repartitioned grouped
    aggregation (all_to_all), a broadcast join and a partitioned join (Q3) on
    `chips` real devices, each compared row-for-row with LocalQueryRunner."""
    import jax

    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.parallel.spmd import mesh_key
    from trino_tpu.runtime.buffer_pool import POOL
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.telemetry.metrics import plan_decisions_counter

    dist = DistributedQueryRunner(n_workers=chips, schema=schema)
    local = LocalQueryRunner(catalog="tpch", schema=schema, target_splits=8)
    for name, value in session.items():
        dist.execute(f"set session {name} = {value}")
    decisions = plan_decisions_counter()

    def joins(kind: str) -> int:
        return int(decisions.value(("join_distribution", kind, "pending")))

    def compare(name: str, sql: str, ordered: bool, collective: str,
                join_kind=None) -> None:
        j0 = joins(join_kind) if join_kind else 0
        rows, facts = timed_query(dist, watch, sql, 1, False)
        by = dist.last_mesh_profile.to_json()["collective_bytes_by"]
        if not by.get(collective):
            raise AssertionError(f"{name}: no {collective} bytes in {by}")
        if join_kind and joins(join_kind) == j0:
            raise AssertionError(f"{name}: no {join_kind} join was planned")
        t0 = time.perf_counter()
        expected = local.execute(sql).rows
        check_rows(f"{name} mesh-{chips} vs local", rows, expected, ordered, 0.0)
        say(query=name, schema=schema, runner="distributed", workers=chips,
            rows=len(rows), matches=["local"], collective_bytes_by=by,
            local_first_s=round(time.perf_counter() - t0, 4), **facts)

    phases.run("mesh:repartitioned_agg", compare, "repartitioned_agg",
               MESH_AGG_SQL, False, "all_to_all/repartition")
    phases.run("mesh:broadcast_join", compare, "broadcast_join",
               MESH_BROADCAST_SQL, False, "all_gather/broadcast", "broadcast")
    phases.run("mesh:partitioned_join_q3", compare, "q3", QUERIES[3], True,
               "all_to_all/repartition", "partitioned")

    def placement() -> None:
        """Stacked scan batches must be committed to `chips` DISTINCT
        devices, one [1, cap] shard each — not all to device 0."""
        want = {d.id for d in dist.wm.devices}
        seen = 0
        for key, (batches, _) in POOL.device.entries.items():
            if key[:2] != ("mesh_scan", mesh_key(dist.wm)):
                continue  # another mesh's entries (none in a --chips run)
            for leaf in jax.tree.leaves(batches[0]):
                shards = leaf.addressable_shards
                got = {s.device.id for s in shards}
                if got != want or any(s.data.shape[0] != 1 for s in shards):
                    raise AssertionError(
                        f"scan {key[3][0][2]}: leaf {leaf.shape} lives on "
                        f"devices {sorted(got)}, wanted one row each on "
                        f"{sorted(want)}"
                    )
                seen += 1
        if not seen:
            raise AssertionError("no mesh_scan entries in the device pool")
        say(check="scan_placement", arrays=seen, devices=sorted(want))

    phases.run("mesh:scan_placement", placement)
    say(memory=memory_facts(), after=f"mesh{chips}:{schema}")


# -- entry ---------------------------------------------------------------------


def run(chips: int = 1, platform: str = "tpu", schema: str = "sf1",
        big_schema="sf10", session=None) -> int:
    """The whole smoke.  `platform`, `schema`, `big_schema` and `session`
    exist for the CPU rehearsal (tests/test_chip_smoke.py calls this at
    `tpch.tiny` on the virtual CPU devices); the command line reaches none
    of them."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != platform:
        print(
            f"chip_smoke: jax.devices()[0].platform is "
            f"{device['platform']!r}, not {platform!r}: no accelerator, "
            f"nothing was run", file=sys.stderr,
        )
        return 1
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, jax "
              f"reports {len(devices)}", file=sys.stderr)
        return 1

    from trino_tpu.parallel.spmd import configure_persistent_cache

    watch = CompileWatch()
    say(device=device, jax=jax.__version__, chips=chips, schema=schema,
        compile_cache_dir=configure_persistent_cache())
    phases = Phases()
    if chips > 1:
        four_chips(phases, watch, schema, chips, session or {})
    else:
        one_chip(phases, watch, schema, big_schema)
    say(summary=True, total_s=round(time.perf_counter() - phases.t0, 2),
        agg_paths=agg_paths(), failed=phases.failed, **watch.snapshot())
    if phases.failed:
        print(f"chip_smoke: FAILED phases: {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = run ONLY the cross-chip path (4-worker mesh vs the "
        "single-device answers); default 1 = the full one-chip smoke",
    )
    args = ap.parse_args(argv)
    return run(chips=args.chips)


if __name__ == "__main__":
    sys.exit(main())
