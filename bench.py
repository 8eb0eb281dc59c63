"""Benchmark harness: TPC-H on the engine, one JSON line on stdout.

Reference role: testing/trino-benchmark (AbstractOperatorBenchmark /
HandTpchQuery1.java:48 print rows/s on a LocalQueryRunner) + the benchto
tpch.yaml workload definitions.

ONE process, on the backend JAX gives it.  There is no probe subprocess, no
re-exec, no retry on another backend and no `except` that turns a failed
phase into exit 0: if the device cannot be reached, or a phase fails, the
run fails loudly.  Every payload — the stdout line and every section of the
side file — is stamped with the device JAX reports (`platform`, `kind`,
`count`), so a CPU rehearsal can never be read as a device number.  The
chip belongs to one process at a time: the mesh, serve and suite phases
run IN THIS PROCESS on the real devices; the only children are the restart
probe's fresh processes, which run BEFORE this process touches JAX.

  * The default invocation measures ONLY the headline query and prints the
    JSON line the moment it is measured.
  * The wider suite (Q1/Q6/Q3/Q18 + TPC-DS + parquet extras: --suite), the
    mesh-vs-local phases (--mesh) and the concurrent-serving bench (--serve)
    are opt-in, run AFTER the headline line is printed, and write their
    results to BENCH_EXTRA.json (a side file written at run time, not
    checked in), never stdout.
  * Reference analog: BenchmarkSuite.java records results per-benchmark as
    they complete, not after the whole suite.

Usage: python bench.py [--sf SF] [--query N] [--runs N] [--suite] [--mesh]
                       [--serve]
Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": {...}}

vs_baseline: speedup of the engine's device pipeline over a single-host
vectorized-numpy implementation of the same query on the same data.  There is
no JVM on this image (no `java` binary), so the reference Java engine cannot
be executed here; see BASELINE.md.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
_EXTRA_PATH = os.path.join(_ROOT, "BENCH_EXTRA.json")


def _device_stamp() -> dict:
    """The device as JAX reports it; rides every payload and section."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _deep_merge(base: dict, updates: dict) -> dict:
    """Recursive dict merge: update values win, sibling sections survive."""
    out = dict(base)
    for k, v in updates.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _merge_extra(updates: dict) -> None:
    """Merge `updates` into BENCH_EXTRA.json instead of rewriting it — a
    suite run must never silently drop sections an earlier run recorded
    (the SF10 walls were lost exactly that way after c807a39)."""
    existing: dict = {}
    try:
        with open(_EXTRA_PATH) as f:
            existing = dict(json.load(f))
    except (OSError, ValueError, TypeError):
        pass
    with open(_EXTRA_PATH, "w") as f:
        json.dump(_deep_merge(existing, updates), f, indent=1)


def _engine_time(runner, sql: str, runs: int) -> dict:
    """cold = first run after clearing the buffer pool (includes generation +
    host->device transfer); warm = best of `runs` with the pool hot (device-
    resident scans, the steady state).  A separate prewarm run compiles every
    fragment kernel first so cold measures data movement, not XLA compiles."""
    from trino_tpu.runtime.buffer_pool import POOL

    runner.execute(sql)  # compile prewarm (benchto prewarm analog)
    POOL.clear()
    t0 = time.perf_counter()
    runner.execute(sql)
    cold = time.perf_counter() - t0
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        runner.execute(sql)
        best = min(best, time.perf_counter() - t0)
    return {"cold_s": cold, "warm_s": best}


def _numpy_query_time(schema: str, query: int, runs: int) -> float:
    """Vectorized-numpy single-node CPU baseline (honest stand-in; see
    bench_numpy.py).  Columns are pre-materialized outside the timed region,
    mirroring the engine's warm buffer pool."""
    from bench_numpy import BASELINES
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector()
    fn = BASELINES[query]
    fn(conn, schema)  # prewarm: materialize + first compute
    best = float("inf")
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        fn(conn, schema)
        best = min(best, time.perf_counter() - t0)
    return best


def _pandas_query_time(schema: str, query: int, runs: int) -> float:
    """Single-node columnar CPU baseline (pandas on the same data)."""
    from tests.tpch_oracle import ORACLES
    from trino_tpu.testing import tpch_pandas

    cache = {}

    def t(name):
        if name not in cache:
            cache[name] = tpch_pandas(schema, name)
        return cache[name]

    ORACLES[query](t)  # prewarm: materialize tables outside the timed region
    best = float("inf")
    for _ in range(max(1, runs)):
        t0 = time.perf_counter()
        ORACLES[query](t)
        best = min(best, time.perf_counter() - t0)
    return best


def _run_headline(args) -> dict:
    """Measure ONLY the headline query and return its payload.  Must stay
    cheap: this is what the driver's default invocation waits on."""
    from trino_tpu.connectors.api import CatalogManager
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.connectors.tpch.generator import TpchGenerator
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.connectors.tpch.schema import SCHEMAS
    from trino_tpu.runtime.runner import LocalQueryRunner

    schema = _schema_for_sf(args.sf)

    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector())
    runner = LocalQueryRunner(catalogs, catalog="tpch", schema=schema, target_splits=8)

    nrows = TpchGenerator(SCHEMAS.get(schema, args.sf)).row_count("lineitem")
    head = _engine_time(runner, QUERIES[args.query], args.runs)
    wall = head["warm_s"]
    rows_per_sec = nrows / wall

    vs_numpy = _numpy_query_time(schema, args.query, args.runs) / wall
    vs_pandas = _pandas_query_time(schema, args.query, 1) / wall

    from trino_tpu.runtime.buffer_pool import POOL

    return {
        "metric": f"tpch_{schema}_q{args.query}_lineitem_rows_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        # headline ratio is vs the vectorized-numpy CPU engine (the honest
        # stand-in); pandas ratio kept for continuity with earlier rounds
        "vs_baseline": round(vs_numpy, 3),
        "vs_pandas": round(vs_pandas, 3),
        "wall_s": round(wall, 4),
        "cold_wall_s": round(head["cold_s"], 4),
        "pool": POOL.stats(),
        "device": _device_stamp(),
    }


def _run_suite(args, runner_schema: str) -> dict:
    """Opt-in wider measurement (AFTER the headline line is already out).
    Results land in BENCH_EXTRA.json, never stdout.  A query that fails
    fails the run; one that would start past BENCH_BUDGET_S is recorded as
    skipped, by name."""
    from trino_tpu.connectors.api import CatalogManager
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.runtime.runner import LocalQueryRunner

    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector())
    runner = LocalQueryRunner(
        catalogs, catalog="tpch", schema=runner_schema, target_splits=8
    )
    deadline = time.perf_counter() + float(
        os.environ.get("BENCH_BUDGET_S", 900)
    )
    skipped = {"skipped": "bench time budget exhausted"}

    def walls(runner, sql, runs):
        if time.perf_counter() > deadline:
            return dict(skipped)
        w = _engine_time(runner, sql, max(1, runs))
        return {k: round(v, 4) for k, v in w.items()}

    queries = {
        f"q{q}": walls(runner, QUERIES[q], args.runs // 2)
        for q in (1, 6, 3, 18)
    }
    # BASELINE configs beyond TPC-H: TPC-DS Q64 (config #4) and the parquet
    # scan path (config #5's PageSource -> scan shape)
    from trino_tpu.connectors.tpcds.queries import QUERIES as DS

    ds = LocalQueryRunner(catalog="tpcds", schema="tiny", target_splits=8)
    extras = {"tpcds_tiny_q64": walls(ds, DS[64], args.runs)}

    import shutil
    import tempfile

    from trino_tpu.connectors.parquet import (
        ParquetConnector,
        write_table_to_parquet,
    )

    root = tempfile.mkdtemp(prefix="bench_pq_")  # parquet files, not a cache
    try:
        write_table_to_parquet(TpchConnector(), "tiny", "lineitem", root)
        cm = CatalogManager()
        cm.register("pq", ParquetConnector(root))
        pq = LocalQueryRunner(cm, catalog="pq", schema="tiny", target_splits=8)
        extras["parquet_tiny_q6"] = walls(pq, QUERIES[6], args.runs)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {"schema": runner_schema, "queries": queries, "extras": extras}


def _run_mesh(schema: str, runs: int) -> dict:
    """Mesh-vs-local walls + per-fragment profile, IN THIS PROCESS on the
    devices JAX gives it (`len(jax.devices())` workers: the real chips on a
    TPU host; as many virtual CPU devices as XLA_FLAGS asks for otherwise).
    One process per chip: nothing here spawns a child.  A failing phase
    raises — it is never folded into the side file next to an exit 0."""
    import jax

    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.runtime.runner import LocalQueryRunner
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    local = LocalQueryRunner(schema=schema, target_splits=8)
    dist = DistributedQueryRunner(n_workers=len(jax.devices()), schema=schema)

    # profile archive riding the mesh bench (telemetry/profile_store): every
    # benched execution's artifact is archived, and the section records the
    # refs — this run becomes next run's profile_diff baseline
    import tempfile as _tempfile
    from trino_tpu.telemetry.profile_store import ProfileStore, attach_profile_store
    _profile_dir = os.environ.get("BENCH_PROFILE_DIR") or os.path.join(
        _tempfile.gettempdir(), "trino_tpu_profile_archive", schema
    )
    _profile_store = attach_profile_store(
        dist, ProfileStore(archive_dir=_profile_dir)
    )

    def warm_q(r, q):
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            r.execute(QUERIES[q])
            best = min(best, time.perf_counter() - t0)
        return best

    def warm(r):
        return warm_q(r, 6)

    def coldstart_run(q):
        # cold execute with compile attribution, warm best-of-runs, then the
        # coldstart contract probe: one more replay that must compile NOTHING
        # (tools/compare_bench.py gates warm_replay_events == 0)
        ev0, cs0 = OBSERVATORY.mark(), OBSERVATORY.total_wall_s
        t0 = time.perf_counter()
        rows = dist.execute(QUERIES[q]).rows
        cold = time.perf_counter() - t0
        cold_events = OBSERVATORY.mark() - ev0
        cold_compile_s = OBSERVATORY.total_wall_s - cs0
        best = warm_q(dist, q)
        # probe AFTER the warm runs: early warm runs may legitimately compile
        # (learned join capacities change buckets on run 1); once settled, a
        # replay must compile NOTHING
        m = OBSERVATORY.mark()
        dist.execute(QUERIES[q])
        return rows, cold, best, {
            "cold_s": round(cold, 4),
            "warm_s": round(best, 4),
            "cold_over_warm": round(cold / max(best, 1e-9), 3),
            "compile_s": round(cold_compile_s, 4),
            "compile_events": cold_events,
            "warm_replay_events": OBSERVATORY.count - m,
        }

    d_rows, mesh_cold, mesh_warm, q6_coldstart = coldstart_run(6)
    t0 = time.perf_counter()
    l_rows = local.execute(QUERIES[6]).rows
    local_cold = time.perf_counter() - t0
    local_warm = warm(local)
    prof = dist.last_mesh_profile

    # Q1: the decimal headline.  The proof-licensed i64 sum fast path
    # (verify.numeric range certificates) must compile ZERO runtime fits
    # checks for the whole cold+warm phase: decimal_fastpath_total deltas are
    # TRACE-time path selections, so runtime_check == 0 across the phase
    # proves even the cold compile never emitted a lax.cond fits probe
    # (tools/compare_bench.py gates this section).
    from trino_tpu.telemetry.metrics import DECIMAL_FASTPATHS, decimal_fastpath_counter
    _fp = decimal_fastpath_counter()

    def fp_snap():
        return {p: int(_fp.value((p,))) for p in DECIMAL_FASTPATHS}

    fp0 = fp_snap()
    q1_rows, q1_mesh_cold, q1_mesh_warm, q1_coldstart = coldstart_run(1)
    fp1 = fp_snap()
    decimal_fastpath = {p: fp1[p] - fp0[p] for p in DECIMAL_FASTPATHS}
    t0 = time.perf_counter()
    l1_rows = local.execute(QUERIES[1]).rows
    q1_local_cold = time.perf_counter() - t0
    q1_local_warm = warm_q(local, 1)

    # Q3 under co-partitioned lineitem/orders layouts: the partitioned-join gap
    # (probe repartition elided + speculative capacity — no host count sync)
    dist.execute(
        "set session table_layouts = "
        "'tpch.%s.lineitem:l_orderkey:%d,tpch.%s.orders:o_orderkey:%d'"
        % (schema, dist.wm.n, schema, dist.wm.n)
    )
    # proof-licensed capacity evidence (verify/capacity.py + compare_bench
    # check_licenses): over the WHOLE Q3 phase — cold and warm alike — the
    # licensed joins must never run the runtime sizing protocol
    # (runtime_check == 0; path selection is per-expansion, so cold counts too)
    # and the schedule license must have pre-dispatched at least one
    # independent build fragment asynchronously
    from trino_tpu.telemetry.metrics import (
        JOIN_CAPACITY_OUTCOMES,
        collective_async_counter,
        join_capacity_counter,
    )
    _jc = join_capacity_counter()
    jc0 = {o: int(_jc.value((o,))) for o in JOIN_CAPACITY_OUTCOMES}
    ca0 = int(collective_async_counter().value(()))
    d3_rows, q3_mesh_cold, q3_mesh_warm, q3_coldstart = coldstart_run(3)
    q3_licenses = {
        "join_capacity": {
            o: int(_jc.value((o,))) - jc0[o] for o in JOIN_CAPACITY_OUTCOMES
        },
        "collective_async": int(collective_async_counter().value(())) - ca0,
        "schedule": (
            dist.last_schedule_license.to_json()
            if getattr(dist, "last_schedule_license", None) is not None
            else None
        ),
    }
    q3_prof = dist.last_mesh_profile
    q3_counters = dict(q3_prof.counters) if q3_prof is not None else {}
    t0 = time.perf_counter()
    l3_rows = local.execute(QUERIES[3]).rows
    q3_local_cold = time.perf_counter() - t0
    q3_local_warm = warm_q(local, 3)

    # telemetry overhead: warm Q6 with span tracing off vs on (the default).
    # Acceptance: tracing-on warm wall within 5% of tracing-off.  INTERLEAVED
    # best-of-N pairs: sequential blocks confound the comparison with machine
    # drift (on a shared 2-core box, block-to-block drift dwarfs the sub-ms
    # tracer cost); alternating off/on samples see the same drift.
    trace_runs = max(5, runs)
    q6_warm_trace_off = float("inf")
    q6_warm_trace_on = float("inf")
    for _ in range(trace_runs):
        dist.properties.set("query_trace", False)
        t0 = time.perf_counter()
        dist.execute(QUERIES[6])
        q6_warm_trace_off = min(q6_warm_trace_off, time.perf_counter() - t0)
        dist.properties.set("query_trace", True)
        t0 = time.perf_counter()
        dist.execute(QUERIES[6])
        q6_warm_trace_on = min(q6_warm_trace_on, time.perf_counter() - t0)

    # registry snapshot: the trajectory carries COUNTERS, not just walls
    # (tools/compare_bench.py gates the zero-invariants on this section).
    # Taken BEFORE the pressure phase: constrained waves may legitimately
    # retry speculative expands, and those must not dirty the unconstrained
    # zero-counter evidence
    from trino_tpu.telemetry import REGISTRY
    metrics_snapshot = {
        k: v for k, v in sorted(REGISTRY.snapshot().items())
        if not k.startswith("trino_tpu_query_wall_seconds_bucket")
    }

    # licensed-never-slower bisection (compare_bench check_licenses gate):
    # re-run warm Q3 with `join_capacity_license = false` so the SAME session
    # measures the runtime sizing path's warm wall next to the licensed wall.
    # A license the economy policy should have declined shows up here as
    # licensed_warm_s >> runtime_warm_s.  The two paths are sampled
    # INTERLEAVED (A/B, per-path minima) under the same instantaneous load —
    # a ratio gate fed one sample from minutes earlier drifts on a busy box.
    # Runs AFTER the registry snapshot: the runtime path legitimately bumps
    # runtime_check / sizing counters that must not pollute the licensed
    # phase's zero-counter evidence.
    dist.properties.set("join_capacity_license", False)
    dist.execute(QUERIES[3])  # settle: compile the runtime path + learn caps
    q3_runtime_warm = q3_licensed_warm = float("inf")
    for _ in range(max(2, runs)):
        dist.properties.set("join_capacity_license", False)
        q3_runtime_warm = min(q3_runtime_warm, warm_q(dist, 3))
        dist.properties.set("join_capacity_license", True)
        q3_licensed_warm = min(q3_licensed_warm, warm_q(dist, 3))
    q3_licenses["licensed_warm_s"] = round(q3_licensed_warm, 4)
    q3_licenses["runtime_warm_s"] = round(q3_runtime_warm, 4)

    # global dictionary service evidence (runtime/dictionary_service +
    # compare_bench check_dictionary): a varchar-keyed distributed join under
    # a layout must co-locate through the shared versioned code assignment —
    # zero repartition collectives, elided exchanges, rows == local — and the
    # dictionary-backed unique business key must license its capacity.  Runs
    # AFTER the registry snapshot (its cold run legitimately compiles).
    from trino_tpu.runtime.dictionary_service import DICTIONARY_SERVICE
    dict_sql = (
        "select count(*) from customer c1 join customer c2 "
        "on c1.c_name = c2.c_name"
    )
    dist.execute(
        "set session table_layouts = 'tpch.%s.customer:c_name:%d'"
        % (schema, dist.wm.n)
    )
    dist.execute(dict_sql)  # settle: compile + learn capacities
    dict_rows = dist.execute(dict_sql).rows
    dprof = dist.last_mesh_profile
    dcounters = dict(dprof.counters) if dprof is not None else {}
    dict_local = local.execute(dict_sql).rows
    dictionary = {
        "exchange_elided": dcounters.get("exchange_elided", 0),
        "repartition_collective": dcounters.get("repartition_collective", 0),
        "join_capacity_proven": dcounters.get("join_capacity_proven", 0),
        "matches_local": (
            sorted(map(str, dict_rows)) == sorted(map(str, dict_local))
        ),
        "service": DICTIONARY_SERVICE.stats(),
    }

    # pressure: Q18 under a pool limit smaller than its build side must
    # complete in k>1 partition waves with filesystem-SPI spill and rows ==
    # the unconstrained local oracle — and every unconstrained query above
    # must have recorded ZERO waves/spill/revocations (degradation is free
    # when there is no pressure).  tools/compare_bench.py gates this section.
    from trino_tpu.bench_pressure import run_pressure
    from trino_tpu.runtime.lifecycle import set_memory_pool_limit
    try:
        pressure = run_pressure(local, dist, QUERIES[18])
    finally:
        set_memory_pool_limit(0)  # never leave the probe's limit armed

    # plan-decision ledger evidence (telemetry/decisions + compare_bench
    # check_decisions): one more WARM execution of each benched query, whose
    # archived artifact must carry a COMPLETE ledger — every exchange-plane
    # byte (all_to_all/all_gather) attributed to exactly one decision, zero
    # unattributed bytes, and zero `regret` verdicts on the warm set.  Runs
    # after the pressure phase with the Q3 layouts restored, so the ledgers
    # describe the same warm shapes the headline walls measured.
    dist.execute(
        "set session table_layouts = "
        "'tpch.%s.lineitem:l_orderkey:%d,tpch.%s.orders:o_orderkey:%d'"
        % (schema, dist.wm.n, schema, dist.wm.n)
    )

    def _warm_ledger(q):
        dist.execute(QUERIES[q])
        ref = _profile_store.refs()[-1]
        art = _profile_store.get(ref["query_id"]) or {}
        return {
            "query_id": ref["query_id"],
            "ledger": art.get("decisions"),
            "collective_bytes_by": art.get("collective_bytes_by") or {},
        }

    decisions_evidence = {"q6": _warm_ledger(6), "q3": _warm_ledger(3)}

    # archived profile-artifact refs for this bench's executions: the
    # comparable record tools/profile_diff.py consumes next run.  A failed
    # flush is recorded — refs to files that never landed must not read as a
    # usable baseline
    _profile_refs = {
        "archive_dir": _profile_dir,
        "flushed": _profile_store.flush(),
        "count": len(_profile_store.refs()),
        "recent": [
            {k: r[k] for k in ("key", "query_id", "sql_hash")}
            for r in _profile_store.refs()[-6:]
        ],
    }

    # `mesh8` in the key names is the side file's FORMAT (tools/drift_bench,
    # tools/profile_diff and tools/baselines/*.json read it), not a worker
    # count: `workers` and the section's `device` stamp say what ran
    return {
        "schema": schema,
        "workers": dist.wm.n,
        "q6_local_warm_s": round(local_warm, 4),
        "q6_local_cold_s": round(local_cold, 4),
        "q6_mesh8_warm_s": round(mesh_warm, 4),
        "q6_mesh8_cold_s": round(mesh_cold, 4),
        "mesh_over_local_warm": round(mesh_warm / max(local_warm, 1e-9), 3),
        "matches_local": sorted(map(str, d_rows)) == sorted(map(str, l_rows)),
        "profile": prof.to_json() if prof is not None else None,
        "q3_local_warm_s": round(q3_local_warm, 4),
        "q3_local_cold_s": round(q3_local_cold, 4),
        "q3_mesh8_warm_s": round(q3_mesh_warm, 4),
        "q3_mesh8_cold_s": round(q3_mesh_cold, 4),
        "q3_mesh_over_local_warm": round(
            q3_mesh_warm / max(q3_local_warm, 1e-9), 3
        ),
        "q3_matches_local": sorted(map(str, d3_rows)) == sorted(map(str, l3_rows)),
        # Q1 decimal-headline evidence: proof-licensed i64 sums, zero runtime
        # fits checks, rows equal to the local oracle
        "q1_local_warm_s": round(q1_local_warm, 4),
        "q1_local_cold_s": round(q1_local_cold, 4),
        "q1_mesh8_warm_s": round(q1_mesh_warm, 4),
        "q1_mesh8_cold_s": round(q1_mesh_cold, 4),
        "q1_mesh_over_local_warm": round(
            q1_mesh_warm / max(q1_local_warm, 1e-9), 3
        ),
        "q1_matches_local": sorted(map(str, q1_rows)) == sorted(map(str, l1_rows)),
        "decimal_fastpath": decimal_fastpath,
        # elision + speculation evidence: warm Q3 must show zero speculative
        # retries and zero probe repartitions under the layouts
        "q3_counters": {
            "exchange_elided": q3_counters.get("exchange_elided", 0),
            "repartition_collective": q3_counters.get("repartition_collective", 0),
            "join_speculative_retry": q3_counters.get("join_speculative_retry", 0),
            "join_overflow_check": q3_counters.get("join_overflow_check", 0),
            "join_capacity_sync": q3_counters.get("join_capacity_sync", 0),
            "join_capacity_proven": q3_counters.get("join_capacity_proven", 0),
            "collective_async": q3_counters.get("collective_async", 0),
            "scan_bucketize": q3_counters.get("scan_bucketize", 0),
        },
        # proof-licensed execution evidence over the Q3 phase (cold + warm):
        # tools/compare_bench.py check_licenses gates runtime_check == 0,
        # proven > 0, and the deleted sizing gather staying deleted
        "licenses": q3_licenses,
        # per-collective byte attribution of the warm Q3 profile (the ROADMAP
        # item-2 evidence: all_to_all vs reduce vs gather, summing to the
        # aggregate collective_bytes by construction).  The capacity_sizing
        # key is ALWAYS emitted (0 when no sizing gather fired) so the
        # licenses gate reads a real zero instead of a stale deep-merged value
        "q3_collective_bytes_by": (
            {
                "gather/capacity_sizing": 0,
                **q3_prof.to_json()["collective_bytes_by"],
            }
            if q3_prof is not None else None
        ),
        # compile observatory: cold wall decomposition + the warm-replay-zero
        # contract per benched query (tools/compare_bench.py gates this)
        "coldstart": {
            "q6": q6_coldstart,
            "q1": q1_coldstart,
            "q3": q3_coldstart,
            "manifest_keys": len(dist.compile_manifest()),
            "total_compile_s": round(OBSERVATORY.total_wall_s, 4),
        },
        # varchar-key co-location through the global dictionary service
        # (tools/compare_bench.py check_dictionary gates this)
        "dictionary": dictionary,
        # memory-pressure degradation proof (budget -> revoke -> wave -> kill)
        "pressure": pressure,
        # plan-decision ledger completeness + zero-regret evidence
        # (tools/compare_bench.py check_decisions gates this)
        "decisions": decisions_evidence,
        # telemetry-on overhead (acceptance: on/off ratio < 1.05 warm)
        "q6_mesh8_warm_trace_off_s": round(q6_warm_trace_off, 4),
        "q6_mesh8_warm_trace_on_s": round(q6_warm_trace_on, 4),
        "trace_overhead_ratio": round(
            q6_warm_trace_on / max(q6_warm_trace_off, 1e-9), 3
        ),
        "profile_artifacts": _profile_refs,
        "metrics": metrics_snapshot,
    }


#: restart-resilience probe (ROADMAP item 3): three FRESH processes run the
#: same first query — cold (populates a persistent XLA cache + saves a
#: workload manifest), persistent (same cache dir: re-traces, reloads
#: executables), prewarmed (cache + manifest replay at start; the query
#: itself must compile NOTHING — tools/compare_bench.py gates
#: prewarmed.query_events == 0).  One JSON line per child, stamped with the
#: device the child ran on.
_RESTART_CODE = """
import json, time
import jax
jax.config.update("jax_enable_x64", True)
manifest_path = @MANIFEST@
save_manifest = @SAVE@
# the probe's cache dir arrives as JAX_COMPILATION_CACHE_DIR: the one
# placement rule then sets no directory in code
from trino_tpu.parallel.spmd import configure_persistent_cache
configure_persistent_cache()
from trino_tpu.parallel import DistributedQueryRunner
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.runtime.prewarm import PrewarmExecutor
from trino_tpu.telemetry.compile_events import OBSERVATORY
sql = QUERIES[@Q@]
runner = DistributedQueryRunner(n_workers=len(jax.devices()), schema="@SCHEMA@")
ex = PrewarmExecutor(runner, manifest_path) if manifest_path else None
prewarm_s = 0.0
if ex is not None and not save_manifest:
    t0 = time.perf_counter()
    ex.run(reason="start", wait=True)
    prewarm_s = time.perf_counter() - t0
mark = OBSERVATORY.mark()
t0 = time.perf_counter()
runner.execute(sql)
wall = time.perf_counter() - t0
if ex is not None and save_manifest:
    # the cold process records the replay set + learned capacities the
    # prewarmed process will restore
    ex.record(sql)
    ex.save()
print(json.dumps({
    "wall_s": round(wall, 4),
    "prewarm_s": round(prewarm_s, 4),
    "compile_s": round(OBSERVATORY.total_wall_s, 4),
    "compile_events": OBSERVATORY.count,
    "query_events": OBSERVATORY.count - mark,
    "prewarm_state": (ex.state if ex is not None and not save_manifest
                      else None),
    "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    },
}), flush=True)
"""


def _run_restart(schema: str) -> dict:
    """First-run walls of restarted processes: cold vs persistent-cache vs
    prewarmed (see _RESTART_CODE).  Returns the `coldstart.restart` block
    (phases keyed cold/persistent/prewarmed).

    Fresh processes need the chip, and a chip belongs to one process at a
    time: main() calls this BEFORE the bench process itself touches JAX,
    and each child exits before the next starts.  The children inherit the
    environment as it is (the backend JAX gives them) plus one variable:
    `JAX_COMPILATION_CACHE_DIR`, a FIXED directory owned by this probe
    (emptied first, so `cold` is cold) next to the program's cache."""
    import shutil

    # spmd.DEFAULT_CACHE_DIR, spelled out: importing the engine here could
    # initialize a backend, and this process must not hold the chip yet
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _ROOT, ".jax_cache"
    )
    cache_dir = os.path.join(base, "restart_probe")
    manifest = os.path.join(base, "restart_probe_manifest.json")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    timeout = float(os.environ.get("BENCH_RESTART_TIMEOUT", 600))
    out: dict = {}
    phases = (
        ("cold", manifest, True),
        ("persistent", None, False),
        ("prewarmed", manifest, False),
    )
    for name, mpath, save in phases:
        # repr(), not json.dumps(): the placeholders must be PYTHON
        # literals (None, not null) inside the child's source
        code = (
            _RESTART_CODE
            .replace("@MANIFEST@", repr(mpath))
            .replace("@SAVE@", "True" if save else "False")
            .replace("@SCHEMA@", schema)
            .replace("@Q@", "6")
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            env=env, stdout=subprocess.PIPE, text=True, timeout=timeout,
            cwd=_ROOT, check=True,
        )
        lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
        out[name] = json.loads(lines[-1])
    return out


def _schema_for_sf(sf: float) -> str:
    from trino_tpu.connectors.tpch.schema import SCHEMAS

    named = next((k for k, v in SCHEMAS.items() if v == sf), None)
    return named or ("tiny" if sf <= 0.01 else "sf1")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--query", type=int, default=1)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument(
        "--suite",
        action="store_true",
        help="after the headline line, also measure Q1/Q6/Q3/Q18 + extras "
        "into BENCH_EXTRA.json (default: headline only)",
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="after the headline line, measure mesh vs single-worker Q6, Q1 "
        "and Q3 (co-partitioned layouts; elision/speculative-retry "
        "counters) walls + per-fragment profile, on every device JAX "
        "reports, into BENCH_EXTRA.json's mesh section",
    )
    ap.add_argument(
        "--serve",
        action="store_true",
        help="after the headline line, run the concurrent-serving bench "
        "(K clients x TPC-H mix through the dispatcher, local lanes + "
        "mesh) into BENCH_EXTRA.json's serve section",
    )
    args = ap.parse_args()
    suite = args.suite or os.environ.get("BENCH_SUITE") == "1"
    mesh = suite or args.mesh or os.environ.get("BENCH_MESH") == "1"
    serve = args.serve or os.environ.get("BENCH_SERVE") == "1"
    mesh_schema = _schema_for_sf(
        float(os.environ.get("BENCH_MESH_SF", args.sf))
    )

    # fresh-process phases FIRST: this process has not touched JAX yet, so
    # each restart child can take the chip and give it back
    restart = _run_restart(mesh_schema) if mesh else None

    from trino_tpu.parallel.spmd import configure_persistent_cache

    configure_persistent_cache()
    device = _device_stamp()
    if restart is not None:
        ran_on = {name: sec["device"] for name, sec in restart.items()}
        if any(d != device for d in ran_on.values()):
            raise RuntimeError(
                f"restart children ran on {ran_on} but this process is on "
                f"{device}: refusing to record them in one section"
            )

    payload = _run_headline(args)
    print(json.dumps(payload), flush=True)  # THE line — out before any suite

    if suite:
        _merge_extra({
            **_run_suite(args, _schema_for_sf(args.sf)),
            "headline": payload,
            "device": device,
        })
    if mesh:
        sec = _run_mesh(mesh_schema, max(1, args.runs // 2))
        sec["coldstart"]["restart"] = restart
        _merge_extra({"mesh": {mesh_schema: {**sec, "device": device}}})
    if serve:
        from trino_tpu.bench_serve import run_serve

        sec = run_serve(
            schema=os.environ.get("BENCH_SERVE_SCHEMA", "tiny"),
            clients=int(os.environ.get("BENCH_SERVE_CLIENTS", 8)),
            rounds=int(os.environ.get("BENCH_SERVE_ROUNDS", 3)),
        )
        _merge_extra({"serve": {**sec, "device": device}})


if __name__ == "__main__":
    main()
