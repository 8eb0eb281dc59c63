#!/usr/bin/env python
"""Dump the compile-key manifest a workload needs (the AOT prewarm input).

Runs the given statements on a DistributedQueryRunner and writes the compile
observatory's manifest: the deduplicated (step, bucket, mesh) key set the
workload had to trace+compile, with per-key compile seconds.  ROADMAP item 3
(persistent compile cache + AOT prewarm) consumes this enumeration — compile
exactly these keys at server start / after mesh resize instead of paying
them at first query.

By default every statement runs twice and the tool FAILS (exit 2) if the
second pass still compiles anything: a manifest is only a usable prewarm
input when the workload's key set is closed under replay.

Capacity learning counts as COLD: a speculative join's first run measures
its tight output capacity (partitioning/speculative.CAP_HISTORY) and the
next run compiles the fused expand at that bucket — so a run that LEARNED a
capacity (CAP_HISTORY.version moved) gets one follow-up cold run before
the closure watermark.  The learned entries are persisted in the manifest
(`cap_history`); seeding them back (`--seed prior_manifest.json`, what a
prewarm executor does at server start) makes the key set close on run 1 —
the Q3 gap PR 6's observatory surfaced.

Usage:
  python tools/prewarm_manifest.py --schema tiny --workers 8 --queries 1,6,3
  python tools/prewarm_manifest.py --sql "select count(*) from lineitem" -o m.json
  python tools/prewarm_manifest.py --queries 3 --seed m.json   # closes on run 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="dump the compile observatory's prewarm manifest"
    )
    ap.add_argument("--schema", default="tiny")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument(
        "--queries", default="6",
        help="comma-separated TPC-H query numbers (default: 6)",
    )
    ap.add_argument(
        "--sql", action="append", default=[],
        help="raw SQL statement (repeatable; overrides --queries)",
    )
    ap.add_argument(
        "--runs", type=int, default=2,
        help="executions per statement; >= 2 proves the key set is closed "
        "(the non-first passes must add zero compile events)",
    )
    ap.add_argument(
        "--seed", default=None,
        help="prior manifest JSON whose cap_history seeds the speculative-"
        "join capacity history before running (the prewarm-executor path: "
        "capacity-learning statements then close on run 1)",
    )
    ap.add_argument("-o", "--out", default=None, help="output file (default: stdout)")
    args = ap.parse_args(argv)

    # the backend is whatever JAX gives this process (on the chip machine:
    # the chip — prewarming another backend's programs warms nothing); the
    # virtual-device flag only takes effect when that backend is the CPU
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.workers}"
        ).strip()
    sys.path.insert(0, ROOT)

    import jax

    jax.config.update("jax_enable_x64", True)

    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.parallel import DistributedQueryRunner
    from trino_tpu.partitioning import CAP_HISTORY
    from trino_tpu.runtime.prewarm import (
        WorkloadManifest,
        replay_statements,
        save_manifest,
    )
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    if args.seed:
        with open(args.seed, "r", encoding="utf-8") as fh:
            seeded = CAP_HISTORY.seed(json.load(fh).get("cap_history"))
        print(f"prewarm_manifest: seeded {seeded} capacity entries",
              file=sys.stderr)

    runner = DistributedQueryRunner(n_workers=args.workers, schema=args.schema)
    stmts = args.sql or [QUERIES[int(q)] for q in args.queries.split(",")]
    warm_events = 0
    for sql in stmts:
        # cold phase: the first run, PLUS one follow-up per run that
        # LEARNED a speculative-join capacity — the next run compiles the
        # fused expand at the learned bucket, which is part of the closed
        # key set, not a closure failure (seeded histories learn nothing
        # and go straight to the watermark).  Same loop the in-process
        # PrewarmExecutor runs at server start (runtime/prewarm).
        extra = replay_statements(runner, [sql]) - 1
        if extra:
            print(
                f"prewarm_manifest: {extra} capacity-learning run(s) before "
                "the closure watermark (seed a prior manifest to close on "
                "run 1)",
                file=sys.stderr,
            )
        mark = OBSERVATORY.mark()
        for _ in range(max(1, args.runs) - 1):
            runner.execute(sql)
        warm_events += OBSERVATORY.count - mark

    watermark = OBSERVATORY.mark()
    manifest = WorkloadManifest(
        statements=stmts,
        # learned speculative-join capacities: seed these back (--seed, or
        # the prewarm executor at server start) so the first run takes the
        # fused path at the right bucket and the key set closes on run 1
        cap_history=CAP_HISTORY.snapshot(),
        watermark=watermark,
        closed=warm_events == 0,
        workers=runner.wm.n,
        compile_keys=runner.compile_manifest(),
    )
    extra_fields = {
        "schema": args.schema,
        "statements": len(stmts),
        "compile_events": OBSERVATORY.count,
        "compile_s": round(OBSERVATORY.total_wall_s, 4),
        "warm_replay_events": warm_events,
    }
    if args.out:
        # the filesystem SPI path a PrewarmExecutor loads at server start
        save_manifest(manifest, args.out, extra=extra_fields)
    else:
        doc = manifest.to_json()
        doc.update(extra_fields)
        print(json.dumps(doc, indent=1, default=str))
    if warm_events:
        # a hard failure, not advice: CI trusts this exit code as the
        # prewarm-closure gate (an unclosed manifest under-covers the
        # workload, so prewarming it cannot make cold starts fully warm)
        print(
            f"prewarm_manifest: ERROR: {warm_events} compile event(s) on "
            "warm replays remain above the closure watermark "
            f"({watermark - warm_events}) — the key set is not closed",
            file=sys.stderr,
        )
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
