#!/usr/bin/env python
"""Archive warm mesh profiles and record the ROADMAP item-2 drift
attribution into BENCH_EXTRA.json's `drift` section.

What it does (in ONE fresh child, on the backend and devices JAX gives it —
this parent never touches JAX, so the child may take the chip; for the CPU
rehearsal run it under JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8):

  1. warms Q6 and archives TWO consecutive warm runs — the **null-diff
     self check**: `profile_diff` over two warm archives of the same
     statement must attribute ~zero drift to every phase (the CI contract
     that keeps the diff tool honest);
  2. warms Q3 under the co-partitioned layouts (the exact bench.py --mesh
     configuration) and archives the best warm run's profile artifact;
  3. diffs the measured walls against a recorded BASELINE era section
     (default: tools/baselines/pr3_mesh_sf1.json — the PR 3 1.62x era)
     and decomposes the CURRENT warm wall per phase and fragment, naming
     the dominant (phase, fragment) cell;
  4. writes the `drift` section (merged into BENCH_EXTRA.json) that
     `tools/compare_bench.py check_drift` gates.

Usage:
  python tools/drift_bench.py                      # sf1, record
  python tools/drift_bench.py --schema tiny --no-record   # CI self-check
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_CHILD_CODE = """
import json, time, tempfile
import jax
jax.config.update("jax_enable_x64", True)
from trino_tpu.runtime.runner import LocalQueryRunner
from trino_tpu.parallel import DistributedQueryRunner
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.telemetry.profile_store import ProfileStore, attach_profile_store

schema = @SCHEMA@
runs = @RUNS@
archive_dir = @ARCHIVE@ or tempfile.mkdtemp(prefix="trino_tpu_drift_")

local = LocalQueryRunner(schema=schema, target_splits=8)
dist = DistributedQueryRunner(n_workers=len(jax.devices()), schema=schema)
store = attach_profile_store(
    dist, ProfileStore(archive_dir=archive_dir, synchronous=True)
)

def warm_best(r, q, n):
    # best-of-n warm wall; the matching run's artifact is the store's most
    # recent ref at that instant (synchronous store: already on disk)
    best, best_ref = float("inf"), None
    for _ in range(n):
        t0 = time.perf_counter()
        r.execute(QUERIES[q])
        w = time.perf_counter() - t0
        if w < best:
            best = w
            best_ref = store.refs()[-1]
    return best, best_ref

# -- Q6 null-diff: two consecutive warm archives of the same statement ----
dist.execute(QUERIES[6])  # cold (compiles)
dist.execute(QUERIES[6])  # settle capacities/buckets
t0 = time.perf_counter(); dist.execute(QUERIES[6])
q6_warm_a_s = time.perf_counter() - t0
q6_ref_a = store.refs()[-1]
t0 = time.perf_counter(); dist.execute(QUERIES[6])
q6_warm_b_s = time.perf_counter() - t0
q6_ref_b = store.refs()[-1]

# -- Q3 under the co-partitioned layouts (bench.py --mesh configuration) --
dist.execute(
    "set session table_layouts = "
    "'tpch.%s.lineitem:l_orderkey:8,tpch.%s.orders:o_orderkey:8'"
    % (schema, schema)
)
t0 = time.perf_counter(); d3_rows = dist.execute(QUERIES[3]).rows
q3_mesh_cold_s = time.perf_counter() - t0
q3_mesh_warm_s, q3_ref = warm_best(dist, 3, runs)
t0 = time.perf_counter(); l3_rows = local.execute(QUERIES[3]).rows
q3_local_cold_s = time.perf_counter() - t0
q3_local_warm_s = float("inf")
for _ in range(runs):
    t0 = time.perf_counter()
    local.execute(QUERIES[3])
    q3_local_warm_s = min(q3_local_warm_s, time.perf_counter() - t0)

def load(ref):
    return json.load(open(ref["path"]))

print(json.dumps({
    "schema": schema,
    "workers": dist.wm.n,
    "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    },
    "archive_dir": archive_dir,
    "q6_warm_a_s": round(q6_warm_a_s, 4),
    "q6_warm_b_s": round(q6_warm_b_s, 4),
    "q6_artifact_a": load(q6_ref_a),
    "q6_artifact_b": load(q6_ref_b),
    "q3_mesh_cold_s": round(q3_mesh_cold_s, 4),
    "q3_mesh_warm_s": round(q3_mesh_warm_s, 4),
    "q3_local_cold_s": round(q3_local_cold_s, 4),
    "q3_local_warm_s": round(q3_local_warm_s, 4),
    "q3_matches_local": sorted(map(str, d3_rows)) == sorted(map(str, l3_rows)),
    "q3_artifact": load(q3_ref),
    "profile_artifacts": store.refs(),
}), flush=True)
"""


def run_child(schema: str, runs: int, archive_dir: str, timeout: float) -> dict:
    code = (
        _CHILD_CODE
        .replace("@SCHEMA@", repr(schema))
        .replace("@RUNS@", str(runs))
        .replace("@ARCHIVE@", repr(archive_dir))
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, cwd=ROOT,
    )
    lines = [l for l in (r.stdout or "").splitlines() if l.startswith("{")]
    if r.returncode != 0 or not lines:
        tail = " | ".join((r.stderr or "").strip().splitlines()[-5:])
        raise RuntimeError(f"drift child rc={r.returncode}: {tail}"[:800])
    return json.loads(lines[-1])


def build_drift_section(measured: dict, baseline_sec: dict,
                        baseline_ref: str) -> dict:
    """Assemble the BENCH_EXTRA `drift` section from a child measurement
    and a recorded baseline-era mesh section."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from profile_diff import diff_artifacts, null_diff_ok
    finally:
        sys.path.pop(0)

    art = measured["q3_artifact"]
    phases = {k: round(float(v), 6) for k, v in art["phases"].items()}
    wall = float(art["wall_s"])
    # dominant (phase, fragment) of the CURRENT warm wall: where the time
    # lives now.  The PR 3 era recorded walls + counters but no Q3 phase
    # breakdown (the archive did not exist yet), so against it the era
    # attribution is wall/ratio factor deltas plus the current profile's
    # decomposition.  A NEW-era baseline (--emit-baseline) carries its
    # q3_artifact, and the era diff becomes artifact-vs-artifact
    # per-phase (profile_diff), never wall-vs-wall.
    era_diff = None
    base_art = baseline_sec.get("q3_artifact")
    if isinstance(base_art, dict):
        era_diff = diff_artifacts(base_art, art)
    dominant_phase = max(phases, key=lambda k: phases[k])
    dominant_fragment, dominant_kind, best = None, None, 0.0
    dominant_frag_phase = None
    for f in art.get("fragments", ()):
        for ph, ms in (f.get("phases_ms") or {}).items():
            if abs(ms) > abs(best):
                best = ms
                dominant_fragment = f["fragment"]
                dominant_kind = f.get("kind", "")
                dominant_frag_phase = ph
    null = diff_artifacts(
        measured["q6_artifact_a"], measured["q6_artifact_b"]
    )
    base_mesh = baseline_sec["q3_mesh8_warm_s"]
    base_local = baseline_sec["q3_local_warm_s"]
    cur_mesh = measured["q3_mesh_warm_s"]
    cur_local = measured["q3_local_warm_s"]
    base_counters = baseline_sec.get("q3_counters", {}) or {}
    cur_counters = art.get("counters", {}) or {}
    return {
        "schema": measured["schema"],
        "device": measured["device"],
        "query": "q3",
        "baseline": {
            "ref": baseline_ref,
            "mesh_warm_s": base_mesh,
            "local_warm_s": base_local,
            "ratio": round(base_mesh / base_local, 3),
        },
        "current": {
            "mesh_warm_s": cur_mesh,
            "local_warm_s": cur_local,
            "ratio": round(cur_mesh / max(cur_local, 1e-9), 3),
            "matches_local": measured["q3_matches_local"],
            "profile_ref": {
                "key": art["key"],
                "sql_hash": art["sql_hash"],
                "mesh": art["mesh"],
            },
        },
        "mesh_wall_delta_s": round(cur_mesh - base_mesh, 4),
        "local_wall_delta_s": round(cur_local - base_local, 4),
        # the ratio drift decomposes multiplicatively: ratio_new/ratio_old
        # = (mesh_new/mesh_old) * (local_old/local_new) — how much of the
        # "regression" is the mesh getting slower vs the LOCAL baseline
        # getting faster (both factors recorded; the gate requires the
        # decomposition, not a vibe)
        "ratio_factors": {
            "mesh": round(cur_mesh / base_mesh, 3),
            "local_inverse": round(base_local / max(cur_local, 1e-9), 3),
        },
        "counters_delta": {
            k: cur_counters.get(k, 0) - base_counters.get(k, 0)
            for k in sorted(set(base_counters) | set(cur_counters))
            if cur_counters.get(k, 0) != base_counters.get(k, 0)
        },
        # artifact-vs-artifact era diff (present iff the baseline era
        # archived its q3_artifact): per-phase deltas between the two
        # eras' warm profiles, the real drift decomposition
        "era_diff": (
            {
                "wall_delta_s": era_diff["wall_delta_s"],
                "phases_delta_s": era_diff["phases_delta_s"],
                "sums_to_wall": era_diff["sums_to_wall"],
            }
            if era_diff is not None else None
        ),
        "attribution": {
            "phases_s": phases,
            "phase_shares": {
                k: round(v / max(wall, 1e-9), 4) for k, v in phases.items()
            },
            "dominant_phase": dominant_phase,
            "dominant_fragment": dominant_fragment,
            "dominant_fragment_kind": dominant_kind,
            "dominant_fragment_phase": dominant_frag_phase,
            # gather/capacity_sizing is ALWAYS emitted (0 when no sizing
            # gather fired — the proof-licensed join contract) so the
            # BENCH_EXTRA deep merge overwrites stale values instead of
            # resurrecting a deleted collective
            "collective_bytes_by": {
                "gather/capacity_sizing": 0,
                **art.get("collective_bytes_by", {}),
            },
            "sums_to_wall": abs(sum(phases.values()) - wall) < 1e-4,
        },
        "null_diff": {
            "query": "q6",
            "wall_delta_s": null["wall_delta_s"],
            "max_phase_delta_s": round(
                max(
                    (abs(v) for v in null["phases_delta_s"].values()),
                    default=0.0,
                ),
                6,
            ),
            "sums_to_wall": null["sums_to_wall"],
            "pass": bool(null_diff_ok(null)),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="archive warm Q3/Q6 mesh profiles and record the "
        "BENCH_EXTRA drift attribution"
    )
    ap.add_argument("--schema", default="sf1")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument(
        "--baseline",
        default=os.path.join(ROOT, "tools", "baselines", "pr3_mesh_sf1.json"),
        help="recorded baseline-era mesh section (tools/baselines/...)",
    )
    ap.add_argument("--archive-dir", default="")
    ap.add_argument(
        "--timeout", type=float,
        default=float(os.environ.get("BENCH_DRIFT_TIMEOUT", 1200)),
    )
    ap.add_argument(
        "--emit-baseline", default="",
        help="also write this run as a NEW era baseline file "
        "(tools/baselines/...) carrying the warm q3_artifact, so the "
        "next era's drift diffs artifact-vs-artifact per phase",
    )
    ap.add_argument(
        "--max-ratio", type=float, default=0.0,
        help="fail (and record the threshold) when the current warm "
        "mesh/local ratio exceeds this — the recorded value becomes part "
        "of the drift section, so compare_bench check_drift re-gates it "
        "on every CI run without re-benching (0 = no threshold)",
    )
    ap.add_argument(
        "--no-record", action="store_true",
        help="print the section, do not merge into BENCH_EXTRA.json",
    )
    ap.add_argument(
        "--null-check-only", action="store_true",
        help="exit on the Q6 null-diff verdict alone (the CI self-check; "
        "still runs Q3 so the archive exercises a join profile)",
    )
    args = ap.parse_args(argv)
    with open(args.baseline, encoding="utf-8") as fh:
        doc = json.load(fh)
    baseline_sec = doc.get("mesh_sf1") or doc
    baseline_ref = doc.get("_source", args.baseline)
    measured = run_child(
        args.schema, args.runs, args.archive_dir, args.timeout
    )
    section = build_drift_section(measured, baseline_sec, baseline_ref)
    if args.max_ratio:
        section["max_ratio"] = args.max_ratio
    if args.emit_baseline:
        with open(args.emit_baseline, "w", encoding="utf-8") as fh:
            json.dump({
                "_source": args.emit_baseline,
                "q3_mesh8_warm_s": measured["q3_mesh_warm_s"],
                "q3_local_warm_s": measured["q3_local_warm_s"],
                "q3_counters": measured["q3_artifact"].get("counters", {}),
                "q3_artifact": measured["q3_artifact"],
            }, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"drift_bench: wrote era baseline {args.emit_baseline}")
    print(json.dumps(section, indent=2, sort_keys=True))
    ok = section["null_diff"]["pass"] and section["attribution"]["sums_to_wall"]
    if args.max_ratio and section["current"]["ratio"] > args.max_ratio:
        print(
            f"drift_bench: FAIL: current warm ratio "
            f"{section['current']['ratio']} > --max-ratio {args.max_ratio}"
        )
        ok = False
    if not args.no_record:
        sys.path.insert(0, ROOT)
        import bench

        # REPLACE the drift section (siblings survive).  _merge_extra's
        # deep merge is wrong here: a re-recorded run must not inherit
        # stale keys from the previous recording (a superseded
        # counters_delta entry would haunt every later era)
        try:
            with open(bench._EXTRA_PATH, encoding="utf-8") as fh:
                extra = dict(json.load(fh))
        except (OSError, ValueError, TypeError):
            extra = {}
        extra["drift"] = section
        with open(bench._EXTRA_PATH, "w", encoding="utf-8") as fh:
            json.dump(extra, fh, indent=1)
        print("drift_bench: recorded `drift` section into BENCH_EXTRA.json")
    if args.null_check_only:
        print(
            "drift_bench: null-diff "
            + ("PASS" if section["null_diff"]["pass"] else "FAIL")
            + f" (q6 wall delta {section['null_diff']['wall_delta_s']:+.4f}s,"
            f" max phase delta {section['null_diff']['max_phase_delta_s']}s)"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
