#!/usr/bin/env python
"""Differential drift attribution over archived query profiles.

The observatory's second half: `telemetry/profile_store` makes profiles
persistent and comparable; this tool makes the comparison.  Given two
archived artifacts of the same statement, decompose the wall delta into compile(trace) vs compute vs
collective vs transfer vs gate-wait vs other per fragment, diff the
per-collective byte attribution by (kind, purpose) and the counter
vocabulary, and name the DOMINANT (phase, fragment) — so a "Q3 regressed
1.62x -> 4.46x" ticket arrives with the phase and fragment that moved,
not a wall and a shrug.

Conservation contract (held by tests/test_profile_store.py,
TestProfileDiff and TestRunnerIntegration):
each artifact's phases sum to its wall EXACTLY (the profile store's
signed-`unattributed` construction), so the per-phase deltas here sum to
the measured wall delta — attribution is conservative and complete, never
a curated subset that quietly drops the inconvenient remainder.

Usage:
  python tools/profile_diff.py A.json B.json              # two artifacts
  python tools/profile_diff.py A.json B.json --threshold 0.1
      # exit 2 when |wall delta| exceeds 10% of A's wall (the drift gate)

Exit status: 0 = inside threshold, 2 = drift above threshold, 1 = bad
input (missing files, incomparable statements).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: phase-delta tolerance of the null-diff contract: two warm archives of
#: the same statement must attribute (almost) nothing to any phase.
#: Relative to wall — an absolute bound would be meaningless across tiny
#: (ms) and sf10 (tens of s) walls.
NULL_DIFF_REL_TOL = 0.35


def _phases(artifact: dict) -> dict:
    return {k: float(v) for k, v in artifact.get("phases", {}).items()}


def diff_artifacts(a: dict, b: dict) -> dict:
    """Structured drift report for artifact A (baseline) -> B (current).

    Raises ValueError when the artifacts are not comparable (different
    statements by sql_hash, or incompatible schema versions)."""
    for side, art in (("A", a), ("B", b)):
        if "phases" not in art or "wall_s" not in art:
            raise ValueError(f"artifact {side} is not a profile artifact")
    if a.get("version") != b.get("version"):
        raise ValueError(
            f"artifact versions differ (A={a.get('version')}, "
            f"B={b.get('version')}): re-archive with one engine build"
        )
    same_stmt = a.get("sql_hash") == b.get("sql_hash")
    wall_a, wall_b = float(a["wall_s"]), float(b["wall_s"])
    pa, pb = _phases(a), _phases(b)
    phase_delta = {
        k: round(pb.get(k, 0.0) - pa.get(k, 0.0), 9)
        for k in sorted(set(pa) | set(pb))
    }
    # per-fragment per-phase deltas (fragments matched by id; a fragment
    # present on one side only diffs against zeros — plan-shape drift is
    # itself a finding, surfaced via `fragments_changed`)
    fa = {f["fragment"]: f for f in a.get("fragments", ())}
    fb = {f["fragment"]: f for f in b.get("fragments", ())}
    by_fragment = {}
    for fid in sorted(set(fa) | set(fb)):
        phases_a = {
            k: v / 1e3
            for k, v in (fa.get(fid, {}).get("phases_ms") or {}).items()
        }
        phases_b = {
            k: v / 1e3
            for k, v in (fb.get(fid, {}).get("phases_ms") or {}).items()
        }
        by_fragment[fid] = {
            "kind": (fb.get(fid) or fa.get(fid, {})).get("kind", ""),
            "wall_delta_s": round(
                fb.get(fid, {}).get("wall_s", 0.0)
                - fa.get(fid, {}).get("wall_s", 0.0),
                6,
            ),
            "phases_delta_s": {
                k: round(phases_b.get(k, 0.0) - phases_a.get(k, 0.0), 6)
                for k in sorted(set(phases_a) | set(phases_b))
            },
        }
    # dominant attribution: the (phase, fragment) cell with the largest
    # absolute per-fragment delta names WHERE the drift lives; the
    # artifact-level dominant phase names WHAT kind of time it is
    dominant_phase = None
    if phase_delta:
        dominant_phase = max(phase_delta, key=lambda k: abs(phase_delta[k]))
    dominant_fragment = None
    dominant_cell = None
    best = 0.0
    for fid, fd in by_fragment.items():
        for ph, d in fd["phases_delta_s"].items():
            if abs(d) > abs(best):
                best = d
                dominant_fragment = fid
                dominant_cell = {
                    "fragment": fid,
                    "kind": fd["kind"],
                    "phase": ph,
                    "delta_s": round(d, 6),
                }
    ca = a.get("collective_bytes_by", {}) or {}
    cb = b.get("collective_bytes_by", {}) or {}
    cta = a.get("counters", {}) or {}
    ctb = b.get("counters", {}) or {}
    wall_delta = wall_b - wall_a
    phase_sum = sum(phase_delta.values())
    return {
        "comparable": same_stmt,
        "sql_hash": b.get("sql_hash"),
        "a": {
            "query_id": a.get("query_id"), "wall_s": round(wall_a, 6),
            "mesh": a.get("mesh"),
        },
        "b": {
            "query_id": b.get("query_id"), "wall_s": round(wall_b, 6),
            "mesh": b.get("mesh"),
        },
        "wall_delta_s": round(wall_delta, 9),
        "wall_ratio": round(wall_b / wall_a, 4) if wall_a > 0 else None,
        "phases_delta_s": phase_delta,
        # conservation witness: the per-phase attributions must sum to the
        # wall delta (float-exact up to accumulation noise)
        "sums_to_wall": abs(phase_sum - wall_delta) < 1e-6,
        "by_fragment": by_fragment,
        "fragments_changed": sorted(set(fa) ^ set(fb)),
        "dominant_phase": dominant_phase,
        "dominant_fragment": dominant_fragment,
        "dominant": dominant_cell,
        "collective_bytes_delta": {
            k: cb.get(k, 0) - ca.get(k, 0)
            for k in sorted(set(ca) | set(cb))
            if cb.get(k, 0) != ca.get(k, 0)
        },
        "counters_delta": {
            k: ctb.get(k, 0) - cta.get(k, 0)
            for k in sorted(set(cta) | set(ctb))
            if ctb.get(k, 0) != cta.get(k, 0)
        },
        "gate_wait_delta_s": round(
            (b.get("gate", {}).get("wait_s", 0.0))
            - (a.get("gate", {}).get("wait_s", 0.0)),
            9,
        ),
        "compile_delta_s": round(
            (b.get("compile", {}).get("compile_s", 0.0))
            - (a.get("compile", {}).get("compile_s", 0.0)),
            6,
        ),
    }


def null_diff_ok(report: dict, rel_tol: float = NULL_DIFF_REL_TOL) -> bool:
    """The null-diff contract: a diff of two warm runs of the SAME
    statement must attribute only noise — every phase delta within
    `rel_tol` of the larger wall, and the conservation witness intact."""
    if not report["sums_to_wall"]:
        return False
    wall = max(report["a"]["wall_s"], report["b"]["wall_s"], 1e-9)
    return all(
        abs(d) <= rel_tol * wall
        for d in report["phases_delta_s"].values()
    )


def render_text(report: dict) -> str:
    lines = []
    a, b = report["a"], report["b"]
    lines.append(
        f"profile_diff: {a['query_id']} ({a['wall_s']:.4f}s) -> "
        f"{b['query_id']} ({b['wall_s']:.4f}s): "
        f"wall {report['wall_delta_s']:+.4f}s "
        f"(x{report['wall_ratio']})"
    )
    if not report["comparable"]:
        lines.append(
            "  WARNING: different statements (sql_hash mismatch) — "
            "deltas compare apples to oranges"
        )
    for k, v in sorted(
        report["phases_delta_s"].items(), key=lambda kv: -abs(kv[1])
    ):
        if abs(v) >= 1e-6:
            lines.append(f"  phase {k:<13} {v:+.4f}s")
    lines.append(
        f"  conservation: phase deltas sum to wall delta: "
        f"{report['sums_to_wall']}"
    )
    dom = report.get("dominant")
    if dom:
        lines.append(
            f"  dominant: fragment {dom['fragment']} [{dom['kind']}] "
            f"{dom['phase']} {dom['delta_s']:+.4f}s"
        )
    for k, v in (report.get("collective_bytes_delta") or {}).items():
        lines.append(f"  collective {k:<24} {v:+d} bytes")
    for k, v in (report.get("counters_delta") or {}).items():
        lines.append(f"  counter {k:<20} {v:+d}")
    if abs(report.get("gate_wait_delta_s", 0.0)) >= 1e-6:
        lines.append(
            f"  gate_wait delta {report['gate_wait_delta_s']:+.4f}s"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two archived query-profile artifacts"
    )
    ap.add_argument("a", help="baseline artifact JSON")
    ap.add_argument("b", help="current artifact JSON")
    ap.add_argument(
        "--threshold", type=float, default=0.10,
        help="relative wall-drift threshold: exit 2 when |delta| exceeds "
        "this fraction of the baseline wall (default 0.10)",
    )
    ap.add_argument("--json", action="store_true", help="print JSON")
    args = ap.parse_args(argv)
    try:
        with open(args.a, encoding="utf-8") as fh:
            a = json.load(fh)
        with open(args.b, encoding="utf-8") as fh:
            b = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"profile_diff: cannot read inputs: {e}")
        return 1
    try:
        report = diff_artifacts(a, b)
    except ValueError as e:
        print(f"profile_diff: {e}")
        return 1
    print(json.dumps(report, indent=2, sort_keys=True) if args.json
          else render_text(report))
    base = report["a"]["wall_s"]
    delta = report["wall_delta_s"]
    if base > 0 and abs(delta) > args.threshold * base:
        print(
            f"profile_diff: DRIFT {delta:+.4f}s exceeds "
            f"{args.threshold:.0%} of baseline ({base:.4f}s)"
        )
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
