#!/usr/bin/env python3
"""CPU rehearsal of every cell, end to end, at `tpch.tiny`.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--cells a,b] [--seeds 1,2,3]

Calls the harness's own `run_cell` (everything `benchmark/run.py` does after
its look for a chip) on four virtual CPU devices, with the configuration's
schema overridden to `tiny` -- an override the command line cannot reach.
Checks the shape of the last line and that the reference agrees
(`correct: true`) for each seed, one traced run per cell among them.
Nothing it prints is a device number: every line is stamped
`"platform": "cpu"`, and shares of a peak are left out.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402

#: `tiny` is far under the broadcast threshold: force the partitioned join,
#: as tests/test_chip_smoke.py does for the same path
TINY = {"schema": "tiny", "session": {"broadcast_join_rows": 100}}

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def check_shape(result: dict, cell, trace: bool) -> list:
    """What is wrong with a result line (empty = nothing)."""
    bad = []
    keys = list(result)
    for k in RESULT_KEYS:
        if k not in keys:
            bad.append(f"no key {k!r}")
    if keys[-1] != "compared":
        bad.append("`compared` is not the last key")
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in result.get("device", {}):
            bad.append(f"device has no {k!r}")
    names = (
        [m["name"] for m in cell.per_layer] if trace
        else [m["name"] for m in cell.end_to_end]
    )
    for name, m in result.get("metrics", {}).items():
        if name not in names:
            bad.append(f"metric {name!r} is not the cell's")
        if set(m) != {"value", "unit"} or not isinstance(
            m["value"], (int, float)
        ):
            bad.append(f"metric {name!r} is not {{value, unit}}")
    if not trace:
        for name in names:
            if name not in result.get("metrics", {}):
                bad.append(f"end-to-end metric {name!r} is missing")
            elif result["metrics"][name]["value"] <= 0:
                bad.append(f"end-to-end metric {name!r} is not above 0")
    for name, c in result.get("compared", {}).items():
        if "value" not in c or "limit" not in c:
            bad.append(f"compared {name!r} lacks value or limit")
    return bad


def rehearse(cells=None, seeds=(1, 2, 3_000_000_011), seconds=1.0) -> int:
    from benchmark.harness import spec
    from benchmark.harness.cell import run_cell

    bench = spec.benchmark()
    failures = 0
    for w in bench["workloads"]:
        if cells and w["name"] not in cells:
            continue
        cell = spec.Cell(w["name"], bench)
        for i, seed in enumerate(seeds):
            trace = i == len(seeds) - 1
            out = io.StringIO()
            result = run_cell(w["name"], seed, seconds, trace,
                              config_overrides=TINY, out=out)
            last = out.getvalue().strip().splitlines()[-1]
            bad = check_shape(json.loads(last), cell, trace)
            if not result["correct"]:
                bad.append(f"correct is false: {result['compared']}")
            if result["device"]["platform"] != "cpu":
                bad.append("the rehearsal ran off the CPU")
            print(json.dumps({
                "rehearsal": w["name"], "seed": seed, "trace": int(trace),
                "platform": result["device"]["platform"],
                "attempted": result["attempted"], "problems": bad,
                "metrics": sorted(result["metrics"]),
            }), flush=True)
            failures += bool(bad)
    return failures


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--seeds", default="1,2,3000000011")
    ap.add_argument("--seconds", type=float, default=1.0)
    a = ap.parse_args()
    n = rehearse(
        [c for c in a.cells.split(",") if c] or None,
        tuple(int(s) for s in a.seeds.split(",")), a.seconds,
    )
    print(json.dumps({"rehearsal_failures": n, "platform": "cpu"}))
    sys.exit(1 if n else 0)
