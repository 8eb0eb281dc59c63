#!/usr/bin/env python3
"""The control of `correct`: the plain reference put in the program's place
with one guarantee of the configuration broken, judged by the same
comparison as the program's answers.  Every control has to come out as NOT
correct; a control that passes means the comparison cannot see that fault.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--schema tiny]

Runs at the cell's own size by default (host work only: it holds no chip).
The benchmark's own runs never run it.  The controls (see
`benchmark/reference/tpch.py`):

- `float32_sums` -- the precision step that would tempt a later PR: the
  configurations state exact decimal aggregates (integers end to end); the
  chip's native arithmetic is float32, and its float64 is emulated from it
  (PR 21 found the float64 one-hot aggregation wrong in the 5th digit);
- `partial_table` -- half of the table left out;
- `unordered` -- ORDER BY rows out of order.

`float32_sums` and `partial_table` have to fail every cell on every seed;
`unordered` every cell whose mix has an ORDER BY statement of two rows or
more.  Exit code 0 = every control failed as it must.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402


def control_cell(name: str, seed: int, schema=None, bench=None) -> dict:
    """{fault: answers_wrong} for one seed, with the statements compared."""
    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec, traffic

    cell = spec.Cell(name, bench)
    schema = schema or cell.config["schema"]
    mix = traffic.Mix(cell.traffic, seed)
    module = importlib.import_module(
        f"benchmark.reference.{cell.config['suite']}"
    )
    suite = module.Suite(schema)
    out = {"workload": name, "seed": seed, "schema": schema, "controls": {}}
    for fault in (None,) + tuple(module.FAULTS):
        t0 = time.perf_counter()
        statements = mix.warmup()
        # the control's answers stand where the program's would
        answers = suite.answers(
            [(st.query, st.params) for st in statements], fault=fault
        )
        for st, a in zip(statements, answers):
            st.rows = a["rows"]
        compared, details = cell_mod.check_answers(
            cell.config["suite"], schema, statements
        )
        out["controls"][fault or "none"] = {
            "correct": cell_mod.is_correct(compared),
            "answers_wrong": compared["answers_wrong"]["value"],
            "of": len(statements),
            "seconds": round(time.perf_counter() - t0, 2),
            "details": details[:2],
        }
    return out


def verdict(result: dict) -> list:
    """What is wrong with a control run (empty = every control failed as it
    must, and the unbroken reference passed)."""
    c = result["controls"]
    bad = []
    if not c["none"]["correct"]:
        bad.append("the unbroken reference does not pass its own comparison")
    for fault in ("float32_sums", "partial_table"):
        if c[fault]["correct"]:
            bad.append(f"control {fault} came out correct")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--schema", default=None)
    a = ap.parse_args(argv)
    failures = 0
    for seed in (int(s) for s in a.seeds.split(",")):
        result = control_cell(a.workload, seed, a.schema)
        result["problems"] = verdict(result)
        failures += bool(result["problems"])
        print(json.dumps(result), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
