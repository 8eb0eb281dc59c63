#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  It holds the chip(s), serves the cell's
configuration behind an in-process `CoordinatorServer`, drives it with
`trino_tpu.client.Client` over HTTP, and prints as its LAST stdout line one
JSON object: `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` with `--trace 1`), then `compared`.  Where JAX finds no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no result:
there is no CPU fallback (`benchmark/rehearse.py` is the CPU rehearsal).
"""

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import spec

    cell = spec.Cell(args.workload)  # an unknown cell fails before JAX starts
    import trino_tpu  # noqa: F401  (absent program -> ImportError, no result)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: jax.devices()[0].platform is "
              f"{devices[0].platform!r}, not 'tpu': no accelerator, nothing "
              f"was run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, jax reports "
              f"{len(devices)}", file=sys.stderr)
        return 3

    from benchmark.harness.cell import run_cell

    run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
             t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
