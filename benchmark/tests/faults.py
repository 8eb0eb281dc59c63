#!/usr/bin/env python3
"""Drives a whole run of a cell at `tpch.tiny` on the CPU with the timed
path broken underneath, and prints the result line: `correct` has to come
out false.  One fault per process (the program caches traced steps, so a
patch must be in place before the first statement).

    python3 benchmark/tests/faults.py <cell> <fault>

Faults (the ones a query engine can have, of the builder's list):

- `answer_altered`: one cell of a statement's result altered where it is
  produced (`LocalQueryRunner.execute`, under the server and the client);
- `half_left_out`: every other split of a table left out of the scan, the
  aggregate taken over the rest;
- `exchange_left_out` (mesh cells): `all_to_all` returns its input, so rows
  stay on the chip they were scanned on;
- `none`: nothing broken -- `correct` has to come out true (the test of the
  test).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

TINY = {"schema": "tiny", "session": {"broadcast_join_rows": 100}}


def break_path(fault: str) -> None:
    import trino_tpu  # noqa: F401

    if fault == "none":
        return
    if fault == "answer_altered":
        from trino_tpu.runtime.runner import LocalQueryRunner

        real = LocalQueryRunner.execute

        def execute(self, sql):
            result = real(self, sql)
            if result.rows:
                first = list(result.rows[0])
                for i in range(len(first) - 1, -1, -1):
                    v = first[i]
                    if isinstance(v, int) and not isinstance(v, bool):
                        first[i] = v + 1
                        break
                    if hasattr(v, "as_tuple"):  # Decimal: one unit of scale
                        first[i] = v + v.__class__(1).scaleb(
                            v.as_tuple().exponent
                        )
                        break
                result.rows[0] = tuple(first)
            return result

        LocalQueryRunner.execute = execute
    elif fault == "half_left_out":
        from trino_tpu.connectors.tpch import TpchConnector

        real_splits = TpchConnector.splits

        def splits(self, handle, target_splits, predicate=None):
            out = real_splits(self, handle, target_splits, predicate)
            return out[::2] if len(out) > 1 else out

        TpchConnector.splits = splits
    elif fault == "exchange_left_out":
        import jax

        jax.lax.all_to_all = lambda x, *a, **k: x
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    cell, fault = sys.argv[1], sys.argv[2]
    break_path(fault)
    from benchmark.harness.cell import run_cell

    run_cell(cell, 7, 0.5, False, config_overrides=TINY)
    return 0


if __name__ == "__main__":
    sys.exit(main())
