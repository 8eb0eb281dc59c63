"""A replayed statement compiles nothing: the CPU shadow of the number every
cell of the benchmark requires on the chip (`compiles_in_window` 0).

Each statement of the four cells' mixes runs on the runner its cell serves,
at the connectors' smallest schema, with one seed's literals: as set-up does,
until a run compiles nothing (at most `WARMUP_PASSES` runs), and then once
more.  That replay may record no JAX `backend_compile` event, counted as the
benchmark counts them (`benchmark/harness/watch.CompileWatch`), and answers
the rows of the run before it.

It is fragile by construction, which is why it is tested: a literal is a jit
key, and a statement's second execution may take another program than its
first (a learned join capacity, a scan read from the buffer pool).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import spec, traffic  # noqa: E402
from benchmark.harness.cell import WARMUP_PASSES  # noqa: E402
from benchmark.harness.watch import CompileWatch  # noqa: E402

SEED = 3000000031

#: (runner, the cell whose mix holds the statement, statement).  On the four
#: workers of `tpch_sf1_mesh4` Q3 is its cell's mix; Q1, Q6 and Q18 are what
#: PERF.md section 7's `tpch_sf10_mesh4.*` will run there.
CASES = [
    ("local", "tpch_sf10.scan_agg", "q1"),
    ("local", "tpch_sf10.scan_agg", "q6"),
    ("local", "tpch_sf1.join_agg", "q3"),
    ("local", "tpch_sf1.join_agg", "q18"),
    ("local", "tpcds_sf1.star_report", "q3"),
    ("local", "tpcds_sf1.star_report", "q7"),
    ("local", "tpcds_sf1.star_report", "q27"),
    ("local", "tpcds_sf1.star_report", "q89"),
    ("mesh4", "tpch_sf10.scan_agg", "q1"),
    ("mesh4", "tpch_sf10.scan_agg", "q6"),
    ("mesh4", "tpch_sf1_mesh4.partitioned_join", "q3"),
    ("mesh4", "tpch_sf1.join_agg", "q18"),
]


@pytest.fixture(scope="module")
def watch():
    return CompileWatch()


@pytest.fixture(scope="module")
def runners():
    """One runner per (kind, catalog), built as `benchmark/harness/serve`
    builds a cell's, at `tiny`."""
    made = {}

    def runner(kind: str, catalog: str):
        if (kind, catalog) not in made:
            if kind == "local":
                from trino_tpu.runtime.runner import LocalQueryRunner

                made[kind, catalog] = LocalQueryRunner(
                    catalog=catalog, schema="tiny", target_splits=8
                )
            else:
                from trino_tpu.parallel import DistributedQueryRunner

                made[kind, catalog] = DistributedQueryRunner(
                    catalog=catalog, schema="tiny", n_workers=4
                )
        return made[kind, catalog]

    return runner


@pytest.mark.parametrize(
    "kind, cell, query", CASES, ids=[f"{k}-{c}-{q}" for k, c, q in CASES]
)
def test_replay_compiles_nothing(watch, runners, kind, cell, query):
    cell = spec.Cell(cell)
    mix = traffic.Mix(cell.traffic, SEED)
    (st,) = [s for s in mix.warmup() if s.query == query]
    runner = runners(kind, cell.config["catalog"])
    rows = None
    for _ in range(WARMUP_PASSES):
        before = watch.compiles
        rows = runner.execute(st.sql).rows
        if watch.compiles == before:
            break
    else:
        pytest.fail(
            f"{query} still compiles on run {WARMUP_PASSES} of {st.params}"
        )
    before = watch.compiles
    replay = runner.execute(st.sql).rows
    assert watch.compiles == before, st.params
    assert replay == rows
