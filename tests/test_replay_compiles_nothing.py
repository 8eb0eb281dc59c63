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

import json
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


#: a second draw of Q3's literals on the mesh: MACHINERY's customers span
#: keys 8..1493 where SEED's BUILDING span 2..1500 at `tiny`, so its
#: customer build has another key domain at the same table size
SECOND_DRAW = 3000000033

#: the mesh join's programs (`parallel/runner.py` `_sized_expansion`)
JOIN_PROGRAMS = ("licensed_expand", "fused_expand", "locate", "expand")


@pytest.fixture(scope="module")
def compiled():
    """The names of the programs compiled (or loaded) since it was cleared."""
    import jax

    names: list = []

    def listen(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(str(fun_name))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return names


def test_mesh_q3_second_draw_compiles_no_join_program(
    watch, runners, compiled
):
    """The direct-table locate's key domain is an argument of the mesh
    join's programs, not a part of their keys: after one draw of Q3's
    literals is warm, a draw whose builds span other keys compiles its own
    scans' predicates and no join program, and then replays compiling
    nothing."""
    cell = spec.Cell("tpch_sf1_mesh4.partitioned_join")
    runner = runners("mesh4", cell.config["catalog"])
    (first,) = traffic.Mix(cell.traffic, SEED).warmup()
    (second,) = traffic.Mix(cell.traffic, SECOND_DRAW).warmup()
    assert (first.params["segment"], second.params["segment"]) == (
        "BUILDING", "MACHINERY"
    )
    for _ in range(WARMUP_PASSES):
        before = watch.compiles
        runner.execute(first.sql)
        if watch.compiles == before:
            break
    compiled.clear()
    rows = runner.execute(second.sql).rows
    _, flat = runner.traces[-1]
    assert [
        json.loads(s["attributes"])["locate"]
        for s in flat if s["name"] == "join"
    ] == ["join_locate_table"] * 2
    assert not [
        n for n in compiled
        if n.removeprefix("jit(").removesuffix(")") in JOIN_PROGRAMS
    ], compiled
    before = watch.compiles
    assert runner.execute(second.sql).rows == rows
    assert watch.compiles == before
