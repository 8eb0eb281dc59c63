"""Tests of the benchmark itself: the files BENCHMARK.json names exist and
agree, the generator is a function of the seed, the comparison fails what it
must (controls, and faults planted under the timed path), the trace
reduction reproduces fixed numbers, and every cell rehearses end to end."""

import copy
import io
import json
import os
import subprocess
import sys

import pytest

from benchmark import control, rehearse, trace_reduce
from benchmark.datagen import tpch as gen
from benchmark.harness import compare, spec, traffic

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
HERE = os.path.dirname(os.path.abspath(__file__))


# -- the files ---------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    cell = spec.Cell(name, BENCH)
    assert cell.chips == cell.config["chips"]
    for q in cell.traffic["queries"]:
        assert q["name"] in cell.config["queries"]
        assert os.path.exists(spec.path(q["template"]))
    for m in cell.per_layer:
        assert os.path.exists(spec.path("readers", m["reader"] + ".py"))
    moves = {m["moves"] for m in cell.per_layer}
    assert moves <= {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("schema", ["sf1", "sf10"])
def test_config_rows_are_the_generators(schema):
    import numpy as np

    t = gen.Tpch(schema)
    lines = int(t.line_counts(np.arange(t.O, dtype=np.int64)).sum())
    for c in BENCH["configs"]:
        with open(os.path.join(spec.REPO_DIR, c["file"])) as f:
            config = json.load(f)
        if config["schema"] == schema:
            assert config["rows"] == {
                "lineitem": lines, "orders": t.O, "customer": t.C
            }


def test_roofline_bytes_per_row():
    from benchmark.readers.roofline import statement_bytes

    config = spec.Cell("tpch_sf10.scan_agg", BENCH).config
    rows = config["rows"]["lineitem"]
    assert statement_bytes(config, "q1") == rows * 11
    assert statement_bytes(config, "q6") == rows * 8


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


# -- traffic -----------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    cell = spec.Cell(name, BENCH)
    big = 2**31 + 12345
    a, b = traffic.Mix(cell.traffic, big), traffic.Mix(cell.traffic, big)
    pa = [st.sql for _ in range(3) for st in a.next_pass()]
    pb = [st.sql for _ in range(3) for st in b.next_pass()]
    assert pa == pb
    # every seed: the same queries per pass, only literals and order differ
    other = traffic.Mix(cell.traffic, 1)
    assert sorted(st.query for st in other.next_pass()) == sorted(
        st.query for st in a.next_pass()
    )
    assert {st.sql for st in a.warmup()} == set(pa)


def test_parameters_stay_in_clause_2_4_ranges():
    cell = spec.Cell("tpch_sf10.scan_agg", BENCH)
    for seed in range(50):
        base = traffic.Mix(cell.traffic, seed).base
        assert 60 <= base["q1"]["delta"] <= 120
        assert base["q6"]["date"] in {f"{y}-01-01" for y in range(1993, 1998)}
        assert base["q6"]["discount"] in {f"0.0{d}" for d in range(2, 10)}
        assert base["q6"]["quantity"] in (24, 25)


# -- the comparison ----------------------------------------------------------


def test_compare_is_exact_and_type_strict():
    from decimal import Decimal

    answer = {"rows": [(1, Decimal("2.50"))], "ordered": False, "key": [],
              "limit": None, "tail": []}
    assert compare.wrong([(1, Decimal("2.50"))], answer) == ""
    assert compare.wrong([(1, Decimal("2.51"))], answer)
    assert compare.wrong([(1, 2.5)], answer)  # a float is not a decimal
    assert compare.wrong([], answer)


def test_compare_order_and_boundary_ties():
    rows = [(1, 9), (2, 7), (3, 7)]
    answer = {"rows": rows[:2], "tail": [rows[2]], "ordered": True,
              "key": [1], "limit": 2}
    assert compare.wrong([(1, 9), (2, 7)], answer) == ""
    assert compare.wrong([(1, 9), (3, 7)], answer) == ""  # tie at the limit
    assert compare.wrong([(2, 7), (1, 9)], answer)  # out of order
    assert compare.wrong([(1, 9), (4, 7)], answer)  # not a row of the table


@pytest.mark.parametrize("name", CELLS)
def test_controls_fail(name):
    """The reference with a guarantee broken, in the program's place."""
    result = control.control_cell(name, 11, schema="tiny", bench=BENCH)
    assert control.verdict(result) == []
    assert not result["controls"]["float32_sums"]["correct"]
    assert not result["controls"]["partial_table"]["correct"]
    assert not result["controls"]["unordered"]["correct"]


FAULTS = [
    (CELLS[0], "none", True),
    (CELLS[0], "answer_altered", False),
    (CELLS[0], "half_left_out", False),
] + [
    (w["name"], fault, False)
    for w in BENCH["workloads"] if w["chips"] > 1
    for fault in ("exchange_left_out", "answer_altered")
]


@pytest.mark.parametrize("name,fault,expect", FAULTS)
def test_fault_under_the_timed_path(name, fault, expect):
    """A whole run (all but the look for a chip) with the timed path broken
    underneath: `correct` comes out false."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"), name, fault],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is expect, result["compared"]
    assert list(result)[-1] == "compared"
    assert "compared answers_wrong" in proc.stderr


# -- the trace reduction -----------------------------------------------------


def test_trace_reduce_self_check():
    assert trace_reduce.self_check() == []


def test_union_and_gaps():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


# -- the rehearsal -----------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses(name):
    assert rehearse.rehearse([name], seeds=(3, 2**31 + 7), seconds=0.5) == 0


@pytest.mark.parametrize("mix", ["streams3", "open"])
def test_mix_keys_need_no_code(mix):
    """streams, open_rate, fresh_literals and clear_pool are read today: a
    later mix that sets them is a data file."""
    from benchmark.harness.cell import run_cell

    bench = copy.deepcopy(BENCH)
    bench["workloads"] = [{
        "name": "t", "config": BENCH["configs"][0]["name"],
        "traffic": "../tests/mixes/" + mix, "chips": 1, "why": "test",
    }]
    result = run_cell("t", 5, 0.5, False, bench=bench, out=io.StringIO(),
                      config_overrides=rehearse.TINY)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 2 and result["failed"] == 0


# -- the command -------------------------------------------------------------


def test_command_refuses_to_run_off_the_chip():
    """No TPU: non-zero exit, no result line, no metric (no CPU fallback)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=spec.REPO_DIR,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "x"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_collectives_are_told_by_opcode():
    """HLO texts as the four-chip trace printed them (PR 25)."""
    a2a = ("%all_to_all.79 = u32[4,1,16384]{2,1,0:T(1,128)S(1)} all-to-all("
           "u32[4,1,16384]{2,1,0:T(1,128)S(1)} %bitcast.74), channel_id=1, "
           "replica_groups={{0,1,2,3}}, dimensions={0}")
    assert trace_reduce.parse_op(a2a) == (
        "all_to_all.79", "u32[4,1,16384]", "all-to-all"
    )
    assert trace_reduce.is_collective(a2a)
    consumer = ("%fusion.5 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion("
                "u32[4,1,16384]{2,1,0} %all-to-all.3), kind=kCustom, "
                "calls=%fused_computation")
    assert not trace_reduce.is_collective(consumer)
    assert trace_reduce.is_collective("%all-gather-start.2")
    assert not trace_reduce.is_collective("%copy-start.1")
