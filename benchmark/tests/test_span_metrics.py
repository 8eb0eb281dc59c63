"""The `span_count` reader on a hand-made run, and the per-layer metrics
that read the engine's `launch` and `host_pull` spans in the CPU rehearsal
of every cell (counts and host times of a rehearsal; never device
numbers)."""

import io
import json

import pytest

from benchmark import rehearse
from benchmark.harness import spec
from benchmark.harness.cell import run_cell
from benchmark.readers import span_count

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SPAN_METRICS = [
    "launches_per_stmt", "host_pulls_per_stmt", "d2h_mb_per_stmt",
    "dispatch_ms", "host_pull_ms", "host_active_ms",
]


def _span(name, **attrs):
    return {"name": name, "duration_ms": 1.0,
            "attributes": json.dumps(attrs) if attrs else ""}


RUN = {"spans": [
    ("q1", [_span("query"), _span("launch", step="filter_project"),
            _span("launch", step="agg_reduce", path="onehot"),
            _span("host_pull", why="capacity", bytes=8),
            _span("host_pull", why="result", bytes=1000)]),
    ("q2", [_span("query"), _span("launch", step="agg_reduce"),
            _span("host_pull", why="result", bytes=3000)]),
    ("q3", [_span("query")]),
]}


@pytest.mark.parametrize("args, expect", [
    ({"of": "launch"}, 1.0),
    ({"of": "host_pull"}, 1.0),
    ({"of": "host_pull", "where": {"why": "result"}}, 2 / 3),
    ({"of": "launch", "where": {"step": "agg_reduce", "path": "onehot"}},
     1 / 3),
    ({"of": "host_pull", "attr": "bytes", "scale": 1e-3}, 4.008 / 3),
    ({"of": "host_pull", "where": {"why": "result"}, "attr": "bytes"},
     4000 / 3),
    ({"of": "compile"}, 0.0),  # no such span anywhere: a count of 0
])
def test_span_count(args, expect):
    assert span_count.read(RUN, **args) == pytest.approx(expect)


@pytest.mark.parametrize("spans", [[], [("q1", [])]])
def test_span_count_reads_nothing_without_a_tree(spans):
    assert span_count.read({"spans": spans}, of="launch") is None


@pytest.fixture(scope="module")
def rehearsed():
    """One traced rehearsal per cell, shared by the cases below."""
    results = {}

    def get(name):
        if name not in results:
            results[name] = run_cell(
                name, 5, 0.5, True, config_overrides=rehearse.TINY,
                out=io.StringIO(),
            )
        return results[name]

    return get


@pytest.mark.parametrize("metric", SPAN_METRICS)
@pytest.mark.parametrize("name", CELLS)
def test_span_metric_reads_in_every_cell(rehearsed, name, metric):
    cell = spec.Cell(name)
    how = [m for m in cell.per_layer if m["name"] == metric]
    assert how and how[0]["reader"] in ("span_count", "span_mean")
    metrics = rehearsed(name)["metrics"]
    assert metric in metrics, sorted(metrics)
    value = metrics[metric]["value"]
    if metric in ("launches_per_stmt", "host_pulls_per_stmt"):
        assert value >= 1
    elif metric == "host_active_ms":
        assert 0 <= value <= metrics["execute_ms"]["value"]
    else:
        assert value > 0
