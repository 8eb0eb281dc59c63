"""Reads BENCHMARK.json and the files it names.  Nothing here knows a cell,
a configuration, a mix or a metric by name."""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def path(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def benchmark() -> dict:
    return _load(os.path.join(REPO_DIR, "BENCHMARK.json"))


def peaks(device_kind: str) -> dict:
    table = _load(path("peaks.json"))
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(k for k in table if not k.startswith('_'))}): add its "
            "published peaks with their source; there is no default"
        )
    return table[device_kind]


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json "
                f"({[w['name'] for w in bench['workloads']]})"
            )
        self.name = name
        self.workload = found[0]
        self.chips = int(self.workload["chips"])
        entry = [
            c for c in bench["configs"] if c["name"] == self.workload["config"]
        ][0]
        self.config = _load(os.path.join(REPO_DIR, entry["file"]))
        self.traffic = _load(
            path("traffic", self.workload["traffic"] + ".json")
        )
        self.end_to_end = [
            m for m in bench["end_to_end"] if _for_cell(m, name)
        ]
        self.per_layer = []
        for m in bench["per_layer"]:
            if _for_cell(m, name):
                how = _load(path("layer_metrics", m["name"] + ".json"))
                self.per_layer.append({**m, **how})
