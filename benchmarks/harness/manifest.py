"""Finds what belongs to a cell by the names in `BENCHMARK.json`:

    workloads/<cell>.json        query list, loop kind and its parameters
    configs/<config>.json        scale, generator, session conf, guarantees,
                                 assumed, reduced
    data/<generator>.py          gen_tables(scale, seed, columns)
    queries/<q>.py               build / reference / needed_bytes
    layer_metrics/<name>.json    reader kind and key of a per-layer metric
    harness/loops/<kind>.py, harness/readers/<kind>.py

Adding a cell, a configuration, a query or a per-layer metric adds files and
`BENCHMARK.json` entries; it edits no file that is there."""
from __future__ import annotations

import importlib
import json
import os
from typing import List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class ManifestError(SystemExit):
    pass


def _json(*parts, what: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise ManifestError(f"benchmark FAILED: {what} has no file; add "
                            f"{os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json", what="the benchmark")


def _module(package: str, name: str, what: str):
    path = os.path.join(BENCH, *package.split("."), name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"benchmark FAILED: {what} {name!r} has no file; "
                            f"add {os.path.relpath(path, ROOT)}")
    return importlib.import_module(f"{package}.{name}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    def __init__(self, name: str):
        bench = benchmark()
        entry = [w for w in bench["workloads"] if w["name"] == name]
        if not entry:
            raise ManifestError(
                f"benchmark FAILED: BENCHMARK.json has no workload {name!r} "
                f"(it has {[w['name'] for w in bench['workloads']]})")
        self.name = name
        self.chips: int = entry[0]["chips"]
        self.spec = _json(BENCH, "workloads", name + ".json",
                          what=f"workload {name!r}")
        self.config_name: str = entry[0]["config"]
        self.config = _json(BENCH, "configs", self.config_name + ".json",
                            what=f"configuration {self.config_name!r}")
        self.generator = _module("data", self.config["generator"],
                                 "data generator")
        self.query_names: List[str] = list(self.spec["queries"])
        self.queries = {q: _module("queries", q, "query")
                        for q in self.query_names}
        self.loop = _module("harness.loops", self.spec["loop"]["kind"],
                            "loop kind")
        self.end_to_end = [m["name"] for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.units = {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}
        self.per_layer = []
        for m in bench["per_layer"]:
            if _applies(m, name):
                spec = _json(BENCH, "layer_metrics", m["name"] + ".json",
                             what=f"per-layer metric {m['name']!r}")
                reader = _module("harness.readers", spec["reader"],
                                 "reader kind")
                self.per_layer.append((m["name"], spec, reader))
