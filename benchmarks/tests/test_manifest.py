import json
import os
import re

from harness import device, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_files_of_benchmark_json():
    bench = manifest.benchmark()
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in bench["configs"]]
             + [k for c in bench["configs"] for k in c["reduced"]]
             + [x for w in bench["workloads"]
                for x in (w["name"], w["config"], w["traffic"])])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_every_cell_finds_its_files_and_agrees_with_them():
    bench = manifest.benchmark()
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"])
        assert cell.spec["config"] == w["config"]
        assert cell.config["chips"] == w["chips"]
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for _name, spec, _reader in cell.per_layer:
            layer = [m for m in bench["per_layer"] if m["name"] == _name][0]
            assert (spec["unit"], spec["layer"]) == (layer["unit"],
                                                     layer["layer"])


def test_a_missing_file_is_named(monkeypatch):
    monkeypatch.setattr(manifest, "benchmark", lambda: {
        "workloads": [{"name": "x.y", "config": "x", "chips": 1}],
        "end_to_end": [], "per_layer": []})
    try:
        manifest.Cell("x.y")
    except SystemExit as exc:
        assert "benchmarks/workloads/x.y.json" in str(exc)
    else:
        raise AssertionError("no error for a workload without a file")


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        device.peaks("cpu")
    except KeyError as exc:
        assert "peaks.json" in str(exc)
    else:
        raise AssertionError("a default peak was handed out")
