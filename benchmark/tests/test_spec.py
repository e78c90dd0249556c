"""BENCHMARK.json: every file it names is found by name, and it keeps
the contract's shape."""
import importlib
import json
import os
import re

import pytest

from benchmark import run

ROOT = run.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1] == "benchmark/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_file_a_cell_names_is_found(cell):
    c = run.load_cell(cell)
    assert c["config"]["name"] == c["cell"]["config"]
    assert run.module("systems", c["config"]["system"])
    assert run.module("references", c["config"]["reference"])
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(run.module("metrics", m["name"]).read)
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and c["per_layer"]


def test_names_units_and_keys():
    seen = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] \
            == c["name"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


def test_a_kind_missing_from_the_peaks_is_an_error():
    roof = importlib.import_module("benchmark.metrics.wgl_roofline")
    assert roof.peaks_for("TPU v5 lite")["vpu_int32_ops_per_s"]["derived"]
    for row in json.load(open(roof.PEAKS)).values():
        assert all(v["source"] for v in row.values())
    with pytest.raises(KeyError):
        roof.peaks_for("TPU v9 imaginary")
