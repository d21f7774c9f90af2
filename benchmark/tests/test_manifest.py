"""BENCHMARK.json against the files it names."""

import json
import os
import re

import pytest

from conftest import BENCH, CHECKOUT
from harness.manifest import Cell, load_manifest, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_keys_and_names():
    m = load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert any(e["name"] == "setup_s" and e["bound"] <= 0.25
               for e in m["end_to_end"])
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert p["moves"] in e2e
        assert set(p["workloads"]) <= cells
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536


def test_every_named_file_exists_and_loads():
    m = load_manifest()
    for w in m["workloads"]:
        cell = Cell(m, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["chips"] == w["chips"]
        load_module("generators", cell.config["data"]["generator"]).make
        load_module("generators",
                    cell.traffic["queries"]["generator"]).Queries
        load_module("references", cell.config["reference"]["name"]).Reference
        layer_metrics = cell.per_layer()
        assert layer_metrics, w["name"]
        for p in layer_metrics + cell.end_to_end():
            load_module("readers", p["reader"]).read
        assert {e["name"] for e in cell.end_to_end()} >= {"setup_s",
                                                          "search_qps"}
    for c in m["configs"]:
        cfg = json.load(open(os.path.join(CHECKOUT, c["file"])))
        assert cfg["source"] == c["source"] or c["source"] in cfg["source"] \
            or cfg["source"][:40] == c["source"][:40]
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("benchmark/")


def test_every_metric_file_is_in_the_manifest():
    m = load_manifest()
    named = {p["name"] for p in m["per_layer"] + m["end_to_end"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert on_disk == named


def test_rehearsal_cells_are_picked_up_as_files(rehearsal_manifest):
    """A configuration, a traffic mix and a cell added as new files and
    manifest entries, no harness file edited."""
    for w in rehearsal_manifest["workloads"]:
        cell = Cell(rehearsal_manifest, w["name"])
        assert cell.config["name"].startswith("tiny_")
        assert cell.per_layer()
    with pytest.raises(Exception):
        Cell(load_manifest(), "tiny_knn.knn_c4")    # not a real cell
