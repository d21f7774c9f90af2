"""BENCHMARK.json and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix; each is
a JSON file under ``benchmark/``. Generators, references and readers are
Python modules found by the names those files give. Adding one of any kind
is adding files and one manifest entry; nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str | None = None) -> dict:
    return load_json(path or os.path.join(CHECKOUT, "BENCHMARK.json"))


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in the manifest")


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (kind: generators,
    references, readers)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"{kind[:-1]} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with its configuration, traffic mix and metrics."""

    def __init__(self, manifest: dict, workload: str):
        """``manifest`` is BENCHMARK.json's object; a rehearsal manifest
        (``benchmark/tests``) may carry ``dirs`` naming where its traffic
        and metric files live."""
        self.manifest = manifest
        dirs = manifest.get("dirs", {})
        self.entry = _by_name(manifest["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        centry = _by_name(manifest["configs"], self.entry["config"],
                          "configuration")
        self.config = load_json(os.path.join(CHECKOUT, centry["file"]))
        self.config_name = centry["name"]
        self.traffic_name = self.entry["traffic"]
        tdir = os.path.join(CHECKOUT, dirs["traffic"]) if "traffic" in dirs \
            else os.path.join(BENCH_DIR, "traffic")
        self.traffic = load_json(os.path.join(
            tdir, self.traffic_name + ".json"))
        self.metrics_dir = os.path.join(CHECKOUT, dirs["metrics"]) \
            if "metrics" in dirs else os.path.join(BENCH_DIR, "metrics")

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def _described(self, kind: str) -> list:
        """The cell's metrics of one kind, each with its descriptor file
        (``benchmark/metrics/<name>.json``: reader and parameters)."""
        out = []
        for m in self.manifest[kind]:
            if not self._reports(m):
                continue
            desc = load_json(os.path.join(self.metrics_dir,
                                          m["name"] + ".json"))
            out.append(dict(m, **{"reader": desc["reader"],
                                  "params": desc.get("params", {})}))
        return out

    def end_to_end(self) -> list:
        return self._described("end_to_end")

    def per_layer(self) -> list:
        return self._described("per_layer")
