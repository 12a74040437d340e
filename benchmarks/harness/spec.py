"""``BENCHMARK.json`` and the files it names.

One configuration is ``<file>`` (its sizes, JSON) and the module of the
same name beside it (how to build it from the program, its reference, its
limits).  One traffic mix is ``<root>/traffic/<traffic>.json``.  One
per-layer metric is ``<root>/layer_metrics/<name>.py`` with a
``read(run)``.  ``<root>`` is the first of the spec's ``paths``.  A name
with no file is an error that says which file is missing.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List


class SpecError(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    cfg: dict            # the configuration's sizes
    adapter: object      # the configuration's module
    mix: dict            # the traffic file
    end_to_end: List[str]
    per_layer: Dict[str, object]   # {metric name: module with read()}


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {path}")
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {path}")
    name = "hvd_bench_" + os.path.splitext(
        os.path.relpath(path))[0].replace(os.sep, "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Spec:
    def __init__(self, repo_root: str):
        self.repo_root = repo_root
        self.data = _load_json(os.path.join(repo_root, "BENCHMARK.json"),
                               "the benchmark")
        self.root = os.path.join(repo_root, self.data["paths"][0])

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.data["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.data["workloads"])
            raise SpecError(f"workload {name!r} is not in BENCHMARK.json; "
                            f"known: {known}")
        conf = next((c for c in self.data["configs"]
                     if c["name"] == entry["config"]), None)
        if conf is None:
            raise SpecError(f"workload {name!r}: configuration "
                            f"{entry['config']!r} is not in BENCHMARK.json")
        cfg_path = os.path.join(self.repo_root, conf["file"])
        cfg = _load_json(cfg_path, f"configuration {conf['name']!r}")
        adapter = _load_module(os.path.splitext(cfg_path)[0] + ".py",
                               f"configuration {conf['name']!r}")
        mix = _load_json(
            os.path.join(self.root, "traffic", entry["traffic"] + ".json"),
            f"traffic {entry['traffic']!r}")
        e2e = [m["name"] for m in self.data["end_to_end"]
               if _applies(m, name)]
        layers = {
            m["name"]: _load_module(
                os.path.join(self.root, "layer_metrics", m["name"] + ".py"),
                f"per-layer metric {m['name']!r}")
            for m in self.data["per_layer"] if _applies(m, name)}
        return Cell(name, entry["config"], entry["traffic"],
                    int(entry["chips"]), cfg, adapter, mix, e2e, layers)

    def unit(self, metric: str) -> str:
        for m in self.data["end_to_end"] + self.data["per_layer"]:
            if m["name"] == metric:
                return m["unit"]
        raise SpecError(f"metric {metric!r} is not in BENCHMARK.json")
