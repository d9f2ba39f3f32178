"""BENCHMARK.json and the data files it names.

A cell is found by its name in `workloads`; its configuration by `file`;
its traffic mix at traffic/<traffic>.json; a per-layer metric at
layer_metrics/<name>.json; a runner at runners/<kind>.py; a reducer at
reducers/<reducer>.py.  Adding any of them is adding files and one manifest
entry; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(directory, name):
    """Import <directory>/<name>.py by path (a later PR adds a file, no
    registry to edit)."""
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{os.path.basename(directory)}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, root=ROOT, bench_dir=None, path=None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "perfbench")
        self.data = _read_json(path or os.path.join(root, "BENCHMARK.json"))

    def cell(self, name):
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name):
        for c in self.data["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name):
        return _read_json(os.path.join(self.bench_dir, "traffic",
                                       name + ".json"))

    def metrics_of(self, cell_name, group):
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_metric(self, name):
        return _read_json(os.path.join(self.bench_dir, "layer_metrics",
                                       name + ".json"))

    def runner(self, kind):
        return load_module(os.path.join(self.bench_dir, "runners"), kind)

    def reducer(self, name):
        return load_module(os.path.join(self.bench_dir, "reducers"), name)

    def peaks(self, device_kind):
        table = _read_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in perfbench/peaks.json"
                f" — add its published peaks with their source")
        return table["devices"][device_kind]

    def out_dir(self, *parts):
        path = os.path.join(self.bench_dir, "out", *parts)
        os.makedirs(path, exist_ok=True)
        return path
