"""BENCHMARK.json and the data files it names: a cell is resolved to its
configuration, its traffic mix and the per-layer metrics that list it — by
name, so a new cell, configuration, mix or metric is new files and new
entries, and no edit here."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path: str) -> Dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.doc["paths"][0])
        self._modules: Dict = {}

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json; "
                       f"there are {[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                doc = _load(os.path.join(self.root, c["file"]))
                doc["name"] = name
                return doc
        raise KeyError(f"no configuration named {name!r}")

    def traffic(self, name: str) -> Dict:
        doc = _load(os.path.join(self.bench_dir, "traffic", name + ".json"))
        doc["name"] = name
        return doc

    def reports(self, metric: Dict, cell: str) -> bool:
        """Whether ``cell`` reports ``metric``: it is listed under the
        metric's ``workloads``, or the metric lists none and the cell reports
        the end-to-end metric it moves."""
        if "workloads" in metric:
            return cell in metric["workloads"]
        moved = metric.get("moves")
        if moved is None:
            return True
        return any(e["name"] == moved and self.reports(e, cell)
                   for e in self.doc["end_to_end"])

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.doc["end_to_end"] if self.reports(m, cell)]

    def per_layer(self, cell: str) -> List[Dict]:
        return [m for m in self.doc["per_layer"] if self.reports(m, cell)]

    def _reader_module(self, module: str):
        """``readers/<module>.py`` of THIS manifest's tree, loaded by path
        (a copy of the benchmark with a reader added finds its own)."""
        if module not in self._modules:
            self._modules[module] = _load_module(
                f"benchmark_reader_{module}",
                os.path.join(self.bench_dir, "readers", module + ".py"))
        return self._modules[module]

    def reader(self, metric: str) -> Callable:
        """The reader function of one per-layer metric, found through
        ``metrics/<metric>.json``: {"reader": "<module>:<function>", ...}
        with the module under ``readers/``."""
        spec = _load(os.path.join(self.bench_dir, "metrics",
                                  metric + ".json"))
        module, _, func = spec["reader"].partition(":")
        mod = self._reader_module(module)
        fn = getattr(mod, func or "read")
        args = spec.get("args", {})
        return lambda ctx: fn(ctx, **args)


def _load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> Dict[str, float]:
    table = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "add it with its source — there is no default")
    return table[device_kind]
