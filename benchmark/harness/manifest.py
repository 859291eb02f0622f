"""BENCHMARK.json and the files it names. A cell is resolved by name to its
configuration (``configs/<config>.json``), the configuration's model family
(its ``family`` key: ``families/<family>/``), its traffic mix
(``traffic/<mix>.json``), the mix's runner (its ``kind`` key:
``runners/<kind>.py``), its limits (``limits/<cell>.json``) and the per-layer
metrics that list it (``metrics/<metric>.json``, each naming a function under
``readers/``). Code is found the way data is: by path from the name, inside
THIS manifest's tree, with no table of names anywhere.

So a new architecture is five things, all of them new files and new entries
and none an edit here: a configuration, its family (the program's builder,
the seed's weights, the plain reference and the counts; the protocol is
``families/README.md``), a traffic mix, limits calibrated on the chip, and
its entries in BENCHMARK.json; a runner only for a new kind of traffic
(``runners/README.md``), a reader only for a new per-layer metric.

What stays general, under ``harness/``: load generation and schedules
(``loadgen``), ``compare.judge`` and the limits, the reduction from a trace
(``trace_reduce``), a kernel's operations and bytes from its operand shapes
(``flops``), ``stats`` and ``peaks.json``; in ``run.py`` the window's
tracer, the context and the result line. The engine session with its
warm-up, the window's clocks and compile counting are the ``open_loop``
runner's, the staged feed and step loop the ``train`` runner's: general over
families, each for its kind of traffic."""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
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

    def family(self, config: Dict):
        """The model family a configuration names under ``family``: the
        package ``families/<family>/`` of THIS manifest's tree, loaded by
        path. It supplies what ``families/README.md`` lists. There is no
        default family: a configuration that names none, or one that has no
        files, ends the run."""
        return self._code(config, "configuration", "family", "families",
                          os.path.join("{}", "__init__.py"), package=True)

    def runner(self, traffic: Dict):
        """The runner a traffic mix names under ``kind``:
        ``runners/<kind>.py`` of THIS manifest's tree, loaded by path, with
        ``run(ctx)`` and ``end_to_end(ctx, name)`` (``runners/README.md``).
        No default either."""
        return self._code(traffic, "traffic mix", "kind", "runners", "{}.py")

    def _code(self, doc: Dict, what: str, key: str, folder: str, file: str,
              package: bool = False):
        name = doc.get(key)
        where = f"{what} {doc.get('name', '?')!r}"
        base = os.path.join(self.bench_dir, folder)
        if not name:
            raise LookupError(f"{where} has no \"{key}\" key in its file; "
                              f"it names the code to load from {base}")
        path = os.path.join(base, file.format(name))
        if not os.path.isfile(path):
            raise LookupError(f"{where} names the {key} {name!r}, and there "
                              f"is no {path}")
        return load_by_path(path, package=package)

    def reader(self, metric: str) -> Callable:
        """The reader function of one per-layer metric, found through
        ``metrics/<metric>.json``: {"reader": "<module>:<function>", ...}
        with the module under ``readers/``."""
        spec = _load(os.path.join(self.bench_dir, "metrics",
                                  metric + ".json"))
        module, _, func = spec["reader"].partition(":")
        mod = load_by_path(os.path.join(self.bench_dir, "readers",
                                        module + ".py"))
        fn = getattr(mod, func or "read")
        args = spec.get("args", {})
        return lambda ctx: fn(ctx, **args)


def load_by_path(path: str, package: bool = False):
    """The module at ``path``, loaded once per process and path (a copy of
    the benchmark with a file added finds its own). It is kept in
    ``sys.modules`` under a name made from the path: a package's relative
    imports and a dataclass's annotations look their module up there."""
    path = os.path.realpath(path)
    stem = os.path.basename(os.path.dirname(path)) if package \
        else os.path.splitext(os.path.basename(path))[0]
    name = (f"benchmark_by_path_{stem}_"
            f"{hashlib.sha1(path.encode()).hexdigest()[:12]}")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, path, submodule_search_locations=[os.path.dirname(path)]
        if package else None)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def peaks(device_kind: str) -> Dict[str, float]:
    table = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "add it with its source — there is no default")
    return table[device_kind]
