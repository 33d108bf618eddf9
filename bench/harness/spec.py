"""Resolution of a cell by name: ``BENCHMARK.json`` -> the cell's
configuration file, traffic file, query modules, per-layer readers,
matchers and work counters, each found by name under ``bench/``.

Nothing here branches on a cell: a cell, configuration, traffic mix,
query, matcher or per-layer metric is added by adding its file and its
``BENCHMARK.json`` entry.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import sys

SCALE_KEYS = ("n_orders", "n_parts", "n_suppliers", "n_customers",
              "n_nations", "price_unit_cents")


def load_module(root: str, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    key = f"_bench_{kind}_{name}_{abs(hash(os.path.abspath(path)))}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with everything it
    names, loaded from files."""

    def __init__(self, root: str, workload: str):
        self.root = root
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _read_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.scale = {k: self.config[k] for k in SCALE_KEYS}
        self.traffic = _read_json(os.path.join(
            root, "bench", "traffic", f"{self.entry['traffic']}.json"))
        self.queries = {q: load_module(root, "queries", q)
                        for q in self.traffic["queries"]}
        self.options = {q: dict(self.traffic.get("options", {}).get(q, {}))
                        for q in self.queries}
        self.limits = {q: _read_json(os.path.join(
            root, "bench", "queries", f"{q}.limits.json"))
            for q in self.queries}

        def applies(m):
            return "workloads" not in m or workload in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]
        self.layers = {m["name"]: load_module(root, "layers", m["name"])
                       for m in self.per_layer}
        self.matchers = {}
        for path in sorted(glob.glob(os.path.join(root, "bench", "matchers",
                                                  "*.json"))):
            m = _read_json(path)
            self.matchers.setdefault(m["layer"], []).extend(m["ops"])
        self.peaks_table = _read_json(os.path.join(root, "bench",
                                                   "peaks.json"))

    def peaks(self, device_kind: str) -> dict:
        devs = self.peaks_table["devices"]
        if device_kind not in devs:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"bench/peaks.json (have {sorted(devs)})")
        return devs[device_kind]

    def work_module(self, kind: str):
        return load_module(self.root, "work", kind)
