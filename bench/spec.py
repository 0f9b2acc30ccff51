"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is the file its ``configs`` entry names, with its plain
reference beside it (``<dir of the file>/<config["reference"]>``). A
traffic mix is ``bench/traffic/<traffic>.json``, a metric's reader is
``bench/metrics/<metric>.py`` and an engine driver is
``bench/drivers/<config["engine"]["kind"]>.py``. Each is looked up under
the benchmark's root first and then beside this file, so a cell whose
files live elsewhere runs with no file here edited.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def find(root: str, sub: str, filename: str) -> str:
    """``<root>/bench/<sub>/<filename>``, else the same file beside this
    module; raises FileNotFoundError naming both places."""
    tried = []
    for base in (os.path.join(root, "bench"), HERE):
        path = os.path.join(base, sub, filename)
        if os.path.isfile(path):
            return path
        tried.append(path)
    raise FileNotFoundError(f"{filename}: not found at {' or '.join(tried)}")


def load_module(path: str):
    """Import a Python file by path under a name made from that path."""
    name = "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: dict
    config_path: str
    traffic: dict
    end_to_end: list[dict]       # this cell's end-to-end metric entries
    per_layer: list[dict]        # this cell's per-layer metric entries
    root: str

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        return load_module(find(self.root, "metrics", f"{metric}.py")).read

    def driver(self):
        kind = self.config["engine"]["kind"]
        return load_module(find(self.root, "drivers", f"{kind}.py")).Driver

    def reference(self):
        path = os.path.join(os.path.dirname(self.config_path),
                            self.config["reference"])
        return load_module(path)


class Bench:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        cpath = os.path.join(self.root, configs[w["config"]]["file"])
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        layer = [m for m in self.spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
        return Cell(name=name, chips=w["chips"], config=load_json(cpath),
                    config_path=cpath,
                    traffic=load_json(find(self.root, "traffic",
                                           f"{w['traffic']}.json")),
                    end_to_end=e2e, per_layer=layer, root=self.root)
