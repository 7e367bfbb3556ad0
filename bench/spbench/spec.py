"""Finds everything a cell needs by name, from ``BENCHMARK.json`` and files.

Layout under the benchmark's directory (``bench/``):

* ``configs/<config>.json``   a configuration, at the path BENCHMARK.json names;
* ``generators/<name>.py``    builds a configuration's operand (``build``);
* ``traffic/<traffic>.json``  a traffic mix: the loop it drives and its numbers;
* ``metrics/<metric>.py``     one per-layer metric's reader (``read``);
* ``limits/<cell>.json``      the limits that decide ``correct`` in a cell.

A new configuration, mix, metric or cell is a new file and a new entry in
BENCHMARK.json; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    name: str
    chips: int
    config: dict                 # the configuration file's contents
    traffic: dict                # the traffic file's contents
    limits: dict                 # compared number -> limit
    end_to_end: List[dict]       # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    bench_dir: str

    def generator(self):
        return _load_module(os.path.join(
            self.bench_dir, "generators", f"{self.config['generator']}.py"),
            f"bench_generator_{self.config['generator']}")

    def reader(self, metric: str):
        return _load_module(os.path.join(self.bench_dir, "metrics",
                                         f"{metric}.py"),
                            f"bench_metric_{metric.replace('.', '_')}")


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


class Spec:
    """BENCHMARK.json at ``root``, its files under ``bench_dir``."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "bench")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            if not isinstance(self.doc.get(key), list):
                raise SpecError(f"BENCHMARK.json has no list {key!r}")
        self.configs: Dict[str, dict] = {c["name"]: c
                                         for c in self.doc["configs"]}
        self.workloads: Dict[str, dict] = {w["name"]: w
                                           for w in self.doc["workloads"]}

    def cell(self, name: str) -> Cell:
        w = self.workloads.get(name)
        if w is None:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; one of "
                            f"{sorted(self.workloads)}")
        c = self.configs.get(w["config"])
        if c is None:
            raise SpecError(f"workload {name!r} names unknown config "
                            f"{w['config']!r}")
        e2e = [m for m in self.doc["end_to_end"] if _applies(m, name)]
        layer = [m for m in self.doc["per_layer"] if _applies(m, name)
                 and any(e["name"] == m["moves"] for e in e2e)]
        return Cell(
            name=name, chips=int(w["chips"]),
            config=_load_json(os.path.join(self.root, c["file"])),
            traffic=_load_json(os.path.join(self.bench_dir, "traffic",
                                            f"{w['traffic']}.json")),
            limits=_load_json(os.path.join(self.bench_dir, "limits",
                                           f"{name}.json")),
            end_to_end=e2e, per_layer=layer, bench_dir=self.bench_dir)
