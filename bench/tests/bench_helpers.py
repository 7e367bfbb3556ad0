"""Shared set-up of the benchmark's CPU tests: import paths, and a copy of
the benchmark whose configurations are cut to a size a test run holds."""
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spbench.spec import Spec  # noqa: E402

# sizes a CPU test run holds; everything else as committed
SMALL = {"hpcg": {"nx": 8, "ny": 8, "nz": 8}, "kron": {"scale": 9}}
SMALL_RATE = 60.0


def copy_bench(root) -> str:
    """BENCHMARK.json and the benchmark's files (tests left out) under
    ``root``; returns the copied benchmark directory."""
    root = str(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    dst = os.path.join(root, "bench")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    return dst


def edit_json(path: str, **changes) -> None:
    with open(path) as f:
        doc = json.load(f)
    doc.update(changes)
    with open(path, "w") as f:
        json.dump(doc, f)


def small_spec(root) -> Spec:
    """The committed benchmark with its configurations cut to CPU size."""
    bench = copy_bench(root)
    for name, changes in SMALL.items():
        edit_json(os.path.join(bench, "configs", f"{name}.json"), **changes)
    edit_json(os.path.join(bench, "traffic", "ppr_serve_over.json"),
              rate_per_s=SMALL_RATE)
    return Spec(str(root), bench)
