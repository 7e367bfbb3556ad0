"""The harness finds configurations, traffic mixes, per-layer metrics and
limits by name; adding one is new files and entries, no edit elsewhere; and
a run refuses anything but a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

import bench_helpers
from spbench.harness import run_cell
from spbench.loops import LOOPS
from spbench.spec import Spec, SpecError


def test_every_committed_cell_resolves():
    spec = Spec()
    doc = spec.doc
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for name in spec.workloads:
        cell = spec.cell(name)
        assert cell.traffic["loop"] in LOOPS
        assert float(cell.limits["spmv_err"]) > 0
        assert hasattr(cell.generator(), "build")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert hasattr(cell.reader(m["name"]), "read")
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in spec.cell(w).end_to_end}


def test_an_unknown_workload_is_refused():
    with pytest.raises(SpecError, match="no workload"):
        Spec().cell("nope.nothing")


def test_a_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    as files and BENCHMARK.json entries are found and run; no file the
    benchmark already has is touched."""
    bench = bench_helpers.copy_bench(tmp_path)
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(dict(json.load(open(os.path.join(
            bench, "configs", "hpcg.json"))), nx=5, ny=6, nz=4), f)
    with open(os.path.join(bench, "traffic", "cg_loose.json"), "w") as f:
        json.dump({"loop": "cg", "tolerance": 1e-3, "max_iters": 5,
                   "check_samples": 3}, f)
    with open(os.path.join(bench, "metrics", "plan.launches.py"), "w") as f:
        f.write("def read(run):\n    return run.window.get('launches')\n")
    with open(os.path.join(bench, "limits", "tiny.cg_loose.json"), "w") as f:
        json.dump({"spmv_err": 1e-5}, f)
    doc = json.load(open(tmp_path / "BENCHMARK.json"))
    doc["configs"].append({"name": "tiny", "source": "test",
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "test"})
    doc["workloads"].append({"name": "tiny.cg_loose", "config": "tiny",
                             "traffic": "cg_loose", "chips": 1,
                             "why": "test"})
    doc["per_layer"].append({"name": "plan.launches", "unit": "launch",
                             "better": "higher", "source": "program_counter",
                             "layer": "plan and guard",
                             "moves": "iters_per_s",
                             "workloads": ["tiny.cg_loose"]})
    for m in doc["end_to_end"]:
        if m["name"] == "iters_per_s":
            m["workloads"].append("tiny.cg_loose")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = Spec(str(tmp_path), bench).cell("tiny.cg_loose")
    assert cell.config["nx"] == 5 and cell.traffic["tolerance"] == 1e-3
    assert [m["name"] for m in cell.per_layer][-1] == "plan.launches"
    out = run_cell(cell, 7, 0.3, True, time.monotonic(), log=lambda *a, **k: 0)
    assert out["correct"] is True
    assert out["metrics"]["plan.launches"]["value"] == out["attempted"] > 0
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bench) for p in fs if p in before}
    assert after == before


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg.cg", "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_on_the_cpu_is_refused_and_names_the_platform():
    r = _run_cli(bench_helpers.ROOT)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "tpu" in r.stderr
    assert "{" not in r.stdout


def test_a_run_without_the_program_is_refused(tmp_path):
    bench_helpers.copy_bench(tmp_path)
    r = _run_cli(str(tmp_path))
    assert r.returncode != 0
    assert "program under test is missing" in r.stderr
    assert "{" not in r.stdout
