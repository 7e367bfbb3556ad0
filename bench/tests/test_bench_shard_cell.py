"""The four-chip HPCG cell: found by name with the chips it asks for, its
two per-layer readers, and what they read where the program keeps no
sharded counters."""
import pytest

import bench_helpers  # noqa: F401  (import paths)
from spbench.harness import RunView
from spbench.spec import Spec
from spbench.tracefile import TraceSummary
from spbench.work import spmm_bytes, spmm_flops

CELL = "hpcg.cg_4chip"


def _view(kernel_s: float) -> RunView:
    trace = TraceSummary(window_s=20.0, busy_s=15.0,
                         kernel_s={"bsr_spmv": kernel_s}, device_ops=[],
                         idle_gaps=[])
    nnz, n = 94_065_400, 3_538_944
    return RunView(trace, {"launches": 600.0}, {"iters_per_s": 30.0},
                   600 * spmm_bytes(nnz, n, n), 600 * spmm_flops(nnz),
                   "TPU v5 lite")


def test_the_cell_is_found_with_four_chips():
    cell = Spec().cell(CELL)
    assert cell.chips == 4
    assert cell.config["nz"] == 4 * cell.config["nx"] == 384
    assert cell.traffic["loop"] == "cg"
    assert float(cell.limits["spmv_err"]) > 0
    names = {m["name"] for m in cell.per_layer}
    assert {"bsr_spmv_roofline.shard", "shard.exchange_mb", "plan.launch_ms",
            "device.idle_pct.solve"} <= names
    assert "bsr_spmv_roofline.solve" not in names
    assert {m["name"] for m in cell.end_to_end} == {"iters_per_s", "setup_s"}


def test_shard_roofline_is_a_quarter_of_the_one_chip_formula(monkeypatch):
    cell = Spec().cell(CELL)
    shard = cell.reader("bsr_spmv_roofline.shard")
    solve = Spec().cell("hpcg.cg").reader("bsr_spmv_roofline.solve")
    monkeypatch.setattr(shard, "chips", lambda: 4)
    view = _view(kernel_s=10.0)
    assert shard.read(view) == pytest.approx(solve.read(view) / 4)
    assert 0 < shard.read(view) <= 100
    assert shard.read(_view(kernel_s=0.0)) is None


def test_exchange_mb_is_bytes_per_launch(monkeypatch):
    import repro.obs
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    monkeypatch.setattr(repro.obs, "default_registry", lambda: reg)
    reader = Spec().cell(CELL).reader("shard.exchange_mb")
    assert reader.read(_view(1.0)) is None       # no sharded launch counted
    reg.inc("shard.launches", 4)
    reg.inc("shard.exchange_bytes", 4 * 60_963_840)
    assert reader.read(_view(1.0)) == pytest.approx(60.96384)
