"""The check that decides ``correct`` fails when the timed path is broken.

Each cell runs end to end on the CPU at a small size, past the harness's
look for a chip, with a fault planted where the program produces its
answers: a step that hands back its input unchanged, half of the answers
left out, one entry of every answer altered, and the control (the
reference at bf16x3, one precision step below the stated f32) in the
program's place. One chip, so no exchange between chips to leave out.
"""
import time

import numpy as np
import pytest

import bench_helpers
from spbench.harness import run_cell
from spbench.reference import matvec_bf16x3

CELLS = ("hpcg.cg", "kron.pagerank", "kron.ppr_serve_over")


def _unchanged(i, x, y):
    return x


def _half_rows_left_out(i, x, y):
    y = np.array(y, np.float32)
    y[y.shape[0] // 2:] = 0.0
    return y


def _half_requests_left_out(i, x, y):
    return None if i % 2 else y


def _altered(i, x, y):
    y = np.array(y, np.float32)
    y[y.shape[0] // 3] += 1.0
    return y


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return bench_helpers.small_spec(tmp_path_factory.mktemp("bench"))


def _run(spec, cell, fault=None, seed=2**33 + 17):
    return run_cell(spec.cell(cell), seed, 0.4, False, time.monotonic(),
                    fault=fault, log=lambda *a, **k: None)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(spec, cell):
    out = _run(spec, cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["spmv_err"]["value"] < \
        out["checks"]["spmv_err"]["limit"] / 3


FAULTS = [(cell, fault) for cell in CELLS for fault in (
    _unchanged, _altered,
    _half_requests_left_out if cell == "kron.ppr_serve_over"
    else _half_rows_left_out)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}" for c, f in FAULTS])
def test_a_broken_path_is_not_correct(spec, cell, fault):
    out = _run(spec, cell, fault)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(spec, cell):
    c = spec.cell(cell)
    a = c.generator().build(c.config, 2**33 + 17)
    out = _run(spec, cell, lambda i, x, y: matvec_bf16x3(a, np.asarray(x)))
    assert out["correct"] is False
    assert out["checks"]["spmv_err"]["value"] > \
        out["checks"]["spmv_err"]["limit"]
