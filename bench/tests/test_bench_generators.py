"""The benchmark's own operand generators: sizes, symmetry, determinism."""
import os
import sys

import numpy as np
import pytest

import bench_helpers

sys.path.insert(0, os.path.join(bench_helpers.BENCH, "generators"))
import kron  # noqa: E402
import stencil27  # noqa: E402

KRON = {"scale": 9, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
        "graph_seed": 27}


def _dense(a):
    n, m = a["shape"]
    out = np.zeros((n, m))
    rows = np.repeat(np.arange(n), np.diff(a["row_ptrs"]))
    out[rows, a["col_idxs"]] = a["vals"]
    return out


@pytest.mark.parametrize("n", [3, 4, 7])
def test_stencil_nonzeros_are_3n_minus_2_cubed(n):
    a = stencil27.build({"nx": n, "ny": n, "nz": n, "diagonal": 26}, 5)
    assert a["shape"] == (n ** 3, n ** 3)
    assert a["vals"].size == (3 * n - 2) ** 3
    assert a["row_ptrs"][-1] == a["vals"].size


def test_stencil_is_symmetric_positive_definite_and_seeded():
    cfg = {"nx": 5, "ny": 4, "nz": 3, "diagonal": 26}
    d = _dense(stencil27.build(cfg, 7))
    assert np.array_equal(d, d.T)
    assert np.all(np.diag(d) == 26)
    assert np.linalg.eigvalsh(d).min() > 0
    assert np.array_equal(d, _dense(stencil27.build(cfg, 7)))
    other = _dense(stencil27.build(cfg, 8))
    assert np.array_equal(other != 0, d != 0) and not np.array_equal(other, d)


def test_kron_structure_is_fixed_by_the_config_and_values_by_the_seed():
    a, b = kron.build(KRON, 1), kron.build(KRON, 2**40 + 3)
    assert np.array_equal(a["row_ptrs"], b["row_ptrs"])
    assert np.array_equal(a["col_idxs"], b["col_idxs"])
    assert not np.array_equal(a["vals"], b["vals"])
    assert np.array_equal(a["vals"], kron.build(KRON, 1)["vals"])
    c = kron.build(dict(KRON, graph_seed=28), 1)
    assert not np.array_equal(a["col_idxs"], c["col_idxs"])


def test_kron_is_a_symmetric_graph_and_a_column_stochastic_operand():
    a = kron.build(KRON, 3)
    d = _dense(a)
    n = 1 << KRON["scale"]
    assert d.shape == (n, n)
    assert np.array_equal(d != 0, d.T != 0)
    assert not np.any(np.diag(d))
    cols = d.sum(axis=0)
    linked = cols > 0
    assert np.allclose(cols[linked], 1.0, atol=1e-5)
    # sorted rows, no duplicates
    for r in range(0, n, 37):
        row = a["col_idxs"][a["row_ptrs"][r]:a["row_ptrs"][r + 1]]
        assert np.all(np.diff(row) > 0)
    # about edgefactor * 2 directed edges per vertex before dropping
    assert 8 * n < a["vals"].size < 32 * n
