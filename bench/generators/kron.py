"""GAP's ``kron`` graph as the weighted PageRank transition matrix.

The structure is the Graph500 Kronecker generator (initiator A, B, C with
D = 1 - A - B - C; ``edgefactor << scale`` edge samples; vertex labels
permuted), symmetrized with self-loops and duplicates dropped, as the GAP
Benchmark Suite builds ``kron``. It is drawn from the configuration's fixed
``graph_seed``, as GAP fixes its graph. The run's seed draws one weight per
undirected edge, uniform in (0, 1] as Graph500's weights, and the operand is
the column-stochastic transition matrix P = W D^-1 (D the weighted degree),
so its values matter to the product.

Kept with the benchmark, apart from the program, so a change to the program
cannot move the operand it is measured on.
"""
from __future__ import annotations

import numpy as np


def edges(cfg: dict) -> np.ndarray:
    """(2, m) directed edges of the symmetrized graph, sorted by (row, col),
    without self-loops or duplicates. Depends on ``cfg`` alone."""
    scale, ef = int(cfg["scale"]), int(cfg["edgefactor"])
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    rng = np.random.default_rng(int(cfg["graph_seed"]))
    m = ef << scale
    ij = np.zeros((2, m), np.int64)
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    # Graph500's kronecker_generator: one quadrant choice per bit
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    ij = rng.permutation(1 << scale)[ij]
    ij = np.concatenate([ij, ij[::-1]], axis=1)
    ij = ij[:, ij[0] != ij[1]]
    key = np.unique(ij[0] * (1 << scale) + ij[1])
    return np.stack([key >> scale, key & ((1 << scale) - 1)])


def build(cfg: dict, seed: int) -> dict:
    """CSR arrays of P = W D^-1 on the ``kron`` graph of ``cfg``, weights
    drawn from ``seed``."""
    n = 1 << int(cfg["scale"])
    row, col = edges(cfg)
    # one weight per undirected edge {u, v}, read by both directions
    lo, hi = np.minimum(row, col), np.maximum(row, col)
    pair = np.unique(lo * n + hi, return_inverse=True)[1]
    w = 1.0 - np.random.default_rng(seed).random(int(pair.max()) + 1)
    w = w[pair]
    degree = np.bincount(col, w, minlength=n)
    vals = (w / degree[col]).astype(np.float32)
    row_ptrs = np.concatenate([[0], np.cumsum(np.bincount(row, minlength=n))])
    return {"row_ptrs": row_ptrs.astype(np.int64),
            "col_idxs": col.astype(np.int32), "vals": vals, "shape": (n, n)}
