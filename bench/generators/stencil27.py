"""HPCG's operator: the 27-point stencil on an nx*ny*nz grid, symmetric.

Row ``(z*ny + y)*nx + x`` couples to every grid neighbour within one step in
each dimension (27 in the interior, fewer on the faces). HPCG fixes the
values (26 on the diagonal, -1 off it). Here each off-diagonal pair
``(i, j) = (j, i)`` gets one value drawn uniform in [-1, 1) from the seed, so
the matrix stays symmetric and diagonally dominant (positive definite, as CG
needs) while a misplaced entry changes the product.

Kept with the benchmark, apart from the program's own generators, so a
change to the program cannot move the operand it is measured on.
"""
from __future__ import annotations

import numpy as np

# (dz, dy, dx) in lexicographic order, so the column grows with the index;
# offset k and 26 - k are mirror images and index 13 is the diagonal
OFFSETS = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
           for dx in (-1, 0, 1)]
DIAGONAL = 13


def build(cfg: dict, seed: int) -> dict:
    """CSR arrays of the stencil named by ``cfg`` (``nx``, ``ny``, ``nz``,
    ``diagonal``), off-diagonal values drawn from ``seed``."""
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])
    n = nx * ny * nz
    z, y, x = np.unravel_index(np.arange(n, dtype=np.int64), (nz, ny, nx))
    # one value per (row, lower offset); the upper offsets read the mirror
    lower = np.random.default_rng(seed).uniform(
        -1.0, 1.0, (n, DIAGONAL)).astype(np.float32)
    cols = np.empty((n, len(OFFSETS)), np.int64)
    valid = np.empty((n, len(OFFSETS)), bool)
    vals = np.empty((n, len(OFFSETS)), np.float32)
    for k, (dz, dy, dx) in enumerate(OFFSETS):
        cols[:, k] = ((z + dz) * ny + (y + dy)) * nx + (x + dx)
        valid[:, k] = ((0 <= z + dz) & (z + dz < nz) & (0 <= y + dy)
                       & (y + dy < ny) & (0 <= x + dx) & (x + dx < nx))
        if k < DIAGONAL:
            vals[:, k] = lower[:, k]
        elif k > DIAGONAL:
            # A[i, j] = A[j, i]: row j holds the pair under offset 26 - k
            vals[:, k] = lower[np.clip(cols[:, k], 0, n - 1),
                               len(OFFSETS) - 1 - k]
    vals[:, DIAGONAL] = float(cfg["diagonal"])
    row_ptrs = np.concatenate([[0], np.cumsum(valid.sum(axis=1))])
    return {"row_ptrs": row_ptrs.astype(np.int64),
            "col_idxs": cols[valid].astype(np.int32),
            "vals": vals[valid], "shape": (n, n)}
