"""The plain reference every answer is compared with, and its control.

``matvec_f64`` is the product straight from the CSR arrays in float64, with
the row bound ``|A| |x|`` an f32 sum is judged against. It imports nothing
of the program and reads only the arrays the generator made.

``matvec_bf16x3`` is the same product computed the way a ``HIGH`` (three
bf16 passes) matmul computes it: each factor split into a bf16 high part and
a bf16 low part, the low-times-low term dropped, the rest summed in f32. It
is the control: the step below the ``HIGHEST`` f32 the configurations state,
so the limit on ``spmv_err`` must fail it.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def _rows(a: dict) -> np.ndarray:
    n = a["shape"][0]
    return np.repeat(np.arange(n), np.diff(a["row_ptrs"]))


def matvec_f64(a: dict, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(A x, |A| |x|) in float64 for a CSR dict and a vector x."""
    n = a["shape"][0]
    rows = _rows(a)
    prod = a["vals"].astype(np.float64) * np.asarray(x, np.float64)[
        a["col_idxs"]]
    return (np.bincount(rows, prod, minlength=n),
            np.bincount(rows, np.abs(prod), minlength=n))


def row_error(y, ref: np.ndarray, bound: np.ndarray) -> float:
    """Largest ``|y - ref| / (|A||x|)`` over rows; inf for a wrong shape or
    a non-finite entry, so a malformed answer can never pass."""
    if y is None:
        return float("inf")
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    return float(np.max(np.abs(y - ref) / np.maximum(bound, 1e-30)))


def _bf16(v: np.ndarray) -> np.ndarray:
    """Round f32 to the nearest bf16 (ties to even), kept as f32."""
    u = np.asarray(v, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def matvec_bf16x3(a: dict, x: np.ndarray) -> np.ndarray:
    """A x with bf16x3 products (``Precision.HIGH``) and f32 row sums."""
    n = a["shape"][0]
    v = a["vals"].astype(np.float32)
    xv = np.asarray(x, np.float32)[a["col_idxs"]]
    v_hi, x_hi = _bf16(v), _bf16(xv)
    v_lo, x_lo = _bf16(v - v_hi), _bf16(xv - x_hi)
    prod = (v_hi * x_hi + (v_hi * x_lo + v_lo * x_hi)).astype(np.float32)
    out = np.zeros(n, np.float32)
    starts = a["row_ptrs"][:-1]
    filled = np.diff(a["row_ptrs"]) > 0
    if prod.size:
        out[filled] = np.add.reduceat(prod, starts[filled], dtype=np.float32)
    return out
