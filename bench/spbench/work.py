"""The work a sparse product needs, whatever layout runs it.

An f32 product Y = A X with A of ``nnz`` stored values, X of ``n_cols`` x
``k`` and Y of ``n_rows`` x ``k`` must read every value once, read X once and
write Y once: ``4 nnz + 4 k (n_cols + n_rows)`` bytes. Indices are left out
because a layout may encode structure without them (a stencil), so this is a
lower bound for every layout, and padding, tiles or reordering can only add
to what the kernel moves, never to this count. Its ``2 nnz k`` operations
need ~1e-5 of the time its bytes need on the chips in ``peaks``, so the
bound is the bytes.
"""
from __future__ import annotations

F32 = 4


def spmm_bytes(nnz: int, n_rows: int, n_cols: int, k: int = 1) -> int:
    """Least bytes an f32 SpMV (k = 1) or SpMM with k right-hand sides
    moves: values once, X once, Y once."""
    return F32 * (int(nnz) + int(k) * (int(n_cols) + int(n_rows)))


def spmm_flops(nnz: int, k: int = 1) -> int:
    """Multiply-adds of the product, counted as two operations."""
    return 2 * int(nnz) * int(k)


def roofline_pct(bytes_moved: float, flops: float, kernel_s: float,
                 peak: dict):
    """Least time the chip could take for the work, over the kernels' device
    time, in percent; None when no kernel time was read."""
    if not kernel_s or kernel_s <= 0:
        return None
    least = max(bytes_moved / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
