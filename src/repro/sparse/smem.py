"""Scalar-memory budget of a Pallas launch, and launches split to fit it.

Every BSR kernel scalar-prefetches its schedule tables (ELL slot tables,
SELL cell streams, spgemm pair/cell lists, spadd source lists) into SMEM,
the TPU core's 1 MiB scalar memory. The compiler refuses a ``pallas_call``
whose tables do not fit, and SMEM pads the minor dim of a 2-D int32 table
to 128 lanes: an ELL table of 1,024 block-rows costs 512 KiB at any width
up to 128, so an operand past about 10^5 rows fits no single launch.

The helpers here split one launch into several along the tables' leading
axis, each within ``SMEM_BUDGET_BYTES``:

* ``split_rows`` — the leading axis is the output row (ELL block-rows,
  spgemm pair rows, spadd output blocks): each launch takes a row range of
  the tables and the outputs concatenate.
* ``split_cells`` — the leading axis is a flattened cell stream whose
  output row (``cell_row`` / ``cell_c``) is nondecreasing. The stream is
  cut at fixed cell counts, so the cuts depend on shapes only and one
  compiled program still serves a whole shape bucket. A row whose cells
  straddle a cut is summed from both launches: each launch's output counts
  only on the rows its cells visit.

Both are traced inside the jitted executors; a launch that fits runs
exactly as before, with no slicing.
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

SMEM_BYTES = 1 << 20
# headroom for the compiler's own scalars (it reports ~144 B) and the grid
SMEM_MARGIN_BYTES = 128 << 10
SMEM_BUDGET_BYTES = SMEM_BYTES - SMEM_MARGIN_BYTES


def _round_up(n: int, q: int) -> int:
    return -(-int(n) // q) * q


def table_bytes(shape: Sequence[int]) -> int:
    """SMEM bytes of one int32 scalar-prefetch table: a 1-D table costs 4 B
    per entry (128-aligned), a 2-D one pads rows to 8 and columns to 128."""
    if len(shape) == 1:
        return 4 * _round_up(shape[0], 128)
    rows, cols = shape
    return 4 * _round_up(rows, 8) * _round_up(cols, 128)


def launch_bytes(shapes: Sequence[Sequence[int]], rows: int) -> int:
    """SMEM bytes of the tables of ``shapes`` with their shared leading
    axis cut to ``rows``."""
    return sum(table_bytes((rows,) + tuple(s[1:])) for s in shapes)


def rows_per_launch(shapes: Sequence[Sequence[int]],
                    budget: int = SMEM_BUDGET_BYTES) -> int:
    """Most leading-axis rows one launch may take so that the tables of
    ``shapes`` (sharing that axis) fit ``budget``: the largest multiple of
    128 whose ``launch_bytes`` fit, else a multiple of 8 that fits, and at
    least 1. ``launch_bytes`` is linear over such multiples (1-D tables
    round 8 rows up to 128, so the 8-row step only errs low)."""
    for q in (128, 8):
        n = budget // launch_bytes(shapes, q) * q
        if n:
            return n
    return 1


def row_ranges(n_rows: int, shapes: Sequence[Sequence[int]],
               budget: int = SMEM_BUDGET_BYTES) -> List[Tuple[int, int]]:
    """Static ``[lo, hi)`` ranges covering ``n_rows``, one per launch."""
    step = rows_per_launch(shapes, budget)
    return [(lo, min(lo + step, n_rows))
            for lo in range(0, max(n_rows, 1), step)]


def split_rows(call: Callable[..., jax.Array], tables: Sequence[jax.Array],
               budget: int = SMEM_BUDGET_BYTES) -> jax.Array:
    """``call(*tables)`` with the tables' leading axis (= output rows) cut
    into SMEM-sized ranges; the per-range outputs concatenate on axis 0."""
    ranges = row_ranges(tables[0].shape[0], [t.shape for t in tables],
                        budget)
    if len(ranges) == 1:
        return call(*tables)
    return jnp.concatenate(
        [call(*(t[lo:hi] for t in tables)) for lo, hi in ranges], axis=0)


def split_cells(call: Callable[..., jax.Array], tables: Sequence[jax.Array],
                out_row: jax.Array, n_out_rows: int,
                budget: int = SMEM_BUDGET_BYTES) -> jax.Array:
    """``call(*tables)`` over a cell stream cut into SMEM-sized ranges.

    ``tables`` are the 1-D per-cell streams (``out_row`` among them) and
    ``call`` returns ``(n_out_rows, ...)`` with only the rows its cells
    visit written. Launch outputs are masked to those rows and summed, so a
    row split across two launches gets both partial sums."""
    ranges = row_ranges(out_row.shape[0], [t.shape for t in tables], budget)
    if len(ranges) == 1:
        return call(*tables)
    total = None
    for lo, hi in ranges:
        y = call(*(t[lo:hi] for t in tables))
        seen = jnp.zeros((n_out_rows,), bool).at[out_row[lo:hi]].set(True)
        part = jnp.where(seen.reshape((-1,) + (1,) * (y.ndim - 1)), y, 0.0)
        total = part if total is None else total + part
    return total
