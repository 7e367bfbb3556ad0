"""ELL/SELL-BSR SpMV + multi-RHS SpMM Pallas TPU kernels (paper Alg. 1
adapted per §4.4 / DESIGN §2).

Schedules
  ELL (global padding, DESIGN §2.2)
    grid = (n_block_rows, max_blocks_per_row); the slot axis is innermost so
    the output block-row stays resident in VMEM across accumulation steps.
    Scalar-prefetched ``block_indices`` / ``block_cols`` drive the BlockSpec
    index maps: the A tile for grid cell (i, j) is ``blocks[idx[i, j]]`` and
    the x segment is ``x[cols[i, j]]`` — data-dependent HBM->VMEM DMA with no
    data-dependent control flow in the kernel body. Padding slots point at a
    trailing all-zeros block (ELLBSR invariant), so irregular rows cost dead
    tile work (the counters' ``padding_fraction``) instead of branches: the
    paper's branch-misprediction bottleneck transformed into a measurable,
    tree-visible quantity.

  SELL (sliced padding, DESIGN §2.3)
    grid = (n_cells,) — a ragged schedule flattened on the host. Three
    scalar-prefetched streams drive the index maps: ``cell_block[t]`` /
    ``cell_col[t]`` pick the A tile and x segment of step t, and
    ``cell_row[t]`` (nondecreasing: the host emits a row's cells
    consecutively in SELL row-sorted order) picks the resident output tile,
    which Pallas flushes exactly when the row index advances. The kernel
    writes in sorted order; the op scatters back through ``row_perm``. The
    grid runs sum_s C*w_s steps instead of n_block_rows*max_w — the padding
    eliminated by slicing is grid steps that simply never launch.

  Vector layout
    A TPU block's last two dims must be multiples of (8, 128) or equal the
    array's. A (1, bs) slice of an (n_block_cols, bs) vector is neither, so
    the SpMV wrappers view x and y as (n, 1, bs): each block is one whole
    (1, bs) row. SpMV has one useful right-hand side, so its tile product
    runs on the VPU in f32, not on the MXU: for each 128-lane group g of
    the tile, ``acc += A[:, g] * x[g]`` with the x row broadcast over
    sublanes, into a (bs, 128) f32 VMEM accumulator (one (bs, bs) group
    where 128 does not divide bs). The row's last cell sums ``acc`` across
    lanes once (a transpose, then a sublane sum) and writes the (1, bs)
    output row. No transpose of A, no MXU pass.

  SpMM (multi-RHS)
    Same two schedules with x blocked as (n_block_cols, bs, k): one A-tile
    DMA now feeds a (bs, bs) @ (bs, k) MXU op at ``precision=HIGHEST``,
    amortizing A traffic across k right-hand sides — the reuse the paper
    finds missing from SpMV. SpMM accumulates into its resident output
    tile.

VMEM per grid cell: double-buffered A tile, x tile and output tile,
2 x (bs*bs + bs*k + bs*k) * 4B for SpMM (at bs=128, k=8 that is ~148 KB)
and 2 x (bs*bs + 2*8*bs) * 4B (a (1, bs) tile pads to 8 sublanes) plus the
bs * 128 * 4B accumulator for SpMV (bs * bs where 128 does not divide bs;
at bs=256, ~670 KB in all): far under VMEM either way. bs in {128, 256}
fills whole vregs and MXU tiles; smaller bs trades padding for partly
empty lanes (autotune.py arbitrates via the tree model).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _tile_product(first, last, blk_ref, x_ref, y_ref, acc_ref=None):
    """Accumulate one grid cell's product into its output row.

    ``first`` / ``last`` mark the row's first and last cell. SpMM
    (``acc_ref`` None): (bs, bs) @ (bs, k) on the MXU at HIGHEST precision,
    added into the resident output tile; the MXU's default f32 pass would
    round both operands to bf16 (~2^-8 relative error), and the f32 API and
    ``ref.py`` promise f32. SpMV: an f32 multiply-add on the VPU into
    ``acc_ref`` (module docstring, "Vector layout"), exact f32 products with
    no MXU pass; the lane sum to the (1, bs) output row runs once, on the
    row's last cell."""
    if acc_ref is None:
        @pl.when(first)
        def _init_tile():
            y_ref[...] = jnp.zeros_like(y_ref)

        y_ref[0] += jnp.dot(blk_ref[0], x_ref[0], precision=HIGHEST,
                            preferred_element_type=jnp.float32)
        return

    @pl.when(first)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lanes = acc_ref.shape[1]
    acc = acc_ref[...]
    for g in range(blk_ref.shape[-1] // lanes):
        cut = slice(g * lanes, (g + 1) * lanes)
        acc += blk_ref[0, :, cut] * x_ref[0, :, cut]
    acc_ref[...] = acc

    @pl.when(last)
    def _flush():
        y_ref[0] = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)


def _ell_kernel(idx_ref, cols_ref, blk_ref, x_ref, y_ref, *acc):
    del idx_ref, cols_ref  # consumed by the index maps
    j = pl.program_id(1)
    _tile_product(j == 0, j == pl.num_programs(1) - 1, blk_ref, x_ref, y_ref,
                  *acc)


def _sell_kernel(idx_ref, cols_ref, rows_ref, blk_ref, x_ref, y_ref, *acc):
    del idx_ref, cols_ref  # consumed by the index maps
    t = pl.program_id(0)
    end = pl.num_programs(0) - 1
    row = rows_ref[t]
    first = jnp.logical_or(t == 0, row != rows_ref[jnp.maximum(t - 1, 0)])
    last = jnp.logical_or(t == end, row != rows_ref[jnp.minimum(t + 1, end)])
    _tile_product(first, last, blk_ref, x_ref, y_ref, *acc)


def _acc_scratch(bs: int, vector: bool) -> list:
    """The SpMV lane-group accumulator, (bs, 128) where 128-lane groups
    tile the block and (bs, bs) where they do not, so its groups cover
    every column; SpMM accumulates in its output."""
    lanes = 128 if bs % 128 == 0 else bs
    return [pltpu.VMEM((bs, lanes), jnp.float32)] if vector else []


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmv_pallas(block_indices: jax.Array, block_cols: jax.Array,
                    blocks: jax.Array, x_blocks: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """y = A @ x with A in ELL-BSR layout.

    Args:
      block_indices: (n_br, mb) int32 — index into ``blocks``; padding slots
        hold ``blocks.shape[0] - 1`` (the all-zeros block).
      block_cols:    (n_br, mb) int32 — block-column of each slot.
      blocks:        (n_blocks + 1, bs, bs) float32, last block all-zeros.
      x_blocks:      (n_block_cols, bs) float32 — dense vector, blocked.
    Returns:
      (n_br, bs) float32 — blocked result vector.
    """
    n_br = block_indices.shape[0]
    bs = blocks.shape[-1]
    y = _ell_call(block_indices, block_cols, blocks,
                  x_blocks.reshape(-1, 1, bs), vector=True, interpret=interpret)
    return y.reshape(n_br, bs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmm_pallas(block_indices: jax.Array, block_cols: jax.Array,
                    blocks: jax.Array, x_blocks: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """Y = A @ X with A in ELL-BSR layout and X multi-RHS.

    Args:
      x_blocks: (n_block_cols, bs, k) float32 — dense RHS, row-blocked; k is
        the lane-aligned RHS tile the A-block DMA is amortized over.
    Returns:
      (n_br, bs, k) float32 — blocked result rows.
    """
    return _ell_call(block_indices, block_cols, blocks, x_blocks,
                     vector=False, interpret=interpret)


def _ell_call(block_indices, block_cols, blocks, x_blocks, *, vector: bool,
              interpret: bool) -> jax.Array:
    """The ELL pallas_call; x_blocks is (n_bc, bs, k), or (n_bc, 1, bs) with
    ``vector``. The output block-row has the x tile's shape."""
    n_br, mb = block_indices.shape
    bs = blocks.shape[-1]
    tile = tuple(x_blocks.shape[1:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_br, mb),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda i, j, idx, cols: (idx[i, j], 0, 0)),
            pl.BlockSpec((1,) + tile, lambda i, j, idx, cols: (cols[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1,) + tile, lambda i, j, idx, cols: (i, 0, 0)),
        scratch_shapes=_acc_scratch(bs, vector),
    )
    return pl.pallas_call(
        _ell_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_br,) + tile, jnp.float32),
        interpret=interpret,
    )(block_indices, block_cols, blocks, x_blocks)


@functools.partial(jax.jit, static_argnames=("n_block_rows", "interpret"))
def bsr_spmv_sell_pallas(cell_block: jax.Array, cell_col: jax.Array,
                         cell_row: jax.Array, blocks: jax.Array,
                         x_blocks: jax.Array, n_block_rows: int,
                         interpret: bool = False) -> jax.Array:
    """y_sorted = P A @ x with A in SELL-BSR layout (bucketed schedule).

    Args:
      cell_block: (n_cells,) int32 — A tile per grid step; pads hold the
        all-zeros block index.
      cell_col:   (n_cells,) int32 — x segment per grid step.
      cell_row:   (n_cells,) int32 — *sorted* output block-row per step,
        nondecreasing so the output tile is revisited only consecutively.
      blocks:     (n_blocks + 1, bs, bs) float32, last block all-zeros.
      x_blocks:   (n_block_cols, bs) float32.
      n_block_rows: static output row count.
    Returns:
      (n_block_rows, bs) float32 in SELL-sorted row order; scatter back with
      ``SELLBSR.row_perm``.
    """
    bs = blocks.shape[-1]
    y = _sell_call(cell_block, cell_col, cell_row, blocks,
                   x_blocks.reshape(-1, 1, bs), n_block_rows, vector=True,
                   interpret=interpret)
    return y.reshape(n_block_rows, bs)


@functools.partial(jax.jit, static_argnames=("n_block_rows", "interpret"))
def bsr_spmm_sell_pallas(cell_block: jax.Array, cell_col: jax.Array,
                         cell_row: jax.Array, blocks: jax.Array,
                         x_blocks: jax.Array, n_block_rows: int,
                         interpret: bool = False) -> jax.Array:
    """Y_sorted = P A @ X: the SELL bucketed schedule with a multi-RHS tile.

    Same contract as ``bsr_spmv_sell_pallas`` with x_blocks of shape
    (n_block_cols, bs, k); returns (n_block_rows, bs, k) in sorted order.
    """
    return _sell_call(cell_block, cell_col, cell_row, blocks, x_blocks,
                      n_block_rows, vector=False, interpret=interpret)


def _sell_call(cell_block, cell_col, cell_row, blocks, x_blocks,
               n_block_rows: int, *, vector: bool,
               interpret: bool) -> jax.Array:
    """The SELL pallas_call; tile shapes as in ``_ell_call``."""
    n_cells = cell_block.shape[0]
    bs = blocks.shape[-1]
    tile = tuple(x_blocks.shape[1:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda t, idx, cols, rows: (idx[t], 0, 0)),
            pl.BlockSpec((1,) + tile, lambda t, idx, cols, rows: (cols[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1,) + tile, lambda t, idx, cols, rows: (rows[t], 0, 0)),
        scratch_shapes=_acc_scratch(bs, vector),
    )
    return pl.pallas_call(
        _sell_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_block_rows,) + tile, jnp.float32),
        interpret=interpret,
    )(cell_block, cell_col, cell_row, blocks, x_blocks)
