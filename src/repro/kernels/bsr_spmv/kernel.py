"""ELL/SELL-BSR SpMV + multi-RHS SpMM, and DIA SpMV, Pallas TPU kernels
(paper Alg. 1 adapted per §4.4 / DESIGN §2).

Schedules
  ELL (global padding, DESIGN §2.2)
    SpMV (``bsr_spmv_pallas``, where 128 divides bs): grid = (n_block_rows,)
    and a tile stream. Scalar-prefetched ``block_indices`` / ``block_cols``
    / ``valid_counts`` name each row's valid tiles ``blocks[idx[i, j]]``
    and x segments ``x[cols[i, j]]`` for j < ``valid_counts[i]``; the kernel
    copies them itself from HBM into a ring of ``STREAM_DEPTH`` VMEM slots,
    keeping STREAM_DEPTH - 1 copies in flight while the VPU multiplies the
    one that landed, and the stream runs on across rows. Padding slots and
    padded rows cost no DMA and no VPU pass: ``padding_fraction`` stays a
    counted property of the layout (the plan counts the slots a launch
    skipped, ``kernel.ell_stream.skipped``) but the kernel no longer pays
    it. Tiles are addressed through ``block_indices`` only, so a container
    whose inserts appended tiles out of row order streams the same way.
    SpMM, and SpMV at other block sizes (Mosaic copies a tile by hand only
    where its lanes are whole 128-lane groups): grid = (n_block_rows,
    max_blocks_per_row), the slot axis innermost so the output block-row
    stays resident in VMEM, and the same tables drive the BlockSpec index
    maps. Padding slots there point at a trailing all-zeros block (ELLBSR
    invariant), so irregular rows cost dead tile work instead of branches.

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

  DIA (diagonal layout, DESIGN §2.5; SpMV only)
    grid = (row blocks,), each 128 * ``dia_rows`` matrix rows. The block
    of every diagonal's values comes by BlockSpec; the x window the block
    reads (its rows plus the band) is copied by hand, the next block's
    while this one computes, so each diagonal's shift is a static offset
    into it: sublane rows, then a lane roll and select. No index is read.
    ``bsr_spmv_pallas`` runs it as its second format (``offsets`` given),
    so a device trace names both SpMV formats alike.

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
bs * 128 * 4B accumulator for the slot-grid SpMV (bs * bs where 128 does
not divide bs); the streamed SpMV holds STREAM_DEPTH x (bs*bs + 8*bs) * 4B
of ring, its double-buffered output row and the accumulator (at bs=256,
~1.2 MB in all): far under VMEM either way. bs in {128, 256}
fills whole vregs and MXU tiles; smaller bs trades padding for partly
empty lanes (autotune.py arbitrates via the tree model).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _vpu_madd(acc, tile_ref, x_ref, k):
    """``acc`` plus the products of tile ``k`` of ``tile_ref`` (n, bs, bs)
    and x row ``k`` of ``x_ref`` (n, 1, bs), on the VPU in f32: for each
    128-lane group g (one group of ``acc``'s width where 128 does not
    divide bs), ``acc += A[:, g] * x[g]`` with the x row broadcast over
    sublanes."""
    lanes = acc.shape[1]
    for g in range(tile_ref.shape[-1] // lanes):
        cut = slice(g * lanes, (g + 1) * lanes)
        acc += tile_ref[k, :, cut] * x_ref[k, :, cut]
    return acc


def _lane_sum(acc):
    """The (1, bs) output row of a (bs, lanes) accumulator: a transpose,
    then a sublane sum."""
    return jnp.sum(acc.T, axis=0, keepdims=True)


def _tile_product(first, last, blk_ref, x_ref, y_ref, acc_ref=None):
    """Accumulate one grid cell's product into its output row.

    ``first`` / ``last`` mark the row's first and last cell. SpMM
    (``acc_ref`` None): (bs, bs) @ (bs, k) on the MXU at HIGHEST precision,
    added into the resident output tile; the MXU's default f32 pass would
    round both operands to bf16 (~2^-8 relative error), and the f32 API and
    ``ref.py`` promise f32. SpMV: ``_vpu_madd`` into ``acc_ref`` (module
    docstring, "Vector layout"), exact f32 products with no MXU pass; the
    lane sum to the (1, bs) output row runs once, on the row's last cell."""
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

    acc_ref[...] = _vpu_madd(acc_ref[...], blk_ref, x_ref, 0)

    @pl.when(last)
    def _flush():
        y_ref[0] = _lane_sum(acc_ref[...])


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


# Tile copies the ELL SpMV stream keeps in its VMEM ring: STREAM_DEPTH - 1
# are in flight while the VPU multiplies the one that has landed. 4 was the
# fastest of 2-4 on both solve cells' operands on a v5e (2: 13.5 / 6.89 ms
# a launch, 3: 11.13 / 5.77, 4: 10.85 / 5.76 at hpcg's 4,096 x 12 grid and
# kron's 128 x 128, bs 256).
STREAM_DEPTH = 4


def ell_streams(bs: int) -> bool:
    """Whether ``bsr_spmv_pallas`` streams a block size's valid tiles.
    Mosaic copies a tile by hand only where its lanes are whole 128-lane
    groups; other block sizes keep the grid over every slot."""
    return bs % 128 == 0


def _ell_stream_kernel(idx_ref, cols_ref, vc_ref, blocks_hbm, x_hbm, y_ref,
                       tiles, xs, sems, cur, acc_ref):
    """One grid step per block-row: the row's ``vc_ref[i]`` valid tiles and
    x segments, copied from HBM into a ring of ``STREAM_DEPTH`` VMEM slots.

    The stream runs over the whole launch, row after row: tile t of the
    stream lands in slot ``t % depth``, and consuming tile t first starts
    the copy of tile ``t + depth - 1`` into the slot tile t - 1 has freed,
    so a row's last tiles overlap the next rows' first copies. ``cur``
    (SMEM, kept across the sequential grid) holds the producer's next (row,
    slot) and the counts of tiles started and consumed. Slots at or past a
    row's valid count are never read: they cost no copy and no VPU pass."""
    n_br = pl.num_programs(0)
    depth = tiles.shape[0]
    i = pl.program_id(0)

    def copies(slot, tile=0, col=0):
        return (pltpu.make_async_copy(blocks_hbm.at[tile], tiles.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(x_hbm.at[col], xs.at[slot],
                                      sems.at[1, slot]))

    def row_len(r):
        return vc_ref[jnp.minimum(r, n_br - 1)]

    def start_next():
        """Start the copies of the producer's next valid tile, if any."""
        def before(c):
            r, j, n = c
            return jnp.logical_and(r < n_br, j >= n)

        def next_row(c):
            return c[0] + 1, 0, row_len(c[0] + 1)

        r, j, _ = lax.while_loop(before, next_row,
                                 (cur[0], cur[1], row_len(cur[0])))

        @pl.when(r < n_br)
        def _start():
            for cp in copies(cur[2] % depth, idx_ref[r, j], cols_ref[r, j]):
                cp.start()
            cur[2] += 1

        cur[0] = r
        cur[1] = j + 1

    @pl.when(i == 0)
    def _prologue():
        for k in range(4):
            cur[k] = 0
        for _ in range(depth - 1):
            start_next()

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def consume(_, carry):
        start_next()
        slot = cur[3] % depth
        for cp in copies(slot):
            cp.wait()
        acc_ref[...] = _vpu_madd(acc_ref[...], tiles, xs, slot)
        cur[3] += 1
        return carry

    lax.fori_loop(0, vc_ref[i], consume, 0)
    y_ref[0] = _lane_sum(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("offsets", "interpret"))
def bsr_spmv_pallas(*arrays: jax.Array,
                    offsets: Optional[Tuple[int, ...]] = None,
                    interpret: bool = False) -> jax.Array:
    """y = A @ x: the family's SpMV entry, for either of its launch formats.

    ELL (``offsets`` None): ``(block_indices, block_cols, valid_counts,
    blocks, x_blocks)``, returning the blocked (n_br, bs) result
    (``_ell_spmv``). DIA (``offsets`` the operand's diagonals): ``(diags,
    x)``, returning the (n_row_pad,) result (``_dia_spmv``). Each format
    keeps its own ``pallas_call``; both run inside this one jitted entry,
    whose name is what a device trace shows for either kernel.
    """
    if offsets is not None:
        return _dia_spmv(*arrays, offsets=offsets, interpret=interpret)
    return _ell_spmv(*arrays, interpret=interpret)


def _ell_spmv(block_indices: jax.Array, block_cols: jax.Array,
              valid_counts: jax.Array, blocks: jax.Array,
              x_blocks: jax.Array, interpret: bool = False) -> jax.Array:
    """y = A @ x with A in ELL-BSR layout, streaming only the valid tiles.

    Args:
      block_indices: (n_br, mb) int32 — index into ``blocks``; slots at or
        past a row's valid count are never read.
      block_cols:    (n_br, mb) int32 — block-column of each slot.
      valid_counts:  (n_br,) int32 — valid slots of each row, a prefix.
      blocks:        (n_blocks + 1, bs, bs) float32.
      x_blocks:      (n_block_cols, bs) float32 — dense vector, blocked.
    Returns:
      (n_br, bs) float32 — blocked result vector.
    """
    n_br = block_indices.shape[0]
    bs = blocks.shape[-1]
    xv = x_blocks.reshape(-1, 1, bs)
    if not ell_streams(bs):
        return _ell_call(block_indices, block_cols, blocks, xv, vector=True,
                         interpret=interpret).reshape(n_br, bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_br,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, bs), lambda i, idx, cols, vc: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((STREAM_DEPTH, bs, bs), jnp.float32),
                        pltpu.VMEM((STREAM_DEPTH, 1, bs), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, STREAM_DEPTH)),
                        pltpu.SMEM((4,), jnp.int32)]
        + _acc_scratch(bs, True),
    )
    y = pl.pallas_call(
        _ell_stream_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_br, 1, bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_indices, block_cols, valid_counts, blocks, xv)
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
    """The ELL grid pallas_call, one step per slot; x_blocks is (n_bc, bs,
    k), or (n_bc, 1, bs) with ``vector``. The output block-row has the x
    tile's shape."""
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


# ----------------------------------------------------------------------- DIA

LANES = 128
# Sublane rows of x (128 matrix rows each) a DIA grid step covers at most,
# and the VMEM the step's double-buffered block of diagonals may take.
DIA_MAX_ROWS = 256
DIA_BLOCK_BYTES = 8 << 20
# Sublane rows the VPU carries across every diagonal at a time.
DIA_CHUNK = 8
# One DIA pallas_call takes at most DIA_GROUP diagonals (it unrolls them)
# spanning at most DIA_BAND columns (its x window, 1 MiB a slot); an
# operand past either runs one call a group of diagonals and sums them.
DIA_GROUP = 64
DIA_BAND = 1 << 18


def dia_rows(n_diags: int, lane_rows: int) -> int:
    """Sublane rows one DIA grid step covers, for ``n_diags`` diagonals
    over ``lane_rows`` lane rows (128 matrix rows each): at most
    ``DIA_MAX_ROWS``, a group's double-buffered diagonals within
    ``DIA_BLOCK_BYTES``, no more than the rows need, a multiple of 8. The
    DIA container pads its lane rows to a multiple of it, and the kernel
    reads the same value back from the padded count."""
    group = min(max(n_diags, 1), DIA_GROUP)
    cap = DIA_BLOCK_BYTES // (2 * 4 * LANES * group) // 8 * 8
    return max(8, min(cap, DIA_MAX_ROWS, -(-lane_rows // 8) * 8))


def _dia_groups(offsets: Tuple[int, ...]):
    """(first, end) index ranges of ``offsets`` (ascending) that one
    ``pallas_call`` takes: at most ``DIA_GROUP`` diagonals spanning at most
    ``DIA_BAND`` columns each."""
    first = 0
    for k in range(1, len(offsets) + 1):
        if k == len(offsets) or k - first == DIA_GROUP \
                or offsets[k] - offsets[first] > DIA_BAND:
            yield first, k
            first = k


def _dia_kernel(diags_ref, x_hbm, y_ref, xwin, sems, *, taps, chunk):
    """One grid step: ``y`` rows [i R, (i + 1) R), R = 128 ``rows``.

    ``x_hbm`` is x as lane rows, shifted so that step i's window of x
    starts at lane row i ``rows``; the window (``xwin``, two slots) is
    copied by hand, the next step's while this one computes. Diagonal k
    reads x at lane ``(q, r) = taps[k]`` past each row: the window's rows
    q.. give lanes r.. and rows q + 1.. the lanes before r, joined by a
    roll of 128 - r lanes and a lane select. ``chunk`` sublane rows at a
    time, every diagonal is multiplied and summed in f32 on the VPU."""
    rows, win = y_ref.shape[0], xwin.shape[1]
    i = pl.program_id(0)
    slot = i % 2

    def window(step, s):
        return pltpu.make_async_copy(x_hbm.at[pl.ds(step * rows, win)],
                                     xwin.at[s], sems.at[s])

    @pl.when(i == 0)
    def _first():
        window(0, 0).start()

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        window(i + 1, 1 - slot).start()

    window(i, slot).wait()
    lane = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)

    def chunk_rows(c, carry):
        c0 = pl.multiple_of(c * chunk, chunk)
        loaded = {}

        def at(q):
            if q not in loaded:
                loaded[q] = xwin[slot, pl.ds(c0 + q, chunk), :]
            return loaded[q]

        acc = jnp.zeros((chunk, LANES), jnp.float32)
        for k, (q, r) in enumerate(taps):
            xs = at(q)
            if r:
                xs = jnp.where(lane < LANES - r,
                               pltpu.roll(xs, LANES - r, 1),
                               pltpu.roll(at(q + 1), LANES - r, 1))
            acc = acc + diags_ref[k, pl.ds(c0, chunk), :] * xs
        y_ref[pl.ds(c0, chunk), :] = acc
        return carry

    lax.fori_loop(0, rows // chunk, chunk_rows, 0)


def _dia_spmv(diags: jax.Array, x: jax.Array, *, offsets: Tuple[int, ...],
              interpret: bool = False) -> jax.Array:
    """y = A @ x with A in the DIA layout: ``diags`` (d, n_row_pad / 128,
    128) float32, one lane row per 128 matrix rows, and ``offsets`` its
    diagonals, ascending; x (n_cols,), read as zero outside its length.
    Returns the (n_row_pad,) result: one ``pallas_call`` a group of
    diagonals (``_dia_groups``; one on a stencil), summed. The padded rows
    must be a whole number of grid steps (``dia_rows``), as the DIA launch
    format pads them (``sparse.tensor.dia_row_pad``)."""
    d, lane_rows, _ = diags.shape
    rows = dia_rows(d, lane_rows)
    if lane_rows % rows:
        raise ValueError(f"{lane_rows} lane rows of diagonals are not a "
                         f"whole number of {rows}-row DIA grid steps")
    parts = [_dia_call(diags if g1 - g0 == d else diags[g0:g1], x,
                       offsets[g0:g1], rows, interpret)
             for g0, g1 in _dia_groups(offsets)]
    if not parts:
        return jnp.zeros((lane_rows * LANES,), jnp.float32)
    return sum(parts[1:], parts[0]).reshape(-1)


def _dia_call(diags, x, offsets, rows, interpret) -> jax.Array:
    """The DIA ``pallas_call`` over one group of diagonals: a grid step a
    block of 128 ``rows`` matrix rows, x padded and shifted into lane rows
    here, inside the launch. Returns (n_row_pad / 128, 128)."""
    d, lane_rows, _ = diags.shape
    steps = lane_rows // rows
    base = min(offsets) // LANES        # lane row of the lowest read
    taps = tuple(divmod(o - LANES * base, LANES) for o in offsets)
    win = -(-(rows + taps[-1][0] + 1) // 8) * 8
    n_x = ((steps - 1) * rows + win) * LANES
    start = LANES * base                # x index of the shifted x's first
    left = max(-start, 0)
    xs = x.astype(jnp.float32)[max(start, 0):][: n_x - left]
    xw = jnp.pad(xs, (left, n_x - left - xs.shape[0])).reshape(-1, LANES)
    return pl.pallas_call(
        functools.partial(_dia_kernel, taps=taps,
                          chunk=math.gcd(DIA_CHUNK, rows)),
        grid=(steps,),
        in_specs=[pl.BlockSpec((d, rows, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lane_rows, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, win, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(diags, xw)
