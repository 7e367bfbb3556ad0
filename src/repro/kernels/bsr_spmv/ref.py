"""Pure-jnp oracles for the ELL/SELL-BSR SpMV and SpMM kernels (same
inputs, same outputs)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@jax.jit
def ref_bsr_spmv(block_indices: jax.Array, block_cols: jax.Array,
                 blocks: jax.Array, x_blocks: jax.Array) -> jax.Array:
    """y[i] = sum_j blocks[idx[i, j]] @ x_blocks[cols[i, j]]."""
    a = blocks[block_indices]          # (n_br, mb, bs, bs)
    xs = x_blocks[block_cols]          # (n_br, mb, bs)
    return jnp.einsum("rmab,rmb->ra", a, xs)


@jax.jit
def ref_bsr_spmm(block_indices: jax.Array, block_cols: jax.Array,
                 blocks: jax.Array, x_blocks: jax.Array) -> jax.Array:
    """Y[i] = sum_j blocks[idx[i, j]] @ x_blocks[cols[i, j]] (multi-RHS)."""
    a = blocks[block_indices]          # (n_br, mb, bs, bs)
    xs = x_blocks[block_cols]          # (n_br, mb, bs, k)
    return jnp.einsum("rmab,rmbk->rak", a, xs)


@functools.partial(jax.jit, static_argnames=("n_block_rows",))
def ref_bsr_spmv_sell(cell_block: jax.Array, cell_col: jax.Array,
                      cell_row: jax.Array, blocks: jax.Array,
                      x_blocks: jax.Array, n_block_rows: int) -> jax.Array:
    """y_sorted[r] = sum over cells t with cell_row[t] == r of
    blocks[cell_block[t]] @ x_blocks[cell_col[t]]."""
    prods = jnp.einsum("tab,tb->ta", blocks[cell_block], x_blocks[cell_col])
    return jax.ops.segment_sum(prods, cell_row, num_segments=n_block_rows)


@functools.partial(jax.jit, static_argnames=("n_block_rows",))
def ref_bsr_spmm_sell(cell_block: jax.Array, cell_col: jax.Array,
                      cell_row: jax.Array, blocks: jax.Array,
                      x_blocks: jax.Array, n_block_rows: int) -> jax.Array:
    """Multi-RHS form of ``ref_bsr_spmv_sell``: x_blocks is (n_bc, bs, k)."""
    prods = jnp.einsum("tab,tbk->tak", blocks[cell_block], x_blocks[cell_col])
    return jax.ops.segment_sum(prods, cell_row, num_segments=n_block_rows)


@functools.partial(jax.jit, static_argnames=("offsets",))
def ref_dia_spmv(diags: jax.Array, x: jax.Array,
                 offsets: tuple) -> jax.Array:
    """y[i] = sum_d diags[d, i] * x[i + offsets[d]], a sum of shifted
    slices of x (read as zero outside its length). ``diags`` is (d,
    n_row_pad / 128, 128); x is (n_cols,) or (n_cols, k); returns
    (n_row_pad,) or (n_row_pad, k)."""
    vals = diags.reshape(len(offsets), -1)
    n_pad = vals.shape[1]
    y = jnp.zeros((n_pad,) + x.shape[1:], jnp.float32)
    if not offsets:
        return y
    lo = max(0, -min(offsets))
    hi = max(0, n_pad + max(offsets) - x.shape[0])
    xp = jnp.pad(x.astype(jnp.float32), [(lo, hi)] + [(0, 0)] * (x.ndim - 1))
    for v, o in zip(vals, offsets):
        seg = jax.lax.slice_in_dim(xp, lo + o, lo + o + n_pad)
        y = y + (v if x.ndim == 1 else v[:, None]) * seg
    return y
