"""Gustavson SpGEMM numeric phase as a Pallas block-pair GEMM (paper Alg. 2).

Gustavson scans rows of A and gathers rows of B ("scan-and-lookup", §3.4).
At block granularity the same dataflow is: for every output block C[i,j],
accumulate A[i,k] @ B[k,j] over the k's where both blocks exist. The host
symbolic phase (ops.spgemm_symbolic) enumerates those (a_idx, b_idx) pairs
in A-row-major order — *the* Gustavson schedule — padded per output block
to ``max_pairs`` with zero-block sentinels.

grid = (n_c_blocks, max_pairs), pair axis innermost: the C tile stays
resident in VMEM while its contributions stream through the MXU, giving the
temporal locality on C that the paper says CPU caches fail to provide for
B (the B-reuse problem becomes *A/B-tile streaming* + C-residency, which is
the TPU-correct formulation).

VMEM per cell: 3 tiles (A, B, C) x bs^2 x 4B x double-buffering; bs=128 ->
~400 KB. MXU does (bs x bs) @ (bs x bs) — full systolic utilization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 tile products: the MXU's default pass rounds f32 operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST


def _spgemm_cells_kernel(ca_ref, cb_ref, cc_ref, a_ref, b_ref, c_ref):
    del ca_ref, cb_ref  # consumed by the index maps
    t = pl.program_id(0)
    first = jnp.logical_or(t == 0, cc_ref[t] != cc_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], precision=HIGHEST,
        preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("n_c_blocks", "interpret"))
def bsr_spgemm_cells_pallas(cell_a: jax.Array, cell_b: jax.Array,
                            cell_c: jax.Array, a_blocks: jax.Array,
                            b_blocks: jax.Array, n_c_blocks: int,
                            interpret: bool = False) -> jax.Array:
    """Cell-flattened Gustavson numeric phase (the SELL trick applied to
    ragged block-pair lists, DESIGN.md §8): one grid step per REAL
    contribution pair instead of (n_c, max_pairs) with hub-padded slots.

    Args:
      cell_a/cell_b: (n_cells,) int32 — A/B tile of grid step t.
      cell_c: (n_cells,) int32 — output C block per step, *nondecreasing*
        (a C block's cells are consecutive), so the C tile stays resident
        and Pallas flushes it exactly when the block index advances.
      a_blocks/b_blocks: (n_a, bs, bs) / (n_b, bs, bs) f32 (no sentinel —
        there is no padding to point at one).
      n_c_blocks: static output block count.
    Returns:
      (n_c_blocks, bs, bs) float32.
    """
    n_cells = cell_a.shape[0]
    bs = a_blocks.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda t, ca, cb, cc: (ca[t], 0, 0)),
            pl.BlockSpec((1, bs, bs), lambda t, ca, cb, cc: (cb[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, bs), lambda t, ca, cb, cc: (cc[t], 0, 0)),
    )
    return pl.pallas_call(
        _spgemm_cells_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_c_blocks, bs, bs), jnp.float32),
        interpret=interpret,
    )(cell_a, cell_b, cell_c, a_blocks, b_blocks)


def _spgemm_kernel(pa_ref, pb_ref, a_ref, b_ref, c_ref):
    del pa_ref, pb_ref
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], precision=HIGHEST,
        preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spgemm_pallas(pair_a: jax.Array, pair_b: jax.Array,
                      a_blocks: jax.Array, b_blocks: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """C.blocks[k] = sum_p a_blocks[pair_a[k, p]] @ b_blocks[pair_b[k, p]].

    Args:
      pair_a/pair_b: (n_c_blocks, max_pairs) int32; padding slots hold the
        zeros-sentinel index (last block of each array).
      a_blocks: (n_a + 1, bs, bs) f32; b_blocks: (n_b + 1, bs, bs) f32.
    Returns:
      (n_c_blocks, bs, bs) float32.
    """
    n_c, mp = pair_a.shape
    bs = a_blocks.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_c, mp),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda k, p, pa, pb: (pa[k, p], 0, 0)),
            pl.BlockSpec((1, bs, bs), lambda k, p, pa, pb: (pb[k, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, bs), lambda k, p, pa, pb: (k, 0, 0)),
    )
    return pl.pallas_call(
        _spgemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_c, bs, bs), jnp.float32),
        interpret=interpret,
    )(pair_a, pair_b, a_blocks, b_blocks)
