"""Legacy SpMV/SpMM entry points — thin shims over the plan/execute facade.

The real dispatch (layout/backend/x-blocking) moved to
``repro.sparse.ops_builtin``; construction moved to
``repro.sparse.SparseTensor.from_csr``. These wrappers keep the historical
signatures working and delegate (DESIGN.md §8 migration table):

    prepare / prepare_sell / prepare_with_schedule
        -> SparseTensor.from_csr(csr, schedule=...) (.build_container for
           the bare host container these shims still return)
    bsr_spmv(a, x) / bsr_spmm(a, X)
        -> plan("spmv"/"spmm", (a,)).execute(x)
    bsr_spmv_scheduled(csr, x, sched)
        -> plan("spmv"/"spmm", (csr,), schedule=sched).execute(x)

The oracle helpers and device-array exporters remain here for tests.
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...core.autotune import SELL_SIGMA, Schedule
from ...core.csr import CSR, ELLBSR, SELLBSR

SparseLayout = Union[ELLBSR, SELLBSR]


def ell_device_arrays(ell: ELLBSR) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Move an ELLBSR container to device arrays for the kernel."""
    return (jnp.asarray(ell.block_indices, jnp.int32),
            jnp.asarray(ell.block_cols, jnp.int32),
            jnp.asarray(ell.blocks, jnp.float32),
            ell.block_size)


def sell_device_arrays(sell: SELLBSR
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Move a SELLBSR container's cell schedule to device arrays."""
    return (jnp.asarray(sell.cell_block, jnp.int32),
            jnp.asarray(sell.cell_col, jnp.int32),
            jnp.asarray(sell.cell_row, jnp.int32),
            jnp.asarray(sell.blocks, jnp.float32))


def prepare(csr: CSR, block_size: int = 128, max_blocks: int | None = None) -> ELLBSR:
    """.. deprecated:: use ``SparseTensor.from_csr`` (returns the device
    pytree; this shim returns the bare host container)."""
    from ...sparse import SparseTensor
    return SparseTensor.build_container(
        csr, Schedule("bsr", block_size, 1.0), max_blocks=max_blocks)


def prepare_sell(csr: CSR, block_size: int = 128, slice_height: int = 8,
                 sigma: int = 64) -> SELLBSR:
    """.. deprecated:: use ``SparseTensor.from_csr(..., layout="sell")``."""
    from ...sparse import SparseTensor
    return SparseTensor.build_container(
        csr, Schedule("bsr", block_size, 1.0, layout="sell",
                      slice_height=slice_height), sigma=sigma)


def prepare_with_schedule(csr: CSR, sched: Schedule,
                          sigma: int = SELL_SIGMA) -> SparseLayout:
    """.. deprecated:: use ``SparseTensor.from_csr(csr, schedule=sched)``."""
    if sched.backend == "dense":
        raise ValueError("dense schedules have no sparse container; "
                         "dispatch to a dense matmul instead")
    from ...sparse import SparseTensor
    return SparseTensor.build_container(csr, sched, sigma=sigma)


def bsr_spmv_scheduled(csr: CSR, x: jax.Array, sched: Schedule,
                       backend: str = "auto") -> jax.Array:
    """.. deprecated:: use ``plan("spmv", (csr,), schedule=sched)``."""
    from ...sparse import plan
    x = jnp.asarray(x)
    op = "spmm" if x.ndim == 2 else "spmv"
    return plan(op, (csr,), schedule=sched, backend=backend).execute(x)


def bsr_spmv(a: SparseLayout, x: jax.Array, backend: str = "auto") -> jax.Array:
    """y = A @ x for a prepared ELL/SELL container.

    .. deprecated:: use ``plan("spmv", (a,))`` — this shim delegates there.
    """
    from ...sparse import plan
    return plan("spmv", (a,), backend=backend).execute(x)


def bsr_spmm(a: SparseLayout, X: jax.Array, backend: str = "auto"
             ) -> jax.Array:
    """Y = A @ X for a prepared ELL/SELL container (multi-RHS).

    .. deprecated:: use ``plan("spmm", (a,))`` — this shim delegates there.
    """
    from ...sparse import plan
    return plan("spmm", (a,), backend=backend).execute(X)


def spmv_oracle(csr: CSR, x: np.ndarray) -> np.ndarray:
    """CSR-semantics oracle (paper Alg. 1), dense math."""
    return csr.to_dense() @ np.asarray(x, np.float32)


def spmm_oracle(csr: CSR, X: np.ndarray) -> np.ndarray:
    """CSR-semantics multi-RHS oracle, dense math."""
    return csr.to_dense() @ np.asarray(X, np.float32)
