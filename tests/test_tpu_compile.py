"""Ahead-of-time compiles of the main path's Pallas launches for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, scalar-prefetch tables past the
1 MiB SMEM). Interpret mode catches neither. The widths are the ones
``chip_smoke.py`` runs (HPCG 27-point stencils, shape-bucketed), so the
row- and cell-split launches of ``repro.sparse.smem`` are compiled too.
Nothing here runs a kernel.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.autotune import Schedule
from repro.sparse.ops_builtin import (_exec_matvec, _exec_matvec_stacked,
                                      _exec_spadd, _exec_spgemm_cells,
                                      _exec_spgemm_pairs)
from repro.sparse.smem import SMEM_BUDGET_BYTES, row_ranges, table_bytes
from repro.sparse.tensor import SparseMeta, SparseTensor

I32, F32 = "int32", "float32"
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described v5e:2x2 as the sharded path's 1-D ``shards`` mesh."""
    from repro.launch.mesh import auto_mesh
    from repro.sparse.partition import SHARD_AXIS
    return auto_mesh((4,), (SHARD_AXIS,), topo.devices)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _ell(sharding, n, bs, n_br, mb, n_blocks):
    meta = SparseMeta("ell", (n, n), bs, n_block_rows=n_br,
                      schedule=Schedule("bsr", bs, 1.0))
    return SparseTensor(meta, {
        "block_indices": _spec(sharding, (n_br, mb), I32),
        "block_cols": _spec(sharding, (n_br, mb), I32),
        "blocks": _spec(sharding, (n_blocks, bs, bs), F32),
        "valid_counts": _spec(sharding, (n_br,), I32)})


def _sell(sharding, n, bs, n_br, n_cells, n_blocks, c=8):
    meta = SparseMeta("sell", (n, n), bs, n_block_rows=n_br, slice_height=c,
                      sigma=64, schedule=Schedule("bsr", bs, 1.0,
                                                  layout="sell",
                                                  slice_height=c))
    return SparseTensor(meta, {
        "cell_block": _spec(sharding, (n_cells,), I32),
        "cell_col": _spec(sharding, (n_cells,), I32),
        "cell_row": _spec(sharding, (n_cells,), I32),
        "row_perm": _spec(sharding, (n_br,), I32),
        "slice_widths": _spec(sharding, (n_br // c,), I32),
        "blocks": _spec(sharding, (n_blocks, bs, bs), F32)})


def _launches(compiled) -> int:
    text = compiled.as_text()
    assert KERNEL in text, "no Pallas kernel in the compiled program"
    return text.count(KERNEL)


# (layout, bs, op): 64^3 stencil = 262,144 rows after shape bucketing.
# ell bs=128: n_br 2048 (two 512 KiB tables unsplit: over SMEM);
# ell bs=256: the selector's pick for the stencil; sell bs=32 and 128:
# 196,608 cells (2.25 MiB of cell streams unsplit). spmv runs the VPU tile
# product (its lane-group accumulator and the row flush), spmm the MXU one;
# the ELL spmv streams each row's valid tiles through its VMEM ring, its
# valid-count table split beside the slot tables.
MATVEC = [("ell", 128, "spmv"), ("ell", 128, "spmm"), ("ell", 256, "spmv"),
          ("sell", 32, "spmv"), ("sell", 32, "spmm"), ("sell", 128, "spmv")]


@pytest.mark.parametrize("layout,bs,op", MATVEC)
def test_matvec_compiles_for_v5e(one_chip, layout, bs, op):
    n = 262144
    if layout == "ell":
        n_br, mb = n // bs, 12
        st = _ell(one_chip, n, bs, n_br, mb, {128: 24576, 256: 12288}[bs])
        tables = [(n_br, mb)] * 2 + ([(n_br,)] if op == "spmv" else [])
    else:
        n_br, n_cells = n // bs, 196608
        st = _sell(one_chip, n, bs, n_br, n_cells, n_cells)
        tables = [(n_cells,)] * 3
    assert sum(table_bytes(t) for t in tables) > SMEM_BUDGET_BYTES
    x = _spec(one_chip, (n,) if op == "spmv" else (n, 8), F32)
    compiled = _exec_matvec.lower(st, x, backend="pallas",
                                  rhs_tile=128).compile()
    assert _launches(compiled) == len(row_ranges(tables[0][0], tables)) > 1


def _stencil_offsets(nx, ny):
    return tuple(sorted({dz * nx * ny + dy * nx + dx for dz in (-1, 0, 1)
                         for dy in (-1, 0, 1) for dx in (-1, 0, 1)}))


@pytest.mark.parametrize("nz", [96, 384])
def test_dia_spmv_compiles_for_v5e(one_chip, nz):
    """hpcg's and hpcg_4rank's DIA launch: the 96x96xnz stencil's 27
    diagonals, one Pallas call named as the family's SpMV entry (the name a
    device trace shows), x padded into lane rows inside the launch."""
    from repro.sparse.tensor import dia_row_pad
    n = 96 * 96 * nz
    pad = dia_row_pad(27, n)
    assert pad == n     # 96 x 96 x nz fills whole row blocks
    meta = SparseMeta("dia", (n, n), 128, n_block_rows=pad // 128,
                      schedule=Schedule("dia", 128, 1.0, layout="dia"),
                      offsets=_stencil_offsets(96, 96))
    st = SparseTensor(meta, {"diags": _spec(one_chip, (27, pad // 128, 128),
                                            F32)})
    compiled = _exec_matvec.lower(st, _spec(one_chip, (n,), F32),
                                  backend="pallas", rhs_tile=128).compile()
    assert _launches(compiled) == 1
    assert "%bsr_spmv_pallas" in compiled.as_text()


def test_stacked_matvec_compiles_for_v5e(one_chip):
    """A mixed-content bucket of two 48^3 stencils: one program, each
    member's streamed ELL SpMV split by block-row range."""
    b, n, bs, n_br, mb, nb = 2, 131072, 128, 1024, 12, 8192
    arrays = {"block_indices": _spec(one_chip, (b, n_br, mb), I32),
              "block_cols": _spec(one_chip, (b, n_br, mb), I32),
              "valid_counts": _spec(one_chip, (b, n_br), I32),
              "blocks": _spec(one_chip, (b, nb, bs, bs), F32)}
    xs = _spec(one_chip, (b, n), F32)
    compiled = _exec_matvec_stacked.lower(arrays, xs, layout="ell",
                                          backend="pallas").compile()
    per_member = len(row_ranges(n_br, [(n_br, mb)] * 2 + [(n_br,)]))
    assert per_member > 1
    assert _launches(compiled) == b * per_member


@pytest.mark.parametrize("mode", ["pairs", "cells"])
def test_spgemm_compiles_for_v5e(one_chip, mode):
    """A*A of the 32^3 stencil at bs=128 (5,236 output blocks, bucketed)."""
    bs, n_blk = 128, 3072
    blocks = [_spec(one_chip, (n_blk, bs, bs), F32)] * 2
    if mode == "pairs":
        tables = [(6144, 12)] * 2
        compiled = _exec_spgemm_pairs.lower(
            *[_spec(one_chip, t, I32) for t in tables], *blocks,
            backend="pallas").compile()
    else:
        tables = [(24576,)] * 3
        compiled = _exec_spgemm_cells.lower(
            *[_spec(one_chip, t, I32) for t in tables], *blocks, n_c=6144,
            backend="pallas").compile()
    assert _launches(compiled) == len(row_ranges(tables[0][0], tables))


def test_spadd_compiles_for_v5e(one_chip):
    """A + B over 196,608 output blocks at bs=32: two source lists of
    768 KiB each, split by output block range."""
    n_c, bs = 196608, 32
    tables = [(n_c,)] * 2
    compiled = _exec_spadd.lower(
        *[_spec(one_chip, t, I32) for t in tables],
        *[_spec(one_chip, (n_c + 1, bs, bs), F32)] * 2,
        backend="pallas").compile()
    assert _launches(compiled) == len(row_ranges(n_c, tables)) > 1


def test_sharded_matvec_compiles_for_four_v5e(four_chips):
    """hpcg.cg_4chip's launch: the 96x96x384 stencil in four row shards,
    each the 4,096 x 12 ELL grid over 32,768 bs=256 tiles (8.6 GB a chip),
    x replicated. One shard_map program: each chip runs the same split
    ELL launches as a one-chip plan, and no collective is compiled in."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sparse.partition import SHARD_AXIS
    from repro.sparse.ops_builtin import _sharded_matvec_exec
    rows = NamedSharding(four_chips, P(SHARD_AXIS))
    n_br, mb, nb, bs = 4096, 12, 32768, 256
    arrays = {"block_indices": _spec(rows, (4, n_br, mb), I32),
              "block_cols": _spec(rows, (4, n_br, mb), I32),
              "valid_counts": _spec(rows, (4, n_br), I32),
              "blocks": _spec(rows, (4, nb, bs, bs), F32)}
    xb = _spec(NamedSharding(four_chips, P()), (16384 * bs,), F32)
    compiled = _sharded_matvec_exec(four_chips, "ell", "pallas", False,
                                    886294).lower(arrays, xb).compile()
    assert _launches(compiled) == len(
        row_ranges(n_br, [(n_br, mb)] * 2 + [(n_br,)])) > 1
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    assert compiled.memory_analysis().argument_size_in_bytes \
        < 9 * 10 ** 9    # one shard and x, per chip
