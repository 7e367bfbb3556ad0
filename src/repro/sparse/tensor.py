"""``SparseTensor``: the pytree-registered device container of the facade.

One class wraps every prepared layout the kernels consume (DESIGN.md §8):

  ell    globally padded ELL-BSR (``core.csr.ELLBSR``)
  sell   sliced SELL-BSR cell schedule (``core.csr.SELLBSR``)
  bsr    raw blocked rows (spgemm/spadd operands; symbolic phase is host-side)
  dense  the dense-schedule escape hatch (density above the autotune threshold)

The device arrays are pytree *leaves* and the structural facts (layout,
shape, block size, the ``Schedule`` that built it) are static aux data, so a
prepared operand passes through ``jit`` / ``vmap`` / buffer donation like
any other array pytree — the property the old ``prepare*`` family of host
containers never had. Construction subsumes that family through
``SparseTensor.from_csr(csr, schedule=...)``; the host-side container is
kept on the instance (outside the pytree) so characterization counters and
unflattened copies inside traced code both work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autotune import SELL_SIGMA, Schedule
from ..core.csr import (BSR, CSR, ELLBSR, SELLBSR, ell_block_cap,
                        sell_layout)
from .prepared import bucket_edge

HostLayout = Union[ELLBSR, SELLBSR, BSR, np.ndarray]

# Leaf names per layout, in flatten order (the pytree contract).
LAYOUT_FIELDS: Dict[str, Tuple[str, ...]] = {
    "ell": ("block_indices", "block_cols", "blocks", "valid_counts"),
    "sell": ("cell_block", "cell_col", "cell_row", "row_perm",
             "slice_widths", "blocks"),
    "bsr": ("block_ptrs", "block_cols", "blocks"),
    "dense": ("dense",),
}


@dataclasses.dataclass(frozen=True)
class SparseMeta:
    """Static (hashable) aux data of a ``SparseTensor`` pytree node."""

    layout: str
    shape: Tuple[int, int]
    block_size: int
    n_block_rows: int = 0
    slice_height: int = 0
    sigma: int = 0
    schedule: Optional[Schedule] = None


class SparseTensor:
    """Device-resident sparse operand; registered as a JAX pytree node."""

    def __init__(self, meta: SparseMeta, arrays: Dict[str, jax.Array],
                 host: Optional[HostLayout] = None) -> None:
        if meta.layout not in LAYOUT_FIELDS:
            raise ValueError(f"unknown layout {meta.layout!r}; "
                             f"one of {sorted(LAYOUT_FIELDS)}")
        self.meta = meta
        self.arrays = dict(arrays)
        # Host container cache — intentionally NOT a pytree leaf: it is a
        # construction-side artifact that tracers cannot carry.
        self._host = host
        # Logical (unbucketed) shape. Shape-bucketed containers carry the
        # padded shape in ``meta`` (so equal buckets share a jit key) and
        # the true shape here, outside the pytree, for output slicing.
        self.true_shape = meta.shape
        # Mutation state (DESIGN.md §14), all outside the pytree: the
        # generation counter bumps on every applied delta (leaf shapes and
        # meta stay identical, so jit never retraces a value update);
        # ``spare_blocks`` is the reserved pool of all-zero block slots a
        # structural insert can claim (``from_csr(..., slack=)`` fills it);
        # ``_mut`` holds the lazily built host bookkeeping of the delta
        # path (block map, free-slot cursors).
        self.generation = 0
        self.spare_blocks: list = []
        self._mut: Optional[dict] = None
        # Index of the shared all-zeros pad block. Bucket padding appends
        # blocks AFTER it (indices keep pointing at the pre-pad position),
        # so ``from_csr`` records it pre-pad; ``blocks.shape[0] - 1`` is
        # only correct for unbucketed containers.
        self._zero_idx: Optional[int] = None

    # -------------------------------------------------------------- pytree
    def tree_flatten(self):
        fields = LAYOUT_FIELDS[self.meta.layout]
        return tuple(self.arrays[f] for f in fields), self.meta

    @classmethod
    def tree_unflatten(cls, meta: SparseMeta, leaves):
        return cls(meta, dict(zip(LAYOUT_FIELDS[meta.layout], leaves)))

    # ------------------------------------------------------------- basics
    @property
    def layout(self) -> str:
        return self.meta.layout

    @property
    def shape(self) -> Tuple[int, int]:
        return self.meta.shape

    @property
    def block_size(self) -> int:
        return self.meta.block_size

    @property
    def schedule(self) -> Optional[Schedule]:
        return self.meta.schedule

    def __repr__(self) -> str:
        return (f"SparseTensor(layout={self.meta.layout!r}, "
                f"shape={self.meta.shape}, bs={self.meta.block_size})")

    # ------------------------------------------------------- construction
    @staticmethod
    def build_container(csr: CSR, schedule: Schedule, *,
                        layout: Optional[str] = None,
                        sigma: int = SELL_SIGMA,
                        max_blocks: Optional[int] = None,
                        full_rows: bool = False) -> HostLayout:
        """Host-side container a ``Schedule`` names (the old ``prepare*``
        family as one rule; kernels' shims delegate here).

        ``full_rows=True`` ignores the schedule's ``ell_quantile`` cap and
        keeps every block: mutable tensors (``slack > 0``) must not truncate
        tail blocks, because a later delta touching a truncated position
        would be indistinguishable from an insert and land in slack with
        only the delta's values — silently dropping the base values.
        """
        if schedule.backend == "dense":
            return csr.to_dense()
        if layout == "bsr":
            return BSR.from_csr(csr, schedule.block_size)
        if schedule.layout == "sell":
            return SELLBSR.from_bsr(BSR.from_csr(csr, schedule.block_size),
                                    max(schedule.slice_height, 1), sigma)
        bsr = BSR.from_csr(csr, schedule.block_size)
        return ELLBSR.from_bsr(bsr, _ell_cap(bsr, schedule, max_blocks,
                                             full_rows))

    @staticmethod
    def default_schedule(block_size: int = 128, layout: Optional[str] = None,
                         slice_height: int = 8) -> Schedule:
        """The Schedule ``from_csr`` assumes when none is given (shared with
        the planners so a store key can be formed before building)."""
        if layout == "sell":
            return Schedule("bsr", block_size, 1.0, layout="sell",
                            slice_height=slice_height)
        return Schedule("bsr", block_size, 1.0)

    @classmethod
    def from_csr(cls, csr: CSR, schedule: Optional[Schedule] = None, *,
                 block_size: int = 128, layout: Optional[str] = None,
                 slice_height: int = 8, sigma: int = SELL_SIGMA,
                 max_blocks: Optional[int] = None,
                 shape_bucket: bool = False,
                 slack: int = 0,
                 device: Optional[jax.Device] = None) -> "SparseTensor":
        """Prepare ``csr`` under ``schedule`` (or the keyword defaults).

        ``layout="bsr"`` forces the raw blocked container regardless of the
        schedule's ell/sell axis (spgemm/spadd operands).

        ``shape_bucket=True`` pads the prepared container's dimensions up to
        power-of-two-ish bucket edges (``prepared.bucket_edge``) so matrices
        of nearby sizes share one jit cache key; the returned tensor's
        ``meta.shape`` is the padded shape and ``true_shape`` the logical
        one (executors slice outputs back outside the traced program).

        ``slack > 0`` reserves mutation headroom in ELL/SELL containers
        (DESIGN.md §14): ``slack`` extra block slots per block-row (ELL) /
        per slice row (SELL) plus a pool of spare all-zero blocks, so
        ``apply_delta`` can absorb structural inserts without a rebuild.
        ``MutableMatrix`` sets ``csr.mutation_slack`` and every planner's
        prep path forwards it here automatically.

        ``device`` places the arrays on that device straight from the host
        (the default device otherwise).
        """
        if schedule is None:
            schedule = cls.default_schedule(block_size, layout, slice_height)
        container, zero_idx, spare = build_host(
            csr, schedule, layout=layout, sigma=sigma, max_blocks=max_blocks,
            shape_bucket=shape_bucket, slack=slack)
        st = cls.from_layout(container, schedule=schedule, device=device)
        st.true_shape = (int(csr.shape[0]), int(csr.shape[1]))
        st.spare_blocks = spare
        st._zero_idx = zero_idx
        return st

    @classmethod
    def from_layout(cls, container: HostLayout,
                    schedule: Optional[Schedule] = None,
                    device: Optional[jax.Device] = None) -> "SparseTensor":
        """Wrap an existing host container (ELLBSR/SELLBSR/BSR/dense), its
        arrays uploaded to ``device`` (the default device when None)."""
        if device is None:
            put = jnp.asarray
        else:
            def put(a, dtype):
                return jax.device_put(np.asarray(a, dtype), device)
        if isinstance(container, ELLBSR):
            if schedule is None:
                schedule = Schedule("bsr", container.block_size, 1.0)
            meta = SparseMeta("ell", container.shape, container.block_size,
                              n_block_rows=container.block_indices.shape[0],
                              schedule=schedule)
            arrays = {
                "block_indices": put(container.block_indices, jnp.int32),
                "block_cols": put(container.block_cols, jnp.int32),
                "blocks": put(container.blocks, jnp.float32),
                "valid_counts": put(container.valid_counts, jnp.int32),
            }
            return cls(meta, arrays, host=container)
        if isinstance(container, SELLBSR):
            if schedule is None:
                schedule = Schedule("bsr", container.block_size, 1.0,
                                    layout="sell",
                                    slice_height=container.slice_height)
            meta = SparseMeta("sell", container.shape, container.block_size,
                              n_block_rows=container.n_block_rows,
                              slice_height=container.slice_height,
                              sigma=container.sigma, schedule=schedule)
            arrays = {
                "cell_block": put(container.cell_block, jnp.int32),
                "cell_col": put(container.cell_col, jnp.int32),
                "cell_row": put(container.cell_row, jnp.int32),
                "row_perm": put(container.row_perm, jnp.int32),
                "slice_widths": put(container.slice_widths, jnp.int32),
                "blocks": put(container.blocks, jnp.float32),
            }
            return cls(meta, arrays, host=container)
        if isinstance(container, BSR):
            if schedule is None:
                schedule = Schedule("bsr", container.block_size, 1.0)
            meta = SparseMeta("bsr", container.shape, container.block_size,
                              n_block_rows=container.n_block_rows,
                              schedule=schedule)
            arrays = {
                "block_ptrs": put(container.block_ptrs, jnp.int32),
                "block_cols": put(container.block_cols, jnp.int32),
                "blocks": put(container.blocks, jnp.float32),
            }
            return cls(meta, arrays, host=container)
        dense = np.asarray(container, np.float32)
        if dense.ndim != 2:
            raise TypeError(f"cannot wrap {type(container).__name__} as a "
                            "SparseTensor")
        if schedule is None:
            schedule = Schedule("dense", 128, 1.0)
        meta = SparseMeta("dense", dense.shape, schedule.block_size,
                          schedule=schedule)
        return cls(meta, {"dense": put(dense, jnp.float32)}, host=dense)

    @classmethod
    def wrap(cls, obj, schedule: Optional[Schedule] = None) -> "SparseTensor":
        """Coerce any accepted operand form — CSR, host container, or an
        already-built SparseTensor — into a SparseTensor."""
        if isinstance(obj, SparseTensor):
            return obj
        if isinstance(obj, CSR):
            return cls.from_csr(obj, schedule=schedule)
        return cls.from_layout(obj, schedule=schedule)

    # ----------------------------------------------------------- mutation
    def apply_delta(self, delta) -> "SparseTensor":
        """Apply a ``repro.sparse.mutate.Delta`` to this prepared container
        in place (DESIGN.md §14).

        Value updates rebind the device leaves to same-shape scatters — no
        host re-prep, and no retrace because the pytree structure and every
        aval are unchanged. Structural inserts claim reserved slack
        (``from_csr(..., slack=)``); when the slack is exhausted the call
        raises ``SlackOverflow`` and the caller (``MutableMatrix``) performs
        an epoch-swap rebuild instead. Bumps ``self.generation``.
        """
        from .mutate import apply_delta_to_tensor
        return apply_delta_to_tensor(self, delta)

    # ---------------------------------------------------------- host side
    def to_host(self) -> HostLayout:
        """The host container (rebuilt from device leaves if this instance
        came out of a pytree unflatten)."""
        if self._host is not None:
            return self._host
        m, a = self.meta, self.arrays
        if m.layout == "ell":
            host: HostLayout = ELLBSR(
                np.asarray(a["block_indices"]), np.asarray(a["block_cols"]),
                np.asarray(a["blocks"]), m.shape, m.block_size,
                np.asarray(a["valid_counts"]))
        elif m.layout == "sell":
            host = SELLBSR(
                np.asarray(a["cell_block"]), np.asarray(a["cell_col"]),
                np.asarray(a["cell_row"]), np.asarray(a["row_perm"]),
                np.asarray(a["slice_widths"]), np.asarray(a["blocks"]),
                m.shape, m.block_size, m.slice_height, m.sigma)
        elif m.layout == "bsr":
            host = BSR(np.asarray(a["block_ptrs"], np.int64),
                       np.asarray(a["block_cols"]), np.asarray(a["blocks"]),
                       m.shape, m.block_size)
        else:
            host = np.asarray(a["dense"])
        self._host = host
        return host


# ------------------------------------------------ shapes before a build

def container_shapes(csr: CSR, schedule: Schedule, *,
                     sigma: int = SELL_SIGMA,
                     shape_bucket: bool = False) -> Dict[str, Tuple[int, ...]]:
    """Shape of each device array ``SparseTensor.from_csr(csr, schedule,
    sigma=sigma, shape_bucket=shape_bucket)`` would hold, from the tile
    count of each block row alone: no tile is built. Every array is 4 bytes
    an element (int32 or float32)."""
    edge = bucket_edge if shape_bucket else int
    if schedule.backend == "dense":
        return {"dense": (edge(csr.n_rows), edge(csr.n_cols))}
    bs = schedule.block_size
    bpr = csr.block_row_tiles(bs)
    n_br, nb = bpr.size, int(bpr.sum()) + 1   # + the shared zero tile
    if schedule.layout == "sell":
        _, widths = sell_layout(bpr, max(schedule.slice_height, 1), sigma)
        n_cells = int(np.repeat(widths, max(schedule.slice_height, 1))
                      [:n_br].sum())
        return {"cell_block": (edge(n_cells),), "cell_col": (edge(n_cells),),
                "cell_row": (edge(n_cells),), "row_perm": (edge(n_br),),
                "slice_widths": (edge(widths.size),),
                "blocks": (edge(nb), bs, bs)}
    mb = ell_block_cap(bpr, min(schedule.ell_quantile, 1.0))
    return {"block_indices": (edge(n_br), edge(mb)),
            "block_cols": (edge(n_br), edge(mb)),
            "blocks": (edge(nb), bs, bs), "valid_counts": (edge(n_br),)}


def prepared_nbytes(csr: CSR, schedule: Schedule, **kw) -> int:
    """Device bytes of the container ``container_shapes`` describes."""
    return sum(4 * int(np.prod(s))
               for s in container_shapes(csr, schedule, **kw).values())


def build_host(csr: CSR, schedule: Schedule, *, layout: Optional[str] = None,
               sigma: int = SELL_SIGMA, max_blocks: Optional[int] = None,
               shape_bucket: bool = False, slack: int = 0
               ) -> Tuple[HostLayout, Optional[int], list]:
    """The host side of ``SparseTensor.from_csr``: the container it wraps,
    the index of that container's shared zero tile (None for bsr/dense),
    and the spare tiles of a mutable one."""
    if shape_bucket and slack == 0 and layout != "bsr" \
            and schedule.backend != "dense" and schedule.layout != "sell":
        container, zero_idx = _bucketed_ell(csr, schedule, max_blocks)
        return container, zero_idx, []
    container = SparseTensor.build_container(
        csr, schedule, layout=layout, sigma=sigma, max_blocks=max_blocks,
        full_rows=slack > 0)
    spare: list = []
    if slack > 0 and isinstance(container, (ELLBSR, SELLBSR)):
        from .mutate import reserve_slack
        container, spare = reserve_slack(container, int(slack))
    zero_idx = (int(container.blocks.shape[0]) - 1
                if isinstance(container, (ELLBSR, SELLBSR)) else None)
    if shape_bucket and not isinstance(container, BSR):
        container = pad_container_to_bucket(container)
    return container, zero_idx, spare


# --------------------------------------------------------- shape bucketing

def _ell_cap(bsr: BSR, schedule: Schedule, max_blocks: Optional[int],
             full_rows: bool) -> Optional[int]:
    """The ELL slot width ``build_container`` gives ``ELLBSR.from_bsr``."""
    if full_rows:
        return None
    if max_blocks is None and schedule.ell_quantile < 1.0:
        return ell_block_cap(bsr.blocks_per_row(), schedule.ell_quantile)
    return max_blocks


def _bucketed_ell(csr: CSR, schedule: Schedule,
                  max_blocks: Optional[int]) -> Tuple[ELLBSR, int]:
    """``build_container`` then ``pad_container_to_bucket`` for an ELL
    schedule, the same arrays, with every tile written once, straight into
    the bucket-sized tile array: no copy of the tiles between the steps.
    Returns the container and its zero tile's index."""
    bs = schedule.block_size
    tiles: list = []

    def alloc(n: int) -> np.ndarray:
        tiles.append(np.zeros((bucket_edge(n + 1), bs, bs), np.float32))
        return tiles[0]

    bsr = BSR.from_csr(csr, bs, alloc=alloc)
    ell = ELLBSR.from_bsr(bsr, _ell_cap(bsr, schedule, max_blocks, False),
                          blocks=tiles[0][: bsr.n_blocks + 1])
    return _pad_ell_to_bucket(ell, tiles=tiles[0]), bsr.n_blocks


def _pad_ell_to_bucket(ell: ELLBSR,
                       tiles: Optional[np.ndarray] = None) -> ELLBSR:
    """Pad an ELL container's dims (block-rows, slot width, block count,
    block-columns) up to bucket edges; numerics unchanged — pad slots point
    at the existing all-zeros block and pad output rows are sliced away.
    ``tiles``, given, is the bucket-sized tile array that already starts
    with ``ell.blocks``; it becomes the container's, uncopied."""
    n_br, mb = ell.block_indices.shape
    nb = ell.blocks.shape[0]            # includes the trailing zero block
    bs = ell.block_size
    zero_idx = nb - 1
    n_bc = -(-ell.shape[1] // bs)
    n_br_p, mb_p = bucket_edge(n_br), bucket_edge(mb)
    nb_p, n_bc_p = bucket_edge(nb), bucket_edge(n_bc)
    bi = np.full((n_br_p, mb_p), zero_idx, np.int32)
    bi[:n_br, :mb] = ell.block_indices
    bc = np.zeros((n_br_p, mb_p), np.int32)
    bc[:n_br, :mb] = ell.block_cols
    if tiles is None:
        blocks = np.zeros((nb_p, bs, bs), np.float32)
        blocks[:nb] = ell.blocks
    else:
        assert tiles.shape[0] == nb_p
        blocks = tiles
    vc = np.zeros(n_br_p, np.int32)
    vc[:n_br] = ell.valid_counts
    return ELLBSR(bi, bc, blocks, (n_br_p * bs, n_bc_p * bs), bs, vc)


def _pad_sell_to_bucket(sell: SELLBSR) -> SELLBSR:
    """Pad a SELL container (cells, block-rows, block count, block-columns)
    up to bucket edges. Pad cells extend the LAST sorted row with zero-block
    contributions, keeping ``cell_row`` nondecreasing (the Pallas
    output-residency contract); ``row_perm`` is identity-extended so padded
    sorted rows scatter onto padded (sliced-away) output rows."""
    n_cells, n_br = sell.n_cells, sell.n_block_rows
    nb = sell.blocks.shape[0]           # includes the trailing zero block
    bs = sell.block_size
    zero_idx = nb - 1
    n_bc = -(-sell.shape[1] // bs)
    n_cells_p, n_br_p = bucket_edge(n_cells), bucket_edge(n_br)
    nb_p, n_bc_p = bucket_edge(nb), bucket_edge(n_bc)
    cb = np.full(n_cells_p, zero_idx, np.int32)
    cb[:n_cells] = sell.cell_block
    cc = np.zeros(n_cells_p, np.int32)
    cc[:n_cells] = sell.cell_col
    last_row = int(sell.cell_row[-1]) if n_cells else 0
    cr = np.full(n_cells_p, last_row, np.int32)
    cr[:n_cells] = sell.cell_row
    perm = np.concatenate([sell.row_perm,
                           np.arange(n_br, n_br_p, dtype=np.int32)])
    n_sl = sell.n_slices
    sw = np.ones(bucket_edge(n_sl), np.int32)   # empty-slice width-1 rule
    sw[:n_sl] = sell.slice_widths
    blocks = np.zeros((nb_p, bs, bs), np.float32)
    blocks[:nb] = sell.blocks
    return SELLBSR(cb, cc, cr, perm, sw, blocks,
                   (n_br_p * bs, n_bc_p * bs), bs, sell.slice_height,
                   sell.sigma)


def pad_container_to_bucket(container: HostLayout) -> HostLayout:
    """Bucket-edge padding rule per layout (no-op for raw BSR, whose exec
    paths consume symbolic products that are bucketed separately)."""
    if isinstance(container, ELLBSR):
        return _pad_ell_to_bucket(container)
    if isinstance(container, SELLBSR):
        return _pad_sell_to_bucket(container)
    if isinstance(container, BSR):
        return container
    dense = np.asarray(container, np.float32)
    r, c = dense.shape
    r_p, c_p = bucket_edge(r), bucket_edge(c)
    if (r_p, c_p) == (r, c):
        return dense
    out = np.zeros((r_p, c_p), np.float32)
    out[:r, :c] = dense
    return out


# ------------------------------------------------------- sharded container

@dataclasses.dataclass(frozen=True)
class ShardedMeta:
    """Static aux data of a ``ShardedSparseTensor`` pytree node: the global
    shape, the contiguous row bounds (shard ``i`` owns rows
    ``[bounds[i], bounds[i+1])``), and the partition strategy."""

    shape: Tuple[int, int]
    bounds: Tuple[int, ...]
    strategy: str = "nnz"


class ShardedSparseTensor:
    """Row-partitioned sparse operand: one prepared ``SparseTensor`` per
    mesh slot, each with its own schedule (DESIGN.md §10).

    The shards are the pytree *children* (each itself a SparseTensor
    pytree), so the whole sharded operand passes through jit / device_put
    like any nested pytree; the row bounds and global shape are static aux
    data. Shards may carry different schedules — the per-shard selector
    path resolves each shard's layout/block size from its own fingerprint,
    which is the point of sharding a skewed matrix.
    """

    def __init__(self, meta: ShardedMeta, shards) -> None:
        shards = tuple(shards)
        if len(shards) != len(meta.bounds) - 1:
            raise ValueError(f"{len(shards)} shards for "
                             f"{len(meta.bounds) - 1} row ranges")
        self.meta = meta
        self.shards = shards

    # -------------------------------------------------------------- pytree
    def tree_flatten(self):
        return self.shards, self.meta

    @classmethod
    def tree_unflatten(cls, meta: ShardedMeta, shards):
        return cls(meta, shards)

    # ------------------------------------------------------------- basics
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.meta.shape

    @property
    def bounds(self) -> Tuple[int, ...]:
        return self.meta.bounds

    def shard_rows(self) -> Tuple[int, ...]:
        b = self.meta.bounds
        return tuple(b[i + 1] - b[i] for i in range(self.n_shards))

    def schedules(self) -> Tuple[Optional[Schedule], ...]:
        return tuple(s.meta.schedule for s in self.shards)

    def __repr__(self) -> str:
        return (f"ShardedSparseTensor(shape={self.meta.shape}, "
                f"n_shards={self.n_shards}, strategy={self.meta.strategy!r})")

    # ------------------------------------------------------- construction
    @classmethod
    def from_csr(cls, csr: CSR, n_shards: int, schedules=None, *,
                 strategy: str = "nnz", shape_bucket: bool = True,
                 sigma: int = SELL_SIGMA) -> "ShardedSparseTensor":
        """Partition ``csr``'s rows (nnz-balanced by default) and prepare
        each shard under its own Schedule.

        ``schedules`` is one Schedule for every shard, a per-shard
        sequence, or None (the matvec default per shard). The heavy lifting
        (partition caching, selector-resolved per-shard schedules, the
        shard_map launch) lives in ``repro.sparse.plan_sharded``; this
        constructor is the standalone container build.
        """
        from .partition import partition_rows
        part = partition_rows(csr, n_shards, strategy)
        if schedules is None or isinstance(schedules, Schedule):
            schedules = [schedules] * part.n_parts
        if len(schedules) != part.n_parts:
            raise ValueError(f"{len(schedules)} schedules for "
                             f"{part.n_parts} shards")
        shards = [SparseTensor.from_csr(shard, schedule=s, sigma=sigma,
                                        shape_bucket=shape_bucket)
                  for shard, s in zip(part.slice(csr), schedules)]
        meta = ShardedMeta((int(csr.shape[0]), int(csr.shape[1])),
                           part.bounds, strategy)
        return cls(meta, shards)


jax.tree_util.register_pytree_node(
    SparseTensor, SparseTensor.tree_flatten, SparseTensor.tree_unflatten)
jax.tree_util.register_pytree_node(
    ShardedSparseTensor, ShardedSparseTensor.tree_flatten,
    ShardedSparseTensor.tree_unflatten)
