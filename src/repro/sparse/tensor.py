"""``SparseTensor``: the pytree-registered device container of the facade.

One class wraps every prepared layout the kernels consume (DESIGN.md §8):

  ell    globally padded ELL-BSR (``core.csr.ELLBSR``)
  sell   sliced SELL-BSR cell schedule (``core.csr.SELLBSR``)
  bsr    raw blocked rows (spgemm/spadd operands; symbolic phase is host-side)
  dense  the dense-schedule escape hatch (density above the autotune threshold)
  dia    one value per (occupied diagonal, row) (``core.csr.DIA``): banded
         SpMV operands, which ``plan()`` picks from their diagonal fill
         (``autotune.takes_dia``); its offsets are static meta

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
import functools
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autotune import DIA_SCHEDULE, SELL_SIGMA, Schedule
from ..core.csr import (BSR, CSR, DIA, ELLBSR, SELLBSR, ell_block_cap,
                        sell_layout)
from ..kernels.bsr_spmv.kernel import LANES, dia_rows
from .prepared import bucket_edge

HostLayout = Union[ELLBSR, SELLBSR, BSR, DIA, np.ndarray]

# Leaf names per layout, in flatten order (the pytree contract).
LAYOUT_FIELDS: Dict[str, Tuple[str, ...]] = {
    "ell": ("block_indices", "block_cols", "blocks", "valid_counts"),
    "sell": ("cell_block", "cell_col", "cell_row", "row_perm",
             "slice_widths", "blocks"),
    "bsr": ("block_ptrs", "block_cols", "blocks"),
    "dense": ("dense",),
    "dia": ("diags",),
}

# The launch format (DESIGN.md §9): the leaves an ell/sell/dense/dia launch
# reads (all but ``slice_widths``, which only construction reads), and how
# each pads when a member grows to a bucket edge or to the shapes a stack
# shares. Slots and cells past a member's own point at its trailing
# all-zero tile; ``cell_row`` repeats its last row, so it stays
# nondecreasing (the Pallas kernels keep a row's output tile resident until
# the row changes); ``row_perm`` extends as the identity, so padded rows
# land on sliced-away output rows; ``slice_widths`` pads with 1, an empty
# slice's width; ``diags`` with 0, a slot no entry holds. Every other leaf
# pads with 0.
LAUNCH_FIELDS: Dict[str, Tuple[str, ...]] = {
    lay: tuple(f for f in LAYOUT_FIELDS[lay] if f != "slice_widths")
    for lay in ("ell", "sell", "dense", "dia")}
ZERO_TILE, LAST, IDENTITY = "zero tile", "last", "identity"
PAD_FILL: Dict[str, object] = {"block_indices": ZERO_TILE,
                               "cell_block": ZERO_TILE, "cell_row": LAST,
                               "row_perm": IDENTITY, "slice_widths": 1,
                               "diags": 0}


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
    offsets: Tuple[int, ...] = ()   # dia: the diagonals, the kernel unrolls


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
        # Valid tiles of an ELL operand, counted on the host once and kept
        # current by the delta path's inserts (``ell_stream``).
        self._stream_tiles: Optional[int] = None

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
        if schedule.layout == "dia":
            offsets = csr.diagonal_offsets()
            return DIA.from_csr(csr, dia_row_pad(offsets.size, csr.n_rows),
                                offsets)
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
        """Wrap an existing host container (ELLBSR/SELLBSR/BSR/DIA/dense),
        its arrays uploaded to ``device`` (the default device when None).
        A DIA container's rows are re-padded to the kernel's row block
        (``dia_row_pad``) where they are not already."""
        if isinstance(container, DIA):
            container = _dia_whole_row_blocks(container)
        layout, host = container_arrays(container)
        if layout == "dense":
            if host["dense"].ndim != 2:
                raise TypeError(f"cannot wrap {type(container).__name__} "
                                "as a SparseTensor")
            if schedule is None:
                schedule = Schedule("dense", 128, 1.0)
            meta = SparseMeta("dense", host["dense"].shape,
                              schedule.block_size, schedule=schedule)
            container = host["dense"]
        elif layout == "dia":
            meta = SparseMeta("dia", container.shape, LANES,
                              n_block_rows=container.diags.shape[1],
                              schedule=schedule or DIA_SCHEDULE,
                              offsets=container.offsets)
        else:
            bs = container.block_size
            if schedule is None:
                schedule = (Schedule("bsr", bs, 1.0, layout="sell",
                                     slice_height=container.slice_height)
                            if layout == "sell" else Schedule("bsr", bs, 1.0))
            n_br = (container.block_indices.shape[0] if layout == "ell"
                    else container.n_block_rows)
            meta = SparseMeta(
                layout, container.shape, bs, n_block_rows=n_br,
                slice_height=getattr(container, "slice_height", 0),
                sigma=getattr(container, "sigma", 0), schedule=schedule)
        put = (jnp.asarray if device is None
               else functools.partial(jax.device_put, device=device))
        return cls(meta, {k: put(v) for k, v in host.items()},
                   host=container)

    @classmethod
    def wrap(cls, obj, schedule: Optional[Schedule] = None) -> "SparseTensor":
        """Coerce any accepted operand form — CSR, host container, or an
        already-built SparseTensor — into a SparseTensor."""
        if isinstance(obj, SparseTensor):
            return obj
        if isinstance(obj, CSR):
            return cls.from_csr(obj, schedule=schedule)
        return cls.from_layout(obj, schedule=schedule)

    def ell_stream(self) -> Tuple[int, int]:
        """An ELL operand's (valid tiles, grid slots): the tiles one SpMV
        launch streams, and the slots of its padded grid. The count comes
        from the host container, or once from the device's valid-count
        table where none is kept."""
        if self._stream_tiles is None:
            vc = (self._host.valid_counts if self._host is not None
                  else self.arrays["valid_counts"])
            self._stream_tiles = int(np.asarray(vc).sum())
        return (self._stream_tiles,
                int(np.prod(self.arrays["block_indices"].shape)))

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
        m = self.meta
        a = {f: np.asarray(self.arrays[f]) for f in LAYOUT_FIELDS[m.layout]}
        if m.layout == "ell":
            host: HostLayout = ELLBSR(**a, shape=m.shape,
                                      block_size=m.block_size)
        elif m.layout == "sell":
            host = SELLBSR(**a, shape=m.shape, block_size=m.block_size,
                           slice_height=m.slice_height, sigma=m.sigma)
        elif m.layout == "bsr":
            a["block_ptrs"] = a["block_ptrs"].astype(np.int64)
            host = BSR(**a, shape=m.shape, block_size=m.block_size)
        elif m.layout == "dia":
            host = DIA(m.offsets, a["diags"], m.shape,
                       int(np.count_nonzero(a["diags"])))
        else:
            host = a["dense"]
        self._host = host
        return host


# ------------------------------------------------ shapes before a build

def container_shapes(csr: CSR, schedule: Schedule, *,
                     sigma: int = SELL_SIGMA,
                     shape_bucket: bool = False) -> Dict[str, Tuple[int, ...]]:
    """Shape of each device array ``SparseTensor.from_csr(csr, schedule,
    sigma=sigma, shape_bucket=shape_bucket)`` would hold, from the tile
    count of each block row (or the diagonal count) alone: no tile is
    built. Every array is 4 bytes an element (int32 or float32). A DIA
    operand is never bucketed."""
    edge = bucket_edge if shape_bucket else int
    if schedule.backend == "dense":
        return {"dense": (edge(csr.n_rows), edge(csr.n_cols))}
    if schedule.layout == "dia":
        d = csr.diagonal_offsets().size
        return {"diags": (d, dia_row_pad(d, csr.n_rows) // LANES, LANES)}
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
    the index of that container's shared zero tile (None for
    bsr/dense/dia), and the spare tiles of a mutable one. A DIA container
    takes no slack and no bucketing: its rows pad to the kernel's row
    block, its columns not at all."""
    if schedule.layout == "dia" and layout != "bsr":
        if slack > 0:
            raise ValueError("a dia operand takes no mutation slack; "
                             "mutable operands use ell or sell")
        return SparseTensor.build_container(csr, schedule), None, []
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


def dia_row_pad(n_diags: int, n_rows: int) -> int:
    """The rows a DIA container of ``n_diags`` diagonals holds for
    ``n_rows``: a whole number of the kernel's row blocks
    (``kernels.bsr_spmv.kernel.dia_rows`` lane rows of 128)."""
    lane_rows = -(-max(n_rows, 1) // LANES)
    step = dia_rows(n_diags, lane_rows)
    return -(-lane_rows // step) * step * LANES


def _dia_whole_row_blocks(D: DIA) -> DIA:
    """``D`` with its rows padded (or cut, past ``D.shape[0]``) to
    ``dia_row_pad``; ``D`` itself where they already are."""
    lanes = dia_row_pad(len(D.offsets), D.shape[0]) // LANES
    if D.diags.shape[1] == lanes:
        return D
    diags = np.zeros((len(D.offsets), lanes, LANES), np.float32)
    keep = min(lanes, D.diags.shape[1])
    diags[:, :keep] = D.diags[:, :keep]
    return DIA(D.offsets, diags, D.shape, D.nnz)


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
    return pad_container_to_bucket(ell, tiles=tiles[0]), bsr.n_blocks


def container_arrays(container: HostLayout
                     ) -> Tuple[str, Dict[str, np.ndarray]]:
    """A host container's layout and its leaves by name, as float32 tiles
    and int32 tables (no copy where they already are)."""
    if isinstance(container, (ELLBSR, SELLBSR, BSR)):
        layout = ("ell" if isinstance(container, ELLBSR) else
                  "sell" if isinstance(container, SELLBSR) else "bsr")
        return layout, {f: np.asarray(getattr(container, f),
                                      np.float32 if f == "blocks"
                                      else np.int32)
                        for f in LAYOUT_FIELDS[layout]}
    if isinstance(container, DIA):
        return "dia", {"diags": np.asarray(container.diags, np.float32)}
    return "dense", {"dense": np.asarray(container, np.float32)}


def common_shape(shapes: Sequence[Tuple[int, ...]]) -> Tuple[int, ...]:
    """Each dimension's maximum over ``shapes``, rounded up to a bucket
    edge, but for a tile array's trailing (bs, bs)."""
    dims = [max(int(s[d]) for s in shapes) for d in range(len(shapes[0]))]
    edged = 1 if len(dims) == 3 else len(dims)
    return tuple(bucket_edge(n) if d < edged else n
                 for d, n in enumerate(dims))


def bucket_target(shapes: Sequence[Dict[str, Tuple[int, ...]]],
                  fields: Optional[Sequence[str]] = None
                  ) -> Dict[str, Tuple[int, ...]]:
    """The shapes a set of members pads to (``common_shape`` per leaf),
    over ``fields`` or every leaf of the first member."""
    return {k: common_shape([s[k] for s in shapes])
            for k in (fields if fields is not None else shapes[0])}


def pad_to(a, shape: Tuple[int, ...], fill=0):
    """``a`` padded after its own entries to ``shape`` with ``fill``, in the
    array module of ``a`` (numpy stays on the host, a jax array on its
    device); ``a`` itself, uncopied, where it already has ``shape``."""
    if tuple(a.shape) == tuple(shape):
        return a
    xp = jnp if isinstance(a, jax.Array) else np
    return xp.pad(a, [(0, t - n) for n, t in zip(a.shape, shape)],
                  constant_values=fill)


def pad_arrays(arrays: Dict, target: Dict[str, Tuple[int, ...]],
               zero_tile: Optional[int] = None) -> Dict:
    """A member's leaves padded to ``target`` (leaf -> shape) by the launch
    format's rule (``PAD_FILL``), the one padding of the format: bucket
    edges, stacked buckets and mesh shards all pad through here. Numpy or
    jax arrays alike, each in its own module; a leaf already at its target
    is returned uncopied. ``zero_tile`` is the index pad slots point at,
    ``blocks``' last by default."""
    if zero_tile is None and "blocks" in arrays:
        zero_tile = arrays["blocks"].shape[0] - 1
    out = {}
    for k, shape in target.items():
        a, rule = arrays[k], PAD_FILL.get(k, 0)
        if any(n > t for n, t in zip(a.shape, shape)):
            raise ValueError(f"{k} {tuple(a.shape)} exceeds its target "
                             f"{tuple(shape)}")
        if tuple(a.shape) == tuple(shape):
            out[k] = a
        elif rule == IDENTITY:
            xp = jnp if isinstance(a, jax.Array) else np
            out[k] = xp.concatenate([a, xp.arange(a.shape[0], shape[0],
                                                  dtype=a.dtype)])
        else:
            fill = (zero_tile if rule == ZERO_TILE else
                    (a[-1] if a.shape[0] else 0) if rule == LAST else rule)
            out[k] = pad_to(a, shape, fill)
    return out


def pad_container_to_bucket(container: HostLayout,
                            tiles: Optional[np.ndarray] = None
                            ) -> HostLayout:
    """The container with every leaf padded up to its bucket edge
    (``pad_arrays``) and its shape to the padded block grid; numerics
    unchanged. Raw BSR is returned as it is: its exec paths consume
    symbolic products that are bucketed on their own. ``tiles``, given, is
    the bucket-sized tile array that already starts with the container's
    tiles; it becomes the container's, uncopied. A DIA container is
    returned as it is too: its rows are already padded to the kernel's row
    block, and its diagonals fix its program anyway."""
    if isinstance(container, (BSR, DIA)):
        return container
    layout, arrays = container_arrays(container)
    zero = arrays["blocks"].shape[0] - 1 if layout != "dense" else None
    if tiles is not None:
        arrays["blocks"] = tiles
    padded = pad_arrays(arrays, bucket_target(
        [{k: v.shape for k, v in arrays.items()}]), zero)
    if layout == "dense":
        return padded["dense"]
    bs = container.block_size
    n_br = padded["block_indices" if layout == "ell" else "row_perm"].shape[0]
    n_bc = bucket_edge(-(-container.shape[1] // bs))
    return dataclasses.replace(container, **padded,
                               shape=(n_br * bs, n_bc * bs))


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
