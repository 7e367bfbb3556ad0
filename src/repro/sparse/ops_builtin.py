"""Built-in op registrations of the plan/execute facade (DESIGN.md §8-§9).

Registered ops: ``spmv`` / ``spmm`` / ``spgemm`` / ``spadd`` / ``moe_gmm`` /
``flash_attention``. Each planner resolves operands into device pytrees
(``SparseTensor``) once, then hands back a ``Plan`` whose launch is a
module-level jitted executor — module-level so the XLA compile cache is
shared across every plan with the same (schedule, backend, shapes), which
is exactly the schedule-bucket compile-key property the selector batches
around.

The zero-rebuild serving path (DESIGN.md §9) rides on two hooks threaded
through every planner:

* ``store`` — a ``PreparedStore``; a warm hit returns the finished
  device-resident operands (prepared ``SparseTensor``, staged spgemm/spadd
  symbolic products, stacked bucket arrays) and skips host prep entirely.
* ``shape_bucket`` (default on) — prepared containers are padded up to
  power-of-two-ish bucket edges so differing matrices present identical
  leaf shapes + static meta to the jitted executors: one compiled program
  serves the whole shape bucket instead of retracing per matrix.

All four bsr ops register bucket planners: a whole same-schedule bucket is
padded to common (edge-rounded) shapes, stacked along a leading axis, and
run as ONE jitted launch — vmapped on the jnp backend, the per-member
kernel schedule unrolled inside one program on interpret/pallas. The
executors bump ``plan.trace_count`` when a program actually retraces, so
tests can assert a bucket compiles once and launches once.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autotune import SELL_SIGMA, Schedule, select_moe_block_size
from ..core.csr import BSR, CSR, ELLBSR, SELLBSR
from ..kernels.bsr_spadd.kernel import bsr_spadd_pallas
from ..kernels.bsr_spadd.ops import spadd_symbolic
from ..kernels.bsr_spadd.ref import ref_block_union_add
from ..kernels.bsr_spgemm.kernel import (bsr_spgemm_cells_pallas,
                                         bsr_spgemm_pallas)
from ..kernels.bsr_spgemm.ops import spgemm_symbolic, spgemm_symbolic_cells
from ..kernels.bsr_spgemm.ref import ref_cell_gemm, ref_pair_gemm
from ..kernels.bsr_spmv.kernel import (bsr_spmm_pallas, bsr_spmm_sell_pallas,
                                       bsr_spmv_pallas, bsr_spmv_sell_pallas)
from ..kernels.bsr_spmv.ref import (ref_bsr_spmm, ref_bsr_spmm_sell,
                                    ref_bsr_spmv, ref_bsr_spmv_sell)
from ..kernels.flash_attention.kernel import flash_attention_pallas
from ..kernels.flash_attention.ref import ref_attention
from ..kernels.moe_gmm.kernel import moe_gmm_pallas
from ..kernels.moe_gmm.ops import route_and_pad  # noqa: F401  (facade re-export)
from ..kernels.moe_gmm.ref import ref_gmm
from ..obs import default_registry, trace as obs_trace
from .plan import Plan, _bump_trace
from .prepared import PreparedStore, array_key, bucket_edge, content_key
from .registry import register_op
from .resilience import check_fault, dense_ref_cap, register_dense_ref
from .smem import split_cells, split_rows
from .tensor import (ShardedMeta, ShardedSparseTensor, SparseTensor,
                     build_host, container_shapes)

MATVEC_LAYOUTS = ("ell", "sell", "dense")


def _cached(store: Optional[PreparedStore], key, builder):
    """Route a host-prep build through the PreparedStore when one is in
    play (``key=None`` marks an uncacheable operand)."""
    check_fault("prep", str(key) if key is not None else "uncached")
    if store is None:
        return builder()
    return store.get_or_build(key, builder)


# ---------------------------------------------------------------------------
# spmv / spmm — single-operand executor
# ---------------------------------------------------------------------------

def _block_x(x: jax.Array, n_cols: int, n_bc: int, bs: int,
             rhs_tile: int) -> jax.Array:
    """Pad the dense RHS to the block grid: (n_bc, bs) or (n_bc, bs, k_pad)."""
    x = x.astype(jnp.float32)
    if x.ndim == 2:
        k = x.shape[1]
        k_pad = -(-k // rhs_tile) * rhs_tile
        xb = jnp.zeros((n_bc * bs, k_pad), jnp.float32)
        return xb.at[:n_cols, :k].set(x).reshape(n_bc, bs, k_pad)
    xb = jnp.zeros((n_bc * bs,), jnp.float32)
    return xb.at[:n_cols].set(x).reshape(n_bc, bs)


def _ell_launch(idx, cols, blocks, xb, multi: bool, interpret: bool):
    """The ELL SpMV/SpMM kernel, split by block-row range to fit SMEM."""
    kern = bsr_spmm_pallas if multi else bsr_spmv_pallas
    return split_rows(
        lambda i, c: kern(i, c, blocks, xb, interpret=interpret), (idx, cols))


def _sell_launch(cb, cc, cr, blocks, xb, n_br: int, multi: bool,
                 interpret: bool):
    """The SELL SpMV/SpMM kernel, its cell stream split to fit SMEM."""
    kern = bsr_spmm_sell_pallas if multi else bsr_spmv_sell_pallas
    return split_cells(
        lambda b, c, r: kern(b, c, r, blocks, xb, n_br, interpret=interpret),
        (cb, cc, cr), cr, n_br)


def _matvec_tiles(arrays, layout: str, xb: jax.Array, n_br: int,
                  backend: str, multi: bool) -> jax.Array:
    """The ell/sell product on the blocked RHS, as block rows (n_br, bs) or
    (n_br, bs, k): the Pallas kernels on pallas/interpret, the jnp reference
    otherwise. One body for the one-chip launch and for each shard."""
    if layout == "sell":
        cb, cc, cr = (arrays["cell_block"], arrays["cell_col"],
                      arrays["cell_row"])
        blocks = arrays["blocks"]
        if backend == "jnp":
            y = (ref_bsr_spmm_sell if multi else ref_bsr_spmv_sell)(
                cb, cc, cr, blocks, xb, n_br)
        else:
            y = _sell_launch(cb, cc, cr, blocks, xb, n_br, multi,
                             interpret=(backend == "interpret"))
        perm = arrays["row_perm"]
        return jnp.zeros_like(y).at[perm].set(y)
    idx, cols = arrays["block_indices"], arrays["block_cols"]
    blocks = arrays["blocks"]
    if backend == "jnp":
        return (ref_bsr_spmm if multi else ref_bsr_spmv)(idx, cols, blocks, xb)
    return _ell_launch(idx, cols, blocks, xb, multi,
                       interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "rhs_tile"))
def _exec_matvec(st: SparseTensor, x: jax.Array, backend: str,
                 rhs_tile: int) -> jax.Array:
    """y = A @ x (or Y = A @ X for 2-D x) for an ell/sell/dense operand."""
    _bump_trace("matvec")
    meta = st.meta
    if meta.layout == "dense":
        return st.arrays["dense"] @ x.astype(jnp.float32)
    if meta.layout not in ("ell", "sell"):
        raise ValueError(f"spmv/spmm cannot execute layout {meta.layout!r}")
    bs = meta.block_size
    n_bc = -(-meta.shape[1] // bs)
    multi = x.ndim == 2
    xb = _block_x(x, meta.shape[1], n_bc, bs, rhs_tile)
    y = _matvec_tiles(st.arrays, meta.layout, xb, meta.n_block_rows, backend,
                      multi)
    if multi:
        k = x.shape[1]
        return y.reshape(y.shape[0] * y.shape[1], -1)[: meta.shape[0], :k]
    return y.reshape(-1)[: meta.shape[0]]


def _plan_matvec(operands, schedule: Optional[Schedule], backend: str, *,
                 op: str, rhs_tile: Optional[int] = None,
                 block_size: int = 128, layout: str = "ell",
                 slice_height: int = 8, sigma: int = SELL_SIGMA,
                 max_blocks: Optional[int] = None,
                 store: Optional[PreparedStore] = None,
                 shape_bucket: bool = True,
                 operand_key: Optional[str] = None, **_) -> Plan:
    (a,) = operands
    if isinstance(a, CSR):
        lay = None if layout == "ell" else layout
        sched = (schedule if schedule is not None
                 else SparseTensor.default_schedule(block_size, lay,
                                                   slice_height))
        # operand_key: the selector already hashed the matrix bytes for its
        # fingerprint memo — reuse it instead of a second O(nnz) sha1 pass
        key = None if store is None else (
            "matvec", operand_key or content_key(a), sched, lay, sigma,
            max_blocks, bool(shape_bucket))
        st = _cached(store, key, lambda: SparseTensor.from_csr(
            a, schedule=sched, layout=lay, slice_height=slice_height,
            sigma=sigma, max_blocks=max_blocks, shape_bucket=shape_bucket,
            slack=getattr(a, "mutation_slack", 0)))
    else:
        st = SparseTensor.wrap(a, schedule)
    if st.layout not in MATVEC_LAYOUTS:
        raise ValueError(f"{op} needs an ell/sell/dense operand, got a "
                         f"{st.layout!r} SparseTensor")
    sched = schedule if schedule is not None else st.meta.schedule
    tile = rhs_tile if rhs_tile is not None else (128 if backend == "pallas"
                                                  else 8)
    true_rows, true_cols = st.true_shape
    pad_rows, pad_cols = st.meta.shape

    def run(x):
        # Bucketed operands: pad the RHS to the bucketed column count
        # OUTSIDE the traced program, so every matrix in a shape bucket
        # presents an identical input signature to the jit cache. The pad
        # stays on device (eager .at[].set) — no host round-trip for
        # device-resident serving inputs.
        if getattr(x, "ndim", None) is None:
            x = np.asarray(x, np.float32)
        if x.shape[0] != pad_cols:
            if x.shape[0] != true_cols:
                raise ValueError(f"{op}: runtime input leading dim "
                                 f"{x.shape[0]} != operand cols {true_cols}")
            x = jnp.zeros((pad_cols,) + tuple(x.shape[1:]), jnp.float32) \
                .at[:true_cols].set(jnp.asarray(x, jnp.float32))
        y = _exec_matvec(st, jnp.asarray(x), backend=backend, rhs_tile=tile)
        return y[:true_rows] if true_rows != pad_rows else y

    return Plan(op=op, schedule=sched, backend=backend, _run=run,
                operands=(st,),
                kernel_rhs=None if st.layout == "dense" else "given")


# ---------------------------------------------------------------------------
# spmv / spmm — stacked bucket launch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("layout", "backend"))
def _exec_matvec_stacked(arrays, xs: jax.Array, layout: str,
                         backend: str) -> jax.Array:
    """One launch for a whole same-schedule bucket: member axis leading.

    ``xs`` is (B, n_bc*bs) or (B, n_bc*bs, k); returns (B, n_br*bs[, k]).
    One jitted program, one dispatch, every member in flight: the jnp
    backend vmaps the fused formulation over the member axis; the
    interpret/pallas backends run the per-member kernel schedule unrolled
    inside the same program (padding made the member shapes identical).
    """
    _bump_trace("matvec_stacked")
    multi = xs.ndim == 3
    if layout == "dense":
        dense = arrays["dense"]
        eq = "bij,bjk->bik" if multi else "bij,bj->bi"
        return jnp.einsum(eq, dense, xs.astype(jnp.float32))
    bs = arrays["blocks"].shape[-1]
    n_bc = xs.shape[1] // bs
    xb = (xs.reshape(xs.shape[0], n_bc, bs, xs.shape[-1]) if multi
          else xs.reshape(xs.shape[0], n_bc, bs))
    interpret = backend == "interpret"
    if backend != "jnp":
        # the kernels index one flat tile stack and one flat RHS: member b's
        # tables are offset into them, where slicing member b out of the
        # stack would copy its tiles for every launch
        nb = arrays["blocks"].shape[1]
        flat_blocks = arrays["blocks"].reshape((-1, bs, bs))
        flat_x = xb.reshape((-1,) + xb.shape[2:])
    if layout == "ell":
        if backend == "jnp":
            def one(idx, cols, blocks, x1):
                eq = "rmab,rmbk->rak" if multi else "rmab,rmb->ra"
                return jnp.einsum(eq, blocks[idx], x1[cols])
            y = jax.vmap(one)(arrays["block_indices"], arrays["block_cols"],
                              arrays["blocks"], xb)
        else:
            y = jnp.stack([
                _ell_launch(arrays["block_indices"][b] + b * nb,
                            arrays["block_cols"][b] + b * n_bc, flat_blocks,
                            flat_x, multi, interpret)
                for b in range(xb.shape[0])])
    else:  # sell
        n_br = arrays["row_perm"].shape[1]
        if backend == "jnp":
            def one(cb, cc, cr, blocks, perm, x1):
                eq = "tab,tbk->tak" if multi else "tab,tb->ta"
                prods = jnp.einsum(eq, blocks[cb], x1[cc])
                ys = jax.ops.segment_sum(prods, cr, num_segments=n_br)
                return jnp.zeros_like(ys).at[perm].set(ys)
            y = jax.vmap(one)(arrays["cell_block"], arrays["cell_col"],
                              arrays["cell_row"], arrays["blocks"],
                              arrays["row_perm"], xb)
        else:
            outs = []
            for b in range(xb.shape[0]):
                ys = _sell_launch(arrays["cell_block"][b] + b * nb,
                                  arrays["cell_col"][b] + b * n_bc,
                                  arrays["cell_row"][b], flat_blocks, flat_x,
                                  n_br, multi, interpret)
                outs.append(jnp.zeros_like(ys).at[arrays["row_perm"][b]]
                            .set(ys))
            y = jnp.stack(outs)
    if multi:
        return y.reshape(y.shape[0], y.shape[1] * y.shape[2], y.shape[3])
    return y.reshape(y.shape[0], -1)


def _stack_pad(mats: Sequence[np.ndarray], fill,
               edge_dims: Tuple[int, ...] = ()) -> np.ndarray:
    """Stack host arrays along a new axis 0, padding each to the common max
    shape with ``fill`` (scalar or per-member list). Dims listed in
    ``edge_dims`` are additionally rounded up to bucket edges so repeat
    buckets with nearby member sizes share one stacked jit key."""
    shape = [max(m.shape[d] for m in mats) for d in range(mats[0].ndim)]
    for d in edge_dims:
        shape[d] = bucket_edge(shape[d])
    fills = fill if isinstance(fill, (list, tuple)) else [fill] * len(mats)
    out = np.stack([np.full(tuple(shape), f, dtype=mats[0].dtype)
                    for f in fills])
    for i, m in enumerate(mats):
        out[(i,) + tuple(slice(0, s) for s in m.shape)] = m
    return out


def _bucket_hosts(members: List, schedule: Schedule, sigma: int) -> List:
    """Per-member host containers WITHOUT device staging — the stacked
    launch uploads only the padded stacks, so staging each member's own
    arrays too would double the host->device traffic."""
    hosts = []
    for m in members:
        if isinstance(m, SparseTensor):
            hosts.append(m.to_host())
        elif isinstance(m, CSR):
            hosts.append(SparseTensor.build_container(m, schedule,
                                                      sigma=sigma))
        else:
            hosts.append(m)   # already an ELLBSR/SELLBSR/dense container
    return hosts


def _member_tensors(members: List, schedule: Schedule, sigma: int,
                    shape_bucket: bool, store, member_keys):
    """Device-resident prepared ``SparseTensor`` per member, through the
    SAME store key the single-request planner uses — or None when the
    bucket cannot take the resident-stacking path (no store, unkeyed or
    non-CSR members).

    Sharing the single-request key is the point: a tenant warmed by either
    path (a solo ``plan()`` or any earlier bucket) is warm for both, and
    the serving engine's ``resident(ck)`` slot bit predicts exactly this
    hit."""
    if store is None or member_keys is None:
        return None
    keys = list(member_keys)
    if len(keys) != len(members) or not all(keys):
        return None
    if not all(isinstance(m, CSR) for m in members):
        return None
    sts = []
    for m, ck in zip(members, keys):
        skey = ("matvec", ck, schedule, None, sigma, None,
                bool(shape_bucket))
        sts.append(_cached(store, skey, lambda m=m: SparseTensor.from_csr(
            m, schedule=schedule, sigma=sigma,
            shape_bucket=bool(shape_bucket),
            slack=getattr(m, "mutation_slack", 0))))
    if len({st.layout for st in sts}) != 1:
        return None
    return sts


def _stack_resident(sts: List, shape_bucket: bool):
    """Stacked bucket arrays built ON DEVICE from per-member prepared
    containers (``jnp.pad`` to common edge dims + ``jnp.stack``), or None
    for layouts without a device formulation.

    This is what makes continuous batching (DESIGN.md §13) pay: under Zipf
    traffic the exact member composition of a bucket rarely repeats, so the
    whole-composition cache alone misses constantly — but a composition of
    *warm members* only costs a device-side stack here (~memcpy), never the
    host container rebuild + re-upload of the cold path. Pad fills mirror
    ``_build_matvec_bucket`` exactly: extra ell/sell cells point at the
    member's own all-zeros block, ``cell_row`` extends the last sorted row
    (edge mode), ``row_perm`` extends with identity."""
    layout = sts[0].layout
    if layout not in ("ell", "sell", "dense"):
        return None
    shapes = [st.true_shape for st in sts]
    if layout == "dense":
        ds = [st.arrays["dense"] for st in sts]
        tgt = [max(d.shape[i] for d in ds) for i in (0, 1)]
        if shape_bucket:
            tgt = [bucket_edge(t) for t in tgt]
        arrays = {"dense": jnp.stack([
            jnp.pad(d, ((0, tgt[0] - d.shape[0]), (0, tgt[1] - d.shape[1])))
            .astype(jnp.float32) for d in ds])}
        return {"arrays": arrays, "shapes": shapes, "layout": layout,
                "bs": sts[0].block_size, "width": int(tgt[1])}
    bs = sts[0].block_size
    A = [st.arrays for st in sts]
    nb = max(a["blocks"].shape[0] for a in A)
    n_bc = -(-max(s[1] for s in shapes) // bs)
    if shape_bucket:
        nb, n_bc = bucket_edge(nb), bucket_edge(n_bc)
    blocks = jnp.stack([
        jnp.pad(a["blocks"].astype(jnp.float32),
                ((0, nb - a["blocks"].shape[0]), (0, 0), (0, 0)))
        for a in A])
    if layout == "ell":
        n_br = max(a["block_indices"].shape[0] for a in A)
        width = max(a["block_indices"].shape[1] for a in A)
        if shape_bucket:
            n_br, width = bucket_edge(n_br), bucket_edge(width)
        idx, cols = [], []
        for a in A:
            bi, bc = a["block_indices"], a["block_cols"]
            pad2 = ((0, n_br - bi.shape[0]), (0, width - bi.shape[1]))
            # pad slots point at this member's own all-zeros block
            idx.append(jnp.pad(bi, pad2,
                               constant_values=a["blocks"].shape[0] - 1))
            cols.append(jnp.pad(bc, pad2))
        arrays = {"block_indices": jnp.stack(idx),
                  "block_cols": jnp.stack(cols), "blocks": blocks}
    else:  # sell
        n_cells = max(a["cell_block"].shape[0] for a in A)
        n_br = max(a["row_perm"].shape[0] for a in A)
        if shape_bucket:
            n_cells, n_br = bucket_edge(n_cells), bucket_edge(n_br)
        cb, cc, cr, rp = [], [], [], []
        for a in A:
            pad1 = ((0, n_cells - a["cell_block"].shape[0]),)
            cb.append(jnp.pad(a["cell_block"], pad1,
                              constant_values=a["blocks"].shape[0] - 1))
            cc.append(jnp.pad(a["cell_col"], pad1))
            # pad cells extend the member's LAST sorted row (see the host
            # builder: cell_row must stay nondecreasing for the Pallas
            # output-residency contract)
            cr.append(jnp.pad(a["cell_row"], pad1, mode="edge")
                      if a["cell_row"].shape[0] else
                      jnp.zeros((n_cells,), a["cell_row"].dtype))
            perm = a["row_perm"]
            rp.append(jnp.concatenate([
                perm, jnp.arange(perm.shape[0], n_br, dtype=perm.dtype)]))
        arrays = {"cell_block": jnp.stack(cb), "cell_col": jnp.stack(cc),
                  "cell_row": jnp.stack(cr), "row_perm": jnp.stack(rp),
                  "blocks": blocks}
    return {"arrays": arrays, "shapes": shapes, "layout": layout,
            "bs": bs, "width": int(n_bc * bs)}


def _members_key(kind: str, members: List, schedule: Schedule,
                 extra: Tuple = (),
                 member_keys: Optional[Sequence[str]] = None
                 ) -> Optional[Tuple]:
    """Store key for a bucket of CSR members (None = uncacheable member).

    ``member_keys`` lets a caller that already hashed its matrices (the
    SelectorService memoizes ``content_key`` per request) skip the second
    O(nnz) hashing pass; one key per member operand, in member order.
    """
    keys = []
    ki = iter(member_keys) if member_keys is not None else None
    for m in members:
        parts = m if isinstance(m, (tuple, list)) else (m,)
        for p in parts:
            if ki is not None:
                k = next(ki, None)
                if k is None:
                    return None
                keys.append(k)
            elif isinstance(p, CSR):
                keys.append(content_key(p))
            else:
                return None
    return (kind, schedule) + extra + (tuple(keys),)


def _build_matvec_bucket(members: List, schedule: Schedule, sigma: int,
                         shape_bucket: bool, store=None, member_keys=None):
    sts = _member_tensors(members, schedule, sigma, shape_bucket, store,
                          member_keys)
    if sts is not None:
        built = _stack_resident(sts, shape_bucket)
        if built is not None:
            return built
    hosts = _bucket_hosts(members, schedule, sigma)
    kinds = {("dense" if isinstance(h, np.ndarray) else
              "sell" if isinstance(h, SELLBSR) else "ell") for h in hosts}
    if len(kinds) != 1:
        raise ValueError(f"bucket mixes layouts {sorted(kinds)}; a bucket "
                         "shares one Schedule by construction")
    layout = kinds.pop()
    # True (unbucketed) output shapes: a SparseTensor member may itself be
    # shape-bucketed, in which case its host container carries the padded
    # shape and ``true_shape`` the logical one.
    shapes = [m.true_shape if isinstance(m, SparseTensor) else h.shape
              for m, h in zip(members, hosts)]
    ed = (0,) if shape_bucket else ()
    ed2 = (0, 1) if shape_bucket else ()
    if layout == "dense":
        arrays = {"dense": jnp.asarray(_stack_pad(
            [np.asarray(h, np.float32) for h in hosts], 0.0,
            edge_dims=ed2))}
        bs = schedule.block_size
        width = int(arrays["dense"].shape[2])
    else:
        bs = hosts[0].block_size
        # Per-member pad slots must keep pointing at that member's own
        # all-zeros block (its index differs member to member).
        zero_idx = [h.blocks.shape[0] - 1 for h in hosts]
        if layout == "ell":
            arrays = {
                "block_indices": jnp.asarray(_stack_pad(
                    [h.block_indices for h in hosts], zero_idx,
                    edge_dims=ed2)),
                "block_cols": jnp.asarray(_stack_pad(
                    [h.block_cols for h in hosts], 0, edge_dims=ed2)),
                "blocks": jnp.asarray(_stack_pad(
                    [h.blocks.astype(np.float32) for h in hosts], 0.0,
                    edge_dims=ed)),
            }
        else:
            n_br = max(h.n_block_rows for h in hosts)
            if shape_bucket:
                n_br = bucket_edge(n_br)
            arrays = {
                "cell_block": jnp.asarray(_stack_pad(
                    [h.cell_block for h in hosts], zero_idx, edge_dims=ed)),
                "cell_col": jnp.asarray(_stack_pad(
                    [h.cell_col for h in hosts], 0, edge_dims=ed)),
                # pad cells extend the member's LAST sorted row (+0 from the
                # zero block), keeping cell_row nondecreasing — the Pallas
                # output-residency contract; padding with row 0 would
                # re-initialize (and zero) row 0's accumulated tile.
                "cell_row": jnp.asarray(_stack_pad(
                    [h.cell_row for h in hosts],
                    [int(h.cell_row[-1]) if h.cell_row.size else 0
                     for h in hosts], edge_dims=ed)),
                # identity-extend each member's permutation so padded sorted
                # rows scatter onto padded (sliced-away) output rows
                "row_perm": jnp.asarray(np.stack([
                    np.concatenate([h.row_perm,
                                    np.arange(h.n_block_rows, n_br,
                                              dtype=np.int32)])
                    for h in hosts])),
                "blocks": jnp.asarray(_stack_pad(
                    [h.blocks.astype(np.float32) for h in hosts], 0.0,
                    edge_dims=ed)),
            }
        n_bc = -(-max(h.shape[1] for h in hosts) // bs)
        if shape_bucket:
            n_bc = bucket_edge(n_bc)
        width = n_bc * bs
    return {"arrays": arrays, "shapes": shapes, "layout": layout,
            "bs": bs, "width": width}


def _plan_matvec_rhs_stacked(members: List, schedule: Schedule,
                             backend: str, *, op: str, rhs_tile,
                             sigma: int, store, shape_bucket: bool,
                             member_keys) -> Plan:
    """Same-matrix bucket as ONE multi-RHS launch (DESIGN.md §13).

    When every member of a bucket is the same matrix (equal content keys —
    the hot-tenant case continuous batching exists for: Zipf traffic piles
    concurrent requests of one matrix), stacking member containers is pure
    waste — B copies of identical operands. The batch is just the matrix's
    single prepared container (the same cached ``SparseTensor`` the
    per-request path uses, so either path warms the other) applied to the
    members' RHS vectors stacked as columns: SpMV x B == one SpMM. The k
    dimension is padded to bucket edges so every occupancy in an edge
    bucket shares one jit key."""
    inner = _plan_matvec((members[0],), schedule, backend, op=op,
                         rhs_tile=rhs_tile, sigma=sigma, store=store,
                         shape_bucket=shape_bucket,
                         operand_key=member_keys[0])
    n = len(members)

    def run(xs):
        if len(xs) != n:
            raise ValueError(f"bucket has {n} members, got {len(xs)} "
                             "runtime inputs")
        xs = [np.asarray(x, np.float32) for x in xs]
        ndims = {x.ndim for x in xs}
        if len(ndims) != 1:
            raise ValueError("stacked launch needs homogeneous runtime "
                             "inputs (got mixed vector/multi-RHS)")
        if n == 1:
            return [inner._run(xs[0])]
        with obs_trace.span("drain_stack", n_members=n):
            if ndims == {1}:
                ks, X = None, np.stack(xs, axis=1)
            else:
                ks = [x.shape[1] for x in xs]
                X = np.concatenate(xs, axis=1)
            k = X.shape[1]
            # power-of-two rounding (not bucket_edge): the RHS width is the
            # jit compile key of the multi-RHS program, and {1,2,4,8,...}
            # is half the keys of the 1.5x edge ladder — occupancy jitter
            # under live traffic then never compiles mid-replay once the
            # pow2 rungs are warm
            k_pad = (1 << (k - 1).bit_length()) if shape_bucket else k
            if k_pad != k:
                X = np.concatenate(
                    [X, np.zeros((X.shape[0], k_pad - k), np.float32)],
                    axis=1)
            X = jnp.asarray(X)
        y = inner._run(X)                       # (true_rows, k_pad)
        if ks is None:
            return [y[:, i] for i in range(n)]
        outs, off = [], 0
        for ki in ks:
            outs.append(y[:, off:off + ki])
            off += ki
        return outs

    return Plan(op=op, schedule=schedule, backend=backend, _run=run,
                operands=inner.operands, n_members=n,
                kernel_rhs=inner.kernel_rhs and "stacked")


def _pad_member_axis(built: Dict, b_pad: int) -> Dict:
    """Pad the stacked member axis up to ``b_pad`` with zero members
    (batch-size bucketing). A zero member is all-zeros arrays: its indices
    are in range (0), its RHS is zeroed by the launch wrapper, so its
    output is exactly zero and sliced away — while every occupancy in
    (prev_edge, b_pad] shares ONE jit compile key instead of one per
    member count. Continuous batching drains at whatever occupancy the
    traffic produced; without this, each distinct bucket size pays its own
    XLA compile."""
    arrays = {
        k: (jnp.concatenate(
            [v, jnp.zeros((b_pad - v.shape[0],) + tuple(v.shape[1:]),
                          v.dtype)], axis=0)
            if int(v.shape[0]) < b_pad else v)
        for k, v in built["arrays"].items()}
    return {**built, "arrays": arrays}


def _plan_matvec_bucket(members: List, schedule: Schedule, backend: str, *,
                        op: str = "spmv", rhs_tile: Optional[int] = None,
                        sigma: int = SELL_SIGMA,
                        store: Optional[PreparedStore] = None,
                        shape_bucket: bool = True,
                        member_keys=None, **_) -> Plan:
    if (store is not None and member_keys is not None
            and all(member_keys) and len(set(member_keys)) == 1
            and all(isinstance(m, CSR) for m in members)):
        # content-pure bucket (affinity slot fill makes these the common
        # case under Zipf traffic): one prepared container, RHS columns
        # stacked — no member stacking, no composition cache entry
        return _plan_matvec_rhs_stacked(
            members, schedule, backend, op=op, rhs_tile=rhs_tile,
            sigma=sigma, store=store, shape_bucket=bool(shape_bucket),
            member_keys=member_keys)
    key = None if store is None else _members_key(
        "matvec_bucket", members, schedule,
        extra=(op, sigma, bool(shape_bucket)), member_keys=member_keys)
    b_pad = bucket_edge(len(members)) if shape_bucket else len(members)
    built = _cached(store, key, lambda: _pad_member_axis(
        _build_matvec_bucket(members, schedule, sigma, shape_bucket,
                             store=store, member_keys=member_keys), b_pad))
    arrays, shapes = built["arrays"], built["shapes"]
    layout, width = built["layout"], built["width"]
    tile = rhs_tile if rhs_tile is not None else (128 if backend == "pallas"
                                                  else 8)

    def run(xs):
        if len(xs) != len(shapes):
            raise ValueError(f"bucket has {len(shapes)} members, got "
                             f"{len(xs)} runtime inputs")
        xs = [np.asarray(x, np.float32) for x in xs]
        sigs = {(x.ndim,) + x.shape[1:] for x in xs}
        if len(sigs) != 1:
            raise ValueError(
                "stacked launch needs homogeneous runtime inputs, got "
                f"{sorted(sigs)}; split the bucket by RHS signature "
                "(SelectorService does this automatically)")
        multi = xs[0].ndim == 2
        with obs_trace.span("drain_stack", n_members=len(xs)):
            if multi:
                k = xs[0].shape[1]
                k_pad = -(-k // tile) * tile
                xpad = np.zeros((b_pad, width, k_pad), np.float32)
                for i, x in enumerate(xs):
                    xpad[i, : x.shape[0], :k] = x
            else:
                xpad = np.zeros((b_pad, width), np.float32)
                for i, x in enumerate(xs):
                    xpad[i, : x.shape[0]] = x
            xpad = jnp.asarray(xpad)
        ys = _exec_matvec_stacked(arrays, xpad, layout=layout,
                                  backend=backend)
        if multi:
            return [ys[i, : shapes[i][0], : xs[i].shape[1]]
                    for i in range(len(xs))]
        return [ys[i, : shapes[i][0]] for i in range(len(xs))]

    return Plan(op=op, schedule=schedule, backend=backend, _run=run,
                n_members=len(shapes),
                kernel_rhs=None if layout == "dense" else "given")


# ---------------------------------------------------------------------------
# spmv / spmm — sharded distributed launch (DESIGN.md §10)
# ---------------------------------------------------------------------------

_SHARDED_EXECS: dict = {}

# the arrays a launch reads, per layout (valid_counts and slice_widths are
# construction-side bookkeeping the kernels never touch)
_LAUNCH_FIELDS = {"ell": ("block_indices", "block_cols", "blocks"),
                  "sell": ("cell_block", "cell_col", "cell_row", "row_perm",
                           "blocks"),
                  "dense": ("dense",)}


def _sharded_matvec_exec(mesh, layout: str, backend: str, multi: bool,
                         rows: int):
    """One jitted shard_map program per (mesh, layout, backend, arity, rows).

    The stacked shard arrays are sharded along the leading member axis (one
    shard per mesh slot) and the blocked RHS is replicated; each slot runs
    the one-chip body (``_matvec_tiles``: the Pallas kernel with its SMEM
    splits, or the jnp reference) on its own shard and returns its first
    ``rows`` output rows. A *row* decomposition needs only a concat of
    per-shard results — no psum — so the program body has zero
    cross-device collectives (the column-partitioned variant would psum
    partial products instead; DESIGN.md §10 records the tradeoff).
    """
    key = (mesh, layout, backend, multi, rows)
    fn = _SHARDED_EXECS.get(key)
    if fn is not None:
        return fn
    from jax import shard_map
    from ..launch.mesh import SHARD_AXIS
    P = jax.sharding.PartitionSpec

    def local(arrays, xb):
        # local leading dim is 1: this slot's single shard
        a = {k: v[0] for k, v in arrays.items()}
        if layout == "dense":
            y = a["dense"] @ xb
        else:
            bs = a["blocks"].shape[-1]
            xblk = xb.reshape((xb.shape[0] // bs, bs) + xb.shape[1:])
            n_br = a["row_perm"].shape[0] if layout == "sell" else 0
            y = _matvec_tiles(a, layout, xblk, n_br, backend, multi)
            y = y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
        return y[None, :rows]

    # check_vma off: a pallas_call's output declares no mesh-axis variance
    mapped = shard_map(local, mesh=mesh, in_specs=(P(SHARD_AXIS), P()),
                       out_specs=P(SHARD_AXIS), check_vma=False)

    def run(arrays, xb):
        _bump_trace("matvec_sharded")
        return mapped(arrays, xb)

    fn = jax.jit(run)
    _SHARDED_EXECS[key] = fn
    return fn


@functools.partial(jax.jit, static_argnames=("width", "k_pad"))
def _pad_rhs(x: jax.Array, width: int, k_pad: int) -> jax.Array:
    """The RHS zero-padded to the shards' column width (and RHS tile)."""
    x = x.astype(jnp.float32)
    if x.ndim == 2:
        return jnp.zeros((width, k_pad), jnp.float32) \
            .at[: x.shape[0], : x.shape[1]].set(x)
    return jnp.zeros((width,), jnp.float32).at[: x.shape[0]].set(x)


@functools.partial(jax.jit, static_argnames=("rows", "k"))
def _concat_rows(ys: jax.Array, rows: Tuple[int, ...],
                 k: Optional[int]) -> jax.Array:
    """Shard i's first ``rows[i]`` output rows, in shard order."""
    y = jnp.concatenate([ys[i, :r] for i, r in enumerate(rows)], axis=0)
    return y if k is None else y[:, :k]


def _home(x) -> jax.Device:
    """Where a launch on ``x`` returns its output: x's device when x lives
    on one, else the default device."""
    if isinstance(x, jax.Array) and len(x.devices()) == 1:
        return next(iter(x.devices()))
    return jax.devices()[0]


def _count_exchange(nbytes: int) -> None:
    reg = default_registry()
    reg.inc("shard.launches")
    reg.inc("shard.exchange_bytes", float(nbytes))


def _pad_to(arr: np.ndarray, shape: Tuple[int, ...], fill) -> np.ndarray:
    """``arr`` zero-copy when it already has ``shape``, else padded to it
    with ``fill`` after its own entries."""
    if arr.shape == tuple(shape):
        return arr
    if any(a > b for a, b in zip(arr.shape, shape)):
        raise ValueError(f"shard array {arr.shape} exceeds the common shape "
                         f"{tuple(shape)}")
    out = np.full(shape, fill, arr.dtype)
    out[tuple(slice(0, n) for n in arr.shape)] = arr
    return out


def _launch_arrays(host, layout: str, target: Dict[str, Tuple[int, ...]]
                   ) -> Dict[str, np.ndarray]:
    """A shard's host container as the arrays a launch reads, padded to the
    common shapes with ``_build_matvec_bucket``'s fills: pad slots point at
    the shard's own last (all-zeros) tile, pad cells extend its last sorted
    row, ``row_perm`` extends with identity."""
    if layout == "dense":
        return {"dense": _pad_to(np.asarray(host, np.float32),
                                 target["dense"], 0.0)}
    zero = host.blocks.shape[0] - 1
    out = {"blocks": _pad_to(host.blocks.astype(np.float32, copy=False),
                             target["blocks"], 0.0)}
    if layout == "ell":
        out["block_indices"] = _pad_to(host.block_indices.astype(np.int32),
                                       target["block_indices"], zero)
        out["block_cols"] = _pad_to(host.block_cols.astype(np.int32),
                                    target["block_cols"], 0)
        return out
    cr = host.cell_row.astype(np.int32)
    out["cell_block"] = _pad_to(host.cell_block.astype(np.int32),
                                target["cell_block"], zero)
    out["cell_col"] = _pad_to(host.cell_col.astype(np.int32),
                              target["cell_col"], 0)
    out["cell_row"] = _pad_to(cr, target["cell_row"],
                              int(cr[-1]) if cr.size else 0)
    perm = host.row_perm.astype(np.int32)
    out["row_perm"] = np.concatenate([perm, np.arange(
        perm.size, target["row_perm"][0], dtype=np.int32)])
    return out


def _build_on_mesh(members: List, schedule: Schedule, sigma: int,
                   shape_bucket: bool, mesh) -> Dict:
    """The shards' launch arrays, stacked along a leading axis sharded over
    the mesh, built one shard at a time: each shard's container is built
    on the host, padded to the shapes every shard shares, uploaded to its
    own device and dropped before the next starts, so the host holds one
    shard's build at a time. The shared shapes come first, from each
    shard's tile counts (``container_shapes``), without building a tile."""
    from ..launch.mesh import SHARD_AXIS
    layout = ("dense" if schedule.backend == "dense" else
              "sell" if schedule.layout == "sell" else "ell")
    shapes = [container_shapes(m, schedule, sigma=sigma,
                               shape_bucket=shape_bucket)
              if isinstance(m, CSR) else
              {k: tuple(v.shape) for k, v in m.arrays.items()}
              for m in members]
    target = {k: tuple(max(s[k][d] for s in shapes)
                       for d in range(len(shapes[0][k])))
              for k in _LAUNCH_FIELDS[layout]}
    if layout == "dense":
        width = target["dense"][1]
    else:
        bs = schedule.block_size
        n_bc = max(-(-m.shape[1] // bs) for m in members)
        width = (bucket_edge(n_bc) if shape_bucket else n_bc) * bs
    devices = list(mesh.devices.flat)
    pieces: Dict[str, List] = {k: [] for k in target}
    for i, (m, dev) in enumerate(zip(members, devices)):
        with obs_trace.span("shard_build", shard=i, n_shards=len(members)):
            host = (build_host(m, schedule, sigma=sigma,
                               shape_bucket=shape_bucket)[0]
                    if isinstance(m, CSR) else m.to_host())
            placed = {k: jax.device_put(v[None], dev) for k, v in
                      _launch_arrays(host, layout, target).items()}
            jax.block_until_ready(placed)
            del host
        for k, v in placed.items():
            pieces[k].append(v)
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(SHARD_AXIS))
    arrays = {k: jax.make_array_from_single_device_arrays(
        (len(members),) + target[k], sharding, pieces[k]) for k in target}
    return {"arrays": arrays, "layout": layout, "width": int(width)}


def _plan_matvec_sharded(operands, schedules, backend: str, *, op: str,
                         part=None, shard_csrs: Optional[List] = None,
                         mesh=None, rhs_tile: Optional[int] = None,
                         sigma: int = SELL_SIGMA,
                         store: Optional[PreparedStore] = None,
                         shape_bucket: bool = True,
                         operand_key: Optional[str] = None, **_) -> Plan:
    """Distributed matvec plan: one prepared shard per mesh slot.

    Homogeneous per-shard schedules execute as ONE shard_map program over
    the ``shards`` mesh axis, on every backend: each slot runs the one-chip
    body (on pallas, the same ``bsr_spmv_pallas`` launch and SMEM splits),
    the padded RHS is replicated from x's device, and the shards' rows come
    back to x's device as one array. Heterogeneous schedules — the
    per-shard selector picking different layouts/block sizes for skewed
    shards — or too few devices take round-robin per-shard launches: each
    shard's operands are committed to its own device, the per-shard jitted
    dispatches overlap asynchronously, and the outputs are concatenated on
    x's device. Either way shards are built one at a time and keep no host
    copy, and the answer never passes through the host. Both the partition
    and the prepared shards ride the PreparedStore, so warm sharded plans
    skip partitioning AND prep (the zero-rebuild property, extended to the
    distributed path). Every launch ticks ``shard.launches`` and adds the
    bytes it moves between chips to ``shard.exchange_bytes``.
    """
    (a,) = operands
    sst: Optional[ShardedSparseTensor] = a if isinstance(
        a, ShardedSparseTensor) else None
    if sst is not None:
        bounds = sst.meta.bounds
        schedules = tuple(s if s is not None else st.meta.schedule
                          for s, st in zip(schedules, sst.shards))
        for st in sst.shards:
            if st.layout not in MATVEC_LAYOUTS:
                raise ValueError(f"{op} needs ell/sell/dense shards, got a "
                                 f"{st.layout!r} SparseTensor")
        shape = sst.meta.shape
        strategy = sst.meta.strategy
    else:
        if part is None:
            raise ValueError("sharded planning needs the RowPartition for a "
                             "CSR operand")
        bounds = part.bounds
        if shard_csrs is None:
            shard_csrs = part.slice(a)
        shape = (int(a.shape[0]), int(a.shape[1]))
        strategy = part.strategy
    n_shards = len(bounds) - 1
    true_rows = tuple(int(bounds[i + 1] - bounds[i]) for i in range(n_shards))
    n_cols = int(shape[1])
    tile = rhs_tile if rhs_tile is not None else (128 if backend == "pallas"
                                                  else 8)
    uniform = len(set(schedules)) == 1 and schedules[0] is not None
    default_registry().set_gauge("shard.count", n_shards)

    if uniform and mesh is None:
        from ..launch.mesh import make_shard_mesh
        mesh = make_shard_mesh(n_shards)
    elif not uniform:
        mesh = None

    def check_x(x):
        if getattr(x, "ndim", None) is None:
            x = np.asarray(x, np.float32)
        if x.shape[0] != n_cols:
            raise ValueError(f"{op}: runtime input leading dim "
                             f"{x.shape[0]} != operand cols {n_cols}")
        return x

    if mesh is not None:
        # ---- single shard_map program over the mesh's shards axis. The
        # stacked arrays are the ONLY device copy: CSR shards go straight
        # from their host containers to their devices, never through
        # per-shard staging, so the store pins one entry for the launch.
        stack_key = None if store is None or not isinstance(a, CSR) else (
            "matvec_shards_stacked", operand_key or content_key(a),
            strategy, bounds, tuple(schedules), sigma,
            bool(shape_bucket), n_shards)
        members = list(sst.shards) if sst is not None else shard_csrs
        built = _cached(store, stack_key, lambda: _build_on_mesh(
            members, schedules[0], sigma, shape_bucket, mesh))
        arrays, width, layout = built["arrays"], built["width"], built["layout"]
        slots = list(mesh.devices.flat)
        homes = [{d} for d in slots]
        r_max = max(true_rows)
        kernel_rhs = None if layout == "dense" else "given"

        def run(x):
            x = check_x(x)
            multi = x.ndim == 2
            k = x.shape[1] if multi else None
            k_pad = -(-k // tile) * tile if multi else 0
            home = _home(x)
            # pad on x's device, then replicate the padded RHS over the
            # mesh (device-to-device): x never round-trips through the host
            xb = jax.device_put(_pad_rhs(x, width=width, k_pad=k_pad),
                                jax.sharding.NamedSharding(
                                    mesh, jax.sharding.PartitionSpec()))
            fn = _sharded_matvec_exec(mesh, layout, backend, multi, r_max)
            ys = jax.device_put(fn(arrays, xb), home)
            # x out to every other slot, each slot's rows back to x's device
            _count_exchange(sum(d != home for d in slots) * 4
                            * (width + r_max) * max(k_pad, 1))
            return _concat_rows(ys, rows=true_rows, k=k)
    else:
        # ---- per-shard fallback: round-robin device placement, one jitted
        # dispatch per shard (async overlap across devices); the path every
        # heterogeneous-schedule plan takes, whatever the backend
        devices = jax.devices()
        shard_devs = [devices[i % len(devices)] for i in range(n_shards)]
        if sst is None:
            key = None if store is None else (
                "matvec_shards", operand_key or content_key(a), strategy,
                bounds, tuple(schedules), sigma, bool(shape_bucket))

            def build_shards():
                shards = []
                for i, (c, s, dev) in enumerate(zip(shard_csrs, schedules,
                                                    shard_devs)):
                    with obs_trace.span("shard_build", shard=i,
                                        n_shards=n_shards):
                        st = SparseTensor.from_csr(
                            c, schedule=s, sigma=sigma,
                            shape_bucket=shape_bucket, device=dev)
                        jax.block_until_ready(st.arrays)
                        st._host = None
                    shards.append(st)
                return ShardedSparseTensor(
                    ShardedMeta(shape, bounds, strategy), shards)

            sst = _cached(store, key, build_shards)
            for st in sst.shards:
                if st.layout not in MATVEC_LAYOUTS:
                    raise ValueError(f"{op} needs ell/sell/dense shards, "
                                     f"got a {st.layout!r} SparseTensor")
        placed = []
        for st, dev in zip(sst.shards, shard_devs):
            nst = SparseTensor(st.meta, {k: jax.device_put(v, dev)
                                         for k, v in st.arrays.items()})
            nst.true_shape = st.true_shape
            placed.append(nst)
        homes = [{d for v in st.arrays.values() for d in v.devices()}
                 for st in placed]
        sub = [_plan_matvec((st,), s, backend, op=op, rhs_tile=rhs_tile)
               for st, s in zip(placed, schedules)]
        kernel_rhs = None if all(q.kernel_rhs is None for q in sub) \
            else "given"

        def run(x):
            x = check_x(x)
            home = _home(x)
            if isinstance(x, jax.Array):
                # committed device input: device-to-device transfer per
                # shard, never through the host
                ys = [p._run(jax.device_put(x, d))
                      for p, d in zip(sub, shard_devs)]
                x_moves = sum(d != home for d in shard_devs) * x.nbytes
            else:
                # host input: each shard's jit places it next to that
                # shard's committed operands
                ys = [p._run(x) for p in sub]
                x_moves = 0
            _count_exchange(x_moves + sum(
                y.nbytes for y, d in zip(ys, shard_devs) if d != home))
            return jnp.concatenate([jax.device_put(y, home) for y in ys],
                                   axis=0)

    return Plan(op=op, schedule=schedules[0] if uniform else None,
                backend=backend, _run=run,
                operands=(sst,) if sst is not None else (),
                n_members=n_shards, n_shards=n_shards,
                shard_devices=[frozenset(h) for h in homes],
                kernel_rhs=kernel_rhs)


# ---------------------------------------------------------------------------
# spgemm — padded pairs ("ell") or flattened cells ("sell" layout axis)
# ---------------------------------------------------------------------------

def _spgemm_pairs_launch(pair_a, pair_b, a_blocks, b_blocks,
                         interpret: bool):
    """The padded-pairs spgemm kernel, split by output block range."""
    return split_rows(
        lambda pa, pb: bsr_spgemm_pallas(pa, pb, a_blocks, b_blocks,
                                         interpret=interpret),
        (pair_a, pair_b))


def _spgemm_cells_launch(cell_a, cell_b, cell_c, a_blocks, b_blocks,
                         n_c: int, interpret: bool):
    """The flat-cells spgemm kernel, its cell stream split to fit SMEM."""
    return split_cells(
        lambda ca, cb, cc: bsr_spgemm_cells_pallas(
            ca, cb, cc, a_blocks, b_blocks, n_c, interpret=interpret),
        (cell_a, cell_b, cell_c), cell_c, n_c)


def _spadd_launch(ia, ib, a_blocks, b_blocks, interpret: bool):
    """The spadd gather-add kernel, split by output block range."""
    return split_rows(
        lambda i1, i2: bsr_spadd_pallas(i1, i2, a_blocks, b_blocks,
                                        interpret=interpret), (ia, ib))


@functools.partial(jax.jit, static_argnames=("backend",))
def _exec_spgemm_pairs(pair_a, pair_b, a_blocks, b_blocks, backend: str):
    _bump_trace("spgemm_pairs")
    if backend == "jnp":
        return ref_pair_gemm(pair_a, pair_b, a_blocks, b_blocks)
    return _spgemm_pairs_launch(pair_a, pair_b, a_blocks, b_blocks,
                                interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("n_c", "backend"))
def _exec_spgemm_cells(cell_a, cell_b, cell_c, a_blocks, b_blocks, n_c: int,
                       backend: str):
    _bump_trace("spgemm_cells")
    if backend == "jnp":
        return ref_cell_gemm(cell_a, cell_b, cell_c, a_blocks, b_blocks, n_c)
    return _spgemm_cells_launch(cell_a, cell_b, cell_c, a_blocks, b_blocks,
                                n_c, interpret=(backend == "interpret"))


def _with_zero_block(blocks: np.ndarray, bs: int) -> np.ndarray:
    return np.concatenate(
        [blocks.astype(np.float32), np.zeros((1, bs, bs), np.float32)])


def _pad_rows(arr: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad axis 0 of a host array to ``n`` rows with ``fill``."""
    if arr.shape[0] >= n:
        return arr
    out = np.full((n,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _as_bsr(a, bs: int, op: str) -> BSR:
    """Coerce a spgemm/spadd operand — CSR, prepared BSR container, or a
    bsr-layout SparseTensor — to the raw blocked form the symbolic phase
    consumes, validating the block size against the schedule's."""
    if isinstance(a, SparseTensor):
        if a.layout != "bsr":
            raise ValueError(f"{op} operands must be raw blocked (bsr) "
                             f"SparseTensors, got layout {a.layout!r}")
        a = a.to_host()
    if isinstance(a, BSR):
        if a.block_size != bs:
            raise ValueError(f"{op} operand was prepared with block_size "
                             f"{a.block_size}, schedule wants {bs}")
        return a
    return BSR.from_csr(a, bs)


def _spgemm_host_products(a, b, schedule: Schedule):
    """Host symbolic products + sentinel-extended block arrays (numpy) —
    shared by the single-plan prepare and the stacked bucket build."""
    bs = schedule.block_size
    bsr_a = _as_bsr(a, bs, "spgemm")
    bsr_b = _as_bsr(b, bs, "spgemm")
    zero_a, zero_b = bsr_a.n_blocks, bsr_b.n_blocks
    a_bl = _with_zero_block(bsr_a.blocks, bs)
    b_bl = _with_zero_block(bsr_b.blocks, bs)
    if schedule.layout == "sell":
        c_ptrs, c_cols, ca, cb, cc = spgemm_symbolic_cells(bsr_a, bsr_b)
        return {"mode": "cells", "c_ptrs": c_ptrs, "c_cols": c_cols,
                "cell_a": ca, "cell_b": cb, "cell_c": cc,
                "a_blocks": a_bl, "b_blocks": b_bl,
                "zero_a": zero_a, "zero_b": zero_b,
                "n_c": int(c_cols.size),
                "out_shape": (a.shape[0], b.shape[1]), "bs": bs}
    c_ptrs, c_cols, pair_a, pair_b = spgemm_symbolic(bsr_a, bsr_b)
    return {"mode": "pairs", "c_ptrs": c_ptrs, "c_cols": c_cols,
            "pair_a": pair_a, "pair_b": pair_b,
            "a_blocks": a_bl, "b_blocks": b_bl,
            "zero_a": zero_a, "zero_b": zero_b,
            "n_c": int(c_cols.size),
            "out_shape": (a.shape[0], b.shape[1]), "bs": bs}


def _prepare_spgemm(a, b, schedule: Schedule,
                    store: Optional[PreparedStore], shape_bucket: bool,
                    operand_key: Optional[str] = None):
    """Device-staged (and optionally bucket-padded) spgemm symbolic-phase
    products; cached in the PreparedStore keyed by exact matrix bytes."""
    key = None
    if store is not None and isinstance(a, CSR) and isinstance(b, CSR):
        key = ("spgemm", schedule.block_size, schedule.layout,
               bool(shape_bucket), operand_key or content_key(a),
               content_key(b))
    return _cached(store, key,
                   lambda: _build_spgemm(a, b, schedule, shape_bucket))


def _build_spgemm(a, b, schedule: Schedule, shape_bucket: bool):
    h = _spgemm_host_products(a, b, schedule)
    n_c, bs = h["n_c"], h["bs"]
    if h["mode"] == "cells":
        ca, cb, cc = h["cell_a"], h["cell_b"], h["cell_c"]
        n_c_pad = n_c
        if shape_bucket:
            n_cells_p = bucket_edge(ca.size)
            n_c_pad = bucket_edge(n_c)
            ca = _pad_rows(ca, n_cells_p, h["zero_a"])
            cb = _pad_rows(cb, n_cells_p, h["zero_b"])
            cc = _pad_rows(cc, n_cells_p, max(n_c - 1, 0))
            h["a_blocks"] = _pad_rows(h["a_blocks"],
                                      bucket_edge(h["a_blocks"].shape[0]), 0.0)
            h["b_blocks"] = _pad_rows(h["b_blocks"],
                                      bucket_edge(h["b_blocks"].shape[0]), 0.0)
        dev = (jnp.asarray(ca), jnp.asarray(cb), jnp.asarray(cc),
               jnp.asarray(h["a_blocks"]), jnp.asarray(h["b_blocks"]))
        prep = {"mode": "cells", "dev": dev, "n_c_pad": n_c_pad}
    else:
        pa, pb = h["pair_a"], h["pair_b"]
        if shape_bucket and pa.size:
            n_c_p, mp_p = bucket_edge(pa.shape[0]), bucket_edge(pa.shape[1])
            pa2 = np.full((n_c_p, mp_p), h["zero_a"], np.int32)
            pa2[: pa.shape[0], : pa.shape[1]] = pa
            pb2 = np.full((n_c_p, mp_p), h["zero_b"], np.int32)
            pb2[: pb.shape[0], : pb.shape[1]] = pb
            pa, pb = pa2, pb2
            h["a_blocks"] = _pad_rows(h["a_blocks"],
                                      bucket_edge(h["a_blocks"].shape[0]), 0.0)
            h["b_blocks"] = _pad_rows(h["b_blocks"],
                                      bucket_edge(h["b_blocks"].shape[0]), 0.0)
        dev = (jnp.asarray(pa), jnp.asarray(pb),
               jnp.asarray(h["a_blocks"]), jnp.asarray(h["b_blocks"]))
        prep = {"mode": "pairs", "dev": dev, "n_c_pad": n_c}
    prep.update({"c_ptrs": h["c_ptrs"], "c_cols": h["c_cols"], "n_c": n_c,
                 "out_shape": h["out_shape"], "bs": bs})
    return prep


def _plan_spgemm(operands, schedule: Optional[Schedule], backend: str, *,
                 block_size: int = 128,
                 store: Optional[PreparedStore] = None,
                 shape_bucket: bool = True,
                 operand_key: Optional[str] = None, **_) -> Plan:
    a, b = operands
    if schedule is None:
        schedule = Schedule("bsr", block_size, 1.0)
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path; dispatch a "
                         "dense matmul instead")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"inner dims mismatch {a.shape} @ {b.shape}")
    prep = _prepare_spgemm(a, b, schedule, store, shape_bucket, operand_key)
    n_c, bs = prep["n_c"], prep["bs"]

    if prep["mode"] == "cells":
        def run():
            if n_c == 0:
                c_blocks = np.zeros((0, bs, bs), np.float32)
            else:
                c_blocks = np.asarray(_exec_spgemm_cells(
                    *prep["dev"], n_c=prep["n_c_pad"], backend=backend))[:n_c]
            return BSR(prep["c_ptrs"], prep["c_cols"], c_blocks,
                       prep["out_shape"], bs)
    else:
        def run():
            if n_c == 0:
                c_blocks = np.zeros((0, bs, bs), np.float32)
            else:
                c_blocks = np.asarray(_exec_spgemm_pairs(
                    *prep["dev"], backend=backend))[:n_c]
            return BSR(prep["c_ptrs"], prep["c_cols"], c_blocks,
                       prep["out_shape"], bs)

    return Plan(op="spgemm", schedule=schedule, backend=backend, _run=run)


# ---------------------------------------------------------------------------
# spgemm / spadd — stacked bucket launches (ROADMAP follow-up closed)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def _exec_spgemm_stacked(pair_a, pair_b, a_blocks, b_blocks, backend: str):
    """One device program for a whole spgemm bucket (padded-pairs mode)."""
    _bump_trace("spgemm_stacked")
    if backend == "jnp":
        def one(pa, pb, ab, bb):
            return jnp.einsum("kpab,kpbc->kac", ab[pa], bb[pb])
        return jax.vmap(one)(pair_a, pair_b, a_blocks, b_blocks)
    interpret = backend == "interpret"
    return jnp.stack([
        _spgemm_pairs_launch(pair_a[i], pair_b[i], a_blocks[i], b_blocks[i],
                             interpret)
        for i in range(pair_a.shape[0])])


@functools.partial(jax.jit, static_argnames=("n_c", "backend"))
def _exec_spgemm_cells_stacked(cell_a, cell_b, cell_c, a_blocks, b_blocks,
                               n_c: int, backend: str):
    """One device program for a whole spgemm bucket (flat-cells mode)."""
    _bump_trace("spgemm_stacked")
    if backend == "jnp":
        def one(ca, cb, cc, ab, bb):
            prods = jnp.einsum("tab,tbc->tac", ab[ca], bb[cb])
            return jax.ops.segment_sum(prods, cc, num_segments=n_c)
        return jax.vmap(one)(cell_a, cell_b, cell_c, a_blocks, b_blocks)
    interpret = backend == "interpret"
    return jnp.stack([
        _spgemm_cells_launch(cell_a[i], cell_b[i], cell_c[i], a_blocks[i],
                             b_blocks[i], n_c, interpret)
        for i in range(cell_a.shape[0])])


@functools.partial(jax.jit, static_argnames=("backend",))
def _exec_spadd_stacked(ia, ib, a_blocks, b_blocks, backend: str):
    """One device program for a whole spadd bucket (block gather-add)."""
    _bump_trace("spadd_stacked")
    if backend == "jnp":
        return jax.vmap(lambda i1, i2, ab, bb: ab[i1] + bb[i2])(
            ia, ib, a_blocks, b_blocks)
    interpret = backend == "interpret"
    return jnp.stack([
        _spadd_launch(ia[i], ib[i], a_blocks[i], b_blocks[i], interpret)
        for i in range(ia.shape[0])])


def _pair_members(members: List, op: str) -> List[Tuple[CSR, CSR]]:
    pairs = []
    for i, m in enumerate(members):
        if not (isinstance(m, (tuple, list)) and len(m) == 2):
            raise ValueError(f"{op} bucket members are (A, B) operand "
                             f"pairs; member {i} is {type(m).__name__}")
        pairs.append((m[0], m[1]))
    return pairs


def _plan_spgemm_bucket(members: List, schedule: Schedule, backend: str, *,
                        store: Optional[PreparedStore] = None,
                        shape_bucket: bool = True,
                        member_keys=None, **_) -> Plan:
    """ONE stacked launch for a same-schedule spgemm bucket: per-member
    symbolic products are padded to common (edge-rounded) shapes, stacked
    along a member axis, and the numeric phase runs as a single device
    program; results are sliced back per member."""
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path")
    pairs = _pair_members(members, "spgemm")
    for i, (a, b) in enumerate(pairs):
        if a.shape[1] != b.shape[0]:
            raise ValueError(f"bucket member {i}: inner dims mismatch "
                             f"{a.shape} @ {b.shape}")
    key = None if store is None else _members_key(
        "spgemm_bucket", members, schedule, extra=(bool(shape_bucket),),
        member_keys=member_keys)
    ed = (0,) if shape_bucket else ()

    def build():
        hs = [_spgemm_host_products(a, b, schedule) for a, b in pairs]
        mode = hs[0]["mode"]
        if mode == "cells":
            stacked = {
                "cell_a": jnp.asarray(_stack_pad(
                    [h["cell_a"] for h in hs], [h["zero_a"] for h in hs],
                    edge_dims=ed)),
                "cell_b": jnp.asarray(_stack_pad(
                    [h["cell_b"] for h in hs], [h["zero_b"] for h in hs],
                    edge_dims=ed)),
                # pad cells accumulate zero products onto the member's LAST
                # output block, keeping cell_c nondecreasing
                "cell_c": jnp.asarray(_stack_pad(
                    [h["cell_c"] for h in hs],
                    [max(h["n_c"] - 1, 0) for h in hs], edge_dims=ed)),
            }
            n_c_pad = max(h["n_c"] for h in hs)
            if shape_bucket:
                n_c_pad = bucket_edge(n_c_pad)
        else:
            stacked = {
                "pair_a": jnp.asarray(_stack_pad(
                    [h["pair_a"] for h in hs], [h["zero_a"] for h in hs],
                    edge_dims=(0, 1) if shape_bucket else ())),
                "pair_b": jnp.asarray(_stack_pad(
                    [h["pair_b"] for h in hs], [h["zero_b"] for h in hs],
                    edge_dims=(0, 1) if shape_bucket else ())),
            }
            n_c_pad = 0
        stacked["a_blocks"] = jnp.asarray(_stack_pad(
            [h["a_blocks"] for h in hs], 0.0, edge_dims=ed))
        stacked["b_blocks"] = jnp.asarray(_stack_pad(
            [h["b_blocks"] for h in hs], 0.0, edge_dims=ed))
        return {"mode": mode, "stacked": stacked, "n_c_pad": n_c_pad,
                "c_ptrs": [h["c_ptrs"] for h in hs],
                "c_cols": [h["c_cols"] for h in hs],
                "n_c": [h["n_c"] for h in hs],
                "out_shapes": [h["out_shape"] for h in hs],
                "bs": hs[0]["bs"]}

    built = _cached(store, key, build)
    st, bs = built["stacked"], built["bs"]

    def run():
        if built["mode"] == "cells":
            cs = _exec_spgemm_cells_stacked(
                st["cell_a"], st["cell_b"], st["cell_c"], st["a_blocks"],
                st["b_blocks"], n_c=built["n_c_pad"], backend=backend)
        else:
            cs = _exec_spgemm_stacked(st["pair_a"], st["pair_b"],
                                      st["a_blocks"], st["b_blocks"],
                                      backend=backend)
        blocks = np.asarray(cs)
        return [BSR(built["c_ptrs"][i], built["c_cols"][i],
                    blocks[i, : built["n_c"][i]], built["out_shapes"][i], bs)
                for i in range(len(built["n_c"]))]

    return Plan(op="spgemm", schedule=schedule, backend=backend, _run=run,
                n_members=len(pairs))


# ---------------------------------------------------------------------------
# spadd
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def _exec_spadd(ia, ib, a_blocks, b_blocks, backend: str):
    _bump_trace("spadd")
    if backend == "jnp":
        return ref_block_union_add(ia, ib, a_blocks, b_blocks)
    return _spadd_launch(ia, ib, a_blocks, b_blocks,
                         interpret=(backend == "interpret"))


def _spadd_host_products(a, b, schedule: Schedule):
    bs = schedule.block_size
    bsr_a = _as_bsr(a, bs, "spadd")
    bsr_b = _as_bsr(b, bs, "spadd")
    c_ptrs, c_cols, ia, ib = spadd_symbolic(bsr_a, bsr_b)
    return {"c_ptrs": c_ptrs, "c_cols": c_cols, "ia": ia, "ib": ib,
            "a_blocks": _with_zero_block(bsr_a.blocks, bs),
            "b_blocks": _with_zero_block(bsr_b.blocks, bs),
            "zero_a": bsr_a.n_blocks, "zero_b": bsr_b.n_blocks,
            "n_c": int(ia.size), "out_shape": a.shape, "bs": bs}


def _prepare_spadd(a, b, schedule: Schedule,
                   store: Optional[PreparedStore], shape_bucket: bool,
                   operand_key: Optional[str] = None):
    key = None
    if store is not None and isinstance(a, CSR) and isinstance(b, CSR):
        # layout is irrelevant to spadd prep (only block_size is consumed),
        # so the key deliberately omits it: sell- and ell-schedule plans of
        # the same block size share one cached entry.
        key = ("spadd", schedule.block_size, bool(shape_bucket),
               operand_key or content_key(a), content_key(b))
    return _cached(store, key,
                   lambda: _build_spadd(a, b, schedule, shape_bucket))


def _build_spadd(a, b, schedule: Schedule, shape_bucket: bool):
    h = _spadd_host_products(a, b, schedule)
    ia, ib = h["ia"], h["ib"]
    if shape_bucket:
        n_c_p = bucket_edge(h["n_c"])
        ia = _pad_rows(ia, n_c_p, h["zero_a"])
        ib = _pad_rows(ib, n_c_p, h["zero_b"])
        h["a_blocks"] = _pad_rows(h["a_blocks"],
                                  bucket_edge(h["a_blocks"].shape[0]), 0.0)
        h["b_blocks"] = _pad_rows(h["b_blocks"],
                                  bucket_edge(h["b_blocks"].shape[0]), 0.0)
    return {"dev": (jnp.asarray(ia), jnp.asarray(ib),
                    jnp.asarray(h["a_blocks"]), jnp.asarray(h["b_blocks"])),
            "c_ptrs": h["c_ptrs"], "c_cols": h["c_cols"], "n_c": h["n_c"],
            "out_shape": h["out_shape"], "bs": h["bs"]}


def _plan_spadd(operands, schedule: Optional[Schedule], backend: str, *,
                block_size: int = 128,
                store: Optional[PreparedStore] = None,
                shape_bucket: bool = True,
                operand_key: Optional[str] = None, **_) -> Plan:
    a, b = operands
    if schedule is None:
        schedule = Schedule("bsr", block_size, 1.0)
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path; dispatch a "
                         "dense matmul instead")
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch {a.shape} vs {b.shape}")
    prep = _prepare_spadd(a, b, schedule, store, shape_bucket, operand_key)
    n_c, bs = prep["n_c"], prep["bs"]

    def run():
        if n_c == 0:
            c_blocks = np.zeros((0, bs, bs), np.float32)
        else:
            c_blocks = np.asarray(_exec_spadd(*prep["dev"],
                                              backend=backend))[:n_c]
        return BSR(prep["c_ptrs"], prep["c_cols"], c_blocks,
                   prep["out_shape"], bs)

    return Plan(op="spadd", schedule=schedule, backend=backend, _run=run)


def _plan_spadd_bucket(members: List, schedule: Schedule, backend: str, *,
                       store: Optional[PreparedStore] = None,
                       shape_bucket: bool = True,
                       member_keys=None, **_) -> Plan:
    """ONE stacked launch for a same-schedule spadd bucket."""
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path")
    pairs = _pair_members(members, "spadd")
    for i, (a, b) in enumerate(pairs):
        if a.shape != b.shape:
            raise ValueError(f"bucket member {i}: shape mismatch "
                             f"{a.shape} vs {b.shape}")
    key = None if store is None else _members_key(
        "spadd_bucket", members, schedule, extra=(bool(shape_bucket),),
        member_keys=member_keys)

    def build():
        hs = [_spadd_host_products(a, b, schedule) for a, b in pairs]
        ed = (0,) if shape_bucket else ()
        stacked = {
            "ia": jnp.asarray(_stack_pad(
                [h["ia"] for h in hs], [h["zero_a"] for h in hs],
                edge_dims=ed)),
            "ib": jnp.asarray(_stack_pad(
                [h["ib"] for h in hs], [h["zero_b"] for h in hs],
                edge_dims=ed)),
            "a_blocks": jnp.asarray(_stack_pad(
                [h["a_blocks"] for h in hs], 0.0, edge_dims=ed)),
            "b_blocks": jnp.asarray(_stack_pad(
                [h["b_blocks"] for h in hs], 0.0, edge_dims=ed)),
        }
        return {"stacked": stacked,
                "c_ptrs": [h["c_ptrs"] for h in hs],
                "c_cols": [h["c_cols"] for h in hs],
                "n_c": [h["n_c"] for h in hs],
                "out_shapes": [h["out_shape"] for h in hs],
                "bs": hs[0]["bs"]}

    built = _cached(store, key, build)
    st, bs = built["stacked"], built["bs"]

    def run():
        cs = _exec_spadd_stacked(st["ia"], st["ib"], st["a_blocks"],
                                 st["b_blocks"], backend=backend)
        blocks = np.asarray(cs)
        return [BSR(built["c_ptrs"][i], built["c_cols"][i],
                    blocks[i, : built["n_c"][i]], built["out_shapes"][i], bs)
                for i in range(len(built["n_c"]))]

    return Plan(op="spadd", schedule=schedule, backend=backend, _run=run,
                n_members=len(pairs))


# ---------------------------------------------------------------------------
# moe_gmm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_n", "tile_k", "backend"))
def _exec_moe(tile_expert, x, w, tile_m: int, tile_n: int, tile_k: int,
              backend: str):
    _bump_trace("moe_gmm")
    if backend == "jnp":
        return ref_gmm(tile_expert, x, w, tile_m=tile_m)
    return moe_gmm_pallas(tile_expert, x, w, tile_m=tile_m, tile_n=tile_n,
                          tile_k=tile_k, interpret=(backend == "interpret"))


def _plan_moe(operands, schedule: Optional[Schedule], backend: str, *,
              tile_m: Optional[int] = None, tile_n: int = 128,
              tile_k: int = 128,
              store: Optional[PreparedStore] = None, **_) -> Plan:
    (tile_expert,) = operands
    tm = tile_m if tile_m is not None else (
        schedule.block_size if schedule is not None else 128)
    key = None if store is None else (
        "moe_gmm", array_key(np.asarray(tile_expert, np.int32)))
    te = _cached(store, key, lambda: jnp.asarray(tile_expert, jnp.int32))

    def run(x, w):
        return _exec_moe(te, jnp.asarray(x), jnp.asarray(w), tile_m=tm,
                         tile_n=tile_n, tile_k=tile_k, backend=backend)

    return Plan(op="moe_gmm", schedule=schedule, backend=backend, _run=run,
                operands=(te,))


def moe_tile_schedule(tokens_per_expert, d_model: int, platform,
                      cache=None) -> Schedule:
    """Selector-backed MoE tile choice for the serving decode path.

    The routing histogram is fingerprinted (``routing_fingerprint``) and
    looked up in a ``ScheduleCache`` exactly like a sparse matrix: decode
    ticks with recurring routing shapes hit the cache instead of re-running
    the imbalance rule. The returned Schedule's ``block_size`` is the
    grouped-GEMM ``tile_m`` (Eq. 5 imbalance rule on a miss).
    """
    from ..selector.fingerprint import routing_fingerprint
    fp = None
    if cache is not None:
        if not cache.context:
            cache.context = "moe_gmm"
        fp = routing_fingerprint(tokens_per_expert, d_model, platform.name)
        hit = cache.get(fp)
        if hit is not None:
            return hit
    tile = select_moe_block_size(np.asarray(tokens_per_expert, np.float64),
                                 d_model, platform)
    sched = Schedule("bsr", tile, 1.0)
    if cache is not None:
        cache.put(fp, sched, "moe-rule")
    return sched


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _plan_flash(operands, schedule: Optional[Schedule], backend: str, *,
                causal: bool = True, block_q: int = 128, block_k: int = 128,
                **_) -> Plan:
    if operands not in ((), None):
        raise ValueError("flash_attention takes no planned operands; pass "
                         "q, k, v to execute()")

    def run(q, k, v):
        if backend == "jnp":
            return ref_attention(q, k, v, causal=causal)
        return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                      block_k=block_k,
                                      interpret=(backend == "interpret"))

    return Plan(op="flash_attention", schedule=schedule, backend=backend,
                _run=run)


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------

def _matvec_bucket_layouts(s: Schedule) -> Tuple[str, ...]:
    return ("dense",) if s.backend == "dense" else (s.layout,)


def _pairop_bucket_layouts(s: Schedule) -> Tuple[str, ...]:
    # spgemm/spadd operands are raw blocked rows whatever the schedule's
    # ell/sell axis says (that axis picks the numeric formulation).
    return ("bsr",)


register_op(
    "spmv", functools.partial(_plan_matvec, op="spmv"),
    operand_spec="(A: CSR | SparseTensor | ELLBSR/SELLBSR) -> execute(x: (n,))",
    layouts=MATVEC_LAYOUTS,
    bucket_planner=functools.partial(_plan_matvec_bucket, op="spmv"),
    bucket_layouts=_matvec_bucket_layouts,
    sharded_planner=functools.partial(_plan_matvec_sharded, op="spmv"))
register_op(
    "spmm", functools.partial(_plan_matvec, op="spmm"),
    operand_spec="(A: CSR | SparseTensor) -> execute(X: (n, k))",
    layouts=MATVEC_LAYOUTS,
    bucket_planner=functools.partial(_plan_matvec_bucket, op="spmm"),
    bucket_layouts=_matvec_bucket_layouts,
    sharded_planner=functools.partial(_plan_matvec_sharded, op="spmm"))
register_op(
    "spgemm", _plan_spgemm,
    operand_spec="(A: CSR, B: CSR) -> execute() -> BSR",
    layouts=("ell", "sell"), symbolic=spgemm_symbolic,
    bucket_planner=_plan_spgemm_bucket,
    bucket_layouts=_pairop_bucket_layouts)
# spadd accepts sell-layout schedules (tuner sweeps emit them; the modeled
# spadd time ignores layout) but executes the block-union path either way —
# only block_size is consumed, matching the legacy schedule= contract.
register_op(
    "spadd", _plan_spadd,
    operand_spec="(A: CSR, B: CSR) -> execute() -> BSR",
    layouts=("ell", "sell"), symbolic=spadd_symbolic,
    bucket_planner=_plan_spadd_bucket,
    bucket_layouts=_pairop_bucket_layouts)
register_op(
    "moe_gmm", _plan_moe,
    operand_spec="(tile_expert: (M/tile_m,)) -> execute(x: (M, K), "
                 "w: (E, K, N))",
    layouts=("ell",))
register_op(
    "flash_attention", _plan_flash,
    operand_spec="() -> execute(q, k, v: (BH, S, D))",
    layouts=("ell",))


# ---------------------------------------------------------------------------
# dense references — the guard's terminal fallback rung (DESIGN.md §11)
# ---------------------------------------------------------------------------
# Pure-numpy implementations matched to each op's execute() contract: same
# runtime signature, same output container, no jax in the loop. Builders
# are LAZY by contract (resilience._DENSE_REFS): the builder call does only
# cheap type + size-cap validation — raising TypeError means the guard has
# no dense rung and the chain ends at jnp — while the O(n*m) densification
# is deferred (and memoized) inside the returned run, so plan() never
# materializes a dense copy unless the guard actually falls to this rung.

def _dense_elems(a) -> int:
    """Element count the dense reference would materialize for one operand
    (cheap: shapes only). Raises TypeError for operand types with no dense
    reference — the same signal `_dense_of` would give, moved to plan time."""
    if isinstance(a, (CSR, BSR)):
        n, m = a.shape
        return int(n) * int(m)
    if isinstance(a, SparseTensor):
        if a.layout == "dense":
            tr, tc = a.true_shape
            return int(tr) * int(tc)
        raise TypeError(f"no dense reference for a prepared {a.layout!r} "
                        "SparseTensor (plan from the CSR to enable the "
                        "dense rung)")
    if isinstance(a, np.ndarray):
        return int(a.size)
    raise TypeError(f"no dense reference for operand {type(a).__name__}")


def _dense_check(a) -> None:
    """Plan-time eligibility gate for the dense rung: unsupported operand
    types and over-cap shapes raise TypeError (→ no dense rung) WITHOUT
    touching any data, so planning a huge matrix never OOMs here."""
    elems = _dense_elems(a)
    cap = dense_ref_cap()
    if elems > cap:
        raise TypeError(f"dense reference refused: {elems} elements exceeds "
                        f"the {cap}-element cap (REPRO_DENSE_REF_MAX_ELEMS)")


def _dense_of(a) -> np.ndarray:
    if isinstance(a, CSR):
        return a.to_dense().astype(np.float32)
    if isinstance(a, BSR):
        return np.asarray(a.to_dense(), np.float32)
    if isinstance(a, SparseTensor):
        if a.layout == "dense":
            tr, tc = a.true_shape
            return np.asarray(a.arrays["dense"], np.float32)[:tr, :tc]
        raise TypeError(f"no dense reference for a prepared {a.layout!r} "
                        "SparseTensor (plan from the CSR to enable the "
                        "dense rung)")
    if isinstance(a, np.ndarray):
        return np.asarray(a, np.float32)
    raise TypeError(f"no dense reference for operand {type(a).__name__}")


def _lazy_dense(a) -> Callable[[], np.ndarray]:
    """Deferred, memoized densification: the dense copy is built on the
    first call — i.e. only once the guard has actually fallen to the dense
    rung — and reused across subsequent launches of the same plan."""
    _dense_check(a)
    box: list = []

    def get() -> np.ndarray:
        if not box:
            box.append(_dense_of(a))
        return box[0]

    return get


def _dense_to_bsr(dense: np.ndarray, bs: int) -> BSR:
    """Re-block a dense product into the BSR container spgemm/spadd
    callers expect (block structure may differ from the symbolic union —
    ``to_dense()`` equivalence is the contract)."""
    return BSR.from_csr(CSR.from_dense(np.asarray(dense, np.float32)), bs)


def _dense_ref_matvec(operands, schedule, **_):
    (a,) = operands
    ad = _lazy_dense(a)

    def run(x):
        d = ad()
        x = np.asarray(x, np.float32)
        if x.shape[0] > d.shape[1]:     # bucket-padded RHS: pad is zeros
            x = x[: d.shape[1]]
        return d @ x

    return run


def _dense_ref_spgemm(operands, schedule, block_size: int = 128, **_):
    a, b = operands
    ad, bd = _lazy_dense(a), _lazy_dense(b)
    bs = schedule.block_size if schedule is not None else block_size

    def run():
        return _dense_to_bsr(ad() @ bd(), bs)

    return run


def _dense_ref_spadd(operands, schedule, block_size: int = 128, **_):
    a, b = operands
    ad, bd = _lazy_dense(a), _lazy_dense(b)
    bs = schedule.block_size if schedule is not None else block_size

    def run():
        return _dense_to_bsr(ad() + bd(), bs)

    return run


def _dense_ref_moe(operands, schedule, tile_m: Optional[int] = None, **_):
    (tile_expert,) = operands
    te = np.asarray(tile_expert, np.int64).ravel()
    tm = tile_m if tile_m is not None else (
        schedule.block_size if schedule is not None else 128)

    def run(x, w):
        x = np.asarray(x, np.float32)
        w = np.asarray(w, np.float32)
        out = np.zeros((x.shape[0], w.shape[2]), np.float32)
        for i, e in enumerate(te):
            lo = i * tm
            hi = min(lo + tm, x.shape[0])
            if lo >= hi:
                break
            out[lo:hi] = x[lo:hi] @ w[int(e)]
        return out

    return run


def _dense_ref_flash(operands, schedule, causal: bool = True, **_):
    def run(q, k, v):
        q = np.asarray(q, np.float32)
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        s = np.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            mask = np.tril(np.ones(s.shape[-2:], bool))
            s = np.where(mask, s, -np.inf)
        s = s - s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p = p / p.sum(axis=-1, keepdims=True)
        return np.einsum("bqk,bkd->bqd", p, v)

    return run


register_dense_ref("spmv", _dense_ref_matvec)
register_dense_ref("spmm", _dense_ref_matvec)
register_dense_ref("spgemm", _dense_ref_spgemm)
register_dense_ref("spadd", _dense_ref_spadd)
register_dense_ref("moe_gmm", _dense_ref_moe)
register_dense_ref("flash_attention", _dense_ref_flash)
