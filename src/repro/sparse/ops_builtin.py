"""Built-in op registrations of the plan/execute facade (DESIGN.md §8-§9).

Registered ops: ``spmv`` / ``spmm`` / ``spgemm`` / ``spadd`` / ``moe_gmm`` /
``flash_attention``. Each planner resolves operands into device pytrees
(``SparseTensor``) once, then hands back a ``Plan`` whose launch is a
module-level jitted executor — module-level so the XLA compile cache is
shared across every plan with the same (schedule, backend, shapes), which
is exactly the schedule-bucket compile-key property the selector batches
around.

The zero-rebuild serving path (DESIGN.md §9) rides on two properties of
every planner:

* ``store`` — a ``PreparedStore``; a warm hit returns the finished
  device-resident operands (prepared ``SparseTensor``, staged spgemm/spadd
  symbolic products, stacked bucket arrays) and skips host prep entirely.
* shape buckets — prepared containers are padded up to power-of-two-ish
  bucket edges (the launch format's one padding rule, ``tensor.pad_arrays``)
  so differing matrices present identical leaf shapes + static meta to the
  jitted executors: one compiled program serves the whole shape bucket
  instead of retracing per matrix.

All four bsr ops register bucket planners: a whole same-schedule bucket is
padded to common (edge-rounded) shapes, stacked along a leading axis, and
run as ONE jitted launch — vmapped on the jnp backend, the per-member
kernel schedule unrolled inside one program on interpret/pallas. The
executors bump ``plan.trace_count`` when a program actually retraces, so
tests can assert a bucket compiles once and launches once.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autotune import SELL_SIGMA, Schedule, select_moe_block_size
from ..core.csr import BSR, CSR
from ..kernels.bsr_spadd.kernel import bsr_spadd_pallas
from ..kernels.bsr_spadd.ops import spadd_symbolic
from ..kernels.bsr_spadd.ref import ref_block_union_add
from ..kernels.bsr_spgemm.kernel import (bsr_spgemm_cells_pallas,
                                         bsr_spgemm_pallas)
from ..kernels.bsr_spgemm.ops import spgemm_symbolic, spgemm_symbolic_cells
from ..kernels.bsr_spgemm.ref import ref_cell_gemm, ref_pair_gemm
from ..kernels.bsr_spmv.kernel import (bsr_spmm_pallas, bsr_spmm_sell_pallas,
                                       bsr_spmv_pallas, bsr_spmv_sell_pallas,
                                       ell_streams)
from ..kernels.bsr_spmv.ref import (ref_bsr_spmm, ref_bsr_spmm_sell,
                                    ref_bsr_spmv, ref_bsr_spmv_sell,
                                    ref_dia_spmv)
from ..kernels.flash_attention.kernel import flash_attention_pallas
from ..kernels.flash_attention.ref import ref_attention
from ..kernels.moe_gmm.kernel import moe_gmm_pallas
from ..kernels.moe_gmm.ops import route_and_pad  # noqa: F401  (facade re-export)
from ..kernels.moe_gmm.ref import ref_gmm
from ..obs import default_registry, trace as obs_trace
from .plan import Plan, _bump_trace
from .prepared import PreparedStore, array_key, bucket_edge, content_key
from .partition import SHARD_AXIS, make_shard_mesh
from .registry import register_op
from .resilience import check_fault, dense_ref_cap, register_dense_ref
from .smem import split_cells, split_rows
from .tensor import (LAUNCH_FIELDS, ShardedMeta, ShardedSparseTensor,
                     SparseTensor, bucket_target, build_host, common_shape,
                     container_arrays, container_shapes, pad_arrays, pad_to)

MATVEC_LAYOUTS = ("ell", "sell", "dense", "dia")


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

def _rhs_tile(backend: str) -> int:
    """The static tile a multi-RHS launch pads k to: a lane row on the
    chip, 8 elsewhere."""
    return 128 if backend == "pallas" else 8


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


def _ell_launch(idx, cols, vc, blocks, xb, multi: bool, interpret: bool):
    """The ELL SpMV/SpMM kernel, split by block-row range to fit SMEM. The
    SpMV streams each row's ``vc`` valid tiles, so its valid-count table
    rides the split (and its SMEM) beside the slot tables."""
    if multi:
        return split_rows(
            lambda i, c: bsr_spmm_pallas(i, c, blocks, xb,
                                         interpret=interpret), (idx, cols))
    return split_rows(
        lambda i, c, v: bsr_spmv_pallas(i, c, v, blocks, xb,
                                        interpret=interpret),
        (idx, cols, vc))


def _sell_launch(cb, cc, cr, blocks, xb, n_br: int, multi: bool,
                 interpret: bool):
    """The SELL SpMV/SpMM kernel, its cell stream split to fit SMEM."""
    kern = bsr_spmm_sell_pallas if multi else bsr_spmv_sell_pallas
    return split_cells(
        lambda b, c, r: kern(b, c, r, blocks, xb, n_br, interpret=interpret),
        (cb, cc, cr), cr, n_br)


def _matvec_tiles(arrays, layout: str, xb: jax.Array, n_br: int,
                  backend: str, multi: bool,
                  offsets: Tuple[int, ...] = ()) -> jax.Array:
    """The product of one operand's launch arrays: on the blocked RHS as
    block rows (n_br, bs) or (n_br, bs, k) for ell/sell (the Pallas kernels
    on pallas/interpret, the jnp reference otherwise), on the flat RHS for
    dense, and for dia (the operand's ``offsets``) as its padded rows
    (n_row_pad,) or (n_row_pad, k). One body for the one-chip launch, each
    member of a stacked bucket and each shard."""
    if layout == "dense":
        return arrays["dense"] @ xb.astype(jnp.float32)
    if layout == "dia":
        diags = arrays["diags"]
        if backend == "jnp":
            return ref_dia_spmv(diags, xb, offsets)

        def one(x):
            return bsr_spmv_pallas(diags, x, offsets=offsets,
                                   interpret=(backend == "interpret"))
        # the DIA kernel is an SpMV: a multi-RHS launch runs it a column
        # at a time
        return jax.lax.map(one, xb.T).T if multi else one(xb)
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
    return _ell_launch(idx, cols, arrays["valid_counts"], blocks, xb, multi,
                       interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "rhs_tile"))
def _exec_matvec(st: SparseTensor, x: jax.Array, backend: str,
                 rhs_tile: int) -> jax.Array:
    """y = A @ x (or Y = A @ X for 2-D x) for an ell/sell/dense/dia
    operand."""
    _bump_trace("matvec")
    meta = st.meta
    multi = x.ndim == 2
    if meta.layout == "dense":
        return _matvec_tiles(st.arrays, "dense", x, 0, backend, multi)
    if meta.layout == "dia":
        return _matvec_tiles(st.arrays, "dia", x, 0, backend, multi,
                             meta.offsets)[: meta.shape[0]]
    if meta.layout not in ("ell", "sell"):
        raise ValueError(f"spmv/spmm cannot execute layout {meta.layout!r}")
    bs = meta.block_size
    n_bc = -(-meta.shape[1] // bs)
    xb = _block_x(x, meta.shape[1], n_bc, bs, rhs_tile)
    y = _matvec_tiles(st.arrays, meta.layout, xb, meta.n_block_rows, backend,
                      multi)
    if multi:
        k = x.shape[1]
        return y.reshape(y.shape[0] * y.shape[1], -1)[: meta.shape[0], :k]
    return y.reshape(-1)[: meta.shape[0]]


def _prepared_matvec(store: Optional[PreparedStore], a: CSR,
                     ck: Optional[str], schedule: Schedule,
                     layout: Optional[str] = None, sigma: int = SELL_SIGMA,
                     max_blocks: Optional[int] = None) -> SparseTensor:
    """``a`` prepared under ``schedule``, bucket-padded, through the one
    store key of a prepared matvec operand: the solo and bucket planners
    share it, so a tenant either warms is warm for both (the serving
    engine's ``resident(ck)`` slot bit predicts exactly this hit), and
    ``MutableMatrix`` reads it back to rebuild an entry. ``ck`` is the
    operand's content key where the caller already hashed it."""
    key = None if store is None else (
        "matvec", ck or content_key(a), schedule, layout, sigma, max_blocks)
    return _cached(store, key, lambda: SparseTensor.from_csr(
        a, schedule=schedule, layout=layout, sigma=sigma,
        max_blocks=max_blocks, shape_bucket=True,
        slack=getattr(a, "mutation_slack", 0)))


def _plan_matvec(operands, schedule: Optional[Schedule], backend: str, *,
                 op: str, block_size: int = 128, layout: str = "ell",
                 slice_height: int = 8, sigma: int = SELL_SIGMA,
                 max_blocks: Optional[int] = None,
                 store: Optional[PreparedStore] = None,
                 operand_key: Optional[str] = None, **_) -> Plan:
    (a,) = operands
    if isinstance(a, CSR):
        lay = None if layout == "ell" else layout
        sched = (schedule if schedule is not None
                 else SparseTensor.default_schedule(block_size, lay,
                                                   slice_height))
        # operand_key: the selector already hashed the matrix bytes for its
        # fingerprint memo — reuse it instead of a second O(nnz) sha1 pass
        st = _prepared_matvec(store, a, operand_key, sched, lay, sigma,
                              max_blocks)
    else:
        st = SparseTensor.wrap(a, schedule)
    if st.layout not in MATVEC_LAYOUTS:
        raise ValueError(f"{op} needs an ell/sell/dense/dia operand, got a "
                         f"{st.layout!r} SparseTensor")
    sched = schedule if schedule is not None else st.meta.schedule
    tile = _rhs_tile(backend)
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

    stream = None
    if st.layout == "ell" and ell_streams(st.block_size):
        st.ell_stream()         # counted now, not in a launch
        stream = st.ell_stream
    if st.layout == "dia":
        default_registry().set_gauge("kernel.dia.fill", st.to_host().fill())
    return Plan(op=op, schedule=sched, backend=backend, _run=run,
                operands=(st,),
                kernel_rhs=None if st.layout in ("dense", "dia") else "given",
                ell_stream=stream, dia=st.layout == "dia")


# ---------------------------------------------------------------------------
# spmv / spmm — stacked bucket launch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("layout", "backend"))
def _exec_matvec_stacked(arrays, xs: jax.Array, layout: str,
                         backend: str) -> jax.Array:
    """One launch for a whole same-schedule bucket: member axis leading.

    ``xs`` is (B, n_bc*bs) or (B, n_bc*bs, k); returns (B, n_br*bs[, k]).
    One jitted program, one dispatch, every member in flight, each through
    the one-operand body ``_matvec_tiles``: vmapped over the member axis on
    the jnp backend (and for dense), unrolled per member on
    interpret/pallas (padding made the member shapes identical).
    """
    _bump_trace("matvec_stacked")
    multi = xs.ndim == 3
    n_br = arrays["row_perm"].shape[1] if layout == "sell" else 0
    if layout == "dense":
        xb = xs
    else:
        bs = arrays["blocks"].shape[-1]
        xb = xs.reshape((xs.shape[0], xs.shape[1] // bs, bs) + xs.shape[2:])
    if backend == "jnp" or layout == "dense":
        y = jax.vmap(lambda a, x: _matvec_tiles(a, layout, x, n_br, backend,
                                                multi))(arrays, xb)
        return y if layout == "dense" else \
            y.reshape((y.shape[0], y.shape[1] * y.shape[2]) + y.shape[3:])
    # the kernels index one flat tile stack and one flat RHS: member b's
    # tile and column tables are offset into them, where slicing member b
    # out of the stack would copy its tiles for every launch
    nb, n_bc = arrays["blocks"].shape[1], xb.shape[1]
    step = {"block_indices": nb, "cell_block": nb, "block_cols": n_bc,
            "cell_col": n_bc}
    flat = {"blocks": arrays["blocks"].reshape((-1, bs, bs))}
    flat_x = xb.reshape((-1,) + xb.shape[2:])

    def member(b):
        a = {k: v[b] + b * step[k] if k in step else v[b]
             for k, v in arrays.items() if k != "blocks"}
        return _matvec_tiles({**a, **flat}, layout, flat_x, n_br, backend,
                             multi)

    y = jnp.stack([member(b) for b in range(xb.shape[0])])
    return y.reshape((y.shape[0], y.shape[1] * y.shape[2]) + y.shape[3:])


class _Member(NamedTuple):
    """A bucket member or shard as a launch sees it."""

    layout: str
    arrays: Dict          # leaf -> numpy (host) or jax (device) array
    shape: Tuple[int, int]    # true (unbucketed) shape
    tiles: int            # valid tiles an ELL SpMV streams (0 otherwise)


def _launch_member(m, schedule: Schedule, sigma: int) -> _Member:
    """``m`` as its launch arrays: a SparseTensor's device arrays, the
    bucketed host container a CSR prepares into, or a host container's
    own. Its valid tiles are counted here, once, on the host."""
    if isinstance(m, SparseTensor):
        return _Member(m.layout, m.arrays, m.true_shape,
                       m.ell_stream()[0] if m.layout == "ell" else 0)
    shape = tuple(m.shape)
    if isinstance(m, CSR):
        m = build_host(m, schedule, sigma=sigma, shape_bucket=True)[0]
    layout, arrays = container_arrays(m)
    return _Member(layout, arrays, shape,
                   int(arrays["valid_counts"].sum()) if layout == "ell"
                   else 0)


def _launch_width(layout: str, target: Dict, shapes, bs: int) -> int:
    """The padded RHS length a stack or mesh of members reads."""
    if layout == "dense":
        return int(target["dense"][1])
    return bucket_edge(max(-(-int(s[1]) // bs) for s in shapes)) * bs


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
                         store=None, member_keys=None) -> Dict:
    """The bucket's launch arrays, padded to the shapes its members share
    and stacked along a leading member axis.

    With a store and a key per member of an all-CSR bucket, the members
    are the store's device-resident ``SparseTensor``s (``_prepared_matvec``,
    the solo planner's key) and the stack is built on the device. This is
    what makes continuous batching (DESIGN.md §13) pay: under Zipf traffic
    the exact member composition of a bucket rarely repeats, so the
    whole-composition cache alone misses constantly — but a composition of
    warm members only costs a device-side stack, never the host container
    rebuild + re-upload. Otherwise each member pads on the host."""
    keys = list(member_keys) if member_keys is not None else []
    if (store is not None and len(keys) == len(members) and all(keys)
            and all(isinstance(m, CSR) for m in members)):
        members = [_prepared_matvec(store, m, ck, schedule, sigma=sigma)
                   for m, ck in zip(members, keys)]
    views = [_launch_member(m, schedule, sigma) for m in members]
    layouts = {v.layout for v in views}
    if len(layouts) != 1:
        raise ValueError(f"bucket mixes layouts {sorted(layouts)}; a bucket "
                         "shares one Schedule by construction")
    layout = layouts.pop()
    target = bucket_target([{k: v.arrays[k].shape for k in v.arrays}
                            for v in views], LAUNCH_FIELDS[layout])
    padded = [pad_arrays(v.arrays, target) for v in views]
    arrays = {}
    for k in target:
        leaves = [p[k] for p in padded]
        arrays[k] = (jnp.stack(leaves)
                     if any(isinstance(a, jax.Array) for a in leaves)
                     else jnp.asarray(np.stack(leaves)))
    bs = target["blocks"][-1] if "blocks" in target else schedule.block_size
    shapes = [v.shape for v in views]
    return {"arrays": arrays, "shapes": shapes, "layout": layout, "bs": bs,
            "width": _launch_width(layout, target, shapes, bs),
            "stream_tiles": sum(v.tiles for v in views)}


def _plan_matvec_rhs_stacked(members: List, schedule: Schedule,
                             backend: str, *, op: str, sigma: int, store,
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
                         sigma=sigma, store=store,
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
            k_pad = 1 << (k - 1).bit_length()
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
                kernel_rhs=inner.kernel_rhs and "stacked",
                ell_stream=inner.ell_stream)


def _built_stream(built: Dict):
    """``Plan.ell_stream`` of a stacked launch: its members' valid tiles,
    counted on the host as the stack was built, and the stacked grid's
    slots; None where its launch does not stream."""
    if built["layout"] != "ell" or not ell_streams(built["bs"]):
        return None
    counts = (built["stream_tiles"],
              int(built["arrays"]["block_indices"].size))
    return lambda: counts


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


def _no_dia(schedules, launch: str) -> None:
    """Stacked buckets and shard meshes take no DIA operand: each DIA
    operand's diagonals fix its own program. The selector never picks one;
    only ``plan()`` launches an operand as DIA."""
    if any(s is not None and s.layout == "dia" for s in schedules):
        raise ValueError(f"a {launch} takes no dia operand; plan() it alone")


def _plan_matvec_bucket(members: List, schedule: Schedule, backend: str, *,
                        op: str = "spmv", sigma: int = SELL_SIGMA,
                        store: Optional[PreparedStore] = None,
                        member_keys=None, **_) -> Plan:
    _no_dia([schedule], "stacked bucket")
    if (store is not None and member_keys is not None
            and all(member_keys) and len(set(member_keys)) == 1
            and all(isinstance(m, CSR) for m in members)):
        # content-pure bucket (affinity slot fill makes these the common
        # case under Zipf traffic): one prepared container, RHS columns
        # stacked — no member stacking, no composition cache entry
        return _plan_matvec_rhs_stacked(
            members, schedule, backend, op=op, sigma=sigma, store=store,
            member_keys=member_keys)
    key = None if store is None else _members_key(
        "matvec_bucket", members, schedule, extra=(op, sigma),
        member_keys=member_keys)
    b_pad = bucket_edge(len(members))
    built = _cached(store, key, lambda: _pad_member_axis(
        _build_matvec_bucket(members, schedule, sigma, store=store,
                             member_keys=member_keys), b_pad))
    arrays, shapes = built["arrays"], built["shapes"]
    layout, width = built["layout"], built["width"]
    tile = _rhs_tile(backend)

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
                kernel_rhs=None if layout == "dense" else "given",
                ell_stream=_built_stream(built))


# ---------------------------------------------------------------------------
# spmv / spmm — sharded distributed launch (DESIGN.md §10)
# ---------------------------------------------------------------------------

_SHARDED_EXECS: dict = {}

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
    P = jax.sharding.PartitionSpec

    def local(arrays, xb):
        # local leading dim is 1: this slot's single shard
        a = {k: v[0] for k, v in arrays.items()}
        n_br = a["row_perm"].shape[0] if layout == "sell" else 0
        if layout != "dense":
            bs = a["blocks"].shape[-1]
            xb = xb.reshape((xb.shape[0] // bs, bs) + xb.shape[1:])
        y = _matvec_tiles(a, layout, xb, n_br, backend, multi)
        if layout != "dense":
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


def _build_on_mesh(members: List, schedule: Schedule, sigma: int,
                   mesh) -> Dict:
    """The shards' launch arrays, stacked along a leading axis sharded over
    the mesh, built one shard at a time: each shard's container is built
    on the host, padded to the shapes every shard shares, uploaded to its
    own device and dropped before the next starts, so the host holds one
    shard's build at a time. The shared shapes come first, from each
    shard's tile counts (``container_shapes``), without building a tile."""
    layout = ("dense" if schedule.backend == "dense" else
              "sell" if schedule.layout == "sell" else "ell")
    shapes = [container_shapes(m, schedule, sigma=sigma, shape_bucket=True)
              if isinstance(m, CSR) else
              {k: tuple(v.shape) for k, v in m.arrays.items()}
              for m in members]
    target = bucket_target(shapes, LAUNCH_FIELDS[layout])
    width = _launch_width(layout, target, [m.shape for m in members],
                          schedule.block_size)
    devices = list(mesh.devices.flat)
    pieces: Dict[str, List] = {k: [] for k in target}
    tiles = 0
    for i, (m, dev) in enumerate(zip(members, devices)):
        with obs_trace.span("shard_build", shard=i, n_shards=len(members)):
            view = _launch_member(m, schedule, sigma)
            placed = {k: jax.device_put(v[None], dev) for k, v in
                      pad_arrays(view.arrays, target).items()}
            jax.block_until_ready(placed)
            tiles += view.tiles
            del view
        for k, v in placed.items():
            pieces[k].append(v)
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(SHARD_AXIS))
    arrays = {k: jax.make_array_from_single_device_arrays(
        (len(members),) + target[k], sharding, pieces[k]) for k in target}
    return {"arrays": arrays, "layout": layout, "width": int(width),
            "stream_tiles": tiles}


def _plan_matvec_sharded(operands, schedules, backend: str, *, op: str,
                         part=None, shard_csrs: Optional[List] = None,
                         mesh=None, sigma: int = SELL_SIGMA,
                         store: Optional[PreparedStore] = None,
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
    _no_dia(schedules, "sharded launch")
    n_shards = len(bounds) - 1
    true_rows = tuple(int(bounds[i + 1] - bounds[i]) for i in range(n_shards))
    n_cols = int(shape[1])
    tile = _rhs_tile(backend)
    uniform = len(set(schedules)) == 1 and schedules[0] is not None
    default_registry().set_gauge("shard.count", n_shards)

    if uniform and mesh is None:
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
            strategy, bounds, tuple(schedules), sigma, n_shards)
        members = list(sst.shards) if sst is not None else shard_csrs
        built = _cached(store, stack_key, lambda: _build_on_mesh(
            members, schedules[0], sigma, mesh))
        arrays, width, layout = built["arrays"], built["width"], built["layout"]
        slots = list(mesh.devices.flat)
        homes = [{d} for d in slots]
        r_max = max(true_rows)
        kernel_rhs = None if layout == "dense" else "given"
        stream = _built_stream({**built, "bs": schedules[0].block_size})

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
                bounds, tuple(schedules), sigma)

            def build_shards():
                shards = []
                for i, (c, s, dev) in enumerate(zip(shard_csrs, schedules,
                                                    shard_devs)):
                    with obs_trace.span("shard_build", shard=i,
                                        n_shards=n_shards):
                        st = SparseTensor.from_csr(
                            c, schedule=s, sigma=sigma, shape_bucket=True,
                            device=dev)
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
        sub = [_plan_matvec((st,), s, backend, op=op)
               for st, s in zip(placed, schedules)]
        kernel_rhs = None if all(q.kernel_rhs is None for q in sub) \
            else "given"
        streams = [q.ell_stream for q in sub if q.ell_stream is not None]

        def stream():
            counts = [f() for f in streams]
            return (sum(c[0] for c in counts), sum(c[1] for c in counts))

        if not streams:
            stream = None

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
                kernel_rhs=kernel_rhs, ell_stream=stream)


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


def _edge_pad(arr: np.ndarray, fill=0) -> np.ndarray:
    """A host array padded to its own bucket-edged shape with ``fill``."""
    return pad_to(arr, common_shape([arr.shape]), fill)


def _stack_padded(arrs: Sequence[np.ndarray], fills) -> jax.Array:
    """Host arrays padded to their shared bucket-edged shape, each with its
    own fill, and stacked along a new leading member axis."""
    shape = common_shape([a.shape for a in arrs])
    return jnp.asarray(np.stack([pad_to(a, shape, f)
                                 for a, f in zip(arrs, fills)]))


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
                    store: Optional[PreparedStore],
                    operand_key: Optional[str] = None):
    """Device-staged, bucket-padded spgemm symbolic-phase products; cached
    in the PreparedStore keyed by exact matrix bytes."""
    key = None
    if store is not None and isinstance(a, CSR) and isinstance(b, CSR):
        key = ("spgemm", schedule.block_size, schedule.layout,
               operand_key or content_key(a), content_key(b))
    return _cached(store, key, lambda: _build_spgemm(a, b, schedule))


def _build_spgemm(a, b, schedule: Schedule):
    h = _spgemm_host_products(a, b, schedule)
    n_c, bs = h["n_c"], h["bs"]
    if h["mode"] == "cells":
        n_c_pad = bucket_edge(n_c)
        ca = _edge_pad(h["cell_a"], h["zero_a"])
        cb = _edge_pad(h["cell_b"], h["zero_b"])
        cc = _edge_pad(h["cell_c"], max(n_c - 1, 0))
        dev = (jnp.asarray(ca), jnp.asarray(cb), jnp.asarray(cc),
               jnp.asarray(_edge_pad(h["a_blocks"])),
               jnp.asarray(_edge_pad(h["b_blocks"])))
        prep = {"mode": "cells", "dev": dev, "n_c_pad": n_c_pad}
    else:
        pa, pb = h["pair_a"], h["pair_b"]
        if pa.size:
            pa = _edge_pad(pa, h["zero_a"])
            pb = _edge_pad(pb, h["zero_b"])
            h["a_blocks"] = _edge_pad(h["a_blocks"])
            h["b_blocks"] = _edge_pad(h["b_blocks"])
        dev = (jnp.asarray(pa), jnp.asarray(pb),
               jnp.asarray(h["a_blocks"]), jnp.asarray(h["b_blocks"]))
        prep = {"mode": "pairs", "dev": dev, "n_c_pad": n_c}
    prep.update({"c_ptrs": h["c_ptrs"], "c_cols": h["c_cols"], "n_c": n_c,
                 "out_shape": h["out_shape"], "bs": bs})
    return prep


def _plan_spgemm(operands, schedule: Optional[Schedule], backend: str, *,
                 block_size: int = 128,
                 store: Optional[PreparedStore] = None,
                 operand_key: Optional[str] = None, **_) -> Plan:
    a, b = operands
    if schedule is None:
        schedule = Schedule("bsr", block_size, 1.0)
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path; dispatch a "
                         "dense matmul instead")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"inner dims mismatch {a.shape} @ {b.shape}")
    prep = _prepare_spgemm(a, b, schedule, store, operand_key)
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
        "spgemm_bucket", members, schedule, member_keys=member_keys)

    def build():
        hs = [_spgemm_host_products(a, b, schedule) for a, b in pairs]
        mode = hs[0]["mode"]
        zeros = [0] * len(hs)
        if mode == "cells":
            stacked = {
                "cell_a": _stack_padded([h["cell_a"] for h in hs],
                                        [h["zero_a"] for h in hs]),
                "cell_b": _stack_padded([h["cell_b"] for h in hs],
                                        [h["zero_b"] for h in hs]),
                # pad cells accumulate zero products onto the member's LAST
                # output block, keeping cell_c nondecreasing
                "cell_c": _stack_padded([h["cell_c"] for h in hs],
                                        [max(h["n_c"] - 1, 0) for h in hs]),
            }
            n_c_pad = bucket_edge(max(h["n_c"] for h in hs))
        else:
            stacked = {
                "pair_a": _stack_padded([h["pair_a"] for h in hs],
                                        [h["zero_a"] for h in hs]),
                "pair_b": _stack_padded([h["pair_b"] for h in hs],
                                        [h["zero_b"] for h in hs]),
            }
            n_c_pad = 0
        stacked["a_blocks"] = _stack_padded([h["a_blocks"] for h in hs],
                                            zeros)
        stacked["b_blocks"] = _stack_padded([h["b_blocks"] for h in hs],
                                            zeros)
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
                   store: Optional[PreparedStore],
                   operand_key: Optional[str] = None):
    key = None
    if store is not None and isinstance(a, CSR) and isinstance(b, CSR):
        # layout is irrelevant to spadd prep (only block_size is consumed),
        # so the key deliberately omits it: sell- and ell-schedule plans of
        # the same block size share one cached entry.
        key = ("spadd", schedule.block_size, operand_key or content_key(a),
               content_key(b))
    return _cached(store, key, lambda: _build_spadd(a, b, schedule))


def _build_spadd(a, b, schedule: Schedule):
    h = _spadd_host_products(a, b, schedule)
    return {"dev": (jnp.asarray(_edge_pad(h["ia"], h["zero_a"])),
                    jnp.asarray(_edge_pad(h["ib"], h["zero_b"])),
                    jnp.asarray(_edge_pad(h["a_blocks"])),
                    jnp.asarray(_edge_pad(h["b_blocks"]))),
            "c_ptrs": h["c_ptrs"], "c_cols": h["c_cols"], "n_c": h["n_c"],
            "out_shape": h["out_shape"], "bs": h["bs"]}


def _plan_spadd(operands, schedule: Optional[Schedule], backend: str, *,
                block_size: int = 128,
                store: Optional[PreparedStore] = None,
                operand_key: Optional[str] = None, **_) -> Plan:
    a, b = operands
    if schedule is None:
        schedule = Schedule("bsr", block_size, 1.0)
    if schedule.backend == "dense":
        raise ValueError("dense schedules have no BSR path; dispatch a "
                         "dense matmul instead")
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch {a.shape} vs {b.shape}")
    prep = _prepare_spadd(a, b, schedule, store, operand_key)
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
        "spadd_bucket", members, schedule, member_keys=member_keys)

    def build():
        hs = [_spadd_host_products(a, b, schedule) for a, b in pairs]
        zeros = [0] * len(hs)
        stacked = {
            "ia": _stack_padded([h["ia"] for h in hs],
                                [h["zero_a"] for h in hs]),
            "ib": _stack_padded([h["ib"] for h in hs],
                                [h["zero_b"] for h in hs]),
            "a_blocks": _stack_padded([h["a_blocks"] for h in hs], zeros),
            "b_blocks": _stack_padded([h["b_blocks"] for h in hs], zeros),
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
