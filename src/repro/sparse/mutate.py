"""Dynamic sparsity: versioned mutable matrices without rebuilds (DESIGN.md §14).

The rest of the stack treats a sparse operand as frozen — ``content_key``
hashes the CSR once, the PreparedStore caches containers under it forever,
and jitted executors bake the container's avals into their trace. Iterative
solvers and streaming-graph workloads break that assumption: the same
matrix is reused thousands of times *and* mutated between reuses. This
module makes mutation a first-class path with three rungs of degradation:

1. **Value-only fast path** — ``SparseTensor.apply_delta`` rebinds the
   device leaves to same-shape ``.at[].set/.add`` scatters. The pytree
   structure and every aval are unchanged, so warm plans keep their traces
   (no host re-prep, no retrace); ``generation`` bumps outside the pytree.
2. **Structural inserts within slack** — ``from_csr(..., slack=)`` reserves
   extra index slots per block-row (ELL) / per slice row (SELL) plus a pool
   of spare all-zero blocks. An insert claims a spare block, points a free
   slot at it, and scatters the values in — still no rebuild, no retrace.
3. **Epoch swap when slack is exhausted** — ``MutableMatrix.apply_delta``
   keeps the old-generation entry serving live plans, rebuilds a fresh
   container from the (already updated) host CSR, and publishes it under
   the new version key. Counted, traced, never a mid-request failure.

Versioning rides on ``content_key``: ``MutableMatrix`` pins
``csr.version_key = f"{base_sha1}@g{generation}"`` so every store key and
selector fingerprint formed after a mutation names the new generation,
while entries keyed under the old generation are popped by
``PreparedStore.pop_matching`` and either rekeyed in place (matvec
containers, rung 1/2), epoch-swapped (rung 3), or dropped (derived
products — spgemm/spadd symbolic stages, stacked buckets, shard stacks —
whose staged arrays genuinely depend on the old values). Sibling operands'
entries are never touched: invalidation is sub-matrix granular.

Fault injection covers the whole path: the ``delta-apply`` site fails the
in-place rekey (forcing an epoch swap) and ``slack-overflow`` simulates
rung-3 exhaustion; both are recovered by the swap, keeping the chaos-gate
identity ``fired == recovered``.

A q<1 ELL schedule truncates tail blocks out of an immutable container;
for mutable tensors that would make a delta touching a truncated position
indistinguishable from an insert — it would land in slack with only the
delta's values, silently dropping the base values. ``from_csr`` therefore
forces full-quantile prep (``full_rows=True``) whenever ``slack > 0``: a
mutable container always holds every block, regardless of the schedule's
``ell_quantile``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Optional, Tuple, Union

import jax
import numpy as np

from ..core.csr import CSR, ELLBSR, SELLBSR
from ..obs import default_registry, ordered, scoped_int
from ..obs import trace as obs_trace
from .prepared import PreparedStore, raw_content_key
from .resilience import (GUARDED_EXCEPTIONS, InjectedFault, _note_handled,
                         check_fault, fault_fired, note_recovery)
from .tensor import LANES, SparseTensor, pad_arrays

# Spare all-zero blocks reserved per unit of slack: ``slack`` bounds
# inserts per block-row, SPARE_FACTOR * slack bounds them matrix-wide.
SPARE_FACTOR = 4


class SlackOverflow(RuntimeError):
    """A structural insert found no free slot / spare block; the caller
    must epoch-swap (rebuild the container) instead."""


@dataclasses.dataclass(frozen=True)
class Delta:
    """A batch of point updates ``A[rows[i], cols[i]] <- / += vals[i]``.

    ``mode="set"`` overwrites, ``mode="add"`` accumulates. Positions must
    be unique within one delta (duplicate positions make "set" order
    dependent); positions absent from the matrix are structural inserts.
    """

    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    mode: str = "set"

    def __post_init__(self):
        if self.mode not in ("set", "add"):
            raise ValueError(f"delta mode {self.mode!r}; one of ('set', 'add')")

    @property
    def size(self) -> int:
        return int(np.asarray(self.rows).size)


DeltaLike = Union[Delta, Tuple]


def as_delta(delta: DeltaLike) -> Delta:
    """Coerce ``Delta`` or a ``(rows, cols, vals[, mode])`` tuple."""
    if isinstance(delta, Delta):
        return delta
    rows, cols, vals = delta[0], delta[1], delta[2]
    mode = delta[3] if len(delta) > 3 else "set"
    return Delta(np.asarray(rows), np.asarray(cols), np.asarray(vals), mode)


def _delta_arrays(delta: Delta) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    rows = np.asarray(delta.rows, np.int64).reshape(-1)
    cols = np.asarray(delta.cols, np.int64).reshape(-1)
    vals = np.asarray(delta.vals, np.float32).reshape(-1)
    if not (rows.size == cols.size == vals.size):
        raise ValueError(f"delta arrays disagree: {rows.size} rows, "
                         f"{cols.size} cols, {vals.size} vals")
    return rows, cols, vals


# ---------------------------------------------------------------------------
# Slack reservation (construction side, called by SparseTensor.from_csr)
# ---------------------------------------------------------------------------

def _grow_blocks(blocks: np.ndarray, spare_n: int
                 ) -> Tuple[np.ndarray, int, list]:
    """Append ``spare_n`` all-zero spare slots between the real blocks and
    the trailing zero block; returns (new_blocks, new_zero_idx, spare_pool).
    Bucket padding later appends *after* the zero block, so the pool's
    indices survive ``pad_container_to_bucket`` untouched."""
    nb = blocks.shape[0] - 1            # real blocks; zero block lives at nb
    bs = blocks.shape[1]
    out = np.zeros((nb + spare_n + 1, bs, bs), np.float32)
    out[:nb] = blocks[:nb]
    return out, nb + spare_n, list(range(nb, nb + spare_n))


def add_slack_ell(ell: ELLBSR, slack: int) -> Tuple[ELLBSR, list]:
    """Widen the slot grid by ``slack`` columns and reserve the spare-block
    pool; numerics unchanged (new slots point at the relocated zero block)."""
    old_zero = ell.blocks.shape[0] - 1
    blocks, zero, spare = _grow_blocks(ell.blocks, max(slack, 1) * SPARE_FACTOR)
    n_br, mb = ell.block_indices.shape
    grid = {"block_indices": np.where(ell.block_indices == old_zero, zero,
                                      ell.block_indices).astype(np.int32),
            "block_cols": ell.block_cols.astype(np.int32)}
    grid = pad_arrays(grid, dict.fromkeys(grid, (n_br, mb + slack)), zero)
    return (ELLBSR(grid["block_indices"], grid["block_cols"], blocks,
                   ell.shape, ell.block_size, ell.valid_counts.copy()), spare)


def add_slack_sell(sell: SELLBSR, slack: int) -> Tuple[SELLBSR, list]:
    """Widen every slice by ``slack`` cells (re-spacing the flat cell
    arrays) and reserve the spare-block pool; numerics unchanged."""
    old_zero = sell.blocks.shape[0] - 1
    blocks, zero, spare = _grow_blocks(sell.blocks,
                                       max(slack, 1) * SPARE_FACTOR)
    C, n_br = sell.slice_height, sell.n_block_rows
    old_sw = sell.slice_widths.astype(np.int64)
    new_sw = old_sw + slack
    old_cpr = np.repeat(old_sw, C)[:n_br]
    new_cpr = np.repeat(new_sw, C)[:n_br]
    old_starts = np.concatenate([[0], np.cumsum(old_cpr)])
    new_starts = np.concatenate([[0], np.cumsum(new_cpr)])
    n_cells = int(new_starts[-1])
    cb = np.full(n_cells, zero, np.int32)
    cc = np.zeros(n_cells, np.int32)
    cr = np.repeat(np.arange(n_br, dtype=np.int64),
                   new_cpr).astype(np.int32)
    # Old cell (row p, slot j) lands at new_starts[p] + j: valid cells stay
    # a contiguous prefix of each row's span, slack cells trail it.
    old_n = int(old_starts[-1])
    rows_old = np.repeat(np.arange(n_br, dtype=np.int64), old_cpr)
    slots_old = np.arange(old_n, dtype=np.int64) - np.repeat(old_starts[:-1],
                                                             old_cpr)
    dest = new_starts[rows_old] + slots_old
    old_cb = sell.cell_block[:old_n]
    cb[dest] = np.where(old_cb == old_zero, zero, old_cb)
    cc[dest] = sell.cell_col[:old_n]
    return (SELLBSR(cb, cc, cr, sell.row_perm.copy(),
                    new_sw.astype(np.int32), blocks, sell.shape,
                    sell.block_size, C, sell.sigma), spare)


def reserve_slack(container, slack: int):
    """Dispatch ``from_csr(..., slack=)`` per layout; (container, spare)."""
    if slack <= 0:
        return container, []
    if isinstance(container, ELLBSR):
        return add_slack_ell(container, int(slack))
    if isinstance(container, SELLBSR):
        return add_slack_sell(container, int(slack))
    return container, []


# ---------------------------------------------------------------------------
# Delta application on a prepared SparseTensor (rungs 1 and 2)
# ---------------------------------------------------------------------------

def _ensure_mut(st: SparseTensor) -> Dict:
    """Lazily built host bookkeeping of the delta path: the (block-row,
    block-col) -> block-index map, and per-row free-slot cursors. Valid
    slots are a contiguous prefix of each row's span by construction, and
    inserts keep it that way."""
    if st._mut is not None:
        return st._mut
    host = st.to_host()
    zero = st._zero_idx if st._zero_idx is not None \
        else int(host.blocks.shape[0]) - 1
    if st.layout == "ell":
        bi, bc = host.block_indices, host.block_cols
        # Valid slots are the contiguous prefix valid_counts names; slots
        # beyond (including bucket-pad slots) all point at the zero block.
        valid = (np.arange(bi.shape[1], dtype=np.int64)[None, :]
                 < host.valid_counts.astype(np.int64)[:, None])
        brs, slots = np.nonzero(valid)
        bmap = {(int(b), int(c)): int(k)
                for b, c, k in zip(brs, bc[brs, slots], bi[brs, slots])}
        st._mut = {"zero": zero, "block_map": bmap,
                   "row_next": valid.sum(axis=1).astype(np.int64)}
    elif st.layout == "sell":
        C = host.slice_height
        n_br = host.n_block_rows
        cpr = np.repeat(host.slice_widths.astype(np.int64), C)[:n_br]
        starts = np.concatenate([[0], np.cumsum(cpr)])
        n = int(starts[-1])                 # bucket-pad cells live beyond
        cb = host.cell_block[:n]
        valid = cb != zero
        rows_sorted = host.cell_row[:n].astype(np.int64)
        inv = np.empty(n_br, np.int64)
        inv[host.row_perm.astype(np.int64)] = np.arange(n_br)
        orig = host.row_perm.astype(np.int64)[rows_sorted[valid]]
        bmap = {(int(b), int(c)): int(k)
                for b, c, k in zip(orig, host.cell_col[:n][valid], cb[valid])}
        st._mut = {"zero": zero, "block_map": bmap, "inv": inv,
                   "starts": starts, "cpr": cpr,
                   "used": np.bincount(rows_sorted[valid],
                                       minlength=n_br).astype(np.int64)}
    elif st.layout == "bsr":
        bpr = np.diff(host.block_ptrs)
        brs = np.repeat(np.arange(bpr.size, dtype=np.int64), bpr)
        st._mut = {"zero": None, "block_map": {
            (int(b), int(c)): k
            for k, (b, c) in enumerate(zip(brs, host.block_cols))}}
    else:
        st._mut = {"zero": None, "block_map": {}}
    return st._mut


def _insert_blocks(st: SparseTensor, mut: Dict, brs: np.ndarray,
                   bcs: np.ndarray, missing: list, ks: np.ndarray) -> None:
    """Claim spare blocks + free slots for the block positions in
    ``missing``; raises SlackOverflow (before mutating anything) when the
    container cannot absorb them."""
    if st.layout not in ("ell", "sell"):
        raise SlackOverflow(
            f"{st.layout} container cannot absorb structural inserts")
    new_blocks: Dict[Tuple[int, int], list] = {}
    for i in missing:
        new_blocks.setdefault((int(brs[i]), int(bcs[i])), []).append(i)
    if len(new_blocks) > len(st.spare_blocks):
        raise SlackOverflow(f"need {len(new_blocks)} spare blocks, "
                            f"pool has {len(st.spare_blocks)}")
    # Validate per-row capacity in full before claiming anything, so an
    # overflowing delta leaves the tensor untouched for the epoch swap.
    if st.layout == "ell":
        cap = st.arrays["block_indices"].shape[1]
        need: Dict[int, int] = {}
        for br, _ in new_blocks:
            need[br] = need.get(br, 0) + 1
        for br, cnt in need.items():
            if int(mut["row_next"][br]) + cnt > cap:
                raise SlackOverflow(f"block-row {br} slot slack exhausted")
        at = []
        for (br, bc), idxs in new_blocks.items():
            k = st.spare_blocks.pop()
            slot = int(mut["row_next"][br])
            mut["row_next"][br] += 1
            mut["block_map"][(br, bc)] = k
            for i in idxs:
                ks[i] = k
            at.append((br, slot, bc, k))
        br_a = np.array([a[0] for a in at], np.int64)
        sl_a = np.array([a[1] for a in at], np.int64)
        bc_a = np.array([a[2] for a in at], np.int32)
        k_a = np.array([a[3] for a in at], np.int32)
        st.arrays["block_indices"] = \
            st.arrays["block_indices"].at[(br_a, sl_a)].set(k_a)
        st.arrays["block_cols"] = \
            st.arrays["block_cols"].at[(br_a, sl_a)].set(bc_a)
        st.arrays["valid_counts"] = \
            st.arrays["valid_counts"].at[br_a].add(1)
        if st._stream_tiles is not None:
            st._stream_tiles += len(br_a)
        host = st._host
        if host is not None:
            host.block_indices[br_a, sl_a] = k_a
            host.block_cols[br_a, sl_a] = bc_a
            np.add.at(host.valid_counts, br_a, 1)
    else:
        need = {}
        for br, _ in new_blocks:
            p = int(mut["inv"][br])
            need[p] = need.get(p, 0) + 1
        for p, cnt in need.items():
            if int(mut["used"][p]) + cnt > int(mut["cpr"][p]):
                raise SlackOverflow(f"slice row {p} cell slack exhausted")
        at = []
        for (br, bc), idxs in new_blocks.items():
            k = st.spare_blocks.pop()
            p = int(mut["inv"][br])
            t = int(mut["starts"][p]) + int(mut["used"][p])
            mut["used"][p] += 1
            mut["block_map"][(br, bc)] = k
            for i in idxs:
                ks[i] = k
            at.append((t, bc, k))
        t_a = np.array([a[0] for a in at], np.int64)
        bc_a = np.array([a[1] for a in at], np.int32)
        k_a = np.array([a[2] for a in at], np.int32)
        st.arrays["cell_block"] = st.arrays["cell_block"].at[t_a].set(k_a)
        st.arrays["cell_col"] = st.arrays["cell_col"].at[t_a].set(bc_a)
        host = st._host
        if host is not None:
            host.cell_block[t_a] = k_a
            host.cell_col[t_a] = bc_a


# Jitted, donating scatters: eager .at[].set pays per-op dispatch (~ms)
# and a functional copy of the whole leaf; with the input buffer donated
# the compiled update aliases in place, so a value delta costs O(delta)
# regardless of container size. Donation is safe because the tensor is the
# leaf's only holder — plan closures capture the SparseTensor object and
# read .arrays at call time, and every derived product (stacked buckets,
# staged spgemm) copies rather than aliases.
@functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=0)
def _scatter2(arr, rows, cols, vals, mode: str):
    ref = arr.at[(rows, cols)]
    return ref.add(vals) if mode == "add" else ref.set(vals)


@functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=0)
def _scatter3(arr, ks, rr, cc, vals, mode: str):
    ref = arr.at[(ks, rr, cc)]
    return ref.add(vals) if mode == "add" else ref.set(vals)


def _dia_delta(st: SparseTensor, rows, cols, vals, mode: str) -> None:
    """A delta on a DIA operand: every position on one of its diagonals
    takes its value in place, stored or not; a position off them raises
    SlackOverflow before anything changes, so the caller epoch-swaps."""
    offsets = np.asarray(st.meta.offsets, np.int64)
    diag = cols - rows
    ks = np.searchsorted(offsets, diag)
    if not offsets.size or not np.array_equal(
            offsets[np.minimum(ks, offsets.size - 1)], diag):
        raise SlackOverflow("delta position off the dia operand's diagonals")
    rr, cc = rows // LANES, rows % LANES
    st.arrays["diags"] = _scatter3(st.arrays["diags"], ks, rr, cc, vals,
                                   mode)
    if st._host is not None:
        if mode == "add":
            np.add.at(st._host.diags, (ks, rr, cc), vals)
        else:
            st._host.diags[ks, rr, cc] = vals


def apply_delta_to_tensor(st: SparseTensor, delta: DeltaLike) -> SparseTensor:
    """In-place delta on a prepared container (``SparseTensor.apply_delta``
    body). Same-shape leaf rebinds only — warm jitted executors see the
    same treedef and avals, so the update costs zero retraces."""
    delta = as_delta(delta)
    rows, cols, vals = _delta_arrays(delta)
    if rows.size == 0:
        st.generation += 1
        return st
    n, m = st.true_shape
    if (rows.min() < 0 or rows.max() >= n
            or cols.min() < 0 or cols.max() >= m):
        raise ValueError(f"delta position outside {st.true_shape}")
    if st.layout == "dia":
        _dia_delta(st, rows, cols, vals, delta.mode)
        st.generation += 1
        return st
    if st.layout == "dense":
        # jitted scatter: eager .at[].set pays per-op dispatch (~ms); the
        # compiled update is the value-churn fast path's actual cost model
        st.arrays["dense"] = _scatter2(st.arrays["dense"], rows, cols,
                                       vals, delta.mode)
        if st._host is not None:
            if delta.mode == "add":
                np.add.at(st._host, (rows, cols), vals)
            else:
                st._host[rows, cols] = vals
        st.generation += 1
        return st
    bs = st.meta.block_size
    mut = _ensure_mut(st)
    bmap = mut["block_map"]
    brs, bcs = rows // bs, cols // bs
    ks = np.empty(rows.size, np.int64)
    missing = []
    for i in range(rows.size):
        k = bmap.get((int(brs[i]), int(bcs[i])))
        if k is None:
            missing.append(i)
        else:
            ks[i] = k
    if missing:
        _insert_blocks(st, mut, brs, bcs, missing, ks)
    rr, cc = rows % bs, cols % bs
    st.arrays["blocks"] = _scatter3(st.arrays["blocks"], ks, rr, cc,
                                    vals, delta.mode)
    host = st._host
    if host is not None:
        if delta.mode == "add":
            np.add.at(host.blocks, (ks, rr, cc), vals)
        else:
            host.blocks[ks, rr, cc] = vals
    st.generation += 1
    return st


# ---------------------------------------------------------------------------
# Host CSR update (the new-generation ground truth)
# ---------------------------------------------------------------------------

def _locate(csr: CSR, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """nnz index per delta position, -1 where the position is absent.

    CSR entries are sorted by (row, col), so one vectorized searchsorted
    over flattened ``row*m + col`` keys resolves the whole delta. The key
    array is O(nnz) to build, so it is cached on the CSR and reused for
    every value-only delta (the streaming hot path); any structural change
    alters nnz and invalidates the stamp."""
    m = csr.shape[1]
    cached = getattr(csr, "_locate_keys", None)
    if cached is None or cached[0] != csr.nnz:
        keys = (np.repeat(np.arange(csr.shape[0], dtype=np.int64),
                          np.diff(csr.row_ptrs)) * m
                + csr.col_idxs.astype(np.int64))
        cached = (csr.nnz, keys)
        csr._locate_keys = cached
    keys = cached[1]
    if keys.size == 0:
        return np.full(rows.size, -1, np.int64)
    q = rows * m + cols
    pos = np.searchsorted(keys, q)
    hit = (pos < keys.size) & (keys[np.minimum(pos, keys.size - 1)] == q)
    return np.where(hit, pos, -1).astype(np.int64)


def apply_delta_csr(csr: CSR, delta: Delta) -> int:
    """Apply ``delta`` to the host CSR in place; returns the number of
    structural (previously absent) positions. Structural inserts rebuild
    the index arrays host-side — O(nnz) bookkeeping that the device
    containers sidestep via slack."""
    rows, cols, vals = _delta_arrays(delta)
    if rows.size == 0:
        return 0
    n, m = csr.shape
    if (rows.min() < 0 or rows.max() >= n
            or cols.min() < 0 or cols.max() >= m):
        raise ValueError(f"delta position outside {csr.shape}")
    idx = _locate(csr, rows, cols)
    have = idx >= 0
    if delta.mode == "add":
        np.add.at(csr.nnz_vals, idx[have], vals[have])
    else:
        csr.nnz_vals[idx[have]] = vals[have]
    n_new = int((~have).sum())
    if n_new:
        lens = np.diff(csr.row_ptrs)
        merged = CSR.from_coo(
            np.concatenate([np.repeat(np.arange(n, dtype=np.int64), lens),
                            rows[~have]]),
            np.concatenate([csr.col_idxs.astype(np.int64), cols[~have]]),
            np.concatenate([csr.nnz_vals, vals[~have]]), csr.shape)
        csr.row_ptrs = merged.row_ptrs
        csr.col_idxs = merged.col_idxs
        csr.nnz_vals = merged.nnz_vals
    return n_new


# ---------------------------------------------------------------------------
# MutableMatrix: versioning + store invalidation + epoch swap (rung 3)
# ---------------------------------------------------------------------------

class MutableMatrix:
    """A CSR whose mutations flow through the PreparedStore correctly.

    Wrapping pins two attributes on the CSR that the rest of the stack
    reads with ``getattr``: ``version_key`` (so ``content_key`` returns
    ``"<base>@g<gen>"`` and every store key / fingerprint formed afterwards
    names this generation) and ``mutation_slack`` (so every planner's prep
    path builds slack-reserving containers). ``apply_delta`` then:

    1. updates the host CSR (the new-generation ground truth),
    2. bumps ``generation`` and re-pins ``version_key``,
    3. pops every store entry referencing the old generation and either
       rekeys it in place (matvec containers take the delta on device),
       epoch-swaps it (slack exhausted or fault injected: rebuild from the
       updated CSR; live plans keep serving the old tensor object), or
       drops it (derived products re-stage on next use),
    4. notifies the DriftMonitor (if attached) to re-fingerprint.
    """

    deltas = scoped_int("deltas")
    value_updates = scoped_int("value_updates")
    structural_inserts = scoped_int("structural_inserts")
    epoch_swaps = scoped_int("epoch_swaps")
    rebuilds = scoped_int("rebuilds")
    rekeyed_entries = scoped_int("rekeyed_entries")
    dropped_entries = scoped_int("dropped_entries")

    def __init__(self, csr: CSR, store: Optional[PreparedStore] = None,
                 monitor=None, slack: int = 4) -> None:
        self._metrics = default_registry().scope("mutation")
        self.csr = csr
        self.store = store
        self.monitor = monitor
        self.slack = max(int(slack), 0)
        self.generation = 0
        self.base_key = raw_content_key(csr)
        csr.version_key = self.version_key
        csr.mutation_slack = self.slack
        if monitor is not None:
            monitor.watch(self)

    @property
    def version_key(self) -> str:
        return f"{self.base_key}@g{self.generation}"

    @property
    def shape(self) -> Tuple[int, int]:
        return self.csr.shape

    def set_values(self, rows, cols, vals) -> "MutableMatrix":
        return self.apply_delta(Delta(np.asarray(rows), np.asarray(cols),
                                      np.asarray(vals), "set"))

    def add_values(self, rows, cols, vals) -> "MutableMatrix":
        return self.apply_delta(Delta(np.asarray(rows), np.asarray(cols),
                                      np.asarray(vals), "add"))

    # ----------------------------------------------------------- mutation
    def apply_delta(self, delta: DeltaLike) -> "MutableMatrix":
        delta = as_delta(delta)
        old_keys = {self.version_key, self.base_key}
        n_struct = apply_delta_csr(self.csr, delta)
        self.generation += 1
        self.csr.version_key = self.version_key
        self.deltas += 1
        self.structural_inserts += n_struct
        self.value_updates += delta.size - n_struct
        if self.store is not None:
            for key, value in self.store.pop_matching(old_keys):
                self._migrate_entry(key, value, delta)
        obs_trace.emit("mutate", self.base_key[:12], base=self.base_key,
                       generation=self.generation, n_values=delta.size,
                       n_structural=n_struct)
        if self.monitor is not None:
            self.monitor.observe(self)
        return self

    def _migrate_entry(self, key, value, delta: Delta) -> None:
        """One popped old-generation entry: rekey, epoch-swap, or drop."""
        new_key = key
        for tok in (f"{self.base_key}@g{self.generation - 1}", self.base_key):
            new_key = PreparedStore.rewrite_key(new_key, tok,
                                                self.version_key)
        if self._rekeyable(key, value):
            try:
                check_fault("delta-apply", key[0])
                if fault_fired("slack-overflow", key[0]):
                    note_recovery("slack-overflow")
                    raise SlackOverflow("injected slack exhaustion")
                value.apply_delta(delta)
            except (SlackOverflow, InjectedFault) as e:
                _note_handled(e)
                self._epoch_swap(key, new_key, e)
                return
            self.store.put(new_key, value)
            self.store.mutation_rekeys += 1
            self.rekeyed_entries += 1
        else:
            # Derived product (spgemm/spadd symbolic stage, stacked bucket,
            # shard stack): its staged arrays bake in old values. Drop it;
            # the next use re-stages against the new generation.
            self.store.mutation_invalidated += 1
            self.dropped_entries += 1

    @staticmethod
    def _rekeyable(key, value) -> bool:
        return (isinstance(value, SparseTensor) and isinstance(key, tuple)
                and len(key) == 6 and key and key[0] == "matvec")

    def _epoch_swap(self, key, new_key, cause: BaseException) -> None:
        """Slack exhausted (or fault injected) on an in-place rekey: the
        old tensor object keeps serving any live plan closure while we
        rebuild the new generation from the updated CSR. Never raises."""
        self.epoch_swaps += 1
        reason = type(cause).__name__
        obs_trace.emit("epoch_swap", key[0], op=key[0], reason=reason,
                       base=self.base_key, generation=self.generation)
        try:
            with obs_trace.span("prep", f"epoch-rebuild:{key[0]}", op=key[0]):
                fresh = self._rebuild_entry(key)
        except GUARDED_EXCEPTIONS:
            fresh = None
        if fresh is None:
            self.store.mutation_invalidated += 1
            self.dropped_entries += 1
            return
        self.store.put(new_key, fresh)
        self.rebuilds += 1

    def _rebuild_entry(self, key) -> Optional[SparseTensor]:
        """Fresh container from the (already mutated) CSR, under the build
        parameters the entry key encodes: ("matvec", ck, sched, layout,
        sigma, max_blocks), always bucket-padded (``ops_builtin.
        _prepared_matvec``)."""
        _, _, sched, lay, sigma, max_blocks = key
        return SparseTensor.from_csr(
            self.csr, schedule=sched, layout=lay, sigma=sigma,
            max_blocks=max_blocks, shape_bucket=True, slack=self.slack)

    def telemetry(self) -> Dict[str, int]:
        return ordered({
            "deltas": self.deltas,
            "value_updates": self.value_updates,
            "structural_inserts": self.structural_inserts,
            "epoch_swaps": self.epoch_swaps,
            "rebuilds": self.rebuilds,
            "rekeyed_entries": self.rekeyed_entries,
            "dropped_entries": self.dropped_entries,
            "generation": self.generation,
        })

    def __repr__(self) -> str:
        return (f"MutableMatrix(shape={self.csr.shape}, "
                f"generation={self.generation}, slack={self.slack})")
