"""The diagonal (DIA) layout: its build from CSR, its launch format, its
Pallas SpMV (interpret mode) and jnp reference against a float64 product,
and the selector's structural pick of it.

A banded operand such as HPCG's 27-point stencil stores one value per
(occupied diagonal, row) and no index; ``plan()`` launches an SpMV of an
immutable operand as DIA from the diagonal fill the selector counts once
per fingerprint. The selector's own picks, which stacked buckets and
shard meshes take, are never DIA, and a power-law graph keeps its tree
pick."""
import importlib
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TPU_V5E, ScheduleTuner, corpus
from repro.core.autotune import (DIA_FILL_MAX, DIA_SCHEDULE, Schedule,
                                 count_diagonals, takes_dia)
from repro.core.csr import CSR, DIA
from repro.core.synthetic import gen_stencil27, gen_zipf
from repro.kernels.bsr_spmv import kernel as K
from repro.kernels.bsr_spmv.ref import ref_dia_spmv
from repro.obs import default_registry
from repro.selector import ScheduleCache, SelectorService
from repro.sparse import (GuardedExecutor, MutableMatrix, PreparedStore,
                          Quarantine, SparseTensor, plan, plan_bucket,
                          plan_sharded)
from repro.sparse.tensor import (LANES, PAD_FILL, container_shapes,
                                 dia_row_pad, pad_arrays, prepared_nbytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hpcg.cg's limit on the row error over |A||x| (bench/limits/hpcg.cg.json)
TOL = 2e-6


def _rows(A):
    return np.repeat(np.arange(A.n_rows), A.row_lengths())


def reference(A, x):
    """(A x, |A| |x|) in float64 straight from the CSR arrays; x may be
    (n_cols,) or (n_cols, k)."""
    x = np.asarray(x, np.float64)
    prod = A.nnz_vals.astype(np.float64)[:, None] * x.reshape(
        x.shape[0], -1)[A.col_idxs]
    rows = _rows(A)
    ref = np.stack([np.bincount(rows, p, minlength=A.n_rows)
                    for p in prod.T], axis=1)
    bound = np.stack([np.bincount(rows, np.abs(p), minlength=A.n_rows)
                      for p in prod.T], axis=1)
    return ref.reshape((A.n_rows,) + x.shape[1:]), \
        bound.reshape((A.n_rows,) + x.shape[1:])


def row_error(y, A, x):
    ref, bound = reference(A, x)
    return float(np.max(np.abs(np.asarray(y, np.float64) - ref)
                        / np.maximum(bound, 1e-30)))


def dia_dense(D):
    """The dense matrix a DIA container holds."""
    out = np.zeros(D.shape, np.float32)
    rows = np.arange(D.shape[0])
    for o, vals in zip(D.offsets, D.diags.reshape(len(D.offsets), -1)):
        ok = (rows + o >= 0) & (rows + o < D.shape[1])
        out[rows[ok], rows[ok] + o] = vals[rows[ok]]
    return out


def _tridiagonal_with_empty(n=300):
    """Three diagonals, the upper one stored as explicit zeros: an occupied
    diagonal with no value."""
    i = np.arange(n)
    rows = np.concatenate([i, i[1:], i[:-1]])
    cols = np.concatenate([i, i[1:] - 1, i[:-1] + 1])
    vals = np.concatenate([np.full(n, 4.0), np.full(n - 1, -1.0),
                           np.zeros(n - 1)]).astype(np.float32)
    order = np.lexsort((cols, rows))
    row_ptrs = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n))])
    return CSR(row_ptrs, cols[order].astype(np.uint32), vals[order], (n, n))


def _row_shard(A, r0, r1):
    """Rows [r0, r1) of ``A``, every column kept: a rectangular operand
    whose offsets are A's shifted by r0."""
    p0, p1 = int(A.row_ptrs[r0]), int(A.row_ptrs[r1])
    return CSR(A.row_ptrs[r0:r1 + 1] - p0, A.col_idxs[p0:p1],
               A.nnz_vals[p0:p1], (r1 - r0, A.n_cols))


def _banded_negative(n=700):
    """Diagonals below the main one only, at lane shifts r != 0."""
    offs = (-300, -129, -5)
    rows, cols = [], []
    for o in offs:
        i = np.arange(max(0, -o), n)
        rows.append(i)
        cols.append(i + o)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = np.random.default_rng(3).uniform(-1, 1, rows.size)
    return CSR.from_coo(rows, cols, vals, (n, n))


STENCIL = gen_stencil27(20, 13, 7, seed=5)      # 1,820 rows: not 128-whole
CASES = {
    "stencil": STENCIL,
    "row_shard": _row_shard(STENCIL, 500, 1300),
    "empty_diagonal": _tridiagonal_with_empty(),
    "below_only": _banded_negative(),
    "one_lane_row": gen_stencil27(5, 4, 3, seed=2),   # 60 rows
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dia_build_matches_the_dense_matrix(case):
    A = CASES[case]
    offs = A.diagonal_offsets()
    pad = dia_row_pad(offs.size, A.n_rows)
    D = DIA.from_csr(A, pad)
    assert D.offsets == tuple(offs) and D.shape == A.shape
    assert D.diags.shape == (offs.size, pad // LANES, LANES)
    np.testing.assert_array_equal(dia_dense(D), A.to_dense())
    # padded rows, and slots whose column falls outside, hold 0
    flat = D.diags.reshape(offs.size, -1)
    assert not flat[:, A.n_rows:].any()
    i = np.arange(A.n_rows)
    for k, o in enumerate(D.offsets):
        assert not flat[k, :A.n_rows][(i + o < 0) | (i + o >= A.n_cols)].any()
    assert D.fill() == pytest.approx(offs.size * A.n_rows / A.nnz)


def test_row_shard_offsets_are_shifted():
    shard = CASES["row_shard"]
    np.testing.assert_array_equal(shard.diagonal_offsets(),
                                  STENCIL.diagonal_offsets() + 500)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_an_operand_with_no_entry_gives_zeros(backend):
    A = CSR(np.zeros(201, np.int64), np.zeros(0, np.uint32),
            np.zeros(0, np.float32), (200, 150))
    y = plan("spmv", A, schedule=DIA_SCHEDULE, backend=backend).execute(
        np.ones(150, np.float32))
    assert y.shape == (200,) and not np.asarray(y).any()
    assert not takes_dia(0, 200, 0)


def test_empty_diagonal_is_occupied_but_zero():
    D = DIA.from_csr(CASES["empty_diagonal"], 384)
    assert D.offsets == (-1, 0, 1)
    assert not D.diags[2].any() and D.diags[1].any()


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_a_container_padded_short_of_a_row_block_is_re_padded(backend):
    """A DIA container padded to 128 rows but not to a whole kernel row
    block plans and runs: the launch format re-pads it."""
    A = CASES["empty_diagonal"]
    D = DIA.from_csr(A, 384)
    assert D.diags.shape[1] * LANES != dia_row_pad(3, A.n_rows)
    p = plan("spmv", D, backend=backend)
    assert p.operands[0].arrays["diags"].shape == (
        3, dia_row_pad(3, A.n_rows) // LANES, LANES)
    x = np.random.default_rng(6).standard_normal(A.n_cols).astype(np.float32)
    assert row_error(p.execute(x), A, x) <= TOL


def test_the_kernel_refuses_rows_short_of_a_row_block():
    D = DIA.from_csr(CASES["empty_diagonal"], 384)
    with pytest.raises(ValueError, match="whole number"):
        K._dia_spmv(jnp.asarray(D.diags), jnp.ones(300, jnp.float32),
                    offsets=D.offsets, interpret=True)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_dia_spmv_matches_float64(case, backend):
    A = CASES[case]
    x = np.random.default_rng(7).standard_normal(A.n_cols).astype(np.float32)
    p = plan("spmv", A, schedule=DIA_SCHEDULE, backend=backend)
    y = p.execute(x)
    assert y.shape == (A.n_rows,)
    assert row_error(y, A, x) <= TOL
    assert p.describe().startswith("plan[spmv] dia")


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_dia_multi_rhs_matches_float64(backend):
    A = CASES["stencil"]
    X = np.random.default_rng(8).standard_normal(
        (A.n_cols, 3)).astype(np.float32)
    y = plan("spmm", A, schedule=DIA_SCHEDULE, backend=backend).execute(X)
    assert y.shape == (A.n_rows, 3)
    assert row_error(y, A, X) <= TOL


@pytest.mark.parametrize("case", ["stencil", "row_shard", "below_only"])
def test_kernel_and_reference_match_float64(case):
    A = CASES[case]
    D = DIA.from_csr(A, dia_row_pad(A.diagonal_offsets().size, A.n_rows))
    x = np.random.default_rng(9).standard_normal(A.n_cols).astype(np.float32)
    diags = jnp.asarray(D.diags)
    y_kernel = K.bsr_spmv_pallas(diags, jnp.asarray(x), offsets=D.offsets,
                                 interpret=True)
    y_ref = ref_dia_spmv(diags, jnp.asarray(x), D.offsets)
    assert y_kernel.shape == y_ref.shape == (D.diags.shape[1] * LANES,)
    assert row_error(y_kernel[:A.n_rows], A, x) <= TOL
    assert row_error(y_ref[:A.n_rows], A, x) <= TOL
    assert not np.asarray(y_kernel[A.n_rows:]).any()


def test_kernel_steps_over_several_row_blocks(monkeypatch):
    """A small step forces several grid steps, so the hand-copied x window
    of the next step is in flight while one computes."""
    monkeypatch.setattr(K, "DIA_MAX_ROWS", 8)
    A = gen_stencil27(16, 16, 12, seed=4)           # 3,072 rows: 3 steps
    pad = dia_row_pad(27, A.n_rows)
    assert pad == 3 * 8 * LANES
    D = DIA.from_csr(A, pad)
    x = np.random.default_rng(1).standard_normal(A.n_cols).astype(np.float32)
    # the body itself, unjitted: it reads the patched step afresh
    y = K._dia_spmv(jnp.asarray(D.diags), jnp.asarray(x),
                    offsets=D.offsets, interpret=True)
    assert row_error(y[:A.n_rows], A, x) <= TOL


@pytest.mark.parametrize("band,group", [(256, 64), (1 << 18, 4), (300, 5)])
def test_kernel_runs_a_call_per_group_of_diagonals(monkeypatch, band, group):
    """Diagonals past one call's band or count run as several calls whose
    products sum: the same answer as one call."""
    monkeypatch.setattr(K, "DIA_BAND", band)
    monkeypatch.setattr(K, "DIA_GROUP", group)
    A = CASES["stencil"]
    offs = tuple(int(o) for o in A.diagonal_offsets())
    assert len(list(K._dia_groups(offs))) > 1
    D = DIA.from_csr(A, dia_row_pad(len(offs), A.n_rows))
    x = np.random.default_rng(5).standard_normal(A.n_cols).astype(np.float32)
    # the body itself, unjitted: it reads the patched limits afresh
    y = K._dia_spmv(jnp.asarray(D.diags), jnp.asarray(x),
                    offsets=D.offsets, interpret=True)
    assert row_error(y[:A.n_rows], A, x) <= TOL
    groups = list(K._dia_groups(offs))
    assert groups[0][0] == 0 and groups[-1][1] == len(offs)
    assert all(g1 - g0 <= group and offs[g1 - 1] - offs[g0] <= band
               for g0, g1 in groups)


@pytest.mark.parametrize("d", [1, 27, 200, 5000])
@pytest.mark.parametrize("n_rows", [1, 60, 1820, 884_736, 3_538_944])
def test_row_pad_is_whole_kernel_steps(d, n_rows):
    pad = dia_row_pad(d, n_rows)
    lane_rows = pad // LANES
    step = K.dia_rows(d, lane_rows)
    assert pad >= n_rows and lane_rows % step == 0 and step % 8 == 0
    group = min(d, K.DIA_GROUP)
    assert 2 * 4 * LANES * group * step <= K.DIA_BLOCK_BYTES


def test_pad_fill_and_nbytes():
    assert PAD_FILL["diags"] == 0
    A = CASES["stencil"]
    st = SparseTensor.from_csr(A, schedule=DIA_SCHEDULE, shape_bucket=True)
    assert st.layout == "dia" and st.meta.offsets == tuple(
        A.diagonal_offsets()) and st.meta.shape == A.shape
    want = {k: tuple(v.shape) for k, v in st.arrays.items()}
    assert container_shapes(A, DIA_SCHEDULE, shape_bucket=True) == want
    assert prepared_nbytes(A, DIA_SCHEDULE, shape_bucket=True) == \
        4 * 27 * dia_row_pad(27, A.n_rows)
    diags = st.to_host().diags
    grown = pad_arrays({"diags": diags},
                       {"diags": (27, diags.shape[1] + 3, LANES)})["diags"]
    assert grown.shape[1] == diags.shape[1] + 3 and not grown[:, -3:].any()


def test_unflattened_tensor_rebuilds_its_host_container():
    import jax
    st = SparseTensor.from_csr(CASES["stencil"], schedule=DIA_SCHEDULE)
    leaves, meta = jax.tree_util.tree_flatten(st)
    back = jax.tree_util.tree_unflatten(meta, leaves)
    host = back.to_host()
    assert isinstance(host, DIA) and host.offsets == st.meta.offsets
    np.testing.assert_array_equal(dia_dense(host),
                                  CASES["stencil"].to_dense())


def test_ell_launch_is_the_ell_body():
    """The two-format entry leaves the ELL SpMV as it was: its result is
    the ELL body's, bit for bit, and the f64 product's."""
    import jax
    from repro.core.csr import BSR, ELLBSR
    A = gen_zipf(300, seed=4, a=1.6)
    ell = ELLBSR.from_bsr(BSR.from_csr(A, 128))
    x = np.random.default_rng(2).standard_normal(A.n_cols).astype(np.float32)
    xb = jnp.asarray(np.pad(x, (0, 384 - 300)).reshape(3, 128))
    args = (jnp.asarray(ell.block_indices), jnp.asarray(ell.block_cols),
            jnp.asarray(ell.valid_counts), jnp.asarray(ell.blocks), xb)
    y = K.bsr_spmv_pallas(*args, interpret=True)
    body = jax.jit(lambda *a: K._ell_spmv(*a, interpret=True))(*args)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(body))
    assert row_error(np.asarray(y).reshape(-1)[:300], A, x) <= TOL


def test_dia_counters():
    reg = default_registry()
    A = CASES["stencil"]
    x = np.ones(A.n_cols, np.float32)
    n0 = reg.get("kernel.dia.launches")
    p = plan("spmv", A, schedule=DIA_SCHEDULE, backend="interpret")
    assert reg.gauge("kernel.dia.fill") == pytest.approx(
        27 * A.n_rows / A.nnz)
    for _ in range(3):
        p.execute(x)
    plan("spmv", A, schedule=DIA_SCHEDULE, backend="jnp").execute(x)
    assert reg.get("kernel.dia.launches") - n0 == 3    # kernel launches only
    # a tile launch ticks no DIA counter
    plan("spmv", A, schedule=Schedule("bsr", 128, 1.0),
         backend="interpret").execute(x)
    assert reg.get("kernel.dia.launches") - n0 == 3


# ------------------------------------------------------------- selection

def _kron(scale=9):
    spec = importlib.util.spec_from_file_location(
        "kron_gen", os.path.join(ROOT, "bench", "generators", "kron.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    a = gen.build({"scale": scale, "edgefactor": 16, "A": 0.57, "B": 0.19,
                   "C": 0.19, "graph_seed": 27}, 11)
    return CSR(a["row_ptrs"], a["col_idxs"].astype(np.uint32), a["vals"],
               a["shape"])


@pytest.fixture(scope="module")
def tuner():
    """The benchmark cells' tuner: the configurations' fixed corpus."""
    return ScheduleTuner("spmv", TPU_V5E).fit(
        corpus(n_matrices=9, n_min=128, n_max=256, seed=3), max_mats=6)


def _service(tuner):
    """A service with a quarantine of its own, as the benchmark builds."""
    ex = GuardedExecutor(quarantine=Quarantine())
    return SelectorService(tuner, cache=ScheduleCache(),
                           prepared_store=PreparedStore(1 << 30),
                           executor=ex, quarantine=ex.quarantine)


def test_a_stencil_gets_dia(tuner):
    """The selector's pick is the tree's; plan() launches the SpMV as DIA,
    through a service and through a tuner alike."""
    A = gen_stencil27(16, seed=1)
    svc = _service(tuner)
    p = plan("spmv", A, selector=svc, backend="jnp")
    assert p.describe() == "plan[spmv] dia via selector-structure"
    assert svc.select(A).schedule.layout != "dia"
    x = np.random.default_rng(0).standard_normal(A.n_cols).astype(np.float32)
    assert row_error(p.execute(x), A, x) <= TOL
    q = Quarantine()
    pt = plan("spmv", A, selector=tuner, backend="jnp",
              executor=GuardedExecutor(quarantine=q))
    assert pt.describe() == "plan[spmv] dia via tuner-structure"
    assert tuner.select(A)[0].layout != "dia"


@pytest.mark.parametrize("which", ["kron", "zipf"])
def test_irregular_operands_keep_their_pick(tuner, which):
    """A power-law graph's diagonals are far too many: its plan takes the
    selector's pick."""
    A = _kron() if which == "kron" else gen_zipf(512, seed=3, a=1.4)
    assert count_diagonals(A) * A.n_rows > DIA_FILL_MAX * A.nnz
    svc = _service(tuner)
    picked = plan("spmv", A, selector=svc, backend="jnp").schedule
    assert picked == _service(tuner).select(A).schedule
    assert picked.layout != "dia"
    assert plan("spmv", A, selector=tuner, backend="jnp").schedule == \
        tuner.select(A)[0] != DIA_SCHEDULE


def test_rhs_width_mutability_buckets_and_shards_never_get_dia(tuner):
    A = gen_stencil27(16, seed=1)
    svc = _service(tuner)
    assert all(d.schedule.layout != "dia" for d in svc.select_shards(
        [_row_shard(A, 0, 2048), _row_shard(A, 2048, 4096)]))
    assert plan("spmm", A, selector=svc, backend="jnp").schedule.layout \
        != "dia"
    wide = ScheduleTuner("spmv", TPU_V5E, n_rhs=4)
    wide.tree, wide.feature_names = tuner.tree, tuner.feature_names
    assert plan("spmv", A, selector=wide, backend="jnp").schedule.layout \
        != "dia"
    mm = MutableMatrix(A, slack=2)
    assert plan("spmv", mm.csr, selector=_service(tuner),
                backend="jnp").schedule.layout != "dia"
    assert takes_dia(27, 100, 2700, n_rhs=1)
    assert not takes_dia(27, 100, 2700, n_rhs=2)
    assert not takes_dia(27, 100, 2700, mutable=True)
    assert not takes_dia(28, 100, 1866)         # fill past DIA_FILL_MAX
    with pytest.raises(ValueError, match="no dia operand"):
        plan_bucket("spmv", [A, A], DIA_SCHEDULE, backend="jnp")
    with pytest.raises(ValueError, match="no dia operand"):
        plan_sharded("spmv", A, n_shards=2, schedule=DIA_SCHEDULE,
                     backend="jnp")


@pytest.mark.parametrize("op", ["spgemm", "spadd"])
def test_two_operand_plans_through_a_selector_keep_tiles(tuner, op):
    """Only an SpMV goes to DIA: a selector's pick for a sparse product or
    sum of stencils plans as before."""
    A = gen_stencil27(8, seed=1)
    B = gen_stencil27(8, seed=2)
    p = plan(op, (A, B), selector=_service(tuner), backend="jnp")
    assert p.schedule.layout != "dia"
    want = A.to_dense().astype(np.float64)
    want = want @ B.to_dense() if op == "spgemm" else want + B.to_dense()
    got = p.execute()
    got = got.to_dense() if hasattr(got, "to_dense") else np.asarray(got)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_a_quarantined_dia_pick_falls_to_the_selector(tuner):
    A = gen_stencil27(16, seed=1)
    svc = _service(tuner)
    svc.quarantine.add(tuner.kernel, "jnp", DIA_SCHEDULE, reason="test")
    p = plan("spmv", A, selector=svc, backend="jnp")
    assert p.schedule == svc.select(A).schedule != DIA_SCHEDULE
    assert p.source.startswith("selector-") and p.source != \
        "selector-structure"


def test_diagonals_are_counted_once_per_fingerprint(tuner, monkeypatch):
    # the module, which the package's ``fingerprint`` function shadows
    fp_mod = importlib.import_module("repro.selector.fingerprint")
    calls = []
    real = fp_mod.count_diagonals
    monkeypatch.setattr(fp_mod, "count_diagonals",
                        lambda A: (calls.append(1), real(A))[1])
    A = gen_stencil27(16, seed=1)
    svc = _service(tuner)
    hits = svc.telemetry()["fp_memo_hits"]   # the process's selector count
    for _ in range(3):
        assert plan("spmv", A, selector=svc,
                    backend="jnp").schedule.layout == "dia"
    svc.select(A)
    assert len(calls) == 1
    assert svc.telemetry()["fp_memo_hits"] - hits == 3


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_a_mutable_stencil_without_slack_takes_deltas(tuner, backend):
    """A MutableMatrix with no slack counts as immutable, so its SpMV is
    DIA: a value on one of its diagonals lands in place, and one off them
    epoch-swaps to a container rebuilt from the updated CSR."""
    A = gen_stencil27(8, seed=3)
    svc = _service(tuner)
    mm = MutableMatrix(A, store=svc.prepared_store, slack=0)
    p = plan("spmv", mm.csr, selector=svc, backend=backend)
    assert p.schedule == DIA_SCHEDULE
    x = np.random.default_rng(4).standard_normal(A.n_cols).astype(np.float32)
    mm.set_values([5, 6], [6, 5 + 64], [2.5, -1.0])   # on two diagonals
    assert mm.rekeyed_entries == 1 and mm.epoch_swaps == 0
    assert row_error(p.execute(x), mm.csr, x) <= TOL
    mm.add_values([0], [300], [1.0])                  # off every diagonal
    assert mm.epoch_swaps == 1 and mm.rebuilds == 1
    q = plan("spmv", mm.csr, selector=svc, backend=backend)
    assert q.schedule == DIA_SCHEDULE
    assert len(q.operands[0].meta.offsets) == 28
    assert row_error(q.execute(x), mm.csr, x) <= TOL


def test_the_count_stops_past_the_limit():
    from repro.core.synthetic import gen_uniform
    A = gen_uniform(20_000, seed=1)             # many bands of rows
    limit = int(DIA_FILL_MAX * A.nnz // A.n_rows)
    assert limit < count_diagonals(A) < A.diagonal_offsets().size
