"""Per-kernel allclose vs ref.py oracle: shape/dtype sweeps, both the jnp
and the Pallas-interpret backends (kernel body executed on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CSR
from repro.kernels import (bsr_spadd, bsr_spgemm, bsr_spmv, flash_attention,
                           moe_gmm)

RNG = np.random.default_rng(42)


def _sparse(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.standard_normal((n, m))
    return CSR.from_dense(d.astype(np.float32))


# ------------------------------------------------------------------ SpMV
@pytest.mark.parametrize("n,bs", [(64, 8), (100, 16), (257, 32), (512, 128),
                                  (96, 96), (600, 256), (400, 192)])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_spmv_allclose(n, bs, backend):
    csr = _sparse(n, n, 0.06, n)
    x = RNG.standard_normal(n).astype(np.float32)
    ell = bsr_spmv.ops.prepare(csr, bs)
    y = np.asarray(bsr_spmv.bsr_spmv(ell, jnp.asarray(x), backend=backend))
    ref = bsr_spmv.ops.spmv_oracle(csr, x)
    np.testing.assert_allclose(y, ref, rtol=2e-5, atol=2e-5)


def test_bsr_spmv_rectangular():
    csr = _sparse(120, 250, 0.05, 7)
    x = RNG.standard_normal(250).astype(np.float32)
    ell = bsr_spmv.ops.prepare(csr, 32)
    y = np.asarray(bsr_spmv.bsr_spmv(ell, jnp.asarray(x), backend="interpret"))
    np.testing.assert_allclose(y, bsr_spmv.ops.spmv_oracle(csr, x),
                               rtol=2e-5, atol=2e-5)


def test_bsr_spmv_ell_capacity_drop():
    """ELL with capped blocks/row drops lowest-priority blocks (documented
    capacity semantics, mirrored by counters.dropped_nnz_fraction)."""
    csr = _sparse(128, 128, 0.2, 3)
    ell = bsr_spmv.ops.prepare(csr, 16, max_blocks=2)
    assert ell.max_blocks == 2


# ------------------------------------------------- SELL (bucketed) SpMV/SpMM
@pytest.mark.parametrize("n,bs,C,sigma", [(64, 8, 2, 8), (100, 16, 4, 2),
                                          (257, 32, 3, 1000), (512, 128, 8, 64),
                                          (700, 256, 2, 4)])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_spmv_sell_allclose(n, bs, C, sigma, backend):
    csr = _sparse(n, n, 0.06, n)
    x = RNG.standard_normal(n).astype(np.float32)
    sell = bsr_spmv.ops.prepare_sell(csr, bs, C, sigma)
    y = np.asarray(bsr_spmv.bsr_spmv(sell, jnp.asarray(x), backend=backend))
    np.testing.assert_allclose(y, bsr_spmv.ops.spmv_oracle(csr, x),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layout", ["ell", "sell"])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_spmm_allclose(layout, backend):
    """Multi-RHS Y = A @ X with an odd k (exercises RHS-tile padding)."""
    n, k, bs = 120, 5, 16
    csr = _sparse(n, n, 0.08, 9)
    X = RNG.standard_normal((n, k)).astype(np.float32)
    a = (bsr_spmv.ops.prepare(csr, bs) if layout == "ell"
         else bsr_spmv.ops.prepare_sell(csr, bs, 4, 16))
    Y = np.asarray(bsr_spmv.bsr_spmm(a, jnp.asarray(X), backend=backend))
    assert Y.shape == (n, k)
    np.testing.assert_allclose(Y, bsr_spmv.ops.spmm_oracle(csr, X),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_sell_one_dense_row_many_empty(backend):
    """Pathological imbalance: a single dense row among empty rows. Empty
    slices keep width 1, so every output row is still initialized."""
    from repro.core.synthetic import gen_row
    csr = gen_row(256, seed=4)
    x = RNG.standard_normal(256).astype(np.float32)
    X = RNG.standard_normal((256, 3)).astype(np.float32)
    sell = bsr_spmv.ops.prepare_sell(csr, 32, 2, 4)
    y = np.asarray(bsr_spmv.bsr_spmv(sell, jnp.asarray(x), backend=backend))
    np.testing.assert_allclose(y, bsr_spmv.ops.spmv_oracle(csr, x),
                               rtol=1e-4, atol=1e-4)
    Y = np.asarray(bsr_spmv.bsr_spmm(sell, jnp.asarray(X), backend=backend))
    np.testing.assert_allclose(Y, bsr_spmv.ops.spmm_oracle(csr, X),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_sell_zipf_allclose(backend):
    """Zipf-distributed (power-law) rows: the distribution SELL exists for."""
    from repro.core.synthetic import gen_zipf
    csr = gen_zipf(512, seed=1)
    x = RNG.standard_normal(512).astype(np.float32)
    sell = bsr_spmv.ops.prepare_sell(csr, 64, 2, 8)
    y = np.asarray(bsr_spmv.bsr_spmv(sell, jnp.asarray(x), backend=backend))
    np.testing.assert_allclose(y, bsr_spmv.ops.spmv_oracle(csr, x),
                               rtol=1e-4, atol=1e-4)


# Block pattern of the row-structure cases, one entry per block-row: a
# hub row of five cells (ELL's slot width mb, longer than the SpMV stream's
# ring), one-cell rows, an empty row (ELL pads it with the zero tile and
# streams nothing, SELL keeps one zero-tile cell), a two-cell row and a row
# of mb - 1 cells.
ROW_BLOCKS = [(0, 1, 2, 3, 4), (1,), (), (0, 4), (2,), (1, 2, 3, 5)]


def _block_pattern(bs, seed):
    """CSR of ROW_BLOCKS at block size ``bs`` (last block-row and column
    cut short), its float64 dense form, and an x."""
    rng = np.random.default_rng(seed)
    n = len(ROW_BLOCKS) * bs - 3
    d = np.zeros((len(ROW_BLOCKS) * bs,) * 2, np.float32)
    for r, cols in enumerate(ROW_BLOCKS):
        for c in cols:
            tile = rng.standard_normal((bs, bs)) * (rng.random((bs, bs)) < 0.3)
            d[r * bs:(r + 1) * bs, c * bs:(c + 1) * bs] = tile
    d = d[:n, :n]
    x = rng.standard_normal(n).astype(np.float32)
    return CSR.from_dense(d), d.astype(np.float64), x


def _ell_dense64(a):
    """The float64 dense matrix an ELL container holds: the tiles of each
    row's valid slots, nothing of its padding."""
    bs = a.block_size
    n_br, mb = a.block_indices.shape
    n_bc = -(-a.shape[1] // bs)
    d = np.zeros((n_br * bs, n_bc * bs))
    for r in range(n_br):
        for j in range(int(a.valid_counts[r])):
            c = int(a.block_cols[r, j])
            d[r * bs:(r + 1) * bs, c * bs:(c + 1) * bs] += \
                a.blocks[a.block_indices[r, j]]
    return d[:a.shape[0], :a.shape[1]]


def _assert_f64_close(y, d64, x):
    """Within f32 rounding of the float64 product, row by row on |A||x|."""
    x64 = x.astype(np.float64)
    bound = np.abs(d64) @ np.abs(x64)
    assert np.all(np.abs(np.asarray(y, np.float64) - d64 @ x64)
                  <= 1e-6 * bound + 1e-30)


@pytest.mark.parametrize("bs", [32, 128, 192, 256])
@pytest.mark.parametrize("layout", ["ell", "ell_capped", "sell"])
def test_spmv_vpu_row_structures(layout, bs):
    """The SpMV tile product (VPU, f32) against the float64 oracle on rows
    of one cell, of many cells and of zero-tile padding only; SELL row
    changes and the SMEM-split launches (one row, or one cell, a launch:
    SELL rows then straddle launches) included. ELL rows hold 0, 1, mb - 1
    and mb valid tiles, which bs 128 and 256 stream and bs 32 and 192 run
    over the whole slot grid; ``ell_capped`` cuts the long rows at 3 slots
    (a q < 1 schedule), and the product is that of the tiles kept."""
    from repro.kernels.bsr_spmv import kernel as K
    from repro.sparse.smem import SMEM_BUDGET_BYTES, split_cells, split_rows
    csr, d64, x = _block_pattern(bs, bs)
    n = x.shape[0]
    n_bc = -(-n // bs)
    xb = jnp.asarray(np.pad(x, (0, n_bc * bs - n)).reshape(n_bc, bs))
    if layout.startswith("ell"):
        cap = 3 if layout == "ell_capped" else None
        a = bsr_spmv.ops.prepare(csr, bs, max_blocks=cap)
        zero = a.blocks.shape[0] - 1
        assert (a.block_indices == zero).sum() >= 4    # padded slots
        mb = a.max_blocks
        assert {0, 1, mb - 1, mb} <= set(a.valid_counts.tolist())
        if cap:
            assert a.valid_counts.sum() < sum(map(len, ROW_BLOCKS))
            d64 = _ell_dense64(a)
        tables = (jnp.asarray(a.block_indices), jnp.asarray(a.block_cols),
                  jnp.asarray(a.valid_counts))
        blocks = jnp.asarray(a.blocks)

        def launch(budget):
            return split_rows(
                lambda i, c, v: K.bsr_spmv_pallas(i, c, v, blocks, xb,
                                                  interpret=True),
                tables, budget=budget)
        perm = None
    else:
        a = bsr_spmv.ops.prepare_sell(csr, bs, 2, 4)
        cr = a.cell_row
        assert (np.diff(cr) > 0).sum() == a.n_block_rows - 1  # row changes
        assert (np.bincount(cr) == 1).any() and (np.bincount(cr) > 1).any()
        tables = (jnp.asarray(a.cell_block), jnp.asarray(a.cell_col),
                  jnp.asarray(cr))
        blocks = jnp.asarray(a.blocks)

        def launch(budget):
            return split_cells(
                lambda b, c, r: K.bsr_spmv_sell_pallas(
                    b, c, r, blocks, xb, a.n_block_rows, interpret=True),
                tables, tables[2], a.n_block_rows, budget=budget)
        perm = jnp.asarray(a.row_perm)
    for budget in (SMEM_BUDGET_BYTES, 1):
        y = launch(budget)
        if perm is not None:
            y = jnp.zeros_like(y).at[perm].set(y)
        _assert_f64_close(np.asarray(y).reshape(-1)[:n], d64, x)
    if layout.startswith("ell") and K.ell_streams(bs):
        # the stream drops only the padding's + 0 terms: bit for bit the
        # product of the grid over every slot
        grid = K._ell_call(*tables[:2], blocks, xb.reshape(-1, 1, bs),
                           vector=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(y).reshape(-1),
                                      np.asarray(grid).reshape(-1))


@pytest.mark.parametrize("sizes", ["equal", "unequal"])
@pytest.mark.parametrize("layout", ["ell", "sell"])
def test_stacked_spmv_vpu_matches_f64(layout, sizes):
    """A stacked bucket (one program, B unrolled SpMV launches into one
    tile stack) at bs 128 against the float64 oracle, member by member;
    ``unequal`` cuts the second member to 400 rows, so the stack pads its
    block-rows (valid count 0 in the ELL stream)."""
    from repro.core.autotune import Schedule
    from repro.sparse import plan_bucket
    mats = [_block_pattern(128, s) for s in (1, 2)]
    if sizes == "unequal":
        _, d64, x = mats[1]
        d64, x = d64[:400, :400], x[:400]
        mats[1] = (CSR.from_dense(d64.astype(np.float32)), d64, x)
    sched = (Schedule("bsr", 128, 1.0) if layout == "ell"
             else Schedule("bsr", 128, 1.0, layout="sell", slice_height=2))
    ys = plan_bucket("spmv", [m[0] for m in mats], sched,
                     backend="interpret").execute([m[2] for m in mats])
    for (_, d64, x), y in zip(mats, ys):
        _assert_f64_close(y, d64, x)


def test_tile_product_provenance_and_counters():
    """SpMV launches run the VPU tile product and SpMM launches (a fused
    same-operand drain among them) the MXU one: ``Plan.tile_product``
    says which for a runtime input, the launch event carries it, and
    ``kernel.tile_product.*`` counts each kernel launch once. A jnp plan
    runs no kernel and counts nothing."""
    from repro.core.autotune import Schedule
    from repro.obs import default_registry, trace as obs_trace
    from repro.sparse import PreparedStore, plan, plan_bucket, plan_sharded
    from repro.sparse.prepared import content_key
    csr, _, x = _block_pattern(32, 5)
    X = np.stack([x, -x, 2 * x], axis=1)
    sched = Schedule("bsr", 32, 1.0)
    reg = default_registry()

    def counts():
        return (reg.get("kernel.tile_product.vpu"),
                reg.get("kernel.tile_product.mxu"))

    spmv = plan("spmv", (csr,), schedule=sched, backend="interpret")
    spmm = plan("spmm", (csr,), schedule=sched, backend="interpret")
    store, ck = PreparedStore(), content_key(csr)
    fused = plan_bucket("spmv", [csr, csr], sched, backend="interpret",
                        store=store, member_keys=(ck, ck))
    bucket = plan_bucket("spmv", [csr, csr], sched, backend="interpret")
    sharded = plan_sharded("spmv", csr, n_shards=2, schedule=sched,
                           backend="interpret")
    plain = plan("spmv", (csr,), schedule=sched, backend="jnp")
    assert (spmv.tile_product(x), spmm.tile_product(X)) == ("vpu", "mxu")
    assert fused.tile_product([x, -x]) == "mxu"
    assert bucket.tile_product([x, -x]) == "vpu"
    assert bucket.tile_product([X, X]) == "mxu"
    assert sharded.tile_product(x) == "vpu"
    assert plain.tile_product(x) is None
    vpu0, mxu0 = counts()
    for _ in range(3):
        spmv.execute(x)
    spmm.execute(X)
    fused.execute([x, -x])
    bucket.execute([x, -x])
    sharded.execute(x)
    plain.execute(x)
    assert counts() == (vpu0 + 5, mxu0 + 2)
    # the launch, not the op name, decides: a matrix RHS through an spmv
    # plan takes the MXU, and the launch event says so
    was = obs_trace.tracer()
    tr = obs_trace.install_tracer(obs_trace.Tracer())
    try:
        spmv.execute(X)
    finally:
        obs_trace.install_tracer(was)
    assert counts() == (vpu0 + 5, mxu0 + 3)
    assert [e["args"]["tile_product"] for e in tr.events()
            if e["type"] == "launch"] == ["mxu"]


def test_ell_stream_counters_hand_count():
    """``kernel.ell_stream.tiles`` / ``.skipped`` per streamed SpMV launch,
    against a hand count of ROW_BLOCKS at bs 128: 13 valid tiles in a
    bucketed 6 x 6 slot grid (shape bucketing rounds mb 5 up to 6), so 23
    slots skipped a launch; a stacked bucket of two is 26 of 2 x 36. SpMM,
    jnp and the bs-32 slot grid stream nothing and count nothing."""
    from repro.core.autotune import Schedule
    from repro.obs import default_registry
    from repro.sparse import plan, plan_bucket
    csr, _, x = _block_pattern(128, 7)
    assert sum(map(len, ROW_BLOCKS)) == 13 and len(ROW_BLOCKS) == 6
    sched = Schedule("bsr", 128, 1.0)
    reg = default_registry()

    def counts():
        return (reg.get("kernel.ell_stream.tiles"),
                reg.get("kernel.ell_stream.skipped"))

    spmv = plan("spmv", (csr,), schedule=sched, backend="interpret")
    bucket = plan_bucket("spmv", [csr, csr], sched, backend="interpret")
    quiet = [plan("spmv", (csr,), schedule=sched, backend="jnp"),
             plan("spmv", (csr,), schedule=Schedule("bsr", 32, 1.0),
                  backend="interpret")]
    t0, s0 = counts()
    spmv.execute(x)
    spmv.execute(x)
    assert counts() == (t0 + 26, s0 + 46)
    bucket.execute([x, -x])
    assert counts() == (t0 + 52, s0 + 92)
    spmv.execute(np.stack([x, -x], axis=1))     # a matrix RHS: the SpMM
    for p in quiet:
        p.execute(x)
    assert counts() == (t0 + 52, s0 + 92)


def test_ell_stream_after_out_of_order_insert():
    """A structural insert claims a spare tile that sits after every row's
    own tiles (out of row order) and a free slot of an empty row: the
    streamed SpMV on the same live plan reads it through block_indices,
    matches the float64 product of the updated matrix, and its stream
    count grows by the inserted tile."""
    from repro.obs import default_registry
    from repro.sparse import Delta, MutableMatrix, PreparedStore, plan
    csr, _, x = _block_pattern(128, 9)
    store = PreparedStore()
    mm = MutableMatrix(csr, store=store, slack=2)
    p = plan("spmv", (csr,), backend="interpret", store=store,
             block_size=128)
    st = p.operands[0]
    tiles0 = st.ell_stream()[0]
    row = ROW_BLOCKS.index(())             # the empty block-row
    mm.apply_delta(Delta(np.array([row * 128 + 5]), np.array([3 * 128 + 7]),
                         np.array([2.5], np.float32)))
    host = st.to_host()
    k = int(host.block_indices[row, 0])
    assert host.valid_counts[row] == 1 and k > host.block_indices[-1].max(
        where=np.arange(host.max_blocks) < host.valid_counts[-1], initial=0)
    assert st.ell_stream()[0] == tiles0 + 1
    reg = default_registry()
    t0 = reg.get("kernel.ell_stream.tiles")
    y = p.execute(x)
    assert reg.get("kernel.ell_stream.tiles") == t0 + tiles0 + 1
    d64 = np.asarray(csr.to_dense(), np.float64)
    assert d64[row * 128 + 5, 3 * 128 + 7] == 2.5
    _assert_f64_close(y, d64, x)


def test_sell_padding_beats_global_ell_on_zipf():
    """Issue acceptance: on the Zipf matrix (n=2048, bs=128), SELL C=8
    sigma=64 wastes at most half the slots global ELL wastes."""
    from repro.core import BSR, ELLBSR, SELLBSR
    from repro.core.synthetic import gen_zipf
    bsr = BSR.from_csr(gen_zipf(2048, seed=0), 128)
    ell_pad = ELLBSR.from_bsr(bsr).ell_padding_fraction()
    sell_pad = SELLBSR.from_bsr(bsr, 8, 64).sell_padding_fraction()
    assert ell_pad > 0.0
    assert sell_pad <= 0.5 * ell_pad, (sell_pad, ell_pad)


def test_sell_container_invariants():
    """row_perm is a permutation, cell_row is nondecreasing (the Pallas
    output-revisit contract), and the static metric forms agree with the
    container counters."""
    from repro.core import BSR, SELLBSR
    from repro.core.metrics import sell_padding_fraction, slice_imbalance
    csr = _sparse(300, 300, 0.05, 13)
    bsr = BSR.from_csr(csr, 32)
    sell = SELLBSR.from_bsr(bsr, 3, 4)
    assert sorted(sell.row_perm.tolist()) == list(range(bsr.n_block_rows))
    assert (np.diff(sell.cell_row) >= 0).all()
    bpr = bsr.blocks_per_row()
    assert sell.sell_padding_fraction() == pytest.approx(
        sell_padding_fraction(bpr, 3, 4))
    assert sell.slice_imbalance() == pytest.approx(slice_imbalance(bpr, 3, 4))


# ------------------------------------------------------------------ SpADD
@pytest.mark.parametrize("n,bs", [(64, 8), (90, 16), (200, 32)])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_spadd_allclose(n, bs, backend):
    a, b_ = _sparse(n, n, 0.05, n), _sparse(n, n, 0.05, n + 1)
    c = bsr_spadd.bsr_spadd(a, b_, block_size=bs, backend=backend)
    np.testing.assert_allclose(c.to_dense(), a.to_dense() + b_.to_dense(),
                               rtol=1e-5, atol=1e-5)


def test_spadd_symbolic_union():
    from repro.core import BSR
    a, b_ = _sparse(64, 64, 0.05, 1), _sparse(64, 64, 0.05, 2)
    ba, bb = BSR.from_csr(a, 16), BSR.from_csr(b_, 16)
    c_ptrs, c_cols, ia, ib = bsr_spadd.spadd_symbolic(ba, bb)
    assert c_ptrs[-1] == len(c_cols) == len(ia) == len(ib)
    # union size >= each input's block count
    assert len(c_cols) >= max(ba.n_blocks, bb.n_blocks)


# ----------------------------------------------------------------- SpGEMM
@pytest.mark.parametrize("n,bs", [(48, 8), (64, 16), (130, 32)])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bsr_spgemm_allclose(n, bs, backend):
    a, b_ = _sparse(n, n, 0.08, n), _sparse(n, n, 0.08, n + 5)
    c = bsr_spgemm.bsr_spgemm(a, b_, block_size=bs, backend=backend)
    ref = a.to_dense() @ b_.to_dense()
    np.testing.assert_allclose(c.to_dense(), ref, rtol=2e-4, atol=2e-4)


def test_bsr_spgemm_rectangular():
    a = _sparse(60, 90, 0.1, 11)
    b_ = _sparse(90, 40, 0.1, 12)
    c = bsr_spgemm.bsr_spgemm(a, b_, block_size=16, backend="jnp")
    np.testing.assert_allclose(c.to_dense(), a.to_dense() @ b_.to_dense(),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- MoE GMM
@pytest.mark.parametrize("tm", [32, 64])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_moe_gmm_allclose(tm, backend):
    T, K, N, E = 200, 64, 96, 3
    tokens = RNG.standard_normal((T, K)).astype(np.float32)
    eot = RNG.integers(0, E, T)
    x, tile_e, inv = moe_gmm.route_and_pad(tokens, eot, E, tile_m=tm)
    w = RNG.standard_normal((E, K, N)).astype(np.float32)
    out = np.asarray(moe_gmm.moe_gmm(jnp.asarray(tile_e), jnp.asarray(x),
                                     jnp.asarray(w), tile_m=tm, tile_n=32,
                                     tile_k=32, backend=backend))
    valid = inv >= 0
    expect = np.einsum("mk,mkn->mn", tokens[inv[valid]], w[eot[inv[valid]]])
    np.testing.assert_allclose(out[valid], expect, rtol=2e-4, atol=2e-4)


def test_route_and_pad_inverse_property():
    T, E, tm = 133, 4, 32
    tokens = RNG.standard_normal((T, 8)).astype(np.float32)
    eot = RNG.integers(0, E, T)
    x, tile_e, inv = moe_gmm.route_and_pad(tokens, eot, E, tile_m=tm)
    # every source token appears exactly once
    assert sorted(inv[inv >= 0].tolist()) == list(range(T))
    # rows grouped consistently with tile_expert
    tok_expert = np.repeat(tile_e, tm)
    for i, src in enumerate(inv):
        if src >= 0:
            assert tok_expert[i] == eot[src]


# --------------------------------------------------------- Flash attention
@pytest.mark.parametrize("s,d,bq,bk", [(128, 32, 32, 32), (256, 64, 64, 128),
                                       (128, 128, 128, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(s, d, bq, bk, causal):
    q = RNG.standard_normal((2, s, d)).astype(np.float32)
    k = RNG.standard_normal((2, s, d)).astype(np.float32)
    v = RNG.standard_normal((2, s, d)).astype(np.float32)
    out = np.asarray(flash_attention.flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        block_q=bq, block_k=bk, backend="interpret"))
    ref = np.asarray(flash_attention.ref_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_chunked_attention():
    """The Pallas kernel and the model's jnp chunked attention agree."""
    from repro.configs import get_config
    from repro.models.attention import chunked_attention
    cfg = get_config("llama3.2-3b", reduced=True)
    B, S, H, D = 2, 128, 4, 16
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    out_model = chunked_attention(cfg, q, k, v, causal=True, chunk=32)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out_kernel = flash_attention.flash_attention(
        qf, kf, vf, causal=True, block_q=32, block_k=32, backend="interpret")
    out_kernel = out_kernel.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_kernel),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- dtype sweep
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), ("bfloat16", 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    s, d = 128, 64
    q = RNG.standard_normal((2, s, d)).astype(np.float32)
    k = RNG.standard_normal((2, s, d)).astype(np.float32)
    v = RNG.standard_normal((2, s, d)).astype(np.float32)
    jd = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    out = np.asarray(flash_attention.flash_attention(
        jnp.asarray(q, jd), jnp.asarray(k, jd), jnp.asarray(v, jd),
        causal=True, block_q=64, block_k=64, backend="interpret"),
        dtype=np.float32)
    ref = np.asarray(flash_attention.ref_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_moe_gmm_dtypes(dtype, tol):
    T, K, N, E, tm = 128, 32, 64, 2, 32
    tokens = RNG.standard_normal((T, K)).astype(np.float32)
    eot = RNG.integers(0, E, T)
    x, tile_e, inv = moe_gmm.route_and_pad(tokens, eot, E, tile_m=tm)
    w = RNG.standard_normal((E, K, N)).astype(np.float32)
    out = np.asarray(moe_gmm.moe_gmm(
        jnp.asarray(tile_e), jnp.asarray(x, dtype), jnp.asarray(w, dtype),
        tile_m=tm, tile_n=32, tile_k=32, backend="interpret"),
        dtype=np.float32)
    valid = inv >= 0
    expect = np.einsum("mk,mkn->mn", tokens[inv[valid]], w[eot[inv[valid]]])
    scale = np.abs(expect).max()
    np.testing.assert_allclose(out[valid] / scale, expect / scale,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 5e-2)])
def test_bsr_spmv_dtypes(dtype, tol):
    csr = _sparse(128, 128, 0.08, 21)
    x = RNG.standard_normal(128).astype(np.float32)
    ell = bsr_spmv.ops.prepare(csr, 32)
    idx, cols, blocks, _ = bsr_spmv.ops.ell_device_arrays(ell)
    from repro.kernels.bsr_spmv.kernel import bsr_spmv_pallas
    n_bc = -(-128 // 32)
    xb = jnp.asarray(np.pad(x, (0, n_bc * 32 - 128)).reshape(n_bc, 32), dtype)
    y = np.asarray(bsr_spmv_pallas(idx, cols, jnp.asarray(ell.valid_counts),
                                   blocks.astype(dtype), xb, interpret=True),
                   dtype=np.float32)
    ref = bsr_spmv.ops.spmv_oracle(csr, x)
    scale = max(np.abs(ref).max(), 1e-6)
    np.testing.assert_allclose(y.reshape(-1)[:128] / scale, ref / scale,
                               rtol=tol, atol=tol)


# ------------------------------------------------- SMEM-split launches
# A tiny budget forces many launches on small operands: 8 rows of two 2-D
# tables (or 128 cells of three 1-D streams, SMEM's 1-D granule) a launch.
TINY_2D = 2 * 4 * 128 * 8
TINY_CELLS = 3 * 4 * 128


def test_smem_budget_math():
    """Table costs match what the v5e compiler accepts (two (1000, 16)
    int32 tables fit its 1 MiB SMEM, two (1024, 16) do not), and every
    range ``row_ranges`` cuts fits the budget and tiles the rows."""
    from repro.sparse.smem import (SMEM_BUDGET_BYTES, launch_bytes,
                                   row_ranges, table_bytes)
    assert 2 * table_bytes((1000, 16)) < 1 << 20 <= 2 * table_bytes((1024, 16))
    for shapes in ([(2048, 12)] * 2, [(196608,)] * 3, [(10, 3000)] * 2,
                   [(6144, 12)] * 2, [(5,)] * 3):
        rr = row_ranges(shapes[0][0], shapes)
        assert rr[0][0] == 0 and rr[-1][1] == shapes[0][0]
        assert all(a[1] == b[0] for a, b in zip(rr, rr[1:]))
        for lo, hi in rr:
            assert launch_bytes(shapes, hi - lo) <= SMEM_BUDGET_BYTES


@pytest.mark.parametrize("layout", ["ell", "sell"])
@pytest.mark.parametrize("k", [None, 3])
def test_split_matvec_launch_matches_oracle(layout, k):
    """ELL split by block-row range and SELL split mid-row give the
    unsplit product (Pallas interpreter)."""
    from repro.kernels.bsr_spmv import kernel as K
    from repro.sparse.smem import row_ranges, split_cells, split_rows
    n, bs = 200, 8
    csr = _sparse(n, n, 0.1, 21)
    X = RNG.standard_normal((n,) if k is None else (n, k)).astype(np.float32)
    n_bc = -(-n // bs)
    xp = np.zeros((n_bc * bs,) + X.shape[1:], np.float32)
    xp[:n] = X
    xb = jnp.asarray(xp.reshape((n_bc, bs) + X.shape[1:]))
    if layout == "ell":
        a = bsr_spmv.ops.prepare(csr, bs)
        tables = (jnp.asarray(a.block_indices), jnp.asarray(a.block_cols))
        blocks = jnp.asarray(a.blocks)
        if k is None:   # the SpMV's valid-count table rides the split
            tables += (jnp.asarray(a.valid_counts),)
            y = split_rows(lambda i, c, v: K.bsr_spmv_pallas(
                i, c, v, blocks, xb, interpret=True), tables, budget=TINY_2D)
        else:
            y = split_rows(lambda i, c: K.bsr_spmm_pallas(
                i, c, blocks, xb, interpret=True), tables, budget=TINY_2D)
        budget = TINY_2D
    else:
        a = bsr_spmv.ops.prepare_sell(csr, bs, 4, 16)
        kern = K.bsr_spmv_sell_pallas if k is None else K.bsr_spmm_sell_pallas
        tables = (jnp.asarray(a.cell_block), jnp.asarray(a.cell_col),
                  jnp.asarray(a.cell_row))
        blocks = jnp.asarray(a.blocks)
        y = split_cells(
            lambda b, c, r: kern(b, c, r, blocks, xb, a.n_block_rows,
                                 interpret=True),
            tables, tables[2], a.n_block_rows, budget=TINY_CELLS)
        y = jnp.zeros_like(y).at[jnp.asarray(a.row_perm)].set(y)
        # cuts every 128 cells land inside multi-cell rows
        assert (np.diff(a.cell_row)[127::128] == 0).any()
        budget = TINY_CELLS
    assert len(row_ranges(tables[0].shape[0], [t.shape for t in tables],
                          budget)) > 2
    y = np.asarray(y).reshape((-1,) + X.shape[1:])[:n]
    ref = (bsr_spmv.ops.spmv_oracle(csr, X) if k is None
           else bsr_spmv.ops.spmm_oracle(csr, X))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


def test_split_spgemm_cells_matches_reference():
    """The Gustavson cell stream cut every 128 cells (mid output block)."""
    from repro.core import BSR
    from repro.kernels.bsr_spgemm.kernel import bsr_spgemm_cells_pallas
    from repro.kernels.bsr_spgemm.ops import spgemm_symbolic_cells
    from repro.kernels.bsr_spgemm.ref import ref_cell_gemm
    from repro.sparse.smem import split_cells
    # 9 block-rows x 9 block-cols: 9 cells per C block, so cuts every 128
    # cells land mid-block
    a, b_ = _sparse(72, 72, 0.15, 31), _sparse(72, 72, 0.15, 32)
    ba, bb = BSR.from_csr(a, 8), BSR.from_csr(b_, 8)
    _, c_cols, ca, cb, cc = spgemm_symbolic_cells(ba, bb)
    n_c = int(c_cols.size)
    tables = tuple(jnp.asarray(t) for t in (ca, cb, cc))
    ab, bbk = jnp.asarray(ba.blocks), jnp.asarray(bb.blocks)
    got = split_cells(
        lambda x, y, z: bsr_spgemm_cells_pallas(x, y, z, ab, bbk, n_c,
                                                interpret=True),
        tables, tables[2], n_c, budget=TINY_CELLS)
    want = ref_cell_gemm(*tables, ab, bbk, n_c)
    assert ca.size > 2 * 128 and (np.diff(cc)[127::128] == 0).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_compile_cache_honours_env_else_checkout(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code. Unset:
    the cache goes to ``.jax_cache`` at the checkout root."""
    from pathlib import Path

    import jax
    from repro.kernels.common import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
