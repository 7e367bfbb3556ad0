"""Sharded sparse execution (DESIGN.md §10): partitioner properties (rows
covered exactly once, nnz-balanced never worse than equal-rows under Eq. 5),
sharded-vs-single-device numerical equivalence for spmv/spmm on gen_zipf
across 1/2/4 shards, per-shard selector provenance, warm-plan prep skips
through the PreparedStore, the ShardedSparseTensor pytree contract, and the
store's index save/load. Runs under any local device count: with fewer
devices than shards the planner falls back to round-robin per-shard
launches (scripts/smoke.sh re-runs this file under 4 simulated devices)."""
import jax
import numpy as np
import pytest

from repro.core import CSR, TPU_V5E, ScheduleTuner, corpus, shard_counters
from repro.core.autotune import Schedule
from repro.core.synthetic import gen_zipf
from repro.selector import ScheduleCache, SelectorService
from repro.sparse import (PreparedStore, ShardedSparseTensor, bounds_imbalance,
                          launch_count, partition_rows, plan, plan_sharded,
                          reset_counters, slice_rows)
from repro.sparse.partition import equal_row_bounds, nnz_balanced_bounds


@pytest.fixture(scope="module")
def zipf():
    return gen_zipf(512, seed=2, a=1.6)


@pytest.fixture(scope="module")
def service():
    tuner = ScheduleTuner("spmv", TPU_V5E).fit(
        corpus(n_matrices=9, n_min=256, n_max=384, seed=3), max_mats=9)
    return SelectorService(tuner, cache=ScheduleCache())


def _x(n, k=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if k is None else (n, k)
    return rng.standard_normal(shape).astype(np.float32)


# ------------------------------------------------------------- partitioner

@pytest.mark.parametrize("strategy", ["nnz", "rows"])
@pytest.mark.parametrize("n_shards", [1, 2, 4, 7])
def test_partition_covers_rows_exactly_once(zipf, strategy, n_shards):
    part = partition_rows(zipf, n_shards, strategy)
    bounds = np.asarray(part.bounds)
    assert bounds[0] == 0 and bounds[-1] == zipf.n_rows
    assert (np.diff(bounds) >= 1).all()          # strictly increasing
    assert sum(part.shard_rows()) == zipf.n_rows
    assert sum(part.shard_nnz) == zipf.nnz
    # reassembling the shards reproduces the matrix
    dense = np.concatenate([slice_rows(zipf, bounds[i], bounds[i + 1])
                            .to_dense() for i in range(part.n_parts)])
    np.testing.assert_array_equal(dense, zipf.to_dense())


@pytest.mark.parametrize("seed,a", [(0, 1.09), (1, 1.5), (2, 1.6), (3, 2.0)])
@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_nnz_bounds_never_worse_than_equal_rows(seed, a, n_shards):
    A = gen_zipf(384, seed=seed, a=a)
    lengths = A.row_lengths()
    nnz_imb = bounds_imbalance(lengths, nnz_balanced_bounds(lengths, n_shards))
    row_imb = bounds_imbalance(lengths, equal_row_bounds(A.n_rows, n_shards))
    assert nnz_imb["mean"] <= row_imb["mean"] + 1e-12


def test_nnz_bounds_strictly_better_on_skewed(zipf):
    """The acceptance-level fact: on zipf a>=1.5 the nnz-balanced split's
    max-shard deviation is strictly below the equal-row split's."""
    lengths = zipf.row_lengths()
    for n_shards in (2, 4, 8):
        nnz_imb = bounds_imbalance(lengths,
                                   nnz_balanced_bounds(lengths, n_shards))
        row_imb = bounds_imbalance(lengths,
                                   equal_row_bounds(zipf.n_rows, n_shards))
        assert nnz_imb["max"] < row_imb["max"]


def test_partition_degenerate_cases():
    # more shards than rows: clamped, still a valid cover
    A = gen_zipf(5, seed=0)
    part = partition_rows(A, 16)
    assert part.n_parts <= 5 and sum(part.shard_rows()) == 5
    # empty matrix
    empty = CSR(np.zeros(4, np.int64), np.zeros(0, np.uint32),
                np.zeros(0, np.float32), (3, 3))
    part = partition_rows(empty, 2)
    assert sum(part.shard_rows()) == 3
    assert part.imbalance() == {"mean": 0.0, "max": 0.0}


def test_shard_counters_features(zipf):
    part = partition_rows(zipf, 4, "nnz")
    feats = shard_counters(zipf, part.bounds)
    assert len(feats) == 4
    assert sum(f["nnz"] for f in feats) == zipf.nnz
    assert all(f["nnz_share_dev"] < 0.05 for f in feats)  # balanced split
    rows_feats = shard_counters(zipf, equal_row_bounds(zipf.n_rows, 4))
    assert max(f["nnz_share_dev"] for f in rows_feats) \
        > max(f["nnz_share_dev"] for f in feats)


# ------------------------------------------------- sharded-vs-single equiv

@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("layout", ["ell", "sell"])
def test_plan_sharded_spmv_matches_single_device(zipf, n_shards, layout):
    sched = (Schedule("bsr", 32, 1.0) if layout == "ell"
             else Schedule("bsr", 32, 1.0, layout="sell", slice_height=4))
    x = _x(zipf.shape[1])
    y_single = np.asarray(plan("spmv", (zipf,), schedule=sched,
                               backend="jnp").execute(x))
    p = plan_sharded("spmv", (zipf,), n_shards=n_shards, schedule=sched,
                     backend="jnp")
    y_sharded = np.asarray(p.execute(x))
    assert p.n_shards == n_shards
    np.testing.assert_allclose(y_sharded, y_single, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_sharded, zipf.to_dense() @ x,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_plan_sharded_spmm_matches_single_device(zipf, n_shards):
    sched = Schedule("bsr", 32, 1.0, layout="sell", slice_height=4, n_rhs=3)
    X = _x(zipf.shape[1], k=3)
    Y_single = np.asarray(plan("spmm", (zipf,), schedule=sched,
                               backend="jnp").execute(X))
    Y_sharded = np.asarray(plan_sharded(
        "spmm", (zipf,), n_shards=n_shards, schedule=sched,
        backend="jnp").execute(X))
    np.testing.assert_allclose(Y_sharded, Y_single, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_plan_sharded_reports_shard_devices(zipf, n_shards):
    """``Plan.shard_devices`` is read from the placed arrays: one device
    per shard, round-robin over the local devices (the shard_map path,
    taken when enough devices exist, puts slot i on device i)."""
    p = plan_sharded("spmv", (zipf,), n_shards=n_shards,
                     schedule=Schedule("bsr", 32, 1.0), backend="jnp")
    devs = jax.devices()
    assert p.shard_devices == [frozenset({devs[i % len(devs)]})
                               for i in range(n_shards)]


def test_plan_sharded_heterogeneous_schedules(zipf):
    """Per-shard schedules may disagree (the skewed-matrix case the
    selector produces); the fallback path still matches the dense oracle."""
    scheds = [Schedule("bsr", 32, 1.0),
              Schedule("bsr", 16, 1.0, layout="sell", slice_height=4),
              Schedule("bsr", 64, 1.0),
              Schedule("bsr", 32, 1.0, layout="sell", slice_height=8)]
    x = _x(zipf.shape[1])
    p = plan_sharded("spmv", (zipf,), n_shards=4, schedules=scheds,
                     backend="jnp")
    assert p.schedule is None          # no single schedule describes it
    np.testing.assert_allclose(np.asarray(p.execute(x)),
                               zipf.to_dense() @ x, rtol=2e-4, atol=2e-4)


def test_plan_sharded_one_logical_launch(zipf):
    reset_counters()
    p = plan_sharded("spmv", (zipf,), n_shards=4,
                     schedule=Schedule("bsr", 32, 1.0), backend="jnp")
    p.execute(_x(zipf.shape[1]))
    assert launch_count("spmv") == 1   # one logical dispatch per execute


def test_plan_sharded_rejects_unknown_op_and_strategy(zipf):
    with pytest.raises(ValueError, match="no sharded execution path"):
        plan_sharded("spgemm", (zipf, zipf), n_shards=2)
    with pytest.raises(ValueError, match="strategy"):
        plan_sharded("spmv", (zipf,), n_shards=2, strategy="hash")


# ------------------------------------------------- selector + store paths

def test_plan_sharded_selector_provenance_per_shard(zipf, service):
    p = plan_sharded("spmv", (zipf,), n_shards=4, selector=service)
    assert p.shard_provenance is not None and len(p.shard_provenance) == 4
    assert all(pr["source"].startswith("selector-")
               for pr in p.shard_provenance)
    assert all(pr["fingerprint_key"] for pr in p.shard_provenance)
    x = _x(zipf.shape[1])
    np.testing.assert_allclose(np.asarray(p.execute(x)),
                               zipf.to_dense() @ x, rtol=2e-4, atol=2e-4)
    tel = service.telemetry()
    assert tel["shard_requests"] >= 4 and tel["sharded_plans"] >= 1


def test_plan_sharded_warm_skips_partition_and_prep(zipf, service):
    """Repeat sharded plans hit the PreparedStore for the row partition AND
    the prepared shard containers (zero-rebuild, distributed flavor)."""
    store = service.prepared_store
    plan_sharded("spmv", (zipf,), n_shards=4, selector=service)
    h0, m0 = store.hits, store.misses
    plan_sharded("spmv", (zipf,), n_shards=4, selector=service)
    assert store.hits >= h0 + 2        # partition entry + shard bundle
    assert store.misses == m0          # nothing rebuilt on the warm plan
    # warm decisions come out of the schedule cache
    p = plan_sharded("spmv", (zipf,), n_shards=4, selector=service)
    assert {pr["source"] for pr in p.shard_provenance} == {"selector-cache"}


def test_plan_sharded_sst_operand_guards(zipf, service):
    """A prepared ShardedSparseTensor carries its schedules: re-selection
    and re-partitioning are refused rather than silently ignored, and the
    provenance says 'prepared', not 'explicit'."""
    sst = ShardedSparseTensor.from_csr(zipf, 2, Schedule("bsr", 32, 1.0))
    with pytest.raises(TypeError, match="CSR first operand"):
        plan_sharded("spmv", (sst,), selector=service)
    with pytest.raises(ValueError, match="re-partition"):
        plan_sharded("spmv", (sst,), n_shards=4)
    p = plan_sharded("spmv", (sst,), backend="jnp")
    assert {pr["source"] for pr in p.shard_provenance} == {"prepared"}


def test_partition_store_entry_bytes_accounted(zipf):
    """The cached row partition holds host CSR slices (not pytree leaves),
    so its bytes must be accounted explicitly — otherwise the LRU could
    never evict a stream of distinct-matrix partitions."""
    from repro.sparse import content_key
    store = PreparedStore()
    plan_sharded("spmv", (zipf,), n_shards=2,
                 schedule=Schedule("bsr", 32, 1.0), store=store)
    key = ("row_partition", content_key(zipf), 2, "nnz")
    assert key in store
    _, nbytes = store._entries[key]
    assert nbytes >= zipf.col_idxs.nbytes + zipf.nnz_vals.nbytes


def test_plan_sharded_with_tuner(zipf, service):
    p = plan_sharded("spmv", (zipf,), n_shards=2, selector=service.tuner)
    assert {pr["source"] for pr in p.shard_provenance} == {"tuner"}
    x = _x(zipf.shape[1])
    np.testing.assert_allclose(np.asarray(p.execute(x)),
                               zipf.to_dense() @ x, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- sharded container

def test_sharded_tensor_pytree_roundtrip(zipf):
    sst = ShardedSparseTensor.from_csr(zipf, 3, Schedule("bsr", 32, 1.0))
    leaves, treedef = jax.tree_util.tree_flatten(sst)
    assert all(isinstance(l, jax.Array) for l in leaves)
    sst2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert sst2.meta == sst.meta and sst2.n_shards == 3
    assert sst2.schedules() == sst.schedules()
    # a prebuilt sharded operand plans without re-partitioning
    x = _x(zipf.shape[1])
    y = np.asarray(plan_sharded("spmv", (sst,), backend="jnp").execute(x))
    np.testing.assert_allclose(y, zipf.to_dense() @ x, rtol=2e-4, atol=2e-4)


def test_sharded_tensor_shard_rows_match_bounds(zipf):
    sst = ShardedSparseTensor.from_csr(zipf, 4, strategy="nnz")
    assert sum(sst.shard_rows()) == zipf.n_rows
    for st, rows in zip(sst.shards, sst.shard_rows()):
        assert st.true_shape[0] == rows


# ----------------------------------------------------- store save / load

def test_prepared_store_save_load_roundtrip(tmp_path, zipf):
    store = PreparedStore()
    plan_sharded("spmv", (zipf,), n_shards=2,
                 schedule=Schedule("bsr", 32, 1.0), store=store)
    plan_sharded("spmv", (zipf,), n_shards=2,
                 schedule=Schedule("bsr", 32, 1.0), store=store)
    path = str(tmp_path / "store.json")
    store.save(path)
    fresh = PreparedStore()
    prior = fresh.load(path)
    assert len(prior["entries"]) == len(store)
    tel = fresh.telemetry()
    assert tel["prior_entries"] == float(len(store))
    assert tel["prior_hit_rate"] == pytest.approx(
        store.telemetry()["hit_rate"])
    # device buffers are NOT persisted: a fresh store serves misses
    assert fresh.hits == 0 and len(fresh) == 0


def test_prepared_store_load_missing_and_stale(tmp_path):
    store = PreparedStore()
    assert store.load(str(tmp_path / "absent.json")) == {}
    stale = tmp_path / "stale.json"
    stale.write_text('{"version": 999, "entries": []}')
    assert store.load(str(stale)) == {}
    assert "prior_entries" not in store.telemetry()
