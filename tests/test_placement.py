"""Placement by size (DESIGN.md §10): ``plan()`` row-shards an operand that
one chip cannot hold over the local chips, the Pallas SpMV runs under
``shard_map`` with x replicated and y gathered onto x's device, shards are
built one at a time and keep no host copy, the PreparedStore charges each
chip its own share, and the guard checks a sharded launch once.

The four-chip cases run ``tests/placement_four_devices.py`` in a child
process on four virtual CPU devices, its kernels in interpret mode; the
shape prediction placement rests on is checked here against real builds."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.autotune import Schedule
from repro.core.csr import BSR, CSR
from repro.core.synthetic import gen_stencil27, gen_zipf
from repro.sparse import PreparedStore, SparseTensor, plan
from repro.sparse.tensor import container_shapes, prepared_nbytes

HERE = os.path.dirname(os.path.abspath(__file__))
# hpcg.cg's limit on the row error over |A||x| (bench/limits/hpcg.cg.json)
HPCG_TOL = 2e-6


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(HERE), "src")]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "placement_four_devices.py")],
        env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_an_operand_too_large_for_one_chip_is_placed_over_four(four):
    assert four["devices"] == 4
    assert four["shards"] == 4 and four["source"] == "sharded-nnz"
    assert four["homes"] == [0, 1, 2, 3]
    assert four["describe"].endswith("via sharded-nnz shards=4")
    assert four["count_gauge"] == 4


def test_an_operand_that_fits_stays_on_one_chip(four):
    # memory read as ten times the operand, and as the CPU reports it (none)
    assert four["fits_shards"] == 1 and four["unpatched_shards"] == 1


def test_placed_pallas_output_matches_one_device_and_the_reference(four):
    assert four["vs_one"] == 0.0
    assert four["err"] <= HPCG_TOL
    assert four["rr_err"] <= HPCG_TOL


def test_placed_output_is_one_device_array_on_x_device(four):
    # x lives on device 2: so does y, on both launch paths
    assert four["is_array"]
    assert four["y_devices"] == [2] and four["rr_devices"] == [2]


def test_exchange_bytes_match_the_hand_count(four):
    assert four["launches"] == 1
    assert four["exchange"] == four["hand_exchange"]


def test_no_shard_keeps_a_host_copy(four):
    assert four["host_operands"] == 0       # only the stacked device arrays
    assert four["rr_host_copies"] == 0


def test_store_charges_each_chip_its_share(four):
    """Under a budget the whole operand overflows, each chip holds its own
    shard: nothing rejected or evicted, and a warm re-plan rebuilds
    nothing. The partition's host arrays count on the default device."""
    assert four["store_rejected"] == 0 and four["store_entries"] == 2
    share = four["store_device_bytes"]
    assert len(share) == 4 and share[0] == share[1] == share[2] < share[3]
    assert four["warm_rebuilds"] == 0


def test_the_guard_checks_a_sharded_launch_once(four):
    assert four["finite_checks"] == 1


def test_the_selector_decides_each_shard(four):
    assert four["sel_shards"] == 4 and four["sel_shard_requests"] == 4
    assert all(s.startswith("selector-") for s in four["sel_sources"])
    assert four["sel_vs_explicit"] == 0.0


# ------------------------------------------- shapes placement rests on

SCHEDULES = [Schedule("bsr", 32, 1.0), Schedule("bsr", 16, 0.9),
             Schedule("bsr", 32, 1.0, layout="sell", slice_height=4),
             Schedule("bsr", 8, 1.0, layout="sell", slice_height=8),
             Schedule("dense", 128, 1.0)]


@pytest.mark.parametrize("bucket", [False, True])
@pytest.mark.parametrize("sched", SCHEDULES, ids=str)
@pytest.mark.parametrize("which", ["stencil", "zipf"])
def test_container_shapes_predict_the_build(which, sched, bucket):
    A = (gen_stencil27(6, 5, 7, seed=1) if which == "stencil"
         else gen_zipf(300, seed=4, a=1.6))
    st = SparseTensor.from_csr(A, schedule=sched, shape_bucket=bucket)
    want = {k: tuple(v.shape) for k, v in st.arrays.items()}
    assert container_shapes(A, sched, shape_bucket=bucket) == want
    assert prepared_nbytes(A, sched, shape_bucket=bucket) == sum(
        v.nbytes for v in st.arrays.values())


@pytest.mark.parametrize("bs", [1, 4, 32, 128])
def test_block_row_tiles_count_bsr_blocks(bs):
    A = gen_zipf(257, seed=2, a=1.4)
    np.testing.assert_array_equal(A.block_row_tiles(bs),
                                  BSR.from_csr(A, bs).blocks_per_row())


def test_bsr_sums_repeated_entries():
    """A CSR whose row repeats a column takes the summing path."""
    A = CSR(np.array([0, 3, 4]), np.array([1, 1, 0, 2], np.uint32),
            np.array([1.0, 2.0, 3.0, 4.0], np.float32), (2, 4))
    np.testing.assert_array_equal(BSR.from_csr(A, 2).to_dense(),
                                  [[3, 3, 0, 0], [0, 0, 4, 0]])


def test_one_device_never_places(monkeypatch):
    import importlib
    plan_mod = importlib.import_module("repro.sparse.plan")
    monkeypatch.setattr(plan_mod, "chip_memory_bytes", lambda: 1)
    A = gen_stencil27(6, seed=0)
    p = plan("spmv", A, schedule=Schedule("bsr", 32, 1.0), backend="jnp")
    assert p.n_shards == 1


def test_store_charges_one_device_as_before():
    """On one device every entry charges it, so the LRU evicts oldest first
    and a value larger than the budget is rejected, as ever."""
    import jax.numpy as jnp
    store = PreparedStore(byte_budget=100)
    store.put(("a",), jnp.zeros(10, jnp.float32))        # 40 bytes
    store.put(("b",), "host value", nbytes=40)
    store.put(("c",), jnp.zeros(10, jnp.float32))
    assert ("a",) not in store and len(store) == 2
    assert store.bytes_in_use == 80 and store.evictions == 1
    assert not store.put(("d",), jnp.zeros(30, jnp.float32))
    assert store.rejected == 1
    assert list(store.device_bytes().values()) == [80]
