"""Placement by size on four CPU devices, for ``tests/test_placement.py``.

Run as ``XLA_FLAGS=--xla_force_host_platform_device_count=4 python
tests/placement_four_devices.py``: plans a small 27-point stencil with one
chip's memory read as too small for it, runs the Pallas kernels in
interpret mode, and prints one JSON object of readings on its last line.
"""
import importlib
import json
import sys

import jax
import numpy as np

from repro.core import TPU_V5E, ScheduleTuner, corpus
from repro.core.autotune import Schedule
from repro.core.synthetic import gen_stencil27
from repro.obs import default_registry
from repro.selector import ScheduleCache, SelectorService
from repro.sparse import (PreparedStore, bucket_edge, plan, plan_sharded,
                          resilience)
from repro.sparse.tensor import prepared_nbytes

# the module, which the package's ``plan`` function shadows
plan_mod = importlib.import_module("repro.sparse.plan")

ELL = Schedule("bsr", 32, 1.0)


def reference(A, x):
    """(A x, |A| |x|) in float64 straight from the CSR arrays."""
    rows = np.repeat(np.arange(A.n_rows), A.row_lengths())
    prod = A.nnz_vals.astype(np.float64) * x.astype(np.float64)[A.col_idxs]
    return (np.bincount(rows, prod, minlength=A.n_rows),
            np.bincount(rows, np.abs(prod), minlength=A.n_rows))


def row_error(y, ref, bound):
    return float(np.max(np.abs(np.asarray(y, np.float64) - ref)
                        / np.maximum(bound, 1e-30)))


def main() -> dict:
    devs = jax.devices()
    A = gen_stencil27(8, 8, 32, seed=5)
    x = jax.device_put(np.random.default_rng(1).standard_normal(
        A.n_cols).astype(np.float32), devs[2])
    ref, bound = reference(A, np.asarray(x))
    out = {"devices": len(devs)}

    # one chip's memory as reported: none on the CPU, so nothing is placed
    one = plan("spmv", A, schedule=ELL, backend="interpret")
    y_one = np.asarray(one.execute(np.asarray(x)))
    out["unpatched_shards"] = one.n_shards

    need = prepared_nbytes(A, ELL, shape_bucket=True)
    plan_mod.chip_memory_bytes = lambda: 10 * need      # fits
    out["fits_shards"] = plan("spmv", A, schedule=ELL,
                              backend="interpret").n_shards
    plan_mod.chip_memory_bytes = lambda: need // 2      # does not fit

    reg = default_registry()
    finite_calls = []
    verdict = resilience._finite_verdict
    resilience._finite_verdict = lambda v: (finite_calls.append(1),
                                            verdict(v))[1]
    # a budget the whole operand would overflow, but each chip's share fits
    store = PreparedStore(byte_budget=need * 3 // 4)
    p = plan("spmv", A, schedule=ELL, backend="interpret", store=store)
    b0, l0 = reg.get("shard.exchange_bytes"), reg.get("shard.launches")
    y = p.execute(x)
    out.update(
        shards=p.n_shards, source=p.source, describe=p.describe(),
        homes=sorted(d.id for h in p.shard_devices for d in h),
        is_array=isinstance(y, jax.Array),
        y_devices=sorted(d.id for d in y.devices()),
        vs_one=float(np.max(np.abs(np.asarray(y) - y_one))),
        err=row_error(y, ref, bound),
        exchange=reg.get("shard.exchange_bytes") - b0,
        launches=reg.get("shard.launches") - l0,
        count_gauge=reg.gauge("shard.count"),
        finite_checks=len(finite_calls),
        host_operands=len(p.operands),
        store_entries=len(store), store_rejected=store.rejected,
        store_device_bytes=sorted(store.device_bytes().values()))
    resilience._finite_verdict = verdict
    # the hand count: padded x out to the three other chips, the largest
    # shard's rows back from each of them, f32
    from repro.sparse.partition import partition_rows
    part = partition_rows(A, 4, "nnz")
    width = bucket_edge(-(-A.n_cols // 32)) * 32
    out["hand_exchange"] = 3 * 4 * (width + max(part.shard_rows()))

    # a warm re-plan finds every shard in the store, nothing rebuilt
    misses = store.misses
    plan("spmv", A, schedule=ELL, backend="interpret", store=store)
    out["warm_rebuilds"] = store.misses - misses

    # shard schedules that differ: round-robin, still one device array on
    # x's device, and no shard keeps its host container
    scheds = [ELL, Schedule("bsr", 16, 1.0), ELL,
              Schedule("bsr", 32, 1.0, layout="sell", slice_height=4)]
    rr = plan_sharded("spmv", A, n_shards=4, schedules=scheds,
                      backend="interpret")
    y_rr = rr.execute(x)
    out.update(rr_err=row_error(y_rr, ref, bound),
               rr_devices=sorted(d.id for d in y_rr.devices()),
               rr_host_copies=sum(st._host is not None
                                  for st in rr.operands[0].shards))

    # through the selector: one decision for the whole, then one per shard
    tuner = ScheduleTuner("spmv", TPU_V5E).fit(
        corpus(n_matrices=6, n_min=128, n_max=256, seed=3), max_mats=4)
    svc = SelectorService(tuner, cache=ScheduleCache(),
                          prepared_store=PreparedStore(need))
    sel = svc.select(A).schedule
    plan_mod.chip_memory_bytes = lambda: prepared_nbytes(
        A, sel, shape_bucket=True) // 2
    ps = plan("spmv", A, selector=svc, backend="interpret")
    same = plan_sharded("spmv", A, n_shards=4, backend="interpret",
                        schedules=[pr["schedule"]
                                   for pr in ps.shard_provenance])
    out.update(sel_shards=ps.n_shards,
               sel_sources=sorted({pr["source"]
                                   for pr in ps.shard_provenance}),
               sel_vs_explicit=float(np.max(np.abs(
                   np.asarray(ps.execute(x)) - np.asarray(same.execute(x))))),
               sel_shard_requests=svc.telemetry()["shard_requests"])
    return out


if __name__ == "__main__":
    print(json.dumps(main()))
    sys.exit(0)
