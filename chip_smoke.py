#!/usr/bin/env python3
"""Bring-up check of the sparse main path on one TPU chip.

Drives the entry points a user calls, on HPCG-style 27-point stencils made
from ``--seed``, and checks every output against a float64 numpy CSR
product that shares no code with the containers or kernels:

* operand  — the 64^3 stencil: 262,144 rows, 6,859,000 nonzeros;
* plan     — ``plan("spmv")`` and ``plan("spmm")`` under explicit ELL
  (bs=128) and SELL (bs=32) schedules and through a ``SelectorService``;
* bucket   — ``plan_bucket("spmv"/"spmm")`` over two different 48^3
  stencils (ELL bs=128, SELL bs=32): one stacked launch, members' tables
  offset into one flat tile stack;
* engine   — a ``ServingEngine`` replay of 16 requests over 48^3 and 64^3
  tenants, batched drains, ledger ``admitted == completed + shed``;
* spgemm   — A*A of the 32^3 stencil through ``plan("spgemm")``.

Every launch must be served by the compiled Pallas kernels: a launch on
another backend, a guard fallback, a dense-reference serve or build, or a
quarantined schedule fails the run, as does any platform but ``tpu``.
The last stdout line is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.

``--four-chips`` instead runs only ``plan_sharded("spmv", n_shards=4)`` on
the 64^3 stencil, one shard_map program with the Pallas kernel and one with
the jnp body, each checked against the one-chip plan and the reference, with the four
shards' arrays on four distinct devices.

Usage:  python chip_smoke.py [--four-chips] [--seed N]
"""
import argparse
import json
import os
import sys
import time
from collections import Counter

import numpy as np

# The f32 level, row by row: |y - y_ref|_i <= TOL * (|A| |x|)_i. An f32
# sum of n rounded products is off by at most ~n * 2^-24 of |A||x|: 1.6e-6
# for a stencil row's 27 terms. A one-pass bf16 MXU product (each operand
# rounded to 2^-8) lands near 4e-3 and fails.
TOL = 1e-5

# A prepared 64^3 operand is 0.8-3.2 GB, past the store's 256 MiB default:
# half of the chip's 16 GB keeps a phase's operands resident across calls.
STORE_BYTES = 8 << 30

# Calls after the first (cold, compiling) one: a launch's warm wall time is
# their median, on the host clock around execute + block_until_ready.
WARM_CALLS = 5


class Failed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Failed(msg)


# ---------------------------------------------------------------- references

def csr_reference(A, X):
    """(A @ X, |A| @ |X|) in float64, straight from the CSR arrays."""
    n = A.shape[0]
    rows = np.repeat(np.arange(n), np.diff(A.row_ptrs))
    cols = A.col_idxs.astype(np.int64)
    vals = A.nnz_vals.astype(np.float64)
    X = np.asarray(X, np.float64)
    Xc = X.reshape(n, -1)
    y = np.empty((n, Xc.shape[1]))
    bound = np.empty_like(y)
    for j in range(Xc.shape[1]):
        prod = vals * Xc[cols, j]
        y[:, j] = np.bincount(rows, prod, minlength=n)
        bound[:, j] = np.bincount(rows, np.abs(prod), minlength=n)
    return y.reshape(X.shape), bound.reshape(X.shape)


def rel_error(y, ref, bound):
    y = np.asarray(y, np.float64)
    check(y.shape == ref.shape, f"output shape {y.shape} != {ref.shape}")
    check(np.isfinite(y).all(), "non-finite output")
    return float(np.max(np.abs(y - ref) / np.maximum(bound, 1e-30)))


def spgemm_reference(A):
    """Entries of A*A in float64 as (row, col, value, |A||A| bound)."""
    n = A.shape[0]
    lens = np.diff(A.row_ptrs).astype(np.int64)
    rows = np.repeat(np.arange(n), lens)
    cols = A.col_idxs.astype(np.int64)
    vals = A.nnz_vals.astype(np.float64)
    # entry (r, k, a) meets every entry of row k
    reps = lens[cols]
    pos = np.repeat(A.row_ptrs[cols].astype(np.int64) - np.cumsum(reps)
                    + reps, reps) + np.arange(int(reps.sum()))
    keys = np.repeat(rows, reps) * n + cols[pos]
    prod = np.repeat(vals, reps) * vals[pos]
    uniq, inv = np.unique(keys, return_inverse=True)
    value = np.bincount(inv, prod)
    bound = np.bincount(inv, np.abs(prod))
    return uniq // n, uniq % n, value, bound


def spgemm_error(C, ref):
    """Relative error of the BSR product at every reference entry, and the
    mass C holds outside them (which must be zero)."""
    rows, cols, value, bound = ref
    bs = C.block_size
    n_bc = -(-C.shape[1] // bs)
    block_row = np.repeat(np.arange(len(C.block_ptrs) - 1),
                          np.diff(C.block_ptrs))
    bkeys = block_row * n_bc + C.block_cols.astype(np.int64)
    order = np.argsort(bkeys)
    want = (rows // bs) * n_bc + cols // bs
    at = np.searchsorted(bkeys[order], want)
    check((at < len(bkeys)).all()
          and (bkeys[order][np.minimum(at, len(bkeys) - 1)] == want).all(),
          "a reference entry falls outside the product's block structure")
    got = np.asarray(C.blocks, np.float64)[order[at], rows % bs, cols % bs]
    err = float(np.max(np.abs(got - value) / np.maximum(bound, 1e-30)))
    outside = float(np.abs(np.asarray(C.blocks, np.float64)).sum()
                    - np.abs(got).sum())
    return err, outside / float(bound.sum())


# ------------------------------------------------------------------- phases

class Smoke:
    def __init__(self, seed):
        import jax
        from repro.obs import Tracer, install_tracer
        from repro.sparse import GuardedExecutor, Quarantine
        self.jax = jax
        self.seed = seed
        self.executor = GuardedExecutor(quarantine=Quarantine())
        self.tracer = install_tracer(Tracer())
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def timed(self, fn):
        """(result, wall seconds, backend-compile seconds) of one call."""
        from repro.sparse import launch_count
        c0, n0, t0 = self.compile_s, launch_count(), time.perf_counter()
        out = fn()
        self.jax.block_until_ready(out if not hasattr(out, "blocks")
                                   else out.blocks)
        return (out, time.perf_counter() - t0, self.compile_s - c0,
                launch_count() - n0)

    def runs(self, fn):
        """One cold and ``WARM_CALLS`` warm ``timed`` calls of ``fn``."""
        return [self.timed(fn) for _ in range(1 + WARM_CALLS)]

    @staticmethod
    def timing(runs):
        """Launches, compile, cold and median warm seconds of ``runs``."""
        warm = [r[1] for r in runs[1:]]
        return (f"launches {sum(r[3] for r in runs)}  compile "
                f"{runs[0][2]:.2f}s  cold {runs[0][1]:.3f}s  warm median "
                f"{np.median(warm):.4f}s (min {min(warm):.4f}s, "
                f"{len(warm)} calls)")

    def report(self, name, plan_, runs, err, extra=""):
        served = plan_.backend
        print(f"{name}: served {served}  {self.timing(runs)}  "
              f"max_rel_err {err:.3e} (tol {TOL:.3e}){extra}", flush=True)
        check(served == "pallas", f"{name} served by {served}, not pallas")
        check(err <= TOL, f"{name} error {err:.3e} over tolerance")

    def planned(self, A, store, label, ref_v, ref_m, x, X, **how):
        from repro.sparse import plan
        for op, rhs, (ref, bound) in (("spmv", x, ref_v), ("spmm", X, ref_m)):
            p = plan(op, A, store=store, executor=self.executor, **how)
            runs = self.runs(lambda: p.execute(rhs))
            err = max(rel_error(r[0], ref, bound) for r in runs)
            self.report(f"{op} {label}", p, runs, err,
                        f"  schedule {p.describe()}")

    def phase_plan(self, tuner):
        from repro.core.autotune import Schedule
        from repro.core.synthetic import gen_stencil27
        from repro.selector import ScheduleCache, SelectorService
        from repro.sparse import PreparedStore
        t0 = time.perf_counter()
        A = gen_stencil27(64, seed=self.seed)
        rng = np.random.default_rng(self.seed)
        x = rng.standard_normal(A.shape[1]).astype(np.float32)
        X = rng.standard_normal((A.shape[1], 8)).astype(np.float32)
        ref_v, ref_m = csr_reference(A, x), csr_reference(A, X)
        print(f"operand: 27-point stencil 64^3, {A.shape[0]} rows, {A.nnz} "
              f"nonzeros, built with references in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        check(A.shape[0] == 262144 and A.nnz == 6859000, "stencil size")
        for label, sched in (
                ("ell bs=128", Schedule("bsr", 128, 1.0)),
                ("sell bs=32 C=8", Schedule("bsr", 32, 1.0, layout="sell",
                                            slice_height=8))):
            self.planned(A, PreparedStore(STORE_BYTES), label,
                         ref_v, ref_m, x, X, schedule=sched)
        svc = SelectorService(tuner, cache=ScheduleCache(),
                              prepared_store=PreparedStore(STORE_BYTES),
                              executor=self.executor,
                              quarantine=self.executor.quarantine)
        self.planned(A, None, "selector", ref_v, ref_m, x, X, selector=svc)

    def phase_bucket(self):
        from repro.core.autotune import Schedule
        from repro.core.synthetic import gen_stencil27
        from repro.sparse import plan_bucket
        mats = [gen_stencil27(48, seed=self.seed + 6),
                gen_stencil27(48, seed=self.seed + 7)]
        rng = np.random.default_rng(self.seed + 8)
        for op, k in (("spmv", None), ("spmm", 8)):
            xs = [rng.standard_normal((A.shape[1],) if k is None
                                      else (A.shape[1], k)).astype(np.float32)
                  for A in mats]
            refs = [csr_reference(A, x) for A, x in zip(mats, xs)]
            for label, sched in (
                    ("ell bs=128", Schedule("bsr", 128, 1.0)),
                    ("sell bs=32 C=8", Schedule("bsr", 32, 1.0, layout="sell",
                                                slice_height=8))):
                p = plan_bucket(op, mats, sched, executor=self.executor)
                runs = self.runs(lambda: p.execute(xs))
                err = max(rel_error(y, *ref) for r in runs
                          for y, ref in zip(r[0], refs))
                self.report(f"{op} bucket 48^3 x2 {label}", p, runs, err,
                            f"  members {p.n_members}")

    def phase_engine(self, tuner):
        from repro.core.synthetic import gen_stencil27
        from repro.selector import ScheduleCache, SelectorService
        from repro.serving import ServingEngine
        from repro.sparse import PreparedStore, launch_count
        tenants = [gen_stencil27(48, seed=self.seed + 1),
                   gen_stencil27(48, seed=self.seed + 2),
                   gen_stencil27(64, seed=self.seed + 3)]
        rng = np.random.default_rng(self.seed + 4)
        svc = SelectorService(tuner, cache=ScheduleCache(),
                              prepared_store=PreparedStore(STORE_BYTES),
                              executor=self.executor,
                              quarantine=self.executor.quarantine)
        outputs = {}
        engine = ServingEngine(svc, slot_max=8,
                               on_result=lambda rid, y: outputs.update(
                                   {rid: y}))
        expect = {}

        def submit(i, t):
            A = tenants[t]
            x = rng.standard_normal(A.shape[1]).astype(np.float32)
            rid = f"req{i}:tenant{t}"
            expect[rid] = (t, x)
            check(engine.submit(f"tenant{t}", A, x, tenant=t, rid=rid),
                  f"{rid} rejected")

        n0, c0, t0 = launch_count(), self.compile_s, time.perf_counter()
        for i, t in enumerate([0, 1, 2, 0, 2, 2, 1, 0, 2, 0, 2, 1, 2]):
            submit(i, t)
        engine.drain_all()
        for i, t in enumerate([0, 1, 2], start=13):   # single-request drains
            submit(i, t)
            engine.drain_all()
        wall = time.perf_counter() - t0
        tel = engine.telemetry()
        errs = []
        for rid, (t, x) in expect.items():
            y = outputs.get(rid)
            check(y is not None, f"engine request {rid} has no output")
            errs.append(rel_error(y, *csr_reference(tenants[t], x)))
        print(f"engine: {len(expect)} requests over tenants 48^3 x2 + 64^3, "
              f"drains {tel['drains']:.0f} (multi-request "
              f"{tel['multi_request_drains']:.0f}, mean size "
              f"{tel['mean_drain_size']:.2f}), launches "
              f"{launch_count() - n0}, compile {self.compile_s - c0:.2f}s, "
              f"wall {wall:.2f}s, max_rel_err {max(errs):.3e}", flush=True)
        print(f"engine ledger: submitted {tel['submitted']:.0f} admitted "
              f"{tel['admitted']:.0f} completed {tel['completed']:.0f} shed "
              f"{tel['shed']:.0f} rejected {tel['rejected']:.0f}", flush=True)
        check(max(errs) <= TOL, "engine output over tolerance")
        check(tel["admitted"] == tel["completed"] + tel["shed"]
              and tel["completed"] == len(expect), "engine ledger")
        check(tel["multi_request_drains"] >= 1, "no batched drain")

    def phase_spgemm(self):
        from repro.core.autotune import Schedule
        from repro.core.synthetic import gen_stencil27
        from repro.sparse import plan
        A = gen_stencil27(32, seed=self.seed + 5)
        ref = spgemm_reference(A)
        for label, sched in (
                ("pairs bs=128", Schedule("bsr", 128, 1.0)),
                ("cells bs=128", Schedule("bsr", 128, 1.0, layout="sell",
                                          slice_height=8))):
            p = plan("spgemm", (A, A), schedule=sched, executor=self.executor)
            runs = self.runs(p.execute)
            errs = [spgemm_error(r[0], ref) for r in runs]
            err = max(e for e, _ in errs)
            outside = max(o for _, o in errs)
            self.report(f"spgemm A*A 32^3 {label}", p, runs, err,
                        f"  ({len(ref[0])} product entries, mass outside "
                        f"them {outside:.1e})")
            check(outside <= TOL, "spgemm mass outside the product")

    def phase_four_chips(self):
        from repro.core.synthetic import gen_stencil27
        from repro.sparse import plan, plan_sharded
        devices = self.jax.devices()
        check(len(devices) >= 4, f"--four-chips needs 4 devices, JAX sees "
              f"{len(devices)}")
        A = gen_stencil27(64, seed=self.seed)
        x = np.random.default_rng(self.seed).standard_normal(
            A.shape[1]).astype(np.float32)
        ref, bound = csr_reference(A, x)
        one = plan("spmv", A, executor=self.executor)
        runs = self.runs(lambda: one.execute(x))
        y_one = np.asarray(runs[-1][0])
        self.report("spmv one chip", one, runs, rel_error(y_one, ref, bound))
        for label, backend in (("pallas shard_map", "auto"),
                               ("jnp shard_map", "jnp")):
            p = plan_sharded("spmv", A, n_shards=4, backend=backend,
                             executor=self.executor)
            runs = self.runs(lambda: p.execute(x))
            homes = p.shard_devices
            check(all(len(h) == 1 for h in homes)
                  and len(frozenset.union(*homes)) == 4,
                  f"{label}: shards sit on {homes}, not 4 devices")
            y = np.asarray(runs[-1][0])
            err = rel_error(y, ref, bound)
            vs_one = float(np.max(np.abs(y - y_one))
                           / max(float(np.max(np.abs(y_one))), 1e-30))
            print(f"spmv sharded {label}: served {p.backend}  shards on "
                  f"{sorted(d.id for h in homes for d in h)}  "
                  f"{self.timing(runs)}  max_rel_err {err:.3e}  max diff vs "
                  f"one chip {vs_one:.3e} (of max |y|)", flush=True)
            check(p.backend == ("pallas" if backend == "auto" else "jnp"),
                  f"{label} served by {p.backend}")
            check(err <= TOL and vs_one <= TOL, f"{label} result mismatch")

    def finish(self, only_pallas):
        """The guard's ledger over every phase, and the backend each traced
        launch was served by."""
        ex = self.executor
        tel = ex.telemetry()
        served = Counter(e["args"].get("backend") for e in self.tracer.events()
                         if e["type"] == "launch")
        print(f"guard: fallbacks {tel['fallbacks']:.0f}  dense_served "
              f"{tel['dense_served']:.0f}  dense_builds "
              f"{tel['dense_builds']:.0f}  quarantine {len(ex.quarantine)}  "
              f"nan_trips {tel['nan_trips']:.0f}  launches by backend "
              f"{dict(served)}", flush=True)
        check(tel["fallbacks"] == 0 and tel["dense_served"] == 0
              and tel["dense_builds"] == 0 and len(ex.quarantine) == 0,
              "the guard stepped off the planned backend")
        check(not only_pallas or set(served) == {"pallas"},
              f"launches served by {dict(served)}, not only pallas")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only plan_sharded over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX platform is {dev.platform!r}, not 'tpu'; "
                 "this check runs only on a TPU")
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    sys.path.insert(0, src)
    try:
        from repro.kernels.common import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import the repro package from {src}: "
                 f"{e}")
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    smoke = Smoke(args.seed)
    try:
        if args.four_chips:
            smoke.phase_four_chips()
        else:
            from repro.core import TPU_V5E, ScheduleTuner, corpus
            tuner = ScheduleTuner("spmv", TPU_V5E).fit(
                corpus(n_matrices=9, n_min=128, n_max=256, seed=3),
                max_mats=6)
            smoke.phase_plan(tuner)
            smoke.phase_bucket()
            smoke.phase_engine(tuner)
            smoke.phase_spgemm()
        smoke.finish(only_pallas=not args.four_chips)
    except Failed as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
