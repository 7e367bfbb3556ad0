"""Serving-side resilience (DESIGN.md §11): deterministic fault injection,
the guarded-execution backend fallback ladder (output equivalence vs the
reference under injected launch faults, for every registered op), NaN/Inf
output guards, schedule quarantine across refits, checksummed
corrupted-state recovery for the ScheduleCache and PreparedStore, and
deadline/backoff admission in the SelectorService."""
import json
import os

import numpy as np
import pytest

from repro.core import CSR, TPU_V5E, ScheduleTuner, corpus
from repro.core.autotune import (DIA_FILL_MAX, Schedule,
                                 candidate_schedules, count_diagonals)
from repro.selector import ScheduleCache, SelectorService
from repro.selector.cache import CACHE_FORMAT_VERSION
from repro.selector.fingerprint import fingerprint
from repro.sparse import (Deadline, FaultInjector, GuardedExecutor,
                          InjectedFault, Plan, PreparedStore, Quarantine,
                          default_executor, default_quarantine,
                          install_injector, plan, plan_bucket, register_op,
                          reset_resilience, with_backoff)
from repro.sparse import resilience
from repro.sparse.registry import _REGISTRY

TRAIN = corpus(n_matrices=9, n_min=256, n_max=384, seed=3)
HELD = corpus(n_matrices=5, n_min=256, n_max=384, seed=91,
              include_synthetic=False)


@pytest.fixture(autouse=True)
def _fresh_resilience():
    """Every test starts with no injector and empty default
    executor/quarantine state, and leaves none behind."""
    reset_resilience()
    yield
    reset_resilience()


@pytest.fixture(scope="module")
def tuner():
    return ScheduleTuner("spmv", TPU_V5E).fit(TRAIN, max_mats=9)


def _sparse(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.standard_normal((n, m))
    return CSR.from_dense(d.astype(np.float32))


# ------------------------------------------------------------ fault injector

def test_injector_deterministic_and_counted():
    a = FaultInjector(0.3, seed=11)
    b = FaultInjector(0.3, seed=11)
    pa = [a.fire("launch") for _ in range(64)]
    pb = [b.fire("launch") for _ in range(64)]
    assert pa == pb                       # same seed -> same firing pattern
    assert 0 < sum(pa) < 64               # rate actually bites, not always
    c = FaultInjector(0.3, seed=12)
    assert [c.fire("launch") for _ in range(64)] != pa   # seed matters
    assert a.checks["launch"] == 64
    assert a.fired["launch"] == sum(pa)
    # sites not in the active set never fire but are still checked
    d = FaultInjector(1.0, seed=0, sites=("prep",))
    assert not d.fire("launch")
    assert d.checks["launch"] == 1 and d.fired["launch"] == 0


def test_check_fault_no_injector_is_noop():
    resilience.check_fault("launch")      # no injector installed
    assert not resilience.fault_fired("cache-read")


# ------------------------------------------------- fallback-chain equivalence

def _clean_and_faulted(op, operands, runtime, schedule=None, **kw):
    """(clean jnp output, output under rate-1.0 launch faults starting at
    interpret). With every launch check firing, the ladder must walk
    interpret -> jnp -> dense and serve the dense reference."""
    clean = plan(op, operands, schedule=schedule, backend="jnp",
                 **kw).execute(*runtime)
    reset_resilience()
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    p = plan(op, operands, schedule=schedule, backend="interpret", **kw)
    faulted = p.execute(*runtime)
    assert default_executor().fallbacks[op] >= 2
    assert default_executor().dense_served >= 1
    assert len(default_quarantine()) >= 2       # interpret + jnp quarantined
    inj = resilience.injector()
    assert sum(inj.fired.values()) == sum(inj.recovered_counts.values()) > 0
    return clean, faulted


@pytest.mark.parametrize("backend,rungs", [
    ("pallas", ["pallas", "jnp", "dense"]),
    ("interpret", ["interpret", "jnp", "dense"])])
def test_pallas_ladder_never_steps_to_interpret(backend, rungs):
    """A compiled launch that fails falls to jnp, never to the Pallas
    interpreter; only a plan built for interpret starts there."""
    assert GuardedExecutor().chain_from(backend, True) == rungs
    A = _sparse(64, 64, 0.1, 2)
    x = np.ones(64, np.float32)
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    p = plan("spmv", A, backend=backend)
    p.execute(x)
    assert p.backend == "dense"
    assert {e["backend"] for e in default_quarantine().entries()} \
        == set(rungs[:2])


def test_fallback_chain_spmv_spmm_match_reference():
    A = _sparse(96, 80, 0.08, 0)
    x = np.random.default_rng(1).standard_normal(80).astype(np.float32)
    clean, faulted = _clean_and_faulted("spmv", A, (x,))
    np.testing.assert_allclose(np.asarray(faulted), np.asarray(clean),
                               rtol=2e-3, atol=2e-3)
    reset_resilience()
    X = np.random.default_rng(2).standard_normal((80, 4)).astype(np.float32)
    clean, faulted = _clean_and_faulted("spmm", A, (X,))
    np.testing.assert_allclose(np.asarray(faulted), np.asarray(clean),
                               rtol=2e-3, atol=2e-3)


def test_fallback_chain_spgemm_spadd_match_reference():
    a = _sparse(64, 64, 0.1, 3)
    b = _sparse(64, 64, 0.1, 4)
    sched = Schedule("bsr", 32, 1.0)
    for op in ("spgemm", "spadd"):
        reset_resilience()
        clean, faulted = _clean_and_faulted(op, (a, b), (), schedule=sched)
        np.testing.assert_allclose(faulted.to_dense(), clean.to_dense(),
                                   rtol=2e-3, atol=2e-3)


def test_fallback_chain_moe_match_reference():
    rng = np.random.default_rng(5)
    tile_expert = np.array([0, 1, 0], np.int32)
    x = rng.standard_normal((12, 8)).astype(np.float32)
    w = rng.standard_normal((2, 8, 16)).astype(np.float32)
    clean, faulted = _clean_and_faulted("moe_gmm", tile_expert, (x, w),
                                        tile_m=4)
    np.testing.assert_allclose(np.asarray(faulted), np.asarray(clean),
                               rtol=2e-3, atol=2e-3)


def test_fallback_chain_flash_match_reference():
    rng = np.random.default_rng(6)
    q, k, v = (rng.standard_normal((2, 16, 8)).astype(np.float32)
               for _ in range(3))
    clean, faulted = _clean_and_faulted("flash_attention", (), (q, k, v))
    np.testing.assert_allclose(np.asarray(faulted), np.asarray(clean),
                               rtol=2e-3, atol=2e-3)


def test_fallback_chain_bucket_matches_reference():
    mats = [_sparse(70 + 9 * i, 60, 0.1, 10 + i) for i in range(3)]
    xs = [np.random.default_rng(20 + i).standard_normal(60).astype(np.float32)
          for i in range(3)]
    sched = Schedule("bsr", 64, 1.0)
    clean = [np.asarray(y) for y in
             plan_bucket("spmv", mats, sched, backend="jnp").execute(xs)]
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    faulted = plan_bucket("spmv", mats, sched,
                          backend="interpret").execute(xs)
    for yc, yf in zip(clean, faulted):
        np.testing.assert_allclose(np.asarray(yf), yc, rtol=2e-3, atol=2e-3)


def test_dense_rung_is_lazy(monkeypatch):
    """plan() must not materialize the O(n*m) dense reference: the
    densification happens only when the guard actually falls to the dense
    rung, and is memoized across launches of the same plan."""
    from repro.sparse import ops_builtin
    calls = []
    orig = ops_builtin._dense_of
    monkeypatch.setattr(ops_builtin, "_dense_of",
                        lambda a: (calls.append(1), orig(a))[1])
    A = _sparse(64, 64, 0.1, 0)
    x = np.ones(64, np.float32)
    p = plan("spmv", A, backend="jnp")
    assert calls == []                    # plan time: no densification
    p.execute(x)
    assert calls == []                    # healthy launches: still none
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    p2 = plan("spmv", A, backend="jnp")
    assert calls == []
    y = p2.execute(x)                     # falls to dense: densify ONCE
    assert len(calls) == 1
    p2.execute(x)                         # memoized across launches
    assert len(calls) == 1
    np.testing.assert_allclose(np.asarray(y), A.to_dense() @ x,
                               rtol=2e-3, atol=2e-3)


def test_dense_rung_size_cap(monkeypatch):
    """Over-cap operands have no dense rung at all (the ladder ends at
    jnp) instead of risking an OOM on the availability path."""
    monkeypatch.setenv("REPRO_DENSE_REF_MAX_ELEMS", "100")
    A = _sparse(64, 64, 0.1, 1)          # 4096 elements > 100 cap
    assert resilience.make_dense_run("spmv", (A,), None, {}) is None
    x = np.ones(64, np.float32)
    y = plan("spmv", A, backend="jnp").execute(x)   # normal path unaffected
    np.testing.assert_allclose(np.asarray(y), A.to_dense() @ x,
                               rtol=2e-3, atol=2e-3)


def test_explicit_executor_isolates_quarantine():
    """Threading an explicit GuardedExecutor through plan() keeps two
    services from cross-contaminating the process-wide defaults."""
    ex1 = GuardedExecutor()
    A = _sparse(64, 64, 0.1, 2)
    x = np.ones(64, np.float32)
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    plan("spmv", A, backend="interpret", executor=ex1).execute(x)
    assert ex1.fallbacks["spmv"] >= 2 and len(ex1.quarantine) >= 2
    assert len(default_quarantine()) == 0         # defaults untouched
    assert default_executor().fallbacks["spmv"] == 0


def test_quarantined_rung_skipped_on_next_plan():
    A = _sparse(64, 64, 0.1, 7)
    x = np.ones(64, np.float32)
    install_injector(FaultInjector(1.0, seed=0, sites=("launch",)))
    plan("spmv", A, backend="interpret").execute(x)   # poisons interpret+jnp
    inj_before = sum(resilience.injector().fired.values())
    skips_before = default_executor().quarantine_skips
    y = plan("spmv", A, backend="interpret").execute(x)
    # both quarantined rungs are skipped up front: no new launch checks
    # fire, the dense rung serves directly
    assert default_executor().quarantine_skips >= skips_before + 2
    assert sum(resilience.injector().fired.values()) == inj_before
    np.testing.assert_allclose(np.asarray(y), A.to_dense() @ x,
                               rtol=2e-3, atol=2e-3)


def test_exhausted_chain_raises():
    def planner(operands, schedule, backend, **kw):
        def run():
            raise RuntimeError("boom")
        return Plan(op="alwaysboom", schedule=schedule, backend=backend,
                    _run=run)
    register_op("alwaysboom", planner, layouts=(), overwrite=True)
    try:
        p = plan("alwaysboom", (), backend="jnp")   # no dense ref registered
        with pytest.raises(RuntimeError, match="boom"):
            p.execute()
        assert default_executor().exhausted == 1
    finally:
        _REGISTRY.pop("alwaysboom", None)


def test_nan_guard_falls_back_and_quarantines():
    def planner(operands, schedule, backend, **kw):
        def run():
            if backend == "interpret":
                return np.full(3, np.nan, np.float32)
            return np.ones(3, np.float32)
        return Plan(op="nanop", schedule=schedule, backend=backend, _run=run)
    register_op("nanop", planner, layouts=(), overwrite=True)
    try:
        y = plan("nanop", (), backend="interpret").execute()
        assert np.isfinite(np.asarray(y)).all()
        assert default_executor().nan_trips == 1
        assert default_quarantine().blocked("nanop", "interpret", None)
    finally:
        _REGISTRY.pop("nanop", None)


def test_quarantine_override_on_last_rung_counted():
    """A quarantined combo on the chain's ONLY remaining rung is served as
    a last resort — and the contract bend is counted, never silent."""
    def planner(operands, schedule, backend, **kw):
        return Plan(op="solorung", schedule=schedule, backend=backend,
                    _run=lambda: np.ones(2, np.float32))
    register_op("solorung", planner, layouts=(), overwrite=True)
    try:
        default_quarantine().add("solorung", "jnp", None, reason="test")
        y = plan("solorung", (), backend="jnp").execute()  # no dense ref
        assert np.allclose(np.asarray(y), 1.0)             # served anyway
        assert default_executor().quarantine_overrides >= 1
        assert default_executor().quarantine_skips == 0
    finally:
        _REGISTRY.pop("solorung", None)


def test_nan_guard_env_opt_out(monkeypatch):
    monkeypatch.setenv("REPRO_NAN_GUARD", "0")
    assert GuardedExecutor().nan_guard is False
    monkeypatch.setenv("REPRO_NAN_GUARD", "1")
    assert GuardedExecutor().nan_guard is True
    assert GuardedExecutor(nan_guard=False).nan_guard is False  # explicit wins


def test_prep_fault_degrades_build_to_dense_reference():
    A = _sparse(64, 64, 0.1, 8)
    x = np.ones(64, np.float32)
    install_injector(FaultInjector(1.0, seed=0, sites=("prep",)))
    p = plan("spmv", A, backend="jnp")
    assert p.source == "guard-dense" and p.backend == "dense"
    assert default_executor().build_retries >= 1
    assert default_executor().dense_builds == 1
    np.testing.assert_allclose(np.asarray(p.execute(x)), A.to_dense() @ x,
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------- corrupted state

def _fill_cache(path, mats):
    cache = ScheduleCache(path=path, context="t")
    for i, A in enumerate(mats):
        cache.put(fingerprint(A), Schedule("bsr", 64 * (i + 1), 1.0), "test")
    assert cache.flush()
    return cache


def test_corrupt_cache_entry_skipped_not_raised(tmp_path):
    path = str(tmp_path / "cache.json")
    mats = [_sparse(64, 64, 0.1, s) for s in (0, 1, 2)]
    _fill_cache(path, mats)
    with open(path) as f:
        payload = json.load(f)
    payload["entries"][1]["schedule"]["block_size"] = 999   # bit flip
    with open(path, "w") as f:
        json.dump(payload, f)
    re = ScheduleCache(path=path, context="t")
    assert len(re) == 2                  # corrupt entry skipped, not fatal
    assert re.corrupt_entries == 1
    assert re.get(fingerprint(mats[0])) is not None
    assert re.get(fingerprint(mats[1])) is None   # the lost entry: a miss


def test_truncated_cache_file_cold_starts_empty(tmp_path):
    path = str(tmp_path / "cache.json")
    _fill_cache(path, [_sparse(64, 64, 0.1, 0)])
    with open(path) as f:
        raw = f.read()
    with open(path, "w") as f:
        f.write(raw[: len(raw) // 2])    # torn write
    re = ScheduleCache(path=path, context="t")
    assert len(re) == 0 and re.corrupt_files == 1
    # and the empty cache still works end to end
    fp = fingerprint(_sparse(64, 64, 0.1, 9))
    re.put(fp, Schedule("bsr", 64, 1.0), "test")
    assert re.flush() and ScheduleCache(path=path, context="t").get(fp)


def test_cache_write_fault_preserves_previous_file(tmp_path):
    path = str(tmp_path / "cache.json")
    mats = [_sparse(64, 64, 0.1, s) for s in (0, 1)]
    cache = _fill_cache(path, [mats[0]])
    with open(path) as f:
        before = f.read()
    install_injector(FaultInjector(1.0, seed=0, sites=("cache-write",)))
    cache.put(fingerprint(mats[1]), Schedule("bsr", 32, 1.0), "test")
    assert cache.flush() is False        # counted, not raised
    assert cache.flush_failures == 1
    with open(path) as f:
        assert f.read() == before        # old file intact, still valid JSON
    inj = resilience.injector()
    assert inj.fired["cache-write"] == inj.recovered_counts["cache-write"] > 0
    install_injector(None)
    assert cache.flush()                 # recovery: next flush lands


def test_cache_read_fault_served_as_miss(tmp_path):
    cache = ScheduleCache(context="t")
    fp = fingerprint(_sparse(64, 64, 0.1, 0))
    cache.put(fp, Schedule("bsr", 64, 1.0), "test")
    install_injector(FaultInjector(1.0, seed=0, sites=("cache-read",)))
    assert cache.get(fp) is None
    assert cache.faulted_reads == 1
    install_injector(None)
    assert cache.get(fp) is not None     # entry itself was never lost


def test_corrupt_store_index_cold_starts_empty(tmp_path):
    path = str(tmp_path / "store.json")
    store = PreparedStore()
    store.put(("k",), np.zeros(8, np.float32))
    assert store.save(path)
    fresh = PreparedStore()
    assert fresh.load(path)["entries"]   # round-trips clean
    with open(path, "w") as f:
        f.write("{not json")
    fresh2 = PreparedStore()
    assert fresh2.load(path) == {}       # truncated: empty, no raise
    assert fresh2.corrupt_loads == 1
    assert fresh2.telemetry()["corrupt_loads"] == 1.0


def test_store_index_entry_checksum(tmp_path):
    path = str(tmp_path / "store.json")
    store = PreparedStore()
    store.put(("a",), np.zeros(4, np.float32))
    store.put(("b",), np.zeros(4, np.float32))
    store.save(path)
    with open(path) as f:
        payload = json.load(f)
    payload["entries"][0]["nbytes"] = 10 ** 9    # flipped bits
    with open(path, "w") as f:
        json.dump(payload, f)
    fresh = PreparedStore()
    prior = fresh.load(path)
    assert len(prior["entries"]) == 1            # bad entry skipped
    assert fresh.corrupt_loads == 1


def test_store_evict_fault_serves_miss_and_rebuilds():
    store = PreparedStore()
    store.put(("k",), np.ones(4, np.float32))
    install_injector(FaultInjector(1.0, seed=0, sites=("store-evict",)))
    assert store.get(("k",)) is None
    assert store.fault_evictions == 1
    install_injector(None)
    rebuilt = store.get_or_build(("k",), lambda: np.zeros(4, np.float32))
    assert rebuilt is not None and ("k",) in store


# ------------------------------------------------ quarantine + selection

def test_quarantine_ttl_expiry():
    q = Quarantine(ttl_ticks=2)
    s = Schedule("bsr", 64, 1.0)
    q.add("spmv", "jnp", s)
    assert q.blocked("spmv", "jnp", s) and q.blocked_any_backend("spmv", s)
    q.tick()
    assert q.blocked("spmv", "jnp", s)
    q.tick()
    assert not q.blocked("spmv", "jnp", s)       # expired: another chance
    assert q.expired == 1 and len(q) == 0


def test_quarantined_schedule_never_reselected_across_refit(tuner):
    svc = SelectorService(tuner, confidence_threshold=0.0)
    # a held matrix plan() does not launch as DIA: the tuner path below
    # re-selects through the quarantine
    A = next(m for _, _, m in HELD
             if count_diagonals(m) * m.n_rows > DIA_FILL_MAX * m.nnz)
    first = svc.select(A)
    assert first.source in ("tree", "verify")
    # the serving loop quarantines the pick (as a failed launch would)
    svc.quarantine.add(tuner.kernel, "jnp", first.schedule, reason="test")
    second = svc.select(A)
    assert second.schedule != first.schedule
    assert svc._counts["quarantine_blocked"] >= 1
    assert svc._counts["negative_examples"] >= 1
    # negative examples carry the penalty time for the poisoned schedule
    assert any(ex["log10_time_s"] >= 0.0 - 1e-9
               for ex in svc.retraining_examples)
    svc.refit(min_examples=1)
    assert svc._counts["refits"] == 1
    third = svc.select(A)
    assert third.schedule != first.schedule      # still never re-served
    # the tuner path honors the same quarantine: its own pick is
    # quarantined too where it is not the service's
    sched, _ = tuner.select(A)
    svc.quarantine.add(tuner.kernel, "jnp", sched, reason="test")
    p = plan("spmv", A, selector=tuner)
    assert p.schedule not in (first.schedule, sched)
    assert p.source == "tuner-requarantined"


def test_verify_sweep_excludes_quarantined_candidates(tuner):
    svc = SelectorService(tuner, confidence_threshold=1.1)  # always verify
    A = HELD[1][2]
    dec = svc.select(A)
    svc.quarantine.add(tuner.kernel, "jnp", dec.schedule)
    dec2 = svc.select(A)
    assert dec2.schedule != dec.schedule
    # quarantine everything -> the sweep is overridden rather than empty
    for s in candidate_schedules(tuner.n_rhs):
        svc.quarantine.add(tuner.kernel, "jnp", s)
    dec3 = svc.select(A)
    assert dec3.schedule is not None
    assert svc._counts["quarantine_overridden"] >= 1


# ------------------------------------------- deadline / backoff / degraded

def test_with_backoff_retries_then_succeeds():
    calls, sleeps = [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"
    assert with_backoff(flaky, max_retries=3, base_s=0.01,
                        sleep=sleeps.append) == "ok"
    assert len(calls) == 3
    assert sleeps == [0.01, 0.02]        # exponential backoff

    def always():
        raise RuntimeError("permanent")
    with pytest.raises(RuntimeError, match="permanent"):
        with_backoff(always, max_retries=2, base_s=0.0, sleep=lambda _: None)


def test_deadline_exceeded_requests_are_shed(tuner):
    svc = SelectorService(tuner, batch_max=4)
    A = HELD[0][2]
    x = np.ones(A.shape[1], np.float32)
    svc.submit("late", A, x, deadline_ms=0.0)    # already expired at drain
    svc.submit("ontime", A, x, deadline_ms=60_000.0)
    decs = svc.process_pending()
    by_name = {d.name: d for d in decs}
    assert by_name["late"].source == "shed" and by_name["late"].y is None
    assert by_name["ontime"].source != "shed"
    assert by_name["ontime"].y is not None
    tel = svc.telemetry()
    assert tel["shed_requests"] == 1.0
    assert tel["executed"] == 1.0
    assert tel["requests"] == 2.0


def test_shed_pressure_enters_degraded_mode(tuner):
    svc = SelectorService(tuner, confidence_threshold=1.1,  # always verify
                          degraded_cooldown=3, batch_max=4)
    A = HELD[2][2]
    svc.submit("late", A, deadline_ms=0.0)
    svc.process_pending()                 # tick 1: shed -> pressure
    assert svc.degraded
    verify_before = svc._counts["verify_fallbacks"]
    svc.submit("now", A)
    decs = svc.process_pending()          # tick 2: degraded, verify shed
    assert decs[0].source == "tree"
    assert svc._counts["verify_fallbacks"] == verify_before
    tel = svc.telemetry()
    assert tel["degraded_served"] >= 1.0
    assert tel["degraded_ticks"] >= 1.0
    for _ in range(3):                    # cooldown drains without pressure
        svc.submit("cool", A)
        svc.process_pending()
    assert not svc.degraded
    svc.submit("after", HELD[3][2])       # unseen matrix: no cache hit
    decs = svc.process_pending()          # healthy again: verify sweep back
    assert decs[0].source == "verify"


def test_output_finite_handles_op_output_shapes():
    assert resilience.output_finite(np.ones(3))
    assert not resilience.output_finite(np.array([1.0, np.inf]))
    assert resilience.output_finite([np.ones(2), np.ones(2)])
    assert not resilience.output_finite([np.ones(2), np.array([np.nan])])
    assert resilience.output_finite(np.array([1, 2]))    # ints have no NaN
    class Blocks:
        blocks = np.ones((2, 2))
    assert resilience.output_finite(Blocks())
    Blocks.blocks = np.array([[np.nan, 1.0]])
    assert not resilience.output_finite(Blocks())
    # device arrays: reduced on device, only the scalar verdict transfers
    import jax.numpy as jnp
    assert resilience.output_finite(jnp.ones(3))
    assert not resilience.output_finite(jnp.array([1.0, jnp.nan]))
    assert resilience.output_finite(jnp.array([1, 2], jnp.int32))


def test_degraded_pick_is_not_cached(tuner):
    """A tree pick served under degraded mode must not enter the
    ScheduleCache: the pressure-shed decision dies with the degraded
    window instead of being served (and persisted) forever after."""
    svc = SelectorService(tuner, confidence_threshold=1.1,  # always verify
                          degraded_cooldown=2, batch_max=4)
    A = HELD[2][2]
    fp = fingerprint(A)
    svc.submit("late", A, deadline_ms=0.0)
    svc.process_pending()                 # shed -> pressure -> degraded
    assert svc.degraded
    svc.submit("now", A)
    decs = svc.process_pending()          # degraded: tree-served
    assert decs[0].source == "tree"
    assert svc.cache.get(fp) is None      # ...but never cached
    while svc.degraded:                   # drain the cooldown window
        svc.submit("cool", A)
        svc.process_pending()
    assert svc.cache.get(fp) is None      # degraded picks never landed
    svc.submit("healthy", A)
    decs = svc.process_pending()          # healthy again: full verify path
    assert decs[0].source == "verify"
    assert svc.cache.get(fp) is not None  # the verified pick IS cached


# ----------------------------------------------------------- chaos (heavy)

@pytest.mark.chaos
def test_chaos_serve_accounts_for_every_fault():
    from repro.selector.serve import main
    tel = main(["--requests", "16", "--train-mats", "6", "--serve-mats", "4",
                "--n-min", "256", "--n-max", "320", "--batch", "4",
                "--execute", "--fault-rate", "0.25", "--fault-seed", "7"])
    assert tel["fault_fired"] > 0
    assert tel["fault_fired"] == tel["fault_recovered"]
    assert tel["exec_checked"] > 0 and tel["exec_mismatches"] == 0
