"""Continuous-batching serving engine (DESIGN.md §13): deterministic-clock
slot/drain semantics, the overload ledger, and trace/registry
reconciliation.

The four pinned behaviours the ISSUE names:
* a slot of same-bucket requests drains as ONE stacked launch
  (launch-counter == 1);
* deadline-expired requests are shed, never executed;
* the hard watermark bounds queue depth under any submit pattern;
* Tracer event counts reconcile exactly with the registry's ``events.*``
  counters, and the ledger identity ``admitted == completed + shed`` holds
  once the engine runs dry.
"""
import time

import numpy as np
import pytest

from repro.core import ScheduleTuner, TPU_V5E, corpus
from repro.obs import Tracer, default_registry, install_tracer
from repro.selector import ScheduleCache, SelectorService
from repro.serving import (ServingEngine, SlotTable, generate_trace, replay,
                           tenant_population, tenant_rhs, zipf_weights)
from repro.sparse import (PreparedStore, content_key, launch_count, plan,
                          plan_bucket, reset_counters)


class FakeClock:
    """Injectable monotonic clock: time moves only when a test says so."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt_s: float) -> None:
        self.t += float(dt_s)


@pytest.fixture(scope="module")
def tuner():
    train = corpus(n_matrices=9, n_min=128, n_max=256, seed=3)
    return ScheduleTuner("spmv", TPU_V5E).fit(train, max_mats=6)


@pytest.fixture(scope="module")
def population():
    return tenant_population(3, n_min=128, n_max=256, seed=17)


@pytest.fixture(scope="module")
def rhs(population):
    return tenant_rhs(population, seed=17)


def _engine(tuner, clock=None, **kw):
    svc = SelectorService(tuner, cache=ScheduleCache(),
                          prepared_store=kw.pop("store", None))
    return ServingEngine(svc, clock=clock, **kw)


def _warm(engine, population, rhs):
    for t, (name, A) in enumerate(population):
        engine.submit(f"warm:{name}", A, rhs[t], tenant=t)
    engine.drain_all()


# --------------------------------------------------- one slot == one launch

def test_same_bucket_requests_drain_in_one_stacked_launch(
        tuner, population, rhs):
    engine = _engine(tuner, slot_max=8)
    _warm(engine, population, rhs)     # selection memo + container + compile
    name, A = population[0]
    reset_counters()
    for j in range(3):
        assert engine.submit(f"r{j}:{name}", A, rhs[0], tenant=0)
    done = engine.tick()               # admit all three, drain ONE slot
    assert done == 3
    assert launch_count("spmv") == 1   # the whole point of the slot
    tel = engine.telemetry()
    # 3 warm singleton drains + the one measured 3-request drain
    assert tel["completed"] == 6.0 and tel["multi_request_drains"] == 1.0
    assert tel["drains"] == 4.0 and tel["drained_members"] == 6.0


def test_fused_same_content_bucket_matches_per_request_results():
    rng = np.random.default_rng(5)
    d = (rng.random((96, 96)) < 0.08) * rng.standard_normal((96, 96))
    from repro.core import CSR
    A = CSR.from_dense(d.astype(np.float32))
    store = PreparedStore()
    ck = content_key(A)
    xs = [rng.standard_normal(96).astype(np.float32) for _ in range(3)]
    from repro.sparse import SparseTensor
    sched = SparseTensor.default_schedule(32, None, 8)
    singles = [np.asarray(plan("spmv", (A,), sched, store=store).execute(x))
               for x in xs]
    pb = plan_bucket("spmv", [A, A, A], sched, store=store,
                     member_keys=(ck,) * 3)
    reset_counters()
    ys = pb.execute(xs)
    assert launch_count("spmv") == 1
    for y, yr in zip(ys, singles):
        np.testing.assert_allclose(np.asarray(y), yr, rtol=2e-5, atol=2e-5)


def test_on_result_delivers_every_output(tuner, population, rhs):
    """Each completed request's output reaches the caller once, batched
    or not, and equals A @ x."""
    got = {}
    engine = _engine(tuner, slot_max=4,
                     on_result=lambda rid, y: got.setdefault(rid, []).append(y))
    want = {}
    for j in range(7):
        t = j % len(population)
        _, A = population[t]
        want[f"o{j}"] = A.to_dense() @ rhs[t]
        engine.submit(f"o{j}", A, rhs[t], tenant=t, rid=f"o{j}")
    engine.drain_all()
    assert sorted(got) == sorted(want) and all(len(v) == 1
                                               for v in got.values())
    assert engine.telemetry()["multi_request_drains"] >= 1
    for rid, ys in got.items():
        np.testing.assert_allclose(ys[0], want[rid], rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- deadline shedding

def test_deadline_expired_requests_shed_not_executed(tuner, population, rhs):
    clock = FakeClock()
    engine = _engine(tuner, clock=clock, deadline_ms=10.0, slot_max=8)
    _warm(engine, population, rhs)
    name, A = population[0]
    reset_counters()
    for j in range(3):
        engine.submit(f"late{j}:{name}", A, rhs[0], tenant=0)
    clock.advance(0.050)               # 50ms >> the 10ms deadline
    engine.tick()
    assert launch_count("spmv") == 0   # shed means NOT executed
    tel = engine.telemetry()
    assert tel["shed"] == 3.0
    assert tel["admitted"] == tel["completed"] + tel["shed"]
    assert engine.backlog == 0


# ----------------------------------------------------------- backpressure

def test_hard_watermark_bounds_queue_depth(tuner, population, rhs):
    engine = _engine(tuner, queue_max=4)
    name, A = population[0]
    outcomes = [engine.submit(f"q{j}:{name}", A, rhs[0], tenant=0)
                for j in range(10)]
    assert outcomes == [True] * 4 + [False] * 6   # depth never exceeds 4
    tel = engine.telemetry()
    assert tel["rejected"] == 6.0 and tel["queue_depth"] == 4.0
    engine.drain_all()
    tel = engine.telemetry()
    assert tel["admitted"] == tel["completed"] + tel["shed"] == 4.0


def test_soft_watermark_sends_degrade_signal(tuner, population, rhs):
    engine = _engine(tuner, queue_max=8, soft_watermark=3)
    name, A = population[0]
    for j in range(5):
        engine.submit(f"s{j}:{name}", A, rhs[0], tenant=0)
    assert engine.telemetry()["degrade_signals"] >= 1.0
    engine.drain_all()


# -------------------------------------------------- trace reconciliation

def test_trace_counts_reconcile_with_registry(tuner, population, rhs):
    reg = default_registry()
    base = {k: reg.get(f"events.{k}") for k in ("enqueue", "admit", "drain")}
    tr = install_tracer(Tracer(registry=reg))
    try:
        engine = _engine(tuner, slot_max=4)
        for j in range(6):
            t = j % len(population)
            name, A = population[t]
            engine.submit(f"rec{j}:{name}", A, rhs[t], tenant=t)
        engine.drain_all()
    finally:
        install_tracer(None)
    counts = tr.counts()
    for k in ("enqueue", "admit", "drain"):
        assert counts.get(k, 0) > 0
        assert reg.get(f"events.{k}") - base[k] == counts.get(k, 0), k
    tel = engine.telemetry()
    assert counts["enqueue"] == tel["submitted"]
    assert counts["admit"] == tel["admitted"]
    assert tel["admitted"] == tel["completed"] + tel["shed"]


def test_ticks_that_only_drain_open_no_admission_span(
        tuner, population, rhs):
    """A tick admits only when the queue holds something: the ticks that
    drain the slots left after one admission open no ``admission`` span."""
    tr = install_tracer(Tracer(registry=default_registry()))
    try:
        engine = _engine(tuner, slot_max=4)
        for t, (name, A) in enumerate(population):
            engine.submit(f"adm{t}:{name}", A, rhs[t], tenant=t)
        engine.drain_all()
    finally:
        install_tracer(None)
    counts = tr.counts()
    # one tick admitted every tenant; one drain per tenant's slot followed
    assert counts["admission"] == 1
    assert counts["drain"] == len(population) > 1
    (ev,) = [e for e in tr.events() if e["type"] == "admission"]
    assert ev["args"]["admitted"] == len(population)


# ------------------------------------------------------------- slot table

def test_affinity_keeps_slots_content_pure(tuner):
    sched, _ = tuner.select(corpus(n_matrices=1, n_min=128, n_max=192,
                                   seed=5)[0][2])
    table = SlotTable(slot_max=2)
    s1 = table.assign("m0", sched, resident=True, affinity="ckA")
    s2 = table.assign("m1", sched, resident=True, affinity="ckA")
    s3 = table.assign("m2", sched, resident=True, affinity="ckB")
    assert s1 is s2 and s1 is not s3           # same content shares a slot
    s4 = table.assign("m3", sched, resident=True, affinity="ckA")
    assert s4 is not s1                        # full slot -> sibling opens
    assert s4.affinity == "ckA" and len(table) == 3
    assert table.backlog() == 4
    picked = table.pick()
    assert picked is s1                        # full slots drain first
    table.take(picked)
    assert table.backlog() == 2


def test_slot_max_one_is_per_request_baseline(tuner):
    sched, _ = tuner.select(corpus(n_matrices=1, n_min=128, n_max=192,
                                   seed=5)[0][2])
    table = SlotTable(slot_max=1)
    slots = {id(table.assign(f"m{i}", sched, False, affinity="ck"))
             for i in range(4)}
    assert len(slots) == 4                     # every request its own slot


# ----------------------------------------------------------- trace replay

def test_zipf_trace_deterministic_and_skewed():
    a = generate_trace(500, 200.0, 6, seed=9)
    b = generate_trace(500, 200.0, 6, seed=9)
    assert a == b                              # byte-for-byte replayable
    c = generate_trace(500, 200.0, 6, seed=10)
    assert a != c
    ts = [r.t_s for r in a]
    assert ts == sorted(ts) and ts[0] > 0.0
    counts = np.bincount([r.tenant for r in a], minlength=6)
    assert counts[0] > counts[-1]              # Zipf head beats the tail
    w = zipf_weights(6)
    assert w[0] > w[-1] and abs(w.sum() - 1.0) < 1e-12


def test_replay_ledger_and_scorecard(tuner, population, rhs):
    engine = _engine(tuner, slot_max=8, deadline_ms=250.0, slo_ms=100.0)
    _warm(engine, population, rhs)
    engine.reset_metrics()
    trace = generate_trace(24, 400.0, len(population), seed=17)
    rep = replay(engine, trace, population, rhs_seed=17)
    assert rep["n_offered"] == 24.0
    assert rep["admitted"] == rep["completed"] + rep["shed"]
    assert rep["completed"] + rep["shed"] + rep["rejected"] == 24.0
    assert rep["achieved_qps"] > 0.0
    for k in ("latency_p50_ms", "latency_p95_ms", "latency_p99_ms",
              "slo_attainment", "mean_drain_size", "prep_eviction_pressure"):
        assert k in rep


def test_reset_metrics_zeroes_ledger_and_refuses_in_flight(
        tuner, population, rhs):
    engine = _engine(tuner)
    name, A = population[0]
    engine.submit(f"rm0:{name}", A, rhs[0], tenant=0)
    with pytest.raises(RuntimeError):
        engine.reset_metrics()                 # request still in flight
    engine.drain_all()
    assert engine.telemetry()["completed"] == 1.0
    engine.reset_metrics()
    tel = engine.telemetry()
    assert tel["submitted"] == tel["completed"] == 0.0
    assert tel["latency_count"] == 0.0


# ------------------------------------------------------------- threading

def test_threaded_engine_start_stop(tuner, population, rhs):
    engine = _engine(tuner, slot_max=8)
    _warm(engine, population, rhs)
    engine.start(idle_s=0.0005)
    try:
        for j in range(8):
            t = j % len(population)
            name, A = population[t]
            assert engine.submit(f"th{j}:{name}", A, rhs[t], tenant=t)
        deadline = time.monotonic() + 30.0
        while engine.backlog and time.monotonic() < deadline:
            time.sleep(0.002)
    finally:
        engine.stop()
    tel = engine.telemetry()
    assert tel["completed"] == float(len(population) + 8)
    assert tel["admitted"] == tel["completed"] + tel["shed"]
