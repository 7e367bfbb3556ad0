"""Observability substrate (DESIGN.md §12): histogram percentile
correctness vs numpy, registry snapshot/delta semantics, scope aliasing,
fake-clock tracer span math (nesting, never-negative durations), Chrome
trace validity, the golden JSONL event schema on a real serve, the
JSONL-counts == registry-counters reconciliation identity, subsystem
``telemetry()`` dicts as genuine registry views, measured-latency feedback
on retraining examples consumed by ``refit()``, thread-safety under
concurrent hammering, and the bench_compare regression differ."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import ScheduleTuner, TPU_V5E, corpus
from repro.obs import (CounterDict, EVENT_FIELDS, EVENT_TYPES, Histogram,
                       MetricsRegistry, Tracer, default_registry,
                       install_tracer, ordered, telemetry_key)
from repro.obs import trace as obs_trace
from repro.obs.report import load_launches, main as report_main, summarize
from repro.obs.schema import TELEMETRY_KEY_RE
from repro.selector import ScheduleCache, SelectorService
from repro.sparse import (FaultInjector, GuardedExecutor, PreparedStore,
                          Quarantine, reset_resilience)

TRAIN = corpus(n_matrices=9, n_min=256, n_max=384, seed=3)
HELD = corpus(n_matrices=4, n_min=256, n_max=384, seed=91,
              include_synthetic=False)


class FakeClock:
    """Injectable monotonic clock the span-math tests drive by hand."""

    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# ------------------------------------------------------------------ metrics

def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=0.0, sigma=1.5, size=1000)
    h = Histogram()
    for v in xs:
        h.observe(float(v))
    for q in (50.0, 95.0, 99.0):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["sum_ms"] == pytest.approx(float(xs.sum()))
    assert snap["min_ms"] == float(xs.min())
    assert snap["max_ms"] == float(xs.max())
    assert snap["p50_ms"] == pytest.approx(float(np.percentile(xs, 50)))
    assert sum(h.buckets) == 1000        # every observation lands somewhere


def test_histogram_empty_and_single_sample():
    h = Histogram()
    assert h.snapshot() == {"count": 0.0, "sum_ms": 0.0, "p50_ms": 0.0,
                            "p95_ms": 0.0, "p99_ms": 0.0}
    h.observe(3.5)
    snap = h.snapshot()
    assert snap["p50_ms"] == snap["p95_ms"] == snap["p99_ms"] == 3.5
    assert snap["min_ms"] == snap["max_ms"] == 3.5


def test_registry_counters_gauges_histograms_and_delta():
    reg = MetricsRegistry()
    reg.inc("a.hits")
    reg.inc("a.hits", 2)
    reg.set_gauge("depth", 7.0)
    reg.observe("lat", 10.0)
    snap1 = reg.snapshot()
    assert snap1["a.hits"] == 3.0
    assert snap1["gauge.depth"] == 7.0
    assert snap1["lat.count"] == 1.0
    assert list(snap1) == sorted(snap1)         # deterministic key order
    reg.inc("a.hits", 4)
    reg.observe("lat", 30.0)
    reg.set_gauge("depth", 2.0)
    d = reg.delta(snap1)
    assert d["a.hits"] == 4.0                   # counters: difference
    assert d["lat.count"] == 1.0                # hist count: difference
    assert d["gauge.depth"] == 2.0              # gauges: current value
    assert "a.misses" not in d                  # unchanged keys dropped
    reg.inc("a.misses", 0.0)
    assert "a.misses" not in reg.delta(reg.snapshot())


def test_registry_rejects_non_snake_case_names():
    reg = MetricsRegistry()
    for bad in ("Hits", "a-b", "9lives", "a b"):
        with pytest.raises(ValueError):
            reg.inc(bad)
    assert telemetry_key("fault_fired_cache-read") == \
        "fault_fired_cache_read"
    with pytest.raises(ValueError):
        telemetry_key("Not Snake")


def test_scopes_never_alias_even_across_reset():
    reg = MetricsRegistry()
    s1, s2 = reg.scope("store"), reg.scope("store")
    assert s1.prefix != s2.prefix
    s1.inc("hits")
    assert s2.get("hits") == 0.0
    reg.reset()
    s3 = reg.scope("store")              # ids survive reset: no aliasing
    assert s3.prefix not in (s1.prefix, s2.prefix)


def test_counter_dict_is_a_registry_view():
    reg = MetricsRegistry()
    scope = reg.scope("svc")
    counts = CounterDict(scope, ("requests", "ticks"))
    counts["requests"] += 1
    counts["requests"] += 1
    assert counts["requests"] == 2 and isinstance(counts["requests"], int)
    assert reg.get(scope.key("requests")) == 2.0
    scope.set("ticks", 5)                # registry write visible in the dict
    assert counts["ticks"] == 5
    with pytest.raises(KeyError):
        counts["nope"]
    with pytest.raises(KeyError):
        counts["nope"] = 1
    assert list(counts) == ["requests", "ticks"]
    assert dict(counts.items()) == {"requests": 2, "ticks": 5}


# ------------------------------------------------------------------- tracer

def test_fake_clock_spans_nest_with_exact_timestamps():
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = Tracer(clock=clock, registry=reg)
    with tr.span("prep", "outer", op="spmv"):
        clock.advance(0.010)
        with tr.span("launch", "inner", op="spmv", backend="jnp",
                     layout="ell", measured_ms=5.0, modeled_ms=1.0):
            clock.advance(0.005)
        clock.advance(0.010)
    inner, outer = tr.events()           # inner closes first
    assert (inner["type"], outer["type"]) == ("launch", "prep")
    assert outer["ts_us"] == 0.0 and outer["dur_us"] == 25000.0
    assert inner["ts_us"] == 10000.0 and inner["dur_us"] == 5000.0
    # containment: the inner span nests inside the outer per thread
    assert outer["ts_us"] <= inner["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] <= outer["ts_us"] + outer["dur_us"]
    # span latencies feed the histogram under the same type
    assert reg.histogram("span_ms.launch").count == 1
    assert reg.histogram("span_ms.launch").sum == pytest.approx(5.0)


def test_spans_never_record_negative_durations():
    clock = FakeClock()
    tr = Tracer(clock=clock, registry=MetricsRegistry())
    with tr.span("prep", "backwards", op="spmv"):
        clock.t -= 5.0                   # a clock that misbehaves
    (ev,) = tr.events()
    assert ev["dur_us"] == 0.0


def test_strict_tracer_rejects_unknown_types():
    tr = Tracer(clock=FakeClock(), registry=MetricsRegistry())
    with pytest.raises(ValueError):
        tr.instant("made_up_type", "x")
    loose = Tracer(clock=FakeClock(), registry=MetricsRegistry(),
                   strict=False)
    loose.instant("bench", "module")     # bench spans may add categories
    assert loose.counts() == {"bench": 1}


def test_chrome_trace_is_valid_and_matches_jsonl(tmp_path):
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = Tracer(clock=clock, registry=reg)
    with tr.span("select", "req0", source="tree", schedule="S"):
        clock.advance(0.001)
    tr.instant("shed", "req1")
    chrome_path, jsonl_path = tmp_path / "t.json", tmp_path / "t.jsonl"
    assert tr.write_chrome_trace(str(chrome_path)) == 2
    assert tr.write_jsonl(str(jsonl_path)) == 2
    trace = json.loads(chrome_path.read_text())   # loads = Perfetto-valid
    assert trace["displayTimeUnit"] == "ms"
    assert len(trace["traceEvents"]) == 2
    for tev in trace["traceEvents"]:
        assert tev["ph"] == "X" and tev["dur"] >= 0.0 and tev["ts"] >= 0.0
        assert tev["cat"] in EVENT_TYPES
    lines = [json.loads(l) for l in jsonl_path.read_text().splitlines()]
    assert [l["type"] for l in lines] == \
        [t["cat"] for t in trace["traceEvents"]]
    # reconciliation identity: JSONL counts == registry events.* counters
    for type_, n in tr.counts().items():
        assert reg.get(f"events.{type_}") == float(n)


def test_installed_tracer_call_sites_are_noops_without_one():
    assert obs_trace.tracer() is None or install_tracer(None) is None
    obs_trace.emit("shed", "nobody")                  # must not raise
    with obs_trace.span("prep", "nobody", op="spmv") as fields:
        fields["extra"] = 1                           # throwaway dict
    tr = install_tracer(Tracer(clock=FakeClock(), registry=MetricsRegistry()))
    try:
        obs_trace.emit("shed", "somebody")
        assert tr.counts() == {"shed": 1}
    finally:
        install_tracer(None)


def _profiled(tmp_path, fn):
    """Host events of a ``jax.profiler`` trace of ``fn()``, one list of
    ``(name, start_ns, end_ns)`` per host thread line."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [[(e.name, e.start_ns, e.end_ns) for e in line.events]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines]


def test_spans_land_in_the_profiler_trace_nested_on_the_calling_thread(
        tmp_path):
    """One selector decision and one guarded launch under a CPU profiler
    session, no Tracer installed: each layer span is a ``repro.<type>``
    event on the thread that opened it, nested as DESIGN.md §12 lists."""
    import jax
    from repro.sparse import plan
    reset_resilience()
    assert obs_trace.tracer() is None
    tuner = ScheduleTuner("spmv", TPU_V5E).fit(TRAIN, max_mats=3)
    svc = SelectorService(tuner, cache=ScheduleCache())
    _, _, A = TRAIN[0]
    x = np.ones(A.shape[1], np.float32)

    def work():
        with jax.profiler.TraceAnnotation("test.calling_thread"):
            plan("spmv", A, selector=svc).execute(x)

    lines = _profiled(tmp_path, work)
    (events,) = [ev for ev in lines
                 if any(n == "test.calling_thread" for n, _, _ in ev)]
    spans = {}
    for name, a, b in events:
        if name.startswith("repro."):
            assert name not in spans, f"{name} twice"
            spans[name] = (a, b)
    for name in ("repro.select", "repro.hash", "repro.fingerprint",
                 "repro.launch", "repro.dispatch", "repro.finite_check"):
        assert name in spans, name
    # no repro.* span on any other thread
    assert not [n for ev in lines if ev is not events
                for n, _, _ in ev if n.startswith("repro.")]

    def inside(inner, outer):
        return (spans[outer][0] <= spans[inner][0]
                and spans[inner][1] <= spans[outer][1])

    assert inside("repro.hash", "repro.select")
    assert inside("repro.fingerprint", "repro.select")
    assert spans["repro.hash"][1] <= spans["repro.fingerprint"][0]
    assert inside("repro.dispatch", "repro.launch")
    assert inside("repro.finite_check", "repro.launch")
    assert spans["repro.dispatch"][1] <= spans["repro.finite_check"][0]
    assert spans["repro.select"][1] <= spans["repro.launch"][0]


def test_profiler_span_names_are_constant_and_not_the_benchmarks_own():
    bench = str(pathlib.Path(__file__).resolve().parent.parent / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from spbench.harness import SPANS
    names = obs_trace.PROFILER_NAMES
    assert set(names) == set(EVENT_TYPES)
    for type_, name in names.items():
        assert name == f"repro.{type_}"
        assert name not in SPANS


# ------------------------------------------- telemetry() as registry views

def _scope_counts(scope):
    """Registry entries under one instance's scope, prefix stripped."""
    pfx = scope.prefix + "."
    return {k[len(pfx):]: v for k, v in scope.registry.snapshot().items()
            if k.startswith(pfx)}


def _assert_view(obj):
    """telemetry() keys are sorted snake_case, and every key the registry
    scope also tracks agrees exactly with the registry's value."""
    tel = obj.telemetry()
    assert list(tel) == sorted(tel)
    assert all(TELEMETRY_KEY_RE.match(k) for k in tel)
    reg_counts = _scope_counts(obj._metrics)
    shared = set(tel) & set(reg_counts)
    assert shared, f"no shared counters for {type(obj).__name__}"
    for k in shared:
        assert tel[k] == reg_counts[k], (type(obj).__name__, k)
    return tel, reg_counts


def test_prepared_store_telemetry_is_registry_view():
    store = PreparedStore(byte_budget=250)
    store.get(("a",))                                   # miss
    store.put(("a",), np.zeros(25, np.float32))
    store.put(("b",), np.zeros(25, np.float32))
    store.get(("a",))                                   # hit
    store.put(("c",), np.zeros(25, np.float32))         # LRU-evicts b
    tel, _ = _assert_view(store)
    assert tel["hits"] == 1 and tel["misses"] == 1 and tel["evictions"] == 1
    # the attribute IS the registry value: a registry write shows through
    store._metrics.set("hits", 41)
    assert store.hits == 41 and store.telemetry()["hits"] == 41


def test_schedule_cache_telemetry_is_registry_view(tmp_path):
    cache = ScheduleCache(path=str(tmp_path / "c.json"))
    from repro.core.autotune import Schedule
    from repro.selector.fingerprint import fingerprint
    rng = np.random.default_rng(0)
    from repro.core import CSR
    A = CSR.from_dense((rng.random((64, 64)) < 0.1).astype(np.float32))
    fp = fingerprint(A)
    cache.get(fp)                                       # miss
    cache.put(fp, Schedule("bsr", 32, 1.0), source="verify",
              modeled_time_s=1e-4)
    cache.get(fp)                                       # hit
    cache.flush()
    tel, _ = _assert_view(cache)
    assert tel["hits"] == 1 and tel["misses"] == 1


def test_guard_and_quarantine_telemetry_are_registry_views():
    reset_resilience()
    ex = GuardedExecutor()
    ex.count_fallback("spmv")
    ex.dense_served += 1
    tel, reg_counts = _assert_view(ex)
    assert tel["fallbacks"] == 1 and reg_counts["fallbacks"] == 1.0
    assert ex.fallbacks["spmv"] == 1                    # per-op dict intact
    q = Quarantine(ttl_ticks=2)
    q.add("spmv", "pallas", "h1", reason="test")
    q.add("spmv", "pallas", "h1", reason="test")        # refresh, not new
    tel, _ = _assert_view(q)
    assert tel["entered"] == 1
    reset_resilience()


def test_fault_injector_telemetry_is_snake_case_and_sorted():
    inj = FaultInjector(0.5, seed=1)
    for _ in range(64):
        inj.fire("cache-read")
    tel = inj.telemetry()
    assert list(tel) == sorted(tel)
    assert all(TELEMETRY_KEY_RE.match(k) for k in tel)
    assert "fault_fired_cache_read" in tel              # dash canonicalized
    assert tel["fault_checks"] == 64


def test_ordered_canonicalizes_and_sorts():
    assert ordered({"b": 2.0, "a": 1.0, "x-y": 3.0}) == \
        {"a": 1.0, "b": 2.0, "x_y": 3.0}
    assert list(ordered({"z": 0.0, "m": 0.0, "a": 0.0})) == ["a", "m", "z"]


# ------------------------------------------------------- concurrency safety

def test_registry_and_tracer_survive_concurrent_hammering():
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    n_threads, n_iter = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        scope = reg.scope("worker")
        for k in range(n_iter):
            reg.inc("shared.total")
            scope.inc("local")
            reg.observe("lat", float(k % 7))
            with tr.span("prep", f"w{i}", op="spmv"):
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert reg.get("shared.total") == float(total)      # no lost updates
    assert reg.sum_prefix("worker.") == float(total)
    assert reg.histogram("lat").count == total
    assert len(tr.events()) == total
    assert reg.get("events.prep") == float(total)
    assert len({ev["tid"] for ev in tr.events()}) == n_threads


# ------------------------------------- end-to-end serve trace (acceptance)

@pytest.fixture(scope="module")
def traced_serve():
    """One traced serve through the real stack: train a tuner, serve 8
    executing requests at confidence_threshold=1.0 (every request takes the
    verify path, so every decision produces a retraining example), with the
    process tracer installed over the default registry."""
    reset_resilience()
    reg = default_registry()
    base = reg.snapshot()
    tr = install_tracer(Tracer(registry=reg))
    try:
        tuner = ScheduleTuner("spmv", TPU_V5E).fit(TRAIN, max_mats=9)
        svc = SelectorService(tuner, cache=ScheduleCache(), batch_max=4,
                              confidence_threshold=1.0)
        rng = np.random.default_rng(0)
        for r in range(8):
            name, _, A = HELD[r % len(HELD)]
            x = rng.standard_normal(A.shape[1]).astype(np.float32)
            svc.submit(f"req{r}:{name}", A, x)
        decisions = svc.run()
    finally:
        install_tracer(None)
    return tr, reg.delta(base), svc, decisions


def test_trace_counts_reconcile_exactly_with_registry(traced_serve):
    tr, delta, _, _ = traced_serve
    counts = tr.counts()
    assert counts.get("select", 0) >= 1 and counts.get("launch", 0) >= 1
    # the acceptance identity: per-event-type JSONL counts == the registry
    # snapshot's events.* counters, exactly, in both directions
    for type_, n in counts.items():
        assert delta.get(f"events.{type_}") == float(n), type_
    for key, v in delta.items():
        if key.startswith("events."):
            assert counts.get(key.split(".", 1)[1], 0) == int(v), key
    # launch spans and the launch_ms histograms tick together
    n_launches = sum(v for k, v in delta.items()
                     if k.startswith("launch_ms.") and k.endswith(".count"))
    assert n_launches == counts["launch"]


def test_serve_jsonl_matches_golden_event_schema(traced_serve):
    tr, _, _, _ = traced_serve
    lines = [json.loads(l) for l in tr.jsonl().splitlines()]
    assert len(lines) == len(tr.events())
    for ev in lines:
        assert ev["type"] in EVENT_TYPES
        assert ev["dur_us"] >= 0.0 and ev["ts_us"] >= 0.0
        for field in EVENT_FIELDS[ev["type"]]:
            assert field in ev, (ev["type"], field)


def test_decisions_and_retraining_examples_carry_measured_latency(
        traced_serve):
    _, _, svc, decisions = traced_serve
    executed = [d for d in decisions if d.y is not None]
    assert executed
    assert all(d.measured_ms is not None and d.measured_ms > 0
               for d in executed)
    with_resid = [d for d in executed if d.residual is not None]
    assert with_resid           # modeled_time_s known => residual attached
    for d in with_resid:
        assert d.residual == pytest.approx(
            np.log10(d.measured_ms / (d.modeled_time_s * 1e3)), abs=1e-9)
    # every verify decision produced a retraining example; rows always
    # carry the measured_ms/residual fields and the executed ones are filled
    rows = svc.retraining_examples
    assert len(rows) >= len(executed)
    assert all("measured_ms" in r and "residual" in r for r in rows)
    assert any(r["measured_ms"] is not None for r in rows)


def test_refit_consumes_measured_latency_examples(traced_serve):
    _, _, svc, _ = traced_serve
    n = len(svc.retraining_examples)
    assert n >= 4
    tel = svc.refit(min_examples=4)
    assert tel["refit"] == 1.0 and tel["examples"] == float(n)
    assert svc.telemetry()["refits"] >= 1


def test_calibration_report_from_serve_trace(traced_serve, tmp_path):
    tr, _, _, _ = traced_serve
    path = tmp_path / "serve.jsonl"
    tr.write_jsonl(str(path))
    launches = load_launches([str(path)])
    assert launches             # serve launches carry measured+modeled
    report = summarize(launches)
    assert report
    for key, row in report.items():
        op, layout, backend = key.split("/")
        assert op == "spmv"
        assert row["launches"] >= 1
        assert row["calibration_scale"] > 0
        assert row["calibrated_mape"] >= 0
        # the scale is exactly 10**mean_residual
        assert row["calibration_scale"] == pytest.approx(
            10.0 ** row["mean_log10_residual"])


def test_report_skips_torn_lines(tmp_path):
    path = tmp_path / "torn.jsonl"
    good = json.dumps({"type": "launch", "op": "spmv", "layout": "ell",
                       "backend": "jnp", "measured_ms": 2.0,
                       "modeled_ms": 1.0})
    path.write_text("{not json\n" + good + "\n"
                    + json.dumps({"type": "launch", "measured_ms": -1.0,
                                  "modeled_ms": 1.0}) + "\n")
    launches = load_launches([str(path)])
    assert len(launches) == 1
    rep = summarize(launches)
    assert rep["spmv/ell/jnp"]["mean_log10_residual"] == \
        pytest.approx(np.log10(2.0))


def test_report_cli_prints_one_row_per_group(tmp_path, capsys):
    path = tmp_path / "one.jsonl"
    path.write_text(json.dumps({"type": "launch", "op": "spmv",
                                "layout": "ell", "backend": "jnp",
                                "measured_ms": 2.0, "modeled_ms": 1.0})
                    + "\n")
    rep = report_main([str(path)])
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 2 and out[0].split()[4] == "resid"
    row = out[1].split()
    assert row[0] == "spmv/ell/jnp" and row[1] == "1"
    assert float(row[4]) == pytest.approx(
        rep["spmv/ell/jnp"]["mean_log10_residual"], abs=0.005)


def test_obs_package_loads_jax_only_on_the_first_span():
    code = ("import sys\n"
            "import repro.obs, repro.obs.report\n"
            "assert 'jax' not in sys.modules, 'jax at import'\n"
            "with repro.obs.span('prep'):\n"
            "    pass\n"
            "assert 'jax' in sys.modules\n")
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# ------------------------------------------------------------ bench_compare

def _bench_compare():
    path = pathlib.Path(__file__).resolve().parent.parent / "scripts" \
        / "bench_compare.py"
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_compare_identical_and_regressed(tmp_path, capsys):
    bc = _bench_compare()
    base = {"k1": {"us": 100.0, "derived": "-"},
            "k2": {"us": 50.0, "derived": "-"},
            "mod/elapsed": {"us": 1000.0, "derived": "-"}}
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))
    assert bc.main([str(a), str(b)]) == 0               # unchanged tree
    regressed = dict(base, k1={"us": 200.0, "derived": "-"},
                     **{"mod/elapsed": {"us": 9000.0, "derived": "-"}})
    b.write_text(json.dumps(regressed))
    assert bc.main([str(a), str(b)]) == 0               # report, not gate
    assert bc.main([str(a), str(b), "--strict"]) == 1   # gate on demand
    out = capsys.readouterr().out
    assert "REGRESSION k1" in out
    assert "elapsed" not in out.split("REGRESSION", 1)[1].splitlines()[0]
    regs, _ = bc.compare(bc.load(str(a)), bc.load(str(b)), 0.25)
    assert [r[0] for r in regs] == ["k1"]               # /elapsed skipped


def test_bench_compare_partial_run_is_not_a_regression(tmp_path):
    bc = _bench_compare()
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"k1": {"us": 100.0}, "k2": {"us": 50.0}}))
    b.write_text(json.dumps({"k1": {"us": 101.0}}))     # k2 missing
    assert bc.main([str(a), str(b), "--strict"]) == 0
