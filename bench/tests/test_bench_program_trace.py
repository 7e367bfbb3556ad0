"""The program's ``repro.*`` spans in a profiler trace, and the device's idle
time charged to them (``spbench.programtrace``): the charging rule on
synthetic intervals, the recorded ``small_spmv`` trace (no program spans)
still reducing to the numbers it always has, and a recorded v5e trace with
program spans (``record_program_trace.py``) giving every per-layer number."""
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (import paths)
from spbench import programtrace, tracefile
from spbench.harness import SPANS
from spbench.programtrace import OTHER, charge_idle

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small_spmv.xplane.pb")
PROGRAM = os.path.join(DATA, "program_spans.xplane.pb")


# ------------------------------------------------------------ charging rule

def test_idle_goes_to_the_innermost_open_span():
    spans = [(0, 10, "outer"), (2, 4, "inner")]
    got = charge_idle([(1, 3), (5, 12)], spans)
    assert got == {"outer": 1 + 5, "inner": 1, OTHER: 2}


def test_spans_on_two_threads_charge_the_one_started_last():
    # thread 1 holds a long span; thread 2 opens one inside it
    got = charge_idle([(0, 10)], [(0, 10, "t1"), (5, 8, "t2")])
    assert got == {"t1": 7, "t2": 3}
    # at equal starts the span that ends first is the inner one
    got = charge_idle([(0, 10)], [(0, 10, "long"), (0, 4, "short")])
    assert got == {"long": 6, "short": 4}


def test_a_gap_with_no_span_is_host_other():
    assert charge_idle([(0, 5)], []) == {OTHER: 5}
    assert charge_idle([(0, 5)], [(6, 9, "later")]) == {OTHER: 5}
    assert charge_idle([], [(0, 5, "busy")]) == {}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_charges_sum_to_the_idle_time_and_match_unit_steps(seed):
    rng = np.random.default_rng(seed)
    spans = []
    for i in range(40):
        s = int(rng.integers(0, 200))
        spans.append((s, s + int(rng.integers(1, 40)), f"s{i % 7}"))
    idle, t = [], 0
    while t < 220:
        a = t + int(rng.integers(0, 6))
        b = a + int(rng.integers(1, 9))
        idle.append((a, b))
        t = b + 1
    got = charge_idle(idle, spans)
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in idle))
    # brute force, one unit step at a time
    want = {}
    for a, b in idle:
        for u in range(a, b):
            live = [(s, -e, n) for s, e, n in spans if s <= u and u + 1 <= e]
            name = max(live)[2] if live else OTHER
            want[name] = want.get(name, 0) + 1
    assert got == pytest.approx(want)


# ------------------------------------------- recorded trace, no repro spans

def test_small_spmv_still_reduces_to_its_numbers():
    s = tracefile.reduce_trace(SMALL, ("execute", "vector_update"))
    assert s.window_s == 0.184321784
    assert s.busy_s == 7.3135e-05
    assert s.kernel_s == {"bsr_spmv": 6.5378e-05}
    assert s.idle_gaps == [
        ("vector_update", 0.090419122), ("vector_update", 0.087489494),
        ("vector_update", 0.001213043), ("execute", 0.001125188),
        ("vector_update", 0.001005365), ("vector_update", 0.000842989),
        ("execute", 0.000827861), ("execute", 0.000280935),
        ("execute", 0.000204171), ("execute", 0.000198331)]


def test_a_trace_without_program_spans_reads_nothing():
    spans = ("execute", "vector_update")
    p = programtrace.read_program(SMALL, spans)
    s = tracefile.reduce_trace(SMALL, spans)
    assert p.window_s == s.window_s
    assert p.idle_s == pytest.approx(s.window_s - s.busy_s, abs=1e-12)
    assert p.program_spans == {}
    assert p.program_idle_s == {OTHER: pytest.approx(p.idle_s)}
    assert {m: f(p) for m, f in programtrace.METRICS.items()} == {
        m: None for m in programtrace.METRICS}


# ------------------------------------------ recorded trace with repro spans

@pytest.fixture(scope="module")
def program():
    return programtrace.read_program(PROGRAM, SPANS)


def test_every_layer_span_is_in_the_recorded_trace(program):
    # the operand was fingerprinted before the trace: the memo serves it
    seen = {n for names in programtrace.LAYER_SPANS.values() for n in names}
    assert set(program.program_spans) == seen - {"repro.fingerprint"} | {
        "repro.prep"}
    assert program.program_spans["repro.launch"][0] == 4   # 3 SpMVs, 1 drain
    assert program.program_spans["repro.drain"][0] == 1
    assert program.program_spans["repro.hash"][0] == 4     # one per request
    assert not set(program.program_spans) & set(SPANS)


def test_every_per_layer_number_reads_finite(program):
    values = {m: f(program) for m, f in programtrace.METRICS.items()}
    for m, v in values.items():
        assert v is not None and np.isfinite(v) and v >= 0, (m, v)
    assert 0 < values["selector.hash_ms"] < 1e3
    assert 0 < values["plan.dispatch_ms"] < 1e3


def test_charged_idle_is_the_devices_idle_time(program):
    s = tracefile.reduce_trace(PROGRAM, SPANS)
    assert program.window_s == s.window_s
    assert program.idle_s == pytest.approx(s.window_s - s.busy_s, abs=1e-9)
    assert sum(program.program_idle_s.values()) == pytest.approx(
        program.idle_s, abs=1e-9)
    layers = sum(program.idle_pct(k) for k in programtrace.LAYER_SPANS)
    assert layers <= s.idle_pct + 1e-9
