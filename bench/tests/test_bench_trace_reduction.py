"""The reduction from a profiler trace to the benchmark's numbers, on a small
trace recorded on one TPU v5e: three guarded ``Plan.execute`` calls of a
1,024-row ELL bs=128 SpMV, each followed by a host sync, under the spans
``execute`` and ``vector_update``."""
import os

import pytest

import bench_helpers  # noqa: F401  (import paths)
from spbench import tracefile

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small_spmv.xplane.pb")
SPANS = ("execute", "vector_update")


@pytest.fixture(scope="module")
def summary():
    return tracefile.reduce_trace(TRACE, SPANS)


def test_op_names_drop_hlo_text_and_suffixes():
    assert tracefile.op_name(
        "%bsr_spmv_pallas.1 = f32[8,1,128]{2,1,0} custom-call(s32[8,1])"
    ) == "bsr_spmv_pallas"
    assert tracefile.op_name("%copy-done.2 = f32[4] copy-done(x)") \
        == "copy-done"
    assert tracefile.op_name("%fusion.3.clone = f32[] fusion()") == "fusion"


def test_window_is_the_extent_of_the_benchmark_spans(summary):
    # the recorded profile is longer than the loop it traced
    assert 0.18 < summary.window_s < 0.19
    assert 0 < summary.busy_s < summary.window_s
    assert summary.idle_pct == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))


def test_kernel_time_is_the_pallas_calls(summary):
    ops = dict(summary.device_ops)
    assert summary.device_ops[0][0] == "bsr_spmv_pallas"
    assert summary.kernel_s["bsr_spmv"] == pytest.approx(
        ops["bsr_spmv_pallas"])
    assert summary.kernel_s["bsr_spmv"] <= summary.busy_s
    # three launches of a few tens of microseconds each
    assert 3e-5 < summary.kernel_s["bsr_spmv"] < 3e-4


def test_idle_gaps_are_named_by_host_spans(summary):
    assert 0 < len(summary.idle_gaps) <= 10
    secs = [s for _, s in summary.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert {name for name, _ in summary.idle_gaps} <= set(SPANS) | {
        "host:other"}
    assert sum(secs) <= summary.window_s - summary.busy_s + 1e-9


def test_union_merges_overlaps():
    assert tracefile._union([(3, 4), (0, 2), (1, 2.5), (4, 5)]) == [
        (0, 2.5), (3, 5)]


def test_a_trace_without_a_device_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    cap = tracefile.Capture(SPANS)
    with cap:
        with jax.profiler.TraceAnnotation("execute"):
            jnp.ones(8).sum().block_until_ready()
    assert cap.read() is None
    assert not os.path.exists(cap.dir)
