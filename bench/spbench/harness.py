"""One run of one cell: set-up, warm-up, the measured window, the check.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Set-up (timed as ``setup_s``, from the start of ``run.py``) makes the
operand from the seed, plans it through the program's selector and warms
every shape the window uses, with JAX's persistent compilation cache in the
checkout. The window then runs for ``--seconds``. Afterwards the device's
memory peak is read, the program's state is freed, and the sampled answers
are compared with the float64 reference. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window with the profiler and
reports its per-layer metrics instead.

Output: earlier stdout lines say what set-up and the window did (compiles
inside the window, generator lateness); the last stdout line is one JSON
object, its last key ``checks``: each compared number with its limit, which
also end standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from typing import Dict, Optional

import numpy as np

from . import reference
from .loops import LOOPS, Fault
from .peaks import peaks
from .spec import Spec, SpecError
from .tracefile import Capture

SPANS = ("execute", "vector_update", "restart", "submit", "select", "drain",
         "on_result")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _Compiles:
    """Programs compiled, seen through ``jax.monitoring``: the backend-compile
    events less the persistent-cache hits, which fire the same event."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.n -= 1


class RunView:
    """What a per-layer reader may read: the trace summary (or None), the
    window's counts, the loop's end-to-end numbers, the work the counts
    stand for, and the chip's peaks."""

    def __init__(self, trace, window: Dict[str, float], end_to_end: dict,
                 bytes_: float, flops: float, device_kind: str):
        self.trace, self.window, self.end_to_end = trace, window, end_to_end
        self.bytes, self.flops = bytes_, flops
        self.device_kind = device_kind

    @property
    def peak(self) -> dict:
        return peaks(self.device_kind)


def _device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def answer_errors(a: dict, answers) -> list:
    """Row error of each sampled answer against the reference."""
    return [reference.row_error(y, *reference.matvec_f64(a, x))
            for x, y in answers]


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             fault: Fault = None, log=print) -> dict:
    """One run of ``cell``; returns the result object (``checks`` last)."""
    import jax
    compiles = _Compiles()
    a = cell.generator().build(cell.config, seed)
    loop = LOOPS[cell.traffic["loop"]](cell, a, seed, seconds, fault)
    loop.setup()
    # set-up's garbage out of the collector's way: a long-lived server
    # would not rescan it in every collection of the window
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t0
    log(f"setup: {setup_s:.3f}s, operand {a['shape'][0]} rows {loop.nnz} "
        f"nonzeros, {loop.plan_text()}, compiles {compiles.n}, cache "
        f"{jax.config.jax_compilation_cache_dir}",
        flush=True)
    try:
        n_compiles = compiles.n
        before = loop.counts()
        cap = Capture(SPANS) if trace else None
        with cap if cap is not None else contextlib.nullcontext():
            loop.window()
        window = loop.window_counts(before)
        in_window = compiles.n - n_compiles
        device = _device_info(cell.chips)
        summary = cap.read() if cap is not None else None
        off_path = loop.off_path()
        late = getattr(loop, "lateness", None)
        log(f"window: {seconds}s, compiles inside {in_window}, attempted "
            f"{loop.attempted()}"
            + ("" if late is None else
               f", generator lateness p50 {np.median(late) * 1e3:.3f}ms max "
               f"{np.max(late) * 1e3:.3f}ms"), flush=True)
        answers = loop.answers()
        loop.release()
    finally:
        gc.unfreeze()
    errors = answer_errors(a, answers)
    limit = float(cell.limits["spmv_err"])
    checks = {"spmv_err": (max(errors, default=float("inf")), limit),
              "off_path": (off_path, 0)}
    unanswered = getattr(loop, "unanswered", None)
    if unanswered is not None:
        checks["unanswered"] = (unanswered(), 0)
    correct = bool(answers) and all(v <= lim for v, lim in checks.values())
    failed = sum(e > limit for e in errors) + int(off_path)
    if hasattr(loop, "rejected"):
        failed += len(loop.rejected) + len(loop.empty) + unanswered()
    metrics: Dict[str, dict] = {}
    values = dict(loop.end_to_end(), setup_s=setup_s)
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        bytes_, flops = loop.work(window)
        view = RunView(summary, window, values, bytes_, flops, device["kind"])
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": int(loop.attempted()),
              "failed": int(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def parse(argv, prog: str):
    ap = argparse.ArgumentParser(prog=prog,
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    """The cell, once the program and a chip it may run on are there, with
    the compilation cache turned on; None (the reason on stderr) otherwise."""
    try:
        cell = Spec().cell(name)
    except SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return None
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program under test is missing ({e}); run from a "
              "checkout that holds src/repro", file=sys.stderr)
        return None
    import jax
    from repro.kernels.common import enable_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX platform is {devs[0].platform!r}, not 'tpu'; the "
              "benchmark measures only on a TPU", file=sys.stderr)
        return None
    if len(devs) < cell.chips:
        print(f"bench: {name} needs {cell.chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return None
    # every program, however small, from the checkout's persistent cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, the result as the
    last line of stdout."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.monotonic() if t0 is None else t0
    args = parse(argv, "bench/run.py")
    cell = load_cell(args.workload)
    if cell is None:
        return 3
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), t0))
    return 0
