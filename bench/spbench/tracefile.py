"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

The profiler writes an ``.xplane.pb``. In it, each chip is a plane named
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per device
operation; the host is the plane ``/host:CPU``, where the benchmark's own
``jax.profiler.TraceAnnotation`` spans sit on the lines of the threads that
opened them; the plane ``Task Environment`` carries the traced window's
start and stop. Event times are nanoseconds from the window's start, on one
clock for host and device. The window is the span of the benchmark's own
host spans inside the profile.

The Pallas kernels carry no ``name=``: an operation is matched by the HLO
name the trace shows, the jitted wrapper's name (``KERNELS``). This is the
one place those names live.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

# kernel family -> HLO names of its Pallas calls in the trace
KERNELS = {
    "bsr_spmv": ("bsr_spmv_pallas", "bsr_spmm_pallas",
                 "bsr_spmv_sell_pallas", "bsr_spmm_sell_pallas"),
}

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"(\.(\d+|clone))*$")


def op_name(event_name: str) -> str:
    """``%bsr_spmv_pallas.1 = f32[...] custom-call(...)`` -> ``bsr_spmv_pallas``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # union of op intervals, mean over chips
    kernel_s: Dict[str, float]          # kernel family -> device seconds
    device_ops: List[Tuple[str, float]]  # top operations by device seconds
    idle_gaps: List[Tuple[str, float]]   # longest idle gaps by host span

    @property
    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _window_ns(planes) -> Optional[Tuple[int, int]]:
    for plane in planes:
        if plane.name == "Task Environment":
            st = {k: v for k, v in plane.stats}
            if "profile_start_time" in st and "profile_stop_time" in st:
                return int(st["profile_start_time"]), int(st["profile_stop_time"])
    return None


def reduce_trace(path: str, spans: Sequence[str], top: int = 10
                 ) -> Optional[TraceSummary]:
    """Summary of one ``.xplane.pb``; None when it holds no device plane.

    The window runs from the first to the last of the benchmark's own host
    ``spans``: the loop's timed work, without the profiler's start-up and
    shut-down around it (the whole profile where no span was recorded)."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    win = _window_ns(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices or win is None:
        return None
    host = [(e.start_ns, e.end_ns, e.name)
            for p in planes if p.name == HOST_PLANE
            for line in p.lines for e in line.events if e.name in spans]
    lo, hi = ((min(h[0] for h in host), max(h[1] for h in host)) if host
              else (0.0, float(win[1] - win[0])))
    length = hi - lo

    busy_total = 0.0
    kernel_ns = {k: 0.0 for k in KERNELS}
    by_op: Dict[str, float] = {}
    merged: List[Tuple[float, float]] = []
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = max(e.start_ns, lo), min(e.end_ns, hi)
                if b <= a:
                    continue
                ivs.append((a, b))
                name = op_name(e.name)
                by_op[name] = by_op.get(name, 0.0) + (b - a)
                for fam, names in KERNELS.items():
                    if name in names:
                        kernel_ns[fam] += b - a
        u = _union(ivs)
        busy_total += sum(b - a for a, b in u)
        merged.extend(u)
    n_dev = len(devices)
    gaps, last = [], lo
    for a, b in _union(merged) + [(hi, hi)]:
        if a > last:
            gaps.append((last, a))
        last = max(last, b)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0.0, "host:other"
        for s, e, name in host:
            overlap = min(b, e) - max(a, s)
            if overlap > best:
                best, label = overlap, name
        labelled.append((label, (b - a) / 1e9))
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=length / 1e9, busy_s=busy_total / n_dev / 1e9,
        kernel_s={k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        device_ops=[(k, v / n_dev / 1e9) for k, v in ops],
        idle_gaps=labelled)


class Capture:
    """Profiler trace of a ``with`` block into a scratch directory under
    TMPDIR, removed once ``summary`` has been read."""

    def __init__(self, spans: Sequence[str]):
        self.spans = tuple(spans)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.summary: Optional[TraceSummary] = None

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def read(self) -> Optional[TraceSummary]:
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if found:
                self.summary = reduce_trace(found[0], self.spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.summary
