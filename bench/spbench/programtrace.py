"""The program's own spans in a profiler trace, and the device's idle time
charged to them.

The program opens a ``jax.profiler.TraceAnnotation`` named ``repro.<type>``
at each layer boundary (``repro/obs/trace.py``; DESIGN.md §12 lists them).
They sit on the host plane beside the benchmark's own spans, on the device's
clock. ``read_program`` reduces one ``.xplane.pb`` over the same window and
the same idle intervals as ``tracefile.reduce_trace``:

* ``program_spans``: for each ``repro.*`` name, the spans that overlap the
  window and their summed duration clipped to it;
* ``program_idle_s``: every idle interval of the device, split at span
  edges, each piece charged to the innermost ``repro.*`` span open at that
  instant on any thread (the one started last), or to ``host:other`` where
  none is open. The charges sum to the window's idle time.

``METRICS`` turns a summary into the per-layer numbers; each is None where
the trace holds no ``repro.*`` span, as with a program that opens none.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from .tracefile import (DEVICE_PLANE, HOST_PLANE, OPS_LINE, _union,
                        _window_ns)

PREFIX = "repro."
OTHER = "host:other"

# layer -> the program spans whose charged idle time is that layer's
LAYER_SPANS = {
    "selector": ("repro.select", "repro.hash", "repro.fingerprint"),
    "engine": ("repro.admission", "repro.drain", "repro.drain_plan",
               "repro.drain_stack", "repro.drain_fetch",
               "repro.drain_answer"),
    "plan": ("repro.launch", "repro.dispatch", "repro.finite_check"),
}


def charge_idle(idle: Sequence[Tuple[float, float]],
                spans: Sequence[Tuple[float, float, str]]
                ) -> Dict[str, float]:
    """Idle time charged to span names: ``idle`` is disjoint ``(start,
    end)`` intervals, ``spans`` are ``(start, end, name)`` from any threads.
    Each stretch of idle time goes to the span open over it that started
    last (at equal starts, the one that ends first), or to ``OTHER``."""
    idle = sorted((a, b) for a, b in idle if b > a)
    charged: Dict[str, float] = {}
    if not idle:
        return charged
    spans = sorted((s, e, n) for s, e, n in spans if e > s)
    points = sorted({t for iv in idle for t in iv}
                    | {t for s, e, _ in spans for t in (s, e)})
    open_: List[Tuple[float, float, int]] = []   # (-start, end, index)
    nxt = k = 0
    for t0, t1 in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][0] <= t0:
            heapq.heappush(open_, (-spans[nxt][0], spans[nxt][1], nxt))
            nxt += 1
        while open_ and open_[0][1] <= t0:
            heapq.heappop(open_)
        while k < len(idle) and idle[k][1] <= t0:
            k += 1
        if k == len(idle):
            break
        # idle edges are among the points: a piece is idle throughout or not
        if idle[k][0] <= t0:
            name = spans[open_[0][2]][2] if open_ else OTHER
            charged[name] = charged.get(name, 0.0) + (t1 - t0)
    return charged


@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    idle_s: float
    program_spans: Dict[str, Tuple[int, float]]   # name -> (count, seconds)
    program_idle_s: Dict[str, float]              # name or OTHER -> seconds

    def mean_ms(self, name: str) -> Optional[float]:
        n, s = self.program_spans.get(name, (0, 0.0))
        return 1e3 * s / n if n else None

    def idle_pct(self, layer: str) -> Optional[float]:
        """Share of the window, in %, in which the device idled under one
        of the layer's spans."""
        if not self.program_spans or self.window_s <= 0:
            return None
        s = sum(self.program_idle_s.get(n, 0.0) for n in LAYER_SPANS[layer])
        return 100.0 * s / self.window_s


def read_program(path: str, spans: Sequence[str]
                 ) -> Optional[ProgramSummary]:
    """Program spans and charged idle time of one ``.xplane.pb`` over the
    window ``tracefile.reduce_trace`` takes: the extent of the benchmark's
    own host ``spans``. None when the trace holds no device plane.

    A temporary copy of ``reduce_trace``'s window, clip and gap walk, for
    the tests and for reading a saved trace by hand; the harness does not
    call it. It goes when ``reduce_trace`` itself hands the gaps it has
    already computed, and the ``repro.*`` host events, to ``charge_idle``
    (PERF.md §7); a second reducer is not to be kept beside it."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    win = _window_ns(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices or win is None:
        return None
    host = [(e.start_ns, e.end_ns, e.name)
            for p in planes if p.name == HOST_PLANE
            for line in p.lines for e in line.events]
    bench = [h for h in host if h[2] in spans]
    lo, hi = ((min(h[0] for h in bench), max(h[1] for h in bench)) if bench
              else (0.0, float(win[1] - win[0])))
    merged: List[Tuple[float, float]] = []
    for plane in devices:
        for line in plane.lines:
            if line.name == OPS_LINE:
                merged.extend((max(e.start_ns, lo), min(e.end_ns, hi))
                              for e in line.events
                              if min(e.end_ns, hi) > max(e.start_ns, lo))
    idle, last = [], lo
    for a, b in _union(merged) + [(hi, hi)]:
        if a > last:
            idle.append((last, a))
        last = max(last, b)
    program = [(max(s, lo), min(e, hi), n) for s, e, n in host
               if n.startswith(PREFIX) and min(e, hi) > max(s, lo)]
    counts: Dict[str, Tuple[int, float]] = {}
    for s, e, n in program:
        c, t = counts.get(n, (0, 0.0))
        counts[n] = (c + 1, t + (e - s) / 1e9)
    charged = charge_idle(idle, program)
    return ProgramSummary(
        window_s=(hi - lo) / 1e9, idle_s=sum(b - a for a, b in idle) / 1e9,
        program_spans=counts,
        program_idle_s={n: v / 1e9 for n, v in charged.items()})


# per-layer metric -> its value from a summary (None where nothing to read)
METRICS = {
    "selector.hash_ms": lambda p: p.mean_ms("repro.hash"),
    "selector.idle_pct.serve": lambda p: p.idle_pct("selector"),
    "engine.idle_pct.serve": lambda p: p.idle_pct("engine"),
    "plan.idle_pct.serve": lambda p: p.idle_pct("plan"),
    "plan.idle_pct.solve": lambda p: p.idle_pct("plan"),
    "plan.dispatch_ms": lambda p: p.mean_ms("repro.dispatch"),
}
