"""The loops a traffic mix drives through the program, by the name in its
``loop`` key: ``cg`` and ``pagerank`` (closed-loop solvers over
``Plan.execute``) and ``serve`` (open-loop requests through
``ServingEngine``).

Each loop builds the system under test from the operand in ``setup`` (timed
as set-up), runs ``window``, reports its end-to-end numbers and the counts
its per-layer readers take, hands its sampled answers to ``answers`` and
frees the program's state in ``release``. ``fault(i, x, y)``, given, replaces
the i-th answer the program produces: the tests plant faults with it.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .arrivals import poisson_arrivals
from .work import spmm_bytes, spmm_flops

# device bytes a prepared operand may keep in the program's PreparedStore:
# the whole chip, so the one operand a cell serves is never evicted
STORE_BYTES = 15 << 30
# how long past the window's close an open-loop run waits for answers
ANSWER_WAIT_S = 60.0

Fault = Optional[Callable[[int, object, object], object]]


def _annotate(name: str, fn):
    """``fn`` inside a profiler span ``name`` (host side, benchmark's own)."""
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


def _service(config: dict):
    """A SelectorService whose tuner is fitted on the configuration's fixed
    corpus, so every seed gets the same schedule and compiled shapes."""
    import repro.core as core
    from repro.core import ScheduleTuner, corpus
    from repro.selector import ScheduleCache, SelectorService
    from repro.sparse import GuardedExecutor, PreparedStore, Quarantine
    sel = config["selector"]
    tuner = ScheduleTuner("spmv", getattr(core, sel["platform"])).fit(
        corpus(**sel["corpus"]), max_mats=int(sel["max_mats"]))
    ex = GuardedExecutor(quarantine=Quarantine())
    return SelectorService(tuner, cache=ScheduleCache(),
                           prepared_store=PreparedStore(STORE_BYTES),
                           executor=ex, quarantine=ex.quarantine)


def _csr(a: dict):
    from repro.core.csr import CSR
    return CSR(a["row_ptrs"], a["col_idxs"].astype(np.uint32), a["vals"],
               a["shape"])


def _hist(name: str) -> Tuple[float, float]:
    from repro.obs import default_registry
    h = default_registry().histogram(name)
    return (0.0, 0.0) if h is None else (float(h.count), float(h.sum))


def _device_seed(seed: int) -> int:
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


class _Loop:
    def __init__(self, cell, a: dict, seed: int, seconds: float,
                 fault: Fault = None):
        self.cell, self.a, self.seed = cell, a, seed
        self.seconds, self.fault = seconds, fault
        self.traffic = cell.traffic
        self.nnz = int(a["vals"].size)
        self.n_rows, self.n_cols = (int(v) for v in a["shape"])

    def backend_ok(self) -> bool:
        """Serving builds a bucket plan per drain; the guard's counters
        alone say whether one left its rung."""
        return True

    def off_path(self) -> float:
        """Launches and builds the guard served off the planned rung over
        the run, plus one if the plan itself ended off it."""
        t = self.svc.executor.telemetry()
        return (t["fallbacks"] + t["dense_served"] + t["dense_builds"]
                + (0 if self.backend_ok() else 1))

    def counts(self) -> Dict[str, float]:
        raise NotImplementedError

    def window_counts(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.counts()
        return {k: now[k] - before.get(k, 0.0) for k in now}


class SolveLoop(_Loop):
    """A user's solver: one ``Plan.execute`` per iteration on device-resident
    vectors, the vector updates in jnp, restarted from a new seeded start
    once it converges or reaches ``max_iters``."""

    def setup(self):
        import jax
        from repro.sparse import plan
        self.svc = _service(self.cell.config)
        self.csr = _csr(self.a)
        self.plan = plan("spmv", self.csr, selector=self.svc)
        self.key = jax.random.PRNGKey(_device_seed(self.seed))
        self.rng = np.random.default_rng([self.seed, 1])
        self.k_samples = int(self.traffic["check_samples"])
        self._compile()
        # warm every shape of the window, a restart included
        self.samples: List[Tuple[object, object]] = []
        self.n_seen = 0
        self._restart()
        for _ in range(3):
            self._step()
        self._restart()
        jax.block_until_ready(self.state)
        self.samples, self.n_seen = [], 0
        self._restart()

    def backend_ok(self) -> bool:
        from repro.kernels.common import resolve_backend
        return self.plan.backend == resolve_backend("auto")

    def plan_text(self) -> str:
        return self.plan.describe()

    def _spmv(self, x):
        import jax
        with jax.profiler.TraceAnnotation("execute"):
            y = self.plan.execute(x)
        if self.fault is not None:
            y = self.fault(self.n_seen, x, y)
        # seeded reservoir sample of the answers, references only
        if len(self.samples) < self.k_samples:
            self.samples.append((x, y))
        else:
            j = int(self.rng.integers(0, self.n_seen + 1))
            if j < self.k_samples:
                self.samples[j] = (x, y)
        self.n_seen += 1
        return y

    def window(self):
        t0 = time.monotonic()
        n = 0
        while True:
            self._step()
            n += 1
            if time.monotonic() - t0 >= self.seconds:
                break
        self.elapsed = time.monotonic() - t0
        self.iters = n

    def end_to_end(self) -> Dict[str, float]:
        return {"iters_per_s": self.iters / self.elapsed}

    def counts(self) -> Dict[str, float]:
        from repro.sparse import launch_count
        n, s = _hist("launch_ms.spmv")
        return {"launches": float(launch_count("spmv")),
                "launch_ms_count": n, "launch_ms_sum": s}

    def work(self, w: Dict[str, float]) -> Tuple[float, float]:
        """(bytes, flops) the launches of a window need."""
        n = w.get("launches", 0.0)
        return (n * spmm_bytes(self.nnz, self.n_rows, self.n_cols),
                n * spmm_flops(self.nnz))

    def answers(self) -> List[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """(input, answer) of each sampled launch, on the host."""
        import jax
        return [(np.asarray(x), None if y is None else np.asarray(y))
                for x, y in jax.device_get(self.samples)]

    def attempted(self) -> int:
        return self.iters

    def release(self):
        self.samples = []
        self.state = None
        self.plan = None
        self.svc.prepared_store.clear()


class CGLoop(SolveLoop):
    """Unpreconditioned conjugate gradients on a right-hand side drawn from
    the seed; restart at ``||r|| <= tolerance ||b||``, at ``max_iters``, or
    on a breakdown (a non-finite residual)."""

    def _compile(self):
        import jax
        import jax.numpy as jnp
        n = self.n_rows

        @jax.jit
        def start(key):
            b = jax.random.normal(key, (n,), jnp.float32)
            bb = jnp.sum(b * b)
            return (jnp.zeros_like(b), b, b, bb), bb

        # elementwise sums, not dots: an f32 dot on the MXU would round to
        # bf16 and the solver's own arithmetic would limit the answers
        @jax.jit
        def update(state, q):
            x, r, p, rr = state
            alpha = rr / jnp.sum(p * q)
            x = x + alpha * p
            r = r - alpha * q
            rr_new = jnp.sum(r * r)
            return (x, r, r + (rr_new / rr) * p, rr_new), rr_new

        self._start, self._update = start, update
        self.tol2 = float(self.traffic["tolerance"]) ** 2
        self.max_iters = int(self.traffic["max_iters"])

    def _restart(self):
        import jax
        with jax.profiler.TraceAnnotation("restart"):
            self.key, sub = jax.random.split(self.key)
            self.state, bb = self._start(sub)
            self.bb, self.k = float(bb), 0

    def _step(self):
        import jax
        q = self._spmv(self.state[2])
        with jax.profiler.TraceAnnotation("vector_update"):
            self.state, rr = self._update(self.state, q)
            rr = float(rr)
        self.k += 1
        if not math.isfinite(rr) or rr <= self.tol2 * self.bb \
                or self.k >= self.max_iters:
            self._restart()


class PageRankLoop(SolveLoop):
    """GAP ``pr``: x <- (1 - d)/n + d P x from a seeded positive start of
    unit sum; restart when ``sum |x_new - x| < tolerance`` or at
    ``max_iters``."""

    def _compile(self):
        import jax
        import jax.numpy as jnp
        n = self.n_rows
        d = float(self.traffic["damping"])

        @jax.jit
        def start(key):
            x = jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5)
            return x / jnp.sum(x)

        @jax.jit
        def update(x, y):
            x_new = (1.0 - d) / n + d * y
            return x_new, jnp.sum(jnp.abs(x_new - x))

        self._start, self._update = start, update
        self.tol = float(self.traffic["tolerance"])
        self.max_iters = int(self.traffic["max_iters"])

    def _restart(self):
        import jax
        with jax.profiler.TraceAnnotation("restart"):
            self.key, sub = jax.random.split(self.key)
            self.state = self._start(sub)
            self.k = 0

    def _step(self):
        import jax
        y = self._spmv(self.state)
        with jax.profiler.TraceAnnotation("vector_update"):
            self.state, diff = self._update(self.state, y)
            diff = float(diff)
        self.k += 1
        if diff < self.tol or self.k >= self.max_iters:
            self._restart()


class ServeLoop(_Loop):
    """Open loop: requests due at seeded Poisson times, each one SpMV of the
    operand with its own seeded vector, offered through
    ``ServingEngine.submit`` by a generator thread while ``engine.start()``
    serves. Latency runs from when a request was due to its ``on_result``."""

    def setup(self):
        from repro.serving import ServingEngine
        self.svc = _service(self.cell.config)
        # benchmark-side spans around the engine's calls into the selector
        self.svc.select = _annotate("select", self.svc.select)
        self.svc.drain_bucket = _annotate("drain", self.svc.drain_bucket)
        self.csr = _csr(self.a)
        rng = np.random.default_rng([self.seed, 2])
        self.offsets = poisson_arrivals(float(self.traffic["rate_per_s"]),
                                        self.seconds,
                                        int(self.traffic["gap_seed"]), rng)
        n_req = len(self.offsets)
        self.xs = rng.standard_normal((n_req, self.n_cols), np.float32)
        k = min(int(self.traffic["check_samples"]), n_req)
        self.sampled = set(int(i) for i in rng.choice(n_req, k, replace=False))
        self.done: Dict[int, float] = {}
        self.outputs: Dict[int, Optional[np.ndarray]] = {}
        self.rejected: set = set()      # refused at submit
        self.empty: set = set()         # answered with no output
        self.engine = ServingEngine(self.svc, on_result=self._on_result,
                                    **self.traffic.get("engine", {}))
        # warm the admission path and every drain width the window can use
        warm_rng = np.random.default_rng([self.seed, 3])
        width = 1
        while width <= self.engine.slots.slot_max:
            for j in range(width):
                self.engine.submit("warm", self.csr, warm_rng.standard_normal(
                    self.n_cols).astype(np.float32), rid=f"warm{width}.{j}")
            self.engine.drain_all()
            width *= 2
        self.schedule = self.svc.select(self.csr).schedule

    def plan_text(self) -> str:
        return f"served under {self.schedule}"

    def _on_result(self, rid: str, y):
        if not rid.isdigit():
            return
        import jax
        with jax.profiler.TraceAnnotation("on_result"):
            i = int(rid)
            if self.fault is not None:
                y = self.fault(i, self.xs[i], y)
            if y is None:
                self.empty.add(i)
            if i in self.sampled:
                self.outputs[i] = None if y is None else np.asarray(y)
            self.done[i] = time.monotonic()

    def _generate(self):
        import jax
        late = np.zeros(len(self.offsets))
        for i, off in enumerate(self.offsets):
            due = self.t0 + off
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            with jax.profiler.TraceAnnotation("submit"):
                late[i] = time.monotonic() - due
                if not self.engine.submit("ppr", self.csr, self.xs[i],
                                          rid=str(i)):
                    self.rejected.add(i)
        self.lateness = late

    def window(self):
        self.engine.start()
        self.t0 = time.monotonic() + 0.01
        gen = threading.Thread(target=self._generate, name="bench-generator")
        gen.start()
        gen.join()
        stop = self.t0 + self.seconds + ANSWER_WAIT_S
        n_req = len(self.offsets)
        while (len(self.done) + len(self.rejected) < n_req
               and time.monotonic() < stop):
            time.sleep(0.002)
        self.engine.stop()
        self.t_end = max(self.done.values(), default=time.monotonic())

    def latencies_ms(self) -> np.ndarray:
        """Due-to-answer latency of every request; a request rejected,
        answered with nothing or never answered sits beyond the tail."""
        beyond = (self.seconds + ANSWER_WAIT_S) * 1e3
        lat = np.full(len(self.offsets), beyond)
        for i, t in self.done.items():
            if i not in self.empty:
                lat[i] = (t - self.t0 - self.offsets[i]) * 1e3
        return lat

    def end_to_end(self) -> Dict[str, float]:
        answered = len(self.done) - len(self.empty)
        span = max(self.t_end - self.t0, self.seconds)
        return {"req_per_s": answered / span,
                "req_p95_ms": float(np.percentile(self.latencies_ms(), 95))}

    def counts(self) -> Dict[str, float]:
        t = self.engine.telemetry()
        n, s = _hist("select_ms")
        return {"drains": t["drains"], "drained_members": t["drained_members"],
                "select_ms_count": n, "select_ms_sum": s}

    def work(self, w: Dict[str, float]) -> Tuple[float, float]:
        drains, members = w.get("drains", 0.0), w.get("drained_members", 0.0)
        per_k = spmm_bytes(0, self.n_rows, self.n_cols, 1)
        return (drains * spmm_bytes(self.nnz, 0, 0) + members * per_k,
                members * spmm_flops(self.nnz))

    def answers(self):
        """(input, answer) of each sampled request that was answered."""
        return [(self.xs[i], self.outputs.get(i)) for i in
                sorted(self.sampled) if i in self.done]

    def unanswered(self) -> int:
        return sum(1 for i in range(len(self.offsets))
                   if i not in self.done and i not in self.rejected)

    def attempted(self) -> int:
        return len(self.offsets)

    def release(self):
        self.engine = None
        self.svc.prepared_store.clear()


LOOPS = {"cg": CGLoop, "pagerank": PageRankLoop, "serve": ServeLoop}
