"""plan/execute: the compile-style front door to every sparse kernel.

``plan(op, operands, schedule=... | selector=...)`` resolves a ``Schedule``
(explicitly, through a fitted ``ScheduleTuner``, or through the online
``SelectorService`` cache/tree/verify path), runs the op's host-side prep +
symbolic phase once, and returns a ``Plan`` — an executable carrying the
resolved schedule, the selection provenance (source / fingerprint / modeled
cost), and a jitted launch. ``plan_bucket`` builds ONE stacked jitted launch
for a whole same-schedule bucket, closing the PR-2 follow-up where bucket
members shared a compiled program but not the launch.

Telemetry: launch and trace counters, now Software PMCs in the process
``MetricsRegistry`` (DESIGN.md §12) under ``plan.launches.<op>`` /
``plan.traces.<key>``. ``launch_count`` ticks once per ``Plan.execute``
(one device program dispatch); ``trace_count`` ticks when a jitted executor
actually retraces. A bucket of N matrices executed through one stacked plan
bumps the launch counter once, not N times — the property the
stacked-launch tests assert. Every ``execute`` is additionally wall-clock
timed: the measurement feeds the ``launch_ms.<op>`` latency histogram, the
``launch`` trace event (measured next to the plan's modeled cost), and the
``Plan.last_measured_s`` field the selector's residual feedback reads.
A launch whose SpMV/SpMM kernel ran (``pallas`` or ``interpret``) also
ticks ``kernel.tile_product.vpu`` or ``.mxu``, as ``Plan.tile_product``
reads it from the launch's runtime input.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core.autotune import SELL_SIGMA, Schedule
from ..core.csr import BSR, CSR, ELLBSR, SELLBSR
from ..kernels.common import resolve_backend
from ..obs import default_registry, trace as obs_trace
from . import resilience
from .prepared import PreparedStore
from .registry import get_op
from .tensor import SparseTensor


def _bump_launch(key: str) -> None:
    default_registry().inc(f"plan.launches.{key}")


def _bump_trace(key: str) -> None:
    default_registry().inc(f"plan.traces.{key}")
    obs_trace.emit("compile", f"trace:{key}", key=key)


def launch_count(op: Optional[str] = None) -> int:
    """Number of ``Plan.execute`` device launches (per op, or total)."""
    reg = default_registry()
    return int(round(reg.get(f"plan.launches.{op}") if op
                     else reg.sum_prefix("plan.launches.")))


def trace_count(key: Optional[str] = None) -> int:
    """Number of executor retraces (per executor key, or total)."""
    reg = default_registry()
    return int(round(reg.get(f"plan.traces.{key}") if key
                     else reg.sum_prefix("plan.traces.")))


def reset_counters() -> None:
    default_registry().clear_prefix("plan.launches.")
    default_registry().clear_prefix("plan.traces.")


@dataclasses.dataclass
class Plan:
    """An executable sparse-op launch with its selection provenance."""

    op: str
    schedule: Optional[Schedule]
    backend: str
    _run: Callable                      # jit-backed launch closure
    operands: tuple = ()                # prepared device operands (pytrees)
    source: str = "explicit"            # "explicit" | "tuner" | "selector-*"
    fingerprint_key: str = ""
    modeled_time_s: Optional[float] = None
    confidence: Optional[float] = None
    n_members: int = 1                  # >1 for stacked bucket plans
    n_shards: int = 1                   # >1 for sharded (distributed) plans
    # per-shard selection provenance (sharded plans): one dict per shard
    # with source / fingerprint_key / schedule — the acceptance-level record
    # that each shard's schedule went through the selector independently
    shard_provenance: Optional[List[Dict]] = None
    # sharded plans: the set of devices holding each shard's arrays, read
    # from the placed arrays themselves (one device per shard when placed)
    shard_devices: Optional[List[frozenset]] = None
    # how a launch's runtime input reaches the bsr_spmv kernels: "given"
    # (each input as it is), "stacked" (member vectors stacked into one
    # multi-RHS launch), None where no such kernel runs (a dense operand,
    # a mesh program). Read by ``tile_product``.
    kernel_rhs: Optional[str] = None
    # wall-clock of the most recent execute (set per call). With the NaN
    # guard on (default) the guarded run synchronizes on the result, so
    # this is end-to-end launch latency, not dispatch-only.
    last_measured_s: Optional[float] = None

    def execute(self, *runtime):
        """Run the planned launch on the runtime inputs (one device program
        dispatch — stacked plans execute their whole bucket here), timed:
        the measurement lands in the ``launch_ms.<op>`` histogram and, when
        a tracer is installed, in a ``launch`` event carrying measured
        wall-clock next to the plan's modeled cost — the raw material of
        the perfmodel calibration report."""
        _bump_launch(self.op)
        with obs_trace.span("launch", f"{self.op}") as ev:
            t0 = time.monotonic()
            out = self._run(*runtime)
            dt = time.monotonic() - t0
            self.last_measured_s = dt
            s = self.schedule
            modeled_ms = (self.modeled_time_s * 1e3
                          if self.modeled_time_s else None)
            # backend/layout read AFTER the run: the guard rewrites
            # ``p.backend`` when the launch fell down the fallback ladder
            tile = self.tile_product(*runtime)
            ev.update(op=self.op, backend=self.backend,
                      layout=(s.layout if s is not None
                              and s.backend != "dense"
                              else "dense" if s is not None else "per-shard"),
                      measured_ms=dt * 1e3, modeled_ms=modeled_ms,
                      source=self.source, n_members=self.n_members,
                      n_shards=self.n_shards, tile_product=tile)
        reg = default_registry()
        reg.observe(f"launch_ms.{self.op}", dt * 1e3)
        if tile is not None:
            reg.inc(f"kernel.tile_product.{tile}")
        return out

    def tile_product(self, *runtime) -> Optional[str]:
        """Where a launch on ``runtime`` runs the bsr_spmv kernels' tile
        product: "vpu" for a vector right-hand side (f32 multiply-add),
        "mxu" for a matrix one (HIGHEST), None where no such kernel runs
        (a jnp or dense launch, or one the guard moved off the kernel)."""
        if self.kernel_rhs is None \
                or self.backend not in ("pallas", "interpret"):
            return None
        xs = runtime[0] if isinstance(runtime[0], (list, tuple)) \
            else runtime[:1]
        if self.kernel_rhs == "stacked" and len(xs) > 1:
            return "mxu"
        return "vpu" if np.ndim(xs[0]) == 1 else "mxu"

    __call__ = execute

    def describe(self) -> str:
        s = self.schedule
        if s is None:
            sched = ("per-shard" if self.n_shards > 1 else "none")
        elif s.backend == "dense":
            sched = "dense"
        else:
            lay = (f"sell C={s.slice_height}" if s.layout == "sell"
                   else f"ell q={s.ell_quantile}")
            sched = f"{s.backend} bs={s.block_size} {lay} rhs={s.n_rhs}"
        extra = f" members={self.n_members}" if self.n_members > 1 else ""
        if self.n_shards > 1:
            extra = f" shards={self.n_shards}"
        return f"plan[{self.op}] {sched} via {self.source}{extra}"


# f32 vectors of the operand's height and width a caller keeps on a chip
# beside the prepared operand: its x and y, and a solver's work vectors
CALLER_VECTORS = 8


def chip_memory_bytes() -> Optional[int]:
    """One local chip's memory, as the device's own ``memory_stats()``
    reports it (``bytes_limit``); None where the backend reports none."""
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def _needs_placement(op: str, a, schedule: Optional[Schedule],
                     sigma: int) -> bool:
    """True when ``a`` prepared under ``schedule`` (the op planner's default
    when None) would not fit one local chip with room for the caller's
    vectors, and there are other local chips to spread it over."""
    import jax
    from .tensor import prepared_nbytes
    if not isinstance(a, CSR) or get_op(op).sharded_planner is None \
            or jax.local_device_count() < 2:
        return False
    limit = chip_memory_bytes()
    if limit is None:
        return False
    sched = schedule if schedule is not None \
        else SparseTensor.default_schedule()
    room = CALLER_VECTORS * 4 * max(sched.n_rhs, 1) * (a.n_rows + a.n_cols)
    return prepared_nbytes(a, sched, sigma=sigma, shape_bucket=True) \
        + room > limit


def _resolve_with_selector(selector, A: CSR, op: str = "",
                           quarantine=None):
    """(Schedule, provenance, operand content key) from a SelectorService
    or a ScheduleTuner. The service already hashed the matrix bytes for its
    fingerprint memo; the key is forwarded so the planner's PreparedStore
    lookup does not pay a second O(nnz) hashing pass. ``quarantine`` is the
    registry the tuner path consults (defaults to the process-wide one)."""
    if not isinstance(A, CSR):
        raise TypeError("selector-based planning needs a CSR first operand, "
                        f"got {type(A).__name__}")
    if hasattr(selector, "process_pending"):      # SelectorService
        dec = selector.select(A)
        return dec.schedule, {
            "source": f"selector-{dec.source}",
            "fingerprint_key": dec.fingerprint_key,
            "modeled_time_s": dec.modeled_time_s,
            "confidence": dec.confidence,
        }, getattr(dec, "ck", None)
    if hasattr(selector, "select"):               # ScheduleTuner
        schedule, info = selector.select(A)
        source = "tuner"
        q = (quarantine if quarantine is not None
             else resilience.default_quarantine())
        if op and schedule is not None \
                and q.blocked_any_backend(op, schedule):
            # never re-serve a poisoned schedule: re-argmin the candidate
            # grid minus the quarantine (None = everything blocked; keep
            # the pick — a degraded answer beats no answer)
            resel = resilience.unquarantined_select(selector, A, op, q)
            if resel is not None:
                schedule, source = resel, "tuner-requarantined"
        return schedule, {
            "source": source,
            "modeled_time_s": info.get("verified_time_s"),
        }, None
    raise TypeError(f"unsupported selector {type(selector).__name__}; pass a "
                    "SelectorService or a fitted ScheduleTuner")


def plan(op: str, operands, schedule: Optional[Schedule] = None,
         selector=None, backend: str = "auto",
         store: Optional[PreparedStore] = None,
         executor: Optional[resilience.GuardedExecutor] = None,
         **op_kwargs) -> Plan:
    """Build an executable ``Plan`` for a registered sparse op.

    Exactly one schedule source applies: an explicit ``schedule``, a
    ``selector`` (``SelectorService`` → cache/tree/verify path, or a fitted
    ``ScheduleTuner`` → tree-argmin + simulation verify), or the op
    planner's defaults.

    ``store`` is a ``PreparedStore``: repeat ``plan()`` traffic for the
    same (matrix bytes, schedule) pair reuses the finished device-resident
    operands and skips host prep entirely. When planning through a
    ``SelectorService`` the service's own prepared store is used unless one
    is passed explicitly.

    ``executor`` is the ``GuardedExecutor`` (fallback policy + failure
    ledger + quarantine) the guard runs under; it defaults to the
    selector's own executor when planning through a ``SelectorService``,
    else the process-wide default. Passing one explicitly keeps two
    services (or threads) from cross-contaminating quarantine state.
    """
    spec = get_op(op)
    if not isinstance(operands, tuple):
        operands = (operands,)
    backend = resolve_backend(backend)
    provenance: Dict[str, object] = {}
    operand_key = None
    if selector is not None and store is None:
        store = getattr(selector, "prepared_store", None)
    if executor is None and selector is not None:
        executor = getattr(selector, "executor", None)
    quarantine = executor.quarantine if executor is not None else None
    if schedule is None and selector is not None:
        schedule, provenance, operand_key = _resolve_with_selector(
            selector, operands[0], op, quarantine=quarantine)
    if schedule is not None and schedule.backend != "dense" \
            and spec.layouts and schedule.layout not in spec.layouts:
        raise ValueError(f"op {op!r} supports layouts {spec.layouts}, "
                         f"schedule asks for {schedule.layout!r}")
    if len(operands) == 1 and _needs_placement(
            op, operands[0], schedule,
            op_kwargs.get("sigma", SELL_SIGMA)):
        # too large for one chip: nnz-balanced row shards over the local
        # chips, each shard's schedule selected on its own
        import jax
        selected = bool(provenance)
        return plan_sharded(
            op, operands, n_shards=jax.local_device_count(),
            schedule=None if selected else schedule,
            selector=selector if selected else None, backend=backend,
            store=store, executor=executor,
            **{"operand_key": operand_key, **op_kwargs})
    # only inject serving-path extras when a store is in play AND the
    # planner declares/accepts them — custom planners registered through
    # the public register_op API need not know about either kwarg
    if store is not None and spec.planner_store_ok:
        op_kwargs = dict(op_kwargs, store=store)
        if operand_key is not None and spec.planner_operand_key_ok:
            op_kwargs.setdefault("operand_key", operand_key)
    # guarded build + guarded launch (DESIGN.md §11): transient prep faults
    # retry, persistent ones degrade to the op's dense reference; every
    # execute runs through the backend fallback ladder
    dense_run = resilience.make_dense_run(op, operands, schedule, op_kwargs)
    with obs_trace.span("prep", f"plan:{op}", op=op):
        p = resilience.guarded_build(
            lambda: spec.planner(operands, schedule, backend, **op_kwargs),
            op=op, schedule=schedule, dense_run=dense_run, executor=executor)
    resilience.guard_plan(
        p, rebuild=lambda b: spec.planner(operands, schedule, b, **op_kwargs),
        dense_run=dense_run, executor=executor)
    for k, v in provenance.items():
        setattr(p, k, v)
    return p


def plan_sharded(op: str, operands, n_shards: Optional[int] = None,
                 schedule: Optional[Schedule] = None,
                 schedules: Optional[Sequence[Schedule]] = None,
                 selector=None, strategy: str = "nnz", backend: str = "auto",
                 mesh=None, store: Optional[PreparedStore] = None,
                 executor: Optional[resilience.GuardedExecutor] = None,
                 operand_key: Optional[str] = None,
                 **op_kwargs) -> Plan:
    """Distributed plan: nnz-balanced row shards, one schedule per shard.

    The first operand's rows are partitioned into ``n_shards`` contiguous
    shards (``strategy="nnz"`` balances work via the Eq. 5 counters;
    ``"rows"`` is the naive equal-row split), each shard's schedule is
    resolved independently — explicitly (``schedule`` for all shards,
    ``schedules`` per shard) or through the ``selector``, whose per-shard
    fingerprints let skewed matrices get different layouts/block sizes per
    shard — and the op's sharded planner builds the launch: one shard_map
    program over the mesh's ``shards`` axis when the shard schedules agree,
    round-robin per-shard dispatches otherwise. ``n_shards`` defaults to
    the local device count (simulate more on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    Per-shard provenance lands on ``Plan.shard_provenance``; the
    PreparedStore (``store=``, or the selector's own) caches the partition
    and the prepared shard operands, so warm sharded plans skip both.
    ``operand_key`` is the operand's ``content_key`` where the caller has
    hashed it already. ``plan()`` comes here by itself for an operand too
    large for one chip.
    """
    import jax
    from .partition import STRATEGIES, partition_rows
    from .tensor import ShardedSparseTensor, SparseTensor
    spec = get_op(op)
    if spec.sharded_planner is None:
        raise ValueError(f"op {op!r} has no sharded execution path; "
                         "ops with one register a sharded_planner")
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown partition strategy {strategy!r}; "
                         f"one of {STRATEGIES}")
    if not isinstance(operands, tuple):
        operands = (operands,)
    backend = resolve_backend(backend)
    a = operands[0]
    if selector is not None and store is None:
        store = getattr(selector, "prepared_store", None)
    if executor is None and selector is not None:
        executor = getattr(selector, "executor", None)

    part = None
    shard_csrs: Optional[List[CSR]] = None
    ck: Optional[str] = None
    from_prepared = False
    if isinstance(a, ShardedSparseTensor):
        n_parts = a.n_shards
        if n_shards is not None and int(n_shards) != n_parts:
            raise ValueError(f"operand is already partitioned into "
                             f"{n_parts} shards; n_shards={n_shards} "
                             "cannot re-partition a ShardedSparseTensor")
        if schedules is None and schedule is None:
            if selector is not None:
                raise TypeError(
                    "selector-resolved sharded planning needs a CSR first "
                    "operand (a prepared ShardedSparseTensor carries its "
                    "shards' schedules; pass the CSR to re-select)")
            schedules = a.schedules()
            from_prepared = True
    elif isinstance(a, CSR):
        if n_shards is None:
            n_shards = jax.local_device_count()
        n_shards = max(int(n_shards), 1)
        if store is not None:
            from .prepared import content_key
            ck = operand_key or content_key(a)
        part_key = None if ck is None else ("row_partition", ck,
                                            n_shards, strategy)
        built = store.get(part_key) if part_key is not None else None
        if built is None:
            part = partition_rows(a, n_shards, strategy)
            built = {"part": part, "shards": part.slice(a)}
            if part_key is not None:
                # CSR shards are plain dataclasses, not pytrees, so the
                # store's generic leaf-nbytes accounting would see 0 bytes
                # and the LRU could never evict them — count them here
                store.put(part_key, built, nbytes=sum(
                    arr.nbytes for c in built["shards"]
                    for arr in (c.row_ptrs, c.col_idxs, c.nnz_vals)))
        part = built["part"]
        shard_csrs = built["shards"]
        n_parts = part.n_parts
    else:
        raise TypeError("plan_sharded needs a CSR or ShardedSparseTensor "
                        f"first operand, got {type(a).__name__}")

    provenance: Optional[List[Dict]] = None
    if schedules is not None:
        scheds = list(schedules)
        if len(scheds) != n_parts:
            raise ValueError(f"{len(scheds)} schedules for {n_parts} shards")
        src = "prepared" if from_prepared else "explicit"
        provenance = [{"source": src, "schedule": s} for s in scheds]
    elif schedule is not None:
        scheds = [schedule] * n_parts
        provenance = [{"source": "explicit", "schedule": schedule}
                      for _ in range(n_parts)]
    elif selector is not None:
        if shard_csrs is None:
            raise TypeError("selector-resolved sharded planning needs a CSR "
                            "first operand (shards must be characterized)")
        if hasattr(selector, "select_shards"):       # SelectorService
            decs = selector.select_shards(shard_csrs, name=f"{op}-shard")
            scheds = [d.schedule for d in decs]
            provenance = [{"source": f"selector-{d.source}",
                           "fingerprint_key": d.fingerprint_key,
                           "confidence": d.confidence,
                           "modeled_time_s": d.modeled_time_s,
                           "schedule": d.schedule} for d in decs]
        elif hasattr(selector, "select"):            # ScheduleTuner
            scheds, provenance = [], []
            for c in shard_csrs:
                s, info = selector.select(c)
                scheds.append(s)
                provenance.append({
                    "source": "tuner", "schedule": s,
                    "modeled_time_s": info.get("verified_time_s")})
        else:
            raise TypeError(f"unsupported selector "
                            f"{type(selector).__name__}")
    else:
        default = SparseTensor.default_schedule()
        scheds = [default] * n_parts
        provenance = [{"source": "default", "schedule": default}
                      for _ in range(n_parts)]
    for s in scheds:
        if s is not None and s.backend != "dense" and spec.layouts \
                and s.layout not in spec.layouts:
            raise ValueError(f"op {op!r} supports layouts {spec.layouts}, "
                             f"a shard schedule asks for {s.layout!r}")

    if store is not None and spec.sharded_store_ok:
        op_kwargs = dict(op_kwargs, store=store)
        if ck is not None:
            op_kwargs.setdefault("operand_key", ck)
    dense_run = resilience.make_dense_run(op, operands, scheds[0], op_kwargs)
    with obs_trace.span("prep", f"plan_sharded:{op}", op=op,
                        n_shards=n_parts):
        p = resilience.guarded_build(
            lambda: spec.sharded_planner(operands, tuple(scheds), backend,
                                         part=part, shard_csrs=shard_csrs,
                                         mesh=mesh, **op_kwargs),
            op=op, schedule=scheds[0], dense_run=dense_run, executor=executor)
    if p.source != "guard-dense":
        p.source = f"sharded-{strategy}"
    resilience.guard_plan(
        p, rebuild=lambda b: spec.sharded_planner(
            operands, tuple(scheds), b, part=part, shard_csrs=shard_csrs,
            mesh=mesh, **op_kwargs),
        dense_run=dense_run, site="shard-dispatch", executor=executor)
    p.shard_provenance = provenance
    return p


def _member_layout(m) -> Optional[str]:
    """Container layout a bucket member arrives in (None = raw CSR, which
    every op can prepare into its own layout)."""
    if isinstance(m, SparseTensor):
        return m.layout
    if isinstance(m, ELLBSR):
        return "ell"
    if isinstance(m, SELLBSR):
        return "sell"
    if isinstance(m, BSR):
        return "bsr"
    if isinstance(m, np.ndarray):
        return "dense"
    return None


def plan_bucket(op: str, operands: Sequence, schedule: Schedule,
                backend: str = "auto",
                store: Optional[PreparedStore] = None,
                executor: Optional[resilience.GuardedExecutor] = None,
                **op_kwargs) -> Plan:
    """One stacked jitted launch for a whole same-schedule bucket.

    ``operands`` is a list of per-member sparse operands (CSR or prepared;
    tuples of operands for binary ops like spgemm/spadd); the returned
    plan's ``execute`` takes the matching list of runtime inputs (none for
    spgemm/spadd) and returns the per-member outputs — all members through
    ONE device program. Every member is validated against the bucket's
    shared Schedule up front, so a mixed or layout-incompatible bucket
    fails here with a per-member error, not deep inside the stacked build.
    """
    spec = get_op(op)
    if spec.bucket_planner is None:
        raise ValueError(f"op {op!r} has no stacked bucket launch")
    if schedule is None:
        raise ValueError("plan_bucket needs the bucket's shared Schedule")
    if schedule.backend != "dense" and spec.layouts \
            and schedule.layout not in spec.layouts:
        raise ValueError(f"op {op!r} supports layouts {spec.layouts}, "
                         f"bucket schedule asks for {schedule.layout!r}")
    members: List = list(operands)
    if not members:
        raise ValueError("empty bucket")
    if spec.bucket_layouts is not None:
        allowed = tuple(spec.bucket_layouts(schedule))
        for i, m in enumerate(members):
            for part in (m if isinstance(m, (tuple, list)) else (m,)):
                got = _member_layout(part)
                if got is not None and got not in allowed:
                    raise ValueError(
                        f"bucket member {i} is a {got!r}-layout operand, "
                        f"incompatible with op {op!r} under the bucket's "
                        f"schedule (expected one of {allowed} or raw CSR); "
                        "buckets share one Schedule by construction")
    backend = resolve_backend(backend)
    if store is not None and spec.bucket_store_ok:
        op_kwargs = dict(op_kwargs, store=store)
    dense_run = resilience.make_dense_bucket_run(op, members, schedule,
                                                op_kwargs)
    with obs_trace.span("prep", f"plan_bucket:{op}", op=op,
                        n_members=len(members)):
        p = resilience.guarded_build(
            lambda: spec.bucket_planner(members, schedule, backend,
                                        **op_kwargs),
            op=op, schedule=schedule, dense_run=dense_run,
            n_members=len(members), executor=executor)
    return resilience.guard_plan(
        p, rebuild=lambda b: spec.bucket_planner(members, schedule, b,
                                                 **op_kwargs),
        dense_run=dense_run, executor=executor)
