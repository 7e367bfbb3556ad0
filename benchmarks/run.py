"""Benchmark harness: one module per paper table/figure + the roofline table.
Prints ``name,us_per_call,derived`` CSV; ``--json OUT`` additionally writes
``{name: {"us": float, "derived": str}}`` so BENCH_*.json trajectory points
are machine-generated instead of scraped from the CSV (the committed
``BENCH_*.json`` files are these, diffable with scripts/bench_compare.py).
``--trace-out`` records the run through the obs Tracer (one span per bench
module, plus every plan/launch event the modules trigger) as Chrome-trace
JSON + a sibling .jsonl event log; ``--metrics-every N`` prints a
metrics-registry delta after every N modules. Set REPRO_BENCH_FULL=1 for
the paper-scale corpus (600 matrices). A module that raises prints its
``<module>/ERROR`` row, the others still run, and the run exits non-zero."""
import argparse
import json
import os
import sys
import time
import traceback

from . import (bench_synthetic_categories, bench_thread_imbalance,
               bench_tree_mape, bench_stall_proxies, bench_importances,
               bench_perf_by_category, bench_kernel_hillclimb,
               bench_kernels_micro, bench_roofline, bench_selector,
               bench_serving, bench_sharded, bench_dynamic)

MODULES = [
    ("table2_fig3", bench_synthetic_categories),
    ("fig4", bench_thread_imbalance),
    ("fig5_fig6", bench_tree_mape),
    ("fig7_fig8", bench_stall_proxies),
    ("fig9_12_15", bench_importances),
    ("fig10_13_17", bench_perf_by_category),
    ("hillclimb_2.63x", bench_kernel_hillclimb),
    ("kernels_micro", bench_kernels_micro),
    ("roofline", bench_roofline),
    ("selector", bench_selector),
    ("serving", bench_serving),
    ("sharded", bench_sharded),
    ("dynamic", bench_dynamic),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on module names")
    ap.add_argument("--json", dest="json_out", default=None, metavar="OUT",
                    help="also write results as JSON to this path")
    ap.add_argument("--trace-out", default=None, metavar="TRACE_JSON",
                    help="write a Chrome-trace JSON (+ sibling .jsonl "
                         "event log) of the bench run")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print a metrics-registry delta every N modules")
    args = ap.parse_args(argv)
    selected = [(name, mod) for name, mod in MODULES
                if not args.only or args.only in name]
    # Simulated device count for the sharded rows (the launch/dryrun.py
    # pattern): only when the run is the sharded module ALONE, so the
    # timing environment of every other module's rows — the cross-PR bench
    # trajectory — is untouched by the CPU being split into virtual
    # devices. Must be set before jax first initializes its backend (no
    # module's run() has executed yet; imports alone don't init), and
    # appended, not overwritten, so an operator's own XLA_FLAGS survive.
    # In a mixed run the sharded rows simply use however many devices
    # exist — the imbalance columns, the acceptance signal, are device-
    # count-independent.
    if [n for n, _ in selected] == ["sharded"] \
            and "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8"
                                   ).strip()
    if args.json_out:
        # Fail fast on an unwritable path without truncating an existing
        # trajectory file (the real write is tmp+rename after the run).
        try:
            with open(args.json_out, "a"):
                pass
        except OSError as e:
            ap.error(f"--json: {e}")
    # observability (DESIGN.md §12): bench modules run inside tracer spans,
    # so a --trace-out run shows per-module wall-clock and every plan
    # prep/compile/launch event the modules trigger underneath
    from repro.obs import Tracer, default_registry, install_tracer
    registry = default_registry()
    prev_snapshot = registry.snapshot()
    trace = None
    if args.trace_out:
        trace = install_tracer(Tracer(registry=registry, strict=False))
    results = {}
    failed = []
    print("name,us_per_call,derived")
    for i, (name, mod) in enumerate(selected, start=1):
        t0 = time.time()
        try:
            if trace is not None:
                with trace.span("bench", name, module=name):
                    rows = mod.run()
            else:
                rows = mod.run()
        except Exception as e:
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
            continue
        for r_name, us, derived in rows:
            print(f"{r_name},{us:.1f},{derived}")
            results[r_name] = {"us": float(us), "derived": derived}
        elapsed_us = (time.time() - t0) * 1e6
        print(f"{name}/elapsed,{elapsed_us:.0f},-")
        results[f"{name}/elapsed"] = {"us": float(elapsed_us), "derived": "-"}
        if args.metrics_every and i % args.metrics_every == 0:
            delta = registry.delta(prev_snapshot)
            prev_snapshot = registry.snapshot()
            moved = "  ".join(
                f"{k}={v:g}" for k, v in sorted(delta.items())
                if k.startswith(("events.", "plan.")))
            print(f"# metrics after {name}: {moved}", file=sys.stderr)
    if trace is not None:
        install_tracer(None)
        n_events = trace.write_chrome_trace(args.trace_out)
        stem, _ = os.path.splitext(args.trace_out)
        trace.write_jsonl(stem + ".jsonl")
        print(f"# trace: {n_events} events -> {args.trace_out} "
              f"(+ {stem}.jsonl)", file=sys.stderr)
    if args.json_out:
        tmp = args.json_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        os.replace(tmp, args.json_out)
    if failed:
        sys.exit(f"benchmark modules raised: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.kernels.common import enable_compile_cache
    enable_compile_cache()
    main()
