#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout: the cells are in ``BENCHMARK.json`` and the
program under test in ``src/repro``. Exits with 3 and prints no result where
JAX finds no TPU, or fewer chips than the cell asks for.
"""
import time

T0 = time.monotonic()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
# JAX's persistent compilation cache at a fixed path inside this checkout,
# whatever the environment names: two checkouts never share one
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(HERE), ".jax_cache")

from spbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
