#!/usr/bin/env python3
"""The control of a cell's check: its reference in the program's place.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py`` does, but every answer the program
produces is replaced by the reference computed one precision step below what
the configurations state: bf16x3 products (``Precision.HIGH``) summed in f32,
where the program promises f32 at ``HIGHEST``. The check must then come out
``correct: false``; the ``spmv_err`` it prints is the control's reading,
the upper end a limit is set below. The benchmark's own runs never do this.
"""
import time

T0 = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
# JAX's persistent compilation cache at a fixed path inside this checkout,
# whatever the environment names: two checkouts never share one
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(HERE), ".jax_cache")

from spbench.harness import emit, load_cell, parse, run_cell  # noqa: E402
from spbench.reference import matvec_bf16x3  # noqa: E402


def control_fault(a: dict):
    """``fault`` hook answering every request with the control."""
    return lambda i, x, y: matvec_bf16x3(a, np.asarray(x))


def main(argv=None) -> int:
    args = parse(argv, "bench/control.py")
    cell = load_cell(args.workload)
    if cell is None:
        return 3
    a = cell.generator().build(cell.config, args.seed)
    emit(run_cell(cell, args.seed, args.seconds, False, T0,
                  fault=control_fault(a)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
