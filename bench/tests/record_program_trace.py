#!/usr/bin/env python3
"""Record ``data/program_spans.xplane.pb``: a small profiler trace, taken on
a TPU, in which the program's ``repro.*`` spans sit beside the benchmark's.

    python3 bench/tests/record_program_trace.py <out.xplane.pb>

The operand is the ``kron`` configuration at SCALE 10 (1,024 vertices),
planned through the benchmark's selector. The trace holds three guarded
SpMVs under ``execute``, each followed by a host sync under
``vector_update``, then four requests submitted to a ``ServingEngine`` and
served by one 4-member drain, with ``select``, ``drain``, ``submit`` and
``on_result`` wrapped as ``bench/spbench/loops.py`` wraps them. Every
shape is warmed before the trace starts.
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

from spbench.loops import _annotate, _csr, _service  # noqa: E402
from spbench.spec import Spec  # noqa: E402

SCALE = 10
MEMBERS = 4


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.serving import ServingEngine
    from repro.sparse import plan

    spec = Spec()
    cell = spec.cell("kron.pagerank")
    config = dict(cell.config, scale=SCALE)
    csr = _csr(cell.generator().build(config, 7))
    svc = _service(config)
    p = plan("spmv", csr, selector=svc)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal(csr.shape[1]).astype(np.float32))
    xs = rng.standard_normal((MEMBERS, csr.shape[1])).astype(np.float32)
    svc.select = _annotate("select", svc.select)
    svc.drain_bucket = _annotate("drain", svc.drain_bucket)
    engine = ServingEngine(svc, on_result=_annotate("on_result",
                                                    lambda rid, y: None))

    def drain(tag):
        for i in range(MEMBERS):
            with jax.profiler.TraceAnnotation("submit"):
                engine.submit("req", csr, xs[i], rid=f"{tag}{i}")
        assert engine.drain_all() == MEMBERS

    def steps():
        for _ in range(3):
            with jax.profiler.TraceAnnotation("execute"):
                y = p.execute(x)
            with jax.profiler.TraceAnnotation("vector_update"):
                float(jnp.sum(y))

    steps()
    drain("warm")
    d = tempfile.mkdtemp(prefix="program-trace-")
    try:
        jax.profiler.start_trace(d)
        steps()
        drain("traced")
        jax.profiler.stop_trace()
        (found,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True)
        shutil.copy(found, out)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes, device "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
