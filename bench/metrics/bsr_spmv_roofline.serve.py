"""Share of the bsr_spmv kernels' roofline over the traced window, in %.

The least time the chip could take for the window's SpMV/SpMM work, counted
by ``spbench.work`` from nnz, n and the real right-hand sides (bytes every
layout must move), over the device time of the ``bsr_spmv`` family's Pallas
calls in the trace. Padding a layout adds kernel time, never work, so it can
only lower this share.
"""
from spbench.work import roofline_pct


def read(run):
    if run.trace is None or not run.bytes:
        return None
    return roofline_pct(run.bytes, run.flops,
                        run.trace.kernel_s.get("bsr_spmv", 0.0), run.peak)
