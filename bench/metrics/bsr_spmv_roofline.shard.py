"""Share of the bsr_spmv kernels' roofline over the traced window of a cell
whose operand is row-sharded over the host's chips, in %.

The least time the chips could take for the window's SpMV work (counted by
``spbench.work`` from nnz, n and the real right-hand sides, as for
``bsr_spmv_roofline.solve``), at the summed HBM peak of every chip the
operand is spread over, over the per-chip mean device time of the
``bsr_spmv`` family's Pallas calls in the trace. The program shards an
operand over all local chips, so those are JAX's local devices.
"""
from spbench.work import roofline_pct


def chips() -> int:
    import jax
    return jax.local_device_count()


def read(run):
    if run.trace is None or not run.bytes:
        return None
    n = chips()
    peak = {k: v * n for k, v in run.peak.items()}
    return roofline_pct(run.bytes, run.flops,
                        run.trace.kernel_s.get("bsr_spmv", 0.0), peak)
