"""Mean host time of one ``Plan.execute`` over the traced window, in ms: the
program's ``launch_ms.spmv`` histogram (sparse/plan.py), host clock around
the guarded launch, the NaN guard's sync included."""


def read(run):
    n = run.window.get("launch_ms_count", 0.0)
    return run.window["launch_ms_sum"] / n if n else None
