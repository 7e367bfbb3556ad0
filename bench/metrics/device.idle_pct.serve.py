"""Share of the traced window in which no operation ran on the device, in %:
1 - (union of the device's op intervals) / window, from the trace."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct
