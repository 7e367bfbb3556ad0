"""Mean host time of one selector decision over the traced window, in ms:
the program's ``select_ms`` histogram (selector/service.py), which includes
the per-request content hash of the operand."""


def read(run):
    n = run.window.get("select_ms_count", 0.0)
    return run.window["select_ms_sum"] / n if n else None
