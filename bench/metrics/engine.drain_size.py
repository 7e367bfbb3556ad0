"""Mean requests per engine drain over the traced window: the engine's own
counters ``drained_members`` / ``drains`` (serving/engine.py)."""


def read(run):
    drains = run.window.get("drains", 0.0)
    return run.window["drained_members"] / drains if drains else None
