"""Bytes one sharded launch moves between chips, in MB (1e6 bytes): the
program's counters ``shard.exchange_bytes`` / ``shard.launches``
(sparse/ops_builtin.py), counted from shapes: x padded out to every chip
but its own, and each other chip's output rows back. Every launch of a plan
moves the same bytes, so the ratio over the run (warm-up included) is the
window's. None where the program keeps no such counters."""


def read(run):
    try:
        from repro.obs import default_registry
    except ImportError:
        return None
    reg = default_registry()
    launches = reg.get("shard.launches")
    if not launches:
        return None
    return reg.get("shard.exchange_bytes") / launches / 1e6
