"""95th percentile of request latency in the window, in ms, from when each
request was due to its ``on_result``; a rejected, empty or unanswered
request sits beyond the tail. Above capacity the queue grows all through the
run, so this tail swings with the smallest change: a per-layer reading
beside the cell's ``req_per_s``, not an end-to-end bound."""


def read(run):
    return run.end_to_end.get("req_p95_ms")
