"""The 99th percentile (nearest rank) of request latency as the client
sees it, from each request's due time to its answer, over every request
of the untraced window; a failed request counts as infinite.  A
per-layer reading, not an end-to-end one: a host stall of about 110 ms
strikes some 10 s windows and not others, so it swings from run to run
by more than any bound could hold."""


def read(run):
    return run.counters.get("p99_ms")
