"""How late the load generator ran: the 99th percentile of each
request's submit time minus its scheduled time, in ms."""


def read(run):
    return run.counters.get("lag_p99_ms")
