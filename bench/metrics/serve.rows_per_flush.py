"""Rows per lookup call: the change in ``ServiceStats.rows`` over the
change in ``ServiceStats.flushes`` across the traced window."""


def read(run):
    return run.counters.get("rows_per_flush")
