"""Scheduler: median admission wait (submit to slot), from the window delta
of the cumulative histogram ``mst_queue_wait_seconds``."""
from benchmarks import stats


def read(ctx):
    buckets = stats.histogram_delta(ctx["before"], ctx["after"], "mst_queue_wait_seconds")
    q = stats.histogram_quantile(buckets, 0.5)
    return None if q is None else q * 1e3
