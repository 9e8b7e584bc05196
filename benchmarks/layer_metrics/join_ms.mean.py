"""Scheduler: mean time of a join, ms — slot claimed to first token out
(``mst_join_seconds``: where ``mst_queue_wait_seconds`` ends to the last
prefill chunk's first token), over the joins that reached decode between the
two scrapes: delta of the histogram's ``_sum`` over delta of its ``_count``,
exact whatever the buckets. A joiner prefills one chunk a tick while the
other slots decode one undoubled block a tick, so this is what more chunks a
tick would move. ``None`` without the family, and where the window held no
join."""
from benchmarks import tick_counters


def read(ctx):
    joins = tick_counters.total(ctx, "mst_join_seconds_count")
    seconds = tick_counters.total(ctx, "mst_join_seconds_sum")
    if not joins or seconds is None:
        return None
    return 1e3 * seconds / joins
