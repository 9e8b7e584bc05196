"""Scheduler: programs a join dispatched between its drain and the slot
decoding — window delta of ``mst_join_programs_total{program}`` summed over
its programs (``claim``: the slot claim; ``chunk``: each prefill chunk;
``finish``: the first token; ``other``: a block import's resume) over the
joins that reached decode in the window (window delta of
``mst_join_seconds_count``). 3 for a one-chunk join, 2 + chunks for a longer
one; a window's edge that cuts a join moves it a little. Each dispatch after
a drain is a point at which the tick thread waits for the interpreter lock
with the device empty (``join_empty_ms.mean``). A program from before the
counter exposes nothing and the metric is left out, as it is where the
window held no join."""
from benchmarks import tick_counters


def read(ctx):
    programs = tick_counters.total(ctx, "mst_join_programs_total")
    joins = tick_counters.total(ctx, "mst_join_seconds_count")
    if programs is None or not joins:
        return None
    return programs / joins
