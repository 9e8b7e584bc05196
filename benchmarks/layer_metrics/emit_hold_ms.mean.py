"""Scheduler: how long a drain's tokens were held back for the joiner's
chunk — window delta of ``mst_emit_hold_seconds_sum`` over the delta of its
``_count``, in milliseconds. One observation a hold: from the first queue
item a tick that drained for a joiner deferred to the flush that put them
all (``scheduler._hand`` / ``_flush_held``; which flush let them go is
``mst_emit_held_total{flush}``). It is what the deferral adds to the
latency of the drained block's tokens: the rest of that block's emit loop,
the slot claim and the chunk's dispatch, a few milliseconds — a reading
near a chunk's length says a hold spans a wait on the device. A program
from before the counter exposes nothing and the metric is left out, as it
is where the window held nothing."""
from benchmarks import tick_counters


def read(ctx):
    seconds = tick_counters.total(ctx, "mst_emit_hold_seconds_sum")
    holds = tick_counters.total(ctx, "mst_emit_hold_seconds_count")
    if seconds is None or not holds:
        return None
    return 1e3 * seconds / holds
