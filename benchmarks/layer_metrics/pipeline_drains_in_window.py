"""Scheduler: times the double-buffered pipeline was drained with work
waiting behind it (a block was in flight at a quiesce): window delta of
``mst_pipeline_drains_total`` over every call site but ``idle``. Each is one
block's overlap lost. A drain at ``idle`` (every slot has finished) delays
nobody, and one always follows the end of a closed-loop window."""
from benchmarks import tick_counters


def read(ctx):
    drains = tick_counters.delta(ctx, "mst_pipeline_drains_total")
    if drains is None:
        return None
    return sum(v for reason, v in drains.items() if reason != "idle")
